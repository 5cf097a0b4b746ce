"""Per-client data pipeline for the FL simulator.

``plan_epoch_indices`` is the ONE batch-plan primitive: the algorithm
planners (``core.algorithms``) pre-draw a (steps, batch) index plan per
client visit — in the sequential engine's visit order, so every engine
consumes an identical RNG stream — and attach the plans to the RoundPlan
IR (``core.plan``). The stacking helpers below live *behind* that IR: they
are the engines' materialization step, never called by planners.

* the sequential engine feeds each plan straight to ``LocalTrainer.train``
  (which draws its own with the identical ``plan_epoch_indices`` calls when
  invoked outside the IR, e.g. by ``Centralized`` or ``ring_optimization``);
* the batched/sharded engines materialize a visit's plans into
  client-stacked pixel arrays + a valid-step mask (``stack_plans``,
  ``stack_client_batches``);
* the fused engine keeps pixels device-resident (``DeviceDataPlane``
  uploads every shard once per experiment, concatenated along one flat
  sample axis) and ships only the int32 index form of the same plans
  (``stack_plan_indices``) — per visit, nothing but indices crosses the
  host/device boundary and batches are gathered inside the jit.
"""
from __future__ import annotations

import dataclasses
import math
from typing import List, Optional, Sequence, Tuple

import numpy as np

from repro.data.partition import partition
from repro.data.synthetic import Dataset


def plan_epoch_indices(
    client: "ClientData", batch_size: int, epochs: int,
    rng: np.random.Generator,
) -> np.ndarray:
    """(steps, batch_size) sample-index plan for ``epochs`` shuffled epochs.

    Each epoch is a permutation; when the shard does not divide evenly into
    full batches, the final batch is topped up by *resampling* uniform
    random indices (``rng.integers``), NOT by wrapping the permutation
    around (static shapes keep the jitted train step cache warm). The
    resample is an extra draw on the shared RNG stream, so any consumer
    that must stay stream-parallel with this plan (both engines do) has to
    make the identical ``permutation`` + ``integers`` calls in the
    identical order — which is why the batched engine pre-draws plans here
    rather than re-implementing them.
    """
    n = len(client)
    num_batches = max(1, int(np.ceil(n / batch_size)))
    rows = []
    for _ in range(epochs):
        idx = rng.permutation(n)
        if num_batches * batch_size > n:
            extra = rng.integers(0, n, size=num_batches * batch_size - n)
            idx = np.concatenate([idx, extra])
        rows.append(idx.reshape(num_batches, batch_size))
    return np.concatenate(rows, axis=0)


def _plan_batch_width(plans: Sequence[Optional[np.ndarray]],
                      width: Optional[int] = None) -> int:
    """Batch width B shared by every real plan in a stack. A stack of only
    ``None`` plans has no batch shape of its own, so the caller must supply
    ``width`` (engines pass the group-wide width — under scenario drops a
    whole hop can lose every real plan); without it, all-``None`` is a
    caller error."""
    if width is not None:
        return width
    for p in plans:
        if p is not None:
            return p.shape[1]
    raise ValueError(
        "cannot stack batch plans: every plan is None (at least one client "
        "in the stack must have a real (steps, batch) index plan, or pass "
        "an explicit batch width)")


def stack_plans(
    clients: Sequence["ClientData"],
    plans: Sequence[Optional[np.ndarray]],
    pad_to: Optional[int] = None,
    width: Optional[int] = None,
) -> Tuple[dict, np.ndarray]:
    """Materialize per-client batch plans into client-stacked arrays.

    Returns ``({"images": (C, S, B, ...), "labels": (C, S, B)}, valid)`` with
    ``S = max steps`` and ``valid`` a (C, S) bool mask. Shorter plans are
    padded by repeating their first batch; a ``None`` plan yields an all-
    invalid row (used for ring positions past a shorter ring's end). Padded
    steps carry real data but are masked to no-ops by the engine.

    ``pad_to`` appends *ghost clients* — all-invalid rows of zero data —
    until the client axis reaches ``pad_to``. The sharded engine uses this
    to round every cohort/ring count up to a multiple of the device-mesh
    size so the ``(C, ...)`` stack shards evenly; ghost rows never train
    (every step invalid) and never draw from the RNG stream. ``width``
    supplies the batch width when the stack might be all-``None``.
    """
    B = _plan_batch_width(plans, width)
    real = [p if p is not None else np.zeros((1, B), np.int64) for p in plans]
    S = max(p.shape[0] for p in real)
    imgs, labs = [], []
    valid = np.zeros((len(clients), S), bool)
    for ci, (c, p) in enumerate(zip(clients, real)):
        s = p.shape[0]
        img, lab = c.images[p], c.labels[p]
        if s < S:
            img = np.concatenate([img, np.repeat(img[:1], S - s, axis=0)])
            lab = np.concatenate([lab, np.repeat(lab[:1], S - s, axis=0)])
        imgs.append(img)
        labs.append(lab)
        valid[ci, :s] = plans[ci] is not None
    out = {"images": np.stack(imgs), "labels": np.stack(labs)}
    if pad_to is not None and pad_to > len(clients):
        ghosts = pad_to - len(clients)
        out = {
            k: np.concatenate(
                [v, np.zeros((ghosts,) + v.shape[1:], v.dtype)])
            for k, v in out.items()
        }
        valid = np.concatenate([valid, np.zeros((ghosts, S), bool)])
    return out, valid


def stack_client_batches(
    clients: Sequence["ClientData"], batch_size: int, epochs: int,
    rng: np.random.Generator, pad_to: Optional[int] = None,
) -> Tuple[dict, np.ndarray]:
    """Plan + stack one cohort's visits, consuming ``rng`` in the sequential
    engine's visit order (client by client). ``pad_to`` ghost-pads the
    client axis (see ``stack_plans``)."""
    plans = [plan_epoch_indices(c, batch_size, epochs, rng) for c in clients]
    return stack_plans(clients, plans, pad_to=pad_to)


def stack_plan_indices(
    plans: Sequence[Optional[np.ndarray]],
    client_rows: Sequence[int],
    pad_to: Optional[int] = None,
    steps: Optional[int] = None,
    width: Optional[int] = None,
) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Index-only analogue of ``stack_plans`` for the fused engine.

    Returns ``(rows, idx, valid)``: ``rows`` is the (C,) int32 fleet row
    (``DeviceDataPlane`` stack position) of each cohort/ring slot, ``idx``
    the (C, S, B) int32 sample-index plan and ``valid`` the (C, S) bool
    step mask. Nothing is materialized: the engine gathers pixels from the
    device-resident plane, so these three arrays are the ENTIRE per-visit
    H2D payload. ``None`` plans (ring positions past a shorter ring's end)
    become all-invalid rows whose indices point at sample 0 — real data,
    masked to a no-op, exactly like ``stack_plans``' padded steps.

    ``steps`` forces the step axis to at least S (the fused ring runner
    pads every hop to the round-global maximum so hops stack along a
    uniform (H, C, S, B) axis); ``pad_to`` appends ghost rows (row 0,
    all-invalid) like ``stack_plans(pad_to=...)``; ``width`` supplies the
    batch width when the stack might be all-``None``.
    """
    B = _plan_batch_width(plans, width)
    S = max((p.shape[0] for p in plans if p is not None), default=0)
    if steps is not None:
        S = max(S, steps)
    if S == 0:
        raise ValueError("cannot stack an all-None hop without `steps`")
    C = len(plans)
    rows = np.asarray(client_rows, np.int32)
    idx = np.zeros((C, S, B), np.int32)
    valid = np.zeros((C, S), bool)
    for ci, p in enumerate(plans):
        if p is None:
            continue
        idx[ci, : p.shape[0]] = p
        valid[ci, : p.shape[0]] = True
    if pad_to is not None and pad_to > C:
        ghosts = pad_to - C
        rows = np.concatenate([rows, np.zeros(ghosts, np.int32)])
        idx = np.concatenate([idx, np.zeros((ghosts, S, B), np.int32)])
        valid = np.concatenate([valid, np.zeros((ghosts, S), bool)])
    return rows, idx, valid


class DeviceDataPlane:
    """Client shards resident on device: upload, then gather per visit.

    Shards are concatenated along ONE flat sample axis — ``images``
    ``(total, D)``, ``labels`` ``(total,)`` — with an int32 ``offsets``
    table giving each client's first row: client ``r``'s sample ``i``
    lives at ``offsets[r] + i``. Each sample is one contiguous feature row
    of ``D = prod(item_shape)`` values (784 for MNIST shapes, 3,072 for
    CIFAR's), flattened on the host before the upload, so a step's batch
    is a bare gather of whole rows; ``item_shape`` records the clients'
    per-sample shape. Batch plans only ever index a client's
    own ``[0, len)`` range, and the skewed shard sizes of the paper's
    non-IID partitions cost NO padding memory. After the upload
    (``nbytes``), the fused engine's per-visit H2D traffic is the int32
    plan arrays from ``stack_plan_indices`` — for the paper's MNIST/CIFAR
    shapes that is ~3 orders of magnitude less than shipping the
    ``stack_plans`` pixel stacks every hop.

    ``client_ids`` builds a *cohort* plane (``data.store.HostStore``): only
    the given fleet ids' shards upload, but ``offsets`` stays fleet-sized
    (``fleet_size``) with each visited id mapped to its cohort-local flat
    start — so the fleet-id ``rows`` arrays of ``stack_plan_indices`` and
    the in-jit row gather are untouched by client virtualization.
    Unvisited (and ghost-padded) ids map to row 0: real data, only ever
    gathered under an all-invalid mask. Default (``None``) is the full
    fleet in id order — today's upload-once plane, bit-for-bit.

    With ``mesh``, shards ARE zero-padded to the cohort maximum ``N_max``
    (and the cohort rounded up to a mesh multiple) before flattening, so
    the sample axis divides the mesh's ``data_axis`` evenly and the
    resident stack partitions alongside the sharded cohort axis instead of
    replicating onto every device; the staging copies are dropped as soon
    as each array lands on device, and ``real_nbytes`` reports the
    unpadded shard bytes next to the padded resident ``nbytes`` so scale
    benchmarks read honestly.
    """

    def __init__(self, clients: Sequence["ClientData"], mesh=None,
                 data_axis: str = "data", client_ids=None,
                 fleet_size: Optional[int] = None):
        import jax
        import jax.numpy as jnp

        if not clients:
            raise ValueError("DeviceDataPlane needs at least one client shard")
        self.num_clients = len(clients)
        if client_ids is None:
            client_ids = np.arange(len(clients))
        client_ids = np.asarray(client_ids, np.int64)
        if fleet_size is None:
            fleet_size = len(clients)
        sizes = [len(c) for c in clients]
        real = sum(c.images.nbytes + c.labels.size * 4 for c in clients)
        self.item_shape = tuple(clients[0].images.shape[1:])
        d = math.prod(self.item_shape)
        if mesh is None:
            imgs = np.concatenate([c.images for c in clients]).reshape(-1, d)
            # int32 host-side so ``nbytes`` matches what actually crosses
            # H2D (jax demotes int64 on transfer when x64 is disabled)
            labs = np.concatenate([c.labels for c in clients]).astype(np.int32)
            starts = np.cumsum([0] + sizes[:-1]).astype(np.int32)
        else:
            from repro.launch.mesh import round_up_to_mesh
            n_max = max(sizes)
            k = round_up_to_mesh(len(clients), mesh, data_axis)
            imgs = np.zeros((k * n_max, d), clients[0].images.dtype)
            labs = np.zeros(k * n_max, np.int32)
            for i, c in enumerate(clients):
                imgs[i * n_max: i * n_max + len(c)] = c.images.reshape(-1, d)
                labs[i * n_max: i * n_max + len(c)] = c.labels
            starts = (np.arange(len(clients), dtype=np.int32) * n_max)
        offs = np.zeros(fleet_size, np.int32)
        offs[client_ids] = starts
        self.nbytes = imgs.nbytes + labs.nbytes + offs.nbytes   # resident/H2D
        self.real_nbytes = real + offs.nbytes                   # sans padding
        if mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec
            shard = NamedSharding(mesh, PartitionSpec(data_axis))
            repl = NamedSharding(mesh, PartitionSpec())
            # drop each staging copy as soon as it lands on device — the
            # dense zero-padded host arrays must not outlive the upload
            self.images = jax.device_put(imgs, shard)
            del imgs
            self.labels = jax.device_put(labs, shard)
            del labs
            self.offsets = jax.device_put(offs, repl)
        else:
            self.images = jnp.asarray(imgs)
            del imgs
            self.labels = jnp.asarray(labs)
            del labs
            self.offsets = jnp.asarray(offs)


@dataclasses.dataclass
class ClientData:
    """One FL device's private shard."""
    client_id: int
    images: np.ndarray
    labels: np.ndarray

    def __len__(self) -> int:
        return len(self.labels)


def make_clients(
    train: Dataset,
    *,
    scheme: str,
    num_devices: int,
    rng: np.random.Generator,
    xi: int = 2,
    alpha: float = 0.3,
) -> List[ClientData]:
    parts = partition(
        train.labels, scheme=scheme, k=num_devices, rng=rng, xi=xi, alpha=alpha
    )
    return [
        ClientData(d, train.images[p], train.labels[p])
        for d, p in enumerate(parts)
    ]


def client_weights(clients: List[ClientData]) -> np.ndarray:
    """|D_i| / |D| weights used by every aggregation rule in the paper."""
    sizes = np.asarray([len(c) for c in clients], np.float64)
    return sizes / sizes.sum()
