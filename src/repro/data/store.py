"""Pluggable client stores — where the fleet's shards live between rounds.

The simulator's data plane used to hard-code one residency policy: the
fused engine uploaded the ENTIRE fleet once per experiment
(``DeviceDataPlane``), so device memory grew O(K) even though a round
only ever touches its cohort. ``ClientStore`` makes that policy a config
choice (``FLConfig.store``):

* ``DeviceStore`` — the upload-once plane, bit-for-bit: one fleet-order
  ``DeviceDataPlane`` built on first use and reused for every block.
  Right when the fleet fits and rounds revisit clients often.
* ``HostStore`` — shards stay host-resident (the ``ClientData`` numpy
  arrays ARE the store); at each schedule block boundary the engine asks
  for the block's **CohortArena**: a ``DeviceDataPlane`` over only the
  visited clients, with the fleet→cohort row remap folded into the
  plane's fleet-sized ``offsets`` table. Plans, the ``stack_plan_indices``
  arrays and the in-jit row gather are identical to the device
  store — the remap is invisible past the offsets table — so the two
  stores are bit-exact while peak device bytes scale with the cohort, not
  K. The previous block's arena is dropped when the next one is staged.
* ``StreamStore`` — the fleet's pixels live in disk-backed ``np.memmap``
  shards (written once at construction into a store-owned temp dir) and a
  block's cohort is gathered straight from the memmap slices into its
  arena: host RAM residency is O(cohort) too, the regime where fleets
  outgrow memory entirely. Cohort arenas are byte-identical to the host
  store's (the memmap round-trip is lossless), so all three stores are
  bit-exact.

The participation of every round in a block is planner-drawn
(``Schedule.visited``), so the visited set is host-knowable before any
dispatch — staging never needs a device readback.

**Prefetch protocol** (``FLConfig.prefetch=1``): ``prefetch(visited)``
hands the NEXT block's gather + ``device_put`` to a one-worker background
thread while the current block's dispatch is still in flight;
``arena(visited)`` consumes a matching prefetch instead of staging
synchronously. During the handover both arenas are live (double buffer —
the staged store never frees the in-use arena under a running dispatch),
so peak residency is capped at 2 cohorts; ``last_pair_nbytes`` reports
that momentary pair for the residency meter. ``stage_seconds`` /
``overlapped_stage_seconds`` accumulate the staging wall and the part of
it the prefetch hid behind the dispatch — the pipeline's measurable win.
"""
from __future__ import annotations

import concurrent.futures
import tempfile
from typing import List, Optional, Sequence, Tuple

import numpy as np

from repro.data.pipeline import ClientData, DeviceDataPlane
from repro.utils.trace import span


class ClientStore:
    """Residency policy for client shards. ``arena(visited)`` returns the
    ``DeviceDataPlane`` serving a block that visits the given fleet ids
    (``None`` = potentially all of them); ``arena_nbytes(visited)`` is the
    H2D cost of that call (0 when the arena is already resident);
    ``prefetch(visited)`` starts staging the NEXT block's arena in the
    background (a no-op for stores with nothing to stage)."""

    kind = ""

    def __init__(self, clients: Sequence[ClientData], mesh=None,
                 data_axis: str = "data"):
        self.clients = list(clients)
        self.mesh = mesh
        self.data_axis = data_axis
        self.stage_seconds = 0.0            # total staging wall
        self.overlapped_stage_seconds = 0.0  # staging wall hidden by prefetch
        self.last_pair_nbytes = 0           # arenas live at the last swap

    def arena(self, visited: Optional[np.ndarray] = None) -> DeviceDataPlane:
        raise NotImplementedError

    def prefetch(self, visited: Optional[np.ndarray] = None) -> None:
        """Start staging the arena for ``visited`` in the background; the
        matching ``arena(visited)`` call consumes it. Default: no-op —
        only stores that stage per block have anything to overlap."""

    def close(self) -> None:
        """Release background resources (the staging thread, disk shards).
        Idempotent; stores are also usable without ever calling it."""


class DeviceStore(ClientStore):
    """Upload the whole fleet once; every block reuses the same plane."""

    kind = "device"

    def __init__(self, clients, mesh=None, data_axis="data"):
        super().__init__(clients, mesh=mesh, data_axis=data_axis)
        self._plane: Optional[DeviceDataPlane] = None

    def arena(self, visited=None) -> DeviceDataPlane:
        if self._plane is None:
            with span("stage_data", lambda s: setattr(
                    self, "stage_seconds", self.stage_seconds + s)):
                self._plane = DeviceDataPlane(
                    self.clients, mesh=self.mesh, data_axis=self.data_axis)
            self.last_pair_nbytes = self._plane.nbytes
        return self._plane

    def arena_nbytes(self, visited=None) -> int:
        first = self._plane is None
        return self.arena(visited).nbytes if first else 0


class _StagedStore(ClientStore):
    """Shared per-block cohort staging: the host and stream stores differ
    only in where ``_cohort`` reads pixels from (RAM vs memmap)."""

    def __init__(self, clients, mesh=None, data_axis="data"):
        super().__init__(clients, mesh=mesh, data_axis=data_axis)
        self._arena: Optional[DeviceDataPlane] = None
        self._visited: Optional[tuple] = None
        # at most one in-flight prefetch: (visited key, future)
        self._pending: Optional[Tuple[tuple, concurrent.futures.Future]] = None
        self._pool: Optional[concurrent.futures.ThreadPoolExecutor] = None

    def _cohort(self, visited: np.ndarray) -> List[ClientData]:
        """The visited clients' shards, wherever this store keeps them."""
        raise NotImplementedError

    def _build(self, visited: np.ndarray) -> Tuple[DeviceDataPlane, float]:
        """Gather + upload one cohort arena; returns (plane, seconds).
        Runs on the staging thread under prefetch — ``device_put`` /
        ``jnp.asarray`` are thread-safe in JAX — and the ready-fence keeps
        the measured wall honest (async dispatch would otherwise return
        before the transfer lands)."""
        import jax
        secs = [0.0]
        with span("stage_data", lambda s: secs.__setitem__(0, s)):
            plane = DeviceDataPlane(
                self._cohort(visited), mesh=self.mesh,
                data_axis=self.data_axis, client_ids=visited,
                fleet_size=len(self.clients))
            jax.block_until_ready((plane.images, plane.labels, plane.offsets))
        return plane, secs[0]

    @staticmethod
    def _key(visited: np.ndarray) -> tuple:
        return tuple(visited.tolist())

    def _as_ids(self, visited) -> np.ndarray:
        if visited is None:
            visited = np.arange(len(self.clients))
        return np.asarray(visited, np.int64)

    def prefetch(self, visited=None) -> None:
        visited = self._as_ids(visited)
        key = self._key(visited)
        if key == self._visited or (
                self._pending is not None and self._pending[0] == key):
            return      # already resident / already staging
        if self._pending is not None:       # superseded prefetch: drain it
            self._pending[1].result()
            self._pending = None
        if self._pool is None:
            self._pool = concurrent.futures.ThreadPoolExecutor(
                max_workers=1, thread_name_prefix="repro-stage")
        self._pending = (key, self._pool.submit(self._build, visited))

    def arena(self, visited=None) -> DeviceDataPlane:
        visited = self._as_ids(visited)
        key = self._key(visited)
        if self._visited == key:
            return self._arena
        pending, self._pending = self._pending, None
        if pending is not None and pending[0] == key:
            # consume the prefetch: the build ran while the previous
            # block's dispatch was in flight, so its whole wall counts as
            # overlapped; BOTH arenas are live until the swap below
            # (double buffer) — that momentary pair is the pipeline's
            # residency high-water mark
            plane, secs = pending[1].result()
            self.stage_seconds += secs
            self.overlapped_stage_seconds += secs
            prev = self._arena.nbytes if self._arena is not None else 0
            self.last_pair_nbytes = prev + plane.nbytes
        else:
            if pending is not None:         # stale prefetch for another set
                pending[1].result()
            self._arena = None      # free the previous cohort BEFORE staging
            plane, secs = self._build(visited)
            self.stage_seconds += secs
            self.last_pair_nbytes = plane.nbytes
        self._arena = plane
        self._visited = key
        return self._arena

    def arena_nbytes(self, visited=None) -> int:
        staged = self._visited
        plane = self.arena(visited)
        return plane.nbytes if self._visited != staged else 0

    def close(self) -> None:
        if self._pending is not None:
            self._pending[1].result()
            self._pending = None
        if self._pool is not None:
            self._pool.shutdown(wait=True)
            self._pool = None


class HostStore(_StagedStore):
    """Host-resident fleet; per block, upload only the visited cohort."""

    kind = "host"

    def _cohort(self, visited):
        return [self.clients[int(i)] for i in visited]


class StreamStore(_StagedStore):
    """Disk-backed fleet: pixels live in ``np.memmap`` shards; per block,
    gather only the visited cohort from disk and upload it. The memmaps
    are written once at construction into a temp dir whose lifetime is
    tied to the store object, and every cohort arena is byte-identical to
    the host store's — memmap slices feed the same ``DeviceDataPlane``
    path — so the stream store is bit-exact by construction."""

    kind = "stream"

    def __init__(self, clients, mesh=None, data_axis="data"):
        super().__init__(clients, mesh=mesh, data_axis=data_axis)
        self._tmp = tempfile.TemporaryDirectory(prefix="repro_stream_")
        c0 = clients[0]
        sizes = np.asarray([len(c) for c in clients], np.int64)
        total = int(sizes.sum())
        self._starts = np.concatenate([[0], np.cumsum(sizes)])
        img_path = f"{self._tmp.name}/images.dat"
        lab_path = f"{self._tmp.name}/labels.dat"
        imgs = np.memmap(img_path, dtype=c0.images.dtype, mode="w+",
                         shape=(total,) + c0.images.shape[1:])
        labs = np.memmap(lab_path, dtype=c0.labels.dtype, mode="w+",
                         shape=(total,))
        for i, c in enumerate(clients):
            s, e = self._starts[i], self._starts[i + 1]
            imgs[s:e] = c.images
            labs[s:e] = c.labels
        imgs.flush()
        labs.flush()
        del imgs, labs
        # reopen read-only: the store serves gathers, never writes
        self._images = np.memmap(img_path, dtype=c0.images.dtype, mode="r",
                                 shape=(total,) + c0.images.shape[1:])
        self._labels = np.memmap(lab_path, dtype=c0.labels.dtype, mode="r",
                                 shape=(total,))
        # the fleet's RAM shards are NOT held here: clients keep only ids
        # + lengths so host residency scales with the cohort, not K
        self.clients = [_ShardRef(c.client_id, len(c)) for c in clients]

    def _cohort(self, visited):
        out = []
        for i in visited:
            s, e = self._starts[int(i)], self._starts[int(i) + 1]
            # np.asarray materializes the cohort slice in RAM (the gather
            # this store exists to bound at O(cohort))
            out.append(ClientData(int(i), np.asarray(self._images[s:e]),
                                  np.asarray(self._labels[s:e])))
        return out

    def close(self) -> None:
        super().close()
        if self._tmp is not None:
            self._images = self._labels = None
            self._tmp.cleanup()
            self._tmp = None


class _ShardRef:
    """Length-only stand-in for a ``ClientData`` shard whose pixels live
    on disk (``StreamStore``): enough for fleet-size / weight bookkeeping
    without keeping K shards resident in RAM."""

    __slots__ = ("client_id", "_len")

    def __init__(self, client_id: int, n: int):
        self.client_id = client_id
        self._len = n

    def __len__(self) -> int:
        return self._len


STORES = {"device": DeviceStore, "host": HostStore, "stream": StreamStore}


def make_store(name: str, clients: List[ClientData], mesh=None,
               data_axis: str = "data") -> ClientStore:
    """Build the residency policy selected by ``FLConfig.store``."""
    if name not in STORES:
        raise ValueError(f"unknown FLConfig.store {name!r}; "
                         "expected 'device', 'host' or 'stream'")
    return STORES[name](clients, mesh=mesh, data_axis=data_axis)
