"""The hop gather reads exactly the planned rows, and never out of bounds.

``core.local.hop_gather`` is a bare row gather from a ``DeviceDataPlane``'s
flat (total, D) feature rows: it promises the compiler every index is in
bounds and emits no out-of-bounds fill. These tests hold it and the fused
engine to that promise on the three plane layouts — the full fleet plane,
a ``HostStore`` cohort plane and a mesh-padded plane (4 faked host
devices, in a subprocess):

* the gathered batch equals the clients' own ``images[ix]`` /
  ``labels[ix]`` bit for bit, over skewed shard sizes;
* a fused FedSR block equals the sequential engine on the same schedule;
* every (row, sample) the fused engine ships — skewed shards, ghost-padded
  lanes, scenario drops, cohort planes — addresses a row inside the plane,
  and every valid step a row of its own client's shard.

Run directly (``python tests/test_hop_gather.py``) this file is the
multi-device payload: one JSON line of the mesh cases' results.
"""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from repro.configs.base import ScenarioConfig

ITEM = (4, 4, 1)
SIZES = (3, 17, 1, 9, 30, 5, 12, 2)       # skewed non-IID shard sizes


def _clients():
    from repro.data.pipeline import ClientData

    rng = np.random.default_rng(5)
    return [ClientData(i, rng.standard_normal((n,) + ITEM).astype(np.float32),
                       rng.integers(0, 10, n))
            for i, n in enumerate(SIZES)]


def gather_matches(kind: str) -> bool:
    """Random rows and plans through the jitted ``hop_gather`` against
    the clients' own arrays, on a ``kind`` plane."""
    import jax
    from repro.core.local import hop_gather
    from repro.data.pipeline import DeviceDataPlane
    from repro.data.store import HostStore

    clients = _clients()
    pool = np.arange(len(clients))
    put = np.asarray
    if kind == "full":
        plane = DeviceDataPlane(clients)
    elif kind == "cohort":
        pool = np.asarray([1, 3, 4, 7])
        plane = HostStore(clients).arena(pool)
    else:
        from jax.sharding import NamedSharding, PartitionSpec
        from repro.launch.mesh import make_sim_mesh
        mesh = make_sim_mesh(len(clients))
        plane = DeviceDataPlane(clients, mesh=mesh)
        lanes = NamedSharding(mesh, PartitionSpec("data"))

        def put(a):
            return jax.device_put(a, lanes)
    assert plane.images.shape[1:] == (np.prod(ITEM),)
    fn = jax.jit(hop_gather, static_argnums=5)
    lens = np.asarray([len(c) for c in clients])
    rng = np.random.default_rng(11)
    C, B = 8, 6
    for _ in range(5):
        rows = rng.choice(pool, C).astype(np.int32)
        ix = rng.integers(0, lens[rows][:, None], (C, B)).astype(np.int32)
        got = fn(plane.images, plane.labels, plane.offsets, put(rows),
                 put(ix), ITEM)
        want_x = np.stack([clients[r].images[i] for r, i in zip(rows, ix)])
        want_y = np.stack([clients[r].labels[i] for r, i in zip(rows, ix)])
        if not (np.array_equal(np.asarray(got["images"]), want_x)
                and np.array_equal(np.asarray(got["labels"]), want_y)):
            return False
    return True


def _fedsr_block(engine, overrides, chunked):
    """2 rounds of FedSR on 8 skewed clients: ``(weights, fused calls)``,
    each call ``(plane, rows, plans, valid)`` as shipped to the trainer."""
    import jax
    from repro.configs import get_config
    from repro.configs.base import FLConfig
    from repro.core.algorithms import make_algorithm
    from repro.core.comm import CommMeter
    from repro.core.local import LocalTrainer
    from repro.data.pipeline import make_clients
    from repro.data.synthetic import make_task
    from repro.models.small import init_small_model

    fl = FLConfig(algorithm="fedsr", num_devices=8, num_edges=2, rounds=2,
                  ring_rounds=2, local_epochs=1, batch_size=8, momentum=0.5,
                  engine=engine, **dict(overrides))
    train, _ = make_task("mnist_like", train_per_class=10, test_per_class=2,
                         seed=0)
    clients = make_clients(train, scheme="dirichlet", num_devices=8,
                           rng=np.random.default_rng(0), alpha=0.5)
    cfg = get_config("fedsr-mlp")
    tr = LocalTrainer(cfg, FLConfig(batch_size=8, momentum=0.5))
    calls = []
    sched, many = tr.train_schedule, tr.train_many_fused

    def spy_schedule(params, plane, xs, carry, **kw):
        calls.append((plane, xs["rows"], xs["plans"], xs["valid"]))
        return sched(params, plane, xs, carry, **kw)

    def spy_many(params, plane, rows, plans, valid, **kw):
        calls.append((plane, rows, plans, valid))
        return many(params, plane, rows, plans, valid, **kw)

    tr.train_schedule, tr.train_many_fused = spy_schedule, spy_many
    algo = make_algorithm("fedsr", tr, clients, fl)
    w = init_small_model(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(7)
    meter = CommMeter(model_bytes=1)
    if chunked:
        w, _ = algo.run_schedule(w, 0, np.full(fl.rounds, 0.05), rng, meter,
                                 {})
    else:
        state = {}
        for t in range(fl.rounds):
            w, state = algo.run_round(w, t, 0.05, rng, meter, state)
    return w, calls, [len(c) for c in clients]


def block_diff(overrides) -> float:
    """Largest gap between a fused FedSR block and the sequential engine
    driven round by round on the same schedule."""
    import jax
    same_plan = tuple((k, v) for k, v in overrides
                      if k not in ("store", "mesh_data_axis"))
    w_seq, _, _ = _fedsr_block("sequential", same_plan, chunked=False)
    w_fused, calls, _ = _fedsr_block("fused", overrides, chunked=True)
    assert calls, "the fused block never reached the trainer"
    return max(float(np.max(np.abs(np.asarray(a) - np.asarray(b))))
               for a, b in zip(jax.tree.leaves(w_seq),
                               jax.tree.leaves(w_fused)))


def bounds_report(overrides, chunked) -> dict:
    """Every fused call's addresses against its plane: counts of what ran
    and of what fell outside."""
    _, calls, lens = _fedsr_block("fused", overrides, chunked)
    lens = np.asarray(lens)
    out = {"calls": len(calls), "outside": 0, "foreign": 0,
           "idle_lanes": 0, "idle_steps": 0}
    for plane, rows, plans, valid in calls:
        offs = np.asarray(plane.offsets)
        rows, plans = np.asarray(rows), np.asarray(plans)
        valid = np.asarray(valid, bool)
        n = plane.labels.shape[0]
        assert plane.images.shape[0] == n
        flat = offs[rows][..., None, None] + plans
        # the offsets table itself (unvisited ids included), the fleet
        # rows, and every flat row a step reads
        out["outside"] += int(((offs < 0) | (offs >= n)).sum())
        out["outside"] += int(((rows < 0) | (rows >= len(offs))).sum())
        out["outside"] += int(((flat < 0) | (flat >= n)).sum())
        own = plans < lens[rows][..., None, None]
        out["foreign"] += int((~own.all(-1) & valid).sum())
        out["idle_lanes"] += int((~valid.any(-1)).sum())
        out["idle_steps"] += int((~valid).sum())
    return out


# (name, FLConfig overrides, chunked): the fused engine's shipping paths
BOUNDS_CASES = [
    ("skewed_block", (), True),
    ("skewed_per_round", (), False),
    ("partial_rings", (("participation", 0.75),), True),
    ("scenario_drops", (("scenario", ScenarioConfig(drop_rate=0.3)),), True),
    ("cohort_plane", (("store", "host"), ("participation", 0.5)), True),
]
MESH = (("mesh_data_axis", "data"), ("participation", 0.75))
MESH_BOUNDS = MESH + (("store", "host"),)


_MESH_RUN = {}


def mesh_payload() -> dict:
    """The mesh cases on the visible (faked) devices."""
    import jax
    return {"ndev": jax.device_count(),
            "gather": gather_matches("mesh"),
            "block_diff": block_diff(MESH),
            "bounds": bounds_report(MESH_BOUNDS, chunked=True)}


def run_mesh_payload(ndev: int = 4) -> dict:
    """``mesh_payload`` in a subprocess with ``ndev`` faked host devices,
    once per test session."""
    if ndev not in _MESH_RUN:
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        env = dict(os.environ)
        env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={ndev}"
        env["PYTHONPATH"] = (os.path.join(root, "src") + os.pathsep
                             + env.get("PYTHONPATH", ""))
        proc = subprocess.run(
            [sys.executable, os.path.abspath(__file__)], cwd=root, env=env,
            capture_output=True, text=True, timeout=600)
        assert proc.returncode == 0, proc.stderr[-2000:]
        _MESH_RUN[ndev] = json.loads(proc.stdout.strip().splitlines()[-1])
    out = _MESH_RUN[ndev]
    assert out["ndev"] == ndev, out
    return out


@pytest.mark.parametrize("kind", ("full", "cohort", "mesh"))
def test_hop_gather_returns_the_planned_rows_bit_for_bit(kind):
    if kind == "mesh":
        assert run_mesh_payload()["gather"]
    else:
        assert gather_matches(kind)


@pytest.mark.parametrize("plane", ("full", "cohort", "mesh"))
def test_fused_fedsr_block_matches_sequential(plane):
    if plane == "mesh":
        diff = run_mesh_payload()["block_diff"]
    else:
        store = (("store", "host"),) if plane == "cohort" else ()
        diff = block_diff(store)
    assert diff <= 1e-5, (plane, diff)


@pytest.mark.parametrize("name,overrides,chunked",
                         BOUNDS_CASES + [("ghost_lanes_mesh", MESH_BOUNDS,
                                          True)],
                         ids=[c[0] for c in BOUNDS_CASES]
                         + ["ghost_lanes_mesh"])
def test_fused_addresses_stay_inside_the_plane(name, overrides, chunked):
    """The in-bounds promise ``hop_gather`` makes the compiler, checked on
    the host: no shipped (row, sample) leaves ``[0, plane rows)``, and
    every valid step reads its own client's shard."""
    if name == "ghost_lanes_mesh":
        rep = run_mesh_payload()["bounds"]
    else:
        rep = bounds_report(overrides, chunked)
    assert rep["calls"] > 0, rep
    assert rep["outside"] == 0 and rep["foreign"] == 0, (name, rep)
    if name in ("partial_rings", "scenario_drops", "ghost_lanes_mesh"):
        # the case exercised what it names: all-invalid lanes or steps
        assert rep["idle_steps"] > 0, (name, rep)
    if name == "ghost_lanes_mesh":
        assert rep["idle_lanes"] > 0, rep


if __name__ == "__main__":
    print(json.dumps(mesh_payload()))
