"""ClientStore units (PR 7): the residency layer that decouples fleet
size K from device memory.

Covers the CohortArena construction (fleet-sized offsets table, so plans
keep fleet ids and the in-jit gather is untouched), the HostStore's
per-block staging/caching policy, the vectorized checkpoint pack/unpack
(ghost dump row, empty seen, host-arena layout), and THE acceptance
claim: host-store peak device bytes scale with the cohort, not the
fleet. Bit-exactness of host vs device store across every algorithm x
engine lives in ``test_engine_matrix.py``.
"""
import numpy as np
import pytest

from repro.data.pipeline import ClientData, DeviceDataPlane


def _clients(sizes=(5, 12, 8, 3)):
    return [ClientData(i, np.full((n, 4, 4, 1), i, np.float32),
                       np.full(n, i % 3, np.int64))
            for i, n in enumerate(sizes)]


# ---------------------------------------------------------------------------
# CohortArena: DeviceDataPlane over a visited subset


def test_cohort_plane_offsets_table_keeps_fleet_ids():
    """A cohort plane holds ONLY the visited shards but its offsets table
    is fleet-sized: plans (and the jitted gather) keep addressing clients
    by fleet id — the fleet→cohort remap is folded into the table."""
    clients = _clients()                        # shard sizes 5, 12, 8, 3
    plane = DeviceDataPlane([clients[1], clients[3]],
                            client_ids=np.asarray([1, 3]), fleet_size=4)
    assert plane.images.shape == (15, 16)       # 12 + 3 samples only
    assert plane.item_shape == (4, 4, 1)
    assert plane.offsets.shape == (4,)
    assert plane.offsets[1] == 0 and plane.offsets[3] == 12
    # unvisited ids point at 0 — a plan never addresses them in-block
    assert plane.offsets[0] == 0 and plane.offsets[2] == 0
    assert (np.asarray(plane.images)[:12] == 1.0).all()
    assert (np.asarray(plane.images)[12:] == 3.0).all()


def test_plane_reports_real_vs_padded_bytes():
    """Unsharded planes concatenate without padding: resident == real.
    (The mesh path pads shards to N_max; ``real_nbytes`` is what the
    samples actually weigh, so the padding overhead is observable.)"""
    plane = DeviceDataPlane(_clients())
    assert plane.real_nbytes == plane.nbytes


# ---------------------------------------------------------------------------
# store policies


def test_device_store_uploads_once():
    from repro.data.store import make_store

    store = make_store("device", _clients())
    assert store.kind == "device"
    first = store.arena_nbytes(np.asarray([0, 2]))
    assert first == store.arena(None).nbytes > 0
    # every later block reuses the fleet plane: no re-upload, same object
    assert store.arena_nbytes(np.asarray([1])) == 0
    assert store.arena(np.asarray([1])) is store.arena(None)


def test_host_store_stages_per_cohort_and_frees():
    from repro.data.store import make_store

    clients = _clients()
    store = make_store("host", clients)
    assert store.kind == "host"
    a = store.arena(np.asarray([1, 3]))
    assert a.images.shape[0] == 15              # cohort samples only
    # same visited set -> cached arena, no re-upload
    assert store.arena_nbytes(np.asarray([1, 3])) == 0
    assert store.arena(np.asarray([1, 3])) is a
    # a new cohort drops the old arena and stages fresh bytes
    b_bytes = store.arena_nbytes(np.asarray([0]))
    b = store.arena(np.asarray([0]))
    assert b is not a and b_bytes == b.nbytes > 0
    assert b.images.shape[0] == 5


def test_make_store_rejects_unknown():
    from repro.data.store import make_store

    with pytest.raises(ValueError, match="unknown FLConfig.store"):
        make_store("disk", _clients())


def test_stream_store_arenas_match_host_store():
    """The memmap round-trip is lossless: a stream-store cohort arena is
    byte-identical to the host store's for the same visited set, and its
    ``clients`` list keeps only lengths (O(1) RAM per shard)."""
    from repro.data.store import make_store

    clients = _clients()
    host = make_store("host", clients)
    stream = make_store("stream", clients)
    assert stream.kind == "stream"
    try:
        for visited in (np.asarray([1, 3]), np.asarray([0]), None):
            a, b = host.arena(visited), stream.arena(visited)
            np.testing.assert_array_equal(np.asarray(a.images),
                                          np.asarray(b.images))
            np.testing.assert_array_equal(np.asarray(a.labels),
                                          np.asarray(b.labels))
            np.testing.assert_array_equal(np.asarray(a.offsets),
                                          np.asarray(b.offsets))
        # fleet bookkeeping survives the shard handoff to disk
        assert [len(c) for c in stream.clients] == [len(c) for c in clients]
        assert not any(hasattr(c, "images") for c in stream.clients)
    finally:
        stream.close()
        host.close()


def test_stream_store_close_is_idempotent():
    from repro.data.store import make_store

    store = make_store("stream", _clients())
    store.arena(np.asarray([2]))
    store.close()
    store.close()                               # second close: no-op


# ---------------------------------------------------------------------------
# prefetch protocol (PR 9): background staging + double buffer


def test_prefetch_consume_counts_overlap_and_pair_bytes():
    """``prefetch(v)`` then ``arena(v)`` consumes the background build:
    its wall lands in BOTH stage_seconds and overlapped_stage_seconds,
    and ``last_pair_nbytes`` reports the double-buffered handover — the
    outgoing arena stays live until the swap, so the pair is prev + new."""
    from repro.data.store import make_store

    store = make_store("host", _clients())
    try:
        a = store.arena(np.asarray([1, 3]))     # sync stage: no overlap
        assert store.stage_seconds > 0.0
        assert store.overlapped_stage_seconds == 0.0
        assert store.last_pair_nbytes == a.nbytes
        store.prefetch(np.asarray([0, 2]))
        b = store.arena(np.asarray([0, 2]))     # consume the prefetch
        assert b.images.shape[0] == 13          # shards 0 (5) + 2 (8)
        assert store.overlapped_stage_seconds > 0.0
        assert store.last_pair_nbytes == a.nbytes + b.nbytes
    finally:
        store.close()


def test_prefetch_skips_resident_and_redundant():
    """Prefetching the arena already staged (full participation every
    block) or the set already pending is a no-op — no second build."""
    from repro.data.store import make_store

    store = make_store("host", _clients())
    try:
        store.arena(np.asarray([1, 3]))
        store.prefetch(np.asarray([1, 3]))      # already resident
        assert store._pending is None
        store.prefetch(np.asarray([0]))
        pending = store._pending
        store.prefetch(np.asarray([0]))         # already staging
        assert store._pending is pending
    finally:
        store.close()


def test_stale_prefetch_falls_back_to_sync_stage():
    """An arena request for a DIFFERENT set than the pending prefetch
    drains the stale build and stages synchronously — correctness never
    depends on the planner's lookahead matching: the sync path frees the
    old arena first, so ``last_pair_nbytes`` is the single new plane."""
    from repro.data.store import make_store

    store = make_store("host", _clients())
    try:
        store.arena(np.asarray([1]))
        before = store.overlapped_stage_seconds
        store.prefetch(np.asarray([0]))         # planner guessed wrong
        c = store.arena(np.asarray([2, 3]))
        assert c.images.shape[0] == 11          # shards 2 (8) + 3 (3)
        assert store._pending is None
        assert store.overlapped_stage_seconds == before     # not overlapped
        assert store.last_pair_nbytes == c.nbytes
    finally:
        store.close()


def test_residency_meter_transient_peak():
    """``record_transient`` folds the double-buffered high-water mark into
    ``peak_bytes`` without disturbing the steady-state fields."""
    from repro.core.comm import ResidencyMeter

    meter = ResidencyMeter()
    meter.record(100, 20)
    assert meter.peak_bytes == 120
    meter.record_transient(250)                 # both buffers live at once
    assert meter.peak_bytes == 250
    assert meter.data_bytes == 100 and meter.state_bytes == 20
    meter.record_transient(90)                  # never lowers the peak
    assert meter.peak_bytes == 250
    meter.record_stage(2.0)
    meter.record_stage(1.0, overlapped=True)
    meter.record_dispatch(0.5)
    assert meter.overlap_fraction == pytest.approx(1.0 / 3.0)
    snap = meter.snapshot()
    assert snap["overlap_fraction"] == pytest.approx(1.0 / 3.0)
    assert snap["dispatch_seconds"] == pytest.approx(0.5)


# ---------------------------------------------------------------------------
# checkpoint pack/unpack (the algo_state.msgpack layout)


def _w_like():
    return {"w": np.zeros((3, 2), np.float32), "b": np.zeros(2, np.float32)}


def test_pack_unpack_round_trip_device_stack():
    import jax.numpy as jnp

    from repro.core.state import pack_client_rows, unpack_client_rows

    K = 4
    stack = {k: jnp.asarray(np.arange(np.prod(s)).reshape(s)
                            .astype(np.float32))
             for k, s in (("w", (K + 1, 3, 2)), ("b", (K + 1, 2)))}
    seen = np.zeros(K + 1, bool)
    seen[[1, 3]] = True
    seen[K] = True                  # the ghost dump row must NEVER pack
    rows = pack_client_rows(stack, seen)
    assert sorted(rows) == [1, 3]
    np.testing.assert_array_equal(rows[1]["w"], np.asarray(stack["w"])[1])
    arena, seen2 = unpack_client_rows(rows, _w_like(), K)
    assert arena["w"].shape == (K + 1, 3, 2)    # device layout has the dump
    np.testing.assert_array_equal(np.asarray(arena["w"])[3],
                                  np.asarray(stack["w"])[3])
    assert (np.asarray(arena["w"])[0] == 0).all()
    np.testing.assert_array_equal(seen2[:K], [False, True, False, True])


def test_pack_empty_seen_and_unpack_empty_rows():
    from repro.core.state import (client_stack, pack_client_rows,
                                  unpack_client_rows)

    K = 3
    assert pack_client_rows(client_stack(_w_like(), K),
                            np.zeros(K + 1, bool)) == {}
    arena, seen = unpack_client_rows({}, _w_like(), K)
    assert not seen.any()
    assert all((np.asarray(x) == 0).all() for x in arena.values())


def test_unpack_host_arena_layout():
    """``device=False`` restores into the host store's ``(K, ...)`` numpy
    arena — no dump row, leaves stay numpy (the residency protocol stages
    them per block, so nothing should land on device at restore time)."""
    from repro.core.state import pack_client_rows, unpack_client_rows

    K = 4
    host = {"w": np.arange(K * 6, dtype=np.float32).reshape(K, 3, 2),
            "b": np.arange(K * 2, dtype=np.float32).reshape(K, 2)}
    seen = np.zeros(K + 1, bool)
    seen[[0, 2]] = True
    rows = pack_client_rows(host, seen)         # host arenas pack too
    arena, seen2 = unpack_client_rows(rows, _w_like(), K, device=False)
    assert isinstance(arena["w"], np.ndarray)
    assert arena["w"].shape == (K, 3, 2)
    np.testing.assert_array_equal(arena["w"][[0, 2]], host["w"][[0, 2]])
    assert (arena["w"][1] == 0).all()
    np.testing.assert_array_equal(seen2[:K], seen[:K])


def test_stage_unstage_rows_round_trip():
    from repro.core.state import host_stack, rowmap_for, stage_rows, \
        unstage_rows

    K = 5
    arena = host_stack(_w_like(), K)
    arena["w"] += np.arange(K, dtype=np.float32)[:, None, None]
    visited = np.asarray([1, 4])
    staged = stage_rows(arena, visited)
    assert staged["w"].shape == (3, 3, 2)       # V + 1 rows, row V = dump
    assert (np.asarray(staged["w"])[2] == 0).all()
    rowmap = rowmap_for(visited, K)
    assert rowmap.tolist() == [2, 0, 2, 2, 1, 2]    # fleet dump K -> V too
    # train rows, dirty the dump, write back: dump dropped on the floor
    staged = {k: v + 10.0 for k, v in staged.items()}
    arena = unstage_rows(arena, visited, staged)
    assert arena["w"][1, 0, 0] == 11.0 and arena["w"][4, 0, 0] == 14.0
    assert arena["w"][0, 0, 0] == 0.0           # unvisited rows untouched


# ---------------------------------------------------------------------------
# THE acceptance claim: peak device bytes are O(cohort), not O(K)


def test_host_store_peak_device_bytes_o_cohort():
    """Quadruple the fleet at a FIXED per-round cohort: the device store's
    peak residency quadruples with it, the host store's stays flat (modulo
    its fleet-sized int32 offsets table) and far below the device store's.
    This is the tier-1 pin of the fleet-scale bench
    (``kernel/fleet_scale_fedsr_hoststore``)."""
    from repro.configs import get_config
    from repro.configs.base import FLConfig
    from repro.core.executor import run_experiment
    from repro.data.synthetic import make_task

    cohort, peaks = 8, {}
    cfg = get_config("fedsr-mlp")
    for K in (96, 384):
        train, test = make_task("mnist_like", train_per_class=K // 10 + 1,
                                test_per_class=2, seed=0)
        for store in ("host", "device"):
            fl = FLConfig(algorithm="fedsr", num_devices=K,
                          num_edges=K // 4, participation=cohort / K,
                          rounds=2, ring_rounds=2, local_epochs=1,
                          batch_size=8, engine="fused", store=store)
            res = run_experiment(task="mnist_like", model_cfg=cfg, fl=fl,
                                 eval_every=2, train=train, test=test)
            peaks[store, K] = res.peak_device_bytes
    # device store: resident fleet grows with K
    assert peaks["device", 384] > 3 * peaks["device", 96]
    # host store: 4x the fleet, ~same cohort residency (the only K-term
    # is the (K,) int32 offsets table — allow it plus slack for cohort
    # shard-size variation)
    assert peaks["host", 384] < 2 * peaks["host", 96]
    # and the cohort arena is a small fraction of the resident fleet
    assert peaks["host", 384] < 0.2 * peaks["device", 384]
