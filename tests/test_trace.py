"""The span log (``repro.utils.trace``): spans, counters, the host
watchers, the profiler's host plane, and the spans of the FL block loop."""
import gc
import glob
import os
import sys
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.configs.base import FLConfig
from repro.core.algorithms import make_algorithm
from repro.core.comm import CommMeter
from repro.core.executor import run_experiment
from repro.core.local import LocalTrainer
from repro.data.pipeline import make_clients
from repro.data.synthetic import make_task
from repro.models.small import init_small_model
from repro.utils import trace

BLOCK = ("fl/plan", "fl/stage", "fl/pack", "fl/put", "fl/dispatch",
         "fl/finish")


@pytest.fixture(autouse=True)
def _clean_log():
    trace.reset()
    yield
    trace.reset()


def _fl(**kw):
    base = dict(algorithm="fedsr", engine="fused", rounds=4, num_devices=8,
                num_edges=2, participation=0.5, ring_rounds=1, batch_size=8)
    return FLConfig(**{**base, **kw})


def _task():
    return make_task("mnist_like", train_per_class=8, test_per_class=4)


def test_nested_spans_count_and_total():
    with trace.span("outer"):
        for _ in range(3):
            with trace.span("inner"):
                time.sleep(0.002)
    spans = trace.snapshot()["spans"]
    assert spans["fl/inner"]["count"] == 3
    assert spans["fl/outer"]["count"] == 1
    assert spans["fl/inner"]["seconds"] >= 0.006
    assert spans["fl/outer"]["seconds"] >= spans["fl/inner"]["seconds"]
    assert spans["fl/inner"]["max_s"] <= spans["fl/inner"]["seconds"]
    assert spans["fl/inner"]["max_s"] >= spans["fl/inner"]["seconds"] / 3
    # events in order of their ends: the three inner spans, then the outer
    names = [e[0] for e in trace.events()]
    assert names == ["fl/inner"] * 3 + ["fl/outer"]
    outer = trace.events()[-1]
    assert all(outer[1] <= e[1] and e[2] <= outer[2]
               for e in trace.events()[:3])


def test_on_done_gets_the_span_seconds():
    got = []
    with trace.span("stage_data", got.append):
        time.sleep(0.001)
    (s,) = got
    assert s == trace.snapshot()["spans"]["fl/stage_data"]["seconds"]
    assert s >= 0.001


def test_span_records_when_the_block_raises():
    with pytest.raises(ValueError):
        with trace.span("plan"):
            raise ValueError("boom")
    assert trace.snapshot()["spans"]["fl/plan"]["count"] == 1
    # the thread's stack of open spans is empty again: a compile now is
    # counted outside any span
    trace.watch_host()
    jax.jit(lambda x: x * 5 - 2)(jnp.ones(5)).block_until_ready()
    assert "fl/plan" not in trace.snapshot()["compiles"]


def test_counters_snapshot_and_reset():
    trace.count("plan_draws", 40)
    trace.count("plan_draws", 2)
    trace.count("h2d_bytes", 1000)
    with trace.span("plan"):
        pass
    snap = trace.snapshot()
    assert snap["counters"] == {"plan_draws": 42, "h2d_bytes": 1000}
    assert set(snap) == {"spans", "counters", "compiles"}
    counts = [(n, v) for n, s, e, v in trace.events() if s == e]
    assert counts == [("plan_draws", 40), ("plan_draws", 2),
                      ("h2d_bytes", 1000)]
    # a snapshot is a copy: later counts do not change it
    trace.count("plan_draws")
    assert snap["counters"]["plan_draws"] == 42
    trace.reset()
    assert trace.snapshot() == {"spans": {}, "counters": {}, "compiles": {}}
    assert trace.events() == []


def test_events_keep_the_latest():
    gc.disable()                    # no collector spans among the counts
    try:
        for i in range(trace.EVENTS + 10):
            trace.count("n", i)
    finally:
        gc.enable()
    ev = trace.events()
    assert len(ev) == trace.EVENTS
    assert ev[0][3] == 10 and ev[-1][3] == trace.EVENTS + 9


def test_threads_lose_no_update():
    """The staging thread logs beside the main thread: no span or count
    is lost under contention."""
    n_threads, n = 2 * (os.cpu_count() or 2), 300
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)

    def work():
        for _ in range(n):
            with trace.span("stage_data"):
                trace.count("h2d_bytes", 3)

    threads = [threading.Thread(target=work) for _ in range(n_threads)]
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
    finally:
        sys.setswitchinterval(interval)
    assert not any(t.is_alive() for t in threads)
    snap = trace.snapshot()
    assert snap["spans"]["fl/stage_data"]["count"] == n_threads * n
    assert snap["counters"]["h2d_bytes"] == 3 * n_threads * n


def test_forced_collection_is_a_gc_span_and_counted():
    trace.watch_host()
    trace.watch_host()              # a second call adds no second hook
    assert gc.callbacks.count(trace._on_gc) == 1
    gc.collect()
    snap = trace.snapshot()
    assert snap["counters"]["gc2"] == 1
    assert snap["counters"]["gc2_s"] > 0
    assert snap["spans"]["fl/gc"]["count"] == 1
    assert snap["spans"]["fl/gc"]["seconds"] == pytest.approx(
        snap["counters"]["gc2_s"])
    gc.collect(0)                   # generation 0: counted, not a span
    snap = trace.snapshot()
    assert snap["counters"]["gc0"] >= 1
    assert snap["spans"]["fl/gc"]["count"] == 1


def test_compile_inside_a_span_is_counted_under_it():
    trace.watch_host()
    x = jnp.arange(7.0)

    def f(v):
        return jnp.cos(v) * 3.0 + 0.5     # a fresh function: it compiles

    with trace.span("dispatch"):
        with trace.span("put"):
            pass
        jax.jit(f)(x).block_until_ready()
    comp = trace.snapshot()["compiles"]
    assert comp["fl/dispatch"]["count"] >= 1
    assert comp["fl/dispatch"]["seconds"] > 0
    assert "fl/put" not in comp


def test_profiler_host_plane_holds_the_plan_span(tmp_path):
    cfg = get_config("fedsr-mlp")
    fl = _fl()
    train, _ = _task()
    rng = np.random.default_rng(0)
    clients = make_clients(train, scheme=fl.partition,
                           num_devices=fl.num_devices, rng=rng, xi=fl.xi,
                           alpha=fl.alpha)
    algo = make_algorithm("fedsr", LocalTrainer(cfg, fl), clients, fl)
    trace.reset()
    jax.profiler.start_trace(str(tmp_path))
    try:
        algo.plan_schedule(0, 2, rng, {})
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(f"{tmp_path}/**/*.xplane.pb", recursive=True)
    data = jax.profiler.ProfileData.from_file(path)
    found = [(plane.name, e.duration_ns) for plane in data.planes
             for line in plane.lines for e in line.events
             if e.name == "fl/plan"]
    assert len(found) == 1
    plane, dur_ns = found[0]
    assert plane.startswith("/host:")
    logged = trace.snapshot()["spans"]["fl/plan"]
    assert logged["count"] == 1
    # the log's clock runs inside the annotation
    assert 0 < logged["seconds"] <= dur_ns * 1e-9 * 1.05 + 1e-5
    assert logged["seconds"] >= dur_ns * 1e-9 * 0.5
    # one count of the batch plans drawn per ring-hop call: one a round
    draws = [v for n, s, e, v in trace.events() if n == "plan_draws"]
    assert len(draws) == 2
    assert sum(draws) == trace.snapshot()["counters"]["plan_draws"] > 0


def test_block_loop_spans_in_experiment_result(tmp_path):
    cfg = get_config("fedsr-mlp")
    fl = _fl()
    train, test = _task()
    res = run_experiment(task="mnist_like", model_cfg=cfg, fl=fl,
                         eval_every=2, train=train, test=test,
                         checkpoint_dir=str(tmp_path), checkpoint_every=2)
    spans = res.spans["spans"]
    blocks = fl.rounds // 2
    for name in BLOCK + ("fl/eval", "fl/checkpoint"):
        assert spans[name]["count"] == blocks, name
    assert set(spans) <= set(trace.SPANS)
    # the device store builds its plane once, on the first block
    assert spans["fl/stage_data"]["count"] == 1
    counters = res.spans["counters"]
    assert counters["plan_draws"] > 0
    assert counters["h2d_bytes"] > 0
    # the first block's dispatch compiles the block program
    assert res.spans["compiles"]["fl/dispatch"]["count"] >= 1
    # each block's spans run in block order
    ev = [e for e in trace.events() if e[0] in BLOCK]
    assert [e[0] for e in ev[:len(BLOCK)]] == list(BLOCK)


def test_experiment_result_spans_cover_only_its_run():
    cfg = get_config("fedsr-mlp")
    train, test = _task()
    with trace.span("plan"):
        pass
    res = run_experiment(task="mnist_like", model_cfg=cfg,
                         fl=_fl(rounds=2), eval_every=2, train=train,
                         test=test)
    assert res.spans["spans"]["fl/plan"]["count"] == 1


def test_h2d_counter_matches_the_trainer():
    cfg = get_config("fedsr-mlp")
    fl = _fl()
    train, _ = _task()
    rng = np.random.default_rng(1)
    clients = make_clients(train, scheme=fl.partition,
                           num_devices=fl.num_devices, rng=rng, xi=fl.xi,
                           alpha=fl.alpha)
    trainer = LocalTrainer(cfg, fl)
    algo = make_algorithm("fedsr", trainer, clients, fl)
    w = init_small_model(jax.random.PRNGKey(0), cfg)
    meter = CommMeter(model_bytes=1)
    state = {}
    h0 = trainer.h2d_bytes
    for t in (0, 2):
        w, state = algo.run_schedule(w, t, np.asarray([0.05, 0.05]), rng,
                                     meter, state)
    assert (trace.snapshot()["counters"]["h2d_bytes"]
            == trainer.h2d_bytes - h0 > 0)


def test_named_scopes_reach_the_block_program():
    """The device-side scopes of ``core.local`` are in the compiled block
    program's op metadata."""
    cfg = get_config("fedsr-mlp")
    fl = _fl()
    train, _ = _task()
    rng = np.random.default_rng(2)
    clients = make_clients(train, scheme=fl.partition,
                           num_devices=fl.num_devices, rng=rng, xi=fl.xi,
                           alpha=fl.alpha)
    trainer = LocalTrainer(cfg, fl)
    algo = make_algorithm("fedsr", trainer, clients, fl)
    w = init_small_model(jax.random.PRNGKey(0), cfg)
    sched = algo.plan_schedule(0, 2, rng, {})
    lrs = np.asarray([0.05, 0.05])
    algo.dispatch_block(sched, w, lrs, {})
    (fn,) = trainer._sched_fns.values()
    xs = algo.engine._stack_cohort_schedule(sched.plans, lrs, "plain", {})
    plane = algo.engine.plane
    hlo = fn.lower(w, {}, plane.images, plane.labels, plane.offsets,
                   {k: jnp.asarray(v) for k, v in xs.items()}
                   ).compile().as_text()
    for scope in ("hop_gather", "local_grad", "momentum_update",
                  "edge_cloud_reduce"):
        assert f"/{scope}/" in hlo, scope
