"""The readers of the program's span log (``bench/spans.py`` and the
``plan_ms``, ``launch_ms``, ``h2d_kb`` and ``gc_ms`` readers): on a
synthetic log, on a program without the log, and on a tiny run of the
training runner."""
import time

import pytest

from bench_tiny import harness, tiny_cell
from bench import spans
from repro.utils import trace

READERS = ("plan_ms.train", "launch_ms.train", "h2d_kb.train",
           "gc_ms.train")


def _read(metric, record):
    return harness.load_module(
        harness.BENCH / "metrics" / f"{metric}.py").read(record)


def _block(t, plan=4.0, pack=1.0, put=0.5, dispatch=2.0, h2d=2048):
    """One block's events from ``t`` (ms): plan, stage, pack, the h2d
    count, put, dispatch, finish, then the eval's 10 ms."""
    ev, at = [], t
    for name, d in (("fl/plan", plan), ("fl/stage", 0.5),
                    ("fl/pack", pack), ("h2d_bytes", None),
                    ("fl/put", put), ("fl/dispatch", dispatch),
                    ("fl/finish", 0.25)):
        if d is None:
            ev.append((name, at * 1e-3, at * 1e-3, h2d))
        else:
            ev.append((name, at * 1e-3, (at + d) * 1e-3, d * 1e-3))
            at += d
    return ev, at + 10.0


def _log(setup=2, blocks=3):
    """``setup`` set-up blocks whose planning is slow, then ``blocks``
    window blocks, a collector span inside the window, one that straddles
    its start and one after it (the runner's clean-up)."""
    ev, t = [], 0.0
    for _ in range(setup):
        b, t = _block(t, plan=50.0, dispatch=500.0, h2d=999)
        ev += b
    start = t
    ev.append(("fl/gc", (start - 2.0) * 1e-3, (start + 1.0) * 1e-3, 3e-3))
    for k in range(blocks):
        b, t = _block(t, h2d=2048 + k)
        ev += b
    ev.insert(len(ev) - 5, ("fl/gc", (t - 15.0) * 1e-3, (t - 14.0) * 1e-3,
                            1e-3))
    ev.append(("fl/gc", (t + 5.0) * 1e-3, (t + 30.0) * 1e-3, 25e-3))
    return ev


@pytest.fixture
def log(monkeypatch):
    ev = _log()
    monkeypatch.setattr(trace, "events", lambda: list(ev))
    spans._last[:] = [None, None]
    yield ev
    spans._last[:] = [None, None]


def test_cut_keeps_the_last_blocks_clipped():
    ev = _log()
    got = spans.cut(ev, 3)
    plans = [e for e in got if e[0] == "fl/plan"]
    assert len(plans) == 3
    assert all(e[2] - e[1] == pytest.approx(4e-3) for e in plans)
    lo, hi = plans[0][1], max(e[2] for e in got)
    assert all(lo <= e[1] <= e[2] <= hi for e in got)
    gcs = [e for e in got if e[0] == "fl/gc"]
    # the straddling pause is clipped at the window's start; the one
    # after the last block is left out
    assert [round((e[2] - e[1]) * 1e3, 9) for e in gcs] == [1.0, 1.0]
    assert spans.cut(ev, 6) is None and spans.cut(ev, 0) is None


def test_readers_on_a_synthetic_log(log, capsys):
    record = {"blocks": 3}
    assert _read("plan_ms.train", record) == pytest.approx(4.0)
    assert _read("launch_ms.train", record) == pytest.approx(3.5)
    assert _read("h2d_kb.train", record) == pytest.approx(2049 / 1024)
    assert _read("gc_ms.train", record) == pytest.approx(2.0 / 3)
    lines = [s for s in capsys.readouterr().out.splitlines() if s]
    assert len(lines) == 1          # printed once per record
    assert '"window_spans"' in lines[0]


def test_summary_counts_spans_and_sums_counters():
    got = spans.summary([("fl/plan", 0.0, 0.25, 0.25),
                         ("fl/plan", 1.0, 1.5, 0.5),
                         ("h2d_bytes", 2.0, 2.0, 100),
                         ("h2d_bytes", 3.0, 3.0, 28)])
    assert got == {"fl/plan": {"count": 2, "seconds": 0.75},
                   "h2d_bytes": 128}


@pytest.mark.parametrize("metric", READERS)
def test_readers_find_nothing_without_the_log(metric, monkeypatch):
    spans._last[:] = [None, None]
    monkeypatch.setattr(spans, "_log", lambda: None)
    assert _read(metric, {"blocks": 3}) is None
    spans._last[:] = [None, None]


@pytest.mark.parametrize("metric", READERS)
def test_readers_find_nothing_past_the_log(metric, log):
    # more window blocks than the log holds plans
    assert _read(metric, {"blocks": 40}) is None
    assert _read(metric, {}) is None


def test_readers_on_a_tiny_run():
    cell = tiny_cell("mlp.table4")
    harness.configure(cell, cache=False)
    ctx = harness.Context(cell, 2**31 + 5, 0.5, False, time.perf_counter())
    out = cell.runner.run(ctx)
    record = out.record
    spans._last[:] = [None, None]
    got = spans.window(record)
    blocks = record["blocks"]
    names = [e[0] for e in got]
    for name in spans.BLOCK_SPANS:
        assert names.count(name) == blocks, name
    assert names.count("h2d_bytes") == blocks
    values = {m: _read(m, record) for m in READERS}
    assert values["plan_ms.train"] > 0 and values["launch_ms.train"] > 0
    assert values["gc_ms.train"] >= 0
    # every block of the cell uploads the same index arrays
    h2d = [v for n, _, _, v in got if n == "h2d_bytes"]
    assert len(set(h2d)) == 1
    assert values["h2d_kb.train"] == pytest.approx(h2d[0] / 1024)
    # the window's plan spans take at most the window's wall time
    assert values["plan_ms.train"] * blocks * 1e-3 < ctx.window_s
    spans._last[:] = [None, None]
