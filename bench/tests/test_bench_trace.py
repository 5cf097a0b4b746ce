"""The trace reduction and the per-layer metric readers, on a small
synthetic trace of two chips."""
import pytest

from bench_tiny import harness
from bench.trace import Trace, _self_times

MS = 1e6    # nanoseconds


def _trace():
    # window 0..100 ms. Chip 0: a loop op (10..50) with two ops inside it,
    # an all-reduce 60..70, one op starting before the window. Chip 1: one
    # op 0..40 and an all-gather 80..90 ms.
    ops = {
        0: [("while.1", 10 * MS, 50 * MS), ("fusion.2", 12 * MS, 20 * MS),
            ("convolution.3", 30 * MS, 45 * MS),
            ("all-reduce.4", 60 * MS, 70 * MS),
            ("fusion.2", -5 * MS, 5 * MS)],
        1: [("fusion.2", 0, 40 * MS), ("all-gather-start.5", 80 * MS, 90 * MS)],
    }
    spans = [("window", 0, 100 * MS), ("run_schedule", 0, 8 * MS),
             ("eval", 50 * MS, 58 * MS), ("readback", 70 * MS, 100 * MS)]
    return Trace(ops=ops, spans=spans, window=(0, 100 * MS))


def test_busy_idle_and_clipping():
    t = _trace()
    busy = t.device_busy_s()
    assert busy[0] == pytest.approx((5 + 40 + 10) * 1e-3)   # 0..5 clipped
    assert busy[1] == pytest.approx(50e-3)
    assert t.window_s == pytest.approx(0.1)
    assert t.busy_s == pytest.approx(52.5e-3)


def test_self_time_subtracts_nested_events():
    st = _self_times([("while", 0, 100), ("a", 10, 20), ("b", 30, 60),
                      ("c", 40, 50), ("a", 70, 80)])
    assert st == {"while": 50, "a": 20, "b": 20, "c": 10}


def test_collectives_and_breakdown():
    t = _trace()
    assert t.collective_s() == pytest.approx(10e-3)          # mean of 10, 10
    b = t.breakdown()
    ops = dict(b["device_ops"])
    assert ops["while.1"] == pytest.approx(17e-3 / 2)        # 40 - 8 - 15
    assert ops["fusion.2"] == pytest.approx((13 + 40) * 1e-3 / 2)
    # chip 0 idles 5..10 (run_schedule), 50..60 (eval), 70..100 (readback)
    assert b["idle_gaps"] == [["readback", pytest.approx(30e-3)],
                              ["eval", pytest.approx(10e-3)],
                              ["run_schedule", pytest.approx(5e-3)]]


def _read(metric, record):
    return harness.load_module(harness.BENCH / "metrics" / f"{metric}.py").read(record)


def test_metric_readers():
    t = _trace()
    peak = {"flops_per_s": 100.0, "bytes_per_s": 10.0}
    train = {"trace": t, "window_s": 2.0, "chips": 2, "peak": peak,
             "lane_steps": 5, "flops": 100.0, "bytes": 4.0, "rounds": 4}
    # flops bound: 100 / (2 x 100) = 0.5 s of the 2 s window
    assert _read("mfu.train", train) == pytest.approx(25.0)
    assert _read("idle_share.train", train) == pytest.approx(47.5)
    # bytes bound: 40 / (2 x 10) = 2 s, the whole window
    assert _read("mfu.train", dict(train, bytes=40.0)) == pytest.approx(100.0)
    assert _read("mfu.train", {k: v for k, v in train.items()
                               if k != "lane_steps"}) is None
