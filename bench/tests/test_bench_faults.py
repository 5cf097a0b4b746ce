"""The control and every fault a cell can have make ``correct`` come out
false, at a tiny size on the CPU. The limits are the cells' own."""
import pytest

from bench_tiny import harness, run, tiny_cell

CELLS = [w["name"] for w in
         harness.read_json(harness.ROOT / "BENCHMARK.json")["workloads"]]


@pytest.mark.parametrize("name", CELLS)
def test_control_is_not_correct(name):
    """The reference with every product from one bfloat16 pass, in the
    program's place."""
    from bench import calibrate
    cell = tiny_cell(name)
    harness.configure(cell, cache=False)
    got = calibrate.reading(cell, 5, 0.2, control=True)
    lim = cell.traffic["limits"]
    assert any(got[k] > lim[k] for k in lim), got


@pytest.mark.parametrize("name", CELLS)
@pytest.mark.parametrize("fault", ["unchanged", "half_batch"])
def test_fault_is_not_correct(name, fault):
    """A run with the timed path broken underneath comes out not correct."""
    from bench import calibrate
    cell = tiny_cell(name)
    with calibrate.FAULTS[fault](cell):
        line = run(cell, 7)
    assert not line["correct"], line["checks"]
