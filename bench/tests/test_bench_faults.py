"""The control and every fault a cell can have make ``correct`` come out
false, at a tiny size on the CPU. The limits are the cells' own."""
import pytest

from bench_tiny import control_reading, harness, run, tiny_cell

CELLS = [w["name"] for w in
         harness.read_json(harness.ROOT / "BENCHMARK.json")["workloads"]]


@pytest.mark.parametrize("name", CELLS)
def test_control_is_not_correct(name):
    """The reference with every product from one bfloat16 pass, in the
    program's place."""
    cell = tiny_cell(name)
    got = control_reading(cell)
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


def test_half_batch_at_the_hop_gather_reads_as_a_half_loss():
    """The half batch planted in the hop gather reads, to every digit, as
    the same fault planted in the classifier's loss: the loss sees the
    first half of each lane's rows either way."""
    from bench import calibrate
    from repro.core import local
    name = CELLS[0]
    cell = tiny_cell(name)
    harness.configure(cell, cache=False)
    with calibrate.FAULTS["half_batch"](cell):
        at_gather = calibrate.reading(cell, 7, 0.2)
    loss = local.classifier_loss

    def half(params, batch, cfg):
        n = batch["labels"].shape[0] // 2
        return loss(params, {k: v[:n] for k, v in batch.items()}, cfg)

    with calibrate._patch(local, "classifier_loss", half):
        at_loss = calibrate.reading(tiny_cell(name), 7, 0.2)
    assert at_gather == at_loss
