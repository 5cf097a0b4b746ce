"""BENCHMARK.json against the benchmark's contract: keys, names and
units, the files each entry names, and which cells report which metric."""
import re

import pytest

from bench_tiny import ROOT, harness, tiny_cell

SPEC = harness.read_json(ROOT / "BENCHMARK.json")
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
TEXT = re.compile(r"^[^\t\n]{1,200}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def _cells():
    return {w["name"]: w for w in SPEC["workloads"]}


def test_top_level_keys_and_sizes():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert len((ROOT / "BENCHMARK.json").read_bytes()) <= 64 * 1024
    assert 1 <= len(SPEC["command"]) <= 32
    assert all(TEXT.match(w) for w in SPEC["command"])
    assert SPEC["command"][1].startswith(tuple(p + "/" for p in SPEC["paths"]))
    assert 1 <= len(SPEC["paths"]) <= 16
    for p in SPEC["paths"]:
        assert re.match(r"^[A-Za-z0-9_./-]{1,200}$", p) and ".." not in p
        assert (ROOT / p).is_dir()
    assert isinstance(SPEC["run_seconds"], int) and 1 <= SPEC["run_seconds"] <= 51
    # a full check of 24 cells has to fit: 2 + 14 runs a cell, each
    # run_seconds + 60 s, 2 x 90 s of compile a cell, 1200 s spare
    assert (2 + 14 * 24) * (SPEC["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200


def test_configs():
    assert 1 <= len(SPEC["configs"]) <= 24
    used = {w["config"] for w in SPEC["workloads"]}
    files = set()
    for c in SPEC["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and c["name"] in used
        assert TEXT.match(c["source"]) and TEXT.match(c["why"])
        assert c["file"].startswith("bench/") and c["file"] not in files
        files.add(c["file"])
        body = harness.read_json(ROOT / c["file"])
        assert body["name"] == c["name"] and body["reduced"] == c["reduced"]
        assert len(c["reduced"]) <= 16
        for base in ("configs", "reference"):
            assert (harness.BENCH / base / f"{c['name']}.py").is_file()


def test_workloads():
    cells = SPEC["workloads"]
    assert 1 <= len(cells) <= 24
    assert len({w["name"] for w in cells}) == len(cells)
    assert len({(w["config"], w["traffic"]) for w in cells}) == len(cells)
    configs = {c["name"] for c in SPEC["configs"]}
    for w in cells:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["config"] in configs and w["chips"] in (1, 4)
        assert TEXT.match(w["why"])
        traffic = harness.read_json(harness.BENCH / "traffic"
                                    / f"{w['traffic']}.json")
        assert (harness.BENCH / "runners" / f"{traffic['runner']}.py").is_file()
        assert set(traffic["limits"]) and all(
            v > 0 for v in traffic["limits"].values())
    four = sum(w["chips"] == 4 for w in cells)
    assert four <= max(1, len(cells) // 2)


def test_every_traffic_file_a_cell_names_has_a_tiny_cut():
    """The CPU tests cut each cell by its own files; no runner reads the
    cut."""
    for w in SPEC["workloads"]:
        traffic = harness.read_json(harness.BENCH / "traffic"
                                    / f"{w['traffic']}.json")
        assert isinstance(traffic.get("tiny"), dict) and traffic["tiny"], w
        tiny_cell(w["name"])
    for runner in (harness.BENCH / "runners").glob("*.py"):
        assert "tiny" not in runner.read_text(), runner


@pytest.mark.parametrize("kind", ["end_to_end", "per_layer"])
def test_metric_entries(kind):
    names = set()
    cells = _cells()
    for m in SPEC[kind]:
        keys = {"name", "unit", "better", "source"}
        keys |= ({"bound"} if kind == "end_to_end" else {"layer", "moves"})
        assert set(m) - {"workloads"} == keys
        assert NAME.match(m["name"]) and m["name"] not in names
        names.add(m["name"])
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
        assert all(c in cells for c in m.get("workloads", []))
        if kind == "end_to_end":
            assert m["source"] in ("host_clock", "device_trace")
            assert 0.01 <= m["bound"] <= 0.25
        else:
            assert TEXT.match(m["layer"])
            assert (harness.BENCH / "metrics" / f"{m['name']}.py").is_file()
    assert 1 <= len(names) <= (16 if kind == "end_to_end" else 128)


def test_every_cell_reports_setup_another_e2e_and_a_layer_metric():
    setup = [m for m in SPEC["end_to_end"] if m["name"] == "setup_s"]
    assert len(setup) == 1 and "workloads" not in setup[0]
    assert setup[0]["bound"] <= 0.25
    for name in _cells():
        cell = harness.Cell.load(name)
        e2e = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in e2e and len(e2e) >= 2
        assert cell.per_layer


def test_each_layer_metric_moves_a_metric_its_cells_report():
    for m in SPEC["per_layer"]:
        for name in m.get("workloads", list(_cells())):
            cell = harness.Cell.load(name)
            assert m["moves"] in {e["name"] for e in cell.end_to_end}, (
                m["name"], name)
    layers = {}
    for m in SPEC["per_layer"]:
        layers.setdefault(m["layer"].lower(), set()).add(m["layer"])
    assert all(len(v) == 1 for v in layers.values())


def test_file_names_are_made_of_name_characters():
    for path in harness.BENCH.rglob("*"):
        if "__pycache__" in path.parts or path.is_dir():
            continue
        rel = path.relative_to(ROOT).as_posix()
        assert re.match(r"^[A-Za-z0-9_./-]+$", rel), rel
