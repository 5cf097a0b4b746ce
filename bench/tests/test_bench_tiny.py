"""The CPU cut of a cell, which comes from the cell's own files, and a new
cell added to a copy of the benchmark as new files only."""
import hashlib
import json
import os
import shutil
import subprocess
import sys

import pytest

from bench_tiny import ROOT, harness, merged, tiny_cell

SPEC = harness.read_json(ROOT / "BENCHMARK.json")
CELL = SPEC["workloads"][0]


def test_tiny_merges_nested_keys_and_leaves_the_file_alone():
    base = {"data": {"train": 600, "test": 100, "noise": 0.15},
            "fl": {"devices": 100, "rings": {"edges": 25, "hops": 4}},
            "limits": [1, 2]}
    kept = json.loads(json.dumps(base))
    got = merged(base, {"data": {"train": 6}, "fl": {"rings": {"edges": 5}},
                        "limits": [3]}, "t.json")
    assert got == {"data": {"train": 6, "test": 100, "noise": 0.15},
                   "fl": {"devices": 100, "rings": {"edges": 5, "hops": 4}},
                   "limits": [3]}
    assert base == kept


@pytest.mark.parametrize("tiny,where", [
    ({"sizes": 3}, "'sizes'"),
    ({"data": {"train": 6, "extra": 1}}, "t.json: data: 'tiny' sets 'extra'"),
])
def test_tiny_refuses_a_key_the_file_lacks(tiny, where):
    with pytest.raises(KeyError, match=where):
        merged({"data": {"train": 600}}, tiny, "t.json")


def test_tiny_cell_refuses_a_traffic_file_without_tiny(monkeypatch):
    read = harness.read_json

    def without_tiny(path):
        body = read(path)
        if path.parent.name == "traffic":
            body.pop("tiny", None)
        return body

    monkeypatch.setattr(harness, "read_json", without_tiny)
    with pytest.raises(ValueError, match=rf"{CELL['traffic']}\.json has no 'tiny'"):
        tiny_cell(CELL["name"])


def test_tiny_cell_cuts_the_traffic_by_its_own_file():
    cell = tiny_cell(CELL["name"])
    full = harness.Cell.load(CELL["name"])
    assert "tiny" not in cell.traffic
    assert cell.traffic == merged({k: v for k, v in full.traffic.items()
                                   if k != "tiny"}, full.traffic["tiny"], "")


def _digests(bench):
    return {p.relative_to(bench).as_posix(): hashlib.sha256(p.read_bytes()).hexdigest()
            for p in sorted(bench.rglob("*"))
            if p.is_file() and "__pycache__" not in p.parts}


def _write(path, body):
    path.write_text(json.dumps(body, indent=1) + "\n")


def test_a_new_cell_is_new_files_only(tmp_path):
    """A second MLP, a traffic copy and a cell, added to a copy of the
    benchmark as new files and new BENCHMARK.json entries: its tiny run is
    correct, and its control and each fault are not."""
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(harness.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    (tmp_path / "src").symlink_to(ROOT / "src")
    bench = tmp_path / "bench"
    before = _digests(bench)

    cfg = harness.read_json(bench / "configs" / "fedsr-mlp.json")
    counts = harness.load_module(harness.BENCH / "configs" / "fedsr-mlp.py")
    config = "fedsr-mlp-400"
    wide = dict(cfg, name=config, mlp_hidden=[400, 400],
                program={"mlp_hidden": "mlp_hidden"})
    wide["params"] = counts.param_count(wide)
    cut = dict(wide, mlp_hidden=[24, 12])
    wide["tiny"] = {"mlp_hidden": cut["mlp_hidden"],
                    "params": counts.param_count(cut)}
    _write(bench / "configs" / f"{config}.json", wide)
    for base in ("configs", "reference"):
        shutil.copy(bench / base / "fedsr-mlp.py", bench / base / f"{config}.py")

    traffic = harness.read_json(bench / "traffic" / f"{CELL['traffic']}.json")
    mix = f"{CELL['traffic']}.k16"
    traffic["tiny"] = {"data": {"train_per_class": 16, "test_per_class": 8},
                       "fl": {"num_devices": 16, "num_edges": 4}}
    _write(bench / "traffic" / f"{mix}.json", traffic)

    spec = harness.read_json(tmp_path / "BENCHMARK.json")
    name = "mlp400.table4"
    spec["configs"].append({"name": config, "source": "a test", "reduced": [],
                            "file": f"bench/configs/{config}.json",
                            "why": "a second MLP"})
    spec["workloads"].append({"name": name, "config": config, "traffic": mix,
                              "chips": 1, "why": "a test"})
    for m in spec["end_to_end"] + spec["per_layer"]:
        if CELL["name"] in m.get("workloads", []):
            m["workloads"].append(name)
    _write(tmp_path / "BENCHMARK.json", spec)

    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    p = subprocess.run([sys.executable, "bench/tests/bench_tiny.py",
                        "--workload", name], cwd=tmp_path, env=env,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode == 0, p.stderr[-4000:]
    assert json.loads(p.stdout.splitlines()[-1]) == {
        "run": True, "control": False, "unchanged": False, "half_batch": False}
    after = _digests(bench)
    assert {k: after[k] for k in before} == before
