"""chip_smoke.py on the CPU backend: its phases at a tiny size (the fused
vs sequential and stacked vs per-model loop checks must hold), its 4-chip
phase on 4 virtual CPU devices, and its refusal to report a result where
there is no TPU."""
import contextlib
import io
import json
import os
import shutil
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import chip_smoke as cs

# batch 8: at batch 32 the 10-sample shards are mostly resampled rows and
# the tiny CNN run turns chaotic enough to drift past the engine bound
TINY = dict(num_devices=8, num_edges=2, participation=0.5, ring_rounds=1,
            batch_size=8,
            task_kwargs=dict(train_per_class=8, test_per_class=4))


def _run_phase(fn, *args, **kwargs):
    """Call a phase, returning its result and the JSON line it printed."""
    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        result = fn(*args, **kwargs)
    (line,) = [json.loads(s) for s in out.getvalue().splitlines() if s]
    return result, line


@pytest.fixture(scope="module")
def cnn_phase():
    (res, test), line = _run_phase(
        cs.train_phase, "fedsr-cnn", "cifar10_like", personalize=cs.HEAD,
        **TINY)
    return res, test, line


@pytest.mark.parametrize("model", ["fedsr-cnn", "fedsr-mlp"])
def test_train_phase_fused_matches_sequential(model, cnn_phase):
    if model == "fedsr-cnn":
        line = cnn_phase[2]
    else:
        _, line = _run_phase(cs.train_phase, model, "mnist_like", **TINY)
    assert line["phase"] == f"train/{model}"
    assert line["compile_s"] > 0                    # the program's count
    assert line["dispatches"] == 2                  # one per eval block
    assert len(line["accuracy"]) == 2
    assert line["max_acc_dev"] <= cs.ACC_TOL
    assert line["max_rel_dev"] <= cs.PRECISION_TOL
    assert line["max_rel_dev_float32"] <= cs.ENGINE_TOL[model]


def test_serve_phase_stacked_matches_loop(cnn_phase):
    res, test, _ = cnn_phase
    assert res.personalized_fleet is not None
    _, line = _run_phase(cs.serve_phase, "fedsr-cnn", res.personalized_fleet,
                         test, requests=16)
    assert line["requests"] == 16
    assert line["distinct_lanes"] == TINY["num_devices"]
    assert line["max_rel_dev"] <= cs.SERVE_TOL
    assert line["max_rel_dev_float32"] <= cs.SERVE_F32_TOL


def test_mesh_phase_on_four_virtual_devices():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    code = ("import chip_smoke as cs\n"
            f"cs.mesh_phase(**{TINY!r})\n")
    proc = subprocess.run([sys.executable, "-c", code], cwd=ROOT, env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-2000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["devices"] == 4
    assert line["dispatches"] == 2
    assert line["max_acc_dev"] <= cs.ACC_TOL
    assert line["max_rel_dev"] <= cs.PRECISION_TOL
    assert line["max_rel_dev_float32"] <= cs.ENGINE_TOL["fedsr-cnn"]


def _no_result(proc) -> bool:
    return proc.returncode != 0 and '"ok"' not in proc.stdout


def test_entry_fails_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, "chip_smoke.py"], cwd=ROOT,
                          env=env, capture_output=True, text=True,
                          timeout=300)
    assert _no_result(proc), (proc.returncode, proc.stdout)
    assert "no TPU" in proc.stderr


def test_entry_fails_outside_the_repo(tmp_path):
    shutil.copy(os.path.join(ROOT, "chip_smoke.py"), tmp_path)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    proc = subprocess.run([sys.executable, "chip_smoke.py"], cwd=tmp_path,
                          env=dict(env, JAX_PLATFORMS="cpu"),
                          capture_output=True, text=True, timeout=300)
    assert _no_result(proc), (proc.returncode, proc.stdout)
