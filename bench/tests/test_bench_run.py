"""Whole benchmark runs on the CPU at a tiny size: a sound run is correct,
each planted fault of the timed path is caught, and a run refuses a CPU."""
import os
import shutil
import subprocess
import sys

import pytest

from bench.tests.conftest import BENCH, ROOT, drive


def _run_py(cwd: str) -> subprocess.CompletedProcess:
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         "smollm2-360m.powersgd.1chip", "--seed", str(2**33 + 1),
         "--seconds", "1", "--trace", "0"],
        cwd=cwd, capture_output=True, text=True, env=env, timeout=300)


def test_refuses_a_cpu():
    proc = _run_py(ROOT)
    assert proc.returncode != 0
    assert "no TPU" in proc.stderr
    assert not proc.stdout.strip()


def test_refuses_without_the_program(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    proc = _run_py(str(tmp_path))
    assert proc.returncode != 0
    assert not proc.stdout.strip()


def test_sound_run_is_correct(tiny_root, tmp_path):
    res = drive(tiny_root, "tiny.powersgd", cache=str(tmp_path))
    assert res["correct"] is True, res["compared"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert set(res["metrics"]) == {"tokens_per_s", "step_ms_p90",
                                   "peak_hbm_gib", "setup_s"}
    assert res["device"]["platform"] == "cpu"
    assert list(res)[-1] == "compared"


@pytest.mark.parametrize("cell,fault,devices", [
    ("tiny.powersgd", "unchanged", 1),
    ("tiny.powersgd", "half_batch", 1),
    ("tiny.powersgd", "labels_are_tokens", 1),
    ("tiny.syncsgd4", "no_exchange", 4),
])
def test_planted_fault_is_not_correct(tiny_root, tmp_path, cell, fault,
                                      devices):
    res = drive(tiny_root, cell, fault, devices, cache=str(tmp_path))
    assert res["correct"] is False, res["compared"]


@pytest.mark.parametrize("fault,correct", [
    ("none", True), ("unchanged", False), ("half_batch", False),
    ("labels_are_tokens", False)])
def test_syncsgd_on_one_device(tiny_root, tmp_path, fault, correct):
    """The one-chip syncSGD cell's plan (no compression, all-reduce over a
    one-device data axis): a sound run is correct, each planted fault of
    the timed path is not."""
    res = drive(tiny_root, "tiny.syncsgd1", fault, cache=str(tmp_path))
    assert res["correct"] is correct, res["compared"]


def test_window_counts_all_work_sent():
    """The window sends steps ahead, and closes only once every step it
    sent has ended: each step is counted once, in order, with its loss."""
    import time

    import jax.numpy as jnp

    from bench import harness

    def step(state, batch, lr):
        time.sleep(0.01)
        return state + 1, {"loss": (state + batch).astype(jnp.float32)}

    def feed():
        i = 0
        while True:
            yield jnp.int32(100 * i)
            i += 1

    state, metrics, win = harness.window(step, jnp.int32(0), feed(),
                                         jnp.float32(0.1), 0.2, depth=4)
    n = int(state)
    assert n >= 4 and len(win["step_s"]) == len(win["wait_s"]) == n
    assert win["losses"] == [101.0 * i for i in range(n)]
    assert win["window_s"] >= 0.2
    assert sum(win["step_s"]) == pytest.approx(win["window_s"], abs=0.05)
    assert harness.ahead({"step2": 1.0, "step3": 1.5}) == 12
