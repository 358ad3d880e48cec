import json
import os
import shutil
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "bench")

#: a SmolLM2-shaped model small enough for the CPU
TINY = dict(name="tiny", n_layers=2, d_model=64, n_heads=4, n_kv_heads=2,
            head_dim=16, d_ff=128, vocab=256)
#: limits for the tiny cells, between what sound runs read (loss 3e-5,
#: grad 2.4e-3, change 2.8e-3, gradient error 0.011) and what the float8
#: control reads (grad 0.013-0.025, gradient error 0.08-0.14) at this size
#: on the CPU
TINY_LIMITS = {"loss_gap": 1e-3, "grad_gap": 0.008, "delta_gap": 0.03,
               "grad_err": 0.04}


def copy_bench(dst: str) -> str:
    """BENCHMARK.json and the files the benchmark finds by name (not the
    rest of its code) under ``dst``; returns ``dst``."""
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), dst)
    for sub in ("configs", "models", "workloads", "metrics", "compressors"):
        shutil.copytree(os.path.join(BENCH, sub),
                        os.path.join(dst, "bench", sub))
    shutil.copy(os.path.join(BENCH, "peaks.json"),
                os.path.join(dst, "bench", "peaks.json"))
    return dst


def add_cell(root: str, name: str, workload: dict) -> None:
    with open(os.path.join(root, "bench", "workloads", name + ".json"),
              "w") as f:
        json.dump(dict(workload, name=name), f)
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        b = json.load(f)
    b["workloads"].append({"name": name, "config": workload["config"],
                           "traffic": name, "chips": workload["chips"],
                           "why": "a test cell"})
    with open(path, "w") as f:
        json.dump(b, f)


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    """A benchmark tree with three tiny cells on the CPU: ``tiny.powersgd``
    and ``tiny.syncsgd1`` (one device, PowerSGD and syncSGD as in the
    one-chip cells) and ``tiny.syncsgd4`` (four devices, syncSGD as in the
    four-chip cell)."""
    root = copy_bench(str(tmp_path_factory.mktemp("bench_root")))
    with open(os.path.join(BENCH, "configs", "smollm2-360m.json")) as f:
        cfg = dict(json.load(f), **TINY)
    with open(os.path.join(root, "bench", "configs", "tiny.json"),
              "w") as f:
        json.dump(cfg, f)
    for name, src, chips in (
            ("tiny.powersgd", "smollm2-360m.powersgd.1chip", 1),
            ("tiny.syncsgd1", "smollm2-360m.syncsgd.1chip", 1),
            ("tiny.syncsgd4", "smollm2-1.7b.syncsgd.4chip", 4)):
        with open(os.path.join(BENCH, "workloads", src + ".json")) as f:
            w = json.load(f)
        # 0.0572 MB closes one bucket per layer and one for the tail, so
        # no PowerSGD matrix has fewer rows than the rank
        w["plan"]["bucket_mb"] = 0.0572
        w.update(config="tiny", chips=chips, seq=64, seqs_per_chip=2,
                 limits=TINY_LIMITS)
        add_cell(root, name, w)
    return root


def drive(root: str, cell: str, fault: str = "none", devices: int = 1,
          cache: str | None = None) -> dict:
    """Run ``bench/tests/drive.py`` and return its result line."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    if cache:
        env["JAX_COMPILATION_CACHE_DIR"] = cache
    proc = subprocess.run(
        [sys.executable, os.path.join(BENCH, "tests", "drive.py"), root,
         cell, fault, str(devices)],
        capture_output=True, text=True, env=env, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])
