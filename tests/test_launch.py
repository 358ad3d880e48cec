"""The launch path's process-level contracts, on the CPU:

  * ``chip_smoke.py`` refuses a host without a TPU (nonzero exit, no
    ``"ok": true`` line);
  * the compile cache honours ``JAX_COMPILATION_CACHE_DIR`` and otherwise
    sits at one fixed, gitignored path in the checkout;
  * the overlap flags go to libtpu's ``LIBTPU_INIT_ARGS``, never to
    ``XLA_FLAGS`` (whose CPU client aborts on ``--xla_tpu_*`` names);
  * ``--layers`` cuts the depth of a config and says so.
"""
import os
import subprocess
import sys

import jax

from repro.launch import compile_cache
from repro.train import overlap

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))


def _cpu_env(tmp_path) -> dict:
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "cache")
    env["PYTHONPATH"] = os.path.join(ROOT, "src") + os.pathsep + \
        env.get("PYTHONPATH", "")
    return env


def test_chip_smoke_refuses_cpu(tmp_path):
    proc = subprocess.run([sys.executable, os.path.join(ROOT, "chip_smoke.py")],
                          capture_output=True, text=True, timeout=300,
                          env=_cpu_env(tmp_path), cwd=tmp_path)
    assert proc.returncode != 0
    assert '"ok": true' not in proc.stdout
    assert "no TPU" in proc.stderr


def test_compile_cache_honours_env(monkeypatch, tmp_path):
    monkeypatch.setenv(compile_cache.ENV, str(tmp_path))
    assert compile_cache.cache_dir() == str(tmp_path)


def test_compile_cache_fixed_path_in_checkout(monkeypatch):
    monkeypatch.delenv(compile_cache.ENV, raising=False)
    path = compile_cache.cache_dir()
    assert path == os.path.join(ROOT, ".jax_cache")
    with open(os.path.join(ROOT, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()


def test_compile_cache_enable_sets_jax(monkeypatch, tmp_path):
    monkeypatch.setenv(compile_cache.ENV, str(tmp_path))
    was = jax.config.jax_compilation_cache_dir
    try:
        assert compile_cache.enable() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == str(tmp_path)
    finally:
        jax.config.update("jax_compilation_cache_dir", was)


def test_overlap_flags_go_to_libtpu(monkeypatch):
    monkeypatch.setenv("XLA_FLAGS", "--xla_force_host_platform_device_count=2")
    monkeypatch.setenv("LIBTPU_INIT_ARGS", "--some_user_flag=1")
    overlap.enable_overlap_flags()
    overlap.enable_overlap_flags()                       # idempotent
    assert os.environ["XLA_FLAGS"] == \
        "--xla_force_host_platform_device_count=2"
    args = os.environ["LIBTPU_INIT_ARGS"]
    assert args.startswith("--some_user_flag=1 ")
    for flag in overlap.TPU_OVERLAP_FLAGS.split():
        assert args.split().count(flag) == 1


def test_layers_cuts_depth(tmp_path):
    proc = subprocess.run(
        [sys.executable, "-m", "repro.launch.train", "--layers", "1",
         "--overlap", "--steps", "2", "--batch", "2", "--seq", "16",
         "--log-every", "1"],
        capture_output=True, text=True, timeout=300, env=_cpu_env(tmp_path),
        cwd=tmp_path)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "n_layers=1 (cut from 2)" in proc.stdout
    assert "[train] done at step 2" in proc.stdout
