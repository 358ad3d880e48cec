"""Drives a whole benchmark run on the CPU at a tiny size, skipping the
harness's look for a chip, optionally with the timed path broken:

    python bench/tests/drive.py <root> <cell> <fault> [devices]

``<root>`` holds a ``BENCHMARK.json`` and a ``bench/`` tree of data files
(the code is this checkout's).  ``<fault>`` is ``none``, ``unchanged`` (the
step returns its state unchanged), ``half_batch`` (half of each batch left
out), ``no_exchange`` (the gradient exchange between devices left out) or
``labels_are_tokens`` (the feed's labels not shifted).
"""
import os
import sys
import time

T_START = time.perf_counter()
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main() -> None:
    root, cell, fault = sys.argv[1:4]
    devices = int(sys.argv[4]) if len(sys.argv) > 4 else 1
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                               f" --xla_force_host_platform_device_count="
                               f"{devices}")
    from bench import harness, program, readings, run, spec
    program.add_src_path()
    wrap = None
    if fault == "unchanged":
        import jax
        import jax.numpy as jnp

        def wrap(step):
            def same(state, batch, lr):
                _, metrics = step(jax.tree.map(jnp.copy, state), batch, lr)
                return state, metrics
            return same
    elif fault == "half_batch":
        def wrap(step):
            return lambda state, batch, lr: step(
                state, readings.half_batch(batch), lr)
    elif fault == "labels_are_tokens":
        def wrap(step):
            return lambda state, batch, lr: step(
                state, dict(batch, labels=batch["tokens"]), lr)
    elif fault == "no_exchange":
        readings.no_exchange()
    elif fault != "none":
        raise SystemExit(f"unknown fault {fault!r}")
    args = run.parse(["--workload", cell, "--seed", str(2**33 + 5),
                      "--seconds", "0.5", "--trace", "0"])
    harness.run(args, T_START, spec.Spec(os.path.join(root, "bench")),
                require_tpu=False, wrap_step=wrap)


if __name__ == "__main__":
    main()
