"""Shared driver for the tests/dist/ subprocess oracles.

Every script in this directory is launched as its own process by
tests/test_distributed.py (the main pytest process must keep seeing ONE
device) and used to duplicate the same four blocks of boilerplate:
forcing the fake-device count before the jax import, the seeded
tinyllama build, the N-step run loop, and the trailing "OK <name>"
emission.  That lives here once.

Import-order contract: ``setup_devices()`` must run BEFORE anything
imports jax (XLA reads the flag at backend init), so scripts do

    import harness
    harness.setup_devices(4)
    import jax  # noqa: E402
    ...

and everything else in this module lazy-imports jax/repro inside the
functions so importing ``harness`` itself stays jax-free.

Structured pass/fail: ``run_main(name, fn)`` prints ``OK <name>`` only
when ``fn`` returns, and ``FAIL <name>: <error>`` (then re-raises, so
the exit code is nonzero) when it doesn't — the runner greps stdout for
the OK line in addition to checking the exit code.
"""
import os
import sys

DEFAULT_DEVICES = 4


def setup_devices(n: int = DEFAULT_DEVICES) -> None:
    """Force ``n`` fake host devices on the CPU platform (never a chip,
    even on a TPU host); must precede the jax import."""
    assert "jax" not in sys.modules, \
        "harness.setup_devices() called after jax was imported"
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + f" --xla_force_host_platform_device_count={n}")


def make_batches(steps: int = 3, vocab: int = 64, seq_len: int = 32,
                 global_batch: int = 8):
    """The scripts' shared seeded token batches."""
    from repro.data.pipeline import Pipeline
    from repro.data.synthetic import DataConfig
    it = iter(Pipeline(DataConfig(vocab=vocab, seq_len=seq_len,
                                  global_batch=global_batch), prefetch=0))
    return [next(it) for _ in range(steps)]


def build_setup(method: str = "none", *, arch: str = "tinyllama-1.1b",
                zero1=None, comm=None, compress_axes=None,
                param_dtype=None, mesh=None, vocab: int = 64,
                bucket_mb: float = 1):
    """Reduced seeded TrainSetup on a (4, 1) data×model mesh (or the
    given one).  Plan fields left ``None`` keep the arch's default."""
    import dataclasses

    from repro.configs import base
    from repro.parallel.compat import make_mesh
    from repro.train import train_step as ts
    cfg = base.reduced(base.get(arch))
    plan_kw = dict(bucket_mb=bucket_mb, overlap=True, compression=method)
    for k, v in (("zero1", zero1), ("comm", comm),
                 ("compress_axes", compress_axes),
                 ("param_dtype", param_dtype)):
        if v is not None:
            plan_kw[k] = v
    cfg = dataclasses.replace(cfg, vocab=vocab, plan=dataclasses.replace(
        cfg.plan, **plan_kw))
    if mesh is None:
        mesh = make_mesh((4, 1), ("data", "model"))
    return ts.build(cfg, mesh)


def run(setup, step_builder, batches, keep_first_params: bool = False):
    """Seeded training loop -> (final state, per-step metrics, and —
    when asked — the params snapshot after step 1)."""
    import jax
    import jax.numpy as jnp

    from repro.train import train_step as ts
    state = ts.init_state(setup, jax.random.key(0))
    step = step_builder(batches[0])
    ms, p1 = [], None
    for i, b in enumerate(batches):
        state, m = step(state, b, jnp.float32(1e-3))
        ms.append(jax.device_get(m))
        if i == 0 and keep_first_params:
            p1 = jax.device_get(state["params"])
    return jax.device_get(state), ms, p1


def assert_bit_identical(sa, sb, ma, mb, label: str) -> None:
    """Params and every per-step metric must match BITWISE."""
    import jax
    import numpy as np
    for pa, pb in zip(jax.tree.leaves(sa["params"]),
                      jax.tree.leaves(sb["params"])):
        np.testing.assert_array_equal(np.asarray(pa), np.asarray(pb),
                                      err_msg=label)
    for a, b in zip(ma, mb):
        for k in a:
            np.testing.assert_array_equal(np.asarray(a[k]),
                                          np.asarray(b[k]),
                                          err_msg=f"{label} metric {k}")


def run_main(name: str, fn) -> None:
    """Structured PASS/FAIL wrapper around a script's main()."""
    try:
        fn()
    except BaseException as e:
        print(f"FAIL {name}: {type(e).__name__}: {e}", flush=True)
        raise
    print(f"OK {name}", flush=True)
