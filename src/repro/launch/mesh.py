"""Production mesh construction (MULTI-POD DRY-RUN step 1).

A function, not a module-level constant, so importing this module never
touches jax device state.  The production target is TPU v5e:
16×16 = 256 chips per pod; the multi-pod mesh adds a leading "pod" axis
(2 pods = 512 chips) whose links are DCN, not ICI — the axis the paper's
compression targets (DESIGN.md §2).
"""
from __future__ import annotations

from repro.parallel.compat import make_mesh as _mk


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mk(shape, axes)


def make_test_mesh(shape=(2, 2, 2), axes=("pod", "data", "model")):
    """Small fake-device mesh for CPU distributed tests."""
    return _mk(shape, axes)


def make_local_mesh():
    """Every visible device on ``data`` (one chip, a four-chip host, or
    ``(1, 1)`` on a single CPU device)."""
    import jax
    return _mk((jax.device_count(), 1), ("data", "model"))


def make_pod_mesh(procs: int | None = None, local: int | None = None,
                  tp: int = 1):
    """Two-tier (pod × data × model) mesh over a LIVE ``jax.distributed``
    pod: the leading "pod" axis spans OS processes (its links cross
    process boundaries — the measured DCN tier), "data" spans each
    process's local devices (the fast in-process tier).

    Requires ``jax.distributed.initialize`` to have run; ``jax.devices()``
    orders devices by process index, so the plain reshape puts each
    process's local devices in one pod row.  Defaults read the live
    topology (``jax.process_count()`` × local device count).
    """
    import jax
    import numpy as np
    from jax.sharding import Mesh

    procs = procs or jax.process_count()
    if local is None:
        local = jax.device_count() // (procs * tp)
    devs = np.array(jax.devices())
    want = procs * local * tp
    if devs.size != want:
        raise ValueError(
            f"pod mesh {procs}×{local}×{tp} needs {want} devices, "
            f"jax.devices() has {devs.size}")
    return Mesh(devs.reshape(procs, local, tp), ("pod", "data", "model"))
