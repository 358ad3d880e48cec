"""The benchmark's inputs made from ``--seed``: weights and PowerSGD's
starting factors.  Both the run and the reference make them here, so the
reference takes nothing that the program made.

Weights are named by path in the layout that the configuration's
architecture module gives (``shapes`` in ``bench/models/<arch>.py``).
Each leaf is drawn from the seed's key folded with a hash of its path, so a
leaf's values do not depend on which other leaves exist.
"""
from __future__ import annotations

import zlib

import jax
import jax.numpy as jnp


def param_dtype(plan: dict):
    """The dtype a cell's plan keeps its working weights in: bfloat16 under
    ZeRO-1 (mixed precision, the fp32 master in the optimizer state) or
    where the plan asks for bfloat16 weights, float32 otherwise."""
    zero1 = plan.get("dp_mode") == "ddp" and plan.get("zero1", False)
    if zero1 or plan.get("param_dtype") == "bfloat16":
        return jnp.bfloat16
    return jnp.float32


def seed_key(seed: int) -> jax.Array:
    """A key from any non-negative seed, all its bits kept."""
    if seed < 0:
        raise ValueError(f"seed {seed} is negative")
    key = jax.random.key(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def leaf(key: jax.Array, path: str, shape, std: float, dtype) -> jax.Array:
    """One leaf: RMSNorm scales are ones, matrices N(0, std^2)."""
    if path.endswith("/scale"):
        return jnp.ones(shape, dtype)
    k = jax.random.fold_in(key, zlib.crc32(path.encode()) & 0x7FFFFFFF)
    return (std * jax.random.normal(k, shape, jnp.float32)).astype(dtype)


def make(key: jax.Array, cfg: dict, dtype, arch) -> dict[str, jax.Array]:
    """Every leaf of architecture module ``arch``, by path (call under
    ``jax.jit``)."""
    return {p: leaf(key, p, s, cfg["init_std"], dtype)
            for p, s in arch.shapes(cfg).items()}


def powersgd_q(key: jax.Array, bucket: int, cols: int, rank: int
               ) -> jax.Array:
    """Bucket ``bucket``'s starting factor Q, (cols, rank) fp32."""
    k = jax.random.fold_in(jax.random.fold_in(key, 0x5053), bucket)
    return jax.random.normal(k, (cols, rank), jnp.float32)
