"""Pure-jnp oracles for every Pallas kernel.

These are the semantics-defining implementations: kernel tests assert
``pallas(interpret=True) ≈ ref`` across shape/dtype sweeps, and the CPU
execution path (tests, dry-run lowering, this container) runs them directly.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


# ---------------------------------------------------------------- powersgd
def powersgd_encode(m: jax.Array, q: jax.Array) -> jax.Array:
    """P = M @ Q  (tall-skinny: rank ≪ cols).  fp32 accumulation."""
    return jnp.dot(m.astype(jnp.float32), q.astype(jnp.float32),
                   precision=jax.lax.Precision.HIGHEST)


def powersgd_decode(p: jax.Array, q: jax.Array) -> jax.Array:
    """M̂ = P @ Qᵀ."""
    return jnp.dot(p.astype(jnp.float32), q.astype(jnp.float32).T,
                   precision=jax.lax.Precision.HIGHEST)


# ---------------------------------------------------------------- bitpack
def pack_signs(g: jax.Array) -> jax.Array:
    """Pack sign bits (g >= 0 -> 1) into uint32 words, little-endian bit order.

    Length is padded to a multiple of 32; pad bits are 0 (negative), which is
    safe because consumers only read the first n vote counts.
    """
    n = g.shape[0]
    words = -(-n // 32)
    bits = (g >= 0).astype(jnp.uint32)
    bits = jnp.pad(bits, (0, words * 32 - n)).reshape(words, 32)
    shifts = jnp.arange(32, dtype=jnp.uint32)
    return jnp.bitwise_or.reduce(bits << shifts, axis=1)


def unpack_signs(packed: jax.Array, n: int) -> jax.Array:
    """Inverse of pack_signs -> {0,1} uint32 vector of length n."""
    shifts = jnp.arange(32, dtype=jnp.uint32)
    bits = (packed[:, None] >> shifts) & jnp.uint32(1)
    return bits.reshape(-1)[:n]


def popcount_votes(gathered: jax.Array, n: int) -> jax.Array:
    """gathered: (p, words) packed bitmaps -> (n,) count of positive votes."""
    shifts = jnp.arange(32, dtype=jnp.uint32)
    bits = (gathered[:, :, None] >> shifts) & jnp.uint32(1)   # (p, words, 32)
    votes = bits.sum(axis=0).reshape(-1)[:n]
    return votes.astype(jnp.int32)


# ---------------------------------------------------------------- top-k
def topk_select(g: jax.Array, k: int) -> tuple[jax.Array, jax.Array]:
    """Exact top-k by magnitude: (signed values, int32 indices)."""
    _, idx = jax.lax.top_k(jnp.abs(g), k)
    return g[idx], idx.astype(jnp.int32)


def topk_threshold_mask(g: jax.Array, threshold: jax.Array) -> jax.Array:
    """|g| >= threshold ? g : 0 — the TPU-friendly dense masking form."""
    return jnp.where(jnp.abs(g) >= threshold, g, 0.0)


def sampled_threshold(g: jax.Array, k: int, key: jax.Array,
                      sample: int = 4096) -> jax.Array:
    """Estimate the |g| threshold that keeps ~k elements via sampling
    (the 'multi-stage' trick of MSTop-K: avoids a full sort)."""
    n = g.shape[0]
    s = min(sample, n)
    idx = jax.random.randint(key, (s,), 0, n)
    sub = jnp.abs(g[idx])
    q = 1.0 - k / n
    return jnp.quantile(sub, q)


# ---------------------------------------------------------------- qsgd
def qsgd_quantize(g: jax.Array, norm: jax.Array, levels: int,
                  key: jax.Array) -> jax.Array:
    """Stochastic uniform quantization to signed int levels in [-levels, levels].

    E[dequantize(q)] = g  (unbiased).
    """
    # the kernel's arithmetic, op for op, so the two agree bit for bit
    scale = jnp.float32(levels) / (norm + 1e-12)
    scaled = jnp.abs(g).astype(jnp.float32) * scale   # in [0, levels]
    low = jnp.floor(scaled)
    prob = scaled - low
    up = jax.random.bernoulli(key, prob)
    mag = low + up.astype(jnp.float32)
    return (jnp.sign(g) * mag).astype(jnp.int8)
