"""Pallas TPU kernels for SignSGD bit packing / majority-vote counting —
the encode/decode hot spot of the paper's 32× scheme (§3.2).

``pack_signs``: 32 sign bits -> one u32 word via shift-or across a (bw, 32)
block (VPU integer ops; the 32-lane minor dim rides the vector lanes).
``popcount_votes``: a (p, words) gathered bitmap -> per-element positive
vote counts; the unpack + popcount runs blocked over words with the full
worker dim resident, accumulating one bit position at a time so the live
set per block is one (p, bw) plane + the (bw, 32) output — never the
(p, bw, 32) bit-plane tensor (a 32× VMEM cut on the planes, ~64× counting
their int32 copies; p = 512, bw = 1024 → ~2 MB in + ~4 MB transients).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _ceil_to(x: int, m: int) -> int:
    return -(-x // m) * m


# --------------------------------------------------------------------------
# pack
# --------------------------------------------------------------------------
def _pack_kernel(g_ref, o_ref):
    # Mosaic has no unsigned reductions: build the word in int32 (bit 31
    # lands on the sign, and the wrapping sum of distinct bit positions is
    # still their bitwise-or), then reinterpret the bits as uint32.
    bits = (g_ref[...] >= 0).astype(jnp.int32)              # (bw, 32)
    shifts = jax.lax.broadcasted_iota(jnp.int32, bits.shape, 1)
    word = jnp.sum(bits << shifts, axis=1, dtype=jnp.int32)
    o_ref[...] = jax.lax.bitcast_convert_type(word, jnp.uint32)


def pack_signs(g: jax.Array, *, bw: int = 2048,
               interpret: bool = False) -> jax.Array:
    """g: (n,) float -> (ceil(n/32),) uint32, little-endian bit order.
    Pad elements are negative (bit 0) — matching ref.pack_signs."""
    n = g.shape[0]
    words = -(-n // 32)
    pw = _ceil_to(words, bw) if words > bw else words
    bw = min(bw, pw)
    pad = pw * 32 - n
    if pad:
        g = jnp.pad(g, (0, pad), constant_values=-1.0)
    g2 = g.reshape(pw, 32)
    out = pl.pallas_call(
        _pack_kernel,
        grid=(pw // bw,),
        in_specs=[pl.BlockSpec((bw, 32), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((bw,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct((pw,), jnp.uint32),
        interpret=interpret,
    )(g2)
    return out[:words]


# --------------------------------------------------------------------------
# majority vote
# --------------------------------------------------------------------------
def _votes_kernel(w_ref, o_ref):
    w = w_ref[...]                                          # (p, bw) u32
    # accumulate per bit position: each iteration touches one (p, bw)
    # plane, never the full (p, bw, 32) bit-plane tensor
    cols = []
    for b in range(32):
        bits = (w >> jnp.uint32(b)) & jnp.uint32(1)         # (p, bw)
        cols.append(jnp.sum(bits.astype(jnp.int32), axis=0))  # (bw,)
    o_ref[...] = jnp.stack(cols, axis=1)                    # (bw, 32)


def popcount_votes(gathered: jax.Array, n: int, *, bw: int = 1024,
                   interpret: bool = False) -> jax.Array:
    """gathered: (p, words) u32 -> (n,) int32 count of positive votes."""
    p, words = gathered.shape
    pw = _ceil_to(words, bw) if words > bw else words
    bw = min(bw, pw)
    if pw != words:
        gathered = jnp.pad(gathered, ((0, 0), (0, pw - words)))
    out = pl.pallas_call(
        _votes_kernel,
        grid=(pw // bw,),
        in_specs=[pl.BlockSpec((p, bw), lambda i: (0, i))],
        out_specs=pl.BlockSpec((bw, 32), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((pw, 32), jnp.int32),
        interpret=interpret,
    )(gathered)
    return out.reshape(-1)[:n]
