"""Platform-dispatching jit'd wrappers for the Pallas kernels.

On TPU, compute hot spots route to the Pallas implementations (explicit
BlockSpec VMEM tiling); everywhere else (CPU tests, dry-run lowering on fake
CPU devices) they route to the pure-jnp oracles in ``ref.py``.  Pass
``force='pallas'``/``force='ref'`` (or set ``repro.kernels.ops.FORCE``) to pin
a path — kernel tests use ``force='pallas'`` with interpret mode.

Attention is the one op whose fallback is not in ``ref.py``: the jnp path
is ``models/attention.py``'s online-softmax loop, and ``chunked_attention``
asks :func:`flash_attention_fits` before taking the kernel.
"""
from __future__ import annotations

import collections

import jax

from repro.kernels import ref

FORCE: str | None = None  # None | "ref" | "pallas"

#: attention calls traced per path ("pallas" | "jnp"), counted at trace
#: time by ``models.attention.chunked_attention``
ATTENTION_PATHS: collections.Counter = collections.Counter()


def _use_pallas(force: str | None) -> bool:
    mode = force or FORCE
    if mode == "ref":
        return False
    if mode == "pallas":
        return True
    return jax.default_backend() == "tpu"


def powersgd_encode(m, q, *, force=None):
    if _use_pallas(force):
        from repro.kernels import powersgd as k
        return k.encode(m, q, interpret=jax.default_backend() != "tpu")
    return ref.powersgd_encode(m, q)


def powersgd_decode(p, q, *, force=None):
    if _use_pallas(force):
        from repro.kernels import powersgd as k
        return k.decode(p, q, interpret=jax.default_backend() != "tpu")
    return ref.powersgd_decode(p, q)


def pack_signs(g, *, force=None):
    if _use_pallas(force):
        from repro.kernels import bitpack as k
        return k.pack_signs(g, interpret=jax.default_backend() != "tpu")
    return ref.pack_signs(g)


def popcount_votes(gathered, n, *, force=None):
    if _use_pallas(force):
        from repro.kernels import bitpack as k
        return k.popcount_votes(gathered, n,
                                interpret=jax.default_backend() != "tpu")
    return ref.popcount_votes(gathered, n)


def unpack_signs(packed, n, *, force=None):
    return ref.unpack_signs(packed, n)


def topk_select(g, k, *, force=None):
    # Exact selection everywhere; the Pallas threshold+mask path is a
    # separate op because its contract (approximate-k) differs.
    return ref.topk_select(g, k)


def topk_threshold_mask(g, threshold, *, force=None):
    if _use_pallas(force):
        from repro.kernels import topk as k
        return k.threshold_mask(g, threshold,
                                interpret=jax.default_backend() != "tpu")
    return ref.topk_threshold_mask(g, threshold)


def qsgd_quantize(g, norm, levels, key, *, force=None):
    if _use_pallas(force):
        from repro.kernels import qsgd as k
        return k.quantize(g, norm, levels, key,
                          interpret=jax.default_backend() != "tpu")
    return ref.qsgd_quantize(g, norm, levels, key)


def flash_attention_fits(q_shape, k_shape, *, force=None) -> bool:
    """Whether self-attention of these (B, S, heads, hd) shapes takes the
    Pallas flash kernel: on TPU (or ``force='pallas'``), and where the
    kernel's tiling takes the sequence length and head size."""
    from repro.kernels import flash_attention as k
    return _use_pallas(force) and k.fits(q_shape[1], k_shape[1], q_shape[3])


def flash_attention(q, k, v, *, causal, softmax_scale=None):
    """Causal or full self-attention over positions 0..S-1 on the Pallas
    kernel.  q: (B, S, L, hd); k, v: (B, S, KVh, hd) -> (B, S, L, hd)."""
    from repro.kernels import flash_attention as fa
    heads_major = (0, 2, 1, 3)
    out = fa.flash_attention(
        q.transpose(heads_major), k.transpose(heads_major),
        v.transpose(heads_major), causal=causal,
        softmax_scale=softmax_scale,
        interpret=jax.default_backend() != "tpu")
    return out.transpose(heads_major)
