"""Attention: causal/full self-attention for training and prefill, GQA,
and the decode paths.

Two paths behind ``chunked_attention``, chosen statically while tracing:

- **Pallas flash kernel** (``kernels/flash_attention.py``) for
  self-attention over the default positions ``0..S-1`` (both position
  arguments ``None``, ``Sq == Sk``) whose sequence length and head size the
  kernel tiles (``kernels/ops.flash_attention_fits``), on TPU or with
  ``force="pallas"``.  Score tiles stay in VMEM, blocks above the causal
  diagonal are skipped, and each q head reads its kv head through the
  kernel's index map.  Its Mosaic calls, forward and backward, are
  labelled ``attention`` in a profile.
- **jnp online-softmax loop** everywhere else: off TPU, cross-attention
  (``encdec.py``), explicit ``batch["positions"]``, context-parallel
  caches, and shapes the kernel does not tile.  Pure JAX (``lax.scan``
  over k chunks, fp32 running max/denominator), so the step lowers on any
  backend and the 32k-prefill activation footprint stays linear in chunk
  size.

``kernels.ops.ATTENTION_PATHS`` counts the calls traced on each path.

Decode supports a context-parallel cache: for long_500k (global_batch=1) the
KV cache is sharded over the "data" mesh axis along sequence and partial
attention is merged with a log-sum-exp reduction (DESIGN.md §5).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import ops

NEG_INF = -1e30


def _group(q: jax.Array, kv_local: int) -> jax.Array:
    """(B, S, L, hd) -> (B, S, kv_local, L//kv_local, hd)."""
    b, s, l, hd = q.shape
    return q.reshape(b, s, kv_local, l // kv_local, hd)


@jax.named_scope("attention")
def chunked_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                      causal: bool,
                      q_positions: Optional[jax.Array] = None,
                      k_positions: Optional[jax.Array] = None,
                      chunk: int = 1024,
                      q_chunk: int = 2048,
                      softmax_scale: Optional[float] = None,
                      force: Optional[str] = None) -> jax.Array:
    """q, k: (B, Sq|Sk, L|KVh, hd); v: (B, Sk, KVh, dv) with KVh | L; the
    result is (B, Sq, L, dv).

    Positions ``None`` mean ``0..S-1``.  Self-attention over those takes
    the Pallas flash kernel where ``ops.flash_attention_fits`` (``force``
    as in ``kernels/ops.py``).  Otherwise the double-chunked (flash)
    structure: an outer scan over q blocks bounds every score/probability
    tensor by (B, q_chunk, heads, chunk) — the O(Sq·Sk) working set never
    materializes (DESIGN.md §4).
    """
    if q_positions is None and k_positions is None and \
            ops.flash_attention_fits(q.shape, k.shape, v.shape,
                                     force=force):
        ops.ATTENTION_PATHS["pallas"] += 1
        return ops.flash_attention(q, k, v, causal=causal,
                                   softmax_scale=softmax_scale)
    ops.ATTENTION_PATHS["jnp"] += 1
    b, sq, l, hd = q.shape
    if q_positions is None:
        q_positions = jnp.broadcast_to(jnp.arange(sq), (b, sq))
    if k_positions is None:
        k_positions = jnp.broadcast_to(jnp.arange(sk := k.shape[1]),
                                       (b, sk))
    if sq > q_chunk:
        padq = (-sq) % q_chunk
        if padq:
            q = jnp.pad(q, ((0, 0), (0, padq), (0, 0), (0, 0)))
            q_positions = jnp.pad(q_positions, ((0, 0), (0, padq)),
                                  constant_values=jnp.iinfo(jnp.int32).max)
        nq = q.shape[1] // q_chunk
        qs = jnp.moveaxis(q.reshape(b, nq, q_chunk, l, hd), 1, 0)
        qp = jnp.moveaxis(q_positions.reshape(b, nq, q_chunk), 1, 0)

        def qstep(_, xs):
            qblk, qpos = xs
            out = _attention_qblock(qblk, k, v, causal=causal,
                                    q_positions=qpos,
                                    k_positions=k_positions, chunk=chunk,
                                    softmax_scale=softmax_scale)
            return (), out

        _, outs = jax.lax.scan(qstep, (), (qs, qp))
        out = jnp.moveaxis(outs, 0, 1).reshape(b, nq * q_chunk, l,
                                               v.shape[-1])
        return out[:, :sq]
    return _attention_qblock(q, k, v, causal=causal,
                             q_positions=q_positions,
                             k_positions=k_positions, chunk=chunk,
                             softmax_scale=softmax_scale)


def _attention_qblock(q: jax.Array, k: jax.Array, v: jax.Array, *,
                      causal: bool, q_positions: jax.Array,
                      k_positions: jax.Array, chunk: int,
                      softmax_scale: Optional[float]) -> jax.Array:
    """Online-softmax over k/v chunks for ONE q block."""
    b, sq, l, hd = q.shape
    sk, kvh, dv = k.shape[1], k.shape[2], v.shape[-1]
    scale = softmax_scale if softmax_scale is not None else hd ** -0.5
    chunk = min(chunk, sk)
    pad = (-sk) % chunk
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        k_positions = jnp.pad(k_positions, ((0, 0), (0, pad)),
                              constant_values=jnp.iinfo(jnp.int32).max)
    nc = k.shape[1] // chunk

    qg = _group(q, kvh).astype(jnp.float32) * scale   # (B,Sq,KVh,G,hd)
    kc = k.reshape(b, nc, chunk, kvh, hd)
    vc = v.reshape(b, nc, chunk, kvh, dv)
    pc = k_positions.reshape(b, nc, chunk)

    # flash-attention structure: the per-chunk scores/probabilities are
    # TRANSIENT — jax.checkpoint makes the backward recompute them per
    # chunk instead of storing O(S²) residuals (DESIGN.md §4; this is what
    # keeps the 32k-token shapes inside 16 GB/chip)
    @jax.checkpoint
    def step(carry, xs):
        m, den, acc = carry
        kb, vb, pb = xs                                 # (B,C,KVh,hd),( ,C)
        s = jnp.einsum("bqkgd,bckd->bqkgc", qg, kb.astype(jnp.float32))
        mask = pb[:, None, None, None, :] <= q_positions[:, :, None, None,
                                                         None] \
            if causal else \
            pb[:, None, None, None, :] < jnp.iinfo(jnp.int32).max
        s = jnp.where(mask, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        den = den * corr + jnp.sum(p, axis=-1)
        pv = jnp.einsum("bqkgc,bckd->bqkgd", p, vb.astype(jnp.float32))
        acc = acc * corr[..., None] + pv
        return (m_new, den, acc), None

    m0 = jnp.full((b, sq, kvh, l // kvh), NEG_INF, jnp.float32)
    d0 = jnp.zeros((b, sq, kvh, l // kvh), jnp.float32)
    a0 = jnp.zeros((b, sq, kvh, l // kvh, dv), jnp.float32)
    (m, den, acc), _ = jax.lax.scan(
        step, (m0, d0, a0),
        (jnp.moveaxis(kc, 1, 0), jnp.moveaxis(vc, 1, 0),
         jnp.moveaxis(pc, 1, 0)))
    out = acc / jnp.maximum(den, 1e-30)[..., None]
    return out.reshape(b, sq, l, dv).astype(q.dtype)


def decode_attention(q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
                     cur_len: jax.Array, *,
                     cache_positions: Optional[jax.Array] = None,
                     seq_shard_axes: tuple[str, ...] = (),
                     softmax_scale: Optional[float] = None) -> jax.Array:
    """Single-token decode. q: (B, 1, L, hd); caches: (B, Sc, KVh, hd).

    `cur_len`: scalar/(B,) number of valid cache positions (global).
    `cache_positions`: (B, Sc) absolute position of each local cache slot —
    required when the cache is context-parallel (sharded over
    `seq_shard_axes` along sequence); partial softmax stats are LSE-merged
    with psums over those axes."""
    b, _, l, hd = q.shape
    sc, kvh = k_cache.shape[1], k_cache.shape[2]
    scale = softmax_scale if softmax_scale is not None else hd ** -0.5
    if cache_positions is None:
        cache_positions = jnp.broadcast_to(jnp.arange(sc), (b, sc))
    cur = jnp.broadcast_to(jnp.asarray(cur_len), (b,))

    qg = _group(q, kvh).astype(jnp.float32)[:, 0] * scale    # (B,KVh,G,hd)
    s = jnp.einsum("bkgd,bckd->bkgc", qg,
                   k_cache.astype(jnp.float32))              # (B,KVh,G,Sc)
    valid = cache_positions[:, None, None, :] < cur[:, None, None, None]
    s = jnp.where(valid, s, NEG_INF)

    m = jnp.max(s, axis=-1)
    if seq_shard_axes:
        m = jax.lax.pmax(m, seq_shard_axes)
    m = jax.lax.stop_gradient(m)
    p = jnp.exp(s - m[..., None])
    den = jnp.sum(p, axis=-1)
    pv = jnp.einsum("bkgc,bckd->bkgd", p, v_cache.astype(jnp.float32))
    if seq_shard_axes:
        den = jax.lax.psum(den, seq_shard_axes)
        pv = jax.lax.psum(pv, seq_shard_axes)
    out = pv / jnp.maximum(den, 1e-30)[..., None]
    return out.reshape(b, 1, l, v_cache.shape[-1]).astype(q.dtype)
