"""Multi-head latent attention (MLA, DeepSeek-V2/V3) for training, prefill
and decode, without a q-LoRA (``MLAConfig.q_lora_rank`` None).

Per layer, with ``h`` the normed input:

    q        = h Wq                      (B, S, H, nope + rope), split
    [c, kpe] = h Wkv_a                   c (B, S, rank), kpe (B, S, rope)
    [kn, v]  = RMSNorm(c) Wkv_b          per head: nope and v
    k        = [kn, RoPE(kpe) broadcast over heads]
    out      = softmax(q k^T * (nope + rope)^-0.5, causal) v  Wo

RoPE is DeepSeek-V3's on the ``rope`` dimensions only: the Hugging Face
``deepseek_v3`` ``apply_rotary_pos_emb`` first permutes each vector's
interleaved pairs (0, 1), (2, 3), ... to (evens, odds), then rotates half
(``layers.apply_rope``), with ``theta ** (-2i / rope)`` for pair ``i``; the
same permutation of q and k leaves their product as the pairwise rotation
gives it.  Attention runs through ``models.attention.chunked_attention``,
so it takes the Pallas flash kernel (q/k heads 192, v heads 128) where
``kernels/ops.flash_attention_fits``.

The projections run under the ``mla`` scope; attention itself under
``attention``.  Prefill and decode cache the expanded per-head k and v.
Tensor parallelism is not supported (``ctx.tp`` must be 1).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.models import attention as attn_ops
from repro.models.layers import (ShardCtx, apply_rope, column_linear,
                                 column_linear_init, rmsnorm, rmsnorm_init,
                                 row_linear, row_linear_init)


def mla_init(key, cfg, ctx: ShardCtx):
    m, h, d = cfg.mla, cfg.n_heads, cfg.d_model
    if ctx.tp != 1 or m.q_lora_rank is not None:
        raise NotImplementedError("MLA with tensor parallelism or a q-LoRA")
    ks = jax.random.split(key, 4)
    params, specs = {}, {}
    params["wq"], specs["wq"] = column_linear_init(
        ks[0], d, h * m.qk_head_dim, ctx)
    params["wkv_a"], specs["wkv_a"] = column_linear_init(
        ks[1], d, m.kv_lora_rank + m.qk_rope_head_dim, ctx)
    params["kv_norm"], specs["kv_norm"] = rmsnorm_init(m.kv_lora_rank, ctx)
    params["wkv_b"], specs["wkv_b"] = column_linear_init(
        ks[2], m.kv_lora_rank, h * (m.qk_nope_head_dim + m.v_head_dim), ctx)
    params["wo"], specs["wo"] = row_linear_init(
        ks[3], h * m.v_head_dim, d, ctx,
        std=1.0 / math.sqrt(h * m.v_head_dim))
    return params, specs


def rope_interleaved(x, positions, theta: float):
    """DeepSeek-V3's RoPE of x (B, S, heads, rope): interleaved pairs to
    (evens, odds), then rotate-half."""
    b, s, h, r = x.shape
    x = x.reshape(b, s, h, r // 2, 2).swapaxes(-1, -2).reshape(b, s, h, r)
    return apply_rope(x, positions, theta)


@jax.named_scope("mla")
def mla_qkv(params, h, cfg, ctx: ShardCtx, positions):
    """h (B, S, d) -> q, k (B, S, H, nope + rope), v (B, S, H, v)."""
    m, nh = cfg.mla, cfg.n_heads
    b, s, _ = h.shape
    nope, rope = m.qk_nope_head_dim, m.qk_rope_head_dim
    q = column_linear(params["wq"], h, ctx).reshape(b, s, nh, nope + rope)
    kv_a = column_linear(params["wkv_a"], h, ctx)
    c, k_pe = kv_a[..., :m.kv_lora_rank], kv_a[..., m.kv_lora_rank:]
    c = rmsnorm(params["kv_norm"], c, m.kv_norm_eps)
    kv = column_linear(params["wkv_b"], c, ctx).reshape(
        b, s, nh, nope + m.v_head_dim)
    k_nope, v = kv[..., :nope], kv[..., nope:]
    q_pe = rope_interleaved(q[..., nope:], positions, cfg.rope_theta)
    k_pe = rope_interleaved(k_pe[:, :, None, :], positions, cfg.rope_theta)
    q = jnp.concatenate([q[..., :nope], q_pe], -1)
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_pe, (b, s, nh, rope))], -1)
    return q, k, v


def mla_apply(params, x, aux, ctx: ShardCtx, cfg, st, cache=None):
    """Attention sub-block on the normed input ``x``; returns (its output
    before the residual, the new cache)."""
    from repro.models.transformer import _cache_write
    b, s = x.shape[0], x.shape[1]
    if st.decoding:
        positions = st.cur_len[:, None]
    elif aux.positions is None:
        positions = jnp.broadcast_to(jnp.arange(s), (b, s))
    else:
        positions = aux.positions[:, :s]
    q, k, v = mla_qkv(params, x, cfg, ctx, positions)
    attn_pos = None if aux.positions is None else positions
    if st.decoding:
        cache = _cache_write(cache, k, v, st, ctx, positions)
        s_c = cache["k"].shape[1]
        out = attn_ops.decode_attention(
            q, cache["k"], cache["v"], st.cur_len + 1,
            cache_positions=jnp.broadcast_to(jnp.arange(s_c), (b, s_c)))
    else:
        if st.mode == "prefill":
            cache = _cache_write(cache, k, v, st, ctx, positions)
        out = attn_ops.chunked_attention(q, k, v, causal=True,
                                         q_positions=attn_pos,
                                         k_positions=attn_pos)
    out = out.reshape(b, s, cfg.n_heads * cfg.mla.v_head_dim)
    return row_linear(params["wo"], out, ctx), cache


def mla_cache_shape(cfg, batch_local: int, cache_len_local: int,
                    dtype=jnp.bfloat16):
    """Per-layer cache of the expanded heads: k (.., H, nope + rope), v
    (.., H, v)."""
    m, nh = cfg.mla, cfg.n_heads
    return {"k": jax.ShapeDtypeStruct(
                (batch_local, cache_len_local, nh, m.qk_head_dim), dtype),
            "v": jax.ShapeDtypeStruct(
                (batch_local, cache_len_local, nh, m.v_head_dim), dtype)}
