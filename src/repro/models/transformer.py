"""Dense transformer backbone: GQA attention block, (Swi)GLU MLP, the
scan-over-layers stack machinery, and the embedding/LM-head wiring.

Every block follows the same contract so families can mix-and-match inside
one scanned stack (DESIGN.md §4/§5):

    init(key, cfg, ctx)                  -> (params, specs)
    apply(params, x, aux, ctx, cfg, st)  -> (x, new_cache)

where ``st`` is a :class:`StepState` describing the mode ("train" | "prefill"
| "decode"), the per-block cache slice, and the dynamic lengths.  ``aux``
carries positions (and M-RoPE ids).  Activations between blocks are
replicated over TP, or seq-sharded with ctx.seq_parallel (Megatron-SP).

Caches are per-layer pytrees stacked along the scan dim by the stack runner.
"""
from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.models import attention as attn_ops
from repro.models import mla
from repro.models.layers import (ShardCtx, TP_AXIS, apply_mrope, apply_rope,
                                 column_linear, column_linear_init,
                                 embedding_lookup, embedding_init,
                                 fsdp_gather, head_layout, local_head_mask,
                                 local_kv_slice, maybe_tp_shared, pad_vocab,
                                 replicated_linear_init, rmsnorm,
                                 rmsnorm_init, row_linear, row_linear_init,
                                 tp_copy, tp_reduce, unembed_logits,
                                 vocab_parallel_xent)


# --------------------------------------------------------------------------
# Step state: mode + cache plumbing
# --------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class StepState:
    mode: str                      # "train" | "prefill" | "decode"
    cache_len: int = 0             # static KV-cache capacity (prefill/decode)
    # dynamic: number of valid cache positions BEFORE this call, (B,) int32
    cur_len: Optional[jax.Array] = None

    @property
    def training(self) -> bool:
        return self.mode == "train"

    @property
    def decoding(self) -> bool:
        return self.mode == "decode"


@dataclasses.dataclass(frozen=True)
class Aux:
    """Per-step position information (full-sequence, replicated over TP).

    ``positions=None`` states, statically, that they are ``0..S-1`` (no
    ``batch["positions"]``): that is what lets self-attention take the
    Pallas flash kernel, which has no position input."""
    positions: Optional[jax.Array] = None    # (B, S) int32
    mrope_positions: Optional[jax.Array] = None   # (3, B, S) int32


# --------------------------------------------------------------------------
# MLP
# --------------------------------------------------------------------------
def mlp_init(key, d: int, d_ff: int, ctx: ShardCtx, kind: str = "swiglu"):
    ks = jax.random.split(key, 3)
    if kind == "swiglu":
        gate, sg = column_linear_init(ks[0], d, d_ff, ctx)
        up, su = column_linear_init(ks[1], d, d_ff, ctx)
        down, sd = row_linear_init(ks[2], d_ff, d, ctx,
                                   std=1.0 / math.sqrt(d_ff))
        return ({"gate": gate, "up": up, "down": down},
                {"gate": sg, "up": su, "down": sd})
    # "gelu": classic 2-matrix FFN (enc-dec backbone)
    fc1, s1 = column_linear_init(ks[0], d, d_ff, ctx)
    fc2, s2 = row_linear_init(ks[1], d_ff, d, ctx, std=1.0 / math.sqrt(d_ff))
    return {"fc1": fc1, "fc2": fc2}, {"fc1": s1, "fc2": s2}


def mlp_apply(params, x, ctx: ShardCtx, kind: str = "swiglu"):
    """x: (B, S[, /tp w/ SP], d) -> same shape.  tp_copy/tp_reduce inside."""
    h = tp_copy(x, ctx)
    if kind == "swiglu":
        g = column_linear(params["gate"], h, ctx)
        u = column_linear(params["up"], h, ctx)
        out = row_linear(params["down"], jax.nn.silu(g) * u, ctx)
    else:
        h1 = jax.nn.gelu(column_linear(params["fc1"], h, ctx))
        out = row_linear(params["fc2"], h1, ctx)
    return tp_reduce(out, ctx)


# --------------------------------------------------------------------------
# GQA attention block
# --------------------------------------------------------------------------
def attn_init(key, cfg, ctx: ShardCtx, d: Optional[int] = None):
    """Attention weights in the padded GQA head layout (layers.head_layout)."""
    d = d or cfg.d_model
    lay = head_layout(cfg.n_heads, cfg.n_kv_heads, cfg.head_dim, ctx.tp)
    ks = jax.random.split(key, 6)
    params: dict = {}
    specs: dict = {}
    # q: columns = padded q heads, sharded over TP
    params["wq"], specs["wq"] = column_linear_init(
        ks[0], d, lay.n_h_pad * lay.head_dim, ctx)
    kv_out = cfg.n_kv_heads * lay.head_dim
    if lay.kv_replicated:
        # kv weights TP-replicated; each device consumes its head slice
        params["wk"], specs["wk"] = replicated_linear_init(ks[1], d, kv_out, ctx)
        params["wv"], specs["wv"] = replicated_linear_init(ks[2], d, kv_out, ctx)
    else:
        params["wk"], specs["wk"] = column_linear_init(ks[1], d, kv_out, ctx)
        params["wv"], specs["wv"] = column_linear_init(ks[2], d, kv_out, ctx)
    params["wo"], specs["wo"] = row_linear_init(
        ks[3], lay.n_h_pad * lay.head_dim, d, ctx,
        std=1.0 / math.sqrt(cfg.n_heads * lay.head_dim))
    if cfg.qk_norm:
        params["q_norm"], specs["q_norm"] = rmsnorm_init(lay.head_dim, ctx)
        params["k_norm"], specs["k_norm"] = rmsnorm_init(lay.head_dim, ctx)
    return params, specs


def _project_qkv(params, h, cfg, ctx: ShardCtx, lay):
    """h: (B, S, d) full-seq -> q (B,S,L,hd) local heads, k/v (B,S,kv_local,hd)."""
    b, s, _ = h.shape
    q = column_linear(params["wq"], h, ctx)
    q = q.reshape(b, s, lay.L, lay.head_dim)
    if lay.kv_replicated:
        cd = ctx.compute_dtype
        wk = maybe_tp_shared(
            fsdp_gather(params["wk"]["w"].astype(cd), ctx, axis=0), ctx)
        wv = maybe_tp_shared(
            fsdp_gather(params["wv"]["w"].astype(cd), ctx, axis=0), ctx)
        k = (h @ wk).reshape(b, s, lay.kv_heads, lay.head_dim)
        v = (h @ wv).reshape(b, s, lay.kv_heads, lay.head_dim)
        k = local_kv_slice(k, lay)
        v = local_kv_slice(v, lay)
    else:
        k = column_linear(params["wk"], h, ctx).reshape(b, s, lay.kv_local,
                                                        lay.head_dim)
        v = column_linear(params["wv"], h, ctx).reshape(b, s, lay.kv_local,
                                                        lay.head_dim)
    if cfg.qk_norm:
        # scales are TP-replicated but consumed by device-distinct heads:
        # grads are partial -> psum on backward (tp_shared)
        from repro.models.layers import tp_shared_tree
        q = rmsnorm(tp_shared_tree(params["q_norm"], ctx), q, cfg.norm_eps)
        k = rmsnorm(tp_shared_tree(params["k_norm"], ctx), k, cfg.norm_eps)
    return q, k, v


def _rotate(q, k, aux: Aux, cfg, positions):
    if cfg.rope == "rope":
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    elif cfg.rope == "mrope":
        mp = aux.mrope_positions
        q = apply_mrope(q, mp, cfg.rope_theta)
        k = apply_mrope(k, mp, cfg.rope_theta)
    return q, k


def _cache_write(cache, k, v, st: StepState, ctx: ShardCtx, positions):
    """Write new k/v at their positions into the (B, S_cache_local, kv, hd)
    cache.  With context-parallel caches each device owns a contiguous
    sequence span; out-of-span writes are dropped."""
    kc, vc = cache["k"], cache["v"]
    s_local = kc.shape[1]
    off = 0
    if ctx.cache_seq_axes:
        off = jax.lax.axis_index(ctx.cache_seq_axes) * s_local
    if st.mode == "prefill":
        # positions are 0..S-1; local span [off, off+s_local)
        s = k.shape[1]
        if not ctx.cache_seq_axes:
            kc = jax.lax.dynamic_update_slice_in_dim(
                kc, k.astype(kc.dtype), 0, axis=1)
            vc = jax.lax.dynamic_update_slice_in_dim(
                vc, v.astype(vc.dtype), 0, axis=1)
        else:
            idx = jnp.arange(s) - off                       # local slots
            b = k.shape[0]
            bi = jnp.arange(b)[:, None]
            kc = kc.at[bi, idx[None, :]].set(k.astype(kc.dtype), mode="drop")
            vc = vc.at[bi, idx[None, :]].set(v.astype(vc.dtype), mode="drop")
    else:  # decode: one token per sequence at positions (B, 1)
        slot = positions[:, 0] - off                        # (B,)
        b = k.shape[0]
        kc = kc.at[jnp.arange(b), slot].set(k[:, 0].astype(kc.dtype),
                                            mode="drop")
        vc = vc.at[jnp.arange(b), slot].set(v[:, 0].astype(vc.dtype),
                                            mode="drop")
    return {"k": kc, "v": vc}


def attn_apply(params, x, aux: Aux, ctx: ShardCtx, cfg, st: StepState,
               cache=None, *, causal: bool = True, d: Optional[int] = None):
    """Full attention sub-block: x + Wo·attn(norm-free input h).

    ``x`` enters *without* the pre-norm (the caller norms); returns the
    attention output (caller adds residual).  h is seq-sharded w/ SP.
    """
    lay = head_layout(cfg.n_heads, cfg.n_kv_heads, cfg.head_dim, ctx.tp)
    h = tp_copy(x, ctx)                                   # gather seq w/ SP
    b, s = h.shape[0], h.shape[1]
    if st.decoding:
        positions = st.cur_len[:, None]                   # (B, 1)
    elif aux.positions is None:
        positions = jnp.broadcast_to(jnp.arange(s), (b, s))
    else:
        positions = aux.positions[:, :s]
    q, k, v = _project_qkv(params, h, cfg, ctx, lay)
    q, k = _rotate(q, k, aux, cfg, positions)
    # default positions go to chunked_attention as None (its kernel path);
    # a context-parallel cache keeps the jnp path
    attn_pos = None if aux.positions is None and not ctx.cache_seq_axes \
        else positions

    if st.training:
        out = attn_ops.chunked_attention(q, k, v, causal=causal,
                                         q_positions=attn_pos,
                                         k_positions=attn_pos)
    elif st.mode == "prefill":
        cache = _cache_write(cache, k, v, st, ctx, positions)
        out = attn_ops.chunked_attention(q, k, v, causal=causal,
                                         q_positions=attn_pos,
                                         k_positions=attn_pos)
    else:  # decode
        cache = _cache_write(cache, k, v, st, ctx, positions)
        s_local = cache["k"].shape[1]
        cache_positions = jnp.broadcast_to(jnp.arange(s_local), (b, s_local))
        if ctx.cache_seq_axes:
            off = jax.lax.axis_index(ctx.cache_seq_axes) * s_local
            cache_positions = cache_positions + off
        out = attn_ops.decode_attention(
            q, cache["k"], cache["v"], st.cur_len + 1,
            cache_positions=cache_positions,
            seq_shard_axes=ctx.cache_seq_axes)

    mask = local_head_mask(lay)
    out = out * mask[None, None, :, None].astype(out.dtype)
    out = out.reshape(b, s, lay.L * lay.head_dim)
    out = row_linear(params["wo"], out, ctx)
    return tp_reduce(out, ctx), cache


def attn_cache_shape(cfg, ctx: ShardCtx, batch_local: int,
                     cache_len_local: int, dtype=jnp.bfloat16):
    """Per-layer KV cache (LOCAL shapes inside shard_map; the caller divides
    cache_len by the context-parallel degree when ctx.cache_seq_axes)."""
    if cfg.mla is not None:
        return mla.mla_cache_shape(cfg, batch_local, cache_len_local, dtype)
    lay = head_layout(cfg.n_heads, cfg.n_kv_heads, cfg.head_dim, ctx.tp)
    return {"k": jax.ShapeDtypeStruct(
                (batch_local, cache_len_local, lay.kv_local, lay.head_dim),
                dtype),
            "v": jax.ShapeDtypeStruct(
                (batch_local, cache_len_local, lay.kv_local, lay.head_dim),
                dtype)}


# --------------------------------------------------------------------------
# Dense block = pre-norm attn + pre-norm MLP
# --------------------------------------------------------------------------
def attention_init(key, cfg, ctx: ShardCtx):
    """The attention sub-block's weights: latent (MLA) where the config
    has ``mla``, GQA otherwise."""
    if cfg.mla is not None:
        return mla.mla_init(key, cfg, ctx)
    return attn_init(key, cfg, ctx)


def attention_apply(params, x, aux: Aux, ctx: ShardCtx, cfg,
                    st: StepState, cache=None):
    if cfg.mla is not None:
        return mla.mla_apply(params, x, aux, ctx, cfg, st, cache)
    return attn_apply(params, x, aux, ctx, cfg, st, cache)


def dense_block_init(key, cfg, ctx: ShardCtx):
    ks = jax.random.split(key, 4)
    pa, sa = attention_init(ks[0], cfg, ctx)
    pm, sm = mlp_init(ks[1], cfg.d_model, cfg.d_ff, ctx)
    pn1, sn1 = rmsnorm_init(cfg.d_model, ctx)
    pn2, sn2 = rmsnorm_init(cfg.d_model, ctx)
    return ({"attn": pa, "mlp": pm, "ln1": pn1, "ln2": pn2},
            {"attn": sa, "mlp": sm, "ln1": sn1, "ln2": sn2})


def dense_block_apply(params, x, aux: Aux, ctx: ShardCtx, cfg, st: StepState,
                      cache=None):
    a, cache = attention_apply(params["attn"],
                               rmsnorm(params["ln1"], x, cfg.norm_eps),
                               aux, ctx, cfg, st, cache)
    x = x + a
    x = x + mlp_apply(params["mlp"], rmsnorm(params["ln2"], x, cfg.norm_eps),
                      ctx)
    return x, cache


# --------------------------------------------------------------------------
# Stack runner: scan over stacked per-layer params (+ caches)
# --------------------------------------------------------------------------
def stack_init(init_fn: Callable, key, n: int):
    """vmap ``init_fn(key) -> (params, specs)`` into stacked params with a
    leading layer dim; specs get a leading None."""
    keys = jax.random.split(key, n)
    params = jax.vmap(lambda k: init_fn(k)[0])(keys)
    # specs are plain Python objects built during tracing — grab them from an
    # abstract (eval_shape) call so no array work happens twice.
    box = {}

    def grab(k):
        p, s = init_fn(k)
        box["s"] = s
        return p

    jax.eval_shape(grab, keys[0])
    specs = jax.tree.map(lambda s: P(None, *s), box["s"],
                         is_leaf=lambda s: isinstance(s, P))
    return params, specs


def run_stack(block_apply: Callable, stacked_params, x, caches,
              st: StepState, remat: str = "none"):
    """Scan ``block_apply(params_l, x, cache_l) -> (x, new_cache_l)`` over the
    stacked layer dim.  ``caches`` is a stacked pytree or None (train)."""

    def body(carry, xs):
        p_l, c_l = xs
        fn = block_apply
        if remat == "full":
            fn = jax.checkpoint(fn)
        elif remat == "dots":
            fn = jax.checkpoint(
                fn, policy=jax.checkpoint_policies.checkpoint_dots)
        y, new_c = fn(p_l, carry, c_l)
        if st.training:
            new_c = 0.0  # uniform scan output
        return y, new_c

    if caches is None:
        n = jax.tree.leaves(stacked_params)[0].shape[0]
        caches = jnp.zeros((n,))
    x, new_caches = jax.lax.scan(body, x, (stacked_params, caches))
    return x, (None if st.training else new_caches)


# --------------------------------------------------------------------------
# LM top/bottom: embedding, final norm, logits, loss
# --------------------------------------------------------------------------
def lm_io_init(key, cfg, ctx: ShardCtx):
    ks = jax.random.split(key, 3)
    pe, se = embedding_init(ks[0], cfg.vocab, cfg.d_model, ctx)
    pn, sn = rmsnorm_init(cfg.d_model, ctx)
    params = {"embed": pe, "final_norm": pn}
    specs = {"embed": se, "final_norm": sn}
    if not cfg.tie_embeddings:
        po, so = embedding_init(ks[1], cfg.vocab, cfg.d_model, ctx)
        params["unembed"], specs["unembed"] = po, so
    return params, specs


def embed_tokens(params, tokens, ctx: ShardCtx, cfg):
    return embedding_lookup(params["embed"], tokens, ctx, cfg.vocab)


def sp_scatter_embeds(embeds, ctx: ShardCtx):
    """Pre-computed (B, S, d) embeddings (vlm/audio stubs) -> SP local shard."""
    if ctx.seq_parallel and ctx.tp > 1:
        s = embeds.shape[1]
        m = jax.lax.axis_index(TP_AXIS)
        return jax.lax.dynamic_slice_in_dim(embeds, m * (s // ctx.tp),
                                            s // ctx.tp, axis=1)
    return embeds


def _unembed_params(params, cfg):
    return params["embed" if cfg.tie_embeddings else "unembed"]


def lm_logits(params, x, ctx: ShardCtx, cfg):
    """x: (B, S[, /tp], d) -> vocab-parallel logits (B, S, V/tp)."""
    x = rmsnorm(params["final_norm"], x, cfg.norm_eps)
    x = tp_copy(x, ctx)                                   # gather seq w/ SP
    return unembed_logits(_unembed_params(params, cfg), x, ctx)


def lm_loss(params, x, labels, ctx: ShardCtx, cfg,
            xent_chunk: int = 1024):
    """Memory-efficient LM loss: the (B, S, V/tp) logits are produced and
    consumed per sequence-chunk under jax.checkpoint, so peak memory holds
    one chunk of logits (DESIGN.md §4).  labels < 0 are masked out.

    Returns (sum_loss, n_tokens) — both LOCAL; caller psums over DP.
    """
    x = rmsnorm(params["final_norm"], x, cfg.norm_eps)
    x = tp_copy(x, ctx)
    b, s, d = x.shape
    table = _unembed_params(params, cfg)
    chunk = min(xent_chunk, s)
    pad = (-s) % chunk
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
        labels = jnp.pad(labels, ((0, 0), (0, pad)), constant_values=-1)
    nc = x.shape[1] // chunk
    xc = x.reshape(b, nc, chunk, d).swapaxes(0, 1)
    lc = labels.reshape(b, nc, chunk).swapaxes(0, 1)

    @jax.checkpoint
    def chunk_loss(xb, lb):
        logits = unembed_logits(table, xb, ctx)           # (B, C, V/tp)
        mask = lb >= 0
        per_tok = vocab_parallel_xent(logits, jnp.maximum(lb, 0), ctx,
                                      cfg.vocab)
        return jnp.sum(per_tok * mask), jnp.sum(mask)

    def body(carry, xs):
        tot, cnt = carry
        l, c = chunk_loss(*xs)
        return (tot + l, cnt + c), None

    (total, count), _ = jax.lax.scan(body, (jnp.float32(0), jnp.int32(0)),
                                     (xc, lc))
    return total, count
