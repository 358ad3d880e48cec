"""Mixture-of-Experts FFN: the capacity layer with expert parallelism
(``moe_apply``) and the drop-free layer (``dropfree_apply``).

The drop-free layer (DeepSeek-V3 routing, ``MoEConfig.drop_free``) routes
every token over all ``n_experts`` in float32 (sigmoid scores; the top-k of
score + correction bias chosen; the chosen scores, renormalised, times
``routed_scale`` weight them).  It
holds experts ``[first_held, first_held + n_held)``, a chip's share of an
expert-parallel layer, and computes every token-slot routed to them with no
capacity: the slots are sorted by expert into a row buffer, each expert's
rows padded to whole tiles, and a grouped matmul runs over the tiles that
hold rows (``kernels/ops.grouped_matmul``: the Pallas kernel
``kernels/gmm.py`` on TPU).  Slots routed to experts held elsewhere add
nothing here; on one chip there is no exchange.  Shared experts are one
SwiGLU of ``n_shared`` experts' width, gated by a sigmoid only where
``shared_gate``.  The layer returns counters: slots routed to held experts,
those of them the grouped matmul left uncomputed (0), and the largest load
over the mean among all ``n_experts``.

The capacity layer: routing is top-k softmax with a fixed per-expert
capacity (dropped tokens fall back to the residual path).  Dispatch is sort-based — O(T·k) memory, no
(T, E, C) one-hot tensor — which is what makes the 32k-token train shapes
fit:

    1. top-k expert ids per token -> flat (T·k,) assignment list
    2. stable-sort by expert id; position-within-expert via cumulative counts
    3. scatter tokens into a (E_pad, C, d) buffer (over-capacity slots drop)
    4. all_to_all over the EP axis: (tp, E_local, C, d) -> (E_local, tp·C, d)
    5. batched expert SwiGLU (experts stacked on the local leading dim)
    6. all_to_all back, gather to token order, combine weighted by router

Experts are sharded E_pad/tp per device over the EP axis (= the TP "model"
axis for training; serving may pass a different axis).  E is padded to a
multiple of the EP degree with dummy experts whose router logits are -inf.

MoE consumes SEQ-SHARDED activations directly under SP (no tp_copy): the
all_to_all already mixes tokens across the axis, so routing local tokens is
both correct and 1/tp cheaper (DESIGN.md §4).
"""
from __future__ import annotations

import dataclasses
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.models.layers import (ShardCtx, TP_AXIS, _trunc_normal,
                                 fsdp_gather, maybe_tp_shared)
from repro.models.transformer import mlp_apply, mlp_init


def pad_experts(n_experts: int, ep: int) -> int:
    return -(-n_experts // ep) * ep


def capacity(tokens_local: int, top_k: int, e_pad: int, ep: int,
             factor: float) -> int:
    """Per-expert, per-source-device slot count.  Multiples of 8 for layout."""
    c = math.ceil(tokens_local * top_k / e_pad * factor)
    return max(8, -(-c // 8) * 8)


def moe_init(key, cfg, ctx: ShardCtx, ep: Optional[int] = None):
    """Routed experts (+ optional shared experts / dense residual).

    Two expert layouts (DESIGN.md §5):
      * default (training): E over the TP "model" axis, d over FSDP;
      * ctx.moe_ep_axis == "data" (2D serving): E over "data", d_ff over
        "model" — expert FFNs are row/column-parallel within each expert
        and residency needs no gather (arctic).
    """
    two_d = ctx.moe_ep_axis is not None and ctx.moe_ep_axis != TP_AXIS
    mc = cfg.moe
    ep = ep or ctx.tp
    e_pad = pad_experts(mc.n_experts, ep)
    d, d_ff = cfg.d_model, cfg.d_ff
    ks = jax.random.split(key, 6)

    def expert_init(k):
        k1, k2, k3 = jax.random.split(k, 3)
        return {
            "gate": _trunc_normal(k1, (d, d_ff), 1 / math.sqrt(d),
                                  ctx.param_dtype),
            "up": _trunc_normal(k2, (d, d_ff), 1 / math.sqrt(d),
                                ctx.param_dtype),
            "down": _trunc_normal(k3, (d_ff, d), 1 / math.sqrt(d_ff),
                                  ctx.param_dtype),
        }

    experts = jax.vmap(expert_init)(jax.random.split(ks[0], e_pad))
    fs = ctx.fsdp_spec()
    if two_d:
        ax = ctx.moe_ep_axis
        expert_specs = {"gate": P(ax, None, TP_AXIS),
                        "up": P(ax, None, TP_AXIS),
                        "down": P(ax, TP_AXIS, None)}
    else:
        # experts stacked (E_pad, ...): E over the EP axis, d over FSDP
        expert_specs = {"gate": P(TP_AXIS, fs, None),
                        "up": P(TP_AXIS, fs, None),
                        "down": P(TP_AXIS, fs, None)}
    params = {
        "router": _trunc_normal(ks[1], (d, e_pad), 0.02, jnp.float32),
        "experts": experts,
    }
    specs = {"router": P(None, None), "experts": expert_specs}
    if mc.n_shared:
        ps, ss = shared_init(ks[2], ks[4], cfg, ctx, d_ff * mc.n_shared)
        params.update(ps)
        specs.update(ss)
    if mc.dense_residual:
        pd, sd = mlp_init(ks[3], d, d_ff, ctx)
        params["dense"], specs["dense"] = pd, sd
    return params, specs


def shared_init(k_mlp, k_gate, cfg, ctx: ShardCtx, width: int):
    """Shared experts: one SwiGLU of ``width``, with a sigmoid gate on its
    output where ``moe.shared_gate`` (qwen2-moe; DeepSeek-V3 has none)."""
    params, specs = {}, {}
    params["shared"], specs["shared"] = mlp_init(k_mlp, cfg.d_model, width,
                                                 ctx)
    if cfg.moe.shared_gate:
        params["shared_gate"] = _trunc_normal(k_gate, (cfg.d_model, 1), 0.02,
                                              jnp.float32)
        specs["shared_gate"] = P(None, None)
    return params, specs


def shared_apply(params, x, ctx: ShardCtx, cfg):
    sh = mlp_apply(params["shared"], x, ctx)
    if not cfg.moe.shared_gate:
        return sh
    gate = jax.nn.sigmoid(
        x.astype(jnp.float32) @ maybe_tp_shared(params["shared_gate"], ctx))
    return sh * gate.astype(x.dtype)


def _route(router_w, x, mc, e_pad: int):
    """x: (T, d) -> (probs (T, k), idx (T, k) int32) — fp32 router math."""
    logits = x.astype(jnp.float32) @ router_w.astype(jnp.float32)
    if e_pad > mc.n_experts:
        pad_mask = jnp.arange(e_pad) >= mc.n_experts
        logits = jnp.where(pad_mask[None, :], -jnp.inf, logits)
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_i = jax.lax.top_k(probs, mc.top_k)
    top_p = top_p / jnp.maximum(top_p.sum(-1, keepdims=True), 1e-9)
    return top_p, top_i.astype(jnp.int32), logits


def _dispatch_indices(top_i, e_pad: int, cap: int):
    """Sort-based slot assignment.  Returns per-(token,k): expert id, slot id,
    keep mask — plus the inverse permutation for combine."""
    t, k = top_i.shape
    flat_e = top_i.reshape(-1)                              # (T·k,)
    order = jnp.argsort(flat_e, stable=True)                # sorted by expert
    sorted_e = flat_e[order]
    counts = jnp.bincount(flat_e, length=e_pad)             # tokens/expert
    starts = jnp.cumsum(counts) - counts                    # exclusive cumsum
    pos_in_e = jnp.arange(t * k) - starts[sorted_e]         # rank w/in expert
    keep = pos_in_e < cap
    # scatter destinations in sorted order; invert to token order
    inv = jnp.argsort(order, stable=True)
    expert_of = sorted_e[inv]                               # == flat_e
    slot_of = pos_in_e[inv]
    keep = keep[inv]
    return expert_of, slot_of, keep


def _ep_all_to_all(buf, ep_axis: Optional[str], ep: int, forward: bool):
    """(E_pad, C, d) <-> (E_local, ep·C, d) over the EP mesh axis.

    all_to_all(split=0, concat=0) on a leading (ep, ...) dim swaps the
    device axis with that dim: dim0 indexes destination before, source
    after."""
    if not ep_axis or ep == 1:
        return buf
    if forward:
        e_pad, c, d = buf.shape
        buf = buf.reshape(ep, e_pad // ep, c, d)            # dim0 = dest
        buf = jax.lax.all_to_all(buf, ep_axis, split_axis=0, concat_axis=0)
        # (ep, E_local, C, d): dim0 = source device
        return buf.transpose(1, 0, 2, 3).reshape(e_pad // ep, ep * c, d)
    e_local, epc, d = buf.shape
    c = epc // ep
    buf = buf.reshape(e_local, ep, c, d).transpose(1, 0, 2, 3)  # dim0 = dest
    buf = jax.lax.all_to_all(buf, ep_axis, split_axis=0, concat_axis=0)
    return buf.reshape(e_local * ep, c, d)


def moe_apply(params, x, ctx: ShardCtx, cfg, ep_axis: Optional[str] = None):
    """x: (B, S_local, d) — tokens stay sharded (SP-friendly).  Returns the
    combined expert output (same shape).  Caller adds the residual."""
    mc = cfg.moe
    if ep_axis is None:
        ep_axis = ctx.moe_ep_axis or (TP_AXIS if ctx.tp > 1 else None)
    two_d = ep_axis is not None and ep_axis != TP_AXIS and ctx.tp > 1
    ep = jax.lax.axis_size(ep_axis) if ep_axis else 1
    b, s, d = x.shape
    t = b * s
    e_pad = pad_experts(mc.n_experts, ep)
    e_local = e_pad // ep
    cap = capacity(t, mc.top_k, e_pad, ep, mc.capacity_factor)

    xt = x.reshape(t, d)
    router_w = maybe_tp_shared(params["router"], ctx)
    probs, top_i, logits = _route(router_w, xt, mc, e_pad)
    expert_of, slot_of, keep = _dispatch_indices(top_i, e_pad, cap)

    # ---- dispatch: (T·k) scatter into (E_pad, C, d) ----
    tok_of = jnp.repeat(jnp.arange(t), mc.top_k)
    buf = jnp.zeros((e_pad, cap, d), ctx.compute_dtype)
    src = xt.astype(ctx.compute_dtype)[tok_of]
    slot_ok = jnp.where(keep, slot_of, cap)                 # cap => dropped
    buf = buf.at[expert_of, slot_ok].set(src, mode="drop")

    # ---- EP exchange + batched expert FFN ----
    buf = _ep_all_to_all(buf, ep_axis, ep, forward=True)    # (E_local, ep·C, d)
    cd = ctx.compute_dtype
    if two_d:
        # 2D layout: d_ff sharded over TP — column×row parallel per expert,
        # psum terminates the row-parallel down projection
        w_g = params["experts"]["gate"].astype(cd)
        w_u = params["experts"]["up"].astype(cd)
        w_d = params["experts"]["down"].astype(cd)
    else:
        w_g = fsdp_gather(params["experts"]["gate"].astype(cd), ctx,
                          axis=1)
        w_u = fsdp_gather(params["experts"]["up"].astype(cd), ctx, axis=1)
        w_d = fsdp_gather(params["experts"]["down"].astype(cd), ctx,
                          axis=1)
    h_g = jnp.einsum("ecd,edf->ecf", buf, w_g)
    h_u = jnp.einsum("ecd,edf->ecf", buf, w_u)
    h = jax.nn.silu(h_g) * h_u
    out = jnp.einsum("ecf,efd->ecd", h, w_d)
    if two_d:
        out = jax.lax.psum(out, TP_AXIS)
    out = _ep_all_to_all(out, ep_axis, ep, forward=False)   # (E_pad, C, d)

    # ---- combine: gather slots back to tokens, weight by router probs ----
    gathered = out[expert_of, jnp.minimum(slot_of, cap - 1)]      # (T·k, d)
    w = (probs.reshape(-1) * keep).astype(jnp.float32)
    combined = jnp.zeros((t, d), jnp.float32).at[tok_of].add(
        gathered.astype(jnp.float32) * w[:, None])
    y = combined.reshape(b, s, d).astype(x.dtype)

    # ---- shared experts / dense residual (plain TP MLPs) ----
    if mc.n_shared:
        y = y + shared_apply(params, x, ctx, cfg)
    if mc.dense_residual:
        y = y + mlp_apply(params["dense"], x, ctx)
    return y, _aux_loss(logits, top_i, mc, e_pad)


def _aux_loss(logits, top_i, mc, e_pad: int):
    """Switch-style load-balancing loss (mean over local tokens)."""
    probs = jax.nn.softmax(logits, axis=-1)                 # (T, E)
    me = jnp.mean(probs, axis=0)
    hits = jnp.zeros((e_pad,), jnp.float32).at[top_i.reshape(-1)].add(1.0)
    ce = hits / jnp.maximum(hits.sum(), 1.0)
    return e_pad * jnp.sum(me * ce)


# --------------------------------------------------------------------------
# drop-free layer
# --------------------------------------------------------------------------
#: rows of a grouped-matmul tile: each held expert's rows are padded to
#: whole tiles
TILE_ROWS = 256
#: the counters that merge by their maximum; the others add up
STAT_MAX = ("load_max_over_mean",)


def merge_stats(a: dict, b: dict) -> dict:
    """Counters of two layers (or microbatches); a layer that keeps none
    gives an empty dict."""
    out = {**a, **b}
    out.update({k: (jnp.maximum if k in STAT_MAX else jnp.add)(a[k], b[k])
                for k in a.keys() & b.keys()})
    return out


def total_stats(stacked: dict) -> dict:
    """Counters stacked over layers (a scan's output) merged into one."""
    return {k: (jnp.max if k in STAT_MAX else jnp.sum)(v)
            for k, v in stacked.items()}


def reduce_stats(stats: dict, axes) -> dict:
    """Counters merged over the mesh ``axes`` (none: as they are)."""
    if not axes:
        return stats
    return {k: (jax.lax.pmax if k in STAT_MAX else jax.lax.psum)(v, axes)
            for k, v in stats.items()}


def dropfree_init(key, cfg, ctx: ShardCtx):
    """Router over all ``n_experts``, the held experts stacked (n_held, ..)
    and the shared experts.  On the training mesh a chip holds its share
    whole (no expert axis)."""
    mc = cfg.moe
    d, f, held = cfg.d_model, mc.d_expert, mc.held
    ks = jax.random.split(key, 6)
    dt = ctx.param_dtype
    params = {
        "router": _trunc_normal(ks[0], (d, mc.n_experts), 0.02, dt),
        "experts": {
            "gate": _trunc_normal(ks[1], (held, d, f), 1 / math.sqrt(d), dt),
            "up": _trunc_normal(ks[2], (held, d, f), 1 / math.sqrt(d), dt),
            "down": _trunc_normal(ks[3], (held, f, d), 1 / math.sqrt(f),
                                  dt)},
    }
    specs = {"router": P(None, None),
             "experts": {k: P(None, None, None)
                         for k in ("gate", "up", "down")}}
    if mc.n_shared:
        ps, ss = shared_init(ks[4], ks[5], cfg, ctx, f * mc.n_shared)
        params.update(ps)
        specs.update(ss)
    return params, specs


def route(logits, mc, bias):
    """Float32 logits (T, n_experts) -> (weights (T, k), experts (T, k)):
    the top-k of sigmoid score + ``bias`` (selection only), weighted by
    their scores renormalised to sum 1, times ``mc.routed_scale``."""
    scores = jax.nn.sigmoid(logits)
    _, idx = jax.lax.top_k(jax.lax.stop_gradient(scores + bias), mc.top_k)
    w = jnp.take_along_axis(scores, idx, axis=-1)
    w = w / (jnp.sum(w, -1, keepdims=True) + 1e-20)
    return w * mc.routed_scale, idx.astype(jnp.int32)


def dispatch_plan(idx, mc, tm: int):
    """Where each token-slot routed to a held expert goes in the row buffer.

    Returns ``(slot_row (T, k), row_tok (M,), row_slot (M,), group_tiles
    (n_held,))``: a slot's row (``M`` where its expert is held elsewhere),
    each row's token (``T`` for a padding row) and slot (``T * k``), and the
    tiles each held expert's rows fill.  ``M`` rows hold the worst case,
    every token's slots on held experts plus a part tile per expert."""
    t, k = idx.shape
    held, n = mc.held, idx.size
    m = -(-(t * min(k, held) + held * (tm - 1)) // tm) * tm
    local = idx.reshape(-1) - mc.first_held
    key = jnp.where((local >= 0) & (local < held), local, held)
    counts = jnp.bincount(key, length=held + 1)
    tiles = -(-counts[:held] // tm)
    first_row = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                                 jnp.cumsum(tiles * tm)])
    order = jnp.argsort(key, stable=True)
    sorted_key = key[order]
    rank = jnp.arange(n) - (jnp.cumsum(counts) - counts)[sorted_key]
    rows = jnp.where(sorted_key < held, first_row[sorted_key] + rank, m)
    slot_row = jnp.zeros((n,), jnp.int32).at[order].set(rows.astype(
        jnp.int32))
    row_tok = jnp.full((m,), t, jnp.int32).at[slot_row].set(
        jnp.arange(n, dtype=jnp.int32) // k, mode="drop")
    row_slot = jnp.full((m,), n, jnp.int32).at[slot_row].set(
        jnp.arange(n, dtype=jnp.int32), mode="drop")
    return (slot_row.reshape(t, k), row_tok, row_slot,
            tiles.astype(jnp.int32))


def slot_counts(idx, row_tok, tiles, mc, tm: int):
    """(token-slots routed to the held experts, those of them left
    uncomputed): the first counted from ``idx`` (T, k), the second less the
    rows of the tiles the grouped matmul runs that hold a token."""
    local = idx - mc.first_held
    held = jnp.sum((local >= 0) & (local < mc.held))
    run = jnp.arange(row_tok.shape[0]) < jnp.sum(tiles) * tm
    computed = jnp.sum((row_tok < idx.shape[0]) & run)
    return held, held - computed


def _pad_row(x):
    return jnp.concatenate([x, jnp.zeros((1,) + x.shape[1:], x.dtype)])


@jax.custom_vjp
def _to_rows(x, slot_row, row_tok):
    """Token rows (T, d) to the row buffer (M, d); padding rows zero."""
    return _pad_row(x)[row_tok]


def _to_rows_fwd(x, slot_row, row_tok):
    return _to_rows(x, slot_row, row_tok), (slot_row, row_tok)


def _to_rows_bwd(res, g):
    slot_row, row_tok = res
    dx = jnp.sum(_pad_row(g)[slot_row].astype(jnp.float32), axis=1)
    return dx.astype(g.dtype), None, None


_to_rows.defvjp(_to_rows_fwd, _to_rows_bwd)


@jax.custom_vjp
def _to_tokens(y, w, slot_row, row_tok, row_slot):
    """Row buffer (M, d) to tokens (T, d): each token's held slots,
    weighted by ``w`` (T, k), summed in float32."""
    g = _pad_row(y)[slot_row].astype(jnp.float32)          # (T, k, d)
    return jnp.einsum("tkd,tk->td", g, w).astype(y.dtype)


def _to_tokens_fwd(y, w, slot_row, row_tok, row_slot):
    return _to_tokens(y, w, slot_row, row_tok, row_slot), \
        (y, w, slot_row, row_tok, row_slot)


def _to_tokens_bwd(res, dy):
    y, w, slot_row, row_tok, row_slot = res
    w_row = _pad_row(w.reshape(-1))[row_slot]
    d_rows = (_pad_row(dy)[row_tok].astype(jnp.float32)
              * w_row[:, None]).astype(y.dtype)
    g = _pad_row(y)[slot_row].astype(jnp.float32)
    dw = jnp.einsum("tkd,td->tk", g, dy.astype(jnp.float32))
    return d_rows, dw, None, None, None


_to_tokens.defvjp(_to_tokens_fwd, _to_tokens_bwd)


def dropfree_apply(params, x, ctx: ShardCtx, cfg, tm: int = TILE_ROWS):
    """x: (B, S, d) -> (routed + shared output (B, S, d), counters).
    The correction bias is a zero buffer (its balancing update is not
    trained here)."""
    from repro.kernels import ops
    mc = cfg.moe
    if ctx.tp != 1:
        raise NotImplementedError("the drop-free layer under tensor "
                                  "parallelism")
    b, s, d = x.shape
    t = b * s
    xt = x.reshape(t, d)
    cd = ctx.compute_dtype
    with jax.named_scope("moe"):
        logits = xt.astype(jnp.float32) @ params["router"].astype(
            jnp.float32)
        w, idx = route(logits, mc, jnp.zeros((mc.n_experts,), jnp.float32))
        slot_row, row_tok, row_slot, tiles = dispatch_plan(idx, mc, tm)
        rows = _to_rows(xt.astype(cd), slot_row, row_tok)
    ex = params["experts"]

    def gm(a, name):
        return ops.grouped_matmul(a, ex[name].astype(cd), tiles, tm=tm)

    h = jax.nn.silu(gm(rows, "gate")) * gm(rows, "up")
    out_rows = gm(h, "down")
    with jax.named_scope("moe"):
        y = _to_tokens(out_rows, w, slot_row, row_tok, row_slot)
        load = jnp.bincount(idx.reshape(-1), length=mc.n_experts)
        held, dropped = slot_counts(idx, row_tok, tiles, mc, tm)
        stats = {
            "held_slots": held, "dropped_slots": dropped,
            "load_max_over_mean": jnp.max(load).astype(jnp.float32)
            / (idx.size / mc.n_experts)}
    y = y.reshape(b, s, d).astype(x.dtype)
    if mc.n_shared:
        y = y + shared_apply(params, x, ctx, cfg)
    return y, stats


# --------------------------------------------------------------------------
# MoE transformer block (attention + MoE FFN)
# --------------------------------------------------------------------------
def moe_block_init(key, cfg, ctx: ShardCtx):
    from repro.models.transformer import attention_init, rmsnorm_init
    ks = jax.random.split(key, 4)
    pa, sa = attention_init(ks[0], cfg, ctx)
    pm, sm = (dropfree_init if cfg.moe.drop_free else moe_init)(
        ks[1], cfg, ctx)
    pn1, sn1 = rmsnorm_init(cfg.d_model, ctx)
    pn2, sn2 = rmsnorm_init(cfg.d_model, ctx)
    return ({"attn": pa, "moe": pm, "ln1": pn1, "ln2": pn2},
            {"attn": sa, "moe": sm, "ln1": sn1, "ln2": sn2})


def moe_block_apply(params, x, aux, ctx: ShardCtx, cfg, st, cache=None):
    """Returns (x, cache, aux loss, counters): the capacity layer's aux loss
    and no counters, or the drop-free layer's counters and aux loss 0."""
    from repro.models.layers import rmsnorm
    from repro.models.transformer import attention_apply
    a, cache = attention_apply(params["attn"],
                               rmsnorm(params["ln1"], x, cfg.norm_eps),
                               aux, ctx, cfg, st, cache)
    x = x + a
    h = rmsnorm(params["ln2"], x, cfg.norm_eps)
    if cfg.moe.drop_free:
        m, stats = dropfree_apply(params["moe"], h, ctx, cfg)
        return x + m, cache, jnp.float32(0.0), stats
    m, aux_loss = moe_apply(params["moe"], h, ctx, cfg)
    return x + m, cache, aux_loss, {}
