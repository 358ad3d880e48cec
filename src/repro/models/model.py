"""The Model facade: ArchConfig -> init / loss / prefill / decode.

One class serves all 10 assigned architectures (DESIGN.md §5).  Families
differ only in their *stack*:

  dense / vlm   scan over L × (attn + SwiGLU)          [vlm: M-RoPE, embeds-in]
  moe           scan over L × (attn + MoE FFN)
  hybrid        scan over G groups × (shared attn block w/ per-group LoRA
                + inner scan over mamba layers)        [zamba2]
  ssm           scan over G groups × (7 mLSTM + 1 sLSTM)  [xlstm]
  audio         encoder scan + decoder scan (cross-attn) [seamless, enc-dec]

All code runs inside ``shard_map`` with manual collectives; params and
caches carry PartitionSpecs for the GLOBAL (logical) arrays.  Cache builders
return (local ShapeDtypeStructs, specs); ``globalize`` maps local -> global
shapes for jit/AOT lowering.
"""
from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ArchConfig
from repro.models import encdec, mamba2, moe as moe_mod, transformer as tf
from repro.models import xlstm
from repro.models.layers import (ShardCtx, TP_AXIS, _trunc_normal,
                                 head_layout, rmsnorm, sinusoidal_positions,
                                 tp_copy)
from repro.models.transformer import Aux, StepState


# --------------------------------------------------------------------------
# helpers
# --------------------------------------------------------------------------
def _stack(init_fn, key, n):
    return tf.stack_init(init_fn, key, n)


def _prepend(spec_tree, extra=1):
    def f(s):
        return P(*([None] * extra), *s)
    return jax.tree.map(f, spec_tree, is_leaf=lambda s: isinstance(s, P))


def _remat(fn, mode: str):
    """Block-level rematerialization.  The wrapped fn's positional args pass
    through optimization_barrier: the backward pass consumes per-layer
    slices of the saved activation stack, and without the barrier XLA
    hoists convert(slice(stack)) into a whole-stack fp32 copy."""
    if mode == "none":
        return fn

    def barriered(*args, **kw):
        args = jax.lax.optimization_barrier(args)
        return fn(*args, **kw)

    if mode == "dots":
        return jax.checkpoint(
            barriered, policy=jax.checkpoint_policies.checkpoint_dots)
    return jax.checkpoint(barriered)


def _scan_with_cache(block_fn, stacked_params, x, caches):
    """Scan blocks carrying the FULL stacked cache; layer l is read with
    dynamic_index and written back in place (XLA aliases the while-loop
    carry with the donated cache buffer — no triple buffering)."""
    n = jax.tree.leaves(stacked_params)[0].shape[0]

    def body(carry, xs):
        y, cache_full = carry
        p_l, idx = xs
        c_l = jax.tree.map(
            lambda c: jax.lax.dynamic_index_in_dim(c, idx, 0,
                                                   keepdims=False),
            cache_full)
        y, nc = block_fn(p_l, y, cache=c_l)
        cache_full = jax.tree.map(
            lambda c, u: jax.lax.dynamic_update_index_in_dim(
                c, u.astype(c.dtype), idx, 0),
            cache_full, nc)
        return (y, cache_full), None

    (x, caches), _ = jax.lax.scan(
        body, (x, caches), (stacked_params, jnp.arange(n)))
    return x, caches


def globalize(sds_tree, spec_tree, mesh_axis_sizes: dict):
    """Local ShapeDtypeStructs + specs -> global ShapeDtypeStructs."""
    def f(sds, spec):
        shape = list(sds.shape)
        for i, entry in enumerate(spec):
            if entry is None:
                continue
            axes = entry if isinstance(entry, tuple) else (entry,)
            for ax in axes:
                shape[i] *= mesh_axis_sizes.get(ax, 1)
        return jax.ShapeDtypeStruct(tuple(shape), sds.dtype)
    return jax.tree.map(f, sds_tree, spec_tree,
                        is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct))


def _lora_init(key, d_in: int, d_out_local_spec, d_out: int, rank: int,
               ctx: ShardCtx, out_tp: bool):
    """LoRA pair: A (d_in, r) fsdp-sharded; B (r, d_out) TP-sharded when the
    base weight's out dim is (zamba2 shared-block adapters)."""
    ka, kb = jax.random.split(key)
    a = _trunc_normal(ka, (d_in, rank), 1.0 / math.sqrt(d_in),
                      ctx.param_dtype)
    b = jnp.zeros((rank, d_out), ctx.param_dtype)
    fs = ctx.fsdp_spec()
    return ({"a": a, "b": b},
            {"a": P(fs, None), "b": P(None, TP_AXIS if out_tp else None)})


def _lora_patch(w_params, lora, ctx: ShardCtx):
    """w (sharded) + A_local @ B_local — the delta composes in sharded space
    because A shards d_in like w's fsdp dim and B shards d_out like w's TP
    dim.  A is TP-replicated but consumed per-TP-shard (partial grads) ->
    tp_shared."""
    from repro.models.layers import maybe_tp_shared
    a = maybe_tp_shared(lora["a"], ctx)
    delta = (a.astype(jnp.float32)
             @ lora["b"].astype(jnp.float32)).astype(w_params["w"].dtype)
    return {**w_params, "w": w_params["w"] + delta}


# --------------------------------------------------------------------------
# Model
# --------------------------------------------------------------------------
ZAMBA_LORA_RANK = 64


class Model:
    def __init__(self, cfg: ArchConfig):
        self.cfg = cfg
        self.family = cfg.family

    # ---------------- init ----------------
    def init(self, key, ctx: ShardCtx):
        cfg = self.cfg
        k_io, k_stack, k_extra = jax.random.split(key, 3)
        params, specs = tf.lm_io_init(k_io, cfg, ctx)

        if self.family in ("dense", "vlm"):
            p, s = _stack(lambda k: tf.dense_block_init(k, cfg, ctx),
                          k_stack, cfg.n_layers)
            params["blocks"], specs["blocks"] = p, s
        elif self.family == "moe":
            p, s = _stack(lambda k: moe_mod.moe_block_init(k, cfg, ctx),
                          k_stack, cfg.n_layers)
            params["blocks"], specs["blocks"] = p, s
        elif self.family == "hybrid":
            params["shared"], specs["shared"] = tf.dense_block_init(
                k_extra, cfg, ctx)
            g = cfg.n_layers // cfg.ssm.attn_every
            p, s = _stack(lambda k: self._zamba_group_init(k, ctx),
                          k_stack, g)
            params["groups"], specs["groups"] = p, s
        elif self.family == "ssm":
            per = cfg.ssm.slstm_every
            g = cfg.n_layers // per
            p, s = _stack(lambda k: self._xlstm_group_init(k, ctx, per),
                          k_stack, g)
            params["groups"], specs["groups"] = p, s
        elif self.family == "audio":
            pe, se = _stack(lambda k: encdec.enc_block_init(k, cfg, ctx),
                            k_stack, cfg.encdec.enc_layers)
            kd = jax.random.fold_in(k_stack, 1)
            pd, sd = _stack(lambda k: encdec.dec_block_init(k, cfg, ctx),
                            kd, cfg.n_layers)
            params["enc_blocks"], specs["enc_blocks"] = pe, se
            params["dec_blocks"], specs["dec_blocks"] = pd, sd
            pn, sn = tf.rmsnorm_init(cfg.d_model, ctx)
            params["enc_norm"], specs["enc_norm"] = pn, sn
        else:
            raise ValueError(self.family)
        return params, specs

    def _zamba_group_init(self, key, ctx):
        cfg = self.cfg
        ks = jax.random.split(key, 8)
        inner, s_inner = _stack(
            lambda k: mamba2.mamba_block_init(k, cfg, ctx),
            ks[0], cfg.ssm.attn_every)
        lora, s_lora = {}, {}
        lay = head_layout(cfg.n_heads, cfg.n_kv_heads, cfg.head_dim, ctx.tp)
        targets = {
            "wq": (cfg.d_model, lay.n_h_pad * lay.head_dim, True),
            "gate": (cfg.d_model, cfg.d_ff, True),
            "up": (cfg.d_model, cfg.d_ff, True),
        }
        for i, (name, (din, dout, out_tp)) in enumerate(targets.items()):
            lora[name], s_lora[name] = _lora_init(
                ks[1 + i], din, None, dout, ZAMBA_LORA_RANK, ctx, out_tp)
        return ({"mamba": inner, "lora": lora},
                {"mamba": s_inner, "lora": s_lora})

    def _xlstm_group_init(self, key, ctx, per: int):
        cfg = self.cfg
        k1, k2 = jax.random.split(key)
        ml, s_ml = _stack(lambda k: xlstm.mlstm_block_init(k, cfg, ctx),
                          k1, per - 1)
        sl, s_sl = xlstm.slstm_block_init(k2, cfg, ctx)
        return {"mlstm": ml, "slstm": sl}, {"mlstm": s_ml, "slstm": s_sl}

    # ---------------- abstract init (dry-run) ----------------
    def abstract_init(self, ctx: ShardCtx):
        box = {}

        def grab(k):
            p, s = self.init(k, ctx)
            box["specs"] = s
            return p

        shapes = jax.eval_shape(grab, jax.random.key(0))
        return shapes, box["specs"]

    # ---------------- forward ----------------
    def _embed_in(self, params, batch, ctx: ShardCtx):
        cfg = self.cfg
        if "embeds" in batch:
            x = tf.sp_scatter_embeds(batch["embeds"].astype(
                ctx.compute_dtype), ctx)
            s_full = batch["embeds"].shape[1]
            bsz = batch["embeds"].shape[0]
        else:
            x = tf.embed_tokens(params, batch["tokens"], ctx, cfg)
            s_full = batch["tokens"].shape[1]
            bsz = batch["tokens"].shape[0]
        if cfg.rope == "none" and self.family == "audio":
            pos = jnp.arange(s_full)
            pe = sinusoidal_positions(pos, cfg.d_model)[None]
            pe = tf.sp_scatter_embeds(
                jnp.broadcast_to(pe, (bsz, s_full, cfg.d_model)), ctx)
            x = x + pe.astype(x.dtype)
        aux = Aux(positions=batch.get("positions"),
                  mrope_positions=batch.get("mrope_positions"))
        return x, aux

    def _run_blocks(self, params, x, aux, ctx, st: StepState, caches):
        """Dispatch to the family stack.  Returns (x, new_caches, moe_aux).

        Train mode scans blocks with remat; prefill/decode carry the FULL
        stacked cache through the scan and update layer l in place
        (dynamic_update_index) — the in-place while-loop carry is what lets
        XLA alias the (donated) cache buffer instead of triple-buffering it.
        """
        cfg = self.cfg
        remat = cfg.plan.remat if st.training else "none"
        fam = self.family
        if fam in ("dense", "vlm"):
            fn = partial(tf.dense_block_apply, aux=aux, ctx=ctx, cfg=cfg,
                         st=st)
            if st.training:
                def body(carry, p_l):
                    y, _ = _remat(fn, remat)(p_l, carry, cache=None)
                    return y, None
                x, _ = jax.lax.scan(body, x, params["blocks"])
                return x, None, 0.0
            x, caches = _scan_with_cache(fn, params["blocks"], x, caches)
            return x, caches, 0.0
        if fam == "moe":
            if st.training:
                def body(carry, p_l):
                    y, acc = carry
                    fn = _remat(partial(moe_mod.moe_block_apply, aux=aux,
                                        ctx=ctx, cfg=cfg, st=st), remat)
                    y, _, al = fn(p_l, y, cache=None)
                    return (y, acc + al), None
                (x, aux_loss), _ = jax.lax.scan(
                    body, (x, jnp.float32(0.0)), params["blocks"])
                return x, None, aux_loss / cfg.n_layers

            def moe_fn(p_l, y, cache):
                y, nc, _ = moe_mod.moe_block_apply(p_l, y, aux=aux, ctx=ctx,
                                                   cfg=cfg, st=st,
                                                   cache=cache)
                return y, nc
            x, caches = _scan_with_cache(moe_fn, params["blocks"], x,
                                         caches)
            return x, caches, 0.0
        if fam == "hybrid":
            shared = params["shared"]
            fn = partial(self._zamba_group_apply, shared=shared, aux=aux,
                         ctx=ctx, st=st, remat=remat)
            if st.training:
                def body(carry, p_g):
                    y, _ = _remat(fn, remat)(p_g, carry, cache=None)
                    return y, None
                x, _ = jax.lax.scan(body, x, params["groups"])
                return x, None, 0.0
            x, caches = _scan_with_cache(fn, params["groups"], x, caches)
            return x, caches, 0.0
        if fam == "ssm":
            fn = partial(self._xlstm_group_apply, ctx=ctx, st=st,
                         remat=remat)
            if st.training:
                def body(carry, p_g):
                    y, _ = _remat(fn, remat)(p_g, carry, cache=None)
                    return y, None
                x, _ = jax.lax.scan(body, x, params["groups"])
                return x, None, 0.0
            x, caches = _scan_with_cache(fn, params["groups"], x, caches)
            return x, caches, 0.0
        raise ValueError(fam)

    def _zamba_group_apply(self, p_g, x, shared, aux, ctx, st, cache=None,
                           remat="none"):
        cfg = self.cfg
        patched = dict(shared)
        patched["attn"] = dict(shared["attn"])
        patched["attn"]["wq"] = _lora_patch(shared["attn"]["wq"],
                                            p_g["lora"]["wq"], ctx)
        patched["mlp"] = dict(shared["mlp"])
        patched["mlp"]["gate"] = _lora_patch(shared["mlp"]["gate"],
                                             p_g["lora"]["gate"], ctx)
        patched["mlp"]["up"] = _lora_patch(shared["mlp"]["up"],
                                           p_g["lora"]["up"], ctx)
        a_cache = None if st.training else cache["attn"]
        attn_fn = _remat(partial(tf.dense_block_apply, aux=aux, ctx=ctx,
                                 cfg=cfg, st=st), remat)
        x, a_cache = attn_fn(patched, x, cache=a_cache)

        mamba_fn = partial(mamba2.mamba_block_apply, ctx=ctx, cfg=cfg,
                           st=st)
        if st.training:
            def inner(carry, p_l):
                y, _ = _remat(mamba_fn, remat)(p_l, carry, cache=None)
                return y, None
            x, _ = jax.lax.scan(inner, x, p_g["mamba"])
            return x, None
        x, m_cache = _scan_with_cache(mamba_fn, p_g["mamba"], x,
                                      cache["mamba"])
        return x, {"attn": a_cache, "mamba": m_cache}

    def _xlstm_group_apply(self, p_g, x, ctx, st, cache=None,
                           remat="none"):
        cfg = self.cfg
        ml_fn = partial(xlstm.mlstm_block_apply, ctx=ctx, cfg=cfg, st=st)
        if st.training:
            def inner(carry, p_l):
                y, _ = _remat(ml_fn, remat)(p_l, carry, cache=None)
                return y, None
            x, _ = jax.lax.scan(inner, x, p_g["mlstm"])
            x, _ = _remat(partial(xlstm.slstm_block_apply, ctx=ctx,
                                  cfg=cfg, st=st), remat)(
                p_g["slstm"], x, cache=None)
            return x, None
        x, ml_cache = _scan_with_cache(ml_fn, p_g["mlstm"], x,
                                       cache["mlstm"])
        x, sl_cache = xlstm.slstm_block_apply(p_g["slstm"], x, ctx, cfg,
                                              st, cache=cache["slstm"])
        return x, {"mlstm": ml_cache, "slstm": sl_cache}

    # ---------------- audio (enc-dec) ----------------
    def _encode(self, params, enc_embeds, ctx: ShardCtx):
        cfg = self.cfg
        x = tf.sp_scatter_embeds(enc_embeds.astype(ctx.compute_dtype), ctx)
        b, s_full = enc_embeds.shape[0], enc_embeds.shape[1]
        pe = sinusoidal_positions(jnp.arange(s_full), cfg.d_model)[None]
        x = x + tf.sp_scatter_embeds(
            jnp.broadcast_to(pe, (b, s_full, cfg.d_model)), ctx).astype(
                x.dtype)
        aux = Aux()

        def body(carry, p_l):
            fn = _remat(partial(encdec.enc_block_apply, aux=aux, ctx=ctx,
                                cfg=cfg),
                        cfg.plan.remat)
            return fn(p_l, carry), None
        x, _ = jax.lax.scan(lambda c, p: body(c, p), x,
                            params["enc_blocks"])
        x = rmsnorm(params["enc_norm"], x, cfg.norm_eps)
        return tp_copy(x, ctx)        # decoder cross-attn wants full seq

    def _run_decoder(self, params, x, aux, ctx, st, caches, memory):
        cfg = self.cfg
        remat = cfg.plan.remat if st.training else "none"
        fn = partial(encdec.dec_block_apply, aux=aux, ctx=ctx, cfg=cfg,
                     st=st, memory=memory)
        if st.training:
            def body(carry, p_l):
                y, _ = _remat(fn, remat)(p_l, carry, cache=None)
                return y, None
            x, _ = jax.lax.scan(body, x, params["dec_blocks"])
            return x, None
        x, caches = _scan_with_cache(fn, params["dec_blocks"], x, caches)
        return x, caches

    # ---------------- public entry points ----------------
    def loss(self, params, batch, ctx: ShardCtx):
        """Returns (loss_sum_local, n_tokens_local, moe_aux_loss)."""
        cfg = self.cfg
        st = StepState(mode="train")
        if self.family == "audio":
            memory = self._encode(params, batch["enc_embeds"], ctx)
            x, aux = self._embed_in(params, batch, ctx)
            x, _, moe_aux = (* self._run_decoder(params, x, aux, ctx, st,
                                                 None, memory), 0.0)
        else:
            x, aux = self._embed_in(params, batch, ctx)
            x, _, moe_aux = self._run_blocks(params, x, aux, ctx, st, None)
        loss_sum, n_tok = tf.lm_loss(params, x, batch["labels"], ctx, cfg)
        return loss_sum, n_tok, moe_aux

    def prefill(self, params, batch, ctx: ShardCtx, caches):
        """Returns (last-position vocab-parallel logits, filled caches)."""
        st = StepState(mode="prefill")
        if self.family == "audio":
            memory = self._encode(params, batch["enc_embeds"], ctx)
            x, aux = self._embed_in(params, batch, ctx)
            x, caches = self._run_decoder(params, x, aux, ctx, st, caches,
                                          memory)
        else:
            x, aux = self._embed_in(params, batch, ctx)
            x, caches, _ = self._run_blocks(params, x, aux, ctx, st, caches)
        logits = tf.lm_logits(params, x[:, -1:], ctx, self.cfg)
        return logits[:, 0], caches

    def decode(self, params, caches, batch, ctx: ShardCtx):
        """batch: tokens (B, 1), cur_len (B,).  Returns (logits, caches)."""
        cfg = self.cfg
        cur = batch["cur_len"]
        st = StepState(mode="decode", cur_len=cur)
        x = tf.embed_tokens(params, batch["tokens"], ctx, cfg)
        if cfg.rope == "none" and self.family == "audio":
            pe = sinusoidal_positions(cur[:, None], cfg.d_model)
            x = x + pe.astype(x.dtype)
        aux = Aux(positions=cur[:, None],
                  mrope_positions=batch.get("mrope_positions"))
        if self.family == "audio":
            x, caches = self._run_decoder(params, x, aux, ctx, st, caches,
                                          None)
        else:
            x, caches, _ = self._run_blocks(params, x, aux, ctx, st, caches)
        logits = tf.lm_logits(params, x, ctx, cfg)
        return logits[:, 0], caches

    # ---------------- caches ----------------
    def cache_shape(self, ctx: ShardCtx, batch_local: int,
                    cache_len_local: int, enc_len: int = 0):
        """(local ShapeDtypeStruct tree, spec tree) for the decode cache."""
        cfg = self.cfg
        fam = self.family
        batch_axes = None if ctx.cache_seq_axes else \
            (tuple(ctx.dp_axes) if ctx.dp_axes else None)
        seq_axes = tuple(ctx.cache_seq_axes) if ctx.cache_seq_axes else None
        tp_ax = TP_AXIS if ctx.tp > 1 else None

        def kv_specs():
            return {"k": P(batch_axes, seq_axes, tp_ax, None),
                    "v": P(batch_axes, seq_axes, tp_ax, None)}

        def stacked(tree, specs, n):
            sds = jax.tree.map(
                lambda s: jax.ShapeDtypeStruct((n,) + s.shape, s.dtype),
                tree, is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct))
            return sds, _prepend(specs)

        if fam in ("dense", "vlm", "moe"):
            sh = tf.attn_cache_shape(cfg, ctx, batch_local, cache_len_local)
            return stacked(sh, kv_specs(), cfg.n_layers)
        if fam == "hybrid":
            g = cfg.n_layers // cfg.ssm.attn_every
            a_sh = tf.attn_cache_shape(cfg, ctx, batch_local,
                                       cache_len_local)
            m_sh = mamba2.mamba_cache_shape(cfg, ctx, batch_local)
            m_spec = {"conv_x": P(batch_axes, None, tp_ax),
                      "conv_bc": P(batch_axes, None, None),
                      "ssd": P(batch_axes, tp_ax, None, None)}
            m_sds, m_spec = stacked(m_sh, m_spec, cfg.ssm.attn_every)
            grp_sds = {"attn": a_sh, "mamba": m_sds}
            grp_spec = {"attn": kv_specs(), "mamba": m_spec}
            return stacked(grp_sds, grp_spec, g)
        if fam == "ssm":
            per = cfg.ssm.slstm_every
            g = cfg.n_layers // per
            ml_sh = xlstm.mlstm_cache_shape(cfg, ctx, batch_local)
            ml_spec = {"conv": P(batch_axes, None, None),
                       "mlstm": (P(batch_axes, tp_ax, None, None),
                                 P(batch_axes, tp_ax, None),
                                 P(batch_axes, tp_ax))}
            sl_sh = xlstm.slstm_cache_shape(cfg, ctx, batch_local)
            st3 = P(batch_axes, None, None)
            sl_spec = {"conv": P(batch_axes, None, None),
                       "slstm": (st3, st3, st3, P(batch_axes, None))}
            ml_sds, ml_spec = stacked(ml_sh, ml_spec, per - 1)
            grp = {"mlstm": ml_sds, "slstm": sl_sh}
            grp_spec = {"mlstm": ml_spec, "slstm": sl_spec}
            return stacked(grp, grp_spec, g)
        if fam == "audio":
            sh = encdec.dec_cache_shape(cfg, ctx, batch_local,
                                        cache_len_local, enc_len)
            spec = {"self": kv_specs(),
                    "cross": (P(batch_axes, None, tp_ax, None),
                              P(batch_axes, None, tp_ax, None))}
            return stacked(sh, spec, cfg.n_layers)
        raise ValueError(fam)


# --------------------------------------------------------------------------
# registry-style helpers (configs/base.py hooks)
# --------------------------------------------------------------------------
def build(cfg: ArchConfig) -> Model:
    return Model(cfg)
