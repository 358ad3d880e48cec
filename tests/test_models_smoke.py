"""Per-arch smoke tests (deliverable f): every assigned architecture at a
REDUCED same-family config runs one forward/train step on CPU (shapes +
no-NaN), plus the prefill->decode == full-forward consistency theorem.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import base
from repro.models import Model, ShardCtx, registry

ARCHS = base.names()


def _zeros_cache(m, ctx, b, cap, enc_len=0):
    sds, _ = m.cache_shape(ctx, b, cap, enc_len=enc_len)
    return jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), sds,
                        is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct))


def _batches(cfg, B, S, key, total=64):
    toks = jax.random.randint(key, (B, total), 0, cfg.vocab)[:, :S + 1]
    train = {"tokens": toks[:, :S], "labels": toks[:, 1:S + 1]}
    prefill = {"tokens": toks[:, :S]}
    extra_dec = {}
    if cfg.family == "vlm":
        emb = jax.random.normal(jax.random.fold_in(key, 1),
                                (B, total, cfg.d_model))[:, :S]
        mp = jnp.broadcast_to(jnp.arange(S), (3, B, S))
        train = {"embeds": emb, "mrope_positions": mp,
                 "labels": toks[:, 1:S + 1]}
        prefill = {"embeds": emb, "mrope_positions": mp}
        extra_dec = {"mrope_positions": jnp.full((3, B, 1), S)}
    elif cfg.family == "audio":
        enc = jax.random.normal(jax.random.fold_in(key, 2),
                                (B, total, cfg.d_model))[:, :S]
        train = {"enc_embeds": enc, "tokens": toks[:, :S],
                 "labels": toks[:, 1:S + 1]}
        prefill = {"enc_embeds": enc, "tokens": toks[:, :S]}
    return toks, train, prefill, extra_dec


@pytest.mark.parametrize("arch", ARCHS)
def test_smoke_train_step(arch):
    cfg = base.reduced(base.get(arch))
    m = Model(cfg)
    ctx = ShardCtx()
    params, specs = m.init(jax.random.key(0), ctx)
    assert jax.tree.structure(params) == jax.tree.structure(
        specs, is_leaf=lambda s: not isinstance(s, (dict, tuple)))
    B, S = 2, 32
    _, train, _, _ = _batches(cfg, B, S, jax.random.key(1))

    def loss_fn(p):
        loss, ntok, aux, _ = m.loss(p, train, ctx)
        return loss / jnp.maximum(ntok, 1)

    loss, grads = jax.value_and_grad(loss_fn)(params)
    assert jnp.isfinite(loss), arch
    gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
                         for g in jax.tree.leaves(grads)))
    assert jnp.isfinite(gnorm) and gnorm > 0, arch
    # output-shape sanity via one logits call
    cache = _zeros_cache(m, ctx, B, S + 4,
                         enc_len=S if cfg.family == "audio" else 0)
    _, _, prefill, _ = _batches(cfg, B, S, jax.random.key(1))
    logits, _ = m.prefill(params, prefill, ctx, cache)
    assert logits.shape == (B, base.reduced(base.get(arch)).vocab) or \
        logits.shape[0] == B
    assert bool(jnp.isfinite(logits).all()), arch


@pytest.mark.parametrize("arch", ARCHS)
def test_smoke_decode_consistency(arch):
    cfg = base.reduced(base.get(arch))
    if cfg.moe.n_experts:
        # capacity drops differ between prefill/decode token counts — use a
        # capacity factor that guarantees no drops for the tiny batch
        cfg = dataclasses.replace(
            cfg, moe=dataclasses.replace(cfg.moe, capacity_factor=8.0))
    m = Model(cfg)
    ctx = ShardCtx()
    params, _ = m.init(jax.random.key(0), ctx)
    B, S, CAP = 2, 16, 24
    toks, _, prefill, extra_dec = _batches(cfg, B, S, jax.random.key(1))
    enc = S if cfg.family == "audio" else 0
    cache = _zeros_cache(m, ctx, B, CAP, enc_len=enc)
    _, cache = m.prefill(params, prefill, ctx, cache)
    ld, _ = m.decode(params, cache,
                     {"tokens": toks[:, S:S + 1],
                      "cur_len": jnp.full((B,), S, jnp.int32), **extra_dec},
                     ctx)
    # reference: full prefill over S+1 tokens — with the SAME frontend-stub
    # inputs (vlm: position S's embed must be the token embedding decode
    # sees; audio: the encoder memory stays at S frames)
    _, _, prefill2, _ = _batches(cfg, B, S + 1, jax.random.key(1))
    if cfg.family == "vlm":
        table = params["embed"]["table"]
        tok_emb = table[toks[:, S]][:, None].astype(
            prefill["embeds"].dtype)
        prefill2 = dict(prefill2)
        prefill2["embeds"] = jnp.concatenate(
            [prefill["embeds"], tok_emb], axis=1)
    elif cfg.family == "audio":
        prefill2 = dict(prefill2)
        prefill2["enc_embeds"] = prefill["enc_embeds"]
    cache2 = _zeros_cache(m, ctx, B, CAP, enc_len=enc)
    lr, _ = m.prefill(params, prefill2, ctx, cache2)
    np.testing.assert_allclose(np.asarray(ld), np.asarray(lr),
                               rtol=2e-2, atol=2e-2)


@pytest.mark.parametrize("arch", ARCHS)
def test_param_count_positive_and_moe_active(arch):
    cfg = base.get(arch)
    n = registry.param_count(cfg)
    assert n > 0
    if cfg.moe.n_experts:
        na = registry.param_count(cfg, active_only=True)
        assert na < n
    assert registry.model_flops(cfg, 1000) > 0


def test_full_param_counts_match_public_sizes():
    """Full configs land near their advertised parameter counts."""
    expect = {
        "tinyllama-1.1b": (1.0e9, 1.25e9),
        "granite-8b": (7.5e9, 9e9),
        "qwen3-32b": (30e9, 35e9),
        "mistral-nemo-12b": (11e9, 13.5e9),
        "arctic-480b": (430e9, 520e9),
        "qwen2-moe-a2.7b": (13e9, 16e9),     # total (not active)
        "zamba2-2.7b": (2.2e9, 3.2e9),
        "xlstm-350m": (0.3e9, 0.45e9),
        "qwen2-vl-7b": (6.5e9, 8.5e9),
        "seamless-m4t-medium": (0.55e9, 1.2e9),
        "moonlight-16b-a3b": (15.5e9, 16.5e9),
    }
    for name, (lo, hi) in expect.items():
        n = registry.param_count(base.get(name))
        assert lo <= n <= hi, (name, n)
    # MoE actives
    a = registry.param_count(base.get("qwen2-moe-a2.7b"), active_only=True)
    assert 2.0e9 <= a <= 3.5e9, a
    a = registry.param_count(base.get("arctic-480b"), active_only=True)
    assert 12e9 <= a <= 25e9, a
    a = registry.param_count(base.get("moonlight-16b-a3b"), active_only=True)
    assert 2.5e9 <= a <= 3.5e9, a


def test_ssd_and_mlstm_match_reference():
    from repro.models.mamba2 import ssd_chunked, ssd_reference
    from repro.models.xlstm import mlstm_chunked, mlstm_reference
    k = jax.random.split(jax.random.key(0), 8)
    b, l, h, p, n = 2, 37, 3, 8, 5
    x = jax.random.normal(k[0], (b, l, h, p))
    dt = jax.nn.softplus(jax.random.normal(k[1], (b, l, h)))
    A = -jnp.exp(jax.random.normal(k[2], (h,)))
    B = jax.random.normal(k[3], (b, l, n))
    C = jax.random.normal(k[4], (b, l, n))
    yr, hr = ssd_reference(x, dt, A, B, C)
    yc, hc = ssd_chunked(x, dt, A, B, C, chunk=8)
    np.testing.assert_allclose(yr, yc, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(hr, hc, rtol=1e-4, atol=1e-4)

    dk, dv = 6, 10
    q = jax.random.normal(k[5], (b, l, h, dk))
    kk = jax.random.normal(k[6], (b, l, h, dk))
    v = jax.random.normal(k[7], (b, l, h, dv))
    ig = jax.random.normal(k[0], (b, l, h))
    fg = jax.random.normal(k[1], (b, l, h)) + 2.0
    yr, cr = mlstm_reference(q, kk, v, ig, fg)
    yc, cc = mlstm_chunked(q, kk, v, ig, fg, chunk=8)
    np.testing.assert_allclose(yr, yc, rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(cr[0], cc[0], rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("fault", ["none", "truncated_tiles", "lost_row"])
def test_dropfree_counts_uncomputed_slots(fault, monkeypatch):
    """The drop-free layer's ``dropped_slots`` reads 0 on a sound dispatch
    and moves when the grouped matmul is given one tile too few, or when a
    slot's row in the buffer loses its token."""
    from repro.models import moe
    cfg = base.reduced(base.get("moonlight-16b-a3b"))
    ctx = ShardCtx(compute_dtype=jnp.float32)
    params, _ = moe.dropfree_init(jax.random.key(0), cfg, ctx)
    x = jax.random.normal(jax.random.key(1), (2, 16, cfg.d_model))
    plan = moe.dispatch_plan

    def planted(idx, mc, tm):
        slot_row, row_tok, row_slot, tiles = plan(idx, mc, tm)
        if fault == "truncated_tiles":
            tiles = tiles.at[jnp.argmax(tiles)].add(-1)
        elif fault == "lost_row":       # row 0 always holds a slot
            row_tok = row_tok.at[0].set(idx.shape[0])
        return slot_row, row_tok, row_slot, tiles
    monkeypatch.setattr(moe, "dispatch_plan", planted)
    _, stats = moe.dropfree_apply(params, x, ctx, cfg, tm=8)
    assert int(stats["held_slots"]) == 2 * 16 * cfg.moe.top_k
    if fault == "none":
        assert int(stats["dropped_slots"]) == 0
    else:
        assert int(stats["dropped_slots"]) > 0
