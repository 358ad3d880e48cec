"""The overlap subsystem's tier-1 contract (single device; the 4-device
bit-exactness oracle is tests/dist/dist_overlap_equivalence.py):

  * leaf-aligned layouts snap boundaries to leaf edges and round-trip
    ``to_buckets``/``from_buckets`` exactly;
  * ``build_layout`` orders buckets by backward completion (reverse layer
    order, tail last) and the readiness map is monotone;
  * ``check_supported`` rejects plans the segmented step cannot honor;
  * non-associative compressors degrade ``schedule="overlap"`` to serial
    (``effective_schedule`` — paper Table 3 made executable);
  * the segmented step trains (loss trajectory agrees with the classic
    scan-based step to fp tolerance — different XLA programs).
"""
import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import base
from repro.core import bucketing
from repro.core.aggregator import AggregatorConfig
from repro.data.pipeline import Pipeline
from repro.data.synthetic import DataConfig
from repro.launch.mesh import make_local_mesh
from repro.train import overlap
from repro.train import train_step as ts


def _overlap_cfg(**plan_overrides):
    cfg = base.reduced(base.get("tinyllama-1.1b"))
    overrides = dict(bucket_mb=1, zero1=False, overlap=True)
    overrides.update(plan_overrides)
    plan = dataclasses.replace(cfg.plan, **overrides)
    return dataclasses.replace(cfg, vocab=64, plan=plan)


# ------------------------------------------------------- leaf alignment
def test_leaf_aligned_roundtrip_exact():
    tree = {"a": jnp.arange(300, dtype=jnp.float32).reshape(10, 30),
            "b": jnp.arange(7, dtype=jnp.float32) + 1000.0,
            "c": jnp.arange(4096, dtype=jnp.float32).reshape(64, 64),
            "d": jnp.float32(3.0)}
    layout = bucketing.layout_for(tree, 0.001, leaf_aligned=True)
    assert layout.leaf_aligned and layout.n_buckets > 1
    # no leaf straddles a boundary: every bucket is whole leaves
    for b in range(layout.n_buckets):
        lo, hi = layout.bucket_leaves(b)
        assert sum(layout.leaf_sizes[lo:hi]) == layout.sizes[b]
    buckets = bucketing.to_buckets(tree, layout)
    back = bucketing.from_buckets(buckets, tree, layout)
    for k in tree:
        np.testing.assert_array_equal(np.asarray(back[k]),
                                      np.asarray(tree[k]))


def test_leaf_aligned_zero_size_trailing_leaf():
    """A zero-size trailing leaf still lands in a bucket that exists."""
    sizes, leaf_bucket = bucketing.leaf_aligned_sizes([5, 0], 5)
    assert max(leaf_bucket) < len(sizes)
    assert sum(sizes) == 5
    layout = bucketing.layout_from_leaf_sizes([5, 0], jnp.float32, 5 / 2**20)
    tree = {"a": jnp.arange(5.0), "b": jnp.zeros((0,))}
    back = bucketing.from_buckets(bucketing.to_buckets(tree, layout),
                                  tree, layout)
    np.testing.assert_array_equal(np.asarray(back["a"]),
                                  np.asarray(tree["a"]))
    assert back["b"].shape == (0,)


def test_leaf_aligned_big_leaf_never_split():
    """A leaf larger than the byte target still lands whole in exactly
    one bucket — the currently-open one, which then closes oversized
    (preceding small leaves ride along; the leaf is never split)."""
    sizes, leaf_bucket = bucketing.leaf_aligned_sizes([10, 5000, 10], 256)
    assert len(set(leaf_bucket)) == len(sizes)
    big_bucket = leaf_bucket[1]
    assert big_bucket == leaf_bucket[0]        # joins the open bucket
    assert sizes[big_bucket] == 10 + 5000      # closes oversized, whole
    assert sum(sizes) == 5020


# ------------------------------------------------------- layout / gating
def test_build_layout_reverse_completion_order():
    setup = ts.build(_overlap_cfg(), make_local_mesh())
    assert setup.overlap
    ov = overlap.build_layout(setup)
    # readiness is monotone in bucket index: earlier buckets complete at
    # earlier (deeper-layer) backward stages
    assert list(ov.bucket_ready) == sorted(ov.bucket_ready)
    assert ov.bucket_ready[-1] == ov.n_stages          # tail flushes last
    # every ordered leaf is covered exactly once by the stage ranges
    covered = []
    for s in range(ov.n_stages + 1):
        lo, hi = ov.stage_leaf_range(s)
        covered.extend(range(lo, hi))
    assert covered == list(range(len(ov.layout.leaf_sizes)))
    # the TrainState's bucket layout IS the overlap layout
    assert ts._bucket_layout(setup).sizes == ov.layout.sizes
    assert ts._bucket_layout(setup).leaf_aligned


def test_check_supported_gates():
    cfg = base.reduced(base.get("tinyllama-1.1b"))
    with pytest.raises(ValueError, match="FSDP"):
        overlap.check_supported(cfg, dataclasses.replace(
            cfg.plan, dp_mode="fsdp"))
    # build() enforces the gate when the plan asks for overlap
    with pytest.raises(ValueError, match="overlap unsupported"):
        ts.build(cfg, make_local_mesh(), dp_mode="fsdp", overlap=True)
    # the PR-3 restrictions are gone: ZeRO-1 and the enc-dec family ride
    # the segmented step now
    overlap.check_supported(cfg, dataclasses.replace(
        cfg.plan, dp_mode="ddp", zero1=True))
    audio = base.reduced(base.get("seamless-m4t-medium"))
    overlap.check_supported(audio, dataclasses.replace(
        audio.plan, dp_mode="ddp"))


def test_build_layout_encdec_two_stacks():
    """The audio family segments BOTH stacks: decoder stages first (their
    grads complete first), then encoder stages, then the tail — and the
    readiness map stays monotone across the stack boundary."""
    cfg = base.reduced(base.get("seamless-m4t-medium"))
    cfg = dataclasses.replace(cfg, vocab=64, plan=dataclasses.replace(
        cfg.plan, bucket_mb=1, overlap=True))
    setup = ts.build(cfg, make_local_mesh())
    ov = overlap.build_layout(setup)
    assert [s.key for s in ov.stacks] == ["dec_blocks", "enc_blocks"]
    dec, enc = ov.stacks
    assert ov.n_stages == dec.n_layers + enc.n_layers
    assert enc.stage0 == dec.n_layers
    assert list(ov.bucket_ready) == sorted(ov.bucket_ready)
    assert ov.bucket_ready[-1] == ov.n_stages
    covered = []
    for s in range(ov.n_stages + 1):
        lo, hi = ov.stage_leaf_range(s)
        covered.extend(range(lo, hi))
    assert covered == list(range(len(ov.layout.leaf_sizes)))
    # ordered-leaf round trip through the two-stack mapping is exact
    grads_like = ts._grads_like_local(setup)
    vals = jax.tree.map(
        lambda s: jnp.arange(np.prod(s.shape), dtype=jnp.float32)
        .reshape(s.shape), grads_like)
    back = overlap._unordered_tree(ov, overlap._ordered_leaves(ov, vals),
                                   vals)
    for a, b in zip(jax.tree.leaves(vals), jax.tree.leaves(back)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_effective_schedule_nonassociative_falls_back():
    setup = ts.build(_overlap_cfg(), make_local_mesh())
    base_cfg = AggregatorConfig(compressor="signsgd",
                                compress_axes=("data",), raw_axes=())
    setup.agg_cfg = base_cfg
    assert overlap.effective_schedule(setup) == "serial"
    setup.agg_cfg = dataclasses.replace(base_cfg, compressor="randomk")
    assert overlap.effective_schedule(setup) == "overlap"
    setup.agg_cfg = dataclasses.replace(base_cfg, compressor="none")
    assert overlap.effective_schedule(setup) == "overlap"


# ------------------------------------------------------- the step itself
def test_segmented_step_matches_classic_scan_step():
    mesh = make_local_mesh()
    data = Pipeline(DataConfig(vocab=64, seq_len=32, global_batch=4),
                    prefetch=0)
    it = iter(data)
    batches = [next(it) for _ in range(3)]

    def run(cfg):
        setup = ts.build(cfg, mesh)
        state = ts.init_state(setup, jax.random.key(0))
        step = ts.make_step(setup)(batches[0])
        losses = []
        for b in batches:
            state, m = step(state, b, jnp.float32(1e-3))
            losses.append(float(m["loss"]))
        return losses

    seg = run(_overlap_cfg())
    classic = run(dataclasses.replace(
        _overlap_cfg(), plan=dataclasses.replace(_overlap_cfg().plan,
                                                 overlap=False)))
    np.testing.assert_allclose(seg, classic, rtol=5e-4)
    assert seg[-1] < seg[0]        # it trains


@pytest.mark.parametrize("overlap_on", [True, False],
                         ids=["overlapped", "classic"])
@pytest.mark.parametrize("zero1", [True, False], ids=["zero1", "adamw"])
def test_step_hlo_carries_layer_scopes(overlap_on, zero1):
    """The step's lowered HLO names its layers in ``op_name`` metadata:
    ``attention`` (forward, recompute and backward), ``grad_sync`` (the
    bucket aggregation) and ``optimizer`` (the update) — what a profiler
    trace of the compiled step attributes device time by."""
    mesh = make_local_mesh()
    batch = next(Pipeline(DataConfig(vocab=64, seq_len=32, global_batch=4),
                          prefetch=0))
    cfg = _overlap_cfg(overlap=overlap_on, zero1=zero1,
                       compression="powersgd", compress_axes="all")
    setup = ts.build(cfg, mesh)
    state = jax.eval_shape(lambda k: ts.init_state(setup, k),
                           jax.random.key(0))
    step = ts.make_step(setup)(batch)
    text = step.lower(state, batch, jnp.float32(1e-3)).as_text(
        dialect="hlo", debug_info=True)
    parts = {c for name in re.findall(r'op_name="([^"]*)"', text)
             for c in re.split(r"[/;()]", name)}
    assert {"attention", "grad_sync", "optimizer"} <= parts


@pytest.mark.parametrize("positions", ["default", "explicit"])
@pytest.mark.parametrize("overlap_on", [True, False],
                         ids=["overlapped", "classic"])
def test_step_attention_path_follows_positions(overlap_on, positions,
                                               monkeypatch):
    """Tracing the train step with the kernels forced: a batch without
    ``positions`` runs every attention on the Pallas flash kernel, one with
    explicit positions every attention on the jnp loop
    (``ops.ATTENTION_PATHS``)."""
    from repro.kernels import ops
    monkeypatch.setattr(ops, "FORCE", "pallas")
    mesh = make_local_mesh()
    batch = next(Pipeline(DataConfig(vocab=64, seq_len=128, global_batch=2),
                          prefetch=0))
    assert "positions" not in batch
    if positions == "explicit":
        batch = dict(batch, positions=jnp.broadcast_to(
            jnp.arange(128, dtype=jnp.int32), (2, 128)))
    # head size 64, which the kernel tiles
    cfg = dataclasses.replace(_overlap_cfg(overlap=overlap_on), head_dim=64)
    setup = ts.build(cfg, mesh)
    state = jax.eval_shape(lambda k: ts.init_state(setup, k),
                           jax.random.key(0))
    before = ops.ATTENTION_PATHS.copy()
    ts.make_step(setup)(batch).lower(state, batch, jnp.float32(1e-3))
    traced = ops.ATTENTION_PATHS - before
    taken, other = ("pallas", "jnp") if positions == "default" \
        else ("jnp", "pallas")
    assert traced[taken] >= 1 and traced[other] == 0, traced


# ------------------------------------------------------- ZeRO-1
def test_zero1_owner_plan_covers_buckets():
    from repro.core import bucketing
    sizes, _ = bucketing.leaf_aligned_sizes([7, 9, 3, 14, 2, 5], 10)
    layout = bucketing.layout_from_leaf_sizes([7, 9, 3, 14, 2, 5],
                                              jnp.float32, 10 / 2**20)
    plan = bucketing.owner_plan(layout, 4)
    assert len(plan.owners) == layout.n_buckets
    # contiguous non-decreasing ownership, every element owned once
    assert list(plan.owners) == sorted(plan.owners)
    assert sum(plan.lengths) == layout.n_elements
    for b in range(layout.n_buckets):
        r = plan.owners[b]
        assert plan.starts[r] <= plan.bucket_offsets[b]
        assert plan.bucket_offsets[b] + layout.sizes[b] \
            <= plan.starts[r] + plan.lengths[r]
    # single-owner buckets expose exactly one gathered-space piece whose
    # offset matches the historic param_offset layout
    for b in range(layout.n_buckets):
        assert plan.pieces[b] == ((plan.param_offset(b), layout.sizes[b]),)
    # more ranks than buckets: the largest buckets are SPLIT so every
    # rank still owns a contiguous sub-bucket (no degenerate trailing
    # ranks), and split buckets reassemble from their per-owner pieces
    n_ranks = layout.n_buckets + 3
    plan2 = bucketing.owner_plan(layout, n_ranks)
    assert sum(plan2.lengths) == layout.n_elements
    assert all(ln > 0 for ln in plan2.lengths)          # full coverage
    assert plan2.cap < layout.n_elements                # state shrinks
    # the real contract: slicing each bucket's pieces out of the
    # (p·cap) gathered-shard space reconstructs the flat bucket exactly
    # (zero1_apply's reassembly, simulated on the host)
    flat = np.arange(layout.n_elements)
    gathered = np.concatenate([
        np.pad(flat[plan2.starts[r]:plan2.starts[r] + plan2.lengths[r]],
               (0, plan2.cap - plan2.lengths[r]), constant_values=-1)
        for r in range(n_ranks)])
    for b in range(layout.n_buckets):
        got = np.concatenate([gathered[off:off + ln]
                              for off, ln in plan2.pieces[b]])
        lo = plan2.bucket_offsets[b]
        np.testing.assert_array_equal(got, flat[lo:lo + layout.sizes[b]])
    # ownership stays contiguous in flat element space
    assert sorted(plan2.starts)[0] == 0
    assert max(plan2.starts[r] + plan2.lengths[r]
               for r in range(n_ranks)) == layout.n_elements


def test_zero1_matches_replicated_adamw():
    """The owner-sharded flat AdamW is the SAME update replicated AdamW
    computes: with bf16 working params on both sides, step 1 is
    bit-identical (identical grads, identical fp32 math), and the
    trajectories stay fp-close after (the only divergence source is
    ZeRO-1's persistent fp32 master vs replicated AdamW's bf16 param
    round-trip)."""
    mesh = make_local_mesh()
    data = Pipeline(DataConfig(vocab=64, seq_len=32, global_batch=4),
                    prefetch=0)
    it = iter(data)
    batches = [next(it) for _ in range(3)]

    def run(zero1):
        cfg = _overlap_cfg(zero1=zero1, param_dtype="bfloat16")
        setup = ts.build(cfg, mesh)
        assert setup.zero1 == zero1
        state = ts.init_state(setup, jax.random.key(0))
        step = overlap.make_step(setup, "serial")(batches[0])
        losses, params1 = [], None
        for i, b in enumerate(batches):
            state, m = step(state, b, jnp.float32(1e-3))
            losses.append(float(m["loss"]))
            if i == 0:
                params1 = jax.device_get(state["params"])
        return losses, params1

    l_z, p_z = run(True)
    l_r, p_r = run(False)
    for a, b in zip(jax.tree.leaves(p_z), jax.tree.leaves(p_r)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg="zero1 vs adamw step 1")
    np.testing.assert_allclose(l_z, l_r, rtol=2e-2)


def test_zero1_master_fill_donates_old_shard():
    """Filling the fp32 master passes ``m`` and ``v`` through in their
    donated buffers (no second copy of the fp32 moments is live), and the
    master holds the params' values."""
    cfg = _overlap_cfg(zero1=True, param_dtype="bfloat16")
    setup = ts.build(cfg, make_local_mesh())
    state = ts.init_state(setup, jax.random.key(0))
    old = state["opt"]["shard"]
    layout = ts._bucket_layout(setup)
    state = ts._fill_zero1_master(setup, state, layout)
    assert old["m"].is_deleted() and old["v"].is_deleted()
    flat = np.concatenate([np.asarray(p, np.float32).ravel()
                           for p in jax.tree.leaves(state["params"])])
    master = np.asarray(state["opt"]["shard"]["master"]).ravel()
    np.testing.assert_array_equal(np.sort(master[:flat.size]),
                                  np.sort(flat))


# ------------------------------------------------------- accumulation
def test_accum_flushes_each_bucket_once(monkeypatch):
    """accum > 1 must issue each bucket's encode->reduce->decode exactly
    ONCE per step (on the final microbatch) — not once per microbatch."""
    from repro.core import aggregator as agg_mod
    from repro.core.aggregator import AggregatorConfig

    setup = ts.build(_overlap_cfg(), make_local_mesh())
    # 1-device mesh drops the collective axes at build time; restore a
    # size-1 axis so the flush path (do_agg) actually runs
    setup.agg_cfg = AggregatorConfig(compressor="none", compress_axes=(),
                                     raw_axes=("data",))
    ov = overlap.build_layout(setup)
    data = Pipeline(DataConfig(vocab=64, seq_len=32, global_batch=4),
                    prefetch=0)
    batch = next(iter(data))
    calls = []
    orig = agg_mod.GradAggregator.aggregate_one

    def counting(self, bucket, st):
        calls.append(1)
        return orig(self, bucket, st)

    monkeypatch.setattr(agg_mod.GradAggregator, "aggregate_one", counting)
    state = ts.init_state(setup, jax.random.key(0))
    step = overlap.make_step(setup, "overlap", accum=2)(batch)
    step(state, batch, jnp.float32(1e-3))       # traces once
    assert len(calls) == ov.layout.n_buckets, \
        (len(calls), ov.layout.n_buckets)


def test_accum_segmented_matches_classic_accum():
    """Segmented accum (per-microbatch backward, flush-on-final) agrees
    with the classic scan-over-microbatches step to fp tolerance."""
    mesh = make_local_mesh()
    data = Pipeline(DataConfig(vocab=64, seq_len=32, global_batch=4),
                    prefetch=0)
    it = iter(data)
    batches = [next(it) for _ in range(3)]

    def run(cfg, accum):
        setup = ts.build(cfg, mesh)
        state = ts.init_state(setup, jax.random.key(0))
        step = ts.make_step(setup, accum=accum)(batches[0])
        losses = []
        for b in batches:
            state, m = step(state, b, jnp.float32(1e-3))
            losses.append(float(m["loss"]))
        return losses

    seg = run(_overlap_cfg(), 2)
    classic = run(dataclasses.replace(
        _overlap_cfg(), plan=dataclasses.replace(_overlap_cfg().plan,
                                                 overlap=False)), 2)
    np.testing.assert_allclose(seg, classic, rtol=5e-4)
    assert seg[-1] < seg[0]


# ------------------------------------------------------- enc-dec
def test_encdec_segmented_matches_classic():
    """The two-stack segmented backward (decoder, then encoder) trains
    the audio family and agrees with the classic scan-based step."""
    mesh = make_local_mesh()
    cfg = base.reduced(base.get("seamless-m4t-medium"))
    cfg = dataclasses.replace(cfg, vocab=64, plan=dataclasses.replace(
        cfg.plan, bucket_mb=1, overlap=True, zero1=False))
    key = jax.random.key(1)
    B, S = 4, 32
    toks = jax.random.randint(key, (B, S + 1), 0, 64)
    enc = jax.random.normal(jax.random.fold_in(key, 2), (B, S, cfg.d_model))
    batch = {"enc_embeds": enc, "tokens": toks[:, :S],
             "labels": toks[:, 1:]}

    def run(c):
        setup = ts.build(c, mesh)
        state = ts.init_state(setup, jax.random.key(0))
        step = ts.make_step(setup)(batch)
        losses = []
        for _ in range(3):
            state, m = step(state, batch, jnp.float32(1e-3))
            losses.append(float(m["loss"]))
        return losses

    seg = run(cfg)
    classic = run(dataclasses.replace(
        cfg, plan=dataclasses.replace(cfg.plan, overlap=False)))
    np.testing.assert_allclose(seg, classic, rtol=1e-3)
    assert seg[-1] < seg[0]


# ------------------------------------------- moe: a dense stack first
def _moonlight_cfg(**plan_overrides):
    cfg = base.reduced(base.get("moonlight-16b-a3b"))
    overrides = dict(bucket_mb=0.05, overlap=True, zero1=True)
    overrides.update(plan_overrides)
    return dataclasses.replace(cfg, vocab=64, n_layers=3, plan=dataclasses
                               .replace(cfg.plan, **overrides))


def test_build_layout_moe_two_stacks():
    """A leading dense stack: the MoE stack's stages first (their grads
    complete first), then the dense stack's, then the tail; the 4-D
    expert leaves are whole leaves of the layout, and ZeRO-1's owner
    plan covers every bucket."""
    setup = ts.build(_moonlight_cfg(), make_local_mesh())
    ov = overlap.build_layout(setup)
    assert [s.key for s in ov.stacks] == ["moe_blocks", "dense_blocks"]
    moe_seg, dense_seg = ov.stacks
    assert (moe_seg.n_layers, dense_seg.n_layers) == (2, 1)
    assert dense_seg.stage0 == moe_seg.n_layers
    assert list(ov.bucket_ready) == sorted(ov.bucket_ready)
    grads_like = ts._grads_like_local(setup)
    experts = grads_like["moe_blocks"]["moe"]["experts"]["gate"]
    assert experts.ndim == 4
    assert int(np.prod(experts.shape[1:])) in ov.layout.leaf_sizes
    vals = jax.tree.map(
        lambda s: jnp.arange(np.prod(s.shape), dtype=jnp.float32)
        .reshape(s.shape), grads_like)
    back = overlap._unordered_tree(ov, overlap._ordered_leaves(ov, vals),
                                   vals)
    for a, b in zip(jax.tree.leaves(vals), jax.tree.leaves(back)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    plan = ts._zero1_plan(setup)
    assert sum(plan.lengths) == ov.layout.n_elements


@pytest.mark.parametrize("accum", [1, 2])
def test_moe_two_stacks_segmented_matches_classic(accum):
    """The overlapped ZeRO-1 step over a dense stack and a drop-free MoE
    stack trains and agrees with the classic scan-based step; the
    drop-free layers' counters come out in the metrics, no slot left
    uncomputed."""
    mesh = make_local_mesh()
    data = Pipeline(DataConfig(vocab=64, seq_len=32, global_batch=4),
                    prefetch=0)
    it = iter(data)
    batches = [next(it) for _ in range(3)]

    def run(cfg):
        setup = ts.build(cfg, mesh)
        state = ts.init_state(setup, jax.random.key(0))
        step = ts.make_step(setup, accum=accum)(batches[0])
        losses = []
        for b in batches:
            state, m = step(state, b, jnp.float32(1e-3))
            losses.append(float(m["loss"]))
        return losses, m

    seg, m = run(_moonlight_cfg())
    classic, mc = run(_moonlight_cfg(overlap=False))
    np.testing.assert_allclose(seg, classic, rtol=2e-3)
    assert seg[-1] < seg[0]
    for metrics in (m, mc):
        assert int(metrics["moe"]["dropped_slots"]) == 0
        # 2 MoE layers x 4 x 32 tokens x top-2 of 4 experts, all held
        assert int(metrics["moe"]["held_slots"]) == 2 * 4 * 32 * 2
        assert float(metrics["moe"]["load_max_over_mean"]) >= 1.0
