"""Overlapped bucketed gradient aggregation — the paper's *optimized*
syncSGD baseline (§2.2, Fig 2), executable.

The analytic model has always credited the baseline with overlap
(``sync_sgd_time = max(compute, overlapped comm) + tail``), but the classic
train step computes the full backward and only then issues every bucket
collective — the serial strawman the paper warns against.  This module
closes that model-vs-execution gap:

  1. The model's block structure is split into per-block ``jax.vjp``
     stages (forward saves one vjp closure per block; backward replays
     them in reverse layer order).  Enc-dec (audio) models segment BOTH
     stacks: decoder blocks first (their grads complete first), then the
     encoder blocks once the accumulated memory cotangent is available.
  2. Gradients are bucketed with the *leaf-aligned* layout
     (``bucketing.layout_for(..., leaf_aligned=True)`` over leaves ordered
     by backward completion: last block first, block 0 next-to-last, then
     the embed/head/shared tail).  Because bucket boundaries snap to leaf
     edges, a bucket is fully determined the moment its layers' grads are
     final.
  3. Under ``schedule="overlap"`` each completed bucket's
     ``encode -> reduce -> decode`` is issued immediately, *between* block
     backward stages, pinned in program order with
     ``jax.lax.optimization_barrier`` so XLA cannot sink the collectives
     behind the remaining backward; the latency-hiding-scheduler flags
     (:data:`TPU_OVERLAP_FLAGS`) then hide each collective under the next
     stage's compute.  ``schedule="serial"`` runs the *same* segmented
     backward and the *same* per-bucket aggregation but issues every
     collective after the full backward — the two schedules are
     bit-identical in results and differ only in issue order, which is
     what makes serial-vs-overlapped step time a pure exposed-comm
     measurement.

Which buckets may pipeline is decided by the resolved **comm plan**
(``repro.parallel.commplan`` / docs/comm_api.md): ring plans (allreduce,
reduce_scatter_allgather, hierarchical) overlap; ``gather_all`` — the
forced resolution for non-associative schemes
(signsgd/qsgd/terngrad/mstopk) — needs every peer's tensors before *any*
decode can complete and its wire cost grows with p, so pipelining
buckets buys nothing (paper Table 3 / Takeaway 1); and
``reduce_to_owner_broadcast`` folds the whole exchange into the sharded
update (no per-bucket collective at all — the backward runs "raw").
``make_step(schedule="overlap")`` therefore degrades those plans to the
serial schedule; ``effective_schedule(setup)`` reports the degradation —
the paper's claim, made executable.

Supported workload matrix (see docs/overlap.md for the decision table):

  * every model family — dense/vlm/moe (``params["blocks"]``), moe with a
    leading dense stack (``params["dense_blocks"]`` then
    ``params["moe_blocks"]``, segmented in backward order: the MoE stack,
    then the dense stack), hybrid/ssm (``params["groups"]``), and the
    enc-dec audio family (``params["dec_blocks"]`` +
    ``params["enc_blocks"]``);
  * ``zero1=True`` — optimizer state owner-sharded along the leaf-aligned
    bucket boundaries (``train_step.zero1_apply``: flat AdamW on the
    owned shard, params all-gathered through the Payload reduce
    machinery);
  * ``accum > 1`` — the segmented backward of microbatches 0..N-2
    accumulates into ordered leaf views; each bucket's
    encode→reduce→decode is issued exactly once, fused into the FINAL
    microbatch's backward in reverse layer order.

Still unsupported: FSDP (there is no DDP bucket exchange to interleave —
the per-layer all_gather AD transpose already overlaps).
``check_supported`` raises with the reason.
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core import aggregator as agg_mod
from repro.core import bucketing
from repro.models import moe as moe_mod

#: libtpu flags that let the latency-hiding scheduler overlap the pinned
#: collectives with backward compute.  They belong in ``LIBTPU_INIT_ARGS``,
#: which only libtpu reads: in ``XLA_FLAGS`` the CPU client (which JAX also
#: creates on a TPU host) aborts on the unknown ``--xla_tpu_*`` names.
#: See :func:`enable_overlap_flags`.
TPU_OVERLAP_FLAGS = (
    "--xla_tpu_enable_latency_hiding_scheduler=true "
    "--xla_tpu_enable_async_collective_fusion=true "
    "--xla_tpu_enable_async_collective_fusion_fuse_all_reduce=true")

#: families whose training stacks the step segments
_FAMILIES = ("dense", "vlm", "moe", "hybrid", "ssm", "audio")


def _stack_keys(arch) -> tuple[str, ...]:
    """The scanned param collections of a model, in BACKWARD-COMPLETION
    order (the reverse of ``models.model.stack_keys``: enc-dec decoder
    grads are final before the encoder's, MoE blocks' before the leading
    dense blocks')."""
    from repro.models.model import stack_keys
    return stack_keys(arch)[::-1]


def enable_overlap_flags() -> None:
    """Append :data:`TPU_OVERLAP_FLAGS` to ``LIBTPU_INIT_ARGS``
    (idempotent).  Call before jax initializes its backends: libtpu reads
    the variable once, when it loads.  No host detection is needed —
    where libtpu never loads, nothing reads the variable."""
    import os
    cur = os.environ.get("LIBTPU_INIT_ARGS", "")
    if "latency_hiding_scheduler" not in cur:
        os.environ["LIBTPU_INIT_ARGS"] = \
            (cur + " " + TPU_OVERLAP_FLAGS).strip()


# --------------------------------------------------------------------------
# support gating
# --------------------------------------------------------------------------
def supports(arch, plan) -> tuple[bool, str]:
    """Can (arch, plan) run the segmented overlapped step?"""
    if plan.dp_mode != "ddp":
        return False, ("overlap interleaves DDP bucket collectives; FSDP's "
                       "per-layer reduce-scatter already overlaps via the "
                       "all_gather AD transpose")
    if arch.family not in _FAMILIES:
        return False, f"family {arch.family!r} has no scanned block " \
                      "stack to segment"
    return True, ""


def check_supported(arch, plan) -> None:
    ok, why = supports(arch, plan)
    if not ok:
        raise ValueError(f"plan.overlap unsupported for {arch.name}: {why}")


def effective_schedule(setup) -> str:
    """The schedule ``make_step(schedule="overlap")`` actually runs,
    resolved from the comm plan (docs/comm_api.md): only ring plans whose
    per-bucket collective returns a complete result
    (``commplan.OVERLAPPABLE``: allreduce / reduce_scatter_allgather /
    hierarchical) can pipeline into the backward.  ``gather_all`` — the
    forced resolution for non-associative payloads (paper Table 3) —
    needs every peer before any decode, so it degrades to ``"serial"``
    (every bucket's gather issued after the full backward); the
    integrated ``reduce_to_owner_broadcast`` path has NO per-bucket
    collective at all (the exchange is folded into the sharded update),
    which reports as ``"raw"``."""
    from repro.parallel import commplan as cp
    if setup.rtob:
        return "raw"
    if not setup.agg_cfg.compress_axes and not setup.agg_cfg.raw_axes:
        return "overlap"      # no collectives at all; schedule is moot
    if setup.agg_cfg.compressor == "none":
        assoc = True
    else:
        assoc = setup.agg_cfg.build().associative
    resolved = setup.agg_cfg.comm.resolve(assoc)
    return "overlap" if resolved.kind in cp.OVERLAPPABLE else "serial"


# --------------------------------------------------------------------------
# layout: leaves ordered by backward completion
# --------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class StackSeg:
    """One scanned block collection's slice of the ordered-leaf space."""
    key: str                      # params key of the collection
    n_layers: int                 # backward stages contributed
    n_leaves: int                 # leaves per layer slice
    stage0: int                   # first stage index of this stack
    leaf0: int                    # first ordered-leaf index of this stack

    @property
    def leaf_end(self) -> int:
        return self.leaf0 + self.n_layers * self.n_leaves


@dataclasses.dataclass(frozen=True)
class OverlapLayout:
    """Leaf-aligned bucket layout over backward-completion-ordered leaves.

    Leaf order: for each stack (decoder before encoder for enc-dec), that
    stack's last block's leaves first, block 0 next-to-last; then the tail
    (everything outside the stacked collections: embed, final norm, lm
    head, hybrid shared block, enc-dec ``enc_norm``).  Stage ``s`` is one
    block's backward; stage ``n_stages`` is the tail (those grads are only
    final once the whole backward — including the embedding lookup's
    transpose — has run).
    """
    layout: bucketing.BucketLayout
    stacks: tuple[StackSeg, ...]
    n_stages: int                  # total block stages (tail == n_stages)
    bucket_ready: tuple[int, ...]  # bucket -> stage after which complete

    def stage_leaf_range(self, s: int) -> tuple[int, int]:
        """Half-open ordered-leaf range written by stage ``s``."""
        for seg in self.stacks:
            if s < seg.stage0 + seg.n_layers:
                lo = seg.leaf0 + (s - seg.stage0) * seg.n_leaves
                return lo, lo + seg.n_leaves
        return self.stacks[-1].leaf_end, len(self.layout.leaf_sizes)

    def buckets_ready_at(self, s: int) -> list[int]:
        return [b for b, r in enumerate(self.bucket_ready) if r == s]


def _split_params(params: dict, keys: tuple[str, ...]):
    rest = {k: v for k, v in params.items() if k not in keys}
    return rest, [params[k] for k in keys]


def build_layout(setup) -> OverlapLayout:
    """The overlap layout for a TrainSetup (shapes from the same local
    gradient tree the classic byte-based layout uses).  Memoized on the
    setup (keyed by the bucket byte target, the one input tests mutate
    after build) — zero1 state construction, make_step, and checkpoint
    shape derivation all need it and would otherwise re-walk the
    abstract param tree each time."""
    import numpy as np

    from repro.train import train_step as ts
    cached = getattr(setup, "_overlap_layout_cache", None)
    if cached is not None and cached[0] == setup.agg_cfg.bucket_mb:
        return cached[1]
    check_supported(setup.arch, setup.arch.plan)
    grads_like = ts._grads_like_local(setup)
    keys = _stack_keys(setup.arch)
    rest, stacks_p = _split_params(grads_like, keys)
    segs: list[StackSeg] = []
    leaf_sizes: list[int] = []
    stage0 = leaf0 = 0
    for key, stacked in zip(keys, stacks_p):
        leaves = jax.tree_util.tree_leaves(stacked)
        n_layers = leaves[0].shape[0]
        per_layer = [int(np.prod(l.shape[1:])) for l in leaves]
        segs.append(StackSeg(key, n_layers, len(per_layer), stage0, leaf0))
        leaf_sizes += per_layer * n_layers
        stage0 += n_layers
        leaf0 += len(per_layer) * n_layers
    leaf_sizes += [int(np.prod(l.shape))
                   for l in jax.tree_util.tree_leaves(rest)]
    n_stages = stage0
    dtype = bucketing._majority_dtype(jax.tree_util.tree_leaves(grads_like))
    layout = bucketing.layout_from_leaf_sizes(leaf_sizes, dtype,
                                              setup.agg_cfg.bucket_mb)

    def stage_of(leaf_idx: int) -> int:
        for seg in segs:
            if leaf_idx < seg.leaf_end:
                return seg.stage0 + (leaf_idx - seg.leaf0) // seg.n_leaves
        return n_stages

    ready = []
    for b in range(layout.n_buckets):
        lo, hi = layout.bucket_leaves(b)
        ready.append(stage_of(hi - 1))
    ov = OverlapLayout(layout, tuple(segs), n_stages, tuple(ready))
    setup._overlap_layout_cache = (setup.agg_cfg.bucket_mb, ov)
    return ov


# --------------------------------------------------------------------------
# the flush engine (shared by the family backwards)
# --------------------------------------------------------------------------
class _Flush:
    """Ordered-leaf store + per-bucket flush for one segmented backward.

    ``stage(s, d_params, carry)`` records stage ``s``'s leaf cotangents —
    adding the accumulated earlier-microbatch gradient and applying the
    1/accum scale when this is the final microbatch — and, under the
    overlap schedule, issues each completed bucket's
    ``encode -> reduce -> decode`` pinned (``optimization_barrier``)
    before ``carry`` feeds the next stage.  ``tail(rest_leaves, like)``
    stores the tail, flushes the remaining buckets (ALL buckets under the
    serial schedule), and reassembles the gradient pytree.
    """

    def __init__(self, setup, ov: OverlapLayout, agg_states, schedule: str,
                 acc=None, inv_accum=None):
        self.setup, self.ov, self.schedule = setup, ov, schedule
        self.acc, self.inv = acc, inv_accum
        self.aggregator = agg_mod.GradAggregator(setup.agg_cfg)
        self.do_agg = schedule != "raw" and \
            bool(setup.agg_cfg.compress_axes or setup.agg_cfg.raw_axes)
        self.squeezed = tuple(jax.tree.map(lambda x: x[0], st)
                              for st in agg_states)
        layout = ov.layout
        self.leaf_vals: list = [None] * len(layout.leaf_sizes)
        self.out_buckets: list = [None] * layout.n_buckets
        self.new_states: list = list(self.squeezed) if self.squeezed \
            else [() for _ in range(layout.n_buckets)]

    def _store(self, s: int, leaves: list):
        lo, hi = self.ov.stage_leaf_range(s)
        assert len(leaves) == hi - lo, (s, len(leaves), lo, hi)
        if self.acc is not None:
            leaves = [(v.astype(jnp.float32) + self.acc[lo + i]) * self.inv
                      for i, v in enumerate(leaves)]
        self.leaf_vals[lo:hi] = leaves

    @jax.named_scope("grad_sync")
    def _flush(self, b: int):
        layout = self.ov.layout
        lo, hi = layout.bucket_leaves(b)
        parts = [v.reshape(-1).astype(layout.dtype)
                 for v in self.leaf_vals[lo:hi]]
        bucket = parts[0] if len(parts) == 1 else jnp.concatenate(parts)
        st = self.squeezed[b] if self.squeezed else ()
        self.out_buckets[b], self.new_states[b] = \
            self.aggregator.aggregate_one(bucket, st)
        return self.out_buckets[b]

    def stage(self, s: int, d_params, carry):
        self._store(s, jax.tree_util.tree_leaves(d_params))
        if self.do_agg and self.schedule == "overlap":
            ready = self.ov.buckets_ready_at(s)
            issued = [self._flush(b) for b in ready]
            if issued:
                # pin program order: the collectives are issued before the
                # next block's backward; the latency-hiding scheduler then
                # overlaps them with that compute.
                carry, *issued = jax.lax.optimization_barrier(
                    (carry, *issued))
                for b, ob in zip(ready, issued):
                    self.out_buckets[b] = ob
        return carry

    def tail(self, rest_leaves: list, params_like):
        ov, layout = self.ov, self.ov.layout
        self._store(ov.n_stages, rest_leaves)
        if self.do_agg:
            if self.schedule == "overlap":
                for b in ov.buckets_ready_at(ov.n_stages):
                    self._flush(b)
            else:
                for b in range(layout.n_buckets):
                    self._flush(b)
            with jax.named_scope("grad_sync"):
                self.leaf_vals = bucketing.buckets_to_leaves(
                    self.out_buckets, self.leaf_vals, layout)
        return _unordered_tree(ov, self.leaf_vals, params_like)

    def new_agg(self, agg_states):
        if self.squeezed:
            return tuple(jax.tree.map(lambda x: x[None], ns)
                         for ns in self.new_states)
        return agg_states


# --------------------------------------------------------------------------
# the segmented step
# --------------------------------------------------------------------------
def _make_aux(batch):
    """Batch-only position info (mirrors Model._embed_in's Aux)."""
    from repro.models.transformer import Aux
    return Aux(positions=batch.get("positions"),
               mrope_positions=batch.get("mrope_positions"))


def _stage_fns(setup, batch, xent_chunk: int):
    """(f_in, blocks, f_out, has_aux, has_shared) — ``blocks`` maps each
    stack's key to its block: the exact remat-wrapped body the serial scan
    runs (so the segmented backward reproduces the scanned backward's
    math), returning ``(out, counters)``.  ``out`` is ``(y, aux loss)``
    where ``has_aux`` (the capacity MoE layer), else ``y``; the counters
    (the drop-free MoE layer's; empty elsewhere) are not differentiated."""
    from repro.models import transformer as tf
    from repro.models.model import _remat
    from repro.models.transformer import StepState

    model, ctx, cfg = setup.model, setup.ctx, setup.arch
    st = StepState(mode="train")
    remat = cfg.plan.remat
    aux = _make_aux(batch)
    fam = model.family
    has_aux = fam == "moe" and not cfg.moe.drop_free
    has_shared = fam == "hybrid"

    def f_in(p_rest):
        if "embeds" in batch:
            return tf.sp_scatter_embeds(
                batch["embeds"].astype(ctx.compute_dtype), ctx)
        return tf.embed_tokens(p_rest, batch["tokens"], ctx, cfg)

    def f_out(p_rest, x):
        loss_sum, n_tok = tf.lm_loss(p_rest, x, batch["labels"], ctx, cfg,
                                     xent_chunk)
        return loss_sum, n_tok

    def dense_block(p_l, x):
        fn = partial(tf.dense_block_apply, aux=aux, ctx=ctx, cfg=cfg, st=st)
        y, _ = _remat(fn, remat)(p_l, x, cache=None)
        return y, {}

    if fam in ("dense", "vlm"):
        block = dense_block
    elif fam == "moe":
        fn = partial(moe_mod.moe_block_apply, aux=aux, ctx=ctx, cfg=cfg,
                     st=st)

        def block(p_l, x):
            y, _, al, stats = _remat(fn, remat)(p_l, x, cache=None)
            return ((y, al) if has_aux else y), stats
        if cfg.first_k_dense:
            return f_in, {"dense_blocks": dense_block, "moe_blocks": block}, \
                f_out, has_aux, has_shared
    elif fam == "hybrid":
        def block(p_g, shared, x):
            fn = partial(model._zamba_group_apply, shared=shared, aux=aux,
                         ctx=ctx, st=st, remat=remat)
            y, _ = _remat(fn, remat)(p_g, x, cache=None)
            return y, {}
    elif fam == "ssm":
        def block(p_g, x):
            fn = partial(model._xlstm_group_apply, ctx=ctx, st=st,
                         remat=remat)
            y, _ = _remat(fn, remat)(p_g, x, cache=None)
            return y, {}
    else:  # pragma: no cover — check_supported gates
        raise ValueError(fam)

    return f_in, {_stack_keys(cfg)[0]: block}, f_out, has_aux, has_shared


def _encdec_fns(setup, batch, xent_chunk: int):
    """The enc-dec stage closures, mirroring ``Model._encode`` /
    ``Model._embed_in`` / ``Model._run_decoder`` math exactly (same remat
    wrapping), so the segmented backward reproduces the scanned one."""
    from repro.models import encdec, transformer as tf
    from repro.models.layers import rmsnorm, sinusoidal_positions, tp_copy
    from repro.models.model import _remat
    from repro.models.transformer import Aux, StepState

    ctx, cfg = setup.ctx, setup.arch
    st = StepState(mode="train")
    remat = cfg.plan.remat
    aux = _make_aux(batch)

    def f_enc_in():
        emb = batch["enc_embeds"]
        x = tf.sp_scatter_embeds(emb.astype(ctx.compute_dtype), ctx)
        b, s_full = emb.shape[0], emb.shape[1]
        pe = sinusoidal_positions(jnp.arange(s_full), cfg.d_model)[None]
        x = x + tf.sp_scatter_embeds(
            jnp.broadcast_to(pe, (b, s_full, cfg.d_model)), ctx).astype(
                x.dtype)
        return x, Aux()

    x0, enc_aux = f_enc_in()

    def enc_block(p_l, x):
        fn = partial(encdec.enc_block_apply, aux=enc_aux, ctx=ctx, cfg=cfg)
        return _remat(fn, remat)(p_l, x)

    def f_mem(p_rest, x):
        return tp_copy(rmsnorm(p_rest["enc_norm"], x, cfg.norm_eps), ctx)

    def f_dec_in(p_rest):
        x = tf.embed_tokens(p_rest, batch["tokens"], ctx, cfg)
        if cfg.rope == "none":
            b, s_full = batch["tokens"].shape
            pe = sinusoidal_positions(jnp.arange(s_full), cfg.d_model)[None]
            pe = tf.sp_scatter_embeds(
                jnp.broadcast_to(pe, (b, s_full, cfg.d_model)), ctx)
            x = x + pe.astype(x.dtype)
        return x

    def dec_block(p_l, x, memory):
        fn = partial(encdec.dec_block_apply, aux=aux, ctx=ctx, cfg=cfg,
                     st=st)
        y, _ = _remat(fn, remat)(p_l, x, cache=None, memory=memory)
        return y

    def f_out(p_rest, x):
        loss_sum, n_tok = tf.lm_loss(p_rest, x, batch["labels"], ctx, cfg,
                                     xent_chunk)
        return loss_sum, n_tok

    return x0, enc_block, f_mem, f_dec_in, dec_block, f_out


def _backward_seed(setup, loss_sum, ntok):
    n_glob = jax.lax.psum(ntok, setup.dp_axes) if setup.dp_axes else ntok
    scale_axes = setup.p_dp // setup.p_fsdp
    return (scale_axes / n_glob.astype(jnp.float32)).astype(loss_sum.dtype)


def _backward_stack(setup, ov: OverlapLayout, params, batch, flush: _Flush,
                    xent_chunk: int):
    """Decoder-only families: forward saves one vjp closure per block
    (the stacks in forward order), backward replays them in reverse layer
    order, stack by stack in backward order, flushing ready buckets."""
    from repro.train.train_step import MOE_AUX_COEF

    f_in, blocks, f_out, has_aux, has_shared = _stage_fns(setup, batch,
                                                          xent_chunk)
    fwd_segs = ov.stacks[::-1]
    p_rest, stacks_p = _split_params(params, tuple(g.key for g in fwd_segs))

    # ---- forward: one vjp closure per block stage --------------------
    x, vjp_in = jax.vjp(f_in, p_rest)
    block_vjps = {}
    aux_vals = []
    stats = {}
    for seg, stacked in zip(fwd_segs, stacks_p):
        block = blocks[seg.key]
        block_vjps[seg.key] = []
        for l in range(seg.n_layers):
            p_l = jax.tree.map(lambda t, _l=l: t[_l], stacked)
            args = (p_l, p_rest["shared"], x) if has_shared else (p_l, x)
            out, vjp_l, c = jax.vjp(block, *args, has_aux=True)
            stats = moe_mod.merge_stats(stats, c)
            if has_aux:
                x, al = out
                aux_vals.append(al)
            else:
                x = out
            block_vjps[seg.key].append(vjp_l)
    loss_sum, vjp_out, ntok = jax.vjp(f_out, p_rest, x, has_aux=True)

    # ---- backward seeds ---------------------------------------------
    seed = _backward_seed(setup, loss_sum, ntok)
    L = len(aux_vals)
    moe_aux = (sum(aux_vals) / L) if has_aux else jnp.float32(0.0)
    aux_seed = jnp.asarray(MOE_AUX_COEF / (L * setup.p_fsdp),
                           aux_vals[0].dtype) if has_aux else None

    # ---- backward: reverse layer order, flushing ready buckets -------
    d_rest_out, d_x = vjp_out(seed)
    shared_acc = None
    for seg in ov.stacks:
        for s in range(seg.n_layers):
            l = seg.n_layers - 1 - s
            cot = (d_x, aux_seed) if has_aux else d_x
            if has_shared:
                d_pl, d_sh, d_x = block_vjps[seg.key][l](cot)
                shared_acc = d_sh if shared_acc is None else \
                    jax.tree.map(jnp.add, shared_acc, d_sh)
            else:
                d_pl, d_x = block_vjps[seg.key][l](cot)
            d_x = flush.stage(seg.stage0 + s, d_pl, d_x)

    d_rest_in, = vjp_in(d_x)
    grads_rest = jax.tree.map(jnp.add, d_rest_out, d_rest_in)
    if shared_acc is not None:
        grads_rest = {**grads_rest,
                      "shared": jax.tree.map(jnp.add, grads_rest["shared"],
                                             shared_acc)}
    grads = flush.tail(jax.tree_util.tree_leaves(grads_rest), params)
    return grads, loss_sum, ntok, moe_aux, stats


def _backward_encdec(setup, ov: OverlapLayout, params, batch, flush: _Flush,
                     xent_chunk: int):
    """Enc-dec (audio) family: decoder stages first (accumulating the
    memory cotangent across every block's cross-attention), then the
    encoder-norm transpose, then the encoder stages."""
    x0, enc_block, f_mem, f_dec_in, dec_block, f_out = _encdec_fns(
        setup, batch, xent_chunk)
    dec_seg, enc_seg = ov.stacks
    p_rest, (p_dec, p_enc) = _split_params(params,
                                           (dec_seg.key, enc_seg.key))

    # ---- forward ------------------------------------------------------
    x_e = x0
    enc_vjps = []
    for l in range(enc_seg.n_layers):
        p_l = jax.tree.map(lambda t, _l=l: t[_l], p_enc)
        x_e, v = jax.vjp(enc_block, p_l, x_e)
        enc_vjps.append(v)
    memory, vjp_mem = jax.vjp(f_mem, p_rest, x_e)
    x, vjp_in = jax.vjp(f_dec_in, p_rest)
    dec_vjps = []
    for l in range(dec_seg.n_layers):
        p_l = jax.tree.map(lambda t, _l=l: t[_l], p_dec)
        x, v = jax.vjp(dec_block, p_l, x, memory)
        dec_vjps.append(v)
    loss_sum, vjp_out, ntok = jax.vjp(f_out, p_rest, x, has_aux=True)

    # ---- backward -----------------------------------------------------
    seed = _backward_seed(setup, loss_sum, ntok)
    d_rest_out, d_x = vjp_out(seed)
    d_mem = None
    for s in range(dec_seg.n_layers):
        l = dec_seg.n_layers - 1 - s
        d_pl, d_x, d_m = dec_vjps[l](d_x)
        d_mem = d_m if d_mem is None else jnp.add(d_mem, d_m)
        d_x, d_mem = flush.stage(s, d_pl, (d_x, d_mem))
    d_rest_in, = vjp_in(d_x)
    d_rest_mem, d_xe = vjp_mem(d_mem)
    for s in range(enc_seg.n_layers):
        l = enc_seg.n_layers - 1 - s
        d_pel, d_xe = enc_vjps[l](d_xe)
        d_xe = flush.stage(enc_seg.stage0 + s, d_pel, d_xe)
    grads_rest = jax.tree.map(lambda a, b, c: a + b + c,
                              d_rest_out, d_rest_in, d_rest_mem)
    grads = flush.tail(jax.tree_util.tree_leaves(grads_rest), params)
    return grads, loss_sum, ntok, jnp.float32(0.0), {}


def _segmented_backward(setup, ov: OverlapLayout, params, batch,
                        agg_states, schedule: str, xent_chunk: int,
                        acc=None, inv_accum=None):
    """Forward (per-block vjp closures) + reverse-order backward with
    per-bucket aggregation.  Returns (grads, new_agg_states, loss_sum,
    ntok, moe_aux, moe_stats).  ``schedule="overlap"`` flushes each completed bucket
    between backward stages, barrier-pinned; ``"serial"`` flushes all
    buckets after the full backward.  Values are bit-identical.
    ``schedule="raw"`` skips aggregation entirely and returns the local
    unaggregated gradients (microbatches 0..N-2 of an accumulated step,
    and the unfused strawman's first dispatch).

    ``acc`` (ordered fp32 leaf list) carries the summed gradients of the
    earlier microbatches; with it, every stored leaf becomes
    ``(current + acc) * inv_accum`` BEFORE any bucket is flushed — so
    under ``accum > 1`` each bucket's encode→reduce→decode runs exactly
    once, on the final microbatch, still in reverse layer order."""
    flush = _Flush(setup, ov, agg_states, schedule, acc, inv_accum)
    if setup.arch.family == "audio":
        grads, loss_sum, ntok, moe_aux, stats = _backward_encdec(
            setup, ov, params, batch, flush, xent_chunk)
    else:
        grads, loss_sum, ntok, moe_aux, stats = _backward_stack(
            setup, ov, params, batch, flush, xent_chunk)
    return grads, flush.new_agg(agg_states), loss_sum, ntok, moe_aux, stats


def make_step(setup, schedule: str = "overlap", accum: int = 1,
              xent_chunk: int = 1024):
    """Segmented-backward step factory; same contract as
    ``train_step.make_step`` (returns ``jitted(batch_example)``).

    ``schedule="overlap"`` silently degrades to ``"serial"`` for
    non-associative compressors (see :func:`effective_schedule`).
    ``accum > 1`` splits the batch into microbatches, accumulates into
    ordered leaf views, and flushes each bucket once on the final
    microbatch.  ``setup.zero1`` routes the update through the
    owner-sharded flat AdamW (``train_step.zero1_apply``).
    """
    from repro.train import train_step as ts

    assert schedule in ("overlap", "serial"), schedule
    assert accum >= 1
    check_supported(setup.arch, setup.arch.plan)
    assert not setup.fsdp_axes
    ov = build_layout(setup)
    if schedule == "overlap":
        schedule = effective_schedule(setup)
    if setup.rtob:
        # reduce_to_owner_broadcast: there is no per-bucket gradient
        # collective to schedule — the update's owner-aligned ring
        # reduce-scatter (zero1_apply) is the only gradient exchange, so
        # the segmented backward runs "raw" under either requested
        # schedule (serial == overlap trivially bit-identical).
        schedule = "raw"
    update_fn = ts.make_update_fn(setup, ov.layout, ov)

    def backward(state, params, batch):
        if accum == 1:
            return _segmented_backward(setup, ov, params, batch,
                                       state["agg"], schedule, xent_chunk)
        b_local = jax.tree_util.tree_leaves(batch)[0].shape[0]
        if b_local % accum:
            raise ValueError(
                f"accum={accum} does not divide the per-device batch "
                f"{b_local} (global batch / DP size); pick batch sizes "
                f"with global_batch % (p_dp * accum) == 0")
        mbs = jax.tree.map(
            lambda x: x.reshape((accum, x.shape[0] // accum) + x.shape[1:]),
            batch)
        acc = None
        loss_sum = jnp.float32(0.0)
        ntok = None
        aux = jnp.float32(0.0)
        stats = {}
        for m in range(accum - 1):
            mb = jax.tree.map(lambda x, _m=m: x[_m], mbs)
            g_m, _, l_m, n_m, a_m, s_m = _segmented_backward(
                setup, ov, params, mb, (), "raw", xent_chunk)
            ordered = [v.astype(jnp.float32)
                       for v in _ordered_leaves(ov, g_m)]
            acc = ordered if acc is None else \
                [a + b for a, b in zip(acc, ordered)]
            loss_sum = loss_sum + l_m
            ntok = n_m if ntok is None else ntok + n_m
            aux = aux + a_m
            stats = moe_mod.merge_stats(stats, s_m)
        mb = jax.tree.map(lambda x: x[accum - 1], mbs)
        grads, new_agg, l_m, n_m, a_m, s_m = _segmented_backward(
            setup, ov, params, mb, state["agg"], schedule, xent_chunk,
            acc=acc, inv_accum=1.0 / accum)
        return (grads, new_agg, loss_sum + l_m, ntok + n_m,
                (aux + a_m) / accum, moe_mod.merge_stats(stats, s_m))

    def step_fn(state, batch, lr):
        params = state["params"]
        grads, new_agg, loss_sum, ntok, aux, stats = backward(state, params,
                                                              batch)
        new_params, new_opt, gnorm = update_fn(params, grads,
                                               state["opt"], lr)
        metrics = ts.train_metrics(setup, loss_sum, ntok, gnorm, aux, stats)
        new_state = {"step": state["step"] + 1, "params": new_params,
                     "opt": new_opt, "agg": new_agg}
        return new_state, metrics

    batch_spec_fn = ts.make_batch_specs(setup)

    def jitted(batch_example):
        from repro.parallel.compat import shard_map
        bspecs = batch_spec_fn(batch_example)
        f = shard_map(step_fn, setup.mesh,
                      in_specs=(setup.state_specs, bspecs, P()),
                      out_specs=(setup.state_specs, P()))
        return jax.jit(f, donate_argnums=(0,))

    return jitted


# --------------------------------------------------------------------------
# the no-overlap strawman: backward and aggregation in separate dispatches
# --------------------------------------------------------------------------
def make_unfused_step(setup, xent_chunk: int = 1024):
    """The paper-Fig-2 strawman, executable: dispatch 1 runs the backward
    and materializes every device's raw gradients; dispatch 2 then issues
    all bucket collectives and the update.  No overlap is *possible*
    across the dispatch boundary — this is what "syncSGD without overlap"
    costs, measured.  Returns ``build(batch_example) -> step`` like
    :func:`make_step`."""
    from repro.parallel.compat import shard_map
    from repro.train import train_step as ts

    check_supported(setup.arch, setup.arch.plan)
    ov = build_layout(setup)
    all_ax = setup.all_axes
    dev = lambda spec_leaf: P(all_ax)  # noqa: E731
    update_fn = ts.make_update_fn(setup, ov.layout, ov)

    def backward_fn(params, batch):
        grads, _, loss_sum, ntok, aux, stats = _segmented_backward(
            setup, ov, params, batch, (), "raw", xent_chunk)
        # leading device dim: raw grads differ per device pre-aggregation
        return jax.tree.map(lambda g: g[None],
                            (grads, loss_sum, ntok, aux, stats))

    def agg_update_fn(state, grads_dev, loss_dev, ntok_dev, aux_dev,
                      stats_dev, lr):
        params = state["params"]
        grads, loss_sum, ntok, aux, stats = jax.tree.map(
            lambda g: g[0], (grads_dev, loss_dev, ntok_dev, aux_dev,
                             stats_dev))
        aggregator = agg_mod.GradAggregator(setup.agg_cfg)
        if setup.rtob:
            # no bucket aggregation: the update's reduce-scatter is the
            # only gradient collective
            new_agg = state["agg"]
        elif setup.agg_cfg.compress_axes or setup.agg_cfg.raw_axes:
            squeezed = tuple(jax.tree.map(lambda x: x[0], st)
                             for st in state["agg"])
            with jax.named_scope("grad_sync"):
                ordered = _ordered_leaves(ov, grads)
                buckets = bucketing.leaves_to_buckets(ordered, ov.layout)
                outs, news = aggregator.aggregate_bucket_list(buckets,
                                                              squeezed)
                ordered = bucketing.buckets_to_leaves(outs, ordered,
                                                      ov.layout)
                grads = _unordered_tree(ov, ordered, grads)
            new_agg = tuple(jax.tree.map(lambda x: x[None], ns)
                            for ns in news) if squeezed else state["agg"]
        else:
            new_agg = state["agg"]
        new_params, new_opt, gnorm = update_fn(params, grads,
                                               state["opt"], lr)
        metrics = ts.train_metrics(setup, loss_sum, ntok, gnorm, aux, stats)
        return {"step": state["step"] + 1, "params": new_params,
                "opt": new_opt, "agg": new_agg}, metrics

    batch_spec_fn = ts.make_batch_specs(setup)

    def build(batch_example):
        bspecs = batch_spec_fn(batch_example)
        gspecs = jax.tree.map(dev, setup.param_specs,
                              is_leaf=lambda s: isinstance(s, P))
        f1 = jax.jit(shard_map(
            backward_fn, setup.mesh,
            in_specs=(setup.state_specs["params"], bspecs),
            out_specs=(gspecs, P(all_ax), P(all_ax), P(all_ax),
                       P(all_ax))))
        f2 = jax.jit(shard_map(
            agg_update_fn, setup.mesh,
            in_specs=(setup.state_specs, gspecs, P(all_ax), P(all_ax),
                      P(all_ax), P(all_ax), P()),
            out_specs=(setup.state_specs, P())),
            donate_argnums=(0, 1))

        def step(state, batch, lr):
            return f2(state, *f1(state["params"], batch), lr)

        return step

    return build


def _ordered_leaves(ov: OverlapLayout, tree) -> list:
    """Gradient pytree -> backward-completion-ordered leaf list (the leaf
    order :func:`build_layout` built the bucket layout over)."""
    rest, stacks = _split_params(tree, tuple(seg.key for seg in ov.stacks))
    out = []
    for seg, stacked in zip(ov.stacks, stacks):
        stacked_leaves = jax.tree_util.tree_leaves(stacked)
        for s in range(seg.n_layers):
            l = seg.n_layers - 1 - s
            out.extend(t[l] for t in stacked_leaves)
    out.extend(jax.tree_util.tree_leaves(rest))
    return out


def _unordered_tree(ov: OverlapLayout, ordered: list, tree_like):
    """Inverse of :func:`_ordered_leaves` (structure from ``tree_like``)."""
    rest, stacks = _split_params(tree_like,
                                 tuple(seg.key for seg in ov.stacks))
    out = {}
    for seg, stacked in zip(ov.stacks, stacks):
        nb, L = seg.n_leaves, seg.n_layers
        new_leaves = []
        for i in range(nb):
            per_layer = [ordered[seg.leaf0 + (L - 1 - l) * nb + i]
                         for l in range(L)]
            new_leaves.append(jnp.stack(per_layer))
        out[seg.key] = jax.tree_util.tree_unflatten(
            jax.tree_util.tree_structure(stacked), new_leaves)
    tail0 = ov.stacks[-1].leaf_end
    new_rest = jax.tree_util.tree_unflatten(
        jax.tree_util.tree_structure(rest), ordered[tail0:])
    return {**new_rest, **out}
