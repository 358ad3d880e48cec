"""The distributed train step: one shard_map over the whole mesh wrapping
loss -> backward -> gradient aggregation (the paper's subject) -> update.

Two DP modes (DESIGN.md §4):

  ddp   params replicated over DP.  Gradients are raveled into 25 MB buckets
        and each bucket is aggregated by the configured compressor across
        the DP axes — the JAX analogue of PyTorch-DDP + comm-hook that the
        paper benchmarks.  ``plan.overlap=True`` swaps in the segmented
        backward with reverse-order bucket collectives fused between
        stages (repro.train.overlap — the paper's optimized baseline);
        ``accum > 1`` accumulates microbatches (overlap mode flushes each
        bucket once, on the final microbatch).  Optional ZeRO-1: the
        optimizer state is owner-sharded ALONG bucket boundaries
        (``bucketing.owner_plan``: each bucket has one owner rank — or,
        with fewer buckets than ranks, the largest buckets split so
        every rank owns a contiguous sub-bucket; a rank's shard is one
        contiguous slice of the flat bucket space); ``zero1_apply`` runs
        flat AdamW on the owned fp32 master and all-gathers the updated
        working-dtype params through the Payload reduce machinery.  One
        zero1 implementation serves the classic, segmented, and unfused
        steps.  WHICH collective moves each payload is the declarative
        ``CommPlan`` (``plan.comm``, docs/comm_api.md); under
        ``comm="reduce_to_owner_broadcast"`` (zero1 + uncompressed) the
        gradient all-reduce disappears entirely — the update's
        owner-aligned ring reduce-scatter plus the param broadcast are
        the step's only exchanges, half the bytes.
  fsdp  params sharded over ctx.fsdp_axes (+ TP); the per-layer all_gather's
        AD transpose IS the ZeRO-3 reduce-scatter.  With HSDP (fsdp over
        "data" only) the surviving pod-axis reduction runs the compressor on
        gradient *shards* — the paper's method applied exactly where the
        bandwidth is scarce.

Loss scaling makes every path produce the same global-mean gradient:
``S = Πdp / (N_tokens_global · Πfsdp)`` so that post-transpose sums over the
fsdp axes and the final pmean over the compress axes land on
``Σ ∂(local)/∂w / N_global``.  Replicated-over-fsdp leaves (norm scales
etc.) get an explicit psum over the fsdp axes instead.

Compressor state (error feedback, PowerSGD warm starts) is carried with a
leading device dim — local (1, ...), global (n_devices, ...) sharded over
every mesh axis — which is correct for any mixture of per-device and
replicated state.
"""
from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Any, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ArchConfig
from repro.core import aggregator as agg_mod
from repro.core import bucketing
from repro.models import Model
from repro.models.layers import ShardCtx
from repro.train import optimizer as opt_mod

from repro.parallel.compat import shard_map

MOE_AUX_COEF = 0.01


def _prod(xs) -> int:
    out = 1
    for x in xs:
        out *= x
    return out


@dataclasses.dataclass
class TrainSetup:
    """Everything needed to init/run/lower distributed training for one
    (arch × mesh) combination."""
    arch: ArchConfig
    mesh: Mesh
    model: Model
    ctx: ShardCtx
    dp_axes: tuple[str, ...]
    fsdp_axes: tuple[str, ...]
    agg_cfg: agg_mod.AggregatorConfig
    opt_cfg: opt_mod.OptConfig
    param_specs: Any = None
    state_specs: Any = None          # full TrainState spec tree
    zero1: bool = False
    # segmented backward + reverse-order bucketed aggregation fused into
    # the backward pass (repro.train.overlap) — the paper's optimized
    # baseline, executable.  Implies the leaf-aligned bucket layout.
    overlap: bool = False

    # ------------------------------------------------------------------
    @property
    def comm(self):
        """The collective schedule (CommPlan) the aggregation runs —
        docs/comm_api.md; carried by the aggregator config."""
        return self.agg_cfg.comm

    @property
    def rtob(self) -> bool:
        """Is the integrated reduce-to-owner/broadcast path active?  Then
        gradients are NOT bucket-aggregated: the update's owner-aligned
        ring reduce-scatter is the only gradient collective, and the
        updated params ride the broadcast (gather) leg — half the
        exchanged bytes of all-reduce + gather."""
        return (self.zero1 and self.agg_cfg.compressor == "none"
                and self.comm.kind == "reduce_to_owner_broadcast")

    @property
    def all_axes(self) -> tuple[str, ...]:
        return tuple(self.mesh.axis_names)

    def axis_size(self, name: str) -> int:
        return dict(zip(self.mesh.axis_names, self.mesh.devices.shape))[name]

    @property
    def p_dp(self) -> int:
        return _prod(self.axis_size(a) for a in self.dp_axes)

    @property
    def p_fsdp(self) -> int:
        return _prod(self.axis_size(a) for a in self.fsdp_axes)

    def sharding(self, spec):
        return jax.tree.map(lambda s: NamedSharding(self.mesh, s), spec,
                            is_leaf=lambda s: isinstance(s, P))


def build(arch: ArchConfig, mesh: Mesh,
          opt_cfg: Optional[opt_mod.OptConfig] = None,
          **plan_overrides) -> TrainSetup:
    plan = dataclasses.replace(arch.plan, **plan_overrides) \
        if plan_overrides else arch.plan
    arch = dataclasses.replace(arch, plan=plan)
    names = tuple(mesh.axis_names)
    sizes = dict(zip(names, mesh.devices.shape))
    tp = sizes.get("model", 1)
    dp_axes = tuple(a for a in ("pod", "data") if a in names)
    multi_pod = "pod" in names and sizes.get("pod", 1) > 1
    if plan.dp_mode == "fsdp":
        fsdp_axes = tuple(a for a in dp_axes
                          if a != "pod" or plan.fsdp_shard_pods)
        fsdp_axes = tuple(a for a in fsdp_axes if sizes.get(a, 1) > 1)
    else:
        fsdp_axes = ()
    zero1 = plan.dp_mode == "ddp" and plan.zero1
    if plan.comm == "reduce_to_owner_broadcast" and not zero1:
        from repro.parallel import commplan as cp
        raise cp.CommPlanError(
            "comm='reduce_to_owner_broadcast' needs an owner-sharded "
            "update: dp_mode='ddp' with zero1=True")
    if plan.overlap:
        from repro.train import overlap as overlap_mod
        overlap_mod.check_supported(arch, plan)
    ctx = ShardCtx(
        tp=tp,
        dp_axes=dp_axes,
        fsdp_axes=fsdp_axes,
        seq_parallel=bool(plan.seq_parallel and tp > 1),
        # ZeRO-1: replicated params are bf16 working copies; the fp32
        # master lives in the DP-sharded optimizer state (mixed-precision
        # ZeRO-1 — what makes the 2.7B DDP archs fit 16 GB/chip).
        # plan.param_dtype="bfloat16" = T5X-style bf16 weights + fp32
        # optimizer stats (arctic-480b).
        param_dtype=jnp.bfloat16
        if (zero1 or plan.param_dtype == "bfloat16") else jnp.float32,
        gather_quant=None if plan.gather_quant == "none"
        else plan.gather_quant,
    )
    agg_cfg = agg_mod.from_plan(plan, multi_pod=multi_pod)
    if plan.dp_mode == "fsdp":
        # compressor applies only to DP axes NOT folded into FSDP
        comp = tuple(a for a in agg_cfg.compress_axes if a not in fsdp_axes
                     and sizes.get(a, 1) > 1)
        agg_cfg = dataclasses.replace(agg_cfg, compress_axes=comp,
                                      raw_axes=())
    else:
        # compress_axes="all" keeps a one-device DP axis: the compressor's
        # encode and decode then run although the collective is a no-op
        # (one chip runs the compressed step this way)
        forced = plan.compress_axes == "all"
        agg_cfg = dataclasses.replace(
            agg_cfg,
            compress_axes=tuple(a for a in agg_cfg.compress_axes
                                if sizes.get(a, 1) > 1
                                or (forced and a in names)),
            raw_axes=tuple(a for a in agg_cfg.raw_axes
                           if sizes.get(a, 1) > 1))
    # fail at build time (not mid-step on a live pod) when a hierarchical
    # plan's intra stage would be empty over the actual reduction axes
    agg_cfg.comm.validate_axes(agg_cfg.raw_axes + agg_cfg.compress_axes)
    ocfg = opt_cfg or opt_mod.OptConfig(name=plan.optimizer)
    setup = TrainSetup(arch=arch, mesh=mesh, model=Model(arch), ctx=ctx,
                       dp_axes=dp_axes, fsdp_axes=fsdp_axes,
                       agg_cfg=agg_cfg, opt_cfg=ocfg,
                       zero1=zero1, overlap=plan.overlap)
    _, specs = setup.model.abstract_init(ctx)
    setup.param_specs = specs
    setup.state_specs = _state_specs(setup)
    return setup


# --------------------------------------------------------------------------
# state construction
# --------------------------------------------------------------------------
def localize(sds_tree, spec_tree, mesh: Mesh):
    """Global ShapeDtypeStructs + specs -> per-device (shard_map local)
    shapes.  Inverse of models.model.globalize."""
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))

    def f(sds, spec):
        shape = list(sds.shape)
        if spec is not None:
            for i, entry in enumerate(spec):
                if entry is None or i >= len(shape):
                    continue
                axes = entry if isinstance(entry, tuple) else (entry,)
                for ax in axes:
                    assert shape[i] % sizes.get(ax, 1) == 0, \
                        (sds.shape, spec, ax)
                    shape[i] //= sizes.get(ax, 1)
        return jax.ShapeDtypeStruct(tuple(shape), sds.dtype)
    return jax.tree.map(f, sds_tree, spec_tree,
                        is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct))


def _grads_like_local(setup: TrainSetup):
    """LOCAL (per-device) gradient shapes — what bucketing sees inside
    shard_map (TP/FSDP shards; grads carry the param dtype)."""
    shapes, _ = setup.model.abstract_init(setup.ctx)
    return localize(shapes, setup.param_specs, setup.mesh)


def _bucket_layout(setup: TrainSetup):
    """The bucket layout the compressor state / ZeRO-1 shards key off.
    Overlap mode uses the leaf-aligned layout over backward-completion-
    ordered leaves (repro.train.overlap); classic mode keeps the
    byte-based flat split.  Memoized on the setup (keyed by bucket_mb,
    like overlap.build_layout) — state specs, init, zero1 plan, and
    checkpoint shapes all read it."""
    if setup.overlap:
        from repro.train import overlap as overlap_mod
        return overlap_mod.build_layout(setup).layout
    cached = getattr(setup, "_layout_cache", None)
    if cached is not None and cached[0] == setup.agg_cfg.bucket_mb:
        return cached[1]
    layout = bucketing.layout_for(_grads_like_local(setup),
                                  setup.agg_cfg.bucket_mb)
    setup._layout_cache = (setup.agg_cfg.bucket_mb, layout)
    return layout


def _zero1_plan(setup: TrainSetup) -> bucketing.OwnerPlan:
    """The bucket -> owner-rank sharding of the optimizer state (ZeRO-1:
    shard boundaries are the bucket boundaries of ``_bucket_layout``)."""
    return bucketing.owner_plan(_bucket_layout(setup), setup.p_dp)


def _zero1_bucket_fns(setup: TrainSetup, layout, ov=None):
    """(``buckets_of(tree)``, ``unbuckets(buckets, like)``) in the
    layout's leaf order — backward-completion order under overlap, plain
    pytree order otherwise.  ``ov`` lets the overlap step pass its own
    ``OverlapLayout`` instead of rebuilding it."""
    if setup.overlap:
        from repro.train import overlap as overlap_mod
        if ov is None:
            ov = overlap_mod.build_layout(setup)

        def buckets_of(tree):
            return bucketing.leaves_to_buckets(
                overlap_mod._ordered_leaves(ov, tree), layout)

        def unbuckets(buckets, like):
            ordered_like = overlap_mod._ordered_leaves(ov, like)
            leaves = bucketing.buckets_to_leaves(buckets, ordered_like,
                                                 layout)
            return overlap_mod._unordered_tree(ov, leaves, like)
    else:
        def buckets_of(tree):
            return bucketing.to_buckets(tree, layout)

        def unbuckets(buckets, like):
            return bucketing.from_buckets(buckets, like, layout)
    return buckets_of, unbuckets


def _state_specs(setup: TrainSetup):
    pspecs = setup.param_specs
    all_ax = setup.all_axes
    dev = P(all_ax)        # leading device dim, as for compressor state
    spec: dict = {"step": P(), "params": pspecs}
    if setup.zero1:
        spec["opt"] = {"t": P(),
                       "shard": {"master": dev, "m": dev, "v": dev}}
    else:
        opt = opt_mod.make(setup.opt_cfg.name, setup.opt_cfg, pspecs)
        spec["opt"] = opt.state_specs(pspecs)
    comp = setup.agg_cfg.build()
    if setup.agg_cfg.compressor != "none" and setup.agg_cfg.compress_axes:
        layout = _bucket_layout(setup)
        n_eff = _agg_sizes(setup, layout)
        states = []
        for n in n_eff:
            st_shape = jax.eval_shape(
                lambda k: comp.init_state(n, k), jax.random.key(0))
            states.append(jax.tree.map(
                lambda s: P(all_ax, *([None] * len(s.shape))), st_shape))
        spec["agg"] = tuple(states)
    else:
        spec["agg"] = ()
    return spec


def _agg_sizes(setup: TrainSetup, layout) -> list[int]:
    """Per-bucket element counts the compressor sees (DDP: bucket sizes;
    FSDP: the same buckets are built over the local shard space)."""
    return list(layout.sizes)


def _n_devices(setup: TrainSetup) -> int:
    return int(np.prod(setup.mesh.devices.shape))


def init_state(setup: TrainSetup, key: jax.Array):
    """Builds the sharded TrainState.

    Initialization runs OUTSIDE shard_map on global logical arrays (the
    repo-wide convention: init global + specs, apply local), then jit's
    out_shardings scatter it onto the mesh.  Per-device state (error
    feedback, ZeRO-1 shards) starts replicated-identical (zeros / shared
    warm starts), which every compressor's contract allows.
    """
    layout = _bucket_layout(setup)
    comp = setup.agg_cfg.build()
    n_dev = _n_devices(setup)

    def init_fn(key):
        params, _ = setup.model.init(key, setup.ctx)
        state: dict = {"step": jnp.zeros((), jnp.int32), "params": params}
        if setup.zero1:
            cap = _zero1_plan(setup).cap
            state["opt"] = {
                "t": jnp.zeros((), jnp.int32),
                "shard": {k: jnp.zeros((n_dev, cap), jnp.float32)
                          for k in ("master", "m", "v")}}
        else:
            opt = opt_mod.make(setup.opt_cfg.name, setup.opt_cfg,
                               setup.param_specs)
            state["opt"] = opt.init(params)
        if setup.agg_cfg.compressor != "none" and \
                setup.agg_cfg.compress_axes:
            ks = jax.random.split(jax.random.fold_in(key, 7),
                                  layout.n_buckets)
            states = tuple(
                jax.tree.map(
                    lambda x: jnp.broadcast_to(x[None],
                                               (n_dev,) + x.shape),
                    comp.init_state(n, ks[i]))
                for i, n in enumerate(_agg_sizes(setup, layout)))
            state["agg"] = states
        else:
            state["agg"] = ()
        return state

    shardings = setup.sharding(setup.state_specs)
    state = jax.jit(init_fn, out_shardings=shardings)(
        jax.random.key(0) if key is None else key)
    if setup.zero1:
        state = _fill_zero1_master(setup, state, layout)
    return state


def fresh_agg_state(setup: TrainSetup, key):
    """Properly-initialized compressor state (sharded) — used at init and
    after an elastic reshard invalidates the per-device saved state."""
    layout = _bucket_layout(setup)
    comp = setup.agg_cfg.build()
    n_dev = _n_devices(setup)
    if setup.agg_cfg.compressor == "none" or \
            not setup.agg_cfg.compress_axes:
        return ()

    def init_fn(k):
        ks = jax.random.split(k, layout.n_buckets)
        return tuple(
            jax.tree.map(
                lambda x: jnp.broadcast_to(x[None], (n_dev,) + x.shape),
                comp.init_state(n, ks[i]))
            for i, n in enumerate(_agg_sizes(setup, layout)))

    shardings = setup.sharding(setup.state_specs["agg"])
    return jax.jit(init_fn, out_shardings=shardings)(key)


def _zero1_flat(layout, plan: bucketing.OwnerPlan,
                buckets: list) -> jax.Array:
    """Owner-sliceable fp32 flat vector: concat the buckets and pad so
    every rank's static-length (cap) slice from its start stays in range
    (ownership runs are contiguous — OwnerPlan).  The single layout both
    zero1 gradient legs slice from."""
    pad = max(s + plan.cap for s in plan.starts) - layout.n_elements
    parts = [b.astype(jnp.float32).reshape(-1) for b in buckets]
    if pad:
        parts.append(jnp.zeros((pad,), jnp.float32))
    return jnp.concatenate(parts) if len(parts) > 1 else parts[0]


def _zero1_own_slice(setup: TrainSetup, layout, plan: bucketing.OwnerPlan,
                     buckets: list) -> jax.Array:
    """This DP rank's owned shard, (cap,) fp32, sliced from the
    rank-indexed start of the padded flat layout."""
    flat = _zero1_flat(layout, plan, buckets)
    dp = tuple(setup.dp_axes)
    rank = jax.lax.axis_index(dp) if dp else jnp.int32(0)
    starts = jnp.asarray(plan.starts, jnp.int32)
    return jax.lax.dynamic_slice_in_dim(flat, starts[rank], plan.cap)


def _zero1_rtob_own_grad(setup: TrainSetup, layout,
                         plan: bucketing.OwnerPlan, buckets):
    """The ``reduce_to_owner_broadcast`` gradient leg: lay the RAW local
    gradient out as owner-aligned ``(p_dp · cap)`` tiles and run ONE ring
    reduce-scatter — each rank receives the SUM of exactly its owned
    shard (``n·(p-1)/p`` bytes when the owner plan is balanced: the wire
    moves ``p·cap ≈ n`` elements, the same cap-padding convention the
    param gather has always had; ``owner_plan`` warns when imbalance
    makes ``cap`` exceed 2× the ideal n/p), then ``/p_dp`` makes it the
    mean.  The global grad norm of the mean gradient comes from a
    psum of each rank's masked owned sum-of-squares (the cap-padded tile
    tail overlaps the next rank's region and must not count).  Clipping
    matches ``clip_by_global_norm`` semantics on the owned shard.

    Returns ``(g_own_mean_clipped, grad_norm)``.
    """
    from repro.parallel import commplan as cp
    cap = plan.cap
    flat = _zero1_flat(layout, plan, buckets)
    tiles = jnp.concatenate([jax.lax.slice_in_dim(flat, s, s + cap)
                             for s in plan.starts])
    dp = tuple(setup.dp_axes)
    summed = cp.owner_reduce_scatter(tiles, dp)           # (cap,) own sum
    g_own = summed / jax.lax.psum(1, dp)                  # own mean
    rank = jax.lax.axis_index(dp)
    ln = jnp.asarray(plan.lengths, jnp.int32)[rank]
    masked = jnp.where(jnp.arange(cap) < ln, g_own, 0.0)
    gnorm = jnp.sqrt(jax.lax.psum(jnp.sum(jnp.square(masked)), dp))
    c = setup.opt_cfg
    if c.grad_clip:
        scale = jnp.minimum(1.0, c.grad_clip / jnp.maximum(gnorm, 1e-12))
        g_own = g_own * scale
    return g_own, gnorm


def zero1_apply(setup: TrainSetup, layout, plan: bucketing.OwnerPlan,
                buckets_of, unbuckets, params, grads, opt_state, lr):
    """Owner-sharded ZeRO-1 AdamW step (shared by the classic and the
    overlapped/segmented steps — which is what keeps the serial and
    overlap schedules bit-identical under ``zero1=True``):

      1. clip grads by global norm (same semantics as ``AdamW.update``),
      2. slice this rank's OWNED buckets out of the aggregated gradient —
         or, under the ``reduce_to_owner_broadcast`` comm plan, reduce the
         RAW gradient straight to its owners with one ring reduce-scatter
         (``_zero1_rtob_own_grad``; the buckets were never all-reduced),
      3. flat AdamW on the fp32 master shard (``flat_adamw_update``),
      4. all-gather the updated working-dtype params through the Payload
         reduce machinery (a parameter shard is a non-associative payload:
         every peer needs every owner's tensors verbatim — under the rtob
         plan this IS the broadcast leg, and the only other collective of
         the step),
      5. reassemble the parameter pytree from the gathered pieces
         (``OwnerPlan.pieces``; a bucket split across owners concatenates
         its per-owner slices).

    Returns ``(new_params, new_opt_state, grad_norm)``.
    """
    from repro.core.compression import base as cbase
    c = setup.opt_cfg
    assert c.name == "adamw", "zero1 shards flat AdamW state"
    t = opt_state["t"] + 1
    if setup.rtob:
        g_own, gnorm = _zero1_rtob_own_grad(setup, layout, plan,
                                            buckets_of(grads))
    else:
        if c.grad_clip:
            grads, gnorm = opt_mod.clip_by_global_norm(
                grads, setup.param_specs, c.grad_clip)
        else:
            gnorm = opt_mod.global_norm(grads, setup.param_specs)
        g_own = _zero1_own_slice(setup, layout, plan, buckets_of(grads))
    st = jax.tree.map(lambda x: x[0], opt_state["shard"])
    master, mv = opt_mod.flat_adamw_update(
        st["master"], g_own, {"m": st["m"], "v": st["v"]}, t, lr, c)
    payload = cbase.Payload({"shard": master.astype(layout.dtype)},
                            associative=False)
    gathered = cbase.reduce_payload(payload, setup.dp_axes) \
        .tensors["shard"]                       # (p_dp, cap)
    flat_p = gathered.reshape(-1)
    new_buckets = []
    for b in range(layout.n_buckets):
        segs = [jax.lax.slice_in_dim(flat_p, off, off + ln)
                for off, ln in plan.pieces[b]]
        new_buckets.append(segs[0] if len(segs) == 1
                           else jnp.concatenate(segs))
    new_params = unbuckets(new_buckets, params)
    new_opt = {"t": t,
               "shard": jax.tree.map(lambda x: x[None],
                                     {"master": master, **mv})}
    return new_params, new_opt, gnorm


def make_update_fn(setup: TrainSetup, layout, ov=None):
    """The optimizer leg shared by the classic, segmented, and unfused
    steps: ``update(params, grads, opt_state, lr) -> (new_params,
    new_opt, grad_norm)`` — owner-sharded flat AdamW under ZeRO-1, the
    configured ``Optimizer`` otherwise.  ONE implementation is what
    keeps the serial and overlapped schedules bit-identical."""
    if setup.zero1:
        plan = _zero1_plan(setup)
        buckets_of, unbuckets = _zero1_bucket_fns(setup, layout, ov)

        @jax.named_scope("optimizer")
        def update(params, grads, opt_state, lr):
            return zero1_apply(setup, layout, plan, buckets_of, unbuckets,
                               params, grads, opt_state, lr)
    else:
        @jax.named_scope("optimizer")
        def update(params, grads, opt_state, lr):
            opt = opt_mod.make(setup.opt_cfg.name, setup.opt_cfg,
                               setup.param_specs)
            new_params, new_opt, om = opt.update(grads, opt_state, params,
                                                 lr)
            return new_params, new_opt, om["grad_norm"]
    return update


def train_metrics(setup: TrainSetup, loss_sum, ntok, gnorm, moe_aux):
    """The step's metrics dict (loss is the DP-global token mean)."""
    dp = setup.dp_axes
    loss_g = jax.lax.psum(loss_sum, dp) if dp else loss_sum
    ntok_g = jax.lax.psum(ntok, dp) if dp else ntok
    return {"loss": loss_g / jnp.maximum(ntok_g.astype(jnp.float32), 1.0),
            "tokens": ntok_g,
            "grad_norm": gnorm,
            "moe_aux": moe_aux}


def _fill_zero1_master(setup: TrainSetup, state, layout):
    """Initialize each rank's fp32 master from its owned param buckets."""
    plan = _zero1_plan(setup)
    buckets_of, _ = _zero1_bucket_fns(setup, layout)

    def fill(params, shard):
        master = _zero1_own_slice(setup, layout, plan, buckets_of(params))
        return {"master": master[None], "m": shard["m"], "v": shard["v"]}

    sspec = setup.state_specs["opt"]["shard"]
    f = shard_map(fill, setup.mesh, in_specs=(setup.param_specs, sspec),
                  out_specs=sspec)
    new_shard = jax.jit(f)(state["params"], state["opt"]["shard"])
    state["opt"] = {**state["opt"], "shard": new_shard}
    return state


# --------------------------------------------------------------------------
# the step
# --------------------------------------------------------------------------
def make_step(setup: TrainSetup, accum: int = 1, xent_chunk: int = 1024):
    """Returns a jitted ``step(state, batch, lr) -> (state, metrics)``."""
    if setup.overlap:
        from repro.train import overlap as overlap_mod
        return overlap_mod.make_step(setup, schedule="overlap",
                                     accum=accum, xent_chunk=xent_chunk)
    model = setup.model
    ctx = setup.ctx
    arch = setup.arch
    layout = _bucket_layout(setup)
    aggregator = agg_mod.GradAggregator(setup.agg_cfg)
    dp = setup.dp_axes
    fsdp = setup.fsdp_axes
    p_dp = setup.p_dp
    p_fsdp = setup.p_fsdp
    scale_axes = p_dp // p_fsdp

    def loss_fn(params, batch):
        loss_sum, ntok, moe_aux = model.loss(params, batch, ctx)
        n_glob = jax.lax.psum(ntok, dp) if dp else ntok
        scaled = loss_sum * (scale_axes / n_glob.astype(jnp.float32))
        if arch.moe.n_experts:
            scaled = scaled + MOE_AUX_COEF * moe_aux / p_fsdp
        return scaled, (loss_sum, ntok, moe_aux)

    grad_fn = jax.value_and_grad(loss_fn, has_aux=True)

    def norm_replicated_over_fsdp(grads):
        """Leaves whose spec has no fsdp axis never went through the
        reduce-scatter transpose: psum them over the fsdp axes."""
        if not fsdp:
            return grads

        def f(g, s):
            axes = opt_mod._axes_of(s)
            if any(a in axes for a in fsdp):
                return g
            return jax.lax.psum(g, fsdp)
        return jax.tree.map(f, grads, setup.param_specs,
                            is_leaf=lambda s: isinstance(s, P))

    @jax.named_scope("grad_sync")
    def aggregate(grads, agg_states):
        """Returns aggregated grads + new compressor states.  The bucket
        loop itself lives in ``GradAggregator.aggregate_bucketed`` (one
        code path with the aggregator); this wrapper only strips/restores
        the leading device dim the TrainState carries on per-device
        compressor state."""
        if setup.agg_cfg.compressor == "none" or \
                not (setup.agg_cfg.compress_axes or setup.agg_cfg.raw_axes):
            return grads, agg_states
        squeezed = tuple(jax.tree.map(lambda x: x[0], st)
                         for st in agg_states)
        out, news = aggregator.aggregate_bucketed(grads, squeezed, layout)
        if squeezed:
            news = tuple(jax.tree.map(lambda x: x[None], ns) for ns in news)
            return out, news
        return out, agg_states

    @jax.named_scope("grad_sync")
    def aggregate_raw(grads):
        """none-compressor path: one mean over the configured axes, moved
        by the configured CommPlan (auto -> pmean, the historic path)."""
        from repro.parallel import commplan as cp
        axes = tuple(setup.agg_cfg.raw_axes) + \
            tuple(setup.agg_cfg.compress_axes)
        if not axes:
            return grads
        plan = setup.agg_cfg.comm
        return jax.tree.map(lambda g: cp.mean_reduce(g, axes, plan), grads)

    update_fn = make_update_fn(setup, layout)

    def one_micro(params, batch):
        (scaled, (loss_sum, ntok, aux)), grads = grad_fn(params, batch)
        return grads, loss_sum, ntok, aux

    def step_fn(state, batch, lr):
        params = state["params"]
        if accum > 1:
            def micro(carry, mb):
                g_acc, l_acc, n_acc, a_acc = carry
                g, l, n, a = one_micro(params, mb)
                return (jax.tree.map(jnp.add, g_acc, g), l_acc + l,
                        n_acc + n, a_acc + a), None
            zeros = jax.tree.map(
                lambda p: jnp.zeros(p.shape, jnp.float32), params)
            mbs = jax.tree.map(
                lambda x: x.reshape((accum, x.shape[0] // accum)
                                    + x.shape[1:]), batch)
            (grads, loss_sum, ntok, aux), _ = jax.lax.scan(
                micro, (zeros, jnp.float32(0), jnp.int32(0),
                        jnp.float32(0)), mbs)
            grads = jax.tree.map(lambda g: g / accum, grads)
            aux = aux / accum
        else:
            grads, loss_sum, ntok, aux = one_micro(params, batch)

        grads = norm_replicated_over_fsdp(grads)
        if setup.rtob:
            # reduce_to_owner_broadcast: no gradient all-reduce — the
            # update's owner-aligned ring reduce-scatter is the only
            # gradient collective (zero1_apply)
            new_agg = state["agg"]
        elif setup.agg_cfg.compressor == "none":
            grads = aggregate_raw(grads)
            new_agg = state["agg"]
        else:
            grads, new_agg = aggregate(grads, state["agg"])

        new_params, new_opt, gnorm = update_fn(params, grads,
                                               state["opt"], lr)
        metrics = train_metrics(setup, loss_sum, ntok, gnorm, aux)
        new_state = {"step": state["step"] + 1, "params": new_params,
                     "opt": new_opt, "agg": new_agg}
        return new_state, metrics

    batch_spec_fn = make_batch_specs(setup)

    def jitted(batch_example):
        bspecs = batch_spec_fn(batch_example)
        f = shard_map(step_fn, setup.mesh,
                      in_specs=(setup.state_specs, bspecs, P()),
                      out_specs=(setup.state_specs,
                                 {"loss": P(), "tokens": P(),
                                  "grad_norm": P(), "moe_aux": P()}))
        return jax.jit(f, donate_argnums=(0,))

    return jitted


def make_batch_specs(setup: TrainSetup):
    dp = tuple(setup.dp_axes) or None

    def fn(batch):
        specs = {}
        for k, v in batch.items():
            if k == "mrope_positions":
                specs[k] = P(None, dp, *([None] * (v.ndim - 2)))
            else:
                specs[k] = P(dp, *([None] * (v.ndim - 1)))
        return specs
    return fn


def local_sgd_sync(setup: TrainSetup):
    """Pod-axis parameter averaging for the --sync-every local-SGD mode
    (bounded-staleness straggler mitigation, DESIGN.md §4)."""
    axes = tuple(a for a in ("pod",) if a in setup.all_axes
                 and setup.axis_size(a) > 1
                 and a not in setup.fsdp_axes)
    if not axes:
        return None

    def sync(state):
        params = jax.tree.map(lambda p: jax.lax.pmean(p, axes),
                              state["params"])
        return {**state, "params": params}

    f = shard_map(sync, setup.mesh, in_specs=(setup.state_specs,),
                  out_specs=setup.state_specs)
    return jax.jit(f, donate_argnums=(0,))
