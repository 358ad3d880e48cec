"""Every call the benchmark makes into the system under test (the ``repro``
package under ``src/``), in one place.

It builds the configuration and the train step through the calls that
``repro.launch.train.prepare`` and ``Trainer.run`` make, puts the
benchmark's seeded inputs into the program's state, and reads the ZeRO-1
optimizer state back per leaf for the correctness comparison.
"""
from __future__ import annotations

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def add_src_path() -> None:
    src = os.path.join(ROOT, "src")
    if src not in sys.path:
        sys.path.insert(0, src)


#: the least size limit of the persistent cache: a compiled 32-layer step
#: is about 212 MB, over the 192 MiB limit a TPU host may set, and an
#: entry over the limit is never written, so every run compiled again
CACHE_MAX_BYTES = 4 * 2**30


def prepare_process(workload: dict) -> str:
    """Before JAX starts its backends: append the overlap flags to
    ``LIBTPU_INIT_ARGS`` (never overwriting them) and turn on the
    persistent compile cache.  Returns the cache directory."""
    add_src_path()
    if workload["plan"].get("overlap"):
        from repro.train.overlap import enable_overlap_flags
        enable_overlap_flags()
    from repro.launch import compile_cache
    path = compile_cache.enable()
    import jax
    limit = jax.config.jax_compilation_cache_max_size
    if 0 < limit < CACHE_MAX_BYTES:
        jax.config.update("jax_compilation_cache_max_size", CACHE_MAX_BYTES)
    return path


def _path_str(path) -> str:
    return "/".join(str(getattr(k, "key", k)) for k in path)


class Program:
    """One cell's compiled train step, its state and its data feed.

    ``arch`` is the configuration's module from ``bench/models``: the
    program's configuration and the leaves' layout.  ``compressor`` is the
    cell's module from ``bench/compressors``; its ``seed_program``, where
    it has one, puts the seed's starting state into the program's
    compressor state."""

    def __init__(self, cfg: dict, workload: dict, arch, compressor=None):
        import jax

        from bench import weights
        from repro.configs.base import ArchConfig, ParallelPlan
        from repro.launch.mesh import make_local_mesh
        from repro.train import train_step as ts
        from repro.train.optimizer import OptConfig

        config = ArchConfig(plan=ParallelPlan(**workload["plan"]),
                            **arch.arch_config(cfg))
        self.cfg, self.workload = cfg, workload
        self.arch, self.compressor = arch, compressor
        self.setup = ts.build(config, make_local_mesh(),
                              opt_cfg=OptConfig(name="adamw",
                                                **workload["optimizer"]))
        self.n_dev = self.setup.mesh.devices.size
        self.global_batch = workload["seqs_per_chip"] * self.n_dev
        abstract, _ = self.setup.model.abstract_init(self.setup.ctx)
        flat, self._treedef = jax.tree_util.tree_flatten_with_path(abstract)
        self.paths = [_path_str(p) for p, _ in flat]
        self.shapes = {p: tuple(a.shape) for p, (_, a) in
                       zip(self.paths, flat)}
        self.param_dtype = flat[0][1].dtype
        if self.param_dtype != weights.param_dtype(workload["plan"]):
            raise ValueError(f"the program keeps its weights in "
                             f"{self.param_dtype}, the benchmark's rule "
                             f"says {weights.param_dtype(workload['plan'])}")
        self._segments_cache = None

    def feed(self, seed: int):
        """The program's synthetic-data pipeline for ``seed`` (the host
        builds each global batch; a prefetch thread moves it over), with
        the cell's ``data`` parameters."""
        from repro.data.pipeline import Pipeline
        from repro.data.synthetic import DataConfig
        return Pipeline(DataConfig(
            vocab=self.cfg["vocab"], seq_len=self.workload["seq"],
            global_batch=self.global_batch, seed=seed,
            **self.workload["data"]))

    # ------------------------------------------------------------- state
    def init_state(self, key):
        """The program's state with the benchmark's weights (and the
        compressor's starting state) from ``key``; under ZeRO-1 the fp32
        master is filled from those weights."""
        import jax

        from bench import weights
        from repro.train import train_step as ts

        setup = self.setup
        if self.shapes != self.arch.shapes(self.cfg):
            raise ValueError(f"program parameters {self.shapes} differ from "
                             f"the benchmark's layout")
        paths, treedef, cfg, dtype, arch = (
            self.paths, self._treedef, self.cfg, self.param_dtype, self.arch)

        def make_params(k):
            w = weights.make(k, cfg, dtype, arch)
            return jax.tree_util.tree_unflatten(treedef,
                                                [w[p] for p in paths])

        state = ts.init_state(setup, key)
        state["params"] = jax.jit(
            make_params,
            out_shardings=setup.sharding(setup.state_specs["params"]))(key)
        seed_program = getattr(self.compressor, "seed_program", None)
        if seed_program is not None and state["agg"]:
            state["agg"] = jax.jit(
                lambda k, agg: seed_program(k, agg, self.workload),
                donate_argnums=1,
                out_shardings=setup.sharding(setup.state_specs["agg"]))(
                    key, state["agg"])
        if setup.zero1:
            state = ts._fill_zero1_master(setup, state,
                                          ts._bucket_layout(setup))
        return state

    def make_step(self, batch):
        from repro.train import train_step as ts
        return ts.make_step(self.setup, accum=self.workload["accum"])(batch)

    # -------------------------------------------------- per-leaf readers
    def _labels(self) -> list[tuple[str, int]]:
        """(label, size) of every leaf in the order the ZeRO-1 flat layout
        holds them, a label being ``path`` or ``path#layer``: backward
        completion order under overlap, the parameters' own order
        otherwise (a stacked leaf's layers one after another)."""
        import jax
        import numpy as np

        from bench.reference import stacked
        from repro.train import overlap

        stacks = self.arch.STACKS

        def per_layer(p):
            return [f"{p}#{l}" for l in range(self.shapes[p][0])]

        if not self.setup.overlap:
            out = []
            for p in self.paths:
                if stacked(p, stacks):
                    size = int(np.prod(self.shapes[p][1:]))
                    out += [(lab, size) for lab in per_layer(p)]
                else:
                    out.append((p, int(np.prod(self.shapes[p]))))
            return out
        ov = overlap.build_layout(self.setup)
        tree = jax.tree_util.tree_unflatten(self._treedef, [
            np.array(per_layer(p)) if stacked(p, stacks) else p
            for p in self.paths])
        return list(zip((str(x) for x in overlap._ordered_leaves(ov, tree)),
                        ov.layout.leaf_sizes))

    def _segments(self):
        """(label, rank, lo, hi, leaf_lo) for every piece of every leaf in
        the ZeRO-1 shards: rank ``rank`` holds elements ``[lo, hi)`` of its
        shard, which are elements ``[leaf_lo, leaf_lo + hi - lo)`` of the
        leaf ``label`` flattened."""
        if self._segments_cache is not None:
            return self._segments_cache
        from repro.train import train_step as ts

        plan = ts._zero1_plan(self.setup)
        segs, off = [], 0
        for label, size in self._labels():
            for r in range(plan.n_ranks):
                lo = max(off, plan.starts[r])
                hi = min(off + size, plan.starts[r] + plan.lengths[r])
                if lo < hi:
                    segs.append((label, r, lo - plan.starts[r],
                                 hi - plan.starts[r], lo - off))
            off += size
        self._segments_cache = segs
        return segs

    def _by_path(self, tree) -> dict:
        import jax
        return dict(zip(self.paths, jax.tree_util.tree_leaves(tree)))

    def grad_tree(self, state):
        """The first moment as the optimizer holds it: the ZeRO-1 flat
        shards ``(n_dev, cap)``, or the per-leaf tree by path."""
        if self.setup.zero1:
            return state["opt"]["shard"]["m"]
        return self._by_path(state["opt"]["m"])

    def weights_now(self, state):
        """The fp32 weights: the ZeRO-1 master shards, or the parameters
        by path."""
        if self.setup.zero1:
            return state["opt"]["shard"]["master"]
        return self._by_path(state["params"])

    def leaf_norms_fn(self, minus_weights: bool):
        """A jitted ``f(x[, key]) -> {label: norm}`` over ``grad_tree`` or
        ``weights_now``, leaf by leaf (per layer for stacked leaves); with
        ``minus_weights`` the seed's starting weights are subtracted first
        (the change since step 0)."""
        import jax
        import jax.numpy as jnp

        from bench import weights
        from bench.reference import leaf_norms
        cfg, dtype, arch = self.cfg, self.param_dtype, self.arch

        if not self.setup.zero1:
            def norms(tree, key=None):
                tree = {p: x.astype(jnp.float32) for p, x in tree.items()}
                if minus_weights:
                    w = weights.make(key, cfg, dtype, arch)
                    tree = {p: x - w[p].astype(jnp.float32)
                            for p, x in tree.items()}
                return leaf_norms(tree, arch.STACKS)
            return jax.jit(norms)

        segs = self._segments()

        def shard_norms(shards, key=None):
            w = weights.make(key, cfg, dtype, arch) if minus_weights \
                else None
            acc: dict = {}
            for label, r, lo, hi, leaf_lo in segs:
                x = shards[r, lo:hi]
                if w is not None:
                    path, _, layer = label.partition("#")
                    ref = w[path][int(layer)] if layer else w[path]
                    x = x - ref.reshape(-1)[leaf_lo:leaf_lo + hi - lo] \
                        .astype(jnp.float32)
                acc[label] = acc.get(label, 0.0) + jnp.sum(x * x)
            return {k: jnp.sqrt(v) for k, v in acc.items()}

        return jax.jit(shard_norms)

    def host_leaves(self, x, scale: float) -> dict:
        """``grad_tree``'s leaves by path on the host, in float32 times
        ``scale``."""
        import numpy as np
        if not self.setup.zero1:
            return {p: np.asarray(v, np.float32) * scale
                    for p, v in x.items()}
        shards = np.asarray(x)
        out = {p: np.zeros(s, np.float32) for p, s in self.shapes.items()}
        for label, r, lo, hi, leaf_lo in self._segments():
            path, _, layer = label.partition("#")
            leaf = out[path][int(layer)] if layer else out[path]
            leaf.reshape(-1)[leaf_lo:leaf_lo + hi - lo] = \
                shards[r, lo:hi] * scale
        return out
