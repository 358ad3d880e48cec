"""Plain float32 reference of a data-parallel training step, for the
comparison that decides ``correct``.

It imports nothing of the program.  The model is the configuration's
architecture module, ``bench/models/<arch>.py`` (found by name,
``Spec.arch``): its leaves (``shapes``), its stacks of layers (``STACKS``)
and its loss (``loss_sum``).  Weights come from ``bench.weights`` and token
batches from ``bench.data``, both made from the seed.  Matrix products run
at ``precision="highest"`` (true float32 on a TPU); ``precision="fp8"`` is
the control: every matrix product of the model, forward and backward,
takes operands rounded to float8 e4m3 with a per-tensor power-of-two
scale, the step below the configuration's bfloat16.

The step it follows:

1. loss = mean next-token cross-entropy over the global batch; gradient of
   it;
2. the cell's compressor, ``bench/compressors/<compression>.py`` (found
   by name, ``Spec.compressor``), on the DDP buckets: whole leaves in the
   order backward completes them (each stack in ``STACKS`` order, last
   layer first, a layer's leaves by name, then the remaining leaves by
   name), a bucket closing once it holds ``bucket_mb`` of gradient in the
   weights' dtype;
3. clipping to ``grad_clip`` by global norm;
4. AdamW with decoupled weight decay, bias-corrected moments.

A compressor module has ``init(key, cfg, workload, arch) -> state`` and
``apply(grads, state, cfg, workload, arch) -> (grads, state)`` over the
mean gradient by path, and may have ``seed_program(key, agg, workload)``,
which puts the same starting state into the program's.

Memory: the batch is split over every device and the optimizer moments
over their last axis; the architecture module keeps activations small.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from bench import weights

# ------------------------------------------------------------ precision
def _pow2_scale(x):
    amax = jnp.max(jnp.abs(x))
    e = jnp.floor(jnp.log2(448.0 / jnp.maximum(amax, 1e-30)))
    return jnp.exp2(e)


def _q8(x):
    """x rounded to float8 e4m3 under a per-tensor power-of-two scale (so
    the rounded values are exact in bfloat16)."""
    s = jax.lax.stop_gradient(_pow2_scale(x))
    return (x * s).astype(jnp.float8_e4m3fn).astype(jnp.float32) / s


def _fp8_einsum(spec: str, a, b):
    @jax.custom_vjp
    def f(a, b):
        return jnp.einsum(spec, _q8(a), _q8(b))

    def fwd(a, b):
        qa, qb = _q8(a), _q8(b)
        return jnp.einsum(spec, qa, qb), (qa, qb)

    def bwd(res, g):
        _, vjp = jax.vjp(lambda x, y: jnp.einsum(spec, x, y), *res)
        return vjp(_q8(g))

    f.defvjp(fwd, bwd)
    return f(a, b)


def _einsum_fn(precision: str):
    if precision == "highest":
        return partial(jnp.einsum, precision=jax.lax.Precision.HIGHEST)
    if precision == "fp8":
        return _fp8_einsum
    raise ValueError(f"precision {precision!r}")


# -------------------------------------------------------------- buckets
def stacked(path: str, stacks) -> bool:
    """Whether leaf ``path`` belongs to one of ``stacks`` (its layers on
    the leading axis)."""
    return path.split("/", 1)[0] in stacks


def ordered_leaves(cfg: dict, arch) -> list[tuple[str, int | None, int]]:
    """(path, layer, size) in the order backward completes them."""
    shapes = arch.shapes(cfg)
    out = []
    for stack in arch.STACKS:
        names = sorted(p for p in shapes if stacked(p, (stack,)))
        for l in reversed(range(shapes[names[0]][0])):
            out += [(p, l, math.prod(shapes[p][1:])) for p in names]
    for p in sorted(shapes):
        if not stacked(p, arch.STACKS):
            out.append((p, None, math.prod(shapes[p])))
    return out


def buckets(cfg: dict, workload: dict, arch) -> list[list[tuple]]:
    """Leaf runs of the DDP buckets (a bucket closes once it holds at
    least ``bucket_mb`` of gradient; a large leaf joins the open bucket
    whole)."""
    itemsize = jnp.dtype(weights.param_dtype(workload["plan"])).itemsize
    target = max(1, int(workload["plan"]["bucket_mb"] * 2**20) // itemsize)
    out, cur, acc = [], [], 0
    for leaf in ordered_leaves(cfg, arch):
        if acc >= target:
            out.append(cur)
            cur, acc = [], 0
        cur.append(leaf)
        acc += leaf[2]
    out.append(cur)
    return out


def map_buckets(fn, grads: dict, state, cfg: dict, workload: dict, arch):
    """``fn(flat, st) -> (flat, st)`` over every DDP bucket's flat gradient
    and its compressor state; returns the gradient rebuilt by path from
    the buckets ``fn`` returned, and the new states."""
    pieces: dict = {}
    new_state = []
    for bkt, st in zip(buckets(cfg, workload, arch), state):
        flat = jnp.concatenate([
            (grads[p][l] if l is not None else grads[p]).reshape(-1)
            for p, l, _ in bkt])
        out, st = fn(flat, st)
        new_state.append(st)
        off = 0
        for p, l, size in bkt:
            pieces[(p, l)] = out[off:off + size]
            off += size
    rebuilt = {}
    for p, g in grads.items():
        if stacked(p, arch.STACKS):
            rebuilt[p] = jnp.stack([pieces[(p, l)].reshape(g.shape[1:])
                                    for l in range(g.shape[0])])
        else:
            rebuilt[p] = pieces[(p, None)].reshape(g.shape)
    return rebuilt, tuple(new_state)


# --------------------------------------------------------------- readers
def leaf_norms(tree: dict, stacks) -> dict:
    """{label: norm} per leaf, per layer (``path#layer``) for the leaves of
    ``stacks``."""
    out = {}
    for p, x in tree.items():
        if stacked(p, stacks):
            n = jnp.sqrt(jnp.sum(x * x, axis=tuple(range(1, x.ndim))))
            for l in range(x.shape[0]):
                out[f"{p}#{l}"] = n[l]
        else:
            out[p] = jnp.sqrt(jnp.sum(x * x))
    return out


# ------------------------------------------------------------------ step
class Reference:
    """Three (or more) reference steps from the seed, on every device,
    with ``arch`` the configuration's module from ``bench/models`` and
    ``compressor`` the cell's module from ``bench/compressors``."""

    def __init__(self, cfg: dict, workload: dict, arch, compressor,
                 precision: str = "highest"):
        self.cfg, self.workload, self.precision = cfg, workload, precision
        devs = np.array(jax.devices())
        self.mesh = Mesh(devs, ("b",))
        self.n = devs.size
        opt = workload["optimizer"]
        w_dtype = weights.param_dtype(workload["plan"])
        rep = NamedSharding(self.mesh, P())

        def moment_sharding(shape):
            if shape[-1] % self.n == 0:
                return NamedSharding(self.mesh,
                                     P(*([None] * (len(shape) - 1)), "b"))
            return rep

        shapes = arch.shapes(cfg)
        self.p_sh = {p: rep for p in shapes}
        self.m_sh = {p: moment_sharding(s) for p, s in shapes.items()}
        self.b_sh = NamedSharding(self.mesh, P("b"))

        def init(key):
            w = weights.make(key, cfg, w_dtype, arch)
            params = {p: v.astype(jnp.float32) for p, v in w.items()}
            zeros = {p: jnp.zeros(v.shape, jnp.float32)
                     for p, v in params.items()}
            return (params, {"m": zeros, "v": dict(zeros)},
                    compressor.init(key, cfg, workload, arch))

        self._init = jax.jit(init, out_shardings=(
            self.p_sh, {"m": self.m_sh, "v": self.m_sh}, rep))

        einsum = _einsum_fn(precision)

        def step(params, moments, st, t, tokens, labels, lr):
            n_tok = jnp.maximum(jnp.sum(labels >= 0), 1).astype(jnp.float32)
            lsum, grads = jax.value_and_grad(
                lambda p: arch.loss_sum(p, tokens, labels, cfg, einsum)
            )(params)
            grads = {p: g / n_tok for p, g in grads.items()}
            grads, st = compressor.apply(grads, st, cfg, workload, arch)
            gnorm = jnp.sqrt(sum(jnp.sum(g * g) for g in grads.values()))
            if opt["grad_clip"]:
                scale = jnp.minimum(1.0, opt["grad_clip"]
                                    / jnp.maximum(gnorm, 1e-12))
                grads = {p: g * scale for p, g in grads.items()}
            grads = {p: jax.lax.with_sharding_constraint(g, self.m_sh[p])
                     for p, g in grads.items()}
            b1, b2 = opt["b1"], opt["b2"]
            bc1, bc2 = 1.0 - b1 ** t, 1.0 - b2 ** t
            m = {p: b1 * moments["m"][p] + (1 - b1) * g
                 for p, g in grads.items()}
            v = {p: b2 * moments["v"][p] + (1 - b2) * g * g
                 for p, g in grads.items()}
            new = {p: params[p] - lr * ((m[p] / bc1)
                                        / (jnp.sqrt(v[p] / bc2) + opt["eps"])
                                        + opt["weight_decay"] * params[p])
                   for p in params}
            return new, {"m": m, "v": v}, st, lsum / n_tok, \
                leaf_norms(grads, arch.STACKS), grads

        self._step = jax.jit(
            step, donate_argnums=(0, 1, 2),
            in_shardings=(self.p_sh, {"m": self.m_sh, "v": self.m_sh}, rep,
                          None, self.b_sh, self.b_sh, None),
            out_shardings=(self.p_sh, {"m": self.m_sh, "v": self.m_sh}, rep,
                           rep, rep, self.m_sh))

        def delta(params, key):
            w = weights.make(key, cfg, w_dtype, arch)
            return leaf_norms({p: params[p] - w[p].astype(jnp.float32)
                               for p in params}, arch.STACKS)

        self._delta = jax.jit(delta)

    def run(self, key, batches: list[dict], keep_grad: bool = False
            ) -> dict:
        """Readings after ``len(batches)`` steps: each step's loss, the
        per-leaf norms of the first step's gradient as the optimizer takes
        it (after compression and clipping) and of the parameters' change;
        with ``keep_grad`` that gradient's leaves too, on the host."""
        with jax.default_matmul_precision("highest"):
            params, moments, st = self._init(key)
            out = {"losses": []}
            lr = jnp.float32(self.workload["lr"])
            for i, b in enumerate(batches):
                params, moments, st, loss, g, gv = self._step(
                    params, moments, st, jnp.float32(i + 1),
                    jnp.asarray(b["tokens"]), jnp.asarray(b["labels"]), lr)
                out["losses"].append(float(loss))
                if i == 0:
                    out["grad"] = {k: float(v) for k, v in g.items()}
                    if keep_grad:
                        out["grad_vec"] = {k: np.asarray(v)
                                           for k, v in gv.items()}
                del gv
            out["delta"] = {k: float(v)
                            for k, v in self._delta(params, key).items()}
        return out
