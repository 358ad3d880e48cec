"""Pallas TPU flash attention for training and prefill: forward, dK/dV and
dQ kernels behind one ``jax.custom_vjp``.

Layout: head-major ``q (B, H, S, hd)``, ``k, v (B, KVh, S, hd)`` with
``KVh | H``; the caller (``kernels/ops.py``) transposes from the model's
``(B, S, heads, hd)``.  Grouped-query attention maps q head ``h`` to kv
head ``h // (H // KVh)`` in the k/v index maps, so k and v are never
repeated.

Every score and probability tile lives in VMEM only.  The MXU takes the
operands' own dtype (bf16 in training) for QK^T, PV, dP, dV, dQ and dK and
accumulates in fp32; the running max, the denominator, the rescaling and
the per-row logsumexp are fp32.  The residuals are the output and the
logsumexp (stored as ``(B, H, 8, S)`` rows, one per sublane), so no
``(S, S)`` tensor reaches HBM.

Causal attention skips the blocks above the diagonal: their grid steps
run no matmul, and their index maps repeat the previous block so no DMA is
issued either.  Only blocks that the diagonal crosses build a mask.

Every kernel runs under ``jax.named_scope("attention")`` and is named
``attention``, so its Mosaic call is labelled ``attention`` in a profile,
forward and backward alike.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
SUBLANES = 8
#: finite, so a fully masked row gives exp(mask - max) = 0, never NaN
_MASK = -0.7 * float(np.finfo(np.float32).max)
_NT = (((1,), (1,)), ((), ()))    # a @ b.T
_NN = (((1,), (0,)), ((), ()))    # a @ b
_F32 = jnp.float32
#: the Mosaic calls' HLO name, which a profile labels them by; without it
#: XLA names them after the innermost scope, ``jvp(attention)`` in the
#: forward pass
_NAME = "attention"


def fits(sq: int, sk: int, hd: int) -> bool:
    """Whether self-attention of these shapes takes the kernel: equal q and
    k lengths in whole 128-row blocks, and a head size the MXU tiles."""
    return sq == sk and sq % LANES == 0 and hd % 64 == 0 and hd <= 256


def _divisor(s: int, cap: int) -> int:
    """The largest power-of-two multiple of 128 up to ``cap`` dividing s."""
    b = cap
    while s % b:
        b //= 2
    return b


def block_sizes(s: int, hd: int) -> tuple[int, int, int, int]:
    """(q rows, k rows) of the forward blocks, then of the backward ones,
    from the sequence length and head size."""
    cap = 512 if hd <= 128 else 256
    b = _divisor(s, cap)
    return b, b, b, b


# --------------------------------------------------------------------------
# shared pieces
# --------------------------------------------------------------------------
def _lanes(x, n: int):
    """(r, 128) with equal lanes -> (r, n)."""
    if n <= LANES:
        return x[:, :n]
    return jnp.tile(x, (1, n // LANES))


def _causal_keep(shape, row0, col0, rows_are_q: bool):
    """Keep-mask of one tile: a key position is kept when it is at or
    before the query position."""
    rows = row0 + lax.broadcasted_iota(jnp.int32, shape, 0)
    cols = col0 + lax.broadcasted_iota(jnp.int32, shape, 1)
    return cols <= rows if rows_are_q else rows <= cols


def _blocks_of_tile(causal: bool, q0, bq: int, k0, bk: int, step):
    """Run ``step(masked)`` for one (q block, k block) pair: not at all above
    the diagonal, without a mask below it, with one where it crosses."""
    if not causal:
        step(False)
        return
    run = k0 <= q0 + bq - 1
    below = k0 + bk - 1 <= q0

    @pl.when(jnp.logical_and(run, below))
    def _():
        step(False)

    @pl.when(jnp.logical_and(run, jnp.logical_not(below)))
    def _():
        step(True)


def _last_k(i, bq: int, bk: int):
    """The last k block that q block ``i`` attends to (causal)."""
    return (i * bq + bq - 1) // bk


def _first_q(j, bq: int, bk: int):
    """The first q block that attends to k block ``j`` (causal)."""
    return (j * bk) // bq


def _params(*semantics: str, vmem: int):
    return pltpu.CompilerParams(dimension_semantics=semantics,
                                vmem_limit_bytes=vmem)


def _vmem_bytes(bq: int, bk: int, hd: int) -> int:
    """A VMEM limit with room for the f32 score tiles and their
    temporaries, the double-buffered operand blocks and the scratch."""
    tiles = 6 * bq * bk * 4
    blocks = 2 * 2 * (2 * bq + 2 * bk) * max(hd, LANES) * 4
    return min(max(32 << 20, 2 * (tiles + blocks)), 100 << 20)


# --------------------------------------------------------------------------
# forward
# --------------------------------------------------------------------------
def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_sc, l_sc, acc_sc, *,
                scale: float, causal: bool, bq: int, bk: int, nk: int):
    i, j = pl.program_id(2), pl.program_id(3)
    hd = acc_sc.shape[-1]

    @pl.when(j == 0)
    def _init():
        m_sc[...] = jnp.full_like(m_sc, _MASK)
        l_sc[...] = jnp.zeros_like(l_sc)
        acc_sc[...] = jnp.zeros_like(acc_sc)

    def step(masked: bool):
        s = lax.dot_general(q_ref[...], k_ref[...], _NT,
                            preferred_element_type=_F32) * scale
        if masked:
            s = jnp.where(_causal_keep(s.shape, i * bq, j * bk, True), s,
                          _MASK)
        m_prev = m_sc[...]
        m_next = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        p = jnp.exp(s - _lanes(m_next, bk))
        alpha = jnp.exp(m_prev - m_next)
        l_sc[...] = alpha * l_sc[...] + p.sum(axis=-1, keepdims=True)
        m_sc[...] = m_next
        v = v_ref[...]
        pv = lax.dot_general(p.astype(v.dtype), v, _NN,
                             preferred_element_type=_F32)
        acc_sc[...] = _lanes(alpha, hd) * acc_sc[...] + pv

    _blocks_of_tile(causal, i * bq, bq, j * bk, bk, step)

    @pl.when(j == nk - 1)
    def _out():
        l = l_sc[...]
        o_ref[...] = (acc_sc[...] * _lanes(1.0 / l, hd)).astype(o_ref.dtype)
        lse = m_sc[...] + jnp.log(l)                     # (bq, 128)
        lse_ref[...] = lse.T[:SUBLANES]                  # (8, bq)


def _forward(q, k, v, *, causal: bool, scale: float, bq: int, bk: int,
             interpret: bool):
    b, h, s, hd = q.shape
    group = h // k.shape[1]
    nq, nk = s // bq, s // bk

    def kv_block(i, j):
        return jnp.minimum(j, _last_k(i, bq, bk)) if causal else j

    kernel = functools.partial(_fwd_kernel, scale=scale, causal=causal,
                               bq=bq, bk=bk, nk=nk)
    flops = 4 * b * h * s * s * hd // (2 if causal else 1)
    return pl.pallas_call(
        kernel,
        grid=(b, h, nq, nk),
        in_specs=[
            pl.BlockSpec((None, None, bq, hd),
                         lambda b_, h_, i, j: (b_, h_, i, 0)),
            pl.BlockSpec((None, None, bk, hd),
                         lambda b_, h_, i, j: (b_, h_ // group,
                                               kv_block(i, j), 0)),
            pl.BlockSpec((None, None, bk, hd),
                         lambda b_, h_, i, j: (b_, h_ // group,
                                               kv_block(i, j), 0)),
        ],
        out_specs=[
            pl.BlockSpec((None, None, bq, hd),
                         lambda b_, h_, i, j: (b_, h_, i, 0)),
            pl.BlockSpec((None, None, SUBLANES, bq),
                         lambda b_, h_, i, j: (b_, h_, 0, i)),
        ],
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype),
                   jax.ShapeDtypeStruct((b, h, SUBLANES, s), _F32)],
        scratch_shapes=[pltpu.VMEM((bq, LANES), _F32),
                        pltpu.VMEM((bq, LANES), _F32),
                        pltpu.VMEM((bq, hd), _F32)],
        compiler_params=_params("parallel", "parallel", "parallel",
                                "arbitrary", vmem=_vmem_bytes(bq, bk, hd)),
        cost_estimate=pl.CostEstimate(
            flops=flops, transcendentals=flops // (4 * hd),
            bytes_accessed=(2 * q.size + 2 * k.size * nq) * q.dtype.itemsize),
        interpret=interpret,
        name=_NAME,
    )(q, k, v)


# --------------------------------------------------------------------------
# backward: dK and dV (a k block gathers every q head of its group)
# --------------------------------------------------------------------------
def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, di_ref, dk_ref, dv_ref,
                dk_sc, dv_sc, *, scale: float, causal: bool, bq: int,
                bk: int, nq: int, group: int):
    j, g, i = pl.program_id(2), pl.program_id(3), pl.program_id(4)

    @pl.when(jnp.logical_and(g == 0, i == 0))
    def _init():
        dk_sc[...] = jnp.zeros_like(dk_sc)
        dv_sc[...] = jnp.zeros_like(dv_sc)

    def step(masked: bool):
        q, k, v, do = q_ref[...], k_ref[...], v_ref[...], do_ref[...]
        # scores transposed, (bk, bq): q positions run along the lanes, so
        # the logsumexp and di rows broadcast down the sublanes
        st = lax.dot_general(k, q, _NT, preferred_element_type=_F32) * scale
        if masked:
            st = jnp.where(_causal_keep(st.shape, j * bk, i * bq, False), st,
                           _MASK)
        pt = jnp.exp(st - lse_ref[:1, :])
        dv_sc[...] += lax.dot_general(pt.astype(do.dtype), do, _NN,
                                      preferred_element_type=_F32)
        dpt = lax.dot_general(v, do, _NT, preferred_element_type=_F32)
        dst = pt * (dpt - di_ref[:1, :])
        dk_sc[...] += lax.dot_general(dst.astype(q.dtype), q, _NN,
                                      preferred_element_type=_F32)

    _blocks_of_tile(causal, i * bq, bq, j * bk, bk, step)

    @pl.when(jnp.logical_and(g == group - 1, i == nq - 1))
    def _out():
        dk_ref[...] = (dk_sc[...] * scale).astype(dk_ref.dtype)
        dv_ref[...] = dv_sc[...].astype(dv_ref.dtype)


def _backward_dkv(q, k, v, do, lse, di, *, causal: bool, scale: float,
                  bq: int, bk: int, interpret: bool):
    b, h, s, hd = q.shape
    kvh = k.shape[1]
    group = h // kvh
    nq, nk = s // bq, s // bk

    def q_block(j, i):
        return jnp.maximum(i, _first_q(j, bq, bk)) if causal else i

    def q_map(b_, kv, j, g, i):
        return b_, kv * group + g, q_block(j, i), 0

    def row_map(b_, kv, j, g, i):
        return b_, kv * group + g, 0, q_block(j, i)

    def kv_map(b_, kv, j, g, i):
        return b_, kv, j, 0

    kernel = functools.partial(_dkv_kernel, scale=scale, causal=causal,
                               bq=bq, bk=bk, nq=nq, group=group)
    flops = 8 * b * h * s * s * hd // (2 if causal else 1)
    return pl.pallas_call(
        kernel,
        grid=(b, kvh, nk, group, nq),
        in_specs=[
            pl.BlockSpec((None, None, bq, hd), q_map),
            pl.BlockSpec((None, None, bk, hd), kv_map),
            pl.BlockSpec((None, None, bk, hd), kv_map),
            pl.BlockSpec((None, None, bq, hd), q_map),
            pl.BlockSpec((None, None, SUBLANES, bq), row_map),
            pl.BlockSpec((None, None, SUBLANES, bq), row_map),
        ],
        out_specs=[pl.BlockSpec((None, None, bk, hd), kv_map),
                   pl.BlockSpec((None, None, bk, hd), kv_map)],
        out_shape=[jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype)],
        scratch_shapes=[pltpu.VMEM((bk, hd), _F32),
                        pltpu.VMEM((bk, hd), _F32)],
        compiler_params=_params("parallel", "parallel", "parallel",
                                "arbitrary", "arbitrary",
                                vmem=_vmem_bytes(bq, bk, hd)),
        cost_estimate=pl.CostEstimate(
            flops=flops, transcendentals=flops // (8 * hd),
            bytes_accessed=(2 * q.size * nk + 4 * k.size) * q.dtype.itemsize),
        interpret=interpret,
        name=_NAME,
    )(q, k, v, do, lse, di)


# --------------------------------------------------------------------------
# backward: dQ
# --------------------------------------------------------------------------
def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, di_ref, dq_ref, dq_sc,
               *, scale: float, causal: bool, bq: int, bk: int, nk: int):
    i, j = pl.program_id(2), pl.program_id(3)

    @pl.when(j == 0)
    def _init():
        dq_sc[...] = jnp.zeros_like(dq_sc)

    def step(masked: bool):
        k = k_ref[...]
        s = lax.dot_general(q_ref[...], k, _NT,
                            preferred_element_type=_F32) * scale
        if masked:
            s = jnp.where(_causal_keep(s.shape, i * bq, j * bk, True), s,
                          _MASK)
        p = jnp.exp(s - lse_ref[0][:, None])
        dp = lax.dot_general(do_ref[...], v_ref[...], _NT,
                             preferred_element_type=_F32)
        ds = p * (dp - di_ref[0][:, None])
        dq_sc[...] += lax.dot_general(ds.astype(k.dtype), k, _NN,
                                      preferred_element_type=_F32)

    _blocks_of_tile(causal, i * bq, bq, j * bk, bk, step)

    @pl.when(j == nk - 1)
    def _out():
        dq_ref[...] = (dq_sc[...] * scale).astype(dq_ref.dtype)


def _backward_dq(q, k, v, do, lse, di, *, causal: bool, scale: float,
                 bq: int, bk: int, interpret: bool):
    b, h, s, hd = q.shape
    group = h // k.shape[1]
    nq, nk = s // bq, s // bk

    def kv_block(i, j):
        return jnp.minimum(j, _last_k(i, bq, bk)) if causal else j

    def q_map(b_, h_, i, j):
        return b_, h_, i, 0

    def row_map(b_, h_, i, j):
        return b_, h_, 0, i

    def kv_map(b_, h_, i, j):
        return b_, h_ // group, kv_block(i, j), 0

    kernel = functools.partial(_dq_kernel, scale=scale, causal=causal,
                               bq=bq, bk=bk, nk=nk)
    flops = 6 * b * h * s * s * hd // (2 if causal else 1)
    return pl.pallas_call(
        kernel,
        grid=(b, h, nq, nk),
        in_specs=[
            pl.BlockSpec((None, None, bq, hd), q_map),
            pl.BlockSpec((None, None, bk, hd), kv_map),
            pl.BlockSpec((None, None, bk, hd), kv_map),
            pl.BlockSpec((None, None, bq, hd), q_map),
            pl.BlockSpec((None, None, SUBLANES, bq), row_map),
            pl.BlockSpec((None, None, SUBLANES, bq), row_map),
        ],
        out_specs=pl.BlockSpec((None, None, bq, hd), q_map),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, hd), _F32)],
        compiler_params=_params("parallel", "parallel", "parallel",
                                "arbitrary", vmem=_vmem_bytes(bq, bk, hd)),
        cost_estimate=pl.CostEstimate(
            flops=flops, transcendentals=flops // (6 * hd),
            bytes_accessed=(3 * q.size + 2 * k.size * nq) * q.dtype.itemsize),
        interpret=interpret,
        name=_NAME,
    )(q, k, v, do, lse, di)


# --------------------------------------------------------------------------
# the differentiable op
# --------------------------------------------------------------------------
@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash(q, k, v, causal, scale, blocks, interpret):
    return _flash_fwd(q, k, v, causal, scale, blocks, interpret)[0]


def _flash_fwd(q, k, v, causal, scale, blocks, interpret):
    bq, bk, _, _ = blocks
    with jax.named_scope("attention"):
        o, lse = _forward(q, k, v, causal=causal, scale=scale, bq=bq, bk=bk,
                          interpret=interpret)
    return o, (q, k, v, o, lse)


def _flash_bwd(causal, scale, blocks, interpret, res, do):
    q, k, v, o, lse = res
    _, _, bq, bk = blocks
    with jax.named_scope("attention"):
        di = jnp.sum(o.astype(_F32) * do.astype(_F32), axis=-1)
        di = jnp.broadcast_to(di[:, :, None, :], lse.shape)
        kw = dict(causal=causal, scale=scale, bq=bq, bk=bk,
                  interpret=interpret)
        dk, dv = _backward_dkv(q, k, v, do, lse, di, **kw)
        dq = _backward_dq(q, k, v, do, lse, di, **kw)
    return dq, dk, dv


_flash.defvjp(_flash_fwd, _flash_bwd)


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool, softmax_scale: float | None = None,
                    blocks: tuple[int, int, int, int] | None = None,
                    interpret: bool = False) -> jax.Array:
    """Self-attention over positions ``0..S-1``.  q: (B, H, S, hd); k, v:
    (B, KVh, S, hd) with ``KVh | H``; returns (B, H, S, hd) in q's dtype.

    ``blocks`` (forward q, k rows; backward q, k rows) defaults to
    :func:`block_sizes` of the shapes."""
    s, hd = q.shape[2], q.shape[3]
    scale = softmax_scale if softmax_scale is not None else hd ** -0.5
    return _flash(q, k, v, causal, float(scale),
                  tuple(blocks or block_sizes(s, hd)), interpret)
