"""Pallas TPU grouped matrix multiplication for the drop-free expert layer:
forward, and the two products of its backward, behind one
``jax.custom_vjp``.

Layout: the rows of ``x (M, K)`` are sorted by group (expert), and each
group's rows are padded to whole tiles of ``tm`` rows, so every row tile
belongs to exactly one group.  ``group_tiles (G,)`` says how many tiles
each group holds, in order; the tiles after the last group's hold no rows
and are never visited: the kernels' grid runs over the tiles that hold
rows only (a grid size read from the device), so the work follows the
routing, not the buffer's worst-case size.  Rows of a tile that is not
visited are left unwritten in every output; the caller never reads them.
Each group's matrix ``w[g]`` is fetched once per run of its tiles.

- ``y = x @ w[g]``: a (tm, K) x (K, N) product per tile;
- ``dx = dy @ w[g]^T``: a (tm, N) x (K, N)^T product per tile;
- ``dw[g] = x_g^T @ dy_g``: accumulated in fp32 over the group's tiles,
  written once at its last tile; a group with no rows gets zeros.

The MXU takes the operands' dtype and accumulates in fp32.  Every call is
named ``moe_gmm``, so its Mosaic call is labelled ``moe_gmm`` in a
profile, forward and backward alike.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NAME = "moe_gmm"
_F32 = jnp.float32
_NN = (((1,), (0,)), ((), ()))    # a @ b
_NT = (((1,), (1,)), ((), ()))    # a @ b.T
_TN = (((0,), (0,)), ((), ()))    # a.T @ b
_VMEM = 96 << 20


def tile_groups(group_tiles: jax.Array, n_tiles_max: int):
    """(group of each tile, tiles that hold rows).  Tiles past the last
    group are given the last group, so their (unused) index maps stay in
    range."""
    ends = jnp.cumsum(group_tiles)
    tile = jnp.arange(n_tiles_max, dtype=jnp.int32)
    group = jnp.searchsorted(ends, tile, side="right").astype(jnp.int32)
    return jnp.minimum(group, group_tiles.shape[0] - 1), ends[-1]


def _params():
    return pltpu.CompilerParams(dimension_semantics=("arbitrary",),
                                vmem_limit_bytes=_VMEM)


def _mm_kernel(tg_ref, x_ref, w_ref, o_ref, *, dims):
    del tg_ref
    o_ref[...] = lax.dot_general(x_ref[...], w_ref[...], dims,
                                 preferred_element_type=_F32
                                 ).astype(o_ref.dtype)


def gmm(x, w, group_tiles, *, tm: int, transpose_rhs: bool = False,
        interpret: bool = False):
    """``x (M, K) @ w[g]`` per row tile, ``w`` (G, K, N); with
    ``transpose_rhs`` ``w`` is (G, N, K) and each tile takes ``w[g]^T``.
    Returns (M, N) in x's dtype; rows of tiles past the groups are not
    written."""
    m, k = x.shape
    n = w.shape[1] if transpose_rhs else w.shape[2]
    tile_group, n_tiles = tile_groups(group_tiles, m // tm)
    if transpose_rhs:
        w_spec = pl.BlockSpec((None, n, k), lambda i, tg: (tg[i], 0, 0))
    else:
        w_spec = pl.BlockSpec((None, k, n), lambda i, tg: (tg[i], 0, 0))
    return pl.pallas_call(
        functools.partial(_mm_kernel, dims=_NT if transpose_rhs else _NN),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(n_tiles,),
            in_specs=[pl.BlockSpec((tm, k), lambda i, tg: (i, 0)), w_spec],
            out_specs=pl.BlockSpec((tm, n), lambda i, tg: (i, 0))),
        out_shape=jax.ShapeDtypeStruct((m, n), x.dtype),
        compiler_params=_params(),
        cost_estimate=pl.CostEstimate(
            flops=2 * m * k * n, transcendentals=0,
            bytes_accessed=(m * (k + n) + w.size) * x.dtype.itemsize),
        interpret=interpret,
        name=_NAME,
    )(tile_group, x, w)


def _tgmm_kernel(tg_ref, n_ref, x_ref, dy_ref, o_ref, acc_sc):
    i = pl.program_id(0)
    g = tg_ref[i]
    first = jnp.logical_or(i == 0, tg_ref[jnp.maximum(i - 1, 0)] != g)
    last = jnp.logical_or(i == n_ref[0] - 1,
                          tg_ref[jnp.minimum(i + 1, tg_ref.shape[0] - 1)]
                          != g)

    @pl.when(first)
    def _():
        acc_sc[...] = jnp.zeros_like(acc_sc)

    acc_sc[...] += lax.dot_general(x_ref[...], dy_ref[...], _TN,
                                   preferred_element_type=_F32)

    @pl.when(last)
    def _():
        o_ref[...] = acc_sc[...].astype(o_ref.dtype)


def tgmm(x, dy, group_tiles, *, tm: int, interpret: bool = False):
    """``dw[g] = x_g^T @ dy_g`` over each group's row tiles: x (M, K), dy
    (M, N) -> (G, K, N) in x's dtype; zeros for a group with no rows."""
    m, k = x.shape
    n = dy.shape[1]
    g = group_tiles.shape[0]
    tile_group, n_tiles = tile_groups(group_tiles, m // tm)
    out = pl.pallas_call(
        _tgmm_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(n_tiles,),
            in_specs=[pl.BlockSpec((tm, k), lambda i, tg, nt: (i, 0)),
                      pl.BlockSpec((tm, n), lambda i, tg, nt: (i, 0))],
            out_specs=pl.BlockSpec((None, k, n),
                                   lambda i, tg, nt: (tg[i], 0, 0)),
            scratch_shapes=[pltpu.VMEM((k, n), _F32)]),
        out_shape=jax.ShapeDtypeStruct((g, k, n), x.dtype),
        compiler_params=_params(),
        cost_estimate=pl.CostEstimate(
            flops=2 * m * k * n, transcendentals=0,
            bytes_accessed=(m * (k + n) + g * k * n) * x.dtype.itemsize),
        interpret=interpret,
        name=_NAME,
    )(tile_group, n_tiles[None], x, dy)
    return jnp.where((group_tiles > 0)[:, None, None], out, 0)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def grouped_matmul(x, w, group_tiles, tm: int, interpret: bool = False):
    """``x (M, K)``, rows grouped in whole ``tm``-row tiles as
    ``group_tiles`` says, times ``w (G, K, N)``: (M, N)."""
    return gmm(x, w, group_tiles, tm=tm, interpret=interpret)


def _gm_fwd(x, w, group_tiles, tm, interpret):
    with jax.named_scope("moe"):
        y = gmm(x, w, group_tiles, tm=tm, interpret=interpret)
    return y, (x, w, group_tiles)


def _gm_bwd(tm, interpret, res, dy):
    x, w, group_tiles = res
    with jax.named_scope("moe"):
        dx = gmm(dy, w, group_tiles, tm=tm, transpose_rhs=True,
                 interpret=interpret)
        dw = tgmm(x, dy, group_tiles, tm=tm, interpret=interpret)
    return dx, dw.astype(w.dtype), None


grouped_matmul.defvjp(_gm_fwd, _gm_bwd)
