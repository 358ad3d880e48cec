"""PowerSGD (Vogels et al., 2019) with error memory, for the reference.

The gradient is cut into DDP buckets (``bench.reference.buckets``).  Each
bucket plus its error memory is zero-padded to a near-square matrix M;
P = M Q, P_hat = an orthonormal basis of P, Q' = M^T P_hat,
M_hat = P_hat Q'^T, error = M - M_hat, Q' kept for the next step.  Every
operation is linear in M but the orthonormalisation, which sees the mean
over replicas, so the mean gradient and one error memory (the replicas'
mean) give the replicated result on any number of chips.

Starting factors Q come from the seed (``bench.weights.powersgd_q``);
``seed_program`` puts the same ones into the program's state.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from bench import flops, weights
from bench.reference import buckets, map_buckets


def _shapes(cfg: dict, workload: dict, arch) -> list[tuple[int, int]]:
    return [flops.powersgd_matrix_shape(sum(leaf[2] for leaf in b))
            for b in buckets(cfg, workload, arch)]


def init(key, cfg: dict, workload: dict, arch):
    rank = workload["plan"]["powersgd_rank"]
    return tuple(
        (weights.powersgd_q(key, i, cols, rank),
         jnp.zeros((sum(leaf[2] for leaf in bkt),), jnp.float32))
        for i, (bkt, (_, cols)) in enumerate(zip(
            buckets(cfg, workload, arch), _shapes(cfg, workload, arch))))


def apply(grads: dict, state, cfg: dict, workload: dict, arch):
    hp = jax.lax.Precision.HIGHEST

    def one(flat, st):
        q, err = st
        n = flat.shape[0]
        rows, cols = flops.powersgd_matrix_shape(n)
        mflat = flat + err
        m = jnp.pad(mflat, (0, rows * cols - n)).reshape(rows, cols)
        p_hat, _ = jnp.linalg.qr(jnp.dot(m, q, precision=hp))
        q_new = jnp.dot(m.T, p_hat, precision=hp)
        m_hat = jnp.dot(p_hat, q_new.T, precision=hp).reshape(-1)[:n]
        return m_hat, (q_new, mflat - m_hat)

    return map_buckets(one, grads, state, cfg, workload, arch)


def seed_program(key, agg, workload: dict):
    """The program's per-bucket PowerSGD states ``agg`` (a leading device
    axis on each) with Q set to the seed's starting factors."""
    rank = workload["plan"]["powersgd_rank"]
    return tuple(
        st._replace(q=jnp.broadcast_to(
            weights.powersgd_q(key, b, st.q.shape[-2], rank)[None],
            st.q.shape))
        for b, st in enumerate(agg))
