"""Scaled signSGD with error feedback (Bernstein et al., 2018; Karimireddy
et al., 2019), for the reference, on one replica.

Per DDP bucket (``bench.reference.buckets``): g = bucket + error,
out = sign(g) x mean |g| (a zero counts as positive), error = g - out
when the plan keeps ``error_feedback`` (its default).  The majority vote
over several replicas needs each replica's own gradient, which this
reference does not compute, so it refuses a cell on more than one chip.
"""
from __future__ import annotations

import jax.numpy as jnp

from bench.reference import buckets, map_buckets


def init(key, cfg: dict, workload: dict, arch):
    if workload["chips"] != 1:
        raise ValueError("the signSGD reference holds for one replica")
    return tuple(jnp.zeros((sum(leaf[2] for leaf in b),), jnp.float32)
                 for b in buckets(cfg, workload, arch))


def apply(grads: dict, state, cfg: dict, workload: dict, arch):
    feedback = workload["plan"].get("error_feedback", True)

    def one(flat, err):
        g = flat + err if feedback else flat
        out = jnp.where(g >= 0, 1.0, -1.0) * jnp.mean(jnp.abs(g))
        return out, (g - out if feedback else err)

    return map_buckets(one, grads, state, cfg, workload, arch)
