"""Parameter accounting for the perf model / roofline (configs/base.py hooks).

``param_count`` is exact-by-construction: it abstractly initializes the real
model (tp=1, so no padding inflation) under ``jax.eval_shape`` and sums leaf
sizes.  ``active_only`` subtracts the never-active routed-expert fraction
(MoE): active = total - routed_params · (1 - top_k / n_experts).
"""
from __future__ import annotations

import functools

import jax
import numpy as np

from repro.models.layers import ShardCtx


@functools.lru_cache(maxsize=64)
def _counts(cfg) -> tuple[int, int]:
    """(total_params, routed_expert_params) for tp=1."""
    from repro.models.model import Model
    ctx = ShardCtx()
    shapes, _ = Model(cfg).abstract_init(ctx)
    total = sum(int(np.prod(l.shape)) for l in jax.tree.leaves(shapes))
    routed = 0
    if cfg.moe.n_experts:
        # experts subtree: {moe_,}blocks/moe/experts {gate, up, down}
        stack = "moe_blocks" if cfg.first_k_dense else "blocks"
        sub = shapes[stack]["moe"]["experts"]
        routed = sum(int(np.prod(l.shape)) for l in jax.tree.leaves(sub))
    return total, routed


def param_count(cfg, active_only: bool = False) -> int:
    total, routed = _counts(cfg)
    if active_only and cfg.moe.n_experts:
        frac = cfg.moe.top_k / cfg.moe.n_experts
        return int(total - routed * (1.0 - frac))
    return total


def model_flops(cfg, tokens: int, training: bool = True) -> float:
    """MODEL_FLOPS = 6·N_active·D (train) or 2·N_active·D (inference)."""
    n = param_count(cfg, active_only=True)
    return (6.0 if training else 2.0) * n * tokens
