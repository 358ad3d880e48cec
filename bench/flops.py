"""Work counts the benchmark divides by measured time.

Kept with the benchmark so that no change to the program can change them.
Every count is of what the algorithm needs, from the configuration's shapes,
whatever implements it.
"""
from __future__ import annotations

import math


def matmul_params(cfg: dict) -> int:
    """Parameters that take part in a matrix multiplication, per token:
    every layer's projections and the LM head (the tied embedding counts
    once, as the head; the embedding lookup is a gather)."""
    d, hd = cfg["d_model"], cfg["head_dim"]
    q = cfg["n_heads"] * hd
    kv = cfg["n_kv_heads"] * hd
    per_layer = d * q + 2 * d * kv + q * d + 3 * d * cfg["d_ff"]
    return cfg["n_layers"] * per_layer + cfg["vocab"] * d


def param_count(cfg: dict) -> int:
    """Every trained parameter: the matmul parameters, two RMSNorm scales
    per layer and the final norm (plus an untied head where there is one)."""
    n = matmul_params(cfg) + (2 * cfg["n_layers"] + 1) * cfg["d_model"]
    if not cfg["tie_embeddings"]:
        n += cfg["vocab"] * cfg["d_model"]
    return n


def train_flops_per_token(cfg: dict, seq: int) -> float:
    """Model FLOPs per trained token: 6 x matmul parameters (forward 2,
    backward 4) plus causal attention, 6 x layers x seq x heads x head_dim
    (QK^T and PV, forward and backward, half of the square kept by the
    causal mask).  Recomputation under remat is not counted."""
    attn = 6 * cfg["n_layers"] * seq * cfg["n_heads"] * cfg["head_dim"]
    return 6.0 * matmul_params(cfg) + attn


LANES = 128


def powersgd_matrix_shape(n: int) -> tuple[int, int]:
    """PowerSGD's bucket matrix: near-square, columns a multiple of the
    lane width; the bucket is zero-padded to rows x cols."""
    cols = int(math.isqrt(n))
    cols = max(LANES, -(-cols // LANES) * LANES)
    cols = min(cols, n)
    return -(-n // cols), cols


def powersgd_work(rows: int, cols: int, rank: int) -> tuple[float, float]:
    """(FLOPs, HBM bytes) of one bucket's three PowerSGD matmuls in fp32:
    P = M Q, Q' = M^T P_hat and M_hat = P_hat Q'^T.  Each reads or writes
    the rows x cols matrix once; the thin factors are read or written
    once per matmul that touches them."""
    flops = 3 * 2.0 * rows * cols * rank
    big = 4.0 * rows * cols
    thin_p, thin_q = 4.0 * rows * rank, 4.0 * cols * rank
    bytes_ = (big + thin_q + thin_p          # M Q: read M, Q; write P
              + big + thin_p + thin_q        # M^T P_hat: read M, P; write Q'
              + thin_p + thin_q + big)       # P_hat Q'^T: read both; write M
    return flops, bytes_


def roofline_seconds(flops: float, bytes_: float, peak: dict
                     ) -> tuple[float, str]:
    """The least time the chip could take, and which bound sets it."""
    t_flops = flops / peak["bf16_flops_per_s"]
    t_bytes = bytes_ / peak["hbm_bytes_per_s"]
    return (t_bytes, "hbm") if t_bytes >= t_flops else (t_flops, "flops")
