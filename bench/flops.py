"""Work counts the benchmark divides by measured time.

Kept with the benchmark so that no change to the program can change them.
Every count is of what the algorithm needs, from the configuration's shapes,
whatever implements it.  A model's FLOPs per token are its architecture
module's (``train_flops_per_token`` in ``bench/models/<arch>.py``).
"""
from __future__ import annotations

import math

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
