"""The numbers that decide ``correct``: gaps between a run's readings and
the reference's, each against a limit of the cell's own.

- ``loss_gap``: the largest relative gap of a step's loss over the first
  steps;
- ``grad_gap``: the worst leaf's gap between the norm of the first
  gradient as the optimizer takes it and the reference's;
- ``delta_gap``: the worst leaf's gap between the norm of the parameters'
  change over those steps and the reference's;
- ``grad_err``: the worst leaf's norm of the difference between the first
  gradient and the reference's.  Rounding that is random from element to
  element cancels in a norm but not in a difference, so this is the
  number that tells a lower precision from the configured one where the
  gradient is not compressed.

A leaf's gap is measured against the reference's norm of that leaf or of
the median leaf, whichever is larger.  Leaves whose reference gradient is
under a thousandth of the median leaf's move by round-off alone and are
left out of both leaf numbers.
"""
from __future__ import annotations

import math
import statistics

import numpy as np

NUMBERS = ("loss_gap", "grad_gap", "delta_gap", "grad_err")
NEGLIGIBLE = 1e-3


def _worst(prog: dict, ref: dict, keep: list, scale: dict | None = None
           ) -> tuple[float, str]:
    """The worst leaf's |prog - ref| over the larger of ``scale`` (by
    default ``ref``) at that leaf and at the median leaf."""
    if set(prog) != set(ref):
        missing = sorted(set(ref) ^ set(prog))[:5]
        raise ValueError(f"leaf sets differ, e.g. {missing}")
    scale = ref if scale is None else scale
    med = statistics.median(scale[k] for k in keep)
    worst, where = 0.0, ""
    for k in keep:
        g = abs(prog[k] - ref[k]) / max(scale[k], med)
        if not math.isfinite(g):
            return math.inf, k
        if g > worst:
            worst, where = g, k
    return worst, where


def leaf_diff_norms(a: dict, b: dict, labels) -> dict:
    """{label: norm of a - b} per leaf of two gradients held as host arrays
    by path: per layer for a leaf that ``labels`` holds by layer
    (``path#layer``), whole otherwise."""
    out = {}
    for p, x in a.items():
        d = x.astype(np.float64) - b[p]
        if p not in labels:
            n = np.sqrt(np.sum(d * d, axis=tuple(range(1, d.ndim))))
            out.update({f"{p}#{l}": float(n[l]) for l in range(d.shape[0])})
        else:
            out[p] = float(np.sqrt(np.sum(d * d)))
    return out


def numbers(prog: dict, ref: dict) -> dict:
    """Gaps of readings ``prog`` against ``ref`` (both as returned by
    ``Reference.run``), with the leaf each worst gap was found at;
    ``grad_err`` only where both hold the gradient's leaves."""
    med = statistics.median(ref["grad"].values())
    keep = sorted(k for k, v in ref["grad"].items() if v >= NEGLIGIBLE * med)
    loss = max(abs(p - r) / abs(r) if math.isfinite(p) else math.inf
               for p, r in zip(prog["losses"], ref["losses"]))
    grad, g_at = _worst(prog["grad"], ref["grad"], keep)
    delta, d_at = _worst(prog["delta"], ref["delta"], keep)
    out = {"loss_gap": loss, "grad_gap": grad, "delta_gap": delta,
           "grad_gap_at": g_at, "delta_gap_at": d_at,
           "leaves_compared": len(keep), "leaves": len(ref["grad"])}
    if "grad_vec" in prog and "grad_vec" in ref:
        out["grad_err"], out["grad_err_at"] = _worst(
            leaf_diff_norms(prog["grad_vec"], ref["grad_vec"], ref["grad"]),
            {k: 0.0 for k in ref["grad"]}, keep, scale=ref["grad"])
    return out


def verdict(nums: dict, limits: dict) -> bool:
    """True when every number the cell compares (those it gives a limit)
    is within its limit; a cell with no limit set is never correct."""
    return bool(limits) and all(lim is not None and nums[k] <= lim
                                for k, lim in limits.items())
