"""The benchmark's own copy of the token batches a cell trains on, made from
``--seed`` without the program, for the reference.

It follows the synthetic LM data the program's pipeline serves: a noisy
fixed random permutation chain, ``tok[t+1] = perm[tok[t]]`` with
probability ``1 - noise`` and uniform otherwise (or uniform throughout for
``kind="uniform"``); batch ``step`` is a pure function of ``(seed, step)``.
Labels are the tokens shifted by one, every position counted.  The
reference trains on these, so a feed that delivers other tokens, labels
or rows than the seed's reads as a gap in ``correct``.

A cell's ``data`` block (``bench/workloads/<cell>.json``) gives ``kind``
and ``noise``; the feed is built from the same block.
"""
from __future__ import annotations

import numpy as np


def batch(vocab: int, seq: int, rows: int, seed: int, step: int,
          kind: str, noise: float) -> dict[str, np.ndarray]:
    """The global batch at ``step``: tokens and labels, (rows, seq) int32."""
    rng = np.random.default_rng((seed * 1_000_033 + step) * 131)
    if kind == "uniform":
        toks = rng.integers(0, vocab, (rows, seq + 1), dtype=np.int64)
    elif kind == "markov":
        perm = np.random.default_rng(seed + 1_000_003).permutation(vocab)
        toks = np.empty((rows, seq + 1), np.int64)
        toks[:, 0] = rng.integers(0, vocab, rows)
        jump = rng.random((rows, seq)) < noise
        rand = rng.integers(0, vocab, (rows, seq))
        for t in range(seq):
            toks[:, t + 1] = np.where(jump[:, t], rand[:, t],
                                      perm[toks[:, t]])
    else:
        raise ValueError(f"data kind {kind!r}")
    return {"tokens": toks[:, :-1].astype(np.int32),
            "labels": toks[:, 1:].astype(np.int32)}


def batches(cfg: dict, workload: dict, rows: int, seed: int, n: int
            ) -> list[dict[str, np.ndarray]]:
    """The first ``n`` global batches of ``rows`` sequences for ``seed``."""
    d = workload["data"]
    return [batch(cfg["vocab"], workload["seq"], rows, seed, s, d["kind"],
                  d["noise"]) for s in range(n)]
