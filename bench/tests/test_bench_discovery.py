"""Cells of other kinds added as new files alone: a plan without ZeRO-1
(the readers take the optimizer's per-leaf state) or without overlap (the
ZeRO-1 layout in the parameters' own order), and another compressor (its
reference found by name under ``bench/compressors``)."""
import json
import os

import pytest

from bench.tests.conftest import BENCH, TINY_LIMITS, add_cell, drive


def _cell(root: str, name: str, src: str, chips: int, limits=None,
          **plan) -> None:
    with open(os.path.join(root, "bench", "workloads",
                           src + ".json")) as f:
        w = json.load(f)
    w["plan"].update(plan)
    add_cell(root, name, dict(w, chips=chips, limits=limits or w["limits"]))


@pytest.mark.parametrize("name,plan,fault,correct", [
    ("nozero1", {"zero1": False}, "none", True),
    ("nozero1.fault", {"zero1": False}, "half_batch", False),
    ("serial", {"overlap": False}, "none", True),
])
def test_cell_of_another_plan(tiny_root, tmp_path, name, plan, fault,
                              correct):
    _cell(tiny_root, "tiny." + name, "tiny.syncsgd4", 4, **plan)
    res = drive(tiny_root, "tiny." + name, fault, devices=4,
                cache=str(tmp_path))
    assert res["correct"] is correct, res["compared"]


def test_cell_with_another_compressor(tiny_root, tmp_path):
    assert os.path.isfile(os.path.join(BENCH, "compressors", "signsgd.py"))
    # signs of near-zero elements differ between the program's bfloat16
    # buckets and the float32 reference, so the element-wise grad_err is
    # not this compressor's number; the norms are
    limits = {k: TINY_LIMITS[k] for k in ("loss_gap", "grad_gap",
                                          "delta_gap")}
    _cell(tiny_root, "tiny.signsgd", "tiny.powersgd", 1, limits,
          compression="signsgd")
    res = drive(tiny_root, "tiny.signsgd", cache=str(tmp_path))
    assert res["correct"] is True, res["compared"]
