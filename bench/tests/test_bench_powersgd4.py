"""PowerSGD over four devices, at a tiny size on the CPU: the four-chip
cell's plan (``smollm2-1.7b.syncsgd.4chip``) with PowerSGD rank 4 over all
axes in place of the all-reduce.  A sound run reads correct, and one with
the exchange between devices left out does not."""
import json
import os

import pytest

from bench.tests.conftest import (BENCH, TINY, TINY_LIMITS, add_cell,
                                  copy_bench, drive)


@pytest.fixture(scope="module")
def psgd4_root(tmp_path_factory):
    root = copy_bench(str(tmp_path_factory.mktemp("psgd4_root")))
    with open(os.path.join(BENCH, "configs", "smollm2-1.7b.json")) as f:
        cfg = dict(json.load(f), **TINY)
    with open(os.path.join(root, "bench", "configs", "tiny.json"),
              "w") as f:
        json.dump(cfg, f)
    with open(os.path.join(BENCH, "workloads",
                           "smollm2-1.7b.syncsgd.4chip.json")) as f:
        w = json.load(f)
    w["plan"].update(compression="powersgd", powersgd_rank=4,
                     compress_axes="all", comm="auto")
    # one bucket per layer and one for the tail, as in the tiny tree of
    # conftest.py: no PowerSGD matrix has fewer rows than the rank
    w["plan"]["bucket_mb"] = 0.0572
    w.update(config="tiny", seq=64, limits=TINY_LIMITS)
    add_cell(root, "tiny.powersgd4", w)
    return root


@pytest.mark.parametrize("fault,correct", [("none", True),
                                           ("no_exchange", False)])
def test_powersgd_on_four_devices(psgd4_root, tmp_path, fault, correct):
    res = drive(psgd4_root, "tiny.powersgd4", fault, 4, cache=str(tmp_path))
    assert res["correct"] is correct, res["compared"]
    assert res["device"]["count"] == 4
