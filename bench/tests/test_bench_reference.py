"""The reference against the program at a tiny size on the CPU, and the
control (the reference in float8) against the reference."""
import os

import numpy as np

from bench import compare, data, weights
from bench.tests.conftest import TINY_LIMITS, drive


def _spec(root):
    from bench import spec
    return spec.Spec(os.path.join(root, "bench"))


def test_four_devices_sound_run_is_correct(tiny_root, tmp_path):
    res = drive(tiny_root, "tiny.syncsgd4", devices=4, cache=str(tmp_path))
    assert res["correct"] is True, res["compared"]
    assert res["device"]["count"] == 4


def _control_numbers(root, cell):
    """The float8 control's numbers and the reference's own, for ``cell``
    at a seed over 32 bits."""
    from bench.reference import Reference
    sp = _spec(root)
    w, cfg = sp.workload(cell), sp.config("tiny")
    arch, comp = sp.arch(cfg), sp.compressor(w)
    batches = data.batches(cfg, w, 2, 2**35 + 3, 3)
    key = weights.seed_key(2**35 + 3)
    ref = Reference(cfg, w, arch, comp).run(key, batches, keep_grad=True)
    ctl = Reference(cfg, w, arch, comp, precision="fp8").run(
        key, batches, keep_grad=True)
    return compare.numbers(ctl, ref), compare.numbers(ref, ref)


def test_control_in_float8_is_not_correct(tiny_root):
    nums, same = _control_numbers(tiny_root, "tiny.powersgd")
    assert not compare.verdict(nums, TINY_LIMITS), nums
    assert nums["grad_err"] > 2 * TINY_LIMITS["grad_err"], nums
    assert compare.verdict(same, TINY_LIMITS), same


def test_control_in_float8_fails_the_syncsgd_cell(tiny_root):
    """The one-chip syncSGD cell's control: with no compressor the float8
    gradient is caught by ``grad_err``."""
    nums, same = _control_numbers(tiny_root, "tiny.syncsgd1")
    assert nums["grad_err"] > 2 * TINY_LIMITS["grad_err"], nums
    assert compare.verdict(same, TINY_LIMITS), same


def test_data_follows_the_program_feed():
    """The reference's own batches are the ones the program's feed serves,
    for a seed over 32 bits."""
    from bench import program
    program.add_src_path()
    from repro.data.synthetic import DataConfig, batch_at
    for kind in ("markov", "uniform"):
        cfg = DataConfig(vocab=97, seq_len=33, global_batch=3,
                         seed=2**33 + 17, noise=0.15, kind=kind)
        for step in range(3):
            want = batch_at(cfg, step)
            got = data.batch(97, 33, 3, 2**33 + 17, step, kind, 0.15)
            for k in ("tokens", "labels"):
                np.testing.assert_array_equal(got[k], want[k])


def test_buckets_match_the_program(tiny_root):
    """The reference's own DDP bucketing gives the program's layout."""
    from bench import program
    from bench.reference import buckets
    sp = _spec(tiny_root)
    w, cfg = sp.workload("tiny.powersgd"), sp.config("tiny")
    arch = sp.arch(cfg)
    program.add_src_path()
    from repro.train import overlap
    prog = program.Program(cfg, w, arch)
    assert overlap.build_layout(prog.setup).layout.sizes == tuple(
        sum(leaf[2] for leaf in b) for b in buckets(cfg, w, arch))
