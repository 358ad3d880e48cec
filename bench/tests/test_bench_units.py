"""Work counts, the peaks table, discovery by name and the trace
reduction, checked without a chip."""
import json
import os

import pytest

from bench import compare, flops, spec, trace_reduce
from bench.tests.conftest import ROOT, add_cell, copy_bench

SP = spec.Spec()


def _cfg(name):
    return SP.config(name)


def test_smollm2_360m_counts():
    cfg = _cfg("smollm2-360m")
    arch = SP.arch(cfg)
    # per layer: q,o 960x960, k,v 960x320, gate/up/down 960x2560
    per_layer = 2 * 960 * 960 + 2 * 960 * 320 + 3 * 960 * 2560
    assert per_layer == 9_830_400
    assert arch.matmul_params(cfg) == 32 * per_layer + 49152 * 960
    assert arch.param_count(cfg) == 361_821_120          # 361.8M
    attn = 6 * 32 * 2048 * 15 * 64
    assert arch.train_flops_per_token(cfg, 2048) == \
        6 * 361_758_720 + attn == 2_548_039_680           # 2.55 GFLOP


def test_smollm2_1p7b_cut_counts():
    cfg = _cfg("smollm2-1.7b")
    arch = SP.arch(cfg)
    assert cfg["n_layers"] == 10 and cfg["reduced"] == {"n_layers": 24}
    per_layer = 4 * 2048 * 2048 + 3 * 2048 * 8192
    assert arch.param_count(cfg) == \
        10 * (per_layer + 2 * 2048) + 49152 * 2048 + 2048 == 771_794_944
    assert arch.train_flops_per_token(cfg, 2048) == \
        6 * (10 * per_layer + 49152 * 2048) + 6 * 10 * 2048 * 32 * 64 \
        == 4_882_169_856                                  # 4.88 GFLOP


def test_powersgd_work_of_one_bucket():
    n = 14_749_440                      # one bucket of the 360M cell
    rows, cols = flops.powersgd_matrix_shape(n)
    assert (rows, cols) == (3841, 3840)
    f, b = flops.powersgd_work(rows, cols, 4)
    assert f == 3 * 2 * 3841 * 3840 * 4
    assert b == 4 * (3 * 3841 * 3840 + 3 * 3841 * 4 + 3 * 3840 * 4)
    t, bound = flops.roofline_seconds(f, b, SP.peak("TPU v5 lite"))
    assert bound == "hbm" and t == pytest.approx(b / 819e9)


def test_matrix_shape_matches_the_program():
    from bench import program
    program.add_src_path()
    from repro.core.compression.powersgd import matrix_shape
    for n in (64, 960, 16448, 14_749_440, 52_101_120):
        assert flops.powersgd_matrix_shape(n) == matrix_shape(n)


def test_peaks_refuse_an_unknown_kind():
    assert SP.peak("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    assert SP.peak("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(spec.SpecError):
        SP.peak("TPU v4")


def test_benchmark_entries_have_their_files():
    b = SP.benchmark
    for c in b["configs"]:
        cfg = json.load(open(os.path.join(ROOT, c["file"])))
        assert cfg["name"] == c["name"] and cfg["source"] == c["source"]
        assert sorted(cfg["reduced"]) == sorted(c["reduced"])
        assert callable(SP.arch(cfg).loss_sum), c["name"]
    for w in b["workloads"]:
        wl = SP.workload(w["name"])
        assert wl["limits"] and set(wl["limits"]) <= set(
            compare.NUMBERS), w["name"]
        assert all(v is not None for v in wl["limits"].values()), w["name"]
        assert wl["why"] == w["why"]
        assert callable(SP.compressor(wl).apply), w["name"]
    for m in b["per_layer"]:
        r = SP.reader(m["name"])
        assert (r.LAYER, r.UNIT, r.MOVES) == \
            (m["layer"], m["unit"], m["moves"])


def test_new_files_are_found_by_name(tmp_path):
    """A configuration, a cell, a per-layer metric and a compressor's
    reference dropped in as files, with entries in BENCHMARK.json: found
    with no other file edited."""
    root = copy_bench(str(tmp_path))
    cfg = dict(_cfg("smollm2-360m"), name="other-model", n_layers=4)
    with open(os.path.join(root, "bench", "configs", "other-model.json"),
              "w") as f:
        json.dump(cfg, f)
    w = SP.workload("smollm2-360m.powersgd.1chip")
    add_cell(root, "other-model.cell", dict(w, config="other-model"))
    with open(os.path.join(root, "bench", "metrics", "steps_seen.py"),
              "w") as f:
        f.write('LAYER = "train step"\nUNIT = "steps"\n'
                'MOVES = "tokens_per_s"\n\n\n'
                'def read(trace, record):\n    return record["steps"]\n')
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        b = json.load(f)
    b["per_layer"].append({"name": "steps_seen", "unit": "steps",
                           "better": "higher", "source": "host_clock",
                           "layer": "train step", "moves": "tokens_per_s"})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(b, f)
    with open(os.path.join(root, "bench", "compressors", "qsgd.py"),
              "w") as f:
        f.write("def init(key, cfg, workload, arch):\n    return ()\n")
    sp = spec.Spec(os.path.join(root, "bench"))
    assert sp.config(sp.workload("other-model.cell")["config"])[
        "n_layers"] == 4
    assert sp.compressor({"plan": {"compression": "qsgd"}}).init(
        None, {}, {}, None) == ()
    names = [m["name"] for m in sp.per_layer("other-model.cell")]
    assert "steps_seen" in names and "compress_kernel_ms" not in names
    assert sp.reader("steps_seen").read({}, {"steps": 5}) == 5
    with pytest.raises(spec.SpecError):
        sp.workload("not-a-cell")


def test_interval_arithmetic():
    u = trace_reduce.union([(0, 2), (1, 3), (5, 6)])
    assert u == [(0, 3), (5, 6)]
    assert trace_reduce.total(u) == 4
    assert trace_reduce.subtract([(0, 10)], [(1, 2), (4, 6)]) == \
        [(0, 1), (2, 4), (6, 10)]
    assert trace_reduce.subtract([(0, 3), (5, 6)], [(2, 5.5)]) == \
        [(0, 2), (5.5, 6)]


def test_op_classes():
    assert trace_reduce.kind_of("all-reduce.12", {})[0] == "collective"
    assert trace_reduce.kind_of("all-gather-start.3", {})[0] == \
        "collective"
    assert trace_reduce.kind_of("fusion.7", {})[0] == "compute"
