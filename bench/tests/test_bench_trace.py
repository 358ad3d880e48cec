"""The trace reduction on a hand-built trace (every number known) and on a
small trace recorded on one v5e (``bench/testdata``)."""
import glob
import os

import pytest

from bench import trace_reduce
from bench.tests.conftest import BENCH


def _xspace(planes: list[tuple[str, dict]]):
    """A ProfileData from [(plane, {line: [(name, start_us, dur_us)]})]."""
    from jax.profiler import ProfileData
    out = []
    for pid, (plane, lines) in enumerate(planes, 1):
        names = sorted({e[0] for evs in lines.values() for e in evs})
        ids = {n: i + 1 for i, n in enumerate(names)}
        body = []
        for lid, (line, evs) in enumerate(lines.items(), 1):
            ev = " ".join(f"events {{ metadata_id: {ids[n]} "
                          f"offset_ps: {int(t * 1e6)} "
                          f"duration_ps: {int(d * 1e6)} }}"
                          for n, t, d in evs)
            body.append(f'lines {{ id: {lid} name: "{line}" '
                        f'timestamp_ns: 0 {ev} }}')
        meta = " ".join(f'event_metadata {{ key: {i} value {{ id: {i} '
                        f'name: "{n}" }} }}' for n, i in ids.items())
        out.append(f'planes {{ id: {pid} name: "{plane}" '
                   f'{" ".join(body)} {meta} }}')
    return ProfileData.from_serialized_xspace(
        ProfileData.text_proto_to_serialized_xspace("\n".join(out)))


KERNEL = ("%encode.7 = f32[64,4] custom-call(f32[64,64] %m), "
          "custom_call_target=\\\"tpu_custom_call\\\"")


def test_hand_built_trace():
    ops0 = [("%fusion.1 = f32[8] fusion()", 0, 400),
            ("%all-reduce.2 = f32[8] all-reduce()", 400, 100),
            (KERNEL, 600, 100),
            ("%while.9 = f32[8] while()", 800, 150),
            ("%fusion.3 = f32[8] fusion()", 800, 100)]
    ops1 = [("%fusion.1 = f32[8] fusion()", 0, 500),
            ("%all-reduce.2 = f32[8] all-reduce()", 500, 100),
            (KERNEL, 600, 100),
            ("%fusion.3 = f32[8] fusion()", 800, 200)]
    host = [("bench.input_wait", 0, 10), ("bench.dispatch", 10, 5),
            ("bench.block", 15, 985)]
    pd = _xspace([
        ("/device:TPU:0", {"XLA Ops": ops0, "Async XLA Ops": [
            ("%all-gather-start.4 = f32[8] all-gather-start()", 300, 150),
            ("%copy-start.5 = f32[8] copy-start()", 0, 900)]}),
        ("/device:TPU:1", {"XLA Ops": ops1}),
        ("/host:CPU", {"python": host})])
    r = trace_reduce.reduce(pd)
    us = 1e-6
    assert r["window_s"] == pytest.approx(1000 * us)
    assert r["n_devices"] == 2
    # busy: dev0 0-500, 600-700, 800-950 = 750; dev1 0-700, 800-1000 = 900
    assert r["busy_s"] == pytest.approx(825 * us)
    # collectives: dev0 300-500 (async all-gather in flight, then the
    # all-reduce) = 200; dev1 500-600 = 100
    assert r["collective_s"] == pytest.approx(150 * us)
    # exposed: dev0 400-500, dev1 500-600
    assert r["exposed_collective_s"] == pytest.approx(100 * us)
    assert r["kernel_s"] == {"encode": pytest.approx(100 * us)}
    ops = dict(r["top_ops"])
    assert ops["fusion.1"] == pytest.approx(450 * us)
    assert ops["fusion.3"] == pytest.approx(150 * us)
    assert "while.9" not in ops          # a loop holds its body's events
    # dev0 idle: 500-600, 700-800, 950-1000, all inside bench.block
    assert [g[0] for g in r["idle_gaps"]] == ["bench.block"] * 3
    assert sum(g[1] for g in r["idle_gaps"]) == pytest.approx(250 * us)


def test_recorded_chip_trace():
    paths = glob.glob(os.path.join(BENCH, "testdata", "*.xplane.pb"))
    assert paths, "bench/testdata holds a trace recorded on a v5e"
    from jax.profiler import ProfileData
    r = trace_reduce.reduce(ProfileData.from_file(paths[0]))
    assert r["n_devices"] == 1
    assert 0 < r["busy_s"] < r["window_s"]
    assert set(r["kernel_s"]) == {"_lambda_"}, "the Pallas kernels"
    assert r["collective_s"] == 0
    assert {g[0] for g in r["idle_gaps"]} <= set(
        trace_reduce.HOST_SPANS) | {"none"}
