"""Device time by the program's named scopes (``bench/scopes.py``) on a
hand-built trace whose event metadata carries ``tf_op`` paths (every
number known) and on the trace recorded on one v5e (``bench/testdata``)."""
import glob
import os
import re

import pytest

from bench import scopes, trace_reduce
from bench.tests.conftest import BENCH

US = 1e-6


def _q(s: str) -> str:
    return s.replace("\\", "\\\\").replace('"', '\\"')


def _xspace(planes, paths=None) -> bytes:
    """A serialized XSpace from [(plane, {line: [(name, start_us,
    dur_us)]})].  ``paths[plane][name]`` lists the ``tf_op`` of each
    metadata entry of that name (the first is the one events point to);
    a path given as ``("ref", path)`` is stored as a reference to a stat
    metadata entry named by it, as the profiler does for repeated
    strings."""
    from jax.profiler import ProfileData
    paths = paths or {}
    out = []
    for pid, (plane, lines) in enumerate(planes, 1):
        pp = paths.get(plane, {})
        names = sorted({e[0] for evs in lines.values() for e in evs}
                       | set(pp))
        ids = {n: i + 1 for i, n in enumerate(names)}
        body = []
        for lid, (line, evs) in enumerate(lines.items(), 1):
            ev = " ".join(f"events {{ metadata_id: {ids[n]} "
                          f"offset_ps: {int(t * 1e6)} "
                          f"duration_ps: {int(d * 1e6)} }}"
                          for n, t, d in evs)
            body.append(f'lines {{ id: {lid} name: "{line}" '
                        f'timestamp_ns: 0 {ev} }}')
        stat_meta = {"tf_op": 1}
        metas, extra = [], len(ids)
        for n, i in ids.items():
            for k, path in enumerate(pp.get(n, [None])):
                mid = i if k == 0 else (extra := extra + 1)
                stat = ""
                if isinstance(path, tuple):
                    ref = stat_meta.setdefault(path[1], len(stat_meta) + 1)
                    stat = f"stats {{ metadata_id: 1 ref_value: {ref} }}"
                elif path is not None:
                    stat = (f'stats {{ metadata_id: 1 '
                            f'str_value: "{_q(path)}" }}')
                metas.append(f'event_metadata {{ key: {mid} value {{ '
                             f'id: {mid} name: "{_q(n)}" {stat} }} }}')
        smeta = " ".join(f'stat_metadata {{ key: {i} value {{ id: {i} '
                         f'name: "{_q(n)}" }} }}'
                         for n, i in stat_meta.items())
        out.append(f'planes {{ id: {pid} name: "{plane}" '
                   f'{" ".join(body)} {" ".join(metas)} {smeta} }}')
    return ProfileData.text_proto_to_serialized_xspace("\n".join(out))


def _pd(data: bytes):
    from jax.profiler import ProfileData
    return ProfileData.from_serialized_xspace(data)


FWD = "jit(step_fn)/jvp(attention)/dot_general:"
BWD = "jit(step_fn)/transpose(jvp(attention))/while"
OPS0 = [("%fusion.1 = f32[8] fusion()", 0, 100),
        ("%while.2 = f32[8] while()", 100, 200),
        ("%fusion.3 = f32[8] fusion()", 100, 100),
        ("%fusion.4 = f32[8] fusion()", 200, 80),
        ("%fusion.5 = f32[8] fusion()", 300, 50),
        ("%all-reduce.6 = f32[8] all-reduce()", 350, 100),
        ("%fusion.7 = f32[8] fusion()", 450, 50),
        ("%fusion.8 = f32[8] fusion()", 500, 100),
        ("%fusion.9 = f32[8] fusion()", 600, 50),
        ("%fusion.10 = f32[8] fusion()", 800, 100)]
#: an all-reduce that JAX named: its layout's "(" precedes the opcode's
PSUM = "%psum.14 = bf16[8]{0:T(1024)(128)(2,1)} all-reduce(%x), to_apply=%r"
OPS1 = [("%fusion.1 = f32[8] fusion()", 0, 300),
        (PSUM, 300, 100),
        ("%copy.12 = f32[8] copy()", 400, 20),
        ("%fusion.8 = f32[8] fusion()", 420, 50),
        ("%dynamic-update-slice.11 = f32[8] dynamic-update-slice()", 470, 50),
        ("%copy.13 = f32[8] copy()", 520, 10),
        ("%fusion.7 = f32[8] fusion()", 530, 30),
        ("%fusion.9 = f32[8] fusion()", 560, 40)]
PATHS = {
    "%fusion.1 = f32[8] fusion()": [FWD],
    "%while.2 = f32[8] while()": [BWD + ":"],
    "%fusion.3 = f32[8] fusion()": [BWD + "/body/mul:"],
    "%fusion.4 = f32[8] fusion()": [BWD + "/body/add:"],
    "%fusion.5 = f32[8] fusion()": ["jit(step_fn)/grad_sync/concatenate:"],
    "%all-reduce.6 = f32[8] all-reduce()": ["jit(step_fn)/grad_sync/psum:"],
    PSUM: ["jit(step_fn)/shard_map/grad_sync/psum:"],
    "%fusion.7 = f32[8] fusion()": [
        "jit(step_fn)/grad_sync/optimizer/add:"],
    "%fusion.8 = f32[8] fusion()": [("ref", "jit(step_fn)/optimizer/mul:")],
    "%fusion.9 = f32[8] fusion()": ["jit(step_fn)/reshape:"],
}
HOST = [("bench.input_wait", 0, 5), ("bench.dispatch", 5, 5),
        ("bench.block", 10, 640), ("bench.input_wait", 650, 110),
        ("data.wait", 651, 109), ("bench.dispatch", 760, 20),
        ("bench.block", 780, 220)]
RUNTIME = [("Outer", 0, 1000), ("PjitFunction(step_fn)", 640, 150),
           ("ParseArguments", 700, 20), ("$time sleep", 650, 110)]


def _planes(with_scopes: bool):
    host = HOST if with_scopes else [h for h in HOST if h[0] != "data.wait"]
    planes = [("/device:TPU:0", {"XLA Ops": OPS0}),
              ("/device:TPU:1", {"XLA Ops": OPS1}),
              ("/host:CPU", {"python": host + RUNTIME[:2] + RUNTIME[3:],
                             "main": [RUNTIME[2],
                                      ("PJRT_LoadedExecutable_Execute",
                                       880, 120)]})]
    if with_scopes:
        # a data.wait on another thread labels nothing
        planes[2][1]["producer"] = [("data.wait", 890, 110)]
    paths = {"/device:TPU:0": PATHS, "/device:TPU:1": PATHS} \
        if with_scopes else {}
    return _xspace(planes, paths)


def test_scopes_of_a_hand_built_trace():
    data = _planes(with_scopes=True)
    r = scopes.reduce(_pd(data), scopes.op_paths(data))
    # chip 0: attention 100 + 100 + 80 (the loop's own 20 is not
    # innermost), grad_sync 50 + 100 (the all-reduce), optimizer 50
    # (nested under grad_sync: the innermost wins) + 100 (a referenced
    # path); fusion.10 has no path and no op after it: unscoped.
    # chip 1: attention 300, grad_sync 100 (a JAX-named all-reduce,
    # which trace_reduce.kind_of takes for compute), optimizer
    # 50 + 30 and the 60 of the two ops without a path between them;
    # copy.12, between grad_sync and optimizer, stays unscoped
    assert r["scope_s"] == pytest.approx(
        {"attention": 290 * US, "grad_sync": 125 * US,
         "optimizer": 145 * US})
    assert r["scope_compute_s"] == pytest.approx(
        {"attention": 290 * US, "grad_sync": 25 * US,
         "optimizer": 145 * US})
    assert r["inferred_s"] == pytest.approx(
        {"attention": 0.0, "grad_sync": 0.0, "optimizer": 30 * US})
    assert r["no_path_s"] == pytest.approx(90 * US)
    # busy: chip 0 0-650, 800-900 = 750; chip 1 0-600
    assert r["unscoped_share"] == pytest.approx(1 - 1120 / 1350)
    assert r["data_wait_s"] == pytest.approx([109 * US])
    # chip 0 idle 650-800 (data.wait 109 of it) and 900-1000 (the block);
    # the float sums of abutting ops leave gaps of 1e-19 s besides
    assert r["idle_gaps"][:2] == [["data.wait", pytest.approx(150 * US)],
                                  ["bench.block", pytest.approx(100 * US)]]
    assert r["gap_runtime"][:2] == [
        ["PjitFunction(step_fn)", pytest.approx(150 * US)],
        ["PJRT_LoadedExecutable_Execute", pytest.approx(100 * US)]]
    assert all(d < 1e-15 for _, d in r["idle_gaps"][2:])
    assert trace_reduce.kind_of(PSUM, {})[0] == "compute"
    assert scopes._kind(PSUM) == "collective"
    line = scopes.describe(r)
    assert "\n" not in line and "unscoped 17.04%" in line


def test_existing_reduction_is_unchanged_by_scopes():
    """trace_reduce reads the same numbers with and without the scopes'
    metadata and the data.wait span; where the trace has neither,
    scopes.reduce finds no scope and labels the idle gaps as it does."""
    with_s = trace_reduce.reduce(_pd(_planes(with_scopes=True)))
    without = trace_reduce.reduce(_pd(_planes(with_scopes=False)))
    assert with_s == without
    assert without["busy_s"] == pytest.approx(675 * US)
    assert [g[0] for g in without["idle_gaps"][:2]] == ["bench.input_wait",
                                                        "bench.block"]
    data = _planes(with_scopes=False)
    r = scopes.reduce(_pd(data), scopes.op_paths(data))
    assert set(r["scope_s"].values()) == {0.0}
    assert r["unscoped_share"] == 1.0
    # every innermost op: busy less the loop's own 20 on chip 0
    assert r["no_path_s"] == pytest.approx(665 * US)
    assert r["idle_gaps"] == without["idle_gaps"]


@pytest.mark.parametrize("tf_op,scope", [
    ("jit(step_fn)/attention/dot_general:", "attention"),
    ("jit(step_fn)/transpose(jvp(attention))/while/body/mul:", "attention"),
    ("jit(step_fn)/optimizer/grad_sync/psum:", "grad_sync"),
    ("jit(step_fn)/grad_sync/optimizer/all-gather:", "optimizer"),
    ("jit(step_fn)/attention/reshape;jit(step_fn)/attention/transpose:",
     "attention"),
    ("reshape;jit(step_fn)/grad_sync/concatenate:", "grad_sync"),
    ("jit(step_fn)/grad_sync/mul;jit(step_fn)/optimizer/mul:", None),
    ("jit(step_fn)/attention_like/mul:", None),
    ("jit(<lambda>)/pallas_call:", None),
    (None, None),
])
def test_scope_of_a_path(tf_op, scope):
    assert scopes.scope_of(tf_op) == scope


def test_one_name_two_paths_is_unscoped():
    name = "%fusion.1 = f32[8] fusion()"
    data = _xspace([("/device:TPU:0", {"XLA Ops": [(name, 0, 10)]})],
                   {"/device:TPU:0": {name: ["jit(f)/attention/mul:",
                                             "jit(f)/optimizer/mul:"]}})
    assert scopes.op_paths(data) == {"/device:TPU:0": {name: None}}


def test_recorded_chip_trace_paths():
    path, = glob.glob(os.path.join(BENCH, "testdata", "*.xplane.pb"))
    with open(path, "rb") as f:
        data = f.read()
    paths = scopes.op_paths(data)
    assert list(paths) == ["/device:TPU:0"]
    kernel = {n: p for n, p in paths["/device:TPU:0"].items()
              if n.startswith("%_lambda_.1 = ")}
    assert len(kernel) == 2, "the two PowerSGD kernels' custom calls"
    assert set(kernel.values()) == {"jit(<lambda>)/pallas_call:"}
    pd = _pd(data)
    r = scopes.reduce(pd, paths)
    assert r["unscoped_share"] == 1.0 and r["data_wait_s"] == []
    assert r["idle_gaps"] == trace_reduce.reduce(pd)["idle_gaps"]


def test_reduce_dir_reads_the_newest_trace(tmp_path):
    """``reduce_dir`` finds a trace where the profiler writes it."""
    src, = glob.glob(os.path.join(BENCH, "testdata", "*.xplane.pb"))
    run = tmp_path / "plugins" / "profile" / "run1"
    run.mkdir(parents=True)
    with open(src, "rb") as f:
        data = f.read()
    (run / "host.xplane.pb").write_bytes(data)
    assert scopes.reduce_dir(str(tmp_path)) == scopes.reduce(
        _pd(data), scopes.op_paths(data))
    with pytest.raises(ValueError):
        scopes.reduce_dir(str(tmp_path / "plugins"))


def test_bench_imports_no_profiler_stack():
    """The trace readers need nothing beyond JAX: no TensorFlow, xprof or
    tsl import anywhere under bench/."""
    bad = re.compile(r"^\s*(import|from)\s+(tensorflow|xprof|tsl)\b", re.M)
    for path in glob.glob(os.path.join(BENCH, "**", "*.py"),
                          recursive=True):
        with open(path) as f:
            assert not bad.search(f.read()), path
