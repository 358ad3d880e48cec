"""Reduce a profiler trace (``.xplane.pb``) to the numbers the per-layer
metrics read.  Kept with the benchmark, so every PR computes them alike.

The trace is read with ``jax.profiler.ProfileData``.  Device operations are
the events on each TPU plane's ``XLA Ops`` line, where a loop's event
holds the events of its body; collectives in flight are also taken from
the ``Async XLA Ops`` line.  Host spans are the harness's own
``TraceAnnotation`` events (``bench.*``).  An event is named by its HLO
instruction (``fusion.12``), and classed by what the trace says of it:

- collectives: all-reduce, all-gather, reduce-scatter, collective-permute,
  all-to-all (and their async start/done halves);
- Pallas kernels: custom calls to Mosaic (``tpu_custom_call``), grouped
  by the instruction's name without its number;
- everything else is compute.

The window runs from the first host span's start to the last one's end.
Busy time is the union of ``XLA Ops`` intervals; time per operation and
per kernel counts innermost events only, so a loop is not counted twice.
"""
from __future__ import annotations

import glob
import os
import re

HOST_SPANS = ("bench.input_wait", "bench.dispatch", "bench.block")
_DEVICE = re.compile(r"^/device:TPU:(\d+)$")
_COLLECTIVE = re.compile(r"all-reduce|all-gather|reduce-scatter|"
                         r"collective-permute|all-to-all|allreduce|"
                         r"allgather|reducescatter")
_OP_LINE = "XLA Ops"
_ASYNC_LINE = "Async XLA Ops"
_SUFFIX = re.compile(r"\.\d+$")


def union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def total(intervals) -> float:
    return sum(e - s for s, e in intervals)


def subtract(a: list, b: list) -> list:
    """Union intervals ``a`` minus union intervals ``b``."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def _stats(ev) -> dict:
    try:
        return dict(ev.stats)
    except (TypeError, ValueError):
        return {}


def kind_of(name: str, stats: dict) -> tuple[str, str]:
    """('collective' | 'kernel' | 'compute', label) for a device op whose
    event name is its HLO text (``%fusion.12 = f32[...] fusion(...)``)."""
    short = name.split(" = ", 1)[0].lstrip("%")
    op = name.split(" = ", 1)[-1]
    text = " ".join([name] + [str(v) for v in stats.values()])
    if _COLLECTIVE.search(short.lower()) or _COLLECTIVE.search(
            op.split("(", 1)[0].lower()) or _COLLECTIVE.search(
            str(stats.get("hlo_category", "")).lower()):
        return "collective", short
    if "tpu_custom_call" in text:
        return "kernel", _SUFFIX.sub("", short)
    return "compute", short


def innermost(ops: list) -> list:
    """The events of ``ops`` (sorted by start, longest first) that hold
    no other event whole."""
    leaf = [True] * len(ops)
    stack: list[int] = []
    for i, (s, e, *_rest) in enumerate(ops):
        while stack and ops[stack[-1]][1] <= s:
            stack.pop()
        if stack and e <= ops[stack[-1]][1]:
            leaf[stack[-1]] = False
        stack.append(i)
    return [op for op, keep in zip(ops, leaf) if keep]


def events(pd):
    """(device ops per device, host spans) of a ``ProfileData``: device
    ops are ``(start_s, end_s, kind, label)``; host spans ``(start_s,
    end_s, name)``."""
    devices: dict[int, list] = {}
    spans = []
    for plane in pd.planes:
        m = _DEVICE.match(plane.name)
        for line in plane.lines:
            if m and line.name in (_OP_LINE, _ASYNC_LINE):
                ops = devices.setdefault(int(m.group(1)), [])
                for ev in line.events:
                    kind, label = kind_of(ev.name, _stats(ev))
                    if line.name == _ASYNC_LINE and kind != "collective":
                        continue
                    if line.name == _ASYNC_LINE:
                        kind = "async_collective"
                    s = ev.start_ns * 1e-9
                    ops.append((s, s + ev.duration_ns * 1e-9, kind, label))
            elif not m and plane.name.startswith("/host"):
                for ev in line.events:
                    if ev.name in HOST_SPANS:
                        s = ev.start_ns * 1e-9
                        spans.append((s, s + ev.duration_ns * 1e-9, ev.name))
    return devices, spans


#: entries of the breakdown's lists, as the result line allows
TOP = 10


def reduce(pd) -> dict:
    """The per-layer numbers of one traced window (seconds, averaged over
    the chips): busy time, collective time and its exposed part, time per
    Pallas kernel, the top operations, and the longest idle gaps of the
    first chip, each named by the host span it fell in."""
    devices, spans = events(pd)
    if not devices:
        raise ValueError("no TPU op events in the trace")
    if not spans:
        raise ValueError("no bench.* host spans in the trace")
    lo = min(s for s, _, _ in spans)
    hi = max(e for _, e, _ in spans)
    n = len(devices)
    busy = coll = exposed = 0.0
    by_op: dict[str, float] = {}
    kernels: dict[str, float] = {}
    gaps = []
    for dev in sorted(devices):
        ops = sorted(((max(s, lo), min(e, hi), k, lab)
                      for s, e, k, lab in devices[dev] if e > lo and s < hi),
                     key=lambda o: (o[0], -o[1]))
        sync = [o for o in ops if o[2] != "async_collective"]
        all_u = union([(s, e) for s, e, _, _ in sync])
        busy += total(all_u)
        c_u = union([(s, e) for s, e, k, _ in ops if "collective" in k])
        other = union([(s, e) for s, e, k, _ in sync if k != "collective"])
        coll += total(c_u)
        exposed += total(subtract(c_u, other))
        for s, e, k, lab in innermost(sync):
            by_op[lab] = by_op.get(lab, 0.0) + (e - s)
            if k == "kernel":
                kernels[lab] = kernels.get(lab, 0.0) + (e - s)
        if dev == min(devices):
            gaps = subtract([(lo, hi)], all_u)
    labelled = []
    for s, e in gaps:
        best, what = 0.0, "none"
        for hs, he, name in spans:
            ov = min(e, he) - max(s, hs)
            if ov > best:
                best, what = ov, name
        labelled.append([what, e - s])
    labelled.sort(key=lambda x: -x[1])
    ops_sorted = sorted(([k, v / n] for k, v in by_op.items()),
                        key=lambda x: -x[1])
    return {"window_s": hi - lo, "busy_s": busy / n, "n_devices": n,
            "collective_s": coll / n, "exposed_collective_s": exposed / n,
            "kernel_s": {k: v / n for k, v in kernels.items()},
            "top_ops": ops_sorted[:TOP], "idle_gaps": labelled[:TOP]}


def reduce_dir(trace_dir: str) -> dict:
    """Reduce the newest ``.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData
    paths = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if not paths:
        raise ValueError(f"no .xplane.pb under {trace_dir}")
    return reduce(ProfileData.from_file(max(paths, key=os.path.getmtime)))
