"""Device time of a traced window by the program's own layers.

The program names three layers with ``jax.named_scope``: ``attention``
(scores, softmax and the weighted sum, forward, recompute and backward),
``grad_sync`` (bucket assembly, encode, reduce, decode, error memory and
the gradient collectives) and ``optimizer`` (the update, with ZeRO-1's
parameter all-gather).  JAX writes the scopes into each HLO instruction's
``op_name`` (``jit(step_fn)/transpose(jvp(attention))/dot_general``), and
the TPU trace carries that path as the ``tf_op`` stat of the operation's
*event metadata*.  ``jax.profiler.ProfileData`` shows only event stats, so
``op_paths`` reads the metadata from the ``.xplane.pb`` wire format itself
(no ``tensorflow``, ``xprof`` or ``tsl`` import): it skips each plane's
lines by their length, so it costs the number of distinct operations, not
of events.  Events are joined to it by name on their plane.

The data pipeline writes a ``data.wait`` host span around the consumer's
wait for a batch.  ``reduce`` adds to what ``trace_reduce.reduce`` gives
(same window, same chips, same innermost operations):

- ``scope_s``: seconds per chip of innermost ``XLA Ops`` under each scope;
- ``scope_compute_s``: the same without collectives;
- ``no_path_s``: seconds per chip of innermost ops whose metadata has no
  path at all.  XLA's own passes make such ops (a loop that relays out
  an all-gather's result, a concatenation rewritten as in-place
  updates); a run of them takes the scope of the ops on both sides of it
  where the two agree, and ``inferred_s`` holds that part of ``scope_s``;
- ``unscoped_share``: the share of busy time under no scope;
- ``data_wait_s``: each ``data.wait`` span in the window;
- ``idle_gaps``: the first chip's longest idle gaps, each labelled by the
  span of the driving thread (the one with ``bench.*`` spans) that
  overlaps it most, where ``data.wait`` takes its part of the
  ``bench.input_wait`` span around it;
- ``gap_runtime``: for the same gaps, the innermost runtime host event
  (``PjitFunction``, ``PJRT_LoadedExecutable_Execute``, ...) that covers
  at least half of the gap, or ``none``.
"""
from __future__ import annotations

import glob
import os
import re

from bench import trace_reduce

SCOPES = ("attention", "grad_sync", "optimizer")
DATA_WAIT = "data.wait"
_SPANS = trace_reduce.HOST_SPANS + (DATA_WAIT,)
_TF_OP = "tf_op"
_NO_PATH = ""               # the scope of an op whose metadata has no path
_WRAPPED = re.compile(r"^[^()]*\((.*)\)$")
_COLLECTIVE_OP = re.compile(
    r"\b(?:all-reduce|all-gather|reduce-scatter|collective-permute|"
    r"all-to-all)(?:-start|-done)?\(")


# --------------------------------------------------------------- wire format
def _varint(b, i: int) -> tuple[int, int]:
    x = shift = 0
    while True:
        c = b[i]
        i += 1
        x |= (c & 0x7F) << shift
        if c < 0x80:
            return x, i
        shift += 7


def _fields(b, lo: int, hi: int):
    """(field number, value) of one message in ``b[lo:hi]``: an int for a
    varint, a ``(start, end)`` slice for a length-delimited field."""
    i = lo
    while i < hi:
        key, i = _varint(b, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(b, i)
        elif wire == 2:
            n, i = _varint(b, i)
            v, i = (i, i + n), i + n
        elif wire == 1:
            v, i = None, i + 8
        elif wire == 5:
            v, i = None, i + 4
        else:
            raise ValueError(f"wire type {wire} at byte {i} of an xplane")
        yield key >> 3, v


def _text(b, span) -> str:
    return bytes(b[span[0]:span[1]]).decode("utf-8", "replace")


def _map_value(b, span):
    """(key, value span) of a ``map<int64, message>`` entry."""
    key, val = 0, (span[0], span[0])
    for f, v in _fields(b, *span):
        if f == 1:
            key = v
        elif f == 2:
            val = v
    return key, val


def _plane_paths(b, lo: int, hi: int) -> tuple[str, dict]:
    """(plane name, {event metadata name: tf_op}) of one ``XPlane``."""
    name, metas, stat_names = "", [], {}
    for f, v in _fields(b, lo, hi):
        if f == 2:                              # name
            name = _text(b, v)
            if not trace_reduce._DEVICE.match(name):
                return name, {}
        elif f == 4:                            # event_metadata
            metas.append(_map_value(b, v)[1])
        elif f == 5:                            # stat_metadata
            sid, val = _map_value(b, v)
            for sf, sv in _fields(b, *val):
                if sf == 2:
                    stat_names[sid] = _text(b, sv)
        # f == 3 (lines) is skipped by its length
    ids = {i for i, n in stat_names.items() if n == _TF_OP}
    out: dict[str, str | None] = {}
    for span in metas:
        ev_name, path = None, None
        for f, v in _fields(b, *span):
            if f == 2:
                ev_name = _text(b, v)
            elif f == 5:                        # XStat
                mid, val = None, None
                for sf, sv in _fields(b, *v):
                    if sf == 1:
                        mid = sv
                    elif sf == 5:               # str_value
                        val = _text(b, sv)
                    elif sf == 7:               # ref_value
                        val = stat_names.get(sv)
                if mid in ids and val is not None:
                    path = val
        if ev_name is None or path is None:
            continue
        if ev_name in out and out[ev_name] != path:
            out[ev_name] = None                 # one name, two paths
        else:
            out[ev_name] = path
    return name, out


def op_paths(data: bytes) -> dict[str, dict[str, str | None]]:
    """{device plane name: {event metadata name: tf_op}} of a serialized
    ``XSpace``.  A name that two metadata entries of a plane give
    different paths maps to None."""
    b = memoryview(data)
    out = {}
    for f, v in _fields(b, 0, len(b)):
        if f == 1:                              # planes
            name, paths = _plane_paths(b, *v)
            if paths:
                out[name] = paths
    return out


# --------------------------------------------------------------- scopes
def _unwrap(part: str) -> str:
    """``transpose(jvp(attention))`` -> ``attention``."""
    while m := _WRAPPED.match(part):
        part = m.group(1)
    return part


def scope_of(tf_op: str | None) -> str | None:
    """The innermost of ``SCOPES`` in an operation's path (``op_name``,
    with the trace's ``:<type>`` suffix).  A fused location lists paths
    joined by ``;``: it has a scope when those that name one agree."""
    if not tf_op:
        return None
    found = set()
    for path in tf_op.rsplit(":", 1)[0].split(";"):
        hit = None
        for part in path.split("/"):
            part = _unwrap(part)
            if part in SCOPES:
                hit = part
        if hit:
            found.add(hit)
    return found.pop() if len(found) == 1 else None


def _kind(name: str) -> str:
    """``trace_reduce.kind_of``, and also a collective whose instruction
    JAX named (``%psum.3 = bf16[8]{0:T(1024)} all-reduce(...)``): there
    the tiled layout's parenthesis comes before the opcode's, so
    ``kind_of`` reads no opcode and calls it compute."""
    if _COLLECTIVE_OP.search(name):
        return "collective"
    return trace_reduce.kind_of(name, {})[0]


def _host_lines(pd):
    for plane in pd.planes:
        if plane.name.startswith("/host"):
            yield from plane.lines


def reduce(pd, paths: dict) -> dict:
    """The scope numbers of one traced window (see the module's doc);
    ``paths`` is ``op_paths`` of the same trace."""
    devices: dict[int, list] = {}
    for plane in pd.planes:
        m = trace_reduce._DEVICE.match(plane.name)
        if not m:
            continue
        names = paths.get(plane.name, {})
        ops = devices.setdefault(int(m.group(1)), [])
        known: dict[str, tuple] = {}        # name -> (kind, scope)
        for line in plane.lines:
            if line.name != trace_reduce._OP_LINE:
                continue
            for ev in line.events:
                name = ev.name
                if name not in known:
                    path = names.get(name)
                    known[name] = (_kind(name),
                                   scope_of(path) if path else _NO_PATH)
                s = ev.start_ns * 1e-9
                ops.append((s, s + ev.duration_ns * 1e-9, *known[name]))
    spans = []                  # the driving thread's: it has bench.* spans
    for line in _host_lines(pd):
        mine = [(ev.start_ns * 1e-9,
                 ev.start_ns * 1e-9 + ev.duration_ns * 1e-9, ev.name)
                for ev in line.events if ev.name in _SPANS]
        if any(n != DATA_WAIT for _, _, n in mine):
            spans += mine
    bench = [sp for sp in spans if sp[2] != DATA_WAIT]
    if not devices or not bench:
        raise ValueError("no TPU ops or no bench.* host spans in the trace")
    lo = min(s for s, _, _ in bench)
    hi = max(e for _, e, _ in bench)
    n = len(devices)
    busy = 0.0
    scope_s = dict.fromkeys(SCOPES, 0.0)
    compute_s = dict.fromkeys(SCOPES, 0.0)
    inferred_s = dict.fromkeys(SCOPES, 0.0)
    no_path = 0.0
    gaps = []
    for dev in sorted(devices):
        ops = sorted(((max(s, lo), min(e, hi), k, sc)
                      for s, e, k, sc in devices[dev] if e > lo and s < hi),
                     key=lambda o: (o[0], -o[1]))
        busy_u = trace_reduce.union([(s, e) for s, e, _, _ in ops])
        busy += trace_reduce.total(busy_u)
        for s, e, k, sc, pathless in _infer(trace_reduce.innermost(ops)):
            no_path += (e - s) * pathless
            if sc is not None:
                scope_s[sc] += e - s
                inferred_s[sc] += (e - s) * pathless
                if k != "collective":
                    compute_s[sc] += e - s
        if dev == min(devices):
            gaps = trace_reduce.subtract([(lo, hi)], busy_u)
    # data.wait lies inside bench.input_wait: it takes its part of it
    waits = trace_reduce.union([(s, e) for s, e, name in spans
                                if name == DATA_WAIT])
    parts = []
    for s, e, name in spans:
        if name == "bench.input_wait":
            parts += [(a, b, name)
                      for a, b in trace_reduce.subtract([(s, e)], waits)]
        else:
            parts.append((s, e, name))
    labelled = []
    for s, e in gaps:
        best, what = 0.0, "none"
        for hs, he, name in parts:
            ov = min(e, he) - max(s, hs)
            if ov > best:
                best, what = ov, name
        labelled.append((e - s, s, e, what))
    top = sorted(labelled, key=lambda g: -g[0])[:trace_reduce.TOP]
    runtime = _covering(pd, [(s, e) for _, s, e, _ in top])
    return {
        "scope_s": {k: v / n for k, v in scope_s.items()},
        "scope_compute_s": {k: v / n for k, v in compute_s.items()},
        "inferred_s": {k: v / n for k, v in inferred_s.items()},
        "no_path_s": no_path / n,
        "unscoped_share": 1.0 - sum(scope_s.values()) / busy if busy
        else None,
        "data_wait_s": [min(e, hi) - max(s, lo) for s, e, name in spans
                        if name == DATA_WAIT and e > lo and s < hi],
        "idle_gaps": [[what, d] for d, _, _, what in top],
        "gap_runtime": [[rt, d] for rt, (d, *_) in zip(runtime, top)],
    }


def _infer(ops: list) -> list:
    """``(start, end, kind, scope, pathless)`` of innermost ops in time
    order: a run of ops with no path (``pathless``) takes the scope of
    the ops with a path on both sides of it where the two agree."""
    out, i = [], 0
    while i < len(ops):
        if ops[i][3] != _NO_PATH:
            out.append((*ops[i], False))
            i += 1
            continue
        j = i
        while j < len(ops) and ops[j][3] == _NO_PATH:
            j += 1
        before = ops[i - 1][3] if i else None
        after = ops[j][3] if j < len(ops) else None
        sc = before if before == after else None
        out += [(s, e, k, sc, True) for s, e, k, _ in ops[i:j]]
        i = j
    return out


def _covering(pd, gaps: list) -> list[str]:
    """For each gap, the shortest runtime host event (not a Python
    function's, not a span of ours) that covers at least half of it."""
    best = [(float("inf"), "none")] * len(gaps)
    if not gaps:
        return []
    floor = 0.5 * min(e - s for s, e in gaps)
    for line in _host_lines(pd):
        for ev in line.events:
            d = ev.duration_ns * 1e-9
            if d < floor or ev.name in _SPANS or ev.name.startswith("$"):
                continue
            hs = ev.start_ns * 1e-9
            for i, (s, e) in enumerate(gaps):
                if d < best[i][0] and \
                        min(e, hs + d) - max(s, hs) >= 0.5 * (e - s):
                    best[i] = (d, ev.name)
    return [name for _, name in best]


def describe(r: dict) -> str:
    """One line for stderr: the share of busy time under no scope, each
    scope's seconds per chip (and the part inferred for ops without a
    path), and the longest idle gaps with the host span and runtime event
    of each."""
    share = r["unscoped_share"]
    gaps = ", ".join(f"{1e3 * d:.3f} ms {what} ({rt})" for (what, d), (rt, _)
                     in zip(r["idle_gaps"][:3], r["gap_runtime"][:3]))
    return ("[bench] scopes: unscoped "
            + ("n/a" if share is None else f"{100 * share:.2f}%")
            + " of busy; " + " ".join(
                f"{k}={v!r}s (inferred {r['inferred_s'][k]!r}s)"
                for k, v in r["scope_s"].items())
            + f"; ops without a path {r['no_path_s']!r}s"
            + f"; longest idle gaps: {gaps or 'none'}")


def reduce_dir(trace_dir: str) -> dict:
    """``reduce`` of the newest ``.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData
    paths = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if not paths:
        raise ValueError(f"no .xplane.pb under {trace_dir}")
    with open(max(paths, key=os.path.getmtime), "rb") as f:
        data = f.read()
    return reduce(ProfileData.from_serialized_xspace(data), op_paths(data))
