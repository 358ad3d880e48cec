"""One benchmark run of one cell: set-up, the first steps compared with the
reference, a timed window, and the result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Order of a run:
 1. overlap flags appended to ``LIBTPU_INIT_ARGS``; compile cache on;
 2. anything but a TPU with the cell's chip count is refused (exit 2);
 3. the train step is built through the program's public calls, its
    state filled with the seed's weights, the feed started;
 4. three steps through the timed call and feed: the cell's one shape
    compiles and warms up, and the readings for ``correct`` are taken;
 5. a window of ``--seconds`` in which steps are dispatched
    ``AHEAD_S`` seconds ahead of the one waited for (a host that stands
    still meanwhile leaves the chip fed), each step's loss read once the
    step has ended; when the time is up nothing more is sent, all that
    was sent is waited for, and the window closes after that wait (with
    ``--trace 1`` under the profiler);
 6. peak memory read, the program's state freed, the reference run over
    the seed's first three batches as ``bench/data.py`` makes them, and
    the result printed: the numbers compared,
    each beside its limit, as the last lines on stderr, then one JSON line
    on stdout.
"""
from __future__ import annotations

import gc
import json
import math
import os
import shutil
import statistics
import sys
import time

CHECK_STEPS = 3
EXIT_NO_CHIP = 2
#: seconds of steps kept dispatched ahead of the step waited for
AHEAD_S = 6.0


class NoChip(Exception):
    pass


def _err(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def device_info(jax, chips: int, require_tpu: bool) -> dict:
    devs = jax.devices()
    d = devs[0]
    if require_tpu and d.platform != "tpu":
        raise NoChip(f"no TPU: JAX found platform {d.platform!r}")
    if require_tpu and len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX found {len(devs)}")
    return {"platform": d.platform, "kind": d.device_kind, "count": len(devs)}


def memory_peak(jax) -> int:
    peaks = []
    for d in jax.devices():
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks)


def p90(xs: list[float]) -> float:
    if len(xs) < 2:
        return xs[0]
    return statistics.quantiles(xs, n=10, method="inclusive")[8]


class _CompileCounter:
    """Counts XLA compilations (backend compile events) while active."""

    def __init__(self, jax):
        self.count = 0
        self.active = False
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, *_a, **_k):
        if self.active and "backend_compile" in event:
            self.count += 1


def first_steps(prog, key, step, feed, batch, lr, keep_grad: bool,
                batch_fn=None):
    """The program's state from ``key`` driven through ``CHECK_STEPS``
    steps of the timed call and feed (``batch`` is the feed's first
    batch).  The first step compiles.  Returns ``(state, readings)``: each
    step's loss, the per-leaf norms of the first gradient as the optimizer
    took it (from its first moment after step 1; with ``keep_grad`` its
    leaves too, on the host) and of the parameters' change after the
    steps (from the fp32 weights).  ``batch_fn`` alters a batch before the
    step sees it (a planted fault)."""
    import jax

    scale = 1.0 / (1.0 - prog.workload["optimizer"]["b1"])
    grad_norms = prog.leaf_norms_fn(minus_weights=False)
    delta_norms = prog.leaf_norms_fn(minus_weights=True)
    times = {"start": time.perf_counter()}
    state = prog.init_state(key)
    jax.block_until_ready(state)
    times["state"] = time.perf_counter()
    readings = {"losses": [], "times": times}
    for i in range(CHECK_STEPS):
        if i:
            batch = next(feed)
        if batch_fn is not None:
            batch = batch_fn(batch)
        state, metrics = step(state, batch, lr)
        readings["losses"].append(float(metrics["loss"]))
        times[f"step{i + 1}"] = time.perf_counter()
        if i == 0:
            m = prog.grad_tree(state)
            readings["grad"] = {k: float(v) * scale
                                for k, v in grad_norms(m).items()}
            if keep_grad:
                readings["grad_vec"] = prog.host_leaves(m, scale)
            del m
    readings["delta"] = {k: float(v) for k, v in delta_norms(
        prog.weights_now(state), key).items()}
    jax.block_until_ready(state)
    times["end"] = time.perf_counter()
    return state, readings


def ahead(times: dict) -> int:
    """Steps to keep dispatched ahead: ``AHEAD_S`` over the last checked
    step's time (each checked step ends in reading its loss)."""
    last = times[f"step{CHECK_STEPS}"] - times[f"step{CHECK_STEPS - 1}"]
    return max(1, math.ceil(AHEAD_S / max(last, 1e-3)))


def window(step, state, feed, lr, seconds: float, depth: int):
    """Steps from ``state`` for ``seconds``, ``depth`` of them dispatched
    ahead of the one waited for; then nothing more is sent and all that
    was sent is waited for.  Returns ``(state, metrics, out)``: ``out``
    has ``window_s`` (first dispatch to the end of the last step),
    ``step_s`` (time between the ends of successive steps, as the host
    saw them; the first from the window's start), ``wait_s`` (each
    step's wait for its batch) and ``losses``."""
    import collections

    import jax

    annotate = jax.profiler.TraceAnnotation
    step_s, wait_s, losses = [], [], []
    pending = collections.deque()
    t_win = last = time.perf_counter()
    metrics = None

    def retire():
        nonlocal last
        with annotate("bench.block"):
            m = pending.popleft()
            jax.block_until_ready(m)
        now = time.perf_counter()
        step_s.append(now - last)
        last = now
        losses.append(float(m["loss"]))

    while time.perf_counter() - t_win < seconds:
        t0 = time.perf_counter()
        with annotate("bench.input_wait"):
            batch = next(feed)
        wait_s.append(time.perf_counter() - t0)
        with annotate("bench.dispatch"):
            state, metrics = step(state, batch, lr)
        pending.append(metrics)
        if len(pending) > depth:
            retire()
    while pending:
        retire()
    jax.block_until_ready(state)
    out = {"window_s": time.perf_counter() - t_win, "step_s": step_s,
           "wait_s": wait_s, "losses": losses}
    return state, metrics, out


def run(argv_ns, t_start: float, spec, *, require_tpu: bool = True,
        wrap_step=None) -> dict:
    """Run one cell; returns the result dict (also printed on stdout).

    ``wrap_step(step) -> step`` lets a test break the timed path."""
    from bench import program as prog_mod

    w = spec.workload(argv_ns.workload)
    cfg = spec.config(w["config"])
    prog_mod.prepare_process(w)

    import jax
    import jax.numpy as jnp

    device = device_info(jax, w["chips"], require_tpu)
    t_jax = time.perf_counter()
    if require_tpu and device["count"] != w["chips"]:
        raise NoChip(f"the cell asks for {w['chips']} chips, JAX found "
                     f"{device['count']}")
    peak = spec.peak(device["kind"]) if require_tpu else None
    compiles = _CompileCounter(jax)

    from bench import compare, data, weights

    seed = argv_ns.seed
    key = weights.seed_key(seed)
    limits = w["limits"]
    arch, compressor = spec.arch(cfg), spec.compressor(w)
    prog = prog_mod.Program(cfg, w, arch, compressor)
    feed = prog.feed(seed)
    batch = next(feed)
    step = prog.make_step(batch)
    if wrap_step is not None:
        step = wrap_step(step)
    lr = jnp.float32(w["lr"])
    tokens_per_step = prog.global_batch * w["seq"]
    state, readings = first_steps(prog, key, step, feed, batch, lr,
                                  keep_grad="grad_err" in limits)

    # ---- the window --------------------------------------------------
    trace_dir = None
    if argv_ns.trace:
        trace_dir = os.path.join(spec.root, ".bench_trace", w["name"])
        shutil.rmtree(trace_dir, ignore_errors=True)
        jax.profiler.start_trace(trace_dir)
    depth = ahead(readings["times"])
    compiles.active = True
    setup_s = time.perf_counter() - t_start
    state, metrics, win = window(step, state, feed, lr, argv_ns.seconds,
                                 depth)
    window_s, step_s, wait_s = win["window_s"], win["step_s"], win["wait_s"]
    compiles.active = False
    if trace_dir:
        jax.profiler.stop_trace()
    mem = memory_peak(jax)
    feed.close()
    del state, metrics, batch, step, feed
    gc.collect()

    # ---- the comparison with the reference ---------------------------
    from bench.reference import Reference
    t_ref = time.perf_counter()
    ref = Reference(cfg, w, arch, compressor).run(
        key, data.batches(cfg, w, prog.global_batch, seed, CHECK_STEPS),
        keep_grad="grad_err" in limits)
    nums = compare.numbers(readings, ref)
    correct = compare.verdict(nums, limits)
    t_ref = time.perf_counter() - t_ref

    n = len(step_s)
    failed = sum(1 for x in win["losses"] if not math.isfinite(x))
    tm = readings["times"]
    _err(f"[bench] {w['name']} seed={seed} device={device} "
         f"setup={setup_s:.3f}s: jax_init={t_jax - t_start:.3f}s "
         f"build={tm['start'] - t_jax:.3f}s "
         f"state={tm['state'] - tm['start']:.3f}s "
         f"step1_compile_or_load={tm['step1'] - tm['state']:.3f}s "
         f"steps2_3_and_readers={tm['end'] - tm['step1']:.3f}s "
         f"to_window={t_start + setup_s - tm['end']:.3f}s; "
         f"window={window_s:.3f}s steps={n} ahead={depth} "
         f"(p90 over {n} samples) "
         f"compiles_in_window={compiles.count} reference={t_ref:.3f}s")
    _err(f"[bench] losses program={readings['losses']} "
         f"reference={ref['losses']}")
    _err(f"[bench] worst grad leaf {nums['grad_gap_at']}, worst change "
         f"leaf {nums['delta_gap_at']}, {nums['leaves_compared']} of "
         f"{nums['leaves']} leaves compared")
    compared = {k: {"value": nums[k], "limit": lim}
                for k, lim in limits.items()}
    _err("[bench] " + " ".join(f"{k}={nums[k]!r}" for k in compare.NUMBERS
                               if k in nums))

    record = {"steps": n, "window_s": window_s, "step_s": step_s,
              "input_wait_s": wait_s, "tokens_per_step": tokens_per_step,
              "chips": device["count"], "peak": peak, "cfg": cfg,
              "workload": w, "arch": arch,
              "flops_per_token": arch.train_flops_per_token(cfg,
                                                            w["seq"])}
    if argv_ns.trace:
        from bench import trace_reduce
        summary = trace_reduce.reduce_dir(trace_dir)
        shutil.rmtree(trace_dir, ignore_errors=True)
        metrics_out = {}
        for m in spec.per_layer(w["name"]):
            val = spec.reader(m["name"]).read(summary, record)
            if val is not None:
                metrics_out[m["name"]] = {"value": val, "unit": m["unit"]}
        device.update(busy_s=summary["busy_s"], window_s=summary["window_s"])
    else:
        e2e = {"tokens_per_s": tokens_per_step * n / window_s,
               "step_ms_p90": p90(step_s) * 1e3,
               "peak_hbm_gib": mem / 2**30,
               "setup_s": setup_s}
        metrics_out = {m["name"]: {"value": e2e[m["name"]],
                                   "unit": m["unit"]}
                       for m in spec.end_to_end(w["name"])}
    device["memory_peak_bytes"] = mem
    result = {"correct": bool(correct), "attempted": n, "failed": failed,
              "metrics": metrics_out, "device": device}
    if argv_ns.trace:
        result["breakdown"] = {
            "device_ops": summary["top_ops"],
            "idle_gaps": summary["idle_gaps"]}
    result["compared"] = compared
    for k, v in compared.items():
        _err(f"compared {k} {v['value']!r} limit {v['limit']!r}")
    print(json.dumps(result), flush=True)
    return result
