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
 5. a window of ``--seconds`` in which every step ends in
    ``block_until_ready`` (with ``--trace 1`` under the profiler);
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
        if i == 0:
            times["step1"] = time.perf_counter()
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

    from bench import compare, data, flops, weights

    seed = argv_ns.seed
    key = weights.seed_key(seed)
    limits = w["limits"]
    compressor = spec.compressor(w)
    prog = prog_mod.Program(cfg, w, compressor)
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
    annotate = jax.profiler.TraceAnnotation
    step_s, wait_s, win_losses = [], [], []
    compiles.active = True
    t_win = time.perf_counter()
    setup_s = t_win - t_start
    now = t_win
    while now - t_win < argv_ns.seconds:
        t0 = time.perf_counter()
        with annotate("bench.input_wait"):
            batch = next(feed)
        t1 = time.perf_counter()
        with annotate("bench.dispatch"):
            state, metrics = step(state, batch, lr)
        with annotate("bench.block"):
            jax.block_until_ready((state, metrics))
        now = time.perf_counter()
        step_s.append(now - t0)
        wait_s.append(t1 - t0)
        win_losses.append(float(metrics["loss"]))
    window_s = now - t_win
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
    ref = Reference(cfg, w, compressor).run(
        key, data.batches(cfg, w, prog.global_batch, seed, CHECK_STEPS),
        keep_grad="grad_err" in limits)
    nums = compare.numbers(readings, ref)
    correct = compare.verdict(nums, limits)
    t_ref = time.perf_counter() - t_ref

    n = len(step_s)
    failed = sum(1 for x in win_losses if not math.isfinite(x))
    tm = readings["times"]
    _err(f"[bench] {w['name']} seed={seed} device={device} "
         f"setup={setup_s:.3f}s: jax_init={t_jax - t_start:.3f}s "
         f"build={tm['start'] - t_jax:.3f}s "
         f"state={tm['state'] - tm['start']:.3f}s "
         f"step1_compile_or_load={tm['step1'] - tm['state']:.3f}s "
         f"steps2_3_and_readers={tm['end'] - tm['step1']:.3f}s "
         f"to_window={t_win - tm['end']:.3f}s; "
         f"window={window_s:.3f}s steps={n} (p90 over {n} samples) "
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
              "workload": w,
              "flops_per_token": flops.train_flops_per_token(cfg, w["seq"])}
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
