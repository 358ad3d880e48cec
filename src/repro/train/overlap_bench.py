"""Measured serial-vs-overlapped DDP step times (paper Fig 2, executable).

Runs the three executable schedules of the segmented DDP step on a forced
multi-device CPU host mesh and reports wall times:

  ``overlap``  bucket collectives fused into the backward (reverse layer
               order, barrier-pinned) — the paper's optimized baseline;
  ``serial``   same fused program, all collectives after the backward;
  ``unfused``  backward and aggregation in separate dispatches — the
               no-overlap strawman (PyTorch backward() then allreduce;
               skipped under ``--accum > 1``).

``--zero1`` owner-shards the optimizer state along bucket boundaries and
``--accum N`` runs N microbatches with flush-on-final-microbatch — the
generalized overlap regimes (docs/overlap.md), measured under the same
round-robin protocol.

Must run in a FRESH process (it forces the host device count and the
latency-hiding-scheduler flags before jax initializes); the
``MeasuredBackend`` spawns it as a subprocess for
``ExperimentSpec(kind="train")`` cells, and ``benchmarks/run.py`` turns
the result into BENCH anchor rows.  Last stdout line is the JSON record:

    PYTHONPATH=src python -m repro.train.overlap_bench --devices 4 --json
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time


def timed_interleaved(setup, batch, builders: dict, reps: int,
                      warmup: int) -> dict:
    """Min-of-reps per-step wall time (s) per schedule, measured
    ROUND-ROBIN (one step of each schedule per rep) so machine-load
    drift hits every schedule equally; min discards contention spikes.
    Each schedule threads its own state so donation stays realistic.

    Shared by this bench and ``repro.train.pod_worker`` (the multi-process
    pod measurement) — jax must already be initialized by the caller."""
    import jax
    import jax.numpy as jnp

    from repro.train import train_step as ts

    runs = {k: [ts.init_state(setup, jax.random.key(0)), b(batch), []]
            for k, b in builders.items()}
    for i in range(warmup + reps):
        for k, run in runs.items():
            state, step, times = run
            t0 = time.perf_counter()
            state, m = step(state, batch, jnp.float32(1e-3))
            jax.block_until_ready(m["loss"])
            run[0] = state
            if i >= warmup:
                times.append(time.perf_counter() - t0)
    return {k: min(run[2]) for k, run in runs.items()}


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default="tinyllama-1.1b")
    ap.add_argument("--devices", type=int, default=4,
                    help="forced host device count (the DDP 'data' axis)")
    ap.add_argument("--method", default="none",
                    help="plan.compression for the aggregated buckets")
    ap.add_argument("--plan", action="append", default=[],
                    metavar="FIELD=VALUE",
                    help="extra ParallelPlan override (repeatable), e.g. "
                         "--plan powersgd_rank=8 --plan qsgd_bits=4")
    ap.add_argument("--zero1", action="store_true",
                    help="owner-shard the optimizer state along bucket "
                         "boundaries (plan.zero1=True)")
    ap.add_argument("--accum", type=int, default=1,
                    help="gradient-accumulation microbatches per step "
                         "(the unfused strawman is skipped when > 1)")
    ap.add_argument("--comm", default="auto",
                    help="collective schedule (CommPlan kind, "
                         "docs/comm_api.md): auto | allreduce | "
                         "reduce_scatter_allgather | "
                         "reduce_to_owner_broadcast (zero1+none only) | "
                         "gather_all | hierarchical[:intra+axes]")
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--bucket-mb", type=int, default=1,
                    help="bucket byte target (small => several buckets "
                         "at smoke scale; production default is 25)")
    ap.add_argument("--reps", type=int, default=30)
    ap.add_argument("--warmup", type=int, default=5)
    ap.add_argument("--json", action="store_true",
                    help="emit one JSON line as the last stdout line")
    args = ap.parse_args(argv)

    # mutate XLA_FLAGS before ANY repro/jax import — repro.train.overlap
    # pulls in the jax import chain, and flags set after jax initializes
    # are silently ignored.  The forced host devices are a CPU stand-in:
    # pin the CPU platform so a run on a TPU host never opens the chip.
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + f" --xla_force_host_platform_device_count={args.devices}")
    from repro.train.overlap import enable_overlap_flags
    enable_overlap_flags()

    import dataclasses

    import jax
    import jax.numpy as jnp

    from repro.configs import base
    from repro.data.pipeline import Pipeline
    from repro.data.synthetic import DataConfig
    from repro.parallel.compat import make_mesh
    from repro.train import overlap
    from repro.train import train_step as ts

    from repro.experiments.backend import coerce_kv
    plan_overrides = {}
    for kv in args.plan:
        k, _, v = kv.partition("=")
        plan_overrides[k] = coerce_kv(v)
    cfg = base.reduced(base.get(args.arch))
    plan_fields = dict(dp_mode="ddp", zero1=args.zero1, overlap=True,
                       compression=args.method, bucket_mb=args.bucket_mb,
                       comm=args.comm)
    plan_fields.update(plan_overrides)      # explicit --plan wins
    cfg = dataclasses.replace(cfg, plan=dataclasses.replace(
        cfg.plan, **plan_fields))
    mesh = make_mesh((args.devices, 1), ("data", "model"))
    setup = ts.build(cfg, mesh)
    ov = overlap.build_layout(setup)
    data = Pipeline(DataConfig(vocab=cfg.vocab, seq_len=args.seq,
                               global_batch=args.batch), prefetch=0)
    batch = next(iter(data))

    builders = {
        "serial": overlap.make_step(setup, "serial", accum=args.accum),
        "overlap": overlap.make_step(setup, "overlap", accum=args.accum),
    }
    if args.accum == 1:
        # the two-dispatch strawman has no accumulated variant
        builders["unfused"] = overlap.make_unfused_step(setup)
    t = timed_interleaved(setup, batch, builders, args.reps, args.warmup)
    t_serial, t_overlap = t["serial"], t["overlap"]

    rec = dict(
        arch=cfg.name, method=args.method, workers=args.devices,
        zero1=args.zero1, accum=args.accum, comm=args.comm,
        plan_overrides=plan_overrides or None,
        n_buckets=ov.layout.n_buckets,
        effective_schedule=overlap.effective_schedule(setup),
        t_serial_us=round(t_serial * 1e6, 1),
        t_overlap_us=round(t_overlap * 1e6, 1),
        overlap_vs_serial=round(t_overlap / t_serial, 4),
        # measured Fig-2 analogue: step-time saving from fusing the
        # collectives into the backward vs issuing them all after it
        # (same program, schedule only).  The unfused row is
        # informational: at CPU smoke scale two small dispatches beat one
        # fused program; on real interconnects it is the worst case.
        fig2_saving_pct=round((1 - t_overlap / t_serial) * 100, 2),
    )
    if "unfused" in t:
        rec["t_unfused_us"] = round(t["unfused"] * 1e6, 1)
    print(f"[overlap_bench] {rec['arch']} method={rec['method']} "
          f"p={rec['workers']} zero1={rec['zero1']} accum={rec['accum']} "
          f"buckets={rec['n_buckets']}: "
          f"serial={rec['t_serial_us']}us overlap={rec['t_overlap_us']}us "
          f"unfused={rec.get('t_unfused_us', '-')}us "
          f"(fig2 saving {rec['fig2_saving_pct']}%)", file=sys.stderr)
    if args.json:
        print(json.dumps(rec))
    return rec


if __name__ == "__main__":
    main()
