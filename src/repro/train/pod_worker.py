"""One worker process of a measured multi-process pod (sim-to-real step).

``MultiProcessBackend`` (repro.experiments.multiproc) launches ``--procs``
copies of this entrypoint; each initializes ``jax.distributed`` against
the shared coordinator, forces ``--local-devices`` fake host devices, and
joins a genuine two-tier (pod × data × model) mesh — the "pod" axis spans
OS processes (gloo collectives over loopback: the measured slow/DCN
tier), "data" spans each process's local devices (in-process XLA: the
fast tier).  The UNCHANGED train/overlap/CommPlan machinery then runs on
that mesh, so ``comm="hierarchical:data"`` exercises a real two-stage
reduction for the first time.

Measured per cell (round-robin min-of-reps, the ``overlap_bench``
protocol):

  * ``t_serial_us`` / ``t_overlap_us`` — the serial and overlapped DDP
    schedules on the pod mesh;
  * ``t_compute_us`` — the same per-device workload on a LOCAL
    single-device mesh (no cross-process collectives), the compute
    offset the calibration fit subtracts
    (``perfmodel.calibration.calibrate_from_results``).

Every process runs the same program; process 0's LAST stdout line is the
JSON record (the ``run_subprocess_json`` protocol), other processes keep
stdout silent.  Must run in a FRESH process (device count + overlap
scheduler flags must precede jax initialization):

    python -m repro.train.pod_worker --procs 2 --proc-id 0 \
        --coordinator 127.0.0.1:9945 --local-devices 2 --json
"""
from __future__ import annotations

import argparse
import json
import os
import sys


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--procs", type=int, required=True,
                    help="total processes in the pod (the 'pod' axis)")
    ap.add_argument("--proc-id", type=int, required=True)
    ap.add_argument("--coordinator", required=True,
                    help="host:port of the jax.distributed coordinator "
                         "(process 0 binds it)")
    ap.add_argument("--local-devices", type=int, default=2,
                    help="forced host device count per process "
                         "(the 'data' axis)")
    ap.add_argument("--arch", default="tinyllama-1.1b")
    ap.add_argument("--method", default="none")
    ap.add_argument("--plan", action="append", default=[],
                    metavar="FIELD=VALUE",
                    help="extra ParallelPlan override (repeatable)")
    ap.add_argument("--zero1", action="store_true")
    ap.add_argument("--accum", type=int, default=1)
    ap.add_argument("--comm", default="auto",
                    help="CommPlan kind (docs/comm_api.md); "
                         "'hierarchical:data' = intra-process ring then "
                         "cross-process ring — the two-tier schedule "
                         "this mesh exists to measure")
    ap.add_argument("--batch", type=int, default=8,
                    help="GLOBAL batch (split over procs × local devices)")
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--bucket-mb", type=float, default=1)
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--warmup", type=int, default=2)
    ap.add_argument("--json", action="store_true",
                    help="process 0 emits one JSON line as its last "
                         "stdout line")
    args = ap.parse_args(argv)

    # flags before ANY repro/jax import (same contract as overlap_bench,
    # CPU platform pinned likewise)
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + f" --xla_force_host_platform_device_count={args.local_devices}")
    from repro.train.overlap import enable_overlap_flags
    enable_overlap_flags()

    import jax
    jax.config.update("jax_cpu_collectives_implementation", "gloo")
    jax.distributed.initialize(coordinator_address=args.coordinator,
                               num_processes=args.procs,
                               process_id=args.proc_id)

    import dataclasses

    import numpy as np
    from jax.sharding import Mesh, NamedSharding

    from repro.configs import base
    from repro.data.pipeline import Pipeline
    from repro.data.synthetic import DataConfig
    from repro.experiments.backend import coerce_kv
    from repro.launch.mesh import make_pod_mesh
    from repro.train import overlap
    from repro.train import train_step as ts
    from repro.train.overlap_bench import timed_interleaved

    pid = args.proc_id
    log = sys.stderr

    plan_overrides = {}
    for kv in args.plan:
        k, _, v = kv.partition("=")
        plan_overrides[k] = coerce_kv(v)
    cfg = base.reduced(base.get(args.arch))
    plan_fields = dict(dp_mode="ddp", zero1=args.zero1, overlap=True,
                      compression=args.method, bucket_mb=args.bucket_mb,
                      comm=args.comm)
    plan_fields.update(plan_overrides)
    cfg = dataclasses.replace(cfg, plan=dataclasses.replace(
        cfg.plan, **plan_fields))

    mesh = make_pod_mesh(args.procs, args.local_devices)
    p_dp = args.procs * args.local_devices
    print(f"[pod_worker {pid}] mesh pod={args.procs} "
          f"data={args.local_devices} (p_dp={p_dp})", file=log)

    setup = ts.build(cfg, mesh)
    ov = overlap.build_layout(setup)
    grad_bytes = int(ov.layout.n_elements) * np.dtype(ov.layout.dtype) \
        .itemsize

    # identical seeded host batch on every process -> global arrays
    data = Pipeline(DataConfig(vocab=cfg.vocab, seq_len=args.seq,
                               global_batch=args.batch), prefetch=0)
    batch = next(iter(data))
    bspecs = ts.make_batch_specs(setup)(batch)
    gbatch = {k: jax.make_array_from_process_local_data(
                  NamedSharding(mesh, bspecs[k]), np.asarray(v))
              for k, v in batch.items()}

    builders = {
        "serial": overlap.make_step(setup, "serial", accum=args.accum),
        "overlap": overlap.make_step(setup, "overlap", accum=args.accum),
    }
    t = timed_interleaved(setup, gbatch, builders, args.reps, args.warmup)
    t_serial, t_overlap = t["serial"], t["overlap"]
    print(f"[pod_worker {pid}] pod: serial={t_serial * 1e6:.1f}us "
          f"overlap={t_overlap * 1e6:.1f}us", file=log)

    # ---- local compute offset: same per-device workload, one local
    # ---- device, no cross-process collectives — the t_comp the
    # ---- calibration fit subtracts from the pod step times
    local_mesh = Mesh(
        np.array(jax.local_devices()[:1]).reshape(1, 1),
        ("data", "model"))
    cfg_local = dataclasses.replace(cfg, plan=dataclasses.replace(
        cfg.plan, compression="none", comm="auto", zero1=False))
    setup_local = ts.build(cfg_local, local_mesh)
    per_dev = max(1, args.batch // p_dp)
    lbatch = {k: np.asarray(v)[:per_dev] for k, v in batch.items()}
    t_local = timed_interleaved(
        setup_local, lbatch,
        {"serial": overlap.make_step(setup_local, "serial")},
        args.reps, args.warmup)
    t_compute = t_local["serial"]
    print(f"[pod_worker {pid}] local compute (1 device, "
          f"batch {per_dev}): {t_compute * 1e6:.1f}us", file=log)

    rec = dict(
        arch=cfg.name, method=args.method, workers=p_dp,
        procs=args.procs, local_devices=args.local_devices,
        zero1=args.zero1, accum=args.accum, comm=args.comm,
        plan_overrides=plan_overrides or None,
        n_buckets=ov.layout.n_buckets,
        effective_schedule=overlap.effective_schedule(setup),
        mesh_axes=list(mesh.axis_names),
        mesh_shape=list(mesh.devices.shape),
        grad_bytes=grad_bytes,
        batch=args.batch, seq=args.seq,
        t_serial_us=round(t_serial * 1e6, 1),
        t_overlap_us=round(t_overlap * 1e6, 1),
        t_compute_us=round(t_compute * 1e6, 1),
        overlap_vs_serial=round(t_overlap / t_serial, 4),
        fig2_saving_pct=round((1 - t_overlap / t_serial) * 100, 2),
    )
    print(f"OK pod_worker {pid}", file=log)
    if args.json and pid == 0:
        # the run_subprocess_json protocol: LAST stdout line is the record
        print(json.dumps(rec))
    return rec


if __name__ == "__main__":
    main()
