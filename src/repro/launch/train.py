"""Training launcher: ``python -m repro.launch.train --arch <id> ...``

Entry point (reduced configs by default) exercising the REAL production
path: mesh -> TrainSetup -> sharded state -> Trainer with checkpointing,
preemption handling and optional local-SGD.  ``--mesh local`` puts every
visible device on the data axis (one chip, or the four chips of a host);
``--full-size --layers N`` runs a config at its published widths with its
depth cut to N layers.  ``--mesh single/multi`` is the pod-scale layout.
"""
import argparse
import os


def parse_args(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="tinyllama-1.1b")
    ap.add_argument("--full-size", action="store_true",
                    help="use the full config (default: reduced smoke size)")
    ap.add_argument("--layers", type=int, default=0,
                    help="cut the depth to this many layers, widths "
                         "unchanged (0: the config's own depth)")
    ap.add_argument("--mesh", default="local",
                    choices=["local", "test", "single", "multi", "pod"])
    ap.add_argument("--devices", type=int, default=0,
                    help="fake-device count for --mesh test")
    # --mesh pod: one member of a multi-process jax.distributed pod on a
    # two-tier (pod × data) mesh — launch one copy per --proc-id, same
    # --procs/--coordinator everywhere (cf. repro.train.pod_worker, the
    # measured-cell variant of the same flow)
    ap.add_argument("--procs", type=int, default=2,
                    help="--mesh pod: total processes in the pod")
    ap.add_argument("--proc-id", type=int, default=0,
                    help="--mesh pod: this process's index")
    ap.add_argument("--coordinator", default="127.0.0.1:12355",
                    help="--mesh pod: jax.distributed coordinator "
                         "host:port (process 0 binds it)")
    ap.add_argument("--local-devices", type=int, default=2,
                    help="--mesh pod: forced host devices per process "
                         "(the 'data' axis; 'pod' spans processes)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--warmup", type=int, default=20)
    ap.add_argument("--compression", default=None,
                    help="none|powersgd|signsgd|mstopk|randomk|qsgd|terngrad")
    ap.add_argument("--compress-axes", default=None, choices=["pod", "all"])
    ap.add_argument("--comm", default=None,
                    help="collective schedule (CommPlan kind, "
                         "docs/comm_api.md): auto|allreduce|"
                         "reduce_scatter_allgather|"
                         "reduce_to_owner_broadcast|gather_all|"
                         "hierarchical[:intra+axes]")
    ap.add_argument("--overlap", action="store_true",
                    help="DDP: fuse reverse-order bucketed aggregation "
                         "into the backward pass (repro.train.overlap)")
    ap.add_argument("--adaptive", action="store_true",
                    help="let the perf model pick compression/comm at "
                         "launch (repro.adaptive; falls back to "
                         "overlapped syncSGD when no win is predicted)")
    ap.add_argument("--sync-every", type=int, default=1)
    ap.add_argument("--accum", type=int, default=1)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=0)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--seed", type=int, default=0)
    return ap.parse_args(argv)


def prepare(args: argparse.Namespace):
    """Everything before the training loop: process flags, the compile
    cache, the config (depth cut included), the mesh, the TrainSetup and
    the data pipeline.  Returns ``(setup, data)``."""
    if args.mesh == "test" and args.devices:
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={args.devices}")
    if args.mesh == "pod":
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count="
              f"{args.local_devices}")
    if args.overlap or args.adaptive:
        # latency-hiding-scheduler flags must precede jax init (libtpu
        # reads them once); adaptive resolves to an overlapped plan even
        # on fallback
        from repro.train.overlap import enable_overlap_flags
        enable_overlap_flags()

    import dataclasses

    import jax

    from repro.launch import compile_cache
    compile_cache.enable()

    if args.mesh == "pod":
        jax.config.update("jax_cpu_collectives_implementation", "gloo")
        jax.distributed.initialize(coordinator_address=args.coordinator,
                                   num_processes=args.procs,
                                   process_id=args.proc_id)

    from repro.configs import base as cfgs
    from repro.data.pipeline import Pipeline
    from repro.data.synthetic import DataConfig
    from repro.launch import mesh as mesh_mod
    from repro.train import train_step as ts

    arch = cfgs.get(args.arch)
    if not args.full_size:
        arch = cfgs.reduced(arch)
    depth = f"n_layers={arch.n_layers}"
    if args.layers:
        if not 1 <= args.layers <= arch.n_layers:
            raise ValueError(f"--layers {args.layers} outside 1.."
                             f"{arch.n_layers} for {arch.name}")
        depth = f"n_layers={args.layers} (cut from {arch.n_layers})"
        arch = dataclasses.replace(arch, n_layers=args.layers)
    if args.mesh == "local":
        mesh = mesh_mod.make_local_mesh()
    elif args.mesh == "pod":
        mesh = mesh_mod.make_pod_mesh(args.procs, args.local_devices)
    elif args.mesh == "test":
        n = len(jax.devices())
        assert n >= 8, "use --devices 8 (or more) with --mesh test"
        mesh = mesh_mod.make_test_mesh((2, n // 4, 2))
    else:
        mesh = mesh_mod.make_production_mesh(
            multi_pod=(args.mesh == "multi"))

    overrides = {}
    if args.compression:
        overrides["compression"] = args.compression
    if args.compress_axes:
        overrides["compress_axes"] = args.compress_axes
    if args.comm:
        overrides["comm"] = args.comm
    if args.overlap:
        # overlap is DDP-only (ZeRO-1 and accum>1 compose with it); say so
        # when we flip the arch's own plan instead of silently
        # benchmarking a different configuration than the arch name
        # suggests
        if arch.plan.dp_mode != "ddp":
            print(f"[train] --overlap forces dp_mode='ddp' "
                  f"(arch plan had dp_mode={arch.plan.dp_mode!r})")
        overrides.update(overlap=True, dp_mode="ddp")
    if args.adaptive:
        from repro.adaptive import controller as actl
        plan = dataclasses.replace(arch.plan, **overrides)
        if plan.dp_mode != "ddp":
            print(f"[train] --adaptive forces dp_mode='ddp' "
                  f"(arch plan had dp_mode={plan.dp_mode!r})")
        plan, decision = actl.resolve_plan(
            plan, arch, n_dev=mesh.devices.size,
            batch=args.batch, seq=args.seq)
        print(f"[train] adaptive: scheme={decision.scheme} "
              f"comm={decision.comm} predicted "
              f"{decision.t_pred * 1e3:.3f} ms/step vs overlapped "
              f"syncSGD {decision.t_base * 1e3:.3f} ms/step")
        arch = dataclasses.replace(arch, plan=plan)
        overrides = {}
    setup = ts.build(arch, mesh, **overrides)
    sched = ""
    if setup.overlap:
        from repro.train import overlap as overlap_mod
        sched = f" overlap={overlap_mod.effective_schedule(setup)}"
    print(f"[train] arch={arch.name} {depth} d_model={arch.d_model} "
          f"d_ff={arch.d_ff} heads={arch.n_heads}/{arch.n_kv_heads} "
          f"vocab={arch.vocab} seq={args.seq} batch={args.batch} "
          f"mesh={dict(zip(mesh.axis_names, mesh.devices.shape))} "
          f"dp_mode={setup.arch.plan.dp_mode} zero1={setup.zero1} "
          f"fsdp={setup.fsdp_axes} accum={args.accum} "
          f"agg={setup.agg_cfg.compressor}@{setup.agg_cfg.compress_axes}"
          f" comm={setup.comm.spec_str()}{sched}")

    data = Pipeline(DataConfig(vocab=arch.vocab, seq_len=args.seq,
                               global_batch=args.batch, seed=args.seed))
    if args.mesh == "pod":
        # the synthetic pipeline is seeded-deterministic, so every process
        # holds the identical global host batch; lift it to global arrays
        # sharded over the pod mesh before it reaches the jitted step
        import numpy as np
        from jax.sharding import NamedSharding

        class _GlobalBatches:
            def __init__(self, inner, setup):
                self.inner, self.setup = inner, setup
                self._specs_fn = ts.make_batch_specs(setup)

            def close(self):
                self.inner.close()

            def __iter__(self):
                for b in self.inner:
                    specs = self._specs_fn(b)
                    yield {k: jax.make_array_from_process_local_data(
                               NamedSharding(self.setup.mesh, specs[k]),
                               np.asarray(v))
                           for k, v in b.items()}

        data = _GlobalBatches(data, setup)
    return setup, data


def main(argv=None):
    """Parse, prepare, train; returns the :class:`Trainer` (its
    ``history`` holds the logged steps, its ``state`` the final state;
    its data pipeline is closed)."""
    args = parse_args(argv)
    setup, data = prepare(args)

    import jax

    from repro.train.schedule import ScheduleConfig
    from repro.train.trainer import Trainer, TrainerConfig

    tcfg = TrainerConfig(
        total_steps=args.steps, log_every=args.log_every,
        ckpt_every=args.ckpt_every, ckpt_dir=args.ckpt_dir,
        sync_every=args.sync_every, accum=args.accum,
        schedule=ScheduleConfig(peak_lr=args.lr, warmup_steps=args.warmup,
                                total_steps=args.steps))
    trainer = Trainer(setup, tcfg, data)
    try:
        state = trainer.run(jax.random.key(args.seed))
    finally:
        data.close()
    print(f"[train] done at step {int(jax.device_get(state['step']))}")
    return trainer


if __name__ == "__main__":
    main()
