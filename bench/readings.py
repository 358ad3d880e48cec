"""The readings that a cell's limits are set from, at the cell's own size,
in one process (the step and the references compile once):

- sound runs of the program on every ``--seeds`` seed (the lower readings);
- the control, the reference in float8 (``reference.py``), on
  ``--control-seeds`` (the upper readings);
- planted faults on ``--fault-seeds``: half of each batch left out (its
  labels masked, the mean taken over the rest) and, on more than one
  chip, the gradient exchange left out.  A step that returns its state
  unchanged reads 1 on ``delta_gap`` by construction and needs no run.

    python3 bench/readings.py --workload <cell> --seeds 11,12,... \\
        --control-seeds 11,12,13 --fault-seeds 11,12,13

Prints one JSON line per reading; not part of a benchmark run.
"""
import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def _ints(s: str) -> list[int]:
    return [int(x) for x in s.split(",") if x]


def half_batch(batch):
    """The fault 'half of the batch left out': the second half of the
    rows gets masked labels, so the mean runs over the first half."""
    import jax.numpy as jnp
    labels = batch["labels"]
    half = labels.shape[0] // 2
    return {**batch, "labels": labels.at[half:].set(-1)}


def no_exchange():
    """Plant 'the exchange between chips left out': every bucket's mean
    over the data axis returns the local gradient."""
    from repro.core import aggregator
    aggregator.cp.mean_reduce = lambda x, axes, plan=None: x


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=_ints, required=True)
    ap.add_argument("--control-seeds", type=_ints, default=[])
    ap.add_argument("--fault-seeds", type=_ints, default=[])
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)

    from bench import compare, data, harness, program, spec, weights
    sp = spec.Spec()
    w = sp.workload(args.workload)
    cfg = sp.config(w["config"])
    program.prepare_process(w)
    import jax
    import jax.numpy as jnp
    device = harness.device_info(jax, w["chips"], require_tpu=True)
    from bench.reference import Reference

    out = open(args.out, "a") if args.out else None

    def emit(rec):
        rec.update(workload=w["name"], device=device)
        line = json.dumps(rec)
        print(line, flush=True)
        if out:
            out.write(line + "\n")
            out.flush()

    arch, compressor = sp.arch(cfg), sp.compressor(w)
    prog = program.Program(cfg, w, arch, compressor)
    lr = jnp.float32(w["lr"])
    ref = Reference(cfg, w, arch, compressor)
    ctl = Reference(cfg, w, arch, compressor, precision="fp8") \
        if args.control_seeds else None
    step = None

    def program_readings(seed, batch_fn=None):
        nonlocal step
        key = weights.seed_key(seed)
        feed = prog.feed(seed)
        batch = next(feed)
        if step is None:
            step = prog.make_step(batch)
        state, readings = harness.first_steps(
            prog, key, step, feed, batch, lr, keep_grad=True,
            batch_fn=batch_fn)
        feed.close()
        del state
        return key, readings

    def reference(key, seed, model):
        return model.run(key, data.batches(cfg, w, prog.global_batch, seed,
                                           harness.CHECK_STEPS),
                         keep_grad=True)

    def numbers(kind, seed, got, r, **extra):
        emit({"kind": kind, "seed": seed, "losses": got["losses"],
              **compare.numbers(got, r), **extra})

    for seed in args.seeds:
        t0 = time.perf_counter()
        key, got = program_readings(seed)
        r = reference(key, seed, ref)
        numbers("program", seed, got, r, ref_losses=r["losses"],
                seconds=time.perf_counter() - t0)
        if seed in args.control_seeds:
            numbers("control_fp8", seed, reference(key, seed, ctl), r)
        if seed in args.fault_seeds:
            _, bad = program_readings(seed, batch_fn=half_batch)
            numbers("fault_half_batch", seed, bad, r)
        del r
    if w["chips"] > 1 and args.fault_seeds:
        no_exchange()
        step = None
        for seed in args.fault_seeds:
            key, bad = program_readings(seed)
            numbers("fault_no_exchange", seed, bad, reference(key, seed, ref))
    if out:
        out.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
