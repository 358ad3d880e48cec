#!/usr/bin/env python3
"""Smoke test of the main path on TPU: the overlapped DDP train step with
gradient compression, through the launcher a user calls.

    python3 chip_smoke.py                # one chip (every visible chip)
    python3 chip_smoke.py --four-chips   # the data=4 path of a 4-chip host

One chip runs three phases in one process:

  device   refuse anything but a TPU; print platform, kind, count and the
           jax / libtpu versions;
  kernels  each main-path Pallas kernel compiled (``interpret=False``, and
           ``tpu_custom_call`` in the compiled program) against its
           ``repro.kernels.ref`` oracle: exact where the CPU tests are
           exact, a stated tolerance for PowerSGD's fp32 matmuls;
  train    tinyllama-1.1b at its published widths, depth cut to
           :data:`LAYERS`, through ``repro.launch.train.main``: a few
           overlapped syncSGD steps, then a few PowerSGD steps with
           ``--compress-axes all``, so the compressor and its kernels run
           inside the step.  Step-1 losses must be finite, near ln(vocab)
           and equal between the two; later losses and grad norms finite.

``--four-chips`` runs only what exists across chips: a data=4 mesh, the
overlapped syncSGD step against the serial schedule (bit-identical on a
CPU mesh), PowerSGD over the data axis, and a check that the parameters
sit on all four devices.

Timings printed here are smoke timings, not benchmark numbers.  The last
stdout line is ``{"ok": true, "device": {...}}``, printed only when every
phase passed; any failure raises and exits nonzero.
"""
from __future__ import annotations

import argparse
import gc
import json
import math
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

ARCH = "tinyllama-1.1b"
#: depth cut: the widths stay published; 8 of 22 layers (0.48B params)
#: is what one 16 GB chip holds with ZeRO-1 AdamW state, PowerSGD error
#: memory and the activations of batch 4 x 2048 tokens
LAYERS = 8
SEQ = 2048
BATCH_PER_CHIP = 4
STEPS = 4
SEED = 0
#: the launcher's size flags (a CPU rehearsal swaps in the smoke config)
SIZE_ARGS = ["--full-size", "--layers", str(LAYERS)]
#: 25 MB fp32 gradient bucket (the DDP default bucket size)
BUCKET = 25 * 2**20 // 4
#: PowerSGD's fp32 matmuls: relative Frobenius error bound.  A single
#: bf16 MXU pass gives ~3e-3 on normal data; a tiling or indexing fault
#: gives O(1).
PSGD_RTOL = 1e-2


class SmokeFailure(Exception):
    pass


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def log(msg: str) -> None:
    print(msg, flush=True)


# --------------------------------------------------------------- device
def device_phase(want: int | None):
    import importlib.metadata

    import jax
    devs = jax.devices()
    d = devs[0]
    check(d.platform == "tpu",
          f"no TPU: jax.devices()[0].platform is {d.platform!r}")
    if want is not None:
        check(len(devs) == want, f"need {want} chips, found {len(devs)}")
    log(f"[device] platform={d.platform} kind={d.device_kind} "
        f"count={len(devs)} jax={jax.__version__} "
        f"libtpu={importlib.metadata.version('libtpu')}")
    log(f"[device] LIBTPU_INIT_ARGS={os.environ.get('LIBTPU_INIT_ARGS')!r} "
        f"(libtpu aborts on a flag it does not know)")
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devs)}


# -------------------------------------------------------------- kernels
def kernel_phase():
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels import bitpack, powersgd, qsgd, ref, topk

    n = BUCKET
    k = jax.random.split(jax.random.key(SEED), 6)
    g = jax.random.normal(k[0], (n,), jnp.float32)
    words = jnp.stack([ref.pack_signs(jax.random.normal(k[1 + i], (n,)))
                       for i in range(4)])                  # p = 4 voters
    m = jax.random.normal(k[5], (2048, 5632), jnp.float32)
    q = jax.random.normal(k[1], (5632, 4), jnp.float32)
    p = jax.random.normal(k[2], (2048, 4), jnp.float32)
    norm = jnp.linalg.norm(g)
    thr = jnp.float32(1.5)

    cases = [
        ("pack_signs", lambda x: bitpack.pack_signs(x, interpret=False),
         ref.pack_signs, (g,), None),
        ("popcount_votes",
         lambda w: bitpack.popcount_votes(w, n, interpret=False),
         lambda w: ref.popcount_votes(w, n), (words,), None),
        ("powersgd.encode", lambda a, b: powersgd.encode(a, b,
                                                         interpret=False),
         ref.powersgd_encode, (m, q), PSGD_RTOL),
        ("powersgd.decode", lambda a, b: powersgd.decode(a, b,
                                                         interpret=False),
         ref.powersgd_decode, (p, q), PSGD_RTOL),
        ("qsgd.quantize",
         lambda x, s, kk: qsgd.quantize(x, s, 127, kk, interpret=False),
         lambda x, s, kk: ref.qsgd_quantize(x, s, 127, kk),
         (g, norm, k[3]), None),
        ("threshold_mask",
         lambda x, t: topk.threshold_mask(x, t, interpret=False),
         ref.topk_threshold_mask, (g, thr), None),
    ]
    for name, kern, oracle, args, rtol in cases:
        t0 = time.perf_counter()
        compiled = jax.jit(kern).lower(*args).compile()
        t_compile = time.perf_counter() - t0
        check("tpu_custom_call" in compiled.as_text(),
              f"{name}: no tpu_custom_call in the compiled program")
        got = np.asarray(jax.block_until_ready(compiled(*args)))
        want = np.asarray(jax.jit(oracle)(*args))
        check(got.shape == want.shape and got.dtype == want.dtype,
              f"{name}: {got.shape}/{got.dtype} vs oracle "
              f"{want.shape}/{want.dtype}")
        if rtol is None:
            bad = int(np.sum(got != want))
            check(bad == 0, f"{name}: {bad} of {got.size} elements differ "
                            f"from the oracle")
            verdict = "bit-equal"
        else:
            err = float(np.linalg.norm(got - want) / np.linalg.norm(want))
            check(err <= rtol, f"{name}: relative error {err} > {rtol}")
            verdict = f"relative Frobenius error {err} (bound {rtol})"
        log(f"[kernels] {name} {args[0].shape}: tpu_custom_call, "
            f"{verdict}; compile {t_compile:.2f} s")


# ---------------------------------------------------------------- train
def train_argv(batch: int, extra=()) -> list[str]:
    return ["--arch", ARCH, *SIZE_ARGS, "--overlap", "--mesh", "local",
            "--steps", str(STEPS), "--batch", str(batch), "--seq", str(SEQ),
            "--log-every", "1", "--seed", str(SEED), *extra]


POWERSGD = ("--compression", "powersgd", "--compress-axes", "all")


def peak_bytes(jax) -> str:
    stats = jax.devices()[0].memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    if peak is None:
        return "not reported"
    return f"{peak} B ({peak / 2**30:.3f} GiB)"


def run_trainer(label: str, argv: list[str], want_kernels: bool):
    """One ``repro.launch.train.main`` run; returns ``(history, vocab)``
    after checking finiteness and whether the step holds Pallas
    kernels."""
    import jax
    import jax.numpy as jnp

    from repro.data.synthetic import batch_at
    from repro.launch import train

    log(f"[train] {label}: python -m repro.launch.train {' '.join(argv)}")
    trainer = train.main(argv)
    hist = trainer.history
    check(len(hist) == STEPS, f"{label}: {len(hist)} logged steps")
    for h in hist:
        check(math.isfinite(h["loss"]) and math.isfinite(h["grad_norm"]),
              f"{label}: non-finite step {h}")
    agg = trainer.setup.agg_cfg
    if want_kernels:
        check(agg.compressor == "powersgd" and agg.compress_axes,
              f"{label}: compressor {agg.compressor}@{agg.compress_axes}")
    text = trainer.step_fn.lower(trainer.state,
                                 batch_at(trainer.data.cfg, 0),
                                 jnp.float32(0.0)).as_text()
    n_kern = text.count("tpu_custom_call")
    check((n_kern > 0) == want_kernels,
          f"{label}: {n_kern} tpu_custom_call in the step")
    steady = [h["step_s"] for h in hist[1:]]
    log(f"[train] {label}: losses {[h['loss'] for h in hist]} grad norms "
        f"{[h['grad_norm'] for h in hist]}; {n_kern} tpu_custom_call in "
        f"the step; smoke timings (host clock, not a benchmark): step 1 "
        f"with compile {hist[0]['step_s']:.3f} s, steady steps "
        f"{[round(s, 4) for s in steady]} s; peak memory so far "
        f"{peak_bytes(jax)}")
    vocab = trainer.setup.arch.vocab
    del trainer
    gc.collect()
    return hist, vocab


def train_phase(n_chips: int):
    batch = BATCH_PER_CHIP * n_chips
    sync, vocab = run_trainer("syncSGD", train_argv(batch), False)
    psgd, _ = run_trainer("PowerSGD", train_argv(batch, POWERSGD), True)
    l_sync, l_psgd = sync[0]["loss"], psgd[0]["loss"]
    check(abs(l_sync - math.log(vocab)) <= 1.0,
          f"step-1 loss {l_sync} far from ln({vocab})={math.log(vocab)}")
    # same parameters, same batch, loss taken before any update
    check(l_sync == l_psgd,
          f"step-1 losses differ: syncSGD {l_sync!r} PowerSGD {l_psgd!r}")
    log(f"[train] step-1 loss {l_sync!r} vs ln({vocab}) "
        f"{math.log(vocab)!r}; syncSGD and PowerSGD step 1 bit-equal")


# ----------------------------------------------------------- four chips
def four_chip_phase():
    """data=4: overlapped vs serial syncSGD, PowerSGD, placement."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import PartitionSpec as P

    from repro.launch import train
    from repro.train import overlap
    from repro.train import train_step as ts

    n = jax.device_count()
    args = train.parse_args(train_argv(BATCH_PER_CHIP * n))
    setup, data = train.prepare(args)
    check(dict(zip(setup.mesh.axis_names, setup.mesh.devices.shape))
          == {"data": n, "model": 1}, f"mesh {setup.mesh}")
    spec_of = ts.make_batch_specs(setup)
    batches = [jax.device_put(b, setup.sharding(spec_of(b)))
               for b in (next(data) for _ in range(STEPS))]
    data.close()
    lr = jax.device_put(jnp.float32(args.lr), setup.sharding(P()))
    runs = {}
    for sched in ("serial", "overlap"):
        state = ts.init_state(setup, jax.random.key(SEED))
        t0 = time.perf_counter()
        step = overlap.make_step(setup, sched)(batches[0]).lower(
            state, batches[0], lr).compile()
        t_compile = time.perf_counter() - t0
        hlo = step.as_text()
        ms, times = [], []
        for b in batches:
            t0 = time.perf_counter()
            state, m = step(state, b, lr)
            ms.append(jax.device_get(m))
            times.append(time.perf_counter() - t0)
        if sched == "overlap":
            placement_check(state, n)
        runs[sched] = (jax.device_get(state["params"]), ms)
        log(f"[four-chips] {sched}: losses "
            f"{[float(m['loss']) for m in ms]} grad norms "
            f"{[float(m['grad_norm']) for m in ms]}; compile "
            f"{t_compile:.1f} s; collectives in the program: "
            f"all-reduce-start {hlo.count('all-reduce-start')} "
            f"all-gather-start {hlo.count('all-gather-start')} "
            f"plain all-reduce {hlo.count(' all-reduce(')}; smoke "
            f"timings (host clock) {[round(t, 4) for t in times]} s")
        del state, step
        gc.collect()

    (p_ser, m_ser), (p_ovl, m_ovl) = runs["serial"], runs["overlap"]
    leaves_s, leaves_o = jax.tree.leaves(p_ser), jax.tree.leaves(p_ovl)
    differ = [i for i, (a, b) in enumerate(zip(leaves_s, leaves_o))
              if not np.array_equal(a, b)]
    max_diff = max((float(np.max(np.abs(np.asarray(leaves_s[i], np.float32)
                                        - np.asarray(leaves_o[i],
                                                     np.float32))))
                    for i in differ), default=0.0)
    metric_equal = all(float(a[k]) == float(b[k])
                       for a, b in zip(m_ser, m_ovl) for k in a)
    log(f"[four-chips] serial vs overlap after {STEPS} steps: "
        f"{len(differ)} of {len(leaves_s)} param leaves differ (max abs "
        f"{max_diff!r}); metrics "
        f"{'bit-equal' if metric_equal else 'differ'}")
    for a, b in zip(m_ser, m_ovl):
        check(abs(float(a["loss"]) - float(b["loss"]))
              <= 1e-3 * abs(float(a["loss"])),
              f"serial vs overlap loss {a['loss']} vs {b['loss']}")
    del runs, p_ser, p_ovl, leaves_s, leaves_o
    gc.collect()
    run_trainer("PowerSGD data=4", train_argv(BATCH_PER_CHIP * n, POWERSGD),
                True)


def placement_check(state, n: int) -> None:
    """Every parameter leaf has one shard on each of the n devices, and
    each device owns a different ZeRO-1 optimizer shard."""
    import jax
    import numpy as np

    devs = set(jax.devices())
    for leaf in jax.tree.leaves(state["params"]):
        shard_devs = {s.device for s in leaf.addressable_shards}
        check(shard_devs == devs and leaf.sharding.device_set == devs,
              f"param leaf {leaf.shape} on {len(shard_devs)} devices")
    master = state["opt"]["shard"]["master"]
    rows = [np.asarray(s.data) for s in master.addressable_shards]
    check({s.device for s in master.addressable_shards} == devs
          and len(rows) == n, "ZeRO-1 master not spread over the devices")
    distinct = len({r.tobytes() for r in rows})
    check(distinct == n, f"ZeRO-1 shards: {distinct} distinct of {n}")
    log(f"[four-chips] placement: {len(jax.tree.leaves(state['params']))} "
        f"param leaves each on all {n} devices; ZeRO-1 master "
        f"{master.shape} holds {distinct} distinct per-device shards")


# ----------------------------------------------------------------- main
def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the data=4 path on a four-chip host")
    args = ap.parse_args(argv)

    # before jax initializes: libtpu reads its flags once, when it loads
    from repro.launch import compile_cache
    from repro.train.overlap import enable_overlap_flags
    enable_overlap_flags()
    log(f"[cache] {compile_cache.enable()}")

    device = device_phase(4 if args.four_chips else None)
    if args.four_chips:
        four_chip_phase()
    else:
        kernel_phase()
        train_phase(device["count"])
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
