"""The training loop: step dispatch, logging, fault tolerance.

Production concerns handled here (DESIGN.md §4):
  * checkpoint/restart — atomic sharded checkpoints every ``ckpt_every``
    steps (+ final), exact resume including data-pipeline cursor and
    compressor error-feedback state;
  * preemption — SIGTERM/SIGINT trap -> synchronous checkpoint -> clean
    exit (trainer.stop_requested);
  * local-SGD mode — ``sync_every > 1`` converts the pod-axis (DCN) sync
    from per-step to per-N-steps: params are averaged across pods every N
    steps while intra-pod sync stays per-step (bounded-staleness straggler
    mitigation at pod granularity, composes with gradient compression);
  * throughput accounting — tokens/s and (on real hardware) step time; on
    CPU these are functional only.
"""
from __future__ import annotations

import dataclasses
import signal
import time
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp

from repro.train import schedule as sched_mod
from repro.train import train_step as ts


@dataclasses.dataclass
class TrainerConfig:
    total_steps: int = 100
    log_every: int = 10
    ckpt_every: int = 0               # 0 = only final
    ckpt_dir: Optional[str] = None
    keep_ckpts: int = 3
    sync_every: int = 1               # local-SGD pod-sync period
    accum: int = 1
    schedule: sched_mod.ScheduleConfig = dataclasses.field(
        default_factory=sched_mod.ScheduleConfig)


class Trainer:
    def __init__(self, setup: ts.TrainSetup, cfg: TrainerConfig,
                 data_iter, state=None):
        self.setup = setup
        self.cfg = cfg
        self.data = data_iter
        self.state = state
        self.step_fn = None
        self.sync_fn = None
        self.stop_requested = False
        self.history: list[dict] = []
        self._manager = None
        if cfg.ckpt_dir:
            from repro.checkpoint.manager import CheckpointManager
            self._manager = CheckpointManager(cfg.ckpt_dir, setup,
                                              keep=cfg.keep_ckpts)

    # ------------------------------------------------------------------
    def _install_signal_handlers(self):
        def handler(signum, frame):
            self.stop_requested = True
        for sig in (signal.SIGTERM, signal.SIGINT):
            try:
                signal.signal(sig, handler)
            except ValueError:
                pass  # not on main thread (tests)

    def _maybe_restore(self, key):
        if self._manager is not None:
            restored = self._manager.restore_latest()
            if restored is not None:
                self.state, cursor = restored
                if cursor is not None and hasattr(self.data, "seek"):
                    self.data.seek(cursor)
                return
        if self.state is None:
            self.state = ts.init_state(self.setup, key)

    # ------------------------------------------------------------------
    def run(self, key=None):
        self._install_signal_handlers()
        self._maybe_restore(key)
        cfg = self.cfg
        batch = next(iter(self.data))
        if self.step_fn is None:
            self.step_fn = ts.make_step(self.setup, accum=cfg.accum)(batch)
        if cfg.sync_every > 1 and self.sync_fn is None:
            self.sync_fn = ts.local_sgd_sync(self.setup)

        start_step = int(jax.device_get(self.state["step"]))
        it = iter(self.data)
        t0 = t_log = time.time()
        tokens_acc = 0
        for step in range(start_step, cfg.total_steps):
            if step > start_step:
                batch = next(it)
            lr = sched_mod.lr_at(cfg.schedule, step)
            self.state, metrics = self.step_fn(self.state, batch,
                                               jnp.float32(lr))
            if self.sync_fn is not None and (step + 1) % cfg.sync_every == 0:
                self.state = self.sync_fn(self.state)
            if cfg.log_every and (step + 1) % cfg.log_every == 0:
                m = jax.device_get(metrics)
                now = time.time()
                tokens_acc += int(m["tokens"]) * cfg.log_every
                dt = now - t0
                # host seconds per step since the last log; the metrics
                # fetch waits for the device, so the first interval
                # includes compilation
                rec = dict(step=step + 1, loss=float(m["loss"]),
                           grad_norm=float(m["grad_norm"]), lr=lr,
                           tok_per_s=tokens_acc / max(dt, 1e-9),
                           step_s=(now - t_log) / cfg.log_every)
                t_log = now
                self.history.append(rec)
                print(f"step {rec['step']:>6d}  loss {rec['loss']:.4f}  "
                      f"gnorm {rec['grad_norm']:.3f}  lr {lr:.2e}  "
                      f"{rec['tok_per_s']:,.0f} tok/s", flush=True)
            if self._manager is not None and cfg.ckpt_every and \
                    (step + 1) % cfg.ckpt_every == 0:
                self._save(step + 1)
            if self.stop_requested:
                print(f"[trainer] preemption signal at step {step + 1}; "
                      "checkpointing and exiting", flush=True)
                self._save(step + 1)
                return self.state
        self._save(cfg.total_steps)
        return self.state

    def _save(self, step: int):
        if self._manager is None:
            return
        cursor = self.data.cursor() if hasattr(self.data, "cursor") else None
        self._manager.save(step, self.state, cursor)
