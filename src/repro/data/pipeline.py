"""Host-sharded data pipeline with background prefetch and an exact cursor.

The pipeline is an iterator of jnp batches.  State is ONE integer (the step
cursor) because batches are pure functions of it — checkpointing the cursor
makes restarts sample-exact.  A single prefetch thread overlaps host-side
generation with device compute (straggler hygiene: every host produces its
batch locally, no central dispenser).
"""
from __future__ import annotations

import queue
import threading
from typing import Optional

import jax.numpy as jnp
from jax.profiler import TraceAnnotation

from repro.data.synthetic import DataConfig, batch_at


class Pipeline:
    def __init__(self, cfg: DataConfig, host: int = 0, num_hosts: int = 1,
                 start_step: int = 0, prefetch: int = 2):
        self.cfg = cfg
        self.host = host
        self.num_hosts = num_hosts
        self._step = start_step
        self._prefetch = prefetch
        self._q: Optional[queue.Queue] = None
        self._thread: Optional[threading.Thread] = None
        self._stop = threading.Event()

    # -------- cursor (checkpointed) --------
    def cursor(self) -> int:
        return self._step

    def seek(self, step: int):
        self._drain()
        self._step = step

    def close(self):
        """Stop the prefetch thread.  Call before the process exits: a
        daemon thread still inside a device transfer while the interpreter
        shuts down aborts the process."""
        self._drain()

    # -------- iteration --------
    def _producer(self, start: int):
        s = start
        while not self._stop.is_set():
            b = batch_at(self.cfg, s, self.host, self.num_hosts)
            b = {k: jnp.asarray(v) for k, v in b.items()}
            try:
                self._q.put((s, b), timeout=0.2)
                s += 1
            except queue.Full:
                continue

    def _drain(self):
        if self._thread is not None:
            self._stop.set()
            try:
                while True:
                    self._q.get_nowait()
            except queue.Empty:
                pass
            self._thread.join(timeout=2.0)
            self._thread = None
            self._stop = threading.Event()

    def __iter__(self):
        return self

    def __next__(self):
        # "data.wait": the consumer's wait for a batch, as a host span in
        # a profiler trace (under a microsecond when none is being taken)
        if self._prefetch <= 0:
            with TraceAnnotation("data.wait"):
                b = batch_at(self.cfg, self._step, self.host,
                             self.num_hosts)
                self._step += 1
                return {k: jnp.asarray(v) for k, v in b.items()}
        if self._thread is None:
            self._q = queue.Queue(maxsize=self._prefetch)
            self._thread = threading.Thread(
                target=self._producer, args=(self._step,), daemon=True)
            self._thread.start()
        with TraceAnnotation("data.wait"):
            s, b = self._q.get()
        self._step = s + 1
        return b
