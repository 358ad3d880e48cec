"""Finds everything a run needs by name, from files under ``bench/``.

``BENCHMARK.json`` (at the checkout root) names the cells and metrics.
Each configuration, cell and per-layer metric lives in a file of its own:

- ``bench/configs/<config>.json``: the model's sizes, its source and cuts,
  and ``arch``, the name of its architecture module;
- ``bench/models/<arch>.py``: the architecture (``bench/models/llama.py``
  says what such a module holds): its leaves, its plain reference
  forward pass and loss, its FLOPs per token, and the program's
  configuration for it;
- ``bench/workloads/<cell>.json``: the job (batch, plan, optimizer, limits);
- ``bench/metrics/<metric>.py``: a reader with ``LAYER``, ``UNIT``,
  ``MOVES`` and ``read(trace, record) -> float | None``;
- ``bench/compressors/<compression>.py``: the reference's step of the
  compressor a cell's plan names (``bench/reference.py`` says what it
  holds);
- ``bench/peaks.json``: the chip's peaks, keyed by ``device_kind``.

A new cell, configuration, architecture or metric is new files plus new
entries in ``BENCHMARK.json``; nothing here changes.
"""
from __future__ import annotations

import importlib.util
import json
import os

BENCH = os.path.dirname(os.path.abspath(__file__))


class SpecError(Exception):
    pass


def _load_json(path: str) -> dict:
    if not os.path.isfile(path):
        raise SpecError(f"missing file {path}")
    with open(path) as f:
        return json.load(f)


class Spec:
    """The benchmark as a tree of files rooted at ``bench`` (the directory
    that holds this module, or a copy of it in tests)."""

    def __init__(self, bench: str = BENCH):
        self.bench = bench
        self.root = os.path.dirname(bench)
        self.benchmark = _load_json(os.path.join(self.root,
                                                 "BENCHMARK.json"))

    def workload(self, name: str) -> dict:
        cells = {w["name"]: w for w in self.benchmark["workloads"]}
        if name not in cells:
            raise SpecError(f"no workload {name!r} in BENCHMARK.json; "
                            f"have {sorted(cells)}")
        w = _load_json(os.path.join(self.bench, "workloads", name + ".json"))
        for key in ("config", "chips"):
            if w[key] != cells[name][key]:
                raise SpecError(f"{name}: {key} {w[key]!r} in its file, "
                                f"{cells[name][key]!r} in BENCHMARK.json")
        return w

    def config(self, name: str) -> dict:
        return _load_json(os.path.join(self.bench, "configs", name + ".json"))

    def peak(self, device_kind: str) -> dict:
        peaks = _load_json(os.path.join(self.bench, "peaks.json"))
        if device_kind not in peaks:
            raise SpecError(f"no peaks for device kind {device_kind!r} in "
                            f"peaks.json (have {sorted(peaks)})")
        return peaks[device_kind]

    def end_to_end(self, cell: str) -> list[dict]:
        return [m for m in self.benchmark["end_to_end"]
                if cell in m.get("workloads", [cell])]

    def per_layer(self, cell: str) -> list[dict]:
        return [m for m in self.benchmark["per_layer"]
                if cell in m.get("workloads", [cell])]

    def _module(self, kind: str, name: str):
        path = os.path.join(self.bench, kind, name + ".py")
        if not os.path.isfile(path):
            raise SpecError(f"no file {path} for {kind[:-1]} {name!r}")
        mod_name = f"bench_{kind}_" + "".join(
            c if c.isalnum() else "_" for c in name)
        loader = importlib.util.spec_from_file_location(mod_name, path)
        mod = importlib.util.module_from_spec(loader)
        loader.loader.exec_module(mod)
        return mod

    def reader(self, metric: str):
        """The module ``bench/metrics/<metric>.py``."""
        return self._module("metrics", metric)

    def arch(self, cfg: dict):
        """The module ``bench/models/<arch>.py`` of a configuration."""
        if "arch" not in cfg:
            raise SpecError(f"configuration {cfg.get('name')!r} names no "
                            f"arch")
        return self._module("models", cfg["arch"])

    def compressor(self, workload: dict):
        """The module ``bench/compressors/<compression>.py`` of a cell."""
        return self._module("compressors",
                            workload["plan"].get("compression", "none"))
