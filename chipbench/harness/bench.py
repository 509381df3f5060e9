"""The benchmark's spec and its result line.

Everything a cell needs is found by name from ``BENCHMARK.json``:
``configs/<config>.json`` (sizes), ``traffic/<traffic>.json`` (the job or
traffic mix, with its ``kind``), ``cells/<cell>.json`` (what belongs to the
pairing: rates found once on the chip, limits of the correctness check),
``jobs/<kind>.py`` (the driver of that kind of job) and
``metrics/<metric>.py`` (one reader per metric). A later cell, mix,
configuration or metric is added as new files; none here is edited."""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import math
import os
import sys
from typing import Any, Dict, List, Optional

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


def _load_json(path):
    with open(path) as f:
        return json.load(f)


def load_spec(root: str = ROOT) -> dict:
    return _load_json(os.path.join(root, "BENCHMARK.json"))


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict          # configs/<config>.json
    traffic: dict         # traffic/<traffic>.json merged with cells/<cell>
    limits: dict          # name -> limit of each compared number
    end_to_end: List[dict]
    per_layer: List[dict]


def find_cell(spec: dict, name: str, bench_dir: str = BENCH_DIR) -> Cell:
    w = {x["name"]: x for x in spec["workloads"]}
    if name not in w:
        raise KeyError(f"no workload {name!r}; known: {sorted(w)}")
    w = w[name]
    conf = {c["name"]: c for c in spec["configs"]}[w["config"]]
    return load_cell(spec, name, os.path.join(ROOT, conf["file"]),
                     w["traffic"], int(w["chips"]), bench_dir)


def load_cell(spec: dict, name: str, config_file: str, traffic_name: str,
              chips: int, bench_dir: str = BENCH_DIR) -> Cell:
    """A cell from its files; its metrics are those ``spec`` gives it."""
    config = _load_json(config_file)
    traffic = _load_json(os.path.join(bench_dir, "traffic",
                                      traffic_name + ".json"))
    cell_file = os.path.join(bench_dir, "cells", name + ".json")
    extra = _load_json(cell_file) if os.path.exists(cell_file) else {}
    limits = extra.pop("limits", {})
    traffic = {**traffic, **extra}
    e2e = [m for m in spec["end_to_end"]
           if "workloads" not in m or name in m["workloads"]]
    e2e_names = {m["name"] for m in e2e}
    per = [m for m in spec["per_layer"]
           if (name in m["workloads"] if "workloads" in m
               else m["moves"] in e2e_names)]
    return Cell(name, chips, config, traffic, limits, e2e, per)


def load_module(kind: str, name: str, bench_dir: str = BENCH_DIR):
    """``jobs/<name>.py`` or ``metrics/<name>.py`` (names may hold dots)."""
    path = os.path.join(bench_dir, kind, name + ".py")
    if bench_dir not in sys.path:
        sys.path.insert(0, bench_dir)
    mod_name = f"chipbench_{kind}_{name.replace('.', '_').replace('-', '_')}"
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Check:
    """One number compared with its limit: ``ok`` when value <= limit."""
    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return math.isfinite(self.value) and self.value <= self.limit


@dataclasses.dataclass
class Run:
    """What a job hands back; metric readers read it."""
    cell: Cell
    device: dict
    peaks: dict
    setup_s: float = 0.0
    window_s: float = 0.0
    attempted: int = 0
    failed: int = 0
    checks: List[Check] = dataclasses.field(default_factory=list)
    memory_peak_bytes: int = 0
    data: Dict[str, Any] = dataclasses.field(default_factory=dict)
    trace: Optional[Any] = None          # harness.trace.Reduced
    error: Optional[str] = None

    @property
    def correct(self) -> bool:
        return (self.error is None and bool(self.checks)
                and all(c.ok for c in self.checks))


def read_metrics(run: Run, entries: List[dict]) -> Dict[str, dict]:
    out = {}
    for m in entries:
        val = load_module("metrics", m["name"]).read(run)
        if val is None:
            continue
        out[m["name"]] = {"value": float(val), "unit": m["unit"]}
    return out


def emit(run: Run, metrics: Dict[str, dict], traced: bool):
    """The check lines on stderr, then the result line on stdout."""
    for c in run.checks:
        print(f"[check] {c.name} {c.value!r} limit {c.limit!r} "
              f"{'ok' if c.ok else 'FAIL'}", file=sys.stderr, flush=True)
    if run.error:
        print(f"[check] error: {run.error}", file=sys.stderr, flush=True)
    dev = dict(run.device, memory_peak_bytes=int(run.memory_peak_bytes))
    line = {"correct": run.correct, "attempted": int(run.attempted),
            "failed": int(run.failed), "metrics": metrics, "device": dev}
    if traced and run.trace is not None:
        dev["busy_s"] = run.trace.busy_s
        dev["window_s"] = run.trace.window_s
        line["breakdown"] = run.trace.breakdown()
    line["checks"] = {c.name: {"value": c.value, "limit": c.limit}
                      for c in run.checks}
    print(json.dumps(line), flush=True)
