#!/usr/bin/env python3
"""The chip benchmark: one cell of ``BENCHMARK.json`` per run.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

Runs on the machine it is started on, from the root of a checkout, and
refuses to run without a TPU: the first JAX device must be one, and there
must be as many as the cell asks for. Set-up (weights drawn on the device
from the seed, programs compiled or read from the checkout's compile
cache, warm-up) is ``setup_s``; then the cell's job is measured for
``--seconds``; after the window the plain reference checks what the timed
path produced. ``--trace 0`` reports the cell's end-to-end metrics;
``--trace 1`` traces the window's first seconds with the profiler and
reports its per-layer metrics. The last line of standard output is the
result (``correct``, ``attempted``, ``failed``, ``metrics``, ``device``,
``breakdown``, and last ``checks``: each compared number with its limit);
the same checks are the last lines of standard error.

``--control fp8`` (not used by the benchmark's own runs) puts the plain
reference computed in fp8 in the program's place and reports the same
comparison; ``--fault <name>`` plants one of the faults the check must
catch (``stale_state``, ``half_batch``, ``altered_token``)."""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
sys.path.insert(0, BENCH_DIR)
os.environ.setdefault("TPU_LOG_DIR", "disabled")   # no compiler logs in /tmp

from harness import bench, device  # noqa: E402


class Ctx:
    def __init__(self, cell, args, devices, t_start):
        self.cell, self.seed, self.seconds = cell, args.seed, args.seconds
        self.trace, self.fault, self.control = (bool(args.trace), args.fault,
                                                args.control)
        self.devices, self.t_start = devices, t_start
        self.device = device.describe(devices)
        self.peaks = device.peaks(self.device["kind"]) \
            if devices[0].platform == "tpu" else {}
        self.memory_stats = []
        self.trace_dir = os.path.join(BENCH_DIR, "out",
                                      f"trace-{cell.name}-{args.seed}")

    def memory_peak(self) -> int:
        """The peak footprint of the fullest chip; its statistics are kept
        for the report."""
        self.memory_stats = device.memory_stats(self.devices)
        return max(device.footprint(s) for s in self.memory_stats)

    def reference_module(self):
        return bench.load_module("references", self.cell.config["reference"])


def run_cell(cell, args, devices, t_start):
    """Drive one run of ``cell`` on ``devices`` and return the ``Run``."""
    ctx = Ctx(cell, args, devices, t_start)
    job = bench.load_module("jobs", cell.traffic["kind"])
    try:
        run = job.run(ctx)
        keep = ("peak_bytes_in_use", "peak_bytes_reserved", "bytes_limit")
        run.data["memory"] = [{k: s[k] for k in keep if k in s}
                              for s in ctx.memory_stats]
        return run
    finally:
        if os.path.isdir(ctx.trace_dir) and not args.keep_trace:
            shutil.rmtree(ctx.trace_dir, ignore_errors=True)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", choices=("fp8",), default=None)
    ap.add_argument("--fault", default=None,
                    choices=("stale_state", "half_batch", "altered_token"))
    ap.add_argument("--keep-trace", action="store_true")
    ap.add_argument("--set", action="append", default=[], metavar="KEY=JSON",
                    help="override a parameter of the cell's mix (the "
                    "one-time knee sweep; never used by the benchmark)")
    args = ap.parse_args(argv)

    spec = bench.load_spec(ROOT)
    cell = bench.find_cell(spec, args.workload)
    for kv in args.set:
        k, v = kv.split("=", 1)
        cell.traffic[k] = json.loads(v)
    import jax
    try:
        devices = device.require_chips(cell.chips)
    except device.NoChip as e:
        sys.exit(f"chipbench: {e}; this benchmark runs only on the chip")
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro import runtime
    cache = runtime.init_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    d = device.describe(devices)
    print(f"[device] {d['platform']} {d['kind']} x{d['count']} | jax "
          f"{jax.__version__} | compile cache {cache}", flush=True)

    run = run_cell(cell, args, devices, T_START)
    traced = bool(args.trace)
    entries = cell.per_layer if traced else cell.end_to_end
    metrics = bench.read_metrics(run, entries) if run.error is None else {}
    for k, v in run.data.items():
        if k in ("readings", "reference_s", "lateness_s", "backlog",
                 "dispatches", "memory", "compiled_memory"):
            print(f"[run] {k}: {v}", flush=True)
    bench.emit(run, metrics, traced)


if __name__ == "__main__":
    main()
