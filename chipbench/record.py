#!/usr/bin/env python3
"""One traced run of a cell, split by the program's own names.

    python3 chipbench/record.py --workload <cell> --seed <n> \
        [--seconds 10] [--trace-seconds <s>] [--set KEY=JSON] [--out <dir>]

Runs the cell as ``run.py --trace 1 --keep-trace`` does (on the chip: the
first device must be a TPU), then joins its device trace with the
``db.*`` scopes of the programs that ran (``harness.scopes``): every
jitted function the run calls is noted with the shapes of its first call,
and after the run the step programs (``STEP_PROGRAMS``) are lowered and
compiled again (from the compile cache) for their HLO ``op_name``s.

The last line of standard output is a JSON object: the run's
correctness, per-layer metrics and checks; device seconds per step program
under each scope (``scope_ms``: per block update, i.e. over the step
programs of the first chip times the chips); idle seconds under each host
span (``idle_ms``: per step program); the unscoped share of busy time;
and the dispatch rate of the step programs while the profiler records and
after it stopped, in the same window (``tracing``). ``--out`` copies the
trace and its op-name map there (how the committed traces were made).

A cell not in ``BENCHMARK.json`` is named with ``--config``,
``--traffic`` and ``--chips``."""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import glob  # noqa: E402
import gzip  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import re  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
for p in (BENCH, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from harness import bench, scopes, trace  # noqa: E402

STEP_PROGRAMS = r"^(step|local_update|shard_map|serve_scan|chunk_step)$"


class Timed:
    """Calls ``obj``, noting the time of each call in ``calls``; the
    compiled program a ``Spy``'s lowering gives is wrapped in one."""

    def __init__(self, obj, calls):
        self.obj, self.calls = obj, calls

    def __call__(self, *args, **kwargs):
        self.calls.append(time.perf_counter())
        return self.obj(*args, **kwargs)

    def compile(self, *args, **kwargs):
        return Timed(self.obj.compile(*args, **kwargs), self.calls)

    def __getattr__(self, name):
        return getattr(self.obj, name)


class Spy:
    """A jitted function that notes the abstract arguments of its first
    call (or lowering) and the time of every call (of it, or of the
    program compiled from its lowering)."""

    def __init__(self, fn):
        self.fn, self.sig, self.calls = fn, None, []
        self.name = getattr(fn, "__name__", "?")

    def _note(self, args, kwargs):
        import jax
        if self.sig is None and not any(
                isinstance(x, jax.core.Tracer)
                for x in jax.tree_util.tree_leaves((args, kwargs))):
            self.sig = (jax.tree_util.tree_map(_abstract, args), kwargs)

    def __call__(self, *args, **kwargs):
        self._note(args, kwargs)
        self.calls.append(time.perf_counter())
        return self.fn(*args, **kwargs)

    def lower(self, *args, **kwargs):
        self._note(args, kwargs)
        return Timed(self.fn.lower(*args, **kwargs), self.calls)

    def __getattr__(self, name):
        return getattr(self.fn, name)


def _abstract(x):
    import jax
    if hasattr(x, "shape") and hasattr(x, "dtype"):
        # an uncommitted array goes wherever the call puts it
        placed = getattr(x, "committed", True)
        return jax.ShapeDtypeStruct(
            x.shape, x.dtype,
            sharding=getattr(x, "sharding", None) if placed else None)
    return x


@contextlib.contextmanager
def spied():
    """Wrap every ``jax.jit`` made inside in a ``Spy``; yields the list."""
    import jax
    made, real = [], jax.jit

    def jit(fn=None, **kw):
        if fn is None:
            return lambda f: jit(f, **kw)
        s = Spy(real(fn, **kw))
        made.append(s)
        return s
    jax.jit = jit
    try:
        yield made
    finally:
        jax.jit = real


@contextlib.contextmanager
def timed_record(marks: dict):
    """``harness.trace.record`` with the times the profiler started, the
    traced part ended and the profiler stopped."""
    real = trace.record

    @contextlib.contextmanager
    def record(out_dir):
        with real(out_dir):
            marks["on"] = time.perf_counter()
            yield
            marks["done"] = time.perf_counter()
        marks["off"] = time.perf_counter()
    trace.record = record
    try:
        yield
    finally:
        trace.record = real


def step_spies(made, rx=STEP_PROGRAMS):
    return [s for s in made if re.match(rx, s.name) and s.sig is not None]


def op_name_maps(spies):
    """The op-name maps of the spied programs. The compile cache's key
    leaves ``op_name``s out, so where a cached executable compiled from
    code without scopes comes back, the program is compiled afresh (the
    same HLO, so the same instruction names)."""
    import jax
    out = {}
    for s in spies:
        args, kwargs = s.sig
        c = s.fn.lower(*args, **kwargs).compile()
        if not any(scopes.scope_of(v)
                   for v in scopes.op_names(c.as_text()).values()):
            jax.config.update("jax_enable_compilation_cache", False)
            try:
                c = s.fn.lower(*args, **kwargs).compile()
            finally:
                jax.config.update("jax_enable_compilation_cache", True)
        scopes.maps_of([c], into=out)
    return out


def rates(spies, marks, t_end):
    """Step-program dispatches per second while tracing and after."""
    calls = sorted(t for s in spies for t in s.calls)
    if not {"on", "done", "off"} <= set(marks):
        return {}
    on = [t for t in calls if marks["on"] <= t < marks["done"]]
    off = [t for t in calls if marks["off"] <= t < t_end]
    out = {"traced_s": marks["done"] - marks["on"], "traced_calls": len(on),
           "untraced_s": t_end - marks["off"], "untraced_calls": len(off)}
    if on and off:
        out["traced_per_s"] = len(on) / out["traced_s"]
        out["untraced_per_s"] = len(off) / out["untraced_s"]
        out["cost"] = 1 - out["traced_per_s"] / out["untraced_per_s"]
    return out


def _ms(d, n):
    return {k: 1e3 * v / max(n, 1) for k, v in
            sorted(d.items(), key=lambda kv: -kv[1])}


def summary(split, n_chips):
    n = sum(v for k, v in split.programs.items()
            if re.match(r"^jit_(step|local_update|shard_map)$", k))
    busy = split.busy_s * max(split.n_devices, 1)
    return {
        "step_programs": n,
        "scope_ms": _ms(split.seconds, n * n_chips),
        "idle_ms": _ms(split.gaps, n),
        "per_execution_ms": {p: _ms(v, split.programs.get(p, 0) * n_chips)
                             for p, v in split.by_program.items()},
        "outer_per_execution_ms": {
            p: _ms(v, split.programs.get(p, 0) * n_chips)
            for p, v in split.outer.items()},
        "unscoped_share_of_busy": (split.scope_seconds(scopes.UNSCOPED)
                                   / busy if busy else None),
        "busy_s": split.busy_s, "window_s": split.window_s,
        "programs": split.programs, "keyed_by": split.keyed_by,
        "same_clock": split.same_clock,
    }


def save_trace(trace_dir, maps, out):
    os.makedirs(out, exist_ok=True)
    src = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                           recursive=True), key=os.path.getmtime)[-1]
    with open(src, "rb") as f, gzip.open(
            os.path.join(out, os.path.basename(src) + ".gz"), "wb") as g:
        shutil.copyfileobj(f, g)
    scopes.save(out, maps)


def record_cell(cell, seed, seconds, devices, out=None, t_start=None):
    """One traced run of ``cell`` on ``devices`` and its split."""
    import run as entry

    class Args:
        trace, control, fault, keep_trace = 1, None, None, True
    Args.seed, Args.seconds = seed, seconds
    t_start = time.perf_counter() if t_start is None else t_start
    marks = {}
    with spied() as made, timed_record(marks):
        run = entry.run_cell(cell, Args, devices, t_start)
    t_end = t_start + run.setup_s + run.window_s
    trace_dir = os.path.join(BENCH, "out", f"trace-{cell.name}-{seed}")
    try:
        if run.error is not None:
            return {"correct": False, "error": run.error}
        spies = step_spies(made)
        maps = op_name_maps(spies)
        split = scopes.reduce(trace_dir, [d.id for d in devices], maps)
        if out:
            save_trace(trace_dir, maps, out)
        return {"correct": run.correct, "error": run.error,
                "metrics": bench.read_metrics(run, cell.per_layer),
                "checks": {c.name: c.value for c in run.checks},
                "split": summary(split, cell.chips),
                "tracing": rates(spies, marks, t_end),
                "all_reduce_exposed_s": run.trace.exposed_seconds(
                    r"all-reduce|all_reduce|psum") if run.trace else None,
                "map_keys": sorted(maps), "tokens": run.data.get("tokens"),
                "steps": run.data.get("steps"), "window_s": run.window_s,
                "setup_s": run.setup_s}
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--trace-seconds", type=float, default=None)
    ap.add_argument("--out", default=None)
    ap.add_argument("--config", default=None)
    ap.add_argument("--traffic", default=None)
    ap.add_argument("--chips", type=int, default=1)
    ap.add_argument("--set", action="append", default=[], metavar="KEY=JSON",
                    help="override a parameter of the cell's mix")
    args = ap.parse_args(argv)

    import jax
    from harness import device
    spec = bench.load_spec(ROOT)
    if args.config:
        cell = bench.load_cell(spec, args.workload, os.path.join(
            BENCH, "configs", args.config + ".json"), args.traffic,
            args.chips)
    else:
        cell = bench.find_cell(spec, args.workload)
    if args.trace_seconds is not None:
        cell.traffic["trace_seconds"] = args.trace_seconds
    for kv in args.set:
        k, v = kv.split("=", 1)
        cell.traffic[k] = json.loads(v)
    devices = device.require_chips(cell.chips)
    from repro import runtime
    runtime.init_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    out = record_cell(cell, args.seed, args.seconds, devices, out=args.out,
                      t_start=T_START)
    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
