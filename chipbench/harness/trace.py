"""Reduce a profiler trace to what the per-layer metrics read.

``record(dir)`` wraps a window in ``jax.profiler`` tracing; ``reduce(dir)``
reads the ``.xplane.pb`` it wrote with ``jax.profiler.ProfileData`` (JAX
alone) and returns a ``Reduced``:

- device busy time: the union of the intervals in which an operation ran
  (the ``XLA Ops`` line of each ``/device:TPU:<n>`` plane), clipped to the
  traced window, averaged over the chips used;
- per-op device time, by HLO instruction name with its numeric suffix
  dropped (a Pallas kernel appears under the ``name=`` its call was
  given); a loop's event spans its body's ops, so sums over families
  count a loop body twice, and the breakdown leaves the containers out;
- per-program device time: the ``XLA Modules`` line, one event per
  execution of a jitted program (``jit_<function name>``);
- the benchmark's own host spans (``TraceAnnotation`` names starting with
  ``bench.``), and the device's idle gaps attributed to the innermost host
  span open at the gap's midpoint.
"""
from __future__ import annotations

import contextlib
import dataclasses
import glob
import gzip
import os
import re
from collections import defaultdict
from typing import Dict, List, Optional, Tuple

WINDOW_SPAN = "bench.window"
# ops whose trace event spans the ops of their body (counted by those)
CONTAINERS = ("while", "conditional", "call")
_SUFFIX = re.compile(r"(\.\d+)+$")


def op_family(name: str) -> str:
    """``%flash_attention_fwd.3 = bf16[...] custom-call(...)`` ->
    ``flash_attention_fwd``; ``jit_step(1234)`` -> ``jit_step``."""
    head = name.split(" = ", 1)[0].split("(", 1)[0].strip().lstrip("%")
    return _SUFFIX.sub("", head)


class Recorder:
    """Start and stop a trace into ``out_dir`` at chosen points, the traced
    part marked with the ``bench.window`` host span."""

    def __init__(self, out_dir: str):
        self.out_dir, self._span, self.on = out_dir, None, False

    def start(self):
        import jax
        os.makedirs(self.out_dir, exist_ok=True)
        jax.profiler.start_trace(self.out_dir)
        self._span = jax.profiler.TraceAnnotation(WINDOW_SPAN)
        self._span.__enter__()
        self.on = True

    def stop(self):
        import jax
        if self.on:
            self._span.__exit__(None, None, None)
            jax.profiler.stop_trace()
            self.on = False


@contextlib.contextmanager
def record(out_dir: str):
    """Trace everything inside the block into ``out_dir``."""
    r = Recorder(out_dir)
    r.start()
    try:
        yield
    finally:
        r.stop()


def _union(iv: List[Tuple[int, int]]) -> List[Tuple[int, int]]:
    out: List[Tuple[int, int]] = []
    for s, e in sorted(iv):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


@dataclasses.dataclass
class Reduced:
    window_s: float
    busy_s: float                        # mean over devices
    n_devices: int
    ops: Dict[str, float]                # op family -> seconds, all devices
    modules: Dict[str, List[float]]      # program -> durations (s), device 0
    spans: Dict[str, List[Tuple[int, int]]]          # host span intervals
    gaps: Dict[str, float]               # host activity -> idle seconds
    timeline: List[Tuple[int, int, str]]  # device 0 programs (start, end)
    raw_ops: Dict[int, List[Tuple[int, int, str]]]    # per device, clipped

    def op_seconds(self, pattern: str) -> float:
        """Device seconds, summed over devices, of op families matching."""
        rx = re.compile(pattern)
        return sum(v for k, v in self.ops.items() if rx.search(k))

    def program(self, pattern: str) -> List[float]:
        """Durations (s) on device 0 of programs whose name matches."""
        rx = re.compile(pattern)
        out: List[float] = []
        for k, v in self.modules.items():
            if rx.search(k):
                out.extend(v)
        return out

    def program_gaps(self, pattern: str) -> List[float]:
        """Seconds between the end of one matching program and the start of
        the next on device 0."""
        rx = re.compile(pattern)
        ev = [(s, e) for s, e, n in self.timeline if rx.search(n)]
        return [max(0, b[0] - a[1]) * 1e-9 for a, b in zip(ev, ev[1:])]

    def exposed_seconds(self, pattern: str) -> float:
        """Device seconds, averaged over devices, in which an op matching
        ``pattern`` runs and no other op does."""
        rx = re.compile(pattern)
        tot = 0.0
        for d, ev in self.raw_ops.items():
            coll = _union([(s, e) for s, e, n in ev if rx.search(n)])
            other = _union([(s, e) for s, e, n in ev if not rx.search(n)])
            j = 0
            for s, e in coll:
                cover = 0
                while j < len(other) and other[j][1] <= s:
                    j += 1
                k = j
                while k < len(other) and other[k][0] < e:
                    cover += min(e, other[k][1]) - max(s, other[k][0])
                    k += 1
                tot += (e - s - cover) * 1e-9
        return tot / max(len(self.raw_ops), 1)

    def breakdown(self) -> dict:
        n = max(self.n_devices, 1)
        top = sorted(((k, v) for k, v in self.ops.items()
                      if k not in CONTAINERS), key=lambda kv: -kv[1])[:10]
        gaps = sorted(self.gaps.items(), key=lambda kv: -kv[1])[:10]
        return {"device_ops": [[k, v / n] for k, v in top],
                "idle_gaps": [[k, v] for k, v in gaps]}


def _load(trace_dir: str):
    """The newest ``.xplane.pb`` (or gzipped ``.xplane.pb.gz``) under
    ``trace_dir``."""
    from jax.profiler import ProfileData
    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb*"),
                             recursive=True), key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    if files[-1].endswith(".gz"):
        with gzip.open(files[-1], "rb") as f:
            return ProfileData.from_serialized_xspace(f.read())
    return ProfileData.from_file(files[-1])


def _device_index(plane_name: str) -> Optional[int]:
    m = re.match(r"/device:TPU:(\d+)", plane_name)
    return int(m.group(1)) if m else None


def reduce(trace_dir: str, devices: Optional[List[int]] = None) -> Reduced:
    """``devices``: the device ids a cell used (default: every TPU plane
    with ops)."""
    pd = _load(trace_dir)
    ops_iv: Dict[int, List[Tuple[int, int, str]]] = defaultdict(list)
    mods: Dict[int, List[Tuple[int, int, str]]] = defaultdict(list)
    spans: Dict[str, List[Tuple[int, int]]] = defaultdict(list)
    for plane in pd.planes:
        dev = _device_index(plane.name)
        for line in plane.lines:
            if dev is not None and line.name == "XLA Ops":
                ops_iv[dev].extend((e.start_ns, e.start_ns + e.duration_ns,
                                    e.name) for e in line.events)
            elif dev is not None and line.name == "XLA Modules":
                mods[dev].extend((e.start_ns, e.start_ns + e.duration_ns,
                                  e.name) for e in line.events)
            elif dev is None:
                for e in line.events:
                    if e.name.startswith("bench."):
                        spans[e.name].append(
                            (int(e.start_ns), int(e.start_ns + e.duration_ns)))
    devs = sorted(devices if devices is not None else ops_iv)
    win = spans.get(WINDOW_SPAN)
    lo, hi = (win[0][0], win[0][1]) if win else (None, None)
    if lo is not None:
        # the window span is on the host clock; keep it only where the device
        # events share that clock (most of their time falls inside it)
        tot = sum(e - s for d in devs for s, e, _ in ops_iv[d])
        inside = sum(max(0, min(e, hi) - max(s, lo))
                     for d in devs for s, e, _ in ops_iv[d])
        if tot == 0 or inside < 0.5 * tot:
            lo = hi = None
    ops: Dict[str, float] = defaultdict(float)
    busy_iv: Dict[int, List[Tuple[int, int]]] = {}
    raw: Dict[int, List[Tuple[int, int, str]]] = {}
    busy = []
    for d in devs:
        iv = []
        raw[d] = []
        for s, e, name in ops_iv[d]:
            if lo is not None:
                s, e = max(s, lo), min(e, hi)
                if e <= s:
                    continue
            iv.append((s, e))
            raw[d].append((s, e, op_family(name)))
            ops[op_family(name)] += (e - s) * 1e-9
        u = _union(iv)
        busy_iv[d] = u
        busy.append(sum(e - s for s, e in u) * 1e-9)
    if lo is None:
        all_iv = [x for d in devs for x in busy_iv[d]]
        lo = min((s for s, _ in all_iv), default=0)
        hi = max((e for _, e in all_iv), default=0)
    modules: Dict[str, List[float]] = defaultdict(list)
    timeline: List[Tuple[int, int, str]] = []
    for d in devs:
        for s, e, name in mods[d]:
            if e <= lo or s >= hi:
                continue
            key = op_family(name)
            if d == devs[0]:
                modules[key].append((e - s) * 1e-9)
                timeline.append((s, e, key))
    gaps: Dict[str, float] = defaultdict(float)
    if devs:
        u = busy_iv[devs[0]]
        edges = [lo] + [x for iv in u for x in iv] + [hi]
        host = [(s, e, n) for n, ivs in spans.items() if n != WINDOW_SPAN
                for s, e in ivs]
        for a, b in zip(edges[0::2], edges[1::2]):
            if b <= a:
                continue
            mid = (a + b) // 2
            open_ = [(e - s, n) for s, e, n in host if s <= mid < e]
            gaps[min(open_)[1] if open_ else "no host span"] += (b - a) * 1e-9
    return Reduced(window_s=(hi - lo) * 1e-9,
                   busy_s=sum(busy) / max(len(busy), 1), n_devices=len(devs),
                   ops=dict(ops), modules=dict(modules),
                   spans=dict(spans), gaps=dict(gaps),
                   timeline=sorted(timeline), raw_ops=raw)
