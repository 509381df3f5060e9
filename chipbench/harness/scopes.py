"""Split a device trace by the program's own names: the ``db.*`` scopes its
jitted steps carry in each HLO instruction's ``op_name``, and the ``db.*``
host spans of its loops (``repro.tracing``).

A trace event names an HLO instruction (``%fusion.688 = ...``), not its
scope. The scope comes from the compiled program's HLO text
(``compiled.as_text()``), read after the window: ``op_names(text)`` maps
each instruction of a program to its ``op_name``. A fusion takes the
``op_name`` of the first matrix product (``dot``, ``convolution``) or
kernel call it fuses, where it fuses one, else of its fused computation's
root (or, where the root has none, its own, else the last one set inside
it). A fusion that fuses ops of two scopes counts whole under one: the
product's, since it costs the most (a weight gradient written straight
into the layer scan's stacked buffer counts under the layer that needs
it, not under the scan whose ``dynamic-update-slice`` is the root), an
approximation that moves the fused elementwise work between neighbouring
scopes. ``save(dir, maps)`` writes the maps of a
run beside its trace (``opnames.json.gz``), keyed by ``program_key``.

``reduce(dir)`` then attributes each ``XLA Ops`` event to the innermost
``db.*`` scope of its instruction in the program execution (``XLA
Modules`` event) that contains it. A program is found by the fingerprint
its execution event carries (``jit_step(4281...)``) where a map was saved
under that key; otherwise by its name, in which case an instruction the
programs of that name disagree on counts as unscoped. Loops (``while``,
``conditional``, ``call``) are left out: their events span their bodies'
ops. Time under no ``db.*`` scope is ``UNSCOPED``.

Idle gaps of the first device go to the innermost host span open at the
gap's midpoint, the harness's ``bench.*`` or the program's ``db.*``, where
the host and device clocks agree (the ``bench.window`` span covers most of
the device's op time, as in ``harness.trace.reduce``); otherwise to
``no host span``.
"""
from __future__ import annotations

import bisect
import dataclasses
import gzip
import json
import os
import re
from collections import defaultdict
from typing import Dict, List, Optional, Tuple

from harness.trace import (CONTAINERS, WINDOW_SPAN, _device_index, _load,
                           _union, op_family)

UNSCOPED = "unscoped"
NO_SPAN = "no host span"
MAP_FILE = "opnames.json.gz"
SPAN_PREFIXES = ("bench.", "db.")

_INSTR = re.compile(r"^\s*(ROOT\s+)?%?([\w.\-]+) = (.*)$")
_COMP = re.compile(r"^\s*(?:ENTRY\s+)?%?([\w.\-]+) .*\{\s*$")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(r"calls=%?([\w.\-]+)")
_HEAVY = re.compile(r"\s(dot|convolution|custom-call)\(")
_SCOPE = re.compile(r"(?<![\w.])db\.[a-z_]+")
# program keys: "<name>(<fingerprint>)", or "<name>" and "<name>[<i>]" for
# the programs of one name whose fingerprint the runtime does not give
_KEY = re.compile(r"^(.*?)(?:\((\d+)\)|\[\d+\])?$")


def scope_path(op_name: Optional[str]) -> Optional[str]:
    """The ``db.*`` scopes named in an ``op_name`` path, outermost first:
    ``jit(step)/transpose(jvp(db.layers))/while/body/db.mlp/dot_general``
    -> ``db.layers/db.mlp``."""
    found = _SCOPE.findall(op_name or "")
    return "/".join(found) if found else None


def scope_of(op_name: Optional[str]) -> Optional[str]:
    """The innermost ``db.*`` scope named in an ``op_name`` path (or in a
    ``scope_path``)."""
    found = _SCOPE.findall(op_name or "")
    return found[-1] if found else None


def outer_scope_of(op_name: Optional[str]) -> Optional[str]:
    """The outermost ``db.*`` scope named in an ``op_name`` path."""
    found = _SCOPE.findall(op_name or "")
    return found[0] if found else None


def op_names(hlo_text: str) -> Dict[str, str]:
    """Instruction name -> ``op_name`` for every instruction of a compiled
    module's text that has one (a fusion: see above), those inside fused
    computations left out (a trace names only the fusion)."""
    own: Dict[str, Optional[str]] = {}
    comp_of: Dict[str, Optional[str]] = {}
    calls: Dict[str, str] = {}
    root: Dict[str, str] = {}
    last: Dict[str, str] = {}
    heavy: Dict[str, str] = {}          # computation -> its first product
    comp = None
    for line in hlo_text.splitlines():
        m = _INSTR.match(line)
        if m is None:
            c = _COMP.match(line)
            if c is not None:
                comp = c.group(1)
            continue
        is_root, name, rest = m.groups()
        op = _OP_NAME.search(rest)
        own[name] = op.group(1) if op else None
        comp_of[name] = comp
        if op and comp is not None:
            last[comp] = op.group(1)
            if _HEAVY.search(rest) and comp not in heavy:
                heavy[comp] = op.group(1)
        if is_root and comp is not None:
            root[comp] = name
        if " fusion(" in rest:
            k = _CALLS.search(rest)
            if k:
                calls[name] = k.group(1)

    def resolve(name: str, depth: int = 0) -> Optional[str]:
        comp = calls.get(name)
        if comp is None or depth > 8:
            return own.get(name)
        r = root.get(comp)
        return (heavy.get(comp) or (resolve(r, depth + 1) if r else None)
                or own.get(name) or last.get(comp))

    fused = set(calls.values())
    out = {}
    for name in own:
        if comp_of[name] in fused:
            continue
        v = resolve(name)
        if v:
            out[name] = v
    return out


def program_key(compiled) -> str:
    """``<module name>(<fingerprint>)``, as a trace's ``XLA Modules`` event
    names an execution, where the runtime gives a numeric fingerprint;
    else the module name alone."""
    exe = compiled.runtime_executable()
    name = exe.hlo_modules()[0].name
    fp = getattr(exe, "fingerprint", None)
    if isinstance(fp, bytes):
        fp = fp.decode(errors="ignore")
    return f"{name}({fp})" if fp and str(fp).isdigit() else name


def maps_of(compiled_list, into=None) -> Dict[str, Dict[str, str]]:
    """``program_key`` -> ``op_names`` for each compiled program, added to
    ``into``; a key already there gets a ``[<i>]`` suffix."""
    out = {} if into is None else into
    for c in compiled_list:
        key, i = program_key(c), 1
        while key in out:
            key, i = f"{program_key(c)}[{i}]", i + 1
        out[key] = op_names(c.as_text())
    return out


def save(trace_dir: str, maps: Dict[str, Dict[str, str]]) -> str:
    """Write the maps beside a trace; only the ``scope_path`` of each
    instruction is kept (what ``reduce`` reads), instructions under no
    scope left out."""
    slim = {k: {i: p for i, p in ((i, scope_path(o)) for i, o in m.items())
                if p} for k, m in maps.items()}
    os.makedirs(trace_dir, exist_ok=True)
    path = os.path.join(trace_dir, MAP_FILE)
    with gzip.open(path, "wt") as f:
        json.dump(slim, f, sort_keys=True, separators=(",", ":"))
    return path


def load(trace_dir: str) -> Dict[str, Dict[str, str]]:
    """The maps ``save`` wrote ({} where there are none)."""
    path = os.path.join(trace_dir, MAP_FILE)
    if not os.path.exists(path):
        return {}
    with gzip.open(path, "rt") as f:
        return json.load(f)


def _by_name(maps: Dict[str, Dict[str, str]]) -> Dict[str, Dict[str, str]]:
    """Program name -> scopes of the instructions every saved program of
    that name agrees on."""
    groups = defaultdict(list)
    for key, m in maps.items():
        k = _KEY.match(key)
        groups[k.group(1) if k else key].append(m)
    out = {}
    for name, ms in groups.items():
        names = set().union(*ms)
        out[name] = {i: ms[0].get(i) for i in names
                     if ms[0].get(i) and all(m.get(i) == ms[0].get(i)
                                             for m in ms)}
    return out


@dataclasses.dataclass
class Split:
    """Device time by ``db.*`` scope and idle time by host span."""
    seconds: Dict[str, float]        # scope (or UNSCOPED) -> s, all devices
    by_program: Dict[str, Dict[str, float]]   # program name -> the same
    outer: Dict[str, Dict[str, float]]   # the same by outermost scope
    busy_s: float                    # mean over devices
    window_s: float
    n_devices: int
    gaps: Dict[str, float]           # host span (or NO_SPAN) -> idle s
    programs: Dict[str, int]         # program name -> executions, device 0
    keyed_by: Dict[str, str]         # program name -> "fingerprint" | "name"
    same_clock: bool

    def scope_seconds(self, name: str) -> float:
        """Device seconds under ``name``, summed over the devices."""
        return self.seconds.get(name, 0.0)


def _instr(event_name: str) -> str:
    return event_name.split(" = ", 1)[0].strip().lstrip("%")


def split(ops: Dict[int, List[Tuple[int, int, str]]],
          modules: Dict[int, List[Tuple[int, int, str]]],
          spans: Dict[str, List[Tuple[int, int]]],
          maps: Dict[str, Dict[str, str]],
          devices: Optional[List[int]] = None) -> Split:
    """The join on event lists (times in ns): ``ops`` and ``modules`` per
    device as (start, end, event name), host ``spans`` by name, and the
    saved ``maps`` (instruction -> scope or ``op_name``)."""
    devs = sorted(devices if devices is not None else ops)
    win = spans.get(WINDOW_SPAN)
    lo, hi = (win[0][0], win[0][1]) if win else (None, None)
    if lo is not None:
        tot = sum(e - s for d in devs for s, e, _ in ops.get(d, []))
        inside = sum(max(0, min(e, hi) - max(s, lo))
                     for d in devs for s, e, _ in ops.get(d, []))
        if tot == 0 or inside < 0.5 * tot:
            lo = hi = None
    same_clock = lo is not None
    scopes_of = {k: {i: scope_path(v) for i, v in m.items()}
                 for k, m in maps.items()}
    by_name = _by_name(scopes_of)
    seconds: Dict[str, float] = defaultdict(float)
    by_program: Dict[str, Dict[str, float]] = defaultdict(
        lambda: defaultdict(float))
    outer: Dict[str, Dict[str, float]] = defaultdict(
        lambda: defaultdict(float))
    busy, keyed_by = [], {}
    programs: Dict[str, int] = defaultdict(int)
    busy0: List[Tuple[int, int]] = []
    for d in devs:
        mods = sorted(modules.get(d, []))
        starts = [s for s, _, _ in mods]
        iv = []
        for s, e, name in ops.get(d, []):
            if lo is not None:
                s, e = max(s, lo), min(e, hi)
                if e <= s:
                    continue
            iv.append((s, e))
            if op_family(name) in CONTAINERS:
                continue
            j = bisect.bisect_right(starts, s) - 1
            scope, pname = None, None
            if j >= 0 and s < mods[j][1]:
                key = mods[j][2]
                k = _KEY.match(key)
                pname = k.group(1) if k else key
                if key in scopes_of:
                    scope = scopes_of[key].get(_instr(name))
                    keyed_by[pname] = "fingerprint"
                else:
                    scope = by_name.get(pname, {}).get(_instr(name))
                    keyed_by.setdefault(pname, "name")
            t, pname = (e - s) * 1e-9, pname or "no program"
            seconds[scope_of(scope) or UNSCOPED] += t
            by_program[pname][scope_of(scope) or UNSCOPED] += t
            outer[pname][outer_scope_of(scope) or UNSCOPED] += t
        u = _union(iv)
        busy.append(sum(e - s for s, e in u) * 1e-9)
        if d == devs[0]:
            busy0 = u
            for s, e, key in mods:
                if lo is None or (e > lo and s < hi):
                    k = _KEY.match(key)
                    programs[k.group(1) if k else key] += 1
    if lo is None:
        lo = min((s for s, _ in busy0), default=0)
        hi = max((e for _, e in busy0), default=0)
    gaps: Dict[str, float] = defaultdict(float)
    host = [(s, e, n) for n, ivs in spans.items() if n != WINDOW_SPAN
            for s, e in ivs] if same_clock else []
    edges = [lo] + [x for iv in busy0 for x in iv] + [hi]
    for a, b in zip(edges[0::2], edges[1::2]):
        if b <= a:
            continue
        mid = (a + b) // 2
        open_ = [(e - s, n) for s, e, n in host if s <= mid < e]
        gaps[min(open_)[1] if open_ else NO_SPAN] += (b - a) * 1e-9
    return Split(seconds=dict(seconds),
                 by_program={k: dict(v) for k, v in by_program.items()},
                 outer={k: dict(v) for k, v in outer.items()},
                 busy_s=sum(busy) / max(len(busy), 1),
                 window_s=(hi - lo) * 1e-9, n_devices=len(devs),
                 gaps=dict(gaps), programs=dict(programs),
                 keyed_by=keyed_by, same_clock=same_clock)


def events(trace_dir: str):
    """(ops, modules, spans) of the trace under ``trace_dir``, as
    ``split`` takes them."""
    pd = _load(trace_dir)
    ops: Dict[int, list] = defaultdict(list)
    mods: Dict[int, list] = defaultdict(list)
    spans: Dict[str, list] = defaultdict(list)
    for plane in pd.planes:
        dev = _device_index(plane.name)
        for line in plane.lines:
            if dev is not None and line.name in ("XLA Ops", "XLA Modules"):
                out = ops if line.name == "XLA Ops" else mods
                out[dev].extend((int(e.start_ns),
                                 int(e.start_ns + e.duration_ns), e.name)
                                for e in line.events)
            elif dev is None:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIXES):
                        spans[e.name].append(
                            (int(e.start_ns),
                             int(e.start_ns + e.duration_ns)))
    return dict(ops), dict(mods), dict(spans)


def reduce(trace_dir: str, devices: Optional[List[int]] = None,
           maps: Optional[Dict[str, Dict[str, str]]] = None) -> Split:
    """The split of the trace under ``trace_dir``, with ``maps`` or those
    saved beside it."""
    ops, mods, spans = events(trace_dir)
    return split(ops, mods, spans, load(trace_dir) if maps is None else maps,
                 devices)
