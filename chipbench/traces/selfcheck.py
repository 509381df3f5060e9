#!/usr/bin/env python3
"""Self-check of the trace reducer on the small trace recorded once on a
v5e and committed beside this file (``ar-lm.train.db/``: five block steps
of the training cell, seed 105, ``run.py --trace 1 --keep-trace --set
trace_seconds=0.8``, the ``.xplane.pb`` gzipped).

    JAX_PLATFORMS=cpu python3 chipbench/traces/selfcheck.py

Prints busy, idle and per-kernel device time from the trace and checks
the reduction's invariants: busy within the window, the union of op
intervals no longer than their sum, every program execution inside the
window, the kernels' time within the busy time."""
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

from harness import trace  # noqa: E402


def check(path: str) -> dict:
    r = trace.reduce(path)
    ops_total = sum(r.ops.values()) / max(r.n_devices, 1)
    flash = r.op_seconds(r"^flash_attention") / max(r.n_devices, 1)
    steps = r.program(r"^jit_step")
    out = {"window_s": r.window_s, "busy_s": r.busy_s,
           "idle_share_%": 100 * (1 - r.busy_s / r.window_s),
           "op_time_sum_s": ops_total, "flash_attention_s": flash,
           "step_programs": len(steps), "step_program_s": sum(steps),
           "top_ops": r.breakdown()["device_ops"][:5],
           "idle_gaps": r.breakdown()["idle_gaps"]}
    assert 0 < r.busy_s <= r.window_s * 1.0001, out
    assert r.busy_s <= ops_total * 1.0001, out
    assert flash <= r.busy_s, out
    assert sum(steps) <= r.window_s * 1.0001, out
    return out


if __name__ == "__main__":
    for name in sorted(os.listdir(HERE)):
        p = os.path.join(HERE, name)
        if os.path.isdir(p):
            print(name, json.dumps(check(p), indent=1))
