#!/usr/bin/env python3
"""The scope split of each trace committed beside this file that carries
its op-name map (``opnames.json.gz``, written by ``chipbench/record.py
--out``): device milliseconds per step program under each ``db.*`` scope,
and the check that under 5% of busy time is unscoped.

    JAX_PLATFORMS=cpu python3 chipbench/traces/scopecheck.py"""
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

from harness import scopes  # noqa: E402


def check(path: str) -> dict:
    s = scopes.reduce(path)
    n = max(sum(s.programs.values()), 1) * max(s.n_devices, 1)
    unscoped = s.scope_seconds(scopes.UNSCOPED) / (s.busy_s * s.n_devices)
    out = {"programs": s.programs, "keyed_by": s.keyed_by,
           "ms_per_program": {k: 1e3 * v / n for k, v in sorted(
               s.seconds.items(), key=lambda kv: -kv[1])},
           "unscoped_share_of_busy": unscoped}
    assert unscoped < 0.05, out
    return out


if __name__ == "__main__":
    for name in sorted(os.listdir(HERE)):
        p = os.path.join(HERE, name)
        if os.path.isdir(p) and scopes.load(p):
            print(name, json.dumps(check(p), indent=1))
