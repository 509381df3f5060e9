"""Open-loop serving traffic from a data file (``traffic/<mix>.json``).

Arrivals and lengths are copied from ``benchmarks/loadgen.py``
(``_arrival_times``: Poisson, or geometric bursts arriving as a Poisson
process; clipped lognormal lengths), with two changes for the benchmark:

- the set of sizes and gaps is drawn once from the mix's ``base_seed`` and
  only its order and the token ids come from the run's seed, so every seed
  offers the same work in another order;
- the gaps are scaled so that exactly ``round(rate * seconds)`` requests
  fall due inside the window: the offered rate is the mix's rate.

Each request is timed from when it was due (its offset from the window's
opening), not from when it was handed to the server."""
from __future__ import annotations

from typing import Dict, List

import numpy as np


def _lengths(rs, n: int, d: dict) -> np.ndarray:
    if d["dist"] == "lognormal":
        x = rs.lognormal(np.log(d["median"]), d["sigma"], n)
    elif d["dist"] == "uniform":
        x = rs.uniform(d["min"], d["max"] + 1, n)
    else:
        raise ValueError(f"unknown length distribution {d['dist']!r}")
    return np.clip(np.floor(x), d["min"], d["max"]).astype(np.int64)


def _gaps(rs, n: int, arrival: str, burst_mean: float) -> np.ndarray:
    if arrival == "poisson":
        return rs.exponential(1.0, n)
    if arrival == "bursty":
        g = np.zeros(n)
        i = 0
        while i < n:
            k = int(rs.geometric(1.0 / burst_mean))
            g[i] = rs.exponential(burst_mean)
            i += k
        return g
    raise ValueError(f"arrival must be 'poisson' or 'bursty', got {arrival!r}")


def requests(mix: dict, seed: int, seconds: float, vocab: int) -> List[Dict]:
    """The requests due in a window of ``seconds``: dicts of ``due`` (s
    after the window opens), ``prompt`` (int32 ids), ``max_new``."""
    n = max(1, int(round(mix["rate"] * seconds)))
    base = np.random.default_rng([int(mix.get("base_seed", 0)), 17])
    plen = _lengths(base, n, mix["prompt"])
    nout = _lengths(base, n, mix["output"])
    gaps = _gaps(base, n, mix.get("arrival", "poisson"),
                 mix.get("burst_mean", 1.0))
    rs = np.random.default_rng([int(seed), 19])
    order = rs.permutation(n)
    if mix.get("arrival", "poisson") == "poisson":
        gaps = rs.permutation(gaps)
    due = np.cumsum(gaps)
    due = due / due[-1] * seconds * (n - 0.5) / n     # last due inside
    out = []
    for i in range(n):
        j = order[i]
        out.append({"due": float(due[i]),
                    "prompt": rs.integers(0, vocab, int(plen[j]),
                                          dtype=np.int32),
                    "max_new": int(nout[j])})
    return out
