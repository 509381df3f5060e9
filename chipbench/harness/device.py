"""Which device a run is on, its published peaks, and its peak memory.

The peak table (``peaks.json``) is keyed by ``device_kind`` as JAX reports
it; a kind that is not in the table is an error, never a default."""
from __future__ import annotations

import json
import os

_PEAKS = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                      "peaks.json")


class NoChip(RuntimeError):
    """The run found no TPU, or fewer chips than the cell asks for."""


def peaks(kind: str) -> dict:
    with open(_PEAKS) as f:
        table = json.load(f)
    if kind not in table or kind.startswith("_"):
        raise KeyError(f"no published peaks for device kind {kind!r}; "
                       f"known: {sorted(k for k in table if k[0] != '_')}")
    return table[kind]


def require_chips(n: int):
    """The first ``n`` TPU devices; raises ``NoChip`` otherwise."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"jax.devices()[0] is {devs[0].platform}, not a TPU")
    if len(devs) < n:
        raise NoChip(f"the cell asks for {n} chips, JAX sees {len(devs)}")
    return devs[:n]


def describe(devs) -> dict:
    d = devs[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devs)}


def memory_stats(devs) -> list:
    """``memory_stats()`` of each of ``devs`` ({} where the backend keeps
    none, as the CPU does)."""
    return [d.memory_stats() or {} for d in devs]


def footprint(stats: dict) -> int:
    """Peak bytes a chip held: buffers in use plus the region the runtime
    reserves for the programs' temporaries, which ``peak_bytes_in_use``
    does not count."""
    return (int(stats.get("peak_bytes_in_use", 0))
            + int(stats.get("peak_bytes_reserved", 0)))

