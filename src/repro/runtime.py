"""Process-wide runtime knobs: the persistent compile cache every entry point
sets up before its first compile, and the env-driven dry-run knobs below.

XLA's HLO cost analysis counts a while-loop body ONCE regardless of trip
count, so the roofline dry-run sets REPRO_SCAN_UNROLL=1 to unroll layer /
attention-tile / CE-chunk scans — the compiled module then carries the true
FLOP/byte counts. Normal execution keeps scans rolled (small HLO, fast
compile). REPRO_ATTN_CHUNK enlarges flash tiles in the dry-run to bound the
unrolled tile count.
"""
from __future__ import annotations

import os
from pathlib import Path

# <checkout>/.jax_cache: a fixed path (git-ignored) — the cache key includes
# the directory, so a path derived from a temp name, pid or time never hits
DEFAULT_COMPILE_CACHE = Path(__file__).resolve().parents[2] / ".jax_cache"


def init_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; call before the first
    compile. Where ``JAX_COMPILATION_CACHE_DIR`` is set JAX already reads it
    and nothing else is set; otherwise the cache lives at the fixed
    ``DEFAULT_COMPILE_CACHE`` inside the checkout. Returns the directory."""
    import jax
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_COMPILE_CACHE))
    return str(DEFAULT_COMPILE_CACHE)


def scan_unroll() -> bool:
    return os.environ.get("REPRO_SCAN_UNROLL", "0") == "1"


def attn_chunk() -> int:
    return int(os.environ.get("REPRO_ATTN_CHUNK", "1024"))
