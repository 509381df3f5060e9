"""Tiny cells for the benchmark's own CPU tests: the same jobs, configs
cut to a size the CPU runs in seconds."""
import copy
import os
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
for p in (BENCH, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from harness import bench  # noqa: E402

TINY_MODEL = {"n_layers": 4, "d_model": 64, "n_heads": 2, "n_kv_heads": 2,
              "head_dim": 32, "d_ff": 128, "vocab_size": 256}


# cells whose jobs are ready but which BENCHMARK.json does not run yet
UNLISTED = {"olmo-1b.serve.chat": ("olmo-1b", "serve.chat", 1)}


def tiny_cell(name: str, **traffic_over) -> bench.Cell:
    spec = bench.load_spec(ROOT)
    if name in UNLISTED:
        config, traffic, chips = UNLISTED[name]
        cell = bench.load_cell(spec, name, os.path.join(
            BENCH, "configs", config + ".json"), traffic, chips)
    else:
        cell = bench.find_cell(spec, name)
    cell = copy.deepcopy(cell)
    cell.config["model"].update(TINY_MODEL)
    cell.traffic.update(traffic_over)
    return cell


class Args:
    def __init__(self, seed=5, seconds=1.0, trace=0, control=None,
                 fault=None):
        self.seed, self.seconds, self.trace = seed, seconds, trace
        self.control, self.fault, self.keep_trace = control, fault, False


def run_tiny(cell, **kw):
    """Drive a run on the CPU: the harness's look for a chip is skipped."""
    import time
    import jax
    import run as entry
    return entry.run_cell(cell, Args(**kw), jax.devices()[:cell.chips],
                          time.perf_counter())
