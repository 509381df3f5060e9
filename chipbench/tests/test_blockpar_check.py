"""The four-chip cell's check (``ar-lm.train.blockpar4``, staged) catches
what it must, on four CPU devices at a size the CPU holds: a step that
returns its state unchanged, half of the batch left out, and the
periphery's exchange between chips left out
(``harness.faults.psum_left_out``) each push the first-gradient or the
change gap over the cell's limit, where the sound program stays under
both; the fp8 control reads above the sound program
on every compared number. (The loss gap's limit is set from chip readings
at the cell's own size; the tiny model's rounding does not meet it.)

The runs share one process started with four host devices, since the
device count is fixed when JAX starts.

    JAX_PLATFORMS=cpu python -m pytest chipbench/tests -q"""
import json
import os
import subprocess
import sys

import pytest

TESTS = os.path.dirname(os.path.abspath(__file__))
SCRIPT = r"""
import json
import os
import tiny
from harness import bench, faults

# the cell is staged: its files exist, BENCHMARK.json does not list it yet
cell = bench.load_cell(bench.load_spec(tiny.ROOT), "ar-lm.train.blockpar4",
                       os.path.join(tiny.BENCH, "configs", "ar-lm.json"),
                       "train.blockpar4", 4)
cell.config["model"].update(tiny.TINY_MODEL)
cell.traffic.update(batch=4, seq_len=32, ref_rows=2)

def readings(run):
    return {"correct": run.correct, "error": run.error,
            **{c.name: c.value for c in run.checks}}

out = {"limits": cell.limits,
       "sound": readings(tiny.run_tiny(cell, seconds=1.0)),
       "fp8": readings(tiny.run_tiny(cell, seconds=1.0, control="fp8"))}
for f in ("stale_state", "half_batch"):
    out[f] = readings(tiny.run_tiny(cell, seconds=1.0, fault=f))
with faults.psum_left_out():
    out["psum_left_out"] = readings(tiny.run_tiny(cell, seconds=1.0))
print("RESULT " + json.dumps(out))
"""
LEAVES = ("first_grad_leaf_gap", "change_leaf_gap")


@pytest.fixture(scope="module")
def runs():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable, "-c", SCRIPT], cwd=TESTS, env=env,
                       capture_output=True, text=True, timeout=1800)
    lines = [x for x in p.stdout.splitlines() if x.startswith("RESULT ")]
    assert p.returncode == 0 and lines, p.stderr[-4000:]
    return json.loads(lines[-1][len("RESULT "):])


def test_sound_program_stays_under_the_leaf_limits(runs):
    s, lim = runs["sound"], runs["limits"]
    assert s["error"] is None and s["untouched_leaf_change"] == 0.0
    for k in LEAVES:
        assert s[k] <= lim[k], (k, s)


@pytest.mark.parametrize("fault", ["stale_state", "half_batch",
                                   "psum_left_out"])
def test_blockpar_fault_is_caught(runs, fault):
    r, lim = runs[fault], runs["limits"]
    assert not r["correct"], r
    assert any(r[k] > lim[k] for k in LEAVES), r


def test_blockpar_control_reads_above_the_program(runs):
    sound, ctrl = runs["sound"], runs["fp8"]
    for k in ("loss_rel_gap",) + LEAVES:
        assert ctrl[k] > sound[k], (k, sound, ctrl)
