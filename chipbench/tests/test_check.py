"""The correctness check catches what it must, at a size the CPU holds.

Each test skips the harness's look for a chip and drives the rest of a
run (``tiny.run_tiny``) with the cell's own limits. With the timed path
broken underneath, ``correct`` comes out false for each fault the cell can
have: a step that returns its state unchanged, half of the batch left out
(the mean over the rest), a served token altered where it is produced.
The fp8 control put in the program's place reads above the sound program
on every compared number that separates them on the chip (the limits
themselves are set from chip readings at the cells' own sizes, where the
tiny model's rounding does not apply; ``PERF.md`` gives them). (The
exchange between chips left out belongs to a four-chip cell; none is in
the benchmark yet.)

    JAX_PLATFORMS=cpu python -m pytest chipbench/tests -q"""
import pytest

import tiny

TRAIN = dict(batch=4, seq_len=32, ref_rows=2)
SERVE = dict(rate=4.0, slots=4, max_prompt=96, max_len=160,
             prompt={"dist": "lognormal", "median": 40, "sigma": 0.5,
                     "min": 8, "max": 96},
             output={"dist": "lognormal", "median": 20, "sigma": 0.5,
                     "min": 4, "max": 64},
             check_tokens=120, drain_cap_s=30)


def readings(run):
    return {c.name: c.value for c in run.checks}


@pytest.fixture(scope="module")
def train_cell():
    return tiny.tiny_cell("ar-lm.train.db", **TRAIN)


@pytest.fixture(scope="module")
def serve_cell():
    return tiny.tiny_cell("olmo-1b.serve.chat", **SERVE)


@pytest.mark.parametrize("fault", ["stale_state", "half_batch"])
def test_training_fault_is_caught(train_cell, fault):
    r = tiny.run_tiny(train_cell, seconds=1.0, fault=fault)
    assert not r.correct, r.checks


def test_training_control_reads_above_the_program(train_cell):
    sound = readings(tiny.run_tiny(train_cell, seconds=1.0))
    ctrl = readings(tiny.run_tiny(train_cell, seconds=1.0, control="fp8"))
    assert sound["untouched_leaf_change"] == 0.0
    for k in ("loss_rel_gap", "first_grad_leaf_gap", "change_leaf_gap"):
        assert ctrl[k] > sound[k], (k, sound, ctrl)


def test_serving_fault_is_caught(serve_cell):
    r = tiny.run_tiny(serve_cell, seconds=3.0, fault="altered_token")
    assert not r.correct, r.checks


def test_serving_control_reads_above_the_program(serve_cell):
    sound = readings(tiny.run_tiny(serve_cell, seconds=3.0))
    ctrl = readings(tiny.run_tiny(serve_cell, seconds=3.0, control="fp8"))
    assert ctrl["control_logit_gap"] > sound["served_logit_gap"], (sound,
                                                                   ctrl)
