"""``record.py``'s plumbing at a size the CPU holds: the jitted step
programs a run calls are found again after it, their op-name maps carry
the program's ``db.*`` scopes, and every step dispatch is timed. (The CPU
writes no TPU planes, so the split itself is empty here; the committed
traces and ``test_scopes`` cover it.)

    JAX_PLATFORMS=cpu python -m pytest chipbench/tests -q"""
import jax

import record
import tiny
from harness import scopes


def test_record_finds_the_step_programs_and_their_scopes(tmp_path):
    cell = tiny.tiny_cell("ar-lm.train.db", batch=4, seq_len=32,
                          ref_rows=2)
    cell.traffic["trace_seconds"] = 0.5
    out = record.record_cell(cell, 2147483999, 1.0, jax.devices()[:1],
                             out=str(tmp_path))
    assert out["error"] is None and out["steps"] > 0
    assert out["map_keys"] and all(k.startswith("jit_step")
                                   for k in out["map_keys"])
    found = {scopes.scope_of(p) for m in scopes.load(str(tmp_path)).values()
             for p in m.values()}
    assert {"db.attn", "db.mlp", "db.readout_ce", "db.optimizer",
            "db.block_view"} <= found
    t = out["tracing"]
    assert t["traced_calls"] + t["untraced_calls"] <= out["steps"]
    assert t["traced_calls"] > 0


def test_spy_notes_concrete_calls_only():
    calls = []

    def f(x):
        return x * 2
    with record.spied() as made:
        g = jax.jit(f)
        jax.jit(lambda y: g(y) + 1)(jax.numpy.ones(3))   # g traced inside
        assert made[0].sig is None
        g(jax.numpy.ones(4))
        calls.append(len(made[0].calls))
    assert made[0].sig[0][0].shape == (4,) and calls == [2]
    assert not isinstance(jax.jit(f), record.Spy)      # jax.jit restored
