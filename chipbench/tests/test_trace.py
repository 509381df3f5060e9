"""The trace reducer on the trace recorded once on a v5e and committed
under ``chipbench/traces/`` (``traces/selfcheck.py`` prints the numbers)."""
import os

import pytest

from harness import trace

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TRACES = os.path.join(HERE, "traces")


def recorded():
    return sorted(os.path.join(TRACES, d) for d in os.listdir(TRACES)
                  if os.path.isdir(os.path.join(TRACES, d)))


@pytest.mark.parametrize("path", recorded(), ids=os.path.basename)
def test_recorded_trace_invariants(path):
    import sys
    sys.path.insert(0, TRACES)
    import selfcheck
    out = selfcheck.check(path)
    assert out["step_programs"] > 0 and out["flash_attention_s"] > 0


def test_union_and_exposure():
    assert trace._union([(0, 2), (1, 3), (5, 6)]) == [(0, 3), (5, 6)]
    r = trace.Reduced(window_s=1, busy_s=1, n_devices=1, ops={}, modules={},
                      spans={}, gaps={}, timeline=[], raw_ops={0: [(0, 10, "fusion"),
                                                (5, 20, "all-reduce"),
                                                (30, 40, "all-reduce")]})
    # 10..20 and 30..40 run with no other op: 20 ns
    assert abs(r.exposed_seconds("all-reduce") - 20e-9) < 1e-15
