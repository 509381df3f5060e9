"""Median device time of one prefill-chunk dispatch (the ``chunk_step``
program: up to ``chunk_size`` prompt tokens for every prefilling slot)."""
import statistics


def read(run):
    if run.trace is None:
        return None
    t = run.trace.program(r"chunk_step")
    return 1e3 * statistics.median(t) if t else None
