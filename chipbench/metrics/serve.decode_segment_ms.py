"""Median device time of one decode-segment dispatch (the ``serve_scan``
program, ``seg_len`` denoise+commit steps over every slot)."""
import statistics


def read(run):
    if run.trace is None:
        return None
    t = run.trace.program(r"serve_scan")
    return 1e3 * statistics.median(t) if t else None
