"""Median time between the end of one engine program (prefill chunk or
decode segment) and the start of the next on the device: the host's
scheduling, admission and bookkeeping between dispatches, as the device
sees it (device trace)."""
import statistics


def read(run):
    if run.trace is None:
        return None
    g = run.trace.program_gaps(r"serve_scan|chunk_step")
    return 1e3 * statistics.median(g) if g else None
