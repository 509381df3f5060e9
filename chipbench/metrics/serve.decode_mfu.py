"""FLOPs the decode segments of the traced window require (every emitted
token: probes through every block, the commit, the readout, at its own
context; ``harness.flops.decode_token_flops``), over the device time of
those ``serve_scan`` dispatches times the chip's bf16 peak."""
from harness import flops


def read(run):
    tr = run.trace
    if tr is None:
        return None
    t = tr.program(r"serve_scan")
    if not t:
        return None
    end = run.data["trace_seconds"]
    f = sum(flops.decode_token_flops(run.cell.config, c)
            for s in run.data["segments"] if s.get("t1", end + 1) <= end
            for c in s["ctx"])
    if f <= 0:
        return None
    return 100.0 * f / (sum(t) * run.peaks["bf16_flops_per_s"])
