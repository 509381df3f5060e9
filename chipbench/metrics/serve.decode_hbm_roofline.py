"""Share of the HBM roofline the decode segments reach: bytes a decode step
must read (the weights for the probes, the commit and the readout, once
per step in which any slot emits; each emitted token's KV twice,
``harness.flops``), over the ``serve_scan`` device time times the HBM
bandwidth."""
from harness import flops


def read(run):
    tr = run.trace
    if tr is None:
        return None
    t = tr.program(r"serve_scan")
    if not t:
        return None
    cfg, end = run.cell.config, run.data["trace_seconds"]
    w = flops.decode_step_weight_bytes(cfg)
    b = 0
    for s in run.data["segments"]:
        if s.get("t1", end + 1) <= end and s["ctx"]:
            b += s.get("kmax", 0) * w + sum(
                flops.decode_token_kv_bytes(cfg, c) for c in s["ctx"])
    if b <= 0:
        return None
    return 100.0 * b / (sum(t) * run.peaks["hbm_bytes_per_s"])
