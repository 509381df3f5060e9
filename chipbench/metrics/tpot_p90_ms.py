"""90th percentile over the requests due in the window of (last token time
- first token time) / (tokens - tokens of the first segment) (host clock);
requests whose every token came in the first segment have no inter-token
time; a failed or unfinished request counts as a miss (+inf)."""
from harness.stats import percentile


def read(run):
    reqs = run.data.get("requests")
    if not reqs:
        return None
    v = []
    for r in reqs.values():
        if r["done"] is None:
            v.append(float("inf"))
            continue
        n = sum(k for _, _, k in r["meta"]) - r["first_seg"]
        if n > 0:
            v.append((r["last"] - r["first"]) * 1e3 / n)
    return percentile(v, 90) if v else None
