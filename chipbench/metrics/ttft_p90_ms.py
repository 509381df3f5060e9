"""90th percentile over every request due in the window of the time from
when it was due to its first delivered token (host clock); a request that
failed or never completed counts as a miss (+inf)."""
from harness.stats import percentile


def read(run):
    reqs = run.data.get("requests")
    if not reqs:
        return None
    v = [(r["first"] - r["due"]) * 1e3 if r["done"] is not None
         else float("inf") for r in reqs.values()]
    return percentile(v, 90)
