"""Median of the server's own enqueue-to-dispatch time per request
(``RequestResult.queue_ms``, on the server's clock)."""
from chipbench.stats import percentile


def read(run):
    waits = [r.result.queue_ms for r in run.requests if r.result is not None]
    return percentile(waits, 50) if waits else None
