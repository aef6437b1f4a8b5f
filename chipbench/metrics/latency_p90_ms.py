"""90th percentile of the time from a request's due time to its resolved
future, of all requests (a failed request counts as infinitely late)."""
import math

from chipbench.stats import percentile


def read(run):
    if not run.requests:
        return None
    v = percentile([r.latency_s for r in run.requests], 90) * 1e3
    return None if math.isinf(v) else v
