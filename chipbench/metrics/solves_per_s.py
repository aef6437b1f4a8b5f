"""Systems solved to their tolerance per second, over the whole window."""
from chipbench.stats import closed_loop_rate


def read(run):
    if not run.batches:
        return None
    return closed_loop_rate(
        [(b.t0, b.t1, int(b.converged.sum())) for b in run.batches]
    )
