"""Device time of the collective operations inside the solve spans per
epoch the batches needed, on the slowest chip (cells on several chips)."""
from chipbench import trace


def read(run):
    sub = run.cell_trace()
    if sub is None or not run.batches or len(sub.chips) < 2:
        return None
    per_chip = [
        trace.busy(sub, c, within=run.solve_spans(), only=trace.COLLECTIVE)
        for c in sub.chips
    ]
    return 1e3 * max(per_chip) / run.live_epochs()
