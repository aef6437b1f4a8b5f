"""Share of the traced window in which no operation ran on the cell's
chips (1 − union of device-operation intervals / window), closed-loop
cells, in percent."""
from chipbench import trace


def read(run):
    sub = run.cell_trace()
    if sub is None or run.kind != "closed_batch":
        return None
    lo, hi = sub.window()
    return 100.0 * (1.0 - trace.busy_mean(sub) / (hi - lo))
