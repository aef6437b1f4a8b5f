"""Device time inside the benchmark's solve spans per epoch the batches
needed (each batch's epochs to tolerance), averaged over the cell's chips.
Epochs a program runs past its batch's tolerance count as its cost."""
from chipbench import trace


def read(run):
    sub = run.cell_trace()
    if sub is None or not run.batches:
        return None
    device_s = trace.busy_mean(sub, within=run.solve_spans())
    return 1e3 * device_s / run.live_epochs()
