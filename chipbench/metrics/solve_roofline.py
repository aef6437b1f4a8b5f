"""Share of its roofline that the solve reaches: the least time the
epochs the batches needed could take on one chip (``chipbench.work``:
every stored entry of A read once per epoch, each chip its share), over
the device time inside the solve spans, in percent."""
from chipbench import trace, work


def read(run):
    sub = run.cell_trace()
    if sub is None or not run.batches:
        return None
    device_s = trace.busy_mean(sub, within=run.solve_spans())
    if device_s <= 0:
        return None
    s = run.sizes
    flops, nbytes = work.epoch_work(s["path"], s["m"], s["n"], s["nnz"], s["k"])
    epochs = run.live_epochs() / len(sub.chips)
    least, _ = work.least_seconds(flops * epochs, nbytes * epochs, run.peak)
    return 100.0 * least / device_s
