"""Epochs each batch needed to bring its slowest column to tolerance
(``SolveResult.iterations_to_tol``), averaged over the window's batches."""


def read(run):
    if not run.batches:
        return None
    return run.live_epochs() / len(run.batches)
