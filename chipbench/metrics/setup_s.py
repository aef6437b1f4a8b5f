"""Process start to window start: system generation, prepare, compile (or
cache load) and warm-up."""


def read(run):
    return run.setup_s
