"""Host clock around ``prepare`` (or the pool's first ``get``), ending in
``block_until_ready`` on everything it placed."""


def read(run):
    return run.prepare_s
