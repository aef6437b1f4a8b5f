"""Deterministic property-test runs.

When hypothesis is installed, load a derandomized profile so every CI run
replays the same examples (no flaky shrink sessions, reproducible failures).
Without hypothesis, repro.testing's fallback runner is seeded per-test and
is deterministic by construction.

Also here: the ``profile`` fixture, which runs a block under
``jax.profiler`` and hands back the program's host spans.
"""
import contextlib
import glob
import os
import warnings

import pytest

try:
    from hypothesis import settings
except ModuleNotFoundError:
    pass
else:
    settings.register_profile("ci", derandomize=True, deadline=None)
    settings.load_profile("ci")


def read_program_events(directory) -> list:
    """The program's host events (``repro.*``) in the one ``.xplane.pb``
    under a ``jax.profiler.start_trace`` directory, in start order:
    ``(name, start ns, end ns, {stat: value}, thread line name)``."""
    from jax.profiler import ProfileData

    (path,) = glob.glob(
        os.path.join(str(directory), "**", "*.xplane.pb"), recursive=True
    )
    out = []
    # jax's stats iterator warns about its own type on every read
    warnings.filterwarnings("ignore", "builtin type event_stats")
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("repro."):
                    out.append((
                        ev.name, ev.start_ns, ev.start_ns + ev.duration_ns,
                        dict(ev.stats), line.name,
                    ))
    return sorted(out, key=lambda e: (e[1], -e[2]))


@pytest.fixture
def profile(tmp_path):
    """``with profile() as events:`` runs the block under
    ``jax.profiler``; after it, ``events`` holds ``read_program_events``
    of the trace."""
    import jax

    @contextlib.contextmanager
    def run():
        events: list = []
        directory = tmp_path / f"profile{len(list(tmp_path.iterdir()))}"
        jax.profiler.start_trace(str(directory))
        try:
            yield events
        finally:
            jax.profiler.stop_trace()
        events.extend(read_program_events(directory))

    return run
