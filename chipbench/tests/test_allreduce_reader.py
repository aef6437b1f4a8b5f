"""The all-reduce reader on a made-up two-chip trace: it counts the
all-reduces under the names XLA gives them, inside the solve spans, per
live epoch, on the slowest chip."""
import types

import numpy as np
import pytest

from chipbench import harness, trace


def _ops(events):
    names, s, e = zip(*events)
    return np.asarray(names, object), np.asarray(s, float), np.asarray(e, float)


def _run(ops, epochs=(4, 6)):
    tr = trace.Trace(ops, {"chipbench.solve": [(0.0, 1.0)],
                           "chipbench.window": [(0.0, 2.0)]})
    batches = [types.SimpleNamespace(iters=np.array([1, e])) for e in epochs]
    return types.SimpleNamespace(
        cell_trace=lambda: tr, batches=batches,
        solve_spans=lambda: tr.span("solve"),
        live_epochs=lambda: harness.Run.live_epochs(
            types.SimpleNamespace(batches=batches)),
    )


def test_reads_psum_named_all_reduces():
    read = harness.load_metric("allreduce_ms_per_epoch")
    run = _run({
        0: _ops([("%fusion.107", 0.0, 0.5), ("%psum.25", 0.5, 0.51),
                 ("%psum.26", 0.6, 0.602), ("%psum.30", 1.5, 1.6)]),
        1: _ops([("%fusion.107", 0.0, 0.4), ("%psum.25", 0.4, 0.43),
                 ("%all-reduce-start.1", 0.7, 0.71)]),
    })
    # chip 1: 30 ms + 10 ms inside the span, over 10 live epochs; the
    # psum after the span does not count
    assert read(run) == pytest.approx(4.0)


def test_silent_on_one_chip_and_without_all_reduces():
    read = harness.load_metric("allreduce_ms_per_epoch")
    assert read(_run({0: _ops([("%psum.1", 0.0, 0.1)])})) is None
    run = _run({c: _ops([("%fusion.3", 0.0, 0.1)]) for c in (0, 1)})
    assert read(run) == 0.0
    assert not read.__globals__["ALL_REDUCE"].match("%psum_fusion.2")
