"""The trace reduction, on a small trace recorded on one v5e chip: three
dense solves of a 1024 x 512 system, each in a ``chipbench.solve`` span
inside one ``chipbench.window`` span."""
import pathlib

import numpy as np
import pytest

from chipbench import trace

FIXTURE = pathlib.Path(__file__).with_name("data") / "small.xplane.pb"


@pytest.fixture(scope="module")
def recorded():
    return trace.load(str(FIXTURE))


def test_planes_and_spans(recorded):
    assert recorded.chips == [0]
    names, s, e = recorded.ops[0]
    assert len(names) == 987 and (e >= s).all()
    assert all(n.startswith("%") and " = " not in n for n in names)
    assert len(recorded.span("solve")) == 3
    lo, hi = recorded.window()
    assert all(lo <= a < b <= hi for a, b in recorded.span("solve"))


def test_busy_is_the_union_of_op_intervals(recorded):
    names, s, e = recorded.ops[0]
    lo, hi = recorded.window()
    # brute force on a 1 us grid
    grid = np.arange(lo, hi, 1e-6)
    covered = np.zeros(grid.size, bool)
    for a, b in zip(s, e):
        covered[(grid >= a) & (grid < b)] = True
    busy = trace.busy(recorded, 0)
    assert busy == pytest.approx(covered.sum() * 1e-6, abs=2e-5)
    assert 0 < busy < hi - lo
    inside = trace.busy(recorded, 0, within=recorded.span("solve"))
    assert inside <= busy + 1e-12
    assert trace.busy_mean(recorded) == busy


def test_breakdown(recorded):
    lo, hi = recorded.window()
    ops = trace.top_ops(recorded)
    assert len(ops) == 10
    secs = [v for _, v in ops]
    assert secs == sorted(secs, reverse=True)
    gaps = trace.idle_gaps(recorded)
    idle = sum(v for _, v in gaps)
    assert idle + trace.busy(recorded, 0) == pytest.approx(hi - lo, rel=1e-9)
    assert {g[0].split(" (")[0] for g in gaps} <= {"solve", "outside spans"}


def test_no_collectives_on_one_chip(recorded):
    assert trace.busy(recorded, 0, only=trace.COLLECTIVE) == 0.0
    assert trace.COLLECTIVE.match("%all-reduce-start.1")
    assert not trace.COLLECTIVE.match("%fusion.3")


def test_union_and_overlap():
    iv = trace.union([0.0, 1.0, 1.5, 5.0], [2.0, 1.2, 3.0, 6.0])
    np.testing.assert_allclose(iv, [[0.0, 3.0], [5.0, 6.0]])
    assert trace.overlap(iv, np.array([[2.5, 5.5]])) == pytest.approx(1.0)
