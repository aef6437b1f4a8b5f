"""Reduction of a profiler trace to the per-layer device numbers.

The trace is the ``.xplane.pb`` that ``jax.profiler.start_trace`` writes,
read with ``jax.profiler.ProfileData`` alone. Device planes are those named
``/device:<accelerator>:<i>``; their ``XLA Ops`` line holds one event per
operation run on that chip. Host spans are the benchmark's own
``jax.profiler.TraceAnnotation`` events, named ``chipbench.*``; all events
share one clock, in nanoseconds from the start of the trace.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re

import numpy as np

SPAN_PREFIX = "chipbench."
DEVICE_PLANE = re.compile(r"^/device:(?!CPU)[A-Z]+:(\d+)$")
OPS_LINE = "XLA Ops"
# collective operations by the instruction names XLA gives them (all-reduce,
# all-gather, reduce-scatter, collective-permute, all-to-all; async halves)
COLLECTIVE = re.compile(
    r"^%?(all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all)"
)


def op_name(text: str) -> str:
    """The instruction name of an ``XLA Ops`` event (``%fusion.3``); the
    event's name is the whole HLO instruction."""
    return text.split(" = ", 1)[0]


@dataclasses.dataclass
class Trace:
    """Device operations per chip and the benchmark's host spans."""

    ops: dict  # chip -> (names (E,), start s (E,), end s (E,))
    spans: dict  # span name -> list of (start s, end s)

    @property
    def chips(self) -> list:
        return sorted(self.ops)

    def span(self, name: str) -> list:
        return self.spans.get(SPAN_PREFIX + name, [])

    def window(self) -> tuple[float, float]:
        """The traced window: the ``chipbench.window`` span."""
        (w,) = self.span("window")
        return w


def find_xplane(directory: str) -> str:
    """The one ``.xplane.pb`` under a ``start_trace`` directory."""
    found = glob.glob(
        os.path.join(directory, "**", "*.xplane.pb"), recursive=True
    )
    if len(found) != 1:
        raise FileNotFoundError(f"{len(found)} xplane files under {directory}")
    return found[0]


def load(path: str) -> Trace:
    """Read the device operations and the host spans out of one trace."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    ops, spans = {}, {}
    for plane in data.planes:
        dev = DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if dev is not None and line.name == OPS_LINE:
                names, t0, dur = [], [], []
                for ev in line.events:
                    names.append(op_name(ev.name))
                    t0.append(ev.start_ns)
                    dur.append(ev.duration_ns)
                t0 = np.asarray(t0, np.float64) * 1e-9
                ops[int(dev.group(1))] = (
                    np.asarray(names, object), t0,
                    t0 + np.asarray(dur, np.float64) * 1e-9,
                )
            elif dev is None:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        s = ev.start_ns * 1e-9
                        spans.setdefault(ev.name, []).append(
                            (s, s + ev.duration_ns * 1e-9)
                        )
    for v in spans.values():
        v.sort()
    return Trace(ops, spans)


def union(starts, ends) -> np.ndarray:
    """Merge intervals into disjoint sorted ``(K, 2)`` intervals."""
    starts, ends = np.asarray(starts, np.float64), np.asarray(ends, np.float64)
    if starts.size == 0:
        return np.zeros((0, 2))
    order = np.argsort(starts, kind="stable")
    s, e = starts[order], np.maximum.accumulate(ends[order])
    new = np.ones(s.size, bool)
    new[1:] = s[1:] > e[:-1]
    idx = np.flatnonzero(new)
    return np.stack([s[idx], np.append(e[idx[1:] - 1], e[-1])], axis=1)


def overlap(a: np.ndarray, b: np.ndarray) -> float:
    """Total length of the intersection of two disjoint interval sets."""
    total, i, j = 0.0, 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i, 0], b[j, 0]), min(a[i, 1], b[j, 1])
        if hi > lo:
            total += hi - lo
        if a[i, 1] < b[j, 1]:
            i += 1
        else:
            j += 1
    return total


def busy(trace: Trace, chip: int, within=None, only=None) -> float:
    """Seconds in which an operation ran on ``chip``, inside the intervals
    ``within`` (default: the traced window), counting only operations whose
    name matches the regex ``only`` when given."""
    names, s, e = trace.ops.get(chip, (np.zeros(0, object), np.zeros(0),
                                      np.zeros(0)))
    if only is not None:
        keep = np.fromiter(
            (bool(only.search(n)) for n in names), bool, len(names)
        )
        s, e = s[keep], e[keep]
    if within is None:
        within = [trace.window()]
    return overlap(union(s, e), union(*zip(*within)) if within else
                   np.zeros((0, 2)))


def busy_mean(trace: Trace, within=None, only=None) -> float:
    """``busy`` averaged over the traced chips."""
    return float(np.mean([busy(trace, c, within, only) for c in trace.chips]))


def self_times(names, starts, ends) -> np.ndarray:
    """Each operation's own time: its duration less the time of the
    operations nested inside it (a ``while`` or ``cond`` event spans the
    operations of its body on the same line)."""
    order = np.argsort(starts, kind="stable")
    own = (ends - starts).astype(np.float64)
    stack: list = []  # indices of the open enclosing events
    for i in order:
        while stack and ends[stack[-1]] <= starts[i]:
            stack.pop()
        if stack and ends[i] <= ends[stack[-1]]:
            own[stack[-1]] -= ends[i] - starts[i]
        stack.append(i)
    return own


def top_ops(trace: Trace, limit: int = 10) -> list:
    """The operations that took most device time of their own in the
    window, averaged over chips: ``[[name, seconds], ...]``."""
    lo, hi = trace.window()
    total: dict = {}
    for chip in trace.chips:
        names, s, e = trace.ops[chip]
        inside = (s >= lo) & (e <= hi)
        own = self_times(names[inside], s[inside], e[inside])
        for name, sec in zip(names[inside], own):
            total[name] = total.get(name, 0.0) + sec / len(trace.chips)
    return [[k, v] for k, v in sorted(total.items(), key=lambda kv: -kv[1])
            ][:limit]


def idle_gaps(trace: Trace, limit: int = 10) -> list:
    """Idle time of chip 0 in the window, by what the host was doing: each
    gap is named after the innermost benchmark span open at its midpoint
    (``outside spans`` when none is). ``[[name, seconds], ...]``, largest
    first, with the count and longest gap of each name in the name."""
    lo, hi = trace.window()
    _, s, e = trace.ops[trace.chips[0]]
    busy_iv = union(s, e)
    edges = np.concatenate([[lo], busy_iv.ravel(), [hi]]).reshape(-1, 2)
    edges = np.clip(edges, lo, hi)
    spans = [
        (a, b, k[len(SPAN_PREFIX):]) for k, v in trace.spans.items()
        for a, b in v if k != SPAN_PREFIX + "window"
    ]
    edges = edges[edges[:, 1] > edges[:, 0]]
    mid = edges.mean(axis=1)
    label = np.full(mid.size, -1)
    # innermost span wins: assign the longest first, the shortest last
    for i in sorted(range(len(spans)), key=lambda i: spans[i][0] - spans[i][1]):
        label[(mid >= spans[i][0]) & (mid <= spans[i][1])] = i
    by_name: dict = {}
    for idx in np.unique(label):
        name = spans[idx][2] if idx >= 0 else "outside spans"
        d = np.diff(edges[label == idx], axis=1)[:, 0]
        tot, cnt, longest = by_name.get(name, (0.0, 0, 0.0))
        by_name[name] = (tot + d.sum(), cnt + d.size, max(longest, d.max()))
    out = sorted(by_name.items(), key=lambda kv: -kv[1][0])[:limit]
    return [
        [f"{k} ({c} gaps, longest {lg:.6f} s)", t] for k, (t, c, lg) in out
    ]
