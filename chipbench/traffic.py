"""The one general traffic generator; a mix is a data file it reads.

``traffic/<name>.json`` holds a ``kind`` and its parameters:

* ``closed_batch`` — back-to-back solves of ``k`` right-hand sides each,
  drawn in turn from a pool of ``pool_batches`` distinct batches (one more
  batch warms up). Parameters: ``k``, ``pool_batches``.
* ``open_poisson`` — single right-hand sides sent to ``SolveServer.submit``
  at Poisson arrivals of ``rate_per_s``, whatever the server is doing.
  Every seed gets the same set of inter-arrival gaps (the exponential
  distribution's quantiles, scaled to fill the window), in an order drawn
  from the seed, so the seed changes the order and never the amount of
  work. Parameters: ``rate_per_s``, ``server`` (``SolveServer`` keywords).

Columns of the run's ``x_true`` are numbered warm-up first.
"""
from __future__ import annotations

import dataclasses

import numpy as np

from chipbench.system import STREAM_ORDER, rng


@dataclasses.dataclass
class Mix:
    kind: str
    k: int  # columns per solve call (1 for single requests)
    columns: int  # x_true columns the run draws
    warmup: np.ndarray  # columns of the warm-up call
    batches: int = 0  # closed loop: distinct batches in the pool
    arrivals: np.ndarray | None = None  # open loop: due times from the start
    request_cols: np.ndarray | None = None  # open loop: column of request i
    server: dict = dataclasses.field(default_factory=dict)

    def plan(self):
        """Closed loop: the columns of each successive batch, cycling
        through the pool."""
        i = 0
        while True:
            j = 1 + i % self.batches
            yield np.arange(j * self.k, (j + 1) * self.k)
            i += 1


def poisson_gaps(rate: float, seconds: float) -> np.ndarray:
    """The fixed set of ``round(rate * seconds)`` inter-arrival gaps: the
    mid-quantiles of the exponential distribution of mean ``1 / rate``,
    scaled so that they add up to ``seconds``."""
    count = max(1, int(round(rate * seconds)))
    gaps = -np.log1p(-(np.arange(count) + 0.5) / count) / rate
    return gaps * (seconds / gaps.sum())


def make(traffic: dict, seed: int, seconds: float) -> Mix:
    kind = traffic["kind"]
    if kind == "closed_batch":
        k, pool = int(traffic["k"]), int(traffic["pool_batches"])
        return Mix(kind, k, k * (pool + 1), np.arange(k), batches=pool)
    if kind == "open_poisson":
        gaps = rng(seed, STREAM_ORDER).permutation(
            poisson_gaps(float(traffic["rate_per_s"]), seconds)
        )
        arrivals = np.cumsum(gaps)  # the last request is due at the end
        return Mix(
            kind, 1, arrivals.size + 1, np.arange(1), arrivals=arrivals,
            request_cols=np.arange(1, arrivals.size + 1),
            server=dict(traffic["server"]),
        )
    raise ValueError(f"unknown traffic kind {kind!r}")
