"""Observability layer: metrics registry, request tracing, convergence
diagnostics, and the one monotonic clock every latency number comes from.

Cheap when disabled by construction: program spans (``trace.span``) are
``jax.profiler`` annotations that record only while the profiler runs,
the in-memory request trace is off unless a ``Tracer`` is passed in (the
hot paths test ``tracer is None``), metrics are plain in-process counter
bumps behind one lock, and the per-block convergence history is a
solve-time opt-in that leaves the disabled program untouched. Jitted code
is never instrumented per-epoch — spans are host-side only, and the
per-block diagnostics ride the solvers' existing ``history`` scan outputs.
"""
from repro.obs import clock
from repro.obs.convergence import (
    audit_epoch_collectives,
    block_residual_history,
    collect_reduces,
    convergence_report,
    per_block_rates,
)
from repro.obs.metrics import MetricsRegistry, start_exposition
from repro.obs.trace import Tracer, span

__all__ = [
    "clock",
    "MetricsRegistry",
    "start_exposition",
    "Tracer",
    "span",
    "audit_epoch_collectives",
    "block_residual_history",
    "collect_reduces",
    "convergence_report",
    "per_block_rates",
]
