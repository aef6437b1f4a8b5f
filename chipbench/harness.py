"""One run of one cell: set-up, the measured window, the check, the line.

``run.py`` parses the arguments, refuses anything but a TPU with the chips
the cell asks for, and calls ``run_cell``. Everything here is driven by
data: the cell names its configuration (``configs/<name>.json``) and its
traffic mix (``traffic/<name>.json``), and every metric is a reader of its
own (``metrics/<name>.py``) found by its name in ``BENCHMARK.json``.
"""
from __future__ import annotations

import asyncio
import dataclasses
import gc
import importlib.util
import json
import math
import pathlib
import shutil
import sys
import tempfile
import time

import numpy as np

from chipbench import system as sysmod
from chipbench import trace as tracemod
from chipbench import traffic as trafficmod
from chipbench import work

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parent
SRC = ROOT / "src"


# ---------------------------------------------------------------------------
# lookup by name
# ---------------------------------------------------------------------------


def load_spec(root: pathlib.Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def find(entries: list, name: str, what: str) -> dict:
    for entry in entries:
        if entry["name"] == name:
            return entry
    raise KeyError(f"no {what} named {name!r}")


def load_config(spec: dict, name: str, root: pathlib.Path = ROOT) -> dict:
    entry = find(spec["configs"], name, "configuration")
    return json.loads((root / entry["file"]).read_text())


def load_traffic(name: str, here: pathlib.Path = HERE) -> dict:
    return json.loads((here / "traffic" / f"{name}.json").read_text())


def load_metric(name: str, here: pathlib.Path = HERE):
    """The ``read(run)`` function of ``metrics/<name>.py``."""
    path = here / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        "chipbench_metric_" + name.replace(".", "_"), path
    )
    if spec is None:
        raise KeyError(f"no metric reader {path}")
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


def cell_metrics(spec: dict, cell: str, traced: bool) -> list:
    """The metric entries this cell reports: its end-to-end metrics in an
    untraced run, its per-layer metrics in a traced one."""
    group = spec["per_layer"] if traced else spec["end_to_end"]
    return [m for m in group if cell in m.get("workloads", [cell])]


# ---------------------------------------------------------------------------
# what a run records
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class Batch:
    """One closed-loop solve call: host-clock bounds, per-column epochs to
    tolerance and convergence, the solution, and which pool columns it
    solved."""

    t0: float
    t1: float
    iters: np.ndarray
    converged: np.ndarray
    x: np.ndarray
    cols: np.ndarray


@dataclasses.dataclass
class Request:
    """One open-loop request: due, submitted and resolved times (host
    clock), and what the server returned (None when it raised)."""

    due: float
    submitted: float
    done: float
    result: object
    error: str | None = None

    @property
    def latency_s(self) -> float:
        return math.inf if self.failed else self.done - self.due

    @property
    def failed(self) -> bool:
        return self.result is None or not self.result.converged


@dataclasses.dataclass
class Run:
    """Everything the metric readers read."""

    cell: dict
    config: dict
    traffic: dict
    device: dict
    peak: dict
    sizes: dict
    setup_s: float = 0.0
    prepare_s: float = 0.0
    batches: list = dataclasses.field(default_factory=list)
    requests: list = dataclasses.field(default_factory=list)
    server_stats: dict = dataclasses.field(default_factory=dict)
    trace: tracemod.Trace | None = None
    chips: list = dataclasses.field(default_factory=list)

    @property
    def kind(self) -> str:
        return self.traffic["kind"]

    def live_epochs(self) -> int:
        """Epochs the window needed: each batch's slowest column's epochs
        to tolerance, summed over batches."""
        return int(sum(int(b.iters.max()) for b in self.batches))

    def solve_spans(self) -> list:
        return self.trace.span("solve") if self.trace else []

    def cell_trace(self) -> tracemod.Trace | None:
        """The trace restricted to this cell's chips (None when untraced)."""
        if self.trace is None:
            return None
        chips = [c for c in self.chips if c in self.trace.ops]
        chips = chips or self.trace.chips[: len(self.chips)]
        if not chips:
            return None
        return tracemod.Trace(
            {c: self.trace.ops[c] for c in chips}, self.trace.spans
        )


# ---------------------------------------------------------------------------
# the compile count inside the window
# ---------------------------------------------------------------------------


class CompileClock:
    """Backend compiles and persistent-cache lookups, from jax's monitoring
    events (process-wide; read as a difference around the window)."""

    def __init__(self, jax):
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.count += 1

    def _event(self, event, **_):
        if event in ("/jax/compilation_cache/cache_hits",
                     "/jax/compilation_cache/cache_misses"):
            self.count += 1


# ---------------------------------------------------------------------------
# set-up
# ---------------------------------------------------------------------------


def _ready(prep):
    import jax

    jax.block_until_ready(jax.tree.leaves(vars(prep)))
    return prep


def _coo(system: sysmod.System):
    from repro.sparse import COOMatrix

    rows, cols, vals = system.coo()
    return COOMatrix(rows, cols, vals, system.core.shape)


def _matrix(config: dict, system: sysmod.System):
    """The matrix in the form the configuration's path takes it."""
    return system.dense() if config["path"] == "dense" else _coo(system)


def _prepare_kwargs(config: dict) -> dict:
    kw = dict(config["prepare"])
    if config["path"] == "matfree_sharded":
        from repro.launch.mesh import make_block_mesh

        kw["mesh"] = make_block_mesh(config["chips"])
    return kw


def _check_path(config: dict, prep) -> None:
    """A run whose program departs from what the configuration states is no
    run: the resolved path and each ``expect`` attribute must match."""
    want = {"path": config["path"], **config.get("expect", {})}
    for key, value in want.items():
        got = getattr(prep, key, None)
        if got != value:
            raise RuntimeError(
                f"configuration states {key}={value!r}; the program ran {got!r}"
            )


# ---------------------------------------------------------------------------
# the two window drivers
# ---------------------------------------------------------------------------


def closed_loop(prep, B32, plan, epochs: int, tol: float, seconds: float):
    """Back-to-back batch solves until ``seconds`` have passed; the batch
    that crosses the end is finished and counted (whole batches)."""
    from jax.profiler import TraceAnnotation

    batches = []
    t_start = time.perf_counter()
    for cols in plan:
        with TraceAnnotation("chipbench.solve"):
            t0 = time.perf_counter()
            res = prep.solve(B32[:, cols], num_epochs=epochs, tol=tol)
            t1 = time.perf_counter()
        per = res.per_column(tol)
        batches.append(Batch(
            t0, t1, np.array([c.iterations for c in per]),
            np.array([c.converged for c in per]), res.x, cols,
        ))
        if t1 - t_start >= seconds:
            break
    return batches


async def open_loop(server, fp, B32, arrivals, t_start: float):
    """Submit request i at ``t_start + arrivals[i]`` whatever the server is
    doing, and wait for every one, also past the window's end."""
    from jax.profiler import TraceAnnotation

    async def client(i: int) -> Request:
        due = t_start + float(arrivals[i])
        await asyncio.sleep(max(0.0, due - time.perf_counter()))
        submitted = time.perf_counter()
        with TraceAnnotation("chipbench.request"):
            try:
                res = await server.submit(fp, B32[:, i])
                err = None
            except Exception as exc:  # a failed request is counted, not raised
                res, err = None, repr(exc)
        return Request(due, submitted, time.perf_counter(), res, err)

    tasks = [asyncio.create_task(client(i)) for i in range(len(arrivals))]
    return list(await asyncio.gather(*tasks))


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------


def _peak_bytes(devices) -> int | None:
    peaks = []
    for d in devices:
        stats = d.memory_stats()
        if stats and "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


def run_cell(
    cell: dict, config: dict, traffic: dict, seed: int, seconds: float,
    traced: bool, t_process: float, compile_clock=None,
):
    """Set up, measure, check. Returns ``(run, answers, info)``: the record
    the metric readers read, the answers with their references, and the
    lines printed before the result."""
    import jax
    from jax.profiler import TraceAnnotation

    from repro.core import prepare
    from repro.serving.queue import PreparedPool, SolveServer

    devices = jax.devices()[: config["chips"]]
    dev0 = devices[0]
    t_begin = time.perf_counter()
    mix = trafficmod.make(traffic, seed, seconds)
    system, X, B, tol = sysmod.inputs(config, seed, mix.columns)
    B32 = B.astype(np.float32)
    epochs = int(config["solve"]["num_epochs"])
    A = _matrix(config, system)
    kw = _prepare_kwargs(config)
    nnz = int(system.core.nnz)
    run = Run(
        cell=cell, config=config, traffic=traffic,
        device={"platform": dev0.platform, "kind": dev0.device_kind,
                "count": len(jax.devices())},
        peak=work.peaks(dev0.device_kind) if dev0.platform == "tpu" else {},
        sizes={"n": system.n, "m": system.m, "nnz": nnz,
               "k": mix.k, "path": config["path"], "chips": config["chips"],
               "tol": tol, "num_epochs": epochs},
        chips=[d.id for d in devices],
    )
    info: dict = {"cell": cell["name"], "seed": seed, "sizes": run.sizes}
    phases = info["setup_phases_s"] = {
        "start_to_system": t_begin - t_process,
        "system_and_rhs": time.perf_counter() - t_begin,
    }

    trace_dir = tempfile.mkdtemp(prefix="chipbench_trace_") if traced else None
    try:
        if mix.kind == "closed_batch":
            with TraceAnnotation("chipbench.prepare"):
                t0 = time.perf_counter()
                prep = _ready(prepare(A, **kw))
                run.prepare_s = time.perf_counter() - t0
            _check_path(config, prep)
            phases["prepare"] = run.prepare_s
            t0 = time.perf_counter()
            with TraceAnnotation("chipbench.warmup"):
                prep.solve(B32[:, mix.warmup], num_epochs=epochs, tol=tol)
            phases["warmup"] = time.perf_counter() - t0
            if traced:
                jax.profiler.start_trace(trace_dir)
            c0 = compile_clock.count if compile_clock else 0
            run.setup_s = time.perf_counter() - t_process
            with TraceAnnotation("chipbench.window"):
                run.batches = closed_loop(
                    prep, B32, mix.plan(), epochs, tol, seconds
                )
            info["compiles_in_window"] = (
                compile_clock.count - c0 if compile_clock else None
            )
            info["resident_bytes"] = prep.memory_bytes
            del prep
        else:
            pool = PreparedPool(**kw)
            fp = pool.register(A)
            with TraceAnnotation("chipbench.prepare"):
                t0 = time.perf_counter()
                prep = _ready(pool.get(fp))
                run.prepare_s = time.perf_counter() - t0
            _check_path(config, prep)
            phases["prepare"] = run.prepare_s
            info["resident_bytes"] = prep.memory_bytes
            del prep

            async def serve():
                async with SolveServer(
                    pool=pool, num_epochs=epochs, tol=tol, **mix.server
                ) as server:
                    t0 = time.perf_counter()
                    with TraceAnnotation("chipbench.warmup"):
                        await server.submit(fp, B32[:, mix.warmup[0]])
                    phases["warmup"] = time.perf_counter() - t0
                    server.reset_stats()
                    if traced:
                        jax.profiler.start_trace(trace_dir)
                    c0 = compile_clock.count if compile_clock else 0
                    t_start = time.perf_counter()
                    run.setup_s = t_start - t_process
                    with TraceAnnotation("chipbench.window"):
                        reqs = await open_loop(
                            server, fp, B32[:, mix.request_cols],
                            mix.arrivals, t_start,
                        )
                    info["compiles_in_window"] = (
                        compile_clock.count - c0 if compile_clock else None
                    )
                    return reqs, server.stats()

            run.requests, run.server_stats = asyncio.run(serve())
            late = [r.submitted - r.due for r in run.requests]
            info["generator_late_ms"] = {
                "p50": 1e3 * float(np.median(late)),
                "max": 1e3 * float(np.max(late)),
            }
            info["server"] = {
                k: run.server_stats[k]
                for k in ("requests", "batches", "mean_batch_size",
                          "failures", "retries", "failed_requests")
            }
            errors = [r.error for r in run.requests if r.error]
            if errors:
                info["request_errors"] = errors[:3]
            del pool
        if traced:
            jax.profiler.stop_trace()
        info["memory_peak_bytes"] = _peak_bytes(devices)
        gc.collect()

        answers = _answers(run, X, mix)
        if traced:
            run.trace = tracemod.load(tracemod.find_xplane(trace_dir))
    finally:
        if trace_dir is not None:
            shutil.rmtree(trace_dir, ignore_errors=True)
    info["batches"] = len(run.batches)
    info["samples"] = len(run.requests) or sum(
        b.x.shape[1] for b in run.batches
    )
    return run, (system, *answers), info


def _answers(run: Run, X: np.ndarray, mix):
    """(solutions (n, K) with NaN columns for answers that never came,
    their x_true (n, K), converged flags (K,))."""
    if run.batches:
        xs = np.concatenate([b.x for b in run.batches], axis=1)
        refs = np.concatenate([X[:, b.cols] for b in run.batches], axis=1)
        conv = np.concatenate([b.converged for b in run.batches])
        return xs, refs, conv
    n = X.shape[0]
    xs = np.stack([
        np.full(n, np.nan) if r.result is None else np.asarray(r.result.x)
        for r in run.requests
    ], axis=1)
    conv = np.array([not r.failed for r in run.requests])
    return xs, X[:, mix.request_cols], conv


def check(config: dict, system, xs, refs, conv, tol: float):
    """Every answer of the window against the float64 reference. Compared,
    each with its limit: the worst answer's float64 residual ‖A x − b‖ on
    the system that was solved, in units of the stated tolerance ``tol``;
    the answers that the solver itself reports as not converged within the
    epoch budget; the answers that never came. Also returned, not compared:
    the worst relative error against x_true. Returns (checks, extra)."""
    missing = ~np.isfinite(xs).all(axis=0)
    good = ~missing
    ratio = sysmod.residual(system, xs[:, good], refs[:, good]) / tol
    checks = {
        "residual": {"value": float(ratio.max(initial=0.0)),
                     "limit": config["check"]["residual_limit"]},
        "unconverged": {"value": int((~conv[good]).sum()), "limit": 0},
        "missing": {"value": int(missing.sum()), "limit": 0},
    }
    extra = {
        "relerr_x_true": float(
            sysmod.relerr(xs[:, good], refs[:, good]).max(initial=0.0)
        ),
    }
    over = np.zeros(conv.shape, bool)
    over[good] = ratio > checks["residual"]["limit"]
    extra["failed"] = int((missing | ~conv | over).sum())
    return checks, extra


def read_metrics(spec: dict, run: Run, traced: bool, here=HERE) -> dict:
    out = {}
    for entry in cell_metrics(spec, run.cell["name"], traced):
        value = load_metric(entry["name"], here)(run)
        if value is not None:
            out[entry["name"]] = {"value": float(value), "unit": entry["unit"]}
    return out


def result_line(spec, run, system_answers, info, traced: bool) -> dict:
    system, xs, refs, conv = system_answers
    checks, extra = check(run.config, system, xs, refs, conv,
                          run.sizes["tol"])
    info.update(extra)
    correct = all(c["value"] <= c["limit"] for c in checks.values())
    device = dict(run.device, memory_peak_bytes=info["memory_peak_bytes"])
    line = {
        "correct": bool(correct),
        "attempted": int(conv.size),
        "failed": extra.pop("failed"),
        "metrics": read_metrics(spec, run, traced),
        "device": device,
    }
    sub = run.cell_trace()
    if traced and sub is not None:
        lo, hi = sub.window()
        device["busy_s"] = tracemod.busy_mean(sub)
        device["window_s"] = hi - lo
        line["breakdown"] = {
            "device_ops": tracemod.top_ops(sub),
            "idle_gaps": tracemod.idle_gaps(sub),
        }
    line["check"] = checks
    return line


def main(argv, t_process: float) -> int:
    import argparse

    ap = argparse.ArgumentParser(description="Run one benchmark cell.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    spec = load_spec()
    cell = find(spec["workloads"], args.workload, "workload")
    config = load_config(spec, cell["config"])
    traffic = load_traffic(cell["traffic"])
    if config["chips"] != cell["chips"]:
        raise ValueError(f"{cell['name']}: the cell and its configuration "
                         "ask for different chip counts")

    import jax

    found = jax.devices()
    if found[0].platform != "tpu":
        print(f"chipbench: needs a TPU; jax found {found[0].platform}",
              file=sys.stderr)
        return 2
    if len(found) < cell["chips"]:
        print(f"chipbench: {cell['name']} needs {cell['chips']} chips; "
              f"jax found {len(found)}", file=sys.stderr)
        return 2
    if not (SRC / "repro").is_dir():
        print(f"chipbench: the program is not there ({SRC / 'repro'})",
              file=sys.stderr)
        return 2
    sys.path.insert(0, str(SRC))
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    clock = CompileClock(jax)

    run, answers, info = run_cell(
        cell, config, traffic, args.seed, args.seconds, bool(args.trace),
        t_process, clock,
    )
    line = result_line(spec, run, answers, info, bool(args.trace))
    print(json.dumps(info), flush=True)
    print(json.dumps(line), flush=True)
    for name, c in line["check"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr, flush=True)
    return 0

