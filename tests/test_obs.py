"""Observability-layer tests (ISSUE 8 tentpole).

What must hold:
  (a) metrics: registry counters/gauges/histograms with labels, the
      Prometheus text rendering, and the HTTP exposition endpoint;
  (b) tracing: spans round-trip through BOTH export formats, and a
      serving run's trace reconstructs every request's queue → dispatch →
      solve timeline (the end-to-end acceptance criterion);
  (c) convergence diagnostics: per-block residual history round-trips on
      all THREE solver paths (dense, matfree, sharded) and the disabled
      mode is bit-identical to a plain solve;
  (d) serving stats: the merged ``SolveServer.stats()`` schema is stable
      and its counters are consistent under concurrent submits
      (hits + prepares + restores == pool gets);
  (e) the one-clock rule: latency accounting reads the injectable clock
      (a ``ManualClock`` run reports deterministic zero latencies).
"""
import asyncio
import json
import urllib.request

import numpy as np
import pytest

from repro.obs.clock import Clock, ManualClock
from repro.obs.convergence import (
    audit_epoch_collectives,
    block_residual_history,
    convergence_report,
    per_block_rates,
)
from repro.obs.metrics import MetricsRegistry, start_exposition
from repro.obs.trace import SERVER_TRACK, Tracer, load_trace, span
from repro.sparse import make_problem

EPOCHS = 40
PREP_KW = dict(num_blocks=8, materialize_p=False)


@pytest.fixture(scope="module")
def problem():
    return make_problem(n=96, m=384, seed=3, dtype=np.float32)


@pytest.fixture(scope="module")
def rhs_batch(problem):
    rng = np.random.default_rng(17)
    xs = rng.standard_normal((96, 4)).astype(np.float32)
    return problem.A @ xs, xs


def _run(coro):
    return asyncio.run(asyncio.wait_for(coro, timeout=120))


# ---------------------------------------------------------------------------
# clock
# ---------------------------------------------------------------------------


def test_manual_clock_advances_deterministically():
    clk = ManualClock()
    assert clk.now() == 0.0
    clk.advance(1.5)
    assert clk.now() == 1.5
    with pytest.raises(ValueError):
        clk.advance(-1.0)


def test_real_clock_is_monotonic():
    clk = Clock()
    a, b = clk.now(), clk.now()
    assert b >= a


# ---------------------------------------------------------------------------
# metrics
# ---------------------------------------------------------------------------


def test_counter_labels_and_values():
    reg = MetricsRegistry()
    c = reg.counter("reqs_total", "requests")
    c.labels(kind="a").inc()
    c.labels(kind="a").inc(2)
    c.labels(kind="b").inc()
    assert reg.value("reqs_total", kind="a") == 3.0
    assert reg.value("reqs_total", kind="b") == 1.0
    assert reg.value("reqs_total", kind="missing") == 0.0
    assert reg.value("never_registered") == 0.0
    with pytest.raises(ValueError):
        c.inc(-1)


def test_kind_mismatch_rejected():
    reg = MetricsRegistry()
    reg.counter("x_total")
    with pytest.raises(ValueError):
        reg.gauge("x_total")


def test_histogram_buckets_and_render():
    reg = MetricsRegistry()
    h = reg.histogram("lat_ms", "latency", buckets=(1.0, 10.0))
    for v in (0.5, 5.0, 50.0):
        h.observe(v)
    text = reg.render()
    assert 'lat_ms_bucket{le="1"} 1' in text
    assert 'lat_ms_bucket{le="10"} 2' in text
    assert 'lat_ms_bucket{le="+Inf"} 3' in text
    assert "lat_ms_count 3" in text
    assert "lat_ms_sum 55.5" in text
    assert "# TYPE lat_ms histogram" in text


def test_gauge_set_and_reset():
    reg = MetricsRegistry()
    g = reg.gauge("ewma_s")
    g.set(0.25)
    assert reg.value("ewma_s") == 0.25
    reg.get("ewma_s").reset()
    assert reg.value("ewma_s") == 0.0


def test_exposition_endpoint_serves_render():
    reg = MetricsRegistry()
    reg.counter("up_total", "liveness").inc()
    server = start_exposition(reg, port=0)
    try:
        host, port = server.server_address[:2]
        body = urllib.request.urlopen(
            f"http://{host}:{port}/metrics", timeout=10
        ).read().decode()
        assert "up_total 1" in body
        assert "# TYPE up_total counter" in body
    finally:
        server.shutdown()
        server.server_close()


# ---------------------------------------------------------------------------
# tracer
# ---------------------------------------------------------------------------


def test_spans_round_trip_both_formats(tmp_path):
    clk = ManualClock()
    tracer = Tracer(clock=clk)
    tid = tracer.new_trace_id()
    span = tracer.begin("queue", trace_id=tid, cat="request", priority="bulk")
    clk.advance(0.010)
    span.end(batch=3)
    tracer.span_at("batch", 0.0, 0.010, cat="server", size=3)
    assert span.duration_ms == pytest.approx(10.0)

    chrome, jsonl = tmp_path / "t.json", tmp_path / "t.jsonl"
    assert tracer.export_chrome(chrome) == 2
    assert tracer.export_jsonl(jsonl) == 2
    for path in (chrome, jsonl):
        recs = load_trace(path)
        assert len(recs) == 2
        by_name = {r["name"]: r for r in recs}
        assert by_name["queue"]["trace_id"] == tid
        assert by_name["queue"]["dur_us"] == pytest.approx(10_000.0)
        assert by_name["queue"]["args"]["priority"] == "bulk"
        assert by_name["batch"]["trace_id"] == SERVER_TRACK

    # the chrome export names its tracks for Perfetto
    events = json.loads(chrome.read_text())["traceEvents"]
    names = {
        e["args"]["name"] for e in events if e.get("ph") == "M"
    }
    assert "server" in names and f"request {tid}" in names


def test_tracer_clear_and_context_manager():
    tracer = Tracer(clock=ManualClock())
    with tracer.span("work", cat="test"):
        pass
    assert len(tracer.spans()) == 1
    tracer.clear()
    assert tracer.spans() == []


# ---------------------------------------------------------------------------
# per-block convergence history — dense / matfree / sharded
# ---------------------------------------------------------------------------


def test_dense_block_history_round_trip(problem, rhs_batch):
    from repro.core import prepare

    B, _ = rhs_batch
    prep = prepare(problem.A, **PREP_KW)
    plain = prep.solve(B, num_epochs=EPOCHS)
    diag = prep.solve(B, num_epochs=EPOCHS, block_history=True)
    # disabled mode is the default — bit-identical solutions and history
    assert np.array_equal(np.asarray(plain.x), np.asarray(diag.x))
    trace = block_residual_history(diag)
    assert trace.shape == (EPOCHS, PREP_KW["num_blocks"], B.shape[1])
    # per-block rows sum to the aggregate residual the history always had
    np.testing.assert_allclose(
        trace.sum(axis=1), np.asarray(diag.history["residual_sq"]),
        rtol=1e-5,
    )


def _matfree_pair(num_blocks=8):
    from repro.core import prepare
    from repro.sparse import generate_schenk_like

    coo = generate_schenk_like(256, sparsity=0.99, seed=5)
    rng = np.random.default_rng(11)
    B = coo.to_dense().astype(np.float32) @ rng.standard_normal(
        (256, 3)
    ).astype(np.float32)
    prep = prepare(coo, mode="matfree", num_blocks=num_blocks)
    return coo, prep, B


def test_matfree_block_history_round_trip():
    coo, prep, B = _matfree_pair()
    plain = prep.solve(B, num_epochs=EPOCHS, gamma=2.0, eta=1.9)
    diag = prep.solve(
        B, num_epochs=EPOCHS, gamma=2.0, eta=1.9, block_history=True
    )
    assert np.array_equal(np.asarray(plain.x), np.asarray(diag.x))
    trace = block_residual_history(diag)
    assert trace.shape == (EPOCHS, 8, 3)
    np.testing.assert_allclose(
        trace.sum(axis=1), np.asarray(diag.history["residual_sq"]),
        rtol=1e-4,
    )
    # single-RHS histories collapse the trailing axis like the rest
    one = prep.solve(
        B[:, 0], num_epochs=EPOCHS, gamma=2.0, eta=1.9, block_history=True
    )
    assert np.asarray(one.history["block_residual_sq"]).shape == (EPOCHS, 8)


def test_sharded_block_history_matches_single_host():
    import jax

    from repro.core import prepare

    coo, single, B = _matfree_pair()
    mesh = jax.make_mesh((1,), ("data",))
    sharded = prepare(coo, mode="matfree", num_blocks=8, mesh=mesh)
    ref = single.solve(
        B, num_epochs=EPOCHS, gamma=2.0, eta=1.9, block_history=True
    )
    got = sharded.solve(
        B, num_epochs=EPOCHS, gamma=2.0, eta=1.9, block_history=True
    )
    np.testing.assert_allclose(
        block_residual_history(got), block_residual_history(ref),
        rtol=1e-4, atol=1e-7,
    )


def test_block_history_requires_enablement(problem, rhs_batch):
    from repro.core import prepare

    B, _ = rhs_batch
    plain = prepare(problem.A, **PREP_KW).solve(B, num_epochs=5)
    with pytest.raises(ValueError, match="block_history=True"):
        block_residual_history(plain)


def test_convergence_report_shapes(problem, rhs_batch):
    from repro.core import prepare

    B, _ = rhs_batch
    diag = prepare(problem.A, **PREP_KW).solve(
        B, num_epochs=EPOCHS, block_history=True
    )
    J, k = PREP_KW["num_blocks"], B.shape[1]
    rates = per_block_rates(diag)
    assert rates.shape == (J, k)
    assert (rates > 0).all() and (rates < 1.0).all()  # contracting blocks
    rep = convergence_report(diag, tol=1e-3)
    assert rep["num_epochs"] == EPOCHS and rep["num_blocks"] == J
    assert rep["slowest_block"].shape == (k,)
    assert (rep["imbalance"] >= 1.0).all()
    assert rep["block_epochs_to_tol"].shape == (J, k)
    assert (rep["block_epochs_to_tol"] <= EPOCHS).all()


def test_collective_audit_block_history_adds_nothing_in_scan():
    import jax

    from repro.core import prepare

    coo, _, B = _matfree_pair()
    mesh = jax.make_mesh((1,), ("data",))
    sharded = prepare(coo, mode="matfree", num_blocks=8, mesh=mesh)
    base = audit_epoch_collectives(sharded, B[:, 0], num_epochs=6)
    with_hist = audit_epoch_collectives(
        sharded, B[:, 0], num_epochs=6, block_history=True
    )
    # per-block rows ride the out_specs: SAME in-scan comms budget
    assert with_hist["ops"] == base["ops"]
    assert with_hist["payload_elems"] == base["payload_elems"]
    # the budget-assertion form is what deployments call
    audit_epoch_collectives(
        sharded, B[:, 0], num_epochs=6, block_history=True,
        max_ops=base["ops"], max_payload_elems=base["payload_elems"],
    )
    with pytest.raises(AssertionError):
        audit_epoch_collectives(
            sharded, B[:, 0], num_epochs=6, tol=1e-3,
            max_ops=base["ops"],  # tol arms the in-scan residual psum
        )


# ---------------------------------------------------------------------------
# serving stats: schema stability + counter consistency
# ---------------------------------------------------------------------------

STATS_SCHEMA = {
    "requests", "batches", "full_batches", "timeout_flushes",
    "deadline_flushes", "drain_flushes", "interactive_batches",
    "bulk_batches", "admission_rejects", "mean_batch_size",
    "prepares", "hits", "evictions", "restores", "restore_ms",
    "gets", "misses",
    # fault-containment counters (ISSUE 9)
    "failures", "retries", "recovered_requests", "failed_requests",
    "cancelled",
    # heterogeneity gauge (ISSUE 10)
    "block_imbalance",
}


def test_stats_schema_and_concurrent_counter_consistency(problem, rhs_batch):
    """Concurrent submits across two systems through a size-1 pool (forced
    evictions + re-prepares): the merged stats keys must be exactly the
    documented schema and hits + prepares + restores must equal gets."""
    B, _ = rhs_batch
    A2 = problem.A + np.float32(1e-3)  # second registered system

    async def main():
        from repro.serving.queue import SolveServer

        async with SolveServer(
            max_batch=4, max_wait_ms=2.0, num_epochs=10, pool_size=1,
            prepare_kwargs=PREP_KW,
        ) as server:
            fa, fb = server.register(problem.A), server.register(A2)
            await asyncio.gather(*(
                server.submit(fa if i % 2 == 0 else fb, B[:, i % B.shape[1]])
                for i in range(12)
            ))
            return server.stats()

    stats = _run(main())
    assert set(stats) == STATS_SCHEMA
    assert stats["requests"] == 12
    assert stats["gets"] == stats["hits"] + stats["prepares"] + stats["restores"]
    assert stats["gets"] == stats["batches"]  # one pool.get per dispatch
    assert stats["misses"] == stats["prepares"] + stats["restores"]
    assert stats["evictions"] > 0  # the alternating systems thrashed size-1


def test_reset_stats_is_registry_backed(problem, rhs_batch):
    B, _ = rhs_batch

    async def main():
        from repro.serving.queue import SolveServer

        async with SolveServer(
            max_batch=2, max_wait_ms=2.0, num_epochs=10,
            prepare_kwargs=PREP_KW,
        ) as server:
            fp = server.register(problem.A)
            await server.submit(fp, B[:, 0])
            before = server.stats()
            server.reset_stats()
            after = server.stats()
            text = server.render_metrics()
            return before, after, text

    before, after, text = _run(main())
    assert before["requests"] == 1 and after["requests"] == 0
    assert after["gets"] == before["gets"]  # pool counters are cumulative
    assert "server_requests_total 0" in text
    assert "# TYPE pool_gets_total counter" in text


def test_manual_clock_latencies_are_deterministic(problem, rhs_batch):
    """With the injectable ManualClock never advanced, every latency the
    server reports must be exactly zero — proof that no wall clock leaks
    into the accounting."""
    B, _ = rhs_batch

    async def main():
        from repro.serving.queue import SolveServer

        async with SolveServer(
            max_batch=1, num_epochs=10, prepare_kwargs=PREP_KW,
            clock=ManualClock(),
        ) as server:
            fp = server.register(problem.A)
            return await asyncio.gather(
                *(server.submit(fp, B[:, i]) for i in range(3))
            )

    for res in _run(main()):
        assert res.queue_ms == 0.0
        assert res.solve_ms == 0.0


# ---------------------------------------------------------------------------
# serving traces: spans reconstruct the request timelines
# ---------------------------------------------------------------------------


def test_server_trace_reconstructs_request_timelines(
    problem, rhs_batch, tmp_path
):
    """The acceptance criterion: a traced serving run exports a Chrome
    trace whose spans rebuild every request's queue → dispatch → solve
    timeline, sessions included."""
    B, _ = rhs_batch
    tracer = Tracer()

    async def main():
        from repro.serving.queue import SolveServer, replay_trace

        async with SolveServer(
            max_batch=2, max_wait_ms=2.0, num_epochs=10,
            prepare_kwargs=PREP_KW, tracer=tracer,
        ) as server:
            fp = server.register(problem.A)
            results = await replay_trace(
                server, fp, B, [0.0] * B.shape[1]
            )
            session = server.open_session(fp)
            await session.update(B[:, 0])
            await session.update(B[:, 1])
            return results

    results = _run(main())
    path = tmp_path / "trace.json"
    tracer.export_chrome(path)
    recs = load_trace(path)

    request_ids = {
        r["trace_id"] for r in recs if r["cat"] == "request"
    }
    # every submitted request (4 replay + 2 session updates) has a track
    assert len(request_ids) == B.shape[1] + 2
    batches = [
        r for r in recs if r["name"] == "batch"
    ]
    assert batches and all(b["trace_id"] == SERVER_TRACK for b in batches)
    assert any(r["name"] == "pool.prepare" for r in recs)
    session_spans = [r for r in recs if r["name"] == "session.update"]
    assert len(session_spans) == 2

    for tid in request_ids:
        spans = {r["name"]: r for r in recs if r["trace_id"] == tid}
        assert {"queue", "solve"} <= set(spans)
        queue, solve = spans["queue"], spans["solve"]
        # contiguous timeline: the queue span ends where the solve starts
        # (both endpoints are the batch's dispatch timestamp)
        assert queue["ts_us"] + queue["dur_us"] == pytest.approx(
            solve["ts_us"], abs=1.0
        )
        # the solve span sits inside its dispatching batch span
        assert any(
            b["ts_us"] - 1.0 <= solve["ts_us"]
            and solve["ts_us"] + solve["dur_us"] <= b["ts_us"] + b["dur_us"] + 1.0
            and b["args"]["batch_size"] == solve["args"]["batch_size"]
            for b in batches
        )
    # scattered results and spans agree on the batch accounting
    sizes = sorted(r.batch_size for r in results)
    span_sizes = sorted(
        s["args"]["batch_size"]
        for s in recs
        if s["name"] == "solve" and s["trace_id"] in request_ids
    )[: len(sizes)]
    assert sum(b["args"]["batch_size"] for b in batches) == len(request_ids)
    del sizes, span_sizes


def test_serve_solver_cli_trace_replay(tmp_path):
    """End-to-end through the CLI: serve_solver.main with tracing enabled
    writes a Chrome trace that covers every replayed request."""
    from repro.launch.serve_solver import main

    out = tmp_path / "serve_trace.json"
    main([
        "--requests", "8", "--rate", "500", "--n", "48", "--m", "96",
        "--num-blocks", "4", "--epochs", "15",
        "--trace-out", str(out),
    ])
    recs = load_trace(out)
    request_ids = {r["trace_id"] for r in recs if r["cat"] == "request"}
    assert len(request_ids) == 8
    for tid in request_ids:
        names = {r["name"] for r in recs if r["trace_id"] == tid}
        assert {"queue", "solve"} <= names


# ---------------------------------------------------------------------------
# tooling: trace report + bench-record comparison
# ---------------------------------------------------------------------------


def test_trace_report_summarizes(tmp_path):
    import sys

    sys.path.insert(0, "tools")
    from trace_report import summarize

    clk = ManualClock()
    tracer = Tracer(clock=clk)
    tracer.span_at("queue", 0.0, 0.002, trace_id=1, cat="request")
    tracer.span_at("solve", 0.002, 0.010, trace_id=1, cat="request")
    tracer.span_at("batch", 0.002, 0.010, cat="server", batch_size=2)
    path = tmp_path / "t.jsonl"
    tracer.export_jsonl(path)
    report = summarize(load_trace(path), top=2)
    assert "3 spans, 3 kinds" in report
    assert "solve" in report and "queue" in report
    assert "batch sizes:" in report
    assert "slowest 2 spans:" in report


def test_compare_records_fails_on_missing_gated_row(capsys):
    import sys

    sys.path.insert(0, "benchmarks")
    from record import compare_records

    baseline = {
        "rows": [
            {"name": "kernels/fused", "us_per_call": 100.0, "gated": True},
            {"name": "kernels/demo", "us_per_call": 50.0},
        ]
    }
    fresh = {"rows": [{"name": "kernels/other", "us_per_call": 10.0}]}
    failures = compare_records(fresh, baseline)
    assert len(failures) == 1
    assert "kernels/fused" in failures[0]
    assert "missing" in failures[0]
    out = capsys.readouterr().out
    assert "kernels/demo" in out  # ungated missing row is noted, not failed
    assert "kernels/other" in out  # fresh-only row noted as ungated


# ---------------------------------------------------------------------------
# program spans on the profiler's clock
# ---------------------------------------------------------------------------


def test_program_span_lands_in_profiler_trace(profile):
    """``span`` is a profiler event named ``repro.<name>`` whose keyword
    arguments and mid-span ``set`` stats reach the ``.xplane.pb``; a child
    opened inside it nests inside it on the same thread."""
    with profile() as events:
        with span("probe", path="dense", k=4) as outer:
            with span("probe.child", batch=7):
                pass
            outer.set(epochs_run=600)
    assert [e[0] for e in events] == ["repro.probe", "repro.probe.child"]
    (_, t0, t1, stats, line), (_, c0, c1, child_stats, child_line) = events
    assert stats == {"path": "dense", "k": 4, "epochs_run": 600}
    assert child_stats == {"batch": 7}
    assert line == child_line and t0 <= c0 <= c1 <= t1
    assert outer.args == stats and outer.seconds > 0


def test_program_span_without_profiler_keeps_its_record():
    """With the profiler off a span still times itself and keeps its args;
    nothing else is recorded anywhere."""
    with span("probe", k=1) as s:
        s.set(epochs_run=3)
    assert s.args == {"k": 1, "epochs_run": 3}
    assert s.t1 is not None and s.seconds >= 0 and s.tracer is None


def test_tracer_spans_share_the_profiler_path(profile):
    """``Tracer.span``/``Tracer.begin`` open the same profiler event as
    ``span`` besides the tracer's record; ``span_at`` (back-filled) reaches
    the tracer only."""
    tracer = Tracer(clock=ManualClock())
    with profile() as events:
        with tracer.span("work", cat="test", batch=1):
            pass
        tracer.begin("queue", trace_id=3, priority="bulk").end(batch=2)
        tracer.span_at("batch", 0.0, 1.0, size=3)
    assert [(e[0], e[3]) for e in events] == [
        ("repro.work", {"batch": 1}),
        ("repro.queue", {"priority": "bulk", "batch": 2}),
    ]
    assert [s.name for s in tracer.spans()] == ["work", "queue", "batch"]


def test_server_phase_spans_share_batch_numbers(problem, rhs_batch, profile):
    """Every coalesced solve leaves ``repro.server.assemble``, ``.solve``
    and ``.deliver`` under one ``batch`` number, in that order, with the
    solver's own ``repro.solve`` (and its run phase) inside ``.solve``."""
    B, _ = rhs_batch

    async def main():
        from repro.serving.queue import SolveServer, replay_trace

        async with SolveServer(
            max_batch=2, max_wait_ms=2.0, num_epochs=10,
            prepare_kwargs=PREP_KW,
        ) as server:
            fp = server.register(problem.A)
            await server.submit(fp, B[:, 0])  # prepare and compile first
            with profile() as events:
                await replay_trace(server, fp, B, [0.0] * B.shape[1])
            return events

    events = _run(main())
    phases = {}
    for name, s, e, stats, line in events:
        if name.startswith("repro.server."):
            phase = name[len("repro.server."):]
            phases.setdefault(stats["batch"], {})[phase] = (s, e, stats, line)
    assert phases
    sizes = 0
    for batch, ph in phases.items():
        assert set(ph) == {"assemble", "solve", "deliver"}
        assert len({p[2]["batch_size"] for p in ph.values()}) == 1
        sizes += ph["solve"][2]["batch_size"]
        assert ph["assemble"][1] <= ph["solve"][0]
        assert ph["solve"][1] <= ph["deliver"][0]
        s0, s1, _, solve_line = ph["solve"]
        inner = [
            e for e in events
            if e[0] in ("repro.solve", "repro.solve.run")
            and s0 <= e[1] <= e[2] <= s1
        ]
        assert [e[0] for e in inner] == ["repro.solve", "repro.solve.run"]
        assert all(e[4] == solve_line for e in inner)
    assert sizes == B.shape[1]
