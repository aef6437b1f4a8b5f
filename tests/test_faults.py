"""Fault-injection + containment-ladder tests (ISSUE 9 tentpole, parts 1+3).

The contract under test:
  (a) a ``FaultPlan`` is deterministic and replayable (JSON round-trip,
      seeded probabilistic rules);
  (b) a poisoned request is ISOLATED — batchmates still get their results
      (bisection), and only the poison future resolves with a structured
      ``SolveFailure``;
  (c) transient faults recover through the ladder (retry → fallback
      re-prepare → checkpoint-bypassing refresh), with watchdog-flagged
      NaN/stall columns entering the same ladder;
  (d) the per-system circuit breaker opens on consecutive dispatch
      failures, fast-fails while open, and closes through a half-open
      trial — all on the injected clock, no real sleeping;
  (e) a cancelled (done-future) request is dropped at dispatch and can
      neither poison nor stall its batch (ISSUE 9 satellite #1);
  (f) the checkpoint store quarantines corrupt/foreign files as
      ``<fp>.npz.bad`` and never re-reads them, while transient IO errors
      and legitimate config mismatches do NOT quarantine (satellite #2).
"""
import asyncio
import threading

import numpy as np
import pytest

from repro.core import prepare
from repro.core.guard import Watchdog
from repro.obs.clock import ManualClock
from repro.serving.checkpoint import CheckpointStore
from repro.serving.faults import (
    FaultInjector,
    FaultPlan,
    FaultRule,
    InjectedFault,
    SolveFailure,
)
from repro.serving.policy import SubmitOptions
from repro.serving.queue import (
    PreparedPool,
    SolveServer,
    matrix_fingerprint,
)
from repro.sparse import make_problem

EPOCHS = 150
PREP_KW = dict(num_blocks=8, materialize_p=False)


@pytest.fixture(scope="module")
def problem():
    return make_problem(n=96, m=384, seed=3, dtype=np.float32)


@pytest.fixture(scope="module")
def rhs_batch(problem):
    rng = np.random.default_rng(17)
    xs = rng.standard_normal((96, 10)).astype(np.float32)
    return problem.A @ xs, xs


def _run(coro):
    return asyncio.run(asyncio.wait_for(coro, timeout=120))


def _injector(*rules, seed=0, clock=None):
    return FaultInjector(FaultPlan(rules=tuple(rules), seed=seed), clock=clock)


# ---------------------------------------------------------------------------
# the plan itself: serialization + determinism
# ---------------------------------------------------------------------------


def test_fault_plan_json_roundtrip(tmp_path):
    plan = FaultPlan(
        rules=(
            FaultRule(site="solve", kind="error", request=7),
            FaultRule(site="checkpoint.load", kind="corrupt", times=1),
            FaultRule(site="solve", kind="nan", request=3, prob=0.5,
                      delay_s=0.25),
        ),
        seed=42,
    )
    assert FaultPlan.from_json(plan.to_json()) == plan
    f = tmp_path / "plan.json"
    f.write_text(plan.to_json())
    assert FaultPlan.load(f) == plan


def test_fault_plan_accepts_dict_rules():
    plan = FaultPlan(rules=({"site": "solve", "kind": "error"},))
    assert plan.rules[0] == FaultRule(site="solve", kind="error")


def test_poisoned_requests_is_only_persistent_targeted_solve_rules():
    plan = FaultPlan(rules=(
        FaultRule(site="solve", kind="error", request=4),  # poison
        FaultRule(site="solve", kind="nan", request=5),  # poison
        FaultRule(site="solve", kind="error", request=6, times=1),  # transient
        FaultRule(site="solve", kind="error", request=7, path="matfree"),
        FaultRule(site="solve", kind="error", request=8, prob=0.5),
        FaultRule(site="prepare", kind="error"),  # not a solve rule
    ))
    assert plan.poisoned_requests == frozenset({4, 5})


def test_probabilistic_rule_is_seed_deterministic():
    rule = FaultRule(site="solve", kind="error", prob=0.5)

    def pattern(seed):
        inj = _injector(rule, seed=seed)
        out = []
        for i in range(32):
            try:
                inj.on_solve("fp", (i,))
                out.append(False)
            except InjectedFault:
                out.append(True)
        return out

    first = pattern(seed=7)
    assert pattern(seed=7) == first  # same plan -> same injections
    assert any(first) and not all(first)  # actually probabilistic
    assert pattern(seed=8) != first  # the seed is live


def test_after_and_times_windows():
    inj = _injector(
        FaultRule(site="prepare", kind="error", after=1, times=2)
    )
    inj.on_prepare("fp")  # match 1: skipped by after
    with pytest.raises(InjectedFault):
        inj.on_prepare("fp")
    with pytest.raises(InjectedFault):
        inj.on_prepare("fp")
    inj.on_prepare("fp")  # times cap reached: rule is spent
    assert inj.fired_total == 2
    (st,) = inj.stats()
    assert st["matches"] == 4 and st["fires"] == 2


def test_delay_advances_manual_clock_without_sleeping():
    clock = ManualClock()
    inj = _injector(
        FaultRule(site="prepare", kind="delay", delay_s=1.5), clock=clock
    )
    t0 = clock.now()
    inj.on_prepare("fp")  # kind="delay": latency only, no raise
    assert clock.now() - t0 == pytest.approx(1.5)


# ---------------------------------------------------------------------------
# containment: isolation, recovery ladder, breaker, timeout
# ---------------------------------------------------------------------------


def test_poison_request_is_isolated_from_batchmates(problem, rhs_batch):
    """A persistently-failing request must funnel down to a singleton
    ``SolveFailure`` via bisection while every batchmate still resolves
    to the solution of its own right-hand side."""
    B, xs = rhs_batch
    k = 6

    async def main():
        async with SolveServer(
            max_batch=k, max_wait_ms=20.0, num_epochs=EPOCHS,
            prepare_kwargs=PREP_KW,
        ) as server:
            fp = server.register(problem.A)
            poison = server.next_request_seq + 2
            server.faults = server.pool.faults = _injector(
                FaultRule(site="solve", kind="error", request=poison)
            )
            results = await asyncio.gather(
                *(server.submit(fp, B[:, i]) for i in range(k)),
                return_exceptions=True,
            )
            return results, server.stats(), poison

    results, stats, poison = _run(main())
    failures = [r for r in results if isinstance(r, BaseException)]
    assert len(failures) == 1
    (failure,) = failures
    assert isinstance(failure, SolveFailure)
    assert failure.request == poison and failure.reason == "error"
    assert failure.attempts >= 2  # original + ladder attempts
    assert results.index(failure) == 2  # the TARGETED request, no other
    for i, res in enumerate(results):
        if i == 2:
            continue
        np.testing.assert_allclose(res.x, xs[:, i], atol=1e-3)
    assert stats["failed_requests"] == 1
    assert stats["recovered_requests"] == 0
    assert stats["requests"] == k - 1
    assert stats["retries"] >= 2  # bisect rounds + the singleton's ladder


def test_transient_solve_fault_recovers_by_retry(problem, rhs_batch):
    B, xs = rhs_batch

    async def main():
        async with SolveServer(
            max_batch=1, max_wait_ms=5.0, num_epochs=EPOCHS,
            prepare_kwargs=PREP_KW,
        ) as server:
            fp = server.register(problem.A)
            server.faults = server.pool.faults = _injector(
                FaultRule(site="solve", kind="error",
                          request=server.next_request_seq, times=1)
            )
            res = await server.submit(fp, B[:, 0])
            return res, server.stats()

    res, stats = _run(main())
    np.testing.assert_allclose(res.x, xs[:, 0], atol=1e-3)
    assert res.attempts == 2  # failed dispatch + successful retry
    assert stats["recovered_requests"] == 1
    assert stats["failed_requests"] == 0
    assert stats["failures"] >= 1 and stats["retries"] >= 1


def test_transient_prepare_fault_recovers(problem, rhs_batch):
    B, xs = rhs_batch

    async def main():
        async with SolveServer(
            max_batch=1, max_wait_ms=5.0, num_epochs=EPOCHS,
            prepare_kwargs=PREP_KW,
        ) as server:
            fp = server.register(problem.A)
            server.faults = server.pool.faults = _injector(
                FaultRule(site="prepare", kind="error", times=1)
            )
            res = await server.submit(fp, B[:, 0])
            return res, server.stats()

    res, stats = _run(main())
    np.testing.assert_allclose(res.x, xs[:, 0], atol=1e-3)
    assert stats["recovered_requests"] == 1


def test_watchdog_catches_nan_column_and_ladder_recovers(problem, rhs_batch):
    """An injected NaN column never reaches its future: the watchdog flags
    it post-solve, healthy batchmates deliver normally, and the flagged
    request recovers on a clean retry."""
    B, xs = rhs_batch
    k = 4

    async def main():
        async with SolveServer(
            max_batch=k, max_wait_ms=20.0, num_epochs=EPOCHS,
            prepare_kwargs=PREP_KW, watchdog=Watchdog(),
        ) as server:
            fp = server.register(problem.A)
            sick = server.next_request_seq + 1
            server.faults = server.pool.faults = _injector(
                FaultRule(site="solve", kind="nan", request=sick, times=1)
            )
            results = await asyncio.gather(
                *(server.submit(fp, B[:, i]) for i in range(k))
            )
            return results, server.stats()

    results, stats = _run(main())
    for i, res in enumerate(results):
        np.testing.assert_allclose(res.x, xs[:, i], atol=1e-3)
        assert np.isfinite(np.asarray(res.x)).all()
    assert results[1].attempts == 2  # the flagged column rode the ladder
    assert results[0].attempts == 1  # batchmates were untouched
    assert stats["recovered_requests"] == 1
    assert stats["failed_requests"] == 0


def test_watchdog_catches_stall_column(problem, rhs_batch):
    B, xs = rhs_batch

    async def main():
        async with SolveServer(
            max_batch=1, max_wait_ms=5.0, num_epochs=EPOCHS,
            prepare_kwargs=PREP_KW, watchdog=Watchdog(),
        ) as server:
            fp = server.register(problem.A)
            server.faults = server.pool.faults = _injector(
                FaultRule(site="solve", kind="stall",
                          request=server.next_request_seq, times=1)
            )
            res = await server.submit(fp, B[:, 0])
            return res, server.stats()

    res, stats = _run(main())
    np.testing.assert_allclose(res.x, xs[:, 0], atol=1e-3)
    assert res.attempts == 2
    assert stats["recovered_requests"] == 1
    assert stats["failures"] >= 1  # the stall was observed and counted


def test_matfree_fault_escalates_to_dense_fallback():
    """A fault pinned to the matfree solver path keeps firing through the
    retries, so the ladder's fallback re-prepare (matfree → dense) is what
    recovers the request — and the pool permanently adopts the sturdy
    path for subsequent traffic."""
    from repro.sparse import generate_schenk_like

    coo = generate_schenk_like(256, sparsity=0.99, seed=5)
    rng = np.random.default_rng(11)
    x_true = rng.standard_normal(256).astype(np.float32)
    b = coo.to_dense().astype(np.float32) @ x_true

    async def main():
        async with SolveServer(
            max_batch=1, max_wait_ms=5.0, num_epochs=400,
            prepare_kwargs=dict(mode="matfree", num_blocks=8),
        ) as server:
            fp = server.register(coo)
            server.faults = server.pool.faults = _injector(
                FaultRule(site="solve", kind="error", path="matfree")
            )
            first = await server.submit(fp, b)
            second = await server.submit(fp, b)
            return first, second, server.stats(), server.pool.resident()

    first, second, stats, resident = _run(main())
    # the fallback-recovered solve IS the dense-path solve: identical
    # program + inputs as the second (undisturbed) request
    np.testing.assert_allclose(first.x, second.x, atol=1e-6)
    assert np.isfinite(np.asarray(first.x)).all()
    assert first.residual_sq < 1e-2 * float(b @ b)
    assert first.attempts == 3  # dispatch + retry (both matfree) + fallback
    assert second.attempts == 1  # the pool stayed on the dense path
    assert stats["recovered_requests"] == 1
    assert stats["failed_requests"] == 0
    (entry,) = resident
    assert entry["path"] == "dense"


def test_circuit_breaker_opens_fast_fails_and_heals(problem, rhs_batch):
    """Deterministic breaker lifecycle on a ManualClock: consecutive
    dispatch failures trip it open, an open breaker fast-fails submits
    without queueing, and the half-open trial after the cooldown closes
    it once the system solves again."""
    B, xs = rhs_batch
    clock = ManualClock()

    async def main():
        async with SolveServer(
            max_batch=1, num_epochs=EPOCHS, prepare_kwargs=PREP_KW,
            clock=clock, breaker_threshold=2, breaker_cooldown_ms=1000.0,
        ) as server:
            fp = server.register(problem.A)
            # 3 fires: req0's dispatch + refresh, req1's dispatch (which
            # trips the breaker); req1's refresh then finds the rule spent
            server.faults = server.pool.faults = _injector(
                FaultRule(site="solve", kind="error", times=3),
                clock=clock,
            )
            opts = SubmitOptions(max_retries=0)  # ladder = refresh only
            out = {}
            with pytest.raises(SolveFailure) as e0:
                await server.submit(fp, B[:, 0], opts)
            out["r0"] = e0.value
            out["r1"] = await server.submit(fp, B[:, 1], opts)
            with pytest.raises(SolveFailure) as e2:  # open: fail fast
                await server.submit(fp, B[:, 2], opts)
            out["r2"] = e2.value
            clock.advance(1.1)  # past the cooldown -> half-open trial
            out["r3"] = await server.submit(fp, B[:, 3], opts)
            v = server.metrics.value
            out["transitions"] = {
                to: int(v("server_breaker_transitions_total", to=to))
                for to in ("open", "half_open", "closed")
            }
            return out, server.stats()

    out, stats = _run(main())
    assert out["r0"].reason == "error"
    np.testing.assert_allclose(out["r1"].x, xs[:, 1], atol=1e-3)
    assert out["r1"].attempts == 2  # recovered on the (spent-rule) refresh
    assert out["r2"].reason == "breaker_open" and out["r2"].attempts == 0
    np.testing.assert_allclose(out["r3"].x, xs[:, 3], atol=1e-3)
    assert out["transitions"] == {"open": 1, "half_open": 1, "closed": 1}
    assert stats["failed_requests"] == 2  # r0 (ladder exhausted) + r2


def test_timeout_budget_bounds_the_ladder(problem, rhs_batch):
    """With a persistent fault, ``timeout_ms`` converts an unbounded
    ladder into a clean structured timeout — backoff runs on the injected
    clock, so the test itself never sleeps."""
    B, _ = rhs_batch
    clock = ManualClock()

    async def main():
        async with SolveServer(
            max_batch=1, num_epochs=EPOCHS, prepare_kwargs=PREP_KW,
            clock=clock, backoff_base_ms=10.0,
        ) as server:
            fp = server.register(problem.A)
            server.faults = server.pool.faults = _injector(
                FaultRule(site="solve", kind="error"), clock=clock
            )
            with pytest.raises(SolveFailure) as exc:
                await server.submit(
                    fp, B[:, 0],
                    SubmitOptions(max_retries=8, timeout_ms=25.0),
                )
            return exc.value, server.stats()

    failure, stats = _run(main())
    assert failure.reason == "timeout"
    # backoff 10ms then 20ms: the budget dies inside the ladder, well
    # before the 9 configured attempts
    assert failure.attempts <= 3
    assert stats["failed_requests"] == 1


def test_cancelled_request_cannot_poison_or_stall_its_batch(
    problem, rhs_batch
):
    """ISSUE 9 satellite #1: a request whose future is already done by
    dispatch time is dropped BEFORE the solve — here the cancelled request
    is also the fault plan's target, so if it were still dispatched the
    whole batch would fail. Batchmates must resolve normally."""
    B, xs = rhs_batch

    async def main():
        async with SolveServer(
            max_batch=4, max_wait_ms=60.0, num_epochs=EPOCHS,
            prepare_kwargs=PREP_KW,
        ) as server:
            fp = server.register(problem.A)
            doomed_seq = server.next_request_seq + 1
            server.faults = server.pool.faults = _injector(
                FaultRule(site="solve", kind="error", request=doomed_seq)
            )
            tasks = [
                asyncio.ensure_future(server.submit(fp, B[:, i]))
                for i in range(3)
            ]
            await asyncio.sleep(0.005)  # all three are queued, none flushed
            tasks[1].cancel()
            results = await asyncio.gather(*tasks, return_exceptions=True)
            return results, server.stats()

    results, stats = _run(main())
    assert isinstance(results[1], asyncio.CancelledError)
    for i in (0, 2):
        np.testing.assert_allclose(results[i].x, xs[:, i], atol=1e-3)
    assert stats["cancelled"] >= 1
    assert stats["failed_requests"] == 0  # the poison rule never fired
    assert stats["failures"] == 0
    assert stats["requests"] == 2


# ---------------------------------------------------------------------------
# checkpoint quarantine (ISSUE 9 satellite #2)
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def prepared(problem):
    return prepare(problem.A, **PREP_KW)


def test_truncated_checkpoint_quarantined_and_never_reread(
    tmp_path, problem, prepared
):
    store = CheckpointStore(tmp_path)
    assert store.save("fp", prepared, dict(PREP_KW))
    target = store.path("fp")
    raw = target.read_bytes()
    target.write_bytes(raw[: len(raw) // 2])  # truncated npz
    assert store.load("fp", dict(PREP_KW)) is None
    assert store.quarantined == 1 and store.load_misses == 1
    bad = target.with_name(target.name + ".bad")
    assert bad.exists() and not target.exists()
    assert bad.read_bytes() == raw[: len(raw) // 2]  # evidence preserved
    # second miss: plain not-found, the bad bytes are never read again
    assert store.load("fp", dict(PREP_KW)) is None
    assert store.quarantined == 1 and store.load_misses == 1


def test_foreign_file_quarantined(tmp_path):
    store = CheckpointStore(tmp_path)
    store.path("fp").write_bytes(b"this was never an npz")
    assert store.load("fp", dict(PREP_KW)) is None
    assert store.quarantined == 1
    assert store.path("fp").with_name("fp.npz.bad").exists()


def test_config_mismatch_misses_without_quarantine(
    tmp_path, problem, prepared
):
    """A checkpoint from a DIFFERENT prepare config is a valid file for
    someone else — it must miss but stay in place."""
    store = CheckpointStore(tmp_path)
    assert store.save("fp", prepared, dict(PREP_KW))
    other = dict(PREP_KW, num_blocks=4)
    assert store.load("fp", other) is None
    assert store.quarantined == 0 and store.load_misses == 1
    assert store.path("fp").exists()
    assert store.load("fp", dict(PREP_KW)) is not None  # still restorable


def test_injected_corruption_quarantines_and_pool_reprepares(
    tmp_path, problem
):
    """The injector damages the file right before the load; the store
    quarantines and the pool transparently falls back to a fresh
    ``prepare`` — serving never needs the checkpoint to make progress."""
    fp_target = matrix_fingerprint(problem.A)
    # after=1: the first match is the cold-start load (no file on disk
    # yet); the fault fires on the SECOND load, when a checkpoint exists
    faults = _injector(
        FaultRule(site="checkpoint.load", kind="corrupt",
                  fingerprint=fp_target, after=1, times=1)
    )
    pool = PreparedPool(
        max_size=1, checkpoint=str(tmp_path), faults=faults, **PREP_KW
    )
    fp = pool.register(problem.A)
    assert fp == fp_target
    pool.get(fp)  # cold prepare + write-through
    other = pool.register(
        make_problem(n=48, m=192, seed=0, dtype=np.float32).A
    )
    pool.get(other)  # evicts fp (max_size=1)
    prep = pool.get(fp)  # miss -> injected corruption -> quarantine -> prepare
    assert prep is not None
    assert pool.checkpoint.quarantined == 1
    assert pool.stats.prepares == 3 and pool.stats.restores == 0
    bad = pool.checkpoint.path(fp).with_name(f"{fp}.npz.bad")
    assert bad.exists()
    # the write-through after the fresh prepare healed the checkpoint
    assert pool.checkpoint.path(fp).exists()


def test_injected_io_errors_do_not_quarantine(tmp_path, problem, prepared):
    """Transient IO failure (``InjectedIOError``/OSError): the bytes on
    disk may be fine, so the store misses WITHOUT quarantining — and a
    failed save leaves no temp litter and no counter movement."""
    faults = _injector(
        FaultRule(site="checkpoint.load", kind="error", times=1),
        FaultRule(site="checkpoint.save", kind="error", after=1),
    )
    store = CheckpointStore(tmp_path, faults=faults)
    assert store.save("fp", prepared, dict(PREP_KW))
    assert store.load("fp", dict(PREP_KW)) is None  # injected read error
    assert store.load_misses == 1 and store.quarantined == 0
    assert store.path("fp").exists()
    assert store.load("fp", dict(PREP_KW)) is not None  # bytes were fine
    assert not store.save("fp2", prepared, dict(PREP_KW))  # injected write
    assert not store.path("fp2").exists()
    assert not list(tmp_path.glob("*.tmp"))


def test_concurrent_writers_never_expose_a_torn_checkpoint(
    tmp_path, problem, prepared
):
    """Many writers racing on one fingerprint (the multi-process serving
    deployment): the temp-file + ``os.replace`` protocol means every
    observable file state is a COMPLETE checkpoint, so a reader loading
    mid-race restores successfully and nothing is ever quarantined."""
    store = CheckpointStore(tmp_path)
    stop = threading.Event()
    errors = []

    def writer():
        while not stop.is_set():
            if not store.save("fp", prepared, dict(PREP_KW)):
                errors.append("save failed")

    # a checkpoint exists before the race, so every read below is a read
    # during the race, not a read that came before the first write
    assert store.save("fp", prepared, dict(PREP_KW))
    threads = [threading.Thread(target=writer) for _ in range(4)]
    for t in threads:
        t.start()
    try:
        reader = CheckpointStore(tmp_path)
        loaded = 0
        for _ in range(25):
            if reader.load("fp", dict(PREP_KW)) is not None:
                loaded += 1
    finally:
        stop.set()
        for t in threads:
            t.join()
    assert not errors
    assert loaded == 25  # every mid-race read saw a whole checkpoint
    assert reader.quarantined == 0 and reader.load_misses == 0
