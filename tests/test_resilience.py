"""Chaos tests for the self-healing serving runtime (PR 7).

The acceptance criterion lives in ``test_chaos_replay_64_request_stream``:
a deterministic fault-injected replay of the 64-request mixed Swan
stream (``benchmarks.serving_bench.request_stream``) at a seeded 10 %
fault rate must (a) resolve **every** ticket — no orphans, no hangs,
(b) never serve a result produced by a failed dispatch, (c) serve every
successful request **bit-exactly** equal to the stepwise-interpreter
oracle, and (d) stay within 2x of fault-free steady-state throughput.

The rest are unit tests of the individual resilience mechanisms:
fault-plan determinism and replay, batch bisection + quarantine,
bounded retry, circuit breaking + tier degradation, deadlines,
admission control, cancellation, close semantics, worker supervision,
and the sampled bit-flip audit.
"""
import time

import numpy as np
import pytest

from benchmarks.serving_bench import _QUICK_MIX, request_stream
from repro.core import engine
from repro.core.interp import MVEInterpreter
from repro.core.machine import MVEConfig
from repro.core.patterns import PATTERNS
from repro.resilience import (CancelledError, CircuitBreaker,
                              DeadlineExceededError, FaultInjector,
                              FaultPlan, FaultSpec, InjectedFault,
                              QuarantinedError, QueueFullError,
                              SchedulerClosedError)
from repro.runtime.scheduler import MVEScheduler

CFG = MVEConfig()
_ORACLE = MVEInterpreter(CFG, compiled=False)


def _oracle_memory(req):
    mem_i, _ = _ORACLE.run_stepwise(list(req.program), req.memory)
    return np.asarray(mem_i)


def _daxpy_reqs(n, seed0=1):
    return [PATTERNS["daxpy"](seed=seed0 + i) for i in range(n)]


def _fired_sig(inj):
    """The replay log reduced to its deterministic fields."""
    return [(f["site"], f["kind"], f["rid"]) for f in inj.fired]


# -- FaultPlan determinism ---------------------------------------------------

def test_fault_plan_random_is_deterministic_in_seed():
    a = FaultPlan.random(seed=42, n_requests=64, rate=0.1, sticky_rids=(7,))
    b = FaultPlan.random(seed=42, n_requests=64, rate=0.1, sticky_rids=(7,))
    assert a.specs == b.specs
    c = FaultPlan.random(seed=43, n_requests=64, rate=0.1, sticky_rids=(7,))
    assert a.specs != c.specs


def test_fault_plan_json_round_trip():
    plan = FaultPlan.random(seed=5, n_requests=32, rate=0.2,
                            sticky_rids=(3,), worker_kills=1)
    back = FaultPlan.from_json(plan.to_json())
    assert back.specs == plan.specs
    assert back.seed == plan.seed


def test_fault_spec_rejects_unknown_kind():
    with pytest.raises(ValueError, match="unknown fault kind"):
        FaultSpec(site="dispatch", kind="gremlin")


def test_chaos_replay_log_is_reproducible():
    """Same plan + same stream + drain mode => identical firing log."""
    plan = FaultPlan.random(seed=9, n_requests=12, rate=0.4, sticky_rids=(4,))
    logs = []
    for _ in range(2):
        inj = FaultInjector(plan, sleep=lambda s: None)
        with MVEScheduler(CFG, promote_after=None, injector=inj) as s:
            reqs = [PATTERNS["daxpy"](seed=i + 1) for i in range(12)]
            for r in reqs:
                s.submit(r.program, r.memory)
            s.drain()
        logs.append(_fired_sig(inj))
    assert logs[0] == logs[1]
    assert logs[0]                      # the plan actually fired


# -- the acceptance criterion ------------------------------------------------

def _replay(stream, injector=None, **kw):
    sched = MVEScheduler(CFG, promote_after=2, injector=injector,
                         audit_rate=1.0, **kw)
    tickets = [sched.submit(r.program, r.memory) for _, r in stream]
    t0 = time.perf_counter()
    sched.drain()
    wall = time.perf_counter() - t0
    sched.close()
    return wall, tickets, sched


def test_chaos_replay_64_request_stream():
    stream = request_stream()           # the 64-request mixed Swan stream
    assert len(stream) == 64
    sticky = (11,)                      # one permanently poisoned request
    plan = FaultPlan.random(seed=2026, n_requests=len(stream), rate=0.10,
                            sticky_rids=sticky)
    assert len(plan) > 3                # the 10% draw actually found victims

    # Warm every executable so both measured replays are steady-state:
    # one clean pass (scheduler tiers + audit cross-executors) and one
    # chaos pass (the recovery paths introduce bisection-half batch
    # shapes the clean pass never compiles).
    _replay(stream)
    _replay(stream, injector=FaultInjector(plan))

    wall_clean, tickets_clean, _ = _replay(stream)
    assert all(t.done() for t in tickets_clean)
    assert all(t.error() is None for t in tickets_clean)

    inj = FaultInjector(plan)
    wall_chaos, tickets, sched = _replay(stream, injector=inj)

    # (a) every ticket resolved -- no orphans, no hangs.
    assert all(t.done() for t in tickets)

    # (b)+(c) every non-quarantined request served bit-exactly equal to
    # the stepwise oracle; the sticky request resolved with the typed
    # quarantine error (never a corrupt/failed-dispatch result).
    failed = {t.rid: t.error() for t in tickets if t.error() is not None}
    assert set(failed) == set(sticky), failed
    assert isinstance(failed[sticky[0]], QuarantinedError)
    for t, (_, req) in zip(tickets, stream):
        if t.rid in failed:
            continue
        assert np.array_equal(t.result().memory, _oracle_memory(req)), \
            f"rid {t.rid} not bit-exact vs the stepwise oracle"

    # The plan's faults really fired and recovery really ran.
    assert inj.injected >= len(plan) - 1    # sticky fires many times
    assert sched.stats.recovered > 0
    assert sched.stats.quarantines == 1

    # (d) steady-state throughput within 2x of fault-free.
    assert wall_chaos <= 2.0 * wall_clean + 0.05, \
        (wall_chaos, wall_clean, sched.stats)


def test_chaos_background_stream_with_worker_kill():
    """Background-mode chaos: injected worker death mid-stream + faults;
    the supervisor restarts the worker and every ticket still resolves."""
    stream = request_stream(mix=_QUICK_MIX)
    plan = FaultPlan.random(seed=3, n_requests=len(stream), rate=0.2,
                            worker_kills=1)
    inj = FaultInjector(plan)
    sched = MVEScheduler(CFG, promote_after=None, background=True,
                         injector=inj, audit_rate=1.0)
    tickets = [sched.submit(r.program, r.memory) for _, r in stream]
    results = [t.result(timeout=60) for t in tickets]
    assert len(results) == len(stream)
    for t, (_, req) in zip(tickets, stream):
        assert np.array_equal(t.result().memory, _oracle_memory(req))
    sched.close()


# -- bisection + quarantine --------------------------------------------------

def test_sticky_request_is_bisected_out_and_quarantined():
    reqs = _daxpy_reqs(4)
    plan = FaultPlan([FaultSpec(site="dispatch", kind="error", rid=2,
                                times=-1)])
    inj = FaultInjector(plan)
    with MVEScheduler(CFG, promote_after=None, injector=inj) as s:
        ts = [s.submit(r.program, r.memory) for r in reqs]
        s.drain()
        assert s.stats.bisections > 0
        with pytest.raises(QuarantinedError) as ei:
            ts[2].result()
        assert ei.value.attempts > 1            # it really was retried
        for i in (0, 1, 3):                     # siblings unharmed, exact
            assert np.array_equal(ts[i].result().memory,
                                  _oracle_memory(reqs[i]))
        # Re-submission while quarantined is rejected with the typed error.
        t = s.submit(reqs[2].program, reqs[2].memory)
        s.drain()
        assert isinstance(t.error(), QuarantinedError)
        assert s.stats.quarantine_rejects == 1


def test_quarantine_cooldown_allows_probe():
    reqs = _daxpy_reqs(1)
    plan = FaultPlan([FaultSpec(site="dispatch", kind="error", rid=0,
                                times=-1)])
    inj = FaultInjector(plan)
    with MVEScheduler(CFG, promote_after=None, injector=inj,
                      quarantine_cooldown_s=0.0) as s:
        t = s.submit(reqs[0].program, reqs[0].memory)
        s.drain()
        assert isinstance(t.error(), QuarantinedError)
        # Cooldown of 0: the next submission probes again (and, the fault
        # being rid-bound, a *fresh* rid now succeeds).
        t2 = s.submit(reqs[0].program, reqs[0].memory)
        s.drain()
        assert t2.error() is None
        assert np.array_equal(t2.result().memory, _oracle_memory(reqs[0]))


# -- retry / breaker / degradation ladder ------------------------------------

def test_transient_fault_recovers_via_retry_bit_exact():
    reqs = _daxpy_reqs(1)
    plan = FaultPlan([FaultSpec(site="dispatch", kind="error", rid=0)])
    inj = FaultInjector(plan)
    with MVEScheduler(CFG, promote_after=None, injector=inj) as s:
        t = s.submit(reqs[0].program, reqs[0].memory)
        s.drain()
        assert np.array_equal(t.result().memory, _oracle_memory(reqs[0]))
        assert s.stats.retries >= 1
        assert s.stats.recovered == 1


def test_open_breaker_degrades_to_oracle_tier():
    """A tier that keeps failing opens its breaker; traffic degrades down
    the ladder and is served by the stepwise oracle — still bit-exact."""
    reqs = _daxpy_reqs(3)
    # Unshielded vm dispatches always fail; recovery paths are shielded,
    # but the breaker (threshold=1) opens on the very first failure.
    plan = FaultPlan([FaultSpec(site="dispatch", kind="error", tier="vm",
                                times=-1)])
    inj = FaultInjector(plan)
    with MVEScheduler(CFG, promote_after=None, injector=inj,
                      breaker=CircuitBreaker(threshold=1, cooldown_s=60.0)
                      ) as s:
        ts = []
        for r in reqs:
            ts.append(s.submit(r.program, r.memory))
            s.drain()
        for t, r in zip(ts, reqs):
            assert np.array_equal(t.result().memory, _oracle_memory(r))
        assert s.stats.breaker_opens >= 1
        assert s.stats.oracle_serves >= 1       # ladder bottomed out
        assert s.stats.demotions >= 1
        assert any(t.result().tier == "oracle" for t in ts)
        health = s.health()
        assert health["breakers"]["open"]       # visible in the snapshot


def test_failed_promotion_does_not_fail_requests():
    reqs = [PATTERNS["daxpy"](seed=1) for _ in range(4)]
    plan = FaultPlan([FaultSpec(site="compile", kind="error", times=-1)])
    inj = FaultInjector(plan)
    with MVEScheduler(CFG, promote_after=2, injector=inj) as s:
        ts = [s.submit(r.program, r.memory) for r in reqs]
        s.drain()
        for t, r in zip(ts, reqs):
            assert np.array_equal(t.result().memory, _oracle_memory(r))
        assert s.stats.promotion_failures >= 1
        assert s.stats.promotions == 0          # fused tier never came up


def test_deep_engine_fault_hook_recovers():
    """Faults injected *inside* the engine (via the vm fault hook) surface
    like any dispatch failure and recover through the same ladder."""
    reqs = _daxpy_reqs(2)
    plan = FaultPlan([FaultSpec(site="engine.dispatch", kind="error")])
    inj = FaultInjector(plan)
    prev = engine.set_fault_hook(inj.engine_hook)
    try:
        with MVEScheduler(CFG, promote_after=None, injector=inj) as s:
            ts = [s.submit(r.program, r.memory) for r in reqs]
            s.drain()
            for t, r in zip(ts, reqs):
                assert np.array_equal(t.result().memory, _oracle_memory(r))
            assert s.stats.recovered >= 1
    finally:
        engine.set_fault_hook(prev)
    assert any(f["site"] == "engine.dispatch" for f in inj.fired)


def test_executor_error_taxonomy():
    assert issubclass(engine.CompileError, engine.ExecutorError)
    assert issubclass(engine.DispatchError, engine.ExecutorError)
    assert issubclass(engine.FinalizeError, engine.ExecutorError)
    assert issubclass(engine.ExecutorError, RuntimeError)


# -- bit-flips + audit -------------------------------------------------------

def test_bitflip_is_caught_and_corrected_by_audit():
    reqs = _daxpy_reqs(4)
    plan = FaultPlan([FaultSpec(site="finalize", kind="bitflip", rid=1,
                                word=5, bit=12)])
    inj = FaultInjector(plan)
    with MVEScheduler(CFG, promote_after=None, injector=inj,
                      audit_rate=1.0) as s:
        ts = [s.submit(r.program, r.memory) for r in reqs]
        s.drain()
        assert s.stats.audit_corrected == 1
        for t, r in zip(ts, reqs):              # corrected result served
            assert np.array_equal(t.result().memory, _oracle_memory(r))


def test_bitflip_without_audit_is_silent():
    """The negative control: the SRAM cell-fault model is *silent* —
    without the audit the corrupted result is served as-is."""
    reqs = _daxpy_reqs(1)
    plan = FaultPlan([FaultSpec(site="finalize", kind="bitflip", rid=0,
                                word=5, bit=12)])
    inj = FaultInjector(plan)
    with MVEScheduler(CFG, promote_after=None, injector=inj) as s:
        t = s.submit(reqs[0].program, reqs[0].memory)
        s.drain()
        assert not np.array_equal(t.result().memory,
                                  _oracle_memory(reqs[0]))


def test_straggler_latency_is_injected_and_logged():
    reqs = _daxpy_reqs(1)
    slept = []
    plan = FaultPlan([FaultSpec(site="dispatch", kind="straggler", rid=0,
                                latency_s=0.25)])
    inj = FaultInjector(plan, sleep=slept.append)
    with MVEScheduler(CFG, promote_after=None, injector=inj) as s:
        t = s.submit(reqs[0].program, reqs[0].memory)
        s.drain()
        assert t.error() is None
    assert slept == [0.25]
    assert _fired_sig(inj) == [("dispatch", "straggler", 0)]


# -- deadlines / admission / cancellation / close ----------------------------

def test_expired_deadline_resolves_typed_error():
    reqs = _daxpy_reqs(1)
    with MVEScheduler(CFG, promote_after=None) as s:
        t = s.submit(reqs[0].program, reqs[0].memory, deadline_s=0.0)
        time.sleep(0.002)
        s.drain()
        with pytest.raises(DeadlineExceededError):
            t.result()
        assert s.stats.deadline_misses == 1


def test_shed_admission_resolves_overflow_with_queue_full():
    reqs = _daxpy_reqs(5)
    with MVEScheduler(CFG, promote_after=None, max_queue=2,
                      admission="shed") as s:
        ts = [s.submit(r.program, r.memory) for r in reqs]
        shed = [t for t in ts if isinstance(t.error(), QueueFullError)]
        assert len(shed) == 3
        assert s.stats.sheds == 3
        s.drain()
        served = [t for t in ts if t.error() is None]
        assert len(served) == 2
        for t in served:
            assert t.result().batch_size >= 1


def test_block_admission_backpressures_until_space():
    reqs = _daxpy_reqs(6)
    with MVEScheduler(CFG, promote_after=None, background=True,
                      max_queue=2, admission="block") as s:
        ts = [s.submit(r.program, r.memory) for r in reqs]
        for t, r in zip(ts, reqs):
            assert np.array_equal(t.result(timeout=30).memory,
                                  _oracle_memory(r))
        assert s.stats.sheds == 0


def test_cancel_pending_ticket():
    reqs = _daxpy_reqs(2)
    with MVEScheduler(CFG, promote_after=None) as s:
        t0 = s.submit(reqs[0].program, reqs[0].memory)
        t1 = s.submit(reqs[1].program, reqs[1].memory)
        assert t0.cancel()
        s.drain()
        with pytest.raises(CancelledError):
            t0.result()
        assert t1.error() is None               # sibling unaffected
        assert not t1.cancel()                  # lost the race: already done
        assert t1.error() is None               # resolution stands


def test_close_resolves_pending_tickets_instead_of_hanging():
    reqs = _daxpy_reqs(2)
    s = MVEScheduler(CFG, promote_after=None)
    t0 = s.submit(reqs[0].program, reqs[0].memory)
    s.close(drain=False)
    with pytest.raises(SchedulerClosedError):
        t0.result(timeout=1)
    with pytest.raises(SchedulerClosedError):
        s.submit(reqs[1].program, reqs[1].memory)


def test_close_with_drain_serves_whats_pending():
    reqs = _daxpy_reqs(2)
    s = MVEScheduler(CFG, promote_after=None)
    ts = [s.submit(r.program, r.memory) for r in reqs]
    s.close()                                   # default drain=True
    for t, r in zip(ts, reqs):
        assert np.array_equal(t.result().memory, _oracle_memory(r))


def test_result_timeout_does_not_orphan_the_ticket():
    reqs = _daxpy_reqs(1)
    with MVEScheduler(CFG, promote_after=None) as s:
        t = s.submit(reqs[0].program, reqs[0].memory)
        with pytest.raises(TimeoutError):
            t.result(timeout=0.001)             # nothing drained yet
        s.drain()
        assert np.array_equal(t.result().memory, _oracle_memory(reqs[0]))


# -- worker supervision ------------------------------------------------------

def test_worker_death_requeues_and_supervisor_restarts():
    reqs = _daxpy_reqs(8)
    plan = FaultPlan([FaultSpec(site="worker", kind="kill")])
    inj = FaultInjector(plan)
    s = MVEScheduler(CFG, promote_after=None, background=True, injector=inj)
    ts = [s.submit(r.program, r.memory) for r in reqs]
    for t, r in zip(ts, reqs):
        assert np.array_equal(t.result(timeout=30).memory,
                              _oracle_memory(r))
    assert s.stats.worker_restarts == 1
    assert s.health()["worker"]["alive"]
    assert inj.counts() == {"kill": 1}
    s.close()


def test_program_server_surfaces_typed_errors_per_request():
    """The launch-layer facade: one quarantined request finishes with
    ``req.error`` set; it never aborts the drain of its neighbours."""
    from repro.launch.serve import MVEProgramServer

    reqs = _daxpy_reqs(3)
    plan = FaultPlan([FaultSpec(site="dispatch", kind="error", rid=1,
                                times=-1)])
    srv = MVEProgramServer(promote_after=None,
                           injector=FaultInjector(plan))
    handles = [srv.submit(r.program, r.memory) for r in reqs]
    done = srv.run_until_drained()
    assert len(done) == 3
    assert isinstance(handles[1].error, QuarantinedError)
    assert handles[1].result is None
    for i in (0, 2):
        assert handles[i].error is None
        assert np.array_equal(handles[i].result.memory,
                              _oracle_memory(reqs[i]))
    assert srv.health()["quarantine"]["total"] == 1
    srv.scheduler.close()


# -- health snapshot ---------------------------------------------------------

def test_health_snapshot_shape():
    reqs = _daxpy_reqs(2)
    plan = FaultPlan([FaultSpec(site="dispatch", kind="error", rid=0)])
    inj = FaultInjector(plan)
    with MVEScheduler(CFG, promote_after=None, injector=inj,
                      audit_rate=1.0) as s:
        for r in reqs:
            s.submit(r.program, r.memory)
        s.drain()
        h = s.health()
    for key in ("pending", "closed", "worker", "stragglers", "breakers",
                "quarantine", "counters", "audit", "injected"):
        assert key in h, key
    assert h["pending"] == 0
    assert h["counters"]["requests"] == 2
    # both batch-mates of the failed group dispatch count as recovered
    assert h["counters"]["recovered"] == 2
    assert h["injected"] == {"error": 1}
    assert h["audit"]["checked"] >= 1
