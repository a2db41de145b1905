"""Spans and copy counts of the serve path (``repro.core.tracing``).

One profiler session for the module: a background ``MVEScheduler`` serves
VM singletons, a promotion and a fused batch of 4 while ``jax.profiler``
traces, and the tests read the trace back with ``ProfileData``.
"""
import glob
import re
import time

import jax
import numpy as np
import pytest

from repro.core import engine, tracing, vm
from repro.core.machine import MVEConfig
from repro.core.patterns import PATTERNS
from repro.runtime.scheduler import MVEScheduler

CFG = MVEConfig()
SCHED_SPANS = (tracing.IDLE, tracing.WINDOW, tracing.GROUP, tracing.PROMOTE,
               tracing.DISPATCH, tracing.SYNC, tracing.FETCH, tracing.RESOLVE)
PROGRAM_SPANS = SCHED_SPANS + (tracing.LOWER, tracing.TABLES)
SINGLES = ("memcpy", "xor_cipher", "reduction")


def _stats(sched):
    return dict(vars(sched.stats))


@pytest.fixture(scope="module")
def session(tmp_path_factory):
    """Serve under the profiler; returns the program spans of each host
    thread, the scheduler's counter deltas and the program-cache misses."""
    trace_dir = str(tmp_path_factory.mktemp("trace"))
    sched = MVEScheduler(cfg=CFG, background=True, promote_after=2,
                         max_batch=4, max_wait_ms=300.0)
    try:
        before = _stats(sched)
        misses = engine.cache_info().program_misses
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=options)
        try:
            for k, name in enumerate(SINGLES):      # VM singletons
                req = PATTERNS[name](seed=10 + k)
                sched.submit(req.program, req.memory).result(timeout=300)
            daxpy = [PATTERNS["daxpy"](seed=20 + k) for k in range(6)]
            for req in daxpy[:2]:   # the second submission promotes
                sched.submit(req.program, req.memory).result(timeout=300)
            tickets = [sched.submit(r.program, r.memory) for r in daxpy[2:]]
            results = [t.result(timeout=300) for t in tickets]
        finally:
            jax.profiler.stop_trace()
        after = _stats(sched)
        misses = engine.cache_info().program_misses - misses
    finally:
        sched.close()
    path = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)[0]
    threads = []
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            spans = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                     for e in line.events if e.name in PROGRAM_SPANS]
            if spans:
                threads.append(spans)
    delta = {k: after[k] - before[k] for k in before}
    return {"threads": threads, "delta": delta, "misses": misses,
            "results": results}


def _named(session, names):
    return [s for spans in session["threads"] for s in spans
            if s[0] in names]


def test_every_sched_span_is_traced(session):
    assert [r.tier for r in session["results"]] == ["fused"] * 4
    assert [r.batch_size for r in session["results"]] == [4] * 4
    found = {s[0] for spans in session["threads"] for s in spans}
    assert set(SCHED_SPANS) <= found


def test_dispatch_and_lower_counts_match_counters(session):
    assert len(_named(session, {tracing.DISPATCH})) \
        == session["delta"]["dispatches"] == len(SINGLES) + 3
    assert len(_named(session, {tracing.LOWER})) == session["misses"]
    assert len(_named(session, {tracing.PROMOTE})) \
        == session["delta"]["promotions"] == 1


def test_worker_spans_do_not_overlap(session):
    workers = [spans for spans in session["threads"]
               if any(s[0] == tracing.IDLE for s in spans)]
    assert len(workers) == 1
    spans = sorted((s for s in workers[0] if s[0] in SCHED_SPANS),
                   key=lambda s: s[1])
    for (_, _, end), (_, start, _) in zip(spans, spans[1:]):
        assert end <= start


def test_queue_counters(session):
    delta = session["delta"]
    assert delta["taken"] == delta["requests"] == len(SINGLES) + 6
    assert delta["queue_wait_s"] > 0.0


def test_copy_counts_by_hand():
    """Bytes copied, from the shapes: a VM singleton of ``daxpy`` uploads
    its image padded to the VM's memory bucket plus one lane row (float32)
    and fetches that buffer and the whole register file; a fused batch of
    4 uploads the stacked images (float32) and fetches them, the Tag of
    each image and the 4 registers ``daxpy`` writes (float32)."""
    lanes, size = CFG.lanes, 16384
    req = PATTERNS["daxpy"](seed=3)
    assert np.asarray(req.memory).shape == (size,)
    padded = (vm.MIN_MEM + lanes) * 4
    with MVEScheduler(cfg=CFG, promote_after=None) as sched:
        ticket = sched.submit(req.program, req.memory)
        sched.drain()
        assert ticket.result().tier == "vm"
        assert sched.stats.h2d_bytes == padded
        assert sched.stats.d2h_bytes == padded + vm.N_REGS * lanes * 4
        assert ticket.queued_at <= ticket.taken_at <= ticket.done_at
    reqs = [PATTERNS["daxpy"](seed=4 + k) for k in range(4)]
    with MVEScheduler(cfg=CFG, promote_after=1) as sched:
        tickets = [sched.submit(r.program, r.memory) for r in reqs]
        sched.drain()
        assert [t.result().batch_size for t in tickets] == [4] * 4
        assert sched.stats.h2d_bytes == 4 * size * 4
        assert sched.stats.d2h_bytes == \
            4 * size * 4 + 4 * lanes + 4 * 4 * lanes * 4


def test_span_is_a_shared_no_op_without_a_session():
    assert not jax.profiler.TraceAnnotation.is_enabled()
    off = tracing.span(tracing.DISPATCH, tier="vm", n=1, rows=1)
    assert off is tracing.span(tracing.GROUP)
    with off:
        pass


def test_span_off_path_costs_under_a_microsecond():
    """Best of 50 rounds of 1000 disabled spans on the CPU."""
    best = float("inf")
    for _ in range(50):
        t0 = time.perf_counter()
        for _ in range(1000):
            with tracing.span(tracing.DISPATCH, tier="vm", n=4, rows=4):
                pass
        best = min(best, (time.perf_counter() - t0) / 1000)
    assert best < 1e-6


def _module_name(lowered) -> str:
    return re.search(r"module @(\S+)", lowered.as_text()).group(1)


def test_executable_names_are_pinned():
    """The module names the benchmark's trace reduction matches
    (``chipbench/trace_reduce.py`` ``EXECUTABLES``): ``jit_execute`` for
    the VM datapath, ``jit__execute`` for a fused program, single and
    batched."""
    sig = vm.default_signature(CFG)
    ex = vm._Executor(sig)
    scalar = jax.ShapeDtypeStruct((), np.int32)
    mem = jax.ShapeDtypeStruct((sig[3] + sig[0],), np.float32)
    tables = ex.table_structs()
    assert _module_name(ex.single._jit.lower(mem, scalar, scalar, *tables)) \
        .startswith("jit_execute")
    batch = jax.ShapeDtypeStruct((2,) + mem.shape, np.float32)
    assert _module_name(ex.batch._jit.lower(batch, scalar, scalar, *tables)) \
        .startswith("jit_execute")
    cp = engine.compile_program(PATTERNS["daxpy"](seed=1).program, CFG,
                                mode="fused")
    masks, zeros = cp._fused_operands()
    one = jax.ShapeDtypeStruct((16384,), np.float32)
    assert _module_name(cp._jit._jit.lower(one, masks, zeros)) \
        .startswith("jit__execute")
    four = jax.ShapeDtypeStruct((4, 16384), np.float32)
    assert _module_name(cp._get_batch_jit()._jit.lower(four, masks, zeros)) \
        .startswith("jit__execute")
