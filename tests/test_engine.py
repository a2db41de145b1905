"""Fused compiled engine vs the step-interpreter oracle (docs/ENGINE.md).

The equivalence contract: for every registered Section-IV pattern the
engine must produce bit-identical memory, registers and Tag latch, and an
identical cost-model trace (every TraceEvent field, including the exact
cache-line counts of random-base accesses).  This file pins
``mode="fused"``; the program-as-data VM (the default mode) has its own
oracle suite in ``tests/test_vm.py``, which covers both executors.
"""
import dataclasses
import sys
import threading

import numpy as np
import pytest
from _load_cases import LOAD_CASES, load_memories

from repro.core import MVEConfig, MVEInterpreter, compile_program, engine, isa
from repro.core.engine import CompiledProgram, cache_info, clear_cache
from repro.core.isa import DType
from repro.core.machine import (OOB_BASE, shift_store_layout, store_layout,
                                store_shape, touched_lines)
from repro.core.patterns import (PATTERNS, run_pattern, run_pattern_batch)

CFG = MVEConfig()
ORACLE = MVEInterpreter(CFG, compiled=False)


def _assert_equivalent(program, memory):
    mem_i, st_i = ORACLE.run_stepwise(program, memory)
    cp = compile_program(program, CFG, mode="fused")
    mem_e, st_e = cp.run(memory)
    np.testing.assert_array_equal(np.asarray(mem_i), np.asarray(mem_e))
    assert set(st_i.regs) == set(st_e.regs)
    for r in st_i.regs:
        np.testing.assert_array_equal(np.asarray(st_i.regs[r]),
                                      np.asarray(st_e.regs[r]))
    np.testing.assert_array_equal(np.asarray(st_i.tag),
                                  np.asarray(st_e.tag))
    assert len(st_i.trace) == len(st_e.trace)
    for i, (a, b) in enumerate(zip(st_i.trace, st_e.trace)):
        assert a.same_as(b), (i, a, b)
    return mem_e, st_e


@pytest.mark.parametrize("name", sorted(PATTERNS))
def test_engine_matches_interpreter(name):
    """Bit-identical memory + identical trace on every pattern."""
    run = PATTERNS[name]()
    mem_e, st_e = _assert_equivalent(run.program, run.memory)
    run.check(np.asarray(mem_e), st_e)


def test_engine_predicated_and_tag():
    """Tag-latch semantics survive compilation (compare + predicated op)."""
    mem = np.zeros(16)
    mem[:8] = np.arange(8)
    prog = [isa.vsetdimc(1), isa.vsetdiml(0, 8),
            isa.vsld(DType.DW, 1, 0, 1),
            isa.vsetdup(DType.DW, 0, 3),
            isa.vcmp(isa.Op.GT, DType.DW, 1, 0),
            isa.vsetdup(DType.DW, 2, 1),
            isa.vadd(DType.DW, 1, 1, 2, predicated=True)]
    _assert_equivalent(prog, mem)


def test_engine_masked_store_and_reduction_mask():
    """Dimension-level masking on stores compiles correctly."""
    mem = np.zeros(64)
    mem[:32] = np.arange(32)
    prog = [isa.vsetdimc(2), isa.vsetdiml(0, 8), isa.vsetdiml(1, 4),
            isa.vsld(DType.F, 0, 0, 1, 2),
            isa.vunsetmask(1), isa.vunsetmask(3),
            isa.vsst(DType.F, 0, 32, 1, 2)]
    mem_e, _ = _assert_equivalent(prog, mem)
    got = np.asarray(mem_e)
    np.testing.assert_array_equal(got[40:48], 0)
    np.testing.assert_array_equal(got[48:56], np.arange(16, 24))


def test_compile_cache_returns_same_object():
    run = PATTERNS["daxpy"]()
    a = compile_program(run.program, CFG)
    b = compile_program(list(run.program), CFG)
    assert a is b
    clear_cache()
    c = compile_program(run.program, CFG)
    assert c is not a


def test_run_pattern_compiled_equals_stepwise():
    run = PATTERNS["alpha_blend"]()
    mem_c, st_c = run_pattern(run, CFG, compiled=True)
    mem_s, st_s = run_pattern(run, CFG, compiled=False)
    np.testing.assert_array_equal(np.asarray(mem_c), np.asarray(mem_s))
    assert len(st_c.trace) == len(st_s.trace)


def test_vmap_batch_matches_per_image_runs():
    """One vmapped call over stacked memory images == N separate runs."""
    seeds = [0, 1, 2, 3]
    runs, mems = run_pattern_batch("daxpy", seeds, CFG)
    mems = np.asarray(mems)
    assert mems.shape[0] == len(seeds)
    for r, got in zip(runs, mems):
        mem_i, _ = ORACLE.run_stepwise(r.program, r.memory)
        np.testing.assert_array_equal(np.asarray(mem_i), got)
        r.check(got, None)


def test_vmap_batch_random_base_pointers_are_dynamic():
    """Random-base (Eq. 1) pointer arrays are data, not compile-time
    constants: a batch whose images carry different pointer tables must
    still be correct under one vmapped compilation."""
    seeds = [0, 7]
    runs, mems = run_pattern_batch("upsample", seeds, CFG)
    mems = np.asarray(mems)
    assert runs[0].program == runs[1].program   # same program, diff ptrs
    for r, got in zip(runs, mems):
        r.check(got, None)


def test_static_trace_exact_without_random_ops():
    """For purely strided programs the whole trace falls out of
    compilation — no execution needed."""
    run = PATTERNS["daxpy"]()
    cp = compile_program(run.program, CFG)
    _, st = cp.run(run.memory)
    assert len(cp.static_trace) == len(st.trace)
    for a, b in zip(cp.static_trace, st.trace):
        assert a.same_as(b)


# -- the compile walk's memo (docs/ENGINE.md "Walk memo") ---------------------

#: The 14 Section-IV patterns, then fresh spmm programs across the serving
#: benchmark's density range (0.02-0.44) and fresh fir programs.
WALK_CASES = ([(name, {}) for name in sorted(PATTERNS)]
              + [("spmm", {"density": float(d), "seed": 100 + i})
                 for i, d in enumerate(np.linspace(0.02, 0.44, 12))]
              + [("fir", {"seed": 200 + i}) for i in range(12)])


def _snapshot(value):
    """Plain, comparable bytes of steps, events, layouts and tables."""
    if isinstance(value, np.ndarray):
        return (value.dtype.str, value.shape, value.tobytes())
    if isinstance(value, (tuple, list)):
        return tuple(_snapshot(v) for v in value)
    if dataclasses.is_dataclass(value):
        return tuple((f.name, _snapshot(getattr(value, f.name)))
                     for f in dataclasses.fields(value))
    if hasattr(value, "__array__"):
        return _snapshot(np.asarray(value))
    return value


def _walk_snapshot(cp):
    tables = cp._vm.tables if cp._vm is not None else ()
    return _snapshot((cp.steps, tables, cp.n_random, cp.mode))


def _shifted(program, delta):
    """``program`` with every memory access's base moved by ``delta``."""
    return tuple(dataclasses.replace(i, base=i.base + delta)
                 if i.op in isa.MEMORY_OPS else i for i in program)


@pytest.mark.parametrize("mode", ["vm", "fused"])
@pytest.mark.parametrize("name,args", WALK_CASES,
                         ids=[f"{n}-{i}" for i, (n, _) in
                              enumerate(WALK_CASES)])
def test_walk_memo_is_exact(name, args, mode):
    """A walk served from the memo, filled by the same control states at
    other bases (other offsets within a line, and whole lines away),
    resolves every step, event and VM table exactly as a cold walk."""
    program = tuple(PATTERNS[name](**args).program)
    clear_cache()
    cold = _walk_snapshot(CompiledProgram(program, CFG, mode=mode))
    clear_cache()
    for delta in (1, 3, 8, 4099, 4096):
        CompiledProgram(_shifted(program, delta), CFG, mode=mode)
    before = cache_info()
    warm = CompiledProgram(program, CFG, mode=mode)
    after = cache_info()
    assert after.walk_misses == before.walk_misses
    assert after.walk_hits > before.walk_hits
    assert _walk_snapshot(warm) == cold
    for s in warm.steps:            # and as the unmemoized functions
        if s.addr is not None:
            assert s.event.lines == touched_lines(
                s.addr, s.lane_mask, s.instr.dtype.nbytes)
            if s.store_layout is not None:
                assert _snapshot(s.store_layout) == _snapshot(
                    store_layout(s.addr, s.lane_mask))


@pytest.mark.parametrize("seed", range(8))
def test_store_layout_shifts_exactly(seed):
    """``shift_store_layout`` of a base-free ``store_shape`` equals
    ``store_layout`` of the shifted addresses: colliding, reversed and
    masked lanes, negative contiguous bases and winners near
    ``OOB_BASE`` included."""
    rng = np.random.default_rng(seed)
    lanes = 64
    lane = np.arange(lanes, dtype=np.int64)
    offsets = [lane, lane[::-1].copy(), lane // 2, lane * 3 - 40,
               rng.integers(-50, 50, lanes)]
    masks = [np.ones(lanes, bool), np.zeros(lanes, bool),
             rng.random(lanes) < 0.5, lane % 7 == 3]
    bases = [0, 5, 41, -30, -100, 1000, OOB_BASE - 60, OOB_BASE + 3]
    for off in offsets:
        for mask in masks:
            shape = store_shape(off, mask)
            for base in bases:
                addr = off + base
                assert _snapshot(shift_store_layout(shape, base, addr,
                                                    mask)) == \
                    _snapshot(store_layout(addr, mask)), (off, mask, base)


def test_walk_memo_counts_and_is_read_only():
    """``clear_cache()`` empties the memo and zeroes its counters; a
    second spmm program with another non-zero count reuses per-lane work;
    the shared arrays refuse writes."""
    clear_cache()
    info = cache_info()
    assert info.walk_hits == info.walk_misses == 0
    first = PATTERNS["spmm"](density=0.1, seed=1).program
    second = PATTERNS["spmm"](density=0.3, seed=2).program
    assert len(first) != len(second)
    compile_program(first, CFG)
    filled = cache_info()
    assert filled.walk_misses > 0
    cp = compile_program(second, CFG)
    assert cache_info().walk_hits > filled.walk_hits
    vector = next(s for s in cp.steps if s.lane_mask is not None)
    config = next(s for s in cp.steps if s.instr.op in isa.CONFIG_OPS)
    random = next(s for s in cp.steps if s.offsets is not None)
    for row in (vector.lane_mask, vector.cb_mask, config.event.cb_mask,
                random.offsets, random.top_idx):
        with pytest.raises(ValueError):
            row[0] = row[0]
    clear_cache()
    assert cache_info().walk_hits == cache_info().walk_misses == 0


def test_walk_memo_is_bounded(monkeypatch):
    """The memo evicts its oldest entries past its byte capacity."""
    monkeypatch.setattr(engine, "_WALK_CAPACITY_BYTES", 200_000)
    clear_cache()
    for seed in range(4):
        compile_program(PATTERNS["fir"](seed=seed).program, CFG)
        compile_program(PATTERNS["spmm"](seed=seed).program, CFG)
        assert engine._WALK_BYTES <= 200_000
    assert engine._WALK_BYTES == sum(n for _, n in engine._WALK.values())
    clear_cache()


def test_walk_memo_racing_duplicate_is_counted_once():
    """A duplicate computed while another caller inserted the same key
    (here: from inside the first computation) leaves the first entry and
    its bytes in place, even where both computed the same object."""
    clear_cache()
    key = ("race",)
    got = engine._walk_memo(key, lambda: engine._walk_memo(key, lambda: 7))
    assert got == 7
    assert len(engine._WALK) == 1
    assert engine._WALK_BYTES == engine._WALK_ENTRY_BYTES
    assert (cache_info().walk_hits, cache_info().walk_misses) == (0, 2)
    clear_cache()


def test_walk_memo_under_concurrent_walks():
    """Walks on eight threads, with a short switch interval,
    lose no count, no entry's bytes and no exactness."""
    programs = [tuple(PATTERNS["spmm"](density=d, seed=7).program)
                for d in (0.05, 0.2)] + \
        [tuple(PATTERNS["fir"](seed=s).program) for s in (1, 2)]
    clear_cache()
    want = [_walk_snapshot(CompiledProgram(p, CFG)) for p in programs]
    info = cache_info()
    lookups = info.walk_hits + info.walk_misses
    threads = 8
    got = [None] * threads
    start = threading.Barrier(threads)
    clear_cache()

    def walk(t):
        start.wait(timeout=60)        # every thread misses the same keys
        got[t] = [_walk_snapshot(CompiledProgram(p, CFG))
                  for p in programs[t % 2:] + programs[:t % 2]]

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        pool = [threading.Thread(target=walk, args=(t,))
                for t in range(threads)]
        for th in pool:
            th.start()
        for th in pool:
            th.join(timeout=120)
    finally:
        sys.setswitchinterval(interval)
    assert not any(th.is_alive() for th in pool)
    info = cache_info()
    assert info.walk_hits + info.walk_misses == threads * lookups
    assert engine._WALK_BYTES == sum(n for _, n in engine._WALK.values())
    for t, snaps in enumerate(got):
        assert snaps == want[t % 2:] + want[:t % 2]
    clear_cache()


# -- static loads: slices and broadcasts, or the gather (machine.load_shape) --

@pytest.mark.parametrize("batch", [None, 16])
@pytest.mark.parametrize("case", sorted(LOAD_CASES))
def test_static_load_lowering_matches_interpreter(case, batch):
    """Each address pattern, alone and in a batch of 16 images, gives
    the interpreter's memory, registers and Tag bit for bit, lowered
    the way ``cache_info()`` counts it."""
    program, want = LOAD_CASES[case]
    mems = load_memories(seed=len(case), count=batch or 1)
    clear_cache()
    if batch is None:
        _assert_equivalent(program, mems[0])
    else:
        cp = compile_program(program, CFG, mode="fused")
        got_mem, got_regs, got_tag = cp.run_batch(np.stack(mems))
        for i, mem in enumerate(mems):
            mem_i, st_i = ORACLE.run_stepwise(program, mem)
            np.testing.assert_array_equal(np.asarray(mem_i),
                                          np.asarray(got_mem[i]))
            for r in st_i.regs:
                np.testing.assert_array_equal(np.asarray(st_i.regs[r]),
                                              np.asarray(got_regs[r][i]))
            np.testing.assert_array_equal(np.asarray(st_i.tag),
                                          np.asarray(got_tag[i]))
    info = cache_info()
    assert (info.loads_sliced, info.loads_gathered) == want


def test_load_counters_count_each_program_once():
    """A program's loads count once, however many executables trace
    it, and ``clear_cache()`` zeroes the counters."""
    program, _ = LOAD_CASES["box_3d"]
    clear_cache()
    cp = compile_program(program, CFG, mode="fused")
    mem = load_memories(seed=0, count=2)
    cp.run(mem[0])
    cp.run_batch(np.stack(mem))
    assert (cache_info().loads_sliced, cache_info().loads_gathered) == (1, 0)
    compile_program(program, CFG, mode="vm").run(mem[0])
    assert cache_info().loads_sliced == 1        # the VM lowers nothing
    clear_cache()
    assert (cache_info().loads_sliced, cache_info().loads_gathered) == (0, 0)
