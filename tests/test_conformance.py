"""Cross-executor differential fuzzer: interp == fused == VM == scheduler.

Extends the random-program strategy of ``tests/test_vm.py`` with the
addressing features that suite leaves out, and adds the fourth executor —
the signature-batched scheduler (:mod:`repro.runtime.scheduler`) — to the
equivalence contract:

* **CB-masked stores**: dimension-mask bits dropped around stores, so the
  blend and sorted-unique scatter paths run partially masked (the mask
  expands to control-block masks, Section V-B);
* **random-base gathers** (Eq. 1): ``vrld`` walks pointer arrays placed
  in memory, so addresses are data-dependent in every executor;
* **random-base scatters**: ``vrst`` stores through per-row pointers;
* **saturating narrow-int reads**: B/W loads from a "wild" region holding
  huge/negative/fractional floats, which must clamp identically in the
  eager casts, the VM's clamp-then-convert, and the vmapped batch.

Every seeded program is executed on several memory variants; each variant
must come back bit-identical (memory, registers, Tag) from all four
executors, with the stepwise interpreter as the oracle.  The scheduler is
exercised through both tiers: the vmapped VM batch (``promote_after=None``)
and the fused batch (``promote_after=1``).

The optimizer (:mod:`repro.opt`) is part of the same equivalence class:
every random program and random frontend kernel is additionally pushed
through each pipeline prefix, and the optimized text must reproduce the
*unoptimized* oracle bit for bit (docs/OPTIMIZER.md) — an optimizer bug
surfaces here as a conformance failure, not a silent miscompile.

The ``*-timed`` pipeline-model targets (:mod:`repro.timing`,
docs/TIMING.md) join the class with an *envelope* property: for every
random program and random frontend kernel, the pipeline model's cycles
must lie within ``[ideal-issue lower bound, fully-serialized upper
bound]`` recomputed from the same TimedOp stream — and timed execution
stays bit-exact vs. the stepwise oracle (the timing layer must never
touch functional semantics).
"""
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st
from _load_cases import LOAD_CASES, load_memories

from repro import opt, targets
from repro.core import MVEConfig, MVEInterpreter, compile_program, isa, rvv
from repro.core.isa import DType, Op
from repro.core.patterns import PATTERNS, RVV_COMPARISON_SET
from repro.frontend import BCAST, DERIVED, SEQ, KernelBuilder
from repro.runtime.scheduler import MVEScheduler

CFG = MVEConfig()
ORACLE = MVEInterpreter(CFG, compiled=False)

# memory map of the fuzzed image
_MEM = 4096
_IN = 0            # [0, 1024): small non-negative ints (safe for any dtype)
_WILD = 1024       # [1024, 1536): huge/negative/fractional floats
_PTR = 1536        # [1536, 2560): pointer arrays for random-base accesses
_OUT = 3072        # [3072, 4096): store targets
_INT_DT = [DType.B, DType.W, DType.DW, DType.QW]


def _random_program_ex(seed, variants=3):
    """One random program + ``variants`` memory images it must serve
    identically.  Pointer arrays are identical across variants (they are
    addressing state); input and wild values differ per variant."""
    rng = np.random.default_rng(seed)
    mems = [np.zeros(_MEM) for _ in range(variants)]
    for v, mem in enumerate(mems):
        vr = np.random.default_rng((seed, v))
        mem[_IN:_IN + 1024] = vr.integers(0, 100, size=1024)
        wild = vr.uniform(-1e6, 1e6, size=512)
        wild[::7] = vr.integers(-300, 70000, size=len(wild[::7]))
        mem[_WILD:_WILD + 512] = np.round(wild, 2)
    prog = [isa.vsetwidth(32)]
    stored = {}                       # reg -> "int" | "float"
    lens = []
    ptr_cursor = _PTR
    masked_now = []

    def set_dims():
        nonlocal lens
        nd = int(rng.integers(1, 3))
        lens = [int(rng.integers(2, 17)) for _ in range(nd)]
        prog.append(isa.vsetdimc(nd))
        for d, ln in enumerate(lens):
            prog.append(isa.vsetdiml(d, ln))

    def total():
        return int(np.prod(lens))

    def inner():
        return int(np.prod(lens[:-1]))

    def int_reg():
        cands = [r for r, k in stored.items() if k == "int"]
        return int(rng.choice(cands)) if cands else None

    def any_reg():
        return int(rng.choice(list(stored))) if stored else None

    def alloc_ptrs(targets):
        """Write a pointer array (same in every variant) into the pointer
        region; returns its base or None when the region is full."""
        nonlocal ptr_cursor
        if ptr_cursor + len(targets) > _OUT - 512:
            return None
        base = ptr_cursor
        ptr_cursor += len(targets)
        for mem in mems:
            mem[base:base + len(targets)] = targets
        return base

    def mask_store_window():
        """CB-masked store coverage: drop a few top-dim elements."""
        idxs = sorted(rng.choice(min(lens[-1], 256),
                                 size=int(rng.integers(1, 3)),
                                 replace=False))
        for i in idxs:
            prog.append(isa.vunsetmask(int(i)))
        masked_now.extend(int(i) for i in idxs)

    def maybe_unmask():
        while masked_now and rng.random() < 0.7:
            prog.append(isa.vsetmask(masked_now.pop()))

    set_dims()
    for _ in range(int(rng.integers(12, 32))):
        c = int(rng.integers(0, 14))
        rd = int(rng.integers(0, 7))
        if c == 0:
            set_dims()
            masked_now.clear()        # fresh dims, fresh mask relevance
        elif c == 1:                                # strided load
            if rng.random() < 0.5:                  # saturating narrow read
                dt = _INT_DT[int(rng.integers(0, 2))]
                base = int(rng.integers(_WILD, max(_WILD + 512 - total(),
                                                   _WILD + 1)))
            else:
                dt = [DType.DW, DType.QW, DType.F,
                      DType.HF][int(rng.integers(0, 4))]
                base = int(rng.integers(0, max(2048 - total(), 1)))
            prog.append(isa.vsld(dt, rd, base,
                                 *([1] + [2] * (len(lens) - 1))))
            stored[rd] = "float" if dt.is_float else "int"
        elif c == 2:                                # random-base gather
            top = lens[-1]
            targets = rng.integers(0, max(768 - inner(), 1), size=top)
            base = alloc_ptrs(targets)
            if base is None:
                continue
            dt = [DType.B, DType.W, DType.F][int(rng.integers(0, 3))]
            prog.append(isa.vrld(dt, rd, base,
                                 *([1] + [2] * (len(lens) - 2))))
            stored[rd] = "float" if dt.is_float else "int"
        elif c == 3:                                # store (maybe CB-masked)
            src = any_reg()
            if src is None:
                continue
            if rng.random() < 0.5:
                mask_store_window()
            dt = DType.F if stored[src] == "float" else DType.DW
            if rng.random() < 0.3:                  # strided -> scatter path
                prog.append(isa.vsetststr(0, 2))
                base = int(rng.integers(_OUT, _MEM - 2 * total()))
                prog.append(isa.vsst(dt, src, base,
                                     *([3] + [2] * (len(lens) - 1))))
            else:
                base = int(rng.integers(_OUT, _MEM - total()))
                prog.append(isa.vsst(dt, src, base,
                                     *([1] + [2] * (len(lens) - 1))))
            maybe_unmask()
        elif c == 4:                                # random-base scatter
            src = any_reg()
            if src is None:
                continue
            top = lens[-1]
            stride = max(inner(), 1)
            if _OUT + top * stride >= _MEM:
                continue
            targets = _OUT + rng.permutation(top) * stride
            base = alloc_ptrs(targets)
            if base is None:
                continue
            if rng.random() < 0.4:
                mask_store_window()
            dt = DType.F if stored[src] == "float" else DType.DW
            prog.append(isa.vrst(dt, src, base,
                                 *([1] + [2] * (len(lens) - 2))))
            maybe_unmask()
        elif c == 5:                                # setdup
            if rng.random() < 0.5:
                prog.append(isa.vsetdup(DType.DW, rd,
                                        int(rng.integers(-50, 50))))
                stored[rd] = "int"
            else:
                prog.append(isa.vsetdup(
                    DType.F, rd, float(np.round(rng.normal(), 3))))
                stored[rd] = "float"
        elif c == 6:                                # narrow int binop
            a, b = int_reg(), int_reg()
            if a is None or b is None:
                continue
            dt = _INT_DT[int(rng.integers(0, 4))]
            op = [isa.vadd, isa.vsub, isa.vmul, isa.vmin, isa.vmax,
                  isa.vxor, isa.vand, isa.vor][int(rng.integers(0, 8))]
            prog.append(op(dt, rd, a, b))
            stored[rd] = "int"
        elif c == 7:                                # 32-bit op, any sources
            a, b = any_reg(), any_reg()
            if a is None or b is None:
                continue
            dt = DType.DW if rng.random() < 0.5 else DType.F
            op = [isa.vadd, isa.vsub, isa.vmul, isa.vmin,
                  isa.vmax][int(rng.integers(0, 5))]
            prog.append(op(dt, rd, a, b,
                           predicated=bool(rng.random() < 0.25)))
            stored[rd] = "float" if dt.is_float else "int"
        elif c == 8:                                # compare (writes Tag)
            a, b = any_reg(), any_reg()
            if a is None or b is None:
                continue
            dt = DType.F if (stored[a] == "float" or stored[b] == "float") \
                else DType.DW
            cmp = [Op.GT, Op.GTE, Op.LT, Op.LTE, Op.EQ,
                   Op.NEQ][int(rng.integers(0, 6))]
            prog.append(isa.vcmp(cmp, dt, a, b))
        elif c == 9:                                # shift immediate
            a = int_reg()
            if a is None:
                continue
            dt = _INT_DT[int(rng.integers(0, 4))]
            prog.append(isa.vshi(dt, rd, a, int(rng.integers(-3, 4))))
            stored[rd] = "int"
        elif c == 10:                               # rotate
            a = int_reg()
            if a is None:
                continue
            dt = _INT_DT[int(rng.integers(0, 3))]
            prog.append(isa.Instr(Op.ROTI, dtype=dt, vd=rd, vs1=a,
                                  imm=int(rng.integers(1, dt.bits))))
            stored[rd] = "int"
        elif c == 11:                               # dim-mask toggles
            idx = int(rng.integers(0, min(lens[-1], 256)))
            prog.append(isa.vunsetmask(idx) if rng.random() < 0.5
                        else isa.vsetmask(idx))
        else:                                       # cvt / cpy
            a = any_reg()
            if a is None:
                continue
            dt = [DType.F, DType.HF, DType.DW, DType.W,
                  DType.B][int(rng.integers(0, 5))]
            prog.append(isa.vcvt(dt, rd, a))
            stored[rd] = "float" if dt.is_float else "int"
    # observable tail store
    src = any_reg()
    if src is not None:
        dt = DType.F if stored[src] == "float" else DType.DW
        prog.append(isa.vsst(dt, src, _OUT,
                             *([1] + [2] * (len(lens) - 1))))
    return prog, mems


def _assert_result_equal(st_i, mem_i, res):
    np.testing.assert_array_equal(np.asarray(mem_i), np.asarray(res.memory))
    assert set(st_i.regs) == set(res.regs)
    for r in st_i.regs:
        np.testing.assert_array_equal(np.asarray(st_i.regs[r]),
                                      np.asarray(res.regs[r]))
    np.testing.assert_array_equal(np.asarray(st_i.tag),
                                  np.asarray(res.tag))


_TIMED_TARGETS = ("mve-bs-timed", "mve-bp-timed", "mve-bh-timed",
                  "mve-ac-timed", "rvv-1d-timed", "neon-timed")


def _check_timed_envelope(prog, mem, oracle=None,
                          target_names=_TIMED_TARGETS):
    """Bit-exactness + the timing envelope contract for timed targets.

    The executed trace is priced through the pipeline model; its total
    must sit inside the ``[lower_bound, upper_bound]`` bracket, which is
    re-derived here from the same TimedOp stream via
    :func:`repro.timing.envelope` (not trusted from the timeline)."""
    from repro import timing

    mem_i, st_i = oracle if oracle is not None \
        else ORACLE.run_stepwise(prog, mem)
    for tname in target_names:
        art = targets.compile(prog, target=tname)
        mem_t, st_t = art.run(mem)
        _assert_result_equal(st_i, mem_i, st_t)          # semantics intact
        tl = art.timeline(st_t)                          # exact trace
        ops, _ = art.target.timed_ops(art.program, art.cfg, st_t.trace)
        lo, hi = timing.envelope(ops, art.target.uarch)
        assert (lo, hi) == (tl.lower_bound, tl.upper_bound), tname
        assert lo - 1e-6 <= tl.total_cycles <= hi + 1e-6, \
            f"{tname}: {tl.total_cycles} outside envelope [{lo}, {hi}]"
        assert {"dependency", "structural",
                "memory-port", "frontend"} <= set(tl.stalls), tname


def _check_all_executors(prog, mems):
    """interp == VM == fused (per image) and == scheduler (batched, both
    tiers), bit for bit."""
    oracle = [ORACLE.run_stepwise(prog, m) for m in mems]
    for mode in ("vm", "fused"):
        cp = compile_program(prog, CFG, mode=mode)
        assert cp.mode == mode
        for (mem_i, st_i), m in zip(oracle, mems):
            mem_e, st_e = cp.run(m)
            _assert_result_equal(st_i, mem_i, st_e)
    # scheduler: same program over all variants coalesces into one
    # vmapped dispatch per tier
    for sched in (MVEScheduler(CFG, promote_after=None),     # VM tier
                  MVEScheduler(CFG, promote_after=1)):       # fused tier
        tickets = [sched.submit(prog, m) for m in mems]
        sched.drain()
        for (mem_i, st_i), t in zip(oracle, tickets):
            _assert_result_equal(st_i, mem_i, t.result())
        assert sched.stats.dispatches < max(len(mems), 2), \
            "variants of one program must share a batched dispatch"
    # the fifth member of the equivalence class: the optimizer — every
    # pipeline prefix of this program must reproduce the same oracle
    # (VM executor; the full pipeline additionally on fused)
    for prefix in opt.pipeline_prefixes():
        full = len(prefix) == len(opt.DEFAULT_PIPELINE)
        opt.verify_optimized(prog, list(mems), passes=prefix, cfg=CFG,
                             modes=("vm", "fused") if full else ("vm",),
                             oracle=oracle)
    # the sixth member: pipeline-model pricing — bit-exact execution,
    # cycles inside the analytic envelope (one aligned-dependence and
    # one synthesized-dependence timed target; the full timed matrix is
    # swept by test_timed_targets_envelope_*)
    _check_timed_envelope(prog, mems[0], oracle=oracle[0],
                          target_names=("mve-bs-timed", "rvv-1d-timed"))


@pytest.mark.parametrize("seed", range(8))
def test_conformance_random_programs(seed):
    """Seeded differential fuzz across all four executors."""
    prog, mems = _random_program_ex(seed)
    _check_all_executors(prog, mems)


@settings(max_examples=8, deadline=None)
@given(st.integers(0, 10**9))
def test_conformance_random_programs_property(seed):
    """Hypothesis-driven version (skips when hypothesis is absent)."""
    prog, mems = _random_program_ex(seed, variants=2)
    _check_all_executors(prog, mems)


# ---------------------------------------------------------------------------
# Deterministic coverage the fuzzer cannot guarantee per-seed.
# ---------------------------------------------------------------------------

def test_cb_masked_store_all_executors():
    """Both store layouts (blend + sorted-unique scatter) under dropped
    dimension-mask bits."""
    mem = np.zeros(_MEM)
    mem[:64] = np.arange(64)
    prog = [isa.vsetwidth(32),
            isa.vsetdimc(2), isa.vsetdiml(0, 8), isa.vsetdiml(1, 8),
            isa.vsld(DType.F, 0, 0, 1, 2),
            isa.vunsetmask(2), isa.vunsetmask(5),
            isa.vsst(DType.F, 0, _OUT, 1, 2),          # masked blend
            isa.vsetststr(0, 2),
            isa.vsst(DType.F, 0, _OUT + 256, 3, 2),    # masked scatter
            isa.vsetmask(2)]
    _check_all_executors(prog, [mem])


@pytest.mark.parametrize("case", sorted(LOAD_CASES))
def test_static_load_classes_all_executors(case):
    """One static load of each address pattern the fused tier tells
    apart (a box read by slices and broadcasts, or the clipped gather),
    over three images, through every executor."""
    program, _ = LOAD_CASES[case]
    _check_all_executors(program, load_memories(seed=len(case), count=3))


def test_random_base_gather_batched_pointer_tables():
    """Random-base pointers are data: the same upsample program with
    *different* shuffled row-pointer tables must batch correctly."""
    runs = [PATTERNS["upsample"](seed=s) for s in (0, 7, 11)]
    assert all(r.program == runs[0].program for r in runs)
    for sched in (MVEScheduler(CFG, promote_after=None),
                  MVEScheduler(CFG, promote_after=1)):
        tickets = [sched.submit(r.program, r.memory) for r in runs]
        sched.drain()
        for r, t in zip(runs, tickets):
            res = t.result()
            assert res.batch_size == len(runs)
            r.check(np.asarray(res.memory), res)


def test_scheduler_mixed_stream_matches_engine():
    """A mixed-signature stream (incl. data-dependent spmm programs)
    served batched == per-request engine runs."""
    reqs = []
    for name, seeds in (("daxpy", (0, 1, 2)), ("spmm", (3, 4)),
                        ("xor_cipher", (0, 5))):
        reqs += [PATTERNS[name](seed=s) for s in seeds]
    sched = MVEScheduler(CFG, promote_after=2)
    tickets = [sched.submit(r.program, r.memory) for r in reqs]
    sched.drain()
    for r, t in zip(reqs, tickets):
        res = t.result()
        mem_e, st_e = compile_program(r.program, CFG).run(r.memory)
        np.testing.assert_array_equal(np.asarray(mem_e),
                                      np.asarray(res.memory))
        r.check(np.asarray(res.memory), res)
    st = sched.stats
    assert st.requests == len(reqs)
    assert st.dispatches < len(reqs)          # batching actually happened
    assert st.batch_efficiency > 1.0


def test_scheduler_background_mode():
    """Async serving: tickets resolve without an explicit drain()."""
    runs = [PATTERNS["daxpy"](seed=s) for s in range(3)]
    with MVEScheduler(CFG, background=True, max_wait_ms=20.0,
                      promote_after=None) as sched:
        tickets = [sched.submit(r.program, r.memory) for r in runs]
        for r, t in zip(runs, tickets):
            res = t.result(timeout=120)
            r.check(np.asarray(res.memory), res)
    assert sched.stats.requests == 3
    with pytest.raises(RuntimeError):
        sched.submit(runs[0].program, runs[0].memory)


def test_cross_target_random_programs():
    """The fuzzer's random programs are also bit-exact across every
    registered target (the targets all execute the shared functional
    engine; docs/TARGETS.md)."""
    for seed in range(3):
        prog, mems = _random_program_ex(seed, variants=1)
        mem_i, st_i = ORACLE.run_stepwise(prog, mems[0])
        for tname in targets.list_targets():
            art = targets.compile(prog, target=tname)
            mem_t, st_t = art.run(mems[0])
            _assert_result_equal(st_i, mem_i, st_t)
        # ...and so is the fully-optimized text, on every target
        opt.verify_across_targets(prog, mems[0], level=opt.MAX_OPT_LEVEL)


# ---------------------------------------------------------------------------
# Cross-target conformance: the RVV path is the same access, sliced —
# bit-exactness across mve-*, rvv-1d and the interp oracle is a tested
# invariant, per pattern and per random frontend kernel.
# ---------------------------------------------------------------------------

_IN_CACHE_TARGETS = ("mve-bs", "mve-bp", "mve-bh", "mve-ac", "rvv-1d")


@pytest.mark.parametrize("name", RVV_COMPARISON_SET)
def test_cross_target_rvv_comparison_set(name):
    run = PATTERNS[name]()
    mem_i, st_i = ORACLE.run_stepwise(run.program, run.memory)
    for tname in _IN_CACHE_TARGETS:
        art = targets.compile(run.program, target=tname)
        mem_t, st_t = art.run(run.memory)
        _assert_result_equal(st_i, mem_i, st_t)
        run.check(np.asarray(mem_t), st_t)


def _random_frontend_kernel(seed: int):
    """A small random @mve.kernel-style build: random dimensionality,
    random stride-mode mix, a few arithmetic ops, masked stores."""
    rng = np.random.default_rng(seed)
    nd = int(rng.integers(1, 4))
    lens = [int(rng.integers(2, 9)) for _ in range(nd)]
    total = int(np.prod(lens))
    b = KernelBuilder(f"fuzz_{seed}")
    x = b.input("x", (total,), DType.F,
                init=rng.standard_normal(total).astype(np.float32))
    y = b.inout("y", (total,), DType.F,
                init=rng.standard_normal(total).astype(np.float32))
    out = b.output("out", (total,), DType.F)
    b.width(32)
    dense = (SEQ,) + (DERIVED,) * (nd - 1)
    with b.dims(*lens):
        vx = x.load(*dense)
        vy = y.load(*dense)
        if nd > 1 and rng.random() < 0.5:
            # replicate x along the top dimension (stride-0 broadcast)
            vx = x.load(*((SEQ,) + (DERIVED,) * (nd - 2) + (BCAST,)))
        acc = vx * vy
        for _ in range(int(rng.integers(1, 4))):
            op = rng.choice(["add", "mul", "min", "max"])
            operand = [vx, vy, acc][int(rng.integers(0, 3))]
            if op == "add":
                acc = acc + operand
            elif op == "mul":
                acc = acc * float(np.round(rng.normal(), 2))
            elif op == "min":
                acc = acc.min(operand)
            else:
                acc = acc.max(operand)
        if lens[-1] > 2 and rng.random() < 0.5:
            with b.masked_off(int(rng.integers(0, lens[-1]))):
                out.store(acc, *dense)
        else:
            out.store(acc, *dense)
    return b.build()


@pytest.mark.parametrize("seed", range(6))
def test_cross_target_random_frontend_kernels(seed):
    k = _random_frontend_kernel(seed)
    mem0 = k.pack()
    mem_i, st_i = ORACLE.run_stepwise(k.program, mem0)
    for tname in _IN_CACHE_TARGETS:
        art = targets.compile(k, target=tname)
        mem_t, st_t = art.run(mem0)
        _assert_result_equal(st_i, mem_i, st_t)
    # frontend kernels go through every optimizer pipeline prefix too
    opt.verify_prefixes(k.program, mem0, cfg=CFG, modes=("vm",))
    # ...and through the pipeline-model envelope contract
    _check_timed_envelope(k.program, mem0, oracle=(mem_i, st_i),
                          target_names=("mve-bs-timed", "rvv-1d-timed"))


# ---------------------------------------------------------------------------
# repro.nn model blocks join the equivalence class (docs/MODELS.md):
# random-shape instances of every zoo family through interp == fused ==
# VM == scheduler == every opt-pipeline prefix == timed envelope.
# ---------------------------------------------------------------------------

def _random_nn_block(seed: int):
    """A randomly-shaped instance of one zoo block family (family cycles
    with the seed so six seeds cover all six)."""
    from repro import nn

    rng = np.random.default_rng(seed)
    family = seed % 6
    if family == 0:
        window = int(2 ** rng.integers(2, 4))
        return nn.kv_gather(window=window, n_kv=int(rng.integers(1, 4)),
                            head_dim=int(2 ** rng.integers(1, 4)),
                            max_seq=2 * window,
                            pos0=int(rng.integers(0, window)), seed=seed)
    if family == 1:
        window = int(2 ** rng.integers(2, 4))
        return nn.kv_scatter(window=window, n_kv=int(rng.integers(1, 4)),
                             head_dim=int(2 ** rng.integers(1, 4)),
                             max_seq=2 * window,
                             pos0=int(rng.integers(0, window)), seed=seed)
    if family == 2:
        chunk = int(2 ** rng.integers(1, 3))
        return nn.attn_tile(tq=int(2 ** rng.integers(2, 4)),
                            tk=chunk * int(rng.integers(1, 3)),
                            d=int(2 ** rng.integers(1, 3)),
                            chunk=chunk, seed=seed)
    if family == 3:
        return nn.gemm_tile(n=int(2 ** rng.integers(2, 5)),
                            kdim=int(rng.integers(2, 5)),
                            m=int(2 ** rng.integers(2, 5)), seed=seed)
    if family == 4:
        return nn.ssm_scan(n_state=int(2 ** rng.integers(2, 4)),
                           d_inner=int(2 ** rng.integers(2, 5)), seed=seed)
    return nn.moe_gather(tokens=int(2 ** rng.integers(2, 5)),
                         d_expert=int(2 ** rng.integers(2, 4)),
                         n_experts=int(2 ** rng.integers(1, 4)),
                         topk=int(rng.integers(1, 4)), seed=seed)


@pytest.mark.parametrize("seed", range(6))
def test_conformance_random_nn_blocks(seed):
    """Every zoo family, random shapes, full equivalence class — plus
    the block's own jnp-oracle check on the oracle executor's result."""
    run = _random_nn_block(seed)
    mem0 = run.kernel.pack()
    mem_i, st_i = ORACLE.run_stepwise(run.kernel.program, mem0)
    run.check(np.asarray(mem_i), st_i)
    _check_all_executors(run.kernel.program, [mem0])


@pytest.mark.parametrize("seed", range(4))
def test_timed_targets_envelope_random_programs(seed):
    """The full timed matrix: every timed target executes the fuzzer's
    random programs bit-exactly and prices them inside the envelope."""
    prog, mems = _random_program_ex(seed, variants=1)
    _check_timed_envelope(prog, mems[0])


@pytest.mark.parametrize("seed", range(3))
def test_timed_targets_envelope_random_frontend_kernels(seed):
    k = _random_frontend_kernel(seed)
    _check_timed_envelope(k.program, k.pack())


# ---------------------------------------------------------------------------
# The Section III-C segment-count formula, as a property of the lowered
# RVV trace:  #segments = ceil(active_lanes / len(inner 1D segment)).
# ---------------------------------------------------------------------------

def _check_segment_formula(seed: int):
    rng = np.random.default_rng(seed)
    nd = int(rng.integers(1, 5))
    lens = [int(rng.integers(1, 17)) for _ in range(nd)]
    modes = [int(rng.integers(0, 4)) for _ in range(nd)]
    prog = [isa.vsetwidth(32), isa.vsetdimc(nd)]
    for d, ln in enumerate(lens):
        prog.append(isa.vsetdiml(d, ln))
        prog.append(isa.vsetldstr(d, int(rng.integers(0, 64))))
    prog.append(isa.vsld(DType.F, 0, 0, *modes))
    trace, stats = rvv.compile_to_rvv(prog, CFG)

    loads = [ev for ev in trace if ev.op is Op.SLD]
    diml_cfg = [ev for ev in trace if ev.op is Op.SET_DIML]
    active = min(int(np.prod(lens)), CFG.lanes)
    assert len(loads) >= 1
    inner = loads[0].contiguous_run
    assert all(ev.contiguous_run == inner for ev in loads)
    # the paper's decomposition count, recomputed from the trace alone
    assert len(loads) == -(-active // inner)
    # one vsetvl/predicate config precedes every partial access (the
    # program's own nd vsetdiml config writes pass through 1:1 on top)
    assert len(diml_cfg) == len(loads) + nd
    assert stats.mask_instructions == len(loads)
    # and the recorded per-access log agrees with the emitted trace
    assert stats.segment_log == [(len(loads), inner, active)]
    assert stats.memory_instructions == len(loads)
    assert stats.move_instructions == len(loads)


@pytest.mark.parametrize("seed", range(12))
def test_rvv_segment_count_formula_seeded(seed):
    _check_segment_formula(seed)


@settings(max_examples=25, deadline=None)
@given(st.integers(0, 10**9))
def test_rvv_segment_count_formula_property(seed):
    """Hypothesis-driven version (skips when hypothesis is absent)."""
    _check_segment_formula(seed)


def test_scheduler_nonfloat_memory_routes_fused():
    """Non-float32-canonical images keep exact integer semantics through
    the scheduler (the VM rejects them; the fused path serves them)."""
    mem = np.zeros(256, dtype=np.int32)
    mem[:8] = (1 << 24) + 1
    prog = [isa.vsetdimc(1), isa.vsetdiml(0, 8),
            isa.vsld(DType.DW, 0, 0, 1),
            isa.vsst(DType.DW, 0, 16, 1)]
    mem_i, st_i = ORACLE.run_stepwise(prog, mem)
    sched = MVEScheduler(CFG, promote_after=None)
    tickets = [sched.submit(prog, mem) for _ in range(2)]
    sched.drain()
    for t in tickets:
        res = t.result()
        assert np.asarray(res.memory).dtype == np.int32
        # fused-routed despite promotion being off: the full fused batch
        # cap applies and the dispatch is accounted to the fused tier
        assert res.tier == "fused" and res.batch_size == 2
        _assert_result_equal(st_i, mem_i, res)
    assert sched.stats.fused_batches == 1
    assert sched.stats.vm_batches == 0
