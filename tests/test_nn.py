"""repro.nn unit tests: composite-op accuracy, per-block oracle
contracts, target/optimizer portability, workload assembly, and the
lazy-import satellite on :mod:`repro.kernels`.

The conformance suite (``tests/test_conformance.py``) pushes random
block shapes through the full executor equivalence class; this file
pins the *numeric* contracts — the exp/recip error bounds docs/MODELS.md
documents, bit-exactness of the integer blocks, and the rtol bound of
the softmax block — plus the subsystem surface (``model_blocks``,
scheduler submission, bench section wiring).
"""
import subprocess
import sys

import numpy as np
import pytest

from repro import opt, targets
from repro.core import MVEConfig
from repro.core.isa import DType
from repro.frontend import BCAST, SEQ, KernelBuilder
from repro.nn import (ATTN_RTOL, BLOCK_KERNELS, MULTIDIM_BLOCKS,
                      model_blocks, ops)

CFG = MVEConfig()


# ---------------------------------------------------------------------------
# Composite ops: the three ISA gaps, measured against numpy.
# ---------------------------------------------------------------------------

def _run_unary(build, xs):
    """Trace ``y = build(b, x_vec)`` over a 1-D input and execute."""
    xs = np.asarray(xs, np.float32)
    b = KernelBuilder("unary")
    xo = b.input("x", (len(xs),), DType.F, init=xs)
    yo = b.output("y", (len(xs),), DType.F)
    b.width(32)
    with b.dims(len(xs)):
        yo.store(build(b, xo.load(SEQ)), SEQ)
    k = b.build()
    mem, _ = k.compile().run(k.pack())
    return k.unpack(np.asarray(mem))["y"]


def test_exp_approx_accuracy():
    """Relative error < 1e-5 over the whole post-max-subtract domain
    (docs/MODELS.md promises ~3e-6; assert with margin but tighter than
    the attention block's rtol)."""
    xs = np.linspace(-60.0, 0.0, 2048).astype(np.float32)
    got = _run_unary(lambda b, v: ops.exp_approx(b, v), xs)
    want = np.exp(xs.astype(np.float64))
    rel = np.abs(got - want) / want
    assert float(rel.max()) < 1e-5
    # exp(0) == 1 exactly: the online-softmax running sum relies on the
    # current chunk's max contributing exactly 1.0
    assert _run_unary(lambda b, v: ops.exp_approx(b, v), [0.0])[0] == 1.0


def test_exp_approx_clamps_underflow():
    got = _run_unary(lambda b, v: ops.exp_approx(b, v), [-1e4, -500.0])
    want = np.exp(-60.0)
    assert np.all(got > 0.0) and np.allclose(got, want, rtol=1e-5)


def test_recip_approx_accuracy():
    """1/s to ~fp32 precision over [1, max_val] — softmax denominators."""
    xs = np.concatenate([np.linspace(1.0, 64.0, 1024),
                         [1.0, 2.0, 63.999, 64.0]]).astype(np.float32)
    got = _run_unary(lambda b, v: ops.recip_approx(b, v, max_val=64.0), xs)
    rel = np.abs(got * xs.astype(np.float64) - 1.0)
    assert float(rel.max()) < 1e-6


@pytest.mark.parametrize("op,npop", [("add", None), ("max", np.max),
                                     ("min", np.min)])
def test_tree_reduce_dim0(op, npop):
    """Cross-dimension reduction matches numpy (add: in the pairwise
    tree order ``tree_sum_ref`` mirrors — bit-exact, not approximate)."""
    from repro.kernels.ref import tree_sum_ref

    rows, n = 8, 32
    rng = np.random.default_rng(0)
    x = rng.standard_normal((rows, n)).astype(np.float32)
    b = KernelBuilder("reduce")
    xo = b.inout("x", (rows, n), DType.F, init=x)
    ro = b.scratch("r", (rows, n), DType.F)
    yo = b.output("y", (rows,), DType.F)
    b.width(32)
    ops.tree_reduce_dim0(b, xo, ro, n, rows, op=op)
    b.dims(rows, ld_strides={0: n})
    yo.store(ro.at(0, 0).load(ops.CR), SEQ)
    k = b.build()
    mem, _ = k.compile().run(k.pack())
    got = k.unpack(np.asarray(mem))["y"]
    if op == "add":
        np.testing.assert_array_equal(got, np.asarray(tree_sum_ref(x)))
    else:
        np.testing.assert_array_equal(got, npop(x, axis=1))


def test_tree_reduce_rejects_non_pow2():
    b = KernelBuilder("bad")
    xo = b.scratch("x", (4, 6), DType.F)
    b.width(32)
    with pytest.raises(ValueError):
        ops.tree_reduce_dim0(b, xo, xo, 6, 4)


# ---------------------------------------------------------------------------
# Block kernels: oracle contracts + register budget.
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name", sorted(BLOCK_KERNELS))
def test_block_oracle(name):
    """Default-shape build passes its jnp-oracle check and fits the
    8-register file at width 32."""
    run = BLOCK_KERNELS[name]()
    assert run.kernel.n_regs <= 8
    mem, state = run.kernel.compile().run(run.memory)
    run.check(np.asarray(mem), state)


def test_attention_error_within_documented_bound():
    run = BLOCK_KERNELS["attn_tile"]()
    mem, _ = run.kernel.compile().run(run.memory)
    assert run.error_of(np.asarray(mem)) < ATTN_RTOL


@pytest.mark.parametrize("name", sorted(BLOCK_KERNELS))
def test_block_every_target_and_opt_level(name):
    """Each block compiles and runs bit-identically on every registered
    target (including the ``*-timed`` twins) and at max opt level."""
    run = BLOCK_KERNELS[name]()
    base, _ = run.kernel.compile().run(run.memory)
    base = np.asarray(base)
    for tname in targets.list_targets():
        mem, _ = run.kernel.compile(target=tname).run(run.memory)
        np.testing.assert_array_equal(np.asarray(mem), base,
                                      err_msg=f"{name} on {tname}")
    mem, _ = run.kernel.compile(opt_level=opt.MAX_OPT_LEVEL).run(run.memory)
    np.testing.assert_array_equal(np.asarray(mem), base)


def test_blocks_through_scheduler():
    """Zoo kernels submit directly to the serving scheduler and come
    back oracle-correct (the serving_lm bench path)."""
    from repro.runtime.scheduler import MVEScheduler

    runs = [BLOCK_KERNELS[n](seed=7) for n in ("kv_gather", "moe_gather",
                                               "ssm_scan")]
    sched = MVEScheduler(CFG, promote_after=1)
    tickets = [sched.submit(r.kernel) for r in runs]
    sched.drain()
    for r, t in zip(runs, tickets):
        r.check(np.asarray(t.result().memory), t.result())


# ---------------------------------------------------------------------------
# Workload assembly + bench section.
# ---------------------------------------------------------------------------

def test_model_blocks_assembly():
    specs = model_blocks(quick=True)
    names = [s.name for s in specs]
    assert len(names) == len(set(names)) and len(specs) >= 6
    assert set(MULTIDIM_BLOCKS) <= {s.run.name for s in specs}
    for s in specs:
        assert s.tiles_per_layer >= 1.0
        mem, state = s.run.kernel.compile().run(s.run.memory)
        s.run.check(np.asarray(mem), state)
    # the multidim flag drives the bench's Fig-10-style assertion
    assert [s.name for s in specs if s.multidim] == list(MULTIDIM_BLOCKS)


def test_models_bench_quick_rows():
    from benchmarks.models_bench import models_bench

    rows = {name: derived for name, _, derived
            in models_bench(only_targets=("mve-bs", "rvv-1d"), quick=True)}
    summary = rows["models/summary"]
    assert "mve_ahead_on_multidim=True" in summary
    assert "models/attn_tile/mve-bs" in rows
    assert "models/block_mix_autotune" in rows
    # per-block oracle rows carry the exactness contract
    assert "exactness=bit" in rows["models/qkv_gemm/oracle"]
    assert "exactness=rtol" in rows["models/attn_tile/oracle"]


def test_autotune_programs_deterministic():
    from repro.silicon.autotune import Candidate, autotune_programs

    runs = [BLOCK_KERNELS[n]() for n in ("kv_gather", "ssm_scan")]
    mix = [(r.name, r.kernel, float(i + 1)) for i, r in enumerate(runs)]
    cands = [Candidate(scheme=s) for s in ("bs", "bp")]
    a = autotune_programs("mix", mix, candidates=cands)
    b = autotune_programs("mix", mix, candidates=cands)
    assert [p.label for p in a.points] == [p.label for p in b.points]
    assert a.best("energy_pj").energy_pj == b.best("energy_pj").energy_pj
    assert len(a.points) == 2 and len(a.front) >= 1


# ---------------------------------------------------------------------------
# Satellite: repro.kernels imports lazily (PEP 562).
# ---------------------------------------------------------------------------

def test_kernels_package_lazy_import():
    """Importing the package (or just ``ref``) must not drag in the
    Pallas TPU kernel modules."""
    code = (
        "import sys; import repro.kernels as kp; from repro.kernels "
        "import ref; assert 'repro.kernels.ref' in sys.modules; "
        "assert 'repro.kernels.ops' not in sys.modules; "
        "assert 'repro.kernels.flash_attention' not in sys.modules; "
        "assert hasattr(ref, 'tree_sum_ref'); "
        "assert 'ops' in dir(kp)"
    )
    subprocess.run([sys.executable, "-c", code], check=True)
