"""Qwen2 decode steps as chained MVE tile programs (``repro.nn.qwen2``)
against the plain reference (``repro.nn.qwen2_ref``), at the
``qwen2-0.5b-smoke`` widths (hidden 56, 7 heads over 1 KV head of 8,
d_ff 96): every width but the head size is not a power of two, so the
contraction padding and the partial attention split are exercised.

Tolerances, each a relative L2 error (``qwen2_ref.compare``, over every
layer's output, the appended keys and values and the final norm):

* ``TOL`` = 1e-4 for the runner against the reference.  The tiles compose
  ``exp``, ``1/x`` and ``1/sqrt(x)`` to about 1e-7..1e-6 relative and sum
  in another order than jnp; the largest reading at these seeds is 3e-7.
  One int8 activation rounded the other way would move a layer's output
  by about 1/(127 * sqrt(56)) = 1e-3, and bfloat16 in the norms or the
  attention reads 1e-2 and more: both fail it.
* 1e-3 for ``quant_step_error`` and ``scale_error`` when the reference is
  forced with the served int8 activations and scales (the benchmark's
  model check): a served activation may lie past half a step from the
  reference's value, and a served scale differ from the reference's,
  only by float error, 127 x ~1e-6 and ~1e-6 (largest reading 2.7e-7 at
  these widths).
"""
import threading

import numpy as np
import pytest

from repro.configs import get_config
from repro.core import MVEConfig
from repro.core.engine import compile_program
from repro.core.interp import MVEInterpreter
from repro.core.isa import DType
from repro.frontend import SEQ, KernelBuilder
from repro.nn import ops, qwen2, qwen2_ref
from repro.runtime.scheduler import MVEScheduler

TOL = 1e-4
SH = qwen2.Shapes.of(get_config("qwen2-0.5b", reduced=True), split=8,
                     max_splits=4)
LAYERS = 2


@pytest.fixture(scope="module")
def params():
    return qwen2.init_params(SH, LAYERS, seed=11)


@pytest.fixture(scope="module")
def sched():
    s = MVEScheduler(MVEConfig(), background=True, promote_after=2)
    yield s
    s.close()


def _rows(ref, t):
    return {"layers": [x[t] for x in ref["layers"]],
            "k": [x[t] for x in ref["k"]], "v": [x[t] for x in ref["v"]],
            "final": ref["final"][t]}


def test_prompt_decode_matches_causal_forward(params, sched):
    """A 12-token prompt decoded token by token through the scheduler
    agrees at every position with the reference's causal full forward
    (12 positions: two whole splits of 8 would be 16, so the last split
    is partial from position 8 on)."""
    dec = qwen2.Qwen2Decoder(SH, params, sched)
    sess = dec.new_session()
    h = np.random.default_rng(1).standard_normal((12, SH.d)).astype(
        np.float32)
    outs = [dec.step(sess, h[t]) for t in range(12)]
    ref = qwen2_ref.forward(params, h, SH.dims())
    errs = [qwen2_ref.compare(o.as_ref(), _rows(ref, t))
            for t, o in enumerate(outs)]
    assert max(errs) < TOL, errs
    assert sess.length == 12 and dec.stats.steps == 12
    # 2 layers x (norm, qkv, rope, attn, merge, o, norm, gate_up,
    # silu_mul, down) + the final norm, a step
    assert dec.stats.stages == 12 * (10 * LAYERS + 1)


def test_decode_from_prefilled_cache(params, sched):
    """Two sessions decoding at once from prefilled caches (19 positions:
    two full splits and a partial third) agree with the reference's
    decode step over the same history."""
    dec = qwen2.Qwen2Decoder(SH, params, sched)
    rng = np.random.default_rng(2)
    sessions, outs = [], {}
    for s in range(2):
        sess = dec.new_session()
        sess.k[:, :, :19] = rng.standard_normal(sess.k[:, :, :19].shape)
        sess.v[:, :, :19] = rng.standard_normal(sess.v[:, :, :19].shape)
        sess.length = 19
        sessions.append(sess)
    h = rng.standard_normal((2, 3, SH.d)).astype(np.float32)

    def client(s):
        outs[s] = [dec.step(sessions[s], h[s, i]) for i in range(3)]

    threads = [threading.Thread(target=client, args=(s,)) for s in range(2)]
    for th in threads:
        th.start()
    for th in threads:
        th.join(timeout=300)
        assert not th.is_alive()
    assert dec.stats.steps == 6 and len(outs) == 2
    for s, sess in enumerate(sessions):
        for i, out in enumerate(outs[s]):
            assert out.pos == 19 + i
            hist = [sess.history(li, out.pos) for li in range(LAYERS)]
            args = (params, h[s, i], out.pos, [x[0] for x in hist],
                    [x[1] for x in hist], SH.dims())
            want = qwen2_ref.decode_step(*args)
            assert qwen2_ref.compare(out.as_ref(), want) < TOL
            # forced with the served int8 activations (the benchmark's
            # check): each is a rounding of the reference's own value
            forced = qwen2_ref.decode_step(*args, forced=out.quant)
            assert forced["quant_step_error"] < 1e-3
            assert forced["scale_error"] < 1e-3
            assert qwen2_ref.compare(out.as_ref(), forced) < TOL


def test_bf16_control_fails_tolerance(params):
    """The reference with its norms and attention rounded to bfloat16 in
    the program's place is rejected by ``TOL`` at every position."""
    h = np.random.default_rng(1).standard_normal((12, SH.d)).astype(
        np.float32)
    ref = qwen2_ref.forward(params, h, SH.dims())
    ctl = qwen2_ref.forward(params, h, SH.dims(), bf16=True)
    errs = [qwen2_ref.compare(_rows(ctl, t), _rows(ref, t))
            for t in range(12)]
    assert min(errs) > 10 * TOL, errs


@pytest.mark.parametrize("kind", qwen2.KINDS)
def test_tile_bit_identical_across_tiers(kind):
    """Each tile kind: the stepwise interpreter, the VM and the fused
    tier give the same memory image bit for bit, within the 8-register
    file."""
    t = qwen2.tiles(SH)[kind]
    assert t.kernel.n_regs <= 8
    mem = qwen2.example_images(SH, seed=5)[kind]
    want, _ = MVEInterpreter(MVEConfig(), compiled=False).run_stepwise(
        list(t.program), mem)
    want = np.asarray(want)
    for mode in ("vm", "fused"):
        got, _ = compile_program(t.program, mode=mode).run(mem)
        np.testing.assert_array_equal(np.asarray(got), want, err_msg=mode)


def test_tile_grid_covers_every_weight_once():
    """At the published widths the GEMV tiles of 128 output channels
    cover q, k, v, o, gate, up and down exactly (docs/MODELS.md)."""
    full = qwen2.Shapes.of(get_config("qwen2-0.5b"), split=512,
                           max_splits=16)
    grid = full.grid()
    assert full.mt == 128
    assert {n: g[2] for n, g in grid.items()} == {
        "q": 7, "k": 1, "v": 1, "o": 7, "gate": 38, "up": 38, "down": 7}
    for k, m, n in grid.values():
        assert n * full.mt == m and full.kg(k) * (k // full.kg(k)) == k
    assert full.eps == 1e-6 and full.group == 7


def test_program_text_does_not_depend_on_data():
    """Tiles are built from widths alone; fused executables are named by
    kind, programs without a name keep the plain module name."""
    a, b = qwen2.tiles(SH), qwen2.tiles(qwen2.Shapes.of(
        get_config("qwen2-0.5b", reduced=True), split=8, max_splits=4))
    assert all(a[k].program == b[k].program for k in qwen2.KINDS)
    cp = compile_program(a["attn"].program, mode="fused")
    assert cp._fused_fn.__name__ == "_execute.dec_attn"
    plain = compile_program(a["attn"].program[:40], mode="fused")
    assert plain._fused_fn.__name__ == "_execute"


def _unary(build, xs):
    xs = np.asarray(xs, np.float32)
    b = KernelBuilder("unary")
    xo = b.input("x", (len(xs),), DType.F, init=xs)
    yo = b.output("y", (len(xs),), DType.F)
    b.width(32)
    with b.dims(len(xs)):
        yo.store(build(b, xo.load(SEQ)), SEQ)
    k = b.build()
    mem, _ = k.compile().run(k.pack())
    return k.unpack(np.asarray(mem))["y"].astype(np.float64)


def test_wide_reciprocal_and_rsqrt_accuracy():
    """1/x and 1/sqrt(x) to about fp32 rounding (measured 1.4e-7) over
    [2**-62, 2**62]: the quantisation scale and RMSNorm's range."""
    xs = np.float32(2.0) ** np.linspace(-62, 62, 2001).astype(np.float32)
    x64 = xs.astype(np.float64)
    got = _unary(lambda b, v: ops.recip_wide(b, v), xs)
    assert np.max(np.abs(got * x64 - 1.0)) < 1e-6
    got = _unary(lambda b, v: ops.rsqrt_wide(b, v), xs)
    assert np.max(np.abs(got * np.sqrt(x64) - 1.0)) < 1e-6


def test_sigmoid_accuracy_over_the_real_line():
    """Relative error under 2e-6 over [-60, 60] (measured 5.1e-7); below
    -60 the exp clamp leaves an absolute error under 1e-26."""
    xs = np.linspace(-60.0, 60.0, 2001).astype(np.float32)
    got = _unary(lambda b, v: ops.sigmoid_approx(b, v), xs)
    want = 1.0 / (1.0 + np.exp(-xs.astype(np.float64)))
    assert np.max(np.abs(got - want) / want) < 2e-6
    tails = _unary(lambda b, v: ops.sigmoid_approx(b, v), [-1e4, 1e4])
    assert tails[0] < 1e-26 and abs(tails[1] - 1.0) < 1e-7


def test_round_even_ties():
    xs = [0.5, 1.5, 2.5, -1.5, -2.5, 126.6, -127.4, 3.49999]
    got = _unary(lambda b, v: ops.round_even(b, v), xs)
    np.testing.assert_array_equal(got, np.round(np.float32(xs)))
