"""LFM2 decode steps as chained MVE tile programs (``repro.nn.lfm2``)
against the plain reference (``repro.nn.lfm2_ref``), at the
``lfm2-8b-a1b-smoke`` widths (hidden 96, 6 heads over 2 KV heads of 16,
dense FFN 160, 8 experts of 24, top 4; layers: conv with the dense FFN,
attention and conv with experts).  The hidden and FFN widths are not
powers of two, so the contraction padding and the partial attention
split are exercised.

Tolerances, each a relative L2 error (``lfm2_ref.compare``, over every
layer's output, the appended keys and values, the convolution states, the
final norm and the logits):

* ``TOL`` = 1e-4 for the runner against the reference.  The tiles compose
  ``exp``, ``1/x`` and ``1/sqrt(x)`` to about 1e-7..1e-6 relative and sum
  in another order than jnp; the largest reading at these seeds is
  4e-7.  One int8 activation rounded the other way would move a layer's
  output by about 1/(127 * sqrt(96)) = 8e-4, and bfloat16 in the norms or
  the attention reads 1e-2 and more: both fail it.
* 1e-3 for ``quant_step_error`` and ``scale_error`` when the reference is
  forced with the served int8 activations and scales: a served
  activation may lie past half a step from the reference's value, and a
  served scale differ from the reference's, only by float error.
"""
import dataclasses
import hashlib

import jax
import numpy as np
import pytest
from _load_cases import lowered_loads

from repro.configs import ARCH_IDS, get_config
from repro.core import MVEConfig
from repro.core.engine import cache_info, clear_cache, compile_program
from repro.core.interp import MVEInterpreter
from repro.nn import lfm2, lfm2_ref, qwen2
from repro.runtime.scheduler import MVEScheduler

TOL = 1e-4
CFG = get_config("lfm2-8b-a1b", reduced=True)
SH = lfm2.Shapes.of(CFG, split=8, max_splits=4, vocab=CFG.vocab_size)
KINDS = lfm2.layer_kinds(CFG.layer_types, CFG.num_dense_layers, range(3))


@pytest.fixture(scope="module")
def params():
    return lfm2.init_params(SH, KINDS, seed=11)


@pytest.fixture(scope="module")
def sched():
    s = MVEScheduler(MVEConfig(), background=True, promote_after=2)
    yield s
    s.close()


def _rows(ref, t):
    return {"layers": [x[t] for x in ref["layers"]],
            "k": [x[t] for x in ref["k"]], "v": [x[t] for x in ref["v"]],
            "state": [x[t] for x in ref["state"]],
            "final": ref["final"][t], "logits": ref["logits"][t]}


def _held(dec):
    """Every image the decoder holds on the device, copied to the host."""
    return [np.asarray(img) for img in jax.tree_util.tree_leaves(
        (dec._held, dec._head))]


def test_decode_from_empty_caches_matches_causal_forward(params, sched):
    """Twelve tokens decoded one by one from empty caches and zero
    convolution states agree at every position with the reference's
    causal full forward (12 positions over splits of 8: the last split is
    partial from position 8 on); the held images never change."""
    dec = lfm2.Lfm2Decoder(SH, params, sched)
    held = _held(dec)
    sess = dec.new_session()
    tokens = np.random.default_rng(1).integers(0, SH.vocab, 12)
    outs = [dec.step(sess, t) for t in tokens]
    ref = lfm2_ref.forward(params, tokens, SH.dims())
    errs = [lfm2_ref.compare(o.as_ref(), _rows(ref, t))
            for t, o in enumerate(outs)]
    assert max(errs) < TOL, errs
    assert sess.length == 12 and dec.stats.steps == 12
    # conv + dense (8 stages), attention + MoE (11), conv + MoE (9),
    # the final norm and the head, a step
    assert dec.stats.stages == 12 * (8 + 11 + 9 + 2)
    assert dec.stats.routes == 12 * 2
    for a, b in zip(held, _held(dec)):
        np.testing.assert_array_equal(a, b)
    # forced with the served int8 activations and ids (the benchmark's
    # check), from the states the previous step produced
    for i in (5, 11):
        out = outs[i]
        k, v = sess.history(0, out.pos)
        want = lfm2_ref.decode_step(params, out.token, out.pos, [k], [v],
                                    outs[i - 1].state, SH.dims(),
                                    forced=out.quant, route_margin=1e-4)
        assert lfm2_ref.compare(out.as_ref(), want) < TOL
        assert want["quant_step_error"] < 1e-3
        assert want["scale_error"] < 1e-3
        assert want["route_mismatches"] == 0


def test_bf16_control_fails_tolerance(params):
    """The reference with its norms and attention in bfloat16 in the
    program's place is rejected by ``TOL`` at every position."""
    tokens = np.random.default_rng(1).integers(0, SH.vocab, 6)
    ref = lfm2_ref.forward(params, tokens, SH.dims())
    ctl = lfm2_ref.forward(params, tokens, SH.dims(), bf16=True)
    errs = [lfm2_ref.compare(_rows(ctl, t), _rows(ref, t))
            for t in range(6)]
    assert min(errs) > 10 * TOL, errs


@pytest.mark.parametrize("kind", lfm2.KINDS)
def test_tile_bit_identical_across_tiers(kind):
    """Each tile kind: the stepwise interpreter (the ISA oracle), the VM
    and the fused tier give the same memory image bit for bit, within the
    8-register file."""
    t = lfm2.tiles(SH)[kind]
    assert t.kernel.n_regs <= 8
    mem = lfm2.example_images(SH, seed=5)[kind]
    want, _ = MVEInterpreter(MVEConfig(), compiled=False).run_stepwise(
        list(t.program), mem)
    want = np.asarray(want)
    for mode in ("vm", "fused"):
        got, _ = compile_program(t.program, mode=mode).run(mem)
        np.testing.assert_array_equal(np.asarray(got), want, err_msg=mode)


def _route(x, w, ws, sx, ebias):
    """The route tile's answer for an int8 input ``x`` and router."""
    t = lfm2.tiles(SH)["route"]
    img = t.zeros.copy()
    img[t.region("x")][:SH.d] = x
    img[t.region("w")].reshape(-1, SH.experts)[:SH.d] = w
    img[t.region("ws")] = ws
    img[t.region("sx")] = sx
    img[t.region("ebias")] = ebias
    img[t.region("iota")] = np.arange(SH.experts)
    mem, _ = compile_program(t.program, mode="fused").run(img)
    return (t.read(mem, "ids").astype(int), t.read(mem, "wts"))


def test_route_ties_break_to_the_lower_index():
    """A zero router gives every expert the score sigmoid(0) = 1/2; with
    five experts' bias tied at the top the picks are those of
    ``lax.top_k``: the lower indices, in order, and equal weights."""
    ebias = np.float32([0.0, 0.3, 0.1, 0.3, 0.3, -0.2, 0.3, 0.3])
    ids, wts = _route(np.ones(SH.d), np.zeros((SH.d, SH.experts)),
                      np.full(SH.experts, 0.01), 0.01, ebias)
    want = np.asarray(jax.lax.top_k(np.float32(0.5) + ebias, SH.topk)[1])
    np.testing.assert_array_equal(ids, want)
    assert ids.tolist() == [1, 3, 4, 6]          # five tied, four picked
    np.testing.assert_allclose(wts, 0.25, rtol=1e-6)


@pytest.mark.parametrize("seed", range(4))
def test_route_matches_top_k_on_biased_scores(seed):
    """Seeded int8 router inputs: the ids are ``lax.top_k`` of the
    float64 sigmoid scores plus the bias, and the weights their
    normalised scores, to about float32 rounding."""
    rng = np.random.default_rng(seed)
    x = rng.integers(-127, 128, SH.d)
    w = rng.integers(-127, 128, (SH.d, SH.experts))
    ws = rng.uniform(1e-3, 2e-3, SH.experts).astype(np.float32)
    sx = np.float32(rng.uniform(1e-3, 2e-3))
    ebias = (0.1 * rng.standard_normal(SH.experts)).astype(np.float32)
    ids, wts = _route(x, w, ws, sx, ebias)
    logits = ((x @ w).astype(np.float32) * ws) * sx
    s = 1.0 / (1.0 + np.exp(-logits.astype(np.float64)))
    biased = s + ebias
    order = np.argsort(-biased, kind="stable")
    assert biased[order[SH.topk - 1]] - biased[order[SH.topk]] > 1e-4
    np.testing.assert_array_equal(
        ids, np.asarray(jax.lax.top_k(biased, SH.topk)[1]))
    np.testing.assert_allclose(wts, s[ids] / s[ids].sum(), rtol=1e-5)


def test_step_after_warm_up_compiles_nothing(params):
    """Once every tile kind is promoted and its fused executables warmed
    (as the benchmark's set-up does), the decoder's own warm-up leaves
    nothing for a step to compile."""
    compiles = []
    armed = [False]

    def listen(event, duration, **kw):
        if armed[0] and event == "/jax/core/compile/backend_compile_duration":
            compiles.append(kw.get("fun_name"))

    jax.monitoring.register_event_duration_secs_listener(listen)
    s = MVEScheduler(MVEConfig(), background=True, promote_after=2,
                     max_batch=2)
    try:
        images = lfm2.example_images(SH, seed=3)
        for kind, t in lfm2.tiles(SH).items():
            for _ in range(2):
                s.submit(t.program, images[kind]).result(timeout=300)
            fused = compile_program(t.program, s.cfg, mode="fused")
            fused.run(images[kind])
            fused.run_batch(np.stack([images[kind]] * 2))
        dec = lfm2.Lfm2Decoder(SH, params, s)
        sess = dec.new_session()
        armed[0] = True
        dec.step(sess, 3)
        armed[0] = False
    finally:
        s.close()
    assert compiles == []


def test_tile_grid_at_published_widths():
    """At the published widths GEMV tiles of 128 output channels cover
    every projection exactly, and a step's 1 192 GEMV tiles hold 704 of
    the routed experts (docs/MODELS.md)."""
    cfg = get_config("lfm2-8b-a1b")
    full = lfm2.Shapes.of(cfg, split=1024, max_splits=8, vocab=8192)
    assert full.mt == 128 and full.group == 4 and full.capacity == 8192
    grid = full.grid()
    n = {k: g[2] for k, g in grid.items()}
    assert n == {"in_proj": 48, "out_proj": 16, "q": 16, "k": 4, "v": 4,
                 "o": 16, "gate": 56, "up": 56, "down": 16,
                 "expert_gate": 14, "expert_up": 14, "expert_down": 16,
                 "head": 64}
    for k, m, tiles in grid.values():
        assert tiles * full.mt == m
    kinds = lfm2.layer_kinds(cfg.layer_types, cfg.num_dense_layers,
                             [0, 2, 3, 4, 5])
    assert kinds == [("conv", True), ("full_attention", False),
                     ("conv", False), ("conv", False), ("conv", False)]
    expert = 4 * full.topk * (n["expert_gate"] + n["expert_up"]
                              + n["expert_down"])
    other = (4 * (n["in_proj"] + n["out_proj"])
             + n["q"] + n["k"] + n["v"] + n["o"]
             + n["gate"] + n["up"] + n["down"] + n["head"])
    assert (expert, expert + other) == (704, 1192)
    assert len(cfg.layer_types) == 24 and cfg.layer_types.count("conv") == 18
    assert "lfm2-8b-a1b" not in ARCH_IDS       # not built by repro.models


@pytest.mark.parametrize("field,value", [
    ("router", "softmax"), ("expert_bias", False),
    ("norm_topk_prob", False), ("qk_norm", False)])
def test_shapes_refuse_what_the_tiles_do_not_compute(field, value):
    """Each routing and norm setting of the configuration is checked: the
    tiles compute sigmoid scores, a picking bias, normalised weights and
    QK-norm, and nothing else is served silently."""
    cfg = dataclasses.replace(CFG, **{field: value})
    with pytest.raises(ValueError, match=field):
        lfm2.Shapes.of(cfg, split=8, max_splits=4, vocab=CFG.vocab_size)


#: sha256 of each Qwen2 tile program's text (``repr`` of its
#: instructions) at the published widths (splits of 512, 16 a merge) and
#: at the smoke widths (splits of 8, 4 a merge): the programs, and so
#: their executables, names and persistent-cache entries, are those the
#: shared tile code has always built.
QWEN2_PROGRAMS = {
    (False, "norm"): "03b4398af095378a86c478bc27ca4b7e"
                     "a7f5636d038c43373414b7dfda61fa9b",
    (False, "gemv_d"): "82b634a07cf241fae7fd456200b7678a"
                       "f3870f6eae4be5a6f5a413dddb57b39b",
    (False, "rope"): "260863459e2029650e969c8449785c11"
                     "86795c4915e4306f771654e1e53439db",
    (False, "attn"): "4b029c9301d35c0c83655c50f03e108f"
                     "df8a770bb02d97cbabcbbcb4eb3bf89b",
    (False, "merge"): "e05cc3952bb0eaa36a51d264760bcb37"
                      "d33693136080f704b745335d0e861124",
    (False, "silu_mul"): "7e42cf5c67790a6b8138b67eb490c24d"
                         "8bc2317aed69d0c0ec4d18367db3461f",
    (False, "gemv_ff"): "a112f18e018b9d4da991040c4eff8d1b"
                        "57641bd095eb00cb1d78dbe872a7ca39",
    (True, "norm"): "847ad5ef2125957b63156ae63b5a9422"
                    "ce507c111fd1de8bc139f2455371c774",
    (True, "gemv_d"): "24259517d5bf177767d3ec37b5238b9a"
                      "ae7deaff5f266bb24c27898355a77ea9",
    (True, "rope"): "b8994a368698abb89aaba8f52170e76b"
                    "493000cce14ed78b7869d01fcbaa361a",
    (True, "attn"): "969ac371ef40905e9bc08d511b7e1419"
                    "6f43adcbc55756edfedcc9189c25c9d0",
    (True, "merge"): "dfe77a706bf9170615588debad8078d9"
                     "aa6b1867fdb3c0e15fd833b371745916",
    (True, "silu_mul"): "97013e4d4af95487976f4b0f28c64234"
                        "d38c74b169298bf1b20c68b0ea255515",
    (True, "gemv_ff"): "0e2203402dc26dbb2930fa288d4d1901"
                       "2994b6091e46277ffe07d2d524e02e51",
}


@pytest.mark.parametrize("smoke", [False, True])
def test_qwen2_programs_unchanged(smoke):
    split, ms = (8, 4) if smoke else (512, 16)
    sh = qwen2.Shapes.of(get_config("qwen2-0.5b", reduced=smoke), split, ms)
    for kind, t in qwen2.tiles(sh).items():
        got = hashlib.sha256(repr(t.program).encode()).hexdigest()
        assert got == QWEN2_PROGRAMS[(smoke, kind)], kind


class _Drained:
    """A ticket whose wait first serves every pending request on the
    calling thread."""

    def __init__(self, sched, ticket):
        self.sched, self.ticket = sched, ticket

    def result(self, timeout=None):
        self.sched.drain()
        return self.ticket.result(timeout)


def _qwen2_runner_record(steps: int = 4):
    """What the Qwen2 runner does over ``steps`` smoke decode steps: each
    submit (its program's hash, whether its image is on the device, the
    image's shape), each copy it counts, its stage and tile counters and
    the scheduler's copy counts.  The scheduler serves on the runner's
    thread (each wait drains what is pending first), so a stage's tiles
    batch by submission order alone, not by a worker's timing."""
    sh = qwen2.Shapes.of(get_config("qwen2-0.5b", reduced=True), split=8,
                         max_splits=4)
    s = MVEScheduler(MVEConfig(), promote_after=2)
    log = []
    submit, count = s.submit, s.count_copies

    def logged_submit(program, image, *a, **k):
        log.append(("submit",
                    hashlib.sha256(repr(program).encode()).hexdigest()[:12],
                    isinstance(image, jax.Array), tuple(np.shape(image))))
        return _Drained(s, submit(program, image, *a, **k))

    def logged_count(*a, **k):
        log.append(("copies", a, tuple(sorted(k.items()))))
        return count(*a, **k)

    s.submit, s.count_copies = logged_submit, logged_count
    try:
        dec = qwen2.Qwen2Decoder(sh, qwen2.init_params(sh, 2, seed=11), s)
        sess = dec.new_session()
        h = np.random.default_rng(1).standard_normal((steps, sh.d))
        for t in range(steps):
            dec.step(sess, h[t].astype(np.float32))
        st = s.stats
        return (hashlib.sha256(repr(log).encode()).hexdigest(),
                (dec.stats.tiles, dec.stats.stages, st.h2d_bytes,
                 st.d2h_bytes, st.resident_requests))
    finally:
        s.close()


def test_qwen2_runner_stages_and_copies_unchanged():
    """The Qwen2 runner, on the stage code it shares with LFM2, submits
    the same tiles in the same order with the same images, counts the
    same copies and moves the same bytes as before the code was shared
    (the digest and counts were read from the runner when its stage code
    lived in ``nn/qwen2.py``)."""
    digest, counts = _qwen2_runner_record()
    assert counts == (428, 84, 3291832, 4237184, 376)
    assert digest == QWEN2_RUNNER_LOG


#: sha256 of :func:`_qwen2_runner_record`'s log.
QWEN2_RUNNER_LOG = ("7e1463e685fbfdc6467cefc722d093dc"
                    "6355d5dfc426b9bd1b6a92ba55009889")


@pytest.mark.parametrize("kind", lfm2.KINDS)
def test_tile_loads_lower_as_slices(kind):
    """Every static load of every tile kind is a box the fused tier reads
    by slices and broadcasts, with no gather (``engine.cache_info()``);
    ``clear_cache()`` zeroes the counts."""
    t = lfm2.tiles(SH)[kind]
    sliced, gathered, loads = lowered_loads(t.program, t.zeros.size)
    assert (sliced, gathered) == (loads, 0) and loads > 0
    clear_cache()
    assert (cache_info().loads_sliced, cache_info().loads_gathered) == (0, 0)


def test_published_gemv_loads_lower_as_slices():
    """The published ``gemv_d`` tile's 80 static loads: 47 runs of ``w``
    and partial sums, 33 broadcasts of ``x`` and its scale."""
    full = lfm2.Shapes.of(get_config("lfm2-8b-a1b"), split=1024,
                          max_splits=8, vocab=8192)
    t = lfm2.tiles(full)["gemv_d"]
    assert lowered_loads(t.program, t.zeros.size) == (80, 0, 80)
    clear_cache()
