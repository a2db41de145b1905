"""Qwen2 decode steps served as chained MVE tile programs.

A decode step of one session (one token through the layers and the final
RMSNorm) becomes a chain of *stages*; each stage submits its tiles through
:meth:`repro.runtime.scheduler.MVEScheduler.submit`, waits for every
answer, and writes the outputs into the next stage's memory images.  The
per-session KV cache lives here, on the host; each session's GEMV tile
images, weights in, live on the device (:class:`Session`).  The plain
reference is
:mod:`repro.nn.qwen2_ref`; docs/MODELS.md ("Qwen2-0.5B on the serving
path") has the tile grid.

Stages of a layer (residual adds ride in the next norm):

1. ``norm``: ``h += delta``; RMSNorm; per-token int8 quantisation;
2. ``qkv``: int8 GEMV tiles of q, k and v, with bias;
3. ``rope``: RoPE on q and k; the runner appends k and v to the cache;
4. ``attn``: split-K GQA attention, one tile per (KV head, split of
   ``split`` positions): the partial max, sum and output of the KV head's
   query heads;
5. ``merge``: the splits combined, normalised and quantised;
6. ``o``: the o_proj GEMV tiles;
7. ``norm`` again (the attention residual), 8. ``gate_up``,
9. ``silu_mul``: SiLU(gate) * up, quantised; 10. ``down``.

Every tile kind has one program whose text depends only on the model's
widths, never on weights, activations or the position: a partial last
split is masked by a bias row, unused splits by their data, and padded
contraction rows are zero.  So every tile of a kind shares one
executable, and the scheduler batches tiles of all sessions together.
Each kind's fused executables are named ``jit__execute.dec_<kind>``
(:func:`repro.core.engine.name_program`).

GEMV tiles (``gemv_d`` over the hidden width, ``gemv_ff`` over the
intermediate) hold the whole contraction ``K`` and
``mt`` output channels: lanes ``(mt, kg)``; lane ``(m, g)`` accumulates
the rows ``k = i * kg + g`` in int32, a tree over ``g`` sums them, and
the result dequantises as ``(acc * s_w) * s_x + b``.  Memory words are
float32, so a partial sum beyond 2**24 in magnitude would round where it
is stored; int8 x int8 over 4 864 rows stays below that for any but
adversarial data.
"""
from __future__ import annotations

import dataclasses
import functools
import math
import threading
import time
from typing import Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..core.engine import name_program
from ..core.isa import DType
from ..core.tracing import NN_LAYER, NN_STAGE, NN_STEP, span
from ..frontend import BCAST, CR, DERIVED, SEQ, KernelBuilder
from .ops import (exp_approx, recip_approx, recip_wide, round_even,
                  rsqrt_wide, sigmoid_approx, tree_reduce_dim0)
from . import qwen2_ref

#: Score bias of a masked position: far below any live score, so its
#: ``exp`` is :func:`~repro.nn.ops.exp_approx`'s floor exp(-60) times a
#: zero value row; a merge's unused split carries it as its max.
MASKED = -30000.0

#: How long a stage waits for a tile's answer before the step fails (the
#: scheduler resolves every ticket; this bounds a stuck device).
STAGE_TIMEOUT_S = 600.0


def _pow2_floor(x: int) -> int:
    return 1 << (int(x).bit_length() - 1)


def _pow2_ceil(x: int) -> int:
    return 1 << max(0, int(x) - 1).bit_length()


@dataclasses.dataclass(frozen=True)
class Shapes:
    """The widths a decode step is built from (``repro.configs``)."""

    d: int                 # hidden
    heads: int
    kv_heads: int
    head_dim: int
    ff: int                # SwiGLU intermediate
    eps: float
    theta: float
    split: int             # positions per attention split tile
    max_splits: int        # splits a merge tile combines (cache capacity)
    lanes: int = 8192

    @classmethod
    def of(cls, cfg, split: int, max_splits: int, lanes: int = 8192
           ) -> "Shapes":
        return cls(d=cfg.d_model, heads=cfg.num_heads,
                   kv_heads=cfg.num_kv_heads,
                   head_dim=cfg.resolved_head_dim, ff=cfg.d_ff,
                   eps=float(cfg.norm_eps), theta=float(cfg.rope_theta),
                   split=int(split), max_splits=int(max_splits),
                   lanes=int(lanes))

    @property
    def group(self) -> int:
        return self.heads // self.kv_heads

    @property
    def capacity(self) -> int:
        return self.split * self.max_splits

    @property
    def mt(self) -> int:
        """Output channels of a GEMV tile: the largest power of two (at
        most 128) dividing every projection's output width, so the tiles
        cover each weight matrix exactly."""
        g = math.gcd(math.gcd(self.heads * self.head_dim,
                              self.kv_heads * self.head_dim),
                     math.gcd(self.ff, self.d))
        return min(128, g & -g)

    def kg(self, k: int) -> int:
        """Lanes along the contraction of a GEMV tile over ``k`` rows."""
        return min(self.lanes // self.mt, _pow2_floor(k))

    def grid(self) -> Dict[str, Tuple[int, int, int]]:
        """Per projection: (contraction, output width, GEMV tiles a
        step), the tiles covering the weight matrix exactly once."""
        hd = self.head_dim
        shapes = {"q": (self.d, self.heads * hd),
                  "k": (self.d, self.kv_heads * hd),
                  "v": (self.d, self.kv_heads * hd),
                  "o": (self.heads * hd, self.d),
                  "gate": (self.d, self.ff), "up": (self.d, self.ff),
                  "down": (self.ff, self.d)}
        return {name: (k, m, m // self.mt) for name, (k, m) in
                shapes.items()}

    def dims(self) -> dict:
        """The widths :mod:`repro.nn.qwen2_ref` reads."""
        return {"heads": self.heads, "kv_heads": self.kv_heads,
                "head_dim": self.head_dim, "eps": self.eps,
                "theta": self.theta}


# ---------------------------------------------------------------------------
# Tile programs.
# ---------------------------------------------------------------------------

def _quantize(b: KernelBuilder, x, n: int, q, s, ab, tmp) -> None:
    """``q = round(x * 127 / max|x|)``, ``s = max|x| / 127`` over the first
    ``n`` words of ``x``; ``ab`` is a zeroed ``(1, _pow2_ceil(n))`` scratch
    and ``tmp`` one word."""
    b.dims(n)
    v = x.load(SEQ)
    ab.store(v.max(b.const(DType.F, 0.0) - v), SEQ)
    npad = _pow2_ceil(n)
    if npad > 1:
        tree_reduce_dim0(b, ab, ab, npad, 1, op="max")
    b.dims(1)
    amax = ab.load(SEQ)
    tmp.store(recip_wide(b, amax) * 127.0, SEQ)
    s.store(amax * float(np.float32(1.0 / 127.0)), SEQ)
    b.dims(n)
    q.store(round_even(b, x.load(SEQ) * tmp.load(BCAST)), SEQ)


def build_norm(sh: Shapes):
    """``h_out = h + delta``; ``x = RMSNorm(h_out) * gamma``;
    ``xq, sx = quantize(x)``."""
    d, dpad = sh.d, _pow2_ceil(sh.d)
    b = KernelBuilder("dec_norm")
    h = b.input("h", (d,))
    delta = b.input("delta", (d,))
    gamma = b.input("gamma", (d,))
    h_out = b.output("h_out", (d,))
    x = b.output("x", (d,))
    xq = b.output("xq", (d,))
    sx = b.output("sx", (1,))
    sq = b.scratch("sq", (1, dpad))
    ab = b.scratch("abs", (1, dpad))
    tmp = b.scratch("tmp", (2,))
    b.width(32)
    b.dims(d)
    hv = h.load(SEQ) + delta.load(SEQ)
    h_out.store(hv, SEQ)
    sq.store(hv * hv, SEQ)
    tree_reduce_dim0(b, sq, sq, dpad, 1, op="add")
    b.dims(1)
    ms = sq.load(SEQ) * float(np.float32(1.0 / d))
    tmp.store(rsqrt_wide(b, ms + sh.eps), SEQ)
    b.dims(d)
    xn = h_out.load(SEQ) * tmp.load(BCAST)
    xn *= gamma.load(SEQ)
    x.store(xn, SEQ)
    _quantize(b, x, d, xq, sx, ab, tmp.at(1))
    return b.build()


def build_gemv(sh: Shapes, k: int):
    """``y = (sum_k x[k] w[k, m]) * s_w[m] * s_x + bias[m]`` for one tile
    of ``mt`` output channels over the whole contraction ``k`` (int8
    values in int32 words; rows past ``k`` are zero)."""
    mt, kg = sh.mt, sh.kg(k)
    steps = -(-k // kg)
    b = KernelBuilder(f"dec_gemv_k{k}")
    x = b.input("x", (steps * kg,), DType.DW)
    w = b.input("w", (steps * kg, mt), DType.DW)
    ws = b.input("ws", (mt,))
    bias = b.input("bias", (mt,))
    sx = b.input("sx", (1,))
    y = b.output("y", (mt,))
    part = b.scratch("part", (kg, mt), DType.DW)
    b.width(32)
    b.dims(mt, kg)
    acc = b.const(DType.DW, 0)
    for i in range(steps):
        xv = x.at(i * kg).load(BCAST, SEQ)
        acc += xv * w.at(i * kg, 0).load(SEQ, DERIVED)
    part.store(acc, SEQ, DERIVED)
    n = kg
    while n > 1:
        half = n // 2
        b.dims(mt, half)
        lo = part.at(0, 0).load(SEQ, DERIVED)
        lo += part.at(half, 0).load(SEQ, DERIVED)
        part.at(0, 0).store(lo, SEQ, DERIVED)
        n = half
    b.dims(mt)
    f = part.load(SEQ).astype(DType.F)
    f *= ws.load(SEQ)
    f *= sx.load(BCAST)
    f += bias.load(SEQ)
    y.store(f, SEQ)
    return b.build()


def build_rope(sh: Shapes):
    """Rotate-half RoPE of every q and k head with one position's
    ``cos``/``sin`` rows."""
    hd, half = sh.head_dim, sh.head_dim // 2
    b = KernelBuilder("dec_rope")
    q = b.input("q", (sh.heads * hd,))
    k = b.input("k", (sh.kv_heads * hd,))
    cos = b.input("cos", (hd,))
    sin = b.input("sin", (hd,))
    q_out = b.output("q_out", (sh.heads * hd,))
    k_out = b.output("k_out", (sh.kv_heads * hd,))
    b.width(32)
    for src, dst, heads in ((q, q_out, sh.heads), (k, k_out, sh.kv_heads)):
        b.dims(half, heads, ld_strides={1: hd}, st_strides={1: hd})
        lo_in = src.at(0).load(SEQ, CR)
        hi_in = src.at(half).load(SEQ, CR)
        lo = lo_in * cos.at(0).load(SEQ, BCAST)
        lo -= hi_in * sin.at(0).load(SEQ, BCAST)
        dst.at(0).store(lo, SEQ, CR)
        hi = hi_in * cos.at(half).load(SEQ, BCAST)
        hi += lo_in * sin.at(half).load(SEQ, BCAST)
        dst.at(half).store(hi, SEQ, CR)
    return b.build()


def _pv_chunk(sh: Shapes) -> int:
    """Positions per lane group of the attention tile's P.V pass."""
    return min(sh.split, _pow2_floor(sh.lanes // (sh.head_dim * sh.group)))


def _score_slices(sh: Shapes) -> int:
    """Slices of the head dimension the attention scores sum in parallel
    lanes: as many as the lanes hold beside (positions, heads)."""
    e = _pow2_floor(max(1, sh.lanes // (sh.split * sh.group)))
    while sh.head_dim % e:
        e //= 2
    return e


def build_attn(sh: Shapes):
    """One split of one KV head's history for its ``group`` query heads:
    scores ``s[p, g] = q[g] . k[p] / sqrt(hd) + bias[p]``, the split's max
    ``m[g]``, ``l[g] = sum_p exp(s - m)`` and the unnormalised
    ``o[g] = sum_p exp(s - m) v[p]``."""
    g, hd, n = sh.group, sh.head_dim, sh.split
    pc, ed = _pv_chunk(sh), _score_slices(sh)
    b = KernelBuilder("dec_attn")
    q = b.input("q", (g, hd))
    k = b.input("k", (n, hd))
    v = b.input("v", (n, hd))
    bias = b.input("bias", (n,))
    m_out = b.output("m", (g,))
    l_out = b.output("l", (g,))
    o_out = b.output("o", (g, hd))
    sc = b.scratch("scores", (g, n))
    red = b.scratch("reduce", (g, n))
    pvs = b.scratch("pv", (g * hd, pc))
    pvr = b.scratch("pv_reduce", (g * hd, pc))
    part = b.scratch("score_parts", (ed, g, n))
    b.width(32)
    # scores, lanes (p, g, e): lane e sums the e-th of ``ed`` slices of
    # the head dimension; the slices are added after the loop
    dl = hd // ed
    b.dims(n, g, ed, ld_strides={0: hd, 1: hd, 2: dl})
    acc = b.const(DType.F, 0.0)
    for dd in range(dl):
        acc += k.at(0, dd).load(CR, BCAST, CR) * q.at(0, dd).load(BCAST, CR,
                                                                 CR)
    part.store(acc, SEQ, DERIVED, DERIVED)
    b.dims(n, g)
    acc = part.at(0, 0, 0).load(SEQ, DERIVED)
    for e in range(1, ed):
        acc += part.at(e, 0, 0).load(SEQ, DERIVED)
    acc *= float(np.float32(1.0 / np.sqrt(hd)))
    acc += bias.load(SEQ, BCAST)
    sc.store(acc, SEQ, DERIVED)
    tree_reduce_dim0(b, sc, red, n, g, op="max")
    b.dims(g, ld_strides={0: n})
    m_out.store(red.load(CR), SEQ)
    b.dims(n, g, ld_strides={1: n})
    p = exp_approx(b, acc - m_out.load(BCAST, SEQ))
    sc.store(p, SEQ, DERIVED)
    tree_reduce_dim0(b, sc, red, n, g, op="add")
    b.dims(g, ld_strides={0: n})
    l_out.store(red.load(CR), SEQ)
    # P.V, lanes (j, d, g) over chunks of pc positions
    b.dims(pc, hd, g, ld_strides={0: hd, 2: n})
    o = b.const(DType.F, 0.0)
    for c in range(n // pc):
        pv = sc.at(0, c * pc).load(SEQ, BCAST, CR)
        o += pv * v.at(c * pc, 0).load(CR, SEQ, BCAST)
    pvs.store(o, SEQ, DERIVED, DERIVED)
    tree_reduce_dim0(b, pvs, pvr, pc, g * hd, op="add")
    b.dims(g * hd, ld_strides={0: pc})
    o_out.store(pvr.load(CR), SEQ)
    return b.build()


def build_merge(sh: Shapes):
    """Combine every split of every head: ``M = max_s m``,
    ``w_s = exp(m_s - M)``, ``o = sum_s w_s o_s / sum_s w_s l_s``; then
    quantise ``o`` (all heads) for o_proj.  Unused splits carry
    ``m = MASKED`` and zero ``l`` and ``o``."""
    nh, g, hd, ns = sh.heads, sh.group, sh.head_dim, sh.max_splits
    n = nh * hd
    b = KernelBuilder("dec_merge")
    m = b.input("m", (nh, ns))
    l = b.input("l", (nh, ns))
    o = b.input("o", (nh, hd, ns))
    oq = b.output("oq", (n,))
    so = b.output("so", (1,))
    out = b.scratch("out", (n,))
    w = b.scratch("w", (nh, ns))
    red = b.scratch("reduce", (nh, ns))
    inv = b.scratch("inv", (nh,))
    part = b.scratch("part", (g * hd, ns))
    pred = b.scratch("part_reduce", (g * hd, ns))
    ab = b.scratch("abs", (1, _pow2_ceil(n)))
    tmp = b.scratch("tmp", (1,))
    b.width(32)
    tree_reduce_dim0(b, m, red, ns, nh, op="max")
    b.dims(ns, nh, ld_strides={1: ns})
    wv = exp_approx(b, m.load(SEQ, DERIVED) - red.load(BCAST, CR))
    w.store(wv, SEQ, DERIVED)
    red.store(wv * l.load(SEQ, DERIVED), SEQ, DERIVED)
    tree_reduce_dim0(b, red, red, ns, nh, op="add")
    b.dims(nh, ld_strides={0: ns})
    inv.store(recip_approx(b, red.load(CR), max_val=ns * sh.split), SEQ)
    for g0 in range(0, nh, g):
        b.dims(ns, hd, g, ld_strides={2: ns})
        t = w.at(g0, 0).load(SEQ, BCAST, CR)
        t *= o.at(g0, 0, 0).load(SEQ, DERIVED, DERIVED)
        part.store(t, SEQ, DERIVED, DERIVED)
        tree_reduce_dim0(b, part, pred, ns, g * hd, op="add")
        b.dims(hd, g, ld_strides={0: ns, 1: hd * ns})
        r = pred.load(CR, CR)
        r *= inv.at(g0).load(BCAST, SEQ)
        out.at(g0 * hd).store(r, SEQ, DERIVED)
    _quantize(b, out, n, oq, so, ab, tmp)
    return b.build()


def build_silu_mul(sh: Shapes):
    """``m = silu(gate) * up``, quantised for the down projection."""
    ff = sh.ff
    if ff > sh.lanes:
        raise ValueError(f"d_ff {ff} exceeds the {sh.lanes} lanes")
    b = KernelBuilder("dec_silu_mul")
    gate = b.input("gate", (ff,))
    up = b.input("up", (ff,))
    mq = b.output("mq", (ff,))
    sm = b.output("sm", (1,))
    mv = b.scratch("m", (ff,))
    ab = b.scratch("abs", (1, _pow2_ceil(ff)))
    tmp = b.scratch("tmp", (1,))
    b.width(32)
    b.dims(ff)
    gv = gate.load(SEQ)
    t = gv * sigmoid_approx(b, gv)
    t *= up.load(SEQ)
    mv.store(t, SEQ)
    _quantize(b, mv, ff, mq, sm, ab, tmp)
    return b.build()


def executable_name(kind: str) -> str:
    """The fused executables of a tile kind are ``jit__execute.<this>``."""
    return f"dec_{kind}"


class Tile:
    """One tile kind: its kernel, program text and a zeroed image."""

    def __init__(self, kind: str, kernel):
        self.kind = kind
        self.kernel = kernel
        self.program = tuple(kernel.program)
        self.zeros = np.zeros(kernel.plan.size, np.float32)
        name_program(self.program, executable_name(kind))

    def region(self, name: str) -> slice:
        return self.kernel.plan.region(name)

    def read(self, memory, name: str) -> np.ndarray:
        return np.array(np.asarray(memory)[self.region(name)], np.float32)

    # -- a GEMV kind's device images (Qwen2Decoder) -----------------------
    @functools.cached_property
    def write_input(self):
        """One jitted update of a tuple of this GEMV kind's device
        images: the first ``len(xq)`` words of ``x`` set to ``xq`` and
        ``sx`` to ``sx``.  Returns new images; the given ones are left as
        they are."""
        x, s = self.region("x").start, self.region("sx").start

        def write_input(images, xq, sx):
            return tuple(img.at[x:x + xq.shape[0]].set(xq).at[s:s + 1]
                         .set(sx) for img in images)
        return jax.jit(write_input)

    @functools.cached_property
    def gather_y(self):
        """One jitted gather of the ``y`` regions of a tuple of device
        answers, concatenated in order."""
        y = self.region("y")

        def gather_y(memories):
            return jnp.concatenate([m[y] for m in memories])
        return jax.jit(gather_y)


#: The tile kinds of a decode step: ``gemv_d`` contracts over the hidden
#: width (q, k, v, o, gate, up), ``gemv_ff`` over the intermediate (down).
KINDS = ("norm", "gemv_d", "rope", "attn", "merge", "silu_mul", "gemv_ff")

#: The GEMV stages of a layer, each the projections of one input vector.
GEMV_STAGES = {"qkv": ("q", "k", "v"), "o": ("o",),
               "gate_up": ("gate", "up"), "down": ("down",)}


@functools.lru_cache(maxsize=8)
def tiles(sh: Shapes) -> Dict[str, Tile]:
    """The tile kinds of a decode step at ``sh``, by kind."""
    ks = {"norm": build_norm(sh), "gemv_d": build_gemv(sh, sh.d),
          "rope": build_rope(sh), "attn": build_attn(sh),
          "merge": build_merge(sh), "silu_mul": build_silu_mul(sh),
          "gemv_ff": build_gemv(sh, sh.ff)}
    return {kind: Tile(kind, ks[kind]) for kind in KINDS}


def example_images(sh: Shapes, seed: int) -> Dict[str, np.ndarray]:
    """One seeded input image per tile kind, in its data's ranges
    (int8 values where a tile reads int8; a live split; a merge of two
    splits).  For warming executables and for tests."""
    rng = np.random.default_rng(seed)
    out = {}
    for kind, t in tiles(sh).items():
        img = t.zeros.copy()
        for name, op in t.kernel.plan.operands.items():
            if op.kind != "input":
                continue
            r = t.region(name)
            n = r.stop - r.start
            if op.dtype is DType.DW:
                img[r] = rng.integers(-127, 128, n)
            elif name in ("ws", "sx"):
                img[r] = rng.uniform(1e-3, 1e-2, n)
            else:
                img[r] = rng.standard_normal(n)
        if kind == "attn":
            bias = img[t.region("bias")]
            bias[:] = 0.0
            bias[sh.split // 2:] = MASKED
        elif kind == "merge":
            ns = sh.max_splits
            img[t.region("m")].reshape(sh.heads, ns)[:, 2:] = MASKED
            img[t.region("l")] = np.abs(img[t.region("l")]) + 1.0
        out[kind] = img
    return out


# ---------------------------------------------------------------------------
# Weights.
# ---------------------------------------------------------------------------

def _linear(rng, k: int, m: int, bias: bool) -> Dict[str, np.ndarray]:
    """Seeded float weights of variance 1/k, quantised symmetric per
    output channel to int8."""
    wf = rng.standard_normal((k, m), dtype=np.float32) / np.float32(
        np.sqrt(k))
    s = np.abs(wf).max(axis=0) / np.float32(127.0)
    w = np.clip(np.round(wf / s), -127, 127).astype(np.int8)
    b = (rng.standard_normal(m, dtype=np.float32) * np.float32(0.1)
         if bias else np.zeros(m, np.float32))
    return {"w": w, "s": s.astype(np.float32), "b": b}


def init_params(sh: Shapes, layers: int, seed: int) -> dict:
    """Seeded W8A8 parameters of ``layers`` decoder layers and the final
    norm, in :mod:`repro.nn.qwen2_ref`'s layout."""
    rng = np.random.default_rng(seed)
    d, hd = sh.d, sh.head_dim

    def gamma():
        return (1.0 + 0.1 * rng.standard_normal(d)).astype(np.float32)

    out = []
    for _ in range(layers):
        out.append({
            "ln1": gamma(),
            "q": _linear(rng, d, sh.heads * hd, True),
            "k": _linear(rng, d, sh.kv_heads * hd, True),
            "v": _linear(rng, d, sh.kv_heads * hd, True),
            "o": _linear(rng, sh.heads * hd, d, False),
            "ln2": gamma(),
            "gate": _linear(rng, d, sh.ff, False),
            "up": _linear(rng, d, sh.ff, False),
            "down": _linear(rng, sh.ff, d, False)})
    return {"layers": out, "norm": gamma()}


# ---------------------------------------------------------------------------
# The runner.
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class DecodeStats:
    """Counters of a runner since construction."""

    steps: int = 0
    tiles: int = 0               # tiles submitted
    stages: int = 0
    stage_wait_s: float = 0.0    # summed first-submit-to-last-answer


class Session:
    """One session's KV cache: per layer ``(kv_heads, capacity, hd)``
    keys and values on the host, the first ``length`` positions written;
    and its own GEMV tile images on the device (``gemv[layer][stage]``:
    a tuple of device arrays, weights in, one a tile of
    :data:`GEMV_STAGES`' projections in order).  Each step replaces a
    stage's tuple by one with that step's input written in (never
    writing into the old images), so no weight crosses to the device
    after the session is made."""

    def __init__(self, sh: Shapes, gemv: List[Dict[str, tuple]]):
        shape = (len(gemv), sh.kv_heads, sh.capacity, sh.head_dim)
        self.k = np.zeros(shape, np.float32)
        self.v = np.zeros(shape, np.float32)
        self.length = 0
        self.gemv = gemv

    def history(self, layer: int, upto: int):
        """Keys and values of positions ``[0, upto)`` as
        ``(upto, kv_heads, hd)``."""
        return (self.k[layer, :, :upto].transpose(1, 0, 2),
                self.v[layer, :, :upto].transpose(1, 0, 2))


@dataclasses.dataclass
class StepOutput:
    """What one step produced: each layer's output hidden state, the keys
    and values it appended, the final normed hidden state."""

    pos: int
    layers: List[np.ndarray]
    k: List[np.ndarray]          # (kv_heads, hd) per layer
    v: List[np.ndarray]
    final: np.ndarray
    #: per layer, the int8 activations and their scales at each
    #: quantisation point (``x``, ``o``, ``x2``, ``m``; qwen2_ref's names)
    quant: List[Dict[str, Tuple[np.ndarray, np.ndarray]]] = \
        dataclasses.field(default_factory=list)

    def as_ref(self) -> dict:
        return {"layers": self.layers, "k": self.k, "v": self.v,
                "final": self.final, "quant": self.quant}


@dataclasses.dataclass(frozen=True)
class GemvStage:
    """One GEMV stage of a layer: its tile kind, contraction, each
    projection's output width, and one host image a tile, weights in
    (what each session uploads)."""

    tile: Tile
    k: int
    widths: Tuple[int, ...]
    images: Tuple[np.ndarray, ...]


class Qwen2Decoder:
    """Decode steps of a Qwen2 stack through an :class:`MVEScheduler`.

    ``params`` are :func:`init_params`'s (any number of layers).  Each
    :meth:`step` is one token of one session; steps of different sessions
    may run at once from different threads.  ``on_tile(kind, program,
    image, result)`` sees every answered tile.  ``image`` is the image
    submitted: a numpy array, or for a GEMV tile one of the session's
    device images (``jax.Array``, see :class:`Session`), whose answer's
    memory, registers and Tag are device arrays too.

    The constructor compiles what the GEMV path adds to the tiles' own
    executables (each stage's input update and ``y`` gather, the
    scheduler's device stack and split at every batch bucket), so a step
    compiles nothing more; the host-device copies the runner makes
    itself count in the scheduler's ``h2d_bytes`` / ``d2h_bytes``."""

    def __init__(self, sh: Shapes, params: dict, sched,
                 on_tile: Optional[Callable] = None):
        self.sh = sh
        self.params = params
        self.sched = sched
        self.on_tile = on_tile
        self.tiles = tiles(sh)
        self.stats = DecodeStats()
        self._lock = threading.Lock()
        self.n_layers = len(params["layers"])
        self._gemv = [{stage: self._gemv_stage_of([lp[p] for p in projs])
                       for stage, projs in GEMV_STAGES.items()}
                      for lp in params["layers"]]
        norm = self.tiles["norm"]
        self._norm = []
        for gamma in [g for lp in params["layers"]
                      for g in (lp["ln1"], lp["ln2"])] + [params["norm"]]:
            img = norm.zeros.copy()
            img[norm.region("gamma")] = gamma
            self._norm.append(img)
        self._warm()

    def _gemv_stage_of(self, projections) -> GemvStage:
        """The stage of ``projections`` (one input): one image per tile of
        ``mt`` output channels, weights in."""
        k = projections[0]["w"].shape[0]
        if k not in (self.sh.d, self.sh.ff):
            raise ValueError(f"a projection over {k} rows: GEMV tiles "
                             f"contract over {self.sh.d} or {self.sh.ff}")
        t = self.tiles["gemv_d" if k == self.sh.d else "gemv_ff"]
        mt = self.sh.mt
        widths, images = [], []
        for p in projections:
            m = p["w"].shape[1]
            widths.append(m)
            for j in range(0, m, mt):
                img = t.zeros.copy()
                w = img[t.region("w")].reshape(-1, mt)
                w[:k] = p["w"][:, j:j + mt]
                img[t.region("ws")] = p["s"][j:j + mt]
                img[t.region("bias")] = p["b"][j:j + mt]
                images.append(img)
        return GemvStage(t, k, tuple(widths), tuple(images))

    def _warm(self) -> None:
        """Compile every executable the GEMV path adds, before the first
        step: each stage's input update and ``y`` gather (the stages have
        the same shapes in every layer), and the scheduler's device stack
        and split of each GEMV kind at every batch bucket."""
        for st in (self._gemv[0].values() if self._gemv else ()):
            t = st.tile
            images = (jnp.zeros(t.zeros.size, jnp.float32),) * len(st.images)
            jax.block_until_ready(t.gather_y(t.write_input(
                images, np.zeros(st.k, np.float32),
                np.zeros(1, np.float32))))
        for kind in ("gemv_d", "gemv_ff"):
            t = self.tiles[kind]
            self.sched.warm_resident(t.program, t.zeros.size)

    def new_session(self) -> Session:
        """A session with an empty cache and its own GEMV images uploaded
        to the device (about 242 MiB at Qwen2-0.5B's widths for 4 layers;
        counted in the scheduler's ``h2d_bytes``)."""
        gemv = [{stage: tuple(jnp.array(img) for img in st.images)
                 for stage, st in layer.items()} for layer in self._gemv]
        self.sched.count_copies(h2d=sum(
            img.nbytes for layer in self._gemv for st in layer.values()
            for img in st.images))
        return Session(self.sh, gemv)

    # -- stages ----------------------------------------------------------
    def _stage(self, kind: str, jobs: List[Tuple[Tile, np.ndarray]]
               ) -> List[np.ndarray]:
        """Submit every tile, wait for every answer; the answers' memory
        images in order."""
        with span(NN_STAGE, kind=kind, tiles=len(jobs)):
            t0 = time.perf_counter()
            tickets = [self.sched.submit(t.program, img) for t, img in jobs]
            results = [tk.result(timeout=STAGE_TIMEOUT_S) for tk in tickets]
            waited = time.perf_counter() - t0
        with self._lock:
            self.stats.stages += 1
            self.stats.tiles += len(jobs)
            self.stats.stage_wait_s += waited
        if self.on_tile is not None:
            for (t, img), res in zip(jobs, results):
                self.on_tile(t.kind, t.program, img, res)
        return [r.memory for r in results]

    def _norm_stage(self, index: int, h, delta):
        t = self.tiles["norm"]
        img = self._norm[index].copy()
        img[t.region("h")] = h
        img[t.region("delta")] = delta
        mem, = self._stage("norm", [(t, img)])
        return (t.read(mem, "h_out"), t.read(mem, "x"), t.read(mem, "xq"),
                t.read(mem, "sx"))

    def _gemv_stage(self, kind: str, sess: Session, layer: int, xq, sx
                    ) -> List[np.ndarray]:
        """GEMV stage ``kind`` of ``layer``: ``xq`` and ``sx`` written into
        the session's device images by one jitted update, every tile
        served, and the ``y`` of every answer gathered and copied to the
        host at once; one output vector per projection."""
        st = self._gemv[layer][kind]
        t = st.tile
        images = t.write_input(sess.gemv[layer][kind], xq, sx)
        sess.gemv[layer][kind] = images
        mems = self._stage(kind, [(t, img) for img in images])
        y = np.asarray(t.gather_y(tuple(mems)))
        self.sched.count_copies(h2d=xq.nbytes + sx.nbytes, d2h=y.nbytes)
        return np.split(y, np.cumsum(st.widths)[:-1])

    def _attention(self, sess: Session, layer: int, q, n: int) -> Tuple:
        """Split tiles over positions ``[0, n)`` and their merge: the
        quantised attention output."""
        sh = self.sh
        at, mt = self.tiles["attn"], self.tiles["merge"]
        g, hd, s = sh.group, sh.head_dim, sh.split
        splits = -(-n // s)
        jobs = []
        for j in range(sh.kv_heads):
            for i in range(splits):
                img = at.zeros.copy()
                img[at.region("q")] = q[j * g * hd:(j + 1) * g * hd]
                img[at.region("k")] = sess.k[layer, j, i * s:(i + 1) * s] \
                    .ravel()
                img[at.region("v")] = sess.v[layer, j, i * s:(i + 1) * s] \
                    .ravel()
                bias = img[at.region("bias")]
                bias[max(0, n - i * s):] = MASKED
                jobs.append((at, img))
        mems = self._stage("attn", jobs)
        img = mt.zeros.copy()
        ns = sh.max_splits
        mm = img[mt.region("m")].reshape(sh.heads, ns)
        ll = img[mt.region("l")].reshape(sh.heads, ns)
        oo = img[mt.region("o")].reshape(sh.heads, hd, ns)
        mm[:] = MASKED
        for idx, mem in enumerate(mems):
            j, i = divmod(idx, splits)
            rows = slice(j * g, (j + 1) * g)
            mm[rows, i] = at.read(mem, "m")
            ll[rows, i] = at.read(mem, "l")
            oo[rows, :, i] = at.read(mem, "o").reshape(g, hd)
        mem, = self._stage("merge", [(mt, img)])
        return mt.read(mem, "oq"), mt.read(mem, "so")

    def step(self, sess: Session, h0) -> StepOutput:
        """One token (hidden state ``h0``) of ``sess`` through every
        layer and the final norm; appends to the session's cache."""
        sh = self.sh
        pos = sess.length
        if pos >= sh.capacity:
            raise ValueError(f"session cache full ({sh.capacity} positions)")
        cos, sin = qwen2_ref.rope_tables([pos], sh.head_dim, sh.theta)
        rt = self.tiles["rope"]
        h = np.asarray(h0, np.float32)
        delta = np.zeros(sh.d, np.float32)
        out = StepOutput(pos, [], [], [], None)
        with span(NN_STEP, pos=pos):
            for li in range(self.n_layers):
                with span(NN_LAYER, index=li):
                    h, _, xq, sx = self._norm_stage(2 * li, h, delta)
                    if li:
                        out.layers.append(h)
                    quant = {"x": (xq, sx)}
                    out.quant.append(quant)
                    q, k, v = self._gemv_stage("qkv", sess, li, xq, sx)
                    img = rt.zeros.copy()
                    img[rt.region("q")] = q
                    img[rt.region("k")] = k
                    img[rt.region("cos")] = cos[0]
                    img[rt.region("sin")] = sin[0]
                    mem, = self._stage("rope", [(rt, img)])
                    k = rt.read(mem, "k_out").reshape(sh.kv_heads, -1)
                    v = v.reshape(sh.kv_heads, -1)
                    sess.k[li, :, pos] = k
                    sess.v[li, :, pos] = v
                    out.k.append(k)
                    out.v.append(v)
                    oq, so = self._attention(sess, li,
                                             rt.read(mem, "q_out"), pos + 1)
                    quant["o"] = (oq, so)
                    a, = self._gemv_stage("o", sess, li, oq, so)
                    h, _, xq, sx = self._norm_stage(2 * li + 1, h, a)
                    quant["x2"] = (xq, sx)
                    gate, up = self._gemv_stage("gate_up", sess, li, xq, sx)
                    st = self.tiles["silu_mul"]
                    img = st.zeros.copy()
                    img[st.region("gate")] = gate
                    img[st.region("up")] = up
                    mem, = self._stage("silu_mul", [(st, img)])
                    quant["m"] = (st.read(mem, "mq"), st.read(mem, "sm"))
                    delta, = self._gemv_stage("down", sess, li,
                                              *quant["m"])
            h, final, _, _ = self._norm_stage(2 * self.n_layers, h, delta)
        out.layers.append(h)
        out.final = final
        sess.length = pos + 1
        with self._lock:
            self.stats.steps += 1
        return out
