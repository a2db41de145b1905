"""Plain ``jax.numpy`` float32 reference of the Qwen2 decoder stack as the
MVE decode-step runner (:mod:`repro.nn.qwen2`) serves it.

No kernels, no batching, and no cache in :func:`forward`; everything runs
under ``jax.default_matmul_precision("highest")``.  The block follows the
Qwen2 description (arXiv:2407.10671; the model's ``config.json``): pre-norm
RMSNorm, grouped-query attention with bias on q, k and v and rotary
position embedding (rotate-half), a SwiGLU MLP, residual adds, a final
RMSNorm.  Departures, each shared with the runner:

* W8A8: every projection's weight is int8, symmetric per output channel
  (scale ``s``), and its input int8, symmetric per token
  (:func:`quantize`); the product accumulates in int32 and dequantises in
  float32 as ``(acc * s_w) * s_x + b``.  The published weights are bf16.
* float32 everywhere else (the published compute dtype is bf16).
* The stack starts from hidden states and ends at the final norm: no
  embedding lookup and no LM head.
* The rotary tables are computed in float64 and rounded to float32
  (:func:`rope_tables`); the published code computes them in float32.

``bf16=True`` rounds the outputs of every RMSNorm and the attention's
scores, probabilities and output to bfloat16: the control that a
comparison's tolerance must reject.

Int8 rounding is discontinuous: a float difference of 1e-7 that moves one
activation across a rounding boundary changes it by a whole step, and the
quantisations after it amplify that.  So :func:`decode_step` can be
*forced* with the program's own int8 activations at the four quantisation
points of each layer (``x``: the attention input, ``o``: the attention
output, ``x2``: the MLP input, ``m``: the down projection's input).  It
then checks that each forced vector is a rounding of its own value and
computes on from the forced values, so every other output compares to
float accuracy.  Two readings say how far the forced values are from this
reference's own: ``quant_step_error``, how far past half an int8 step the
farthest element lies (in steps), and ``scale_error``, the largest
relative difference of a scale.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np


def _bf16(x, on: bool):
    return x.astype(jnp.bfloat16).astype(jnp.float32) if on else x


def quantize(x):
    """Per-token symmetric int8: ``(q, s)`` with ``x ~= q * s``."""
    amax = jnp.max(jnp.abs(x), axis=-1, keepdims=True)
    q = jnp.round(x * (127.0 / amax))
    return q, amax / 127.0


def linear(q, s, p: Dict[str, np.ndarray]):
    """W8A8 projection of quantised rows ``q`` (scale ``s``)."""
    acc = jax.lax.dot(q.astype(jnp.int8), jnp.asarray(p["w"], jnp.int8),
                      preferred_element_type=jnp.int32)
    return (acc.astype(jnp.float32) * jnp.asarray(p["s"])) * s + \
        jnp.asarray(p["b"])


def rmsnorm(x, g, eps: float, bf16: bool = False):
    y = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return _bf16(y * jnp.asarray(g), bf16)


def rope_tables(positions: Sequence[int], head_dim: int, theta: float):
    """``(cos, sin)`` of shape ``(len(positions), head_dim)``, float32."""
    inv = theta ** (-np.arange(0, head_dim, 2, dtype=np.float64) / head_dim)
    ang = np.asarray(positions, np.float64)[:, None] * inv[None, :]
    ang = np.concatenate([ang, ang], axis=-1)
    return np.cos(ang).astype(np.float32), np.sin(ang).astype(np.float32)


def rope(x, cos, sin):
    """Rotate-half RoPE of ``x`` (..., heads, head_dim) with tables of
    shape (..., head_dim)."""
    half = x.shape[-1] // 2
    rot = jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)
    return x * cos[..., None, :] + rot * sin[..., None, :]


def attention(q, k, v, mask, group: int, bf16: bool = False):
    """``q`` (T, heads, hd) over ``k``/``v`` (S, kv_heads, hd); ``mask``
    (T, S) True where a query may attend.  Returns (T, heads * hd)."""
    scale = 1.0 / np.sqrt(q.shape[-1])
    kk = jnp.repeat(k, group, axis=1)
    vv = jnp.repeat(v, group, axis=1)
    s = _bf16(jnp.einsum("thd,shd->ths", q, kk) * scale, bf16)
    s = jnp.where(mask[:, None, :], s, -jnp.inf)
    p = jnp.exp(s - jnp.max(s, axis=-1, keepdims=True))
    p = _bf16(p / jnp.sum(p, axis=-1, keepdims=True), bf16)
    o = _bf16(jnp.einsum("ths,shd->thd", p, vv), bf16)
    return o.reshape(o.shape[0], -1)


def _quant(x, key: str, forced, quant: dict, errs: list):
    """``quantize(x)``, or the forced ``(q, s)`` of ``key`` after checking
    that it rounds ``x``."""
    q, s = quantize(x)
    if forced is not None:
        fq = jnp.asarray(forced[key][0], jnp.float32).reshape(q.shape)
        fs = jnp.asarray(forced[key][1], jnp.float32).reshape(s.shape)
        y = x * (127.0 / jnp.max(jnp.abs(x), axis=-1, keepdims=True))
        errs["step"].append(float(jnp.max(jnp.abs(fq - y))) - 0.5)
        errs["scale"].append(float(jnp.max(jnp.abs(fs - s) / s)))
        q, s = fq, fs
    quant[key] = (np.asarray(q), np.asarray(s))
    return q, s


def _layer(lp, h, cos, sin, k_past, v_past, mask, dims, bf16,
           forced=None, errs=None):
    """One decoder layer over ``h`` (T, d) whose keys and values are
    appended after ``k_past``/``v_past`` (P, kv_heads, hd).  Returns the
    output, the keys, the values and the layer's ``(q, s)`` per
    quantisation point."""
    nh, nkv, hd, eps = dims["heads"], dims["kv_heads"], dims["head_dim"], \
        dims["eps"]
    t = h.shape[0]
    quant = {}
    xq, xs = _quant(rmsnorm(h, lp["ln1"], eps, bf16), "x", forced, quant,
                    errs)
    q = linear(xq, xs, lp["q"]).reshape(t, nh, hd)
    k = linear(xq, xs, lp["k"]).reshape(t, nkv, hd)
    v = linear(xq, xs, lp["v"]).reshape(t, nkv, hd)
    q, k = rope(q, cos, sin), rope(k, cos, sin)
    kk = jnp.concatenate([k_past, k], axis=0)
    vv = jnp.concatenate([v_past, v], axis=0)
    o = attention(q, kk, vv, mask, nh // nkv, bf16)
    oq, os_ = _quant(o, "o", forced, quant, errs)
    h = h + linear(oq, os_, lp["o"])
    mq, ms = _quant(rmsnorm(h, lp["ln2"], eps, bf16), "x2", forced, quant,
                    errs)
    g = linear(mq, ms, lp["gate"])
    u = linear(mq, ms, lp["up"])
    dq, ds = _quant(jax.nn.silu(g) * u, "m", forced, quant, errs)
    return h + linear(dq, ds, lp["down"]), k, v, quant


def forward(params, h, dims, bf16: bool = False) -> dict:
    """Causal forward of ``h`` (T, d) at positions 0..T-1, no cache.
    Returns ``layers`` (each layer's output, (T, d)), ``k``/``v`` (each
    layer's roped keys and values, (T, kv_heads, hd)) and ``final``."""
    with jax.default_matmul_precision("highest"):
        h = jnp.asarray(h, jnp.float32)
        t = h.shape[0]
        cos, sin = rope_tables(range(t), dims["head_dim"], dims["theta"])
        mask = jnp.tril(jnp.ones((t, t), bool))
        empty = jnp.zeros((0, dims["kv_heads"], dims["head_dim"]),
                          jnp.float32)
        out = {"layers": [], "k": [], "v": []}
        for lp in params["layers"]:
            h, k, v, _ = _layer(lp, h, cos, sin, empty, empty, mask, dims,
                                bf16)
            out["layers"].append(np.asarray(h))
            out["k"].append(np.asarray(k))
            out["v"].append(np.asarray(v))
        out["final"] = np.asarray(rmsnorm(h, params["norm"], dims["eps"],
                                          bf16))
    return out


def decode_step(params, h, pos: int, k_cache: List[np.ndarray],
                v_cache: List[np.ndarray], dims, bf16: bool = False,
                forced: Optional[List[dict]] = None) -> dict:
    """One token ``h`` (d,) at position ``pos`` over each layer's cache
    ``k_cache[l]``/``v_cache[l]`` (pos, kv_heads, hd).  Returns the same
    keys as :func:`forward`, for the one token, and ``quant`` (each
    layer's ``{point: (q, s)}``).  With ``forced`` (a served step's
    ``quant``) the int8 activations are the served ones, and
    ``quant_step_error`` and ``scale_error`` say how far they are from
    roundings of this reference's values."""
    with jax.default_matmul_precision("highest"):
        h = jnp.asarray(h, jnp.float32)[None, :]
        cos, sin = rope_tables([pos], dims["head_dim"], dims["theta"])
        mask = jnp.ones((1, pos + 1), bool)
        out = {"layers": [], "k": [], "v": [], "quant": []}
        errs = {"step": [], "scale": []}
        for li, (lp, kc, vc) in enumerate(zip(params["layers"], k_cache,
                                              v_cache)):
            h, k, v, quant = _layer(
                lp, h, cos, sin, jnp.asarray(kc), jnp.asarray(vc), mask,
                dims, bf16, None if forced is None else forced[li], errs)
            out["layers"].append(np.asarray(h[0]))
            out["k"].append(np.asarray(k[0]))
            out["v"].append(np.asarray(v[0]))
            out["quant"].append(quant)
        if forced is not None:
            out["quant_step_error"] = max(errs["step"])
            out["scale_error"] = max(errs["scale"])
        out["final"] = np.asarray(rmsnorm(h, params["norm"], dims["eps"],
                                          bf16)[0])
    return out


def rel_error(got, want) -> float:
    """``||got - want|| / ||want||`` (L2, float64)."""
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


def compare(got: dict, want: dict, positions: Optional[slice] = None
            ) -> float:
    """The largest :func:`rel_error` over every layer's output, keys and
    values, and the final norm (rows ``positions`` of each)."""
    sel = positions if positions is not None else slice(None)
    errs = [rel_error(np.asarray(got["final"])[sel],
                      np.asarray(want["final"])[sel])]
    for key in ("layers", "k", "v"):
        for g, w in zip(got[key], want[key]):
            errs.append(rel_error(np.asarray(g)[sel], np.asarray(w)[sel]))
    return max(errs)
