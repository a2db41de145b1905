"""Pallas TPU kernel: flash attention forward (online softmax).

The perf-critical compute hot-spot of every transformer cell in the
framework.  Block sizes are MXU/VPU aligned (q-block 128, kv-block 128,
head_dim expected 64/128).  The kv stream for one (batch*head) is VMEM
resident per grid step; the q dimension is gridded, and causal masking
skips fully-masked kv blocks via the loop bound.

Oracle: :func:`repro.kernels.ref.flash_attention_ref` (fp32 softmax).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from .platform import pallas_call

DEFAULT_BQ = 128
DEFAULT_BK = 128
NEG_INF = -1e30


def _flash_kernel(scale: float, causal: bool, bq: int, bk: int,
                  skp: int, sk_true: int, offset: int,
                  q_ref, k_ref, v_ref, o_ref):
    """One q block against the kv stream.

    ``offset = sk_true - sq_true`` aligns the causal diagonal (decode
    convention: the last query row sees the full kv horizon).  Padded kv
    rows (``kpos >= sk_true``) are masked unconditionally.
    """
    qi = pl.program_id(1)
    q = q_ref[0].astype(jnp.float32) * scale          # (bq, d)
    d = q.shape[-1]

    nk = skp // bk
    if causal:
        last_row = qi * bq + bq - 1 + offset
        upper = jnp.clip(last_row // bk + 1, 0, nk)
    else:
        upper = nk

    def body(j, carry):
        acc, m, l = carry
        kblk = k_ref[0, pl.ds(j * bk, bk), :].astype(jnp.float32)
        vblk = v_ref[0, pl.ds(j * bk, bk), :].astype(jnp.float32)
        logits = jax.lax.dot_general(
            q, kblk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)       # (bq, bk)
        kpos = j * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        valid = kpos < sk_true
        if causal:
            qpos = qi * bq + jax.lax.broadcasted_iota(
                jnp.int32, (bq, bk), 0) + offset
            valid = valid & (qpos >= kpos)
        logits = jnp.where(valid, logits, NEG_INF)
        m_new = jnp.maximum(m, logits.max(axis=-1))
        p = jnp.where(logits > NEG_INF / 2,
                      jnp.exp(logits - m_new[:, None]), 0.0)
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + p.sum(axis=-1)
        acc = acc * alpha[:, None] + jax.lax.dot_general(
            p, vblk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return acc, m_new, l_new

    acc = jnp.zeros((bq, d), jnp.float32)
    m0 = jnp.full((bq,), NEG_INF, jnp.float32)
    l0 = jnp.zeros((bq,), jnp.float32)
    acc, m, l = jax.lax.fori_loop(0, upper, body, (acc, m0, l0))
    l = jnp.where(l == 0.0, 1.0, l)                   # fully-masked rows
    o_ref[0] = (acc / l[:, None]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "scale", "block_q",
                                             "block_k"))
def flash_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                    causal: bool = True, scale: float | None = None,
                    block_q: int = DEFAULT_BQ,
                    block_k: int = DEFAULT_BK) -> jnp.ndarray:
    """(B, H, Sq, D) attention over (B, H, Sk, D) keys/values.

    Sq/Sk are padded to block multiples internally; the causal diagonal is
    aligned to the *unpadded* sizes (decode convention).
    """
    b, h, sq, d = q.shape
    sk = k.shape[2]
    scale = float(scale if scale is not None else 1.0 / np.sqrt(d))
    bq = min(block_q, max(8, sq))
    bk = min(block_k, max(8, sk))
    pq, pk = -sq % bq, -sk % bk
    qf = q.reshape(b * h, sq, d)
    kf = k.reshape(b * h, sk, d)
    vf = v.reshape(b * h, sk, d)
    if pq:
        qf = jnp.pad(qf, ((0, 0), (0, pq), (0, 0)))
    if pk:
        kf = jnp.pad(kf, ((0, 0), (0, pk), (0, 0)))
        vf = jnp.pad(vf, ((0, 0), (0, pk), (0, 0)))
    sqp, skp = qf.shape[1], kf.shape[1]

    kernel = functools.partial(_flash_kernel, scale, causal, bq, bk,
                               skp, sk, sk - sq)
    out = pallas_call(
        kernel,
        grid=(b * h, sqp // bq),
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda bh, i: (bh, i, 0)),
            pl.BlockSpec((1, skp, d), lambda bh, i: (bh, 0, 0)),
            pl.BlockSpec((1, skp, d), lambda bh, i: (bh, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, d), lambda bh, i: (bh, i, 0)),
        out_shape=jax.ShapeDtypeStruct((b * h, sqp, d), q.dtype),
    )(qf, kf, vf)
    return out[:, :sq, :].reshape(b, h, sq, d)
