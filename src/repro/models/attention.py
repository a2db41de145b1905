"""GQA attention: chunked-causal (train/prefill), cached decode, cross.

The chunked path scans query chunks so peak logits memory is
``(B, heads, chunk, S)`` — the jnp mirror of the Pallas flash kernel
(:mod:`repro.kernels.flash_attention`), which replaces it on TPU when
``cfg.use_pallas``.  GQA is computed grouped, never materializing repeated
KV heads.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..kernels import ops as kops
from ..parallel.axes import constrain

NEG_INF = -1e30


def _grouped(q: jnp.ndarray, kv_heads: int) -> jnp.ndarray:
    """(B,S,Hq,D) -> (B,S,K,G,D)."""
    b, s, hq, d = q.shape
    return q.reshape(b, s, kv_heads, hq // kv_heads, d)


def chunked_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                      causal: bool = True,
                      segment_ids: Optional[jnp.ndarray] = None,
                      kv_segment_ids: Optional[jnp.ndarray] = None,
                      chunk: int = 512,
                      use_pallas: bool = False) -> jnp.ndarray:
    """q: (B,Sq,Hq,D); k/v: (B,Sk,K,D) -> (B,Sq,Hq,D)."""
    b, sq, hq, d = q.shape
    sk, kh = k.shape[1], k.shape[2]
    scale = 1.0 / np.sqrt(d)

    if use_pallas and segment_ids is None and d in (64, 128):
        qt = q.transpose(0, 2, 1, 3)
        g = hq // kh
        kt = jnp.repeat(k.transpose(0, 2, 1, 3), g, axis=1)
        vt = jnp.repeat(v.transpose(0, 2, 1, 3), g, axis=1)
        out = kops.flash_attention(qt, kt, vt, causal=causal,
                                   force_pallas=True)
        return out.transpose(0, 2, 1, 3)

    # GQA: repeat kv heads so the merged head axis shards like Megatron
    # TP (64/16 etc.); for head counts not divisible by the model axis
    # the 'act_heads' rule falls back and the q-chunk 'seq' rule takes
    # the mesh axis instead (sequence-parallel attention).  Repeat order
    # matches the (kv, group) factoring used by decode_attention.
    g = hq // kh
    kr = jnp.repeat(k, g, axis=2) if g > 1 else k          # (B,Sk,Hq,D)
    vr = jnp.repeat(v, g, axis=2) if g > 1 else v
    kr = constrain(kr, "batch", None, "act_heads", None)
    vr = constrain(vr, "batch", None, "act_heads", None)

    chunk = min(chunk, sq)
    pad = -sq % chunk
    qp = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0))) if pad else q
    if pad and segment_ids is not None:
        segment_ids = jnp.pad(segment_ids, ((0, 0), (0, pad)))
    nq = qp.shape[1] // chunk
    qs = qp.reshape(b, nq, chunk, hq, d).swapaxes(0, 1)    # (nq,B,c,H,D)
    seg_q = (segment_ids.reshape(b, nq, chunk).swapaxes(0, 1)
             if segment_ids is not None else None)
    kv_seg = kv_segment_ids if kv_segment_ids is not None else segment_ids
    kpos = jnp.arange(sk)
    offset = sk - sq

    def body(i, qc, sq_c):
        # bf16 operands + fp32 accumulation (preferred_element_type), so
        # the backward cotangents stay in the model dtype — input-side
        # .astype(f32) casts were materializing 2 GB f32 activation
        # cotangents outside the layer loop on the 72B cell.
        qc = constrain(qc, "batch", None, "act_heads", None)
        logits = jnp.einsum("bchd,bshd->bhcs", qc, kr)
        logits = logits.astype(jnp.float32) * scale
        logits = constrain(logits, "batch", "act_heads", "seq", None)
        valid = jnp.ones((b, 1, chunk, sk), bool)
        if causal:
            qpos = i * chunk + jnp.arange(chunk) + offset
            valid = valid & (qpos[:, None] >= kpos[None, :])[None, None]
        if sq_c is not None and kv_seg is not None:
            valid = valid & (sq_c[:, None, :, None] ==
                             kv_seg[:, None, None, :])
        logits = jnp.where(valid, logits, NEG_INF)
        probs = jax.nn.softmax(logits, axis=-1)
        probs = constrain(probs, "batch", "act_heads", "seq", None)
        out = jnp.einsum("bhcs,bshd->bchd", probs.astype(vr.dtype), vr)
        out = constrain(out, "batch", None, "act_heads", None)
        return out.astype(q.dtype)

    def scan_body(i, xs):
        if seg_q is not None:
            qc, sq_c = xs
        else:
            qc, sq_c = xs, None
        return i + 1, body(i, qc, sq_c)

    # checkpoint each chunk: the backward recomputes the (chunk, Sk)
    # probability block instead of saving it — flash-attention residual
    # behavior at the remat level.
    scan_body = jax.checkpoint(scan_body)
    xs = (qs, seg_q) if seg_q is not None else qs
    _, outs = jax.lax.scan(scan_body, 0, xs)
    out = outs.swapaxes(0, 1).reshape(b, sq + pad, hq, d)
    return out[:, :sq]


def decode_attention(q: jnp.ndarray, k_cache: jnp.ndarray,
                     v_cache: jnp.ndarray,
                     cache_index: jnp.ndarray) -> jnp.ndarray:
    """One-token attention over a (B,S,K,D) cache filled up to and
    including ``cache_index``."""
    b, one, hq, d = q.shape
    s, kh = k_cache.shape[1], k_cache.shape[2]
    scale = 1.0 / np.sqrt(d)
    if k_cache.dtype == jnp.float8_e4m3fn:     # quantized KV cache
        k_cache = k_cache.astype(q.dtype)
        v_cache = v_cache.astype(q.dtype)
    qg = _grouped(q, kh)                                   # (B,1,K,G,D)
    logits = jnp.einsum("bokgd,bskd->bkgos", qg, k_cache)
    logits = logits.astype(jnp.float32) * scale
    # kv-sequence sharded attention (flash-decode): each device scores its
    # cache slice; XLA turns the softmax into a partial-max/sum reduce.
    logits = constrain(logits, "batch", None, None, None, "kv_seq")
    ci = jnp.broadcast_to(jnp.asarray(cache_index), (b,))
    valid = (jnp.arange(s)[None, :] <= ci[:, None]
             )[:, None, None, None, :]
    logits = jnp.where(valid, logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1)
    probs = constrain(probs, "batch", None, None, None, "kv_seq")
    out = jnp.einsum("bkgos,bskd->bokgd", probs.astype(v_cache.dtype),
                     v_cache)
    return out.reshape(b, one, hq, d).astype(q.dtype)


def update_cache(k_cache: jnp.ndarray, v_cache: jnp.ndarray,
                 k_new: jnp.ndarray, v_new: jnp.ndarray,
                 cache_index: jnp.ndarray
                 ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Write the new token's K/V at ``cache_index`` (functional update).

    A scalar index writes one seq slice; a (B,) index writes each batch
    row at its own position (continuous-batching decode)."""
    ci = jnp.asarray(cache_index)
    if ci.ndim == 0:
        k_cache = jax.lax.dynamic_update_slice_in_dim(
            k_cache, k_new.astype(k_cache.dtype), ci, axis=1)
        v_cache = jax.lax.dynamic_update_slice_in_dim(
            v_cache, v_new.astype(v_cache.dtype), ci, axis=1)
    else:
        rows = jnp.arange(k_cache.shape[0])
        k_cache = k_cache.at[rows, ci].set(
            k_new[:, 0].astype(k_cache.dtype))
        v_cache = v_cache.at[rows, ci].set(
            v_new[:, 0].astype(v_cache.dtype))
    return k_cache, v_cache
