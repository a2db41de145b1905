"""Public jit'd wrappers around the Pallas kernels.

Every op dispatches between the Pallas kernel (compiled on TPU,
interpreted elsewhere — :mod:`repro.kernels.platform`) and the pure-jnp
oracle in :mod:`repro.kernels.ref`.  The models call through here so a
single flag flips the whole framework between kernel and XLA paths.
"""
from __future__ import annotations

import os

import jax.numpy as jnp

from . import ref
from .bitplane_gemm import bitplane_matmul, int8_matmul
from .flash_attention import flash_attention as _flash_pallas

# Models use the oracle path by default on CPU (fast XLA fusion); tests and
# TPU deployments flip this on.
_USE_PALLAS = os.environ.get("REPRO_USE_PALLAS", "0") == "1"


def use_pallas() -> bool:
    return _USE_PALLAS


def quantized_matmul(x: jnp.ndarray, wq: jnp.ndarray, scale: jnp.ndarray,
                     bitserial: bool = False,
                     force_pallas: bool | None = None) -> jnp.ndarray:
    """x(float) @ dequant(wq int8, per-col scale) with int8 activations.

    Serving-path op: activations quantized per-row, weights pre-quantized
    per-column; exact int32 accumulation then one fp rescale.
    """
    xq, xs = ref.quantize_rowwise_ref(x)
    pallas = _USE_PALLAS if force_pallas is None else force_pallas
    if pallas:
        fn = bitplane_matmul if bitserial else int8_matmul
        acc = fn(xq, wq)
    else:
        acc = (ref.bitplane_matmul_ref(xq, wq) if bitserial
               else ref.int8_matmul_ref(xq, wq))
    return acc.astype(jnp.float32) * xs * scale[None, :]


def flash_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                    causal: bool = True, scale: float | None = None,
                    force_pallas: bool | None = None) -> jnp.ndarray:
    pallas = _USE_PALLAS if force_pallas is None else force_pallas
    if pallas:
        return _flash_pallas(q, k, v, causal=causal, scale=scale)
    return ref.flash_attention_ref(q, k, v, causal=causal, scale=scale)
