"""Pallas TPU kernel: bit-plane quantized GEMM (bit-serial, TPU-adapted).

The paper's engine computes n-bit multiplies bit-serially (n^2+5n cycles,
Table II) because SRAM peripherals only see one bit-slice per cycle.  The
TPU-native translation of "bit-serial" is *bit-plane* decomposition: an
int8 weight matrix is a sum of 8 binary planes

    W = -128*P7 + sum_{b=0..6} 2^b * Pb,     Pb in {0,1}

so an int8 GEMM becomes 8 binary GEMMs on the MXU with shifted int32
accumulation.  The same O(bits) structure the paper exploits for
low-precision speedups (Section VII-E) shows up here as: fewer planes for
int4 weights -> proportionally less MXU work.

Two kernels:
  * ``int8_matmul``   — direct int8 x int8 -> int32 tiled MXU matmul
                        (the production path).
  * ``bitplane_matmul`` — the bit-serial-structured variant, numerically
                        identical, used for the precision-scaling study.

Tiles are MXU-aligned (128 x 128); K is resident per tile pair.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .platform import pallas_call

BM, BN = 128, 128


def _dot_i8(x, w):
    """int8 x int8 -> int32 on the MXU (int32 operands are refused)."""
    return jax.lax.dot_general(x, w, (((1,), (0,)), ((), ())),
                               preferred_element_type=jnp.int32)


def _int8_kernel(x_ref, w_ref, o_ref):
    o_ref[...] = _dot_i8(x_ref[...], w_ref[...])


def _bitplane_kernel(nbits: int, x_ref, w_ref, o_ref):
    x = x_ref[...]
    wi = w_ref[...].astype(jnp.int32)
    acc = jnp.zeros((x.shape[0], wi.shape[1]), jnp.int32)
    for b in range(nbits):                      # bit-serial over planes
        plane = ((wi >> b) & 1).astype(jnp.int8)
        partial = _dot_i8(x, plane)
        # two's complement: the top plane is the negative power
        sign = -1 if b == nbits - 1 else 1
        acc = acc + sign * (partial << b)
    o_ref[...] = acc


def _tiled_call(kernel, x, w):
    m, k = x.shape
    k2, n = w.shape
    assert k == k2, (x.shape, w.shape)
    pm, pn = -m % BM, -n % BN
    if pm or pn:
        x = jnp.pad(x, ((0, pm), (0, 0)))
        w = jnp.pad(w, ((0, 0), (0, pn)))
    gm, gn = x.shape[0] // BM, w.shape[1] // BN
    out = pallas_call(
        kernel,
        grid=(gm, gn),
        in_specs=[pl.BlockSpec((BM, k), lambda i, j: (i, 0)),
                  pl.BlockSpec((k, BN), lambda i, j: (0, j))],
        out_specs=pl.BlockSpec((BM, BN), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((x.shape[0], w.shape[1]), jnp.int32),
    )(x, w)
    return out[:m, :n]


@jax.jit
def int8_matmul(x: jnp.ndarray, w: jnp.ndarray) -> jnp.ndarray:
    """Exact int8 x int8 -> int32 matmul, (M,K) @ (K,N)."""
    return _tiled_call(_int8_kernel, x, w)


@functools.partial(jax.jit, static_argnames=("nbits",))
def bitplane_matmul(x: jnp.ndarray, w: jnp.ndarray,
                    nbits: int = 8) -> jnp.ndarray:
    """Bit-serial-structured int matmul; identical to int8_matmul for
    nbits=8, proportionally cheaper for narrower weights."""
    return _tiled_call(functools.partial(_bitplane_kernel, nbits), x, w)
