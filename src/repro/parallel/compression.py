"""Gradient compression: int8 all-gather all-reduce with error feedback.

Distributed-optimization trick for the cross-pod (DCN) gradient sync: the
pod axis has ~10x less bandwidth than ICI, so gradients crossing it are
quantized to int8 with a psum-shared scale.  An all-gather of int8 shards
moves half the bytes of a bf16 ring all-reduce at pod count 2 (and the
error-feedback residual keeps SGD unbiased in expectation).

Two entry points:

  * :func:`compressed_allreduce_mean` — collective primitive, used inside
    ``shard_map`` (tests run it on a host-device mesh);
  * :func:`make_dp_train_step` — a shard_map data-parallel trainer for
    replicated-parameter models (used by examples/tests to demonstrate
    end-to-end compressed sync + error feedback).
"""
from __future__ import annotations

import functools
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh
from jax.sharding import PartitionSpec as P


def quantize_int8(x: jnp.ndarray, scale: jnp.ndarray) -> jnp.ndarray:
    return jnp.clip(jnp.round(x / scale), -127, 127).astype(jnp.int8)


def compressed_allreduce_mean(x: jnp.ndarray, axis_name: str
                              ) -> jnp.ndarray:
    """Mean over ``axis_name`` with int8 wire format (shard_map body)."""
    n = jax.lax.psum(1, axis_name)
    amax = jax.lax.pmax(jnp.max(jnp.abs(x)), axis_name)
    scale = jnp.maximum(amax, 1e-8) / 127.0
    q = quantize_int8(x.astype(jnp.float32), scale)
    gathered = jax.lax.all_gather(q, axis_name)        # int8 on the wire
    return gathered.astype(jnp.float32).sum(axis=0) * scale / n


def compress_with_feedback(grads, residual):
    """Apply error feedback: g' = g + residual; the caller transmits
    quantize(g') and keeps the new residual g' - dequant(quant(g'))."""
    def one(g, r):
        gf = g.astype(jnp.float32) + r
        amax = jnp.max(jnp.abs(gf))
        scale = jnp.maximum(amax, 1e-8) / 127.0
        q = quantize_int8(gf, scale)
        deq = q.astype(jnp.float32) * scale
        return deq.astype(g.dtype), gf - deq
    flat_g, treedef = jax.tree.flatten(grads)
    flat_r = jax.tree.leaves(residual)
    outs = [one(g, r) for g, r in zip(flat_g, flat_r)]
    new_g = jax.tree.unflatten(treedef, [o[0] for o in outs])
    new_r = jax.tree.unflatten(treedef, [o[1] for o in outs])
    return new_g, new_r


def make_dp_train_step(loss_fn: Callable, optimizer_update: Callable,
                       mesh: Mesh, axis: str = "data",
                       compress: bool = True):
    """Pure-DP trainer: params replicated, batch sharded over ``axis``,
    gradient mean over ``axis`` int8-compressed with error feedback.

    loss_fn(params, batch) -> scalar; optimizer_update(params, grads,
    opt_state) -> (params, opt_state).
    """

    def step(params, opt_state, residual, batch):
        def body(params, opt_state, residual, batch):
            loss, grads = jax.value_and_grad(loss_fn)(params, batch)
            if compress:
                grads = jax.tree.map(
                    lambda g: compressed_allreduce_mean(
                        g.astype(jnp.float32), axis), grads)
                grads, residual = compress_with_feedback(grads, residual)
            else:
                grads = jax.tree.map(
                    lambda g: jax.lax.pmean(g, axis), grads)
            params, opt_state = optimizer_update(params, grads, opt_state)
            loss = jax.lax.pmean(loss, axis)
            return params, opt_state, residual, loss

        rep = P()
        sharded = P(axis)
        return shard_map(
            body, mesh=mesh,
            in_specs=(rep, rep, rep,
                      jax.tree.map(lambda _: sharded, batch)),
            out_specs=(rep, rep, rep, rep),
            check_vma=False,
        )(params, opt_state, residual, batch)

    return jax.jit(step)


def zeros_like_residual(params):
    return jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
