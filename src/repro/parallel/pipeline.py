"""Pipeline parallelism: a GPipe schedule as a shard_map program.

Each device along the ``stage`` mesh axis holds one stage's parameters;
microbatches stream through ``jax.lax.ppermute`` in a ``lax.scan`` over
M + S - 1 schedule slots (the classic GPipe bubble).  Because ppermute is
differentiable (its transpose is the reverse permutation), ``jax.grad``
through :func:`pipeline_apply` yields correct per-stage parameter
gradients — no hand-written backward schedule is needed for this
forward-checkpointed formulation.

This complements the DP/FSDP/TP/SP/EP shardings in ``parallel/axes.py``:
on pods larger than the 16-way TP sweet spot, stages replace depth-wise
FSDP regathering with point-to-point activation transfers (bubble
fraction (S-1)/(M+S-1), amortized by microbatch count).

Used by ``examples``/tests on host devices; the same program lowers for
TPU meshes with a ('stage',) or ('stage', 'data') topology.
"""
from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh
from jax.sharding import PartitionSpec as P


def pipeline_apply(stage_fn: Callable, mesh: Mesh, num_stages: int,
                   axis: str = "stage"):
    """Returns ``apply(stacked_params, micro_x) -> (M, mb, ...)`` where
    ``stacked_params`` has a leading stage dim (sharded over ``axis``) and
    ``micro_x`` is (M, mb, ...) microbatches (replicated).

    ``stage_fn(params_slice, x) -> y`` must keep the activation shape
    (a residual-block stack), so it can flow through every stage.
    """

    def body(params, micro_x):
        # shard_map gives each stage params with a leading dim of 1
        params = jax.tree.map(lambda a: a[0], params)
        stage = jax.lax.axis_index(axis)
        s_count = jax.lax.psum(1, axis)
        m = micro_x.shape[0]
        slots = m + num_stages - 1
        perm = [(s, s + 1) for s in range(num_stages - 1)]

        def step(buf, t):
            i = t - stage                       # microbatch index here
            active = jnp.logical_and(i >= 0, i < m)
            x_in = jnp.where(stage == 0,
                             micro_x[jnp.clip(i, 0, m - 1)], buf)
            y = stage_fn(params, x_in)
            y = jnp.where(active, y, jnp.zeros_like(y))
            out = jnp.where(
                jnp.logical_and(stage == s_count - 1, active),
                y, jnp.zeros_like(y))
            nxt = jax.lax.ppermute(y, axis, perm)
            return nxt, out

        zero = jnp.zeros_like(micro_x[0])
        _, outs = jax.lax.scan(step, zero, jnp.arange(slots))
        # only the last stage produced outputs; replicate via psum
        outs = jax.lax.psum(outs, axis)
        # slot t on the last stage carried microbatch t - (S-1)
        return outs[num_stages - 1:]

    return shard_map(
        body, mesh=mesh,
        in_specs=(P(axis), P()),
        out_specs=P(),
        check_vma=False,
    )


def bubble_fraction(num_stages: int, num_micro: int) -> float:
    """GPipe bubble overhead: (S-1)/(M+S-1)."""
    return (num_stages - 1) / (num_micro + num_stages - 1)
