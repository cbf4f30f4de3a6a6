"""Pipeline parallelism: GPipe schedule over a mesh axis via shard_map.

TPU-native PP: stages live on the ``pod`` axis (cross-pod DCN links carry
only the (microbatch, d_model) activation edge — the whole point of putting
PP, not DP, across pods at 1000+ chips). The schedule is SPMD: every device
runs the same program; ``lax.ppermute`` shifts activations stage->stage+1
each tick, and the first/last stages feed/drain microbatches. Differentiable
(grad flows back through the reverse permutes), so the same primitive serves
training.

Bubble fraction = (S-1)/(M+S-1) — choose n_micro >> n_stages.
"""

from __future__ import annotations

from collections.abc import Callable
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def spmd_pipeline(stage_fn: Callable, n_stages: int, n_micro: int,
                  axis: str = "pod"):
    """Build the per-shard pipeline body.

    ``stage_fn(stage_params, x, stage_idx) -> x`` is one stage's compute.
    The returned body has signature ``(stage_params_local, x_micro) -> y``
    with ``x_micro`` (n_micro, mb, ...) resident on every stage (only stage 0
    reads it) and y (n_micro, mb, ...) produced by the last stage (garbage on
    other stages; caller masks/selects).
    Must run inside ``shard_map`` over ``axis``.
    """

    def body(stage_params: Any, x_micro: jax.Array) -> jax.Array:
        stage = lax.axis_index(axis)
        n_total = n_micro + n_stages - 1
        mb_shape = x_micro.shape[1:]
        state = jnp.zeros(mb_shape, x_micro.dtype)     # in-flight activation
        out = jnp.zeros_like(x_micro)

        fwd = [(i, (i + 1) % n_stages) for i in range(n_stages)]

        def tick(carry, t):
            state, out = carry
            # stage 0 ingests microbatch t (when in range)
            inject = x_micro[jnp.minimum(t, n_micro - 1)]
            state = jnp.where(stage == 0, inject, state)
            state = stage_fn(stage_params, state, stage)
            # last stage drains microbatch t-(S-1)
            emit_idx = t - (n_stages - 1)
            emit = jnp.logical_and(stage == n_stages - 1, emit_idx >= 0)
            out = lax.cond(
                emit,
                lambda o: lax.dynamic_update_index_in_dim(
                    o, state, jnp.maximum(emit_idx, 0), 0),
                lambda o: o, out)
            # shift stage -> stage+1 (the wrap edge's payload is ignored)
            state = lax.ppermute(state, axis, fwd)
            return (state, out), None

        (state, out), _ = lax.scan(tick, (state, out),
                                   jnp.arange(n_total))
        # only the last stage wrote `out` (zeros elsewhere); make it
        # replicated so the P() out_spec is honest
        return lax.psum(out, axis)

    return body


def make_pipeline_fn(stage_fn: Callable, mesh: Mesh, n_micro: int,
                     axis: str = "pod"):
    """jit-ready pipelined apply.

    ``stage_params`` pytree must have a leading stage axis (== axis size);
    inputs/outputs (n_micro, mb, ...) are replicated across the pipe axis.
    """
    n_stages = mesh.shape[axis]
    body = spmd_pipeline(stage_fn, n_stages, n_micro, axis)

    def wrapped(stage_params_local, x_micro):
        # stage params arrive with a leading length-1 stage shard; drop it
        sp = jax.tree.map(lambda a: a[0], stage_params_local)
        return body(sp, x_micro)

    pspec = P(axis)   # prefix spec: leading stage axis on every param leaf
    xspec = P()       # microbatch tensor replicated across the pipe axis
    f = jax.shard_map(wrapped, mesh=mesh, in_specs=(pspec, xspec),
                      out_specs=xspec, check_vma=False)
    return jax.jit(f)
