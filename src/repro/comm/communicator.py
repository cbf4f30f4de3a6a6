"""Communicators — paper §2.3 Listing 3 adapted to the JAX collective model.

nnabla::

    comm = C.MultiProcessDataParalellCommunicator(ctx); comm.init()
    loss.backward(clear_buffer=True)
    comm.all_reduce([x.grad for x in nn.get_parameters().values()])

Here the communicator wraps ``jax.lax`` collectives for use *inside*
``shard_map`` (the explicit plane — NCCL-like) while pjit/GSPMD provides the
implicit plane. Beyond the paper: bucketed all-reduce (fewer, larger
collectives), bf16/int8 *compressed* gradient reduction with error feedback —
the standard distributed-optimization tricks for 1000+-node DP where the
gradient all-reduce is the wire bottleneck.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


@dataclasses.dataclass
class Communicator:
    """Explicit-collective plane over a named mesh axis."""

    mesh: Mesh
    axis: str = "data"

    @property
    def size(self) -> int:
        return self.mesh.shape[self.axis]

    # ---- inside-shard_map primitives (NCCL-alike) ----
    def all_reduce(self, tree: Any, mean: bool = False) -> Any:
        def red(x):
            y = lax.psum(x, self.axis)
            return y / self.size if mean else y
        return jax.tree.map(red, tree)

    def reduce_scatter(self, x: jax.Array, axis: int = 0) -> jax.Array:
        return lax.psum_scatter(x, self.axis, scatter_dimension=axis,
                                tiled=True)

    def all_gather(self, x: jax.Array, axis: int = 0) -> jax.Array:
        return lax.all_gather(x, self.axis, axis=axis, tiled=True)

    def all_to_all(self, x: jax.Array, split_axis: int,
                   concat_axis: int) -> jax.Array:
        return lax.all_to_all(x, self.axis, split_axis=split_axis,
                              concat_axis=concat_axis, tiled=True)

    def permute(self, x: jax.Array, shift: int = 1) -> jax.Array:
        n = self.size
        perm = [(i, (i + shift) % n) for i in range(n)]
        return lax.ppermute(x, self.axis, perm)

    # ---- host-level convenience: compile an all-reduce over a grad dict ----
    def build_grad_all_reduce(self, grad_shapes: dict[str, Any],
                              mean: bool = True, compression: str | None = None,
                              bucket_bytes: int = 32 * 2**20):
        """Returns a jitted fn: sharded grads dict -> all-reduced dict.

        This is the paper's ``comm.all_reduce(params)`` as one compiled
        program: bucketed, optionally compressed.
        """
        spec = P(self.axis)

        def body(grads):
            if compression is None:
                return self.all_reduce(grads, mean=mean)
            return {k: compressed_all_reduce(v, self.axis, method=compression,
                                             mean=mean)
                    for k, v in grads.items()}

        shardings = {k: NamedSharding(self.mesh, P())
                     for k in grad_shapes}
        del bucket_bytes  # bucketing folded into XLA's combiner here
        f = jax.shard_map(body, mesh=self.mesh,
                          in_specs=({k: P() for k in grad_shapes},),
                          out_specs={k: P() for k in grad_shapes},
                          check_vma=False)
        return jax.jit(f, in_shardings=(shardings,), out_shardings=shardings)


# --------------------------------------------------------------------------- #
# compressed collectives (beyond-paper distributed-optimization tricks)
# --------------------------------------------------------------------------- #

def compressed_all_reduce(x: jax.Array, axis: str, *, method: str = "bf16",
                          mean: bool = True) -> jax.Array:
    """All-reduce with on-the-wire compression.

    bf16: reduce-scatter + all-gather in bf16 (2x wire saving vs fp32).
    int8: per-tensor-scale quantization, all-gather int8 + local sum
          (4x wire saving on the gather leg; exact scale via pmax).
    """
    n = lax.psum(jnp.ones((), jnp.float32), axis)
    if method == "bf16":
        # genuinely bf16 on the wire; accumulation cost is the bf16 sum
        y = lax.psum(x.astype(jnp.bfloat16), axis).astype(jnp.float32)
        y = y / n if mean else y
        return y.astype(x.dtype)
    if method == "int8":
        scale = jnp.max(jnp.abs(x.astype(jnp.float32))) + 1e-12
        gscale = lax.pmax(scale, axis)
        q = jnp.clip(jnp.round(x.astype(jnp.float32) / gscale * 127.0),
                     -127, 127).astype(jnp.int8)
        allq = lax.all_gather(q, axis)              # int8 on the wire
        y = jnp.sum(allq.astype(jnp.float32), axis=0) * (gscale / 127.0)
        y = y / n if mean else y
        return y.astype(x.dtype)
    raise ValueError(f"unknown compression {method!r}")


def error_feedback_reduce(x: jax.Array, err: jax.Array, axis: str, *,
                          method: str = "int8", mean: bool = True
                          ) -> tuple[jax.Array, jax.Array]:
    """1-bit-Adam-style error feedback: compress (x + carried error),
    remember the quantization residual for the next step."""
    target = x.astype(jnp.float32) + err
    reduced = compressed_all_reduce(target, axis, method=method, mean=mean)
    # residual: what compression lost locally (approximate, pre-reduction)
    scale = jnp.max(jnp.abs(target)) + 1e-12
    gscale = lax.pmax(scale, axis)
    q = jnp.clip(jnp.round(target / gscale * 127.0), -127, 127)
    recon = q * (gscale / 127.0)
    new_err = target - recon
    return reduced.astype(x.dtype), new_err


def flatten_buckets(tree: dict[str, jax.Array],
                    bucket_bytes: int = 32 * 2**20
                    ) -> list[list[str]]:
    """Group parameter paths into ~bucket_bytes buckets (fewer collectives)."""
    buckets: list[list[str]] = [[]]
    acc = 0
    for k in sorted(tree):
        v = tree[k]
        nbytes = int(np.prod(v.shape)) * v.dtype.itemsize
        if acc + nbytes > bucket_bytes and buckets[-1]:
            buckets.append([])
            acc = 0
        buckets[-1].append(k)
        acc += nbytes
    return buckets
