"""Mamba-2 SSD scan as a Pallas TPU kernel.

TPU adaptation of the SSD block decomposition: one (batch, head) per outer
grid cell, chunks streamed along the innermost (sequential) grid axis with
the running (N, P) state held in VMEM scratch — the recurrence carries across
grid steps instead of across GPU thread blocks. Per chunk the kernel does the
three MXU matmuls of the duality form (C·Bᵀ masked by the decay matrix,
state read-out, state update) in fp32.

Layout: the wrapper lays every operand out head-major (``(B, H, S, P)``,
``(B, G, S, N)``) so each block is a ``(Q, P)`` / ``(Q, N)`` tile of one
head — a one-head slice of a ``(B, S, H, P)`` array is a size-1 sublane
block, which the TPU compiler rejects. The elementwise prologue
(``x * dt``, ``dt * A``) and the ``D`` skip term run in XLA around the
kernel. The per-chunk log-decay arrives as a ``(1, Q)`` row; Mosaic has
no cumsum, so the kernel builds the cumulative decay as masked lane and
sublane sums.

Validated against ``ref.ssd_chunked``/``ref.ssd_naive`` in interpret mode
and compiled for TPU v5e (``tests/test_tpu_compile.py``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_kernel(x_ref, ld_ref, b_ref, c_ref, y_ref, hout_ref, h_scr, *,
                chunk: int):
    ci = pl.program_id(2)
    nc = pl.num_programs(2)

    @pl.when(ci == 0)
    def _init():
        h_scr[...] = jnp.zeros_like(h_scr)

    xbar = x_ref[0, 0].astype(jnp.float32)             # (Q, P)  x * dt
    Bm = b_ref[0, 0].astype(jnp.float32)               # (Q, N)
    Cm = c_ref[0, 0].astype(jnp.float32)               # (Q, N)
    logd = ld_ref[0, 0, 0]                             # (1, Q)  dt * A

    i = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    j = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    # cumulative log decay as a column (csum_c[i] = sum_{j<=i} logd[j])
    # and as a row; the row needs logd as a column first (diagonal pick)
    csum_c = jnp.sum(jnp.where(j <= i, logd, 0.0), axis=1, keepdims=True)
    logd_c = jnp.sum(jnp.where(j == i, logd, 0.0), axis=1, keepdims=True)
    csum_r = jnp.sum(jnp.where(i <= j, logd_c, 0.0), axis=0, keepdims=True)

    # intra-chunk: masked (C Bᵀ) with pairwise decay i <- j
    L = jnp.where(i >= j, jnp.exp(csum_c - csum_r), 0.0)   # (Q, Q)
    scores = jax.lax.dot_general(Cm, Bm, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
    y = jax.lax.dot_general(scores * L, xbar, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)  # (Q, P)

    # inter-chunk: read out the carried state
    h_prev = h_scr[...]                                # (N, P)
    y += jax.lax.dot_general(Cm * jnp.exp(csum_c), h_prev,
                             (((1,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)
    y_ref[0, 0] = y

    # state update: h = exp(sum logd) h + (B * decay_to_end)ᵀ xbar
    total = csum_c[chunk - 1:, :]                      # (1, 1)
    h_new = jnp.exp(total) * h_prev \
        + jax.lax.dot_general(Bm * jnp.exp(total - csum_c), xbar,
                              (((0,), (0,)), ((), ())),
                              preferred_element_type=jnp.float32)
    h_scr[...] = h_new

    @pl.when(ci == nc - 1)
    def _emit():
        hout_ref[0, 0] = h_new


def ssd(x, dt, A, Bm, Cm, D=None, *, chunk: int = 64, h0=None,
        return_state: bool = False, interpret: bool = False):
    """Pallas SSD. Shapes as in :mod:`repro.kernels.ssd.ref`.

    ``h0`` is not supported in-kernel (prefill starts cold); callers that
    split sequences across calls combine states at the ref layer.
    """
    if h0 is not None:
        raise NotImplementedError("kernel path starts from h=0; "
                                  "use the ref for stateful continuation")
    B, S, H, P = x.shape
    G, N = Bm.shape[2], Bm.shape[3]
    if S % chunk:
        raise ValueError(f"S={S} % chunk={chunk} != 0")
    nc = S // chunk
    group = H // G

    dt = dt.astype(jnp.float32)
    # head-major operands: (B, H, S, P), (B, H, nc, 1, Q), (B, G, S, N)
    xbar = (x.astype(jnp.float32) * dt[..., None]).transpose(0, 2, 1, 3)
    logd = (dt * A.astype(jnp.float32)).transpose(0, 2, 1) \
        .reshape(B, H, nc, 1, chunk)
    Bh, Ch = (m.transpose(0, 2, 1, 3) for m in (Bm, Cm))

    x_spec = pl.BlockSpec((1, 1, chunk, P), lambda b, h, ci: (b, h, ci, 0))
    bc_spec = pl.BlockSpec((1, 1, chunk, N),
                           lambda b, h, ci, g=group: (b, h // g, ci, 0))
    y, hT = pl.pallas_call(
        functools.partial(_ssd_kernel, chunk=chunk),
        grid=(B, H, nc),
        in_specs=[
            x_spec,
            pl.BlockSpec((1, 1, 1, 1, chunk),
                         lambda b, h, ci: (b, h, ci, 0, 0)),
            bc_spec,
            bc_spec,
        ],
        out_specs=[
            x_spec,
            pl.BlockSpec((1, 1, N, P), lambda b, h, ci: (b, h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, S, P), jnp.float32),
            jax.ShapeDtypeStruct((B, H, N, P), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((N, P), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="ssd",
    )(xbar, logd, Bh, Ch)

    y = y.transpose(0, 2, 1, 3)
    if D is not None:
        y = y + x.astype(jnp.float32) * D.astype(jnp.float32)[:, None]
    y = y.astype(x.dtype)
    if return_state:
        return y, jnp.moveaxis(hT, 2, 3)  # (B, H, P, N)
    return y
