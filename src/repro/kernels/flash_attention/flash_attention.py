"""Flash attention as a Pallas TPU kernel.

TPU-native adaptation of the (GPU-origin) flash-attention insight: stream KV
blocks through VMEM against a resident Q block with an online softmax, so the
(Sq, Sk) logits matrix never exists in HBM. Tiling is MXU-aligned
(block_q x block_k >= 128x128, head_dim lanes = 128) and the accumulator
lives in VMEM scratch that persists across the innermost (KV) grid axis —
the TPU grid is sequential, which replaces the GPU kernel's thread-block
reduction with a legal cross-step carry.

The wrappers lay q, k and v out head-major, ``(B, H, S, D)``, so every
block is a ``(rows, D)`` tile of one head: a one-head slice of a
``(B, S, H, D)`` array would put a size-1 block on the sublane axis, which
the TPU compiler rejects. GQA is handled in the index maps (query head h
reads KV head h // group). Causal masking skips fully-masked KV blocks via
``pl.when``.

Validated against ``ref.mha_reference`` in interpret mode (CPU container)
and compiled for TPU v5e (``tests/test_tpu_compile.py``).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BLOCK_Q = 128
DEFAULT_BLOCK_K = 128
NEG_INF = -1e30


def online_softmax_step(q, k, v, mask, scale, m_scr, l_scr, acc_scr):
    """One KV block of the flash recurrence: q (rows, D), k/v (bk, D),
    carries m/l (rows, 1) and acc (rows, D) in VMEM scratch."""
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    s = jnp.where(mask, s, NEG_INF)
    m_prev = m_scr[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new)
    l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
    acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_scr[...] = m_new


def init_carry(m_scr, l_scr, acc_scr):
    m_scr[...] = jnp.full_like(m_scr, NEG_INF)
    l_scr[...] = jnp.zeros_like(l_scr)
    acc_scr[...] = jnp.zeros_like(acc_scr)


def carry_output(l_scr, acc_scr, dtype):
    """The attention output the carry holds: acc / l, in ``dtype``."""
    return (acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)).astype(dtype)


def _carry_scratch(rows: int, D: int):
    return [pltpu.VMEM((rows, 1), jnp.float32),      # m
            pltpu.VMEM((rows, 1), jnp.float32),      # l
            pltpu.VMEM((rows, D), jnp.float32)]      # acc


def _fa_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
               scale: float, causal: bool, window: int | None,
               block_q: int, block_k: int, seq_k: int):
    qi = pl.program_id(2)
    ki = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ki == 0)
    def _init():
        init_carry(m_scr, l_scr, acc_scr)

    q_start = qi * block_q
    k_start = ki * block_k

    # Whole-block skip: in causal mode a KV block strictly above the
    # diagonal (and, with a window, one entirely below it) contributes
    # nothing -> don't even load it.
    run = True
    if causal:
        run = k_start <= q_start + block_q - 1
    if window is not None:
        run = jnp.logical_and(
            run, k_start + block_k - 1 >= q_start - window + 1)

    @pl.when(run)
    def _body():
        qpos = q_start + jax.lax.broadcasted_iota(jnp.int32,
                                                  (block_q, block_k), 0)
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32,
                                                  (block_q, block_k), 1)
        mask = kpos < seq_k
        if causal:
            mask &= qpos >= kpos
        if window is not None:
            mask &= (qpos - kpos) < window
        online_softmax_step(q_ref[0, 0].astype(jnp.float32),
                            k_ref[0, 0].astype(jnp.float32),
                            v_ref[0, 0].astype(jnp.float32),
                            mask, scale, m_scr, l_scr, acc_scr)

    @pl.when(ki == nk - 1)
    def _fini():
        o_ref[0, 0] = carry_output(l_scr, acc_scr, o_ref.dtype)


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, window: int | None = None,
                    scale: float | None = None,
                    block_q: int = DEFAULT_BLOCK_Q,
                    block_k: int = DEFAULT_BLOCK_K,
                    interpret: bool = False) -> jax.Array:
    """q (B, Sq, Hq, D); k/v (B, Sk, Hkv, D) -> (B, Sq, Hq, D)."""
    B, Sq, Hq, D = q.shape
    _, Sk, Hkv, _ = k.shape
    if Hq % Hkv:
        raise ValueError(f"Hq={Hq} not a multiple of Hkv={Hkv}")
    group = Hq // Hkv
    scale = scale if scale is not None else 1.0 / math.sqrt(D)

    bq = min(block_q, Sq)
    bk = min(block_k, Sk)
    # pad seq lengths to block multiples
    Sq_p = -(-Sq // bq) * bq
    Sk_p = -(-Sk // bk) * bk
    if Sq_p != Sq:
        q = jnp.pad(q, ((0, 0), (0, Sq_p - Sq), (0, 0), (0, 0)))
    if Sk_p != Sk:
        k = jnp.pad(k, ((0, 0), (0, Sk_p - Sk), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, Sk_p - Sk), (0, 0), (0, 0)))
    # head-major: (B, H, S, D)
    q, k, v = (a.transpose(0, 2, 1, 3) for a in (q, k, v))

    kernel = functools.partial(
        _fa_kernel, scale=scale, causal=causal, window=window,
        block_q=bq, block_k=bk, seq_k=Sk)
    q_spec = pl.BlockSpec((1, 1, bq, D), lambda b, h, qi, ki: (b, h, qi, 0))
    kv_spec = pl.BlockSpec((1, 1, bk, D),
                           lambda b, h, qi, ki, g=group: (b, h // g, ki, 0))
    out = pl.pallas_call(
        kernel,
        grid=(B, Hq, Sq_p // bq, Sk_p // bk),
        in_specs=[q_spec, kv_spec, kv_spec],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hq, Sq_p, D), q.dtype),
        scratch_shapes=_carry_scratch(bq, D),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(q, k, v)
    return out.transpose(0, 2, 1, 3)[:, :Sq]


def _fd_kernel(len_ref, q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr,
               *, scale: float, block_k: int):
    b = pl.program_id(0)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        init_carry(m_scr, l_scr, acc_scr)

    length = len_ref[b]
    k_start = ki * block_k

    @pl.when(k_start < length)
    def _body():
        q = q_ref[0, 0].astype(jnp.float32)                 # (rep, D)
        kpos = k_start + jax.lax.broadcasted_iota(
            jnp.int32, (q.shape[0], block_k), 1)
        online_softmax_step(q, k_ref[0, 0].astype(jnp.float32),
                            v_ref[0, 0].astype(jnp.float32),
                            kpos < length, scale, m_scr, l_scr, acc_scr)

    @pl.when(ki == nk - 1)
    def _fini():
        o_ref[0, 0] = carry_output(l_scr, acc_scr, o_ref.dtype)


def flash_decode(q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
                 lengths: jax.Array, *, scale: float | None = None,
                 block_k: int = 512, interpret: bool = False) -> jax.Array:
    """Single-token decode. q (B, 1, Hq, D); caches (B, Smax, Hkv, D);
    lengths (B,). Grid streams the cache; one (batch, kv-head) per step with
    the query's ``rep`` grouped heads resident. Blocks past a row's length
    keep the last live block's index, so they cost no DMA."""
    B, one, Hq, D = q.shape
    assert one == 1
    _, Smax, Hkv, _ = k_cache.shape
    rep = Hq // Hkv
    scale = scale if scale is not None else 1.0 / math.sqrt(D)
    bk = min(block_k, Smax)
    Sk_p = -(-Smax // bk) * bk
    if Sk_p != Smax:
        k_cache = jnp.pad(k_cache, ((0, 0), (0, Sk_p - Smax), (0, 0), (0, 0)))
        v_cache = jnp.pad(v_cache, ((0, 0), (0, Sk_p - Smax), (0, 0), (0, 0)))
    # head-major: q (B, Hkv, rep, D), caches (B, Hkv, S, D)
    qh = q.reshape(B, Hkv, rep, D)
    k_cache, v_cache = (a.transpose(0, 2, 1, 3) for a in (k_cache, v_cache))

    def kv_map(b, h, ki, len_ref):
        last = jnp.maximum(len_ref[b] - 1, 0) // bk
        return b, h, jnp.minimum(ki, last), 0

    q_spec = pl.BlockSpec((1, 1, rep, D), lambda b, h, ki, *_: (b, h, 0, 0))
    kv_spec = pl.BlockSpec((1, 1, bk, D), kv_map)
    out = pl.pallas_call(
        functools.partial(_fd_kernel, scale=scale, block_k=bk),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,            # lengths
            grid=(B, Hkv, Sk_p // bk),
            in_specs=[q_spec, kv_spec, kv_spec],
            out_specs=q_spec,
            scratch_shapes=_carry_scratch(rep, D),
        ),
        out_shape=jax.ShapeDtypeStruct((B, Hkv, rep, D), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(jnp.asarray(lengths, jnp.int32), qh, k_cache, v_cache)
    return out.reshape(B, 1, Hq, D)
