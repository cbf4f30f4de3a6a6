"""Quantized KV block pools: dtypes, scales, and the jnp quant/dequant.

The paged KV pools can be stored in int8 or fp8 (e4m3fn) instead of the
compute dtype, halving (vs bf16) the HBM bytes every block costs — the
block budget every other subsystem
(tiering, spec decode, tp sharding) spends doubles for free. This module
owns the *scheme*; the kernels (:mod:`.flash_attention.paged_attention`)
and the XLA references (:mod:`.flash_attention.ref`) own the fusion.

Scheme: symmetric absmax, one scale per written **token slot per
kv-head** — scale arrays shaped ``(num_blocks, block_size, Hkv)``
(float32) ride alongside each ``(num_blocks, block_size, Hkv, D)`` pool.
Why per-(slot, head) rather than the coarser per-(block, head):

* **Pure scatter.** Decode writes one token into a partially-filled
  block. A block-granular scale would need the block's other slots
  re-scaled on every write (read-modify-write, breaking the donated
  fused scatter); a per-slot scale is computed from the written token
  alone and lands through the same output index map.
* **Speculative decode stays bitwise.** The engine guarantees the
  spec-k stream equals the spec-0 stream. A block-wide absmax would
  make accepted tokens' quantized values depend on *rejected* draft
  tokens sharing the block; per-slot scales keep each token's stored
  bytes a pure function of that token.

The byte cost: scales add 4 bytes per token per head next to ``D``
payload bytes, so int8 + scales is ``(D + 4) / (2 * D)`` of bf16 —
0.53x at D = 64. Per-channel (per-D-lane) scales are a recorded
follow-on (ROADMAP), as is an int4 packed layout.
"""

from __future__ import annotations

from typing import Any

import jax.numpy as jnp

SCALE_DTYPE = jnp.float32

FP8_DTYPE = jnp.float8_e4m3fn

_QMAX = {jnp.dtype(jnp.int8): 127.0,
         jnp.dtype(FP8_DTYPE): 448.0}     # e4m3fn max finite

# floor on the absmax so a silent/zero token quantizes to zeros with a
# harmless scale instead of dividing by zero
_EPS = 1e-12


def is_quantized(dtype: Any) -> bool:
    """True when ``dtype`` is a quantized KV storage dtype (needs scales)."""
    return jnp.dtype(dtype) in _QMAX


def qmax(dtype: Any) -> float:
    """Largest representable magnitude used as the absmax target."""
    return _QMAX[jnp.dtype(dtype)]


def resolve_kv_dtype(name: str | None, compute_dtype: Any):
    """Map a ``--kv-dtype`` string to a concrete storage dtype.

    ``None``/"native" keep the compute dtype (unquantized).
    """
    if name is None or name in ("", "native"):
        return jnp.dtype(compute_dtype)
    table = {
        "int8": jnp.int8,
        "fp8": FP8_DTYPE,
        "bf16": jnp.bfloat16, "bfloat16": jnp.bfloat16,
        "fp16": jnp.float16, "float16": jnp.float16, "half": jnp.float16,
        "fp32": jnp.float32, "float32": jnp.float32,
    }
    if name not in table:
        raise ValueError(f"unknown kv dtype {name!r} (expected int8, fp8, "
                         f"bf16, fp16, fp32 or native)")
    return jnp.dtype(table[name])


def kv_dtype_name(dtype: Any) -> str:
    """Canonical short name for reporting (metrics line, CacheSpec)."""
    d = jnp.dtype(dtype)
    if d == jnp.dtype(jnp.int8):
        return "int8"
    if d == jnp.dtype(FP8_DTYPE):
        return "fp8"
    return {"bfloat16": "bf16", "float16": "fp16",
            "float32": "fp32"}.get(d.name, d.name)


def quantize(x, qdtype):
    """Quantize ``(..., D)`` to ``qdtype`` with per-``(...)`` absmax scales.

    Returns ``(q, scale)`` where ``q`` has ``x``'s shape in ``qdtype``
    and ``scale`` is float32 shaped like ``x`` minus the last axis, such
    that ``q * scale[..., None] ~= x``. Matches the Pallas fused-write
    kernel op-for-op (f32 absmax, round-to-nearest for ints) so the XLA
    and kernel paths produce bit-identical pools.
    """
    qd = jnp.dtype(qdtype)
    xf = x.astype(jnp.float32)
    amax = jnp.maximum(jnp.max(jnp.abs(xf), axis=-1), _EPS)
    # explicit reciprocal multiply: XLA rewrites division-by-constant to
    # this anyway, but inconsistently across lowering contexts — writing
    # the multiply keeps the fused Pallas write bit-identical to this path
    scale = (amax * (1.0 / qmax(qd))).astype(SCALE_DTYPE)
    q = xf / scale.astype(jnp.float32)[..., None]
    if jnp.issubdtype(qd, jnp.integer):
        q = jnp.clip(jnp.round(q), -qmax(qd), qmax(qd))
    return q.astype(qd), scale


def dequantize(q, scale, out_dtype=jnp.float32):
    """Inverse of :func:`quantize`: ``q (..., D)`` times ``scale (...)``."""
    return (q.astype(jnp.float32)
            * scale.astype(jnp.float32)[..., None]).astype(out_dtype)
