"""Plain reference of the decoder-only models the configurations run.

Straight ``jax.numpy`` in float32 at ``HIGHEST`` matmul precision, with no
kernels, cache, paging or batching of requests: each sequence runs whole,
layer by layer, with the layer's weights made again from the seed
(:mod:`harness.model`). It imports nothing of the program.

:func:`logits` holds the loop; the model's architecture module
(``archs/<model_type>.py``) gives the embedding, each layer's equation and
the head, built from the shared helpers here: :func:`q8`, ``_mm``,
``_rms``, ``_rope`` and ``_attention``.

``lowp=True`` is the control: every matmul operand rounded to float8
(e4m3) with one scale per row or output channel, the next precision down
from the bfloat16 the configurations state.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from harness.model import global_params, layer_params

HI = jax.lax.Precision.HIGHEST
F8 = jnp.float8_e4m3fn
F8_MAX = 448.0


def q8(a, axis):
    """Round ``a`` to float8 with one scale per slice along ``axis``."""
    s = jnp.max(jnp.abs(a), axis=axis, keepdims=True) / F8_MAX
    s = jnp.where(s > 0, s, 1.0)
    return (a / s).astype(F8).astype(jnp.float32) * s


def _mm(eq, a, b, lowp, a_axis=-1, b_axis=0):
    if lowp:
        a, b = q8(a, a_axis), q8(b, b_axis)
    return jnp.einsum(eq, a, b, precision=HI)


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def _rope(x, cos, sin):
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(q, k, v, scale, lowp, window=None):
    """Causal grouped-query attention, in blocks of queries so the score
    matrix fits. q (N, S, H, D); k, v (N, S, Hkv, D). With ``window``,
    query ``i`` sees only keys ``i - window + 1 .. i``."""
    N, S, H, D = q.shape
    rep = H // k.shape[2]
    k = jnp.repeat(k, rep, axis=2)
    v = jnp.repeat(v, rep, axis=2)
    if lowp:
        q, k, v = q8(q, -1), q8(k, -1), q8(v, -1)
    qb = S
    while qb > 128 and N * H * qb * S * 4 > (1 << 29):
        qb //= 2
    outs = []
    kpos = jnp.arange(S)[None, :]
    for s0 in range(0, S, qb):
        qs = q[:, s0:s0 + qb]
        sc = jnp.einsum("nqhd,nkhd->nhqk", qs, k, precision=HI) * scale
        qpos = s0 + jnp.arange(qs.shape[1])[:, None]
        seen = kpos <= qpos
        if window is not None:
            seen = seen & (kpos > qpos - window)
        sc = jnp.where(seen, sc, -jnp.inf)
        p = jax.nn.softmax(sc, axis=-1)
        if lowp:
            p = q8(p, -1)
        outs.append(jnp.einsum("nhqk,nkhd->nqhd", p, v, precision=HI))
    return jnp.concatenate(outs, axis=1)


def logits(m, seed: int, tokens, want, *, lowp: bool = False):
    """float32 logits (N, P, V) of the sequences ``tokens`` (N, S) at
    positions ``want`` (N, P): row n, entry p predicts the token that
    follows position ``want[n, p]``. Sequences are right-padded; causal
    attention keeps the pads out of every earlier position."""
    tokens = jnp.asarray(tokens, jnp.int32)
    g = global_params(m, seed)
    x = m.embed(tokens, g, lowp)
    pos = m.positions(tokens.shape[1])
    for layer in range(m.layers):
        x = m.layer(layer, x, layer_params(m, seed, layer), pos, lowp)
    return m.head(x, jnp.asarray(want, jnp.int32), g, lowp)
