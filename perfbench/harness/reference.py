"""Plain reference of the decoder-only transformer the configurations run.

Straight ``jax.numpy`` in float32 at ``HIGHEST`` matmul precision, with no
kernels, cache, paging or batching of requests: each sequence runs whole,
causal attention over all its positions, layer by layer, with the layer's
weights made again from the seed (:mod:`harness.model`). It imports nothing
of the program.

What it computes, per layer (pre-norm; RMSNorm with the file's ``eps``;
rotary embedding in the rotate-half form; grouped-query attention scaled by
``attention_multiplier``): ``x += o(attn(norm(x)))``, then ``x += ffn(norm(x))``
where ``ffn`` is the gated SiLU MLP, or for a mixture of experts the softmax
router's top-k experts, their gates renormalized to sum to 1, every chosen
expert applied to every token (no capacity, nothing dropped). Logits are the
final norm times the tied embedding or the untied head.

``lowp=True`` is the control: every matmul operand rounded to float8
(e4m3) with one scale per row or output channel, the next precision down
from the bfloat16 the configurations state.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from harness.model import Model, global_params, layer_params

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


def _attention(m: Model, q, k, v, lowp):
    """Causal grouped-query attention, in blocks of queries so the score
    matrix fits. q (N, S, H, D); k, v (N, S, Hkv, D)."""
    N, S, H, D = q.shape
    rep = H // m.kv_heads
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
        sc = jnp.einsum("nqhd,nkhd->nhqk", qs, k, precision=HI) * m.attn_scale
        qpos = s0 + jnp.arange(qs.shape[1])[:, None]
        sc = jnp.where(kpos <= qpos, sc, -jnp.inf)
        p = jax.nn.softmax(sc, axis=-1)
        if lowp:
            p = q8(p, -1)
        outs.append(jnp.einsum("nhqk,nkhd->nqhd", p, v, precision=HI))
    return jnp.concatenate(outs, axis=1)


@functools.partial(jax.jit, static_argnums=(0, 4))
def _layer(m: Model, x, w, rope, lowp):
    w = {n: a.astype(jnp.float32) for n, a in w.items()}
    cos, sin = rope
    N, S, d = x.shape
    hd = m.head_dim
    h = _rms(x, w["layers/ln_attn/gamma"], m.eps)
    q = _mm("nsd,de->nse", h, w["layers/attn_q/kernel"], lowp)
    k = _mm("nsd,de->nse", h, w["layers/attn_k/kernel"], lowp)
    v = _mm("nsd,de->nse", h, w["layers/attn_v/kernel"], lowp)
    q = _rope(q.reshape(N, S, m.heads, hd), cos, sin)
    k = _rope(k.reshape(N, S, m.kv_heads, hd), cos, sin)
    v = v.reshape(N, S, m.kv_heads, hd)
    a = _attention(m, q, k, v, lowp).reshape(N, S, m.heads * hd)
    x = x + _mm("nse,ed->nsd", a, w["layers/attn_o/kernel"], lowp)
    h = _rms(x, w["layers/ln_mlp/gamma"], m.eps)
    if m.family == "moe":
        probs = jax.nn.softmax(
            jnp.einsum("nsd,de->nse", h, w["layers/moe_router/kernel"],
                       precision=HI), axis=-1)
        top, idx = jax.lax.top_k(probs, m.top_k)
        top = top / jnp.sum(top, -1, keepdims=True)
        gates = jnp.sum(jax.nn.one_hot(idx, m.experts) * top[..., None], -2)
        g = _mm("nsd,edf->nsef", h, w["layers/moe_wi_gate"], lowp, -1, 1)
        u = _mm("nsd,edf->nsef", h, w["layers/moe_wi_up"], lowp, -1, 1)
        act = jax.nn.silu(g) * u * gates[..., None]
        y = _mm("nsef,efd->nsd", act, w["layers/moe_wo"], lowp, -1, 1)
    else:
        g = _mm("nsd,df->nsf", h, w["layers/mlp_gate/kernel"], lowp)
        u = _mm("nsd,df->nsf", h, w["layers/mlp_up/kernel"], lowp)
        y = _mm("nsf,fd->nsd", jax.nn.silu(g) * u,
                w["layers/mlp_down/kernel"], lowp)
    return x + y


@functools.partial(jax.jit, static_argnums=(0, 3))
def _embed(m: Model, tokens, g, lowp):
    x = jnp.take(g["tok_emb/W"].astype(jnp.float32), tokens, axis=0)
    return q8(x, -1) if lowp else x


@functools.partial(jax.jit, static_argnums=(0, 4))
def _head(m: Model, x, want, g, lowp):
    """Logits (N, P, V) at positions ``want`` (N, P)."""
    x = jnp.take_along_axis(x, want[..., None], axis=1)
    x = _rms(x, g["ln_final/gamma"].astype(jnp.float32), m.eps)
    if m.tied:
        return _mm("npd,vd->npv", x, g["tok_emb/W"].astype(jnp.float32),
                   lowp, -1, -1)
    return _mm("npd,dv->npv", x, g["lm_head/kernel"].astype(jnp.float32),
               lowp)


def _rope_tables(m: Model, S: int):
    inv = 1.0 / (m.rope_theta ** (jnp.arange(0, m.head_dim, 2,
                                             dtype=jnp.float32) / m.head_dim))
    f = jnp.arange(S, dtype=jnp.float32)[:, None] * inv[None, :]
    return jnp.cos(f)[None, :, None, :], jnp.sin(f)[None, :, None, :]


def logits(m: Model, seed: int, tokens, want, *, lowp: bool = False):
    """float32 logits (N, P, V) of the sequences ``tokens`` (N, S) at
    positions ``want`` (N, P): row n, entry p predicts the token that
    follows position ``want[n, p]``. Sequences are right-padded; causal
    attention keeps the pads out of every earlier position."""
    tokens = jnp.asarray(tokens, jnp.int32)
    g = global_params(m, seed)
    x = _embed(m, tokens, g, lowp)
    rope = _rope_tables(m, tokens.shape[1])
    for layer in range(m.layers):
        x = _layer(m, x, layer_params(m, seed, layer), rope, lowp)
    return _head(m, x, jnp.asarray(want, jnp.int32), g, lowp)
