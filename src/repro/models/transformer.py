"""Decoder-only LM transformer family: dense GQA, MoE, M-RoPE variants.

Covers phi3.5-moe, granite-moe, deepseek-coder, llama3.2, mistral-nemo,
granite-34b, qwen2-vl (backbone; patch embeddings stubbed upstream).

Written against the functional core (``PF``/``F`` on plain arrays inside
``nn.init``/``nn.apply``) so one definition serves the eager plane, the smoke
tests and the pjit distributed runtime. Activations carry logical-axis
annotations (:mod:`repro.distributed.sharding`) — the launcher's rule table
decides the physical layout.
"""

from __future__ import annotations

import math
from typing import Any

import jax
import jax.numpy as jnp
from jax import lax

import repro.core as nn
from repro.core import context as _ctx
from repro.core import functions as F
from repro.core import initializer as I
from repro.core import parametric as PF
from repro.configs.base import ModelConfig
from repro.distributed.sharding import constrain, named_zeros
from repro.kernels import ops as K
from repro.kernels import quant

MOE_AUX_COEF = 0.01


# --------------------------------------------------------------------------- #
# positions / rotary
# --------------------------------------------------------------------------- #

def default_positions(cfg: ModelConfig, B: int, S: int,
                      offset: jax.Array | int = 0) -> jax.Array:
    base = jnp.arange(S, dtype=jnp.int32)[None, :]
    off = jnp.asarray(offset, jnp.int32)
    if off.ndim == 1:  # per-row positions (continuous batching)
        pos = base + off[:, None]
    else:
        pos = base + off
    pos = jnp.broadcast_to(pos, (B, S))
    if cfg.mrope:  # text-only stream: t == h == w
        pos = jnp.broadcast_to(pos[..., None], (B, S, 3))
    return pos


def _mrope_sections(half: int) -> tuple[int, int, int]:
    """Qwen2-VL splits the rotary half-dim into (t, h, w) sections 1:1.5:1.5
    (e.g. 16/24/24 for head_dim 128)."""
    t = half // 4
    h = (half - t) // 2
    return t, h, half - t - h


def rope_tables(cfg: ModelConfig, positions: jax.Array
                ) -> tuple[jax.Array, jax.Array]:
    """cos/sin of shape (B, S, head_dim//2), fp32."""
    hd = cfg.resolved_head_dim
    half = hd // 2
    inv = 1.0 / (cfg.rope_theta ** (jnp.arange(0, hd, 2, jnp.float32) / hd))
    if cfg.mrope:
        assert positions.ndim == 3 and positions.shape[-1] == 3
        t, h, w = _mrope_sections(half)
        sec = jnp.concatenate([jnp.zeros(t, jnp.int32),
                               jnp.ones(h, jnp.int32),
                               jnp.full((w,), 2, jnp.int32)])
        pos = positions[..., sec]            # (B, S, half): component per freq
        freqs = pos.astype(jnp.float32) * inv[None, None, :]
    else:
        assert positions.ndim == 2
        freqs = positions.astype(jnp.float32)[..., None] * inv[None, None, :]
    return jnp.cos(freqs), jnp.sin(freqs)


# --------------------------------------------------------------------------- #
# building blocks
# --------------------------------------------------------------------------- #

def norm(cfg: ModelConfig, x, name: str):
    if cfg.norm == "layernorm":
        return PF.layer_normalization(x, name=name)
    return PF.rms_norm(x, name=name)


def _activate(cfg: ModelConfig, x):
    return F.gelu(x) if cfg.act == "gelu" else F.silu(x)


def attention(cfg: ModelConfig, x, cos, sin, *, name: str = "attn",
              causal: bool = True, window: int | None = None,
              cache: tuple[jax.Array, jax.Array] | None = None,
              cache_pos: jax.Array | None = None,
              pages: jax.Array | None = None,
              cross_kv: tuple[jax.Array, jax.Array] | None = None,
              use_rope: bool = True):
    """GQA attention. Returns (out, new_cache | None).

    ``cache``: (k, v) of shape (B, Smax, Hkv, hd) — decode path writes the new
    K/V at ``cache_pos`` and attends against the whole cache.
    ``pages``: (B, max_blocks) int32 page tables switching ``cache`` to the
    block-paged layout — (k, v) become (num_blocks, block_size, Hkv, hd)
    pools shared by all rows; writes scatter through the page table and
    reads gather through it (``K.attention_*_paged``).
    ``cross_kv``: precomputed encoder K/V (whisper cross-attention).
    """
    B, S, d = x.shape
    H, Kh, hd = cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim

    with jax.named_scope("attention/qkv"):
        q = PF.dense(x, H * hd, name=f"{name}_q", use_bias=cfg.qkv_bias)
        q = q.reshape(B, S, H, hd)
        if cross_kv is None:
            k = PF.dense(x, Kh * hd, name=f"{name}_k", use_bias=cfg.qkv_bias)
            v = PF.dense(x, Kh * hd, name=f"{name}_v", use_bias=cfg.qkv_bias)
            k = k.reshape(B, S, Kh, hd)
            v = v.reshape(B, S, Kh, hd)
            if use_rope:
                q = F.apply_rope(q, cos, sin)
                k = F.apply_rope(k, cos, sin)
        else:
            k, v = cross_kv

        q = constrain(q, "batch", "seq", "heads", "head_dim")
        if cross_kv is None:
            k = constrain(k, "batch", "seq", "kv_heads", "head_dim")
            v = constrain(v, "batch", "seq", "kv_heads", "head_dim")

    # Merged batch×kv-head sharding: when the head count doesn't divide the
    # model axis (deepseek: 56 H / 8 KV on a 16-wide axis), flatten
    # (batch, kv_head) into one dim that DOES divide the whole mesh — fully
    # local attention, zero attention collectives. (GQA groups stay intact:
    # each merged row is one kv head with its `rep` query heads.)
    # Long sequences only: at short (train) seqs the attention region is
    # cheap to replicate, while the merged layout's boundary resharding
    # lowers to XLA's replicate-then-partition path (see EXPERIMENTS §Perf).
    if cache is None and cross_kv is None and S >= 8192:
        from repro.distributed.sharding import get_env
        env = get_env()
        mesh = env.mesh
        if (mesh is not None and not mesh.empty and "model" in mesh.shape
                and H % mesh.shape["model"] != 0
                and (B * Kh) % (mesh.shape["model"]
                                * mesh.shape.get("data", 1)) == 0):
            rep = H // Kh
            qm = q.reshape(B, S, Kh, rep, hd).transpose(0, 2, 1, 3, 4) \
                .reshape(B * Kh, S, rep, hd)
            km = k.transpose(0, 2, 1, 3).reshape(B * Kh, S, 1, hd)
            vm = v.transpose(0, 2, 1, 3).reshape(B * Kh, S, 1, hd)
            qm = constrain(qm, "batch_kv", "seq", None, None)
            km = constrain(km, "batch_kv", "seq", None, None)
            vm = constrain(vm, "batch_kv", "seq", None, None)
            with jax.named_scope("attention/walk"):
                ym = K.attention(qm, km, vm, causal=causal, window=window,
                                 unroll=cfg.scan_unroll is True)
            ym = constrain(ym, "batch_kv", "seq", None, None)
            y = ym.reshape(B, Kh, S, rep, hd).transpose(0, 2, 1, 3, 4) \
                .reshape(B, S, H * hd)
            with jax.named_scope("attention/out"):
                out = PF.dense(y, d, name=f"{name}_o",
                               w_init=I.scaled_normal(1.0, H * hd))
            return constrain(out, "batch", "seq", "embed"), None

    if cache is None and cross_kv is None:
        # Degraded-heads short-seq case (e.g. deepseek 56H on model=16 at
        # train): shard the QUERY sequence over the model axis instead —
        # attention compute partitions 16x, softmax stays chip-local over
        # the full KV (k/v all-gathered once per layer, a few hundred MB).
        from repro.distributed.sharding import get_env
        env = get_env()
        mesh = env.mesh
        if (mesh is not None and not mesh.empty and "model" in mesh.shape
                and H % mesh.shape["model"] != 0
                and S % mesh.shape["model"] == 0):
            from repro.kernels.flash_attention import ref as _fa_ref
            q = constrain(q, "batch", "attn_seq", None, None)
            k = constrain(k, "batch", None, None, None)
            v = constrain(v, "batch", None, None, None)
            with jax.named_scope("attention/walk"):
                y = _fa_ref.mha_reference(q, k, v, causal=causal,
                                          window=window)
            y = constrain(y, "batch", "attn_seq", None, None)
            y = y.reshape(B, S, H * hd)
            with jax.named_scope("attention/out"):
                out = PF.dense(y, d, name=f"{name}_o",
                               w_init=I.scaled_normal(1.0, H * hd))
            return constrain(out, "batch", "seq", "embed"), None

    new_cache = None
    if cache is not None and pages is not None:
        # block-paged cache: scatter the chunk's K/V through the page table,
        # then attend through the gathered per-row view. ``cache_pos`` must
        # be per-row (B,) — the paged engine always schedules per-row.
        # A 4-tuple cache carries a quantized pool's (NB, bs, Hkv) scale
        # arrays; the quant/dequant fuses into the write/read kernels.
        quantized = len(cache) == 4
        if quantized:
            k_pool, v_pool, k_scale, v_scale = cache
        else:
            k_pool, v_pool = cache
            k_scale = v_scale = None
        pos_arr = jnp.asarray(cache_pos, jnp.int32)
        assert pos_arr.ndim == 1, "paged attention needs per-row positions"
        with jax.named_scope("attention/kv_write"):
            if quantized:
                k_pool, k_scale = K.paged_cache_write(
                    k_pool, k, pages, pos_arr, pool_scale=k_scale)
                v_pool, v_scale = K.paged_cache_write(
                    v_pool, v, pages, pos_arr, pool_scale=v_scale)
                k_scale = constrain(k_scale, None, None, "kv_heads")
                v_scale = constrain(v_scale, None, None, "kv_heads")
            else:
                k_pool = K.paged_cache_write(k_pool, k, pages, pos_arr)
                v_pool = K.paged_cache_write(v_pool, v, pages, pos_arr)
            # pin the pool's kv-head sharding through the scatter so GSPMD
            # carries it across layers (tp serving; no-op without a mesh)
            k_pool = constrain(k_pool, None, None, "kv_heads", "head_dim")
            v_pool = constrain(v_pool, None, None, "kv_heads", "head_dim")
        with jax.named_scope("attention/walk"):
            if S > 1:
                y = K.attention_prefill_paged(
                    q, k_pool, v_pool, pages, pos_arr,
                    k_scale=k_scale, v_scale=v_scale)
            else:
                y = K.attention_decode_paged(
                    q, k_pool, v_pool, pages, pos_arr + 1,
                    k_scale=k_scale, v_scale=v_scale)
        new_cache = (k_pool, v_pool, k_scale, v_scale) if quantized \
            else (k_pool, v_pool)
    elif cache is not None:
        k_cache, v_cache = cache
        assert cache_pos is not None
        pos_arr = jnp.asarray(cache_pos, jnp.int32)
        with jax.named_scope("attention/kv_write"):
            if pos_arr.ndim == 1:  # per-row positions (continuous batching)
                upd = jax.vmap(
                    lambda c, n, p: lax.dynamic_update_slice(c, n, (p, 0, 0)))
                k_cache = upd(k_cache, k.astype(k_cache.dtype), pos_arr)
                v_cache = upd(v_cache, v.astype(v_cache.dtype), pos_arr)
                row_pos = pos_arr
            else:
                k_cache = lax.dynamic_update_slice(
                    k_cache, k.astype(k_cache.dtype), (0, cache_pos, 0, 0))
                v_cache = lax.dynamic_update_slice(
                    v_cache, v.astype(v_cache.dtype), (0, cache_pos, 0, 0))
                row_pos = jnp.full((B,), pos_arr, jnp.int32)
            k_cache = constrain(k_cache, "batch", "kv_seq", "kv_heads",
                                "head_dim")
            v_cache = constrain(v_cache, "batch", "kv_seq", "kv_heads",
                                "head_dim")
        with jax.named_scope("attention/walk"):
            if S > 1:
                # chunked prefill: queries must stay causal *within* the
                # chunk (query i sees cache[: pos + i + 1]), not all see
                # pos + S.
                y = K.attention_prefill(q, k_cache, v_cache, row_pos)
            else:
                y = K.attention_decode(q, k_cache, v_cache, row_pos + 1)
        new_cache = (k_cache, v_cache)
    else:
        with jax.named_scope("attention/walk"):
            y = K.attention(q, k, v, causal=causal and cross_kv is None,
                            window=window, unroll=cfg.scan_unroll is True)

    with jax.named_scope("attention/out"):
        y = constrain(y, "batch", "seq", "heads", "head_dim")
        y = y.reshape(B, S, H * hd)
        out = PF.dense(y, d, name=f"{name}_o",
                       w_init=I.scaled_normal(1.0, H * hd))
    return constrain(out, "batch", "seq", "embed"), new_cache


def mlp(cfg: ModelConfig, x, *, name: str = "mlp", d_ff: int | None = None):
    d = x.shape[-1]
    dff = d_ff or cfg.d_ff
    with jax.named_scope("mlp"):
        if cfg.act == "silu":  # gated (llama-style)
            g = PF.dense(x, dff, name=f"{name}_gate")
            u = PF.dense(x, dff, name=f"{name}_up")
            h = F.silu(g) * u
        else:
            h = _activate(cfg, PF.dense(x, dff, name=f"{name}_up",
                                        use_bias=True))
        h = constrain(h, "batch", "seq", "mlp")
        out = PF.dense(h, d, name=f"{name}_down",
                       w_init=I.scaled_normal(1.0, dff))
        return constrain(out, "batch", "seq", "embed")


# --------------------------------------------------------------------------- #
# Mixture of Experts (GShard/Switch-style capacity dispatch)
# --------------------------------------------------------------------------- #

def moe_capacity(cfg: ModelConfig, group_size: int) -> int:
    c = int(math.ceil(group_size * cfg.top_k * cfg.capacity_factor
                      / cfg.n_experts))
    return max(4, -(-c // 4) * 4)  # round up to a multiple of 4


def moe_block(cfg: ModelConfig, x, *, name: str = "moe", token_mask=None):
    """Top-k token-choice MoE with fixed expert capacity (token dropping).

    Dispatch/combine are one-hot einsums — fixed shapes, TPU-friendly; the
    experts dim is sharded over 'model' (expert parallelism) so the dispatched
    activations move through an all-to-all.
    ``token_mask`` (B, S) bool: False tokens (chunked-prefill pads) are
    dropped from routing entirely so they cannot consume expert capacity.
    Returns (y, aux_loss).
    """
    B, S, d = x.shape
    E, k = cfg.n_experts, cfg.top_k
    T = B * S
    Gs = min(cfg.moe_group_size, T)
    if T % Gs and token_mask is not None:
        # ragged token count: one dispatch group. Serving-only (chunks are
        # small); training keeps the loud assert below — silently setting
        # Gs = T there would scale capacity with T and blow up the
        # dispatch tensors instead of flagging a bad config.
        Gs = T
    nG = T // Gs
    assert nG * Gs == T, (T, Gs)
    C = moe_capacity(cfg, Gs)

    xg = x.reshape(nG, Gs, d)
    xg = constrain(xg, "expert_group", None, "embed")

    with jax.named_scope("moe/router"):
        # router in fp32 (numerics: paper's "BN in fp32" rule applies to
        # routing)
        router_w = nn.get_parameter_or_create(
            f"{name}_router/kernel", (d, E), I.normal(0.02 / math.sqrt(d)),
            dtype=jnp.float32)
        logits = jnp.einsum("gsd,de->gse", xg.astype(jnp.float32), router_w)
        probs = jax.nn.softmax(logits, axis=-1)                # (nG,Gs,E)
        gate_vals, expert_idx = lax.top_k(probs, k)            # (nG,Gs,k)
        gate_vals = gate_vals / (jnp.sum(gate_vals, -1, keepdims=True)
                                 + 1e-9)
        # Switch load-balance auxiliary loss
        me = jnp.mean(probs, axis=(0, 1))                       # (E,)
        ce = jnp.mean(jax.nn.one_hot(expert_idx[..., 0], E,
                                     dtype=jnp.float32), axis=(0, 1))
        aux = E * jnp.sum(me * ce)

    cdt = _ctx.get_default_context().policy.compute_dtype
    with jax.named_scope("moe/dispatch"):
        # position of each (token, choice) in its expert's queue
        oh_flat = jax.nn.one_hot(expert_idx.reshape(nG, Gs * k), E,
                                 dtype=jnp.int32)              # (nG,Gs*k,E)
        if token_mask is not None:
            # zeroed one-hots make pads rank -1 in every queue -> never kept
            tm = jnp.repeat(token_mask.reshape(nG, Gs), k, axis=1)
            oh_flat = oh_flat * tm[..., None].astype(jnp.int32)
        pos_flat = jnp.cumsum(oh_flat, axis=1) * oh_flat - 1
        pos_tok = pos_flat.max(-1).reshape(nG, Gs, k)          # (nG,Gs,k)
        keep = (pos_tok >= 0) & (pos_tok < C)

        dispatch = jnp.zeros((nG, Gs, E, C), cdt)
        combine = jnp.zeros((nG, Gs, E, C), cdt)
        for i in range(k):
            ohe = jax.nn.one_hot(expert_idx[..., i], E, dtype=cdt)
            ohc = jax.nn.one_hot(pos_tok[..., i], C, dtype=cdt)
            sel = (ohe[..., None] * ohc[..., None, :]) \
                * keep[..., i, None, None].astype(cdt)
            dispatch = dispatch + sel
            combine = combine + sel * gate_vals[..., i, None, None].astype(
                cdt)
        dispatch = constrain(dispatch, "expert_group", None, "expert", None)
        combine = constrain(combine, "expert_group", None, "expert", None)

        expert_in = jnp.einsum("gsd,gsec->gecd", xg.astype(cdt), dispatch)
        expert_in = constrain(expert_in, "expert_group", "expert", None,
                              "embed")

    with jax.named_scope("moe/experts"):
        wg = nn.get_parameter_or_create(f"{name}_wi_gate", (E, d, cfg.d_ff),
                                        I.lecun_normal())
        wu = nn.get_parameter_or_create(f"{name}_wi_up", (E, d, cfg.d_ff),
                                        I.lecun_normal())
        wo = nn.get_parameter_or_create(f"{name}_wo", (E, cfg.d_ff, d),
                                        I.scaled_normal(1.0, cfg.d_ff))
        h = jnp.einsum("gecd,edf->gecf", expert_in, wg.astype(cdt))
        u = jnp.einsum("gecd,edf->gecf", expert_in, wu.astype(cdt))
        h = jax.nn.silu(h.astype(jnp.float32)).astype(cdt) * u
        h = constrain(h, "expert_group", "expert", None, "mlp")
        expert_out = jnp.einsum("gecf,efd->gecd", h, wo.astype(cdt))
        expert_out = constrain(expert_out, "expert_group", "expert", None,
                               "embed")

    with jax.named_scope("moe/combine"):
        y = jnp.einsum("gecd,gsec->gsd", expert_out, combine)
        y = y.reshape(B, S, d)
        return constrain(y, "batch", "seq", "embed"), aux


# --------------------------------------------------------------------------- #
# decoder blocks / full model
# --------------------------------------------------------------------------- #

def decoder_block(cfg: ModelConfig, x, cos, sin, *, cache=None,
                  cache_pos=None, pages=None, use_rope: bool = True,
                  token_mask=None):
    """Pre-norm block. Returns (x, aux, new_cache)."""
    h = norm(cfg, x, "ln_attn")
    a, new_cache = attention(cfg, h, cos, sin, cache=cache,
                             cache_pos=cache_pos, pages=pages,
                             use_rope=use_rope)
    x = x + a
    h = norm(cfg, x, "ln_mlp")
    if cfg.family == "moe":
        m, aux = moe_block(cfg, h, token_mask=token_mask)
    else:
        m, aux = mlp(cfg, h), jnp.zeros((), jnp.float32)
    return x + m, aux, new_cache


def embed_tokens(cfg: ModelConfig, tokens):
    x = PF.embed(tokens, cfg.vocab_size, cfg.d_model, name="tok_emb")
    return constrain(x, "batch", "seq", "embed")


def lm_head(cfg: ModelConfig, x):
    with jax.named_scope("lm_head"):
        if cfg.tie_embeddings:
            table = nn.get_parameter_or_create(
                "tok_emb/W", (cfg.vocab_size, cfg.d_model), I.normal(0.02))
            cdt = _ctx.get_default_context().policy.compute_dtype
            logits = jnp.einsum("bsd,vd->bsv", x, table.astype(cdt))
        else:
            logits = PF.dense(x, cfg.vocab_size, name="lm_head")
        return constrain(logits, "batch", "seq", "vocab")


def forward(cfg: ModelConfig, tokens, positions=None, last_only: bool = False):
    """Full-sequence forward (train / prefill). Returns (logits, aux).

    ``last_only``: only produce logits for the final position (prefill serving
    — skips the (B, S, V) logits buffer and its vocab matmul).
    """
    B, S = tokens.shape[:2]
    if positions is None:
        positions = default_positions(cfg, B, S)
    x = embed_tokens(cfg, tokens)
    cos, sin = rope_tables(cfg, positions)

    def block(carry, idx):
        h, aux = carry
        h, aux_i, _ = decoder_block(cfg, h, cos, sin)
        return h, aux + aux_i

    x, aux = nn.layer_stack("layers", cfg.n_layers, block,
                            (x, jnp.zeros((), jnp.float32)),
                            remat=cfg.remat, unroll=cfg.scan_unroll)
    if last_only:
        x = x[:, -1:]
    x = norm(cfg, x, "ln_final")
    return lm_head(cfg, x), aux


def forward_hidden(cfg: ModelConfig, tokens, positions=None):
    """Backbone forward stopping before the LM head: (hidden, aux)."""
    B, S = tokens.shape[:2]
    if positions is None:
        positions = default_positions(cfg, B, S)
    x = embed_tokens(cfg, tokens)
    cos, sin = rope_tables(cfg, positions)

    def block(carry, idx):
        h, aux = carry
        h, aux_i, _ = decoder_block(cfg, h, cos, sin)
        return h, aux + aux_i

    x, aux = nn.layer_stack("layers", cfg.n_layers, block,
                            (x, jnp.zeros((), jnp.float32)),
                            remat=cfg.remat, unroll=cfg.scan_unroll)
    return norm(cfg, x, "ln_final"), aux


def ce_from_hidden_chunked(cfg: ModelConfig, x, labels, chunk: int):
    """Cross-entropy over sequence chunks: the (B, S, V) logits tensor never
    materializes — peak is one (B, chunk, V) block, rematerialized in the
    backward pass (jax.checkpoint per chunk)."""
    B, S, d = x.shape
    chunk = min(chunk, S)
    if S % chunk:
        chunk = S  # ragged: fall back to one block
    nc = S // chunk
    xc = x.reshape(B, nc, chunk, d).swapaxes(0, 1)      # (nc, B, c, d)
    lc = labels.reshape(B, nc, chunk).swapaxes(0, 1)
    xc = constrain(xc, None, "batch", None, "embed")
    lc = constrain(lc, None, "batch", None)

    @jax.checkpoint
    def one(xi, li):
        xi = constrain(xi, "batch", None, "embed")
        logits = lm_head(cfg, xi)
        ce = F.softmax_cross_entropy(logits, li)
        return jnp.sum(constrain(ce, "batch", None))

    def step(acc, xs):
        xi, li = xs
        return acc + one(xi, li), None

    total, _ = jax.lax.scan(step, jnp.zeros((), jnp.float32), (xc, lc))
    return total / (B * S)


def loss_fn(cfg: ModelConfig, tokens, labels, positions=None):
    """Mean next-token cross-entropy (+ MoE aux). Scalar fp32."""
    if cfg.loss_chunk:
        x, aux = forward_hidden(cfg, tokens, positions)
        loss = ce_from_hidden_chunked(cfg, x, labels, cfg.loss_chunk)
        return loss + MOE_AUX_COEF * aux / max(1, cfg.n_layers)
    logits, aux = forward(cfg, tokens, positions)
    ce = F.softmax_cross_entropy(logits, labels)
    loss = jnp.mean(ce) + MOE_AUX_COEF * aux / max(1, cfg.n_layers)
    return loss


# --------------------------------------------------------------------------- #
# decode (serving) path
# --------------------------------------------------------------------------- #

def init_kv_cache(cfg: ModelConfig, batch: int, max_seq: int,
                  dtype=jnp.bfloat16) -> dict[str, Any]:
    hd = cfg.resolved_head_dim
    shape = (cfg.n_layers, batch, max_seq, cfg.n_kv_heads, hd)
    names = ("layers", "batch", "kv_seq", "kv_heads", "head_dim")
    return {"k": named_zeros(names, shape, dtype),
            "v": named_zeros(names, shape, dtype)}


def kv_cache_specs(cfg: ModelConfig, batch: int, max_seq: int,
                   dtype=jnp.bfloat16) -> dict[str, jax.ShapeDtypeStruct]:
    hd = cfg.resolved_head_dim
    shape = (cfg.n_layers, batch, max_seq, cfg.n_kv_heads, hd)
    return {"k": jax.ShapeDtypeStruct(shape, dtype),
            "v": jax.ShapeDtypeStruct(shape, dtype)}


def init_paged_kv_cache(cfg: ModelConfig, num_blocks: int, block_size: int,
                        dtype=jnp.bfloat16) -> dict[str, Any]:
    """Block-paged KV pool: no batch axis — rows address blocks through
    per-slot page tables, so memory scales with allocated blocks, not
    ``batch * max_seq``. Block 0 is the engine's garbage block.

    A quantized ``dtype`` (int8/fp8, :mod:`repro.kernels.quant`) adds
    per-(slot, head) f32 scale leaves ``k_scale``/``v_scale`` shaped
    (n_layers, num_blocks, block_size, Hkv) next to the pools — the block
    axis stays axis 1 on every leaf, so the engine's block extraction,
    tier spill/fetch and store fingerprint treat them like pool leaves.

    Under an active serving env (tensor-parallel engine) the pools come
    out sharded on the kv-head axis — each device is born holding
    ``1/tp`` of every block (scales shard the same head axis) — degrading
    to replicated for GQA geometries where ``Hkv`` doesn't divide the
    model axis."""
    hd = cfg.resolved_head_dim
    shape = (cfg.n_layers, num_blocks, block_size, cfg.n_kv_heads, hd)
    names = ("layers", None, None, "kv_heads", "head_dim")
    out = {"k": named_zeros(names, shape, dtype),
           "v": named_zeros(names, shape, dtype)}
    if quant.is_quantized(dtype):
        s_names = ("layers", None, None, "kv_heads")
        out["k_scale"] = named_zeros(s_names, shape[:-1], quant.SCALE_DTYPE)
        out["v_scale"] = named_zeros(s_names, shape[:-1], quant.SCALE_DTYPE)
    return out


def paged_kv_cache_specs(cfg: ModelConfig, num_blocks: int, block_size: int,
                         dtype=jnp.bfloat16) -> dict[str, jax.ShapeDtypeStruct]:
    hd = cfg.resolved_head_dim
    shape = (cfg.n_layers, num_blocks, block_size, cfg.n_kv_heads, hd)
    out = {"k": jax.ShapeDtypeStruct(shape, dtype),
           "v": jax.ShapeDtypeStruct(shape, dtype)}
    if quant.is_quantized(dtype):
        out["k_scale"] = jax.ShapeDtypeStruct(shape[:-1], quant.SCALE_DTYPE)
        out["v_scale"] = jax.ShapeDtypeStruct(shape[:-1], quant.SCALE_DTYPE)
    return out


def decode_step(cfg: ModelConfig, tokens, cache: dict[str, Any],
                pos: jax.Array, positions=None):
    """One decode step. tokens (B, 1); cache as from init_kv_cache;
    ``pos`` scalar int32 (synchronized batch decode). Returns (logits, cache).
    """
    B, S = tokens.shape
    if positions is None:
        positions = default_positions(cfg, B, S, offset=pos)
    x = embed_tokens(cfg, tokens)
    cos, sin = rope_tables(cfg, positions)

    def block(h, idx, layer_cache):
        h, _, new_cache = decoder_block(cfg, h, cos, sin,
                                        cache=(layer_cache["k"],
                                               layer_cache["v"]),
                                        cache_pos=pos)
        return h, {"k": new_cache[0], "v": new_cache[1]}

    x, new_cache = nn.layer_stack_with_output(
        "layers", cfg.n_layers, block, x,
        xs={"k": cache["k"], "v": cache["v"]}, unroll=cfg.scan_unroll)
    x = norm(cfg, x, "ln_final")
    return lm_head(cfg, x), new_cache


def gather_last_valid(x: jax.Array, length: jax.Array) -> jax.Array:
    """(B, C, d) -> (B, 1, d), picking position length[b]-1 per row."""
    idx = jnp.maximum(jnp.asarray(length, jnp.int32) - 1, 0)
    return jnp.take_along_axis(x, idx[:, None, None], axis=1)


def prefill(cfg: ModelConfig, tokens, cache: dict[str, Any],
            pos: jax.Array, length: jax.Array, positions=None):
    """Chunked prefill: absorb a (B, C) prompt chunk into the KV cache.

    ``pos`` (B,) is each row's cache write offset; ``length`` (B,) the number
    of valid tokens in the chunk (rows are right-padded to C). One fused call
    writes K/V for the whole chunk and returns logits at each row's last
    valid position, shape (B, 1, V), plus the updated cache — replacing C
    teacher-forced decode steps. Pad positions produce garbage logits that
    the gather skips, their cache entries are overwritten by the next chunk
    before any query can attend to them, and they are masked out of MoE
    routing so they cannot steal expert capacity from valid tokens.
    """
    B, C = tokens.shape
    pos = jnp.asarray(pos, jnp.int32)
    length = jnp.asarray(length, jnp.int32)
    if positions is None:
        positions = default_positions(cfg, B, C, offset=pos)
    x = embed_tokens(cfg, tokens)
    cos, sin = rope_tables(cfg, positions)
    valid = jnp.arange(C)[None, :] < length[:, None]

    def block(h, idx, layer_cache):
        h, _, new_cache = decoder_block(cfg, h, cos, sin,
                                        cache=(layer_cache["k"],
                                               layer_cache["v"]),
                                        cache_pos=pos, token_mask=valid)
        return h, {"k": new_cache[0], "v": new_cache[1]}

    x, new_cache = nn.layer_stack_with_output(
        "layers", cfg.n_layers, block, x,
        xs={"k": cache["k"], "v": cache["v"]}, unroll=cfg.scan_unroll)
    x = gather_last_valid(x, length)
    x = norm(cfg, x, "ln_final")
    return lm_head(cfg, x), new_cache


def prefill_paged(cfg: ModelConfig, tokens, cache: dict[str, Any],
                  pages: jax.Array, pos: jax.Array, length: jax.Array,
                  positions=None, last_only: bool = True):
    """Chunked prefill against the block-paged cache (see :func:`prefill`
    for chunk semantics). ``cache`` from :func:`init_paged_kv_cache`;
    ``pages`` (B, max_blocks) int32 per-row page tables. A C = 1 call is a
    paged decode step — the engine uses this one entry for both shapes.

    ``last_only=False`` returns logits at *every* chunk position,
    (B, C, V) instead of the gathered (B, 1, V): the chunk-causal mask
    means position ``i``'s logits condition on exactly ``tokens[:, :i+1]``
    plus the cache, which is what speculative verification needs — one
    ``(B, 1 + k)`` decode-prefill call scores all ``k`` draft tokens for
    free. The extra cost is skipping the gather (the ``C`` lm_head columns
    were computed either way).
    """
    B, C = tokens.shape
    pos = jnp.asarray(pos, jnp.int32)
    length = jnp.asarray(length, jnp.int32)
    if positions is None:
        positions = default_positions(cfg, B, C, offset=pos)
    x = embed_tokens(cfg, tokens)
    cos, sin = rope_tables(cfg, positions)
    valid = jnp.arange(C)[None, :] < length[:, None]

    quantized = "k_scale" in cache

    def block(h, idx, layer_cache):
        c = (layer_cache["k"], layer_cache["v"])
        if quantized:
            c += (layer_cache["k_scale"], layer_cache["v_scale"])
        h, _, new_cache = decoder_block(cfg, h, cos, sin, cache=c,
                                        cache_pos=pos, pages=pages,
                                        token_mask=valid)
        out = {"k": new_cache[0], "v": new_cache[1]}
        if quantized:
            out["k_scale"], out["v_scale"] = new_cache[2], new_cache[3]
        return h, out

    x, new_cache = nn.layer_stack_with_output(
        "layers", cfg.n_layers, block, x,
        xs=dict(cache), unroll=cfg.scan_unroll)
    if last_only:
        x = gather_last_valid(x, length)
    x = norm(cfg, x, "ln_final")
    return lm_head(cfg, x), new_cache
