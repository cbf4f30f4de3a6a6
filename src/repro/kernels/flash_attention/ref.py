"""Pure-jnp oracle for the flash-attention kernel.

Also the XLA fallback path used whenever ``Context.kernels == "xla"`` (e.g.
the CPU dry-run container, where TPU Pallas cannot lower).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def mha_reference(q: jax.Array, k: jax.Array, v: jax.Array, *,
                  causal: bool = True, window: int | None = None,
                  scale: float | None = None) -> jax.Array:
    """q: (B, Sq, Hq, D); k/v: (B, Sk, Hkv, D); GQA by head broadcast.

    fp32 logits + softmax; output cast back to q.dtype.
    """
    B, Sq, Hq, D = q.shape
    _, Sk, Hkv, _ = k.shape
    if Hq % Hkv:
        raise ValueError(f"Hq={Hq} not a multiple of Hkv={Hkv}")
    rep = Hq // Hkv
    scale = scale if scale is not None else 1.0 / math.sqrt(D)

    qh = q.reshape(B, Sq, Hkv, rep, D)
    logits = jnp.einsum("bqhrd,bkhd->bhrqk", qh, k,
                        preferred_element_type=jnp.float32) * scale
    if causal or window is not None:
        qpos = jnp.arange(Sq)[:, None] + (Sk - Sq)
        kpos = jnp.arange(Sk)[None, :]
        mask = jnp.ones((Sq, Sk), bool)
        if causal:
            mask &= qpos >= kpos
        if window is not None:
            mask &= (qpos - kpos) < window
        logits = jnp.where(mask[None, None, None], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1).astype(v.dtype)
    out = jnp.einsum("bhrqk,bkhd->bqhrd", probs, v,
                     preferred_element_type=jnp.float32)
    return out.reshape(B, Sq, Hq, D).astype(q.dtype)


def _block_update(carry, q_i, k_i, v_i, mask, scale):
    """One online-softmax accumulation step (fp32)."""
    m, l, acc = carry
    s = jnp.einsum("...qhrd,...khd->...hrqk", q_i, k_i,
                   preferred_element_type=jnp.float32) * scale
    s = jnp.where(mask, s, -1e30)
    m_new = jnp.maximum(m, s.max(-1))
    alpha = jnp.exp(m - m_new)
    p = jnp.exp(s - m_new[..., None])
    l_new = l * alpha + p.sum(-1)
    acc_new = acc * alpha[..., None] + jnp.einsum(
        "...hrqk,...khd->...hrqd", p, v_i,
        preferred_element_type=jnp.float32)
    return m_new, l_new, acc_new


def mha_chunked(q: jax.Array, k: jax.Array, v: jax.Array, *,
                causal: bool = True, window: int | None = None,
                scale: float | None = None, block_q: int = 1024,
                block_k: int = 1024, unroll: int | bool = 1) -> jax.Array:
    """Blockwise online-softmax attention in pure XLA ops.

    The flash-attention *algorithm* without the Pallas kernel: stream KV
    blocks against resident Q blocks carrying (m, l, acc); the (Sq, Sk)
    logits matrix never materializes, so peak memory is O(block_q·block_k)
    instead of O(Sq·Sk).

    Causal mode uses the **folded schedule**: q-block rows i and nq-1-i are
    paired; row i needs i+1 KV blocks and its partner needs nq-i, so every
    pair needs exactly nq+1 — a static loop bound that skips the upper
    triangle's compute entirely (2x fewer FLOPs than mask-only blocking,
    visible in HLO, not just at runtime).
    """
    B, Sq, Hq, D = q.shape
    _, Sk, Hkv, _ = k.shape
    rep = Hq // Hkv
    scale = scale if scale is not None else 1.0 / math.sqrt(D)
    bq = min(block_q, Sq)
    bk = min(block_k, Sk)
    use_fold = (causal and window is None and Sq == Sk and bq == bk
                and Sq % bq == 0 and (Sq // bq) % 2 == 0)
    if (Sq % bq or Sk % bk) or (causal and not use_fold and Sq != Sk):
        # ragged / offset shapes (tests, speculative decode): plain reference
        return mha_reference(q, k, v, causal=causal, window=window,
                             scale=scale)
    nq, nk = Sq // bq, Sk // bk

    qb = q.reshape(B, nq, bq, Hkv, rep, D)
    kb = k.reshape(B, nk, bk, Hkv, D)
    vb = v.reshape(B, nk, bk, Hkv, D)
    tri_q = jnp.arange(bq)[:, None]
    tri_k = jnp.arange(bk)[None, :]

    if use_fold:
        npair = nq // 2
        pair_i = jnp.arange(npair)                   # rows 0..npair-1
        pair_j = nq - 1 - pair_i                     # rows nq-1..npair
        q_lo = qb[:, :npair]                         # (B,P,bq,Hkv,rep,D)
        q_hi = qb[:, npair:][:, ::-1]
        # move pair dim first for scan-friendly batching
        q_lo = jnp.moveaxis(q_lo, 1, 0)              # (P,B,bq,...)
        q_hi = jnp.moveaxis(q_hi, 1, 0)

        def kv_step(carry, s):
            lo, hi = carry
            # row i consumes kv s while s <= i; afterwards row j consumes
            # kv (s - i - 1); per pair, exactly one block of work per step.
            on_lo = s <= pair_i                                   # (P,)
            ki = jnp.where(on_lo, jnp.minimum(s, nk - 1),
                           jnp.clip(s - pair_i - 1, 0, nk - 1))   # (P,)
            k_i = jnp.moveaxis(kb[:, ki], 1, 0)       # (P,B,bk,Hkv,D)
            v_i = jnp.moveaxis(vb[:, ki], 1, 0)
            selm = on_lo[:, None, None, None, None]   # m/l (P,B,Hkv,rep,bq)
            sela = on_lo[:, None, None, None, None, None]  # acc (+D)
            selq = on_lo[:, None, None, None, None, None]  # q (P,B,bq,h,r,D)
            q_sel = jnp.where(selq, q_lo, q_hi)
            cur = (jnp.where(selm, lo[0], hi[0]),
                   jnp.where(selm, lo[1], hi[1]),
                   jnp.where(sela, lo[2], hi[2]))
            row = jnp.where(on_lo, pair_i, pair_j)                # (P,)
            # mask: diagonal block needs the triangle; off-diagonal is full
            diag = row == ki
            qpos = row[:, None, None] * bq + tri_q[None]
            kpos = ki[:, None, None] * bk + tri_k[None]
            mask = jnp.where(diag[:, None, None], qpos >= kpos, True)
            mask = mask[:, None, None, None, :, :]    # (P,1,1,1,bq,bk)
            new = _block_update(cur, q_sel, k_i, v_i, mask, scale)
            sels = (selm, selm, sela)
            lo = tuple(jnp.where(sl, nw, old)
                       for sl, nw, old in zip(sels, new, lo))
            hi = tuple(jnp.where(sl, old, nw)
                       for sl, nw, old in zip(sels, new, hi))
            return (lo, hi), None

        def init():
            m0 = jnp.full((npair, B, Hkv, rep, bq), -1e30, jnp.float32)
            l0 = jnp.zeros((npair, B, Hkv, rep, bq), jnp.float32)
            a0 = jnp.zeros((npair, B, Hkv, rep, bq, D), jnp.float32)
            return (m0, l0, a0)

        (lo, hi), _ = jax.lax.scan(kv_step, (init(), init()),
                                   jnp.arange(nq + 1), unroll=unroll)

        def finalize(t):
            m, l, acc = t
            out = acc / jnp.maximum(l, 1e-30)[..., None]
            return jnp.moveaxis(out, 4, 2)            # (P,B,bq,Hkv,rep,D)

        o_lo = finalize(lo)
        o_hi = finalize(hi)[::-1]
        out = jnp.concatenate([o_lo, o_hi], axis=0)   # (nq,B,bq,...)
        out = jnp.moveaxis(out, 0, 1)                 # (B,nq,bq,...)
        return out.reshape(B, Sq, Hq, D).astype(q.dtype)

    # non-causal / windowed: plain blockwise sweep with masking
    off = Sk - Sq

    def q_block(carry, qi):
        q_i = qb[:, qi]

        def kv_step(c, ki):
            k_i = kb[:, ki]
            v_i = vb[:, ki]
            qpos = qi * bq + tri_q + off
            kpos = ki * bk + tri_k
            mask = jnp.ones((bq, bk), bool)
            if causal:
                mask = mask & (qpos >= kpos)
            if window is not None:
                mask = mask & ((qpos - kpos) < window)
            return _block_update(c, q_i, k_i, v_i,
                                 mask[None, None, None], scale), None

        m0 = jnp.full((B, Hkv, rep, bq), -1e30, jnp.float32)
        l0 = jnp.zeros((B, Hkv, rep, bq), jnp.float32)
        a0 = jnp.zeros((B, Hkv, rep, bq, D), jnp.float32)
        (m, l, acc), _ = jax.lax.scan(kv_step, (m0, l0, a0),
                                      jnp.arange(nk), unroll=unroll)
        out = acc / jnp.maximum(l, 1e-30)[..., None]
        return carry, jnp.moveaxis(out, 3, 1)         # (B,bq,Hkv,rep,D)

    _, outs = jax.lax.scan(q_block, 0, jnp.arange(nq), unroll=unroll)
    out = jnp.moveaxis(outs, 0, 1)                    # (B,nq,bq,...)
    return out.reshape(B, Sq, Hq, D).astype(q.dtype)


def prefill_reference(q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
                      pos: jax.Array, *, scale: float | None = None
                      ) -> jax.Array:
    """Chunked-prefill attention: a (B, C, Hq, D) query chunk against a
    (B, Smax, Hkv, D) cache whose rows were just written at per-row offsets
    ``pos`` (B,).

    Chunk-causal: query i of row b attends to cache entries j <= pos[b] + i.
    Cache entries beyond the chunk (stale slots from an earlier occupant of
    the row) are never visible because pos[b] + C - 1 bounds the window.
    """
    B, C, Hq, D = q.shape
    _, Smax, Hkv, _ = k_cache.shape
    rep = Hq // Hkv
    scale = scale if scale is not None else 1.0 / math.sqrt(D)
    qh = q.reshape(B, C, Hkv, rep, D)
    logits = jnp.einsum("bqhrd,bkhd->bhrqk", qh, k_cache,
                        preferred_element_type=jnp.float32) * scale
    qpos = pos[:, None] + jnp.arange(C)[None, :]               # (B, C)
    valid = jnp.arange(Smax)[None, None, :] <= qpos[..., None]  # (B, C, Smax)
    logits = jnp.where(valid[:, None, None], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1).astype(v_cache.dtype)
    out = jnp.einsum("bhrqk,bkhd->bqhrd", probs, v_cache,
                     preferred_element_type=jnp.float32)
    return out.reshape(B, C, Hq, D).astype(q.dtype)


def gather_pages(pool: jax.Array, pages: jax.Array) -> jax.Array:
    """Materialize per-row dense caches from a block pool.

    ``pool`` (num_blocks, block_size, Hkv, D) — or a (num_blocks,
    block_size, Hkv) scale array; any trailing shape rides along.
    ``pages`` (B, max_blocks) int32 block ids (0 = the garbage block —
    rows past a request's length, masked out downstream). Returns
    (B, max_blocks * block_size, ...), the exact dense cache the row
    would have held, so the dense references below apply unchanged and
    paged-vs-dense logits agree bitwise.
    """
    bs = pool.shape[1]
    B, MB = pages.shape
    g = jnp.take(pool, pages, axis=0)            # (B, MB, bs, ...)
    return g.reshape((B, MB * bs) + pool.shape[2:])


def paged_slots(pages: jax.Array, pos: jax.Array, C: int,
                bs: int) -> jax.Array:
    """Flat pool slot of every token of a (B, C) chunk: token i of row b
    lands at ``pages[b, p // bs] * bs + p % bs`` with ``p = pos[b] + i``.
    Positions past the table's last column go to the garbage block 0 —
    clipping the column instead would overwrite whatever live block sits
    in the last entry. Returns (B * C,) int32."""
    MB = pages.shape[1]
    p = (jnp.asarray(pos, jnp.int32)[:, None]
         + jnp.arange(C, dtype=jnp.int32)[None, :])
    col = p // bs
    blk = jnp.take_along_axis(pages, jnp.clip(col, 0, MB - 1), axis=1)
    blk = jnp.where(col < MB, blk, 0)
    return (blk * bs + p % bs).reshape(-1)


def scatter_slots(pool: jax.Array, slots: jax.Array,
                  new: jax.Array) -> jax.Array:
    """Write a (B, C, ...) chunk into a (num_blocks, block_size, ...) pool
    (or scale array) at the flat ``slots`` of :func:`paged_slots`."""
    nb, bs = pool.shape[:2]
    flat = pool.reshape((nb * bs,) + pool.shape[2:])
    flat = flat.at[slots].set(
        new.astype(pool.dtype).reshape((-1,) + pool.shape[2:]))
    return flat.reshape(pool.shape)


def _gather_dequant(pool, scale_arr, pages):
    """Gather a quantized pool + its scales into the dense f32 cache the
    unquantized row would have held — the jnp mirror of the kernel's
    in-VMEM dequant, so the same dense oracles apply to quantized pools."""
    dense = gather_pages(pool, pages).astype(jnp.float32)
    s = gather_pages(scale_arr, pages).astype(jnp.float32)
    return dense * s[..., None]


def paged_prefill_reference(q: jax.Array, k_pool: jax.Array,
                            v_pool: jax.Array, pages: jax.Array,
                            pos: jax.Array, *, scale: float | None = None,
                            k_scale: jax.Array | None = None,
                            v_scale: jax.Array | None = None) -> jax.Array:
    """Chunk-causal prefill attention through a page table: gather each
    row's blocks into its dense-equivalent cache, then delegate to
    :func:`prefill_reference` (the oracle for paged-vs-dense equivalence).
    Quantized pools pass their (NB, bs, Hkv) scales via k_scale/v_scale
    and are dequantized in the gather."""
    if k_scale is not None:
        return prefill_reference(q, _gather_dequant(k_pool, k_scale, pages),
                                 _gather_dequant(v_pool, v_scale, pages),
                                 pos, scale=scale)
    return prefill_reference(q, gather_pages(k_pool, pages),
                             gather_pages(v_pool, pages), pos, scale=scale)


def paged_decode_reference(q: jax.Array, k_pool: jax.Array,
                           v_pool: jax.Array, pages: jax.Array,
                           lengths: jax.Array, *, scale: float | None = None,
                           k_scale: jax.Array | None = None,
                           v_scale: jax.Array | None = None) -> jax.Array:
    """Single-token decode attention through a page table (see
    :func:`paged_prefill_reference`)."""
    if k_scale is not None:
        return decode_reference(q, _gather_dequant(k_pool, k_scale, pages),
                                _gather_dequant(v_pool, v_scale, pages),
                                lengths, scale=scale)
    return decode_reference(q, gather_pages(k_pool, pages),
                            gather_pages(v_pool, pages), lengths, scale=scale)


def decode_reference(q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
                     lengths: jax.Array, *, scale: float | None = None
                     ) -> jax.Array:
    """Single-token decode: q (B, 1, Hq, D) against a (B, Smax, Hkv, D) cache.

    ``lengths`` (B,) — number of valid cache entries per sequence.
    """
    B, _, Hq, D = q.shape
    _, Smax, Hkv, _ = k_cache.shape
    rep = Hq // Hkv
    scale = scale if scale is not None else 1.0 / math.sqrt(D)
    qh = q.reshape(B, Hkv, rep, D)
    logits = jnp.einsum("bhrd,bkhd->bhrk", qh, k_cache,
                        preferred_element_type=jnp.float32) * scale
    valid = jnp.arange(Smax)[None, :] < lengths[:, None]
    logits = jnp.where(valid[:, None, None, :], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1).astype(v_cache.dtype)
    out = jnp.einsum("bhrk,bkhd->bhrd", probs, v_cache,
                     preferred_element_type=jnp.float32)
    return out.reshape(B, 1, Hq, D).astype(q.dtype)
