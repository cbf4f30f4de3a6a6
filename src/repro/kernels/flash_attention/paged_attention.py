"""Paged attention as Pallas TPU kernels: walk the page table in VMEM.

The XLA references (:func:`ref.paged_decode_reference` /
:func:`ref.paged_prefill_reference`) gather every row's blocks into a dense
``(B, max_blocks * block_size, Hkv, D)`` copy of the cache before attending —
one full extra HBM round-trip per step plus a transient allocation that
scales with the *worst-case* table width, not the request's actual length.

The kernels here never build that view. The page table and per-row
positions ride in as scalar-prefetch operands
(:class:`pltpu.PrefetchScalarGridSpec`). One grid step attends over a KV
tile of ``P`` pool pages, ``P * block_size`` keys (:func:`pages_per_tile`:
the fewest pages that fill 128 lanes, at most the table's width), so the
grid is ``(B, ceil(max_blocks / P))``. Pages are not contiguous in the
pool, so the tile is ``P`` BlockSpec'd operands, one per page of the
tile, each passing the pool itself: page ``p``'s index map looks column
``kt * P + p`` up in the table, and the Pallas pipeline DMAs that whole
``(block_size, Hkv, D)`` block — every kv head at once, each block read
from HBM once — while the grid step before computes, the next row's first
tile included. In VMEM the kernel stacks each kv head's slices of the
``P`` pages into one ``(P * block_size, D)`` operand: per head one
``(C * rep, D) x (D, P * bs)`` QK^T, the chunk-causal mask over the
tile's key positions and one online-softmax update whose ``p @ v``
contracts over the whole tile (m, l, acc per kv head, carried in VMEM
scratch across the sequential tile axis). Columns past a row's last
visible page, and past the table in a last partial tile, keep that
page's index, so tiles past the row's end cost no DMA, and ``pl.when``
skips their FLOPs; the keys such columns repeat are masked by position.

Manual DMAs from a ``memory_space=ANY`` pool would do the same, but
Mosaic refuses a DMA out of an HBM array whose minor dimension is under
128 lanes (granite-moe's head dim 64), while a block whose last two dims
are the array's full ``(Hkv, D)`` is legal for any head count and head
dim. The per-head ``(bs, D)`` operands are sliced from VMEM, never from
HBM: a one-head slice of an HBM block is a misaligned DMA that Mosaic
rejects.

Both entry points share one kernel:

* :func:`paged_decode` — q ``(B, 1, Hq, D)`` vs ``lengths`` (B,): query
  sees positions ``< lengths[b]``. This is the C = 1 / ``pos = lengths - 1``
  special case of the chunk-causal walk.
* :func:`paged_prefill` — q ``(B, C, Hq, D)`` vs ``pos`` (B,): query i of
  row b sees gathered positions ``<= pos[b] + i`` (the chunk-causal mask of
  ``ref.prefill_reference``).

GQA: the wrapper lays q out head-major as ``(B, Hkv, C * rep, D)`` — each
kv head's ``rep`` query heads flattened with the chunk into one MXU
operand, row ``c * rep + r``.

:func:`paged_write` is the fused scatter companion: the *output* BlockSpec
index map computes each token's ``(block, slot)`` destination from the
scalar-prefetched table, so the chunk lands directly in the pool
(``input_output_aliases`` donates it — no read-modify-write of the flat
pool, no materialized scatter indices). Tokens past the row's table width
are redirected into the garbage block 0, which no valid mask ever reads.

Quantized pools (int8/fp8, see :mod:`repro.kernels.quant`): the walk
pipelines each page's ``(bs, Hkv)`` scale tile next to its K/V page and
dequantizes in VMEM (one VPU broadcast multiply per head — no dequantized
pool copy ever exists in HBM, and the DMA'd bytes are *halved*).
:func:`paged_write` quantizes with the XLA path's own
:func:`quant.quantize`, lands the payload through the kernel and
scatters the scale rows in XLA.

Validated against the gather-then-dense references in interpret mode (CPU
container, block sizes 4/8/16, GQA, ragged lengths on block and tile
edges) and compiled for TPU v5e at real widths
(``tests/test_tpu_compile.py``). Lengths/pos semantics
assume ``lengths >= 1`` for any row whose output is consumed (the engine
always decodes at ``pos + 1``); a length-0 row yields zeros, not the
reference's garbage-uniform average.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import quant
from repro.kernels.flash_attention import flash_attention as fa
from repro.kernels.flash_attention import ref


TILE_TOKENS = 128          # keys per grid step: the MXU's and a vreg's lanes


def pages_per_tile(bs: int, MB: int) -> int:
    """Pool pages the walk attends per grid step: the fewest whose
    ``P * bs`` tokens fill :data:`TILE_TOKENS` lanes, never more pages
    than the table has columns."""
    return max(1, min(MB, -(-TILE_TOKENS // bs)))


def _paged_attn_kernel(pages_ref, pos_ref, q_ref, *rest, bs: int, P: int,
                       MB: int, C: int, rep: int, n_kv: int, scale: float,
                       quantized: bool):
    """One (batch row, kv tile) grid step of the paged walk.

    A tile is ``P`` pool pages, ``P * bs`` keys; ``rest`` holds the P K
    pages, the P V pages (then, quantized, the P K and P V scale tiles),
    the output and the scratch. Scratch persists across the innermost
    (sequential) grid axis: m/l/acc carry the online softmax of every kv
    head. ``pos_ref[b] + C`` is the row's visible-token count — for
    decode (C = 1, pos = lengths - 1) that is exactly ``lengths[b]``.
    """
    del pages_ref
    k_pages, v_pages = rest[:P], rest[P:2 * P]
    if quantized:
        ks_pages, vs_pages = rest[2 * P:3 * P], rest[3 * P:4 * P]
    o_ref, m_scr, l_scr, acc_scr = rest[-4:]
    b = pl.program_id(0)
    kt = pl.program_id(1)
    nt = pl.num_programs(1)
    tile = P * bs
    visible = pos_ref[b] + C          # tokens any query of this row can see

    @pl.when(kt == 0)
    def _init():
        fa.init_carry(m_scr, l_scr, acc_scr)

    @pl.when(kt * tile < visible)
    def _body():
        # chunk-causal: query i (s-row i * rep + r) sees kpos <= pos + i
        qpos = pos_ref[b] + jax.lax.broadcasted_iota(
            jnp.int32, (C * rep, tile), 0) // rep
        kpos = kt * tile + jax.lax.broadcasted_iota(
            jnp.int32, (C * rep, tile), 1)
        mask = kpos <= qpos
        if MB % P:                    # the last tile runs past the table
            mask &= kpos < MB * bs
        for h in range(n_kv):
            # kv head h of the tile's pages, stacked in VMEM: (tile, D)
            q = q_ref[0, h].astype(jnp.float32)               # (C*rep, D)
            k = jnp.concatenate([r[0, :, h, :] for r in k_pages])
            v = jnp.concatenate([r[0, :, h, :] for r in v_pages])
            k, v = k.astype(jnp.float32), v.astype(jnp.float32)
            if quantized:
                # dequant in VMEM: one broadcast multiply per tile
                k = k * jnp.concatenate([r[0, :, h:h + 1] for r in ks_pages])
                v = v * jnp.concatenate([r[0, :, h:h + 1] for r in vs_pages])
            fa.online_softmax_step(q, k, v, mask, scale, m_scr.at[h],
                                   l_scr.at[h], acc_scr.at[h])

    @pl.when(kt == nt - 1)
    def _fini():
        for h in range(n_kv):
            o_ref[0, h] = fa.carry_output(l_scr.at[h], acc_scr.at[h],
                                          o_ref.dtype)


def _paged_walk(q, k_pool, v_pool, pages, pos, *, scale, interpret,
                k_scale=None, v_scale=None):
    """Shared pallas_call builder: q (B, C, Hq, D) through the page table
    with the chunk-causal mask anchored at per-row ``pos``. Quantized
    pools (int8/fp8) pass their (NB, bs, Hkv) scale arrays, which ride
    the pipeline page by page next to K and V."""
    B, C, Hq, D = q.shape
    _, bs, Hkv, _ = k_pool.shape
    MB = pages.shape[1]
    if Hq % Hkv:
        raise ValueError(f"Hq={Hq} not a multiple of Hkv={Hkv}")
    quantized = k_scale is not None
    if quantized and v_scale is None:
        raise ValueError("k_scale given without v_scale")
    rep = Hq // Hkv
    scale = scale if scale is not None else 1.0 / math.sqrt(D)
    P = pages_per_tile(bs, MB)
    # head-major q: (B, Hkv, C * rep, D), row c * rep + r
    qh = (q.reshape(B, C, Hkv, rep, D).transpose(0, 2, 1, 3, 4)
          .reshape(B, Hkv, C * rep, D))

    def page_spec(p, block):
        """Page p of every tile, looked up in the table. Columns past the
        row's last visible page (or past the table) keep that page's
        index, so the tiles past a row's end cost no copy."""
        def index(b, kt, pages_ref, pos_ref):
            last = jnp.maximum(pos_ref[b] + C - 1, 0) // bs
            col = jnp.minimum(jnp.minimum(kt * P + p, last), MB - 1)
            return (pages_ref[b, col],) + (0,) * (len(block) - 1)
        return pl.BlockSpec(block, index)

    row_spec = pl.BlockSpec((1, Hkv, C * rep, D),
                            lambda b, kt, *_: (b, 0, 0, 0))
    kv_specs = [page_spec(p, (1, bs, Hkv, D)) for p in range(P)]
    in_specs = [row_spec] + 2 * kv_specs
    operands = [pages, jnp.asarray(pos, jnp.int32), qh]
    operands += P * [k_pool] + P * [v_pool]
    if quantized:
        in_specs += 2 * [page_spec(p, (1, bs, Hkv)) for p in range(P)]
        operands += P * [k_scale] + P * [v_scale]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,            # pages, pos
        grid=(B, pl.cdiv(MB, P)),
        in_specs=in_specs,
        out_specs=row_spec,
        scratch_shapes=[
            pltpu.VMEM((Hkv, C * rep, 1), jnp.float32),   # m
            pltpu.VMEM((Hkv, C * rep, 1), jnp.float32),   # l
            pltpu.VMEM((Hkv, C * rep, D), jnp.float32),   # acc
        ],
    )
    out = pl.pallas_call(
        functools.partial(_paged_attn_kernel, bs=bs, P=P, MB=MB, C=C,
                          rep=rep, n_kv=Hkv, scale=scale,
                          quantized=quantized),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hkv, C * rep, D), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="paged_attention",
    )(*operands)
    return (out.reshape(B, Hkv, C, rep, D).transpose(0, 2, 1, 3, 4)
            .reshape(B, C, Hq, D))


def paged_decode(q, k_pool, v_pool, pages, lengths, *, scale=None,
                 k_scale=None, v_scale=None,
                 interpret: bool = False) -> jax.Array:
    """Single-token decode through the page table. q (B, 1, Hq, D); pools
    (num_blocks, block_size, Hkv, D); pages (B, max_blocks) int32;
    lengths (B,) valid token counts (the query sees kpos < lengths[b]).
    Quantized pools pass (NB, bs, Hkv) scales via k_scale/v_scale."""
    B, one, _, _ = q.shape
    assert one == 1, "decode takes a single query token per row"
    return _paged_walk(q, k_pool, v_pool, pages,
                       jnp.asarray(lengths, jnp.int32) - 1,
                       scale=scale, interpret=interpret,
                       k_scale=k_scale, v_scale=v_scale)


def paged_prefill(q, k_pool, v_pool, pages, pos, *, scale=None,
                  k_scale=None, v_scale=None,
                  interpret: bool = False) -> jax.Array:
    """Chunk-causal prefill through the page table. q (B, C, Hq, D);
    query i of row b sees gathered positions ``<= pos[b] + i``."""
    return _paged_walk(q, k_pool, v_pool, pages, pos,
                       scale=scale, interpret=interpret,
                       k_scale=k_scale, v_scale=v_scale)


def prefill_dense(q, k_cache, v_cache, pos, *, scale=None,
                  interpret: bool = False) -> jax.Array:
    """Chunk-causal prefill against a *dense* (B, Smax, Hkv, D) cache,
    run through the paged kernel: a contiguous cache is just a block pool
    with the identity page table (row b's blocks are b*MB .. b*MB+MB-1),
    so the reshape is free and no dedicated dense kernel is needed."""
    B, C, Hq, D = q.shape
    _, Smax, Hkv, _ = k_cache.shape
    # largest power-of-two tile <= 128 dividing Smax (gcd with 2^7); odd
    # Smax degrades to bs=1 — correct, but size caches in block multiples
    bs = math.gcd(Smax, 128)
    MB = Smax // bs
    k_pool = k_cache.reshape(B * MB, bs, Hkv, D)
    v_pool = v_cache.reshape(B * MB, bs, Hkv, D)
    pages = (jnp.arange(B, dtype=jnp.int32)[:, None] * MB
             + jnp.arange(MB, dtype=jnp.int32)[None, :])
    return _paged_walk(q, k_pool, v_pool, pages, pos,
                       scale=scale, interpret=interpret)


def _paged_write_kernel(pages_ref, pos_ref, new_ref, pool_ref, out_ref):
    # The scatter is entirely in the output index map; the body just lands
    # the token's (Hkv, D) tile in its block slot.
    del pages_ref, pos_ref, pool_ref
    out_ref[...] = new_ref[...].astype(out_ref.dtype)


def paged_write(pool, new, pages, pos, *, pool_scale=None,
                interpret: bool = False):
    """Fused scatter of a (B, C, Hkv, D) chunk into a (NB, bs, Hkv, D)
    pool: token i of row b lands at block ``pages[b, (pos[b]+i) // bs]``,
    slot ``(pos[b]+i) % bs``. Tokens past the table width go to the
    garbage block 0 (never read). The pool is donated in place
    (``input_output_aliases``): no flat-index materialization, no
    read-modify-write of untouched blocks.

    With ``pool_scale`` (quantized (NB, bs, Hkv) scale array), the chunk
    is absmax-quantized by :func:`quant.quantize` — the op the XLA path
    runs, so pools are bit-identical across kernel modes — the payload
    lands through the kernel, and the per-token scale rows (4 bytes per
    token and head) scatter in XLA: a one-slot ``(1, Hkv)`` block of the
    ``(bs, Hkv)`` scale tile is not a legal TPU block. Returns
    ``(pool, pool_scale)``."""
    NB, bs, Hkv, D = pool.shape
    B, C = new.shape[:2]
    MB = pages.shape[1]
    pos = jnp.asarray(pos, jnp.int32)
    if pool_scale is not None:
        new, new_scale = quant.quantize(new, pool.dtype)

    def out_map(b, i, pages_ref, pos_ref):
        p = pos_ref[b] + i
        col = p // bs
        blk = jnp.where(col < MB, pages_ref[b, jnp.minimum(col, MB - 1)], 0)
        return blk, p % bs, 0, 0

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,            # pages, pos
        grid=(B, C),
        in_specs=[
            pl.BlockSpec((1, 1, Hkv, D), lambda b, i, *_: (b, i, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),     # donated pool (unread)
        ],
        out_specs=pl.BlockSpec((1, 1, Hkv, D), out_map),
    )
    pool = pl.pallas_call(
        _paged_write_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(pool.shape, pool.dtype),
        # operand 3 counting the two scalar-prefetch args: (pages, pos,
        # new, pool) -> pool aliases the single output
        input_output_aliases={3: 0},
        interpret=interpret,
        name="paged_write",
    )(pages, pos, new, pool)
    if pool_scale is None:
        return pool
    slots = ref.paged_slots(pages, pos, C, bs)
    return pool, ref.scatter_slots(pool_scale, slots, new_scale)
