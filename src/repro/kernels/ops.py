"""Kernel dispatch layer.

Models call these; the active :class:`repro.core.context.Context` decides
whether the Pallas TPU kernel, its interpret-mode build (CPU validation), or
the plain-XLA reference executes. The serving engine picks the mode from the
platform (:func:`repro.core.context.resolve_kernels`): compiled Pallas on a
TPU, the ambient context elsewhere (the XLA references by default; TPU
Pallas cannot lower on CPU backends).

Op x mode matrix (which implementation runs, and — for the paged ops —
where quantized pools (int8/fp8, :mod:`repro.kernels.quant`) convert):

=========================  ==============  ==============  ===================
op                         xla             xla_chunked     pallas[_interpret]
=========================  ==============  ==============  ===================
attention                  mha_reference   mha_chunked     flash_attention
attention_decode           decode ref      decode ref      flash_decode
attention_prefill          prefill ref     prefill ref     paged walk [#f1]_
attention_decode_paged     gather+dense    gather+dense    paged_decode
  quant (k/v_scale)        dequant in the  dequant in the  dequant in VMEM
                           gather [#f3]_   gather [#f3]_   post-DMA [#f4]_
attention_prefill_paged    gather+dense    gather+dense    paged_prefill
  quant (k/v_scale)        dequant in the  dequant in the  dequant in VMEM
                           gather [#f3]_   gather [#f3]_   post-DMA [#f4]_
paged_cache_write          jnp scatter     jnp scatter     fused paged_write
  quant (pool_scale)       jnp quantize +  jnp quantize +  jnp quantize,
                           2-array scatter 2-array scatter payload fused,
                                                           scales scattered
ssd                        ssd_chunked     ssd_chunked     ssd kernel [#f2]_
ssd_decode_step            jnp             jnp             jnp (elementwise)
=========================  ==============  ==============  ===================

.. [#f1] dense prefill is the paged walk over an identity page table (a
   contiguous cache reshapes to a block pool for free).
.. [#f2] stateful continuation (``h0``) always takes the chunked-jnp path.
.. [#f3] ``gather_pages`` on the quantized pool + scale array, then one
   broadcast multiply — the dense copy is f32, so the same dense oracle
   applies and XLA-vs-Pallas parity holds at quantized dtypes too.
.. [#f4] *why VMEM*: dequantizing right after the block's pipelined DMA
   means the HBM traffic is the **quantized** bytes (the whole point
   of the scheme — the walk is bandwidth-bound), the dequant multiply
   hides in the next block's DMA shadow, and the MXU sees exactly the
   high-precision operands of the unquantized walk, leaving the online
   softmax carry and chunk-causal mask untouched. A dequantized pool
   never exists in HBM in any mode.

Quantization scheme (shared by all modes): symmetric absmax, one f32
scale per written (token slot, kv head) — scale arrays (NB, bs, Hkv)
alongside each (NB, bs, Hkv, D) pool. Per-*slot* (not per-block) scales
keep the fused write a pure scatter (no read-modify-write of sibling
slots) and keep speculative decode bitwise: a stored token's bytes never
depend on rejected draft tokens sharing its block. Every mode quantizes
with the same :func:`repro.kernels.quant.quantize`, so pools are
bit-identical across modes and spill/fetch round-trips are exact.

Speculative verify steps (PR 6) add **no rows**: a ``(B, 1 + k)`` draft
window is just another chunk width through ``attention_prefill_paged``
and ``paged_cache_write``. Two properties of the existing rows make this
sound in every mode:

* chunk-causal masking is by *position* (``kpos <= qpos``), so K/V
  written at positions ``>= pos + length`` — pad columns then, rejected
  drafts now — are invisible to every real query of this and of any
  later step until the positions are legitimately rewritten;
* the scatter path is a plain last-writer-wins overwrite, so re-writing
  a rejected draft's slot position next step needs no clearing pass.

Tensor parallelism (serving mesh with a ``model`` axis active in the
ambient :class:`repro.distributed.sharding.ShardingEnv` at trace time):

=========================  =====================  =========================
op                         xla / xla_chunked      pallas[_interpret]
=========================  =====================  =========================
attention_{prefill,decode} GSPMD partitions the   shard_map over kv heads
 [+ _paged variants]       jnp reference (rule    (``Hkv % tp == 0``) or
                           table + ``constrain``  over grouped query heads
                           hints keep kv-head     (GQA ``Hkv < tp``: KV
                           dims sharded)          replicates); else runs
                                                  fully replicated
paged_cache_write          GSPMD scatter          shard_map over kv heads;
                                                  per-shard kernel keeps
                                                  ``input_output_aliases``
                                                  pool donation
=========================  =====================  =========================

The shard_map body is the *unchanged* single-device kernel: with the pool
sharded on kv heads, every shard walks the full page table over its local
``Hkv/tp`` head slice of every block — block ids stay global, the
pipelined block walk and fused-scatter donation work per shard exactly
as they do on one device.
"""

from __future__ import annotations

import jax
from jax.sharding import PartitionSpec as P

from repro.core import context as _ctx
from repro.kernels.flash_attention import ref as fa_ref
from repro.kernels.ssd import ref as ssd_ref


# --------------------------------------------------------------------------- #
# tensor-parallel wrapping of the Pallas kernels
#
# The XLA reference paths are plain jnp: under a serving mesh GSPMD
# partitions them from the rule-table constraints alone. pallas_call has no
# partitioning rule, so the Pallas builds must be wrapped in shard_map with
# an explicit layout — chosen here, at the dispatch layer, so neither the
# kernels nor the models know the mesh exists.
# --------------------------------------------------------------------------- #

def _tp_mesh():
    """The serving mesh, iff a >1-wide ``model`` axis is active at trace
    time (the engine scopes its ShardingEnv around step tracing)."""
    from repro.distributed.sharding import get_env
    mesh = get_env().mesh
    if mesh is None or mesh.empty or "model" not in mesh.shape:
        return None
    return mesh if mesh.shape["model"] > 1 else None


def _repl(*arrays):
    return tuple(P() for _ in arrays)


def _head_spec(a, ax):
    """PartitionSpec sharding array ``a``'s axis ``ax`` on the model axis."""
    ax = ax % a.ndim
    return P(*(None,) * ax, "model", *(None,) * (a.ndim - ax - 1))


def _tp_heads_call(fn, q, kv_args, rep_args, kv_axes=None):
    """Run ``fn(q, *kv_args, *rep_args) -> (B, C, Hq, D)`` under shard_map.

    ``kv_args`` carry the kv-head axis at the per-arg position in
    ``kv_axes`` (default -2 for every arg: block pools ``(NB, bs, Hkv, D)``
    and dense caches ``(B, S, Hkv, D)`` both do; quantized scale arrays
    ``(NB, bs, Hkv)`` pass -1); ``rep_args`` (page tables, positions,
    lengths) replicate. Layouts, in preference order: shard kv heads (each
    shard walks only its local pool slice); GQA ``Hkv < tp``: replicate
    KV, shard the per-group query heads; indivisible probe geometries:
    run fully replicated.
    """
    mesh = _tp_mesh()
    if mesh is None:
        return fn(q, *kv_args, *rep_args)
    if kv_axes is None:
        kv_axes = (-2,) * len(kv_args)
    tp = mesh.shape["model"]
    B, C, Hq, D = q.shape
    Hkv = kv_args[0].shape[-2]
    rep_specs = _repl(*rep_args)
    if Hkv % tp == 0:
        # q heads are grouped contiguously by kv head (head h serves kv
        # head h // rep), so sharding the q-head axis into tp contiguous
        # chunks lands each chunk on the shard holding its kv heads.
        kv_specs = tuple(
            _head_spec(a, ax) for a, ax in zip(kv_args, kv_axes))
        sharded = jax.shard_map(
            fn, mesh=mesh,
            in_specs=(P(None, None, "model", None),) + kv_specs + rep_specs,
            out_specs=P(None, None, "model", None), check_vma=False)
        return sharded(q, *kv_args, *rep_args)
    group = Hq // Hkv
    if group % tp == 0:
        # replicate KV, split each kv head's query group across shards;
        # regrouping happens inside the shard so GQA ratios stay intact
        qg = q.reshape(B, C, Hkv, group, D)

        def _grouped(qg_loc, *args):
            out = fn(qg_loc.reshape(B, C, -1, D), *args)
            return out.reshape(qg_loc.shape)

        sharded = jax.shard_map(
            _grouped, mesh=mesh,
            in_specs=(P(None, None, None, "model", None),)
            + _repl(*kv_args) + rep_specs,
            out_specs=P(None, None, None, "model", None), check_vma=False)
        return sharded(qg, *kv_args, *rep_args).reshape(B, C, Hq, D)
    sharded = jax.shard_map(fn, mesh=mesh,
                            in_specs=_repl(q, *kv_args) + rep_specs,
                            out_specs=P(), check_vma=False)
    return sharded(q, *kv_args, *rep_args)


def _tp_write_call(fn, pool, new, pages, pos, pool_scale=None):
    """Fused paged scatter under shard_map: pool and chunk both shard on
    the kv-head axis (position -2; a quantized scale array shards the same
    head axis at -1), page table and positions replicate. The per-shard
    kernel still donates its pool (+ scale) slice in place via
    ``input_output_aliases``."""
    mesh = _tp_mesh()
    if mesh is None:
        return fn(pool, new, pages, pos) if pool_scale is None \
            else fn(pool, new, pages, pos, pool_scale)
    tp = mesh.shape["model"]
    split = pool.shape[-2] % tp == 0
    kv = _head_spec(pool, -2) if split else P()
    if pool_scale is None:
        sharded = jax.shard_map(fn, mesh=mesh, in_specs=(kv, kv, P(), P()),
                                out_specs=kv, check_vma=False)
        return sharded(pool, new, pages, pos)
    sc = _head_spec(pool_scale, -1) if split else P()
    sharded = jax.shard_map(fn, mesh=mesh, in_specs=(kv, kv, P(), P(), sc),
                            out_specs=(kv, sc), check_vma=False)
    return sharded(pool, new, pages, pos, pool_scale)


def attention(q, k, v, *, causal: bool = True, window: int | None = None,
              scale: float | None = None,
              unroll: int | bool = 1, block: int = 1024) -> jax.Array:
    mode = _ctx.get_default_context().kernels
    if mode == "xla":
        return fa_ref.mha_reference(q, k, v, causal=causal, window=window,
                                    scale=scale)
    if mode == "xla_chunked":
        # flash algorithm in plain XLA (online softmax over KV blocks).
        # Cost probes fully unroll the block scans (while-body undercount);
        # they use a larger block so the unrolled HLO stays compilable —
        # total FLOPs/bytes are block-size invariant.
        if unroll is True:
            block = max(block, 4096)
        return fa_ref.mha_chunked(q, k, v, causal=causal, window=window,
                                  scale=scale, block_q=block, block_k=block,
                                  unroll=unroll)
    from repro.kernels.flash_attention import flash_attention as fa
    return fa.flash_attention(q, k, v, causal=causal, window=window,
                              scale=scale,
                              interpret=(mode == "pallas_interpret"))


def attention_decode(q, k_cache, v_cache, lengths, *, scale=None) -> jax.Array:
    mode = _ctx.get_default_context().kernels
    if mode in ("xla", "xla_chunked"):
        return fa_ref.decode_reference(q, k_cache, v_cache, lengths,
                                       scale=scale)
    from repro.kernels.flash_attention import flash_attention as fa

    def _call(q_, k_, v_, len_):
        return fa.flash_decode(q_, k_, v_, len_, scale=scale,
                               interpret=(mode == "pallas_interpret"))

    return _tp_heads_call(_call, q, (k_cache, v_cache), (lengths,))


def attention_prefill(q, k_cache, v_cache, pos, *, scale=None) -> jax.Array:
    """Chunk-causal attention for chunked prefill: q (B, C, Hq, D) against a
    (B, Smax, Hkv, D) cache; query i of row b sees cache[: pos[b] + i + 1].
    """
    mode = _ctx.get_default_context().kernels
    if mode in ("xla", "xla_chunked"):
        # no chunked-XLA variant: the chunk is short and the cache read is
        # one bandwidth pass, so blockwise XLA would buy nothing here
        return fa_ref.prefill_reference(q, k_cache, v_cache, pos, scale=scale)
    from repro.kernels.flash_attention import paged_attention as pa

    def _call(q_, k_, v_, pos_):
        return pa.prefill_dense(q_, k_, v_, pos_, scale=scale,
                                interpret=(mode == "pallas_interpret"))

    return _tp_heads_call(_call, q, (k_cache, v_cache), (pos,))


def attention_decode_paged(q, k_pool, v_pool, pages, lengths, *,
                           scale=None, k_scale=None,
                           v_scale=None) -> jax.Array:
    """Single-token decode against a block-paged cache: q (B, 1, Hq, D),
    pools (num_blocks, block_size, Hkv, D), ``pages`` (B, max_blocks) int32
    block ids per row, ``lengths`` (B,) valid token counts.

    XLA modes lower to the gather-then-dense reference — one extra full
    HBM pass plus a transient dense copy sized by the worst-case table
    width. Pallas modes walk the page table in VMEM (one pipelined DMA per
    block, no materialized gather): :mod:`.flash_attention.paged_attention`.

    Quantized pools (int8/fp8) pass their (NB, bs, Hkv) scale arrays via
    ``k_scale``/``v_scale``; see the matrix above for where each mode
    dequantizes.
    """
    mode = _ctx.get_default_context().kernels
    if mode in ("xla", "xla_chunked"):
        return fa_ref.paged_decode_reference(q, k_pool, v_pool, pages,
                                             lengths, scale=scale,
                                             k_scale=k_scale,
                                             v_scale=v_scale)
    from repro.kernels.flash_attention import paged_attention as pa

    if k_scale is not None:
        def _call_q(q_, k_, v_, ks_, vs_, pages_, len_):
            return pa.paged_decode(q_, k_, v_, pages_, len_, scale=scale,
                                   k_scale=ks_, v_scale=vs_,
                                   interpret=(mode == "pallas_interpret"))

        return _tp_heads_call(_call_q, q, (k_pool, v_pool, k_scale, v_scale),
                              (pages, lengths), kv_axes=(-2, -2, -1, -1))

    def _call(q_, k_, v_, pages_, len_):
        return pa.paged_decode(q_, k_, v_, pages_, len_, scale=scale,
                               interpret=(mode == "pallas_interpret"))

    return _tp_heads_call(_call, q, (k_pool, v_pool), (pages, lengths))


def attention_prefill_paged(q, k_pool, v_pool, pages, pos, *,
                            scale=None, k_scale=None,
                            v_scale=None) -> jax.Array:
    """Chunk-causal prefill against a block-paged cache: q (B, C, Hq, D)
    with query i of row b seeing positions ``<= pos[b] + i`` gathered
    through the row's page table (see :func:`attention_decode_paged` for
    the layout, mode dispatch and quantized-pool handling).
    """
    mode = _ctx.get_default_context().kernels
    if mode in ("xla", "xla_chunked"):
        return fa_ref.paged_prefill_reference(q, k_pool, v_pool, pages, pos,
                                              scale=scale, k_scale=k_scale,
                                              v_scale=v_scale)
    from repro.kernels.flash_attention import paged_attention as pa

    if k_scale is not None:
        def _call_q(q_, k_, v_, ks_, vs_, pages_, pos_):
            return pa.paged_prefill(q_, k_, v_, pages_, pos_, scale=scale,
                                    k_scale=ks_, v_scale=vs_,
                                    interpret=(mode == "pallas_interpret"))

        return _tp_heads_call(_call_q, q, (k_pool, v_pool, k_scale, v_scale),
                              (pages, pos), kv_axes=(-2, -2, -1, -1))

    def _call(q_, k_, v_, pages_, pos_):
        return pa.paged_prefill(q_, k_, v_, pages_, pos_, scale=scale,
                                interpret=(mode == "pallas_interpret"))

    return _tp_heads_call(_call, q, (k_pool, v_pool), (pages, pos))


def paged_cache_write(pool, new, pages, pos, *, pool_scale=None):
    """Scatter a (B, C, Hkv, D) K/V chunk into a (NB, bs, Hkv, D) pool.

    Token i of row b lands at flat slot ``pages[b, p // bs] * bs + p % bs``
    with ``p = pos[b] + i``. Rows whose page-table entry is 0 (idle slots,
    pad columns past a row's allocation) scatter into the garbage block,
    which no valid mask ever reads — so the write needs no predication.
    Tokens whose position falls past the table's last column likewise go to
    the garbage block: clipping the column instead would silently overwrite
    whatever live block sits in the last entry.

    Pallas modes fuse the scatter into a kernel whose output index map
    computes each token's (block, slot) destination directly (pool donated
    in place); XLA modes use the flat jnp scatter below.

    With ``pool_scale`` (quantized pool's (NB, bs, Hkv) scale array), the
    chunk is absmax-quantized to the pool dtype on the way in — inside the
    Pallas scatter body, or as a jnp quantize feeding a two-array scatter
    in the XLA modes (bit-identical results) — and ``(pool, pool_scale)``
    is returned.
    """
    mode = _ctx.get_default_context().kernels
    if mode not in ("xla", "xla_chunked"):
        from repro.kernels.flash_attention import paged_attention as pa

        if pool_scale is not None:
            def _call_q(pool_, new_, pages_, pos_, scale_):
                return pa.paged_write(pool_, new_, pages_, pos_,
                                      pool_scale=scale_,
                                      interpret=(mode == "pallas_interpret"))

            return _tp_write_call(_call_q, pool, new, pages, pos, pool_scale)

        def _call(pool_, new_, pages_, pos_):
            return pa.paged_write(pool_, new_, pages_, pos_,
                                  interpret=(mode == "pallas_interpret"))

        return _tp_write_call(_call, pool, new, pages, pos)
    slots = fa_ref.paged_slots(pages, pos, new.shape[1], pool.shape[1])
    if pool_scale is not None:
        from repro.kernels import quant
        new_q, s_new = quant.quantize(new, pool.dtype)
        return (fa_ref.scatter_slots(pool, slots, new_q),
                fa_ref.scatter_slots(pool_scale, slots, s_new))
    return fa_ref.scatter_slots(pool, slots, new)


def ssd(x, dt, A, Bm, Cm, D=None, *, chunk: int = 64, h0=None,
        return_state: bool = False, unroll: int | bool = 1):
    mode = _ctx.get_default_context().kernels
    # The Pallas kernel always starts from h=0; stateful continuation
    # (chunked prefill) goes through the chunked-jnp path in every mode.
    if mode in ("xla", "xla_chunked") or h0 is not None:
        return ssd_ref.ssd_chunked(x, dt, A, Bm, Cm, D, chunk=chunk, h0=h0,
                                   return_state=return_state, unroll=unroll)
    from repro.kernels.ssd import ssd_kernel
    return ssd_kernel.ssd(x, dt, A, Bm, Cm, D, chunk=chunk, h0=h0,
                          return_state=return_state,
                          interpret=(mode == "pallas_interpret"))


def ssd_decode_step(h, x_t, dt_t, A, B_t, C_t, D=None):
    # Single-token state update is elementwise-dominated; XLA fuses it well.
    return ssd_ref.ssd_decode_step(h, x_t, dt_t, A, B_t, C_t, D)
