"""Pallas paged-attention kernels vs the gather-then-dense references.

Everything runs the kernels in interpret mode (CPU container): same kernel
logic as the compiled TPU build, minus Mosaic. Sweeps cover block sizes
{4, 8, 16}, GQA ratios (incl. MQA), ragged lengths exactly on / one off
block boundaries and multi-page tile edges, all-idle rows, the tile rule,
the fused scatter (incl. the overrun -> garbage-block regression), the
dense-prefill-as-paged-walk route, and an engine-level smoke with
``kernels="pallas_interpret"``.
"""

import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import context as ctx
from repro.kernels import ops
from repro.kernels.flash_attention import paged_attention as pa
from repro.kernels.flash_attention import ref as fa_ref


def rand(shape, seed, dtype=jnp.float32):
    return jnp.asarray(np.random.default_rng(seed).normal(size=shape), dtype)


def make_pools(B, MB, bs, Hkv, D, seed=0):
    """Pools + a shuffled (non-contiguous) page table, garbage block 0."""
    NB = B * MB + 1
    kp = rand((NB, bs, Hkv, D), seed)
    vp = rand((NB, bs, Hkv, D), seed + 1)
    perm = np.random.default_rng(seed + 2).permutation(np.arange(1, NB))
    pages = jnp.asarray(perm[:B * MB].reshape(B, MB), jnp.int32)
    return kp, vp, pages


def interpret_ctx():
    return ctx.context_scope(dataclasses.replace(
        ctx.get_default_context(), kernels="pallas_interpret"))


# ---------------------------------------------------------------------- #
# decode parity
# ---------------------------------------------------------------------- #

@pytest.mark.parametrize("bs", [4, 8, 16])
@pytest.mark.parametrize("Hq,Hkv", [(4, 4), (8, 2), (8, 1)])  # GQA + MQA
def test_paged_decode_parity(bs, Hq, Hkv):
    B, D, MB = 4, 32, 48 // bs
    kp, vp, pages = make_pools(B, MB, bs, Hkv, D, seed=bs)
    q = rand((B, 1, Hq, D), 7)
    # boundary sweep: exactly on a block edge, one before, one after, full
    lengths = jnp.asarray([bs, bs - 1, bs + 1, MB * bs], jnp.int32)
    got = pa.paged_decode(q, kp, vp, pages, lengths, interpret=True)
    want = fa_ref.paged_decode_reference(q, kp, vp, pages, lengths)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-5, rtol=1e-5)


def test_paged_decode_all_idle_row():
    """An idle slot has an all-zero page table and length 1 (the engine
    decodes at pos + 1): the kernel must read only the garbage block and
    still agree with the reference."""
    B, bs, MB, Hq, Hkv, D = 3, 8, 4, 4, 2, 32
    kp, vp, pages = make_pools(B, MB, bs, Hkv, D, seed=3)
    pages = pages.at[1, :].set(0)                  # row 1 idle
    lengths = jnp.asarray([2 * bs + 3, 1, bs], jnp.int32)
    q = rand((B, 1, Hq, D), 11)
    got = pa.paged_decode(q, kp, vp, pages, lengths, interpret=True)
    want = fa_ref.paged_decode_reference(q, kp, vp, pages, lengths)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-5, rtol=1e-5)


def test_paged_decode_bf16():
    B, bs, MB, Hq, Hkv, D = 2, 8, 4, 4, 2, 64
    NB = B * MB + 1
    kp = rand((NB, bs, Hkv, D), 0, jnp.bfloat16)
    vp = rand((NB, bs, Hkv, D), 1, jnp.bfloat16)
    pages = jnp.asarray(1 + np.arange(B * MB).reshape(B, MB), jnp.int32)
    q = rand((B, 1, Hq, D), 2, jnp.bfloat16)
    lengths = jnp.asarray([5, 29], jnp.int32)
    got = pa.paged_decode(q, kp, vp, pages, lengths, interpret=True)
    want = fa_ref.paged_decode_reference(q, kp, vp, pages, lengths)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               atol=2e-2, rtol=2e-2)


# ---------------------------------------------------------------------- #
# chunk-causal prefill parity
# ---------------------------------------------------------------------- #

@pytest.mark.parametrize("bs", [4, 8, 16])
@pytest.mark.parametrize("Hq,Hkv", [(4, 4), (8, 2)])
def test_paged_prefill_parity(bs, Hq, Hkv):
    """Chunks spanning block boundaries mid-chunk: C = 5 with pos at, one
    before and one past a block edge, plus a fresh row at pos 0."""
    B, C, D, MB = 4, 5, 32, 48 // bs
    kp, vp, pages = make_pools(B, MB, bs, Hkv, D, seed=10 + bs)
    q = rand((B, C, Hq, D), 13)
    pos = jnp.asarray([0, bs - 1, bs, bs + 1], jnp.int32)
    got = pa.paged_prefill(q, kp, vp, pages, pos, interpret=True)
    want = fa_ref.paged_prefill_reference(q, kp, vp, pages, pos)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-5, rtol=1e-5)


def test_paged_prefill_chunk_causality():
    """The kernel's mask is per-query: later queries in the chunk must see
    strictly more of the cache (checked against a manual per-row oracle)."""
    B, C, bs, MB, Hq, Hkv, D = 1, 4, 4, 4, 2, 2, 16
    kp, vp, pages = make_pools(B, MB, bs, Hkv, D, seed=21)
    q = rand((B, C, Hq, D), 22)
    pos = jnp.asarray([3], jnp.int32)
    got = np.asarray(pa.paged_prefill(q, kp, vp, pages, pos, interpret=True))
    dense_k = fa_ref.gather_pages(kp, pages)
    dense_v = fa_ref.gather_pages(vp, pages)
    for i in range(C):
        # query i as a standalone decode over pos+i+1 visible tokens
        one = fa_ref.decode_reference(
            q[:, i:i + 1], dense_k, dense_v,
            jnp.asarray([int(pos[0]) + i + 1], jnp.int32))
        np.testing.assert_allclose(got[:, i:i + 1], np.asarray(one),
                                   atol=1e-5, rtol=1e-5,
                                   err_msg=f"query {i} sees wrong window")


def test_dense_prefill_routes_through_paged_walk():
    """ops.attention_prefill in pallas modes runs the paged kernel over an
    identity page table (free reshape of the contiguous cache)."""
    B, C, Smax, Hq, Hkv, D = 2, 6, 48, 4, 2, 32
    q = rand((B, C, Hq, D), 31)
    kc = rand((B, Smax, Hkv, D), 32)
    vc = rand((B, Smax, Hkv, D), 33)
    pos = jnp.asarray([0, 37], jnp.int32)
    want = fa_ref.prefill_reference(q, kc, vc, pos)
    with interpret_ctx():
        got = ops.attention_prefill(q, kc, vc, pos)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-5, rtol=1e-5)


# ---------------------------------------------------------------------- #
# multi-page tiles
# ---------------------------------------------------------------------- #

@pytest.mark.parametrize("bs,MB,P", [
    (4, 80, 32), (8, 40, 16), (16, 528, 8),   # P * bs = 128
    (16, 3, 3), (8, 1, 1),                    # capped at the table width
    (48, 10, 3), (1, 300, 128),               # bs not dividing 128
    (128, 8, 1), (64, 2, 2)])                 # prefill_dense's bs
def test_pages_per_tile(bs, MB, P):
    assert pa.pages_per_tile(bs, MB) == P
    assert 1 <= P <= MB
    assert P * bs >= pa.TILE_TOKENS or P == MB


def tile_edge_case(bs):
    """A table of 2.5 tiles (MB not a multiple of P) and rows ending on a
    tile edge, one short of it, at 1, mid-tile past an edge, at the
    table's end and 3 past it (a padded chunk overrunning the table),
    plus an idle row (all-zero table). Returns the table's width and the
    rows' visible lengths."""
    P = pa.pages_per_tile(bs, 10 ** 6)
    tile = P * bs
    MB = 2 * P + P // 2
    visible = [tile, tile - 1, 1, tile + 3, MB * bs, MB * bs + 3, 1]
    return MB, visible


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("C", [1, 5], ids=["decode", "prefill"])
@pytest.mark.parametrize("bs", [4, 8, 16])
def test_paged_walk_tile_edges(bs, C, dtype):
    """Walks of several multi-page tiles against the gather-then-dense
    references. With C = 5 the row ending at ``tile + 3`` has its causal
    diagonal cross the first tile edge."""
    B, Hq, Hkv, D = 7, 8, 2, 32
    MB, visible = tile_edge_case(bs)
    kp, vp, pages = make_pools(B, MB, bs, Hkv, D, seed=70 + bs)
    kp, vp = kp.astype(dtype), vp.astype(dtype)
    pages = pages.at[B - 1, :].set(0)                 # last row idle
    q = rand((B, C, Hq, D), 71, dtype)
    if C == 1:
        lengths = jnp.asarray(visible, jnp.int32)
        got = pa.paged_decode(q, kp, vp, pages, lengths, interpret=True)
        want = fa_ref.paged_decode_reference(q, kp, vp, pages, lengths)
    else:
        # the first row's chunk ends on the tile edge; the 1-token row is
        # a fresh prompt, its chunk sees 1..C tokens
        pos = jnp.asarray([max(v - C, 0) for v in visible], jnp.int32)
        got = pa.paged_prefill(q, kp, vp, pages, pos, interpret=True)
        want = fa_ref.paged_prefill_reference(q, kp, vp, pages, pos)
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               atol=tol, rtol=tol)


# ---------------------------------------------------------------------- #
# ops dispatch
# ---------------------------------------------------------------------- #

def test_ops_paged_dispatch_modes_agree():
    B, bs, MB, Hq, Hkv, D = 2, 8, 4, 4, 2, 32
    kp, vp, pages = make_pools(B, MB, bs, Hkv, D, seed=41)
    q = rand((B, 1, Hq, D), 42)
    qc = rand((B, 3, Hq, D), 43)
    lengths = jnp.asarray([7, 2 * bs], jnp.int32)
    pos = jnp.asarray([2, bs - 2], jnp.int32)
    base_dec = ops.attention_decode_paged(q, kp, vp, pages, lengths)
    base_pre = ops.attention_prefill_paged(qc, kp, vp, pages, pos)
    with interpret_ctx():
        k_dec = ops.attention_decode_paged(q, kp, vp, pages, lengths)
        k_pre = ops.attention_prefill_paged(qc, kp, vp, pages, pos)
    np.testing.assert_allclose(np.asarray(k_dec), np.asarray(base_dec),
                               atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(k_pre), np.asarray(base_pre),
                               atol=1e-5, rtol=1e-5)


# ---------------------------------------------------------------------- #
# fused cache write
# ---------------------------------------------------------------------- #

def test_paged_write_fused_matches_scatter():
    B, C, bs, MB, Hkv, D = 2, 5, 4, 4, 2, 16
    kp, _, pages = make_pools(B, MB, bs, Hkv, D, seed=51)
    new = rand((B, C, Hkv, D), 52)
    pos = jnp.asarray([3, 9], jnp.int32)
    want = ops.paged_cache_write(kp, new, pages, pos)       # jnp scatter
    with interpret_ctx():
        got = ops.paged_cache_write(kp, new, pages, pos)    # fused kernel
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("mode", ["xla", "pallas_interpret"])
def test_paged_write_overrun_hits_garbage_block(mode):
    """Regression: a chunk whose ``pos + C`` runs past the page table's
    last column must spill into the garbage block 0 — the old clip
    redirected those tokens into whatever LIVE block sat in the last
    column, corrupting another request's cache."""
    B, C, bs, MB, Hkv, D = 1, 4, 4, 3, 2, 8
    NB = B * MB + 1
    kp = rand((NB, bs, Hkv, D), 61)
    new = rand((B, C, Hkv, D), 62)
    pages = jnp.asarray([[3, 1, 2]], jnp.int32)
    pos = jnp.asarray([bs * MB - 2], jnp.int32)   # tokens 2,3 overrun
    with ctx.context_scope(dataclasses.replace(
            ctx.get_default_context(), kernels=mode)):
        out = np.asarray(ops.paged_cache_write(kp, new, pages, pos))
    old = np.asarray(kp)
    npnew = np.asarray(new)
    # in-bounds tokens land in the last column's block (id 2)
    np.testing.assert_array_equal(out[2, bs - 2], npnew[0, 0])
    np.testing.assert_array_equal(out[2, bs - 1], npnew[0, 1])
    # overrun tokens land in garbage block 0 — NOT in block 2
    np.testing.assert_array_equal(out[0, 0], npnew[0, 2])
    np.testing.assert_array_equal(out[0, 1], npnew[0, 3])
    # every non-garbage block slot outside the two written ones untouched
    mask = np.ones((NB, bs), bool)
    mask[0] = False
    mask[2, bs - 2:] = False
    np.testing.assert_array_equal(out[mask], old[mask])


# ---------------------------------------------------------------------- #
# engine smoke under the interpret kernels
# ---------------------------------------------------------------------- #

def test_engine_pallas_interpret_matches_xla():
    from repro.configs.base import ModelConfig
    from repro.models.registry import get_model
    from repro.serving.engine import Request, ServingEngine
    import repro.core as nn
    import jax

    cfg = ModelConfig(name="pk", family="dense", n_layers=2, d_model=64,
                      n_heads=4, n_kv_heads=2, d_ff=128, vocab_size=97,
                      head_dim=16, remat="none")
    api = get_model(cfg)
    params = nn.init(lambda t: api.forward(t), jax.random.key(0),
                     jnp.zeros((1, 8), jnp.int32))
    outs = []
    for kernels in ("xla", "pallas_interpret"):
        eng = ServingEngine(api, params, max_batch=2, max_seq=32, chunk=4,
                            block_size=4, kernels=kernels)
        assert eng.paged
        for i in range(3):
            eng.submit(Request(uid=i, prompt=[1 + i, 2, 3, 4, 5],
                               max_new_tokens=4))
        outs.append({r.uid: r.generated for r in eng.run_until_drained()})
    assert outs[0] == outs[1]
