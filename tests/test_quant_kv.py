"""Quantized KV pools: kernel parity ladder, fused write, engine streams.

The scheme (:mod:`repro.kernels.quant`): pools stored int8/fp8 with one
float32 absmax scale per (token slot, kv-head), quant fused into the
write scatter, dequant into the attention walk. Covered here:

* op x mode parity at quantized dtypes — xla / xla_chunked /
  pallas_interpret against the fp32 dense oracle with a per-dtype
  tolerance ladder, and pallas against xla tight (same math, the only
  difference is where the dequant runs);
* GQA/MQA head ratios, lengths exactly on / one off block edges, chunk
  widths spanning block boundaries mid-chunk;
* the fused quant write: bit-identical pools+scales across modes
  (donation-compatible), bounded round-trip error, garbage-block overrun;
* engine end-to-end: identical int8 streams across kernel modes, greedy
  stability vs unquantized pools (divergence rate bounded + reported),
  spec-decode bitwise guarantee, bytes/token accounting (<= 0.55x bf16
  at D=64), $REPRO_KV_DTYPE resolution, fp8 pools.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.core as nn
from repro.configs.base import ModelConfig
from repro.core import context as ctx
from repro.kernels import ops, quant
from repro.kernels.flash_attention import paged_attention as pa
from repro.kernels.flash_attention import ref as fa_ref
from repro.models.registry import get_model
from repro.serving.engine import Request, ServingEngine

QDTYPES = [jnp.int8, quant.FP8_DTYPE]
QIDS = ["int8", "fp8"]
# attention-output tolerance vs the fp32 oracle: int8 keeps ~0.4%
# relative error per element, fp8 e4m3 (3 mantissa bits) several times
# that — the ladder the acceptance criteria ask for
TOL = {jnp.dtype(jnp.int8): 5e-2, jnp.dtype(quant.FP8_DTYPE): 1.5e-1}


def rand(shape, seed, dtype=jnp.float32):
    return jnp.asarray(np.random.default_rng(seed).normal(size=shape), dtype)


def make_qpools(B, MB, bs, Hkv, D, qdtype, seed=0):
    """fp32 pools + their quantized twins + a DISJOINT page table (block
    ids unique across rows, like the real allocator hands out)."""
    NB = B * MB + 1
    kp = rand((NB, bs, Hkv, D), seed)
    vp = rand((NB, bs, Hkv, D), seed + 1)
    kq, ks = quant.quantize(kp, qdtype)
    vq, vs = quant.quantize(vp, qdtype)
    perm = np.random.default_rng(seed + 2).permutation(np.arange(1, NB))
    pages = jnp.asarray(perm[:B * MB].reshape(B, MB), jnp.int32)
    return (kp, vp), (kq, ks, vq, vs), pages


def mode_ctx(mode):
    return ctx.context_scope(dataclasses.replace(
        ctx.get_default_context(), kernels=mode))


# ---------------------------------------------------------------------- #
# quant scheme unit behavior
# ---------------------------------------------------------------------- #

@pytest.mark.parametrize("qdtype", QDTYPES, ids=QIDS)
def test_quantize_round_trip_bounded(qdtype):
    x = rand((3, 7, 2, 32), 5)
    q, s = quant.quantize(x, qdtype)
    assert q.dtype == jnp.dtype(qdtype)
    assert s.dtype == quant.SCALE_DTYPE and s.shape == x.shape[:-1]
    back = quant.dequantize(q, s)
    rel = float(jnp.max(jnp.abs(back - x)) / jnp.max(jnp.abs(x)))
    assert rel < (0.01 if qdtype == jnp.int8 else 0.08), rel


def test_quantize_zero_vector_is_safe():
    q, s = quant.quantize(jnp.zeros((2, 4, 8)), jnp.int8)
    assert not np.any(np.isnan(np.asarray(s)))
    np.testing.assert_array_equal(np.asarray(quant.dequantize(q, s)), 0.0)


def test_resolve_kv_dtype_names():
    assert quant.resolve_kv_dtype(None, jnp.bfloat16) == jnp.bfloat16
    assert quant.resolve_kv_dtype("native", jnp.float32) == jnp.float32
    assert quant.resolve_kv_dtype("int8", jnp.bfloat16) == jnp.int8
    assert quant.resolve_kv_dtype("bf16", jnp.float32) == jnp.bfloat16
    assert quant.is_quantized(quant.resolve_kv_dtype("int8", jnp.float32))
    assert not quant.is_quantized(jnp.bfloat16)
    with pytest.raises(ValueError):
        quant.resolve_kv_dtype("int7", jnp.float32)
    got = quant.resolve_kv_dtype("fp8", jnp.float32)
    assert quant.is_quantized(got) and quant.kv_dtype_name(got) == "fp8"


# ---------------------------------------------------------------------- #
# op x mode parity ladder
# ---------------------------------------------------------------------- #

@pytest.mark.parametrize("qdtype", QDTYPES, ids=QIDS)
@pytest.mark.parametrize("bs", [4, 8])
@pytest.mark.parametrize("Hq,Hkv", [(4, 4), (8, 2), (8, 1)])  # GQA + MQA
def test_paged_decode_quant_parity(bs, Hq, Hkv, qdtype):
    B, D, MB = 4, 32, 32 // bs
    (kp, vp), (kq, ks, vq, vs), pages = make_qpools(
        B, MB, bs, Hkv, D, qdtype, seed=bs)
    q = rand((B, 1, Hq, D), 7)
    # boundary sweep: exactly on a block edge, one before, one after, full
    lengths = jnp.asarray([bs, bs - 1, bs + 1, MB * bs], jnp.int32)
    oracle = fa_ref.paged_decode_reference(q, kp, vp, pages, lengths)
    got_x = fa_ref.paged_decode_reference(q, kq, vq, pages, lengths,
                                          k_scale=ks, v_scale=vs)
    got_p = pa.paged_decode(q, kq, vq, pages, lengths,
                            k_scale=ks, v_scale=vs, interpret=True)
    tol = TOL[jnp.dtype(qdtype)]
    # ladder rung 1: quantized output near the fp32 oracle
    np.testing.assert_allclose(np.asarray(got_x), np.asarray(oracle),
                               atol=tol, rtol=tol)
    # rung 2: VMEM-dequant kernel tight against the gather-dequant ref
    np.testing.assert_allclose(np.asarray(got_p), np.asarray(got_x),
                               atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("qdtype", QDTYPES, ids=QIDS)
@pytest.mark.parametrize("C", [1, 5])
@pytest.mark.parametrize("bs", [4, 8])
def test_paged_prefill_quant_parity(bs, C, qdtype):
    """Chunks spanning block boundaries mid-chunk, incl. C=1 (the decode-
    as-prefill shape the mixed step actually runs)."""
    B, Hq, Hkv, D, MB = 4, 4, 2, 32, 32 // bs
    (kp, vp), (kq, ks, vq, vs), pages = make_qpools(
        B, MB, bs, Hkv, D, qdtype, seed=10 + bs)
    q = rand((B, C, Hq, D), 13)
    pos = jnp.asarray([0, bs - 1, bs, bs + 1], jnp.int32)
    oracle = fa_ref.paged_prefill_reference(q, kp, vp, pages, pos)
    got_x = fa_ref.paged_prefill_reference(q, kq, vq, pages, pos,
                                           k_scale=ks, v_scale=vs)
    got_p = pa.paged_prefill(q, kq, vq, pages, pos,
                             k_scale=ks, v_scale=vs, interpret=True)
    tol = TOL[jnp.dtype(qdtype)]
    np.testing.assert_allclose(np.asarray(got_x), np.asarray(oracle),
                               atol=tol, rtol=tol)
    np.testing.assert_allclose(np.asarray(got_p), np.asarray(got_x),
                               atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("qdtype", QDTYPES, ids=QIDS)
@pytest.mark.parametrize("C", [1, 5], ids=["decode", "prefill"])
@pytest.mark.parametrize("bs", [4, 8, 16])
def test_paged_walk_quant_tile_edges(bs, C, qdtype):
    """Quantized walks of several multi-page tiles (MB not a multiple of
    the tile's pages): the scale tiles ride with their pages, rows end on
    a tile edge, one short of it, at 1, past an edge, at the table's end
    and past it, one row idle."""
    B, Hq, Hkv, D = 7, 8, 2, 32
    P = pa.pages_per_tile(bs, 10 ** 6)
    tile = P * bs
    MB = 2 * P + P // 2
    visible = [tile, tile - 1, 1, tile + 3, MB * bs, MB * bs + 3, 1]
    _, (kq, ks, vq, vs), pages = make_qpools(B, MB, bs, Hkv, D, qdtype,
                                             seed=80 + bs)
    pages = pages.at[B - 1, :].set(0)                 # last row idle
    q = rand((B, C, Hq, D), 81)
    scales = dict(k_scale=ks, v_scale=vs)
    if C == 1:
        lengths = jnp.asarray(visible, jnp.int32)
        got = pa.paged_decode(q, kq, vq, pages, lengths, interpret=True,
                              **scales)
        want = fa_ref.paged_decode_reference(q, kq, vq, pages, lengths,
                                             **scales)
    else:
        pos = jnp.asarray([max(v - C, 0) for v in visible], jnp.int32)
        got = pa.paged_prefill(q, kq, vq, pages, pos, interpret=True,
                               **scales)
        want = fa_ref.paged_prefill_reference(q, kq, vq, pages, pos,
                                              **scales)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-5, rtol=1e-5)


def test_ops_dispatch_modes_agree_quant():
    """All three CPU-runnable modes through the ops layer, same result."""
    B, bs, MB, Hq, Hkv, D = 2, 8, 4, 4, 2, 32
    _, (kq, ks, vq, vs), pages = make_qpools(B, MB, bs, Hkv, D, jnp.int8,
                                             seed=41)
    q = rand((B, 1, Hq, D), 42)
    qc = rand((B, 3, Hq, D), 43)
    lengths = jnp.asarray([7, 2 * bs], jnp.int32)
    pos = jnp.asarray([2, bs - 2], jnp.int32)
    outs_d, outs_p = [], []
    for mode in ("xla", "xla_chunked", "pallas_interpret"):
        with mode_ctx(mode):
            outs_d.append(np.asarray(ops.attention_decode_paged(
                q, kq, vq, pages, lengths, k_scale=ks, v_scale=vs)))
            outs_p.append(np.asarray(ops.attention_prefill_paged(
                qc, kq, vq, pages, pos, k_scale=ks, v_scale=vs)))
    for got_d, got_p in zip(outs_d[1:], outs_p[1:]):
        np.testing.assert_allclose(got_d, outs_d[0], atol=1e-5, rtol=1e-5)
        np.testing.assert_allclose(got_p, outs_p[0], atol=1e-5, rtol=1e-5)


# ---------------------------------------------------------------------- #
# fused quant write
# ---------------------------------------------------------------------- #

@pytest.mark.parametrize("qdtype", QDTYPES, ids=QIDS)
def test_paged_write_quant_bitwise_across_modes(qdtype):
    """The Pallas fused quant-scatter and the jnp quantize-then-scatter
    must produce BIT-IDENTICAL pools and scales: the engine flips kernel
    modes between runs and the prefix digests assume the pool bytes are
    a pure function of the written tokens."""
    B, C, bs, MB, Hkv, D = 2, 5, 4, 4, 2, 16
    NB = B * MB + 1
    pool = jnp.zeros((NB, bs, Hkv, D), qdtype)
    scale = jnp.zeros((NB, bs, Hkv), quant.SCALE_DTYPE)
    new = rand((B, C, Hkv, D), 52)
    perm = np.random.default_rng(53).permutation(np.arange(1, NB))
    pages = jnp.asarray(perm[:B * MB].reshape(B, MB), jnp.int32)
    pos = jnp.asarray([3, 9], jnp.int32)
    with mode_ctx("xla"):
        want_p, want_s = ops.paged_cache_write(pool, new, pages, pos,
                                               pool_scale=scale)
    with mode_ctx("pallas_interpret"):
        got_p, got_s = ops.paged_cache_write(pool, new, pages, pos,
                                             pool_scale=scale)
    np.testing.assert_array_equal(
        np.asarray(got_p).view(np.uint8), np.asarray(want_p).view(np.uint8))
    np.testing.assert_array_equal(np.asarray(got_s), np.asarray(want_s))


def test_paged_write_quant_round_trip():
    """write -> dequant recovers the written tokens within int8 error."""
    B, C, bs, MB, Hkv, D = 2, 4, 4, 3, 2, 16
    NB = B * MB + 1
    pool = jnp.zeros((NB, bs, Hkv, D), jnp.int8)
    scale = jnp.zeros((NB, bs, Hkv), quant.SCALE_DTYPE)
    new = rand((B, C, Hkv, D), 60)
    pages = jnp.asarray(1 + np.arange(B * MB).reshape(B, MB), jnp.int32)
    pos = jnp.asarray([0, bs - 1], jnp.int32)
    with mode_ctx("pallas_interpret"):
        pool2, scale2 = ops.paged_cache_write(pool, new, pages, pos,
                                              pool_scale=scale)
    back = quant.dequantize(pool2, scale2)
    for b in range(B):
        for i in range(C):
            p = int(pos[b]) + i
            blk, slot = int(pages[b, p // bs]), p % bs
            np.testing.assert_allclose(
                np.asarray(back[blk, slot]), np.asarray(new[b, i]),
                atol=2e-2, rtol=2e-2)


@pytest.mark.parametrize("mode", ["xla", "pallas_interpret"])
def test_paged_write_quant_overrun_hits_garbage_block(mode):
    """The overrun->garbage-block guarantee must hold for the scale
    scatter too — an overrun scale landing in a live block would corrupt
    a neighbour's dequant even with the payload safely redirected."""
    B, C, bs, MB, Hkv, D = 1, 4, 4, 3, 2, 8
    NB = B * MB + 1
    pool = jnp.zeros((NB, bs, Hkv, D), jnp.int8)
    scale = jnp.full((NB, bs, Hkv), 7.0, quant.SCALE_DTYPE)
    new = rand((B, C, Hkv, D), 62)
    pages = jnp.asarray([[3, 1, 2]], jnp.int32)
    pos = jnp.asarray([bs * MB - 2], jnp.int32)   # tokens 2,3 overrun
    with mode_ctx(mode):
        out_p, out_s = ops.paged_cache_write(pool, new, pages, pos,
                                             pool_scale=scale)
    out_s = np.asarray(out_s)
    # in-bounds scales land in the last column's block (id 2), overruns
    # in garbage block 0; everything else keeps the 7.0 sentinel
    assert (out_s[2, bs - 2:] != 7.0).all()
    assert (out_s[0, :2] != 7.0).all()
    mask = np.ones((NB, bs), bool)
    mask[0, :2] = False
    mask[2, bs - 2:] = False
    np.testing.assert_array_equal(out_s[mask], 7.0)


# ---------------------------------------------------------------------- #
# engine end-to-end
# ---------------------------------------------------------------------- #

CFG = ModelConfig(name="qkv", family="dense", n_layers=2, d_model=64,
                  n_heads=4, n_kv_heads=2, d_ff=128, vocab_size=97,
                  head_dim=16, remat="none")
HYB = ModelConfig(name="qhyb", family="hybrid", n_layers=2, d_model=64,
                  n_heads=4, n_kv_heads=2, d_ff=128, vocab_size=97,
                  head_dim=16, ssm_state=16, ssm_head_dim=32, ssm_chunk=4,
                  attn_every=2, remat="none")
# head_dim 64: the geometry the bytes-ratio acceptance bound is stated at
CFG64 = ModelConfig(name="qkv64", family="dense", n_layers=1, d_model=128,
                    n_heads=2, n_kv_heads=1, d_ff=128, vocab_size=97,
                    head_dim=64, remat="none")

_PARAMS: dict[str, dict] = {}


def init_params(cfg=CFG):
    if cfg.name not in _PARAMS:
        api = get_model(cfg)
        _PARAMS[cfg.name] = nn.init(
            lambda t: api.forward(t), jax.random.key(0),
            jnp.zeros((1, 8), jnp.int32))
    return _PARAMS[cfg.name]


def run_streams(cfg, n=5, **kw):
    eng = ServingEngine(get_model(cfg), init_params(cfg), max_batch=3,
                        max_seq=64, chunk=8, **kw)
    rng = np.random.default_rng(0)
    for uid in range(n):
        eng.submit(Request(uid=uid,
                           prompt=rng.integers(1, 96, 11 + uid).tolist(),
                           max_new_tokens=10))
    return {r.uid: r.generated for r in eng.run_until_drained()}, eng


@pytest.mark.parametrize("cfg", [CFG, HYB], ids=["dense", "hybrid"])
def test_engine_int8_streams_identical_across_modes(cfg):
    xla, e = run_streams(cfg, kv_dtype="int8")
    pi, _ = run_streams(cfg, kv_dtype="int8", kernels="pallas_interpret")
    assert e.kv_dtype == "int8"
    assert e.state["kv"]["k"].dtype == jnp.int8 if cfg is HYB else True
    assert xla == pi, "int8 streams differ between kernel modes"


def test_engine_int8_greedy_stability():
    """Quantization may flip near-tied argmaxes, but most greedy streams
    must survive intact; the divergence rate is the reported number.

    The baseline pins kv_dtype="native" so the int8 CI leg's
    REPRO_KV_DTYPE can't quantize BOTH engines and pass vacuously."""
    base, eb = run_streams(CFG, n=6, kv_dtype="native")
    q, eq = run_streams(CFG, n=6, kv_dtype="int8")
    assert eb.kv_dtype == "fp32" and eq.kv_dtype == "int8"
    div = sum(base[u] != q[u] for u in base) / len(base)
    print(f"\nint8 greedy divergence rate: {div:.2f} "
          f"({sum(base[u] != q[u] for u in base)}/{len(base)} streams)")
    assert div <= 0.5, f"int8 pools diverge {div:.0%} of greedy streams"


def test_engine_int8_spec_decode_stays_bitwise():
    plain, _ = run_streams(CFG, kv_dtype="int8")
    spec, e = run_streams(CFG, kv_dtype="int8", spec_k=3)
    assert e.spec is not None
    assert spec == plain, "speculation changed an int8 token stream"


def test_engine_scale_leaves_and_reset_safety():
    """Scale leaves exist, carry the block axis at 1, and survive slot
    admission untouched (the _admit reset must skip them — zeroing would
    corrupt every live block's dequant)."""
    _, eng = run_streams(CFG, n=4, kv_dtype="int8")
    ks = eng.state["k_scale"]
    assert ks.dtype == quant.SCALE_DTYPE
    assert ks.shape[1] == eng.num_blocks     # block axis at 1
    assert eng.state["k"].dtype == jnp.int8
    # 4 requests through 3 slots => slot reuse happened; live scales must
    # be non-zero (a zeroed scale dequantizes the whole block to 0)
    assert float(jnp.abs(ks[:, 1:]).max()) > 0.0


def test_kv_bytes_per_token_ratio_at_d64():
    """The acceptance bound: int8 pools + scales <= 0.55x the bf16 bytes
    at head_dim 64 — (D + 4) / (2D) = 0.531, from spec accounting."""
    _, e_bf = run_streams(CFG64, n=1, cache_dtype=jnp.bfloat16,
                          kv_dtype="native")
    _, e_q = run_streams(CFG64, n=1, cache_dtype=jnp.bfloat16,
                         kv_dtype="int8")
    b_bf, b_q = e_bf.kv_bytes_per_token(), e_q.kv_bytes_per_token()
    ratio = b_q / b_bf
    print(f"\nkv bytes/token: bf16={b_bf:.0f} int8={b_q:.0f} "
          f"ratio={ratio:.3f}")
    assert ratio <= 0.55, f"int8 bytes ratio {ratio:.3f} > 0.55"
    assert e_q.metrics_summary()["kv_bytes_per_token"] == b_q


def test_engine_env_var_resolution(monkeypatch):
    monkeypatch.setenv("REPRO_KV_DTYPE", "int8")
    eng = ServingEngine(get_model(CFG), init_params(CFG), max_batch=2,
                        max_seq=32, chunk=8)
    assert eng.kv_dtype == "int8"
    assert eng.state["k"].dtype == jnp.int8
    # explicit arg wins over the env
    eng2 = ServingEngine(get_model(CFG), init_params(CFG), max_batch=2,
                         max_seq=32, chunk=8, kv_dtype="native")
    assert eng2.kv_dtype == "fp32"


def test_engine_fp8_requested_always_quantizes():
    """kv_dtype=fp8 allocates float8_e4m3fn pools — never silently
    unquantized."""
    _, eng = run_streams(CFG, n=2, kv_dtype="fp8")
    assert eng.kv_dtype == "fp8"
    assert eng.state["k"].dtype == quant.FP8_DTYPE
