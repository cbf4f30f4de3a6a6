"""Compile the main-path Pallas kernels for a described TPU v5e.

Interpret mode never checks the TPU's (8, 128) tiling rules, VMEM limits
or Mosaic's lowering coverage; the TPU compiler, which ships with jaxlib,
does — for a chip that is described, not attached. Each case lowers one
kernel at real widths (granite-moe-1b-a400m attention: Hq 16, Hkv 8,
D 64, block 16; the paged walk also at mistral-nemo-12b's Hq 32, Hkv 8,
D 128 and the benchmark cell's 8 x 256 chunk over a 528-column table;
mamba2-370m SSD: 32 heads, P 64, N 128, chunk 256) and
asserts the compiled program holds a ``tpu_custom_call`` and the lowered
module the kernel's ``name=``, which a profiler trace shows. Nothing
runs.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, so a module-level call would
make pytest-xdist workers collect different tests.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

B, C, HQ, HKV, D, BS, MB = 4, 64, 16, 8, 64, 16, 32
NB = B * MB + 1


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this jaxlib
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # described-chip executables can be written to the persistent cache
    # but never read back here; keep the cache out of these compiles
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topo
    jax.config.update("jax_enable_compilation_cache", prev)


@pytest.fixture(scope="module")
def one_chip(topo):
    dev = SingleDeviceSharding(topo.devices[0])

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=dev)
    return spec


def compile_has_kernel(name, fn, *args):
    lowered = jax.jit(fn).lower(*args)
    assert f'kernel_name = "{name}"' in lowered.as_text()
    assert "tpu_custom_call" in lowered.compile().as_text()


def _pools(spec, dtype):
    quantized = dtype == jnp.int8
    pool = spec((NB, BS, HKV, D), dtype)
    scale = spec((NB, BS, HKV), jnp.float32) if quantized else None
    return pool, scale


# mistral-nemo-12b attention at the benchmark cell's shape: 8 rows, chunk
# 256, a 528-column page table (max_seq 8448 / block 16), 4225 blocks
MISTRAL = dict(B=8, HQ=32, HKV=8, D=128, BS=16, MB=528, NB=4225)
GRANITE = dict(B=B, HQ=HQ, HKV=HKV, D=D, BS=BS, MB=MB, NB=NB)


@pytest.mark.parametrize("dtype,width,shape", [
    pytest.param(jnp.bfloat16, 1, GRANITE, id="decode-bf16"),
    pytest.param(jnp.bfloat16, C, GRANITE, id="prefill-bf16"),
    pytest.param(jnp.int8, 1, GRANITE, id="decode-int8"),
    pytest.param(jnp.int8, C, GRANITE, id="prefill-int8"),
    pytest.param(jnp.bfloat16, 1, MISTRAL, id="mistral-decode-bf16"),
    pytest.param(jnp.bfloat16, 256, MISTRAL, id="mistral-prefill-bf16"),
])
def test_paged_walk_compiles(one_chip, dtype, width, shape):
    from repro.kernels.flash_attention import paged_attention as pa
    s = shape
    pool = one_chip((s["NB"], s["BS"], s["HKV"], s["D"]), dtype)
    q = one_chip((s["B"], width, s["HQ"], s["D"]), jnp.bfloat16)
    pages = one_chip((s["B"], s["MB"]), jnp.int32)
    rows = one_chip((s["B"],), jnp.int32)
    entry = pa.paged_decode if width == 1 else pa.paged_prefill
    if dtype == jnp.bfloat16:
        compile_has_kernel("paged_attention", lambda *a: entry(*a),
                           q, pool, pool, pages, rows)
    else:
        scale = one_chip((s["NB"], s["BS"], s["HKV"]), jnp.float32)
        compile_has_kernel(
            "paged_attention",
            lambda q_, k, v, g, r, ks, vs: entry(q_, k, v, g, r,
                                                 k_scale=ks, v_scale=vs),
            q, pool, pool, pages, rows, scale, scale)


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.int8],
                         ids=["bf16", "quantized"])
def test_paged_write_compiles(one_chip, dtype):
    from repro.kernels.flash_attention import paged_attention as pa
    pool, scale = _pools(one_chip, dtype)
    new = one_chip((B, C, HKV, D), jnp.bfloat16)
    pages = one_chip((B, MB), jnp.int32)
    pos = one_chip((B,), jnp.int32)
    if scale is None:
        compile_has_kernel("paged_write", lambda *a: pa.paged_write(*a),
                           pool, new, pages, pos)
    else:
        compile_has_kernel(
            "paged_write",
            lambda p, n, g, r, s: pa.paged_write(p, n, g, r, pool_scale=s),
            pool, new, pages, pos, scale)


def test_tp4_sharded_walk_compiles(topo):
    """The tp=4 engine's attention: ops dispatch wraps the walk in
    shard_map over the kv heads, so each chip walks Hkv = 2."""
    import dataclasses

    from repro.core import context as ctx
    from repro.distributed.sharding import ShardingEnv, sharding_env
    from repro.kernels import ops

    mesh = Mesh(np.asarray(topo.devices[:4]).reshape(1, 4),
                ("data", "model"),
                axis_types=(jax.sharding.AxisType.Auto,) * 2)
    heads = NamedSharding(mesh, P(None, None, "model", None))
    repl = NamedSharding(mesh, P())
    q = jax.ShapeDtypeStruct((B, C, HQ, D), jnp.bfloat16, sharding=heads)
    pool = jax.ShapeDtypeStruct((NB, BS, HKV, D), jnp.bfloat16,
                                sharding=heads)
    pages = jax.ShapeDtypeStruct((B, MB), jnp.int32, sharding=repl)
    pos = jax.ShapeDtypeStruct((B,), jnp.int32, sharding=repl)
    pallas = dataclasses.replace(ctx.get_default_context(), kernels="pallas")
    with ctx.context_scope(pallas), sharding_env(ShardingEnv(mesh=mesh)):
        lowered = jax.jit(ops.attention_prefill_paged).lower(
            q, pool, pool, pages, pos)
    text = lowered.compile().as_text()
    assert "tpu_custom_call" in text
    assert 'kernel_name = "paged_attention"' in lowered.as_text()
    # the per-chip kernel sees its 2-head shard of the pool
    assert f"tensor<{NB}x{BS}x2x{D}xbf16>" in lowered.as_text()


def test_flash_attention_compiles(one_chip):
    from repro.kernels.flash_attention import flash_attention as fa
    q = one_chip((1, 512, HQ, D), jnp.bfloat16)
    kv = one_chip((1, 512, HKV, D), jnp.bfloat16)
    compile_has_kernel("flash_attention", lambda *a: fa.flash_attention(*a),
                       q, kv, kv)


def test_flash_decode_compiles(one_chip):
    from repro.kernels.flash_attention import flash_attention as fa
    q = one_chip((B, 1, HQ, D), jnp.bfloat16)
    cache = one_chip((B, 1024, HKV, D), jnp.bfloat16)
    lengths = one_chip((B,), jnp.int32)
    compile_has_kernel("flash_decode", lambda *a: fa.flash_decode(*a),
                       q, cache, cache, lengths)


def test_ssd_compiles(one_chip):
    from repro.kernels.ssd import ssd_kernel
    Bt, S, H, Pd, G, N = 1, 1024, 32, 64, 1, 128
    compile_has_kernel(
        "ssd", lambda *a: ssd_kernel.ssd(*a, chunk=256, return_state=True),
        one_chip((Bt, S, H, Pd), jnp.bfloat16),
        one_chip((Bt, S, H), jnp.float32),
        one_chip((H,), jnp.float32),
        one_chip((Bt, S, G, N), jnp.bfloat16),
        one_chip((Bt, S, G, N), jnp.bfloat16),
        one_chip((H,), jnp.float32))
