
"""Beyond-paper optimizations == paper-faithful math (the §Perf safety net)."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.core as nn
from repro.configs.base import ModelConfig
from repro.kernels.flash_attention import ref as fa_ref
from repro.models import transformer as T


def rand(shape, seed=0, dtype=jnp.float32):
    return jnp.asarray(np.random.default_rng(seed).normal(size=shape), dtype)


class TestFoldedChunkedAttention:
    @pytest.mark.parametrize("S,bq", [(256, 32), (512, 64), (256, 128)])
    def test_folded_causal_matches_reference(self, S, bq):
        q, k, v = rand((2, S, 4, 64), 1), rand((2, S, 2, 64), 2), \
            rand((2, S, 2, 64), 3)
        got = fa_ref.mha_chunked(q, k, v, causal=True, block_q=bq, block_k=bq)
        want = fa_ref.mha_reference(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=2e-6, rtol=2e-5)

    def test_noncausal_and_window(self):
        q, k, v = rand((1, 256, 4, 32), 4), rand((1, 256, 4, 32), 5), \
            rand((1, 256, 4, 32), 6)
        for kw in ({"causal": False}, {"causal": True, "window": 64}):
            got = fa_ref.mha_chunked(q, k, v, block_q=64, block_k=64, **kw)
            want = fa_ref.mha_reference(q, k, v, **kw)
            np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                       atol=2e-6, rtol=2e-5)

    def test_grads_match(self):
        q, k, v = rand((1, 128, 2, 32), 7), rand((1, 128, 2, 32), 8), \
            rand((1, 128, 2, 32), 9)
        g1 = jax.grad(lambda q: fa_ref.mha_chunked(
            q, k, v, causal=True, block_q=32, block_k=32).sum())(q)
        g2 = jax.grad(lambda q: fa_ref.mha_reference(
            q, k, v, causal=True).sum())(q)
        np.testing.assert_allclose(np.asarray(g1), np.asarray(g2),
                                   atol=2e-5, rtol=2e-4)

    def test_unrolled_matches_scanned(self):
        q, k, v = rand((1, 256, 2, 32), 10), rand((1, 256, 2, 32), 11), \
            rand((1, 256, 2, 32), 12)
        a = fa_ref.mha_chunked(q, k, v, causal=True, block_q=64, block_k=64)
        b = fa_ref.mha_chunked(q, k, v, causal=True, block_q=64, block_k=64,
                               unroll=True)
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)


CFG = ModelConfig(name="t", family="dense", n_layers=2, d_model=64,
                  n_heads=4, n_kv_heads=2, d_ff=128, vocab_size=97,
                  head_dim=16, remat="none")


class TestChunkedLoss:
    def test_loss_and_grads_match_plain(self):
        cfgc = dataclasses.replace(CFG, loss_chunk=8)
        rng = np.random.default_rng(0)
        toks = jnp.asarray(rng.integers(1, 97, (2, 32)), jnp.int32)
        labs = jnp.asarray(rng.integers(1, 97, (2, 32)), jnp.int32)
        params = nn.init(lambda t: T.forward(CFG, t), jax.random.key(0), toks)
        l1 = nn.apply(lambda t, l: T.loss_fn(CFG, t, l), params, toks, labs)
        l2 = nn.apply(lambda t, l: T.loss_fn(cfgc, t, l), params, toks, labs)
        assert abs(float(l1) - float(l2)) < 1e-5
        g1 = jax.grad(lambda p: nn.apply(
            lambda t, l: T.loss_fn(CFG, t, l), p, toks, labs))(params)
        g2 = jax.grad(lambda p: nn.apply(
            lambda t, l: T.loss_fn(cfgc, t, l), p, toks, labs))(params)
        for k in g1:
            np.testing.assert_allclose(np.asarray(g1[k]), np.asarray(g2[k]),
                                       atol=2e-5, rtol=2e-4)


MERGED_CODE = """
import jax, jax.numpy as jnp, numpy as np
from repro.launch.mesh import make_host_mesh
import repro.core as nn
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.configs.base import ModelConfig
from repro.models import transformer as T
from repro.distributed.sharding import ShardingEnv, sharding_env

# 6 heads on a 4-wide model axis -> merged batch x kv-head path triggers
cfg = ModelConfig(name="m", family="dense", n_layers=1, d_model=48,
                  n_heads=6, n_kv_heads=2, d_ff=64, vocab_size=64,
                  head_dim=8, remat="none")
rng = np.random.default_rng(0)
toks = jnp.asarray(rng.integers(1, 64, (4, 16)), jnp.int32)
params = nn.init(lambda t: T.forward(cfg, t), jax.random.key(0), toks)
ref, _ = nn.apply(lambda t: T.forward(cfg, t), params, toks)  # no mesh

mesh = make_host_mesh((2, 4), ("data", "model"))
env = ShardingEnv(mesh=mesh,
                  axis_rules={"batch": "data", "heads": "model",
                              "batch_kv": ("data", "model"),
                              "seq": None, "embed": None})
with sharding_env(env):
    f = jax.jit(lambda p, t: nn.apply(lambda tt: T.forward(cfg, tt), p, t)[0])
    got = f(params, toks)
np.testing.assert_allclose(np.asarray(ref, np.float32),
                           np.asarray(got, np.float32), atol=2e-2, rtol=2e-2)
print("MERGED-OK")
"""


def test_merged_batch_kv_sharding_matches(subproc):
    out = subproc(MERGED_CODE, devices=8)
    assert "MERGED-OK" in out


class TestSplitProj:
    def test_split_decode_matches_forward(self):
        cfg = ModelConfig(name="s", family="ssm", n_layers=2, d_model=64,
                          n_heads=4, n_kv_heads=1, d_ff=0, vocab_size=97,
                          ssm_state=16, ssm_head_dim=32, ssm_chunk=8,
                          remat="none", ssm_split_proj=True)
        from repro.models import mamba as M
        rng = np.random.default_rng(1)
        S = 8
        seq = jnp.asarray(rng.integers(1, 97, (1, S)), jnp.int32)
        ps = nn.init(lambda t: M.forward(cfg, t), jax.random.key(0), seq)
        full, _ = nn.apply(lambda t: M.forward(cfg, t), ps, seq)
        st = M.init_state(cfg, 1, dtype=jnp.float32)
        outs = []
        for i in range(S):
            lg, st = nn.apply(lambda t, s, p: M.decode_step(cfg, t, s, p),
                              ps, seq[:, i:i + 1], st,
                              jnp.asarray(i, jnp.int32))
            outs.append(lg[:, 0])
        np.testing.assert_allclose(np.asarray(full),
                                   np.asarray(jnp.stack(outs, 1)),
                                   atol=5e-3, rtol=1e-2)
