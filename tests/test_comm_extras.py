
"""Communicator extras: bucketing, error feedback (subprocess collectives)."""

import jax.numpy as jnp
import numpy as np

from repro.comm import flatten_buckets


def test_flatten_buckets_respects_size():
    tree = {f"p{i}": jnp.zeros((1024, 1024), jnp.float32)  # 4 MiB each
            for i in range(10)}
    buckets = flatten_buckets(tree, bucket_bytes=8 * 2**20)
    assert sum(len(b) for b in buckets) == 10
    assert all(len(b) <= 2 for b in buckets)      # 2 x 4 MiB fits, 3 doesn't
    flat = [k for b in buckets for k in b]
    assert flat == sorted(tree)                    # deterministic order


def test_single_giant_tensor_gets_own_bucket():
    tree = {"big": jnp.zeros((64, 1024, 1024), jnp.float32),
            "small": jnp.zeros(4, jnp.float32)}
    buckets = flatten_buckets(tree, bucket_bytes=2**20)
    assert ["big"] in buckets


EF_CODE = """
import jax, jax.numpy as jnp, numpy as np
from repro.launch.mesh import make_host_mesh
from jax.sharding import PartitionSpec as P
from repro.comm import error_feedback_reduce

mesh = make_host_mesh((4,), ("data",))
rng = np.random.default_rng(0)
x = jnp.asarray(rng.normal(size=(4, 256)), jnp.float32)
err0 = jnp.zeros((4, 256), jnp.float32)

f = jax.shard_map(lambda v, e: error_feedback_reduce(v, e, "data"),
                  mesh=mesh, in_specs=(P("data"), P("data")),
                  out_specs=(P("data"), P("data")), check_vma=False)
exact = np.asarray(x).mean(0)

# the EF guarantee: the RUNNING MEAN of estimates converges to the exact
# value (the carried residual cancels quantization bias over steps), and
# the residual stays bounded
err = err0
ests = []
for _ in range(16):
    est, err = f(x, err)
    ests.append(np.asarray(est)[0])
e_mean = np.abs(np.mean(ests, axis=0) - exact).max()
one, _ = f(x, err0)
e_singleshot = np.abs(np.asarray(one)[0] - exact).max()
assert e_mean <= e_singleshot * 0.75, (e_mean, e_singleshot)
assert np.abs(np.asarray(err)).max() < 1.0
print("EF-OK", e_mean, e_singleshot)
"""


def test_error_feedback_runs(subproc):
    out = subproc(EF_CODE, devices=4)
    assert "EF-OK" in out
