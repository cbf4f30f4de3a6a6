"""Paper §2.3 / Listing 3: distributed all-reduce scaling.

Measures the communicator's grad all-reduce (plain / bf16 / int8-compressed)
over every device this process sees, in the harness's own process: a chip
belongs to one process at a time, so no child process is started. On a
CPU host, ``XLA_FLAGS=--xla_force_host_platform_device_count=8`` set before
jax starts gives the run 8 devices; on one device the reduce is a no-op.
"""

import time

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.comm import Communicator, compressed_all_reduce
from repro.launch.mesh import make_host_mesh


def main() -> None:
    n_dev = len(jax.devices())
    mesh = make_host_mesh((n_dev,), ("data",))
    comm = Communicator(mesh, axis="data")
    bodies = {
        "fp32": lambda v: comm.all_reduce(v, mean=True),
        "bf16": lambda v: compressed_all_reduce(v, "data", method="bf16"),
        "int8": lambda v: compressed_all_reduce(v, "data", method="int8"),
    }
    for size_mb in (1, 16):
        x = jnp.ones((n_dev, size_mb * 2**20 // 4), jnp.float32)
        for name, body in bodies.items():
            f = jax.jit(jax.shard_map(body, mesh=mesh, in_specs=P("data"),
                                      out_specs=P("data"), check_vma=False))
            jax.block_until_ready(f(x))
            ts = []
            for _ in range(5):
                t0 = time.perf_counter()
                jax.block_until_ready(f(x))
                ts.append(time.perf_counter() - t0)
            us = sorted(ts)[2] * 1e6
            print(f"collectives/allreduce_{size_mb}MB_{name},{us:.1f},"
                  f"{size_mb / (us / 1e6) / 1024:.2f}GBps x{n_dev} "
                  f"{jax.devices()[0].platform}", flush=True)

if __name__ == "__main__":
    main()
