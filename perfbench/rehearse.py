#!/usr/bin/env python3
"""Rehearse the benchmark without a chip (no test loads this file).

    python3 perfbench/rehearse.py smoke [cell ...]   # CPU, smoke size
    python3 perfbench/rehearse.py aot [cell ...]     # compile for a v5e

``smoke`` runs each cell through the harness on the CPU at the size of
:mod:`harness.smoke`, untraced and traced, and prints each result line:
it finds wrong paths, arguments and control flow. It gives the CPU four
virtual devices, so a four-replica cell runs on four. ``aot`` compiles each
cell's warmed step shapes at their real size for a described TPU v5e (the
TPU compiler ships with jaxlib; nothing runs) and prints each program's
``memory_analysis``: it finds kernels Mosaic refuses and programs that do
not fit the chip. Without cell names it takes every cell of
``BENCHMARK.json``.
"""

from __future__ import annotations

import os
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))


def cells(names: list[str]) -> list[str]:
    if names:
        return names
    import json
    spec = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    return [w["name"] for w in spec["workloads"]]


def smoke(names: list[str]) -> None:
    import json

    from harness import drive, spec
    from harness.smoke import smoke_cell
    for name in cells(names):
        for trace in (False, True):
            cell = smoke_cell(spec.load_cell(name))
            t0 = time.perf_counter()
            res = drive.run(cell, seed=2**33 + 7, seconds=3.0, trace=trace,
                            t_start=t0)
            print(f"{name} trace={int(trace)} "
                  f"({time.perf_counter() - t0:.1f}s): {json.dumps(res)}",
                  flush=True)


def aot(names: list[str]) -> None:
    import functools
    import math

    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from harness import drive, spec
    from harness.model import for_cell
    from repro.models.registry import get_model
    from repro.serving.engine import ServingEngine

    drive.setup_context()
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    dev = SingleDeviceSharding(topo.devices[0])

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=dev)

    for name in cells(names):
        cell = spec.load_cell(name)
        m = for_cell(cell)
        api = get_model(m.to_repro())
        e = cell["engine"]
        B, C0, bs = e["max_batch"], e["chunk"], 16
        mb = math.ceil((e["max_seq"] + C0) / bs)
        params = {n: sds(((depth,) if depth else ()) + s, dt)
                  for n, (depth, s, dt, _) in m.leaves().items()}
        state = jax.tree.map(
            lambda a: sds(a.shape, a.dtype),
            api.paged_state_specs(B, B * mb + 1, bs, jnp.bfloat16))
        # the engine's own step function, on an engine that holds nothing
        eng = object.__new__(ServingEngine)
        eng.api, eng.kernels, eng._env = api, "pallas", None
        for C, do_sample in drive.warm_shapes(cell):
            i32 = functools.partial(sds, dtype=jnp.int32)
            f32 = functools.partial(sds, dtype=jnp.float32)
            t0 = time.perf_counter()
            fn = functools.partial(eng._step_paged_fn, do_sample=do_sample)
            compiled = jax.jit(fn).lower(
                params, i32((B, C)), state, i32((B, mb)), i32((B,)),
                i32((B,)), f32((B,)), i32((B,)), f32((B,)), i32((B,)),
                i32((B,))).compile()
            ma = compiled.memory_analysis()
            text = compiled.as_text()
            print(f"{name} C={C} do_sample={do_sample}: compiled in "
                  f"{time.perf_counter() - t0:.1f}s; arguments "
                  f"{ma.argument_size_in_bytes}, outputs "
                  f"{ma.output_size_in_bytes}, temps "
                  f"{ma.temp_size_in_bytes}, aliased "
                  f"{ma.alias_size_in_bytes}, generated code "
                  f"{ma.generated_code_size_in_bytes} bytes; "
                  f"tpu_custom_call x{text.count('tpu_custom_call')}",
                  flush=True)


def main() -> int:
    if len(sys.argv) < 2 or sys.argv[1] not in ("smoke", "aot"):
        print(__doc__, file=sys.stderr)
        return 2
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=4").strip()
    (smoke if sys.argv[1] == "smoke" else aot)(sys.argv[2:])
    return 0


if __name__ == "__main__":
    sys.exit(main())
