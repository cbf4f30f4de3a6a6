#!/usr/bin/env python3
"""Smoke check: serve granite-moe-1b-a400m at full width on a TPU.

    python chip_smoke.py             # one chip
    python chip_smoke.py --chips 4   # the multi-chip paths only

One chip: builds the model at its published widths with random weights
made from ``--seed``, in bf16 (``pure_bf16`` context, bf16 paged KV), and
serves 4 requests (128-token prompts, 32 new tokens each, chunk 64,
max_seq 512) through :class:`ServingEngine` with the kernels the platform
picks (compiled Pallas on a TPU). It checks that the compiled step holds a
Pallas kernel (``tpu_custom_call``), that every request finishes without
error, and that one prefill step's logits agree with the XLA reference
mode on the same chip.

``--chips 4`` runs only what exists across chips: a tp=4 engine's prefill
logits against a tp=1 engine on device 0, and four one-chip replicas
behind the :class:`Router`, each of which must serve requests.

The ``[smoke]`` lines are a smoke check, not benchmark numbers. The last
line is one JSON object naming the device; a failed check exits non-zero
without it. With no TPU it exits 1 at once: there is no CPU fallback.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ARCH = "granite-moe-1b-a400m"
REQUESTS, PROMPT_LEN, MAX_NEW, CHUNK, MAX_SEQ = 4, 128, 32, 64, 512
# relative RMS difference allowed between two bf16 runs of one prefill
# chunk's logits (Pallas vs the XLA reference; tp=4 vs tp=1). They round
# in different orders: on CPU, 24 narrow granite-moe layers differed by
# 9e-3 (kernel) and 1.2e-2 (tp=4). A wrong kernel or layout differs by
# O(1). Top-1 agreement is printed, not gated: random weights give
# near-tied logits.
LOGITS_TOL = 5e-2
# what the compiled step must contain, and the kernel mode that puts it
# there (a CPU rehearsal swaps both for the interpret-mode build)
KERNELS = "pallas"
KERNEL_OP = "tpu_custom_call"


class SmokeFailure(Exception):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def log(msg: str) -> None:
    print(f"[smoke] {msg}", flush=True)


def build(cfg, seed: int):
    """The model's api and random weights, made on device from ``seed``
    in the ambient context's parameter dtype."""
    import jax
    import jax.numpy as jnp

    import repro.core as nn
    from repro.models.registry import get_model

    api = get_model(cfg)
    init = jax.jit(lambda key: nn.init(lambda t: api.forward(t), key,
                                       jnp.zeros((1, 8), jnp.int32)))
    t0 = time.perf_counter()
    params = jax.block_until_ready(init(jax.random.key(seed)))
    by_dtype: dict[str, int] = {}
    for v in params.values():
        by_dtype[str(v.dtype)] = by_dtype.get(str(v.dtype), 0) + int(v.size)
    # norm gains and the MoE router are float32 by design
    log(f"model {cfg.name}: {sum(by_dtype.values()):,} params "
        f"({', '.join(f'{n:,} {d}' for d, n in sorted(by_dtype.items()))}),"
        f" init {time.perf_counter() - t0:.1f}s (compile included)")
    return api, params


def engine_kw() -> dict:
    import jax.numpy as jnp
    return dict(max_batch=REQUESTS, max_seq=MAX_SEQ, chunk=CHUNK,
                cache_dtype=jnp.bfloat16)


def make_engine(api, params, **kw):
    from repro.serving.engine import ServingEngine
    return ServingEngine(api, params, **engine_kw(), **kw)


def make_requests(cfg, seed: int, n: int):
    import numpy as np

    from repro.serving.engine import Request
    rng = np.random.default_rng(seed)
    return [Request(uid=i, max_new_tokens=MAX_NEW,
                    prompt=rng.integers(1, cfg.vocab_size,
                                        PROMPT_LEN).tolist())
            for i in range(n)]


def step_hlo(engine) -> str:
    """Compiled HLO of the engine's chunk-wide prefill step."""
    import jax.numpy as jnp
    B, C = engine.B, engine.chunk

    def i32(*shape):
        return jnp.zeros(shape, jnp.int32)

    return engine._step.lower(
        engine.params, i32(B, C), engine.state,
        jnp.asarray(engine.scheduler.pages), i32(B),
        jnp.ones(B, jnp.int32), jnp.zeros(B, jnp.float32), i32(B),
        jnp.ones(B, jnp.float32), i32(B), i32(B),
        do_sample=False).compile().as_text()


def prefill_logits(engine, tokens):
    """float32 (B, C, V) logits of one prefill chunk written at position 0
    of the engine's fresh pool, through its own kernel mode and mesh."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    import repro.core as nn
    B, C = tokens.shape
    mb = engine.max_blocks
    pages = jnp.asarray(1 + np.arange(B * mb, dtype=np.int32).reshape(B, mb))
    pos = jnp.zeros(B, jnp.int32)
    length = jnp.full(B, C, jnp.int32)

    def fn(params, state, toks, pages):
        with engine._kernel_scope():
            logits, _ = nn.apply(
                lambda t, s, g, p, n: engine.api.prefill_paged(
                    t, s, g, p, n, last_only=False),
                params, toks, state, pages, pos, length)
        return logits.astype(jnp.float32)

    return np.asarray(jax.jit(fn)(engine.params, engine.state,
                                  jnp.asarray(tokens), pages))


def compare_logits(got, want, what: str) -> None:
    import numpy as np
    check(bool(np.isfinite(got).all() and np.isfinite(want).all()),
          f"{what}: non-finite logits")
    rel = float(np.linalg.norm(got - want) / np.linalg.norm(want))
    top1 = float(np.mean(got.argmax(-1) == want.argmax(-1)))
    log(f"{what}: logits {got.shape}, relative RMS difference {rel:.2e} "
        f"(tolerance {LOGITS_TOL:.0e}), max |diff| "
        f"{float(np.abs(got - want).max()):.3e}, top-1 agreement "
        f"{top1:.3f}")
    check(rel <= LOGITS_TOL, f"{what}: relative RMS difference {rel:.2e} "
                             f"> {LOGITS_TOL:.0e}")


def check_served(reqs, what: str) -> int:
    for r in reqs:
        check(r.done and r.error is None,
              f"{what}: request {r.uid} done={r.done} error={r.error!r}")
        check(len(r.generated) == MAX_NEW,
              f"{what}: request {r.uid} generated {len(r.generated)} "
              f"of {MAX_NEW} tokens")
    return sum(len(r.generated) for r in reqs)


def one_chip(cfg, seed: int) -> None:
    import jax
    import numpy as np

    api, params = build(cfg, seed)
    engine = make_engine(api, params)
    check(engine.kernels == KERNELS,
          f"engine resolved kernels={engine.kernels!r}, not {KERNELS!r}")
    check(engine.kv_dtype == "bf16", f"KV pools are {engine.kv_dtype}")
    log(f"engine: kernels={engine.kernels}, KV {engine.kv_dtype} "
        f"{engine.num_blocks} blocks x {engine.block_size} tok, batch "
        f"{engine.B}, chunk {engine.chunk}, max_seq {engine.max_seq}")

    t0 = time.perf_counter()
    hlo = step_hlo(engine)
    log(f"compiled prefill step in {time.perf_counter() - t0:.1f}s; "
        f"{KERNEL_OP} x{hlo.count(KERNEL_OP)}")
    check(KERNEL_OP in hlo, f"compiled step has no {KERNEL_OP}")

    reqs = make_requests(cfg, seed, REQUESTS)
    tokens = np.asarray([r.prompt[:CHUNK] for r in reqs], np.int32)
    compare_logits(prefill_logits(engine, tokens),
                   prefill_logits(make_engine(api, params, kernels="xla"),
                                  tokens),
                   f"prefill {KERNELS} vs xla")

    for r in reqs:
        engine.submit(r)
    steps = 0
    t0 = time.perf_counter()
    while engine.has_work():
        engine.step()
        steps += 1
        check(steps <= 10 * (PROMPT_LEN + MAX_NEW), "engine did not drain")
    wall = time.perf_counter() - t0
    tokens_out = check_served(reqs, "serve")
    check(engine.worker_crashed == 0, "engine worker crashed")
    log(f"served {len(reqs)}/{REQUESTS} requests, {tokens_out} tokens in "
        f"{steps} steps, {wall:.1f}s wall (first-step compile included)")
    stats = jax.devices()[0].memory_stats() or {}
    log(f"peak_bytes_in_use {stats.get('peak_bytes_in_use', 'n/a')}")


def four_chips(cfg, seed: int) -> None:
    import jax
    import numpy as np

    from repro.serving.router import Router, make_replica_engines
    check(len(jax.devices()) >= 4, f"--chips 4 needs 4 devices, found "
                                   f"{len(jax.devices())}")
    api, params = build(cfg, seed)
    reqs = make_requests(cfg, seed, REQUESTS)
    tokens = np.asarray([r.prompt[:CHUNK] for r in reqs], np.int32)
    tp1 = make_engine(api, params)
    tp4 = make_engine(api, params, tp=4)
    check(tp4.kernels == KERNELS, f"tp=4 kernels={tp4.kernels!r}")
    log(f"tp=4 engine over {[d.id for d in tp4.mesh.devices.flat]}: "
        f"KV {tp4.kv_dtype}, layout {tp4.tp_layout()}")
    compare_logits(prefill_logits(tp4, tokens), prefill_logits(tp1, tokens),
                   "prefill tp=4 vs tp=1")
    del tp1, tp4

    engines = make_replica_engines(api, params, replicas=4, **engine_kw())
    # round robin gives every replica two requests, so every chip serves;
    # prefix-affinity placement of random prompts can leave one idle.
    # Every replica compiles its step on its first call: no step deadline
    router = Router(engines, policy="round_robin",
                    step_deadline_s=float("inf"))
    reqs = make_requests(cfg, seed + 1, 2 * len(engines))
    routed = [router.submit(r) for r in reqs]
    t0 = time.perf_counter()
    router.run_until_drained()
    tokens_out = check_served(reqs, "router")
    per_replica = [len(e.completed) for e in engines]
    check(all(n > 0 for n in per_replica),
          f"a replica served nothing: completed per replica {per_replica}")
    check(router.replica_deaths == 0 and
          all(e.worker_crashed == 0 for e in engines),
          f"replica failures: {router.stats()}")
    log(f"router: {len(reqs)} requests over {len(engines)} replicas "
        f"(routed {[routed.count(r) for r in range(len(engines))]}, "
        f"completed {per_replica}), {tokens_out} tokens, 0 failed, "
        f"{time.perf_counter() - t0:.1f}s wall (compiles included)")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: serve on one chip; 4: only the tp=4 and "
                         "4-replica router paths")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform!r}",
              file=sys.stderr)
        return 1
    sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))
    from repro.launch.cache import enable_compile_cache
    log(f"compile cache: {enable_compile_cache()}")

    import repro.core as nn
    from repro.configs import get_arch
    nn.set_default_context(nn.get_extension_context(
        "tpu", type_config="pure_bf16"))
    cfg = get_arch(ARCH)
    log(f"device {dev.device_kind} x{len(jax.devices())}, dtype bf16, "
        f"seed {args.seed}")
    try:
        if args.chips == 1:
            one_chip(cfg, args.seed)
        else:
            four_chips(cfg, args.seed)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
