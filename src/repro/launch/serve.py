"""Serving driver: continuous batching + chunked prefill for any LM arch.

Batch mode (default) drives a synthetic workload through the engine and
prints per-request metrics:

  PYTHONPATH=src python -m repro.launch.serve --arch llama3.2-1b --smoke \
      --requests 8 --max-new 16 --prompt-len 64 --chunk 16 \
      --temperature 0.8 --top-k 40 --top-p 0.95

Server mode (``--port``) serves an actual HTTP/SSE port instead: the
asyncio frontend (:mod:`repro.serving.frontend`) streams tokens per
request over Server-Sent Events while engine worker threads run the step
loop continuously; ``--replicas R`` runs R engine replicas behind the
prefix-affinity router (:mod:`repro.serving.router`), carving the device
set into R disjoint (1, tp) meshes when the devices are there:

  PYTHONPATH=src python -m repro.launch.serve --arch llama3.2-1b --smoke \
      --port 8080 --replicas 2 --max-queue 64
  curl -N localhost:8080/generate -d '{"prompt": [3, 1, 4], "max_new_tokens": 8}'
  curl localhost:8080/health; curl localhost:8080/metrics

Flags:
  --chunk N        prompt tokens absorbed per slot per prefill step (one
                   fused call writes the KV cache / SSM state for the whole
                   chunk); 1 falls back to token-by-token absorption
  --temperature T  sampling temperature for all requests; 0 = greedy argmax
  --top-k K        keep only the K highest-probability tokens (<= 0 = off)
  --top-p P        nucleus sampling: keep the smallest token set with
                   cumulative probability >= P (>= 1 = off)
  --block-size N   paged-KV block size in tokens (families that support it;
                   pure-SSM state stays dense)
  --num-blocks N   KV pool size in blocks (0 = every slot can reach
                   max-seq); admission is gated on free blocks
  --no-paged       force the PR-1 dense per-slot cache layout
  --no-prefix-cache  disable cross-request prompt-prefix block reuse
  --host-cache-gb G  tiered KV cache: size a host-RAM spill pool to G GiB;
                   cold registered prefixes spill there under eviction
                   pressure and fetch back into HBM on a hit (0 = off)
  --host-cache-blocks N  size the host pool in blocks exactly (tests and
                   benches; overrides --host-cache-gb)
  --kv-store DIR   persist registered prefix chains to DIR at the end of a
                   batch run and warm-load them (into the host tier) at
                   startup — digest-keyed, CRC'd, layout-checked; a stale
                   or corrupt store logs a warning and serves cold
  --kernels MODE   kernel mode for the jitted step; by default the platform
                   picks it: the compiled Pallas kernels on a TPU (the
                   paged-attention page-table walk), the ambient context
                   elsewhere (the gather-then-dense XLA references). Set it
                   to compare: xla, xla_chunked, pallas (TPU only; raises
                   elsewhere) or pallas_interpret (the same kernels on the
                   CPU interpreter). Defaults to $REPRO_KERNELS when set.
  --kv-dtype D     paged KV pool storage dtype: native (default; the
                   compute dtype), int8 or fp8 (quantized pools with
                   per-(token, kv-head) scales — quant fused into the
                   write scatter, dequant into the attention walk; ~0.53x
                   the bf16 HBM bytes/token at head_dim 64, so the same
                   pool holds ~2x the cached tokens), or bf16/fp16/fp32.
                   Defaults to $REPRO_KV_DTYPE when set.
  --tp N           tensor parallelism: shard params and the paged KV pools
                   over an N-wide (data=1, model=N) mesh so one engine
                   spans N devices (each holds 1/N of the KV bytes). Needs
                   N devices — on CPU set
                   XLA_FLAGS=--xla_force_host_platform_device_count=N.
                   1 (default) = the single-device engine, unchanged.
  --scheduler P    queue policy (repro.serving.scheduler): "priority"
                   (default; priority classes, FIFO tie-break, block-level
                   preemption of strictly-lower-priority actives under
                   pool pressure) or "fifo" (priorities ignored, never
                   preempts — the literal pre-PR-5 queue)
  --priority LIST  comma-separated priority cycle assigned round-robin
                   across requests (e.g. "0,0,2": every third request is
                   high-priority); higher = more urgent. Default "0".
  --sched-aging S  anti-starvation: a queued request gains one priority
                   class per S seconds of wait (0 = off)
  --spec-k K       speculative decoding: verify up to K n-gram draft
                   tokens per slot per decode step (paged pure-KV
                   families only). The output stream is bitwise the
                   --spec-k 0 stream — drafts change step count, never
                   tokens. 0 (default) = off.
  --spec-ngram N   longest history suffix the proposer matches (default 3)
  --no-spec        force speculative decoding off (overrides --spec-k)
  --port P         serve HTTP/SSE on port P (0 = ephemeral, printed at
                   startup) instead of running the batch workload
  --host H         bind address for --port (default 127.0.0.1)
  --replicas R     engine replicas behind the prefix-affinity router
                   (server mode; needs R*tp devices for per-replica
                   meshes, else replicas share the default device)
  --max-queue N    per-replica admission backpressure: POSTs get 503
                   once a replica's queue holds N requests (default 32)
  --request-timeout S  default per-request wall-clock deadline in server
                   mode: a stream with no completion within S seconds is
                   cancelled (blocks freed) and fails with 504 semantics;
                   a request's own "deadline_s" body field overrides it
                   (0 = unbounded, the default)
  --step-deadline S  replica health watchdog (multi-replica server mode):
                   a replica whose step exceeds S seconds goes SUSPECT,
                   twice consecutively goes DEAD — its queued + in-flight
                   requests migrate bitwise to survivors and probes
                   re-admit it when it recovers (0 = off, the default)
  --shed-below F   graceful degradation: when the alive-replica fraction
                   drops to <= F (and at least one replica is dead),
                   requests at priority <= --shed-priority are shed with
                   503 + Retry-After (default 0.5)
  --shed-priority P  highest priority class shed under degradation
                   (default 0)

Per-request metrics (TTFT, queue wait, decode tok/s, prefix-hit tokens,
speculative acceptance rate when --spec-k is on) print at the end.
"""

from __future__ import annotations

import argparse
import os
import sys
import time


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3.2-1b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--max-batch", type=int, default=4)
    ap.add_argument("--max-seq", type=int, default=128)
    ap.add_argument("--prompt-len", type=int, default=0,
                    help="fixed prompt length; 0 = random short prompts")
    ap.add_argument("--chunk", type=int, default=16,
                    help="prefill chunk size (1 = token-by-token)")
    ap.add_argument("--temperature", type=float, default=0.0,
                    help="0 = greedy")
    ap.add_argument("--top-k", type=int, default=0)
    ap.add_argument("--top-p", type=float, default=1.0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--block-size", type=int, default=16)
    ap.add_argument("--num-blocks", type=int, default=0,
                    help="KV pool blocks; 0 = worst-case sized")
    ap.add_argument("--no-paged", action="store_true",
                    help="use the dense per-slot cache layout")
    ap.add_argument("--no-prefix-cache", action="store_true")
    ap.add_argument("--host-cache-gb", type=float, default=0.0,
                    help="host-RAM spill tier size in GiB (0 = no tier)")
    ap.add_argument("--host-cache-blocks", type=int, default=0,
                    help="host-RAM spill tier size in blocks (overrides "
                         "--host-cache-gb; 0 = use the GiB sizing)")
    ap.add_argument("--kv-store", default=None,
                    help="directory for the persistent prefix store "
                         "(warm restarts; None = off)")
    ap.add_argument("--tp", type=int, default=1,
                    help="tensor-parallel width (devices per engine)")
    ap.add_argument("--scheduler", default="priority",
                    choices=["priority", "fifo"],
                    help="queue policy: priority classes + preemption, or "
                         "plain FIFO")
    ap.add_argument("--priority", default="0",
                    help="comma-separated priority cycle assigned "
                         "round-robin across requests (higher = more "
                         "urgent)")
    ap.add_argument("--sched-aging", type=float, default=0.0,
                    help="seconds of queue wait per aged priority class "
                         "(0 = no aging)")
    ap.add_argument("--spec-k", type=int, default=0,
                    help="max speculative draft tokens per decode step "
                         "(0 = off)")
    ap.add_argument("--spec-ngram", type=int, default=3,
                    help="longest n-gram the draft proposer matches")
    ap.add_argument("--no-spec", action="store_true",
                    help="force speculative decoding off")
    ap.add_argument("--port", type=int, default=None,
                    help="serve HTTP/SSE on this port (0 = ephemeral) "
                         "instead of running the batch workload")
    ap.add_argument("--host", default="127.0.0.1",
                    help="bind address for --port")
    ap.add_argument("--replicas", type=int, default=1,
                    help="engine replicas behind the prefix-affinity "
                         "router (server mode)")
    ap.add_argument("--max-queue", type=int, default=32,
                    help="per-replica queue depth that triggers 503 "
                         "backpressure in server mode")
    ap.add_argument("--request-timeout", type=float, default=0.0,
                    help="default per-request deadline in seconds for "
                         "server mode (0 = unbounded)")
    ap.add_argument("--step-deadline", type=float, default=0.0,
                    help="replica step-time deadline in seconds for the "
                         "health watchdog (0 = off; multi-replica only)")
    ap.add_argument("--shed-below", type=float, default=0.5,
                    help="shed low-priority traffic when alive/total "
                         "replicas <= this fraction")
    ap.add_argument("--shed-priority", type=int, default=0,
                    help="highest priority class shed under degraded "
                         "capacity")
    kernel_modes = ["xla", "xla_chunked", "pallas", "pallas_interpret"]
    ap.add_argument("--kernels",
                    default=os.environ.get("REPRO_KERNELS") or None,
                    choices=kernel_modes,
                    help="kernel mode for the serving step (default: "
                         "$REPRO_KERNELS, else pallas on a TPU and the "
                         "ambient context elsewhere)")
    kv_dtypes = ["native", "int8", "fp8", "bf16", "fp16", "fp32"]
    ap.add_argument("--kv-dtype",
                    default=os.environ.get("REPRO_KV_DTYPE") or None,
                    choices=kv_dtypes,
                    help="paged KV pool storage dtype: int8/fp8 quantize "
                         "with fused per-token scales; native (default) "
                         "keeps the compute dtype "
                         "(default: $REPRO_KV_DTYPE or native)")
    args = ap.parse_args(argv)
    # argparse does not validate `choices` against env-supplied defaults
    if args.kernels is not None and args.kernels not in kernel_modes:
        ap.error(f"invalid kernel mode {args.kernels!r} "
                 f"(from $REPRO_KERNELS?)")
    if args.kv_dtype is not None and args.kv_dtype not in kv_dtypes:
        ap.error(f"invalid kv dtype {args.kv_dtype!r} "
                 f"(from $REPRO_KV_DTYPE?)")
    try:
        priorities = [int(p) for p in args.priority.split(",") if p != ""]
    except ValueError:
        ap.error(f"--priority must be a comma-separated int list, "
                 f"got {args.priority!r}")
    if not priorities:
        priorities = [0]

    import jax
    import jax.numpy as jnp
    import numpy as np

    import repro.core as nn
    from repro.configs import get_arch
    from repro.launch.cache import enable_compile_cache
    from repro.models.registry import get_model
    from repro.serving.engine import Request, ServingEngine

    enable_compile_cache()

    cfg = get_arch(args.arch)
    if args.smoke:
        cfg = cfg.smoke()
    if cfg.family == "audio":
        print("serve CLI drives LM-style decode; whisper needs frames — "
              "use repro.models.whisper.init_decode_state directly")
        return 2
    api = get_model(cfg)
    print(f"loading {cfg.name}: {cfg.param_count():,} params "
          f"({'smoke' if args.smoke else 'full'})", flush=True)
    S0 = max(8, cfg.ssm_chunk if cfg.ssm_state else 8)
    params = nn.init(lambda t: api.forward(t), jax.random.key(0),
                     jnp.zeros((1, S0), jnp.int32))

    engine_kw = dict(max_batch=args.max_batch,
                     max_seq=args.max_seq, chunk=args.chunk,
                     paged=(None if not args.no_paged else False),
                     block_size=args.block_size,
                     num_blocks=args.num_blocks or None,
                     prefix_cache=not args.no_prefix_cache,
                     kernels=args.kernels,
                     scheduler=args.scheduler,
                     aging_s=args.sched_aging,
                     spec_k=0 if args.no_spec else args.spec_k,
                     spec_ngram=args.spec_ngram,
                     host_cache_blocks=args.host_cache_blocks or None,
                     host_cache_gb=args.host_cache_gb,
                     kv_store=args.kv_store,
                     kv_dtype=args.kv_dtype)

    if args.port is not None:
        # server mode: HTTP/SSE frontend, optional multi-replica router
        from repro.serving.frontend import AsyncFrontend
        from repro.serving.router import Router, make_replica_engines
        if args.replicas < 1:
            ap.error(f"--replicas must be >= 1, got {args.replicas}")
        router_kw = {}
        if args.step_deadline > 0:
            router_kw["step_deadline_s"] = args.step_deadline
        if args.replicas > 1:
            engines = make_replica_engines(
                api, params, replicas=args.replicas, tp=args.tp,
                **engine_kw)
            target = Router(engines, **router_kw)
            print(f"router: {args.replicas} replicas, prefix-affinity "
                  f"routing, tp={args.tp} each"
                  + (f", step deadline {args.step_deadline:g}s"
                     if args.step_deadline > 0 else ""), flush=True)
        else:
            target = ServingEngine(api, params, tp=args.tp, **engine_kw)
        fe = AsyncFrontend(
            target, host=args.host, port=args.port,
            max_queue=args.max_queue,
            request_timeout=args.request_timeout or None,
            step_deadline_s=args.step_deadline or None,
            shed_below=args.shed_below, shed_priority=args.shed_priority)
        fe.run_forever()
        return 0

    engine = ServingEngine(api, params, tp=args.tp, **engine_kw)
    if engine.spec is not None:
        print(f"speculative: k={engine.spec.k} n-gram drafts "
              f"(<= {engine.spec.max_ngram}-token suffix match)",
              flush=True)
    if args.scheduler != "priority" or len(priorities) > 1 \
            or args.sched_aging:
        print(f"scheduler: {args.scheduler}, priority cycle {priorities}, "
              f"aging {args.sched_aging:g}s", flush=True)
    if engine.paged:
        print(f"paged KV: {engine.num_blocks} blocks x "
              f"{engine.block_size} tok, {engine.kv_dtype} pools "
              f"({engine.kv_bytes_per_token():.0f} B/tok)"
              f"{', prefix cache on' if engine.prefix else ''}"
              f" | kernels="
              f"{engine.kernels or nn.get_default_context().kernels}",
              flush=True)
        if engine.prefix is not None and hasattr(engine.prefix, "host"):
            print(f"tiered KV: host pool {engine.prefix.host.capacity} "
                  f"blocks"
                  + (f", warm store {args.kv_store} "
                     f"({len(engine.prefix.host)} entries preloaded)"
                     if args.kv_store else ""), flush=True)
    if engine.tp > 1:
        from repro.launch.serve_shardings import per_device_state_bytes
        print(f"tensor parallel: tp={engine.tp} over "
              f"{[d.platform for d in jax.devices()[:engine.tp]]} | "
              f"{per_device_state_bytes(engine.state) / 2**20:.2f} MiB "
              f"cache/device", flush=True)
        for leaf, spec in sorted(engine.tp_layout().items()):
            print(f"  state {leaf}: {spec}", flush=True)
    rng = np.random.default_rng(args.seed)
    for i in range(args.requests):
        plen = args.prompt_len or int(rng.integers(2, 6))
        prompt = rng.integers(1, cfg.vocab_size, plen).tolist()
        engine.submit(Request(uid=i, prompt=prompt,
                              max_new_tokens=args.max_new,
                              priority=priorities[i % len(priorities)],
                              temperature=args.temperature,
                              top_k=args.top_k, top_p=args.top_p,
                              seed=args.seed + i))
    t0 = time.time()
    done = engine.run_until_drained()
    dt = time.time() - t0
    toks = sum(len(r.generated) for r in done)
    for r in sorted(done, key=lambda r: r.uid)[:4]:
        print(f"  req {r.uid}: {r.prompt[:6]}{'...' if len(r.prompt) > 6 else ''}"
              f" -> {r.generated[:8]}...")
    print(f"{len(done)} requests, {toks} tokens, {dt:.2f}s "
          f"-> {toks / dt:.1f} tok/s", flush=True)
    m = engine.metrics_summary()
    if m:
        line = (f"mean TTFT {m['mean_ttft_s'] * 1e3:.1f}ms | "
                f"mean queue wait {m['mean_queue_wait_s'] * 1e3:.1f}ms | "
                f"mean decode {m['mean_decode_tok_per_s']:.1f} tok/s")
        if "mean_prefix_hit_tokens" in m:
            line += (f" | prefix hits "
                     f"{m['mean_prefix_hit_tokens']:.1f} tok/req")
        if "kv_bytes_per_token" in m:
            line += (f" | KV {engine.kv_dtype} "
                     f"{m['kv_bytes_per_token']:.0f} B/tok")
        if "host_pool_capacity" in m:
            line += (f" | tier: {m['tier_spilled_blocks']:.0f} spilled / "
                     f"{m['tier_fetched_blocks']:.0f} fetched blk, host "
                     f"{m['host_pool_blocks']:.0f}/"
                     f"{m['host_pool_capacity']:.0f}, host hits "
                     f"{m.get('mean_host_hit_tokens', 0.0):.1f} tok/req, "
                     f"fetch EWMA {m['tier_fetch_ewma_s'] * 1e3:.1f}ms")
        if m.get("preemptions"):
            line += (f" | {m['preemptions']:.0f} preemptions, "
                     f"{m['requeues']:.0f} requeues")
        if m.get("truncated_requests"):
            line += (f" | {m['truncated_requests']:.0f} truncated "
                     f"prompt{'s' if m['truncated_requests'] != 1 else ''}")
        if "spec_accept_rate" in m:
            line += (f" | spec accept {m['spec_accept_rate'] * 100:.0f}% "
                     f"({m['spec_accepted']:.0f}/{m['spec_proposed']:.0f})")
        print(line, flush=True)
    if args.kv_store:
        n = engine.save_kv_store()
        print(f"kv-store: {n} prefix blocks persisted to {args.kv_store}",
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
