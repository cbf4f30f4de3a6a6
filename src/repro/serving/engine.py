"""Serving runtime: continuous batching over a block-paged KV cache with
prefix reuse, chunked prefill and sampling.

A fixed-slot batch (compiled once per step shape); requests stream in and
out of slots without recompilation. Since PR 2 the KV cache is **paged**:

* instead of one dense ``(max_seq,)`` K/V region per slot, every attention
  layer/site owns a global pool of ``num_blocks`` fixed-size blocks
  (``block_size`` tokens, default 16) shared by all slots. Each slot holds
  a ``(max_blocks,)`` page table of block ids; the jitted step scatters new
  K/V through the table (:func:`repro.kernels.ops.paged_cache_write`) and
  attends through it (``attention_prefill_paged`` / ``attention_decode_paged``).
  Block 0 is a garbage block absorbing pad-column and idle-row writes, so
  the scatter needs no masking and nothing ever reads it.
* a slot therefore consumes blocks proportional to its request's **actual**
  length (prompt + max_new), not ``max_seq`` — and admission is gated on
  free blocks in the pool, not on worst-case slot capacity. Blocks return
  to the free list the moment a request completes.
* a **prefix cache** (vLLM-style, :mod:`repro.serving.paged`) keys each
  full prompt block by a chained 128-bit prefix digest; admission reuses
  cache-hit leading blocks by refcount (shared blocks are read-only —
  writes always start at or past the first private block, so copy-on-write
  degenerates to recomputing the partial tail block) and skips prefill
  over the hit tokens. Per-request skip counts land in ``metrics.prefix_hit_tokens``.
  Reuse is enabled only when the family's :class:`~repro.models.registry.
  CacheSpec` marks it sound (pure-KV families; recurrent/hybrid state must
  absorb every prompt token, so mamba/zamba run paged-KV without skipping).
* recurrent state (SSM ``h``, conv windows) stays dense per slot — it is
  O(1) in sequence — and is zeroed on slot reuse as before; families with
  no paged support at all (pure SSM, audio) fall back to the dense layout.
* the attention hot path follows the platform: on a TPU the engine runs
  the compiled Pallas kernels (the paged walk pipelines each page-table
  block into VMEM instead of the gather-then-dense XLA reference);
  elsewhere the ambient context decides, and ``"pallas_interpret"``
  validates the same kernels on CPU. The mode scopes only the engine's
  jitted step, not the process.
* since PR 4 one engine can span a **(data, model) mesh**: ``tp=N`` (or an
  explicit ``mesh=``) shards the params Megatron-style and the paged K/V
  pools on the kv-head axis (:mod:`repro.launch.serve_shardings` owns the
  policy), so every device holds ``1/tp`` of the KV bytes and the jitted
  step runs GSPMD-partitioned with explicit in/out shardings. All host-side
  machinery — allocator, page tables, prefix cache, scheduling — is
  layout-blind: block ids mean the same thing on every shard, page tables
  and positions replicate. Pallas kernel modes wrap the per-shard kernels
  in ``shard_map`` at the dispatch layer (each shard walks only its local
  pool slice, fused-scatter pool donation included); the default ``tp=1``
  builds no mesh at all and stays bitwise-identical to the single-device
  engine.

Scheduling policy lives in :mod:`repro.serving.scheduler` since PR 5: the
engine owns only the device-facing machinery (the jitted step, the
sharding env, metrics aggregation) and drives a host-side
:class:`~repro.serving.scheduler.Scheduler` that owns the queue, the
block allocator / prefix-cache handles and all per-slot bookkeeping.
Requests carry a ``priority`` class (higher = more urgent; FIFO within a
class, which makes the all-default case exactly the PR-1..4 FIFO), an
anti-starvation aging knob bounds queue wait, and under pool pressure the
scheduler preempts lower-priority actives block-by-block (requeue-as-
prefill — see the scheduler module docstring for the policy and its
rationale). Mechanically, prompts are absorbed ``chunk`` tokens per slot
per step through one fused ``prefill`` call (decode IS prefill with
C = 1), mixed (B, chunk)/(B, 1) steps, freed slots refilled with no
draining barrier. Two compiled shapes × greedy/sampled variants: at most
four compilations per engine.

Sampling: per-request temperature, top-k, top-p and PRNG seed (see
:mod:`repro.serving.sampling`), fused into the jitted step;
``temperature=0`` (default) is greedy argmax.

Speculative decoding (PR 6, ``spec_k > 0``): pure-decode steps widen
into ``(B, 1 + spec_k)`` *verify* steps. A zero-parameter n-gram
proposer (:mod:`repro.serving.speculative`) drafts tokens from each
request's own history; the drafts ride the existing chunked prefill
path with ``last_only=False``, whose chunk-causal logits verify every
draft position in one call; the host accepts the longest prefix of
drafts matching the per-position targets and emits them plus the
first-divergence target. Acceptance is exact-match against the tokens
the non-speculative engine would emit — greedy argmax, or the
per-``(seed, len(generated))`` PRNG draw — so the output stream is
**bitwise identical** to a ``spec_k=0`` run, always; drafts only change
how many steps it takes. Rejected drafts cost nothing to undo: their
K/V writes sit at positions ``>= pos`` that chunk-causal attention
never reads and the next step overwrites (``CacheSpec.spec_decode``
gates this on positional pure-KV state). Default ``spec_k=0`` — the
engine is byte-for-byte the PR-5 engine unless asked.

Quantized KV pools (PR 10, ``kv_dtype="int8"``/``"fp8"``): the paged
pools store int8 or fp8 (e4m3fn) with per-(token-slot, kv-head) float32
scale leaves riding alongside (``"k_scale"``/``"v_scale"``). Quant fuses
into the write scatter, dequant into the attention walk (in VMEM on the
Pallas path) — no dequantized pool ever materializes in HBM, and every
host-side subsystem (allocator, tiering spill/fetch, prefix store, tp
sharding) carries the scale leaves automatically because they are
ordinary KV leaves with the block axis at 1. Resolution order for the dtype:
explicit ``kv_dtype=`` > ``$REPRO_KV_DTYPE`` > ``CacheSpec.kv_dtype``.
``kv_bytes_per_token()`` reports the realized per-token HBM cost
(pool + scales); at D = 64, int8 is ~0.53x of bf16.

Per-request metrics on ``Request.metrics``: queue wait, time-to-first-
token, decode tokens/s, prefill/decode step counts, prefix-hit tokens.
Accessors are NaN-safe — reading ``ttft`` before the first token lands or
``decode_tok_per_s`` of a single-token generation returns ``nan``, never a
garbage epoch delta or a fake 0.0.

Spans and counters. Every step writes ``jax.profiler.TraceAnnotation``
spans, which cost about a microsecond each with no profiler attached and
land on the device's clock in a profiler trace. Each carries the stat
``replica`` (``engine_id``: the replica index a
:class:`~repro.serving.router.Router` gives the engine, 0 for a lone one):

* ``serve.step``: one :meth:`ServingEngine.step`, whole; stats ``step``
  (steps dispatched before this one), and once admission has run ``B``,
  ``C`` and ``rows`` (active rows);
* ``serve.admit``: :meth:`Scheduler.admit` and the recurrent-state reset;
* ``serve.stage``: the step's numpy arrays, built and uploaded;
* ``serve.dispatch``: the jitted step's call, which returns once the
  step is enqueued;
* ``serve.readback``: the sampled tokens' read, which waits for the device;
* ``serve.bookkeep``: cursor advance, prompt-block registration, token
  append, finish and the ``on_tokens`` callbacks;
* ``serve.inbox``: :class:`~repro.serving.frontend.EngineWorker` draining
  its submit and cancel inboxes between steps.

On the device, ``jax.named_scope`` names the work of a step (op-name
metadata such as ``.../attention/walk/paged_attention``): ``attention/qkv``,
``attention/kv_write``, ``attention/walk``, ``attention/out``, ``mlp``,
``moe/router``, ``moe/dispatch``, ``moe/experts``, ``moe/combine``,
``lm_head`` and ``sample``. Parameter paths do not change.

:meth:`ServingEngine.counters` gives cumulative step counters, counted on
the host from the step's numpy arrays (no device read): ``steps``,
``chunk_steps`` (C > 1), ``token_slots`` (B x C dispatched),
``tokens_dispatched`` (the active rows' valid tokens, prompt and decode),
``row_steps`` (active rows) and ``tokens_emitted``.
:meth:`ServingEngine.metrics_summary` derives ``padding_share``,
``chunk_step_share``, ``batch_occupancy`` and ``tokens_per_step`` from them
(:func:`step_metrics`).
"""

from __future__ import annotations

import contextlib
import dataclasses
import functools
import math
import os
import time
import warnings
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

import repro.core as nn
from repro.core import context as _ctx
from repro.distributed import sharding as _sh
from repro.kernels import quant
from repro.models.registry import ModelApi
from repro.serving import sampling
from repro.serving.scheduler import Scheduler

# Every KV-pool leaf key: the quantized pools carry per-(slot, head)
# scale arrays next to the int8/fp8 payload. One tuple feeds both
# consumers — _is_kv_leaf (spill/fetch, layout fingerprint, byte
# accounting) and _admit's recurrent-state reset skip — so a new leaf
# kind can never be spilled but not reset-protected (or vice versa).
_KV_KEYS = ("k", "v", "k_scale", "v_scale")

COUNTERS = ("steps", "chunk_steps", "token_slots", "tokens_dispatched",
            "row_steps", "tokens_emitted")


def step_metrics(counts: dict[str, int], row_slots: int) -> dict[str, float]:
    """What the step counters (:meth:`ServingEngine.counters`, or their
    sum over replicas) say: the padding among the dispatched token slots,
    the chunk-wide steps among all, the active rows among ``row_slots``
    (steps x B), and the tokens emitted per step. Empty before the first
    step."""
    steps = counts["steps"]
    if not steps:
        return {}
    return {"padding_share": 1.0 - counts["tokens_dispatched"]
            / counts["token_slots"],
            "chunk_step_share": counts["chunk_steps"] / steps,
            "batch_occupancy": counts["row_steps"] / row_slots,
            "tokens_per_step": counts["tokens_emitted"] / steps}


@dataclasses.dataclass
class RequestMetrics:
    submit_t: float = 0.0       # time.monotonic at submit()
    admit_t: float = 0.0        # latest admission into a slot
    first_token_t: float = 0.0  # first sampled token appended
    done_t: float = 0.0
    prefill_steps: int = 0
    decode_steps: int = 0
    prefix_hit_tokens: int = 0  # prompt tokens skipped via the prefix cache
    host_hit_tokens: int = 0    # ... of which were fetched from the host tier
    preemptions: int = 0        # times this request was evicted mid-flight
    # sum of per-stint queue waits (submit->admit plus every re-admit gap),
    # maintained by Scheduler.admit; NaN until first admitted
    queued_s: float = float("nan")
    spec_proposed: int = 0      # draft tokens this request verified
    spec_accepted: int = 0      # ... of which matched the token stream

    @property
    def queue_wait(self) -> float:
        """Total time spent queued, summed over stints — a preempted
        request's time *running* between stints is service, not wait.
        NaN until the request is admitted. Falls back to the single-stint
        ``admit_t - submit_t`` when the stint accumulator never ran (e.g.
        metrics objects populated by hand)."""
        if not math.isnan(self.queued_s):
            return self.queued_s
        if self.admit_t == 0.0 or self.submit_t == 0.0:
            return float("nan")
        return self.admit_t - self.submit_t

    @property
    def ttft(self) -> float:
        """Time to first token, from submit; NaN until that token lands."""
        if self.first_token_t == 0.0 or self.submit_t == 0.0:
            return float("nan")
        return self.first_token_t - self.submit_t

    def decode_tok_per_s(self, n_generated: int) -> float:
        """Steady-state decode rate; NaN when undefined (single-token
        generations have no decode interval, unfinished requests no span).
        """
        if n_generated <= 1:
            return float("nan")
        dt = self.done_t - self.first_token_t
        if not dt > 0.0:
            return float("nan")
        return (n_generated - 1) / dt


@dataclasses.dataclass
class Request:
    uid: int
    prompt: list[int]
    max_new_tokens: int = 16
    eos_id: int | None = None
    # scheduling class: higher = more urgent; FIFO within a class. The
    # default 0 everywhere reproduces plain FIFO admission exactly.
    priority: int = 0
    # sampling knobs: temperature 0 = greedy; top_k <= 0 / top_p >= 1 disable
    temperature: float = 0.0
    top_k: int = 0
    top_p: float = 1.0
    seed: int | None = None     # None -> uid; PRNG is per (seed, token index)
    # streaming hook: called from inside the step loop every time this
    # request emits tokens — ``on_tokens(req, new_tokens, done)`` with the
    # tokens appended THIS step (>= 1; a speculative verify step can emit
    # several) and whether the request just completed. The callback runs
    # on whichever thread drives the engine (the frontend's worker thread
    # wraps it in call_soon_threadsafe to reach asyncio consumers); it
    # must be cheap and must not touch the engine. None = no streaming.
    on_tokens: Any = dataclasses.field(default=None, repr=False)
    # filled by the engine
    generated: list[int] = dataclasses.field(default_factory=list)
    done: bool = False
    # set by Scheduler.submit when the prompt was clipped to max_seq - 1:
    # the response continues a truncated prompt, not the one submitted
    truncated: bool = False
    # terminal failure reason, set before the final on_tokens fires:
    # deadline expiry, worker crash, failed migration, abandoned drain
    error: str | None = None
    # set by Router.harvest when a dead replica's request was moved to a
    # survivor and resumed through the requeue-as-prefill path
    migrated: bool = False
    metrics: RequestMetrics = dataclasses.field(default_factory=RequestMetrics)


class ServingEngine:
    def __init__(self, api: ModelApi, params: dict[str, Any], *,
                 max_batch: int = 4, max_seq: int = 256, chunk: int = 16,
                 cache_dtype=jnp.float32, paged: bool | None = None,
                 block_size: int = 16, num_blocks: int | None = None,
                 prefix_cache: bool = True,
                 kernels: _ctx.KernelMode | None = None,
                 mesh=None, tp: int | None = None,
                 scheduler: str = "priority", aging_s: float = 0.0,
                 preemption: bool = True,
                 spec_k: int = 0, spec_ngram: int = 3,
                 host_cache_blocks: int | None = None,
                 host_cache_gb: float = 0.0, kv_store: str | None = None,
                 kv_dtype: str | None = None):
        self.api = api
        self.params = params
        # tensor parallelism: tp=N builds a (1, N) (data, model) host mesh
        # (or pass an explicit mesh with a "model" axis). tp=1 / no mesh is
        # the unchanged single-device engine — no env, no device_put, the
        # exact pre-mesh trace.
        if tp is not None and tp < 1:
            raise ValueError(f"tp must be >= 1, got {tp}")
        if mesh is None and tp is not None and tp > 1:
            from repro.launch.mesh import make_serving_mesh
            mesh = make_serving_mesh(tp)
        elif mesh is not None:
            if "model" not in mesh.axis_names:
                raise ValueError("serving mesh needs a 'model' axis, got "
                                 f"{mesh.axis_names}")
            if tp is not None and tp != mesh.shape["model"]:
                raise ValueError(
                    f"tp={tp} conflicts with the mesh's model axis of "
                    f"{mesh.shape['model']} — pass one or the other")
        self.mesh = mesh
        self.tp = int(mesh.shape["model"]) if mesh is not None else 1
        if mesh is not None:
            from repro.launch.serve_shardings import make_serve_env
            self._env = make_serve_env(mesh, api.cfg)
            with _sh.sharding_env(self._env):
                self.params = jax.device_put(
                    params, _sh.params_shardings(params))
        else:
            self._env = None
        # kernel mode pinned for the jitted step, resolved from the
        # platform when not given: compiled Pallas on a TPU, the ambient
        # context (None) elsewhere. "pallas_interpret" runs the same kernel
        # logic on CPU, "xla*" the gather-then-dense references. Typos and
        # "pallas" off a TPU raise here, not deep inside Mosaic lowering.
        self.kernels = _ctx.resolve_kernels(kernels)
        self.B = max_batch
        self.max_seq = max_seq
        # APIs without a prefill entry fall back to one-token absorption
        # (a C=1 prefill is exactly one decode step)
        self.chunk = max(1, int(chunk)) if api.prefill is not None else 1
        self._prefill_fn = api.prefill if api.prefill is not None else (
            lambda t, s, p, l: api.decode_step(t, s, p))
        self.completed: list[Request] = []
        # incremented by a crashing EngineWorker so the failure is visible
        # in metrics_summary even when the dead replica completed nothing
        self.worker_crashed = 0
        # the replica index a Router gives this engine; every span says it
        self.engine_id = 0
        # replaced whole once per step, so a reader on another thread never
        # sees half a step's update
        self._counts = dict.fromkeys(COUNTERS, 0)

        can_page = api.prefill_paged is not None and api.cache_spec.paged
        self.paged = can_page if paged is None else (paged and can_page)
        # paged-pool storage dtype: explicit arg > $REPRO_KV_DTYPE > the
        # family default in CacheSpec.kv_dtype. "int8"/"fp8" allocate
        # quantized pools with per-(slot, head) scale leaves (see
        # :mod:`repro.kernels.quant`); "native" keeps cache_dtype. Dense
        # fallback engines ignore the knob — quantization is a paged-pool
        # layout, the dense cache always stays in the compute dtype.
        if kv_dtype is None:
            kv_dtype = (os.environ.get("REPRO_KV_DTYPE")
                        or api.cache_spec.kv_dtype)
        self.kv_pool_dtype = (quant.resolve_kv_dtype(kv_dtype, cache_dtype)
                              if self.paged else jnp.dtype(cache_dtype))
        self.kv_dtype = quant.kv_dtype_name(self.kv_pool_dtype)
        # tiered KV cache: a host-RAM pool cold registered prefixes spill
        # into instead of being dropped (and a disk store for warm
        # restarts). Only meaningful where the prefix cache itself is —
        # paged engines of prefix_reuse families.
        tiering_ok = (self.paged and prefix_cache
                      and api.cache_spec.prefix_reuse)
        host_blocks = 0
        if tiering_ok:
            if host_cache_blocks is not None:
                host_blocks = int(host_cache_blocks)
            elif host_cache_gb > 0:
                from repro.serving.tiering import blocks_for_bytes
                host_blocks = blocks_for_bytes(
                    host_cache_gb,
                    self._per_block_bytes(block_size, self.kv_pool_dtype))
            elif kv_store:
                # a persistent store with no explicit host sizing still
                # needs a host tier to warm-load into: default to 4x the
                # usable HBM pool (the "~10x effective capacity" lever
                # scales with this knob, not a magic constant)
                mb = -(-(max_seq + self.chunk) // block_size)
                nb = (num_blocks if num_blocks is not None
                      else max_batch * mb + 1)
                host_blocks = 4 * (nb - 1)
        self._kv_store = kv_store if tiering_ok else None
        # every scheduling decision — queue order, placement, eviction,
        # preemption — and all per-slot bookkeeping lives in the scheduler;
        # it is host-side and layout-blind, so tp=N engines construct it
        # identically to tp=1
        self.scheduler = Scheduler(
            max_batch=max_batch, max_seq=max_seq, chunk=self.chunk,
            paged=self.paged, block_size=block_size, num_blocks=num_blocks,
            prefix_cache=prefix_cache and api.cache_spec.prefix_reuse,
            policy=scheduler, aging_s=aging_s, preemption=preemption,
            host_cache_blocks=host_blocks)
        # speculative decoding: spec_k > 0 turns pure-decode steps into
        # (B, 1 + spec_k) verify steps over n-gram drafts. Sound only for
        # positional pure-KV state (CacheSpec.spec_decode) on the paged
        # path — rejecting here beats silently decoding a corrupt stream.
        if spec_k:
            if not (self.paged and api.cache_spec.spec_decode):
                raise ValueError(
                    f"spec_k={spec_k} needs a paged pure-KV cache: family "
                    f"{api.cfg.family!r} has paged={self.paged}, "
                    f"spec_decode={api.cache_spec.spec_decode} — "
                    f"speculative rollback cannot rewind recurrent state")
            from repro.serving.speculative import NgramProposer
            self.spec = NgramProposer(k=int(spec_k),
                                      max_ngram=int(spec_ngram))
        else:
            self.spec = None
        if self.paged:
            with self._env_scope():
                self.state = api.paged_state_init(
                    max_batch, self.scheduler.num_blocks,
                    self.scheduler.block_size, self.kv_pool_dtype)
            if host_blocks > 0:
                # the tiered cache is layout-blind; the engine — which
                # owns the pools — injects the block extract/insert I/O
                self.scheduler.prefix.bind_device_io(
                    self._extract_blocks, self._insert_blocks)
                if self._kv_store:
                    self._warm_restart()
            # 8 replicated metadata args: pages, pos, length + 5 sampling
            self._step = self._jit_step(self._step_paged_fn, n_meta=8)
            if self.spec is not None:
                self._step_spec = self._jit_step(self._step_spec_fn,
                                                 n_meta=8)
        else:
            # dense fallback: one (max_seq + chunk)-deep region per slot.
            # chunk-1 headroom: a C-wide cache write starting at pos <=
            # max_seq-1 must never clamp (pad columns past a row's valid
            # length would otherwise shift onto live entries)
            with self._env_scope():
                self.state = api.decode_state_init(
                    max_batch, max_seq + self.chunk, cache_dtype)
            self._step = self._jit_step(self._step_fn, n_meta=7)

    # ------------------------------------------------------------------ #
    # read-only views into the scheduler (benchmarks/tests introspect
    # these; the engine itself never touches allocator or prefix-cache
    # internals — that is the scheduler's job)
    # ------------------------------------------------------------------ #
    @property
    def queue(self):
        return self.scheduler.queue

    @property
    def active(self):
        return self.scheduler.active

    @property
    def pos(self):
        return self.scheduler.pos

    @property
    def alloc(self):
        return self.scheduler.alloc

    @property
    def prefix(self):
        return self.scheduler.prefix

    @property
    def num_blocks(self):
        return self.scheduler.num_blocks

    @property
    def block_size(self):
        return self.scheduler.block_size

    @property
    def max_blocks(self):
        return self.scheduler.max_blocks

    # ------------------------------------------------------------------ #
    def _sample_or_greedy(self, logits, temps, top_k, top_p, seeds, counts,
                          do_sample):
        with jax.named_scope("sample"):
            last = logits[:, -1, :].astype(jnp.float32)
            if do_sample:
                return sampling.sample(last, temps, top_k, top_p, seeds,
                                       counts)
            # all-greedy batch (the default): skip the (B, V) sort pipeline
            return jnp.argmax(last, axis=-1).astype(jnp.int32)

    def _env_scope(self):
        """The engine's ShardingEnv, active while building state and while
        TRACING the jitted step: ``constrain`` calls in the models and the
        shard_map wrapping in :mod:`repro.kernels.ops` both read the
        thread-local env at trace time. Null without a mesh."""
        if self._env is None:
            return contextlib.nullcontext()
        return _sh.sharding_env(self._env)

    def _kernel_scope(self):
        """Context overrides applied while TRACING the jitted step — kernel
        dispatch in :mod:`repro.kernels.ops` reads the ambient context at
        trace time, so scoping the trace pins the engine's kernel mode (and
        its serving mesh) regardless of what the caller's context says."""
        stack = contextlib.ExitStack()
        if self.kernels:              # None/"" -> ambient context
            stack.enter_context(_ctx.context_scope(dataclasses.replace(
                _ctx.get_default_context(), kernels=self.kernels)))
        stack.enter_context(self._env_scope())
        return stack

    def _jit_step(self, fn, *, n_meta: int):
        """Compile the step. Single-device engines keep the plain jit of
        PRs 1-3 (bitwise-identical trace). Under a mesh the step is pinned
        with explicit in/out shardings: params and state keep their
        placement fixed-point (no first-step reshard, no sharding drift
        between the state returned by step N and consumed by step N+1),
        tokens/pages/positions/sampling knobs and the sampled token
        replicate. ``n_meta`` counts those replicated metadata args."""
        if self._env is None:
            return jax.jit(fn, static_argnames=("do_sample",))
        from jax.sharding import NamedSharding, PartitionSpec
        repl = NamedSharding(self.mesh, PartitionSpec())

        def put(a):
            sh = getattr(a, "sharding", None)
            return sh if isinstance(sh, NamedSharding) else repl

        p_sh = jax.tree.map(put, self.params)
        s_sh = jax.tree.map(put, self.state)
        # jit rejects kwargs once in_shardings is given, so the static
        # do_sample flag is pre-bound: one jitted callee per variant
        # (exactly the two traces the single-device path compiles lazily)
        jitted = {
            ds: jax.jit(functools.partial(fn, do_sample=ds),
                        in_shardings=(p_sh, repl, s_sh) + (repl,) * n_meta,
                        out_shardings=(repl, s_sh))
            for ds in (False, True)
        }
        return lambda *args, do_sample: jitted[do_sample](*args)

    def tp_layout(self) -> dict[str, str]:
        """Realized state placement (leaf path -> spec or "replicated");
        {} for single-device engines. See ``CacheSpec.tp_note`` for the
        per-family rationale behind replicated leaves."""
        if self._env is None:
            return {}
        from repro.launch.serve_shardings import state_layout
        return state_layout(self.state)

    # ------------------------------------------------------------------ #
    # tiered-cache device I/O and persistence: the TieredPrefixCache is
    # layout-blind, so the engine — owner of the pools — provides the
    # hooks that move block contents between HBM and host numpy, and the
    # layout descriptor the disk store checks compatibility against.
    # ------------------------------------------------------------------ #
    @staticmethod
    def _is_kv_leaf(path) -> bool:
        """KV pool leaves are keyed by ``_KV_KEYS`` — payload pools plus
        the quantized pools' scale arrays, the same set _admit's
        recurrent-state reset skips. Their block axis is axis 1:
        ``(n_layers, num_blocks, block_size, n_kv_heads, head_dim)`` for
        pools, ``(n_layers, num_blocks, block_size, n_kv_heads)`` for
        scales — so spill/fetch/layout code slicing axis 1 covers both."""
        last = path[-1]
        return (isinstance(last, jax.tree_util.DictKey)
                and last.key in _KV_KEYS)

    def _per_block_bytes(self, block_size: int, pool_dtype) -> int:
        """Host-RAM bytes one spilled block occupies across every KV pool
        leaf — scale arrays included for quantized pools (sizes
        ``--host-cache-gb`` into a block count). Computed from specs with
        a 2-block probe pool — no device allocation."""
        specs = self.api.paged_state_specs(1, 2, block_size, pool_dtype)
        total = 0
        for path, leaf in jax.tree_util.tree_flatten_with_path(specs)[0]:
            if self._is_kv_leaf(path) and leaf.shape[1] == 2:
                total += (int(np.prod(leaf.shape)) // 2
                          * np.dtype(leaf.dtype).itemsize)
        return total

    def _extract_blocks(self, bids: list[int]) -> dict[str, np.ndarray]:
        """Pull blocks ``bids`` of every KV leaf to host numpy, stacked on
        axis 1 (one gather per leaf for the whole batch — the spill path
        calls this once per eviction pass). ``copy_to_host_async`` is a
        best-effort overlap hint: real on TPU/GPU, a no-op on CPU jax."""
        idx = jnp.asarray(bids, jnp.int32)
        subs: list[tuple[str, Any]] = []
        for path, leaf in jax.tree_util.tree_flatten_with_path(self.state)[0]:
            if self._is_kv_leaf(path):
                sub = leaf[:, idx]
                try:
                    sub.copy_to_host_async()
                except (AttributeError, RuntimeError):
                    pass
                subs.append((jax.tree_util.keystr(path), sub))
        return {k: np.asarray(v) for k, v in subs}

    def _insert_blocks(self, bids: list[int],
                       data: dict[str, np.ndarray]) -> None:
        """Write host block data back into freshly allocated HBM blocks
        (one scatter per leaf for the whole fetched chain). Under a mesh
        the scatter result is pinned back to the leaf's sharding so the
        state's placement fixed-point survives the update."""
        idx = jnp.asarray(bids, jnp.int32)

        def put(path, leaf):
            if not self._is_kv_leaf(path):
                return leaf
            arr = jnp.asarray(data[jax.tree_util.keystr(path)], leaf.dtype)
            new = leaf.at[:, idx].set(arr)
            if self.mesh is not None:
                new = jax.device_put(new, leaf.sharding)
            return new

        self.state = jax.tree_util.tree_map_with_path(put, self.state)

    def kv_bytes_per_token(self) -> float:
        """HBM bytes one cached token costs across every KV leaf — pools
        plus scale arrays for quantized dtypes, summed over layers/sites.
        Pure spec arithmetic (no device reads); NaN for dense engines.
        ``bench_serving --quant`` reports this and ``compare.py`` gates
        it lower-is-better."""
        if not self.paged:
            return float("nan")
        bs = self.scheduler.block_size
        return self._per_block_bytes(bs, self.kv_pool_dtype) / bs

    def kv_layout(self) -> dict:
        """The pool layout the disk store records and checks on load: a
        store written under any other block size, family, dtype or leaf
        geometry is unusable bytes and must fail the warm restart."""
        leaves = {}
        for path, leaf in jax.tree_util.tree_flatten_with_path(self.state)[0]:
            if self._is_kv_leaf(path):
                shape = list(leaf.shape[:1]) + list(leaf.shape[2:])
                leaves[jax.tree_util.keystr(path)] = [
                    shape, str(np.dtype(leaf.dtype))]
        return {"block_size": self.scheduler.block_size,
                "kind": self.api.cache_spec.kind,
                "family": self.api.cfg.family,
                "leaves": leaves}

    def save_kv_store(self) -> int:
        """Persist every registered prefix block — both tiers — to the
        ``kv_store`` directory (atomic, CRC'd, layout-stamped). Returns
        the number of entries written; 0 when no store is configured."""
        if not self._kv_store:
            return 0
        from repro.checkpoint.manager import PrefixStore
        entries = self.scheduler.prefix.snapshot()
        PrefixStore(self._kv_store).save(entries, self.kv_layout())
        return len(entries)

    def _warm_restart(self) -> None:
        """Load a previous run's prefix store into the HOST tier. Any
        failure — missing, corrupt, layout mismatch — means serve cold;
        a stale store must never crash startup."""
        from repro.checkpoint.manager import PrefixStore
        try:
            entries = PrefixStore(self._kv_store).load(self.kv_layout())
        except FileNotFoundError:
            return          # first run: nothing to warm from
        except Exception as e:   # corrupt npz/meta, CRC, layout mismatch
            warnings.warn(
                f"kv-store {self._kv_store!r} unusable ({e}); serving cold",
                RuntimeWarning)
            return
        self.scheduler.prefix.preload_host(entries)

    def _step_fn(self, params, tokens, state, pos, length,
                 temps, top_k, top_p, seeds, counts, *, do_sample):
        with self._kernel_scope():
            logits, new_state = nn.apply(
                lambda t, s, p, l: self._prefill_fn(t, s, p, l),
                params, tokens, state, pos, length)
        next_tok = self._sample_or_greedy(logits, temps, top_k, top_p,
                                          seeds, counts, do_sample)
        return next_tok, new_state

    def _step_paged_fn(self, params, tokens, state, pages, pos, length,
                       temps, top_k, top_p, seeds, counts, *, do_sample):
        with self._kernel_scope():
            logits, new_state = nn.apply(
                lambda t, s, g, p, l: self.api.prefill_paged(t, s, g, p, l),
                params, tokens, state, pages, pos, length)
        next_tok = self._sample_or_greedy(logits, temps, top_k, top_p,
                                          seeds, counts, do_sample)
        return next_tok, new_state

    def _step_spec_fn(self, params, tokens, state, pages, pos, length,
                      temps, top_k, top_p, seeds, cnt0, *, do_sample):
        """Speculative verify step: the same chunked paged prefill, but
        keeping the FULL (B, C, V) chunk-causal logits (``last_only=
        False``) and turning every position into a target token — the
        token non-speculative decoding would emit at that stream index
        (position i of row b draws with PRNG coordinate ``cnt0[b] + i``).
        The host compares drafts against targets and accepts the longest
        matching prefix; pad positions compute garbage targets nobody
        reads."""
        with self._kernel_scope():
            logits, new_state = nn.apply(
                lambda t, s, g, p, l: self.api.prefill_paged(
                    t, s, g, p, l, last_only=False),
                params, tokens, state, pages, pos, length)
        with jax.named_scope("sample"):
            logits = logits.astype(jnp.float32)
            if do_sample:
                targets = sampling.sample_chunk(logits, temps, top_k, top_p,
                                                seeds, cnt0)
            else:
                targets = sampling.greedy_chunk(logits)
        return targets, new_state

    # ------------------------------------------------------------------ #
    def submit(self, req: Request) -> None:
        """Enqueue a request (may raise when it can never fit the pool —
        see :meth:`Scheduler.submit`)."""
        self.scheduler.submit(req, time.monotonic())

    def resubmit(self, req: Request) -> None:
        """Adopt a request that already ran (and possibly generated
        tokens) on another engine — replica death, worker crash. Its
        generated tokens fold into a resume prompt via the scheduler's
        requeue-as-prefill path, so the continued stream is bitwise the
        uninterrupted one (see :meth:`Scheduler.resubmit`; raises
        ValueError when the resume prompt can no longer fit)."""
        self.scheduler.resubmit(req, time.monotonic())

    def cancel(self, uid: int) -> bool:
        """Drop a queued or active request by uid, freeing its blocks;
        False when the uid is unknown (already completed — benign)."""
        return self.scheduler.cancel(uid)

    def _span(self, name: str, **stats) -> TraceAnnotation:
        return TraceAnnotation(name, replica=self.engine_id, **stats)

    def counters(self) -> dict[str, int]:
        """The cumulative step counters (see the module docstring)."""
        return dict(self._counts)

    def _count(self, C: int, length: np.ndarray, active_slots: list[int],
               emitted: int) -> None:
        c = self._counts
        self._counts = {
            "steps": c["steps"] + 1,
            "chunk_steps": c["chunk_steps"] + (C > 1),
            "token_slots": c["token_slots"] + self.B * C,
            "tokens_dispatched": c["tokens_dispatched"]
            + int(length[active_slots].sum()),
            "row_steps": c["row_steps"] + len(active_slots),
            "tokens_emitted": c["tokens_emitted"] + emitted}

    def _admit(self, now: float) -> None:
        with self._span("serve.admit"):
            fresh = self.scheduler.admit(now)
            if not fresh:
                return
            idx = jnp.asarray(fresh, jnp.int32)

            # Zero the admitted rows of every *recurrent* state leaf so a
            # freed slot's SSM state can't leak forward (batch is axis 1,
            # see registry docstring). KV-cache leaves — _KV_KEYS, i.e.
            # "k"/"v" plus quantized pools' "k_scale"/"v_scale" — are
            # skipped: paged pools have no batch axis at all (axis 1 is
            # the BLOCK axis; zeroing a scale leaf there would corrupt
            # live blocks), and a dense cache is positionally overwritten
            # and length-masked.
            def reset(path, a):
                last = path[-1]
                if (isinstance(last, jax.tree_util.DictKey)
                        and last.key in _KV_KEYS):
                    return a
                return a.at[:, idx].set(0)
            self.state = jax.tree_util.tree_map_with_path(reset, self.state)

    def step(self) -> int:
        """One synchronized mixed prefill/decode step; returns #active."""
        with self._span("serve.step", step=self._counts["steps"]) as span:
            sched = self.scheduler
            self._admit(time.monotonic())
            active_slots = [s for s, r in enumerate(sched.active)
                            if r is not None]
            if not active_slots:
                return 0
            prefilling = any(len(sched.pending_prompt[s]) > 1
                             for s in active_slots)
            # no slot is mid-prompt: run the (B, 1 + spec_k) verify step
            # instead of a (B, 1) decode step. Prefill steps stay on the
            # plain path — bitwise identical to the non-speculative engine.
            verify = self.spec is not None and not prefilling
            C = (1 + self.spec.k if verify
                 else self.chunk if prefilling else 1)
            span.set_metadata(B=self.B, C=C, rows=len(active_slots))
            if verify:
                return self._step_speculative(active_slots)
            return self._step_mixed(active_slots, C)

    def _step_mixed(self, active_slots: list[int], C: int) -> int:
        """One (B, C) step: prompt rows absorb up to C tokens, decode rows
        one; rows whose prompt is done emit a token."""
        sched = self.scheduler
        B = self.B
        with self._span("serve.stage"):
            tokens = np.zeros((B, C), np.int32)
            length = np.ones(B, np.int32)
            temps = np.zeros(B, np.float32)
            top_k = np.zeros(B, np.int32)
            top_p = np.ones(B, np.float32)
            seeds = np.zeros(B, np.int32)
            counts = np.zeros(B, np.int32)
            emits = [False] * B
            prompt_done = []
            for s in active_slots:
                req = sched.active[s]
                pend = sched.pending_prompt[s]
                if pend:
                    k = min(C, len(pend))
                    for i in range(k):
                        tokens[s, i] = pend.popleft()
                    length[s] = k
                    emits[s] = not pend   # prompt fully absorbed: sample now
                    if not pend:
                        prompt_done.append(s)
                    req.metrics.prefill_steps += 1
                else:
                    tokens[s, 0] = (req.generated[-1] if req.generated
                                    else (req.prompt[-1] if req.prompt
                                          else 0))
                    emits[s] = True
                    req.metrics.decode_steps += 1
                temps[s] = req.temperature
                top_k[s] = req.top_k
                top_p[s] = req.top_p
                # mask to 31 bits: callers often derive 64-bit seeds (hashes)
                seeds[s] = (req.seed if req.seed is not None
                            else req.uid) & 0x7FFFFFFF
                # count = tokens generated so far: a preempted-then-resumed
                # request keeps its generated list, so the per-(seed, count)
                # PRNG stream continues exactly where it left off
                counts[s] = len(req.generated)
            do_sample = any(temps[s] > 0.0 for s in active_slots)
            host = ((sched.pages,) if self.paged else ()) + (
                sched.pos, length, temps, top_k, top_p, seeds, counts)
            tokens, meta = jnp.asarray(tokens), [jnp.asarray(a) for a in host]
        with self._span("serve.dispatch"):
            next_tok, self.state = self._step(
                self.params, tokens, self.state, *meta, do_sample=do_sample)
        with self._span("serve.readback"):
            next_tok = np.asarray(next_tok)
        with self._span("serve.bookkeep"):
            now = time.monotonic()
            if self.paged:
                for s in prompt_done:
                    sched.register_prompt_blocks(s)
            emitted = 0
            for s in active_slots:
                req = sched.active[s]
                sched.advance(s, int(length[s]))
                if not emits[s]:
                    continue  # still absorbing prompt
                emitted += 1
                req.generated.append(int(next_tok[s]))
                if req.metrics.first_token_t == 0.0:
                    req.metrics.first_token_t = now
                hit_eos = (req.eos_id is not None
                           and req.generated[-1] == req.eos_id)
                if (len(req.generated) >= req.max_new_tokens or hit_eos
                        or sched.pos[s] >= self.max_seq - 1):
                    req.done = True
                    req.metrics.done_t = now
                    self.completed.append(req)
                    sched.finish(s)
                if req.on_tokens is not None:
                    req.on_tokens(req, [req.generated[-1]], req.done)
            self._count(C, length, active_slots, emitted)
        return sum(1 for r in sched.active if r is not None)

    def _step_speculative(self, active_slots: list[int]) -> int:
        """One (B, 1 + spec_k) speculative verify step over pure-decode
        slots.

        Per decoding slot the n-gram proposer drafts up to ``k_s`` tokens
        from the request's own ``prompt + generated`` history, where
        ``k_s = min(spec_k, remaining - 1, max_seq - 2 - pos)`` caps the
        window so acceptance can never overshoot the request's token
        budget or the ``max_seq`` finish boundary (the emitted stream
        truncates at exactly the same length a token-at-a-time run
        would). The step feeds ``[t0, d_1 .. d_k]`` as a chunk — the KV
        writes land at ``pos .. pos + k``, the chunk-causal kernels give
        verification logits for every position in ONE call — and the
        host accepts the longest prefix of drafts matching the
        per-position targets, then emits the accepted drafts plus the
        first-divergence target (the "bonus" token the verify logits
        already paid for). ``pos`` advances by the number of emitted
        tokens; the rejected tail needs no cleanup because positions
        ``>= pos`` are invisible to chunk-causal attention and the next
        step overwrites them.

        A slot holding exactly one pending prompt token rides the step
        draft-free (its target at position 0 IS its first sampled token);
        idle rows write into the garbage block as always.
        """
        sched = self.scheduler
        C = 1 + self.spec.k
        B = self.B
        with self._span("serve.stage"):
            tokens = np.zeros((B, C), np.int32)
            length = np.ones(B, np.int32)
            temps = np.zeros(B, np.float32)
            top_k = np.zeros(B, np.int32)
            top_p = np.ones(B, np.float32)
            seeds = np.zeros(B, np.int32)
            cnt0 = np.zeros(B, np.int32)
            drafts: dict[int, list[int]] = {}
            prompt_done = []
            for s in active_slots:
                req = sched.active[s]
                pend = sched.pending_prompt[s]
                if pend:   # single leftover prompt token: absorb, no drafts
                    tokens[s, 0] = pend.popleft()
                    drafts[s] = []
                    prompt_done.append(s)
                    req.metrics.prefill_steps += 1
                else:
                    g = len(req.generated)
                    cap = min(self.spec.k, req.max_new_tokens - g - 1,
                              self.max_seq - 2 - int(sched.pos[s]))
                    d = (self.spec.propose(
                            req.prompt[: self.max_seq - 1] + req.generated,
                            cap)
                         if cap > 0 else [])
                    drafts[s] = d
                    tokens[s, 0] = (req.generated[-1] if req.generated
                                    else (req.prompt[-1] if req.prompt
                                          else 0))
                    for i, tok in enumerate(d):
                        tokens[s, 1 + i] = tok
                    length[s] = 1 + len(d)
                    req.metrics.decode_steps += 1
                temps[s] = req.temperature
                top_k[s] = req.top_k
                top_p[s] = req.top_p
                seeds[s] = (req.seed if req.seed is not None
                            else req.uid) & 0x7FFFFFFF
                # PRNG coordinate base: position i of this row draws with
                # count = len(generated) + i, exactly the coordinates a
                # token-at-a-time run would use for those stream indices
                cnt0[s] = len(req.generated)
            do_sample = any(temps[s] > 0.0 for s in active_slots)
            tokens, meta = jnp.asarray(tokens), [jnp.asarray(a) for a in (
                sched.pages, sched.pos, length, temps, top_k, top_p, seeds,
                cnt0)]
        with self._span("serve.dispatch"):
            targets, self.state = self._step_spec(
                self.params, tokens, self.state, *meta, do_sample=do_sample)
        with self._span("serve.readback"):
            targets = np.asarray(targets)
        with self._span("serve.bookkeep"):
            now = time.monotonic()
            for s in prompt_done:
                sched.register_prompt_blocks(s)
            n_emitted = 0
            for s in active_slots:
                req = sched.active[s]
                d = drafts[s]
                t = targets[s]
                a = 0
                while a < len(d) and d[a] == int(t[a]):
                    a += 1
                # accepted drafts + the target at the first divergence
                # (when every draft matched, that's the position after the
                # last one)
                emitted = d[:a] + [int(t[a])]
                if req.eos_id is not None and req.eos_id in emitted:
                    emitted = emitted[: emitted.index(req.eos_id) + 1]
                req.generated.extend(emitted)
                n_emitted += len(emitted)
                kept = len(emitted) - 1
                sched.commit_spec(s, len(d), kept)   # pos += len(emitted)
                req.metrics.spec_proposed += len(d)
                req.metrics.spec_accepted += kept
                if req.metrics.first_token_t == 0.0:
                    req.metrics.first_token_t = now
                hit_eos = (req.eos_id is not None
                           and req.generated[-1] == req.eos_id)
                if (len(req.generated) >= req.max_new_tokens or hit_eos
                        or sched.pos[s] >= self.max_seq - 1):
                    req.done = True
                    req.metrics.done_t = now
                    self.completed.append(req)
                    sched.finish(s)
                if req.on_tokens is not None:
                    req.on_tokens(req, emitted, req.done)
            self._count(C, length, active_slots, n_emitted)
        return sum(1 for r in sched.active if r is not None)

    def has_work(self) -> bool:
        """Anything queued or active? (Delegates to the scheduler; the
        frontend's worker thread polls this to decide whether to step or
        sleep.)"""
        return self.scheduler.has_work()

    def run_until_drained(self, max_steps: int = 10_000) -> list[Request]:
        for _ in range(max_steps):
            n = self.step()
            if n == 0 and not self.scheduler.has_work():
                return self.completed
        if not self.scheduler.has_work():
            return self.completed
        # a wedged pool (or a genuinely longer workload) must not
        # masquerade as a clean drain — report what is still stuck
        queued = len(self.scheduler.queue)
        active = sum(1 for r in self.scheduler.active if r is not None)
        raise RuntimeError(
            f"run_until_drained: {max_steps} steps exhausted with {active} "
            f"active and {queued} queued requests undrained — the pool may "
            f"be wedged; raise max_steps only if the workload is genuinely "
            f"this long ({len(self.completed)} requests did complete)")

    # ------------------------------------------------------------------ #
    def metrics_summary(self) -> dict[str, float]:
        """Aggregate per-request metrics over completed requests (NaN
        entries — e.g. decode rate of single-token generations — are
        excluded from the means, never averaged in)."""
        done = self.completed
        if not done:
            # a replica whose worker crashed before completing anything
            # must still surface the crash, not an empty summary
            return ({"worker_crashed": float(self.worker_crashed)}
                    if self.worker_crashed else {})

        def finite_mean(vals):
            vals = [v for v in vals if not math.isnan(v)]
            return sum(vals) / len(vals) if vals else float("nan")

        out = {
            "requests": float(len(done)),
            "mean_ttft_s": finite_mean(r.metrics.ttft for r in done),
            "mean_queue_wait_s": finite_mean(
                r.metrics.queue_wait for r in done),
            "mean_decode_tok_per_s": finite_mean(
                r.metrics.decode_tok_per_s(len(r.generated)) for r in done),
            # responses that continue a CLIPPED prompt (Scheduler.submit
            # truncated it to max_seq - 1): callers watching this summary
            # must be able to see that without scanning every request
            "truncated_requests": float(
                sum(1 for r in done if r.truncated)),
        }
        out.update(self.scheduler.stats())  # preemptions/requeues[/blocks]
        out.update(step_metrics(self._counts, self._counts["steps"] * self.B))
        if self.worker_crashed:
            out["worker_crashed"] = float(self.worker_crashed)
        if self.paged:
            out["mean_prefix_hit_tokens"] = (
                sum(r.metrics.prefix_hit_tokens for r in done) / len(done))
            out["mean_host_hit_tokens"] = (
                sum(r.metrics.host_hit_tokens for r in done) / len(done))
            # realized pool layout cost (the dtype name itself is on
            # ``engine.kv_dtype``; this summary is float-valued)
            out["kv_bytes_per_token"] = self.kv_bytes_per_token()
        return out
