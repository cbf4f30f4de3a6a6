"""Multi-replica request router: prefix-affinity placement over R engines.

One :class:`~repro.serving.engine.ServingEngine` is one replica — its own
params copy, KV block pool, prefix cache and scheduler, optionally pinned
to its own device slice (:func:`repro.launch.mesh.make_replica_meshes`
carves the device set into R disjoint ``(1, tp)`` meshes — the realized
``data`` axis of the production mesh). The :class:`Router` owns R such
replicas and decides, per request, which one serves it.

Routing policy (``policy="affinity"``, the default)
---------------------------------------------------
Prefix caches are per-replica, so *where* a request lands decides whether
its prompt prefix is a cache hit or a cold re-prefill. The router reuses
the exact key chain the :class:`~repro.serving.paged.PrefixCache` already
computes (:func:`repro.serving.paged.prefix_keys` — chained 128-bit
blake2b digests, one per full prompt block) as its affinity signal, in
escalating order:

1. **Live-cache affinity** — ``peek`` every replica's prefix map with the
   request's key chain (a pure read; no refcount/LRU/stat skew). If any
   replica holds cached blocks for this prompt, route to the replica with
   the *deepest* hit run (ties broken by load): the request rides blocks
   that already exist and skips prefill over them.
2. **Cold-hash affinity** — no replica holds the prefix yet: route by a
   stable hash of the chain's *first* key (``keys[0]`` commits to the
   whole first prompt block, so every request sharing a leading block
   hashes to the same replica). The first arrival of a prefix family
   warms exactly the replica its siblings will hash to — sticky sessions
   without any shared state between router and replicas. A load escape
   hatch overrides the hash when the target is clearly overloaded
   (queue+active depth exceeds the lightest replica's by more than
   ``imbalance``, or it cannot admit while another replica can — the
   :meth:`~repro.serving.scheduler.Scheduler.would_admit` probe): a hot
   replica must not absorb unbounded traffic just because a popular
   prefix hashes to it.
3. **Pure load** — prompts shorter than one block have no keys: route to
   the least-loaded replica (queue+active depth, then the EWMA-TTFT
   signal fed back by :meth:`Router.observe_ttft`, then replica id).

``policy="random"`` (seeded) and ``policy="round_robin"`` ignore affinity
entirely — they are the control arms the router benchmark compares
against (affinity must strictly beat them on shared-prefix traffic).

Correctness note: routing NEVER changes a request's token stream. Every
replica computes the same function (same params, same per-``(seed,
len(generated))`` PRNG coordinates, batch-composition-independent steps),
so placement affects latency and cache hits only — the router benchmark
asserts streams are bitwise identical to a single-replica run.

Concurrency note: the sync driver (:meth:`step` / :meth:`run_until_
drained`) steps replicas in-process. Under the async frontend each
replica is stepped by its own worker thread and :meth:`route` runs on the
asyncio thread — its reads of replica state (``peek``, queue depth,
``would_admit``) are racy-but-safe: single dict/list reads under the GIL
that can only yield a slightly stale *placement*, never corrupt state.

Replica health (PR 8)
---------------------
Each replica carries a state machine ``HEALTHY -> SUSPECT -> DEAD`` plus
probe-based re-admission. The signals: a per-replica
:class:`~repro.distributed.resilience.StragglerMonitor` EWMA z-score on
per-step wall time flags *sustained* slowdowns (SUSPECT — informational,
it accelerates the deadline path but never changes routing), a hard
step-deadline overrun escalates SUSPECT and then kills (two consecutive
overruns -> DEAD), and any exception out of ``step`` kills immediately.
A fast step heals SUSPECT back to HEALTHY. DEAD replicas are excluded
from all routing — live-cache affinity, the cold ``keys[0]`` hash (which
re-maps onto the live set), load fallback, random and round-robin — and
their queued + in-flight requests are **migrated**: harvested off the
dead scheduler (blocks freed host-side) and resubmitted to survivors
through :meth:`~repro.serving.scheduler.Scheduler.resubmit`, the
requeue-as-prefill path, so completed streams are bitwise identical to a
fault-free run (see :mod:`repro.serving.faults` for the exactness
argument). The sync driver probes DEAD replicas once per :meth:`step`
(an empty ``step()`` attempt — a recovered replica stops raising);
``probe_successes`` consecutive clean probes readmit it with a reset
watchdog and a flushed prefix cache (post-crash cache contents are
untrusted). With every replica HEALTHY all of this is inert: the routing
pool is the full replica set and every decision is byte-for-byte the
health-free router.
"""

from __future__ import annotations

import math
import random
import time

from repro.distributed.resilience import StragglerMonitor
from repro.serving.engine import (COUNTERS, Request, ServingEngine,
                                  step_metrics)
from repro.serving.paged import prefix_keys

POLICIES = ("affinity", "random", "round_robin")

# replica health states (the full machine: HEALTHY <-> SUSPECT -> DEAD,
# DEAD -> HEALTHY only through probe-based re-admission)
HEALTHY, SUSPECT, DEAD = "healthy", "suspect", "dead"


class Router:
    """Route requests across homogeneous serving-engine replicas.

    ``engines`` must be interchangeable — same model, ``max_seq``, paged
    layout and block size — because routing must never change what a
    request computes, only where. Heterogeneous pools would also break
    key-chain affinity (keys are per-``block_size``).
    """

    def __init__(self, engines: list[ServingEngine], *,
                 policy: str = "affinity", imbalance: int = 2,
                 seed: int = 0, step_deadline_s: float = 30.0,
                 probe_successes: int = 2, auto_probe: bool = True):
        if not engines:
            raise ValueError("need at least one engine replica")
        if policy not in POLICIES:
            raise ValueError(f"unknown router policy {policy!r}; "
                             f"one of {POLICIES}")
        e0 = engines[0]
        for i, e in enumerate(engines[1:], 1):
            if (e.max_seq, e.paged, e.block_size) != (
                    e0.max_seq, e0.paged, e0.block_size):
                raise ValueError(
                    f"replica {i} differs from replica 0: "
                    f"(max_seq, paged, block_size) = "
                    f"{(e.max_seq, e.paged, e.block_size)} vs "
                    f"{(e0.max_seq, e0.paged, e0.block_size)} — replicas "
                    f"must be interchangeable")
        self.engines = engines
        for rid, e in enumerate(engines):
            e.engine_id = rid      # the ``replica`` stat of its spans
        self.policy = policy
        self.imbalance = int(imbalance)
        self.max_seq = e0.max_seq
        self.block_size = e0.block_size
        # affinity needs per-replica prefix caches to aim at
        self._affine = (policy == "affinity" and e0.paged
                        and e0.scheduler.prefix is not None)
        self._rng = random.Random(seed)
        self._rr = 0
        # routing stats (the bench and /metrics read these)
        self.routed = [0] * len(engines)       # per-replica request count
        self.affinity_hits = 0    # routed onto a live cached prefix
        self.affinity_hit_blocks = 0   # ... total peeked depth
        self.cold_affinity = 0    # cold prefix, routed by key hash
        self.load_fallbacks = 0   # hash target overloaded -> least-load
        self.load_routed = 0      # no keys: pure load routing
        # per-replica EWMA of observed TTFT (s): a soft load signal the
        # driver feeds back via observe_ttft; NaN until first observation
        self.ewma_ttft = [float("nan")] * len(engines)
        # sync-driver bookkeeping: completed-list watermark per replica
        # (step() scans the tail for fresh completions to feed the EWMA)
        self._done_seen = [0] * len(engines)
        # ---- replica health (HEALTHY -> SUSPECT -> DEAD + re-admission)
        self.step_deadline_s = float(step_deadline_s)
        self.probe_successes = int(probe_successes)
        self.auto_probe = bool(auto_probe)
        self.health = [HEALTHY] * len(engines)
        self.health_reason = [""] * len(engines)
        self.watchdog = [StragglerMonitor() for _ in engines]
        self._probe_ok = [0] * len(engines)     # consecutive clean probes
        self.death_t = [float("nan")] * len(engines)
        self.last_death_t = float("nan")
        self.replica_deaths = 0
        self.readmissions = 0
        self.migrated_requests = 0
        self.migration_failures = 0

    # ------------------------------------------------------------------ #
    # load signals
    # ------------------------------------------------------------------ #
    def depth(self, rid: int) -> int:
        """Queue + active depth of one replica (the primary load signal)."""
        sched = self.engines[rid].scheduler
        return sched.queue_depth + sum(
            1 for r in sched.active if r is not None)

    def _load_key(self, rid: int):
        t = self.ewma_ttft[rid]
        return (self.depth(rid), 0.0 if math.isnan(t) else t, rid)

    def observe_ttft(self, rid: int, ttft_s: float,
                     alpha: float = 0.2) -> None:
        """Fold one observed TTFT into replica ``rid``'s EWMA load signal
        (the async frontend calls this from its first-token events; the
        sync driver from completion scans)."""
        if math.isnan(ttft_s):
            return
        prev = self.ewma_ttft[rid]
        self.ewma_ttft[rid] = (ttft_s if math.isnan(prev)
                               else (1 - alpha) * prev + alpha * ttft_s)

    def _overloaded(self, rid: int, req: Request,
                    pool: list[int]) -> bool:
        """Is the hash-affine target a bad idea right now? True when its
        depth exceeds the lightest live replica's by more than
        ``imbalance``, or when it cannot admit the request while some
        other live replica can (the scheduler's pure would_admit probe)."""
        depths = {r: self.depth(r) for r in pool}
        if depths[rid] > min(depths.values()) + self.imbalance:
            return True
        if not self.engines[rid].scheduler.would_admit(req):
            return any(self.engines[r].scheduler.would_admit(req)
                       for r in pool if r != rid)
        return False

    # ------------------------------------------------------------------ #
    # replica health: HEALTHY -> SUSPECT -> DEAD, probe re-admission
    # ------------------------------------------------------------------ #
    def alive(self) -> list[int]:
        """Replica ids eligible for routing (everything not DEAD; SUSPECT
        is informational — a suspect replica still computes correctly,
        just slowly, and yanking its traffic on a z-score would make
        routing jitter-sensitive)."""
        return [r for r, h in enumerate(self.health) if h != DEAD]

    def record_step_time(self, rid: int, dt: float) -> None:
        """Feed one observed step wall time into replica ``rid``'s
        watchdog. A sustained straggler verdict (EWMA z-score) marks
        SUSPECT; a hard ``step_deadline_s`` overrun marks SUSPECT and, on
        a second consecutive overrun, DEAD (the caller migrates); a fast
        step heals SUSPECT back to HEALTHY."""
        if self.health[rid] == DEAD:
            return
        verdict = self.watchdog[rid].observe(dt)
        if dt >= self.step_deadline_s:
            if self.health[rid] == SUSPECT:
                self.mark_dead(
                    rid, f"step deadline: {dt:.3f}s >= "
                         f"{self.step_deadline_s:.3f}s, sustained")
            else:
                self.health[rid] = SUSPECT
                self.health_reason[rid] = (
                    f"step deadline miss ({dt:.3f}s)")
        elif verdict.is_straggler:
            if self.health[rid] == HEALTHY:
                self.health[rid] = SUSPECT
                self.health_reason[rid] = (
                    f"sustained straggler (z={verdict.z_score:.1f})")
        elif self.health[rid] == SUSPECT:
            self.health[rid] = HEALTHY
            self.health_reason[rid] = ""

    def mark_dead(self, rid: int, reason: str = "") -> None:
        """Transition ``rid`` to DEAD (idempotent). Marks only — callers
        that own the engine's thread follow up with :meth:`harvest` /
        :meth:`migrate` to move its work."""
        if self.health[rid] == DEAD:
            return
        self.health[rid] = DEAD
        self.health_reason[rid] = reason
        self.death_t[rid] = self.last_death_t = time.monotonic()
        self._probe_ok[rid] = 0
        self.replica_deaths += 1

    def harvest(self, rid: int) -> list[Request]:
        """Pull every in-flight and queued request off replica ``rid``,
        freeing its host-side blocks (finish decrefs; a later revival
        starts from a clean scheduler). Must run on whichever thread owns
        the engine — the sync driver, or a crashed worker's own thread
        after its step loop exited. Actives first (they hold generated
        tokens — the oldest work), then the queue in scheduling order."""
        sched = self.engines[rid].scheduler
        out: list[Request] = []
        for slot, req in enumerate(sched.active):
            if req is None:
                continue
            sched.finish(slot)
            out.append(req)
        out.extend(sched.drain_queue())
        for req in out:
            req.migrated = True
        return out

    def place_migrated(self, req: Request,
                       submit=None) -> int | None:
        """Route one harvested request to a survivor and resubmit it
        through the requeue-as-prefill path (bitwise resume — see
        :mod:`repro.serving.faults`). ``submit(rid, req)`` overrides the
        direct engine resubmit (the frontend hands off to worker inboxes
        instead). Returns the target rid, or None when the request could
        not be placed — no survivor, or a resume prompt that no longer
        fits — in which case it is failed loudly (``req.error`` set, the
        stream's final callback fired)."""
        try:
            rid = self.route(req)
            if submit is None:
                self.engines[rid].resubmit(req)
            else:
                submit(rid, req)
        except (RuntimeError, ValueError, MemoryError) as e:
            req.error = f"migration failed: {e}"
            req.done = True
            self.migration_failures += 1
            if req.on_tokens is not None:
                req.on_tokens(req, [], True)
            return None
        self.migrated_requests += 1
        return rid

    def migrate(self, rid: int, reason: str = "") -> int:
        """Kill ``rid`` and move its work to survivors (the sync-driver
        path: mark DEAD, harvest, re-route each request). Returns how
        many requests were successfully migrated."""
        self.mark_dead(rid, reason)
        return sum(1 for req in self.harvest(rid)
                   if self.place_migrated(req) is not None)

    def probe(self, rid: int) -> bool:
        """One liveness probe of a DEAD replica: attempt a (normally
        empty) ``step()`` — a still-dead engine raises, a recovered one
        no-ops. ``probe_successes`` consecutive clean probes readmit."""
        try:
            self.engines[rid].step()
        except Exception:
            self._probe_ok[rid] = 0
            return False
        self._probe_ok[rid] += 1
        if self._probe_ok[rid] >= self.probe_successes:
            self.readmit(rid)
        return True

    def readmit(self, rid: int) -> None:
        """Bring a recovered replica back into the routing pool: fresh
        watchdog statistics (the distribution that killed it is stale)
        and a flushed prefix cache — after a real crash the pool's
        contents are untrusted, and re-prefilling a cold cache is always
        correct (prefix hits never change tokens, only latency)."""
        self.health[rid] = HEALTHY
        self.health_reason[rid] = ""
        self.watchdog[rid].reset()
        self._probe_ok[rid] = 0
        self.readmissions += 1
        sched = self.engines[rid].scheduler
        if sched.prefix is not None:
            sched.prefix.evict(sched.num_blocks)

    # ------------------------------------------------------------------ #
    # routing
    # ------------------------------------------------------------------ #
    def route(self, req: Request) -> int:
        """Pick the replica for ``req`` (records stats, mutates no
        replica state). The frontend calls this then submits to the
        chosen replica's worker; :meth:`submit` does both for sync use.
        DEAD replicas are excluded — with every replica alive the pool is
        the full set and each policy's decision sequence is exactly the
        health-free one. Raises RuntimeError when no replica is alive."""
        pool = self.alive()
        if not pool:
            raise RuntimeError(
                "no live replicas: every replica is marked dead")
        if self.policy == "random":
            rid = pool[self._rng.randrange(len(pool))]
        elif self.policy == "round_robin":
            while True:
                rid = self._rr % len(self.engines)
                self._rr += 1
                if self.health[rid] != DEAD:
                    break
        else:
            rid = self._route_affinity(req, pool)
        self.routed[rid] += 1
        return rid

    def _route_affinity(self, req: Request, pool: list[int]) -> int:
        keys = (prefix_keys(req.prompt[: self.max_seq - 1],
                            self.block_size) if self._affine else [])
        if keys:
            # peek_depth, not len(peek(..)): tier-aware — a replica whose
            # prefix chain spilled to its host pool still attracts the
            # request (the fetch there is far cheaper than a re-prefill
            # anywhere else). Identical for single-tier replicas.
            depths = {
                r: (self.engines[r].scheduler.prefix.peek_depth(keys)
                    if self.engines[r].scheduler.prefix is not None else 0)
                for r in pool
            }
            best = max(depths.values())
            if best > 0:
                # a replica already holds this prefix: deepest hit wins,
                # load breaks ties
                rid = min((r for r in pool if depths[r] == best),
                          key=self._load_key)
                self.affinity_hits += 1
                self.affinity_hit_blocks += best
                return rid
            # cold prefix: stable hash of the first block's key over the
            # live pool, so the whole prefix family converges on one
            # replica (and re-converges onto a survivor after a death)
            rid = pool[int.from_bytes(keys[0][:8], "little") % len(pool)]
            if len(pool) > 1 and self._overloaded(rid, req, pool):
                self.load_fallbacks += 1
                return min(pool, key=self._load_key)
            self.cold_affinity += 1
            return rid
        self.load_routed += 1
        return min(pool, key=self._load_key)

    def submit(self, req: Request) -> int:
        """Route and enqueue; returns the chosen replica id."""
        rid = self.route(req)
        self.engines[rid].submit(req)
        return rid

    # ------------------------------------------------------------------ #
    # sync driver (benchmarks/tests; the async frontend threads replicas)
    # ------------------------------------------------------------------ #
    def step(self) -> int:
        """One step on every live replica that has work; returns total
        active. Also harvests fresh completions into the TTFT EWMA. This
        is where the sync driver's fault tolerance lives: a step that
        raises kills the replica and migrates its work; a step whose wall
        time trips the watchdog (:meth:`record_step_time`) does the same
        once the state machine reaches DEAD; DEAD replicas are probed for
        re-admission instead of stepped."""
        total = 0
        for rid, eng in enumerate(self.engines):
            if self.health[rid] == DEAD:
                if self.auto_probe:
                    self.probe(rid)
                continue
            if eng.has_work():
                t0 = time.monotonic()
                try:
                    total += eng.step()
                except Exception as e:
                    self.migrate(rid, f"step raised: {e!r}")
                    continue
                self.record_step_time(rid, time.monotonic() - t0)
                if self.health[rid] == DEAD:
                    # the watchdog killed it on this step's wall time;
                    # the step itself completed, so generated tokens are
                    # consistent and the harvest resumes after them
                    self.migrate(rid)
                    continue
            done = eng.completed
            for req in done[self._done_seen[rid]:]:
                self.observe_ttft(rid, req.metrics.ttft)
            self._done_seen[rid] = len(done)
        return total

    def has_work(self) -> bool:
        return any(e.has_work() for e in self.engines)

    def run_until_drained(self, max_steps: int = 10_000) -> list[Request]:
        for _ in range(max_steps):
            self.step()
            if not self.has_work():
                return self.completed
        raise RuntimeError(
            f"router drain: {max_steps} steps exhausted with work left on "
            f"{sum(1 for e in self.engines if e.has_work())} replicas")

    @property
    def completed(self) -> list[Request]:
        out: list[Request] = []
        for e in self.engines:
            out.extend(e.completed)
        return out

    # ------------------------------------------------------------------ #
    def stats(self) -> dict[str, float]:
        """Routing-layer counters (per-replica spread + affinity mix)."""
        total = sum(self.routed)
        keyed = self.affinity_hits + self.cold_affinity + self.load_fallbacks
        out = {
            "replicas": float(len(self.engines)),
            "routed_total": float(total),
            "affinity_hits": float(self.affinity_hits),
            "affinity_hit_blocks": float(self.affinity_hit_blocks),
            "cold_affinity": float(self.cold_affinity),
            "load_fallbacks": float(self.load_fallbacks),
            "load_routed": float(self.load_routed),
        }
        if keyed:
            out["affinity_hit_rate"] = self.affinity_hits / keyed
        for rid, c in enumerate(self.routed):
            out[f"replica{rid}_routed"] = float(c)
        out["replicas_alive"] = float(len(self.alive()))
        if self.replica_deaths:
            out["replica_deaths"] = float(self.replica_deaths)
            out["migrated_requests"] = float(self.migrated_requests)
            out["migration_failures"] = float(self.migration_failures)
            out["readmissions"] = float(self.readmissions)
        return out

    def metrics_summary(self) -> dict[str, float]:
        """Cross-replica aggregate of the engines' per-request summaries
        (means weighted by completed-request count), the step shares of
        the engines' summed counters, and routing stats."""
        summaries = [(e.metrics_summary(), e) for e in self.engines]
        summaries = [(m, e) for m, e in summaries if m]
        out: dict[str, float] = {}
        if summaries:
            # .get: a crashed replica with zero completions reports only
            # {"worker_crashed": n} — it carries no request weight
            total = sum(m.get("requests", 0.0) for m, _ in summaries)
            out["requests"] = total
            for key in ("mean_ttft_s", "mean_queue_wait_s",
                        "mean_decode_tok_per_s", "mean_prefix_hit_tokens",
                        "mean_host_hit_tokens"):
                vals = [(m[key], m.get("requests", 0.0))
                        for m, _ in summaries
                        if key in m and not math.isnan(m[key])]
                w = sum(n for _, n in vals)
                if vals and w:
                    out[key] = sum(v * n for v, n in vals) / w
            for key in ("preemptions", "requeues", "truncated_requests",
                        "spec_proposed", "spec_accepted", "cancelled",
                        "worker_crashed"):
                s = sum(m.get(key, 0.0) for m, _ in summaries)
                if key in summaries[0][0] or s:
                    out[key] = s
        counts = [e.counters() for e in self.engines]
        out.update(step_metrics(
            {k: sum(c[k] for c in counts) for k in COUNTERS},
            sum(c["steps"] * e.B for c, e in zip(counts, self.engines))))
        out.update(self.stats())
        return out


def make_replica_engines(api, params, *, replicas: int, tp: int = 1,
                         use_meshes: bool | None = None,
                         **engine_kw) -> list[ServingEngine]:
    """Build ``replicas`` interchangeable engines for a :class:`Router`.

    ``use_meshes=True`` pins each replica to its own device slice via
    :func:`repro.launch.mesh.make_replica_meshes` (needs ``replicas * tp``
    devices — the realized data axis); ``False`` co-locates every replica
    on the default device (distinct pools and schedulers, shared compute —
    fine for tests and CPU benches); ``None`` (default) uses meshes when
    the devices are there. ``tp > 1`` always needs meshes.
    """
    import jax

    if use_meshes is None:
        use_meshes = tp > 1 or jax.device_count() >= replicas * tp
    if tp > 1 and not use_meshes:
        raise ValueError("tp > 1 replicas need per-replica meshes")
    if use_meshes:
        from repro.launch.mesh import make_replica_meshes
        meshes = make_replica_meshes(replicas, tp)
    else:
        meshes = None
    return [
        ServingEngine(api, params,
                      mesh=None if meshes is None else meshes[r],
                      **engine_kw)
        for r in range(replicas)
    ]
