"""Async streaming frontend: an HTTP/SSE server over the serving engine.

Pure-stdlib asyncio (no framework dependency — the CI container installs
only jax + numpy): a hand-rolled HTTP/1.1 parser over
``asyncio.start_server``, Server-Sent Events for token streaming. The
design decouples *submission* from *computation* from *streaming*:

* **Worker threads** (:class:`EngineWorker`, one per replica) drive the
  blocking jitted step loop continuously — the asyncio event loop never
  blocks on device compute. Each worker is the ONLY thread that touches
  its engine: HTTP handlers hand requests over through a thread-safe
  inbox the worker drains before every step, so the engine and scheduler
  stay single-threaded with zero locks in the hot path.
* **Per-request asyncio queues** carry tokens out: the engine's
  ``Request.on_tokens`` callback fires inside the step loop and posts
  ``(tokens, done, t)`` onto the request's queue via
  ``loop.call_soon_threadsafe`` — the one safe thread boundary — and the
  HTTP handler awaits the queue and writes SSE events as they land.
  A slow client therefore never stalls the step loop (tokens buffer in
  its queue) and a fast engine never waits for the network.
* **Backpressure** comes from scheduler admission: a POST is rejected
  with 503 (+ ``Retry-After``) when the target replica's queue depth
  reaches ``max_queue``, or — queue empty but the pool hopeless — when
  the scheduler's pure :meth:`~repro.serving.scheduler.Scheduler.
  would_admit` probe says the request could not be placed even at the
  head of the line. Trial-submitting and catching the rejection would
  skew admission stats and wedge head-of-line order; the probe mutates
  nothing.
* **Graceful drain**: :meth:`AsyncFrontend.shutdown` with ``drain=True``
  (the default) stops accepting new work (503), lets every in-flight
  stream run to completion, then stops the workers and closes the
  listener. ``drain=False`` abandons active requests (their streams get
  a final ``error`` event).

Streaming protocol (Server-Sent Events)
---------------------------------------
``POST /generate`` with a JSON body::

    {"prompt": [1, 2, 3], "max_new_tokens": 16, "temperature": 0.0,
     "top_k": 0, "top_p": 1.0, "seed": null, "priority": 0,
     "eos_id": null, "stream": true}

With ``stream`` true (default) the response is ``text/event-stream``:
one ``data:`` event per engine emission (a speculative verify step can
carry several tokens), then a final summary event, then ``[DONE]``::

    data: {"tokens": [17], "index": 0}
    data: {"tokens": [4, 9], "index": 1}
    data: {"done": true, "uid": 3, "replica": 0, "n": 3,
           "tokens": [17, 4, 9], "ttft_s": 0.01, "truncated": false}
    data: [DONE]

With ``stream`` false the same summary object comes back as one
``application/json`` response. ``GET /health`` reports liveness and load;
``GET /metrics`` the engine/router ``metrics_summary()`` plus frontend
stream metrics (tokens streamed, mean per-token latency = mean gap
between consecutive SSE emissions of a stream, rejects).

Multi-replica mode: construct with a :class:`~repro.serving.router.
Router` — the handler calls ``router.route(req)`` on the asyncio thread
(reads are racy-but-safe; see the router docstring) and submits to the
chosen replica's worker, feeding first-token latencies back into the
router's EWMA-TTFT load signal.

Edge resilience (PR 8)
----------------------
* **Crash-safe workers**: an exception out of the step loop no longer
  kills the thread silently — the worker marks itself crashed, bumps the
  engine's ``worker_crashed`` counter, and either hands its work to the
  frontend's ``on_crash`` hook (which marks the replica DEAD in the
  router and *migrates* queued + in-flight requests to surviving
  workers through the bitwise requeue-as-prefill path — see
  :mod:`repro.serving.faults`) or, with no survivors, aborts every
  stream with an error event and frees its blocks. The inbox never
  hangs: a crashed worker refuses new submits.
* **Disconnect cancellation**: a client that drops mid-SSE-stream
  cancels its request — the worker's thread-safe cancel inbox reaches
  :meth:`~repro.serving.scheduler.Scheduler.cancel`, finishing the slot
  and decref'ing its blocks instead of generating into an abandoned
  queue.
* **Per-request deadlines**: ``deadline_s`` in the POST body (or the
  frontend-wide ``request_timeout``) bounds a stream's total wall time;
  expiry cancels the request and fails the stream with 504 semantics.
* **Graceful degradation**: when the surviving-replica fraction drops
  to ``shed_below`` or less, requests at priority <= ``shed_priority``
  are shed with 503 + Retry-After — low-priority traffic queues nowhere
  while a degraded pool digests the migrated backlog.
* **Stuck-step watchdog** (``step_deadline_s``): a worker stuck *inside*
  one step past the deadline is marked DEAD for routing immediately and
  quarantined — it hands its work back for migration the moment the
  stuck step returns (mid-step state cannot be moved safely; see the
  faults module on step-boundary recovery), while per-request deadlines
  bound the damage if it never does.
* **Client retry**: :func:`client_generate` retries transient 503s with
  exponential backoff + jitter (:func:`retry_delays`), seeded for
  deterministic tests.
"""

from __future__ import annotations

import asyncio
import dataclasses
import json
import queue as _queue
import random
import threading
import time

from jax.profiler import TraceAnnotation

from repro.serving.engine import Request, ServingEngine
from repro.serving.router import DEAD, Router


class WorkerQuarantined(RuntimeError):
    """Raised inside a worker's step loop when the stuck-step watchdog
    quarantined it: routes the worker through its own crash path so its
    requests migrate at the first safe (step-boundary) moment."""


class EngineWorker(threading.Thread):
    """Background thread driving one engine's step loop continuously.

    The only thread that touches the engine after start(). Submissions
    arrive through :meth:`submit` / :meth:`resubmit` (thread-safe inbox,
    drained before each step); a submit the engine rejects (over-long
    prompt that can never fit the pool) sets ``req.error`` and fires the
    request's callback with ``done=True`` so the waiting stream fails
    loudly instead of hanging. :meth:`cancel` rides a second inbox,
    drained after submissions so a cancel always wins over its own
    submit. ``idle_wait`` bounds the sleep while there is no work.

    Crash safety: an exception out of the step loop is caught — the
    worker closes its inbox, bumps ``engine.worker_crashed``, and either
    defers to ``on_crash(worker, exc)`` (the frontend's migration hook;
    return True when the requests were taken care of) or aborts every
    queued/active/pending stream itself with an error event, freeing all
    blocks. Either way the thread exits cleanly and nothing hangs.
    """

    def __init__(self, engine: ServingEngine, *, idle_wait: float = 0.01,
                 name: str | None = None, on_crash=None):
        super().__init__(name=name or "engine-worker", daemon=True)
        self.engine = engine
        self.idle_wait = float(idle_wait)
        self._inbox: _queue.Queue[tuple[Request, bool]] = _queue.Queue()
        self._cancels: _queue.Queue[int] = _queue.Queue()
        self._wake = threading.Event()
        self._stopping = False
        self._drain = True
        self._closed = False          # refuse submits after stop()
        self._quarantined = False
        self.on_crash = on_crash      # callable(worker, exc) -> bool
        self.crashed = False
        self.crash_error: str | None = None
        self.steps = 0
        # wall-clock start of the step currently executing (None between
        # steps): the frontend's stuck-step watchdog polls this
        self.step_started_t: float | None = None

    def submit(self, req: Request) -> None:
        """Thread-safe: hand a request to the step loop."""
        if self._closed:
            raise RuntimeError("worker is shutting down")
        self._inbox.put((req, False))
        self._wake.set()

    def resubmit(self, req: Request) -> None:
        """Thread-safe: hand over a request migrating from a dead
        replica — drained into :meth:`ServingEngine.resubmit`, the
        bitwise requeue-as-prefill resume."""
        if self._closed:
            raise RuntimeError("worker is shutting down")
        self._inbox.put((req, True))
        self._wake.set()

    def cancel(self, uid: int) -> None:
        """Thread-safe: drop ``uid`` wherever it is (queued, active, or
        still in the inbox) at the next step boundary. No ``_closed``
        check — cancelling during drain must still work."""
        self._cancels.put(uid)
        self._wake.set()

    def quarantine(self) -> None:
        """Thread-safe: ask the worker to stop and hand its work back at
        the next step boundary (the stuck-step watchdog calls this; the
        worker itself raises :class:`WorkerQuarantined` when it sees the
        flag, routing through the crash/migration path)."""
        self._quarantined = True
        self._closed = True
        self._wake.set()

    def stop(self, *, drain: bool = True, timeout: float | None = 30.0
             ) -> None:
        """Stop the loop: ``drain=True`` finishes all queued/active work
        first; ``drain=False`` abandons it (active requests' callbacks
        fire once with ``req.error`` set)."""
        self._closed = True
        self._drain = drain
        self._stopping = True
        self._wake.set()
        self.join(timeout)

    def _drain_inbox(self) -> None:
        cancelled: set[int] = set()
        while True:
            try:
                uid = self._cancels.get_nowait()
            except _queue.Empty:
                break
            if not self.engine.cancel(uid):
                # not in the engine yet: it may still sit in the submit
                # inbox below — swallow it there
                cancelled.add(uid)
        while True:
            try:
                req, resume = self._inbox.get_nowait()
            except _queue.Empty:
                return
            if req.uid in cancelled:
                continue
            try:
                if resume:
                    self.engine.resubmit(req)
                else:
                    self.engine.submit(req)
            except (ValueError, MemoryError) as e:
                self._abort(req, str(e))

    def drain_pending(self) -> list[Request]:
        """Pop not-yet-submitted requests out of the inbox. Crash-path
        only: the caller is the crashed thread itself (or holds the
        joined thread), so nothing races the engine."""
        out = []
        while True:
            try:
                req, _ = self._inbox.get_nowait()
            except _queue.Empty:
                return out
            out.append(req)

    def run(self) -> None:   # pragma: no cover - exercised via frontend
        try:
            self._run_loop()
        except Exception as e:
            # crash-safe: the step loop must never die silently — streams
            # would hang and stop(drain=True) would block to timeout
            self.crashed = True
            self.crash_error = repr(e)
            self._closed = True
            self.engine.worker_crashed += 1
            handled = False
            if self.on_crash is not None:
                try:
                    handled = bool(self.on_crash(self, e))
                except Exception:   # the hook must not re-kill the thread
                    handled = False
            if not handled:
                self._abort_all(f"replica worker crashed: {e!r}")

    def _run_loop(self) -> None:
        eng = self.engine
        while True:
            with TraceAnnotation("serve.inbox", replica=eng.engine_id):
                self._drain_inbox()
            if self._quarantined:
                raise WorkerQuarantined(
                    "quarantined by the stuck-step watchdog")
            if self._stopping and not self._drain:
                break
            if eng.has_work():
                self.step_started_t = time.monotonic()
                try:
                    eng.step()
                finally:
                    self.step_started_t = None
                self.steps += 1
            elif self._stopping and self._inbox.empty():
                break
            else:
                self._wake.wait(self.idle_wait)
                self._wake.clear()
        if self._stopping and not self._drain:
            # abandoned requests: fail their streams, free their blocks
            self._abort_all("aborted: frontend shut down without drain")

    def _abort_all(self, msg: str) -> None:
        """Fail every request this worker still owns — active slots
        (blocks freed), the scheduler queue, and the unsubmitted inbox —
        with one final error event each."""
        eng = self.engine
        for slot, req in enumerate(eng.scheduler.active):
            if req is None:
                continue
            eng.scheduler.finish(slot)
            self._abort(req, msg)
        for req in eng.scheduler.drain_queue():
            self._abort(req, msg)
        for req in self.drain_pending():
            self._abort(req, msg)

    @staticmethod
    def _abort(req: Request, msg: str) -> None:
        req.error = msg
        if req.on_tokens is not None:
            req.on_tokens(req, [], True)


@dataclasses.dataclass
class FrontendStats:
    """Stream-level metrics the engine cannot see (it has no notion of a
    connection): acceptance/rejection counts and per-token SSE latency —
    the gap between consecutive emissions of one stream, aggregated over
    all streams. ``mean_inter_token_s`` is the serving-side analogue of
    decode tok/s as a *client* experiences it."""
    requests_accepted: int = 0
    requests_rejected: int = 0
    requests_completed: int = 0
    requests_failed: int = 0
    tokens_streamed: int = 0
    inter_token_sum_s: float = 0.0
    inter_token_n: int = 0
    # resilience counters (all zero — and absent from as_dict — on the
    # healthy path)
    requests_cancelled: int = 0   # client disconnected mid-stream
    requests_timed_out: int = 0   # per-request deadline expired
    requests_shed: int = 0        # rejected by degraded-capacity shedding
    requests_migrated: int = 0    # moved off a crashed replica's worker
    workers_crashed: int = 0

    @property
    def mean_inter_token_s(self) -> float:
        if self.inter_token_n == 0:
            return float("nan")
        return self.inter_token_sum_s / self.inter_token_n

    def as_dict(self) -> dict[str, float]:
        out = {
            "frontend_requests_accepted": float(self.requests_accepted),
            "frontend_requests_rejected": float(self.requests_rejected),
            "frontend_requests_completed": float(self.requests_completed),
            "frontend_requests_failed": float(self.requests_failed),
            "frontend_tokens_streamed": float(self.tokens_streamed),
        }
        if self.inter_token_n:
            out["frontend_mean_inter_token_s"] = self.mean_inter_token_s
        for key in ("requests_cancelled", "requests_timed_out",
                    "requests_shed", "requests_migrated",
                    "workers_crashed"):
            v = getattr(self, key)
            if v:
                out[f"frontend_{key}"] = float(v)
        return out


class AsyncFrontend:
    """HTTP/SSE server over one engine or a multi-replica router.

    Lifecycle::

        fe = AsyncFrontend(engine_or_router, port=0)
        await fe.start()          # workers spin up, socket listens
        ...                       # fe.port is the bound port
        await fe.shutdown()       # drain in-flight streams, stop workers

    or from sync code, ``fe.run_forever()`` (Ctrl-C drains and exits).
    """

    def __init__(self, target: ServingEngine | Router, *,
                 host: str = "127.0.0.1", port: int = 0,
                 max_queue: int = 32, idle_wait: float = 0.01,
                 request_timeout: float | None = None,
                 step_deadline_s: float | None = None,
                 shed_below: float = 0.5, shed_priority: int = 0):
        if isinstance(target, Router):
            self.router: Router | None = target
            engines = target.engines
        else:
            self.router = None
            engines = [target]
        self.engines = engines
        self.workers = [
            EngineWorker(e, idle_wait=idle_wait, name=f"engine-worker-{i}",
                         on_crash=self._worker_crashed)
            for i, e in enumerate(engines)
        ]
        self.host = host
        self.port = port              # 0 = ephemeral; real port after start
        self.max_queue = int(max_queue)
        # default total-wall-time deadline per request (None = unbounded);
        # a request's own "deadline_s" body field overrides it
        self.request_timeout = request_timeout
        # stuck-step watchdog (None = off): needs a router to mark DEAD in
        self.step_deadline_s = step_deadline_s
        # degraded-capacity shedding: when alive/total <= shed_below (and
        # at least one replica is dead), priority <= shed_priority is 503'd
        self.shed_below = float(shed_below)
        self.shed_priority = int(shed_priority)
        self.stats = FrontendStats()
        self.accepting = False
        self._server: asyncio.AbstractServer | None = None
        self._loop: asyncio.AbstractEventLoop | None = None
        self._watchdog_task: asyncio.Task | None = None
        self._uid = 0
        self._inflight = 0
        self._idle = asyncio.Event()
        self._idle.set()

    # ------------------------------------------------------------------ #
    # lifecycle
    # ------------------------------------------------------------------ #
    async def start(self) -> None:
        self._loop = asyncio.get_running_loop()
        for w in self.workers:
            w.start()
        self._server = await asyncio.start_server(
            self._handle_conn, self.host, self.port)
        self.port = self._server.sockets[0].getsockname()[1]
        self.accepting = True
        if self.step_deadline_s and self.router is not None:
            self._watchdog_task = self._loop.create_task(self._watchdog())

    async def shutdown(self, *, drain: bool = True,
                       timeout: float = 60.0) -> None:
        """Stop accepting (new POSTs get 503); with ``drain`` wait for
        every in-flight stream to finish before stopping the workers and
        closing the listener."""
        self.accepting = False
        if self._watchdog_task is not None:
            self._watchdog_task.cancel()
            self._watchdog_task = None
        if drain:
            try:
                await asyncio.wait_for(self._idle.wait(), timeout)
            except asyncio.TimeoutError:   # pragma: no cover - safety net
                pass
        for w in self.workers:
            # stop() joins the worker thread: run it off the event loop
            await asyncio.get_running_loop().run_in_executor(
                None, lambda w=w: w.stop(drain=drain))
        if self._server is not None:
            self._server.close()
            await self._server.wait_closed()

    def run_forever(self) -> None:   # pragma: no cover - CLI convenience
        async def _main():
            await self.start()
            print(f"serving on http://{self.host}:{self.port} "
                  f"({len(self.engines)} replica"
                  f"{'s' if len(self.engines) > 1 else ''})", flush=True)
            try:
                await asyncio.Event().wait()
            finally:
                await self.shutdown()
        try:
            asyncio.run(_main())
        except KeyboardInterrupt:
            pass

    # ------------------------------------------------------------------ #
    # fault handling
    # ------------------------------------------------------------------ #
    def _worker_crashed(self, worker: EngineWorker,
                        exc: BaseException) -> bool:
        """Crash hook, called ON the dying worker's thread — its step
        loop has exited, so its engine is safe to touch from here. With a
        router and at least one survivor the crashed replica's work
        migrates: queued + in-flight requests are harvested (blocks
        freed) and resubmitted to surviving workers' thread-safe inboxes
        through the requeue-as-prefill path, so their streams continue
        bitwise (see :mod:`repro.serving.faults`). Returns False — "not
        handled, abort everything" — when there is no router or no
        survivor."""
        self.stats.workers_crashed += 1
        if self.router is None:
            return False
        rid = self.workers.index(worker)
        self.router.mark_dead(rid, repr(exc))
        if not self.router.alive():
            return False
        moved = self.router.harvest(rid) + worker.drain_pending()
        for req in moved:
            target = self.router.place_migrated(
                req, submit=lambda t, r: self.workers[t].resubmit(r))
            if target is not None:
                self.stats.requests_migrated += 1
        return True

    async def _watchdog(self) -> None:
        """Stuck-step watchdog: a worker inside ONE engine step for
        longer than ``step_deadline_s`` is marked DEAD (routing excludes
        it immediately) and quarantined — the worker raises out of its
        loop at the next step boundary and its work migrates via the
        crash hook. Mid-step state cannot be moved safely (the stalled
        thread owns the engine), so migration waits for the stall to
        break; per-request deadlines bound the damage if it never does."""
        assert self.router is not None and self.step_deadline_s
        interval = max(self.step_deadline_s / 4.0, 0.005)
        while True:
            await asyncio.sleep(interval)
            now = time.monotonic()
            for rid, w in enumerate(self.workers):
                if self.router.health[rid] == DEAD or w.crashed:
                    continue
                t0 = w.step_started_t
                if t0 is not None and now - t0 >= self.step_deadline_s:
                    self.router.mark_dead(
                        rid, f"stuck in one step > "
                             f"{self.step_deadline_s:.3f}s")
                    w.quarantine()

    # ------------------------------------------------------------------ #
    # request plumbing
    # ------------------------------------------------------------------ #
    def _total_depth(self) -> int:
        return sum(w._inbox.qsize() + e.scheduler.queue_depth
                   for w, e in zip(self.workers, self.engines))

    def _make_request(self, body: dict) -> Request:
        uid = self._uid
        self._uid += 1
        prompt = body.get("prompt")
        if (not isinstance(prompt, list) or not prompt
                or not all(isinstance(t, int) for t in prompt)):
            raise ValueError("'prompt' must be a non-empty list of ints")
        return Request(
            uid=uid, prompt=prompt,
            max_new_tokens=int(body.get("max_new_tokens", 16)),
            eos_id=body.get("eos_id"),
            priority=int(body.get("priority", 0)),
            temperature=float(body.get("temperature", 0.0)),
            top_k=int(body.get("top_k", 0)),
            top_p=float(body.get("top_p", 1.0)),
            seed=body.get("seed"))

    def _admission_check(self, req: Request, rid: int) -> str | None:
        """Returns a rejection reason, or None to admit. Queue depth is
        the primary backpressure signal; an *empty* queue with a pool
        that could never place the request (would_admit probe) rejects
        immediately rather than parking the request at the head of the
        line to starve everything behind it."""
        if self.router is not None:
            alive = self.router.alive()
            if (len(alive) < len(self.engines)
                    and len(alive) / len(self.engines) <= self.shed_below
                    and req.priority <= self.shed_priority):
                self.stats.requests_shed += 1
                return (f"degraded: {len(alive)}/{len(self.engines)} "
                        f"replicas alive, shedding priority <= "
                        f"{self.shed_priority}")
        sched = self.engines[rid].scheduler
        depth = self.workers[rid]._inbox.qsize() + sched.queue_depth
        if depth >= self.max_queue:
            return (f"replica {rid} queue is full "
                    f"({depth}/{self.max_queue})")
        if depth == 0 and not sched.would_admit(req) \
                and not sched.has_work():
            # nothing running, nothing queued, still unplaceable: the
            # request can never fit (too many blocks) — reject now
            return (f"request needs more KV blocks than replica {rid}'s "
                    f"pool can ever free")
        return None

    # ------------------------------------------------------------------ #
    # HTTP layer
    # ------------------------------------------------------------------ #
    async def _handle_conn(self, reader: asyncio.StreamReader,
                           writer: asyncio.StreamWriter) -> None:
        try:
            await self._handle_one(reader, writer)
        except (asyncio.IncompleteReadError, ConnectionResetError,
                BrokenPipeError, asyncio.TimeoutError):
            pass                       # client went away mid-request
        finally:
            try:
                writer.close()
                await writer.wait_closed()
            except (ConnectionResetError, OSError):  # pragma: no cover
                pass

    async def _handle_one(self, reader, writer) -> None:
        request_line = await asyncio.wait_for(reader.readline(), 30.0)
        if not request_line:
            return
        try:
            method, path, _ = request_line.decode("ascii").split()
        except ValueError:
            await self._respond(writer, 400, {"error": "bad request line"})
            return
        headers: dict[str, str] = {}
        while True:
            line = await asyncio.wait_for(reader.readline(), 30.0)
            if line in (b"\r\n", b"\n", b""):
                break
            key, _, val = line.decode("latin1").partition(":")
            headers[key.strip().lower()] = val.strip()
        body = b""
        clen = int(headers.get("content-length", "0") or 0)
        if clen:
            body = await asyncio.wait_for(reader.readexactly(clen), 30.0)

        if method == "GET" and path == "/health":
            await self._respond(writer, 200, self._health())
        elif method == "GET" and path == "/metrics":
            await self._respond(writer, 200, self._metrics())
        elif method == "POST" and path == "/generate":
            await self._handle_generate(writer, body)
        else:
            await self._respond(writer, 404,
                                {"error": f"no route {method} {path}"})

    def _health(self) -> dict:
        active = sum(sum(1 for r in e.scheduler.active if r is not None)
                     for e in self.engines)
        out = {"status": "ok" if self.accepting else "draining",
               "replicas": len(self.engines),
               "queued": self._total_depth(), "active": active}
        if self.router is not None and (
                any(h != "healthy" for h in self.router.health)
                or self.router.replica_deaths):
            out["replica_health"] = list(self.router.health)
        return out

    def _metrics(self) -> dict:
        src = self.router if self.router is not None else self.engines[0]
        out = dict(src.metrics_summary())
        out.update(self.stats.as_dict())
        # JSON has no NaN: drop undefined aggregates rather than emitting
        # the non-standard token json.dumps would produce
        return {k: v for k, v in out.items()
                if not (isinstance(v, float) and v != v)}

    async def _respond(self, writer, status: int, obj: dict) -> None:
        reasons = {200: "OK", 400: "Bad Request", 404: "Not Found",
                   503: "Service Unavailable", 504: "Gateway Timeout"}
        payload = json.dumps(obj).encode()
        head = (f"HTTP/1.1 {status} {reasons.get(status, 'OK')}\r\n"
                "Content-Type: application/json\r\n"
                f"Content-Length: {len(payload)}\r\n"
                + ("Retry-After: 1\r\n" if status == 503 else "")
                + "Connection: close\r\n\r\n").encode()
        writer.write(head + payload)
        await writer.drain()

    # ------------------------------------------------------------------ #
    # /generate
    # ------------------------------------------------------------------ #
    async def _handle_generate(self, writer, raw: bytes) -> None:
        if not self.accepting:
            self.stats.requests_rejected += 1
            await self._respond(writer, 503, {"error": "shutting down"})
            return
        try:
            body = json.loads(raw.decode() or "{}")
            req = self._make_request(body)
        except (ValueError, UnicodeDecodeError) as e:
            await self._respond(writer, 400, {"error": str(e)})
            return
        try:
            rid = self.router.route(req) if self.router is not None else 0
        except RuntimeError as e:      # every replica marked dead
            self.stats.requests_rejected += 1
            await self._respond(writer, 503, {"error": str(e)})
            return
        reason = self._admission_check(req, rid)
        if reason is not None:
            self.stats.requests_rejected += 1
            await self._respond(writer, 503, {"error": reason})
            return
        deadline = body.get("deadline_s", self.request_timeout)
        deadline = float(deadline) if deadline is not None else None

        loop = self._loop
        q: asyncio.Queue = asyncio.Queue()

        def on_tokens(r: Request, toks: list[int], done: bool) -> None:
            # runs on the worker thread, inside the step loop: the queue
            # put is marshalled onto the event loop — the only thread
            # crossing. time.monotonic here stamps true emission time so
            # per-token latency excludes event-loop scheduling delay.
            loop.call_soon_threadsafe(
                q.put_nowait, (list(toks), done, time.monotonic()))

        req.on_tokens = on_tokens
        stream = bool(body.get("stream", True))
        try:
            self.workers[rid].submit(req)
        except RuntimeError as e:   # worker crashed/quarantined just now
            self.stats.requests_rejected += 1
            await self._respond(writer, 503, {"error": str(e)})
            return
        self.stats.requests_accepted += 1
        self._inflight += 1
        self._idle.clear()
        try:
            if stream:
                await self._stream_sse(writer, req, rid, q,
                                       deadline=deadline)
            else:
                await self._collect_json(writer, req, rid, q,
                                         deadline=deadline)
        except (ConnectionResetError, BrokenPipeError):
            # client dropped mid-stream: cancel so the engine stops
            # generating into an abandoned queue and frees the blocks
            self.workers[rid].cancel(req.uid)
            self.stats.requests_cancelled += 1
            raise
        finally:
            self._inflight -= 1
            if self._inflight == 0:
                self._idle.set()

    def _summary_obj(self, req: Request, rid: int) -> dict:
        err = getattr(req, "error", None)
        out = {"done": True, "uid": req.uid, "replica": rid,
               "n": len(req.generated), "tokens": list(req.generated),
               "truncated": req.truncated}
        ttft = req.metrics.ttft
        if ttft == ttft:               # NaN-safe: omit when undefined
            out["ttft_s"] = round(ttft, 6)
        if err is not None:
            out["error"] = err
        return out

    async def _consume(self, req: Request, rid: int, q: asyncio.Queue,
                       per_event, deadline: float | None = None) -> None:
        """Drain the request's token queue to completion, maintaining
        stream metrics; ``per_event(toks, index)`` runs for every
        emission (the SSE writer, or a no-op for non-streaming). With a
        ``deadline`` (total wall seconds from now) an overrunning request
        is cancelled on its worker and the stream fails with a
        "deadline exceeded" error (504 for non-streaming)."""
        index = 0
        last_t: float | None = None
        first = True
        t_end = (time.monotonic() + deadline) if deadline is not None \
            else None
        while True:
            if t_end is None:
                toks, done, t = await q.get()
            else:
                try:
                    toks, done, t = await asyncio.wait_for(
                        q.get(), max(t_end - time.monotonic(), 0.0))
                except asyncio.TimeoutError:
                    self.workers[rid].cancel(req.uid)
                    req.error = (f"deadline exceeded: no completion "
                                 f"within {deadline:.3f}s")
                    self.stats.requests_timed_out += 1
                    self.stats.requests_failed += 1
                    return
            if toks:
                if first and self.router is not None:
                    self.router.observe_ttft(
                        rid, t - req.metrics.submit_t)
                first = False
                self.stats.tokens_streamed += len(toks)
                if last_t is not None:
                    # one emission = one step: the gap amortizes over the
                    # tokens it carried (speculative steps emit several)
                    self.stats.inter_token_sum_s += t - last_t
                    self.stats.inter_token_n += len(toks)
                last_t = t
                await per_event(toks, index)
                index += 1
            if done:
                if getattr(req, "error", None) is None:
                    self.stats.requests_completed += 1
                else:
                    self.stats.requests_failed += 1
                return

    async def _stream_sse(self, writer, req, rid, q, *,
                          deadline: float | None = None) -> None:
        writer.write(b"HTTP/1.1 200 OK\r\n"
                     b"Content-Type: text/event-stream\r\n"
                     b"Cache-Control: no-store\r\n"
                     b"Connection: close\r\n\r\n")
        await writer.drain()

        async def emit(toks: list[int], index: int) -> None:
            ev = json.dumps({"tokens": toks, "index": index})
            writer.write(f"data: {ev}\n\n".encode())
            await writer.drain()

        await self._consume(req, rid, q, emit, deadline)
        summary = json.dumps(self._summary_obj(req, rid))
        writer.write(f"data: {summary}\n\ndata: [DONE]\n\n".encode())
        await writer.drain()

    async def _collect_json(self, writer, req, rid, q, *,
                            deadline: float | None = None) -> None:
        async def emit(toks: list[int], index: int) -> None:
            pass
        await self._consume(req, rid, q, emit, deadline)
        obj = self._summary_obj(req, rid)
        if "error" not in obj:
            status = 200
        elif obj["error"].startswith("deadline exceeded"):
            status = 504
        else:
            status = 400
        await self._respond(writer, status, obj)


# ---------------------------------------------------------------------- #
# minimal client (tests + benchmarks; avoids an HTTP-library dependency)
# ---------------------------------------------------------------------- #

def retry_delays(retries: int, *, base_s: float = 0.05,
                 cap_s: float = 2.0, jitter: float = 0.1, rng=None):
    """Exponential backoff with multiplicative jitter: yields ``retries``
    delays ``min(cap_s, base_s * 2**i) * (1 + jitter * U[0,1))``. The
    jitter de-synchronizes a thundering herd of clients all told
    Retry-After by the same overloaded frontend; pass a seeded ``rng``
    for deterministic tests."""
    rng = rng if rng is not None else random
    for i in range(retries):
        yield min(cap_s, base_s * (2.0 ** i)) * (1.0 + jitter
                                                 * rng.random())


async def client_generate(host: str, port: int, *, stream: bool = True,
                          timeout: float = 120.0, retries: int = 0,
                          retry_base_s: float = 0.05,
                          retry_cap_s: float = 2.0,
                          retry_jitter: float = 0.1, retry_rng=None,
                          **payload) -> dict:
    """POST /generate and consume the response; returns the final summary
    object with ``events`` = the streamed SSE event list prepended, plus
    ``attempts``. Transient 503s (backpressure, degraded-capacity
    shedding) are retried up to ``retries`` times with exponential
    backoff + jitter; any other status returns immediately. The
    token-level test client: asserts nothing, decodes everything."""
    delays = retry_delays(retries, base_s=retry_base_s, cap_s=retry_cap_s,
                          jitter=retry_jitter, rng=retry_rng)
    attempt = 0
    while True:
        out = await _client_generate_once(host, port, stream=stream,
                                          timeout=timeout, **payload)
        attempt += 1
        if out.get("http_status") != 503 or attempt > retries:
            out["attempts"] = attempt
            return out
        await asyncio.sleep(next(delays))


async def _client_generate_once(host: str, port: int, *,
                                stream: bool = True,
                                timeout: float = 120.0,
                                **payload) -> dict:
    reader, writer = await asyncio.open_connection(host, port)
    try:
        body = json.dumps(dict(payload, stream=stream)).encode()
        writer.write(
            (f"POST /generate HTTP/1.1\r\nHost: {host}\r\n"
             "Content-Type: application/json\r\n"
             f"Content-Length: {len(body)}\r\n"
             "Connection: close\r\n\r\n").encode() + body)
        await writer.drain()
        status_line = await asyncio.wait_for(reader.readline(), timeout)
        status = int(status_line.split()[1])
        while True:   # headers
            line = await asyncio.wait_for(reader.readline(), timeout)
            if line in (b"\r\n", b"\n", b""):
                break
        if not stream or status != 200:
            raw = await asyncio.wait_for(reader.read(), timeout)
            return dict(json.loads(raw.decode() or "{}"),
                        http_status=status, events=[])
        events: list[dict] = []
        summary: dict = {}
        buf = b""
        while True:
            chunk = await asyncio.wait_for(reader.readline(), timeout)
            if not chunk:
                break
            buf += chunk
            if not buf.endswith(b"\n\n") and chunk not in (b"\n", b"\r\n"):
                continue
            text = buf.decode().strip()
            buf = b""
            if not text.startswith("data:"):
                continue
            data = text[len("data:"):].strip()
            if data == "[DONE]":
                break
            obj = json.loads(data)
            if obj.get("done"):
                summary = obj
            else:
                events.append(obj)
        return dict(summary, http_status=status, events=events)
    finally:
        writer.close()
        try:
            await writer.wait_closed()
        except (ConnectionResetError, OSError):   # pragma: no cover
            pass


async def client_get(host: str, port: int, path: str,
                     timeout: float = 30.0) -> dict:
    """GET a JSON endpoint (/health, /metrics)."""
    reader, writer = await asyncio.open_connection(host, port)
    try:
        writer.write((f"GET {path} HTTP/1.1\r\nHost: {host}\r\n"
                      "Connection: close\r\n\r\n").encode())
        await writer.drain()
        status_line = await asyncio.wait_for(reader.readline(), timeout)
        status = int(status_line.split()[1])
        clen = 0
        while True:
            line = await asyncio.wait_for(reader.readline(), timeout)
            if line in (b"\r\n", b"\n", b""):
                break
            k, _, v = line.decode("latin1").partition(":")
            if k.strip().lower() == "content-length":
                clen = int(v)
        raw = await asyncio.wait_for(reader.readexactly(clen), timeout) \
            if clen else b"{}"
        return dict(json.loads(raw.decode()), http_status=status)
    finally:
        writer.close()
        try:
            await writer.wait_closed()
        except (ConnectionResetError, OSError):   # pragma: no cover
            pass
