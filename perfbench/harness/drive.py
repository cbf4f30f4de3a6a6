"""Run one cell once: set up, warm up, drive the traffic through the served
path for the window, read the metrics, check the served tokens.

The window drives what the frontend drives: one :class:`EngineWorker` step
thread per engine, and for several replicas :meth:`Router.route` in front
of them. The client here stands in for the HTTP socket: it submits
:class:`Request` objects and stamps every token in ``on_tokens``, as
``AsyncFrontend._handle_generate`` does.
"""

from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import math
import queue
import shutil
import sys
import tempfile
import threading
import time

import numpy as np

from harness import check, spec, traffic
from harness.model import for_cell, make_params
from harness.record import StepRecorder


@dataclasses.dataclass
class Record:
    """One request as its client saw it (``perf_counter`` times)."""
    spec: traffic.RequestSpec
    req: object                 # the program's Request
    due: float
    sent: float = math.nan
    replica: int = 0
    times: list = dataclasses.field(default_factory=list)
    done_t: float = math.nan
    error: str | None = None
    session: object = None      # (session, turn, prompt) for sessions


@dataclasses.dataclass
class RunData:
    """What a per-layer metric's reader may read."""
    cell: dict
    model: object               # archs/<model_type>.py's Model
    peaks: object               # harness.peaks.Peaks
    chips: int
    devices: list[str]          # device id of each replica, in order
    seconds: float
    w0: float
    w1: float
    records: list[Record]
    steps: list                 # harness.record.Step, traced runs only
    trace: dict | None          # harness.trace.load(), traced runs only
    router: dict | None         # Router.stats()


class CompileCounter:
    """Counts XLA compilations while ``active``: none may happen inside
    the window."""

    def __init__(self):
        import jax
        self.active = False
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_) -> None:
        if self.active and event == "/jax/core/compile/backend_compile_duration":
            self.count += 1


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def setup_context():
    import jax

    import repro.core as nn
    nn.set_default_context(nn.get_extension_context(
        jax.default_backend(), type_config="pure_bf16"))


def build_engines(cell: dict, m, params):
    """The cell's engines, and a router in front of them when the cell has
    several replicas. Kernels follow the platform (Pallas on a TPU)."""
    import jax.numpy as jnp

    from repro.models.registry import get_model
    from repro.serving.engine import ServingEngine
    from repro.serving.router import Router, make_replica_engines

    api = get_model(m.to_repro())
    e = cell["engine"]
    kw = dict(max_batch=e["max_batch"], max_seq=e["max_seq"],
              chunk=e["chunk"], cache_dtype=jnp.bfloat16, kv_dtype="native",
              spec_k=0)
    replicas = e.get("replicas", 1)
    if replicas == 1:
        return [ServingEngine(api, params, **kw)], None
    engines = make_replica_engines(api, params, replicas=replicas, **kw)
    # every replica is healthy by construction: no step deadline
    return engines, Router(engines, policy=e["router"],
                           step_deadline_s=float("inf"))


def check_params(m, params) -> None:
    """The benchmark's leaves are exactly the program's: same names, shapes
    and dtypes."""
    import jax
    import jax.numpy as jnp

    import repro.core as nn
    from repro.models.registry import get_model
    api = get_model(m.to_repro())
    want = jax.eval_shape(lambda k: nn.init(lambda t: api.forward(t), k,
                                            jnp.zeros((1, 8), jnp.int32)),
                          jax.random.key(0))
    got = {k: (v.shape, v.dtype) for k, v in params.items()}
    want = {k: (v.shape, v.dtype) for k, v in want.items()}
    if got != want:
        raise ValueError(f"benchmark weights do not match the program's "
                         f"parameters: {sorted(set(got) ^ set(want))} "
                         f"{[k for k in got if k in want and got[k] != want[k]]}")


def warm(engine, shapes: list[tuple[int, bool]]) -> None:
    """Compile each ``(C, do_sample)`` step shape the cell's traffic uses,
    calling the engine's jitted step as :meth:`ServingEngine.step` does
    (every row writes to the garbage block 0), and the eager programs that
    admission runs."""
    import jax
    import jax.numpy as jnp
    B = engine.B
    for C, do_sample in shapes:
        z = np.zeros(B, np.int32)
        tok, engine.state = engine._step(
            engine.params, jnp.asarray(np.zeros((B, C), np.int32)),
            engine.state, jnp.asarray(np.zeros_like(engine.scheduler.pages)),
            jnp.asarray(z), jnp.asarray(np.ones(B, np.int32)),
            jnp.asarray(np.zeros(B, np.float32)), jnp.asarray(z),
            jnp.asarray(np.ones(B, np.float32)), jnp.asarray(z),
            jnp.asarray(z), do_sample=do_sample)
        np.asarray(tok)
    jax.block_until_ready(engine.state)
    # admission builds the admitted slots' index on the device eagerly, one
    # program per count of slots admitted at once
    for k in range(1, B + 1):
        jnp.asarray(list(range(k)), jnp.int32)


def warm_shapes(cell: dict) -> list[tuple[int, bool]]:
    """C in {chunk, 1}; sampled variants only where the mix samples, and
    greedy ones only where it has greedy requests."""
    mix = cell["mix"]
    share = mix.get("greedy_share", 1.0)
    samples = [False] if share >= 1.0 else [True, False]
    return [(C, s) for C in (cell["engine"]["chunk"], 1) for s in samples]


def memory_peak(devices) -> int:
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks) if peaks else 0


def load_reader(name: str):
    path = spec.metric_path(name)
    sp = importlib.util.spec_from_file_location(f"bench_metric_{name}", path)
    mod = importlib.util.module_from_spec(sp)
    sp.loader.exec_module(mod)
    return mod


class Client:
    """Sends the cell's traffic and stamps what comes back."""

    def __init__(self, cell, m, seed: int, workers, router):
        self.workers, self.router = workers, router
        self.load, self.mix = cell["load"], cell["mix"]
        self.m, self.seed = m, seed
        self.records: list[Record] = []
        self.done: queue.Queue = queue.Queue()
        self.uid = 0

    # -- sending ------------------------------------------------------- #
    def submit(self, rs: traffic.RequestSpec, due: float,
               session=None) -> Record:
        from repro.serving.engine import Request
        self.uid += 1
        req = Request(uid=self.uid, prompt=rs.prompt,
                      max_new_tokens=rs.max_new,
                      temperature=rs.temperature, top_p=rs.top_p,
                      seed=rs.seed)
        rec = Record(spec=rs, req=req, due=due, session=session)

        def on_tokens(r, toks, done, rec=rec):
            t = time.perf_counter()
            rec.times.extend([t] * len(toks))
            if done:
                rec.done_t = t
                rec.error = r.error
                self.done.put(rec)

        req.on_tokens = on_tokens
        rid = self.router.route(req) if self.router is not None else 0
        rec.replica = rid
        rec.sent = time.perf_counter()
        self.workers[rid].submit(req)
        self.records.append(rec)
        return rec

    # -- the traffic --------------------------------------------------- #
    def plan(self, span_s: float) -> None:
        kind, load = self.mix["kind"], self.load
        if kind == "requests" and load["mode"] == "open":
            self.todo = traffic.open_loop(self.mix, self.m.vocab, self.seed,
                                          load["rate_per_s"], span_s)
        elif kind == "requests":
            self.todo = traffic.closed_loop(
                self.mix, self.m.vocab, self.seed,
                load["clients"] * load["requests_per_client"])
        else:
            self.todo = traffic.sessions(
                self.mix, self.m.vocab, self.seed,
                load["clients"] * load["sessions_per_client"])
        self.next = 0

    def start(self, t0: float) -> None:
        self.t0 = t0
        if self.load["mode"] == "open":
            return
        for _ in range(self.load["clients"]):
            self._next_closed(t0)

    def _take(self):
        if self.next >= len(self.todo):
            raise RuntimeError("the traffic plan ran out: raise "
                               "requests_per_client or sessions_per_client")
        item = self.todo[self.next]
        self.next += 1
        return item

    def _next_closed(self, now: float) -> None:
        if self.mix["kind"] == "requests":
            self.submit(self._take(), now)
        else:
            self._next_turn(None, now)

    def _next_turn(self, rec: Record | None, now: float) -> None:
        """The session's next turn, or a new session when it ended."""
        max_ctx = self.mix["max_context"]
        if rec is not None:
            sess, turn, history = rec.session
            history = history + rec.req.generated
            turn += 1
        if rec is None or turn >= len(sess.users):
            sess, turn, history = self._take(), 0, None
            history = list(sess.system)
        prompt = history + sess.users[turn]
        if len(prompt) + sess.outputs[turn] >= max_ctx:
            return self._next_turn(None, now)
        rs = traffic.RequestSpec(index=sess.index * 16 + turn, prompt=prompt,
                                 max_new=sess.outputs[turn],
                                 seed=sess.index)
        self.submit(rs, now, session=(sess, turn, prompt))

    def poll(self, until: float) -> None:
        """Send what is due, and answer completions, until ``until``."""
        open_loop = self.load["mode"] == "open"
        while True:
            now = time.perf_counter()
            if now >= until:
                return
            if open_loop:
                if self.next >= len(self.todo):
                    time.sleep(until - now)
                    return
                rs = self.todo[self.next]
                due = self.t0 + rs.due_s
                if due > now:
                    time.sleep(min(due, until) - now)
                    continue
                self.next += 1
                self.submit(rs, due)
            else:
                try:
                    rec = self.done.get(timeout=until - now)
                except queue.Empty:
                    return
                if rec.error is None:
                    if self.mix["kind"] == "requests":
                        self._next_closed(time.perf_counter())
                    else:
                        self._next_turn(rec, time.perf_counter())


def run(cell: dict, seed: int, seconds: float, trace: bool, *,
        t_start: float, hook=None, control: bool = False
        ) -> dict:
    """One run of ``cell``; returns the result line's object. ``hook``
    (tests) is called with the built engines before they are warmed;
    ``control`` also judges the float8 control's tokens (``control.py``)."""
    import jax

    from harness.peaks import peaks
    from repro.serving.frontend import EngineWorker

    setup_context()
    compiles = CompileCounter()
    m = for_cell(cell)
    chips = cell["chips"]
    devices = jax.devices()[:chips]
    kind = devices[0].device_kind
    pk = peaks(kind) if devices[0].platform == "tpu" else None

    params = make_params(m, seed)
    jax.block_until_ready(params)
    check_params(m, params)
    engines, router = build_engines(cell, m, params)
    del params
    for e in engines:
        if e.kv_dtype != "bf16":
            raise ValueError(f"KV pools are {e.kv_dtype}, the cell says bf16")
    if hook is not None:
        hook(engines)
    for e in engines:
        warm(e, warm_shapes(cell))
    used = [str(next(iter(e.mesh.devices.flat)).id) if e.mesh is not None
            else str(devices[0].id) for e in engines]
    recorder = StepRecorder(engines) if trace else None
    workers = [EngineWorker(e, name=f"engine-worker-{i}")
               for i, e in enumerate(engines)]
    for w in workers:
        w.start()

    preroll = cell["load"]["preroll_s"]
    client = Client(cell, m, seed, workers, router)
    try:
        client.plan(preroll + seconds)
        tcfg = cell.get("trace", {})
        trace_from = preroll + tcfg.get("start_frac", 0.3) * seconds
        trace_len = min(tcfg.get("seconds", 5.0), seconds)
        t0 = time.perf_counter()
        setup_s = t0 - t_start
        client.start(t0)
        w0, w1 = t0 + preroll, t0 + preroll + seconds
        client.poll(w0)
        compiles.active = True
        logdir = None
        if trace:
            # the profiler starts and stops on a thread of its own, so
            # that the client keeps sending on time while it does
            client.poll(t0 + trace_from)
            logdir = tempfile.mkdtemp(prefix="bench-trace-")
            # no Python tracer: it would slow the host loop being measured
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            th = threading.Thread(target=jax.profiler.start_trace,
                                  args=(logdir,),
                                  kwargs={"profiler_options": opts})
            th.start()
            client.poll(min(w1, t0 + trace_from + trace_len))
            th.join()
            th = threading.Thread(target=jax.profiler.stop_trace)
            th.start()
            client.poll(w1)
            th.join()
        else:
            client.poll(w1)
        compiles.active = False
        window_compiles = compiles.count
    finally:
        snap = {id(r): (list(r.times), r.done_t, r.error)
                for r in client.records}
        for w in workers:
            w.stop(drain=False, timeout=60.0)

    # the window has closed: keep what the client saw by then
    records = list(client.records)
    for r in records:
        r.times, r.done_t, r.error = snap[id(r)]
    mem = memory_peak([d for d in devices if str(d.id) in used])
    run_data = RunData(
        cell=cell, model=m, peaks=pk, chips=chips, devices=used,
        seconds=seconds, w0=w0, w1=w1, records=records,
        steps=recorder.steps if recorder else [],
        trace=None, router=router.stats() if router is not None else None)

    out_metrics: dict = {}
    device = {"platform": devices[0].platform, "kind": kind,
              "count": len(jax.devices()), "memory_peak_bytes": mem}
    breakdown = None
    if trace:
        from harness import layers
        from harness import trace as tr
        run_data.trace = tr.load(logdir)
        shutil.rmtree(logdir, ignore_errors=True)
        busy, window = layers.device_busy(run_data)
        if window:
            device["busy_s"] = busy
            device["window_s"] = window
            breakdown = layers.breakdown(run_data)
        for name in cell["per_layer"]:
            v = load_reader(name).read(run_data)
            if v is not None:
                out_metrics[name] = {"value": float(v[0]), "unit": v[1]}
    else:
        from harness import e2e
        vals = e2e.compute(run_data)
        vals["setup_s"] = (setup_s, "s")
        for name in cell["end_to_end"]:
            out_metrics[name] = {"value": float(vals[name][0]),
                                 "unit": vals[name][1]}

    late = [r.sent - r.due for r in records if w0 <= r.due < w1]
    print(json.dumps({"generator": {
        "sent_in_window": len(late),
        "late_p50_ms": 1e3 * float(np.percentile(late, 50)) if late else 0.0,
        "late_p99_ms": 1e3 * float(np.percentile(late, 99)) if late else 0.0,
        "late_max_ms": 1e3 * float(max(late)) if late else 0.0,
        "compiles_in_window": window_compiles}}), flush=True)

    # free the program's weights and state before the reference takes the
    # chip, whatever still refers to the engines
    held = {id(a): a for e in engines
            for a in jax.tree.leaves((e.params, e.state))}
    for a in held.values():
        a.delete()
    del engines, router, workers, client, recorder
    run_data.trace = None
    gc.collect()

    attempted = [r for r in records if w0 <= r.due < w1]
    failed = [r for r in attempted if r.error is not None]
    verdict = check.check(cell, m, seed, records, w1, control=control)
    result = {
        "correct": bool(verdict["ok"] and not failed
                        and window_compiles == 0),
        "attempted": len(attempted), "failed": len(failed),
        "metrics": out_metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = verdict["checks"]
    result["checks"]["compiles_in_window"] = {"value": window_compiles,
                                              "limit": 0}
    result["checks"]["failed_requests"] = {"value": len(failed), "limit": 0}
    if control:
        # a control that gives no number has failed
        result["control"] = {
            "correct": bool(verdict.get("control_ok", False)),
            "max_logit_gap": verdict.get("control_gap", {"value": None})}
    return result
