"""The engine's own spans, step counters and device scope names."""

import glob
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models.registry import get_model
from repro.serving.engine import Request, ServingEngine
from repro.serving.frontend import EngineWorker
from repro.serving.router import Router

from test_serving import CFG, LM_CFGS, init_params, make_engine

PHASES = ["serve.admit", "serve.stage", "serve.dispatch", "serve.readback",
          "serve.bookkeep"]


def profiled_spans(tmp_path, run) -> list:
    """``[name, start_ns, end_ns, stats]`` of every ``serve.*`` span that
    ``run()`` writes under the profiler, by start."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        run()
    finally:
        jax.profiler.stop_trace()
    path = sorted(glob.glob(f"{tmp_path}/**/*.xplane.pb", recursive=True))[-1]
    pd = jax.profiler.ProfileData.from_file(path)
    return sorted(([e.name, int(e.start_ns), int(e.start_ns + e.duration_ns),
                    dict(e.stats)]
                   for plane in pd.planes for line in plane.lines
                   for e in line.events if e.name.startswith("serve.")),
                  key=lambda s: s[1])


def test_step_spans_nest_in_phase_order(tmp_path):
    eng = make_engine(max_batch=2, chunk=4)
    eng.submit(Request(uid=0, prompt=list(range(1, 7)), max_new_tokens=3))
    eng.step()                                    # compile outside the trace

    def run():
        for _ in range(3):
            eng.step()

    spans = profiled_spans(tmp_path, run)
    steps = [s for s in spans if s[0] == "serve.step"]
    # the prompt's second chunk (C = 4), then two decode steps (C = 1)
    assert [(st["step"], st["B"], st["C"], st["rows"], st["replica"])
            for _, _, _, st in steps] == [(1, 2, 4, 1, 0), (2, 2, 1, 1, 0),
                                          (3, 2, 1, 1, 0)]
    for _, t0, t1, _ in steps:
        inner = [s for s in spans if t0 <= s[1] and s[2] <= t1
                 and s[0] != "serve.step"]
        assert [s[0] for s in inner] == PHASES
        assert all(a[2] <= b[1] for a, b in zip(inner, inner[1:]))
        assert all(s[3]["replica"] == 0 for s in inner)


def test_worker_spans_its_inbox_and_router_numbers_replicas(tmp_path):
    engines = [make_engine(max_batch=2, chunk=4) for _ in range(2)]
    Router(engines)
    assert [e.engine_id for e in engines] == [0, 1]
    done = []
    req = Request(uid=0, prompt=[1, 2, 3], max_new_tokens=2,
                  on_tokens=lambda r, toks, fin: fin and done.append(r))
    worker = EngineWorker(engines[1])

    def run():
        worker.start()
        worker.submit(req)
        worker.stop(drain=True, timeout=120)

    spans = profiled_spans(tmp_path, run)
    assert done == [req]
    names = {s[0] for s in spans}
    assert {"serve.inbox", "serve.step", *PHASES} <= names
    assert {s[3]["replica"] for s in spans} == {1}


def test_counters_match_a_scripted_sequence():
    """Two rows, chunk 4: a 6-token prompt (then two decode steps) beside
    a 2-token prompt that finishes after one decode step.

    step 1, C 4: rows absorb 4 and 2 tokens; the short one emits
    step 2, C 4: the long row's last 2 tokens (emits), the short one
                 decodes 1 (emits, done)
    steps 3-4, C 1: the long row decodes 1 each (emits; done at 3 tokens)
    """
    eng = make_engine(max_batch=2, chunk=4)
    eng.submit(Request(uid=0, prompt=list(range(1, 7)), max_new_tokens=3))
    eng.submit(Request(uid=1, prompt=[7, 8], max_new_tokens=2))
    eng.run_until_drained()
    assert eng.counters() == {
        "steps": 4, "chunk_steps": 2,
        "token_slots": 2 * 4 + 2 * 4 + 2 * 1 + 2 * 1,
        "tokens_dispatched": (4 + 2) + (2 + 1) + 1 + 1,
        "row_steps": 2 + 2 + 1 + 1, "tokens_emitted": 3 + 2}
    m = eng.metrics_summary()
    assert m["padding_share"] == pytest.approx(1 - 11 / 20)
    assert m["chunk_step_share"] == 0.5
    assert m["batch_occupancy"] == pytest.approx(6 / 8)
    assert m["tokens_per_step"] == 5 / 4


def test_router_sums_counters_before_deriving_shares():
    engines = [make_engine(max_batch=2, chunk=4),
               make_engine(max_batch=3, chunk=4)]
    router = Router(engines)
    for uid in range(4):
        router.submit(Request(uid=uid, prompt=list(range(1, 3 + 3 * uid)),
                              max_new_tokens=2 + uid))
    router.run_until_drained()
    c = [e.counters() for e in engines]
    assert all(x["steps"] for x in c)
    m = router.metrics_summary()
    tot = {k: c[0][k] + c[1][k] for k in c[0]}
    assert m["padding_share"] == pytest.approx(
        1 - tot["tokens_dispatched"] / tot["token_slots"])
    assert m["chunk_step_share"] == pytest.approx(
        tot["chunk_steps"] / tot["steps"])
    assert m["batch_occupancy"] == pytest.approx(
        tot["row_steps"] / (2 * c[0]["steps"] + 3 * c[1]["steps"]))
    assert m["tokens_per_step"] == pytest.approx(
        tot["tokens_emitted"] / tot["steps"])


def test_speculative_steps_count_their_drafts():
    eng = ServingEngine(get_model(CFG), init_params(CFG), max_batch=2,
                        max_seq=64, chunk=4, spec_k=2)
    # a repeating prompt gives the n-gram proposer drafts to verify
    eng.submit(Request(uid=0, prompt=[5, 6, 7] * 3, max_new_tokens=6))
    (req,) = eng.run_until_drained()
    c = eng.counters()
    assert c["tokens_emitted"] == len(req.generated) == 6
    assert c["row_steps"] == c["steps"] == c["chunk_steps"]
    # two (2, 4) steps take 8 prompt tokens; the ninth rides the first
    # (2, 3) verify step draft-free, and every later one dispatches its
    # last token and its drafts
    assert c["token_slots"] == 2 * 2 * 4 + (c["steps"] - 2) * 2 * 3
    assert c["tokens_dispatched"] == (
        8 + 1 + (c["steps"] - 3) + req.metrics.spec_proposed)


def compiled_step_text(eng) -> str:
    B = eng.B
    z = jnp.zeros(B, jnp.int32)
    args = (eng.params, jnp.zeros((B, eng.chunk), jnp.int32), eng.state,
            jnp.asarray(np.zeros_like(eng.scheduler.pages)), z,
            jnp.ones(B, jnp.int32), jnp.zeros(B, jnp.float32), z,
            jnp.ones(B, jnp.float32), z, z)
    return eng._step.lower(*args, do_sample=False).compile().as_text()


@pytest.mark.parametrize("cfg", LM_CFGS[:2], ids=["dense", "moe"])
def test_step_names_its_device_scopes(cfg):
    eng = make_engine(max_batch=2, chunk=4, cfg=cfg)
    text = compiled_step_text(eng)
    ffn = (["mlp"] if cfg.family == "dense" else
           ["moe/router", "moe/dispatch", "moe/experts", "moe/combine"])
    for scope in ["attention/qkv", "attention/kv_write", "attention/walk",
                  "attention/out", "lm_head", "sample", *ffn]:
        assert re.search(f'op_name="[^"]*/{scope}/', text), scope


def test_scopes_leave_parameter_paths_alone():
    assert sorted(init_params(LM_CFGS[1])) == [
        "layers/attn_k/kernel", "layers/attn_o/kernel",
        "layers/attn_q/kernel", "layers/attn_v/kernel",
        "layers/ln_attn/gamma", "layers/ln_mlp/gamma",
        "layers/moe_router/kernel", "layers/moe_wi_gate", "layers/moe_wi_up",
        "layers/moe_wo", "lm_head/kernel", "ln_final/gamma", "tok_emb/W"]
