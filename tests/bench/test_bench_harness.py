"""The harness end to end on the CPU at smoke size, past the look for a
chip: a sound run is correct, a run whose tokens are altered where they are
produced, or whose step leaves its state unchanged, is not; the float8
control fails the comparison the program passes; and ``run.py`` without a
TPU prints no result."""

import json
import os
import subprocess
import sys
import time

import pytest

from harness import check, drive, spec
from harness.model import Model, make_params
from harness.record import StepRecorder
from harness.smoke import smoke_cell

REPO = spec.REPO
SEED = 2**32 + 11


def smoke(name):
    cell = smoke_cell(spec.load_cell(name))
    # at smoke width the program and the reference agree to rounding
    cell["check"]["max_logit_gap"] = 0.05
    return cell


@pytest.mark.parametrize("name", ["granite-moe.chat", "mistral-nemo.longdoc"])
def test_sound_run_is_correct(name):
    res = drive.run(smoke(name), SEED, 2.0, False, t_start=time.perf_counter())
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert set(res["metrics"]) == set(spec.load_cell(name)["end_to_end"])
    assert res["metrics"]["output_tokens_per_s"]["value"] > 0
    assert res["checks"]["max_logit_gap"]["value"] <= 0.05
    assert res["checks"]["compiles_in_window"]["value"] == 0


def alter_tokens(engines):
    """A fault planted in the timed path: every sampled token is moved to
    the next vocabulary id where the step produces it."""
    for e in engines:
        orig = e._step
        vocab = e.api.cfg.vocab_size

        def step(*args, _orig=orig, **kw):
            tok, state = _orig(*args, **kw)
            return (tok + 1) % vocab, state
        e._step = step


def keep_state(engines):
    """A fault planted in the timed path: the step returns the state it was
    given, so no step's keys and values reach the cache."""
    for e in engines:
        orig = e._step

        def step(params, tokens, state, *rest, _orig=orig, **kw):
            tok, _ = _orig(params, tokens, state, *rest, **kw)
            return tok, state
        e._step = step


def test_altered_tokens_are_not_correct():
    res = drive.run(smoke("granite-moe.chat"), SEED, 2.0, False,
                    t_start=time.perf_counter(), hook=alter_tokens)
    assert not res["correct"]
    assert res["checks"]["max_logit_gap"]["value"] > 0.05


@pytest.mark.parametrize("fault", [alter_tokens, keep_state],
                         ids=["altered-tokens", "state-unchanged"])
def test_faults_in_the_benchmarked_cell_are_not_correct(fault):
    res = drive.run(smoke("mistral-nemo.longdoc"), SEED, 2.0, False,
                    t_start=time.perf_counter(), hook=fault)
    assert not res["correct"]
    assert res["checks"]["max_logit_gap"]["value"] > 0.05


def test_float8_control_reads_wider_than_the_program():
    """The program's greedy streams, checked against the reference and
    against the reference computed in float8, over the same positions: the
    same comparison passes the program and fails the control."""
    from repro.serving.engine import Request
    cell = smoke("granite-moe.chat")
    drive.setup_context()
    m = Model.from_config(cell["config"], cell["model"])
    engines, _ = drive.build_engines(cell, m, make_params(m, SEED))
    eng = engines[0]
    reqs = [Request(uid=i, prompt=list(range(3 + i, 40 + 7 * i)),
                    max_new_tokens=24) for i in range(4)]
    for r in reqs:
        eng.submit(r)
    eng.run_until_drained()
    r = check.readings(cell, m, SEED, [(q.prompt, q.generated) for q in reqs],
                       control=True)
    assert r["tokens"] == 96
    limit = cell["check"]["max_logit_gap"]
    assert check.judge(r["served"], 0, limit)
    assert not check.judge(r["control"], 0, limit)


def test_control_run_reports_the_control_not_correct():
    """A whole run with the control on: the program's run is correct and
    the control's own verdict, by the same comparison, is not."""
    res = drive.run(smoke("mistral-nemo.longdoc"), SEED, 2.0, False,
                    t_start=time.perf_counter(), control=True)
    assert res["correct"], res["checks"]
    assert res["control"]["correct"] is False
    assert res["control"]["max_logit_gap"]["value"] > \
        res["checks"]["max_logit_gap"]["limit"]


def test_step_record_reads_what_the_step_dispatches():
    """The traced run's step record: B and C from the dispatched tokens,
    every prompt token and every fed-back token counted once, one emitting
    row per served token, and the page table's shape."""
    from repro.serving.engine import Request
    cell = smoke("mistral-nemo.longdoc")
    drive.setup_context()
    m = Model.from_config(cell["config"], cell["model"])
    engines, _ = drive.build_engines(cell, m, make_params(m, SEED))
    eng = engines[0]
    rec = StepRecorder(engines)
    reqs = [Request(uid=i, prompt=list(range(3 + 5 * i, 20 + 9 * i)),
                    max_new_tokens=3 + i) for i in range(6)]
    for r in reqs:
        eng.submit(r)
    eng.run_until_drained()
    chunk = cell["engine"]["chunk"]
    assert rec.steps and {s.C for s in rec.steps} == {chunk, 1}
    for s in rec.steps:
        assert s.B == eng.B and s.pages == (eng.B, eng.max_blocks)
        assert all(0 < n <= s.C for _, n, _ in s.rows)
    rows = [r for s in rec.steps for r in s.rows]
    assert sum(n for _, n, _ in rows) == sum(
        len(r.prompt) + r.max_new_tokens - 1 for r in reqs)
    assert sum(1 for *_, e in rows if e) == sum(len(r.generated) for r in reqs)


def test_run_without_a_tpu_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "perfbench/run.py", "--workload", "granite-moe.chat",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=120)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "TPU" in p.stderr


def test_benchmark_json_names_files_the_harness_finds():
    b = json.loads((REPO / "BENCHMARK.json").read_text())
    for w in b["workloads"]:
        cell = spec.load_cell(w["name"])
        assert cell["config"] == w["config"]
        assert cell["traffic"] == w["traffic"]
        assert cell["chips"] == w["chips"]
        assert set(cell["end_to_end"]) == {
            e["name"] for e in b["end_to_end"]
            if w["name"] in e.get("workloads", [w["name"]])}
        assert set(cell["per_layer"]) == {
            p["name"] for p in b["per_layer"]
            if w["name"] in p.get("workloads", [w["name"]])}
        for name in cell["per_layer"]:
            assert hasattr(drive.load_reader(name), "read")
    for c in b["configs"]:
        assert (REPO / c["file"]).is_file()
