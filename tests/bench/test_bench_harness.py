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
from harness.model import for_cell, make_params
from harness.record import StepRecorder
from harness.smoke import smoke_cell
from planted import alter_tokens, keep_state

REPO = spec.REPO
SEED = 2**32 + 11


def smoke(name):
    cell = smoke_cell(spec.load_cell(name))
    # at smoke width the program and the reference agree to rounding
    cell["check"]["max_logit_gap"] = 0.05
    return cell


def first_finished(cell, seed, records, w1):
    """The first greedy requests the window finished, in the order they
    were sent, as many as the cell checks."""
    fin = [r for r in check.finished(records, w1)
           if len(r.req.generated) == r.spec.max_new]
    return sorted(fin, key=lambda r: r.req.uid)[:cell["check"]["sample"]]


def scripted(name, monkeypatch):
    """The smoke cell as a fixed script, whatever the CPU's load: one client
    sends the cell's requests one after another, so every step has one row
    and the same shape in every run, and the check reads the first two
    greedy requests it finished rather than a sample of however many
    finished in the window. (A sample can reach a request whose bfloat16
    near-tie reads 0.06 at smoke width, whatever the step shapes.)"""
    cell = smoke(name)
    cell["load"] = {"mode": "closed", "clients": 1, "requests_per_client": 400,
                    "preroll_s": 1.0}
    cell["check"]["sample"] = 2
    monkeypatch.setattr(check, "sample", first_finished)
    return cell


@pytest.mark.parametrize("name", ["granite-moe.chat", "mistral-nemo.longdoc"])
def test_sound_run_is_correct(name, monkeypatch):
    res = drive.run(scripted(name, monkeypatch), SEED, 3.0, False,
                    t_start=time.perf_counter())
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert set(res["metrics"]) == set(spec.load_cell(name)["end_to_end"])
    assert res["metrics"]["output_tokens_per_s"]["value"] > 0
    assert res["checks"]["max_logit_gap"]["value"] <= 0.05
    assert res["checks"]["compiles_in_window"]["value"] == 0


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
    m = for_cell(cell)
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


def test_control_run_reports_the_control_not_correct(monkeypatch):
    """A whole run with the control on: the program's run is correct and
    the control's own verdict, by the same comparison, is not."""
    res = drive.run(scripted("mistral-nemo.longdoc", monkeypatch), SEED, 3.0,
                    False, t_start=time.perf_counter(), control=True)
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
    m = for_cell(cell)
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
