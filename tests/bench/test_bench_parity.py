"""Scripted parity of the served path with each architecture's reference,
on the CPU at smoke size.

For every configuration, the program's ``ServingEngine`` serves a fixed
script of greedy requests: arrivals at fixed step numbers (no clock), so
that the steps mix chunk-wide prefill rows with decode rows, then decode
alone, the same way in every run. The logits each emitted token was
chosen from are read where the step samples (``_sample_or_greedy``,
shadowed on the instance), and the whole sequences then run through the
plain reference of the configuration's architecture. Two numbers:

- ``token_gap``: the widest gap between the reference's best logit and its
  logit of a served token, as ``check.readings`` reads it in a run. It
  catches a wrong token.
- ``logit_err``: the median over served tokens of the RMS of (served
  logits - reference logits) over the RMS of the reference's. It catches a
  token that is right for the wrong reason, such as a step whose keys and
  values never reach the cache, where a random model's best token often
  survives. The median, because a mixture of experts' top-k choice is
  discontinuous: where two experts' router scores nearly tie, bfloat16
  rounding picks the other one, and that token's logits move far though
  nothing is wrong.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from harness import check, drive, reference, spec
from harness.model import make_params, parse
from harness.smoke import smoke_cell
from planted import alter_tokens, keep_state

SEED = 2**33 + 21
CONFIGS = sorted(p.stem for p in (spec.BENCH / "configs").glob("*.json"))

# (step at which it arrives, prompt length, tokens to serve); four slots, so
# the last two wait for a slot to free
SCRIPT = [(0, 37, 12), (0, 20, 16), (2, 61, 10), (5, 9, 20), (9, 44, 8),
          (12, 90, 6)]

# The program keeps weights, activations and the KV cache in bfloat16
# (unit roundoff 2^-8) and runs RMSNorm at a fixed eps of 1e-6; the
# reference runs float32 at HIGHEST and the file's eps. At smoke width
# (d 128, two layers, logits of std about 4) a served logit is off by about
# 0.03 (logit_err 0.006-0.008), so where the two best tokens nearly tie the
# served one can read a gap of a few hundredths: sound readings were
# token_gap 0.0-0.061 over three seeds and logit_err 0.0059-0.0082 over five
# (a router near-tie put single granite tokens at 0.47 and 0.64). The faults read
# token_gap 20-35 (a token moved to the next id) and logit_err 0.41-1.11
# with token_gap 2.9-12 (a step that keeps its state). Limits: token_gap
# 0.25, 4x the widest sound reading and 10x below the faults'; logit_err
# 0.05, 6x the sound median and 8x below the faults'.
TOKEN_GAP = 0.25
LOGIT_ERR = 0.05


def scripted_cell(name: str) -> dict:
    return smoke_cell({
        "config": name, "model": spec.load("configs", name),
        "engine": {}, "mix": {"kind": "requests"},
        "load": {"mode": "closed", "clients": 1},
        "check": {"sample": len(SCRIPT), "max_logit_gap": TOKEN_GAP}})


def serve_script(name: str, seed: int, fault=None):
    """Serve :data:`SCRIPT`; returns the cell, the model, and per request
    its prompt, served tokens and the served logits of each token."""
    from repro.serving.engine import Request
    cell = scripted_cell(name)
    drive.setup_context()
    m = parse(cell["config"], cell["model"])
    engines, _ = drive.build_engines(cell, m, make_params(m, seed))
    eng = engines[0]
    seen = []                   # per step: the (B, V) logits sampled from

    def sampler(logits, *args, _orig=eng._sample_or_greedy):
        jax.debug.callback(lambda a: seen.append(np.asarray(a)),
                           logits[:, -1, :].astype(jnp.float32))
        return _orig(logits, *args)
    eng._sample_or_greedy = sampler
    if fault is not None:
        fault(engines)
    rows = []                   # per step: [(slot, request)] that emit
    inner = eng._step

    def dispatch(*args, **kw):
        sched = eng.scheduler
        rows.append([(s, r) for s, r in enumerate(sched.active)
                     if r is not None and not sched.pending_prompt[s]])
        return inner(*args, **kw)
    eng._step = dispatch

    g = np.random.default_rng(seed)
    todo = [(at, Request(uid=i, prompt=g.integers(1, m.vocab, n).tolist(),
                         max_new_tokens=k))
            for i, (at, n, k) in enumerate(SCRIPT)]
    reqs = [r for _, r in todo]
    step = 0
    while todo or eng.has_work():
        while todo and todo[0][0] <= step:
            eng.submit(todo.pop(0)[1])
        eng.step()
        jax.effects_barrier()
        step += 1
    assert len(seen) == len(rows) == step
    served = {r.uid: [] for r in reqs}
    for logits, emit in zip(seen, rows):
        for s, r in emit:
            served[r.uid].append(logits[s])
    return cell, m, [(r.prompt, r.generated, np.stack(served[r.uid]))
                     for r in reqs]


def readings(cell, m, seed, served) -> dict[str, float]:
    seqs = [(p, out) for p, out, _ in served]
    tokens, want, ids, mask = check._batch(cell, seqs)
    ref = np.asarray(reference.logits(m, seed, tokens, want))
    got = np.take_along_axis(ref, ids[..., None], -1)[..., 0]
    gap = (ref.max(-1) - got)[mask]
    err = []
    for i, (_, out, logits) in enumerate(served):
        assert logits.shape == (len(out), m.vocab)
        r = ref[i, :len(out)]
        err.append(np.sqrt(((logits - r) ** 2).mean(-1) / (r ** 2).mean(-1)))
    return {"token_gap": float(gap.max()),
            "logit_err": float(np.median(np.concatenate(err))),
            "tokens": int(mask.sum())}


def parity(r) -> bool:
    return r["token_gap"] <= TOKEN_GAP and r["logit_err"] <= LOGIT_ERR


@pytest.mark.parametrize("name", CONFIGS)
def test_served_logits_match_the_reference(name):
    cell, m, served = serve_script(name, SEED)
    assert [len(out) for _, out, _ in served] == [k for *_, k in SCRIPT]
    r = readings(cell, m, SEED, served)
    assert r["tokens"] == sum(k for *_, k in SCRIPT)
    assert parity(r), r


@pytest.mark.parametrize("fault", [alter_tokens, keep_state],
                         ids=["altered-tokens", "state-unchanged"])
@pytest.mark.parametrize("name", CONFIGS)
def test_planted_fault_fails_parity(name, fault):
    cell, m, served = serve_script(name, SEED, fault)
    r = readings(cell, m, SEED, served)
    assert not parity(r), r
