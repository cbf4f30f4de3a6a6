"""Whether the timed path served the right tokens.

Once the window has closed and the program's state is freed, a sample of
the greedy requests the window finished (drawn from the seed, with the
longest of them in it, and with one from every replica) is run through the
plain reference (:mod:`harness.reference`), each prompt with its served
tokens. For every served token the check reads how far its reference logit
lies below the reference's best at that position; the widest such gap over
the sample is compared with the cell's limit. A greedy token that the
reference also ranks first reads 0; rounding can put a near-tie first and
read a small gap; a wrong token reads a wide one.

The control (``control=True``) puts in the served tokens' place the tokens
that the reference computed in float8 puts first, at the same positions,
and judges them by the same comparison with the same limit: its verdict,
``control_ok``, has to come out false.
"""

from __future__ import annotations

import numpy as np

from harness import reference, traffic


def finished(records, w1: float) -> list:
    return [r for r in records
            if r.spec.greedy and r.error is None and r.done_t <= w1]


def sample(cell: dict, seed: int, records, w1: float) -> list:
    fin = [r for r in finished(records, w1)
           if len(r.req.generated) == r.spec.max_new]
    if not fin:
        return []
    g = traffic.rng(seed, 9)
    order = [fin[i] for i in g.permutation(len(fin))]
    picked = [max(fin, key=lambda r: len(r.req.prompt) + r.spec.max_new)]
    for rid in sorted({r.replica for r in fin}):
        if all(p.replica != rid for p in picked):
            picked.append(next(r for r in order if r.replica == rid))
    for r in order:
        if len(picked) >= cell["check"]["sample"]:
            break
        if all(r is not p for p in picked):
            picked.append(r)
    return picked


def _batch(cell: dict, seqs):
    """Right-padded tokens (N, max_seq) and, per row, the positions whose
    logits predict the served tokens, with the served ids and a mask."""
    n = max(cell["check"]["sample"], len(seqs))
    S = cell["engine"]["max_seq"]
    P = max(cell["mix"]["output"]["max"], max(len(s) for _, s in seqs))
    tokens = np.zeros((n, S), np.int32)
    want = np.zeros((n, P), np.int32)
    served = np.zeros((n, P), np.int32)
    mask = np.zeros((n, P), bool)
    for i, (prompt, out) in enumerate(seqs):
        seq = list(prompt) + list(out[:-1])
        tokens[i, :len(seq)] = seq
        k = len(out)
        want[i, :k] = len(prompt) - 1 + np.arange(k)
        served[i, :k] = out
        mask[i, :k] = True
    return tokens, want, served, mask


def readings(cell: dict, m, seed: int, seqs, *, control: bool = False
             ) -> dict[str, float]:
    """Widest logit gap of the served tokens (``served``), and with
    ``control`` of the tokens the float8 reference puts first
    (``control``), over the sequences ``seqs`` = [(prompt, served)]."""
    import jax.numpy as jnp
    tokens, want, served, mask = _batch(cell, seqs)
    ref = reference.logits(m, seed, tokens, want)
    best = jnp.max(ref, axis=-1)

    def widest(ids):
        got = jnp.take_along_axis(ref, jnp.asarray(ids)[..., None], -1)[..., 0]
        gap = np.asarray(best - got)
        return float(gap[mask].max())

    out = {"served": widest(served), "tokens": int(mask.sum())}
    if control:
        low = reference.logits(m, seed, tokens, want, lowp=True)
        out["control"] = widest(np.asarray(jnp.argmax(low, axis=-1)))
    return out


def judge(gap: float, short: int, limit: float) -> bool:
    """The comparison that decides ``correct`` for a set of tokens."""
    return short == 0 and gap <= limit


def check(cell: dict, m, seed: int, records, w1: float, *,
          control: bool = False) -> dict:
    """The verdict: ``ok`` and each number compared beside its limit; with
    ``control``, also the float8 control's own verdict (``control_ok``)."""
    picked = sample(cell, seed, records, w1)
    short = sum(1 for r in finished(records, w1)
                if len(r.req.generated) != r.spec.max_new)
    limit = cell["check"]["max_logit_gap"]
    checks = {"short_answers": {"value": short, "limit": 0},
              "requests_checked": {"value": len(picked), "limit": 1}}
    if not picked:
        checks["max_logit_gap"] = {"value": None, "limit": limit}
        return {"ok": False, "checks": checks}
    r = readings(cell, m, seed,
                 [(p.req.prompt, p.req.generated) for p in picked],
                 control=control)
    checks["tokens_checked"] = {"value": r["tokens"], "limit": 1}
    checks["max_logit_gap"] = {"value": r["served"], "limit": limit}
    out = {"ok": judge(r["served"], short, limit), "checks": checks}
    if control:
        out["control_ok"] = judge(r["control"], short, limit)
        out["control_gap"] = {"value": r["control"], "limit": limit}
    return out
