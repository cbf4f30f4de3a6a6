"""The one traffic generator: a mix file of parameters in, requests out.

Every seed gets the same sizes and arrival gaps in the same order: each
length or gap is a quantile of its distribution at a stratified level
``(i + 0.5) / n``, laid out in an order that the mix fixes, and the seed
picks only the token ids (and the weights). A window that is shorter than
a few request lifetimes then sees the same work under every seed, and the
spread between runs is the system's, not the draw's. (Orders drawn from
the seed, even shuffled only within runs of four, moved the tokens per
second of a 51 s window by 10-18% from seed to seed on the chip, where
two runs of one seed agree within about 1%.)

Mix kinds:

- ``requests``: independent requests. Prompt and output lengths are
  log-normal (``median``, ``sigma``) clipped to ``[min, max]``; a share of
  them (``greedy_share``) decodes greedily, the rest sample with the mix's
  ``temperature`` and ``top_p``. The cell's ``load`` sends them open loop
  (Poisson arrivals at ``rate_per_s``) or closed loop (``clients`` that each
  send the next request when the last one finished).
- ``sessions``: multi-turn sessions. Each opens with one of
  ``system_prompts`` shared system prompts, chosen by a Zipf law of
  exponent ``zipf_s``, then has ``turns`` user turns; a turn's prompt is the
  system prompt, every earlier turn and its served answer, and the new user
  turn, and the session ends before the prompt and answer would pass
  ``max_context`` tokens.
"""

from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist

import numpy as np

# how many draws one stratified block of lengths holds in a closed loop
BLOCK = 16


@dataclasses.dataclass
class RequestSpec:
    index: int
    prompt: list[int]
    max_new: int
    temperature: float = 0.0
    top_p: float = 1.0
    seed: int = 0
    due_s: float = 0.0          # open loop: send time after traffic start

    @property
    def greedy(self) -> bool:
        return self.temperature == 0.0


@dataclasses.dataclass
class SessionSpec:
    index: int
    system: list[int]
    users: list[list[int]]      # one user turn per entry
    outputs: list[int]          # tokens to serve for each turn


def rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng([int(seed) & (2**63 - 1), stream])


def levels(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def lognormal(d: dict, u: np.ndarray) -> np.ndarray:
    """Quantiles ``u`` of a log-normal with ``d``'s median and sigma,
    clipped to ``[min, max]`` and rounded to whole tokens."""
    z = np.array([NormalDist().inv_cdf(x) for x in u])
    v = np.exp(math.log(d["median"]) + d["sigma"] * z)
    return np.clip(np.rint(v), d["min"], d["max"]).astype(int)


def arrange(values: np.ndarray, stream: int) -> np.ndarray:
    """``values`` in an order fixed by ``stream``, the same for every
    seed."""
    return values[np.random.default_rng(stream).permutation(len(values))]


def stratified(d: dict, n: int, stream: int) -> np.ndarray:
    return arrange(lognormal(d, levels(n)), stream)


def blocked(d: dict, n: int, stream: int) -> np.ndarray:
    """``n`` lengths in blocks of :data:`BLOCK`, each block the same
    stratified multiset."""
    nb = -(-n // BLOCK)
    base = lognormal(d, levels(BLOCK))
    return np.concatenate([arrange(base, stream + b)
                           for b in range(nb)])[:n]


def _sampling(mix: dict, n: int) -> np.ndarray:
    """Which of ``n`` requests decode greedily: ``greedy_share`` of every
    block, so each block has the same count."""
    share = mix.get("greedy_share", 1.0)
    flags = np.zeros(n, bool)
    for b0 in range(0, n, BLOCK):
        k = min(BLOCK, n - b0)
        flags[b0:b0 + k] = arrange(np.arange(k) < round(share * k),
                                   30 + b0)
    return flags


def _requests(mix: dict, vocab: int, seed: int, prompt_lens, out_lens,
              greedy) -> list[RequestSpec]:
    tok = rng(seed, 3)
    out = []
    for i, (p, o, gr) in enumerate(zip(prompt_lens, out_lens, greedy)):
        out.append(RequestSpec(
            index=i, prompt=tok.integers(1, vocab, int(p)).tolist(),
            max_new=int(o),
            temperature=0.0 if gr else float(mix["temperature"]),
            top_p=1.0 if gr else float(mix.get("top_p", 1.0)),
            seed=int(tok.integers(0, 2**31 - 1))))
    return out


def open_loop(mix: dict, vocab: int, seed: int, rate: float,
              span_s: float) -> list[RequestSpec]:
    """Poisson arrivals at ``rate`` over ``span_s`` seconds: the gaps are
    stratified quantiles of the exponential, so every seed sends the same
    number of requests, with the same gaps, over the same span."""
    n = max(1, int(round(rate * span_s)))
    gaps = arrange(-np.log1p(-levels(n)) / rate, 10)
    reqs = _requests(mix, vocab, seed, stratified(mix["prompt"], n, 11),
                     stratified(mix["output"], n, 12), _sampling(mix, n))
    due = np.cumsum(gaps)
    for r, t in zip(reqs, due):
        r.due_s = float(t)
    return reqs


def closed_loop(mix: dict, vocab: int, seed: int, n: int
                ) -> list[RequestSpec]:
    """``n`` requests in the order the clients take them."""
    return _requests(mix, vocab, seed, blocked(mix["prompt"], n, 1000),
                     blocked(mix["output"], n, 2000), _sampling(mix, n))


def zipf_ranks(count: int, s: float, u: np.ndarray) -> np.ndarray:
    """Quantiles ``u`` of a Zipf law over ranks ``0 .. count - 1``."""
    p = 1.0 / np.arange(1, count + 1) ** s
    cdf = np.cumsum(p) / p.sum()
    return np.minimum(np.searchsorted(cdf, u), count - 1)


def sessions(mix: dict, vocab: int, seed: int, n: int) -> list[SessionSpec]:
    """``n`` sessions in the order the clients take them; each block of
    ``block`` sessions is the same stratified multiset of system prompts,
    turn counts and lengths."""
    tok = rng(seed, 5)
    k = mix["system_prompts"]
    systems = [tok.integers(1, vocab, mix["system_len"]).tolist()
               for _ in range(k)]
    block = mix["block"]
    u = levels(block)
    ranks = zipf_ranks(k, mix["zipf_s"], u)
    lo, hi = mix["turns"]
    turns = np.floor(lo + u * (hi - lo + 1)).astype(int)
    total = int(turns.sum())
    out = []
    for b0 in range(0, n, block):
        sys_ix = arrange(ranks, 40 + b0)
        n_turns = arrange(turns, 41 + b0)
        users = iter(stratified(mix["user"], total, 42 + b0))
        outs = iter(stratified(mix["output"], total, 43 + b0))
        for j in range(min(block, n - b0)):
            t = int(n_turns[j])
            out.append(SessionSpec(
                index=b0 + j, system=systems[int(sys_ix[j])],
                users=[tok.integers(1, vocab, int(next(users))).tolist()
                       for _ in range(t)],
                outputs=[int(next(outs)) for _ in range(t)]))
    return out
