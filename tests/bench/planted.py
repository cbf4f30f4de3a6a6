"""Faults planted in the timed path, for the tests that have to see a run
come out not correct. Each takes the built engines and shadows the jitted
step on the instance, as ``harness/record.py`` does."""


def alter_tokens(engines):
    """Every sampled token is moved to the next vocabulary id where the
    step produces it."""
    for e in engines:
        orig = e._step
        vocab = e.api.cfg.vocab_size

        def step(*args, _orig=orig, **kw):
            tok, state = _orig(*args, **kw)
            return (tok + 1) % vocab, state
        e._step = step


def keep_state(engines):
    """The step returns the state it was given, so no step's keys and
    values reach the cache."""
    for e in engines:
        orig = e._step

        def step(params, tokens, state, *rest, _orig=orig, **kw):
            tok, _ = _orig(params, tokens, state, *rest, **kw)
            return tok, state
        e._step = step
