"""Engine step loop: share of the token slots the window's steps dispatched
(B x C each) that carried no token, 100 x (1 - tokens dispatched / token
slots), from the engines' ``counters()`` read when the window opens and
when it closes, summed over replicas. Moves ``output_tokens_per_s``.

``run.counters`` holds one ``(at_open, at_close)`` pair of counter dicts
per engine; a run without it has nothing to read."""


def read(run):
    pairs = getattr(run, "counters", None) or []
    slots = sum(b["token_slots"] - a["token_slots"] for a, b in pairs)
    if not slots:
        return None
    sent = sum(b["tokens_dispatched"] - a["tokens_dispatched"]
               for a, b in pairs)
    return 100.0 * (1.0 - sent / slots), "%"
