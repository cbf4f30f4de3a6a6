"""The readers of the engine's own counters and spans, on hand-made data."""

import types

import pytest

from harness import drive


def read(name, **run):
    return drive.load_reader(name).read(types.SimpleNamespace(**run))


def counts(slots, sent):
    return {"steps": 0, "chunk_steps": 0, "token_slots": slots,
            "tokens_dispatched": sent, "row_steps": 0, "tokens_emitted": 0}


def test_padding_share_sums_replicas_over_the_window():
    pairs = [(counts(100, 40), counts(100 + 2048, 40 + 300)),
             (counts(0, 0), counts(2048, 200))]
    v, unit = read("engine.padding_share", counters=pairs)
    assert unit == "%"
    assert v == pytest.approx(100 * (1 - 500 / 4096))


def step(rid, i, t0, t1, dispatch_end, readback_end):
    """A ``serve.step`` span (times in us) and its phase spans."""
    us = 1000
    st = {"replica": rid}
    return [["serve.step", t0 * us, (t1 - t0) * us, dict(st, step=i)],
            ["serve.admit", (t0 + 1) * us, 1 * us, st],
            ["serve.stage", (t0 + 2) * us, (dispatch_end - t0 - 5) * us, st],
            ["serve.dispatch", (dispatch_end - 2) * us, 2 * us, st],
            ["serve.readback", dispatch_end * us,
             (readback_end - dispatch_end) * us, st],
            ["serve.bookkeep", readback_end * us, 3 * us, st]]


def test_host_turn_pairs_consecutive_steps_of_each_replica():
    spans = (step(0, 5, 100, 300, 140, 280)
             + step(0, 6, 310, 600, 350, 560)
             + step(0, 7, 610, 900, 640, 880)
             # replica 1 has no step 6 in the trace: steps 5 and 7 are no
             # pair
             + step(1, 5, 105, 400, 200, 390)
             + step(1, 7, 420, 700, 450, 690)
             + [["serve.inbox", 302_000, 5_000, {"replica": 0}],
                ["bench.step", 99_000, 202_000, {"step": 0, "replica": 0}]])
    spans.sort(key=lambda s: s[1])
    v, unit = read("engine.host_turn_ms", trace={"spans": spans})
    assert unit == "ms"
    # 5 -> 6: 350 - 280 us; 6 -> 7: 640 - 560 us
    assert v == pytest.approx((70 + 80) / 2 / 1000)


def test_readers_find_nothing_where_the_program_writes_nothing():
    bench_only = [["bench.step", 0, 10, {"step": 0, "replica": 0}]]
    assert read("engine.host_turn_ms", trace={"spans": bench_only}) is None
    assert read("engine.host_turn_ms", trace=None) is None
    assert read("engine.padding_share") is None
