"""The trace reduction, on hand-made events and on a small recorded trace."""

import gzip
import json
import types

import pytest

from harness import drive, layers, spec, trace
from harness.model import parse
from harness.smoke import smoke_config
from harness.spec import BENCH

# two overlapping ops, a gap, one op; a kernel among them
EVENTS = [[100, 50, "fusion.1"], [120, 60, "_paged_attn_kernel"],
          [300, 100, "fusion.2"]]
SPANS = [["bench.step", 90, 200, {"step": 0, "replica": 0}],
         ["bench.dispatch", 95, 10, {"replica": 0}],
         ["bench.step", 295, 110, {"step": 1, "replica": 0}]]


def test_busy_union_and_gaps_by_hand():
    assert trace.union(EVENTS) == [(100, 180), (300, 400)]
    assert trace.busy_ns(EVENTS, 90, 405) == 80 + 100
    assert trace.busy_ns(EVENTS, 150, 350) == 30 + 50      # clipped
    assert trace.gaps(EVENTS, 90, 405) == [(90, 100), (180, 300), (400, 405)]


WALK = ('%closed_call.9 = bf16[2,4,8,64]{3,2,1,0} custom-call(s32[2,5]{1,0} '
        '%p, s32[2]{0} %n, bf16[2,8,4,64]{3,2,1,0} %q), '
        'custom_call_target="tpu_custom_call"')
WRITE = ('%closed_call.7 = bf16[9,16,1,64]{3,2,1,0} custom-call(s32[2,5]{1,0} '
         '%p, bf16[9,16,1,64]{3,2,1,0} %k), custom_call_target="tpu_custom_call", '
         'output_to_operand_aliasing={{}: (1, {})}')
STEP = types.SimpleNamespace(step=0, pages=(2, 5))


def model(name, smoke=False):
    c = spec.load("configs", name)
    return parse(name, smoke_config(c) if smoke else c)


# the mistral architecture at smoke size: two layers, one walk per layer
TWO_LAYERS = model("mistral-nemo-12b-pp4", smoke=True)


def walk_ns():
    return drive.load_reader("paged_attention_roofline").walk_ns


def test_kernel_time_by_name():
    """The events of a step's span, and the walk's time among them: one
    unaliased Pallas call per layer that takes the page table."""
    assert trace.in_span(EVENTS, 100, 300) == EVENTS[:2]
    assert trace.in_span(EVENTS, 110, 1000) == EVENTS[1:]   # starts before
    evs = [[0, 5, WRITE], [5, 7, WALK], [12, 5, WRITE], [17, 7, WALK],
           [30, 3, "%fusion.1 = f32[2] fusion()"]]
    assert walk_ns()(evs, STEP, TWO_LAYERS) == 14
    # kernels other than Pallas: nothing to read
    assert walk_ns()([[0, 3, "%fusion.1 = f32[2] fusion()"]], STEP,
                     TWO_LAYERS) == 0


@pytest.mark.parametrize("evs", [
    [[5, 7, WALK]],                                   # one walk, two layers
    [[5, 7, WALK], [17, 7, WALK], [29, 7, WALK]],     # three, two layers
    [[5, 7, WALK], [17, 7, WALK.replace("closed_call.9", "closed_call.8")]],
    [[5, 7, WALK], [17, 7, WALK], [30, 2, WALK.replace("s32[2,5]", "s32[4]")]],
    [[0, 5, WRITE], [5, 7, WALK + ", output_to_operand_aliasing={{}: (2, {})}"]],
], ids=["count", "one-too-many", "two-programs", "stray-kernel", "walk-aliases"])
def test_walk_reader_refuses_what_it_does_not_know(evs):
    assert TWO_LAYERS.walk_calls() == (2, 1)
    with pytest.raises(ValueError, match="paged walk"):
        walk_ns()(evs, STEP, TWO_LAYERS)


def test_idle_gaps_by_host_span():
    got = dict(trace.idle_by_span(EVENTS, SPANS, 90, 405))
    # 90-100: inside the dispatch span (innermost); 180-300: midpoint 240
    # inside step 0; 400-405: inside step 1
    assert got == {"bench.dispatch": 10 / 1e9, "bench.step": 125 / 1e9}
    assert trace.top_ops(EVENTS, 0, 1000, n=2) == [
        ["fusion.2", 100 / 1e9], ["_paged_attn_kernel", 60 / 1e9]]


class Run:
    """The parts of a run the trace readers look at."""

    def __init__(self, tr, steps=(), peaks=None, model=None):
        self.trace, self.steps, self.peaks, self.model = tr, list(steps), \
            peaks, model
        self.devices = sorted(tr["device"])[:1]


def test_device_busy_and_idle_share_by_hand():
    run = Run({"device": {"0": EVENTS}, "spans": SPANS})
    busy, window = layers.device_busy(run)
    assert window == pytest.approx(315 / 1e9)      # first step to last step
    assert busy == pytest.approx(180 / 1e9)
    assert layers.idle_pct(run) == pytest.approx(100 * (1 - 180 / 315))


RECORDED = BENCH / "testdata" / "trace_small.json.gz"


def test_recorded_trace():
    """Two steps of the granite-moe chat cell traced on a TPU v5e (see the
    file's ``about``): the paged walk is found among the step's Pallas
    calls, and the reduction reads what it read when it was recorded."""
    with gzip.open(RECORDED, "rt") as f:
        rec = json.load(f)
    tr = rec["trace"]
    run = Run(tr)
    busy, window = layers.device_busy(run)
    want = rec["expect"]
    assert busy == pytest.approx(want["busy_s"])
    assert window == pytest.approx(want["window_s"])
    evs = tr["device"]["0"]
    w = layers.window_ns(run)
    # one walk per layer in each of the two steps: 24 layers, a (32, 132)
    # page table
    granite = model("granite-moe-1b-a400m")
    got = [walk_ns()(trace.in_span(evs, sp["start"], sp["end"]),
                     types.SimpleNamespace(step=sp["step"], pages=(32, 132)),
                     granite)
           for sp in trace.step_spans(tr["spans"])]
    assert len(got) == 2 and all(got)
    assert sum(got) / 1e9 == pytest.approx(want["paged_attn_s"])
    assert dict(trace.idle_by_span(evs, tr["spans"], *w)) == \
        pytest.approx(want["idle_by_span"])
    assert 0 < busy < window
