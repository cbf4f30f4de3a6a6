"""The traffic generator and the client that sends it (no engine, no chip)."""

import time

import numpy as np

from harness import drive, spec, traffic
from harness.model import for_cell

SEED = 2**33 + 5          # seeds may exceed 32 bits


def mix(name):
    return spec.load("traffic", name)


def test_open_loop_same_seed_same_requests_and_clipped():
    m = mix("chat")
    a = traffic.open_loop(m, 49155, SEED, 3.0, 60.0)
    b = traffic.open_loop(m, 49155, SEED, 3.0, 60.0)
    assert [(r.prompt, r.max_new, r.due_s, r.temperature) for r in a] == \
        [(r.prompt, r.max_new, r.due_s, r.temperature) for r in b]
    assert len(a) == 180
    assert all(m["prompt"]["min"] <= len(r.prompt) <= m["prompt"]["max"]
               for r in a)
    assert all(m["output"]["min"] <= r.max_new <= m["output"]["max"]
               for r in a)
    assert all(0 < t < 49155 for r in a for t in r.prompt)
    # one in eight decodes greedily, the rest sample with the mix's settings
    assert sum(r.greedy for r in a) == round(180 / 8) or \
        abs(sum(r.greedy for r in a) - 180 / 8) <= 12
    assert all(r.temperature == 0.7 and r.top_p == 0.95
               for r in a if not r.greedy)


def test_every_seed_gets_the_same_sizes_and_gaps():
    m = mix("chat")
    a = traffic.open_loop(m, 49155, 1, 3.0, 60.0)
    b = traffic.open_loop(m, 49155, SEED, 3.0, 60.0)
    assert sorted(len(r.prompt) for r in a) == sorted(len(r.prompt) for r in b)
    assert sorted(r.max_new for r in a) == sorted(r.max_new for r in b)
    assert a[-1].due_s == b[-1].due_s     # the same gaps
    assert [r.prompt for r in a] != [r.prompt for r in b]
    # the arrivals span the window at the rate asked for
    assert abs(a[-1].due_s - 60.0) < 60.0 * 0.1
    med = np.median([len(r.prompt) for r in a])
    assert abs(med - m["prompt"]["median"]) < 0.15 * m["prompt"]["median"]


def test_every_seed_sends_the_same_work_in_the_same_order():
    m = mix("chat")
    a = traffic.open_loop(m, 49155, 7, 3.0, 60.0)
    b = traffic.open_loop(m, 49155, SEED, 3.0, 60.0)
    assert [(len(r.prompt), r.max_new, r.due_s, r.greedy) for r in a] == \
        [(len(r.prompt), r.max_new, r.due_s, r.greedy) for r in b]
    assert [r.prompt for r in a] != [r.prompt for r in b]


def test_closed_loop_blocks_hold_the_same_multiset():
    m = mix("longdoc")
    a = traffic.closed_loop(m, 131072, 3, 64)
    b = traffic.closed_loop(m, 131072, SEED, 64)
    assert [len(r.prompt) for r in a] == [len(r.prompt) for r in b]
    B = traffic.BLOCK
    for i in range(0, 64, B):
        assert sorted(len(r.prompt) for r in a[i:i + B]) == \
            sorted(len(r.prompt) for r in a[:B])
    assert all(r.greedy for r in a)
    assert all(1024 <= len(r.prompt) <= 7168 and 32 <= r.max_new <= 512
               for r in a)


def test_sessions_share_system_prompts_by_zipf():
    m = mix("sessions")
    s = traffic.sessions(m, 49155, SEED, 256)
    again = traffic.sessions(m, 49155, SEED, 256)
    assert [x.users for x in s] == [x.users for x in again]
    systems = {}
    for x in s:
        systems.setdefault(tuple(x.system), 0)
        systems[tuple(x.system)] += 1
        assert len(x.system) == 1024
        assert 2 <= len(x.users) <= 6 and len(x.users) == len(x.outputs)
    counts = sorted(systems.values(), reverse=True)
    assert len(counts) <= 32 and counts[0] > 4 * counts[-1]


class FakeWorker:
    def __init__(self):
        self.got = []

    def submit(self, req):
        self.got.append(req)


def client(name, **load):
    cell = spec.load_cell(name)
    cell["load"].update(load)
    m = for_cell(cell)
    w = FakeWorker()
    return drive.Client(cell, m, SEED, [w], None), w


def test_open_loop_client_records_schedule_and_lateness():
    c, w = client("granite-moe.chat", rate_per_s=40.0)
    c.plan(0.5)
    t0 = time.perf_counter()
    c.start(t0)
    c.poll(t0 + 0.5)
    assert len(w.got) == len(c.records) >= 15
    for rec, rs in zip(c.records, c.todo):
        assert rec.due == t0 + rs.due_s          # due as scheduled
        assert rec.sent >= rec.due                # lateness is recorded
        assert rec.sent - rec.due < 0.25


def test_session_turns_carry_system_prompt_and_history_as_prefix():
    c, w = client("granite-moe.router4-sessions", clients=1)
    c.plan(1.0)
    c.start(time.perf_counter())
    first = c.records[0]
    sess = first.session[0]
    assert first.req.prompt == sess.system + sess.users[0]
    # the served answer becomes history: the next turn's prompt extends
    # the last prompt and its answer
    first.req.generated = list(range(7, 7 + sess.outputs[0]))
    first.done_t = time.perf_counter()
    c._next_turn(first, time.perf_counter())
    nxt = c.records[1].req.prompt
    assert nxt == first.req.prompt + first.req.generated + sess.users[1]
    assert len(nxt) + sess.outputs[1] < 2048
