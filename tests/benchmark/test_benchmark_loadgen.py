"""The load loop on a fake server and a fake clock: an open loop times
from when a request was due, a closed loop sends on completion, a request
that is refused or never finishes counts as failed."""

import numpy as np

from benchmark.lib.loadgen import run_load
from benchmark.lib.traffic import Request, ServePlan


class Clock:
    def __init__(self):
        self.t = 100.0

    def now(self):
        return self.t

    def sleep(self, dt):
        self.t += max(dt, 1e-4)


class Stream:
    """Tokens appear ``first`` seconds after the submit, then one every
    ``gap`` seconds."""

    def __init__(self, clock, n, first, gap, never=False):
        self.clock, self.t0, self.n = clock, clock.now(), n
        self.first, self.gap, self.never = first, gap, never
        self.error = None

    @property
    def tokens(self):
        if self.never:
            return []
        dt = self.clock.now() - self.t0 - self.first
        return list(range(max(0, min(self.n, int(dt // self.gap) + 1)))) \
            if dt >= 0 else []

    @property
    def done(self):
        return len(self.tokens) == self.n


def _reqs(n, tokens=4):
    return [Request([1, 2, 3], tokens) for _ in range(n)]


def test_open_loop_times_from_due_and_reports_lateness():
    clock = Clock()
    stall = {"first": True}

    def submit(prompt, n):
        if stall.pop("first", False):
            clock.t += 0.5          # the first submit blocks the generator
        return Stream(clock, n, first=0.1, gap=0.05)

    plan = ServePlan(_reqs(3), np.array([0.0, 0.2, 0.4]), None)
    recs = run_load(submit, plan, seconds=2.0, drain_s=1.0, poll_s=0.01,
                    clock=clock.now, sleep=clock.sleep)
    assert [r.due_s for r in recs] == [0.0, 0.2, 0.4]
    assert recs[0].sent_s < 0.01
    # requests 1 and 2 were due during the stall and went out after it
    assert recs[1].sent_s >= 0.5 and recs[2].sent_s >= 0.5
    late = [r.sent_s - r.due_s for r in recs]
    assert late[1] > 0.29 and late[2] > 0.09
    # time to first token runs from DUE: 0.3 s of waiting + 0.1 s
    ttft = [r.token_s[0] - r.due_s for r in recs]
    assert 0.39 < ttft[1] < 0.45
    assert all(r.ok and len(r.tokens) == 4 for r in recs)
    gaps = np.diff(recs[2].token_s)
    assert np.allclose(gaps, 0.05, atol=0.011)


def test_open_loop_sends_nothing_after_the_window():
    clock = Clock()
    plan = ServePlan(_reqs(3), np.array([0.0, 0.5, 1.5]), None)
    recs = run_load(lambda p, n: Stream(clock, n, 0.01, 0.01), plan,
                    seconds=1.0, drain_s=1.0, poll_s=0.01,
                    clock=clock.now, sleep=clock.sleep)
    assert len(recs) == 2


def test_closed_loop_sends_the_next_when_the_last_ends():
    clock = Clock()
    plan = ServePlan(_reqs(6, tokens=2), None, [[0, 2, 4], [1, 3, 5]])
    recs = run_load(lambda p, n: Stream(clock, n, 0.1, 0.1), plan,
                    seconds=5.0, drain_s=1.0, poll_s=0.01,
                    clock=clock.now, sleep=clock.sleep)
    assert len(recs) == 6 and all(r.ok for r in recs)
    by_client = sorted((r for r in recs if r.index % 2 == 0),
                       key=lambda r: r.index)
    for before, after in zip(by_client, by_client[1:]):
        assert 0 <= after.sent_s - before.done_s < 0.02
    assert all(r.sent_s == r.due_s for r in recs)


def test_refused_and_unfinished_requests_fail():
    clock = Clock()
    calls = []

    def submit(prompt, n):
        calls.append(n)
        if len(calls) == 1:
            raise RuntimeError("queue full")
        return Stream(clock, n, 0.1, 0.1, never=len(calls) == 2)

    plan = ServePlan(_reqs(3), np.array([0.0, 0.1, 0.2]), None)
    recs = run_load(submit, plan, seconds=1.0, drain_s=0.5, poll_s=0.01,
                    clock=clock.now, sleep=clock.sleep)
    assert [r.ok for r in recs] == [False, False, True]
    assert "queue full" in recs[0].error
    assert "unfinished" in recs[1].error and recs[1].done_s is None
    assert clock.now() - 100.0 < 1.6          # the drain is bounded


def test_the_gauge_is_sampled_every_turn_of_the_loop():
    """``on_poll`` is how the serve driver reads the KV pool's fill: once
    a turn, so at least once a poll interval, through window and drain."""
    clock = Clock()
    seen = []
    plan = ServePlan(_reqs(2), np.array([0.0, 0.5]), None)
    run_load(lambda p, n: Stream(clock, n, first=0.1, gap=0.05), plan,
             seconds=1.0, drain_s=1.0, poll_s=0.01, clock=clock.now,
             sleep=clock.sleep, on_poll=lambda: seen.append(clock.now()))
    assert len(seen) >= 90 and max(np.diff(seen)) <= 0.011
