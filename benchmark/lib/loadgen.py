"""Open- and closed-loop load from one thread, timed at the client.

One loop sends what is due, reads every open stream for new tokens, and
sleeps until the next arrival or the next poll, whichever is first.  A
token's time is when the client saw it, at most one poll after the server
put it on the stream.  In an open loop a request's clock starts when it
was DUE, not when it was sent, so a stall of the generator or the server
is charged to the requests that waited through it; how late each request
was sent is recorded beside it.

The loop is independent of the server: ``submit(prompt, max_new_tokens)``
returns any object with ``tokens`` (a snapshot list), ``done`` and
``error``.  ``clock`` and ``sleep`` can be replaced, which is how the
tests run it on a fake server without waiting.
"""

from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Callable, List, Optional

from benchmark.lib.traffic import ServePlan


@dataclass
class Record:
    """One request as the client saw it; times in seconds from the
    window's start."""
    index: int
    prompt_tokens: int
    asked_tokens: int
    due_s: float
    sent_s: Optional[float] = None
    token_s: List[float] = field(default_factory=list)
    tokens: List[int] = field(default_factory=list)
    done_s: Optional[float] = None
    error: Optional[str] = None

    @property
    def ok(self) -> bool:
        return self.done_s is not None and self.error is None


def run_load(submit: Callable, plan: ServePlan, seconds: float,
             drain_s: float, poll_s: float,
             clock: Callable[[], float] = time.perf_counter,
             sleep: Callable[[float], None] = time.sleep,
             on_poll: Optional[Callable[[], None]] = None) -> List[Record]:
    """Drive ``plan`` for ``seconds``, then wait up to ``drain_s`` for the
    requests in flight.  Returns a record for every request that was due
    (open loop) or sent (closed loop) inside the window.  ``on_poll`` is
    called once a turn of the loop, for a gauge the caller samples."""
    reqs = plan.requests
    records: List[Record] = []
    active: List[tuple] = []               # (record, stream, client)
    t0 = clock()

    def send(i: int, due: float, client: Optional[int], now: float) -> None:
        r = reqs[i]
        rec = Record(i, len(r.prompt), r.max_new_tokens, due)
        records.append(rec)
        rec.sent_s = now
        try:
            stream = submit(r.prompt, r.max_new_tokens)
        except Exception as e:      # refused or shed: counts as failed
            rec.error = f"{type(e).__name__}: {e}"
            rec.done_s = now
            if client is not None:
                idle.append(client)
            return
        active.append((rec, stream, client))

    nxt = 0                                  # open loop: next arrival
    if plan.clients is not None:
        cursor = [0] * len(plan.clients)
        idle = list(range(len(plan.clients)))
    else:
        cursor, idle = [], []

    while True:
        now = clock() - t0
        in_window = now < seconds
        if plan.due_s is not None:
            while nxt < len(reqs) and plan.due_s[nxt] <= now and in_window:
                send(nxt, float(plan.due_s[nxt]), None, now)
                nxt += 1
        elif in_window:
            waiting, idle[:] = list(idle), []
            for c in waiting:
                if cursor[c] < len(plan.clients[c]):
                    i = plan.clients[c][cursor[c]]
                    cursor[c] += 1
                    send(i, now, c, now)
        if on_poll is not None:
            on_poll()
        still = []
        for rec, stream, client in active:
            toks = stream.tokens
            if len(toks) > len(rec.tokens):
                rec.token_s.extend([now] * (len(toks) - len(rec.tokens)))
                rec.tokens = toks
            if stream.done:
                err = stream.error
                rec.error = None if err is None else \
                    f"{type(err).__name__}: {err}"
                rec.done_s = now
                if client is not None:
                    idle.append(client)
            else:
                still.append((rec, stream, client))
        active = still
        if not in_window and (not active or now >= seconds + drain_s):
            break
        wake = now + poll_s
        if plan.due_s is not None and nxt < len(reqs) and in_window:
            wake = min(wake, float(plan.due_s[nxt]))
        if idle and in_window:
            continue
        sleep(max(0.0, wake - (clock() - t0)))
    for rec, stream, _ in active:            # cut at the end of the drain
        rec.error = "unfinished at the end of the drain period"
        cancel = getattr(stream, "cancel", None)
        if cancel is not None:
            cancel()
    return records
