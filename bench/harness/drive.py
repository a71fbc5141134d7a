"""Drive the system under test with a traffic mix: warm-up, then the
measured window.

A closed loop: a client keeps ``outstanding`` requests in the engine. It
calls the engine's stepwise wave API one step at a time and replaces each
request that finishes before the next step. Client and server share one
thread, so each request is timed from the moment the client sends it to
the moment its classes are in the client's hands.
"""
from __future__ import annotations

import contextlib
import time
from dataclasses import dataclass, field
from typing import Callable, List

from harness import system

clock = time.monotonic          # the program's SystemClock reads the same


def no_span(name: str):
    return contextlib.nullcontext()


@dataclass
class Window:
    """What the client saw in one window. Answered requests are kept as
    columns of plain numbers (no object per request), so that a window of
    hundreds of thousands of requests adds nothing for the garbage
    collector to walk."""
    t0: float
    t1: float = 0.0             # end of the measured window
    # answered requests, one entry each, in the order they were answered
    index: List[int] = field(default_factory=list)     # pool index
    n: List[int] = field(default_factory=list)         # classes asked for
    sent: List[float] = field(default_factory=list)    # sent
    done: List[float] = field(default_factory=list)    # answer in hand
    count: List[int] = field(default_factory=list)     # classes served
    classes: List[int] = field(default_factory=list)   # all, concatenated
    inflight: list = field(default_factory=list)  # (request, index)
    last: List[tuple] = field(default_factory=list)  # (index, n) answered
                                                     # by the last step

    @property
    def seconds(self) -> float:
        return self.t1 - self.t0

    def answer(self, index: int, n: int, sent: float, done: float,
               out) -> None:
        self.index.append(index)
        self.n.append(n)
        self.sent.append(sent)
        self.done.append(done)
        self.count.append(len(out))
        self.classes.extend(out)

    def outs(self):
        """(pool index, classes) of every answered request."""
        k = 0
        for j, c in zip(self.index, self.count):
            yield j, self.classes[k:k + c]
            k += c


def _submit_closed(eng, pool, i0: int, k: int, live: dict, t: float) -> int:
    Request, reqs = system.request_type(), []
    for i in range(i0, i0 + k):
        j, prompt, stream, n = pool.item(i)
        r = Request(prompt=prompt, max_new_tokens=n, stream=stream)
        live[id(r)] = (r, j, n, t)
        reqs.append(r)
    eng.gru_wave_enqueue(reqs)
    return i0 + k


def warm(eng, pool, traffic: dict) -> None:
    """Run every shape the mix will use once, through the same entry
    points the window uses: a full cohort (the prefill bucket and the
    decode step), then each admit size ``k`` the mix can produce (one
    cache scatter program per ``k``)."""
    slots = int(traffic["slots"])
    ks = traffic["warm_admits"]
    ks = range(1, slots + 1) if ks == "all" else [int(k) for k in ks]
    Request = system.request_type()
    for k in [slots, *ks]:
        eng.gru_wave_enqueue([Request(prompt=pool.item(i)[1],
                                      max_new_tokens=1,
                                      stream=pool.item(i)[2])
                              for i in range(k)])
        while eng.gru_wave_active():
            eng.gru_wave_step()


def closed_loop(eng, pool, traffic: dict, seconds: float,
                start: Callable[[], None], stop: Callable[[], None],
                span=no_span) -> Window:
    """``span(name)``: a context that marks a host span in a trace."""
    live: dict = {}
    nxt = _submit_closed(eng, pool, 0, int(traffic["outstanding"]), live,
                         clock())
    start()
    w = Window(t0=clock())
    end = w.t0 + seconds
    while True:
        with span("bench.step"):
            finished = eng.gru_wave_step()
        b = clock()
        with span("bench.client"):
            w.last = []
            for r in finished:
                _, j, n, t = live.pop(id(r))
                w.answer(j, n, t, b, r.out)
                w.last.append((j, n))
            if b < end and finished:
                nxt = _submit_closed(eng, pool, nxt, len(finished), live, b)
        if b >= end:
            break
    w.t1 = b
    w.inflight = [(r, j) for r, j, _, _ in live.values()]
    stop()
    return w
