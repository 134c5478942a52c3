"""The client side: drives ``ServeEngine.submit/pump/step`` in a closed
loop for a fixed window, and records when every request was sent and
when each of its tokens came back.

The engine's ``step`` blocks until the device returns the step's tokens,
and ``pump`` until the admitting prefill's first tokens are sampled, so
a host clock read in ``Request.on_token`` is the time the token was
ready.  Each engine call runs inside a ``jax.profiler.TraceAnnotation``
(``bench.pump``, ``bench.step``), and the window itself inside
``bench.window``, which the traced run uses to name what the host was
doing.

The loop turns ``preroll_steps`` times before the window opens, so that
the window measures it in its steady state rather than a cold engine
filling up.  ``on_open`` is called as the window opens (the harness
stamps the end of set-up and starts the profiler there).
"""
from __future__ import annotations

import dataclasses
import itertools
import time
from typing import Callable, List, Optional

import jax

from traffic import Job


@dataclasses.dataclass
class Rec:
    job: Job
    req: object
    due: float
    sent: float
    times: List[float] = dataclasses.field(default_factory=list)


@dataclasses.dataclass
class WindowResult:
    t0: float
    t_end: float
    recs: List[Rec]
    steps: int = 0
    pumps: int = 0
    pump_s: float = 0.0
    admitted: int = 0
    longest_turn_s: float = 0.0


def staggered(job: Job, client: int, clients: int) -> Job:
    """A client's first job of the pre-roll, cut to ``(client + 1/2) /
    clients`` of its output, so that the clients' requests end, and new
    ones are admitted, evenly from the first step on, as in a loop that
    has been running a while."""
    keep = max(1, round(job.max_new * (client + 0.5) / clients))
    return dataclasses.replace(job, max_new=keep)


class LoadGen:
    def __init__(self, eng, request_cls, clock: Callable[[], float]
                 = time.perf_counter):
        self.eng = eng
        self.Request = request_cls
        self.now = clock
        self.recs: List[Rec] = []
        self.res: Optional[WindowResult] = None

    # -- engine calls, annotated -----------------------------------------
    def _send(self, job: Job, due: float) -> Rec:
        rec = Rec(job, None, due, self.now())
        req = self.Request(rid=len(self.recs), prompt=job.prompt,
                           max_new_tokens=job.max_new, temperature=0.0,
                           on_token=lambda r, tok: rec.times.append(
                               self.now()))
        rec.req = req
        self.recs.append(rec)
        self.eng.submit(req)
        return rec

    def _turn(self, in_window: bool) -> None:
        t = self.now()
        with jax.profiler.TraceAnnotation("bench.pump"):
            n = self.eng.pump()
        t_pumped = self.now()
        with jax.profiler.TraceAnnotation("bench.step"):
            self.eng.step()
        if in_window:
            self.res.pump_s += t_pumped - t
            self.res.admitted += n
            self.res.pumps += 1
            self.res.steps += 1
            self.res.longest_turn_s = max(self.res.longest_turn_s,
                                          self.now() - t)

    def _open_window(self, on_open: Optional[Callable[[], None]]):
        if on_open is not None:
            on_open()
        t0 = self.now()
        self.res = WindowResult(t0, t0, self.recs)
        return t0

    # -- loops -----------------------------------------------------------
    def closed(self, jobs: List[Job], clients: int, seconds: float,
               preroll_steps: int = 0,
               on_open: Optional[Callable[[], None]] = None
               ) -> WindowResult:
        """``clients`` callers, each sending its next job the moment its
        last request completes; jobs are taken from ``jobs`` in order.
        The first ``clients`` jobs are cut by ``staggered`` and the loop
        turns ``preroll_steps`` times before the window opens."""
        order = itertools.cycle(jobs)
        t = self.now()
        live = [self._send(staggered(next(order), c, clients), t)
                for c in range(clients)]

        def refill() -> None:
            for c, rec in enumerate(live):
                if rec.req.outcome is not None:
                    live[c] = self._send(next(order), self.now())

        for _ in range(preroll_steps):
            self._turn(False)
            refill()
        t0 = self._open_window(on_open)
        with jax.profiler.TraceAnnotation("bench.window"):
            while self.now() - t0 < seconds:
                self._turn(True)
                refill()
        self.res.t_end = self.now()
        return self.res
