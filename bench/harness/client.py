"""The client: an open-loop load generator in front of ``ServeEngine``.

It takes each request when it is due, hands it to ``submit_request``,
drives ``generate``, and stamps every token as it reaches the client.
Latency is taken from the due time, so a stall that delays later
submissions counts against them.

``max_wave`` is the cell's admission gate: the client keeps at most that
many requests in the engine's queue (None: all that are due) and holds
the rest itself, so with 1 every admission wave prefills one prompt.
The engine compiles one prefill program per (wave size, prompt bucket),
and wave sizes follow timing, so only a gate keeps the programs a window
uses to a set that set-up can warm: nothing may compile inside the
window. The wait in the gate counts in ``queue_wait_p95_ms`` and in
the time to the first token.
"""
from __future__ import annotations

import collections
import contextlib
import dataclasses
import time
from typing import Callable, Optional

from harness.traffic import Req


@dataclasses.dataclass
class Record:
    req: Req
    due: float                       # perf_counter seconds
    released: Optional[float] = None  # when the client saw it due
    times: list = dataclasses.field(default_factory=list)
    ticks: list = dataclasses.field(default_factory=list)
    tokens: list = dataclasses.field(default_factory=list)
    finish: Optional[str] = None
    engine_req: object = None


class Client:
    def __init__(self, eng, reqs: list[Req], *, t0: float,
                 max_wave: Optional[int], Request):
        self.eng = eng
        self.recs = [Record(r, t0 + r.due) for r in reqs]
        self.max_wave = max_wave
        self._Request = Request
        self._next = 0
        self._pending: collections.deque = collections.deque()
        self._gen = None
        self.outstanding = 0
        self.span = no_span

    def _release(self, now: float) -> None:
        while (self._next < len(self.recs)
               and self.recs[self._next].due <= now):
            rec = self.recs[self._next]
            rec.released = now
            self._pending.append(rec)
            self._next += 1

    def _submit(self) -> None:
        eng = self.eng
        while self._pending and (self.max_wave is None
                                 or len(eng.scheduler) < self.max_wave):
            rec = self._pending.popleft()
            r = rec.req
            rec.engine_req = self._Request(rid=r.rid, prompt=r.prompt,
                                           max_new=r.max_new)
            eng.submit_request(rec.engine_req)
            self.outstanding += 1

    def run(self, until: Callable[[float], bool]) -> None:
        """Serve until ``until(now)`` holds or the schedule is done."""
        clock = time.perf_counter
        by_rid = {rec.req.rid: rec for rec in self.recs}
        while True:
            now = clock()
            if until(now):
                return
            with self.span("submit"):
                self._release(now)
                self._submit()
            if not self.outstanding and not self._pending:
                if self._next >= len(self.recs):
                    return
                with self.span("wait_due"):
                    time.sleep(max(0.0, min(self.recs[self._next].due - now,
                                            0.05)))
                continue
            if self._gen is None:
                self._gen = self.eng.generate()
            try:
                with self.span("engine_tick"):
                    ev = next(self._gen)
            except StopIteration:
                self._gen = None
                continue
            t = clock()
            with self.span("record"):
                rec = by_rid[ev.rid]
                if ev.token is not None:
                    rec.times.append(t)
                    rec.tokens.append(int(ev.token))
                    rec.ticks.append(self.eng.decode_steps)
                if ev.finished:
                    rec.finish = ev.finish_reason
                    self.outstanding -= 1

    def stop(self) -> None:
        """Cancel everything still queued or live and drain the events."""
        self._pending.clear()
        self._next = len(self.recs)
        for rec in self.recs:
            if rec.engine_req is not None and rec.finish is None:
                self.eng.cancel(rec.req.rid)
        self.run(lambda now: False)
        self.close()

    def close(self) -> None:
        if self._gen is not None:
            self._gen.close()
            self._gen = None


def no_span(name: str):
    """Stand-in for ``jax.profiler.TraceAnnotation`` outside the traced
    slice."""
    return contextlib.nullcontext()
