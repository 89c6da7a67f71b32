"""Decode-interval ledger: what each dispatch-to-dispatch interval of
the scheduler loop was spent behind.

A decoding request's pace is what the device ran between its tokens:
its own decode steps, and whatever admission work was queued in front
of them. The loop reads the clock once a decode dispatch; this ledger
books the interval that ends there (its seconds, the steps of the
dispatch it waited for, one interval) to exactly one class, over the
whole life of the process, so that a window's differences say what a
step costs behind each kind of work and how often:

- ``clean``: no admission work was dispatched in this loop iteration
  nor in either of the two before;
- ``padded``: a chunk of a ladder past every row's prompt (its program
  computes nothing: a dispatch and an iteration, no forward);
- ``chunk``: a chunk of a ladder that ran its forward;
- ``admit``: a single-shot admission or a session wake.

**The episode rule.** Under the one-tick pipeline the loop dispatches
tick m as soon as tick m-2 has been read back, so the interval that
ends at dispatch m is the device time of tick m-2 plus whatever else
was queued before it, and its steps are tick m-2's K. Admission work
dispatched in iteration j therefore lands in the interval ending at
dispatch j+2 (a chunk, which nothing waits for), or shortens the one
ending at j+1 (a single-shot admission's first-token read drains the
pipeline). So the interval in which work was noted and the two after
it are one episode, booked to the class that opened it. Where one
iteration did more than one kind, or an episode opens inside another,
the class is the dearest (``admit`` over ``chunk`` over ``padded``):
an interval goes to the dearest class noted in its own iteration or
the two before. Intervals of 250 ms or more are load valleys and are
booked nowhere; nor is the interval a speculative tick ends (the loop
drained the pipeline for it), the one after it, nor the one after that
(their steps are not a decode dispatch's).

A request's own share is a difference of :meth:`totals` between its
first token and its release (serve/scheduler.py, ``sched.decode.cut``).
Because an interval is booked two dispatches after its work was
queued, those differences are off by at most two intervals at each end
of the some hundreds a request decodes through.

The two older readings of the same interval are outputs of the same
call: ``stall_ms`` (the longest interval, in ms, that admission work
was noted in: ``decode_stall_ms``) and ``wall_hist`` (a reservoir of
every interval's ms a step: ``decode_wall_ms``).

One ledger belongs to one thread (the scheduler loop): no lock and no
clock read of its own. Other threads may read the totals; a torn read
is harmless for a counter on ``/metrics``.
"""

from __future__ import annotations

from typing import Optional

from ..utils.metrics import Histogram

CLEAN, PADDED, CHUNK, ADMIT = range(4)      # dearest last
CLASSES = ("clean", "padded", "chunk", "admit")
VALLEY_S = 0.25


class IntervalLedger:
    __slots__ = ("seconds", "steps", "intervals", "dispatches", "stall_ms",
                 "wall_hist", "_noted", "_recent", "_last", "_prev_k",
                 "_last_emit_t")

    def __init__(self) -> None:
        self.seconds = [0.0] * 4    # by class
        self.steps = [0] * 4
        self.intervals = [0] * 4
        # Admission dispatches by class, closed intervals only ([CLEAN]
        # stays 0): a request's meta, not /metrics (the scheduler's own
        # counters already export each kind).
        self.dispatches = [0] * 4
        self.stall_ms = 0.0
        self.wall_hist = Histogram("decode_wall_ms")
        self._noted = [0] * 4       # dispatches since the last token-emitting one
        self._recent = (CLEAN, CLEAN)   # classes noted in the two intervals before
        self._last: Optional[tuple] = None  # (time, K) of the last decode dispatch
        self._prev_k = 0            # K of the decode dispatch before _last (0: none)
        self._last_emit_t: Optional[float] = None

    def cut(self, cls: int) -> None:
        """Admission work of class ``cls`` was dispatched."""
        self._noted[cls] += 1

    def rest(self) -> None:
        """No row is decoding: the stall gauge must not bridge the gap
        to the next dispatch (it stalled nobody)."""
        self._last_emit_t = None

    def reset_stall(self) -> None:
        self.stall_ms = 0.0
        self._last_emit_t = None

    def note(self, now: float, K: int) -> None:
        """A token-emitting dispatch at ``now``: a decode tick of ``K``
        steps, or a speculative tick (``K`` = 0, whose wall is not a
        decode step's). Closes the interval that ends here."""
        noted = self._noted
        cls = (ADMIT if noted[ADMIT] else CHUNK if noted[CHUNK]
               else PADDED if noted[PADDED] else CLEAN)
        if cls:
            if self._last_emit_t is not None:
                gap = (now - self._last_emit_t) * 1e3
                if gap > self.stall_ms:
                    self.stall_ms = gap
            for c in (PADDED, CHUNK, ADMIT):
                self.dispatches[c] += noted[c]
                noted[c] = 0
        self._last_emit_t = now
        booked = max(cls, *self._recent)
        self._recent = (cls, self._recent[0])
        last = self._last
        if K and last is not None and now - last[0] < VALLEY_S:
            dt = now - last[0]
            # Per-STEP wall: the interval spans the previous tick's host
            # drain and whatever device time the pipeline could not
            # hide, over that tick's K steps.
            self.wall_hist.observe(dt * 1e3 / last[1])
            if self._prev_k:
                self.seconds[booked] += dt
                self.steps[booked] += self._prev_k
                self.intervals[booked] += 1
        self._prev_k = last[1] if last is not None else 0
        self._last = (now, K) if K else None

    def totals(self) -> tuple:
        """(seconds in cut episodes, steps booked, steps in cut
        episodes, chunk, padded and admit dispatches): what a request
        takes at its first token and again at its release."""
        s, d = self.steps, self.dispatches
        cut = s[PADDED] + s[CHUNK] + s[ADMIT]
        return (self.seconds[PADDED] + self.seconds[CHUNK]
                + self.seconds[ADMIT], s[CLEAN] + cut, cut,
                d[CHUNK], d[PADDED], d[ADMIT])
