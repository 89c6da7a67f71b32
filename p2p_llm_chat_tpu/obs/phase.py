"""Loop-phase timer: where the scheduler thread's wall goes.

The third observability plane, beside the per-request spans
(obs/trace.py) and the event ring (obs/flight.py): the scheduler loop
marks the phase it is in, ``with phases("readback"):``, and each mark
does three things and nothing else —

- enters a ``jax.profiler.TraceAnnotation("sched.<name>")``, which puts
  the host's phases on the device trace's own clock (an idle gap of the
  device can then be given to the phase the host was in); while no
  profiler session is open the annotation costs a flag test;
- adds the phase's **self time** to a plain float: phases nest, and the
  time spent in an inner phase is subtracted from the outer one, so the
  phases of one loop iteration never add up to more than its wall;
- remembers the phase's name, so a reader on another thread (the stall
  gauge) and the watchdog's ``stall_enter`` event can say where a long
  iteration spent its time.

One :class:`LoopPhases` belongs to one thread (the scheduler loop): no
lock, two ``time.monotonic()`` reads and a few float adds per mark.
Other threads may read the floats; a torn read is harmless for a gauge.
The same file keeps the two boot-time readings that share its purpose:
seconds of compilation heard through ``jax.monitoring``, and the
process's age as the OS records it. docs/observability.md has the phase
table and how to read a trace with it.
"""

from __future__ import annotations

import os
import time
from typing import Optional

import jax

PHASES = ("idle", "admit", "prefill_chunk", "decode_dispatch", "readback",
          "stream", "warmup")

_COMPILE_EVENTS = ("/jax/core/compile/backend_compile_duration",
                   "/jax/compilation_cache/cache_retrieval_time_sec")


class _Phase:
    """One named phase: its accumulated self and inclusive seconds, and
    the state of the mark in progress. Reused by every mark of its name
    (a phase never nests inside itself on purpose; if it does, the inner
    mark is a no-op and its time stays with the outer one)."""

    __slots__ = ("name", "label", "seconds", "inclusive", "_owner", "_t0",
                 "_child", "_parent", "_ann", "_kw", "_depth")

    def __init__(self, owner: "LoopPhases", name: str) -> None:
        self.name = name
        self.label = "sched." + name
        self.seconds = 0.0          # self time, inner phases subtracted
        self.inclusive = 0.0        # whole marks, inner phases included
        self._owner = owner
        self._t0 = 0.0
        self._child = 0.0
        self._parent: Optional[_Phase] = None
        self._ann = None
        self._kw: dict = {}
        self._depth = 0

    def __enter__(self) -> "_Phase":
        if self._depth:
            self._depth += 1
            return self
        self._depth = 1
        owner = self._owner
        self._parent = owner._top
        owner._top = self
        owner.current = self.name
        self._child = 0.0
        self._ann = jax.profiler.TraceAnnotation(self.label, **self._kw)
        self._ann.__enter__()
        self._t0 = time.monotonic()
        return self

    def __exit__(self, *exc) -> bool:
        self._depth -= 1
        if self._depth:
            return False
        dur = time.monotonic() - self._t0
        self._ann.__exit__(*exc)
        self._ann = None
        own = dur - self._child
        self.seconds += own
        self.inclusive += dur
        owner, parent = self._owner, self._parent
        if own > owner._slowest_s:
            owner._slowest_s = own
            owner.slowest = self.name
        if parent is not None:
            parent._child += dur
        owner._top = parent
        owner.current = parent.name if parent is not None else ""
        return False


class LoopPhases:
    """The phases of one loop thread. ``phases(name, **kw)`` returns the
    context manager; ``kw`` go to the trace annotation only."""

    def __init__(self, names: tuple = PHASES) -> None:
        self._by_name = {n: _Phase(self, n) for n in names}
        self._top: Optional[_Phase] = None
        self.current = ""           # innermost phase now, "" between marks
        self.slowest = ""           # largest single self time since mark_iteration
        self._slowest_s = 0.0

    def __call__(self, name: str, **kw) -> _Phase:
        p = self._by_name[name]
        p._kw = kw
        return p

    def mark_iteration(self) -> None:
        """A loop iteration starts: forget the last one's slowest phase."""
        self.slowest = ""
        self._slowest_s = 0.0

    def seconds(self, name: str) -> float:
        return self._by_name[name].seconds

    def inclusive(self, name: str) -> float:
        return self._by_name[name].inclusive


class CompileClock:
    """Seconds this process has spent compiling (or fetching compiled
    programs from the persistent cache), summed from the durations JAX
    reports through ``jax.monitoring``. JAX's listener registry is
    process-wide, and so is this quantity: :func:`compile_clock` hands
    out the one instance."""

    def __init__(self) -> None:
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._heard)

    def _heard(self, event: str, duration: float, **_kw) -> None:
        if event in _COMPILE_EVENTS:
            self.seconds += duration


_CLOCK: Optional[CompileClock] = None


def compile_clock() -> CompileClock:
    """The process's compile clock, started at the first call (the
    serving entry point calls it before the first compile)."""
    global _CLOCK
    if _CLOCK is None:
        _CLOCK = CompileClock()
    return _CLOCK


def process_age_s() -> Optional[float]:
    """Seconds since this process started, from the OS's own record
    (``/proc``: start time in clock ticks since boot against the uptime),
    so that interpreter start-up and imports are counted. None where
    ``/proc`` cannot say."""
    try:
        with open("/proc/self/stat", "rb") as f:
            # Fields after the parenthesised command name; starttime is
            # field 22 of the whole line, 20th after the ')'.
            start_ticks = float(f.read().rsplit(b")", 1)[1].split()[19])
        with open("/proc/uptime", "rb") as f:
            uptime = float(f.read().split()[0])
        return max(0.0, uptime - start_ticks / os.sysconf("SC_CLK_TCK"))
    except (OSError, ValueError, IndexError):
        return None
