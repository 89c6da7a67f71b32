"""Loop-phase timer: where the scheduler thread's wall goes.

The third observability plane, beside the per-request spans
(obs/trace.py) and the event ring (obs/flight.py): the scheduler loop
marks the phase it is in, ``with phases("readback"):``, and each mark
does four things and nothing else —

- enters a ``jax.profiler.TraceAnnotation("sched.<name>")``, which puts
  the host's phases on the device trace's own clock (an idle gap of the
  device can then be given to the phase the host was in); while no
  profiler session is open the annotation costs a flag test. A phase's
  annotation covers the marks inside it; a part's, and ``other``'s,
  covers its self time only (it pauses around a mark inside it);
- adds the phase's **self time** to a plain float: phases nest, and the
  time spent in an inner phase is subtracted from the outer one, so the
  phases of one loop iteration never add up to more than its wall;
- in one loop iteration of :data:`CPU_EVERY`, does the same with the
  thread's **CPU time** (``time.thread_time``), beside the wall seconds
  of the same marks: wall less CPU is the time the thread was off the
  CPU, waiting for the interpreter lock, for the runtime or for the OS.
  Not in every iteration, because that clock is a call into the kernel
  and under a sandboxed kernel a dear one (5.8 us under gVisor, in
  10 ms ticks: PERF.md §6, PR 34);
- remembers the name of the mark with the largest single self time of
  the iteration, so the watchdog's ``stall_enter`` event can say where a
  long iteration spent its time.

A **part** (:data:`PARTS`: ``collect``, ``gap``, ``plan``, ``build``,
``upload``, ``launch``) is a mark inside a phase that says what the phase was doing:
``with phases("launch"):`` under ``admit`` is the phase ``admit.launch``,
annotated ``sched.admit.launch``, with seconds, CPU seconds and a count
of marks of its own. A part belongs to the nearest phase around it that
is no part; where that phase lists no such part (:data:`PARTS_OF`) the
mark is a no-op and the time stays where it was. ``total(phase)`` is
the phase's self time with its parts': what the phase's name meant
before there were parts.

One :class:`LoopPhases` belongs to one thread (the scheduler loop): no
lock, two ``time.monotonic()`` reads and a few float adds per mark (and
two ``time.thread_time()`` reads in a sampled iteration). Other threads may read the floats; a torn
read is harmless for a gauge. The same file keeps the two boot-time
readings that share its purpose: seconds of compilation heard through
``jax.monitoring``, and the process's age as the OS records it.
docs/observability.md has the phase table and how to read a trace with
it.
"""

from __future__ import annotations

import os
import time
from typing import Optional

import jax

PHASES = ("idle", "admit", "prefill_chunk", "decode_dispatch", "readback",
          "stream", "warmup", "other")
# The thread's CPU clock is read in one loop iteration of this many, at
# every mark of it, so that nested marks subtract whole.
CPU_EVERY = 8
PARTS = ("collect", "gap", "plan", "build", "upload", "launch")
# The parts each phase is divided into. A part marked under a phase
# that does not list it (a launch inside a warm-up job) is not timed.
PARTS_OF = {
    "admit": PARTS,
    "prefill_chunk": ("build", "upload", "launch"),
    "decode_dispatch": ("upload", "launch"),
    "stream": ("launch",),
}

_COMPILE_EVENTS = ("/jax/core/compile/backend_compile_duration",
                   "/jax/compilation_cache/cache_retrieval_time_sec")


class _Phase:
    """One named phase, or one part of one: its accumulated self and
    inclusive seconds, its marks, the self CPU seconds of the marks whose
    CPU clock was read with their self wall seconds (``cpu``,
    ``cpu_wall``), and the state of the mark in progress. Reused by every mark of its name (a phase never
    nests inside itself on purpose; if it does, the inner mark is a
    no-op and its time stays with the outer one)."""

    __slots__ = ("name", "label", "phase", "leaf", "seconds", "inclusive",
                 "cpu", "cpu_wall", "marks", "_owner", "_t0", "_c0",
                 "_cpu_on", "_child", "_child_cpu", "_parent", "_ann",
                 "_kw", "_depth")

    def __init__(self, owner: "LoopPhases", name: str,
                 phase: Optional[str] = None) -> None:
        self.name = name if phase is None else f"{phase}.{name}"
        self.label = "sched." + self.name
        self.phase = phase or name  # the phase a part marked inside belongs to
        # A part, and "other", is annotated over its self time only: the
        # annotation ends where a mark inside begins and starts again
        # where that ends, so that in a trace it never covers another
        # mark (a device gap goes to the event that overlaps it most).
        self.leaf = phase is not None or name == "other"
        self.seconds = 0.0          # self time, inner phases subtracted
        self.inclusive = 0.0        # whole marks, inner phases included
        self.cpu = 0.0              # self CPU time, where it was read
        self.cpu_wall = 0.0         # self time of those same marks
        self.marks = 0
        self._owner = owner
        self._t0 = 0.0
        self._c0 = 0.0
        self._cpu_on = False
        self._child = 0.0
        self._child_cpu = 0.0
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
        parent = self._parent = owner._top
        owner._top = self
        self._child = 0.0
        self._child_cpu = 0.0
        if parent is not None and parent.leaf:
            parent._ann.__exit__(None, None, None)
        self._annotate()
        # The CPU clock is read inside the wall clock's two reads, so a
        # mark's CPU seconds cannot pass its wall seconds.
        self._t0 = time.monotonic()
        self._cpu_on = owner._cpu_on
        if self._cpu_on:
            self._c0 = time.thread_time()
        return self

    def __exit__(self, *exc) -> bool:
        self._depth -= 1
        if self._depth:
            return False
        dur_cpu = time.thread_time() - self._c0 if self._cpu_on else 0.0
        dur = time.monotonic() - self._t0
        self._ann.__exit__(*exc)
        self._ann = None
        own = dur - self._child
        self.seconds += own
        self.inclusive += dur
        self.marks += 1
        owner, parent = self._owner, self._parent
        if self._cpu_on:
            self.cpu += dur_cpu - self._child_cpu
            self.cpu_wall += own
        if own > owner._slowest_s:
            owner._slowest_s = own
            owner.slowest = self.name
        if parent is not None:
            parent._child += dur
            parent._child_cpu += dur_cpu
            if parent.leaf:
                parent._annotate()
        owner._top = parent
        return False

    def _annotate(self) -> None:
        self._ann = jax.profiler.TraceAnnotation(self.label, **self._kw)
        self._ann.__enter__()


class _NoMark:
    """A part marked where its phase lists none: nothing is timed."""

    def __enter__(self) -> "_NoMark":
        return self

    def __exit__(self, *exc) -> bool:
        return False


_NO_MARK = _NoMark()


class LoopPhases:
    """The phases of one loop thread. ``phases(name, **kw)`` returns the
    context manager; ``kw`` go to the trace annotation only. A part's
    name is its own where it is marked (``"launch"``) and its phase's
    with it where it is read (``"admit.launch"``)."""

    def __init__(self, names: tuple = PHASES) -> None:
        self._by_name = {n: _Phase(self, n) for n in names}
        # A part, as it is marked: by the phase around it and its name.
        self._parts = {(n, part): _Phase(self, part, n) for n in names
                       for part in PARTS_OF.get(n, ())}
        self._by_name.update((p.name, p) for p in self._parts.values())
        self._top: Optional[_Phase] = None
        self.slowest = ""           # largest single self time since mark_iteration
        self._slowest_s = 0.0
        self._iterations = 0
        self._cpu_on = True         # the CPU clock is read at this mark

    def __call__(self, name: str, **kw):
        if name in PARTS:
            top = self._top
            p = self._parts.get(
                (top.phase if top is not None else "other", name))
            if p is None:
                return _NO_MARK
        else:
            p = self._by_name[name]
        p._kw = kw
        return p

    def mark_iteration(self) -> None:
        """A loop iteration starts (between marks): forget the last
        one's slowest phase, and say whether this one reads the CPU
        clock."""
        self.slowest = ""
        self._slowest_s = 0.0
        self._cpu_on = self._iterations % CPU_EVERY == 0
        self._iterations += 1

    def seconds(self, name: str) -> float:
        return self._by_name[name].seconds

    def inclusive(self, name: str) -> float:
        return self._by_name[name].inclusive

    def cpu(self, name: str) -> float:
        return self._by_name[name].cpu

    def cpu_wall(self, name: str) -> float:
        return self._by_name[name].cpu_wall

    def marks(self, name: str) -> int:
        return self._by_name[name].marks

    def total(self, name: str) -> float:
        """A phase's self seconds with its parts': its wall less the
        phases (not the parts) marked inside it."""
        return self.seconds(name) + sum(
            self.seconds(f"{name}.{p}") for p in PARTS_OF.get(name, ()))

    def cpu_total(self, name: str) -> float:
        return self.cpu(name) + sum(
            self.cpu(f"{name}.{p}") for p in PARTS_OF.get(name, ()))

    def cpu_wall_total(self, name: str) -> float:
        return self.cpu_wall(name) + sum(
            self.cpu_wall(f"{name}.{p}") for p in PARTS_OF.get(name, ()))


class CompileClock:
    """Seconds this process has spent compiling (or fetching compiled
    programs from the persistent cache), summed from the durations JAX
    reports through ``jax.monitoring``, and how many such durations it
    has heard. It goes on counting after the server is ready: a compile
    inside serving is what a run most needs to hear of. JAX's listener
    registry is process-wide, and so are these quantities:
    :func:`compile_clock` hands out the one instance."""

    def __init__(self) -> None:
        self.seconds = 0.0
        self.events = 0
        jax.monitoring.register_event_duration_secs_listener(self._heard)

    def _heard(self, event: str, duration: float, **_kw) -> None:
        if event in _COMPILE_EVENTS:
            self.seconds += duration
            self.events += 1


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
