"""From a profiler trace (``.xplane.pb``) to numbers. Read with
``jax.profiler.ProfileData`` and nothing else.

What a TPU trace holds (looked at by hand on a v5e, PR 22): one plane
per chip, ``/device:TPU:<i>``, whose line ``XLA Ops`` carries one event
per executed HLO operation (a ``while`` or a ``call`` spans its body's
events on the same line, so times by name are *self* times: an event's
duration less its children's); ``XLA Modules`` carries one event per
executed program; ``/host:CPU`` carries one line per host thread with
the JAX runtime's own events. All planes share one clock.

``reduce`` returns, over the chips that ran anything:

``window_s``   first event to last event of the whole trace
``busy_s``     union of the device-operation intervals, mean over chips
``pallas_s``   self time of Pallas (Mosaic) custom calls, mean over chips
``device_ops`` [[name, seconds], ...] the operations that took most self
               time (mean over chips)
``idle_gaps``  [[host event, seconds], ...] the idle time of chip 0's
               longest gaps, summed by the host event that overlaps each
               gap most
``modules``    [[program, seconds, calls], ...] by executed program
"""

from __future__ import annotations

import glob
import os
import re

import numpy as np

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
# An operation is a Pallas kernel when its name or one of its string
# stats says so. pallas_call lowers to the HLO custom call target
# "tpu_custom_call" (Mosaic).
PALLAS_MARKS = ("tpu_custom_call", "mosaic", "pallas")
_GAPS_ATTRIBUTED = 200


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def _is_device(plane_name: str) -> bool:
    return plane_name.startswith("/device:TPU:") and " " not in plane_name


def _events(line) -> list:
    """[(start_ns, end_ns, name, is_pallas)], sorted by start."""
    out = []
    for e in line.events:
        if e.duration_ns <= 0:
            continue
        name = e.name
        low = name.lower()
        pallas = any(m in low for m in PALLAS_MARKS)
        if not pallas:
            for _, v in e.stats:
                if isinstance(v, str) and any(m in v.lower()
                                              for m in PALLAS_MARKS):
                    pallas = True
                    break
        out.append((e.start_ns, e.start_ns + e.duration_ns, name, pallas))
    out.sort(key=lambda t: (t[0], -t[1]))
    return out


_HLO = re.compile(r"^(%[\w.\-]+) = (\(?[a-z0-9]+\[[^\]]*\])?")


def short_name(name: str) -> str:
    """An operation's event name is its whole HLO line. For a report:
    the result's name and type, the kernel mark, at most 96 characters.
    (Times are summed under the whole line, which tells two programs'
    ``%fusion.3`` apart.)"""
    m = _HLO.match(name)
    if not m:
        return name[:96]
    out = m.group(1) + (" " + m.group(2) if m.group(2) else "")
    if "custom-call" in m.group(1) or "custom_call_target" in name:
        t = re.search(r'custom_call_target="([^"]+)"', name)
        out += " " + (t.group(1) if t else "custom-call")
    return out[:96]


def union(intervals: list) -> list:
    """Merged, sorted [(start, end)] of possibly overlapping intervals."""
    merged: list = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            if e > merged[-1][1]:
                merged[-1] = (merged[-1][0], e)
        else:
            merged.append((s, e))
    return merged


def self_times(events: list) -> dict:
    """name -> [self_ns, is_pallas]: each event's duration less the
    events nested inside it (``events`` sorted by start, outer first)."""
    out: dict = {}
    stack: list = []        # [end, name, pallas, child_ns, dur]

    def close(item) -> None:
        end, name, pallas, child, dur = item
        rec = out.setdefault(name, [0, pallas])
        rec[0] += max(0, dur - child)

    for s, e, name, pallas in events:
        while stack and stack[-1][0] <= s:
            close(stack.pop())
        if stack:
            stack[-1][3] += min(e, stack[-1][0]) - s
        stack.append([e, name, pallas, 0, e - s])
    while stack:
        close(stack.pop())
    return out


def _host_events(planes) -> tuple:
    """(starts, ends, names) of every host-thread event with a length."""
    starts, ends, names = [], [], []
    for p in planes:
        if not p.name.startswith("/host:CPU"):
            continue
        for line in p.lines:
            thread = line.name.split("/")[0]
            for e in line.events:
                if e.duration_ns > 0:
                    starts.append(e.start_ns)
                    ends.append(e.start_ns + e.duration_ns)
                    names.append(f"{thread}:{e.name}")
    return np.asarray(starts, float), np.asarray(ends, float), names


def attribute_gaps(gaps: list, host: tuple) -> dict:
    """host event -> idle ns: each gap goes to the host event that
    overlaps it most (the shortest such, where several cover it)."""
    starts, ends, names = host
    out: dict = {}
    for g0, g1 in gaps:
        label = "(no host event)"
        if len(names):
            overlap = np.minimum(ends, g1) - np.maximum(starts, g0)
            best = overlap.max()
            if best > 0:
                cand = np.flatnonzero(overlap >= 0.999 * best)
                label = names[cand[np.argmin((ends - starts)[cand])]]
        out[label] = out.get(label, 0.0) + (g1 - g0)
    return out


def reduce_planes(planes, top: int = 10) -> dict:
    # ProfileData.planes can be walked once (jaxlib 0.9.0), and the host
    # events are read in a second walk.
    planes = list(planes)
    lo, hi = None, None
    per_chip = []
    modules: dict = {}
    for p in planes:
        for line in p.lines:
            for e in line.events:
                s, t = e.start_ns, e.start_ns + e.duration_ns
                lo = s if lo is None or s < lo else lo
                hi = t if hi is None or t > hi else hi
        if not _is_device(p.name):
            continue
        ops = [ln for ln in p.lines if ln.name == OPS_LINE]
        if not ops:
            continue
        evs = _events(ops[0])
        if evs:
            per_chip.append((p.name, evs))
        for ln in p.lines:
            if ln.name == MODULES_LINE:
                for e in ln.events:
                    rec = modules.setdefault(e.name, [0.0, 0])
                    rec[0] += e.duration_ns
                    rec[1] += 1
    if not per_chip or lo is None:
        return {}
    n = len(per_chip)
    busy_ns, by_name = 0.0, {}
    for _, evs in per_chip:
        merged = union([(s, e) for s, e, _, _ in evs])
        busy_ns += sum(e - s for s, e in merged)
        for name, (ns, pallas) in self_times(evs).items():
            rec = by_name.setdefault(name, [0.0, pallas])
            rec[0] += ns
    per_chip.sort()
    merged0 = union([(s, e) for s, e, _, _ in per_chip[0][1]])
    edges = [lo] + [x for se in merged0 for x in se] + [hi]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    gaps.sort(key=lambda g: g[0] - g[1])
    idle = attribute_gaps(gaps[:_GAPS_ATTRIBUTED], _host_events(planes))

    def top_of(d: dict) -> list:
        return [[k, v / 1e9] for k, v in
                sorted(d.items(), key=lambda kv: -kv[1])[:top]]
    return {
        "chips": n,
        "window_s": (hi - lo) / 1e9,
        "busy_s": busy_ns / n / 1e9,
        "pallas_s": sum(ns for ns, p in by_name.values() if p) / n / 1e9,
        "device_ops": [[short_name(k), v] for k, v in
                       top_of({k: v[0] / n for k, v in by_name.items()})],
        "idle_gaps": top_of(idle),
        "longest_gap_s": (gaps[0][1] - gaps[0][0]) / 1e9 if gaps else 0.0,
        "gaps": len(gaps),
        "modules": [[k, v[0] / n / 1e9, v[1]] for k, v in
                    sorted(modules.items(), key=lambda kv: -kv[1][0])[:top]],
    }


def reduce(path: str, top: int = 10) -> dict:
    """``path``: an ``.xplane.pb`` file, or the directory given to
    ``jax.profiler.start_trace``."""
    from jax.profiler import ProfileData
    if os.path.isdir(path):
        path = find_xplane(path)
    return reduce_planes(ProfileData.from_file(path).planes, top)


def sample(path: str, per_line: int = 12) -> list:
    """The first events of every line, stats and all: what to look at by
    hand before trusting :func:`reduce` on a new device or JAX."""
    from jax.profiler import ProfileData
    if os.path.isdir(path):
        path = find_xplane(path)
    out = []
    for p in ProfileData.from_file(path).planes:
        for line in p.lines:
            evs = []
            for i, e in enumerate(line.events):
                if i >= per_line:
                    break
                evs.append({"name": e.name, "start_ns": e.start_ns,
                            "dur_ns": e.duration_ns,
                            "stats": {k: (v if isinstance(v, (int, float))
                                          else str(v)[:160])
                                      for k, v in e.stats}})
            out.append({"plane": p.name, "line": line.name, "events": evs})
    return out
