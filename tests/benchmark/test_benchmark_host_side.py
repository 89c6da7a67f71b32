"""The six readers of the host's side (ISSUE 34): each returns its number
from hand-built observations, and no value (and no fault) on a program
that has no such counters or spans, as the parent has not. Their
manifest entries are present, in the cells that report the metric each
moves, and the scheduler exports every series a reader names."""

import os
import re
import types

import pytest

from benchmark import manifest, metrics, roofline

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BACKLOG = "mixtral-8x7b-v0.1-l6.chat-backlog"
STEADY = "mixtral-8x7b-v0.1-l6.chat-steady"
TPOT_CELLS = [BACKLOG, "olmoe-1b-7b-0125.chat-backlog",
              "openpangu-ultra-moe-718b-l9e16.long-context",
              "nemotron-3-super-120b-a12b-l22e128.chat-backlog"]
STEADY_CELLS = ["mistral-7b-v0.3.chat-steady", STEADY]
S = "scheduler serve/scheduler.py"
F = "HTTP front serve/api.py"
ENTRIES = {
    "admit_host_ms": ("ms", "program_counter", S, "tpot_p50_ms", TPOT_CELLS),
    "launch_ms": ("ms", "program_counter", S, "tpot_p50_ms", TPOT_CELLS),
    "loop_offcpu_share": ("%", "program_counter", S, "tpot_p50_ms",
                          TPOT_CELLS),
    "launch_starved_share": ("%", "program_counter", S, "tpot_p50_ms",
                             TPOT_CELLS),
    "stream_handoff_ms": ("ms", "program_counter", F, "tpot_p50_ms",
                          TPOT_CELLS),
    "front_accept_p50_ms": ("ms", "program_span", F, "itl_p50_ms",
                            STEADY_CELLS),
}


def _obs(cell=BACKLOG, **kw):
    rec = types.SimpleNamespace(ok=True, due_t=6.0, prompt_bytes=400,
                                tokens=100, chunk_t=[6.5, 7.0],
                                chunk_tokens=[1, 99])
    return metrics.Observations(
        records=[rec], ramp_s=5.0, window_s=51.0,
        cell=manifest.load_cell(cell, ROOT),
        peaks=roofline.peaks_for("TPU v5 lite"), **kw)


def _read(name, obs):
    return manifest.load_reader(obs.cell.root, name)(obs)


def _loop(phase, wall, cpu=None, marks=None) -> dict:
    """A phase's or a part's series at a window's end (its start: 0)."""
    out = {f"serve_loop_{phase}_seconds_total": wall}
    if cpu is not None:
        # The CPU clock is read in one iteration of eight: an eighth of
        # the wall, and the CPU seconds of those marks.
        out[f"serve_loop_{phase}_cpu_wall_seconds_total"] = wall / 8
        out[f"serve_loop_{phase}_cpu_seconds_total"] = cpu / 8
    if marks is not None:
        out[f"serve_loop_{phase}_marks_total"] = marks
    return out


def _window(end: dict) -> dict:
    return {"counters_start": {k: 0 for k in end}, "counters_end": end}


# One window of a program that has everything, in round numbers.
FULL = {
    **_loop("admit", 4.0, 3.0), **_loop("prefill_chunk", 2.0, 1.5),
    **_loop("decode_dispatch", 6.0, 5.0), **_loop("stream", 3.0, 1.0),
    **_loop("other", 1.0, 0.5),
    **_loop("admit_gap", 0.5, 0.0, 10),
    **_loop("admit_upload", 1.0, 0.5, 100),
    **_loop("admit_launch", 0.5, 0.5, 100),
    **_loop("prefill_chunk_upload", 0.5, 0.25, 50),
    **_loop("prefill_chunk_launch", 0.25, 0.25, 50),
    **_loop("decode_dispatch_upload", 0.25, 0.25, 40),
    **_loop("decode_dispatch_launch", 4.0, 3.0, 2000),
    **_loop("stream_launch", 0.25, 0.25, 350),
    "serve_admit_batches_total": 100, "prefill_chunks_total": 50,
    "serve_launch_admit_total": 100, "serve_launch_admit_starved_total": 60,
    "serve_launch_prefill_chunk_total": 50,
    "serve_launch_prefill_chunk_starved_total": 15,
    "serve_launch_decode_total": 2000,
    "serve_launch_decode_starved_total": 140,
    "serve_stream_handoff_seconds_total": 1.5,
    "serve_stream_deltas_total": 6000,
}
WANT = {
    # (4 + 2) s over 100 + 50 dispatches.
    "admit_host_ms": 40.0,
    # (0.5 + 0.25 + 4 + 0.25) s over 100 + 50 + 2000 + 350 marks.
    "launch_ms": 2.0,
    # wall 16 - 7.25 = 8.75, CPU 11 - 5 = 6: 1 - 6 / 8.75.
    "loop_offcpu_share": 100.0 * (1.0 - 6.0 / 8.75),
    "launch_starved_share": 10.0,
    "stream_handoff_ms": 0.25,
}


@pytest.mark.parametrize("name", sorted(WANT))
def test_reader_divides_window_differences(name):
    assert _read(name, _obs(**_window(FULL))) == pytest.approx(WANT[name])
    # A window is a difference: what the counters held before is not in it.
    start = {k: 7 for k in FULL}
    end = {k: v + 7 for k, v in FULL.items()}
    obs = _obs(counters_start=start, counters_end=end)
    assert _read(name, obs) == pytest.approx(WANT[name])


# The parent's program: the seven phases and the site counters, no part,
# no CPU clock, no launch or hand-off counter.
PARENT = {**_loop("admit", 4.0), **_loop("prefill_chunk", 2.0),
          **_loop("decode_dispatch", 6.0), **_loop("stream", 3.0),
          "serve_admit_batches_total": 100, "prefill_chunks_total": 50,
          "serve_loop_seconds_total": 51.0}


@pytest.mark.parametrize("name", sorted(WANT))
@pytest.mark.parametrize("case", ["parent", "empty", "still", "half"])
def test_reader_without_its_counters_or_its_events_is_no_value(name, case):
    if case == "parent":
        kw = _window(PARENT)
    elif case == "empty":
        kw = {}
    elif case == "still":           # the series are there and none moved
        kw = {"counters_start": FULL, "counters_end": FULL}
    else:                           # one end of the window lacks them
        kw = {"counters_start": {}, "counters_end": FULL}
    assert _read(name, _obs(**kw)) is None


def test_front_accept_is_the_median_of_its_span():
    obs = _obs(STEADY, spans={"api.accept": [0.3, 0.1, 0.2, 9.0, 0.25],
                              "api.request": [1000.0]})
    assert _read("front_accept_p50_ms", obs) == 0.25
    assert _read("front_accept_p50_ms", _obs(STEADY)) is None
    assert _read("front_accept_p50_ms",
                 _obs(STEADY, spans={"api.request": [1.0]})) is None


@pytest.mark.parametrize("name", sorted(ENTRIES))
def test_manifest_entry_is_present_in_the_cells_that_report_what_it_moves(
        name):
    man = manifest.load_manifest(ROOT)
    unit, source, layer, moves, cells = ENTRIES[name]
    entries = [m for m in man["per_layer"] if m["name"] == name]
    assert entries == [{"name": name, "unit": unit, "better": "lower",
                        "source": source, "layer": layer, "moves": moves,
                        "workloads": cells}]
    judged = next(m for m in man["end_to_end"] if m["name"] == moves)
    assert set(cells) <= set(judged["workloads"])
    for w in man["workloads"]:
        reported = {m["name"] for m in
                    manifest.load_cell(w["name"], ROOT).per_layer}
        assert (name in reported) == (w["name"] in cells)
    # A layer the benchmark already names.
    assert layer in {m["layer"] for m in man["per_layer"]
                     if m["name"] not in ENTRIES}


@pytest.mark.parametrize("name", sorted(WANT))
def test_the_scheduler_exports_every_series_the_reader_names(name):
    with open(os.path.join(ROOT, "p2p_llm_chat_tpu", "serve",
                           "scheduler.py")) as f:
        source = f.read()
    seen: set = set()
    obs = _obs(**_window(FULL))
    obs.counter_delta = lambda n, stretch=False: (
        seen.add(n), FULL.get(n))[1]
    assert _read(name, obs) is not None
    assert seen
    for series in seen:
        assert re.search(rf'"{series}"', source), series
