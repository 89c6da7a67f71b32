"""The per-layer metrics that read the scheduler's interval ledger, a
request's own share of it, and the compile clock after ready (PR 51):
each reader on counters that move, that stand still and that are absent
(the parent commit is such a program: nothing and no raise), the paired
spans, and the manifest's entries. JAX-free.
"""

import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import manifest, metrics  # noqa: E402

BENCH = os.path.join(ROOT, "benchmark")
CLASSES = ("chunk", "padded", "admit")
STEP_MS = {c: c + "_step_ms" for c in CLASSES}
STEP_SHARE = {c: c + "_step_share" for c in CLASSES}
READERS = (*STEP_MS.values(), *STEP_SHARE.values(), "req_cut_share_p50",
           "window_compile_s")
TPOT_CELLS = [
    "mixtral-8x7b-v0.1-l6.chat-backlog", "olmoe-1b-7b-0125.chat-backlog",
    "openpangu-ultra-moe-718b-l9e16.long-context",
    "nemotron-3-super-120b-a12b-l22e128.chat-backlog",
    "phi-4-mini-flash-reasoning.reasoning-backlog",
    "mellum2-12b-a2.5b-instruct-l16.code-context",
    "lfm2-8b-a1b.thread-recap", "keye-vl-2.0-30b-a3b-l12.doc-context"]


def _series(cls: str, what: str) -> str:
    head = "serve_decode_clean_" if cls == "clean" else \
        f"serve_decode_cut_{cls}_"
    return head + what + "_total"


def _counters(**steps_seconds) -> dict:
    """class=(steps, seconds) -> the ledger's series."""
    out = {}
    for cls, (steps, seconds) in steps_seconds.items():
        out[_series(cls, "steps")] = steps
        out[_series(cls, "seconds")] = seconds
    return out


# A window of 1,000 booked steps: 100 clean at 10 ms, 600 behind real
# chunks at 40 ms, 200 behind padded ones at 12.5 ms, 100 behind
# admissions at 30 ms.
START = {**_counters(clean=(50, 0.5), chunk=(10, 1.0), padded=(0, 0.0),
                     admit=(40, 2.0)),
         "serve_compile_seconds_total": 88.5}
END = {**_counters(clean=(150, 1.5), chunk=(610, 25.0), padded=(200, 2.5),
                   admit=(140, 5.0)),
       "serve_compile_seconds_total": 88.5}
WANT = {"chunk_step_ms": 40.0, "padded_step_ms": 12.5, "admit_step_ms": 30.0,
        "chunk_step_share": 60.0, "padded_step_share": 20.0,
        "admit_step_share": 10.0, "window_compile_s": 0.0}


def _obs(**kw) -> metrics.Observations:
    return metrics.Observations(records=[], ramp_s=5.0, window_s=51.0, **kw)


def _read(name: str, obs):
    return manifest.load_reader(BENCH, name)(obs)


@pytest.mark.parametrize("name", sorted(WANT))
def test_reader_on_counters_that_move(name):
    obs = _obs(counters_start=dict(START), counters_end=dict(END))
    assert _read(name, obs) == pytest.approx(WANT[name])


def test_the_classes_close_the_wall_of_a_booked_step():
    """sum(share x step wall) over the four classes is the booked
    seconds over the booked steps: what PERF.md holds against tick_ms
    taken over the whole window."""
    obs = _obs(counters_start=dict(START), counters_end=dict(END))
    clean_ms = _read("decode_step_ms", obs)
    shares = {c: _read(STEP_SHARE[c], obs) for c in CLASSES}
    closed = (100.0 - sum(shares.values())) * clean_ms / 100.0 + sum(
        shares[c] * _read(STEP_MS[c], obs) / 100.0 for c in CLASSES)
    assert closed == pytest.approx((1.0 + 24.0 + 2.5 + 3.0) * 1e3 / 1000)


@pytest.mark.parametrize("name", READERS)
def test_reader_finds_nothing_on_a_program_without_the_ledger(name):
    """The parent: the two clean counters, no cut series, no compile
    clock after ready, no sched.decode.cut."""
    old = {k: v for k, v in START.items() if "_clean_" in k}
    end = {k: END[k] for k in old}
    obs = _obs(counters_start=old, counters_end=end,
               spans={"sched.decode": [900.0, 1100.0]})
    assert _read(name, obs) is None
    assert _read(name, _obs()) is None


@pytest.mark.parametrize("cls", CLASSES)
def test_a_class_that_booked_no_step_has_no_step_wall_and_a_share_of_0(cls):
    end = {**END, _series(cls, "steps"): START[_series(cls, "steps")],
           _series(cls, "seconds"): START[_series(cls, "seconds")]}
    obs = _obs(counters_start=dict(START), counters_end=end)
    assert _read(STEP_MS[cls], obs) is None
    assert _read(STEP_SHARE[cls], obs) == 0.0


@pytest.mark.parametrize("name", sorted(STEP_SHARE.values()))
def test_a_window_that_booked_no_step_at_all_has_no_share(name):
    obs = _obs(counters_start=dict(START), counters_end=dict(START))
    assert _read(name, obs) is None


def test_a_compile_inside_the_window_is_read_in_seconds():
    end = {**END, "serve_compile_seconds_total": 91.75}
    obs = _obs(counters_start=dict(START), counters_end=end)
    assert _read("window_compile_s", obs) == pytest.approx(3.25)


def test_request_share_is_the_median_of_paired_spans():
    obs = _obs(spans={"sched.decode": [1000.0, 2000.0, 400.0, 0.0],
                      "sched.decode.cut": [100.0, 1000.0, 400.0, 0.0]})
    # 10%, 50%, 100%; the request with no decode wall has no share.
    assert _read("req_cut_share_p50", obs) == pytest.approx(50.0)
    clean = _obs(spans={"sched.decode": [1000.0, 2000.0],
                        "sched.decode.cut": [0.0, 0.0]})
    assert _read("req_cut_share_p50", clean) == 0.0


@pytest.mark.parametrize("spans", [
    {"sched.decode": [1000.0, 2000.0], "sched.decode.cut": [100.0]},
    {"sched.decode": [1000.0]},
    {"sched.decode.cut": [100.0]},
    {"sched.decode": [], "sched.decode.cut": []}],
    ids=["unpaired", "no-cut-span", "no-decode-span", "no-request"])
def test_request_share_of_lists_that_do_not_pair_is_nothing(spans):
    assert _read("req_cut_share_p50", _obs(spans=spans)) is None


def _manifest() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


@pytest.mark.parametrize("name", READERS)
def test_manifest_entry_and_its_reader(name):
    doc = _manifest()
    entry = [m for m in doc["per_layer"] if m["name"] == name]
    assert len(entry) == 1
    entry = entry[0]
    assert entry["moves"] == "tpot_p50_ms" and entry["better"] == "lower"
    assert entry["source"] == ("program_span" if name == "req_cut_share_p50"
                               else "program_counter")
    assert entry["layer"] == ("launcher and engine serve/engine.py"
                              if name == "window_compile_s"
                              else "scheduler serve/scheduler.py")
    assert entry["unit"] == ("ms" if name in STEP_MS.values() else
                             "s" if name == "window_compile_s" else "%")
    tpot = [m for m in doc["end_to_end"] if m["name"] == "tpot_p50_ms"][0]
    assert tpot["workloads"][:8] == TPOT_CELLS
    # Listed where its reader finds something to read: cells of
    # tpot_p50_ms's, in that list's order.
    assert entry["workloads"] == [c for c in tpot["workloads"]
                                  if c in entry["workloads"]]
    assert entry["workloads"]
    assert callable(manifest.load_reader(BENCH, name))
    for cell in entry["workloads"]:
        assert name in [m["name"] for m in
                        manifest.load_cell(cell, ROOT).per_layer]


def test_each_metric_lists_the_cells_where_its_reader_finds_something():
    """The shares, the request's and the compile clock's read something
    in every cell; a class's step wall only where the class books steps
    by the hundred a window (my chip runs, PR 51, PERF.md §5): no
    single-shot admission where every prompt climbs a ladder, 8-15
    steps a window behind padded chunks in the chat mix."""
    by_name = {m["name"]: m for m in _manifest()["per_layer"]}
    for name in (*STEP_SHARE.values(), "chunk_step_ms", "req_cut_share_p50",
                 "window_compile_s"):
        assert by_name[name]["workloads"][:8] == TPOT_CELLS, name
    chat = [c for c in TPOT_CELLS if c.endswith(".chat-backlog")]
    assert by_name["admit_step_ms"]["workloads"][:3] == chat
    assert by_name["padded_step_ms"]["workloads"][:5] == [
        c for c in TPOT_CELLS if c not in chat]


def test_the_eight_stand_behind_what_was_there_in_their_order():
    """Behind PR 49's last entry, in the issue's order; a later PR's
    entries may follow."""
    names = [m["name"] for m in _manifest()["per_layer"]]
    at = [names.index(n) for n in (
        "sparse_keep_share", "chunk_step_ms", "chunk_step_share",
        "padded_step_ms", "padded_step_share", "admit_step_ms",
        "admit_step_share", "req_cut_share_p50", "window_compile_s")]
    assert at == sorted(at) and len(set(names)) == len(names)
