"""``sample_sort_share`` and ``sample_sort_share.steady`` (PR 52): the
share of a window's decode dispatches that carried a sampling row, read
from two counters; 0 under greedy traffic; no value, and no fault, on a
program without the counter (the parent commit is one). JAX-free."""

import json
import os

import pytest

from benchmark import manifest, metrics

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmark")
SORTS = "serve_decode_sort_dispatches_total"
TICKS = "serve_decode_ticks_total"
MOVES = {"sample_sort_share": "tpot_p50_ms",
         "sample_sort_share.steady": "itl_p50_ms"}


def _read(name, start, end):
    obs = metrics.Observations(records=[], ramp_s=5.0, window_s=51.0,
                               counters_start=start, counters_end=end)
    return manifest.load_reader(BENCH, name)(obs)


@pytest.mark.parametrize("name", sorted(MOVES))
@pytest.mark.parametrize("sorts,want", [(0, 0.0), (300, 25.0), (1200, 100.0)],
                         ids=["greedy", "a-quarter", "every-dispatch"])
def test_share_is_a_window_difference(name, sorts, want):
    got = _read(name, {SORTS: 40, TICKS: 500},
                {SORTS: 40 + sorts, TICKS: 1700})
    assert got == pytest.approx(want) and isinstance(got, float)


@pytest.mark.parametrize("name", sorted(MOVES))
@pytest.mark.parametrize("start,end", [
    ({TICKS: 500}, {TICKS: 1700}),                          # the parent
    ({SORTS: 0, TICKS: 500}, {SORTS: 0, TICKS: 500}),       # no dispatch ran
    ({}, {})], ids=["no-counter", "no-dispatch", "no-scrape"])
def test_no_counter_or_no_dispatch_is_no_value(name, start, end):
    assert _read(name, start, end) is None


@pytest.mark.parametrize("name", sorted(MOVES))
def test_manifest_entry_lists_the_cells_that_report_what_it_moves(name):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        doc = json.load(f)
    entry = [m for m in doc["per_layer"] if m["name"] == name]
    assert len(entry) == 1
    moved = [m for m in doc["end_to_end"] if m["name"] == MOVES[name]][0]
    assert entry[0] == {
        "name": name, "unit": "%", "better": "lower",
        "source": "program_counter", "layer": "model programs models/",
        "moves": MOVES[name], "workloads": moved["workloads"]}
    for cell in entry[0]["workloads"]:
        assert name in [m["name"] for m in
                        manifest.load_cell(cell, ROOT).per_layer]


def test_the_two_names_cover_every_cell_once():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        doc = json.load(f)
    listed = [c for m in doc["per_layer"] if m["name"] in MOVES
              for c in m["workloads"]]
    assert sorted(listed) == sorted(w["name"] for w in doc["workloads"])


def test_the_scheduler_exports_the_series_the_readers_name():
    with open(os.path.join(ROOT, "p2p_llm_chat_tpu", "serve",
                           "scheduler.py")) as f:
        source = f.read()
    assert f'"{SORTS}"' in source and f'"{TICKS}"' in source
