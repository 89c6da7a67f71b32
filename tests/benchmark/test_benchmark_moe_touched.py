"""``moe_touched_share``: the share of a routed model's decode expert
stream that some live row asked for, read from two window counters; no
value, and no fault, on a program that has no such counters."""

import os
import types

import pytest

from benchmark import manifest, metrics, roofline

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CELL = "mixtral-8x7b-v0.1-l6.chat-steady"
TOUCHED = "serve_moe_decode_experts_touched_total"
SLOTS = "serve_moe_decode_expert_slots_total"


def _obs(**kw):
    cell = manifest.load_cell(CELL, ROOT)
    rec = types.SimpleNamespace(ok=True, due_t=6.0, prompt_bytes=400,
                                tokens=100, chunk_t=[6.5, 7.0],
                                chunk_tokens=[1, 99])
    return metrics.Observations(records=[rec], ramp_s=5.0, window_s=51.0,
                                cell=cell,
                                peaks=roofline.peaks_for("TPU v5 lite"), **kw)


def _read(obs):
    return manifest.load_reader(obs.cell.root, "moe_touched_share")(obs)


def test_share_is_a_window_difference():
    obs = _obs(counters_start={TOUCHED: 100, SLOTS: 480},
               counters_end={TOUCHED: 100 + 21 * 1000, SLOTS: 480 + 48000})
    assert _read(obs) == pytest.approx(43.75)


@pytest.mark.parametrize("start,end", [
    ({"x": 1}, {"x": 2}),                                   # the parent
    ({TOUCHED: 5, SLOTS: 48}, {TOUCHED: 5, SLOTS: 48}),     # no step ran
    ({SLOTS: 48}, {SLOTS: 96}),                             # half a program
])
def test_no_counters_or_no_steps_is_no_value(start, end):
    assert _read(_obs(counters_start=start, counters_end=end)) is None


def test_manifest_entry_is_the_last_and_names_the_steady_mixtral_cell():
    entry = manifest.load_manifest(ROOT)["per_layer"][-1]
    assert entry == {
        "name": "moe_touched_share", "unit": "%", "better": "lower",
        "source": "program_counter", "layer": "model programs models/",
        "moves": "itl_p50_ms", "workloads": [CELL]}
    reported = {m["name"] for m in manifest.load_cell(CELL, ROOT).per_layer}
    assert "moe_touched_share" in reported
    other = manifest.load_cell("mistral-7b-v0.3.chat-steady", ROOT)
    assert "moe_touched_share" not in {m["name"] for m in other.per_layer}


def test_the_scheduler_exports_the_series_the_reader_names():
    with open(os.path.join(ROOT, "p2p_llm_chat_tpu", "serve",
                           "scheduler.py")) as f:
        source = f.read()
    assert f'"{TOUCHED}"' in source and f'"{SLOTS}"' in source
