"""``attn_walk_share``: of the flash-append kernel's (row, chunk)
programs in a window's decode dispatches, the share whose chunk lay
inside its row's context, read from two window counters; no value, and
no fault, on a program that has no such counters."""

import os
import types

import pytest

from benchmark import manifest, metrics, roofline

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CELLS = ["mixtral-8x7b-v0.1-l6.chat-backlog", "olmoe-1b-7b-0125.chat-backlog"]
WALKED = "serve_attn_chunks_walked_total"
TOTAL = "serve_attn_chunks_total"


def _obs(cell=CELLS[1], **kw):
    rec = types.SimpleNamespace(ok=True, due_t=6.0, prompt_bytes=400,
                                tokens=100, chunk_t=[6.5, 7.0],
                                chunk_tokens=[1, 99])
    return metrics.Observations(records=[rec], ramp_s=5.0, window_s=51.0,
                                cell=manifest.load_cell(cell, ROOT),
                                peaks=roofline.peaks_for("TPU v5 lite"), **kw)


def _read(obs):
    return manifest.load_reader(obs.cell.root, "attn_walk_share")(obs)


@pytest.mark.parametrize("cell", CELLS)
def test_share_is_a_window_difference(cell):
    obs = _obs(cell, counters_start={WALKED: 700, TOTAL: 1280},
               counters_end={WALKED: 700 + 43 * 1000, TOTAL: 1280 + 128000})
    assert _read(obs) == pytest.approx(33.59375)


@pytest.mark.parametrize("start,end", [
    ({"x": 1}, {"x": 2}),                                   # the parent
    ({WALKED: 5, TOTAL: 48}, {WALKED: 5, TOTAL: 48}),       # gather windows only
    ({TOTAL: 48}, {TOTAL: 96}),                             # half a program
])
def test_no_counters_or_no_kernel_dispatch_is_no_value(start, end):
    assert _read(_obs(counters_start=start, counters_end=end)) is None


def test_manifest_entry_names_the_two_backlog_cells_and_no_other():
    entry = [m for m in manifest.load_manifest(ROOT)["per_layer"]
             if m["name"] == "attn_walk_share"]
    assert entry == [{
        "name": "attn_walk_share", "unit": "%", "better": "lower",
        "source": "program_counter",
        "layer": "kernels ops/quant_mm.py ops/paged_attention.py",
        "moves": "tpot_p50_ms", "workloads": CELLS}]
    for cell in manifest.load_manifest(ROOT)["workloads"]:
        reported = {m["name"] for m in
                    manifest.load_cell(cell["name"], ROOT).per_layer}
        assert ("attn_walk_share" in reported) == (cell["name"] in CELLS)


def test_the_scheduler_exports_the_series_the_reader_names():
    with open(os.path.join(ROOT, "p2p_llm_chat_tpu", "serve",
                           "scheduler.py")) as f:
        source = f.read()
    assert f'"{WALKED}"' in source and f'"{TOTAL}"' in source
