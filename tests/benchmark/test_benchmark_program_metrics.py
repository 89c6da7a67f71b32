"""The per-layer metrics that read what the program counts about itself
(PR 23): each reader on hand-made observations, and on observations of a
program that lacks the counters, where it must return nothing and not
raise (the parent commit of the PR that adds a metric is such a
program). JAX-free.
"""

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import manifest, metrics  # noqa: E402

BENCH = os.path.join(ROOT, "benchmark")
STEADY = ["mistral-7b-v0.3.chat-steady", "mixtral-8x7b-v0.1-l6.chat-steady"]
BACKLOG = ["mixtral-8x7b-v0.1-l6.chat-backlog"]
# A benchmark metric's name that happens to be spelled like a /metrics
# series: kept out of list displays, where graftcheck's metrics-contract
# analyzer would take it for a series some test reads.
STEP_MS = "decode_step_ms"

# A window of a program that decoded 1,000 steps (200 fused dispatches
# of 4 and 200 plain ones) over 20 s of loop wall.
START = {
    "serve_decode_ticks_total": 100, "decode_fused_ticks_total": 50,
    "decode_fused_steps_total": 200,
    "serve_admitted_total": 10, "serve_admit_batches_total": 8,
    "serve_decode_row_steps_total": 1000,
    "serve_loop_seconds_total": 100.0,
    "serve_loop_readback_seconds_total": 60.0,
    "serve_loop_idle_seconds_total": 30.0,
    "serve_prefill_tokens_total": 5000,
    "serve_prefill_tokens_padded_total": 40000,
    "serve_decode_clean_seconds_total": 2.0,
    "serve_decode_clean_steps_total": 100,
    "serve_boot_load_seconds": 19.5, "serve_boot_warmup_seconds": 71.25,
    "serve_boot_compile_seconds": 33.0,
}
END = {
    "serve_decode_ticks_total": 500, "decode_fused_ticks_total": 250,
    "decode_fused_steps_total": 1000,
    "serve_admitted_total": 60, "serve_admit_batches_total": 48,
    "serve_decode_row_steps_total": 4000,
    "serve_loop_seconds_total": 120.0,
    "serve_loop_readback_seconds_total": 76.0,
    "serve_loop_idle_seconds_total": 31.0,
    "serve_prefill_tokens_total": 15000,
    "serve_prefill_tokens_padded_total": 120000,
    "serve_decode_clean_seconds_total": 5.4,
    "serve_decode_clean_steps_total": 300,
    "serve_boot_load_seconds": 19.5, "serve_boot_warmup_seconds": 71.25,
    "serve_boot_compile_seconds": 33.0,
}
MODULES = [["jit_prefill_chunk_mid(123)", 2.0, 40],
           ["jit_decode_fused_steps(45)", 1.0, 60],
           ["jit_prefill_admit_paged_prefix(6)", 1.5, 12],
           ["jit_kv_zero_row(7)", 0.5, 30]]

CASES = {
    # 50 requests in 40 admissions
    "admit_rows_mean": 1.25,
    # 3,000 row-steps over 800 fused + 200 plain steps
    "decode_rows_mean": 3.0,
    # (20 s of loop - 16 s reading back - 1 s idle) over 1,000 steps
    "host_ms_per_step": 3.0,
    # 10,000 real positions of 80,000 computed
    "prefill_pad_share": 87.5,
    # 16 s of 20 s
    "device_wait_share": 80.0,
    # 3.4 s over 200 steps
    "decode_step_ms": 17.0,
    # 3.5 s of 5 s
    "prefill_device_share": 70.0,
    "boot_load_s": 19.5,
    "boot_warmup_s": 71.25,
    "boot_compile_s": 33.0,
}


def _obs(**kw) -> metrics.Observations:
    return metrics.Observations(records=[], ramp_s=5.0, window_s=51.0, **kw)


@pytest.mark.parametrize("name", sorted(CASES))
def test_reader_on_a_program_that_counts(name):
    obs = _obs(counters_start=dict(START), counters_end=dict(END),
               trace={"modules": MODULES, "busy_s": 5.0, "window_s": 5.2})
    value = manifest.load_reader(BENCH, name)(obs)
    assert value == pytest.approx(CASES[name])


@pytest.mark.parametrize("name", sorted(CASES))
def test_reader_finds_nothing_on_a_program_without_the_counters(name):
    """The parent of the PR that added these: decode counters and a
    trace, none of the new series, programs named by position."""
    old = {k: v for k, v in START.items()
           if k in ("serve_decode_ticks_total", "decode_fused_ticks_total",
                    "decode_fused_steps_total", "serve_admitted_total")}
    end = {k: END[k] for k in old}
    obs = _obs(counters_start=old, counters_end=end,
               trace={"modules": [["jit__decode_fused(45)", 1.0, 60],
                                  ["jit__chunk_mid(123)", 2.0, 40]],
                      "busy_s": 3.0, "window_s": 3.1})
    assert manifest.load_reader(BENCH, name)(obs) is None
    assert manifest.load_reader(BENCH, name)(_obs()) is None


STEPS = ("serve_decode_ticks_total", "decode_fused_ticks_total",
         "decode_fused_steps_total")


@pytest.mark.parametrize("name,still", [
    ("admit_rows_mean", ("serve_admit_batches_total",)),
    ("prefill_pad_share", ("serve_prefill_tokens_padded_total",)),
    (STEP_MS, ("serve_decode_clean_steps_total",)),
    ("device_wait_share", ("serve_loop_seconds_total",)),
    ("decode_rows_mean", STEPS),
    ("host_ms_per_step", STEPS)])
def test_reader_divides_by_nothing_that_stood_still(name, still):
    """A counter that did not move in the window (no admission, no clean
    interval, no step) gives no value, not a division by 0."""
    end = {**END, **{k: START[k] for k in still}}
    assert manifest.load_reader(BENCH, name)(
        _obs(counters_start=dict(START), counters_end=end)) is None


def test_boot_gauge_not_yet_set_is_no_value():
    start = {**START, "serve_boot_compile_seconds": 0.0}
    assert manifest.load_reader(BENCH, "boot_compile_s")(
        _obs(counters_start=start, counters_end=dict(END))) is None


def test_manifest_appends_the_ten_and_nothing_else_moved():
    per_layer = manifest.load_manifest(ROOT)["per_layer"]
    names = [m["name"] for m in per_layer]
    assert names[-10:] == [
        "admit_rows_mean", "decode_rows_mean", "host_ms_per_step",
        "prefill_pad_share", "device_wait_share", STEP_MS,
        "prefill_device_share", "boot_load_s", "boot_warmup_s",
        "boot_compile_s"]
    assert names[0] == "gen_lag_p99_ms" and names[16] == "hbm_peak_gb"
    by = {m["name"]: m for m in per_layer}
    for n in ("admit_rows_mean", "decode_rows_mean", "host_ms_per_step"):
        assert by[n]["workloads"] == STEADY and by[n]["moves"] == "itl_p50_ms"
    for n in ("prefill_pad_share", "device_wait_share", STEP_MS,
              "prefill_device_share"):
        assert by[n]["workloads"] == BACKLOG
        assert by[n]["moves"] == "tpot_p50_ms"
    for n in ("boot_load_s", "boot_warmup_s", "boot_compile_s"):
        assert "workloads" not in by[n] and by[n]["moves"] == "setup_s"
        assert by[n]["layer"] == "launcher and engine serve/engine.py"
    assert by["prefill_device_share"]["source"] == "device_trace"
    assert all(by[n]["source"] == "program_counter" for n in CASES
               if n != "prefill_device_share")


@pytest.mark.parametrize("cell,expected", [
    (STEADY[0], {"admit_rows_mean", "decode_rows_mean", "host_ms_per_step",
                 "boot_load_s", "boot_warmup_s", "boot_compile_s"}),
    (BACKLOG[0], {"prefill_pad_share", "device_wait_share", STEP_MS,
                  "prefill_device_share", "boot_load_s", "boot_warmup_s",
                  "boot_compile_s"})])
def test_cell_reports_its_new_metrics(cell, expected):
    got = {m["name"] for m in manifest.load_cell(cell, ROOT).per_layer}
    assert expected <= got
    assert not (set(CASES) - expected) & got


def test_series_the_readers_name_are_exported_by_the_scheduler():
    """A reader and the program agree on a series' spelling."""
    with open(os.path.join(ROOT, "p2p_llm_chat_tpu", "serve",
                           "scheduler.py")) as f:
        source = f.read()
    for series in set(START) - {"serve_decode_ticks_total",
                                "decode_fused_ticks_total",
                                "decode_fused_steps_total",
                                "serve_admitted_total"}:
        assert f'"{series}"' in source, series
        readers = [n for n in CASES if series in open(os.path.join(
            BENCH, "layer_metrics", n + ".py")).read()]
        assert readers, series
