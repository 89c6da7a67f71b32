"""The scheduler loop and the boot path measure themselves.

- the phase timer (obs/phase.py): nested self time, the current and the
  slowest phase;
- a tiny scheduler: the seven phases plus "other" close on the loop's
  wall, the counts at the dispatch sites are exact for prompts of known
  lengths and warm-up adds nothing to them, the boot gauges are set;
- the phases are on the profiler's clock: a CPU profile holds
  ``sched.*`` host events and the benchmark's gap attribution names
  them;
- a cold warm-up job over the loop budget is no stall, a stall after
  ready still is, and its ``stall_enter`` event names the phase;
- names are contracts: every ``pallas_call`` passes a literal ``name=``
  and every program the scheduler jits is named by its kind.

All on the CPU: counts and control flow, never a device timing.
"""

import ast
import json
import os
import threading
import time
import types

import pytest

import jax
import jax.numpy as jnp

from p2p_llm_chat_tpu.models import llama
from p2p_llm_chat_tpu.models.configs import get_config
from p2p_llm_chat_tpu.obs import phase as phase_mod
from p2p_llm_chat_tpu.obs.phase import (PHASES, LoopPhases, compile_clock,
                                        process_age_s)
from p2p_llm_chat_tpu.serve.backend import (GenerateOptions, GenerateRequest,
                                            RequestStats)
from p2p_llm_chat_tpu.serve.scheduler import BatchScheduler
from p2p_llm_chat_tpu.tokenizer import ByteTokenizer
from p2p_llm_chat_tpu.utils import failpoints as fp

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CFG = get_config("tiny")
PARAMS = llama.init_params(CFG, jax.random.PRNGKey(0), dtype=jnp.float32)
TOK = ByteTokenizer(vocab_size=CFG.vocab_size)
PHASE_SERIES = [f"serve_loop_{n}_seconds_total" for n in PHASES]
SITE_COUNTERS = ("serve_admit_batches_total", "serve_admit_rows_padded_total",
                 "serve_prefill_tokens_total",
                 "serve_prefill_tokens_padded_total",
                 "serve_decode_row_steps_total")


def _scheduler(**kw) -> BatchScheduler:
    kw.setdefault("num_slots", 4)
    kw.setdefault("max_seq", 128)
    kw.setdefault("prefill_chunk", 32)
    return BatchScheduler(PARAMS, CFG, TOK, **kw)


def _generate(sched: BatchScheduler, prompt: str, n: int) -> str:
    req = GenerateRequest(prompt=prompt, options=GenerateOptions(
        max_tokens=n, temperature=0.0, seed=1))
    return "".join(sched.submit(req, RequestStats()))


# -- the primitive -------------------------------------------------------------

class _Clock:
    def __init__(self) -> None:
        self.t = 100.0

    def monotonic(self) -> float:
        return self.t


@pytest.fixture()
def clock(monkeypatch):
    c = _Clock()
    monkeypatch.setattr(phase_mod, "time",
                        types.SimpleNamespace(monotonic=c.monotonic))
    return c


def test_phase_self_time_is_less_its_inner_phases(clock):
    ph = LoopPhases()
    with ph("admit"):
        clock.t += 1.0
        with ph("readback", rows=3):
            clock.t += 2.0
        with ph("stream"):
            clock.t += 0.5
            with ph("readback"):
                clock.t += 0.25
        clock.t += 0.125
    assert ph.seconds("admit") == pytest.approx(1.125)
    assert ph.seconds("readback") == pytest.approx(2.25)
    assert ph.seconds("stream") == pytest.approx(0.5)
    assert ph.inclusive("admit") == pytest.approx(3.875)
    assert ph.inclusive("stream") == pytest.approx(0.75)
    assert sum(ph.seconds(n) for n in PHASES) == pytest.approx(3.875)


def test_phase_names_the_current_and_the_slowest(clock):
    ph = LoopPhases()
    assert ph.current == "" and ph.slowest == ""
    with ph("admit"):
        assert ph.current == "admit"
        with ph("warmup"):
            assert ph.current == "warmup"
            clock.t += 3.0
        assert ph.current == "admit"
        clock.t += 1.0
    assert ph.current == ""
    with ph("decode_dispatch"):
        clock.t += 2.0
    assert ph.slowest == "warmup"
    ph.mark_iteration()
    assert ph.slowest == ""
    with ph("stream"):
        clock.t += 0.001
    assert ph.slowest == "stream"


def test_phase_reentered_by_its_own_name_keeps_the_outer_time(clock):
    ph = LoopPhases()
    with ph("readback"):
        clock.t += 1.0
        with ph("readback"):
            clock.t += 1.0
        clock.t += 1.0
    assert ph.seconds("readback") == pytest.approx(3.0)
    assert ph.current == ""


def test_phase_time_is_kept_when_the_body_raises(clock):
    ph = LoopPhases()
    with pytest.raises(RuntimeError):
        with ph("admit"):
            with ph("readback"):
                clock.t += 1.0
                raise RuntimeError("device reset")
    assert ph.seconds("readback") == pytest.approx(1.0)
    assert ph.current == ""
    with pytest.raises(KeyError):
        ph("no-such-phase")


def test_boot_clocks():
    age = process_age_s()
    assert age is not None and 0.0 < age < 24 * 3600
    clk = compile_clock()
    assert clk is compile_clock()
    before = clk.seconds
    # A program no other test compiles, too quick for the persistent
    # cache: compiled here, heard by the listener.
    jax.jit(lambda x: x * 3.25 + 0.5)(jnp.ones((3,))).block_until_ready()
    assert clk.seconds > before


# -- a tiny scheduler ------------------------------------------------------------

@pytest.fixture(scope="module")
def served():
    """Warm-up, then three prompts of known lengths sent one after the
    other; snapshots after the warm-up, after the traffic and after the
    loop thread has ended."""
    sched = _scheduler(decode_fuse_max=1)
    try:
        sched.warmup(prompt_buckets=(16, 32, 64))
        warm = sched.metrics_snapshot()
        # Bytes + BOS: 11, 41 and 21 tokens.
        for prompt, n in (("a" * 10, 5), ("b" * 40, 6), ("c" * 20, 7)):
            assert _generate(sched, prompt, n) is not None
        time.sleep(0.3)             # the pipelined last tick drains
        live = sched.metrics_snapshot()
    finally:
        sched.stop()
    return {"warm": warm, "live": live, "end": sched.metrics_snapshot()}


def test_phases_and_other_close_on_the_loop_wall(served):
    m = served["end"]
    loop = m["serve_loop_seconds_total"]
    phases = sum(m[k] for k in PHASE_SERIES)
    assert all(m[k] >= 0.0 for k in PHASE_SERIES)
    assert loop > 0.0 and m["serve_loop_iterations_total"] > 0
    # "other" is a difference: never negative, and small.
    assert phases <= loop + 1e-6
    assert phases >= 0.9 * loop
    for k in ("serve_loop_warmup_seconds_total",
              "serve_loop_idle_seconds_total",
              "serve_loop_readback_seconds_total",
              "serve_loop_decode_dispatch_seconds_total",
              "serve_loop_admit_seconds_total",
              "serve_loop_stream_seconds_total",
              "serve_loop_prefill_chunk_seconds_total"):
        assert m[k] > 0.0, k


def test_warmup_adds_nothing_to_the_site_counters(served):
    warm = served["warm"]
    for k in SITE_COUNTERS + ("serve_decode_clean_steps_total",
                              "serve_admitted_total"):
        assert warm[k] == 0, k
    assert warm["serve_boot_programs_total"] >= 8


def test_site_counters_are_exact(served):
    m = served["live"]
    assert m["serve_admitted_total"] == 3
    # 11 tokens -> bucket 16, single shot; 41 -> bucket 64, two chunks
    # of 32; 21 -> bucket 32, single shot.
    assert m["serve_admit_batches_total"] == 3
    assert m["prefill_chunks_total"] == 2
    assert m["serve_prefill_tokens_total"] == 11 + 41 + 21
    # Each arrived alone, so every program is 1 row wide: 16, 2 x 32, 32.
    assert m["serve_admit_rows_padded_total"] == 3
    assert m["serve_prefill_tokens_padded_total"] == 16 + 64 + 32
    # One live row, K = 1: a request of n tokens takes its first from
    # the prefill and n dispatches (n - 1 steps and the one the
    # pipeline had already sent when the last token was read).
    assert m["serve_decode_ticks_total"] == 5 + 6 + 7
    assert m["serve_decode_row_steps_total"] == 5 + 6 + 7
    # Clean intervals: only dispatches two or more after an admission.
    assert 0 < m["serve_decode_clean_steps_total"] < 18
    assert m["serve_decode_clean_seconds_total"] > 0.0


def test_boot_gauges_are_set_once_ready(served):
    m = served["warm"]
    assert m["serve_boot_load_seconds"] > 0.0
    assert m["serve_boot_warmup_seconds"] > 0.0
    assert m["serve_boot_compile_seconds"] > 0.0
    assert m["serve_boot_warmup_seconds"] <= (
        m["serve_loop_seconds_total"] + 1.0)
    assert served["live"]["serve_boot_programs_total"] == (
        m["serve_boot_programs_total"])


def test_rows_are_counted_where_two_requests_decode_together():
    sched = _scheduler()
    try:
        outs = []
        threads = [threading.Thread(
            target=lambda p=p: outs.append(_generate(sched, p, 24)))
            for p in ("first of two", "second of two")]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
        assert len(outs) == 2
        time.sleep(0.3)
        m = sched.metrics_snapshot()
    finally:
        sched.stop()
    steps = (m["decode_fused_steps_total"]
             + m["serve_decode_ticks_total"] - m["decode_fused_ticks_total"])
    assert steps <= m["serve_decode_row_steps_total"] <= 2 * steps
    # The two arrived within the collection window more often than not;
    # either way an admission carried at least one request.
    assert 1 <= m["serve_admit_batches_total"] <= 2
    assert m["serve_admitted_total"] == 2


# -- on the profiler's clock -------------------------------------------------------

def test_profile_holds_the_phases_and_gaps_take_their_names(tmp_path):
    from jax.profiler import ProfileData

    from benchmark import trace_reduce
    sched = _scheduler(num_slots=2)
    try:
        _generate(sched, "compile everything first", 12)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
        try:
            _generate(sched, "a few ticks under the profiler", 12)
        finally:
            jax.profiler.stop_trace()
    finally:
        sched.stop()
    planes = ProfileData.from_file(
        trace_reduce.find_xplane(str(tmp_path))).planes
    starts, ends, names = trace_reduce._host_events(planes)
    by_phase = {}
    for s, e, n in zip(starts, ends, names):
        if ":sched." in n:
            by_phase.setdefault(n.split(":", 1)[1], []).append((s, e))
    for name in ("sched.decode_dispatch", "sched.readback", "sched.admit",
                 "sched.stream"):
        assert by_phase.get(name), (name, sorted(by_phase))
    # Keyword arguments travel as the event's stats, not in its name.
    assert all("#" not in n for n in by_phase)
    # A gap inside a phase is given to that phase by the benchmark's own
    # attribution; nested phases: the shortest that covers it.
    gaps = [(s + (e - s) * 0.25, s + (e - s) * 0.75)
            for s, e in (by_phase["sched.readback"][0],
                         by_phase["sched.decode_dispatch"][0],
                         by_phase["sched.admit"][0])]
    got = trace_reduce.attribute_gaps(gaps, (starts, ends, names))
    assert "(no host event)" not in got
    assert any(k.endswith(":sched.readback") for k in got)
    # With only the phases to read, every gap takes a phase's name (the
    # runtime's own events inside a dispatch are shorter and win there).
    only = [i for i, n in enumerate(names) if ":sched." in n]
    got = trace_reduce.attribute_gaps(
        gaps, (starts[only], ends[only], [names[i] for i in only]))
    assert sorted(k.split(":", 1)[1] for k in got) == [
        "sched.admit", "sched.decode_dispatch", "sched.readback"]


# -- the watchdog and warm-up ------------------------------------------------------

def test_cold_warmup_is_no_stall_and_a_later_stall_names_its_phase(tmp_path):
    sched = _scheduler(num_slots=2, loop_budget_ms=40.0)
    path = str(tmp_path / "flight.json")
    sched._flight.path = path
    fp.disarm_all()
    try:
        # A "compile": every admission job of the warm-up takes four
        # budgets on the loop thread.
        fp.arm("serve.scheduler.admit", "delay:160")
        sched.warmup(prompt_buckets=(16,), windows=(128,))
        fp.disarm_all()
        m = sched.metrics_snapshot()
        assert sched.ready
        assert m["serve_loop_warmup_seconds_total"] >= 0.3
        assert m["loop_stall_ms"] == 0 and m["loop_stall_last_ms"] == 0
        assert m["serve_flight_dumps_total"] == 0
        assert not os.path.exists(path)
        assert "stall_enter" not in [e["kind"]
                                     for e in sched.flight_snapshot()]
        # Once ready, the same delay in a decode dispatch is a stall.
        fp.arm("serve.scheduler.dispatch", "delay:160")
        _generate(sched, "stall probe", 3)
        fp.disarm_all()
        deadline = time.monotonic() + 10.0
        while (time.monotonic() < deadline and not
               sched.metrics_snapshot()["serve_flight_dumps_total"]):
            time.sleep(0.05)
        m = sched.metrics_snapshot()
        assert m["serve_flight_dumps_total"] >= 1
        assert m["loop_stall_ms"] >= 40.0
        with open(path) as fh:
            doc = json.load(fh)
        stall = next(e for e in doc["events"] if e["kind"] == "stall_enter")
        assert stall["phase"] == "decode_dispatch"
    finally:
        fp.disarm_all()
        sched.stop()


def test_warmup_job_after_ready_still_counts_as_a_stall():
    """A background warm-up on a serving scheduler stalls live streams:
    only the boot's own warm-up is exempt."""
    sched = _scheduler(num_slots=2, loop_budget_ms=40.0)
    fp.disarm_all()
    try:
        assert sched.ready          # never warmed: ready at once
        from p2p_llm_chat_tpu.serve.scheduler import _WarmupJob
        job = _WarmupJob(lambda: time.sleep(0.16), threading.Event())
        sched._admit_q.put(job)
        assert job.done.wait(timeout=10)
        deadline = time.monotonic() + 5.0
        while (time.monotonic() < deadline
               and not sched.metrics_snapshot()["loop_stall_ms"]):
            time.sleep(0.02)
        assert sched.metrics_snapshot()["loop_stall_ms"] >= 40.0
    finally:
        sched.stop()


# -- names that are contracts ------------------------------------------------------

def _calls(path: str, attr: str) -> list:
    with open(os.path.join(ROOT, path)) as f:
        tree = ast.parse(f.read())
    return [n for n in ast.walk(tree)
            if isinstance(n, ast.Call) and isinstance(n.func, ast.Attribute)
            and n.func.attr == attr]


@pytest.mark.parametrize("path,count", [
    ("p2p_llm_chat_tpu/ops/quant_mm.py", 8),
    ("p2p_llm_chat_tpu/ops/paged_attention.py", 4)])
def test_every_pallas_call_passes_a_literal_name(path, count):
    calls = _calls(path, "pallas_call")
    assert len(calls) == count
    names = []
    for call in calls:
        kw = {k.arg: k.value for k in call.keywords}
        assert isinstance(kw.get("name"), ast.Constant), (path, call.lineno)
        names.append(kw["name"].value)
    assert all(isinstance(n, str) and n.replace("_", "").isalnum()
               for n in names)
    assert len(set(names)) == len(names)


def test_every_scheduler_program_is_named_by_its_kind():
    kinds = ("prefill_", "decode_", "spec_", "kv_")
    jits = [c for c in _calls("p2p_llm_chat_tpu/serve/scheduler.py", "jit")
            if isinstance(c.func.value, ast.Name) and c.func.value.id == "jax"]
    assert len(jits) >= 14
    for call in jits:
        fn = call.args[0]
        assert isinstance(fn, ast.Name), (
            f"scheduler.py:{call.lineno}: jax.jit of something other than "
            "a named function")
        assert fn.id.startswith(kinds), (call.lineno, fn.id)


def test_a_lowered_program_carries_its_kind():
    """What the name is for: the module a trace shows is jit_<name>."""
    sched = _scheduler(num_slots=2)
    try:
        prog = sched._decode_for(128)
        args = (sched._params, sched._next_dev, sched._cache,
                sched._active_dev, sched._temps_dev, sched._top_ks_dev,
                sched._top_ps_dev, sched._keys, sched._ring_dev,
                sched._rps_dev)
        text = prog.lower(*args).as_text()
    finally:
        sched.stop()
    assert "module @jit_decode_step" in text
