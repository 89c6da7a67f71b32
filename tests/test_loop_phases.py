"""The scheduler loop and the boot path measure themselves.

- the phase timer (obs/phase.py): nested self time, wall and CPU
  alike, the parts of a phase, the current and the slowest phase;
- a tiny scheduler: the eight phases, "other" among them, close on the
  loop's wall, a phase's seconds hold its parts', the counts at the
  dispatch sites are exact for prompts of known lengths and warm-up
  adds nothing to them, the boot gauges are set;
- the phases and their parts are on the profiler's clock: a CPU profile
  holds ``sched.*`` host events, a part under its phase's name, and the
  benchmark's gap attribution names them;
- a cold warm-up job over the loop budget is no stall, a stall after
  ready still is, and its ``stall_enter`` event names the phase;
- names are contracts: every ``pallas_call`` passes a literal ``name=``,
  every program the scheduler jits is named by its kind, and every call
  of one from the serving loop sits inside a ``launch`` mark.

All on the CPU: counts and control flow, never a device timing.
"""

import ast
import json
import os
import re
import threading
import time
import types

import pytest

import jax
import jax.numpy as jnp

from p2p_llm_chat_tpu.models import family_for, llama
from p2p_llm_chat_tpu.models.configs import get_config
from p2p_llm_chat_tpu.models.llama import KVCache
from p2p_llm_chat_tpu.obs import phase as phase_mod
from p2p_llm_chat_tpu.obs.phase import (CPU_EVERY, PARTS, PARTS_OF, PHASES,
                                        LoopPhases, compile_clock,
                                        process_age_s)
from p2p_llm_chat_tpu.serve.backend import (GenerateOptions, GenerateRequest,
                                            RequestStats)
from p2p_llm_chat_tpu.serve import scheduler as sched_mod
from p2p_llm_chat_tpu.serve.scheduler import BatchScheduler
from p2p_llm_chat_tpu.tokenizer import ByteTokenizer
from p2p_llm_chat_tpu.utils import failpoints as fp

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CFG = get_config("tiny")
PARAMS = llama.init_params(CFG, jax.random.PRNGKey(0), dtype=jnp.float32)
TOK = ByteTokenizer(vocab_size=CFG.vocab_size)
PHASE_SERIES = [f"serve_loop_{n}_seconds_total" for n in PHASES]
PART_NAMES = [f"{n}_{p}" for n, ps in PARTS_OF.items() for p in ps]
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
    """The wall (``t``) and the thread's CPU clock (``c``), moved by
    hand: ``work`` moves both, ``wait`` the wall alone."""

    def __init__(self) -> None:
        self.t = 100.0
        self.c = 7.0
        self.cpu_reads = 0

    def monotonic(self) -> float:
        return self.t

    def thread_time(self) -> float:
        self.cpu_reads += 1
        return self.c

    def work(self, s: float) -> None:
        self.t += s
        self.c += s

    def wait(self, s: float) -> None:
        self.t += s


@pytest.fixture()
def clock(monkeypatch):
    c = _Clock()
    monkeypatch.setattr(phase_mod, "time", types.SimpleNamespace(
        monotonic=c.monotonic, thread_time=c.thread_time))
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


def test_nested_marks_subtract_wall_and_cpu_alike(clock):
    ph = LoopPhases()
    with ph("other"):
        clock.work(0.5)
        with ph("admit"):
            clock.work(1.0)
            clock.wait(2.0)             # off the CPU inside admit itself
            with ph("readback"):
                clock.wait(4.0)         # blocked on the device
                clock.work(0.25)
            with ph("launch"):
                clock.work(0.125)
                clock.wait(0.5)         # blocked inside the runtime
        clock.wait(0.0625)
    want = {"other": (0.5625, 0.5), "admit": (3.0, 1.0),
            "readback": (4.25, 0.25), "admit.launch": (0.625, 0.125)}
    for name, (wall, cpu) in want.items():
        assert ph.seconds(name) == pytest.approx(wall), name
        assert ph.cpu(name) == pytest.approx(cpu), name
        assert ph.marks(name) == 1
    # A phase's total holds its parts' and not the phases inside it.
    assert ph.total("admit") == pytest.approx(3.625)
    assert ph.cpu_total("admit") == pytest.approx(1.125)
    assert ph.inclusive("admit") == pytest.approx(7.875)
    assert sum(ph.total(n) for n in PHASES) == pytest.approx(8.4375)
    assert sum(ph.cpu_total(n) for n in PHASES) == pytest.approx(1.875)


def test_the_cpu_clock_is_read_in_one_iteration_of_a_few(clock):
    """At every mark of that iteration (nested marks subtract whole)
    and at none of the others'; the wall of the marks it was read in is
    kept beside it, so their ratio is exact."""
    ph = LoopPhases()
    for it in range(2 * CPU_EVERY):
        ph.mark_iteration()
        before = clock.cpu_reads
        with ph("other"):
            clock.work(0.25)
            with ph("admit"):
                clock.work(1.0)
                clock.wait(1.0)
                with ph("launch"):
                    clock.work(0.5)
        assert clock.cpu_reads - before == (6 if it % CPU_EVERY == 0 else 0)
    assert ph.seconds("admit") == pytest.approx(2.0 * 2 * CPU_EVERY)
    assert ph.marks("admit.launch") == 2 * CPU_EVERY
    for name, wall, cpu in (("other", 0.25, 0.25), ("admit", 2.0, 1.0),
                            ("admit.launch", 0.5, 0.5)):
        assert ph.cpu_wall(name) == pytest.approx(2 * wall), name
        assert ph.cpu(name) == pytest.approx(2 * cpu), name
    assert ph.cpu_total("admit") == pytest.approx(3.0)
    assert ph.cpu_wall_total("admit") == pytest.approx(5.0)


@pytest.mark.parametrize("phase,part", [
    (n, p) for n in PHASES for p in PARTS])
def test_a_part_belongs_to_the_phase_around_it(clock, phase, part):
    """Timed under the phases that list it (PARTS_OF), under its
    phase's name; a no-op elsewhere: the time stays with the phase."""
    ph = LoopPhases()
    with ph(phase):
        clock.work(1.0)
        with ph("stream" if phase != "stream" else "readback"):
            # A phase in between does not adopt the part...
            clock.work(0.25)
        with ph(part, rows=2) as mark:
            clock.work(0.5)
            with ph("launch" if part != "launch" else "upload"):
                # ...and a part inside a part is its phase's too.
                clock.work(0.125)
    if part in PARTS_OF.get(phase, ()):
        assert mark.label == f"sched.{phase}.{part}"
        assert ph.marks(f"{phase}.{part}") == 1
        assert ph.seconds(f"{phase}.{part}") >= 0.5
        assert ph.seconds(phase) <= 1.0 + 0.125
    else:
        with pytest.raises(KeyError):
            ph.seconds(f"{phase}.{part}")
        assert ph.seconds(phase) >= 1.5
    assert ph.total(phase) == pytest.approx(1.625)
    assert ph.cpu_total(phase) == pytest.approx(1.625)
    assert ph._top is None


def test_phase_names_the_slowest_of_the_iteration(clock):
    ph = LoopPhases()
    assert ph.slowest == ""
    with ph("admit"):
        with ph("warmup"):
            clock.t += 3.0
        clock.t += 1.0
    assert ph._top is None
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
    assert ph._top is None


def test_phase_time_is_kept_when_the_body_raises(clock):
    ph = LoopPhases()
    with pytest.raises(RuntimeError):
        with ph("admit"):
            with ph("readback"):
                clock.t += 1.0
                raise RuntimeError("device reset")
    assert ph.seconds("readback") == pytest.approx(1.0)
    assert ph._top is None
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
    assert loop > 0.0 and m["serve_loop_iterations_total"] > 0
    # "other" is a phase: the eight are the loop's wall, less the few
    # statements an iteration runs outside its outermost mark.
    assert phases <= loop + 1e-6
    assert phases >= 0.99 * loop
    for k in PHASE_SERIES:
        assert m[k] > 0.0, k


@pytest.mark.parametrize("name", list(PHASES) + PART_NAMES)
def test_cpu_seconds_sit_beside_the_wall_and_never_pass_it(served, name):
    m = served["end"]
    wall = m[f"serve_loop_{name}_seconds_total"]
    cpu_wall = m[f"serve_loop_{name}_cpu_wall_seconds_total"]
    cpu = m[f"serve_loop_{name}_cpu_seconds_total"]
    # The CPU clock is read inside the wall clock's two reads, in some
    # of the marks: CPU <= the wall of those marks <= the wall of all
    # (a mark inside costs its outer one a clock read's worth of slack).
    assert 0.0 <= cpu <= cpu_wall + 5e-5, (name, cpu, cpu_wall)
    assert cpu_wall <= wall + 1e-9, (name, cpu_wall, wall)
    if name == "idle" and cpu_wall:
        assert cpu < 0.5 * cpu_wall     # a wait: the thread is off the CPU


@pytest.mark.parametrize("phase", sorted(PARTS_OF))
def test_a_phases_seconds_hold_its_parts(served, phase):
    """``serve_loop_<phase>_seconds_total`` means what it meant before
    the parts: the readers that divide it are the benchmark's."""
    m = served["end"]
    parts = [f"serve_loop_{phase}_{p}" for p in PARTS_OF[phase]]
    for p in parts:
        assert (m[p + "_marks_total"] > 0) == (m[p + "_seconds_total"] > 0)
    assert m[f"serve_loop_{phase}_launch_marks_total"] > 0
    total = m[f"serve_loop_{phase}_seconds_total"]
    named = sum(m[p + "_seconds_total"] for p in parts)
    assert named <= total + 1e-9
    if phase in ("admit", "prefill_chunk"):
        # The named parts hold the phase: what is left is bookkeeping.
        assert named >= 0.8 * total, (phase, named, total)


def test_launches_are_counted_by_kind_and_starved_ones_beside(served):
    warm, m = served["warm"], served["live"]
    d = {k: m[k] - warm[k] for k in m if k.startswith("serve_launch_")}
    # Three admissions, one of them a ladder of two chunks; 18 decode
    # dispatches (test_site_counters_are_exact).
    assert d["serve_launch_admit_total"] == 2
    assert d["serve_launch_prefill_chunk_total"] == 2
    assert d["serve_launch_decode_total"] == 18
    for kind in ("admit", "prefill_chunk", "decode"):
        assert 0 <= d[f"serve_launch_{kind}_starved_total"] <= (
            d[f"serve_launch_{kind}_total"])
    # Each arrived at an empty device: nothing was in flight.
    assert d["serve_launch_admit_starved_total"] == 2
    # The launch marks of the three feeding phases are those launches.
    for phase, kind in (("admit", "admit"), ("prefill_chunk",) * 2,
                        ("decode_dispatch", "decode")):
        assert m[f"serve_loop_{phase}_launch_marks_total"] == (
            d[f"serve_launch_{kind}_total"])


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
                 "sched.stream", "sched.other",
                 # The parts, under their phases' names.
                 "sched.admit.collect", "sched.admit.plan",
                 "sched.admit.build", "sched.admit.upload",
                 "sched.admit.launch", "sched.decode_dispatch.launch"):
        assert by_phase.get(name), (name, sorted(by_phase))
    # A part lies inside a mark of its phase.
    for part in ("sched.admit.launch", "sched.decode_dispatch.launch"):
        outer = by_phase[part.rsplit(".", 1)[0]]
        assert all(any(s0 <= s and e <= e0 for s0, e0 in outer)
                   for s, e in by_phase[part]), part
    # A part, and "other", is annotated over its self time only: it
    # covers no other mark (it would take every gap that spans two).
    for leaf in ("sched.other", "sched.admit.plan"):
        assert len(by_phase[leaf]) > len(by_phase["sched.admit"])
        for name, events in by_phase.items():
            if name != leaf:
                assert not any(s0 <= s and e <= e0 and e > s
                               for s0, e0 in by_phase[leaf]
                               for s, e in events), (leaf, name)
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
    # A gap shorter than the part it falls in names the part; one that
    # spans several names the phase around them.
    assert sorted(k.split(":", 1)[1].split(".")[1] for k in got) == [
        "admit", "decode_dispatch", "readback"]
    s, e = by_phase["sched.admit.launch"][0]
    got = trace_reduce.attribute_gaps(
        [(s + (e - s) * 0.25, s + (e - s) * 0.75)],
        (starts[only], ends[only], [names[i] for i in only]))
    assert [k.split(":", 1)[1] for k in got] == ["sched.admit.launch"]


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
        # The counts are the process's: a test of another file that
        # lands on this worker reads its own site's from zero.
        fp.reset_hits()
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
    ("p2p_llm_chat_tpu/ops/paged_attention.py", 6)])
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


@pytest.mark.parametrize("fn_name", [
    "_admit_chunk", "_start_prefill_carry", "_prefill_step",
    "_dispatch_prefill_chunk", "_admit_wake"])
def test_no_dispatch_uploads_array_by_array(fn_name):
    """Source-level: an admission goes up in one packed buffer, through
    ``_admit_upload`` alone, and a chunk uploads nothing: none of these
    says ``jnp.asarray`` or ``jax.device_put`` itself. (``_release``
    still uploads its row: handing it over as a host scalar did not
    shorten the launch on the chip, PERF.md §6, PR 36.)"""
    with open(os.path.join(
            ROOT, "p2p_llm_chat_tpu/serve/scheduler.py")) as f:
        tree = ast.parse(f.read())
    fn = next(n for n in ast.walk(tree)
              if isinstance(n, ast.FunctionDef) and n.name == fn_name)
    uploads = [(n.lineno, n.func.attr) for n in ast.walk(fn)
               if isinstance(n, ast.Call)
               and isinstance(n.func, ast.Attribute)
               and n.func.attr in ("asarray", "device_put")
               and isinstance(n.func.value, ast.Name)
               and n.func.value.id in ("jnp", "jax")]
    assert not uploads, (fn_name, uploads)


# The handles the scheduler keeps its compiled programs under, and the
# local names it calls them by.
_PROGRAM_ATTRS = ("_admit_j", "_admit_prefix_j", "_zero_row_j",
                  "_gather_pages_j", "_scatter_pages_j")
_PROGRAM_NAMES = ("prog", "decode_j", "spec_j")
_PROGRAM_MAKERS = ("_decode_for", "_decode_fused_for", "_spec_for",
                   "_spec_tree_for", "_wake_for", "_prefill_chunk_for")


def _is_program_call(call: ast.Call) -> bool:
    f = call.func
    if isinstance(f, ast.Attribute):
        return f.attr in _PROGRAM_ATTRS
    if isinstance(f, ast.Name):
        return f.id in _PROGRAM_NAMES
    # self._wake_for(w, S)(...): the maker's result called in place.
    return (isinstance(f, ast.Call) and isinstance(f.func, ast.Attribute)
            and f.func.attr in _PROGRAM_MAKERS)


def _is_launch_mark(node: ast.With) -> bool:
    return any(isinstance(i.context_expr, ast.Call)
               and i.context_expr.args
               and isinstance(i.context_expr.args[0], ast.Constant)
               and i.context_expr.args[0].value == "launch"
               for i in node.items)


def test_every_program_the_loop_calls_sits_inside_a_launch():
    """Source-level: in the functions that serve (warm-up's and the
    device probe's run under the ``warmup`` phase, whose parts are not
    timed), a call of a compiled program has a ``with ...("launch")``
    around it."""
    with open(os.path.join(
            ROOT, "p2p_llm_chat_tpu/serve/scheduler.py")) as f:
        tree = ast.parse(f.read())
    cls = next(n for n in tree.body if isinstance(n, ast.ClassDef)
               and n.name == "BatchScheduler")
    found, bare = [], []

    def walk(node, fn, marked):
        for child in ast.iter_child_nodes(node):
            m = marked or (isinstance(child, ast.With)
                           and _is_launch_mark(child))
            if isinstance(child, ast.Call) and _is_program_call(child):
                found.append(fn)
                if not m:
                    bare.append((fn, child.lineno))
            walk(child, fn, m)

    for fn in cls.body:
        if isinstance(fn, ast.FunctionDef) and not fn.name.startswith(
                ("_warm", "_probe_device_step", "__init__")):
            walk(fn, fn.name, False)
    assert not bare, bare
    # The sites ISSUE 34 lists, and the small ones beside them.
    assert set(found) >= {"_admit_chunk", "_admit_wake",
                          "_dispatch_prefill_chunk", "_dispatch_tick",
                          "_spec_tick", "_release", "_retain_session",
                          "_park_session", "_wake_install_kv"}
    assert len(found) >= 12


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


# -- a ladder's padded chunks: one cond, on the admission buffer -------------------

def _chunk_program_texts(family: str) -> dict:
    """offset -> (the StableHLO of the chunk program there, the index of
    the packed admission buffer among ``@main``'s arguments), for the
    first, a mid and the final chunk of a four-chunk bucket of
    ``family``'s test size."""
    cfg = get_config(family)
    params = family_for(cfg).init_params(cfg, jax.random.PRNGKey(0),
                                         dtype=jnp.float32)
    sched = BatchScheduler(params, cfg, ByteTokenizer(
        vocab_size=cfg.vocab_size), num_slots=2, max_seq=128,
        prefill_chunk=32)
    try:
        shapes = lambda tree: jax.tree.map(   # noqa: E731
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), tree)
        packed = jax.ShapeDtypeStruct((1, sched_mod._admit_layout(
            sched._cache.max_pages_per_row)[-1] + 128), jnp.int32)
        carried = [jax.eval_shape(lambda: KVCache.create(
            cfg, 1, 128, dtype=sched._dtype)),
            jax.eval_shape(lambda: sched._chunk_logits0(1))]
        installs = shapes([sched._keys, sched._next_dev, sched._temps_dev,
                           sched._top_ks_dev, sched._top_ps_dev,
                           sched._ring_dev, sched._rps_dev])
        texts = {}
        for off in (0, 64, 96):
            args = [shapes(sched._params), packed, *(carried if off else ()),
                    shapes(sched._cache), *(installs if off == 96 else ())]
            text = sched._prefill_chunk_for(0, 128, off, 32).lower(
                *args).as_text()
            # Where the buffer sits among @main's arguments (jit drops
            # those the program never reads): the one of its shape.
            at, = re.findall(
                r"%%arg(\d+): tensor<1x%dxi32>" % packed.shape[1], text)
            texts[off] = (text, int(at))
    finally:
        sched.stop()
    return texts


def _case_predicate_arguments(text: str) -> list[set]:
    """For every ``stablehlo.case`` of ``text``: the arguments of its
    function that its index is computed from, by walking the SSA
    definitions back (a call's result depends on all its operands)."""
    found = []
    for body in re.split(r"\n\s*func\.func ", text)[1:]:
        defs = {}
        for line in body.splitlines():
            m = re.match(r"\s*(%[\w#]+)(?::\d+)? = (.*)", line)
            if m:
                defs[m.group(1)] = set(re.findall(r"%[\w]+", m.group(2)))
        for m in re.finditer(r'"?stablehlo\.case"?\((%\w+)\)', body):
            seen, todo = set(), [m.group(1)]
            while todo:
                name = todo.pop()
                if name not in seen:
                    seen.add(name)
                    todo.extend(defs.get(name, ()))
            found.append({int(n[4:]) for n in seen if n.startswith("%arg")})
    return found


@pytest.mark.parametrize("family", ["tiny", "tiny-mellum2"])
def test_a_chunk_behind_the_first_runs_under_one_cond_on_the_buffer(family):
    """``mid`` and ``final``: exactly one ``stablehlo.case`` around the
    forward, and what decides it is computed from the packed admission
    buffer and from nothing else (no weight, no carry: the scheduler's
    arithmetic over the entries' lengths and rows). ``first`` always
    holds a real position and has none. ``final`` holds one more, the
    sampler's around its candidate sort (models/sampling.sample_batched,
    PR 52), decided by the entries' temperatures: the buffer again."""
    texts = _chunk_program_texts(family)
    assert "stablehlo.case" not in texts[0][0]
    for off, conds in ((64, 1), (96, 2)):
        text, packed_at = texts[off]
        assert _case_predicate_arguments(text) == [{packed_at}] * conds, off


def test_the_hybrid_familys_chunk_has_no_cond_of_its_own():
    """The test of padding is the scheduler's, for every family: the
    family that had it first (PR 49, for indexed models) has none."""
    with open(os.path.join(
            ROOT, "p2p_llm_chat_tpu/models/nemotron_h.py")) as f:
        tree = ast.parse(f.read())
    fn = next(n for n in tree.body if isinstance(n, ast.FunctionDef)
              and n.name == "prefill_chunk_counted")
    names = {n.attr for n in ast.walk(fn) if isinstance(n, ast.Attribute)}
    assert not names & {"is_indexed", "cond", "eval_shape"}, names
