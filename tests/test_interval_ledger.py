"""The decode-interval ledger (obs/intervals.py) and what the scheduler
hangs on it: every dispatch-to-dispatch interval booked to exactly one
class, the older readings (decode_stall_ms, decode_wall_ms, the two
clean counters) as outputs of the same call with their old values, a
traced request's own differences on its spans, and the compile clock
heard after ready.

The ledger reads no clock, so its tests are scripts: ("d", dt, K) a
decode dispatch dt seconds after the last event, ("s", dt) a speculative
tick, ("cut", cls) admission work dispatched, ("rest",) no row decoding.
"""

import random
import threading
import time

import pytest

import jax
import jax.numpy as jnp

from p2p_llm_chat_tpu.loadgen.report import _dominant_phase, _span_phase
from p2p_llm_chat_tpu.models import llama
from p2p_llm_chat_tpu.models.configs import get_config
from p2p_llm_chat_tpu.obs.intervals import (ADMIT, CHUNK, CLASSES, CLEAN,
                                            PADDED, IntervalLedger)
from p2p_llm_chat_tpu.obs.phase import compile_clock
from p2p_llm_chat_tpu.obs.trace import TraceStore
from p2p_llm_chat_tpu.serve.backend import (GenerateOptions, GenerateRequest,
                                            RequestStats)
from p2p_llm_chat_tpu.serve.scheduler import BatchScheduler, _WarmupJob
from p2p_llm_chat_tpu.tokenizer import ByteTokenizer


class _Player:
    """A ledger and the clock its script moves."""

    def __init__(self):
        self.led, self.t = IntervalLedger(), 50.0

    def play(self, script):
        led = self.led
        for ev in script:
            if ev[0] == "d":
                self.t += ev[1]
                led.note(self.t, ev[2])
            elif ev[0] == "s":
                self.t += ev[1]
                led.note(self.t, 0)
            elif ev[0] == "cut":
                led.cut(ev[1])
            else:
                led.rest()
        return led


def _play(script):
    return _Player().play(script)


D = ("d", 0.01, 2)      # a decode dispatch of two steps, 10 ms on


def _booked(led):
    return {CLASSES[c]: (round(led.seconds[c], 6), led.steps[c],
                         led.intervals[c])
            for c in range(4) if led.intervals[c]}


# name -> (script, what is booked: class -> (seconds, steps, intervals)).
# The first two dispatches of a script book nothing: the first has no
# interval behind it, the second no dispatch it waited for.
SCRIPTS = {
    "clean": ([D] * 5, {"clean": (0.03, 6, 3)}),
    "a chunk opens an episode of three": (
        [D, D, D, ("cut", CHUNK), D, D, D, D],
        {"clean": (0.02, 4, 2), "chunk": (0.03, 6, 3)}),
    "a padded chunk is a class of its own": (
        [D, D, ("cut", PADDED), D, D, D, D],
        {"clean": (0.01, 2, 1), "padded": (0.03, 6, 3)}),
    "an admission or a wake": (
        [D, D, ("cut", ADMIT), D, D, D, D, D],
        {"clean": (0.02, 4, 2), "admit": (0.03, 6, 3)}),
    "two kinds in one iteration go to the dearest": (
        [D, D, ("cut", PADDED), ("cut", ADMIT), D, D, D, D],
        {"clean": (0.01, 2, 1), "admit": (0.03, 6, 3)}),
    "an episode inside a dearer one waits its turn": (
        [D, D, ("cut", CHUNK), D, ("cut", PADDED), D, D, D, D],
        {"clean": (0.01, 2, 1), "chunk": (0.03, 6, 3),
         "padded": (0.01, 2, 1)}),
    "a dearer one inside takes over": (
        [D, D, ("cut", PADDED), D, ("cut", ADMIT), D, D, D, D],
        {"clean": (0.01, 2, 1), "padded": (0.01, 2, 1),
         "admit": (0.03, 6, 3)}),
    "a chunk every iteration leaves nothing clean": (
        [D, D] + [("cut", CHUNK), D] * 4, {"chunk": (0.04, 8, 4)}),
    "a valley is booked nowhere and still holds": (
        [D, D, ("cut", CHUNK), D, ("d", 0.3, 2), D, D],
        {"clean": (0.01, 2, 1), "chunk": (0.02, 4, 2)}),
    "steps are the dispatch's it waited for": (
        [("d", 0.01, 4), ("d", 0.01, 1), ("d", 0.02, 3), ("d", 0.03, 2)],
        {"clean": (0.05, 5, 2)}),
    "a speculative tick's wall is nobody's step": (
        [D, D, D, ("s", 0.01), D, D, D],
        {"clean": (0.02, 4, 2)}),
}


@pytest.mark.parametrize("name", sorted(SCRIPTS))
def test_each_interval_is_booked_to_one_class(name):
    script, want = SCRIPTS[name]
    led = _play(script)
    assert _booked(led) == want


def test_dispatches_are_counted_by_class_when_their_interval_closes():
    p = _Player()
    led = p.play([D, D, ("cut", PADDED), ("cut", PADDED), ("cut", ADMIT)])
    assert led.dispatches == [0, 0, 0, 0]       # noted, not yet closed
    p.play([D, ("cut", CHUNK), D, D])
    assert led.dispatches == [0, 2, 1, 1]
    seconds, steps, steps_cut, chunks, padded, admits = led.totals()
    assert (chunks, padded, admits) == (1, 2, 1)
    assert steps == steps_cut == 6 and seconds == pytest.approx(0.03)


class _Parent:
    """The three readings as they stood before the ledger
    (serve/scheduler.py at PR 50: _note_admission_gap, the _wall_hist
    site of _dispatch_tick, _note_clean_interval), line for line."""

    def __init__(self):
        self.clean_s, self.clean_steps, self.clean_hold = 0.0, 0, 0
        self.prev_k, self.last_dispatch, self.last_decode_t = 0, None, None
        self.admit_since_tick, self.stall_ms, self.wall = False, 0.0, []

    def note_admission_gap(self, now):
        if self.last_decode_t is not None and self.admit_since_tick:
            gap = (now - self.last_decode_t) * 1e3
            if gap > self.stall_ms:
                self.stall_ms = gap
        self.last_decode_t = now
        self.admit_since_tick = False

    def dispatch_tick(self, now, K):
        admitted = self.admit_since_tick
        self.note_admission_gap(now)
        last = self.last_dispatch
        if last is not None and now - last[0] < 0.25:
            self.wall.append((now - last[0]) * 1e3 / last[1])
        if admitted:
            self.clean_hold = 2
        elif self.clean_hold:
            self.clean_hold -= 1
        elif last is not None and self.prev_k and now - last[0] < 0.25:
            self.clean_s += now - last[0]
            self.clean_steps += self.prev_k
        self.prev_k = last[1] if last is not None else 0
        self.last_dispatch = (now, K)

    def spec_tick(self, now):
        self.last_dispatch = None
        self.note_admission_gap(now)


def _random_script(seed, n=400):
    rng = random.Random(seed)
    out = []
    for _ in range(n):
        r = rng.random()
        if r < 0.25:
            out.append(("cut", rng.choice((PADDED, CHUNK, ADMIT))))
        elif r < 0.29:
            out.append(("s", rng.choice((0.004, 0.02))))
        elif r < 0.32:
            out.append(("rest",))
        else:
            out.append(("d", rng.choice((0.003, 0.011, 0.04, 0.26, 0.3)),
                        rng.choice((1, 2, 4))))
    return out


@pytest.mark.parametrize("seed", range(6))
def test_the_old_readings_keep_their_values(seed):
    """serve_decode_clean_*, decode_stall_ms and decode_wall_ms's
    samples, on a few hundred random events, against the code they
    replaced; and the four classes hold every interval the old wall
    reservoir sampled that had a dispatch to wait for."""
    script = _random_script(seed)
    led, old, t = IntervalLedger(), _Parent(), 50.0
    for ev in script:
        if ev[0] == "d":
            t += ev[1]
            led.note(t, ev[2])
            old.dispatch_tick(t, ev[2])
        elif ev[0] == "s":
            t += ev[1]
            led.note(t, 0)
            old.spec_tick(t)
        elif ev[0] == "cut":
            led.cut(ev[1])
            old.admit_since_tick = True
        else:
            led.rest()
            old.last_decode_t = None
    assert led.seconds[CLEAN] == pytest.approx(old.clean_s, abs=1e-12)
    assert led.steps[CLEAN] == old.clean_steps and old.clean_steps > 0
    assert led.stall_ms == old.stall_ms and old.stall_ms > 0
    assert led.wall_hist.count == len(old.wall)
    assert led.wall_hist.sum == pytest.approx(sum(old.wall))
    assert sum(led.intervals) <= len(old.wall)
    assert all(led.intervals[c] for c in range(4))


def test_the_stall_gauge_is_the_longest_interval_work_was_noted_in():
    p = _Player()
    led = p.play([D, D, ("cut", CHUNK), ("d", 0.04, 2), ("d", 0.09, 2)])
    assert led.stall_ms == pytest.approx(40.0)
    # No row decoding: the next admission stalled nobody.
    p.play([("rest",), ("cut", ADMIT), ("d", 5.0, 2)])
    assert led.stall_ms == pytest.approx(40.0)
    # A speculative tick emits tokens too, and closes an interval.
    p.play([("cut", CHUNK), ("s", 0.07)])
    assert led.stall_ms == pytest.approx(70.0)
    led.reset_stall()
    assert led.stall_ms == 0.0
    p.play([("cut", CHUNK), ("d", 0.5, 2)])
    assert led.stall_ms == 0.0      # the reset forgot the last dispatch


def test_the_wall_reservoir_samples_ms_a_step_and_skips_valleys():
    led = _play([("d", 0.01, 4), ("d", 0.02, 2), ("d", 0.3, 2),
                 ("d", 0.03, 1)])
    # 20 ms over the 4 steps before it, 30 ms over 2; not the valley.
    assert led.wall_hist.count == 2
    assert led.wall_hist.sum == pytest.approx(5.0 + 15.0)
    assert led.wall_hist.percentile(50) in (pytest.approx(5.0),
                                            pytest.approx(15.0))


@pytest.mark.parametrize("seed", range(3))
def test_a_requests_cut_and_clean_walls_add_up_to_its_decode_wall(seed):
    """Between two dispatches with no valley and no speculative tick
    between them, the differences of the ledger's totals are the wall,
    to the millisecond: what the scheduler records as sched.decode.cut
    and what is left of sched.decode beside it."""
    rng = random.Random(seed)
    p = _Player()
    led = p.play([D, D, D])
    first, clean0 = led.totals(), led.seconds[CLEAN]
    script = []
    for _ in range(300):
        if rng.random() < 0.3:
            script.append(("cut", rng.choice((PADDED, CHUNK, ADMIT))))
        script.append(("d", rng.choice((0.004, 0.013, 0.05)), 2))
    p.play(script)
    wall = sum(ev[1] for ev in script if ev[0] == "d")
    cut = led.totals()[0] - first[0]
    clean = led.seconds[CLEAN] - clean0
    assert 0.0 < cut < wall and clean > 0.0
    assert round((cut + clean) * 1e3) == round(wall * 1e3)
    assert led.totals()[1] - first[1] == 600     # two steps a dispatch


def test_the_cut_span_is_no_phase_of_a_breach():
    assert _span_phase("sched.decode.cut") is None
    assert _span_phase("sched.decode") == "decode"
    spans = [{"name": "sched.queue_wait", "dur_ms": 150.0},
             {"name": "sched.decode", "dur_ms": 100.0},
             {"name": "sched.decode.cut", "dur_ms": 90.0}]
    assert _dominant_phase(spans) == "queue_wait"


# -- on a tiny scheduler -------------------------------------------------------

CFG = get_config("tiny")
PARAMS = llama.init_params(CFG, jax.random.PRNGKey(0), dtype=jnp.float32)
TOK = ByteTokenizer(vocab_size=CFG.vocab_size)
NEW_SERIES = (
    "serve_decode_clean_intervals_total",
    "serve_decode_cut_chunk_seconds_total",
    "serve_decode_cut_chunk_steps_total",
    "serve_decode_cut_padded_seconds_total",
    "serve_decode_cut_padded_steps_total",
    "serve_decode_cut_admit_seconds_total",
    "serve_decode_cut_admit_steps_total",
    "serve_compile_seconds_total", "serve_compiles_total")


def _request(prompt, n, trace_id=""):
    return GenerateRequest(
        prompt=prompt, trace_id=trace_id, trace_sampled=bool(trace_id),
        options=GenerateOptions(max_tokens=n, temperature=0.0, seed=1))


@pytest.fixture(scope="module")
def traced():
    """A warmed scheduler with a span store. A long request decodes
    while a 70-token prompt climbs its four-chunk ladder (bucket 128 at
    a chunk of 32: the last chunk lies past the prompt) and a short one
    admits single-shot; then an unsampled request; then a program
    compiled on the loop thread after ready."""
    sched = BatchScheduler(PARAMS, CFG, TOK, num_slots=4, max_seq=256,
                           prefill_chunk=32, decode_fuse_max=1)
    store = TraceStore()
    sched.set_trace_store(store)
    released = []
    release = sched._release

    def _release(row):
        released.append(sched._slots[row])
        release(row)
    sched._release = _release
    try:
        sched.warmup(prompt_buckets=(16, 32, 128))
        warm = sched.metrics_snapshot()
        flight_warm = [e["kind"] for e in sched.flight_snapshot()]
        out = {}

        def _run(key, prompt, n, tid):
            out[key] = "".join(sched.submit(_request(prompt, n, tid),
                                            RequestStats()))
        long_ = threading.Thread(target=_run,
                                 args=("long", "a" * 10, 100, "a1" * 8))
        long_.start()
        deadline = time.monotonic() + 60
        while not sched._any_active() and time.monotonic() < deadline:
            time.sleep(0.005)
        _run("ladder", "b" * 69, 4, "b2" * 8)
        _run("single", "c" * 12, 4, "c3" * 8)
        long_.join(timeout=120)
        _run("unsampled", "d" * 12, 4, "")
        job = _WarmupJob(
            lambda: jax.jit(lambda x: x * 7.125 - 0.375)(
                jnp.ones((5,))).block_until_ready(), threading.Event())
        sched._admit_q.put(job)
        assert job.done.wait(timeout=60) and job.err is None
        time.sleep(0.3)
        live = sched.metrics_snapshot()
        flight = sched.flight_snapshot()
    finally:
        sched.stop()
    return {"warm": warm, "live": live, "flight": flight,
            "flight_warm": flight_warm, "store": store, "out": out,
            "released": released}


def _spans(traced, tid):
    return {s["name"]: s for s in traced["store"].get(tid)}


def test_the_series_are_nine_and_flat(traced):
    for k in NEW_SERIES:
        assert k in traced["live"], k
    cut = [k for k in traced["live"] if k.startswith("serve_decode_cut_")]
    assert sorted(cut) == sorted(k for k in NEW_SERIES if "_cut_" in k)


@pytest.mark.parametrize("tid", ["a1" * 8, "b2" * 8, "c3" * 8])
def test_a_traced_requests_cut_span_sits_beside_its_decode_span(traced, tid):
    names = [s["name"] for s in traced["store"].get(tid)]
    at = names.index("sched.decode")
    assert names[at + 1] == "sched.decode.cut"
    assert names.count("sched.decode") == names.count("sched.decode.cut") == 1
    spans = _spans(traced, tid)
    dec, cut = spans["sched.decode"], spans["sched.decode.cut"]
    assert cut["t0_ms"] == dec["t0_ms"]
    assert 0.0 <= cut["dur_ms"] <= dec["dur_ms"]
    assert "meta" not in cut
    meta = dec["meta"]
    assert set(meta) == {"tokens", "row", "steps", "steps_cut", "chunks",
                         "padded", "admits"}
    assert 0 <= meta["steps_cut"] <= meta["steps"]


def test_the_long_request_carries_what_cut_into_it(traced):
    meta = _spans(traced, "a1" * 8)["sched.decode"]["meta"]
    # The ladder's four chunks, one of them past the prompt, and the
    # short prompt's single-shot admission, all while it decoded.
    assert meta["chunks"] == 3 and meta["padded"] == 1
    assert meta["admits"] >= 1
    assert meta["steps_cut"] >= 5
    assert meta["steps"] <= meta["tokens"] + 3
    assert _spans(traced, "a1" * 8)["sched.decode.cut"]["dur_ms"] > 0.0


def test_a_ladders_prefill_span_says_what_the_ladder_was(traced):
    meta = _spans(traced, "b2" * 8)["sched.prefill"]["meta"]
    assert {k: meta[k] for k in ("chunks", "padded", "bucket", "shared")} \
        == {"chunks": 3, "padded": 1, "bucket": 128, "shared": 1}
    single = _spans(traced, "c3" * 8)["sched.prefill"]["meta"]
    assert set(single) == {"tokens", "row"}


def test_an_unsampled_request_leaves_nothing_on_its_slot(traced):
    unsampled = [s for s in traced["released"] if not s.req.trace_sampled]
    assert len(unsampled) == 1 and unsampled[0].cut0 is None
    sampled = [s for s in traced["released"] if s.req.trace_sampled]
    assert len(sampled) == 3
    assert all(isinstance(s.cut0, tuple) and len(s.cut0) == 6
               for s in sampled)


def test_the_classes_and_the_older_gauges_moved_with_the_traffic(traced):
    warm, live = traced["warm"], traced["live"]
    for c in ("chunk", "padded", "admit"):
        steps = (live[f"serve_decode_cut_{c}_steps_total"]
                 - warm[f"serve_decode_cut_{c}_steps_total"])
        seconds = (live[f"serve_decode_cut_{c}_seconds_total"]
                   - warm[f"serve_decode_cut_{c}_seconds_total"])
        assert steps > 0 and seconds > 0.0, c
    assert live["serve_decode_clean_intervals_total"] > 0
    assert (live["serve_decode_clean_steps_total"]
            >= live["serve_decode_clean_intervals_total"])
    # One step a dispatch here: every booked interval is one step.
    booked = (live["serve_decode_clean_steps_total"]
              + sum(live[f"serve_decode_cut_{c}_steps_total"]
                    for c in ("chunk", "padded", "admit")))
    assert 0 < booked <= live["serve_decode_ticks_total"]
    assert live["decode_stall_ms"] > 0.0
    assert live["decode_wall_ms"] > 0.0


def test_a_compile_after_ready_is_heard_and_named(traced):
    warm, live = traced["warm"], traced["live"]
    assert "compile" not in traced["flight_warm"]
    assert warm["serve_compiles_total"] > 0
    assert (warm["serve_compile_seconds_total"]
            >= warm["serve_boot_compile_seconds"] > 0.0)
    assert live["serve_compiles_total"] > warm["serve_compiles_total"]
    assert (live["serve_compile_seconds_total"]
            >= warm["serve_compile_seconds_total"])
    # The boot's gauge is frozen at ready.
    assert live["serve_boot_compile_seconds"] \
        == warm["serve_boot_compile_seconds"]
    events = [e for e in traced["flight"] if e["kind"] == "compile"]
    assert events and events[-1]["n"] >= 1 and events[-1]["it"] > 0
    assert events[-1]["seconds"] >= 0.0


def test_the_compile_clock_counts_what_it_hears():
    clk = compile_clock()
    n, s = clk.events, clk.seconds
    jax.jit(lambda x: x * 9.0625 + 1.75)(jnp.ones((7,))).block_until_ready()
    assert clk.events > n and clk.seconds > s
