"""The host's side off the scheduler's own marks (ISSUE 34):

- a thread held off the CPU by another that spins on the interpreter
  lock reads off-CPU time in a Python phase, and next to none alone;
- the hand-off from the loop's ``push`` to the HTTP thread's dequeue:
  the sum over a stream is the sum of dequeue minus put times, and it
  joins the scheduler's counters exactly once however the stream ends;
- the HTTP front's own spans, ``api.accept`` and ``api.first_write``:
  once a sampled request, never for an unsampled one.

All on the CPU: control flow and clocks, never a device timing.
"""

import json
import queue
import threading
import time
import types
import urllib.request

import jax
import jax.numpy as jnp
import pytest

from p2p_llm_chat_tpu.models import llama
from p2p_llm_chat_tpu.models.configs import get_config
from p2p_llm_chat_tpu.obs.phase import LoopPhases
from p2p_llm_chat_tpu.obs.trace import HEADER
from p2p_llm_chat_tpu.serve import scheduler as sched_mod
from p2p_llm_chat_tpu.serve.api import OllamaServer
from p2p_llm_chat_tpu.serve.backend import (GenerateOptions, GenerateRequest,
                                            RequestStats)
from p2p_llm_chat_tpu.serve.engine import TPUEngine
from p2p_llm_chat_tpu.serve.scheduler import (BatchScheduler, _Slot,
                                              _SlotStream)
from p2p_llm_chat_tpu.tokenizer import ByteTokenizer

CFG = get_config("tiny")
PARAMS = llama.init_params(CFG, jax.random.PRNGKey(0), dtype=jnp.float32)
TOK = ByteTokenizer(vocab_size=CFG.vocab_size)
HANDOFF = "serve_stream_handoff_seconds_total"
DELTAS = "serve_stream_deltas_total"


# -- wall less CPU is time off the CPU ---------------------------------------

def _python_work(n: int = 400_000) -> int:
    x = 0
    for i in range(n):
        x += i * i % 7
    return x


def _offcpu_share(spin: bool) -> float:
    """Share of a Python phase's wall that its thread was off the CPU,
    marked on a thread of its own (a LoopPhases belongs to one)."""
    ph = LoopPhases()
    stop = threading.Event()

    def spinner() -> None:
        while not stop.is_set():
            _python_work(20_000)

    def marked() -> None:
        with ph("other"):
            for _ in range(4):
                with ph("stream"):
                    _python_work()

    spinners = [threading.Thread(target=spinner, daemon=True)
                for _ in range(2 if spin else 0)]
    for t in spinners:
        t.start()
    worker = threading.Thread(target=marked)
    worker.start()
    worker.join(timeout=120)
    stop.set()
    for t in spinners:
        t.join(timeout=10)
    assert ph.marks("stream") == 4 and ph.cpu("stream") > 0.0
    return 1.0 - ph.cpu("stream") / ph.seconds("stream")


def test_a_spinning_thread_shows_as_off_cpu_time_in_a_python_phase():
    alone = _offcpu_share(spin=False)
    contended = _offcpu_share(spin=True)
    # Two spinners on the interpreter lock: the marked thread runs about
    # a third of the time. Alone it has the lock to itself (a busy test
    # machine may still deschedule it now and then).
    assert contended > 0.3, (alone, contended)
    assert alone < contended - 0.2, (alone, contended)


# -- the hand-off -------------------------------------------------------------

@pytest.fixture()
def stopped():
    """A scheduler whose loop thread has ended: _consume and the
    counters are driven by hand, on one thread, against a clock moved by
    hand."""
    sched = BatchScheduler(PARAMS, CFG, TOK, num_slots=2, max_seq=64)
    sched.stop()
    return sched


@pytest.fixture()
def clock(monkeypatch):
    c = types.SimpleNamespace(t=50.0)
    monkeypatch.setattr(sched_mod, "time", types.SimpleNamespace(
        monotonic=lambda: c.t, monotonic_ns=time.monotonic_ns))
    return c


def _slot(stats=None) -> _Slot:
    return _Slot(req=GenerateRequest(prompt="x"), stats=stats,
                 out_q=queue.Queue(), seed=0)


@pytest.mark.parametrize("ending", ["finish", "fail", "cancel", "close"])
def test_handoff_is_dequeue_less_put_and_is_folded_once(stopped, clock,
                                                        ending):
    sched, stats = stopped, RequestStats()
    slot = _slot(stats)
    stream = _SlotStream(sched._consume(slot), slot)
    clock.t = 51.0
    slot.push("a")
    slot.push("")                       # nothing to hand over: no delta
    clock.t = 51.5
    slot.push("b")
    assert stats.first_push_t == 51.0
    clock.t = 52.0                      # the HTTP thread wakes: a burst
    assert next(stream) == "ab"
    assert slot.handoff_n == 2
    assert slot.handoff_s == pytest.approx((52.0 - 51.0) + (52.0 - 51.5))
    clock.t = 53.0
    slot.push("c")
    clock.t = 53.25
    # Nothing joins the counters while the stream is open.
    assert sched.metrics_snapshot()[DELTAS] == 0
    if ending == "finish":
        slot.finish()
        assert list(stream) == ["c"]
    elif ending == "fail":
        slot.fail("device reset")
        with pytest.raises(RuntimeError, match="device reset"):
            list(stream)
    elif ending == "cancel":
        assert next(stream) == "c"
        stream.close()                  # the client left mid-stream
        assert slot.cancelled.is_set()
    else:
        stream.close()                  # closed with a delta still queued
    got = 3 if ending != "close" else 2
    want = 1.5 + (0.25 if got == 3 else 0.0)
    for _ in range(2):                  # a second close folds nothing
        m = sched.metrics_snapshot()
        assert m[DELTAS] == got
        assert m[HANDOFF] == pytest.approx(want)
        stream.close()


def test_a_stream_closed_before_it_started_folds_nothing(stopped, clock):
    slot = _slot()
    stream = _SlotStream(stopped._consume(slot), slot)
    slot.push("never read")
    stream.close()
    del stream
    m = stopped.metrics_snapshot()
    assert m[DELTAS] == 0 and m[HANDOFF] == 0.0


def test_streams_fold_their_handoff_as_they_end():
    sched = BatchScheduler(PARAMS, CFG, TOK, num_slots=2, max_seq=64)
    try:
        n = 0
        for prompt in ("first", "second"):
            req = GenerateRequest(prompt=prompt, options=GenerateOptions(
                max_tokens=6, temperature=0.0, seed=1))
            n += sum(1 for _ in sched.submit(req, RequestStats()))
            m = sched.metrics_snapshot()
            # Bursts are joined for the client; the counter is of deltas.
            assert m[DELTAS] >= n > 0
            assert 0.0 < m[HANDOFF] < 5.0
    finally:
        sched.stop()


# -- the HTTP front's own spans -------------------------------------------------

@pytest.fixture(scope="module")
def server():
    eng = TPUEngine(PARAMS, CFG, TOK, num_slots=2, max_seq=64)
    srv = OllamaServer(eng, addr="127.0.0.1:0").start()
    yield srv
    srv.stop()
    eng.scheduler.stop()


def _post(srv, path: str, body: dict, trace: str) -> list:
    req = urllib.request.Request(
        srv.url + path, data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json", HEADER: trace})
    with urllib.request.urlopen(req, timeout=60) as r:
        return [json.loads(ln) for ln in r.read().splitlines() if ln]


@pytest.mark.parametrize("path,body,sampled", [
    ("/api/generate", {"prompt": "hello"}, True),
    ("/api/chat", {"messages": [{"role": "user", "content": "hi"}]}, True),
    ("/api/generate", {"prompt": "hello"}, False),
    ("/api/chat", {"messages": [{"role": "user", "content": "hi"}]}, False),
])
def test_front_spans_once_a_sampled_request_never_unsampled(server, path,
                                                            body, sampled):
    tid = f"{abs(hash((path, sampled))) % (1 << 60):016x}"
    lines = _post(server, path, {**body, "options": {"num_predict": 4}},
                  f"{tid};s={1 if sampled else 0}")
    assert lines[-1]["done"] is True and len(lines) >= 2
    spans = {}
    for s in server.trace.get(tid):
        spans.setdefault(s["name"], []).append(s)
    if not sampled:
        assert spans == {}
        return
    assert len(spans["api.request"]) == 1
    for name in ("api.accept", "api.first_write"):
        assert len(spans[name]) == 1, (name, sorted(spans))
        assert spans[name][0]["meta"]["parent"] == "api.request"
        assert 0.0 <= spans[name][0]["dur_ms"] < 60_000
    # accept ends before the first line is written, inside the request.
    acc, fw, env = (spans[k][0] for k in
                    ("api.accept", "api.first_write", "api.request"))
    assert acc["t0_ms"] <= env["t0_ms"] + 1e-3      # starts before parse
    assert acc["t0_ms"] + acc["dur_ms"] <= fw["t0_ms"] + fw["dur_ms"] + 1e-3
    assert fw["t0_ms"] + fw["dur_ms"] <= env["t0_ms"] + env["dur_ms"] + 1e-3


def test_a_reply_that_is_not_streamed_has_accept_and_no_first_write(server):
    tid = "feedface00c0ffee"
    lines = _post(server, "/api/generate",
                  {"prompt": "whole", "stream": False,
                   "options": {"num_predict": 3}}, f"{tid};s=1")
    assert lines[-1]["done"] is True
    names = sorted(s["name"] for s in server.trace.get(tid))
    assert names.count("api.accept") == 1
    assert "api.first_write" not in names
