"""Stack-wide fault injection: every named failpoint site armed, every
degradation contract asserted (ISSUE 5).

The contracts, per docs/robustness.md: no deadlock, no wedged consumer,
shed requests get well-formed errors (503 + Retry-After in milliseconds,
not queue_timeout_s), faulted layers degrade or recover, and completed
greedy requests still match the solo oracle.

Fast tests here are tier-1 (interpret/CPU); the HTTP-level chaos matrix
and the directory-outage/recovery leg are slow-marked (ci.sh full).
"""

import json
import socket
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from p2p_llm_chat_tpu.models import llama
from p2p_llm_chat_tpu.models.configs import get_config
from p2p_llm_chat_tpu.models.llama import KVCache
from p2p_llm_chat_tpu.serve.api import OllamaServer
from p2p_llm_chat_tpu.serve.backend import (FakeLLM, GenerateOptions,
                                            GenerateRequest, OverloadError,
                                            RequestStats)
from p2p_llm_chat_tpu.serve.engine import TPUEngine
from p2p_llm_chat_tpu.tokenizer import ByteTokenizer
from p2p_llm_chat_tpu.utils import backoff as backoff_mod
from p2p_llm_chat_tpu.utils import failpoints as fp
from p2p_llm_chat_tpu.utils.backoff import Backoff, with_retries
from p2p_llm_chat_tpu.utils.failpoints import FailpointError, failpoint
from p2p_llm_chat_tpu.utils.http import HttpError, http_json

CFG = get_config("tiny")
PARAMS = llama.init_params(CFG, jax.random.PRNGKey(0), dtype=jnp.float32)
TOK = ByteTokenizer(vocab_size=CFG.vocab_size)
STOP_IDS = set(CFG.eos_token_ids) | {TOK.eos_id}
MAX_SEQ = 128


@pytest.fixture(autouse=True)
def _clean_failpoints():
    """No armed site may leak across tests — the whole registry is
    process-global by design."""
    fp.disarm_all()
    yield
    fp.disarm_all()


def oracle(prompt: str, max_new: int) -> str:
    """Solo batch=1 greedy loop with the engine's stop rules."""
    ids = TOK.encode(prompt, add_bos=True)
    cache = KVCache.create(CFG, 1, MAX_SEQ, jnp.float32)
    logits, cache = llama.prefill(PARAMS, CFG, jnp.asarray([ids]),
                                  jnp.asarray([len(ids)]), cache)
    last = np.asarray(logits[0, len(ids) - 1])
    out = []
    for _ in range(max_new):
        t = int(last.argmax())
        if t in STOP_IDS:
            break
        out.append(t)
        lg, cache = llama.decode_step(PARAMS, CFG, jnp.asarray([[t]]), cache)
        last = np.asarray(lg[0, 0])
    return TOK.decode(out)


# -- registry / grammar (no engine) -------------------------------------------

def test_disarmed_site_is_noop_and_uncounted():
    assert failpoint("serve.api.parse") is None
    assert fp.hits("serve.api.parse") == 0


def test_arm_raise_and_hit_counter():
    fp.arm("t.raise", "raise:boom")
    with pytest.raises(FailpointError, match="boom"):
        failpoint("t.raise")
    assert fp.hits("t.raise") == 1
    fp.disarm("t.raise")
    assert failpoint("t.raise") is None


def test_count_modifier_self_disarms():
    fp.arm("t.count", "raise*2")
    for _ in range(2):
        with pytest.raises(FailpointError):
            failpoint("t.count")
    assert failpoint("t.count") is None      # self-disarmed after 2
    assert fp.hits("t.count") == 2


def test_delay_drop_error_prob_kinds():
    fp.arm("t.delay", "delay:30")
    t0 = time.monotonic()
    act = failpoint("t.delay")
    assert act is not None and act.kind == "delay"
    assert time.monotonic() - t0 >= 0.025
    fp.arm("t.drop", "drop")
    assert failpoint("t.drop").kind == "drop"
    fp.arm("t.err", "error:nope")
    act = failpoint("t.err")
    assert act.kind == "error" and act.msg == "nope"
    fp.arm("t.never", "raise@0")             # probability 0: never fires
    assert failpoint("t.never") is None
    assert fp.hits("t.never") == 0


def test_grammar_rejects_malformed_specs():
    for bad in ("explode", "raise*0", "raise@2", "delay", "delay:x"):
        with pytest.raises(ValueError):
            fp.parse_spec(bad)


def test_env_arming(monkeypatch):
    monkeypatch.setenv("FAIL_POINTS", "t.env=raise*1, t.env2=drop")
    fp.load_env(force=True)
    assert "t.env" in fp.armed_sites() and "t.env2" in fp.armed_sites()
    with pytest.raises(FailpointError):
        failpoint("t.env")
    monkeypatch.setenv("FAIL_POINTS", "not-an-entry")
    with pytest.raises(ValueError):
        fp.load_env(force=True)


def test_site_catalog_matches_docs():
    """docs/robustness.md documents every KNOWN_SITES entry (the doc IS
    the operator-facing contract — drift means undriveable chaos)."""
    import os
    doc = open(os.path.join(os.path.dirname(__file__), "..", "docs",
                            "robustness.md"), encoding="utf-8").read()
    for site in fp.KNOWN_SITES:
        assert site in doc, f"site {site} missing from docs/robustness.md"


# -- backoff helper -----------------------------------------------------------

def test_backoff_sequence_grows_jittered_and_capped():
    bo = Backoff(base_s=0.1, max_s=0.4, jitter=0.5)
    seen = [bo.next() for _ in range(5)]
    # Each sample sits in [base*(1-jitter), base] of its step.
    for s, base in zip(seen, (0.1, 0.2, 0.4, 0.4, 0.4)):
        assert base * 0.5 <= s <= base + 1e-9
    bo.reset()
    assert bo.peek() == 0.1
    with pytest.raises(ValueError):
        Backoff(base_s=0, max_s=1)


def test_with_retries_recovers_and_counts():
    before = backoff_mod.retries_total()
    calls = []

    def flaky():
        calls.append(1)
        if len(calls) < 3:
            raise ConnectionError("down")
        return "ok"

    assert with_retries(flaky, attempts=3, base_s=0.01, max_s=0.02) == "ok"
    assert backoff_mod.retries_total() - before == 2
    # Non-retryable errors surface immediately.
    with pytest.raises(HttpError):
        with_retries(lambda: (_ for _ in ()).throw(HttpError(404, "x")),
                     attempts=3, base_s=0.01, max_s=0.02)


def test_with_retries_respects_budget():
    t0 = time.monotonic()
    with pytest.raises(ConnectionError):
        with_retries(lambda: (_ for _ in ()).throw(ConnectionError("x")),
                     attempts=50, base_s=0.05, max_s=0.1, budget_s=0.2)
    assert time.monotonic() - t0 < 1.0


# -- HTTP front (FakeLLM — no model) ------------------------------------------

@pytest.fixture()
def server():
    srv = OllamaServer(FakeLLM(), addr="127.0.0.1:0").start()
    yield srv
    srv.stop()


def test_api_parse_error_and_raise_are_well_formed(server):
    fp.arm("serve.api.parse", "error:injected parse fault")
    status, body = http_json("POST", f"{server.url}/api/generate",
                             {"prompt": "x", "stream": False},
                             raise_for_status=False)
    assert status == 500 and "injected parse fault" in body["error"]
    fp.arm("serve.api.parse", "raise*1")
    status, body = http_json("POST", f"{server.url}/api/generate",
                             {"prompt": "x", "stream": False},
                             raise_for_status=False)
    assert status == 500 and "error" in body
    # Disarmed (count exhausted + explicit) -> next request serves.
    fp.disarm("serve.api.parse")
    status, body = http_json("POST", f"{server.url}/api/generate",
                             {"prompt": "hi\n\nReply:", "stream": False},
                             timeout=30)
    assert status == 200 and body["done"] is True
    assert fp.hits("serve.api.parse") == 2


def test_api_stream_raise_emits_error_record(server):
    fp.arm("serve.api.stream", "raise*1")
    req = urllib.request.Request(
        f"{server.url}/api/generate",
        data=json.dumps({"prompt": "hello\n\nReply:"}).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=30) as resp:
        lines = [json.loads(l) for l in resp.read().decode().splitlines()]
    assert lines[-1]["done"] is True and "error" in lines[-1]
    # Next stream is clean.
    with urllib.request.urlopen(req, timeout=30) as resp:
        lines = [json.loads(l) for l in resp.read().decode().splitlines()]
    assert lines[-1]["done"] is True and "error" not in lines[-1]


def test_api_stream_drop_discards_chunk_but_terminates(server):
    fp.arm("serve.api.stream", "drop*1")
    req = urllib.request.Request(
        f"{server.url}/api/generate",
        data=json.dumps({"prompt": "hello there\n\nReply:"}).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=30) as resp:
        lines = [json.loads(l) for l in resp.read().decode().splitlines()]
    assert lines[-1]["done"] is True
    assert fp.hits("serve.api.stream") >= 1


def test_metrics_exports_failpoint_hits_and_retry_counter(server):
    fp.arm("serve.api.parse", "error*1")
    http_json("POST", f"{server.url}/api/generate",
              {"prompt": "x", "stream": False}, raise_for_status=False)
    with urllib.request.urlopen(f"{server.url}/metrics", timeout=5) as resp:
        text = resp.read().decode()
    assert 'failpoint_hits_total{site="serve.api.parse"}' in text
    assert "retry_attempts_total" in text
    assert "# TYPE failpoint_hits_total counter" in text


def test_readyz_gates_on_backend_probe():
    class Gated(FakeLLM):
        ok = False

        def ready(self):
            return self.ok

    backend = Gated()
    srv = OllamaServer(backend, addr="127.0.0.1:0").start()
    try:
        status, body = http_json("GET", f"{srv.url}/readyz",
                                 raise_for_status=False)
        assert status == 503 and body["status"] == "warming"
        backend.ok = True
        status, body = http_json("GET", f"{srv.url}/readyz")
        assert status == 200 and body["status"] == "ready"
        # Liveness stays a static 200 either way.
        status, _ = http_json("GET", f"{srv.url}/healthz")
        assert status == 200
    finally:
        srv.stop()


def test_failed_backend_is_terminal_500_and_ends_serve_forever():
    """A backend reporting a terminal failure (a failed warmup) answers
    /readyz 500 "failed" — not a 503 a launcher would keep polling —
    and serve_forever ends the process non-zero."""
    class Failing(FakeLLM):
        err = None

        def failed(self):
            return self.err

    backend = Failing()
    srv = OllamaServer(backend, addr="127.0.0.1:0")
    done = {}

    def serve():
        try:
            srv.serve_forever()
        except SystemExit as e:
            done["code"] = e.code

    t = threading.Thread(target=serve, daemon=True)
    t.start()
    deadline = time.monotonic() + 5
    while srv._server is None and time.monotonic() < deadline:
        time.sleep(0.01)
    status, body = http_json("GET", f"{srv.url}/readyz")
    assert status == 200
    backend.err = "XlaRuntimeError: the compiler refused a kernel"
    status, body = http_json("GET", f"{srv.url}/readyz",
                             raise_for_status=False)
    assert status == 500 and body["status"] == "failed"
    assert "compiler refused" in body["error"]
    t.join(timeout=5)
    assert not t.is_alive() and done["code"] == 1


def test_readyz_default_ready_without_probe(server):
    status, body = http_json("GET", f"{server.url}/readyz")
    assert status == 200 and body["status"] == "ready"


# -- scheduler / engine sites -------------------------------------------------

@pytest.fixture(scope="module")
def engine():
    eng = TPUEngine(PARAMS, CFG, TOK, num_slots=3, max_seq=MAX_SEQ)
    yield eng
    eng.stop()


def run(engine, prompt, max_tokens=8, **opts):
    stats = RequestStats()
    req = GenerateRequest(prompt=prompt, options=GenerateOptions(
        max_tokens=max_tokens, **opts))
    text = "".join(engine.generate_stream(req, stats))
    return text, stats


@pytest.mark.model
def test_admit_failpoint_fails_request_cleanly_then_recovers(engine):
    fp.arm("serve.scheduler.admit", "raise*1")
    with pytest.raises(RuntimeError, match="admission failed"):
        run(engine, "fault at admit", max_tokens=4)
    text, _ = run(engine, "after admit fault", max_tokens=8)
    assert text == oracle("after admit fault", 8)
    assert fp.hits("serve.scheduler.admit") == 1


@pytest.mark.model
def test_dispatch_failpoint_resets_and_recovers(engine):
    fp.arm("serve.scheduler.dispatch", "raise*1")
    with pytest.raises(RuntimeError, match="reset"):
        run(engine, "fault at dispatch", max_tokens=8)
    text, _ = run(engine, "after dispatch fault", max_tokens=8)
    assert text == oracle("after dispatch fault", 8)


@pytest.mark.model
def test_readback_failpoint_resets_and_recovers(engine):
    fp.arm("serve.engine.readback", "raise*1")
    with pytest.raises(RuntimeError, match="reset"):
        run(engine, "fault at readback", max_tokens=8)
    text, _ = run(engine, "after readback fault", max_tokens=8)
    assert text == oracle("after readback fault", 8)


@pytest.mark.model
def test_promotion_failpoint_drops_build_serving_unaffected(engine):
    """A faulted prefix-promotion build is dropped (promotion is an
    optimization); serving never notices. The head must cross the
    64-token promotion grain, repeated promote_after (2) times."""
    fp.arm("serve.scheduler.promote", "raise")
    long_prompt = ("p" * 70) + " tail"
    a, _ = run(engine, long_prompt, max_tokens=4)
    b, _ = run(engine, long_prompt, max_tokens=4)
    assert a == b == oracle(long_prompt, 4)
    deadline = time.monotonic() + 10.0
    while time.monotonic() < deadline and not fp.hits(
            "serve.scheduler.promote"):
        time.sleep(0.05)
    assert fp.hits("serve.scheduler.promote") >= 1, \
        "promotion build never ran"
    text, _ = run(engine, "after promote fault", max_tokens=8)
    assert text == oracle("after promote fault", 8)


@pytest.mark.model
def test_overload_shed_is_fast_wellformed_503(engine):
    """The acceptance bar: at capacity, a shed request gets OverloadError
    (HTTP: 503 + Retry-After) in milliseconds — never a queue-deadline
    burn. Capacity is held deterministically by slowing decode ticks
    with the dispatch delay failpoint."""
    sched = engine.scheduler
    saved_qmax = sched.queue_max
    srv = OllamaServer(engine, addr="127.0.0.1:0").start()
    holders = []
    try:
        fp.arm("serve.scheduler.dispatch", "delay:40")
        opts = GenerateOptions(max_tokens=60)

        def hold(p):
            it = engine.generate_stream(
                GenerateRequest(prompt=p, options=opts), RequestStats())
            holders.append(threading.Thread(target=lambda: "".join(it)))
            holders[-1].start()

        for i in range(3):                  # fill all 3 slots
            hold(f"hold the batch {i}")
        deadline = time.monotonic() + 10.0
        while (time.monotonic() < deadline
               and sched.metrics_snapshot()["serve_batch_occupancy"] < 3):
            time.sleep(0.02)
        assert sched.metrics_snapshot()["serve_batch_occupancy"] == 3
        # Bound the queue only once the batch is full, so the holders
        # themselves never shed while racing through the queue.
        sched.queue_max = 2
        for i in range(2):                  # fill the bounded queue
            hold(f"queue dweller {i}")
        deadline = time.monotonic() + 5.0
        while (time.monotonic() < deadline and sched._queue_depth() < 2):
            time.sleep(0.02)
        assert sched._queue_depth() == 2

        # Direct submit: OverloadError, immediately.
        t0 = time.monotonic()
        with pytest.raises(OverloadError):
            engine.generate_stream(
                GenerateRequest(prompt="shed me", options=opts),
                RequestStats())
        assert time.monotonic() - t0 < 0.05

        # HTTP: 503 + Retry-After, well-formed JSON error, fast.
        t0 = time.monotonic()
        req = urllib.request.Request(
            f"{srv.url}/api/generate",
            data=json.dumps({"prompt": "shed me too",
                             "stream": False}).encode(),
            headers={"Content-Type": "application/json"})
        with pytest.raises(urllib.error.HTTPError) as e:
            urllib.request.urlopen(req, timeout=5)
        elapsed = time.monotonic() - t0
        assert e.value.code == 503
        assert e.value.headers.get("Retry-After")
        assert "error" in json.loads(e.value.read())
        assert elapsed < 2.0, f"shed took {elapsed:.2f}s (want < 50 ms " \
                              "server-side; bound is CI-lenient)"
        snap = sched.metrics_snapshot()
        assert snap["requests_shed_total"] >= 2
    finally:
        fp.disarm_all()                     # un-slow the decode ticks
        sched.queue_max = saved_qmax
        for t in holders:
            t.join(timeout=60)
        srv.stop()
    assert not any(t.is_alive() for t in holders), "consumer wedged"
    # The queue drains and the engine still serves oracle-exact.
    text, _ = run(engine, "after the storm", max_tokens=8)
    assert text == oracle("after the storm", 8)


@pytest.mark.model
def test_engine_readiness_semantics(engine):
    """Never-warmed scheduler: ready as soon as the loop runs. A started
    warmup flips it not-ready until completion."""
    sched = engine.scheduler
    assert engine.ready() is True
    sched._warmup_started, sched._warmup_done_at = True, None
    try:
        assert engine.ready() is False
        sched._warmup_done_at = time.monotonic()
        assert engine.ready() is True
    finally:
        sched._warmup_started, sched._warmup_done_at = False, 0.0


@pytest.mark.model
def test_warmup_that_raises_marks_the_engine_failed(engine):
    """A warmup job that raises (here the admit failpoint; on the chip,
    a program the compiler refuses) is terminal: the engine reports the
    failure, never goes ready, and the jobs queued behind the failed
    one are skipped instead of compiled."""
    sched = engine.scheduler
    hits = fp.snapshot().get("serve.scheduler.admit", 0)
    fp.arm("serve.scheduler.admit", "raise")
    try:
        engine.warmup((32,), background=False)
        assert "FailpointError" in engine.failed()
        assert engine.ready() is False
        # Only the first job ran: the rest of the ladder was void.
        assert fp.snapshot()["serve.scheduler.admit"] == hits + 1
    finally:
        fp.disarm_all()
        sched.warmup_error = None
        sched._warmup_started, sched._warmup_done_at = False, 0.0
    assert engine.failed() is None and engine.ready() is True
    text, _ = run(engine, "after the failed warmup", max_tokens=4)
    assert text == oracle("after the failed warmup", 4)


@pytest.mark.model
def test_watchdog_exports_loop_stall_gauge(engine):
    sched = engine.scheduler
    saved = sched.loop_budget_ms
    try:
        sched.loop_budget_ms = 0.001      # every iteration over-budget
        run(engine, "stall probe", max_tokens=4)
        deadline = time.monotonic() + 5.0
        while (time.monotonic() < deadline
               and sched.metrics_snapshot()["loop_stall_ms"] == 0):
            time.sleep(0.02)
        assert sched.metrics_snapshot()["loop_stall_ms"] > 0
    finally:
        sched.loop_budget_ms = saved


# -- P2P control plane --------------------------------------------------------

def test_directory_client_retries_recover_and_bound():
    from p2p_llm_chat_tpu.directory import DirectoryClient, DirectoryService
    svc = DirectoryService(addr="127.0.0.1:0").start()
    try:
        cli = DirectoryClient(svc.url, timeout=2.0, attempts=3)
        before = backoff_mod.retries_total()
        fp.arm("p2p.directory.register", "error*2")
        cli.register("najy", "peerid", ["addr"])   # 3rd attempt lands
        assert backoff_mod.retries_total() - before == 2
        fp.arm("p2p.directory.lookup", "error*2")
        rec = cli.lookup("najy")                   # recovery after 2 faults
        assert rec.peer_id == "peerid"
        # Unlimited fault: bounded failure, no hang.
        fp.arm("p2p.directory.lookup", "error")
        t0 = time.monotonic()
        with pytest.raises(ConnectionError):
            cli.lookup("najy")
        assert time.monotonic() - t0 < 6.0
    finally:
        svc.stop()


def test_dht_rpc_drop_degrades_fast_and_recovers():
    pytest.importorskip("cryptography")  # p2p identity needs it; absent = same skip as the p2p suites
    from p2p_llm_chat_tpu.p2p.dht import DHTNode
    from p2p_llm_chat_tpu.p2p.identity import Identity
    a = DHTNode(Identity.generate(), "127.0.0.1:0",
                rpc_timeout_s=0.3).start()
    b = DHTNode(Identity.generate(), "127.0.0.1:0",
                rpc_timeout_s=0.3).start()
    try:
        b.bootstrap([a.addr])
        b.put_self_record("cannan", ["/ip4/127.0.0.1/tcp/1"])
        assert a.get_record("cannan") is not None
        fp.arm("p2p.dht.rpc", "drop")       # every datagram lost
        t0 = time.monotonic()
        assert a.get_record("zoe", budget_s=2.0) is None
        assert time.monotonic() - t0 < 4.0  # drop short-circuits timeouts
        fp.disarm("p2p.dht.rpc")
        assert a.get_record("cannan") is not None   # recovery
    finally:
        a.close()
        b.close()


def test_transport_handshake_failpoint_fails_dial_then_recovers():
    pytest.importorskip("cryptography")  # p2p identity needs it; absent = same skip as the p2p suites
    from p2p_llm_chat_tpu.p2p import P2PHost
    from p2p_llm_chat_tpu.p2p.transport import HandshakeError
    server = P2PHost(listen_addr="127.0.0.1:0").start()
    got = []
    server.set_stream_handler("/t/1", lambda s, pid: got.append(s.read_all()))
    client = P2PHost(listen_addr="127.0.0.1:0").start()
    try:
        fp.arm("p2p.transport.handshake", "error*1")
        with pytest.raises(HandshakeError, match="injected"):
            client.new_stream(server.addrs()[0], "/t/1")
        stream = client.new_stream(server.addrs()[0], "/t/1")  # recovery
        stream.send_frame(b"after fault")
        stream.close()
        deadline = time.monotonic() + 5.0
        while time.monotonic() < deadline and not got:
            time.sleep(0.02)
        assert got == [b"after fault"]
    finally:
        client.close()
        server.close()


def test_relay_control_failpoint_drop_and_error():
    pytest.importorskip("cryptography")  # p2p identity needs it; absent = same skip as the p2p suites
    from p2p_llm_chat_tpu.relay import RelayService
    from p2p_llm_chat_tpu.p2p.transport import (recv_json_frame,
                                                send_json_frame)
    relay = RelayService(addr="127.0.0.1:0").start()

    def control(msg):
        maddr = relay.addr()
        s = socket.create_connection((maddr.host, maddr.port), timeout=5)
        s.settimeout(5)
        try:
            send_json_frame(s, msg)
            return recv_json_frame(s)
        finally:
            s.close()

    try:
        fp.arm("p2p.relay.control", "drop*1")
        assert control({"type": "bogus"}) is None      # closed, no reply
        fp.arm("p2p.relay.control", "error*1")
        resp = control({"type": "bogus"})
        assert resp == {"ok": False, "error": "injected fault"}
        # Disarmed: the relay still serves (well-formed refusal).
        resp = control({"type": "bogus"})
        assert resp["ok"] is False and "unknown type" in resp["error"]
    finally:
        relay.stop()


# -- slow chaos legs (ci.sh full) ---------------------------------------------

@pytest.mark.slow
@pytest.mark.model
def test_http_chaos_matrix(engine):
    """Armed faults at every serve-plane site under concurrent HTTP
    load: every request ends in a valid response or a well-formed error
    (no hang, no malformed frame), and a post-chaos greedy request
    matches the solo oracle."""
    srv = OllamaServer(engine, addr="127.0.0.1:0").start()
    scenarios = [
        ("serve.api.parse", "raise*2"),
        ("serve.api.parse", "error*2"),
        ("serve.api.stream", "raise*2"),
        ("serve.api.stream", "drop*2"),
        ("serve.scheduler.admit", "raise*1"),
        ("serve.scheduler.dispatch", "raise*1"),
        ("serve.engine.readback", "raise*1"),
        ("serve.scheduler.dispatch", "delay:20*4"),
    ]
    try:
        for site, spec in scenarios:
            fp.disarm_all()
            fp.arm(site, spec)
            outcomes = []

            def one(i):
                stream = i % 2 == 0
                body = {"prompt": f"chaos {site} {i}\n\nReply:",
                        "stream": stream,
                        "options": {"num_predict": 6}}
                req = urllib.request.Request(
                    f"{srv.url}/api/generate",
                    data=json.dumps(body).encode(),
                    headers={"Content-Type": "application/json"})
                try:
                    with urllib.request.urlopen(req, timeout=60) as resp:
                        raw = resp.read().decode()
                    if stream:
                        lines = [json.loads(l) for l in raw.splitlines()]
                        assert lines[-1]["done"] is True
                    else:
                        assert json.loads(raw)["done"] is True
                    outcomes.append("ok")
                except urllib.error.HTTPError as e:
                    assert "error" in json.loads(e.read())
                    outcomes.append(f"http {e.code}")
                except AssertionError:
                    raise
                except Exception as e:   # noqa: BLE001
                    outcomes.append(f"unexpected {type(e).__name__}: {e}")

            threads = [threading.Thread(target=one, args=(i,))
                       for i in range(6)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=120)
            assert not any(t.is_alive() for t in threads), \
                f"wedged consumer under {site}={spec}"
            assert len(outcomes) == 6, (site, spec, outcomes)
            assert not any(o.startswith("unexpected") for o in outcomes), \
                (site, spec, outcomes)
        fp.disarm_all()
        status, body = http_json("POST", f"{srv.url}/api/generate", {
            "prompt": "post chaos oracle", "stream": False,
            "options": {"num_predict": 8}}, timeout=60)
        assert status == 200
        assert body["response"] == oracle("post chaos oracle", 8)
    finally:
        srv.stop()


@pytest.mark.slow
def test_directory_outage_degrades_to_dht_and_recovers(monkeypatch):
    """The full outage story: directory dies -> nodes resolve each other
    through the DHT rung and messages still deliver; directory restarts
    (in-memory, records lost) -> the jittered re-register loop
    repopulates it and direct lookups recover."""
    pytest.importorskip("cryptography")  # p2p identity needs it; absent = same skip as the p2p suites
    from p2p_llm_chat_tpu.directory import DirectoryService
    from p2p_llm_chat_tpu.node import ChatNode

    monkeypatch.setenv("NODE_REREGISTER_S", "0.4")
    directory = DirectoryService(addr="127.0.0.1:0").start()
    port = int(directory.url.rsplit(":", 1)[1])
    a = ChatNode(username="najy", http_addr="127.0.0.1:0",
                 directory_url=directory.url, bootstrap_addrs="",
                 relay_addrs="", identity_file="",
                 dht_addr="127.0.0.1:0", dht_bootstrap="").start()
    b = ChatNode(username="cannan", http_addr="127.0.0.1:0",
                 directory_url=directory.url, bootstrap_addrs="",
                 relay_addrs="", identity_file="",
                 dht_addr="127.0.0.1:0",
                 dht_bootstrap="%s:%d" % a.dht.addr).start()
    directory2 = None
    try:
        deadline = time.time() + 5.0
        while time.time() < deadline and a.dht.get_record("cannan") is None:
            time.sleep(0.05)
        assert a.dht.get_record("cannan") is not None, "b never published"

        # Outage: a has never paired with b — only the DHT rung can
        # resolve the send.
        directory.stop()
        status, resp = http_json(
            "POST", f"{a.http_url}/send",
            {"to_username": "cannan", "content": "over the DHT"})
        assert status == 200, resp
        deadline = time.time() + 5.0
        inbox = []
        while time.time() < deadline and not inbox:
            _, inbox = http_json("GET", f"{b.http_url}/inbox?after=")
            time.sleep(0.05)
        assert inbox and inbox[0]["content"] == "over the DHT"

        # Recovery: restart the (record-losing) directory on the same
        # port; the re-register loops repopulate it without operator
        # action, and a direct lookup answers again.
        directory2 = DirectoryService(addr=f"127.0.0.1:{port}").start()
        deadline = time.time() + 15.0
        found = False
        while time.time() < deadline and not found:
            status, _ = http_json(
                "GET", f"{directory2.url}/lookup?username=cannan",
                raise_for_status=False)
            found = status == 200
            time.sleep(0.1)
        assert found, "re-register never repopulated the directory"
        status, _ = http_json(
            "POST", f"{a.http_url}/send",
            {"to_username": "cannan", "content": "after recovery"})
        assert status == 200
    finally:
        a.stop()
        b.stop()
        if directory2 is not None:
            directory2.stop()
