"""grafttrace (obs/, round 15): wire contract, bounded stores, the
flight-recorder ring, fleet-wide context propagation, and SLO-breach
phase attribution.

Fast tests here are tier-1 (pure units + one FakeLLM fleet — no model,
no compile); the dump-on-stall leg builds a real CPU engine and is
slow-marked (ci.sh full runs the whole file).
"""

import json
import threading
import urllib.error
import urllib.request

import pytest

from p2p_llm_chat_tpu.loadgen.report import (_dominant_phase, _span_phase,
                                             build_ledger)
from p2p_llm_chat_tpu.loadgen.scenarios import (REGISTRY, SLO, Endpoints,
                                                Scenario)
from p2p_llm_chat_tpu.obs import flight as flight_mod
from p2p_llm_chat_tpu.obs import trace as trace_mod
from p2p_llm_chat_tpu.obs.flight import FlightRecorder
from p2p_llm_chat_tpu.obs.trace import (TraceContext, TraceStore, mint,
                                        parse_header, sampled_for)
from p2p_llm_chat_tpu.serve import FakeLLM, OllamaServer, ReplicaRouter
from p2p_llm_chat_tpu.utils.metrics import Registry


TID = "deadbeefdeadbeefdeadbeefdeadbeef"


# -- wire contract ------------------------------------------------------------

def test_parse_header_grammar():
    # Bare ids: 8..64 lowercase hex, case-normalized.
    assert parse_header(TID).trace_id == TID
    assert parse_header("  DEADBEEF  ").trace_id == "deadbeef"
    assert parse_header("a" * 64).trace_id == "a" * 64
    # Malformed: never an error, always None (the hop mints or skips).
    for bad in (None, "", "short", "g" * 16, "a" * 65, "a" * 7,
                "deadbeef beef", ";s=1", "xyz;s=1"):
        assert parse_header(bad) is None
    # Unknown flags are ignored; the id still parses.
    assert parse_header(f"{TID};v=2;foo").trace_id == TID


def test_parse_header_sample_pin_wins(monkeypatch):
    # An explicit ;s= is the origin's verdict — it overrides the local
    # rate in BOTH directions.
    monkeypatch.setenv("TRACE_SAMPLE", "0")
    assert parse_header(f"{TID};s=1").sampled is True
    assert parse_header(TID).sampled is False
    monkeypatch.setenv("TRACE_SAMPLE", "1")
    assert parse_header(f"{TID};s=0").sampled is False
    assert parse_header(TID).sampled is True


def test_mint_header_roundtrip():
    ctx = mint(rate=1.0)
    assert len(ctx.trace_id) == 32 and ctx.sampled is True
    back = parse_header(ctx.header_value())
    assert back == ctx
    off = mint(rate=0.0)
    assert off.sampled is False
    assert off.header_value().endswith(";s=0")
    assert parse_header(off.header_value()).sampled is False


def test_sampling_is_deterministic_and_monotone():
    ids = [f"{i:08x}cafe" for i in (0, 1, 7, 0x7fffffff, 0xffffffff)]
    for tid in ids:
        assert sampled_for(tid, 1.0) is True
        assert sampled_for(tid, 0.0) is False
        for rate in (0.1, 0.5, 0.9):
            # Pure function of (id, rate): every process that sees the
            # id reaches the same verdict — the merge invariant.
            expect = int(tid[:8], 16) / float(1 << 32) < rate
            assert sampled_for(tid, rate) is expect
            assert sampled_for(tid, rate) == sampled_for(tid, rate)
        # Monotone in rate: once sampled, stays sampled at higher rates.
        verdicts = [sampled_for(tid, r) for r in (0.1, 0.5, 0.9, 1.0)]
        assert verdicts == sorted(verdicts)


# -- the bounded store --------------------------------------------------------

def test_store_evicts_whole_traces_fifo():
    st = TraceStore(replica="r0", max_traces=3)
    for tid in ("a" * 8, "b" * 8, "c" * 8):
        st.add(tid, "sched.decode", 0.0, 0.010, tokens=4)
        st.add(tid, "api.request", 0.0, 0.020)
    st.add("d" * 8, "api.request", 0.0, 0.005)
    # The OLDEST trace went, whole — never half a timeline.
    assert st.get("a" * 8) == []
    assert st.ids() == ["b" * 8, "c" * 8, "d" * 8]
    assert st.stats() == {"traces": 3, "spans": 5, "max_traces": 3}
    spans = st.get("b" * 8)
    assert [s["name"] for s in spans] == ["sched.decode", "api.request"]
    assert spans[0]["replica"] == "r0"
    assert spans[0]["meta"] == {"tokens": 4}
    # get() hands back copies — a caller mutating them can't corrupt
    # the store.
    spans[0]["name"] = "vandalized"
    assert st.get("b" * 8)[0]["name"] == "sched.decode"


def test_store_span_noop_when_unsampled():
    st = TraceStore(max_traces=4)
    with st.span(None, "api.request"):
        pass
    with st.span(TraceContext("ab" * 8, sampled=False), "api.request"):
        pass
    assert st.stats()["spans"] == 0
    with st.span(TraceContext("ab" * 8, sampled=True), "api.request",
                 endpoint="response") as sp:
        sp.meta["tokens"] = 7      # mid-span decisions land on the span
    spans = st.get("ab" * 8)
    assert len(spans) == 1
    assert spans[0]["meta"] == {"endpoint": "response", "tokens": 7}
    assert spans[0]["dur_ms"] >= 0.0


def test_store_binds_registry_series():
    st = TraceStore(max_traces=2)
    reg = Registry()
    st.bind_registry(reg)
    st.add("a" * 8, "api.request", 0.0, 0.001)
    st.add("b" * 8, "api.request", 0.0, 0.001)
    st.add("c" * 8, "api.request", 0.0, 0.001)   # evicts a
    assert reg.counter("serve_trace_spans_total").value == 3
    assert reg.gauge("serve_trace_entries").value == 2


# -- the flight recorder ------------------------------------------------------

def test_flight_ring_wraps_and_dumps(tmp_path):
    path = str(tmp_path / "flight.json")
    fr = FlightRecorder(capacity=16, path=path)
    assert FlightRecorder(capacity=2, path=path).capacity == 8  # floor
    for i in range(40):
        fr.note("dispatch", it=i, inflight=1)
    snap = fr.snapshot()
    assert len(snap) == 16
    # Oldest-first, and the ring kept the 16 NEWEST events.
    assert [ev["it"] for ev in snap] == list(range(24, 40))
    assert fr.dumps_total() == 0
    got = fr.dump("unit_test", extra={"probe": True})
    assert got == path
    with open(path) as fh:
        doc = json.load(fh)
    assert doc["reason"] == "unit_test"
    assert doc["dumps"] == 1 and doc["n_events"] == 16
    assert doc["probe"] is True
    assert doc["events"][-1]["kind"] == "dispatch"
    assert doc["events"][-1]["it"] == 39
    # Repeat dumps overwrite in place — "the last interesting moment".
    fr.note("stall_enter", it=40, over_ms=99.0)
    fr.dump("watchdog_stall")
    with open(path) as fh:
        doc = json.load(fh)
    assert doc["dumps"] == 2
    assert doc["events"][-1]["kind"] == "stall_enter"


def test_flight_default_path_and_env_override(monkeypatch, tmp_path):
    # The scheduler constructs FlightRecorder() with no path — this
    # branch must resolve without touching disk until a dump.
    monkeypatch.delenv("TRACE_FLIGHT_PATH", raising=False)
    fr = FlightRecorder(capacity=8)
    assert f"graftflight-{__import__('os').getpid()}.json" in fr.path
    monkeypatch.setenv("TRACE_FLIGHT_PATH", str(tmp_path / "custom.json"))
    assert FlightRecorder(capacity=8).path == str(tmp_path / "custom.json")


def test_flight_note_is_concurrency_safe(tmp_path):
    fr = FlightRecorder(capacity=64, path=str(tmp_path / "f.json"))
    threads = [threading.Thread(
        target=lambda: [fr.note("admit", it=i, n=1) for i in range(200)])
        for _ in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert len(fr.snapshot()) == 64


# -- breach attribution (report.py) -------------------------------------------

def _span(name, dur_ms):
    return {"name": name, "t0_ms": 0.0, "dur_ms": dur_ms}


def test_span_phase_mapping():
    assert _span_phase("sched.queue_wait") == "queue_wait"
    assert _span_phase("sched.prefill") == "prefill"
    assert _span_phase("sched.wake") == "wake"
    assert _span_phase("sched.decode") == "decode"
    assert _span_phase("disagg.handoff") == "handoff"
    assert _span_phase("disagg.import") == "handoff"
    assert _span_phase("router.route") == "route"
    assert _span_phase("node.send") == "p2p"
    # The envelope span contains every other phase — it must never win
    # dominance, so it maps to no phase at all.
    assert _span_phase("api.request") is None


def test_dominant_phase_sums_and_tiebreaks():
    assert _dominant_phase(None) is None
    assert _dominant_phase([]) is None
    assert _dominant_phase([_span("api.request", 1000)]) is None
    spans = [_span("api.request", 1000), _span("sched.queue_wait", 400),
             _span("sched.decode", 150), _span("sched.decode", 100)]
    # decode sums to 250 but queue_wait's single 400 still dominates.
    assert _dominant_phase(spans) == "queue_wait"
    # Exact tie: alphabetical, so reruns produce identical ledgers.
    tie = [_span("sched.decode", 100), _span("disagg.handoff", 100)]
    assert _dominant_phase(tie) == "decode"


def _registry_one(name="s"):
    return {name: Scenario(name, 1.0,
                           SLO(ttft_p50_ms=1000, ttft_p95_ms=100,
                               itl_p95_ms=50, max_shed_frac=0.5),
                           build=lambda rng, peer, ep: [])}


def _rec(ttft, tid="", itl=(), scenario="s"):
    from p2p_llm_chat_tpu.loadgen.driver import TraceRecord
    return TraceRecord(scenario=scenario, peer=0, sched_s=0.0,
                       ttft_ms=ttft, itl_ms=list(itl), trace_id=tid)


def test_breach_attribution_joins_timelines():
    timelines = {
        "aa" * 8: [_span("api.request", 500),
                   _span("sched.queue_wait", 400),
                   _span("sched.decode", 50)],
        "bb" * 8: [_span("sched.decode", 300)],
    }
    recs = [
        _rec(10.0),                              # met the SLO
        _rec(500.0, tid="aa" * 8),               # TTFT breach -> queue_wait
        _rec(10.0, tid="bb" * 8, itl=[200.0]),   # ITL breach  -> decode
        _rec(500.0, tid="cc" * 8),               # timeline gone -> fallback
        _rec(10.0, itl=[200.0]),                 # no id at all -> fallback
    ]
    row = build_ledger(recs, _registry_one(), duration_s=1.0,
                       timelines=timelines)
    attr = row["scenarios"]["s"]["breach_attribution"]
    assert attr["n_breached"] == 4
    assert attr["by_phase"] == {"client_itl": 1, "client_ttft": 1,
                                "decode": 1, "queue_wait": 1}
    assert row["scenarios"]["s"]["goodput_rps"] == 1.0
    # A callable lookup (the fetch_timelines shape) behaves identically.
    row2 = build_ledger(recs, _registry_one(), duration_s=1.0,
                        timelines=lambda tid: timelines.get(tid))
    assert (row2["scenarios"]["s"]["breach_attribution"]
            == attr)


def test_breach_attribution_absent_when_clean():
    row = build_ledger([_rec(10.0), _rec(20.0)], _registry_one(),
                       duration_s=1.0,
                       timelines={"zz": [_span("sched.decode", 9000)]})
    assert row["scenarios"]["s"]["breach_attribution"] is None
    assert row["verdict"] == "pass"


# -- relay_path scenario (loadgen registry) -----------------------------------

def test_relay_path_scenario_registered_and_degrades():
    import random
    assert "relay_path" in REGISTRY
    scen = REGISTRY["relay_path"]
    rng = random.Random(7)
    # Chat plane present: one measured non-streaming /send, aimed half
    # the ring away from the sender.
    ep = Endpoints(serve_url="http://s", node_urls=tuple(
        f"http://n{i}" for i in range(4)), users=tuple(
        f"peer{i:02d}" for i in range(4)))
    steps = scen.build(rng, 1, ep)
    assert len(steps) == 1 and steps[0].measured
    assert steps[0].url == "http://n1/send"
    assert steps[0].payload["to_username"] == "peer03"
    assert not getattr(steps[0], "stream", False)
    # Stub / serve-only runs degrade to the serve-level equivalent.
    steps = scen.build(rng, 1, Endpoints(serve_url="http://s"))
    assert steps[0].url == "http://s/api/chat"
    assert steps[0].stream


# -- HTTP surface: single replica (FakeLLM, lean) -----------------------------

def _post_json(url, body, headers=None, timeout=30):
    hdr = {"Content-Type": "application/json"}
    hdr.update(headers or {})
    req = urllib.request.Request(url, data=json.dumps(body).encode(),
                                 headers=hdr)
    with urllib.request.urlopen(req, timeout=timeout) as r:
        return r.status, json.loads(r.read())


def _get_json(url, timeout=10):
    with urllib.request.urlopen(url, timeout=timeout) as r:
        return json.loads(r.read())


def _gen_body(prompt, session="", stream=False):
    body = {"model": "tiny", "prompt": prompt, "stream": stream,
            "options": {"num_predict": 8, "temperature": 0.0, "seed": 1}}
    if session:
        body["session"] = session
    return body


def test_serve_trace_endpoint_records_api_span():
    srv = OllamaServer(FakeLLM(name="rep"), addr="127.0.0.1:0").start()
    try:
        st, body = _post_json(f"{srv.url}/api/generate",
                              _gen_body("trace me\n\nReply:"),
                              headers={"X-Graft-Trace": f"{TID};s=1"})
        assert st == 200 and body["done"] is True
        doc = _get_json(f"{srv.url}/admin/trace?id={TID}")
        assert doc["id"] == TID
        spans = {s["name"]: s for s in doc["spans"]}
        assert "api.request" in spans
        assert spans["api.request"]["meta"]["endpoint"] == "response"
        assert spans["api.request"]["meta"]["tokens"] >= 0
        assert spans["api.request"]["replica"] == srv.url.split("://", 1)[1]
        listing = _get_json(f"{srv.url}/admin/trace")
        assert TID in listing["traces"]
        assert listing["stats"]["spans"] >= 1
        # s=0 pins the verdict off: the request runs, nothing recorded.
        off = "ab" * 8
        st, _ = _post_json(f"{srv.url}/api/generate",
                           _gen_body("dark\n\nReply:"),
                           headers={"X-Graft-Trace": f"{off};s=0"})
        assert st == 200
        with pytest.raises(urllib.error.HTTPError) as ei:
            _get_json(f"{srv.url}/admin/trace?id={off}")
        assert ei.value.code == 404
        ei.value.close()
        # FakeLLM has no flight surface: on-demand dump is a clean 501.
        with pytest.raises(urllib.error.HTTPError) as ei:
            _post_json(f"{srv.url}/admin/trace/dump", {})
        assert ei.value.code == 501
        ei.value.close()
    finally:
        srv.stop()


# -- fleet propagation incl. a disagg handoff (FakeLLM + real tier, lean) -----

class ParkLLM(FakeLLM):
    """FakeLLM carrying a REAL KVTier through the migration hooks plus
    the round-14 ``prefill_park`` surface — the minimal backend on
    which the router's prefill->decode handoff (and therefore the
    cross-replica trace merge) completes end to end."""

    def __init__(self) -> None:
        super().__init__(name="rep")
        from p2p_llm_chat_tpu.serve.kv_tier import KVTier
        self.tier = KVTier(host_bytes=1 << 20)

    def session_list(self):
        return self.tier.sessions_meta()

    def session_export(self, key):
        return self.tier.export_payload(key)

    def session_import(self, data):
        from p2p_llm_chat_tpu.serve.kv_tier import deserialize_session
        sess = deserialize_session(data)
        if sess is None or not self.tier.adopt(sess):
            return None
        return sess

    def session_forget(self, key):
        return self.tier.forget(key)

    def prefill_park(self, greq):
        import numpy as np
        from p2p_llm_chat_tpu.serve.kv_tier import SessionKV
        key = f"sid:{greq.session}" if greq.session else "head:deadbeef00"
        arr = np.zeros(32, np.int8)
        self.tier.insert(SessionKV(key=key, tokens=tuple(range(40)),
                                   length=40, host=((arr, arr, None, None),
                                                    1),
                                   nbytes=2 * arr.nbytes))
        return {"key": key, "len": 40, "parked": True}


def _wait_for(fn, timeout=15.0, msg="condition"):
    import time
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if fn():
            return
        time.sleep(0.02)
    raise AssertionError(f"timed out waiting for {msg}")


def test_fleet_trace_merges_handoff_spans():
    """One traced new conversation through a prefill/decode fleet: the
    router's merged /admin/trace?id= timeline carries the router-side
    walk + handoff envelope AND both replicas' handoff legs, on one
    t0_ms axis, under the single client-pinned id."""
    pre = OllamaServer(ParkLLM(), addr="127.0.0.1:0",
                       replica_class="prefill").start()
    dec = OllamaServer(ParkLLM(), addr="127.0.0.1:0",
                       replica_class="decode").start()
    rt = ReplicaRouter([pre.url, dec.url], addr="127.0.0.1:0",
                       scrape_ms=50).start()
    try:
        def classes_seen():
            reps = _get_json(f"{rt.url}/admin/replicas")["replicas"]
            by = {r["url"]: r for r in reps}
            return all(u in by and by[u]["class"] == c and by[u]["ready"]
                       for u, c in ((pre.url, "prefill"),
                                    (dec.url, "decode")))
        _wait_for(classes_seen, msg="router class view")
        st, body = _post_json(f"{rt.url}/api/generate",
                              _gen_body("fresh conversation\n\nReply:",
                                        session="conv-trace"),
                              headers={"X-Graft-Trace": f"{TID};s=1"},
                              timeout=60)
        assert st == 200 and body["done"] is True

        def merged():
            try:
                doc = _get_json(f"{rt.url}/admin/trace?id={TID}")
            except urllib.error.HTTPError as e:
                e.close()
                return None
            names = {s["name"] for s in doc["spans"]}
            want = {"router.route", "disagg.handoff",
                    "disagg.prefill_park", "disagg.import", "api.request"}
            return doc if want <= names else None

        holder = {}

        def have_merged():
            doc = merged()
            if doc is not None:
                holder["doc"] = doc
            return "doc" in holder

        _wait_for(have_merged, msg="merged timeline")
        spans = holder["doc"]["spans"]
        # One axis: the merge is t0_ms-sorted across processes.
        assert spans == sorted(spans, key=lambda s: s["t0_ms"])
        by_name = {}
        for s in spans:
            by_name.setdefault(s["name"], []).append(s)
        handoff = by_name["disagg.handoff"][0]
        assert handoff["replica"] == "router"
        assert handoff["meta"]["outcome"] == "ok"
        assert handoff["meta"]["key"] == "sid:conv-trace"
        assert handoff["meta"]["prefill"] == pre.url
        assert handoff["meta"]["decode"] == dec.url
        pre_addr = pre.url.split("://", 1)[1]
        dec_addr = dec.url.split("://", 1)[1]
        # Each handoff leg was recorded by the replica that ran it.
        assert by_name["disagg.prefill_park"][0]["replica"] == pre_addr
        assert by_name["disagg.import"][0]["replica"] == dec_addr
        assert by_name["disagg.import"][0]["meta"]["key"] == "sid:conv-trace"
        # The accepted request landed decode-side after the flip.
        assert by_name["api.request"][0]["replica"] == dec_addr
        assert by_name["router.route"][0]["meta"]["replica"] == dec.url
    finally:
        rt.stop()
        pre.stop()
        dec.stop()


# -- dump-on-stall: the flight recorder names the stalling event --------------

@pytest.mark.slow
@pytest.mark.model
def test_stall_dump_names_dispatch_iteration(tmp_path):
    """Armed ``serve.scheduler.dispatch=delay`` + a tiny loop budget:
    the watchdog's episode-entry dump must land on disk, carry the
    ``stall_enter`` marker, and share that marker's loop iteration with
    a ``dispatch`` event — the one-line diagnosis the recorder exists
    for. Also the loop_stall max/last split and the dump counter."""
    import time

    import jax
    import jax.numpy as jnp

    from p2p_llm_chat_tpu.models import llama
    from p2p_llm_chat_tpu.models.configs import get_config
    from p2p_llm_chat_tpu.serve.backend import (GenerateOptions,
                                                GenerateRequest,
                                                RequestStats)
    from p2p_llm_chat_tpu.serve.engine import TPUEngine
    from p2p_llm_chat_tpu.tokenizer import ByteTokenizer
    from p2p_llm_chat_tpu.utils import failpoints as fp

    cfg = get_config("tiny")
    params = llama.init_params(cfg, jax.random.PRNGKey(0),
                               dtype=jnp.float32)
    eng = TPUEngine(params, cfg, ByteTokenizer(vocab_size=cfg.vocab_size),
                    num_slots=2, max_seq=128)
    sched = eng.scheduler
    path = str(tmp_path / "flight.json")
    sched._flight.path = path
    saved_budget = sched.loop_budget_ms
    fp.disarm_all()
    try:
        sched.loop_budget_ms = 50.0
        fp.arm("serve.scheduler.dispatch", "delay:250")
        stats = RequestStats()
        text = "".join(eng.generate_stream(
            GenerateRequest(prompt="stall probe",
                            options=GenerateOptions(max_tokens=4,
                                                    temperature=0.0,
                                                    seed=1)), stats))
        assert text is not None

        def dumped():
            snap = sched.metrics_snapshot()
            return snap["serve_flight_dumps_total"] >= 1
        deadline = time.monotonic() + 10.0
        while time.monotonic() < deadline and not dumped():
            time.sleep(0.05)
        snap = sched.metrics_snapshot()
        assert snap["serve_flight_dumps_total"] >= 1
        # High-water max AND last-episode gauge both saw the stall.
        assert snap["loop_stall_ms"] >= 50.0
        assert snap["loop_stall_last_ms"] >= 50.0
        with open(path) as fh:
            doc = json.load(fh)
        assert doc["reason"] == "watchdog_stall"
        kinds = [ev["kind"] for ev in doc["events"]]
        assert "stall_enter" in kinds
        stall = next(ev for ev in doc["events"]
                     if ev["kind"] == "stall_enter")
        assert stall["over_ms"] >= 50.0
        # The diagnosis: the stalling iteration's dispatch is IN the
        # ring, noted before the device call that hung.
        assert any(ev["kind"] == "dispatch" and ev["it"] == stall["it"]
                   for ev in doc["events"])
    finally:
        fp.disarm_all()
        sched.loop_budget_ms = saved_budget
        eng.stop()
