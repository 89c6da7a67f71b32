"""Replica-router serving tests (serve/router.py): N full-stack engines
behind one backpressure-aware HTTP front.

Fast tier-1 legs run fully in-process over FakeLLM replicas — routing,
streaming pass-through, 503 failover, sub-100 ms saturated-fleet shed,
drain semantics, session affinity, and /metrics aggregation need no
model. The engine-level drain hook gets one tiny-model scheduler test
(model-marked), and the two-OS-process full-stack matrix (both replicas
running paged KV + speculation + prefix cache, aggregate throughput vs
one replica, Ollama wire contract through the router) is slow-marked
into ci.sh full.
"""

import json
import os
import socket
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request

import pytest

from p2p_llm_chat_tpu.serve import FakeLLM, OllamaServer, ReplicaRouter
from p2p_llm_chat_tpu.serve.backend import OverloadError
from p2p_llm_chat_tpu.serve.router import (_merge_label, parse_metrics_text)
from p2p_llm_chat_tpu.utils.http import HttpError, http_json

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class SheddingLLM(FakeLLM):
    """A replica at capacity: every submit sheds (the scheduler's
    queue_max fast-fail), so its front answers 503 + Retry-After."""

    def __init__(self, name: str = "rep") -> None:
        super().__init__(name=name)
        self.sheds = 0

    def generate_stream(self, req, stats=None):
        self.sheds += 1
        raise OverloadError("server at capacity: injected", retry_after_s=3.0)


class LabeledMetricsLLM(FakeLLM):
    """Backend whose snapshot carries an already-labeled series (the
    per-draft-source spec keys / serve/multi.py model labels) — the
    router must MERGE its replica label into the brace block."""

    def __init__(self, name: str = "rep", occupancy: float = 1.0) -> None:
        super().__init__(name=name)
        self.occupancy = occupancy

    def metrics_snapshot(self):
        return {
            "serve_batch_occupancy": self.occupancy,
            'serve_spec_proposed_total{source="ngram"}': 5 * self.occupancy,
        }


def _fleet(n: int = 2, backend_factory=None, **router_kw):
    """n in-process replicas + a router; returns (router, replicas)."""
    backend_factory = backend_factory or (lambda i: FakeLLM(name="rep"))
    reps = [OllamaServer(backend_factory(i), addr="127.0.0.1:0").start()
            for i in range(n)]
    router_kw.setdefault("scrape_ms", 100)
    rt = ReplicaRouter([r.url for r in reps], addr="127.0.0.1:0",
                       **router_kw).start()
    return rt, reps


def _stop(rt, reps):
    rt.stop()
    for r in reps:
        r.stop()


def _routed(rt) -> list:
    _, body = http_json("GET", f"{rt.url}/admin/replicas")
    return [r["routed"] for r in body["replicas"]]


def _gen(url: str, prompt: str, stream: bool = False, session: str = None,
         timeout: float = 30):
    headers = {"Content-Type": "application/json"}
    if session:
        headers["X-Session-Id"] = session
    req = urllib.request.Request(
        f"{url}/api/generate",
        data=json.dumps({"model": "rep", "prompt": prompt,
                         "stream": stream}).encode(),
        headers=headers)
    with urllib.request.urlopen(req, timeout=timeout) as r:
        raw = r.read().decode()
    if stream:
        return [json.loads(l) for l in raw.splitlines()]
    return json.loads(raw)


# -- routing + wire contract -------------------------------------------------

def test_distinct_requests_spread_over_replicas():
    rt, reps = _fleet(2)
    try:
        for i in range(8):
            body = _gen(rt.url, f"req number {i}\n\nReply:")
            assert body["done"] is True
            assert f"req number {i}" in body["response"]
        routed = _routed(rt)
        assert sum(routed) == 8
        # The rotating tiebreak spreads an instant-request burst; both
        # replicas must take real traffic (exact split is timing-free).
        assert all(n > 0 for n in routed), routed
    finally:
        _stop(rt, reps)


def test_streaming_ndjson_preserved_through_router():
    rt, reps = _fleet(2)
    try:
        lines = _gen(rt.url, "stream me please\n\nReply:", stream=True)
        assert len(lines) >= 2
        assert all(not l["done"] for l in lines[:-1])
        assert lines[-1]["done"] is True
        text = "".join(l.get("response", "") for l in lines)
        assert "stream me please" in text
    finally:
        _stop(rt, reps)


def test_streaming_is_incremental_through_router():
    """Tokens must FORWARD as the replica produces them — read1, not
    read(n): on a chunked upstream, read(n) loops across chunk
    boundaries until n bytes accumulate, which buffers an entire
    sub-16KB generation and destroys streaming while still passing any
    final-bytes assertion. Pin the first line arriving well before the
    stream completes."""
    slow = FakeLLM(name="rep", token_delay_s=0.15)
    rt, reps = _fleet(1, backend_factory=lambda i: slow)
    try:
        req = urllib.request.Request(
            f"{rt.url}/api/generate",
            data=json.dumps({"model": "rep",
                             "prompt": "incremental streaming check"
                                       "\n\nReply:"}).encode(),
            headers={"Content-Type": "application/json"})
        t0 = time.monotonic()
        with urllib.request.urlopen(req, timeout=30) as resp:
            first = resp.readline()
            t_first = time.monotonic() - t0
            rest = resp.read()
        t_total = time.monotonic() - t0
        assert first and json.loads(first)["done"] is False
        assert rest
        # ~8 words x 150 ms = ~1.2 s total; the first delta must beat
        # HALF of that by a wide margin (buffered-whole-response fails
        # with t_first ~= t_total).
        assert t_total > 0.6, t_total
        assert t_first < 0.5 * t_total, (t_first, t_total)
    finally:
        _stop(rt, reps)


def test_chat_embed_tags_proxied():
    rt, reps = _fleet(2)
    try:
        st, body = http_json("POST", f"{rt.url}/api/chat", {
            "model": "rep",
            "messages": [{"role": "user", "content": "lunch tomorrow?"}],
            "stream": False})
        assert st == 200 and "lunch tomorrow?" in body["message"]["content"]
        st, body = http_json("POST", f"{rt.url}/api/embed",
                             {"model": "rep", "input": ["a", "b"]})
        assert st == 200 and len(body["embeddings"]) == 2
        st, tags = http_json("GET", f"{rt.url}/api/tags")
        assert st == 200 and tags["models"][0]["name"] == "rep"
        with urllib.request.urlopen(f"{rt.url}/", timeout=5) as r:
            assert r.read() == b"Ollama is running"
    finally:
        _stop(rt, reps)


# -- backpressure: failover, saturation, readiness ---------------------------

def test_503_fails_over_to_healthy_replica():
    """One replica shedding (503 + Retry-After at submit): every request
    lands on the healthy replica, counted as router retries."""
    shedding = SheddingLLM()
    rt, reps = _fleet(2, backend_factory=lambda i: (
        shedding if i == 0 else FakeLLM(name="rep")))
    try:
        for i in range(4):
            body = _gen(rt.url, f"failover {i}\n\nReply:")
            assert body["done"] is True
        _, body = http_json("GET", f"{rt.url}/admin/replicas")
        by_idx = {r["index"]: r for r in body["replicas"]}
        # Replica 1 served everything; any attempt that hit replica 0
        # first was shed there and retried onto 1.
        assert shedding.sheds >= 1       # the shedding replica was tried
        assert by_idx[1]["routed"] >= 4
        with urllib.request.urlopen(f"{rt.url}/metrics", timeout=5) as r:
            text = r.read().decode()
        assert "router_retries_total" in text
    finally:
        _stop(rt, reps)


def test_saturated_fleet_sheds_fast_with_retry_after():
    """Every replica at capacity: the router exhausts the candidate list
    with NO sleeping and answers 503 + Retry-After in well under 100 ms
    (the acceptance bar — backpressure must never burn the client's
    deadline)."""
    rt, reps = _fleet(2, backend_factory=lambda i: SheddingLLM())
    try:
        t0 = time.monotonic()
        with pytest.raises(HttpError) as e:
            http_json("POST", f"{rt.url}/api/generate",
                      {"model": "rep", "prompt": "x", "stream": False},
                      timeout=10)
        elapsed = time.monotonic() - t0
        assert e.value.status == 503
        assert elapsed < 0.1, f"shed took {elapsed * 1e3:.0f} ms"
        # Retry-After propagated from the replicas' own shed responses
        # (SheddingLLM advertises 3 s).
        req = urllib.request.Request(
            f"{rt.url}/api/generate",
            data=json.dumps({"model": "rep", "prompt": "x",
                             "stream": False}).encode(),
            headers={"Content-Type": "application/json"})
        with pytest.raises(urllib.error.HTTPError) as he:
            urllib.request.urlopen(req, timeout=10)
        assert he.value.headers.get("Retry-After") == "3"
        he.value.close()
    finally:
        _stop(rt, reps)


def test_unready_replica_excluded_and_fleet_readyz():
    class NotReady(FakeLLM):
        def ready(self):
            return False

    rt, reps = _fleet(2, backend_factory=lambda i: (
        NotReady(name="rep") if i == 0 else FakeLLM(name="rep")))
    try:
        for i in range(3):
            _gen(rt.url, f"warmgate {i}\n\nReply:")
        routed = _routed(rt)
        assert routed[0] == 0 and routed[1] == 3, routed
        st, _ = http_json("GET", f"{rt.url}/readyz")
        assert st == 200
    finally:
        _stop(rt, reps)
    # ALL replicas unready -> fleet not ready (503 + Retry-After).
    rt, reps = _fleet(2, backend_factory=lambda i: NotReady(name="rep"))
    try:
        time.sleep(0.3)     # let a scrape observe the probes
        with pytest.raises(HttpError) as e:
            http_json("GET", f"{rt.url}/readyz")
        assert e.value.status == 503
    finally:
        _stop(rt, reps)


def test_dead_replica_marked_unreachable_and_skipped():
    """A replica whose process is gone: the first failed proxy marks it
    not-alive; subsequent requests go straight to the survivor."""
    rt, reps = _fleet(2)
    try:
        reps[0].stop()                   # replica 0 vanishes
        for i in range(4):
            body = _gen(rt.url, f"survivor {i}\n\nReply:")
            assert body["done"] is True
    finally:
        _stop(rt, reps[1:])


# -- draining ----------------------------------------------------------------

def test_drain_completes_inflight_and_routes_away():
    """Draining a replica: its live stream finishes intact, new work
    routes to the other replica, undrain restores it."""
    slow = FakeLLM(name="rep", token_delay_s=0.08)
    rt, reps = _fleet(2, backend_factory=lambda i: (
        slow if i == 0 else FakeLLM(name="rep")))
    try:
        # Pin a session onto replica 0 (the slow one) so the stream we
        # drain under is known to live there.
        _gen(rt.url, "pin\n\nReply:", session="s-drain")
        _, body = http_json("GET", f"{rt.url}/admin/replicas")
        home = next(r["index"] for r in body["replicas"] if r["routed"])
        lines: list = []
        errs: list = []

        def stream_worker():
            try:
                lines.extend(_gen(rt.url, "long slow stream here\n\nReply:",
                                  stream=True, session="s-drain"))
            except Exception as e:          # noqa: BLE001
                errs.append(e)

        th = threading.Thread(target=stream_worker)
        th.start()
        time.sleep(0.15)                    # stream is live mid-flight
        st, _ = http_json("POST", f"{rt.url}/admin/drain",
                          {"replica": home})
        assert st == 200
        th.join(timeout=30)
        assert not errs, errs
        assert lines and lines[-1]["done"] is True   # stream completed
        # The drained replica's own front reports draining on /readyz
        # (the forwarded engine-level hook).
        rep_url = next(r["url"] for r in
                       http_json("GET", f"{rt.url}/admin/replicas")[1]
                       ["replicas"] if r["index"] == home)
        with pytest.raises(HttpError) as e:
            http_json("GET", f"{rep_url}/readyz")
        assert e.value.status == 503
        # Embed is a work-accepting endpoint too: a drained replica
        # sheds it with the same 503 contract (it bypasses the
        # scheduler, so the front-level check is the only gate).
        with pytest.raises(HttpError) as e:
            http_json("POST", f"{rep_url}/api/embed", {"input": "x"})
        assert e.value.status == 503
        # New sessions route away from the drained replica.
        before = _routed(rt)
        for i in range(3):
            _gen(rt.url, f"post drain {i}\n\nReply:", session="s-drain")
        after = _routed(rt)
        assert after[home] == before[home], (before, after)
        # Undrain restores eligibility (and the replica's /readyz).
        st, _ = http_json("POST", f"{rt.url}/admin/undrain",
                          {"replica": home})
        assert st == 200
        st, _ = http_json("GET", f"{rep_url}/readyz")
        assert st == 200
    finally:
        _stop(rt, reps)


@pytest.mark.model
def test_scheduler_drain_hook_finishes_inflight_sheds_new():
    """Engine-level drain (the hook the replica's /admin/drain calls):
    an in-flight stream finishes EXACTLY as without the drain, a new
    submit fast-fails with OverloadError, ready flips false; undrain
    restores submits."""
    import jax
    import jax.numpy as jnp

    from p2p_llm_chat_tpu.models import llama
    from p2p_llm_chat_tpu.models.configs import get_config
    from p2p_llm_chat_tpu.serve.backend import (GenerateOptions,
                                                GenerateRequest)
    from p2p_llm_chat_tpu.serve.engine import TPUEngine
    from p2p_llm_chat_tpu.tokenizer import ByteTokenizer

    cfg = get_config("tiny")
    params = llama.init_params(cfg, jax.random.PRNGKey(0),
                               dtype=jnp.float32)
    tok = ByteTokenizer(vocab_size=cfg.vocab_size)
    eng = TPUEngine(params, cfg, tok, num_slots=2, max_seq=128)
    try:
        opts = GenerateOptions(max_tokens=24, temperature=0.0)
        ref = "".join(eng.generate_stream(
            GenerateRequest(prompt="drain me", options=opts)))
        stream = eng.generate_stream(
            GenerateRequest(prompt="drain me", options=opts))
        got = [next(stream)]                 # in-flight before the drain
        eng.drain()
        assert eng.ready() is False
        with pytest.raises(OverloadError):
            eng.generate_stream(GenerateRequest(prompt="rejected",
                                                options=opts))
        got.extend(stream)                   # finishes under drain
        assert "".join(got) == ref
        snap = eng.metrics_snapshot()
        assert snap["serve_draining"] == 1
        assert snap["requests_shed_total"] >= 1
        eng.undrain()
        assert eng.ready() is True
        out = "".join(eng.generate_stream(
            GenerateRequest(prompt="drain me", options=opts)))
        assert out == ref
    finally:
        eng.stop()


# -- session affinity --------------------------------------------------------

def test_session_affinity_pins_and_rehomes():
    rt, reps = _fleet(3)
    try:
        _gen(rt.url, "first\n\nReply:", session="conv-1")
        home = next(i for i, n in enumerate(_routed(rt)) if n)
        for i in range(5):
            _gen(rt.url, f"turn {i}\n\nReply:", session="conv-1")
        routed = _routed(rt)
        assert routed[home] == 6, routed     # every turn stayed home
        # Drain the home replica: the session rehomes and STAYS on its
        # new home afterwards.
        http_json("POST", f"{rt.url}/admin/drain", {"replica": home})
        for i in range(3):
            _gen(rt.url, f"rehomed {i}\n\nReply:", session="conv-1")
        routed2 = _routed(rt)
        assert routed2[home] == 6, routed2
        new_home = max((n, i) for i, n in enumerate(routed2)
                       if i != home)[1]
        assert routed2[new_home] >= 3
    finally:
        _stop(rt, reps)


def test_session_key_derivation():
    """Conversation-id derivation: explicit header/body wins; /api/chat
    keys on the first TWO messages — stable from turn 2 on, and NOT
    collapsed by an app-wide shared system prompt (keying on message 0
    alone would pin every conversation to one home replica);
    /api/generate keys on the context head; one-shot prompts get none."""
    sk = ReplicaRouter.session_key
    assert sk("/api/generate", {}, {"x-session-id": "abc"}) == "abc"
    assert sk("/api/generate", {"session": "s9"}, {}) == "s9"
    sys0 = {"role": "system", "content": "You are helpful."}
    u0 = {"role": "user", "content": "hello"}
    a0 = {"role": "assistant", "content": "hi there"}
    u1 = {"role": "user", "content": "more"}
    a1 = {"role": "assistant", "content": "sure"}
    u2 = {"role": "user", "content": "even more"}
    # Stable across later turns: the first-two prefix never changes.
    k2 = sk("/api/chat", {"messages": [sys0, u0, a0, u1]}, {})
    k3 = sk("/api/chat", {"messages": [sys0, u0, a0, u1, a1, u2]}, {})
    assert k2 is not None and k2 == k3
    # A shared system prompt must NOT collapse distinct conversations.
    other = sk("/api/chat", {"messages": [
        sys0, {"role": "user", "content": "different opener"}]}, {})
    assert other is not None and other != k2
    kc = sk("/api/generate", {"context": [1, 2, 3]}, {})
    assert kc is not None
    assert sk("/api/generate", {"context": [1, 2, 3, 9]}, {}) != kc
    assert sk("/api/generate", {"prompt": "one shot"}, {}) is None


# -- metrics aggregation -----------------------------------------------------

def test_metrics_replica_labels_and_fleet_totals():
    """Per-replica series get a replica label (merged INTO an existing
    brace block — the serve/multi.py model-label discipline), and the
    unsuffixed fleet series equals the sum of the replica scrapes."""
    rt, reps = _fleet(2, backend_factory=lambda i: LabeledMetricsLLM(
        occupancy=float(i + 1)))
    try:
        for i in range(4):
            _gen(rt.url, f"traffic {i}\n\nReply:")
        with urllib.request.urlopen(f"{rt.url}/metrics", timeout=5) as r:
            text = r.read().decode()
        snap = parse_metrics_text(text)
        # Label merge: already-labeled series nests replica INSIDE the
        # block; a second {} suffix would break the whole scrape.
        assert 'serve_spec_proposed_total{source="ngram",replica="0"}' in snap
        assert 'serve_spec_proposed_total{source="ngram",replica="1"}' in snap
        assert "{source" not in text.split("}{")[0] or "}{" not in text
        # Fleet totals = sum over replicas, for plain and labeled series.
        assert snap["serve_batch_occupancy"] == 3.0        # 1 + 2
        assert snap['serve_spec_proposed_total{source="ngram"}'] == 15.0
        assert (snap["serve_requests_total"]
                == snap['serve_requests_total{replica="0"}']
                + snap['serve_requests_total{replica="1"}'])
        assert snap["serve_requests_total"] == 4.0
        # The router's own counters ride along.
        assert snap["router_requests_total"] == 4.0
        assert 'router_routed_total{replica="0"}' in snap
    finally:
        _stop(rt, reps)


class PrefixStoreLLM(FakeLLM):
    """Backend exposing a REAL PrefixStore through the round-11 share
    hooks (the engine's surface, without the model): the router's
    reconciliation pass must move entries between replicas."""

    def __init__(self, name: str = "rep") -> None:
        super().__init__(name=name)
        from p2p_llm_chat_tpu.serve.prefix import PrefixStore
        self.store = PrefixStore()

    def prefix_hashes(self):
        return self.store.hashes()

    def prefix_export(self, h):
        return self.store.export_payload(h)

    def prefix_import(self, data):
        return self.store.import_payload(data)


def test_prefix_share_syncs_replicas():
    """A prefix promoted on replica 0 appears on replica 1 within a few
    scrape passes: the router lists by token hash and has the lacking
    replica PULL the payload from the promoting one."""
    import numpy as np
    import jax.numpy as jnp
    from p2p_llm_chat_tpu.serve.prefix import PrefixEntry, token_hash

    backends = []

    def factory(i):
        b = PrefixStoreLLM()
        backends.append(b)
        return b

    rt, reps = _fleet(2, backend_factory=factory, prefix_share=True)
    try:
        ids = tuple(int(t) for t in range(40))
        rng = np.random.RandomState(0)
        k = jnp.asarray(rng.randn(2, 40, 2, 4), jnp.float32)
        # hits >= 1: only proven entries ship (the sync's hotness floor).
        backends[0].store.put(PrefixEntry(ids=ids, k=k, v=k + 1, hits=3))
        deadline = time.monotonic() + 10.0
        while time.monotonic() < deadline:
            if len(backends[1].store) >= 1:
                break
            time.sleep(0.05)
        got = backends[1].store.snapshot()
        assert got and got[0].ids == ids, "prefix never synced"
        assert got[0].token_hash == token_hash(ids)
        np.testing.assert_array_equal(np.asarray(got[0].k),
                                      np.asarray(k))
        with urllib.request.urlopen(f"{rt.url}/metrics", timeout=5) as r:
            snap = parse_metrics_text(r.read().decode())
        assert snap["router_prefix_syncs_total"] >= 1.0
        # Stable state: both replicas list the hash; no resync churn.
        time.sleep(0.4)
        assert len(backends[1].store) == 1
    finally:
        _stop(rt, reps)


def test_prefix_share_skips_storeless_replicas():
    """FakeLLM replicas answer 501 on /admin/prefix — the router marks
    them unsupported once and the sync pass stays a no-op (no error
    spam, no counter movement)."""
    rt, reps = _fleet(2, prefix_share=True)
    try:
        time.sleep(0.5)              # several scrape+sync passes
        with urllib.request.urlopen(f"{rt.url}/metrics", timeout=5) as r:
            snap = parse_metrics_text(r.read().decode())
        assert snap.get("router_prefix_syncs_total", 0) == 0
        assert snap.get("router_prefix_sync_failures_total", 0) == 0
        # Under the router's lock: the scrape thread is still running,
        # and GRAFTCHECK_LOCKCHECK=1 enforces the guarded-by annotation
        # on test readers too.
        with rt._mu:
            assert rt._prefix_unsupported == {0, 1}
    finally:
        _stop(rt, reps)


class KVTierMetricsLLM(FakeLLM):
    """Backend exporting the round-11 kv_* session gauges."""

    def __init__(self, name: str = "rep", parked: float = 2.0) -> None:
        super().__init__(name=name)
        self.parked = parked

    def metrics_snapshot(self):
        return {"kv_parked_sessions": self.parked,
                "kv_open_sessions": self.parked + 1,
                "kv_host_bytes": 1000.0 * self.parked,
                "kv_waked_total": self.parked,
                "kv_wake_p50_ms": 5.0}


def test_metrics_kv_tier_fleet_aggregation():
    """Session/byte gauges sum into unsuffixed fleet totals (capacity
    numbers an operator adds up); wake quantiles stay per-replica only
    (summing a p50 would fabricate a number under the real name)."""
    rt, reps = _fleet(2, backend_factory=lambda i: KVTierMetricsLLM(
        parked=float(i + 1)))
    try:
        with urllib.request.urlopen(f"{rt.url}/metrics", timeout=5) as r:
            snap = parse_metrics_text(r.read().decode())
        assert snap['kv_parked_sessions{replica="0"}'] == 1.0
        assert snap['kv_parked_sessions{replica="1"}'] == 2.0
        assert snap["kv_parked_sessions"] == 3.0           # fleet sum
        assert snap["kv_open_sessions"] == 5.0
        assert snap["kv_host_bytes"] == 3000.0
        assert snap["kv_waked_total"] == 3.0               # counter sums
        assert 'kv_wake_p50_ms{replica="0"}' in snap
        assert "kv_wake_p50_ms" not in snap   # no fabricated fleet p50
    finally:
        _stop(rt, reps)


def test_merge_label_and_parse_helpers():
    assert _merge_label("m_total", 'replica="2"') == 'm_total{replica="2"}'
    assert (_merge_label('m_total{a="b"}', 'replica="2"')
            == 'm_total{a="b",replica="2"}')
    parsed = parse_metrics_text(
        "# TYPE a counter\na 1.5\n"
        'b{x="y z"} 2\nmalformed\n# c 9\n')
    assert parsed == {"a": 1.5, 'b{x="y z"}': 2.0}


# -- the two-OS-process full-stack matrix (ci.sh full) -----------------------

def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _spawn_replica(port: int, extra_env: dict = ()) -> subprocess.Popen:
    """One full-stack engine process: paged KV + speculation + prefix
    cache + chunked prefill + fused-K — the whole single-host feature
    set the lockstep plane strips (the point of replica-router mode)."""
    env = dict(
        os.environ,
        PYTHONPATH=REPO,
        # One compute thread per replica, in EVERY phase: the scaling
        # claim is "a replica owns its accelerator; adding replicas
        # adds hardware". On a shared-CPU host a single XLA process
        # grabs every core, so without the cap the fleet phase just
        # splits the same cores two ways and the structural 2-waves-vs-
        # 4-waves win washes out to ~1.0x (measured). Capping both
        # phases keeps per-replica capability constant — the thing the
        # fleet is supposed to double.
        XLA_FLAGS=("--xla_force_host_platform_device_count=1 "
                   "--xla_cpu_multi_thread_eigen=false "
                   "intra_op_parallelism_threads=1"),
        OMP_NUM_THREADS="1",
        OPENBLAS_NUM_THREADS="1",
        JAX_PLATFORMS="cpu",
        SERVE_BACKEND="tpu",
        MODEL_CONFIG="tiny",
        LLM_MODEL="tiny",
        SERVE_MAX_SEQ="128",
        # 2 rows per replica: the throughput phase drives 8 requests, so
        # ONE replica serves them in 4 sequential waves while the fleet
        # runs 2 waves per replica in parallel — per-replica capacity is
        # what the fleet doubles, and the workload must exceed it or the
        # comparison measures HTTP overhead, not serving.
        SERVE_SLOTS="2",
        SERVE_PAGE_SIZE="16",
        SERVE_SPEC="2",
        SERVE_PREFIX="1",
        # Register the workload's common head up front: every request
        # then splices this prefix (the cache is exercised for real),
        # and — because observe() skips grains covered by a longer
        # registered entry — no auto-promotion build can fire MID-
        # measurement (a background splice-program compile on whichever
        # replica crossed the sighting threshold later was measured
        # inflating the fleet phase ~2x).
        SERVE_PREFIX_TEXTS="replica workload ",
        SERVE_WARMUP="32,64",
        SERVE_ADDR=f"127.0.0.1:{port}",
        SERVE_ROUTER_UPSTREAMS="",
        SERVE_COORDINATOR="",
        **dict(extra_env or ()),
    )
    code = ("import jax\n"
            "jax.config.update('jax_platforms', 'cpu')\n"
            "from p2p_llm_chat_tpu.serve.api import main\nmain()\n")
    return subprocess.Popen([sys.executable, "-c", code], env=env,
                            stdout=subprocess.PIPE,
                            stderr=subprocess.STDOUT)


def _wait_ready(url: str, procs, deadline_s: float = 240) -> None:
    deadline = time.monotonic() + deadline_s
    while time.monotonic() < deadline:
        for p in procs:
            if p.poll() is not None:
                out = p.stdout.read().decode(errors="replace")
                raise AssertionError(
                    f"process died rc={p.returncode}:\n{out[-3000:]}")
        try:
            with urllib.request.urlopen(f"{url}/readyz", timeout=5):
                return
        except (urllib.error.URLError, ConnectionError, OSError):
            time.sleep(1.0)
    raise AssertionError(f"{url} never became ready")


def _shutdown(procs) -> None:
    for p in procs:
        p.terminate()
    for p in procs:
        try:
            p.wait(timeout=10)
        except subprocess.TimeoutExpired:
            p.kill()


@pytest.mark.slow
@pytest.mark.model
def test_two_process_replica_router_full_stack():
    """The Round-10 acceptance matrix: two OS-process replicas, each the
    FULL single-host stack (paged KV + spec + prefix cache), behind the
    router. Distinct greedy requests through the router match the
    direct-replica output exactly (identical random-init params — same
    seed — make replicas interchangeable), the Ollama contract including
    streaming holds through the router, BOTH replicas serve, and the
    routed fleet beats one replica on the same workload (wall-clock;
    each replica is its own OS process, so the fleet uses both cores).
    A failpoint-saturated replica routes around, and a drained replica
    finishes in-flight work while new work lands elsewhere."""
    ports = [_free_port(), _free_port()]
    router_port = _free_port()
    procs = [_spawn_replica(p) for p in ports]
    router_env = dict(
        os.environ, PYTHONPATH=REPO,
        SERVE_ADDR=f"127.0.0.1:{router_port}",
        SERVE_ROUTER_UPSTREAMS=",".join(
            f"http://127.0.0.1:{p}" for p in ports),
        SERVE_ROUTER_SCRAPE_MS="200",
    )
    procs.append(subprocess.Popen(
        [sys.executable, "-m", "p2p_llm_chat_tpu.serve.router"],
        env=router_env, stdout=subprocess.PIPE,
        stderr=subprocess.STDOUT))
    url = f"http://127.0.0.1:{router_port}"
    rep0 = f"http://127.0.0.1:{ports[0]}"
    try:
        for u in (rep0, f"http://127.0.0.1:{ports[1]}", url):
            _wait_ready(u, procs)

        # 96-token greedy decodes: long enough that decode ticks — the
        # thing replicas parallelize — dominate the wall, not admission
        # or HTTP round trips.
        def gen(base: str, prompt: str, n: int = 96, stream: bool = False):
            req = urllib.request.Request(
                f"{base}/api/generate",
                data=json.dumps({
                    "model": "tiny", "prompt": prompt, "stream": stream,
                    "options": {"num_predict": n}}).encode(),
                headers={"Content-Type": "application/json"})
            with urllib.request.urlopen(req, timeout=120) as r:
                raw = r.read().decode()
            if stream:
                return [json.loads(l) for l in raw.splitlines()]
            return json.loads(raw)

        # Warm both replicas' serving programs (admission buckets +
        # decode windows compile on first touch beyond the warmup set).
        prompts = [f"replica workload {i}" for i in range(8)]
        for base in (rep0, f"http://127.0.0.1:{ports[1]}"):
            for p in prompts[:2]:
                gen(base, p)

        # Byte-exactness leg: the router adds NOTHING to the payload —
        # a solo request through the router equals the same solo request
        # direct to a replica (identical processes, params and solo
        # scheduling on every replica). Byte equality is asserted only
        # solo-vs-solo ON PURPOSE: with random-init weights the logits
        # are near-tied, and the spec verify forward matches the decode
        # forward to 2e-4 (test_spec), not bitwise — so a different
        # spec/fuse tick SCHEDULE (solo vs concurrently-batched rows)
        # can legitimately flip an argmax tie tokens into a 96-token
        # greedy completion. Real checkpoints don't sit on ties; the
        # schedule-invariance oracle at trained-model sharpness is
        # test_spec's job, not this matrix's.
        wants = {p: gen(rep0, p)["response"] for p in prompts[:3]}
        for p in prompts[:3]:
            assert gen(url, p)["response"] == wants[p]

        # Ollama contract through the router: streaming NDJSON shape +
        # terminal stats record carrying the same bytes.
        lines = gen(url, prompts[0], stream=True)
        assert lines[-1]["done"] is True
        assert "eval_count" in lines[-1]
        streamed = "".join(l.get("response", "") for l in lines)
        assert streamed == wants[prompts[0]]

        # Throughput phases: all 8 requests concurrently — through ONE
        # replica, then through the router over both.
        def drive(base: str) -> float:
            errs: list = []
            outs: dict = {}

            def worker(p: str) -> None:
                try:
                    outs[p] = gen(base, p)
                except Exception as e:      # noqa: BLE001
                    errs.append(e)

            ths = [threading.Thread(target=worker, args=(p,))
                   for p in prompts]
            t0 = time.monotonic()
            for t in ths:
                t.start()
            for t in ths:
                t.join(timeout=180)
            wall = time.monotonic() - t0
            assert not errs, errs
            for p in prompts:
                assert outs[p]["done"] is True
                assert outs[p]["eval_count"] > 0
                assert outs[p]["response"]
            return wall

        # Best-of-2 per phase: one transient stall (GC, a scrape burst,
        # a noisy CI neighbor) on a 2-core box can swallow the whole
        # structural margin; the MINIMUM wall is the honest measure of
        # each topology's capability on the same workload.
        t_single = min(drive(rep0), drive(rep0))
        t_fleet = min(drive(url), drive(url))

        # Both replicas took real traffic.
        with urllib.request.urlopen(f"{url}/admin/replicas",
                                    timeout=10) as r:
            reps = json.loads(r.read())["replicas"]
        assert all(rp["routed"] > 0 for rp in reps), reps

        # Aggregate throughput: same workload, two OS processes vs one
        # (throughput == tokens/wall over the same workload, so the
        # wall ratio IS the throughput ratio). Each capped replica
        # process wants ~2 cores (python host loop + its XLA thread),
        # so the fleet can only EXPRESS its structural 2-waves-vs-4-
        # waves win where both replicas get that in parallel — >= 4
        # cores. There the Round-10 bar applies: >= 1.8x. On a 2-core
        # container the single phase already overlaps host+device
        # across both cores and the fleet time-slices the same two
        # (measured ~0.9-1.1x, an arithmetic ceiling, not a router
        # defect) — so the assertion there is the one thing the router
        # still owes: bounded overhead, never a pathological slowdown.
        speedup = t_single / t_fleet
        if (os.cpu_count() or 2) >= 4:
            assert speedup >= 1.8, (t_single, t_fleet, speedup)
        else:
            assert t_fleet <= 1.35 * t_single, (t_single, t_fleet, speedup)

        # /metrics aggregation over real engines: fleet totals = sum of
        # replica series for the serving-plane counters.
        with urllib.request.urlopen(f"{url}/metrics", timeout=10) as r:
            snap = parse_metrics_text(r.read().decode())
        for base_name in ("serve_requests_total", "serve_admitted_total"):
            per = [v for k, v in snap.items()
                   if k.startswith(base_name + "{")]
            assert len(per) == 2 and abs(sum(per) - snap[base_name]) < 1e-6

        # Drain replica 0 through the router: new work lands on replica
        # 1 only; replica 0's own front reports draining; undrain
        # restores it.
        with urllib.request.urlopen(urllib.request.Request(
                f"{url}/admin/drain", data=b'{"replica": 0}',
                headers={"Content-Type": "application/json"},
                method="POST"), timeout=10) as r:
            r.read()
        time.sleep(0.5)                      # a scrape sees the flip
        routed_before = [rp["routed"] for rp in json.loads(
            urllib.request.urlopen(f"{url}/admin/replicas", timeout=10)
            .read())["replicas"]]
        for i in range(3):
            assert gen(url, prompts[i])["response"] == wants[prompts[i]]
        routed_after = [rp["routed"] for rp in json.loads(
            urllib.request.urlopen(f"{url}/admin/replicas", timeout=10)
            .read())["replicas"]]
        assert routed_after[0] == routed_before[0]
        assert routed_after[1] == routed_before[1] + 3
        with pytest.raises(urllib.error.HTTPError) as he:
            urllib.request.urlopen(f"{rep0}/readyz", timeout=5)
        assert he.value.code == 503
        he.value.close()
        with urllib.request.urlopen(urllib.request.Request(
                f"{url}/admin/undrain", data=b'{"replica": 0}',
                headers={"Content-Type": "application/json"},
                method="POST"), timeout=10) as r:
            r.read()
        with urllib.request.urlopen(f"{rep0}/readyz", timeout=5) as r:
            assert r.status == 200
    finally:
        _shutdown(procs)


@pytest.mark.slow
@pytest.mark.model
def test_two_process_router_failpoint_overload():
    """Induced overload (the acceptance's failpoint leg): replica 0's
    admission site armed to raise on every admit — its requests die
    server-side, the router fails over, and every request still
    completes on the healthy replica."""
    ports = [_free_port(), _free_port()]
    router_port = _free_port()
    procs = [
        _spawn_replica(ports[0], extra_env={
            "FAIL_POINTS": "serve.scheduler.admit=raise"}),
        _spawn_replica(ports[1]),
    ]
    router_env = dict(
        os.environ, PYTHONPATH=REPO,
        SERVE_ADDR=f"127.0.0.1:{router_port}",
        SERVE_ROUTER_UPSTREAMS=",".join(
            f"http://127.0.0.1:{p}" for p in ports),
        SERVE_ROUTER_SCRAPE_MS="200",
    )
    procs.append(subprocess.Popen(
        [sys.executable, "-m", "p2p_llm_chat_tpu.serve.router"],
        env=router_env, stdout=subprocess.PIPE,
        stderr=subprocess.STDOUT))
    url = f"http://127.0.0.1:{router_port}"
    try:
        for u in (f"http://127.0.0.1:{ports[0]}",
                  f"http://127.0.0.1:{ports[1]}", url):
            _wait_ready(u, procs)
        for i in range(6):
            req = urllib.request.Request(
                f"{url}/api/generate",
                data=json.dumps({
                    "model": "tiny", "prompt": f"chaos {i}",
                    "stream": False,
                    "options": {"num_predict": 12}}).encode(),
                headers={"Content-Type": "application/json"})
            with urllib.request.urlopen(req, timeout=120) as r:
                body = json.loads(r.read())
            assert body["done"] is True
        with urllib.request.urlopen(f"{url}/admin/replicas",
                                    timeout=10) as r:
            reps = json.loads(r.read())["replicas"]
        by_idx = {rp["index"]: rp for rp in reps}
        assert by_idx[1]["routed"] >= 6, reps
    finally:
        _shutdown(procs)


# -- live session migration + elastic fleet (round 13) ------------------------

class SessionTierLLM(FakeLLM):
    """Backend exposing a REAL KVTier through the round-13 migration
    hooks (the engine's surface without a model): the router's
    drain-as-migration must move payloads between replicas' tiers."""

    def __init__(self, name: str = "rep") -> None:
        super().__init__(name=name)
        from p2p_llm_chat_tpu.serve.kv_tier import KVTier
        self.tier = KVTier(host_bytes=1 << 20)
        self.park_alls = 0

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

    def session_park_all(self):
        self.park_alls += 1


def _parked_session(key: str, nbytes: int = 64):
    import numpy as np
    from p2p_llm_chat_tpu.serve.kv_tier import SessionKV
    arr = np.zeros(nbytes // 2, np.int8)
    return SessionKV(key=key, tokens=tuple(range(40)), length=40,
                     host=((arr, arr, None, None), 1), nbytes=2 * arr.nbytes)


def _router_metrics(rt) -> dict:
    with urllib.request.urlopen(f"{rt.url}/metrics", timeout=5) as r:
        return parse_metrics_text(r.read().decode())


def test_drain_migrates_sessions_and_flips_affinity():
    """Drain-as-migration over real tiers: every session parked on the
    drained replica moves to the survivor (export -> import -> forget on
    ack), the affinity table flips — including the anonymous head:-keyed
    entry — and the ledger counts migrations, never losses."""
    backends: list = []

    def factory(i):
        b = SessionTierLLM()
        backends.append(b)
        return b

    rt, reps = _fleet(2, backend_factory=factory)
    try:
        backends[0].tier.insert(_parked_session("sid:conv-mig"))
        backends[0].tier.insert(_parked_session("head:cafebabe12345678"))
        st, body = http_json("POST", f"{rt.url}/admin/drain", {"replica": 0})
        assert st == 200
        mig = body["migration"]
        assert mig["migrated"] == 2 and mig["failed"] == 0, mig
        assert mig["dest"] == 1
        assert backends[0].park_alls == 1          # the park-all pre-step ran
        assert set(backends[1].tier.sessions_meta()) == {
            "sid:conv-mig", "head:cafebabe12345678"}
        assert backends[0].tier.sessions_meta() == {}   # forgotten on ack
        # Not an eviction on the source (capacity dashboards unmoved).
        assert backends[0].tier.stats()["evicted_total"] == 0
        # Affinity flipped atomically: explicit ids strip the sid:
        # prefix; head: keys ride verbatim.
        with rt._mu:
            assert rt._sessions["conv-mig"] == 1
            assert rt._sessions["head:cafebabe12345678"] == 1
        snap = _router_metrics(rt)
        assert snap["kv_sessions_migrated_total"] == 2.0
        assert snap.get("kv_sessions_lost_total", 0) == 0.0
        assert snap["router_migration_ms_count"] == 2.0
    finally:
        _stop(rt, reps)


def test_failed_export_retains_source_and_client_unaffected():
    """The serve.kv_tier.export failpoint contract under a drain: the
    migration step fails, the SOURCE keeps the session (no forget ever
    fires), the failure is counted — and a client request through the
    router still completes."""
    from p2p_llm_chat_tpu.utils import failpoints
    backends: list = []

    def factory(i):
        b = SessionTierLLM()
        backends.append(b)
        return b

    rt, reps = _fleet(2, backend_factory=factory)
    try:
        backends[0].tier.insert(_parked_session("sid:sticky"))
        failpoints.arm("serve.kv_tier.export", "raise")
        try:
            st, body = http_json("POST", f"{rt.url}/admin/drain",
                                 {"replica": 0})
        finally:
            failpoints.disarm_all()
        assert st == 200
        assert body["migration"]["migrated"] == 0
        assert body["migration"]["failed"] == 1
        # Both replicas consistent: source retains, destination clean.
        assert "sid:sticky" in backends[0].tier.sessions_meta()
        assert backends[1].tier.sessions_meta() == {}
        snap = _router_metrics(rt)
        assert snap["router_migration_failures_total"] == 1.0
        assert snap["kv_sessions_migrated_total"] == 0.0
        # The client never sees any of it.
        out = _gen(rt.url, "still serving after failed export\n\nReply:")
        assert out["done"] is True
    finally:
        _stop(rt, reps)


def test_migrate_failpoint_fails_step_and_source_retains():
    """The serve.router.migrate failpoint contract: the fault fires in
    the router's own per-session migrate loop (before the import POST
    ever leaves), the step counts as failed, no forget fires, and the
    source keeps the session — same retention posture as a failed
    export, proving the router side of the loop honors it too."""
    from p2p_llm_chat_tpu.utils import failpoints
    backends: list = []

    def factory(i):
        b = SessionTierLLM()
        backends.append(b)
        return b

    rt, reps = _fleet(2, backend_factory=factory)
    try:
        backends[0].tier.insert(_parked_session("sid:stuck"))
        failpoints.arm("serve.router.migrate", "raise")
        try:
            st, body = http_json("POST", f"{rt.url}/admin/drain",
                                 {"replica": 0})
        finally:
            failpoints.disarm_all()
        assert st == 200
        assert body["migration"]["migrated"] == 0
        assert body["migration"]["failed"] == 1
        assert "sid:stuck" in backends[0].tier.sessions_meta()
        assert backends[1].tier.sessions_meta() == {}
        snap = _router_metrics(rt)
        assert snap["router_migration_failures_total"] == 1.0
    finally:
        _stop(rt, reps)


def test_dead_replica_counts_lost_sessions_and_rehomes():
    """Replica death: the ledger counts the replica's LAST-SCRAPED open
    sessions (the KV that actually existed — not the LRU-bounded
    affinity entries), affinity entries homed on it drop (follow-ups
    rebalance and cold re-prefill — never an error)."""
    backends: list = []

    def factory(i):
        b = SessionTierLLM()
        backends.append(b)
        return b

    rt, reps = _fleet(2, backend_factory=factory)
    try:
        _gen(rt.url, "pin me\n\nReply:", session="doomed-1")
        with rt._mu:
            home = rt._sessions["doomed-1"]
        # One real parked session on the home replica, observed by the
        # scrape loop before the death (the ledger's evidence).
        backends[home].tier.insert(_parked_session("sid:doomed-1"))
        home_rep = next(r for r in rt._replica_snapshot()
                        if r.index == home)
        deadline = time.monotonic() + 5.0
        while time.monotonic() < deadline:
            with rt._mu:
                seen = home_rep.sessions or ()
            if "sid:doomed-1" in seen:
                break
            time.sleep(0.05)
        assert "sid:doomed-1" in seen, "scrape never observed the session"
        reps[home].stop()                      # the home replica dies
        # The follow-up turn must still complete, on the survivor.
        out = _gen(rt.url, "follow-up\n\nReply:", session="doomed-1")
        assert out["done"] is True
        deadline = time.monotonic() + 5.0
        lost = 0.0
        while time.monotonic() < deadline:
            lost = _router_metrics(rt).get("kv_sessions_lost_total", 0.0)
            if lost >= 1.0:
                break
            time.sleep(0.05)
        assert lost == 1.0                     # the real session, once
        with rt._mu:
            assert rt._sessions.get("doomed-1") != home
    finally:
        rt.stop()
        for r in reps:
            try:
                r.stop()
            except Exception:          # noqa: BLE001 — already stopped
                pass


def test_autoscaler_scales_up_then_down_via_drain():
    """The queue-driven autoscaler: sustained backpressure spawns a
    replica (counted, fleet grows, new replica takes traffic once
    ready); an idle fleet retires the spawned one through
    drain-as-migration (counted, fleet shrinks, only spawner-owned
    replicas are victims)."""
    from p2p_llm_chat_tpu.serve.router import Autoscaler

    class DepthLLM(FakeLLM):
        def __init__(self):
            super().__init__(name="rep")
            self.depth = 50.0

        def metrics_snapshot(self):
            return {"serve_queue_depth": self.depth}

    base = DepthLLM()
    spawned: list = []

    def spawn():
        srv = OllamaServer(FakeLLM(name="rep"), addr="127.0.0.1:0").start()
        spawned.append(srv)
        return srv.url

    retired: list = []

    def retire(url):
        retired.append(url)
        for s in spawned:
            if s.url == url:
                s.stop()

    rt, reps = _fleet(1, backend_factory=lambda i: base, scrape_ms=50)
    rt.attach_autoscaler(Autoscaler(
        spawn_fn=spawn, retire_fn=retire,
        can_retire_fn=lambda url: any(s.url == url for s in spawned),
        min_replicas=1, max_replicas=2, up_q=4.0, down_q=0.5, sustain=2))
    try:
        deadline = time.monotonic() + 10.0
        while time.monotonic() < deadline:
            _, body = http_json("GET", f"{rt.url}/admin/replicas")
            if len(body["replicas"]) == 2:
                break
            time.sleep(0.05)
        assert len(body["replicas"]) == 2, "never scaled up"
        assert len(spawned) == 1
        snap = _router_metrics(rt)
        assert snap["router_autoscale_up_total"] == 1.0
        # Pressure collapses: the fleet idles down to min, retiring the
        # SPAWNED replica (boot upstreams are the operator's).
        base.depth = 0.0
        deadline = time.monotonic() + 10.0
        while time.monotonic() < deadline:
            _, body = http_json("GET", f"{rt.url}/admin/replicas")
            if len(body["replicas"]) == 1:
                break
            time.sleep(0.05)
        assert len(body["replicas"]) == 1, "never scaled down"
        assert retired == [spawned[0].url]
        assert body["replicas"][0]["index"] == 0   # the boot replica stays
        snap = _router_metrics(rt)
        assert snap["router_autoscale_down_total"] == 1.0
        # Still serving throughout.
        assert _gen(rt.url, "post scale\n\nReply:")["done"] is True
    finally:
        _stop(rt, reps)
        for s in spawned:
            try:
                s.stop()
            except Exception:          # noqa: BLE001 — may be stopped
                pass
