"""Disaggregated prefill/decode serving (serve/disagg.py round 14).

The correctness contract: a conversation served prefill-replica →
handoff → decode-replica produces output BYTE-identical to the same
seeds on a replica it never left — the prefill side parks exactly
``ids[:-1]`` (scheduler.prefill_park), the payload moves over the PR 11
migration wire, and the decode side's verify-shaped wake samples the
first token as the first draw of the request's own seeded RNG. The
robustness contract (failpoint ``serve.disagg.handoff``): any failed
handoff step degrades to finishing the request on the prefill replica —
never a client-visible error, ``disagg_handoff_failures_total`` moves,
``kv_sessions_lost_total`` does not.

Fast legs (tier-1, wired into ci.sh fast): class-flag parsing, pool
routing with the mixed-compatibility fallback and the 501
unsupported-memo, the class re-resolution regression (a replica
restarted on the same port with a new role must CHANGE pools — pinning
the first-seen class was the round-14 bug), per-class autoscale up/down
with spawner-owned victims, and ONE combined 2-engine leg: the
byte-identity oracle (engine-level and through the real router;
explicit sid and anonymous head-hash) plus handoff-failure degradation
under the failpoint.

Slow legs (ci.sh full): the two-OS-process handoff matrix through the
real router, and the chaos leg — a 1-prefill + 2-decode fleet under
live loadgen (disagg_session/group_chat/long_ctx mix) with
``serve.disagg.handoff=raise@0.3`` armed: zero client-visible errors,
zero session loss, and admission prefill work provably OFF the decode
replicas (their ``prefill_chunks_total`` stays 0).
"""

import json
import os
import socket
import subprocess
import sys
import time
import urllib.error
import urllib.request

import pytest

import jax
import jax.numpy as jnp

from p2p_llm_chat_tpu.models import llama
from p2p_llm_chat_tpu.models.configs import get_config
from p2p_llm_chat_tpu.serve import FakeLLM, OllamaServer, ReplicaRouter
from p2p_llm_chat_tpu.serve.backend import (GenerateOptions,
                                            GenerateRequest, RequestStats)
from p2p_llm_chat_tpu.serve.disagg import (ClassAutoscaler,
                                           replica_class_from_env)
from p2p_llm_chat_tpu.serve.engine import TPUEngine
from p2p_llm_chat_tpu.serve.router import parse_metrics_text
from p2p_llm_chat_tpu.tokenizer import ByteTokenizer
from p2p_llm_chat_tpu.utils import failpoints

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

CFG = get_config("tiny")
PARAMS = llama.init_params(CFG, jax.random.PRNGKey(0), dtype=jnp.float32)
TOK = ByteTokenizer(vocab_size=CFG.vocab_size)

PROMPT1 = "hello there, how are you doing today my good friend?"
PROMPT2 = " tell me one more thing before we finish?"
ANON = "an entirely anonymous conversation opener, long enough to index!"


def run(engine, prompt, session="", max_tokens=8, ctx=()):
    stats = RequestStats()
    req = GenerateRequest(prompt=prompt, session=session,
                          context=tuple(ctx),
                          options=GenerateOptions(max_tokens=max_tokens,
                                                  temperature=0.0, seed=1))
    return "".join(engine.generate_stream(req, stats)), stats


def make_engine(slots=2, buckets=(64, 128), prefill_chunk=256):
    eng = TPUEngine(PARAMS, CFG, TOK, num_slots=slots, max_seq=256,
                    page_size=64, kv_quant=True,
                    kv_host_gb=1.0, kv_idle_s=1e9,
                    prefill_chunk=prefill_chunk)
    eng.warmup(buckets=buckets)
    return eng


def wait_for(fn, timeout=15.0, msg="condition"):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if fn():
            return
        time.sleep(0.02)
    raise AssertionError(f"timed out waiting for {msg}")


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _gen(url, prompt, session="", ctx=(), timeout=120):
    body = {"model": "tiny", "prompt": prompt, "stream": False,
            "options": {"num_predict": 8, "temperature": 0.0, "seed": 1}}
    if session:
        body["session"] = session
    if ctx:
        body["context"] = list(ctx)
    req = urllib.request.Request(
        f"{url}/api/generate", data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=timeout) as r:
        return json.loads(r.read())


def _replicas(rt) -> list:
    with urllib.request.urlopen(f"{rt if isinstance(rt, str) else rt.url}"
                                "/admin/replicas", timeout=10) as r:
        return json.loads(r.read())["replicas"]


def _router_snap(url: str) -> dict:
    with urllib.request.urlopen(f"{url}/metrics", timeout=10) as r:
        return parse_metrics_text(r.read().decode())


def _wait_classes(rt, want: dict) -> None:
    """Poll until the router's table shows each url's expected class
    (and readiness) — the scrape loop needs a pass or two."""
    def ok():
        reps = _replicas(rt)
        by_url = {r["url"]: r for r in reps}
        return all(u in by_url and by_url[u]["class"] == c
                   and by_url[u]["ready"] for u, c in want.items())
    wait_for(ok, msg=f"router class view {want}")


# -- class flag ---------------------------------------------------------------

def test_replica_class_from_env(monkeypatch):
    monkeypatch.delenv("SERVE_REPLICA_CLASS", raising=False)
    assert replica_class_from_env() == "mixed"
    for cls in ("prefill", "decode", "mixed"):
        monkeypatch.setenv("SERVE_REPLICA_CLASS", cls)
        assert replica_class_from_env() == cls
    monkeypatch.setenv("SERVE_REPLICA_CLASS", "Decode ")
    assert replica_class_from_env() == "decode"   # normalized
    monkeypatch.setenv("SERVE_REPLICA_CLASS", "gpu")
    with pytest.raises(SystemExit):
        replica_class_from_env()
    # The front validates its constructor arg the same way.
    with pytest.raises(ValueError):
        OllamaServer(FakeLLM(name="rep"), addr="127.0.0.1:0",
                     replica_class="bogus")


# -- pool routing: fallback + unsupported memo (FakeLLM, no engine) ----------

def test_pool_fallback_and_unsupported_memo():
    """A class-tagged fleet whose prefill replica has NO session tier
    (FakeLLM): the first new conversation attempts the handoff, gets
    the 501, memoizes the replica as disagg-unsupported, and still
    completes on the fallback path — and with the prefill pool
    unsupported, new work avoids decode-class replicas (stable
    demotion), landing on the prefill replica."""
    pre = OllamaServer(FakeLLM(name="rep"), addr="127.0.0.1:0",
                       replica_class="prefill").start()
    dec = OllamaServer(FakeLLM(name="rep"), addr="127.0.0.1:0",
                       replica_class="decode").start()
    rt = ReplicaRouter([pre.url, dec.url], addr="127.0.0.1:0",
                       scrape_ms=50).start()
    try:
        _wait_classes(rt, {pre.url: "prefill", dec.url: "decode"})
        for i in range(3):
            body = _gen(rt.url, f"fresh conversation {i}\n\nReply:")
            assert body["done"] is True
        with rt._mu:
            assert rt._disagg_unsupported, "501 was not memoized"
        snap = _router_snap(rt.url)
        assert snap.get("disagg_handoffs_total", 0) == 0
        assert snap.get("disagg_handoff_failures_total", 0) == 0
        assert snap['router_pool_replicas{class="prefill"}'] == 1.0
        assert snap['router_pool_replicas{class="decode"}'] == 1.0
        assert snap['router_pool_replicas{class="mixed"}'] == 0.0
        # New work avoided the decode replica (admission belongs on
        # the prefill/mixed pools).
        by_url = {r["url"]: r for r in _replicas(rt)}
        assert by_url[pre.url]["routed"] == 3
        assert by_url[dec.url]["routed"] == 0
    finally:
        rt.stop()
        pre.stop()
        dec.stop()
    # Mixed-only fleet: no pools, no handoff attempts at all.
    reps = [OllamaServer(FakeLLM(name="rep"), addr="127.0.0.1:0").start()
            for _ in range(2)]
    rt = ReplicaRouter([r.url for r in reps], addr="127.0.0.1:0",
                       scrape_ms=50).start()
    try:
        wait_for(lambda: all(r["ready"] for r in _replicas(rt)),
                 msg="mixed fleet ready")
        assert _gen(rt.url, "plain fleet\n\nReply:")["done"] is True
        with rt._mu:
            assert not rt._disagg_unsupported
        assert _router_snap(rt.url).get("disagg_handoffs_total", 0) == 0
    finally:
        rt.stop()
        for r in reps:
            r.stop()


# -- the class re-resolution regression --------------------------------------

def test_class_reresolved_on_restart_same_port():
    """A replica restarted on the SAME port with a NEW role is a
    different pool member: the scrape loop must re-resolve the class on
    every pass, not pin the first sighting — the round-14 bug routed
    new conversations at a replica that no longer ran admission
    work."""
    port = _free_port()
    addr = f"127.0.0.1:{port}"
    url = f"http://127.0.0.1:{port}"
    first = OllamaServer(FakeLLM(name="rep"), addr=addr,
                         replica_class="prefill").start()
    other = OllamaServer(FakeLLM(name="rep"), addr="127.0.0.1:0").start()
    rt = ReplicaRouter([url, other.url], addr="127.0.0.1:0",
                       scrape_ms=50).start()
    second = None
    try:
        _wait_classes(rt, {url: "prefill"})
        first.stop()
        wait_for(lambda: not next(r for r in _replicas(rt)
                                  if r["url"] == url)["alive"],
                 msg="death noticed")
        # Same port, new role: the restart story an operator actually
        # performs when rebalancing a fleet's class split.
        second = OllamaServer(FakeLLM(name="rep"), addr=addr,
                              replica_class="decode").start()
        _wait_classes(rt, {url: "decode"})
        snap = _router_snap(rt.url)
        assert snap['router_pool_replicas{class="prefill"}'] == 0.0
        assert snap['router_pool_replicas{class="decode"}'] == 1.0
    finally:
        rt.stop()
        other.stop()
        for s in (first, second):
            if s is not None:
                try:
                    s.stop()
                except Exception:   # noqa: BLE001 — already stopped
                    pass


# -- per-class autoscaling ----------------------------------------------------

class PressureLLM(FakeLLM):
    """Backend whose exported gauges simulate pool pressure: queue
    depth (the prefill signal) and in-flight streams + slot occupancy
    (the decode signal)."""

    def __init__(self) -> None:
        super().__init__(name="rep")
        self.depth = 0.0
        self.streams = 0.0
        self.occ = 0.0

    def metrics_snapshot(self):
        return {"serve_queue_depth": self.depth,
                "serve_inflight_requests": self.streams,
                "serve_batch_occupancy": self.occ}


def test_class_autoscaler_scales_pools_independently():
    """Prefill-pool pressure (admission queue depth) spawns a PREFILL
    replica and leaves the decode pool alone; decode-pool pressure
    (in-flight streams + occupancy) then spawns a DECODE replica; when
    both pressures collapse, scale-down retires ONLY spawner-owned
    members — per class, through drain-as-migration."""
    pre = PressureLLM()
    dec = PressureLLM()
    fronts = [OllamaServer(pre, addr="127.0.0.1:0",
                           replica_class="prefill").start(),
              OllamaServer(dec, addr="127.0.0.1:0",
                           replica_class="decode").start()]
    spawned: dict = {"prefill": [], "decode": []}
    retired: list = []

    def spawn_for(cls):
        def spawn():
            srv = OllamaServer(FakeLLM(name="rep"), addr="127.0.0.1:0",
                               replica_class=cls).start()
            spawned[cls].append(srv)
            return srv.url
        return spawn

    def can_retire(url):
        return any(s.url == url for ss in spawned.values() for s in ss)

    def retire(url):
        retired.append(url)
        for ss in spawned.values():
            for s in ss:
                if s.url == url:
                    s.stop()

    rt = ReplicaRouter([f.url for f in fronts], addr="127.0.0.1:0",
                       scrape_ms=50).start()
    rt.attach_autoscaler(ClassAutoscaler(
        {"prefill": spawn_for("prefill"), "decode": spawn_for("decode")},
        retire_fn=retire, can_retire_fn=can_retire,
        min_replicas=1, max_replicas=2, up_q=4.0, down_q=0.5, sustain=2))
    try:
        pre.depth = 50.0
        wait_for(lambda: len(spawned["prefill"]) == 1,
                 msg="prefill pool scale-up")
        time.sleep(0.4)     # several more ticks at sustained pressure
        assert len(spawned["prefill"]) == 1     # capped at max per class
        assert not spawned["decode"], \
            "decode pool scaled on PREFILL pressure"
        dec.streams = 6.0
        dec.occ = 4.0
        wait_for(lambda: len(spawned["decode"]) == 1,
                 msg="decode pool scale-up")
        snap = _router_snap(rt.url)
        assert snap["router_autoscale_up_total"] == 2.0
        # Pressure collapses: both spawned members retire (one at a
        # time — a single in-flight retirement gates both classes);
        # the boot replicas are the operator's and stay.
        pre.depth = 0.0
        dec.streams = dec.occ = 0.0
        wait_for(lambda: len(retired) == 2 and len(_replicas(rt)) == 2,
                 timeout=25.0, msg="both pools scale-down")
        assert sorted(retired) == sorted(
            s.url for ss in spawned.values() for s in ss)
        assert {r["url"] for r in _replicas(rt)} == {f.url for f in fronts}
    finally:
        rt.stop()
        for f in fronts:
            f.stop()
        for ss in spawned.values():
            for s in ss:
                try:
                    s.stop()
                except Exception:   # noqa: BLE001 — may be stopped
                    pass


# -- the byte-identity oracle + failure degradation (the acceptance core) ----

@pytest.mark.model
def test_disagg_byte_identity_and_failure_degradation():
    """ONE combined 2-engine leg (tier-1 budget: engine warmups are the
    cost — everything below shares them).

    1. Engine-level: prefill_park on A retains exactly ids[:-1];
       export → import on B; the request on B WAKES (not cold-admits)
       and its output is byte-identical to B's own never-disaggregated
       oracle — turn 2 included.
    2. Through the real router with class-tagged fronts: a new
       conversation rides the handoff (counter moves, affinity lands
       on the decode replica, the source forgot its copy on ack), an
       ANONYMOUS conversation rides it via the head-hash index, both
       byte-identical.
    3. Failpoint: with serve.disagg.handoff=raise armed, the next new
       conversation still completes byte-identically (degraded to the
       prefill replica), the failure counter moves, the lost-session
       ledger does NOT."""
    a = make_engine()   # the prefill side
    b = make_engine()   # the decode side
    fronts = []
    rt = None
    try:
        # Never-disaggregated oracle on B.
        o1, os_ = run(b, PROMPT1, "oracle")
        o2, _ = run(b, PROMPT2, "oracle", ctx=os_.context)

        # 1. Engine-level handoff.
        meta = a.prefill_park(GenerateRequest(
            prompt=PROMPT1, session="m",
            options=GenerateOptions(max_tokens=8, temperature=0.0,
                                    seed=1)))
        assert meta is not None and meta["key"] == "sid:m"
        # Parked EXACTLY the prompt minus its suffix token: the wake
        # must have >= 1 token left whose logits seed sampling.
        n_ids = len(TOK.encode(PROMPT1, add_bos=True))
        assert meta["len"] == n_ids - 1
        payload = a.session_export("sid:m")
        assert payload is not None
        assert "sid:m" in a.scheduler._tier.sessions_meta()  # retained
        assert b.session_import(payload) is not None
        waked0 = b.scheduler.metrics_snapshot()["kv_waked_total"]
        m1, s1 = run(b, PROMPT1, "m")
        assert m1 == o1, "disagg turn 1 diverged from the oracle"
        snap = b.scheduler.metrics_snapshot()
        assert snap["kv_waked_total"] == waked0 + 1, \
            "first token was not sampled off the imported session"
        m2, _ = run(b, PROMPT2, "m", ctx=s1.context)
        assert m2 == o2, "disagg turn 2 diverged from the oracle"
        assert a.session_forget("sid:m") is True

        # Too short to leave an indexable suffix: no park, no key.
        assert a.prefill_park(GenerateRequest(
            prompt="x", options=GenerateOptions(max_tokens=4))) is None

        # 2. The same contract through the real router.
        fronts = [OllamaServer(a, addr="127.0.0.1:0",
                               replica_class="prefill").start(),
                  OllamaServer(b, addr="127.0.0.1:0",
                               replica_class="decode").start()]
        rt = ReplicaRouter([f.url for f in fronts], addr="127.0.0.1:0",
                           scrape_ms=100).start()
        _wait_classes(rt, {fronts[0].url: "prefill",
                           fronts[1].url: "decode"})
        r1 = _gen(rt.url, PROMPT1, session="rr")
        assert r1["response"] == o1, "routed disagg turn 1 diverged"
        snap = _router_snap(rt.url)
        assert snap["disagg_handoffs_total"] == 1.0
        assert snap["disagg_handoff_ms_count"] >= 1.0
        with rt._mu:
            assert rt._sessions.get("rr") == 1   # affinity: decode home
        assert "sid:rr" not in a.scheduler._tier.sessions_meta(), \
            "source copy survived the ack"
        r2 = _gen(rt.url, PROMPT2, session="rr", ctx=r1["context"])
        assert r2["response"] == o2, "routed disagg turn 2 diverged"

        # Anonymous: no session id anywhere — the head-hash index
        # carries the handoff AND the affinity flip.
        ao1, _ = run(b, ANON, "anon-oracle")
        ra = _gen(rt.url, ANON)
        assert ra["response"] == ao1, "anonymous disagg diverged"
        assert _router_snap(rt.url)["disagg_handoffs_total"] == 2.0

        # 3. Handoff chaos: armed raise -> degraded to the prefill
        # replica, still byte-identical, never an error.
        failpoints.arm("serve.disagg.handoff", "raise")
        try:
            rf = _gen(rt.url, PROMPT1, session="deg")
        finally:
            failpoints.disarm_all()
        assert rf["response"] == o1, "degraded handoff diverged"
        snap = _router_snap(rt.url)
        assert snap["disagg_handoff_failures_total"] == 1.0
        assert snap.get("kv_sessions_lost_total", 0) == 0
    finally:
        if rt is not None:
            rt.stop()
        for f in fronts:
            f.stop()
        a.stop()
        b.stop()


# -- the two-OS-process matrix (ci.sh full) ----------------------------------

def _spawn_replica(port: int, cls: str) -> subprocess.Popen:
    env = dict(
        os.environ,
        PYTHONPATH=REPO,
        XLA_FLAGS="--xla_force_host_platform_device_count=1",
        OMP_NUM_THREADS="1",
        JAX_PLATFORMS="cpu",
        SERVE_BACKEND="tpu",
        MODEL_CONFIG="tiny",
        LLM_MODEL="tiny",
        SERVE_MAX_SEQ="128",
        SERVE_SLOTS="2",
        SERVE_PAGE_SIZE="16",
        SERVE_KV_HOST_GB="1",
        SERVE_KV_IDLE_S="3600",
        SERVE_WARMUP="32,64",
        SERVE_ADDR=f"127.0.0.1:{port}",
        SERVE_REPLICA_CLASS=cls,
        SERVE_ROUTER_UPSTREAMS="",
        SERVE_COORDINATOR="",
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
        except Exception:   # noqa: BLE001 — keep polling
            time.sleep(1.0)
    raise AssertionError(f"{url} never became ready")


@pytest.mark.slow
@pytest.mark.model
def test_two_process_disagg_handoff_matrix():
    """The acceptance matrix leg: real OS-process prefill and decode
    replicas behind the real router process. A fresh conversation rides
    the handoff and is byte-identical to the same conversation served
    directly by the decode replica; the ledger shows the handoff and
    zero lost sessions; the decode replica's wake (not a cold admit)
    produced the first token."""
    p_port, d_port, r_port = _free_port(), _free_port(), _free_port()
    procs = [_spawn_replica(p_port, "prefill"),
             _spawn_replica(d_port, "decode")]
    router_env = dict(
        os.environ, PYTHONPATH=REPO,
        SERVE_ADDR=f"127.0.0.1:{r_port}",
        SERVE_ROUTER_UPSTREAMS=(f"http://127.0.0.1:{p_port},"
                                f"http://127.0.0.1:{d_port}"),
        SERVE_ROUTER_SCRAPE_MS="200",
    )
    procs.append(subprocess.Popen(
        [sys.executable, "-m", "p2p_llm_chat_tpu.serve.router"],
        env=router_env, stdout=subprocess.PIPE,
        stderr=subprocess.STDOUT))
    rurl = f"http://127.0.0.1:{r_port}"
    durl = f"http://127.0.0.1:{d_port}"
    try:
        for u in (f"http://127.0.0.1:{p_port}", durl, rurl):
            _wait_ready(u, procs)
        wait_for(lambda: {r["class"] for r in _replicas(rurl)}
                 == {"prefill", "decode"},
                 timeout=30.0, msg="router class view")

        # Control: the identical conversation DIRECTLY on the decode
        # replica (identical random-init replicas — outputs are
        # replica-independent).
        c1 = _gen(durl, PROMPT1, session="ctrl")
        c2 = _gen(durl, PROMPT2, session="ctrl", ctx=c1["context"])

        m1 = _gen(rurl, PROMPT1, session="mig", timeout=180)
        assert m1["response"] == c1["response"], "handoff turn diverged"
        m2 = _gen(rurl, PROMPT2, session="mig", ctx=m1["context"])
        assert m2["response"] == c2["response"], "post-handoff diverged"

        snap = _router_snap(rurl)
        assert snap["disagg_handoffs_total"] >= 1.0
        assert snap["disagg_handoff_failures_total"] == 0.0
        assert snap.get("kv_sessions_lost_total", 0) == 0
        with urllib.request.urlopen(f"{durl}/metrics", timeout=10) as r:
            dsnap = parse_metrics_text(r.read().decode())
        assert dsnap["kv_waked_total"] >= 1.0, \
            "decode replica cold-admitted instead of waking"
    finally:
        for p in procs:
            p.terminate()
        for p in procs:
            try:
                p.wait(timeout=10)
            except subprocess.TimeoutExpired:
                p.kill()


# -- disagg under load with handoff chaos (ci.sh full) -----------------------

@pytest.mark.slow
@pytest.mark.model
def test_disagg_fleet_under_load_with_handoff_chaos():
    """The acceptance run: a 1-prefill + 2-decode in-process fleet
    under open-loop loadgen (disagg_session + group_chat) with
    ``serve.disagg.handoff=raise@0.3`` armed mid-run. Contracts: zero
    client-visible errors (failed handoffs degrade to the prefill
    replica), zero session loss, the chaos ledger holds, and admission
    prefill work stays OFF the decode replicas — their
    ``prefill_chunks_total`` is 0 while the prefill replica's moved
    (the disagg_session openers chunk there)."""
    from p2p_llm_chat_tpu.loadgen import (ChaosWindow, Endpoints,
                                          LoadDriver, REGISTRY,
                                          build_schedule, check_contracts,
                                          parse_mix)

    # prefill_chunk=64 splits the workload classes cleanly: the
    # ~120-token disagg_session openers genuinely CHUNK wherever they
    # admit (the prefill replica, if disaggregation is doing its job),
    # while the ~40-token group_chat fans sit under the budget — so a
    # racy fan member that cold-admits on a decode replica (identical
    # concurrent new conversations can lose the head-index race) still
    # produces zero chunks there, keeping the 0-chunk assertion exact.
    # Openers also stay shallow enough that the post-handoff wake fits
    # max_seq (the suffix rounds UP to the smallest warmed bucket).
    # Bucket 256 is warmed ahead of the chaos window (the PR 11
    # precedent — this leg tests handoff chaos, not cold compiles).
    eng_p = make_engine(buckets=(64, 128, 256), prefill_chunk=64)
    eng_d1 = make_engine(buckets=(64, 128, 256), prefill_chunk=64)
    eng_d2 = make_engine(buckets=(64, 128, 256), prefill_chunk=64)
    fronts = [OllamaServer(eng_p, addr="127.0.0.1:0",
                           replica_class="prefill").start(),
              OllamaServer(eng_d1, addr="127.0.0.1:0",
                           replica_class="decode").start(),
              OllamaServer(eng_d2, addr="127.0.0.1:0",
                           replica_class="decode").start()]
    rt = ReplicaRouter([f.url for f in fronts], addr="127.0.0.1:0",
                       scrape_ms=100).start()
    try:
        _wait_classes(rt, {fronts[0].url: "prefill",
                           fronts[1].url: "decode",
                           fronts[2].url: "decode"})
        sched = build_schedule(
            parse_mix("disagg_session=2,group_chat=1"),
            rate_rps=2.0, duration_s=6.0, seed=7, n_peers=4)
        drv = LoadDriver(Endpoints(serve_url=rt.url), REGISTRY,
                         workers=8, timeout_s=120.0)
        chaos = ChaosWindow("serve.disagg.handoff=raise@0.3",
                            arm_at_s=1.0, disarm_at_s=5.0)
        recs = drv.run(sched, chaos=chaos)
        assert recs
        bad = [r for r in recs if r.status in ("error", "truncated")]
        assert not bad, [(r.scenario, r.error_kind, r.error)
                         for r in bad]
        rep = check_contracts(recs, disarm_at_s=5.0)
        assert rep.ok, rep.violations

        snap = _router_snap(rt.url)
        moved = (snap.get("disagg_handoffs_total", 0)
                 + snap.get("disagg_handoff_failures_total", 0))
        assert moved >= 1, "no handoff was ever attempted"
        assert snap.get("kv_sessions_lost_total", 0) == 0
        # The disaggregation dividend: decode replicas ran ZERO
        # admission prefill chunks — every chunk landed on the prefill
        # replica (wakes forward a short suffix, never a chunk ladder).
        p_chunks = eng_p.scheduler.metrics_snapshot()[
            "prefill_chunks_total"]
        d_chunks = [e.scheduler.metrics_snapshot()["prefill_chunks_total"]
                    for e in (eng_d1, eng_d2)]
        assert p_chunks > 0, \
            "disagg_session openers never chunked on the prefill side"
        assert d_chunks == [0, 0], \
            f"admission chunk work leaked onto decode replicas: {d_chunks}"
    finally:
        failpoints.disarm_all()
        rt.stop()
        for f in fronts:
            f.stop()
        for e in (eng_p, eng_d1, eng_d2):
            e.stop()
