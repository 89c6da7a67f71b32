"""At-least-once chat delivery under peer churn (PR 20).

The tier-1 oracle for the outbox wire (node.py): a message sent while
its recipient is DOWN answers a well-formed queued 200, survives in the
sender's outbox, and lands EXACTLY ONCE (byte-identical) once the peer
returns inside the outbox TTL — redelivery (at-least-once) composed
with receiver-side msg_id dedup (inbox.py) must read as exactly-once to
the client. Drop accounting (overflow/TTL), directory liveness
(DIR_TTL_S eviction + /deregister), and the three PR-20 failpoint sites
(p2p.node.deliver / p2p.node.resolve / p2p.directory.evict) are pinned
here too; the process-kill matrix (real ``python -m ..node`` processes
under a NodeChurnWindow) is slow-marked.
"""

import json
import signal
import subprocess
import sys
import time
import urllib.request

import pytest

from p2p_llm_chat_tpu.directory import DirectoryRecord, DirectoryService
from p2p_llm_chat_tpu.loadgen.chaos import NodeChurnWindow, check_churn_delivery
from p2p_llm_chat_tpu.node import ChatNode
from p2p_llm_chat_tpu.proto import ChatMessage, mint_msg_id, now_rfc3339
from p2p_llm_chat_tpu.utils import failpoints as fp
from p2p_llm_chat_tpu.utils.http import HttpError, http_json


@pytest.fixture(autouse=True)
def _clean_failpoints():
    yield
    fp.disarm_all()
    fp.reset_hits()


def _node(user, dir_url, **kw):
    kw.setdefault("http_addr", "127.0.0.1:0")
    kw.setdefault("bootstrap_addrs", "")
    kw.setdefault("relay_addrs", "")
    kw.setdefault("identity_file", "")
    kw.setdefault("dht_addr", "off")
    return ChatNode(username=user, directory_url=dir_url, **kw).start()


def _metrics_text(base_url):
    with urllib.request.urlopen(f"{base_url}/metrics", timeout=5.0) as r:
        return r.read().decode("utf-8")


def _metric(text, head):
    """Value of the first exposition line starting with ``head``
    (exact-name or labeled series prefix); None when absent."""
    for line in text.splitlines():
        if line.startswith(head) and not line.startswith("#"):
            return float(line.rsplit(" ", 1)[1])
    return None


def _wait_inbox(node_url, want_count, timeout=10.0):
    deadline = time.time() + timeout
    inbox = []
    while time.time() < deadline:
        _, inbox = http_json("GET", f"{node_url}/inbox?after=")
        if len(inbox) >= want_count:
            return inbox
        time.sleep(0.05)
    raise AssertionError(
        f"inbox never reached {want_count} messages (have {len(inbox)})")


def test_churn_exactly_once_across_restart(tmp_path):
    """The headline oracle: kill the recipient, send through the
    window (every answer a well-formed queued 200), restart — every
    body arrives exactly once, byte-identical, in send order."""
    directory = DirectoryService(addr="127.0.0.1:0").start()
    key = str(tmp_path / "cannan.key")
    a = _node("najy", directory.url)
    b = _node("cannan", directory.url, identity_file=key)
    b2 = None
    try:
        http_json("POST", f"{a.http_url}/send",
                  {"to_username": "cannan", "content": "warmup"})
        _wait_inbox(b.http_url, 1)

        b.stop()                               # the churn window opens
        sent = [f"through the window #{i} ✨" for i in range(3)]
        for body in sent:
            status, resp = http_json("POST", f"{a.http_url}/send",
                                     {"to_username": "cannan",
                                      "content": body}, timeout=20.0)
            assert status == 200
            assert resp["status"] == "queued"
            assert resp["msg_id"] and resp["id"]

        b2 = _node("cannan", directory.url, identity_file=key)
        inbox = _wait_inbox(b2.http_url, 3, timeout=15.0)

        got = [m["content"] for m in inbox]
        oracle = check_churn_delivery(sent, got)
        assert oracle["ok"], oracle
        assert got == sent                     # byte-identical, in order

        text = _metrics_text(a.http_url)
        assert _metric(text, "p2p_redelivered_total") >= 3
        assert _metric(text, "p2p_outbox_depth") == 0
        assert _metric(text, 'p2p_messages_dropped_total{reason="ttl"}') == 0
        assert _metric(text, "p2p_delivery_ms_count") >= 4
    finally:
        a.stop()
        if b2 is not None:
            b2.stop()
        directory.stop()


def test_dedup_suppresses_forced_double_send():
    """Wire-level idempotency: the SAME msg_id delivered twice (a lost
    ack forces exactly this) appends once; the duplicate is counted and
    still acked (the second _deliver must succeed, not error)."""
    directory = DirectoryService(addr="127.0.0.1:0").start()
    a = _node("najy", directory.url)
    b = _node("cannan", directory.url)
    try:
        rec = a.dir.lookup("cannan")
        msg = ChatMessage(from_user="najy", to_user="cannan",
                          content="dup?", timestamp=now_rfc3339(),
                          msg_id=mint_msg_id("najy", 999, "dup?"))
        for _ in range(2):
            errors = []
            assert a._deliver(rec, msg, errors), errors
        time.sleep(0.1)
        _, inbox = http_json("GET", f"{b.http_url}/inbox?after=")
        assert [m["content"] for m in inbox] == ["dup?"]
        assert _metric(_metrics_text(b.http_url),
                       "p2p_dedup_suppressed_total") == 1
    finally:
        a.stop()
        b.stop()
        directory.stop()


def test_restarted_sender_mints_fresh_ids():
    """REGRESSION: msg_id carries a per-boot nonce. The per-sender seq
    counter resets to 0 on restart, so without the nonce a restarted
    sender's first message repeating an earlier (seq, content) pair —
    a first 'hi' after every boot — would re-mint the old id and be
    silently dedup-suppressed by a receiver that stayed up."""
    directory = DirectoryService(addr="127.0.0.1:0").start()
    a = _node("najy", directory.url)
    b = _node("cannan", directory.url)
    a2 = None
    try:
        http_json("POST", f"{a.http_url}/send",
                  {"to_username": "cannan", "content": "hi"})
        _wait_inbox(b.http_url, 1)
        a.stop()                        # sender restarts; receiver stays up
        a2 = _node("najy", directory.url)
        _, resp = http_json("POST", f"{a2.http_url}/send",
                            {"to_username": "cannan", "content": "hi"})
        assert resp["status"] == "sent"
        inbox = _wait_inbox(b.http_url, 2)
        assert [m["content"] for m in inbox] == ["hi", "hi"]
        assert len({m["msg_id"] for m in inbox}) == 2
        assert _metric(_metrics_text(b.http_url),
                       "p2p_dedup_suppressed_total") in (None, 0)
    finally:
        if a2 is not None:
            a2.stop()
        b.stop()
        directory.stop()


def test_send_joins_parked_backlog_preserving_order():
    """REGRESSION: a fresh /send to a recipient with a parked backlog
    must JOIN the outbox queue, not deliver directly — otherwise it
    jumps ahead of the older messages the redelivery worker hasn't
    flushed yet, breaking send order."""
    directory = DirectoryService(addr="127.0.0.1:0").start()
    a = _node("najy", directory.url)
    b = _node("cannan", directory.url)
    try:
        http_json("POST", f"{a.http_url}/send",
                  {"to_username": "cannan", "content": "warmup"})
        _wait_inbox(b.http_url, 1)
        fp.arm("p2p.node.deliver", "raise")   # park a backlog
        _, resp = http_json("POST", f"{a.http_url}/send",
                            {"to_username": "cannan", "content": "first"},
                            timeout=20.0)
        assert resp["status"] == "queued"
        # Pin the backlog: the worker can't re-resolve while this is
        # armed, but /send's direct path (dir.lookup) still can — the
        # exact shape of the bug: recipient reachable, backlog parked.
        # The two arms change under the worker's own round lock: a round
        # that had resolved before the first arm and dialled after the
        # second would deliver "first" and leave no backlog to join.
        with a._flush_mu:
            fp.arm("p2p.node.resolve", "raise")
            fp.disarm("p2p.node.deliver")
        _, resp = http_json("POST", f"{a.http_url}/send",
                            {"to_username": "cannan", "content": "second"},
                            timeout=20.0)
        assert resp["status"] == "queued"     # joins the queue, no jump
        fp.disarm("p2p.node.resolve")
        a._flush_outbox()      # one round by hand, not the worker's backoff
        inbox = _wait_inbox(b.http_url, 3, timeout=15.0)
        assert [m["content"] for m in inbox] == ["warmup", "first", "second"]
    finally:
        a.stop()
        b.stop()
        directory.stop()


def test_outbox_overflow_and_ttl_drop_accounting(monkeypatch):
    """Bounded loss is ACCOUNTED loss: a 2-deep outbox fed 3 queued
    sends drops the oldest (overflow); the survivors expire at the TTL
    (ttl) — both visible on /metrics, depth settling to 0."""
    monkeypatch.setenv("P2P_OUTBOX_MAX", "2")
    monkeypatch.setenv("P2P_OUTBOX_TTL_S", "0.2")
    directory = DirectoryService(addr="127.0.0.1:0").start()
    a = _node("najy", directory.url)
    b = _node("cannan", directory.url)
    try:
        http_json("POST", f"{a.http_url}/send",
                  {"to_username": "cannan", "content": "warmup"})
        _wait_inbox(b.http_url, 1)
        b.stop()
        for i in range(3):
            _, resp = http_json("POST", f"{a.http_url}/send",
                                {"to_username": "cannan",
                                 "content": f"m{i}"}, timeout=20.0)
            assert resp["status"] == "queued"

        deadline = time.time() + 8.0
        while time.time() < deadline:
            text = _metrics_text(a.http_url)
            if _metric(text,
                       'p2p_messages_dropped_total{reason="ttl"}') == 2:
                break
            time.sleep(0.1)
        text = _metrics_text(a.http_url)
        assert _metric(
            text, 'p2p_messages_dropped_total{reason="overflow"}') == 1
        assert _metric(text, 'p2p_messages_dropped_total{reason="ttl"}') == 2
        assert _metric(text, "p2p_outbox_depth") == 0
    finally:
        a.stop()
        directory.stop()


def test_graceful_shutdown_deregisters():
    """stop() removes the directory record BEFORE the process dies, so
    the fleet stops resolving a peer that said goodbye (the reference
    never deregisters — SURVEY.md §2 C5)."""
    directory = DirectoryService(addr="127.0.0.1:0").start()
    a = _node("najy", directory.url)
    b = _node("cannan", directory.url)
    try:
        b.stop()                    # deregister is synchronous in stop()
        with pytest.raises(HttpError) as e:
            http_json("GET", f"{directory.url}/lookup?username=cannan")
        assert e.value.status == 404
        # The sender is still there — deregister is peer_id-guarded.
        _, rec = http_json("GET", f"{directory.url}/lookup?username=najy")
        assert rec["peer_id"] == a.host.peer_id
    finally:
        a.stop()
        directory.stop()


def test_directory_ttl_eviction_counts_and_404s():
    """DIR_TTL_S liveness: a record whose heartbeat lapses is evicted
    by the sweep (counted on /metrics) and /lookup 404s it."""
    directory = DirectoryService(addr="127.0.0.1:0", ttl_seconds=0.15).start()
    try:
        http_json("POST", f"{directory.url}/register",
                  {"username": "ghost", "peer_id": "p1", "addrs": []})
        deadline = time.time() + 5.0
        status = 200
        while time.time() < deadline:
            status, _ = http_json(
                "GET", f"{directory.url}/lookup?username=ghost",
                raise_for_status=False)
            if status == 404:
                break
            time.sleep(0.05)
        assert status == 404
        assert _metric(_metrics_text(directory.url),
                       "directory_evictions_total") >= 1
    finally:
        directory.stop()


def test_directory_evict_failpoint_stalls_sweep():
    """p2p.directory.evict contract: an armed eviction SKIPS (the
    record outlives its TTL in the store — no crash, no partial
    delete), while /lookup still answers 404 by racing ahead of the
    sweep; disarming lets the next sweep finish the job."""
    directory = DirectoryService(addr="127.0.0.1:0", ttl_seconds=0.1).start()
    try:
        http_json("POST", f"{directory.url}/register",
                  {"username": "ghost", "peer_id": "p1", "addrs": []})
        fp.arm("p2p.directory.evict", "drop")
        time.sleep(0.5)
        assert directory.store.get("ghost") is not None   # eviction stalled
        status, _ = http_json("GET", f"{directory.url}/lookup?username=ghost",
                              raise_for_status=False)
        assert status == 404                   # lookup races ahead anyway
        assert fp.hits("p2p.directory.evict") >= 1
        fp.disarm("p2p.directory.evict")
        deadline = time.time() + 5.0
        while time.time() < deadline:
            if directory.store.get("ghost") is None:
                break
            time.sleep(0.05)
        assert directory.store.get("ghost") is None
    finally:
        directory.stop()


def test_directory_evict_failpoint_raise_keeps_lookup_contract():
    """REGRESSION: an armed ``raise`` on p2p.directory.evict must
    degrade the /lookup path the same way it degrades the sweep — the
    expired record answers the contracted 404, never a 500."""
    directory = DirectoryService(addr="127.0.0.1:0", ttl_seconds=0.1).start()
    try:
        http_json("POST", f"{directory.url}/register",
                  {"username": "ghost", "peer_id": "p1", "addrs": []})
        fp.arm("p2p.directory.evict", "raise")
        time.sleep(0.3)
        status, _ = http_json("GET", f"{directory.url}/lookup?username=ghost",
                              raise_for_status=False)
        assert status == 404                   # degraded, not a 500
        assert directory.store.get("ghost") is not None   # evict skipped
    finally:
        directory.stop()


def test_evict_compare_and_delete_spares_reregistered_record():
    """REGRESSION: eviction is compare-and-delete — a node
    re-registering between the sweep's age check and the delete keeps
    its fresh record instead of 404ing while live."""
    svc = DirectoryService(addr="127.0.0.1:0", ttl_seconds=5.0)  # no sweep
    svc.store.set(DirectoryRecord("u", "p1", [],
                                  last="2000-01-01T00:00:00Z"))
    # The sweep snapshot saw the stale record and computed age > ttl;
    # the node re-registers before the delete lands:
    svc.store.set(DirectoryRecord("u", "p1", [], last=now_rfc3339()))
    svc._evict("u", age=10.0)
    assert svc.store.get("u") is not None
    assert svc._m_evictions.value == 0         # spared, not counted
    # And a record that IS still stale gets deleted + counted.
    svc.store.set(DirectoryRecord("u", "p1", [],
                                  last="2000-01-01T00:00:00Z"))
    svc._evict("u", age=10.0)
    assert svc.store.get("u") is None
    assert svc._m_evictions.value == 1


def test_deliver_failpoint_queues_then_recovers():
    """p2p.node.deliver contract: an armed delivery fails the attempt —
    the send degrades to the well-formed queued 200, and the message
    lands (exactly once) after disarm, on the worker's schedule."""
    directory = DirectoryService(addr="127.0.0.1:0").start()
    a = _node("najy", directory.url)
    b = _node("cannan", directory.url)
    try:
        fp.arm("p2p.node.deliver", "raise")
        _, resp = http_json("POST", f"{a.http_url}/send",
                            {"to_username": "cannan", "content": "delayed"},
                            timeout=20.0)
        assert resp["status"] == "queued"
        assert fp.hits("p2p.node.deliver") >= 1
        fp.disarm("p2p.node.deliver")
        inbox = _wait_inbox(b.http_url, 1, timeout=15.0)
        assert [m["content"] for m in inbox] == ["delayed"]
    finally:
        a.stop()
        b.stop()
        directory.stop()


def test_resolve_failpoint_parks_recipient():
    """p2p.node.resolve contract: a failed re-resolution leaves the
    whole recipient queued for the round (no loss, no crash); disarm
    and the next round resolves + delivers."""
    directory = DirectoryService(addr="127.0.0.1:0").start()
    a = _node("najy", directory.url)
    b = _node("cannan", directory.url)
    b2 = None
    try:
        http_json("POST", f"{a.http_url}/send",
                  {"to_username": "cannan", "content": "warmup"})
        _wait_inbox(b.http_url, 1)
        fp.arm("p2p.node.resolve", "raise")
        b.stop()
        _, resp = http_json("POST", f"{a.http_url}/send",
                            {"to_username": "cannan", "content": "parked"},
                            timeout=20.0)
        assert resp["status"] == "queued"
        b2 = _node("cannan", directory.url)
        time.sleep(0.6)                 # worker rounds tick; resolve armed
        _, inbox = http_json("GET", f"{b2.http_url}/inbox?after=")
        assert inbox == []              # still parked — recipient queued
        assert fp.hits("p2p.node.resolve") >= 1
        fp.disarm("p2p.node.resolve")
        inbox = _wait_inbox(b2.http_url, 1, timeout=15.0)
        assert [m["content"] for m in inbox] == ["parked"]
    finally:
        a.stop()
        if b2 is not None:
            b2.stop()
        directory.stop()


def test_churn_window_lifecycle_and_oracle_helpers():
    """NodeChurnWindow drives kill_fn/restart_fn on schedule and its
    stop() restores a still-open window; check_churn_delivery flags
    loss and duplication and passes exactly-once."""
    calls = []
    w = NodeChurnWindow(kill_fn=lambda: calls.append("kill"),
                        restart_fn=lambda: calls.append("restart"),
                        peer=3, kill_at_s=0.01)
    w.start(0.0)
    deadline = time.time() + 5.0
    while time.time() < deadline and not w.churned:
        time.sleep(0.01)
    assert w.churned
    w.stop()                            # open window: stop() restores
    assert calls == ["kill", "restart"]
    w.stop()                            # idempotent
    assert calls == ["kill", "restart"]

    assert check_churn_delivery(["a", "b"], ["b", "a"])["ok"]
    assert check_churn_delivery(["a", "b"], ["a"])["lost"] == ["b"]
    assert check_churn_delivery(["a"], ["a", "a"])["duplicated"] == ["a"]


# ---------------------------------------------------------------------------
# process-kill matrix (slow): real node processes under a NodeChurnWindow
# ---------------------------------------------------------------------------

def _free_port():
    import socket
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _spawn_node(user, port, dir_url, identity_file, repo_root,
                extra_env=None):
    import os
    env = dict(os.environ)
    env.update({
        "MYNAMEIS": user,
        "HTTP_ADDR": f"127.0.0.1:{port}",
        "DIRECTORY_URL": dir_url,
        "DHT_ADDR": "off",
        "NATPMP": "0",
        "IDENTITY_FILE": identity_file,
        "NODE_REREGISTER_S": "1",
    })
    env.update(extra_env or {})
    return subprocess.Popen(
        [sys.executable, "-m", "p2p_llm_chat_tpu.node"],
        cwd=repo_root, env=env,
        stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL)


def _wait_healthz(url, timeout=20.0):
    deadline = time.time() + timeout
    while time.time() < deadline:
        try:
            http_json("GET", f"{url}/healthz", timeout=2.0)
            return
        except Exception:   # noqa: BLE001 — still booting
            time.sleep(0.1)
    raise AssertionError(f"{url} never came up")


@pytest.mark.slow
@pytest.mark.parametrize("sig", ["SIGKILL", "SIGTERM"])
def test_process_kill_matrix(tmp_path, sig):
    """Real churn: the recipient is a real ``python -m ..node`` process
    killed hard (SIGKILL — the directory keeps advertising the corpse)
    or gracefully (SIGTERM — it deregisters on the way out), then
    respawned by the NodeChurnWindow. Either way the messages sent
    through the window land exactly once."""
    import os
    repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    directory = DirectoryService(addr="127.0.0.1:0").start()
    pa, pb = _free_port(), _free_port()
    key_a = str(tmp_path / "a.key")
    key_b = str(tmp_path / "b.key")
    a = _spawn_node("najy", pa, directory.url, key_a, repo_root)
    b = _spawn_node("cannan", pb, directory.url, key_b, repo_root)
    a_url, b_url = f"http://127.0.0.1:{pa}", f"http://127.0.0.1:{pb}"
    procs = {"b": b}
    try:
        _wait_healthz(a_url)
        _wait_healthz(b_url)
        http_json("POST", f"{a_url}/send",
                  {"to_username": "cannan", "content": "warmup"},
                  timeout=20.0)
        _wait_inbox(b_url, 1, timeout=20.0)

        def kill_fn():
            procs["b"].send_signal(getattr(signal, sig))
            procs["b"].wait(timeout=20)

        def restart_fn():
            procs["b"] = _spawn_node("cannan", pb, directory.url,
                                     key_b, repo_root)

        window = NodeChurnWindow(kill_fn=kill_fn, restart_fn=restart_fn,
                                 peer=1, kill_at_s=0.0, restart_at_s=3.0)
        window.start(0.0)
        deadline = time.time() + 10.0
        while time.time() < deadline and not window.churned:
            time.sleep(0.05)
        assert window.churned
        procs["b"].wait(timeout=20)       # the kill landed

        sent = [f"{sig} window #{i}" for i in range(2)]
        for body in sent:
            _, resp = http_json("POST", f"{a_url}/send",
                                {"to_username": "cannan", "content": body},
                                timeout=30.0)
            assert resp["status"] == "queued", resp

        _wait_healthz(b_url, timeout=30.0)
        inbox = _wait_inbox(b_url, 2, timeout=30.0)
        oracle = check_churn_delivery(
            sent, [m["content"] for m in inbox])
        assert oracle["ok"], oracle
        window.stop()
    finally:
        for p in (a, procs["b"]):
            try:
                p.kill()
                p.wait(timeout=10)
            except Exception:   # noqa: BLE001 — already dead
                pass
        directory.stop()


@pytest.mark.slow
def test_peer_churn_chaos_leg(tmp_path):
    """The ci.sh-full chaos leg: 8 real node processes under peer_churn
    traffic (the REGISTRY['peer_churn'] scenario builder generates every
    arrival) with ``p2p.node.deliver=raise@0.2`` armed in every node AND
    a NodeChurnWindow SIGKILLing + respawning one of them mid-run.
    Contract: every send the fleet accepted (200 "sent" OR "queued")
    lands exactly once — zero loss, zero duplicates — and the outbox
    drop ledger stays flat (nothing aged out or overflowed)."""
    import os
    import random as _random
    import threading

    from p2p_llm_chat_tpu.loadgen.scenarios import REGISTRY, Endpoints

    repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    n = 8
    victim = 3
    directory = DirectoryService(addr="127.0.0.1:0", ttl_seconds=30.0).start()
    chaos_env = {"FAIL_POINTS": "p2p.node.deliver=raise@0.2"}
    ports = [_free_port() for _ in range(n)]
    users = tuple(f"peer{i:02d}" for i in range(n))
    keys = [str(tmp_path / f"{u}.key") for u in users]
    procs = [_spawn_node(users[i], ports[i], directory.url, keys[i],
                         repo_root, chaos_env) for i in range(n)]
    urls = tuple(f"http://127.0.0.1:{p}" for p in ports)
    try:
        for u in urls:
            _wait_healthz(u, timeout=60.0)

        def kill_fn():
            procs[victim].kill()
            procs[victim].wait(timeout=20)

        def restart_fn():
            procs[victim] = _spawn_node(
                users[victim], ports[victim], directory.url, keys[victim],
                repo_root, chaos_env)

        window = NodeChurnWindow(kill_fn=kill_fn, restart_fn=restart_fn,
                                 peer=victim, kill_at_s=0.0,
                                 restart_at_s=2.5)
        window.start(0.0)
        deadline = time.time() + 10.0
        while time.time() < deadline and not window.churned:
            time.sleep(0.05)
        assert window.churned
        procs[victim].wait(timeout=20)

        # peer_churn traffic, started AFTER the kill landed so the
        # victim's post-restart inbox sees every accepted send aimed at
        # it (a pre-kill delivery would die with the killed process —
        # delivery is the contract here, not inbox durability).
        ep = Endpoints(serve_url="http://unused.invalid",
                       node_urls=urls, users=users)
        build = REGISTRY["peer_churn"].build
        sent_mu = threading.Lock()
        sent: dict = {u: [] for u in users}

        def arrival(i):
            step = build(_random.Random(i), i % n, ep)[0]
            try:
                status, resp = http_json("POST", step.url, step.payload,
                                         timeout=30.0,
                                         raise_for_status=False)
            except Exception:   # noqa: BLE001 — dead front: error budget
                return
            if status == 200 and resp.get("status") in ("sent", "queued"):
                with sent_mu:
                    sent[step.payload["to_username"]].append(
                        step.payload["content"])

        arrivals = list(range(48))
        workers = []
        for w in range(4):
            def run(w=w):
                for i in arrivals[w::4]:
                    arrival(i)
                    time.sleep(0.02)
            t = threading.Thread(target=run)
            t.start()
            workers.append(t)
        for t in workers:
            t.join(timeout=120)
        window.stop()

        # Settle: every accepted message must leave every outbox.
        _wait_healthz(urls[victim], timeout=60.0)
        deadline = time.time() + 90.0
        while time.time() < deadline:
            depths = [_metric(_metrics_text(u), "p2p_outbox_depth")
                      for u in urls]
            if all(d == 0 for d in depths):
                break
            time.sleep(0.25)
        assert all(d == 0 for d in depths), f"outboxes never drained: {depths}"

        redelivered = 0
        for i, u in enumerate(urls):
            text = _metrics_text(u)
            redelivered += _metric(text, "p2p_redelivered_total") or 0
            for reason in ("ttl", "overflow"):
                assert _metric(
                    text,
                    f'p2p_messages_dropped_total{{reason="{reason}"}}') == 0
            _, inbox = http_json("GET", f"{u}/inbox?after=")
            oracle = check_churn_delivery(
                sent[users[i]], [m["content"] for m in inbox])
            assert oracle["ok"], (users[i], oracle)
        assert redelivered > 0      # the queued path actually carried load
    finally:
        for p in procs:
            try:
                p.kill()
                p.wait(timeout=10)
            except Exception:   # noqa: BLE001 — already dead
                pass
        directory.stop()
