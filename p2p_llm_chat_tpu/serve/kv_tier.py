"""Multi-tier KV: host-RAM session parking for mostly-idle conversations.

The north-star workload is millions of chat sessions that are idle
between turns, but a session's KV historically lived in HBM for the
request's lifetime and evaporated at finish — a follow-up turn re-paid
the whole history's prefill. At KV economics of 16 KB/token (int8,
bench-moe) HBM bounds *open* sessions long before it
bounds *decoding* sessions; pinned host RAM is ~50x larger per chip.
This module adds the vLLM-style memory hierarchy on top of the paged
pool (ops/paged_kv.py):

- **resident**: a finished request whose client named a
  session keeps its physical pages in the pool — the row is released
  and its table zeroed, but the pages stay out of the allocator. A
  follow-up whose prompt extends the session's tokens wakes for free:
  the pages re-enter a fresh row's table and only the new turn's suffix
  runs a forward (serve/scheduler.py `_admit_wake`).
- **parked**: under idle timeout or page-pool pressure the
  session's raw KV words (int8 + scales included — bit-exact, never a
  requantize) are gathered in one dispatch and copied to host arrays;
  the pages go back to the allocator. Wake re-uploads the payload
  (prefetch starts at match time, so the H2D copy overlaps whatever
  admission work — including a PR 3 chunk ladder — runs ahead of it)
  and scatters it into freshly-allocated pages in one dispatch.
- **evicted**: the host pool is budgeted (``SERVE_KV_HOST_GB``); the
  cost policy below drops the worst parked sessions entirely. A dropped
  session's follow-up simply cold-admits (full prefill) — tiering is a
  pure optimization, invisible in outputs.

Eviction policy (shared with serve/prefix.py's byte-budget mode):
cost = bytes x recency — the biggest, longest-idle entries go first,
so one huge stale session cannot squat while many small warm ones are
dropped (plain LRU would keep it; plain largest-first would churn hot
long chats).

Correctness: park/wake round-trips the exact pool words, so a resumed
greedy stream is BYTE-identical to one whose session never left HBM
(pinned by tests/test_kv_tier.py). Host-side policy lives here; the
device programs live in ops/paged_kv.py (gather_pages/scatter_pages)
and serve/scheduler.py (the wake admission program).

Threading: the scheduler thread performs every state transition
(park/wake/retain run between device dispatches it owns); /metrics
scrapes read the tables from HTTP threads — hence the lock on the
session index. Host payload arrays are immutable after parking.
"""

from __future__ import annotations

import io
import threading
import time
from dataclasses import dataclass, field
from typing import Optional

from ..utils.failpoints import failpoint
from ..utils.log import get_logger

log = get_logger("serve.kv_tier")

# Wire-format version for serialize_session / deserialize_session
# (bumped on any incompatible layout change; importers reject unknown
# versions rather than guess — the serve/prefix.py convention).
_WIRE_VERSION = 1
# The one pool family a payload may come from; an importer refuses any
# other ``kind`` (a peer's payload is outside input).
_WIRE_KIND = "paged"

# Token-head index grain: sessions of at least this many tokens are
# findable by the hash of their first HEAD_GRAIN token ids (a follow-up
# prompt that extends the session shares them verbatim), so wake works
# for /api/generate context continuation even when the client never
# sends an explicit session id. Shorter sessions are only reachable by
# explicit id — their prefill is too cheap to matter.
HEAD_GRAIN = 32


def head_key(ids) -> Optional[str]:
    """The anonymous session index key: ``head:`` + sha1 over the
    NATIVE int64 bytes of the first HEAD_GRAIN token ids, or None when
    too short to index. THE single derivation — the scheduler's
    retention key, the router's affinity key, and the disagg prefill
    key all call this, so a migrated/handed-off session's key can never
    drift from the one a follow-up turn derives."""
    if len(ids) < HEAD_GRAIN:
        return None
    import hashlib

    import numpy as np
    return "head:" + hashlib.sha1(np.asarray(
        ids[:HEAD_GRAIN], np.int64).tobytes()).hexdigest()[:16]


def cost_evict(items: list[tuple], over_bytes: float,
               now: Optional[float] = None) -> list:
    """Pick victims until at least ``over_bytes`` bytes are freed.

    ``items``: (key, nbytes, last_used) triples. Victims are chosen by
    descending cost = nbytes x idle seconds (floor 1 ms so entries
    touched this instant still rank by size). Returns the victim keys —
    the caller owns the actual removal. Shared by the host session pool
    and the PrefixStore byte budget so the two tiers cannot drift."""
    if over_bytes <= 0:
        return []
    t = time.monotonic() if now is None else now
    scored = sorted(items, key=lambda it: it[1] * max(1e-3, t - it[2]),
                    reverse=True)
    victims, freed = [], 0.0
    for key, nbytes, _ in scored:
        if freed >= over_bytes:
            break
        victims.append(key)
        freed += nbytes
    return victims


@dataclass
class SessionKV:
    """One open session's KV, in whichever tier it currently occupies.

    ``tokens``: the ids whose KV is trusted (prompt + all generated but
    the last — the cache never holds the final emitted token's KV);
    ``length`` == len(tokens). Exactly one of ``pages`` (resident) /
    ``host`` (parked) is set; ``host`` is the raw-bits payload tuple
    ((k, v, k_scale, v_scale), n_pages), the scales None for a float
    pool."""

    key: str
    tokens: tuple
    length: int
    pages: Optional[list] = None          # resident: physical page ids
    host: Optional[tuple] = None          # parked: (arrays, span)
    nbytes: int = 0                       # host bytes when parked
    last_used: float = field(default_factory=time.monotonic)

    @property
    def parked(self) -> bool:
        return self.host is not None


# -- cross-replica session wire format ---------------------------------------

def serialize_session(sess: SessionKV) -> bytes:
    """One PARKED session -> bytes, for a peer replica (the live
    cross-replica migration payload: raw pool words + scales exactly as
    parked, plus the token ids and index key). The arrays ship verbatim
    (int8 payload and head-major scales included — never a requantize),
    so an import followed by the destination's verify-shaped wake
    resumes the conversation byte-identically to never having moved.
    ``kind`` names the pool family the payload came from (``_WIRE_KIND``;
    span = page count) — the importer validates the payload against its
    own geometry before adopting."""
    import numpy as np
    assert sess.parked, "only parked sessions serialize (park first)"
    arrays, span = sess.host
    present = [a is not None for a in arrays]
    # Arrays ship as RAW BYTES + explicit dtype/shape sidecars, not as
    # native npz arrays: npz round-trips extension dtypes (the bf16
    # pools) as anonymous void records ("|V2"), silently losing the
    # dtype the importer validates — and raw bytes make bit-exactness
    # trivially true for every pool dtype.
    payload = {}
    for i, a in enumerate(arrays):
        if a is None:
            continue
        a = np.ascontiguousarray(a)
        payload[f"a{i}"] = np.frombuffer(a.tobytes(), np.uint8)
        payload[f"a{i}_dtype"] = np.bytes_(str(a.dtype).encode())
        payload[f"a{i}_shape"] = np.asarray(a.shape, np.int64)
    buf = io.BytesIO()
    np.savez_compressed(
        buf, version=np.int64(_WIRE_VERSION),
        key=np.bytes_(sess.key.encode()),
        kind=np.bytes_(_WIRE_KIND.encode()),
        tokens=np.asarray(sess.tokens, np.int64),
        length=np.int64(sess.length), span=np.int64(span),
        present=np.asarray(present, bool), **payload)
    return buf.getvalue()


def _np_dtype(name: str):
    """Resolve a dtype string, including the ml_dtypes extension types
    (bfloat16 & friends) plain numpy cannot name."""
    import numpy as np
    try:
        return np.dtype(name)
    except TypeError:
        import ml_dtypes
        return np.dtype(getattr(ml_dtypes, name))


def deserialize_session(data: bytes) -> Optional[SessionKV]:
    """Bytes -> a parked :class:`SessionKV`, or None on a malformed or
    incompatible-version payload (peer payloads are untrusted input —
    a bad one must never raise into the serving plane). Geometry
    validation against the adopting pool is the scheduler's job
    (``session_import``): this function only restores the container."""
    import numpy as np
    try:
        with np.load(io.BytesIO(data)) as z:
            if int(z["version"]) != _WIRE_VERSION:
                return None
            key = z["key"].tobytes().decode()
            kind = z["kind"].tobytes().decode()
            tokens = tuple(int(t) for t in z["tokens"])
            length = int(z["length"])
            span = int(z["span"])
            present = [bool(p) for p in z["present"]]
            arrays = []
            for i, p in enumerate(present):
                if not p:
                    arrays.append(None)
                    continue
                dt = _np_dtype(z[f"a{i}_dtype"].tobytes().decode())
                shape = tuple(int(s) for s in z[f"a{i}_shape"])
                arrays.append(np.frombuffer(
                    z[f"a{i}"].tobytes(), dt).reshape(shape))
            arrays = tuple(arrays)
    except Exception:   # noqa: BLE001 — peer payloads are untrusted
        return None
    if (not key or kind != _WIRE_KIND or span <= 0
            or not (0 < length <= len(tokens))
            or len(arrays) != 4 or arrays[0] is None):
        return None
    nbytes = sum(a.nbytes for a in arrays if a is not None)
    return SessionKV(key=key, tokens=tokens, length=length,
                     host=(arrays, span), nbytes=nbytes)


class KVTier:
    """Session index + host-pool budget accounting.

    State transitions (retain/park/wake/drop) run on the scheduler
    thread only — it owns the device buffers the transitions copy — so
    the lock exists for the /metrics readers, not for mutual exclusion
    between writers."""

    def __init__(self, host_bytes: float, idle_s: float = 30.0,
                 max_sessions: int = 4096) -> None:
        self.host_budget = float(host_bytes)
        self.idle_s = idle_s
        self.max_sessions = max_sessions
        self._mu = threading.Lock()
        self._sessions: dict[str, SessionKV] = {}   # guarded-by: _mu
        self._by_head: dict[tuple, str] = {}        # guarded-by: _mu
        self.host_bytes = 0                         # guarded-by: _mu
        # Counters: monotonic, written through the note_* helpers (or
        # internally under the lock) so the guarded-by annotation is
        # executable under GRAFTCHECK_LOCKCHECK=1 — round-13 replaced
        # the bare "torn reads harmless" += pokes, which were true but
        # unverifiable.
        self.n_parked_total = 0       # guarded-by: _mu
        self.n_waked_total = 0        # guarded-by: _mu
        self.n_wake_cold_total = 0    # guarded-by: _mu — follow-ups that found no session
        self.n_wake_tokens_total = 0  # guarded-by: _mu — prompt tokens wake did NOT re-prefill
        self.n_evicted_total = 0      # guarded-by: _mu
        self.n_pages_freed_total = 0  # guarded-by: _mu — HBM pages released by parking
        # grafttrace (round 15): optional tier-event observer — the
        # owning scheduler points this at its flight recorder so
        # park/wake/adopt/forget/evict land in the loop event ring.
        # ALWAYS invoked OUTSIDE ``_mu``: the observer appends under
        # its own lock, and nesting it under the index lock would hand
        # the lock-order analyzer a new edge for nothing.
        self.observer = None

    def _notify(self, kind: str, **meta) -> None:
        cb = self.observer
        if cb is not None:
            try:
                cb(kind, **meta)
            except Exception:   # noqa: BLE001 — observability never faults the tier
                pass

    # -- index ---------------------------------------------------------------

    @staticmethod
    def _head(tokens) -> Optional[tuple]:
        if len(tokens) < HEAD_GRAIN:
            return None
        return tuple(tokens[:HEAD_GRAIN])

    def counts(self) -> tuple[int, int]:
        """(resident, parked) session counts."""
        with self._mu:
            parked = sum(1 for s in self._sessions.values() if s.parked)
            return len(self._sessions) - parked, parked

    def resident_sessions(self) -> list[SessionKV]:
        """Resident sessions, least-recently-used first (the park-
        under-pressure scan order)."""
        with self._mu:
            res = [s for s in self._sessions.values() if not s.parked]
        return sorted(res, key=lambda s: s.last_used)

    def lookup(self, key: str, prompt_ids: list,
               count_miss: bool = True) -> Optional[SessionKV]:
        """Session whose tokens are a PROPER prefix of ``prompt_ids``
        (>= 1 suffix token must remain — its logits seed sampling), by
        explicit key first, else by the token-head index (context
        continuation with no session header). A key match whose content
        diverged (client edited history) is dropped — its KV can never
        serve this conversation again. Misses count toward
        ``kv_wake_cold_total`` only when a session was plausibly being
        continued (an indexable key existed) and ``count_miss`` is set
        (claim's re-validation does not double-count)."""
        with self._mu:
            s = self._sessions.get(key) if key else None
            if s is None:
                h = self._head(prompt_ids)
                if h is not None:
                    s = self._sessions.get(self._by_head.get(h, ""))
        indexable = bool(key) or self._head(prompt_ids) is not None
        if s is None:
            if count_miss and indexable:
                with self._mu:
                    self.n_wake_cold_total += 1
            return None
        if not (0 < s.length < len(prompt_ids)
                and tuple(prompt_ids[: s.length]) == s.tokens):
            if key and s.key == key:
                self.drop(s)        # diverged history: stale forever
            if count_miss and indexable:
                with self._mu:
                    self.n_wake_cold_total += 1
            return None
        s.last_used = time.monotonic()
        return s

    def insert(self, sess: SessionKV) -> None:
        """Register (or replace) a session. Callers must :meth:`take`
        any older entry under the same key first — the scheduler owns
        page/byte recycling, and the index cap is enforced by draining
        :meth:`overflow_victims` right after an insert."""
        with self._mu:
            self._sessions[sess.key] = sess
            h = self._head(sess.tokens)
            if h is not None:
                self._by_head[h] = sess.key
            if sess.parked:
                self.host_bytes += sess.nbytes

    def take(self, key: str) -> Optional[SessionKV]:
        """Remove and return a session (wake / replace): the caller now
        owns its pages or host payload."""
        with self._mu:
            s = self._sessions.pop(key, None)
            if s is None:
                return None
            h = self._head(s.tokens)
            if h is not None and self._by_head.get(h) == key:
                del self._by_head[h]
            if s.parked:
                self.host_bytes -= s.nbytes
            return s

    def claim(self, key: str, prompt_ids: list) -> Optional[SessionKV]:
        """Validated take: the wake path's claim — returns the session
        (removed from the index; the caller owns its pages/payload) only
        if it still extends ``prompt_ids``. None = it vanished or
        diverged since matching; the request cold-admits."""
        s = self.lookup(key, prompt_ids, count_miss=False)
        if s is None:
            return None
        return self.take(s.key)

    def drop(self, sess: SessionKV) -> Optional[list]:
        """Evict a session entirely. Returns its resident pages (for the
        caller to free) or None if it was parked/absent."""
        s = self.take(sess.key)
        if s is None:
            return None
        with self._mu:
            self.n_evicted_total += 1
        self._notify("evict", key=sess.key)
        return s.pages

    # -- cross-replica migration (serve/router.py drives this over the
    # /admin/session endpoints; payload format above) ------------------------

    def sessions_meta(self) -> dict[str, dict]:
        """{key: {len, nbytes, parked, idle_s}} — the migration control
        surface (GET /admin/session): small JSON, no KV bytes; the
        router decides who pulls what from whom."""
        with self._mu:
            now = time.monotonic()
            return {k: {"len": s.length, "nbytes": int(s.nbytes),
                        "parked": s.parked,
                        "idle_s": round(now - s.last_used, 3)}
                    for k, s in self._sessions.items()}

    def export_payload(self, key: str) -> Optional[bytes]:
        """Serialize one PARKED session for a peer replica. None when
        the key is absent or still resident (residency means device
        pages — the caller parks first via the scheduler's park-all
        hook). The session is RETAINED: migration removes it only after
        the destination acks the import (POST /admin/session/forget),
        so a failed export/import leaves the source fully consistent —
        the failpoint contract docs/robustness.md pins."""
        failpoint("serve.kv_tier.export")
        with self._mu:
            s = self._sessions.get(key)
            if s is None or not s.parked:
                return None
        # Host payload arrays are immutable after parking, and the
        # session object's host tuple is never mutated in place — the
        # serialize can safely run outside the lock.
        return serialize_session(s)

    def adopt(self, sess: SessionKV) -> bool:
        """Install an imported (parked) session. False when a RESIDENT
        session already holds the key — the local copy is live device
        state and strictly fresher; adopting over it would leak its
        pages (only the scheduler thread may free those). A parked
        local copy is replaced (index + host bytes only — safe from the
        HTTP thread that runs imports). Host-budget enforcement over
        PARKED victims runs inline; resident-session policy stays with
        the scheduler loop's own sweeps."""
        with self._mu:
            old = self._sessions.get(sess.key)
            if old is not None and not old.parked:
                return False
            if old is not None:
                # Parked replacement is index + byte accounting only —
                # done under ONE lock hold with the insert, so a
                # concurrent retain can never interleave between the
                # check and the replace (its pages would leak).
                h = self._head(old.tokens)
                if h is not None and self._by_head.get(h) == old.key:
                    del self._by_head[h]
                del self._sessions[old.key]
                self.host_bytes -= old.nbytes
            self._sessions[sess.key] = sess
            h = self._head(sess.tokens)
            if h is not None:
                self._by_head[h] = sess.key
            self.host_bytes += sess.nbytes
        for victim in self.host_victims():      # parked by definition
            self.drop(victim)
        self._notify("adopt", key=sess.key, nbytes=int(sess.nbytes))
        return True

    def forget(self, key: str) -> bool:
        """Drop a PARKED session without counting an eviction (the
        migration ack path: the session now lives on another replica —
        capacity-eviction dashboards must not read migrations as
        pressure). Resident sessions refuse: their pages are the
        scheduler's to free."""
        with self._mu:
            s = self._sessions.get(key)
            if s is None or not s.parked:
                return False
            h = self._head(s.tokens)
            if h is not None and self._by_head.get(h) == key:
                del self._by_head[h]
            del self._sessions[key]
            self.host_bytes -= s.nbytes
        self._notify("forget", key=key)
        return True

    # -- counters (the scheduler's write API; lock taken here so the
    # guarded-by annotations hold under runtime lockcheck) -------------------

    def note_parked(self, pages_freed: int = 0) -> None:
        with self._mu:
            self.n_parked_total += 1
            self.n_pages_freed_total += pages_freed
        self._notify("park", pages_freed=pages_freed)

    def note_waked(self, n: int, tokens_saved: int = 0) -> None:
        with self._mu:
            self.n_waked_total += n
            self.n_wake_tokens_total += tokens_saved
        self._notify("wake", n=n, tokens_saved=tokens_saved)

    def stats(self) -> dict[str, float]:
        """One consistent locked snapshot of the counters + host pool —
        the read API for /metrics and tests (a bare ``tier.n_*`` read
        from another thread fails under GRAFTCHECK_LOCKCHECK=1, by
        design)."""
        with self._mu:
            return {
                "host_bytes": self.host_bytes,
                "parked_total": self.n_parked_total,
                "waked_total": self.n_waked_total,
                "wake_cold_total": self.n_wake_cold_total,
                "wake_tokens_total": self.n_wake_tokens_total,
                "evicted_total": self.n_evicted_total,
                "pages_freed_total": self.n_pages_freed_total,
            }

    # -- policy --------------------------------------------------------------

    def park_candidates(self, now: Optional[float] = None,
                        force: bool = False) -> list[SessionKV]:
        """Resident sessions due for parking: idle past ``idle_s`` (or
        every resident session when ``force`` — pool pressure), oldest
        first."""
        t = time.monotonic() if now is None else now
        out = [s for s in self.resident_sessions()
               if force or (t - s.last_used) >= self.idle_s]
        return out

    def host_victims(self) -> list[SessionKV]:
        """Parked sessions the byte budget says must go, worst
        cost (bytes x idle) first."""
        with self._mu:
            over = self.host_bytes - self.host_budget
            if over <= 0:
                return []
            items = [(s.key, s.nbytes, s.last_used)
                     for s in self._sessions.values() if s.parked]
            by_key = {s.key: s for s in self._sessions.values()}
        return [by_key[k] for k in cost_evict(items, over)]

    def overflow_victims(self) -> list[SessionKV]:
        """Sessions past the index cap, least-recently-used first."""
        with self._mu:
            over = len(self._sessions) - self.max_sessions
            if over <= 0:
                return []
            ordered = sorted(self._sessions.values(),
                             key=lambda s: s.last_used)
        return ordered[:over]

    def reset_resident(self) -> None:
        """Drop every RESIDENT session (error-path recovery: the pool
        and allocator were rebuilt, so resident pages are dangling ids
        over dead content). Parked payloads live on host and survive."""
        with self._mu:
            dead = [s for s in self._sessions.values() if not s.parked]
            for s in dead:
                del self._sessions[s.key]
                h = self._head(s.tokens)
                if h is not None and self._by_head.get(h) == s.key:
                    del self._by_head[h]
        if dead:
            log.warning("dropped %d resident session(s) on device reset",
                        len(dead))
