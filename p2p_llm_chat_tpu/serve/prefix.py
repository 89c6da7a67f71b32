"""Shared-prefix KV cache for admission (vLLM-style prefix caching).

The reference's co-pilot wraps every suggestion in one fixed template
(web/streamlit_app.py:93) — every request the north-star workload serves
begins with the same token prefix. Chat requests with history share even
longer prefixes (all turns but the last). Recomputing that prefix's KV on
every admission is pure waste: this module prefills a prefix ONCE, keeps
its per-layer K/V on device, and admission then prefills only each
request's suffix, attending over the cached prefix (a continuation
forward at position offset P — the same masking shape the speculative
verify path uses).

Host-side policy lives here; the device-side admission programs live in
serve/scheduler.py (`_admit_batch_prefix[_paged]`). Two ways an entry is
born:

- **registered**: the serve front knows its template(s) up front
  (SERVE_PREFIX_TEXTS; the co-pilot template is registered by default) —
  built during warmup, so the programs compile before traffic.
- **promoted**: `observe()` counts repeated prompt heads at power-of-two
  grain; a head seen ``promote_after`` times is promoted and its KV built
  on the spot (one prefill dispatch; on TPU the first promotion of a new
  (P, S) shape pays a compile, which is logged).

A third way, round 11: **imported** — the replica router
(serve/router.py) watches each replica's promoted entries by token hash
and tells replicas missing a hot prefix to pull it from the replica
that built it (`export_payload`/`import_payload`, raw bytes over the
/admin/prefix endpoints). A prefix promoted by traffic on one replica
is then injectable on every other, so session-affinity imbalance no
longer decides which replica gets the admission win. Imported entries
are grain-snapped by construction (only auto-promoted heads are worth
shipping; registered templates exist on every replica from boot), so
the grain pre-warm's compiled splice programs cover them.

Auto-promoted prefix lengths are snapped DOWN to the grain ladder so the
compiled admission-program shapes stay bounded: P in {64, 128, 256, 512}
and the suffix reuses the existing prompt-bucket ladder. REGISTERED
templates cache at their exact token length instead — the set is small
and known at warmup, and ladder-snapping would silently drop templates
shorter than the smallest grain (the co-pilot template is ~18 tokens
under a real llama3 BPE vocabulary).

Eviction: ``max_bytes`` > 0 switches the store to the tier cost policy
(cost = bytes x recency, shared with serve/kv_tier.py's host pool) —
the biggest, longest-idle entries go first, replacing the blunt
count-capped LRU (which treated a 512-token entry and an 18-token
template as equal occupancy). ``max_entries`` stays as a hard sanity
cap either way. ``hits/misses/evictions`` are exported on /metrics
(the store tracked hits internally for LRU long before round 11, but
exported nothing).

Correctness: the cached K/V is produced by the same prefill math on the
same weights, so a prefix-cached admission is oracle-equal to the full
prefill (pinned by tests/test_prefix.py against the uncached scheduler).
Entries are only read between admission dispatches on the scheduler
thread; `register` and `import_payload` may run on other threads, hence
the lock.
"""

from __future__ import annotations

import hashlib
import io
import threading
import time
from dataclasses import dataclass, field
from typing import Optional

DEFAULT_GRAIN_LADDER = (64, 128, 256, 512)

# Wire-format version for export_payload / import_payload (bumped on
# any incompatible change; importers reject unknown versions).
_WIRE_VERSION = 1


def token_hash(ids) -> str:
    """Stable cross-replica identity of a prefix: sha256 over the token
    ids as little-endian int64 words (dtype-pinned so the hash cannot
    drift with numpy defaults across hosts). The router's shared-tier
    key — replicas serving the same checkpoint produce identical KV for
    identical ids, so the hash alone decides 'already have it'."""
    import numpy as np
    return hashlib.sha256(
        np.asarray(list(ids), dtype="<i8").tobytes()).hexdigest()


@dataclass
class PrefixEntry:
    """One cached prefix: ``ids`` (exactly P tokens — a ladder length for
    auto-promoted heads, any length for registered templates) and its
    prefilled K/V, shaped [L, P, Hkv, D] on device. A hybrid model's
    entry also keeps ``state``: the recurrent layers' state at the
    prefix's END (ops/state_pool.snapshot), which a suffix starts from.
    Pages can be cut back to a shorter prefix; a state cannot: an entry
    serves at its exact length only, which is all ``match`` ever does."""

    ids: tuple[int, ...]
    k: object                    # jax.Array [L, P, Hkv, D]
    v: object                    # jax.Array [L, P, Hkv, D]
    hits: int = 0
    last_used: float = field(default_factory=time.monotonic)
    state: object = None         # ops/state_pool.StatePool, no row axis

    @property
    def length(self) -> int:
        return len(self.ids)

    @property
    def nbytes(self) -> int:
        k = getattr(self.k, "nbytes", 0) or 0
        v = getattr(self.v, "nbytes", 0) or 0
        return int(k) + int(v) + int(getattr(self.state, "nbytes", 0) or 0)

    @property
    def token_hash(self) -> str:
        return token_hash(self.ids)


class PrefixStore:
    """Keyed by the exact token tuple; `match` finds the longest cached
    prefix of a prompt, `observe` drives auto-promotion."""

    def __init__(self, grain_ladder: tuple[int, ...] = DEFAULT_GRAIN_LADDER,
                 max_entries: int = 8, promote_after: int = 2,
                 max_tracked: int = 256, max_bytes: int = 0) -> None:
        self.grain_ladder = tuple(sorted(grain_ladder))
        self.max_entries = max_entries
        self.promote_after = promote_after
        self.max_tracked = max_tracked
        self.max_bytes = max_bytes
        self._lock = threading.Lock()
        self._entries: dict[tuple[int, ...], PrefixEntry] = {}
        # head tuple -> times seen (insertion-ordered; trimmed FIFO).
        self._seen: dict[tuple[int, ...], int] = {}
        # /metrics counters (monotonic ints; torn reads harmless).
        self.hits_total = 0
        self.misses_total = 0
        self.evictions_total = 0

    def __len__(self) -> int:
        return len(self._entries)

    @property
    def hits(self) -> int:
        with self._lock:
            return sum(e.hits for e in self._entries.values())

    @property
    def nbytes(self) -> int:
        with self._lock:
            return sum(e.nbytes for e in self._entries.values())

    @property
    def state_nbytes(self) -> int:
        """Of :attr:`nbytes`, the entries' state snapshots: one row's
        recurrent state a layer whatever the entry's length, so for a
        model that keeps one the store is sized in snapshots, not
        tokens (76 MB each for granite-4.0-h-micro; SERVE_PREFIX_MB
        counts them)."""
        with self._lock:
            return sum(int(getattr(e.state, "nbytes", 0) or 0)
                       for e in self._entries.values())

    def match(self, ids: list[int]) -> Optional[PrefixEntry]:
        """Longest entry that is a proper prefix of ``ids`` (at least one
        suffix token must remain to prefill — its logits seed sampling)."""
        with self._lock:
            best: Optional[PrefixEntry] = None
            for key, entry in self._entries.items():
                P = len(key)
                if P < len(ids) and tuple(ids[:P]) == key:
                    if best is None or P > best.length:
                        best = entry
            if best is not None:
                best.hits += 1
                best.last_used = time.monotonic()
                self.hits_total += 1
            else:
                self.misses_total += 1
            return best

    def observe(self, ids: list[int]) -> Optional[tuple[int, ...]]:
        """Count this prompt's heads at every ladder grain; return a head
        that just crossed ``promote_after`` sightings (longest first) and
        should be promoted to a cached entry, else None. The caller builds
        the KV and calls :meth:`put`.

        Grains already covered by a LONGER matching entry are not
        tracked: match() always picks the longest prefix, so a shorter
        entry for the same head would never be used — building it would
        be pure compile/prefill cost (observed: a hot template triggered
        one pointless promotion per ladder grain)."""
        candidate: Optional[tuple[int, ...]] = None
        with self._lock:
            covered = 0
            for key in self._entries:
                lk = len(key)
                # Only a PROPER prefix covers (match() needs a suffix
                # token left): an entry equal to the whole prompt cannot
                # serve it, so it must not suppress shorter grains.
                if covered < lk < len(ids) and tuple(ids[:lk]) == key:
                    covered = lk
            for g in self.grain_ladder:
                if g >= len(ids):       # need >= 1 suffix token
                    break
                if g <= covered:
                    continue
                head = tuple(ids[:g])
                if head in self._entries:
                    continue
                n = self._seen.get(head, 0) + 1
                self._seen[head] = n
                if n >= self.promote_after:
                    candidate = head    # longest qualifying grain wins
            while len(self._seen) > self.max_tracked:
                self._seen.pop(next(iter(self._seen)))
            if candidate is not None:
                del self._seen[candidate]
        return candidate

    def put(self, entry: PrefixEntry) -> None:
        """Insert (idempotent), then evict down to policy: the byte
        budget first when ``max_bytes`` is set — cost = bytes x recency
        (kv_tier.cost_evict, shared with the session host pool), so one
        giant stale entry goes before many small warm ones — and the
        ``max_entries`` count cap as the hard sanity bound either way.
        Safe between admission dispatches: evicted device arrays are
        freed by refcount after their last use.

        Entry lengths are NOT required to be on the grain ladder:
        auto-promoted heads are ladder lengths by construction
        (``observe`` only counts ladder grains), but registered
        templates cache at their exact token length — the operator names
        finitely many, and warmup compiles their admission shapes."""
        from .kv_tier import cost_evict
        with self._lock:
            self._entries[entry.ids] = entry
            if self.max_bytes:
                over = (sum(e.nbytes for e in self._entries.values())
                        - self.max_bytes)
                if over > 0:
                    items = [(e.ids, e.nbytes, e.last_used)
                             for e in self._entries.values()
                             if e.ids != entry.ids]    # newest never evicts itself
                    for ids in cost_evict(items, over):
                        del self._entries[ids]
                        self.evictions_total += 1
            while len(self._entries) > self.max_entries:
                lru = min(self._entries.values(), key=lambda e: e.last_used)
                del self._entries[lru.ids]
                self.evictions_total += 1

    def lengths(self) -> list[int]:
        """Distinct cached prefix lengths (for warmup compilation)."""
        with self._lock:
            return sorted({len(k) for k in self._entries})

    def snapshot(self) -> list[PrefixEntry]:
        with self._lock:
            return list(self._entries.values())

    # -- cross-replica shared tier (router-driven import/export) -------------

    def hashes(self) -> dict[str, dict]:
        """{token_hash: {"len": P, "hits": n}} for every cached entry —
        the router's scrape surface (GET /admin/prefix): small JSON, no
        KV bytes; the hash alone decides which replicas lack what."""
        with self._lock:
            return {e.token_hash: {"len": e.length, "hits": e.hits}
                    for e in self._entries.values()}

    def export_payload(self, h: str) -> Optional[bytes]:
        """Serialize one entry (by token hash) for a peer replica: ids +
        K/V as float32 (bf16 -> f32 is lossless; the importer casts back
        to its compute dtype) in an npz container. None = not cached."""
        import numpy as np
        import jax
        with self._lock:
            entry = next((e for e in self._entries.values()
                          if e.token_hash == h), None)
        if entry is None:
            return None
        k = np.asarray(jax.device_get(entry.k), dtype=np.float32)
        v = np.asarray(jax.device_get(entry.v), dtype=np.float32)
        buf = io.BytesIO()
        np.savez_compressed(
            buf, version=np.int64(_WIRE_VERSION),
            ids=np.asarray(entry.ids, np.int64),
            dtype=np.bytes_(str(entry.k.dtype).encode()), k=k, v=v)
        return buf.getvalue()

    def import_payload(self, data: bytes) -> Optional[PrefixEntry]:
        """Install a peer's exported entry (idempotent — an already-
        cached head just refreshes). Returns the entry, or None on a
        malformed/incompatible payload (logged by the caller). The K/V
        was computed by the same prefill math on the same checkpoint on
        the exporting replica, so admission through an imported entry
        keeps the oracle-equality contract."""
        import numpy as np
        import jax.numpy as jnp
        try:
            with np.load(io.BytesIO(data)) as z:
                if int(z["version"]) != _WIRE_VERSION:
                    return None
                ids = tuple(int(t) for t in z["ids"])
                dtype = z["dtype"].tobytes().decode()
                k = jnp.asarray(z["k"]).astype(dtype)
                v = jnp.asarray(z["v"]).astype(dtype)
        except Exception:   # noqa: BLE001 — peer payloads are untrusted
            return None
        if not ids or k.ndim != 4 or k.shape != v.shape \
                or k.shape[1] != len(ids):
            return None
        entry = PrefixEntry(ids=ids, k=k, v=v)
        self.put(entry)
        return entry
