"""Continuous-batching scheduler: many requests, one decode loop.

This is the component that turns the model into a *server*. The reference
issues one blocking Ollama call per suggestion (web/streamlit_app.py:91-95);
here all peers' requests are merged into a single fixed-shape batched decode
loop on the TPU (BASELINE.json config 3: 32 concurrent peers, p50 TTFT
target < 150 ms).

Design, shaped by XLA's compilation model (SURVEY.md §7 "hard parts"):

- **Fixed shapes.** The KV cache is ``[L, num_slots, max_seq, Hkv, D]`` and
  the decode step is one jitted program over all ``num_slots`` rows, traced
  once. Requests churn without recompilation because admission/eviction
  only changes *data* (an ``active`` mask + per-row lengths), never shapes.
- **Fused device steps, minimal host traffic.** Sampling runs *inside* the
  jitted programs with per-row options and per-row PRNG keys
  (models/sampling.sample_batched), so a decode tick transfers B int32
  tokens instead of [B, vocab] f32 logits (4 MB -> 128 bytes at B=32,
  vocab=32k). Next-step input tokens and PRNG keys
  stay resident on device; the host reads tokens only to detokenise,
  stream, and detect stops.
- **Admit = batched prefill + fused insert + first token.** Pending
  requests (drained through a ~3 ms arrival-gap window so a concurrent
  burst lands together) are grouped by power-of-two prompt bucket and
  prefilled *together* in chunks from a two-size ladder (8 or num_slots
  rows; short chunks carry padding entries whose installs are
  scatter-dropped via an out-of-range row sentinel), then one fused
  program splices the whole chunk's kv into the big cache in a single
  vector scatter and samples each row's first token from its prefill
  logits — one device dispatch + one tiny readback per chunk, so TTFT
  does not wait for the next decode tick and a 32-request burst costs
  one dispatch, not 32.
- **Single scheduler thread.** All device work and slot bookkeeping happen
  on one thread (the race-safety strategy SURVEY.md §5 prescribes); HTTP
  threads communicate via queues only.
- **Park, don't shrink.** Finished/empty rows stay in the batch with
  ``active=False``; decode_step leaves their lengths unchanged and their
  garbage logits/tokens are ignored (models/llama.py decode_step docstring
  — the overwrite-before-trust invariant).
"""

from __future__ import annotations

import collections
import itertools
import queue
import threading
import time
from dataclasses import dataclass, field
from typing import Iterator, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec

from ..models import family_for
from ..models.configs import ModelConfig
from ..models.layers import causal_mask
from ..models.llama import KVCache
from ..models.sampling import sample_batched, sample_step_batched
from ..obs.flight import FlightRecorder
from ..obs.intervals import ADMIT, CHUNK, CLEAN, PADDED, IntervalLedger
from ..obs.phase import LoopPhases, compile_clock, process_age_s
from ..ops.paged_attention import flash_append_chunk_pages
from ..ops.paged_kv import (IndexedPast, PageAllocator, PagedKVCache,
                            copy_slot,
                            gather_pages, scatter_pages, set_row_table,
                            write_prefill_batch, write_prefill_chunk)
from ..ops.state_pool import (StatePool, from_snapshot, snapshot,
                              ssm_kernel_covers, write_rows)
from ..tokenizer import Tokenizer
from ..utils.env import env_float
from ..utils.failpoints import failpoint
from ..utils.log import get_logger
from .backend import (GenerateOptions, GenerateRequest, OverloadError,
                      RequestStats, normalize_request)
from .prefix import PrefixEntry, PrefixStore

log = get_logger("serve.scheduler")

_MIN_BUCKET = 16
# Admission programs come in two widths a bucket: 1 row, for the request
# that arrives alone or takes the one row a finished request freed, and
# 2 rows, for requests collected together, wherever two rows' P + S
# tokens stay inside _ADMIT_PAIR_TOKENS (_admit_widths). No wider,
# because a second row is not free and further rows buy nothing: on a
# v5e a 2 x 256 dispatch costs 1.8x a 1 x 256 one in Mixtral-8x7B's
# layers (29.0 ms against 16.0), 2.2x in OLMoE's and 1.9-2.3x in
# Nemotron-H's, in the single-shot splice and in every chunk of a
# ladder (tools/check_admit_pair.py; PERF.md section 6, PR 39). A routed
# model's one-row dispatch of 256 positions already sits at the ridge:
# its expert buckets hold twice the positions routed to them (the
# capacity factor) or all of them (dropless), so their arithmetic takes
# as long as the weight stream it was meant to hide under, and from
# there a dispatch's time grows with its rows, dummy entries included.
# So a burst runs pair after pair, decode ticks in between, and two
# requests never pay for a 4- or 8-row program; and nothing waits for a
# partner: sharing a dispatch saves a tenth of two, at best.
_ADMIT_PAIR_TOKENS = 2048
# ... and inside this many bytes of the small cache both rows are
# prefilled into ([cache_layers, R, P + S, Hkv, D], K and V, in the
# compute dtype). Tokens stand for bytes while a token holds a few
# dozen layers of grouped heads (Mistral 7B: 131 KB, 0.27 GB a pair at
# 2,048); a looped stack's token holds one cache layer a PASS and a layer
# (Ouro-2.6B: 192 of 16 heads, 1.57 MB), and its pair at 1,024 would be
# 3.2 GB of a chip whose pool already takes what the weights left.
_ADMIT_PAIR_BYTES = 1 << 30
# A ladder's LAST chunk hands a carry of more than this a row back to the
# host (which drops it), so that the carry is donated and the chunk's
# forward writes it where it lies. A carry that dies in the program
# cannot be donated (no output to alias), so XLA copies it before the
# layer scan writes into it, and once more around the padding ``cond``:
# twice the carry in temporaries. Nobody sees that at Mistral 7B's 146 MB
# a row of 1,112 positions; a looped stack's 1,024-token row is 1.6 GB,
# and its last chunk wanted 3.0 GB of copies beside a pool that had left
# 2.4 (my chip run, PR 53: "Used 16.70G of 15.75G hbm").
_CARRY_IN_PLACE_BYTES = 1 << 29
# Cap on the R x S footprint of an operator-fixed width (admit_chunk):
# the fused prefill materialises a [L, R, S(+P), Hkv, D] small cache, so
# wide chunks at long prompt buckets would transiently eat gigabytes of
# HBM (32 x 2048 at a 1B config is ~6 GB).
_ADMIT_TOKEN_BUDGET = 16384
# Repeat-penalty recent-token window (Ollama repeat_last_n default).
_RING = 64
# Shortest registered prefix worth a cache entry: below this the saved
# prefill compute is noise next to the admission program's fixed cost.
_MIN_REGISTER_PREFIX = 8
# Adaptive speculation: below this EMA of accepted-drafts-per-tick the
# verify pass costs more than it saves; probe intermittently instead.
# EMAs are PER DRAFT SOURCE (ngram | model): a cold n-gram index on
# free-form output must not throttle model drafting, and vice versa.
_SPEC_EMA_FLOOR = 0.5
_SPEC_EMA_ALPHA = 0.1
# Cold start: each source seeds at 2x the floor (speculation gets a fair
# shot) and zero-acceptance ticks decay with this faster alpha, so a
# workload that never accepts throttles within ~3 spec ticks instead of
# the ~20 the old spec_k-optimistic seed burned (ISSUE 6 satellite).
_SPEC_EMA_SEED = 2 * _SPEC_EMA_FLOOR
_SPEC_EMA_ZERO_ALPHA = 0.3
_SPEC_PROBE_EVERY = 8
# Deferred prefix-promotion builds prefer idle ticks, but under
# sustained load one build is allowed per this many decode ticks.
_PROMOTE_EVERY_TICKS = 256
# Widest suffix bucket a session wake admits single-shot: the wake
# forward is ONE dispatch (no chunk ladder yet — recorded headroom), so
# its decode-stall contribution is bounded by one S-wide verify.
# Longer new turns cold-admit through the chunked path instead.
_WAKE_MAX_SUFFIX = 256


def _bucket(n: int, max_seq: int) -> int:
    """Smallest power-of-two >= n (>= _MIN_BUCKET), capped at max_seq."""
    b = _MIN_BUCKET
    while b < n:
        b *= 2
    return min(b, max_seq)


def _admit_layout(mppr: int) -> tuple[int, ...]:
    """The one int32 buffer that carries an admission to the device: a
    row an entry, and in a row, back to back, the entry's five ints
    (len / row / seed / top_k / total len), its three floats as their
    bits (temperature / top_p / repeat_penalty), its penalty ring
    [_RING], its page table [mppr] and its S tokens, so a buffer
    [R, W] names R and S by its shape. Returns the four column cuts
    between the parts; the last is where the tokens start. One
    transfer an admission and none for a ladder's later chunk, where
    five arrays went up a dispatch: -0.8 to -1.2 ms of host a loop
    iteration in the full-batch cells (PERF.md §6, PR 36)."""
    return tuple(itertools.accumulate((5, 3, _RING, mppr)))


def _admit_unpack(buf, mppr: int) -> tuple:
    """_admit_layout's inverse: (tokens [R,S], ints [5,R], floats
    [3,R], rings [R,_RING], tables [R,mppr]) of a packed buffer. Of a
    host buffer they are views, so what is written to them is written
    to it; of a device buffer, inside a program, static slices.
    ``floats`` comes back float32, bit for bit what the host wrote."""
    cuts = _admit_layout(mppr)
    ints, floats, rings, tables, tokens = (
        buf[:, lo:hi] for lo, hi in zip((0, *cuts), (*cuts, None)))
    if isinstance(buf, np.ndarray):
        floats = floats.view(np.float32)
    else:
        floats = jax.lax.bitcast_convert_type(floats, jnp.float32)
    return tokens, ints.T, floats.T, rings, tables


def _admit_buffer(R: int, S: int, mppr: int, vocab_size: int) -> tuple:
    """A fresh host buffer for R entries of S tokens and its five
    views, holding what an entry that carries nothing holds: no
    tokens, no pages, an empty penalty window (the sentinel
    ``vocab_size``), top_p and repeat penalty 1.0."""
    buf = np.zeros((R, _admit_layout(mppr)[-1] + S), np.int32)
    views = _admit_unpack(buf, mppr)
    views[2][1:] = 1.0
    views[3][:] = vocab_size
    return buf, views


def _unless_padding(real, run, *carried):
    """``run(*carried)`` where the scalar ``real`` holds; where it does
    not, ``carried`` as it came and nothing computed (one ``lax.cond``:
    the ladder's chunk programs, ``_make_prefill_chunk_program._fwd``)."""
    return jax.lax.cond(real, run, lambda *carried: carried, *carried)


def _causal_pairs(lo: int, hi: int) -> int:
    """(query, key) pairs of prompt positions lo..hi-1 under a causal
    mask: position i attends i + 1 positions."""
    return (hi * (hi + 1) - lo * (lo + 1)) // 2


def _pow2_floor(n: int) -> int:
    """Largest power of two <= n; 1 for n < 1."""
    return 1 << (max(n, 1).bit_length() - 1)


@dataclass
class _Slot:
    """Host-side state for one batch row. Touched only by the scheduler
    thread after admission."""

    req: GenerateRequest
    stats: Optional[RequestStats]
    out_q: "queue.Queue[Optional[tuple]]"             # (delta, put time)
    seed: int
    ids: list[int] = field(default_factory=list)      # generated ids
    prompt_ids: list[int] = field(default_factory=list)
    text: str = ""                                     # decoded from ids[:decoded_upto]
    decoded_upto: int = 0                              # ids already folded into text
    streamed: int = 0                                  # len of text already yielded
    max_new: int = 0
    ctx_len: int = 0                                   # host mirror of lengths[row]
    ctx_budget: int = 0                                # max ctx this slot may hold
    pages: Optional[list[int]] = None                  # physical pages
    cancelled: threading.Event = field(default_factory=threading.Event)
    error: Optional[str] = None                        # surfaced by submit()
    prefix: Optional[PrefixEntry] = None               # cached-prefix admission
    prefix_checked: bool = False                       # match() ran for this slot
    # Session wake (multi-tier KV, serve/kv_tier.py): the matched open
    # session's key, and — for parked sessions — the prefetched
    # on-device payload as (session object, device arrays): the H2D
    # copy starts at match time so it overlaps admission work queued
    # ahead of the wake dispatch, and the session stamp invalidates the
    # prefetch if the session is replaced/re-parked before the claim (a
    # stale payload scattered under a NEWER session's sizes would break
    # the byte-identity contract, or crash the jitted scatter). Like
    # every _Slot field, the stamps are confined to the scheduler loop
    # thread (the _Replica precedent: the guard lives on the OWNING
    # scheduler, whose tables carry the machine-checked annotations) —
    # set at match time, cleared on claim/demote/error, never read off
    # the loop.
    wake_key: Optional[str] = None
    wake_dev: Optional[tuple] = None
    last_emit_t: float = 0.0                           # inter-token gap tracking
    # grafttrace: when this slot's admission dispatch began — splits the
    # request's pre-first-token wall into queue wait (arrival -> here)
    # and prefill (here -> install) for the sched.* spans. 0 = never
    # dispatched (the spans fall back to the install stamp).
    admit_t: float = 0.0
    # The interval ledger's totals at this request's first token
    # (IntervalLedger.totals), for the differences _release records on
    # sched.decode and sched.decode.cut. None for an unsampled request.
    cut0: Optional[tuple] = None
    # Admission-queue depth accounting (overload shedding): on_depart
    # fires exactly once, at the earlier of batch-row install or any
    # terminal outcome — the depth gauge must count submitted-but-not-
    # yet-admitted requests only, and warmup jobs share the same queue.
    on_depart: Optional[object] = None
    departed: bool = False

    # The hand-off from the loop to the HTTP thread: seconds between
    # a delta's put (push) and its dequeue (Scheduler._consume), summed
    # by the one consumer and folded into the scheduler's counters when
    # the stream ends.
    handoff_s: float = 0.0
    handoff_n: int = 0

    def push(self, delta: str) -> None:
        if delta:
            now = time.monotonic()
            if self.stats is not None and self.stats.first_push_t is None:
                self.stats.first_push_t = now
            self.out_q.put((delta, now))

    done: bool = False                                 # finish() has run

    def depart(self) -> None:
        if not self.departed:
            self.departed = True
            if self.on_depart is not None:
                self.on_depart()

    def finish(self) -> None:
        self.depart()
        self.done = True
        if self.stats is not None and self.stats.total_s is None:
            self.stats.total_s = time.monotonic() - self.req.arrival_time
        if self.stats is not None and self.stats.context is None:
            # Ollama /api/generate "context": ids a follow-up request can
            # send back to continue this exchange.
            self.stats.context = list(self.prompt_ids) + list(self.ids)
        self.out_q.put(None)

    def fail(self, msg: str) -> None:
        """Finish with an error the consumer re-raises (the API front maps
        it to Ollama's error record / 500, which the UI degrades to the
        reference's "(LLM error)" string)."""
        self.error = msg
        self.finish()


@dataclass
class _PrefillCarry:
    """Host state of a half-prefilled admission chunk (chunked prefill:
    the prompt lands in fixed token-budget chunks, decode ticks run in
    between — see BatchScheduler.prefill_chunk). Touched only by the
    scheduler thread. ``kv``/``logits`` are the device carry: the small
    continuation cache accumulating the chunks' KV and the [R, vocab]
    merged last-prompt-position logits the final chunk samples from."""

    chunk: list[_Slot]
    rows: list[int]
    S: int                         # suffix bucket (the chunk ladder's span)
    off: int                       # suffix tokens already prefilled
    C: int                         # chunk width, snapshotted at admission —
    # a runtime toggle of scheduler.prefill_chunk must not
    # reshape or never-finish an in-flight carry
    prefix: Optional[PrefixEntry]  # shared broadcast prefix (or None)
    kv: Optional[object]           # device carry cache [L,R,P0+S,Hkv,D]
    logits: Optional[object]       # device carry [R,V] f32
    packed: object                 # the admission's packed buffer, on the
    # device since the admission (_admit_layout): every chunk program
    # takes it whole and slices its own tokens, so no chunk uploads
    padded: int = 0                # chunks dispatched past every row's suffix


class _SlotStream:
    """Iterator over a submitted request's deltas. submit() enqueues the
    slot EAGERLY (the overload check must run at call time), so the
    cancel path can no longer live only in the consuming generator's
    ``finally`` — a generator closed or GC'd before its first next()
    never runs its body, which would leave an orphaned queued request
    decoding to completion for nobody. This wrapper cancels the slot on
    close() and on GC even when iteration never started (idempotent:
    cancelled.set() on a finished slot is a no-op)."""

    __slots__ = ("_gen", "_slot")

    def __init__(self, gen, slot) -> None:
        self._gen = gen
        self._slot = slot

    def __iter__(self) -> "_SlotStream":
        return self

    def __next__(self) -> str:
        return next(self._gen)

    def close(self) -> None:
        self._slot.cancelled.set()
        self._gen.close()

    def __del__(self) -> None:
        try:
            self.close()
        except Exception:   # noqa: BLE001 — interpreter-shutdown GC
            pass


class _WarmupJob:
    """A closure executed ON the scheduler thread (posted via the admit
    queue). Warmup dispatches the real programs against the live device
    buffers, and only the scheduler thread may touch those — running the
    job anywhere else would race the decode loop."""

    __slots__ = ("fn", "void", "done", "err")

    def __init__(self, fn, void: threading.Event) -> None:
        self.fn = fn
        # Shared by one warmup's jobs: set by the first that fails, and
        # the jobs queued behind it then skip — no point compiling the
        # rest of a ladder that can never go ready.
        self.void = void
        self.done = threading.Event()
        self.err: Optional[BaseException] = None

    def run(self) -> None:
        try:
            if not self.void.is_set():
                self.fn()
        except BaseException as e:   # noqa: BLE001 — re-raised by caller
            self.void.set()
            self.err = e
        finally:
            self.done.set()


class BatchScheduler:
    """Owns the device state (params, KV cache, per-row sampling state)
    and the decode loop."""

    # Read by benchmark/serve_cell.py's reference check.
    kv_mode = "paged"

    def __init__(self, params: dict, config: ModelConfig,
                 tokenizer: Tokenizer, num_slots: int = 8,
                 max_seq: int = 1024, mesh=None, page_size: int = 64,
                 num_pages: Optional[int] = None,
                 admit_chunk: Optional[int] = None,
                 queue_timeout_s: Optional[float] = 60.0,
                 spec_k: int = 0,
                 prefix_cache: bool = False,
                 prefix_promote_after: int = 2,
                 kv_quant: bool = False,
                 decode_fuse_max: int = 4,
                 prefill_chunk: int = 256,
                 queue_max: Optional[int] = None,
                 loop_budget_ms: Optional[float] = None,
                 drafter: Optional[object] = None,
                 kv_host_gb: float = 0.0,
                 kv_idle_s: float = 30.0,
                 spec_tree_nodes: int = 0,
                 spec_tree_gap: float = 4.0) -> None:
        """``admit_chunk``: a fixed admission width an operator may set.
        None (default): a dispatch is as wide as what it carries — the
        1-row program for a request admitted alone, the bucket's 2-row
        program (where two rows stay inside about 2,048 tokens:
        _admit_widths) for requests collected together, a larger burst
        through several of those with decode ticks in between. A fixed
        power of two makes that the ONLY width, for every admission
        (narrowed only where the bucket would pass the HBM budget):
        fewer programs to warm, and every lone request padded to it.

        ``queue_timeout_s``: server-side admission deadline. A request
        that has not reached a batch row this long after arrival fails
        with an error instead of waiting forever (the reference's client
        gives up at 60 s — web/streamlit_app.py:95 — so holding its
        request longer only wastes pool space). None disables.

        ``queue_max``: admission-queue depth bound (overload shedding).
        A submit() arriving with this many requests already queued
        (submitted, not yet in a batch row) fails IMMEDIATELY with
        :class:`OverloadError` — the HTTP front maps it to ``503 +
        Retry-After`` — instead of burning ``queue_timeout_s`` in line
        only to expire. None (default) sizes to ``8 * num_slots`` (the
        batch churning several times over is work the deadline can
        plausibly still cover; deeper than that, the tail would expire
        anyway and fast-failing is strictly kinder to clients). 0
        disables (unbounded legacy queue). Shed requests count in
        ``requests_shed_total``.

        ``loop_budget_ms``: scheduler-loop watchdog budget. An
        iteration of the serving loop that exceeds this wall budget
        (a mid-serving compile, a wedged device call, a pathological
        host stall) is logged once per stall episode and exported as
        the ``loop_stall_ms`` max gauge — the liveness signal an
        operator alerts on. None reads ``SERVE_LOOP_BUDGET_MS``
        (default 5000); 0 disables.

        ``spec_k``: speculative decoding: each tick verifies up to K
        drafted tokens per row in one forward
        (models/llama.verify_step[_paged] + exact acceptance sampling),
        so ticks emit 1..K+1 tokens. 0 disables. Drafts come from a
        priority-ordered hybrid of sources (utils/draft.DraftSource):
        prompt-lookup n-grams first (~free when they hit — quoting
        workloads), then — when ``drafter`` is set — a resident draft
        model filling in on n-gram misses (free-form workloads). Each
        source throttles on its OWN acceptance EMA.

        ``drafter``: a serve/draft_model.ModelDrafter resident alongside
        the target (same batch geometry, same vocabulary — validated
        here). None = n-gram-only speculation (the pre-round-9
        behavior). Requires ``spec_k`` > 0 to have any effect.

        ``spec_tree_nodes``: tree speculation (round 17). > 0 turns the
        spec tick's verify into a TREE of that many nodes (pow2-snapped
        up): node 0 the current token, nodes 1..spec_k the main draft
        chain, the rest top-2 sibling leaves placed at the drafter's
        least-certain main positions (top-1/top-2 logit gap below
        ``spec_tree_gap``). One batched verify scores every root path
        via a tree-topology attention mask
        (models/llama.verify_tree[_paged]); acceptance stays
        distribution-exact (models/sampling.spec_verify_tree), and
        greedy output is BIT-identical tree on/off. Needs spec_k >= 1
        and at least one sibling slot (nodes >= spec_k + 2) —
        otherwise it normalizes to 0 and the linear program runs.
        Sources without runner-up scores (n-gram) degrade to a linear
        chain through the tree program (utils/draft.DraftSource.
        draft_tree_batch).

        ``kv_quant``: store the paged pool as int8 with per-(slot,
        kv-head) scales (ops/paged_kv.py). Decode is KV-bandwidth-bound,
        so this trades ~s/2 elementwise KV rounding (outputs may differ
        slightly from the bf16 oracle) for half the attention read
        traffic and double the context capacity per pool byte. Under
        kv_quant, spec-mode output tracks plain-tick output to rounding
        error rather than bit-exactly: both attend-before-write paths
        see the current block at full precision, but the verify block's
        EARLIER drafts are unquantized where the plain path, once they
        commit, reads them quantized — logit ties can flip
        (ops/paged_attention.paged_attention_verify_append).

        ``decode_fuse_max``: fused multi-step decode — one dispatch runs
        up to this many decode steps as an on-device ``lax.scan``
        (models/llama.decode_fused), amortising the per-tick host
        dispatch + readback by K. K adapts per tick: 1 whenever speculation
        could run, any row is within K tokens of its budget, or — with
        chunked prefill disabled or not covering every bucket (max_seq
        not a chunk multiple) — admissions are pending;
        otherwise it doubles up to this cap. 1 disables. Decision table
        in _choose_fuse_k, pinned by tests/test_fused_decode.py.
        Output is bit-identical to plain ticks (same programs per step,
        same key/ring streams; EOS parks rows inside the scan).

        ``prefill_chunk``: chunked prefill (Sarathi-style stall-free
        admission). A prompt whose bucket exceeds this token budget
        (power-of-two snapped) prefills in fixed chunks the loop
        interleaves with decode ticks — one chunk dispatch per loop
        iteration — so no single admission dispatch stalls in-flight
        decodes longer than one chunk's compute, and fused decode keeps
        ramping while a backlog drains (pre-chunking, ANY pending
        admission collapsed K to 1 and a 512-token admission froze
        every stream for its whole prefill). Chunked output is
        BIT-identical to the single-shot admission (the continuation
        forward runs at the full bucket width — models/llama.
        prefill_chunk — and the final chunk samples from the same
        logits; pinned by tests/test_chunked_prefill.py). 0 disables
        (whole-bucket admission, the legacy fused-K collapse rule).

        ``kv_host_gb``: multi-tier KV — host-RAM session parking
        (serve/kv_tier.py). > 0 enables: a finished request whose
        client named a session (or whose prompt is long enough to
        index by token head) keeps its KV *open* — resident in the
        page pool first, parked to a host-RAM copy under idle timeout
        (``kv_idle_s``) or page-pool pressure, dropped entirely by the
        bytes x recency cost policy when the host budget fills. A
        follow-up whose prompt extends the session's tokens *wakes* it:
        parked pages re-upload and scatter back in one dispatch
        (prefetched at match time, so the copy overlaps admission work
        ahead of it) and only the new turn's suffix runs a forward —
        admission compute drops from O(history) to O(new turn), and
        open sessions are bounded by host RAM instead of HBM. Resumed
        greedy output is BYTE-identical to a never-parked session (the
        raw pool words round-trip). 0 disables (legacy: finish frees).

        ``prefix_cache``: shared-prefix KV caching (serve/prefix.py).
        Prompts that begin with a cached prefix (the co-pilot template,
        a chat history head) prefill only their suffix, attending over
        the prefix KV computed once — admission compute drops from
        O(full prompt) to O(suffix). Register known templates via
        :meth:`register_prefix` / warmup ``prefix_texts``; repeated
        heads auto-promote after ``prefix_promote_after`` sightings."""
        self.kv_quant = kv_quant
        # The gather->flash-append boundary this process's programs bake
        # in at trace time: a function of the model's pool geometry, the
        # mesh and the platform, so fixed for the engine's life.
        self._paged_flash_min_w = self._flash_min_w(config, mesh, kv_quant)
        if admit_chunk is not None and admit_chunk < 1:
            raise ValueError(f"admit_chunk must be >= 1, got {admit_chunk}")
        self.admit_chunk = admit_chunk
        self.queue_timeout_s = queue_timeout_s
        # Overload shedding (see docstring): depth counts REQUESTS only
        # (warmup jobs share _admit_q, and a background 8B warmup is
        # hundreds of queued jobs — counting them would shed every
        # request at boot). The counter moves on submit and on each
        # slot's depart (install or terminal), from HTTP threads and
        # the scheduler thread both, hence the lock.
        if queue_max is not None and queue_max < 0:
            raise ValueError(f"queue_max must be >= 0, got {queue_max}")
        self.queue_max = (8 * num_slots if queue_max is None else queue_max)
        # Intended serving-plane hierarchy (machine-checked by
        # graftcheck lock-order): the admission-depth lock orders before
        # the KV tier's index lock — scheduler code may touch the tier
        # while accounting depth, but KVTier must never call back into
        # submit/depart while holding its own lock.
        # lock-order: BatchScheduler._depth_mu < KVTier._mu
        self._depth_mu = threading.Lock()
        self._queued_requests = 0     # guarded-by: _depth_mu
        self._n_shed = 0              # guarded-by: _depth_mu
        # The streams' hand-off (_Slot.handoff_s), folded as each ends.
        self._handoff_s = 0.0         # guarded-by: _depth_mu
        self._n_deltas = 0            # guarded-by: _depth_mu
        # Draining (replica-router mode, serve/router.py): a draining
        # scheduler finishes its in-flight streams but refuses NEW
        # submissions (OverloadError -> the front's 503) and reports
        # not-ready so balancers route new sessions elsewhere. An Event
        # (not a bare bool) so readers never see a torn flip.
        self._draining = threading.Event()
        # Scheduler-loop watchdog (see docstring).
        self.loop_budget_ms = (env_float("SERVE_LOOP_BUDGET_MS", 5000.0)
                               if loop_budget_ms is None else loop_budget_ms)
        self._loop_stall_ms = 0.0     # owned-by: _loop
        self._loop_stalled = False    # owned-by: _loop
        # Last COMPLETE stall episode's over-budget wall (round 15):
        # ``loop_stall_ms`` above is a high-water max that never resets,
        # so a dashboard can't see recovery — this one re-stamps per
        # episode and falls back to 0-ish readings between them.
        self._loop_stall_last_ms = 0.0  # owned-by: _loop
        # grafttrace (obs/): loop-iteration counter for flight-recorder
        # events, the always-on event ring itself, and the span store.
        # The store reference is installed once at wiring time
        # (set_trace_store, before traffic) and read by _loop; None =
        # tracing off for this scheduler.
        self._loop_iter = 0           # owned-by: _loop
        self._last_fuse_k = 0         # owned-by: _loop
        self._flight = FlightRecorder()
        self._trace = None
        # Loop-phase timer (obs/phase.py): where this thread's wall
        # goes, as self times by phase, on the profiler's clock and as
        # window counters (serve_loop_*_seconds_total).
        self._phase = LoopPhases()    # owned-by: _loop
        # Whether a launch found the device empty (_note_launch): the
        # newest output the loop holds of its last launch (one no later
        # call is given to donate before the next launch has looked at
        # it), and by the kind of launch, how many there were and at how
        # many of them that output had already arrived.
        self._last_out = None         # owned-by: _loop
        self._n_launch = {"admit": 0, "prefill_chunk": 0,
                          "decode": 0}             # owned-by: _loop
        self._n_starved = dict(self._n_launch)     # owned-by: _loop
        self._loop_s = 0.0            # owned-by: _loop — wall of all iterations
        self._warm_iter = 0           # owned-by: _loop — last iteration that ran a warm-up job
        # Boot gauges (serve_boot_*): set once each, at the end of this
        # constructor and when a warm-up finishes. The compile clock is
        # the process's own (started here if the entry point has not).
        clock = compile_clock()
        # (events, seconds) of it as the loop last looked (_watchdog).
        self._compiles_heard = (clock.events, clock.seconds)  # owned-by: _loop
        self._boot_load_s = 0.0
        self._boot_warmup_s = 0.0
        self._boot_compile_s = 0.0
        self._n_warmup_jobs = 0       # owned-by: _loop
        # Heartbeat: start time of the CURRENT loop iteration (written
        # by _loop each pass, read by metrics_snapshot) — lets the gauge
        # expose an in-flight stall a wedged iteration would otherwise
        # only report after it ends (i.e. never, for a hung device
        # call). Torn reads of a float are harmless for a gauge.
        self._loop_beat: Optional[float] = None
        # Readiness (/readyz): warmup gating — see the ``ready`` property.
        self._warmup_started = False
        self._warmup_done_at: Optional[float] = 0.0
        # Set once when a warmup fails; never cleared (see warmup()).
        self.warmup_error: Optional[str] = None
        self.spec_k = spec_k
        self.config = config
        self.tokenizer = tokenizer
        self.num_slots = num_slots
        self.max_seq = min(max_seq, config.max_seq_len)
        self.mesh = mesh
        self.page_size = page_size
        # Default pool: every slot at max_seq (num_slots x max_seq) plus
        # the garbage page. A request is admitted at its *actual* budget,
        # so a smaller pool (or more slots) serves the same traffic in
        # less HBM; override via num_pages / SERVE_PAGES.
        self.num_pages = (num_pages if num_pages is not None else
                          num_slots * -(-self.max_seq // page_size) + 1)
        self._dtype = params["embed"].dtype
        # One position of a prefill's dense carry (models/llama.KVCache:
        # K and V of every cache layer, in the compute dtype).
        self._carry_token_bytes = (
            config.cache_layers * config.cache_kv_heads
            * (config.cache_k_dim + config.cache_v_dim)
            * jnp.dtype(self._dtype).itemsize)
        # llama or mixtral — same functional surface (models.family_for),
        # so dense and MoE configs serve through one scheduler.
        self._model = family_for(config)
        model = self._model
        if config.is_latent:
            # Paths that assume per-head K and V pages refuse a latent
            # model here, by name, before anything is compiled.
            for on, what in (
                    (mesh is not None, "a mesh (its cache is one latent "
                     "head, which cannot be split by heads)"),
                    (bool(spec_k) or drafter is not None, "speculative "
                     "decoding (the verify programs read per-head K and "
                     "V pages)")):
                if on:
                    raise ValueError(
                        f"{config.name} keeps a latent KV cache "
                        f"(kv_lora_rank {config.kv_lora_rank}) and is not "
                        f"served under {what}")
        if config.state_layers:
            # Paths that assume "a row's past is its pages" refuse a
            # model with recurrent state, convolution windows or window
            # rings here, by name (``state_kinds``): each would have to
            # carry them or roll them back.
            for on, what in (
                    (mesh is not None, "a mesh (the state pool is not "
                     "laid out over one)"),
                    (bool(spec_k) or drafter is not None, "speculative "
                     "decoding (a rejected draft would have to roll them "
                     "back)"),
                    (bool(kv_host_gb) and kv_host_gb > 0, "session "
                     "parking (SERVE_KV_HOST_GB: park and wake move pages "
                     "and would leave them behind)")):
                if on:
                    raise ValueError(
                        f"{config.name} keeps {config.state_kinds} "
                        f"beside its pages and is not served under {what}")
        if config.is_indexed:
            # Paths that know K and V alone refuse an indexed model here,
            # by name, with what each would need (ROADMAP.md, Reach).
            for on, what in (
                    (mesh is not None, "a mesh (its index keys have one "
                     "head and its selection gathers rows of every KV "
                     "head: the pool's head split would need a gather "
                     "under shard_map)"),
                    (bool(spec_k) or drafter is not None, "speculative "
                     "decoding (the verify programs attend a block of "
                     "positions over the whole window and write no index "
                     "key: they would need a selection a block "
                     "position)"),
                    (bool(kv_host_gb) and kv_host_gb > 0, "session "
                     "parking (SERVE_KV_HOST_GB: the tier's payload is "
                     "K, V and their scales, and would have to carry the "
                     "index keys gather_pages hands it)")):
                if on:
                    raise ValueError(
                        f"{config.name} keeps an index key a token beside "
                        f"K and V (index_topk {config.index_topk}) and is "
                        f"not served under {what}")
        if config.ut_steps > 1:
            # A looped stack (models/llama._walk) is carried through the
            # dense family's programs on one chip. Paths that walk a stack
            # themselves, or were never run over one, refuse it here, by
            # name, with what each would need (ROADMAP.md, Reach).
            for on, what in (
                    (config.is_moe or config.is_latent or config.is_hybrid,
                     f"the {model.__name__.rsplit('.', 1)[-1]} family "
                     "(only models/llama.py's walk knows passes: a routed, "
                     "latent or hybrid layer would need its counts and "
                     "its state carried a pass at a time)"),
                    (mesh is not None, "a mesh (parallel/ring.py and "
                     "parallel/pipeline.py scan the layers once, and the "
                     "pool's head split was never run over "
                     f"{config.cache_layers} cache layers)"),
                    (bool(spec_k) or drafter is not None, "speculative "
                     "decoding (the verify programs walk the passes, but "
                     "acceptance, the tree's slot compaction and a "
                     "drafter were never run over a looped stack)")):
                if on:
                    raise ValueError(
                        f"{config.name} walks its {config.num_layers} "
                        f"layers {config.ut_steps} times a token "
                        f"(ut_steps) and is not served under {what}")
        # Where an admission's packed buffer goes (_admit_upload): every
        # device of a mesh, committed; else the default device.
        self._packed_sharding = (
            None if mesh is None
            else NamedSharding(mesh, PartitionSpec()))
        # Width of the counts a routed model's programs hand back behind
        # their tokens (_with_moe): 2, or the family's own.
        self._moe_w = getattr(model, "STATS_WIDTH", 2)
        # Models whose prefill programs take the mask of real positions
        # and hand counts back (the ``_counted`` / ``_touched`` forms): a
        # routed model's, and a hybrid's, whose recurrent state and
        # window rings padding must not move, routed layers or none.
        self._counted = config.is_moe or config.is_hybrid
        # What the counts behind a prefill's first tokens are, entry by
        # entry, in the family's own words (_count_moe reads them).
        self._moe_prefill = (model.prefill_stats(config)
                             if self._counted else ())
        # Decode is bandwidth-bound and pays a fixed cost per
        # weight-matmul call: fuse the column-parallel projection pairs
        # (wq|wk|wv, w_gate|w_up) into single wider matmuls
        # (models/llama.fuse_params — exact, works on bf16 and int8).
        # Under a mesh the fused columns interleave as per-device blocks
        # and shard over tp (llama.fuse_tp_for), so TP serving keeps the
        # fused-matmul win too.
        if hasattr(model, "fuse_params"):
            from ..models.llama import fuse_tp_for
            params = model.fuse_params(params,
                                       tp=fuse_tp_for(config, mesh),
                                       mesh=mesh)
        self._params = params
        # Weight-stream accounting, stamped once at build: actual stored
        # bytes of the tree (int4 packed counts half a byte per logical
        # weight) and the quantization mode label — the /metrics
        # `model_weight_bytes{quant=}` gauge and the boot log's weight-GB
        # line. Decode streams ~all of it per step, so this is the
        # bandwidth denominator for the step-time roofline.
        from ..models.quant import param_bytes, quant_mode
        self._weight_bytes = param_bytes(params)
        self._quant_mode = quant_mode(params)
        log.info("model weights: %.3f GB (%s)",
                 self._weight_bytes / 1e9, self._quant_mode or "bf16")
        if config.ut_steps > 1:
            log.info("looped model %s: %d layers walked %d times a token "
                     "with one set of weights, %d cache layers, the final "
                     "norm and the exit gate after every pass (the last "
                     "pass's logits, always: serve_loop_exit_mass_total "
                     "counts what the gate says)", config.name,
                     config.num_layers, config.ut_steps,
                     config.cache_layers)
        if config.is_moe:
            log.info("routed model %s: %d experts, top-%d %s; prefill "
                     "capacity factor %s; QK-norm %s; what the buckets "
                     "drop is counted: serve_moe_assignments_total, "
                     "serve_moe_dropped_total", config.name,
                     config.num_experts, config.num_experts_per_tok,
                     "renormalised" if config.moe_renormalize
                     else "with the softmax's own weights",
                     config.moe_capacity_factor or "none (dropless)",
                     "over the whole projection" if config.qk_norm_whole
                     else "none")
            if config.router_width > config.num_experts:
                log.info("%s holds %d of the %d experts its router scores "
                         "(%s scores, scaling %.2f), %d shared, %d leading "
                         "dense layer(s); pairs routed elsewhere are not "
                         "computed here: serve_moe_routed_pairs_total, "
                         "serve_moe_local_pairs_total", config.name,
                         config.num_experts, config.router_width,
                         config.moe_scoring, config.routed_scaling_factor,
                         config.num_shared_experts, config.first_k_dense)
        # Whether Mamba-2's decode step is the kernel that moves live
        # rows' state only (ops/state_pool.decode_update's own predicate,
        # read once: the traced programs bake it in): what the state
        # counters below count a step's rows by.
        self._state_kernel = (config.ssm_layers > 0
                              and not config.mamba1_inner
                              and ssm_kernel_covers(config.ssm_state_shape))
        self._log_kernels()

        self._slots: list[Optional[_Slot]] = [None] * num_slots  # owned-by: _loop
        self._waiting: list[_Slot] = []  # owned-by: _loop — admitted later, no pages yet
        self._stop_ids = set(config.eos_token_ids)
        eos = getattr(tokenizer, "eos_id", None)
        if eos is not None and 0 <= eos < config.vocab_size:
            self._stop_ids.add(eos)

        self._reset_device_state()
        self._log_pool()

        self._admit_q: "queue.Queue[Optional[_Slot]]" = queue.Queue()
        self._admit_carry: list[_Slot] = []  # owned-by: _loop — prepared chunks awaiting rows
        self._closed = threading.Event()
        # Serving-plane counters (SURVEY.md §5 metrics plan: queue depth,
        # batch occupancy, decode ticks). Plain ints written only by the
        # scheduler thread; snapshotted by metrics_snapshot().
        self._n_admitted = 0          # owned-by: _loop
        self._n_decode_ticks = 0      # owned-by: _loop
        self._n_expired = 0           # owned-by: _loop
        self._n_spec_accepted = 0     # owned-by: _loop — draft tokens accepted by verify
        # Counts at the dispatch sites (owned-by: _loop), so that ratios
        # are measured where the work happens: admission dispatches
        # started and the rows their programs were wide (R, dummy
        # entries included), prompt positions that had to be computed
        # against the positions the padded programs computed, live rows
        # x steps per decode dispatch. (The decode dispatch intervals
        # are the ledger's, below.)
        self._n_admit_batches = 0
        self._n_admit_uploads = 0
        # Dispatches, single-shot or chunk, that carried more than one
        # request (over batches + chunks: how often a dispatch is shared).
        self._n_admit_pair_dispatches = 0     # owned-by: _loop
        self._n_admit_rows_padded = 0
        self._n_prefill_tokens = 0
        self._n_prefill_padded = 0
        # (prompt position, context position) pairs the computed prompt
        # positions attend causally: position i of a prompt sees i + 1.
        self._n_prefill_pairs = 0
        # A routed model's prefill programs (admission at every width,
        # the chunk ladder, prefix builds): routed (token, expert) pairs
        # of real prompt positions, and those that found their capacity
        # bucket full. Decode and wake buckets are exact and add nothing.
        self._n_moe_assigned = 0
        self._n_moe_dropped = 0
        # Rows the experts' matmuls of the dropless prefills ran over
        # (mixtral.no_stats): over the assignments, the layout's padding.
        self._n_moe_prefill_rows = 0     # owned-by: _loop
        # Of the experts a decode step could have streamed (layers x
        # experts, each step of each dispatch), those a live row reached:
        # the others' weights were not read (ops/quant_mm.py).
        self._n_moe_decode_touched = 0   # owned-by: _loop
        self._n_moe_decode_slots = 0     # owned-by: _loop
        # A model that holds a share of the experts its router scores
        # (ModelConfig.moe_router_width): (token, expert) pairs routed,
        # prefill's real prompt positions and decode's live rows, and
        # those of them routed to an expert held here.
        self._n_moe_routed_pairs = 0     # owned-by: _loop
        self._n_moe_local_pairs = 0      # owned-by: _loop
        # The same model's prefill dispatches that carried a request
        # (an admission, each chunk of its ladder, a prefix build), a
        # routed layer each: how many there were. What each multiplied
        # is _n_moe_prefill_rows, beside _n_moe_local_pairs: its
        # prefills go onto tiles too (models/pangu._routed_local).
        self._n_moe_prefill_layers = 0       # owned-by: _loop
        # Cache rows the decode steps' live rows attended (the sum, over
        # row-steps, of the row's context length at that step).
        self._n_attn_ctx_tokens = 0      # owned-by: _loop
        # Decode dispatches whose window runs the flash-append kernel:
        # the (row, chunk) programs of its grid, a step, and those of
        # them whose chunk starts inside its row's context — the ones
        # the kernel fetches and folds (_note_attn_chunks).
        self._n_attn_chunks = 0          # owned-by: _loop
        self._n_attn_chunks_walked = 0   # owned-by: _loop
        # Recurrent state (a hybrid model's ops/state_pool.py): rows whose
        # state the decode dispatches moved (the live ones where Mamba-2's
        # kernel is the update; every slot's, each step, where XLA's one
        # program over the pool's rows is), those of them live, the bytes
        # that is (read and written), and the prefix hits that started
        # from an entry's snapshot.
        self._n_state_row_steps = 0      # owned-by: _loop
        self._n_state_row_steps_live = 0  # owned-by: _loop
        self._n_state_bytes = 0          # owned-by: _loop
        self._n_state_snapshots = 0      # owned-by: _loop
        # Window rings and a page layer that other layers read too (the
        # SambaY kinds of models/nemotron_h.py), as bytes the decode
        # dispatches had to read: live rows x window layers x the
        # positions a row's ring holds (its length, at most the window),
        # and live rows x context x the layers that read the one pool.
        self._n_window_bytes = 0         # owned-by: _loop
        self._n_shared_kv_bytes = 0      # owned-by: _loop
        self._shared_kv_readers = (
            config.hybrid_pattern.count("*")
            + config.hybrid_pattern.count("x")
            if "x" in config.hybrid_pattern else 0)
        # Page layers that are fewer than the model's layers, each read
        # by its own layer alone (the others keep rings, convolution
        # windows or recurrent state and no pages): live rows x context
        # x bytes a token x those layers.
        self._n_page_kv_bytes = 0        # owned-by: _loop
        # An indexed model (models/nemotron_h.py's ``s``): the index keys
        # a decode dispatch scores (live rows x context x bytes a key x
        # layers x fused steps), and per row-layer-step the positions its
        # attention attends under the selection's mask beside the
        # positions in context (the masked walk READS all of them).
        self._n_index_kv_bytes = 0       # owned-by: _loop
        self._n_sparse_selected = 0      # owned-by: _loop
        self._n_sparse_context = 0       # owned-by: _loop
        self._page_kv_layers = (
            config.cache_layers
            if (config.is_hybrid and not self._shared_kv_readers)
            or config.ut_steps > 1 else 0)
        # A looped stack (ModelConfig.ut_steps > 1): passes over the
        # stack the dispatches asked for (ut_steps a decode step, and a
        # prefill dispatch that computes something), the weight bytes the
        # decode steps' passes re-read (host arithmetic: passes x the
        # stack's stored bytes), and the exit pdf summed over live rows a
        # pass, which rides behind a decode dispatch's tokens
        # (_with_moe).
        self._looped = config.ut_steps > 1
        self._stack_bytes = (param_bytes(params["layers"])
                             if self._looped else 0)
        self._n_loop_passes = 0          # owned-by: _loop
        self._n_loop_weight_bytes = 0    # owned-by: _loop
        self._loop_exit_mass = [0.0] * config.ut_steps   # owned-by: _loop
        # Iterations in which a request waited for pages (_waiting) while
        # a row stood free: the pool, not the rows, held the batch.
        self._n_page_starved_iters = 0   # owned-by: _loop
        self._moe_unread: collections.deque = collections.deque()
        self._n_decode_row_steps = 0
        self._n_decode_sort_dispatches = 0
        # Shared-prefix KV cache (serve/prefix.py): prompt-head matches
        # skip recomputing the prefix at admission. Ladder grains that
        # could never pass the admission budget guard (P + smallest
        # suffix bucket > max_seq) are excluded up front — otherwise
        # snap/observe would build entries (HBM + an LRU slot each) that
        # every match rejects.
        if prefix_cache:
            from .prefix import DEFAULT_GRAIN_LADDER
            ladder = tuple(g for g in DEFAULT_GRAIN_LADDER
                           if g + _MIN_BUCKET <= self.max_seq)
            # SERVE_PREFIX_MB > 0 switches eviction to the byte-budget
            # cost policy (bytes x recency, shared with the session
            # tier); the count cap then relaxes to a sanity bound —
            # entry count stops standing in for entry size. 0 keeps the
            # legacy count-capped LRU.
            mb = env_float("SERVE_PREFIX_MB", 0.0)
            self._prefix = (PrefixStore(grain_ladder=ladder,
                                        promote_after=prefix_promote_after,
                                        max_bytes=int(mb * 1e6),
                                        max_entries=64 if mb > 0 else 8)
                            if ladder else None)
        else:
            self._prefix = None
        self._n_prefix_admits = 0     # owned-by: _loop — requests admitted via a cached prefix
        self._n_prefix_tokens = 0     # owned-by: _loop — prompt tokens NOT recomputed
        self._promote_q: list[tuple] = []  # owned-by: _loop — heads awaiting a build slot
        self._last_promote_tick = 0   # owned-by: _loop
        # Off-thread promotion builds: the build's jit compile + prefill
        # read only the (immutable) params, so a worker thread computes
        # the prefix KV while live ticks keep flowing; the scheduler
        # thread remains the only WRITER of the store (it integrates
        # finished builds from _promote_done each loop iteration).
        # Measured before: an identical-prompt burst promoted its head
        # mid-burst and the on-thread compile stalled every in-flight
        # stream ~5 s.
        self._promote_work: "queue.Queue[Optional[tuple]]" = queue.Queue()
        self._promote_done: "queue.Queue[tuple]" = queue.Queue()
        self._promote_pending: set = set()  # owned-by: _loop — submitted, not yet integrated
        self._promote_worker: Optional[threading.Thread] = None
        # Round 18: the promotion worker ALSO ahead-of-time compiles
        # (lower + compile, never execute) every admission program the
        # new prefix will serve through — the splice jits donate the
        # live cache/sampling buffers, so the worker can never RUN them,
        # but AOT compilation touches only shapes. The executables merge
        # into these loop-owned tables in _drain_promotions, BEFORE the
        # entry goes live; _admit_chunk/_dispatch_prefill_chunk consult
        # them ahead of the lazily-compiling jit wrappers. Measured
        # before: the first prefix-hit admission after a mid-traffic
        # promotion compiled its (P, S, R) splice ON the scheduler
        # thread — a multi-second decode_stall_ms spike for every
        # in-flight stream (the grain pre-warm only covers the smallest
        # suffix bucket).
        self._admit_prefix_aot: dict[tuple, object] = {}   # owned-by: _loop — (P,S,R) -> Compiled
        self._prefill_chunk_aot: dict[tuple, object] = {}  # owned-by: _loop — (P0,S,off,C,R) -> Compiled
        self._params_struct = None    # lazy jax.ShapeDtypeStruct tree of params
        # Fused multi-step decode state (tentpole of the wall/device-gap
        # work): the ramp remembers the last dispatched K, the counters
        # feed /metrics (decode_fused_* — realized K is steps/dispatches),
        # and the wall histogram samples steady-state per-step wall time.
        if decode_fuse_max < 1:
            raise ValueError(
                f"decode_fuse_max must be >= 1, got {decode_fuse_max}")
        self.decode_fuse_max = decode_fuse_max
        self._fuse_ramp = 1           # owned-by: _loop
        self._n_fused_ticks = 0       # owned-by: _loop — dispatches with K > 1
        self._n_fused_steps = 0       # owned-by: _loop — decode steps inside fused dispatches
        self._n_decode_steps = 0      # owned-by: _loop — decode steps across plain dispatches
        self._n_spec_ticks = 0        # owned-by: _loop — speculative dispatches (no K;
                                      # they must not dilute the realized mean)
        # Every decode dispatch-to-dispatch interval, booked to what cut
        # into it (obs/intervals.py): the admission sites note their
        # class (cut), the two token-emitting dispatches close the
        # interval (note). decode_stall_ms, decode_wall_ms and the
        # serve_decode_{clean,cut_*}_* counters are its outputs.
        self._ledger = IntervalLedger()   # owned-by: _loop
        self._decode_device_ms = 0.0  # measured once at warmup (probe)
        # Chunked prefill (tentpole of the admission-stall work): prompts
        # whose bucket exceeds this budget admit in fixed chunks the loop
        # interleaves with decode ticks. Power-of-two snapped so the
        # chunk ladder divides every power-of-two bucket; the TOP bucket
        # is capped at max_seq, which need not be a multiple — that
        # bucket falls back to single-shot admission (the S % C gates at
        # _admit_steps and the chunked-admission branch), because a
        # ladder whose offsets step past S would never hit its final
        # chunk.
        if prefill_chunk < 0:
            raise ValueError(
                f"prefill_chunk must be >= 0, got {prefill_chunk}")
        self.prefill_chunk = (_bucket(prefill_chunk, self.max_seq)
                              if prefill_chunk else 0)
        self._prefill_carry: Optional[_PrefillCarry] = None  # owned-by: _loop
        self._n_prefill_chunks = 0    # owned-by: _loop — chunk dispatches
        # ... of which those past every row's suffix: the chunk programs
        # compute nothing there (_make_prefill_chunk_program._fwd).
        self._n_prefill_chunks_padded = 0     # owned-by: _loop
        # reset_decode_stall handshake: req set by the caller, serviced
        # (and ack'd) by _loop at the top of every iteration.
        self._stall_reset_req = threading.Event()
        self._stall_reset_ack = threading.Event()
        # park_all handshake (live session migration, serve/router.py):
        # same event discipline as the stall reset — the request is set
        # by an HTTP thread, serviced by _loop (which owns the device
        # buffers the park gathers copy), ack'd when every resident
        # session (or the one named by _park_all_key) sits in host RAM
        # and is exportable. Single-caller discipline, like the stall
        # reset: the key is written before the event sets, read after
        # it clears (the Event publishes it).
        self._park_all_req = threading.Event()
        self._park_all_ack = threading.Event()
        self._park_all_key: Optional[str] = None
        from ..utils.metrics import Histogram
        self._tbt_hist = Histogram("inter_token_ms")
        # Multi-tier KV (serve/kv_tier.py): host-RAM session parking.
        # All tier state transitions run on the scheduler thread (they
        # copy device buffers only it may touch); the KVTier index
        # itself is locked for /metrics readers.
        self._tier = None
        if kv_host_gb and kv_host_gb > 0:
            from .kv_tier import KVTier
            self._tier = KVTier(kv_host_gb * 1e9, idle_s=kv_idle_s)
            self._tier.observer = self._tier_event
            log.info("KV tiering on: %.2f GB host budget, idle park "
                     "after %.1fs", kv_host_gb, kv_idle_s)
        self._wake_hist = Histogram("kv_wake_ms")
        self._last_tier_sweep = 0.0   # owned-by: _loop
        # Wake/cold admission fairness: set after a contended round
        # dispatched a wake ahead of carried cold work — the NEXT
        # contended round lets the cold chunk go first (a sustained
        # wake stream must not starve cold admissions to their queue
        # deadline).
        self._wake_rr_cold = False    # owned-by: _loop
        # Draft sources, priority order: n-gram prompt-lookup first (it
        # is ~free when it hits), the resident draft model filling in on
        # misses. The model drafter must match the target's batch
        # geometry and vocabulary — draft ids feed the verify forward
        # directly, so a vocab mismatch would silently verify garbage.
        self._draft_model = drafter if spec_k else None
        if self._draft_model is not None:
            d = self._draft_model
            if d.config.vocab_size != config.vocab_size:
                raise ValueError(
                    f"drafter vocab {d.config.vocab_size} != target "
                    f"vocab {config.vocab_size}: a draft model must "
                    "share its target's vocabulary")
            if d.num_slots != num_slots or d.max_seq < self.max_seq:
                raise ValueError(
                    f"drafter geometry (slots={d.num_slots}, "
                    f"max_seq={d.max_seq}) does not cover the target's "
                    f"(slots={num_slots}, max_seq={self.max_seq})")
            if d.k != spec_k:
                raise ValueError(
                    f"drafter k={d.k} != spec_k={spec_k}")
        # Tree-speculation budget normalization: pow2-snap up, then
        # require at least one sibling slot past root + main chain —
        # a tree with no branch budget is the linear program with
        # extra mask plumbing, so it degrades to 0 (linear path).
        nodes = int(spec_tree_nodes or 0)
        if nodes > 0 and spec_k > 0:
            snapped = 1 << max(0, nodes - 1).bit_length()
            if snapped != nodes:
                log.info("spec_tree_nodes %d snapped to %d", nodes, snapped)
            nodes = snapped
            if nodes < spec_k + 2:
                log.info("spec_tree_nodes %d < spec_k+2 (%d): no sibling "
                         "budget — tree speculation off (linear spec)",
                         nodes, spec_k + 2)
                nodes = 0
        else:
            nodes = 0
        self.spec_tree_nodes = nodes
        self.spec_tree_gap = float(spec_tree_gap)
        self._tree_base_np: Optional[tuple] = None   # owned-by: _loop
        # Tree-speculation counters (owned-by: _loop): total tree nodes
        # verified, drafted rows per tree dispatch, and accepted tokens
        # on tree ticks — /metrics serve_spec_tree_* series.
        self._n_spec_tree_nodes = 0
        self._n_spec_tree_rows = 0
        self._n_spec_tree_accepted = 0
        # Per-source verify-dispatch counts (ticks where that source
        # drafted >= 1 row) — the accepted-tokens-per-verify-dispatch
        # denominator. owned-by: _loop.
        self._n_spec_dispatch_src: dict[str, int] = {}
        # Adaptive speculation: PER-SOURCE EMA of accepted drafts per
        # spec tick. The verify forward computes K+1 positions for every
        # row, so when a source's drafts stop landing, paying its
        # proposal cost (and the verify it triggers) every tick is pure
        # loss — below the floor, that source only probes every
        # _SPEC_PROBE_EVERY ticks until acceptance recovers. Seeds are
        # mildly optimistic (2x floor) and zero-acceptance ticks decay
        # fast — see _SPEC_EMA_SEED. Sources late-init via
        # _ensure_sources so a spec_k toggled 0 -> K at runtime (the
        # attribute is runtime-togglable) still gets n-gram
        # speculation, like the pre-round-9 per-slot drafters did.
        self._sources: list = []               # owned-by: _loop (state inside)
        self._spec_ema: dict[str, float] = {}  # owned-by: _loop
        self._spec_cooldown: dict[str, int] = {}   # owned-by: _loop
        # Per-source proposed/accepted draft-token counters (/metrics
        # spec_draft_source observability).
        self._n_spec_proposed_src: dict[str, int] = {}  # owned-by: _loop
        self._n_spec_accepted_src: dict[str, int] = {}  # owned-by: _loop
        self._ensure_sources()

        # Jitted programs. decode is compiled once; admit once per
        # (chunk-rows, prompt-bucket) shape pair — both power-of-two
        # bucketed, so the compile cache stays small. A program takes
        # the name of the function it jits, and that name is a contract:
        # it begins with the program's kind (prefill_, decode_, spec_,
        # kv_), which is what a reader of a device trace sums by
        # (`XLA Modules` events read jit_prefill_..., jit_decode_...;
        # tests/test_loop_phases.py holds every jit site to it).
        routed = self._counted

        def _with_moe(toks, moe):
            """A dispatch's tokens with a routed model's count behind
            them (``moe`` int32 [2], None for a dense model), so that
            the one array the host already reads carries both: the
            prefill's drop count behind an admission's first tokens
            ([R] -> [R + 2]), the experts a decode dispatch touched
            behind its tokens ([B] or [K, B] -> [K * B + 2])."""
            if moe is None:
                return toks
            if moe.dtype != jnp.int32:
                # A looped model's exit mass, float32 [ut_steps]: its
                # bits, which _process_tick reads back as they are.
                moe = jax.lax.bitcast_convert_type(moe, jnp.int32)
            return jnp.concatenate([toks.reshape(-1), moe])

        def _make_decode(kv_window: int):
            def decode_step(params, tokens, cache, active, temps, top_ks,
                            top_ps, keys, ring, rps):
                # The emitted token's context position is lengths+1 (the
                # INPUT token occupies lengths) — writing at lengths would
                # clobber the previous tick's emission in the ring.
                emit_pos = cache.lengths + 1
                pages = -(-kv_window // self.page_size)
                moe = None
                if routed:
                    # A routed model's step also counts the experts its
                    # live rows reached (models/mixtral.py).
                    logits, cache, moe = model.decode_step_paged_touched(
                        params, config, tokens, cache, mesh, active=active,
                        pages=pages)
                elif self._looped:
                    # A looped model's step also sums its exit pdf over
                    # the live rows (models/llama.py).
                    logits, cache, moe = model.decode_step_paged_exit(
                        params, config, tokens, cache, mesh, active=active,
                        pages=pages)
                else:
                    logits, cache = model.decode_step_paged(
                        params, config, tokens, cache, mesh, active=active,
                        pages=pages)
                # Shared sample + penalty-ring step (parked rows' ring
                # writes drop) — the ONE implementation the fused path's
                # scan body also runs, so fused-K output stays
                # bit-identical to K plain ticks.
                toks, keys, ring = sample_step_batched(
                    logits[:, 0, :], keys, temps, top_ks, top_ps, ring=ring,
                    rp=rps, emit_pos=emit_pos, active=active)
                # Parked rows keep their previous input token so their
                # (ignored) next step stays stable regardless of their
                # garbage sample.
                next_tokens = jnp.where(active[:, None], toks[:, None], tokens)
                return _with_moe(toks, moe), next_tokens, cache, keys, ring
            return jax.jit(decode_step, donate_argnums=(1, 2, 7, 8))

        self._make_decode = _make_decode
        self._decode_programs: dict[int, object] = {}

        def _make_decode_fused(kv_window: int, K: int):
            """Fused K-step decode program (models/llama.decode_fused):
            one dispatch runs K scan steps, each the exact plain-step
            computation — decode + on-device sampling + ring update —
            carrying cache/next-token/keys/ring/active on device. EOS
            parks rows mid-scan (see decode_fused). Readback shrinks to
            K*B int32 per K tokens instead of K round-trips — the
            host-dispatch share of the decode tick amortises by K."""
            # graftcheck: sync-ok host-side constant, not a device readback
            stop_ids = np.asarray(sorted(self._stop_ids), np.int32)

            def decode_fused_steps(params, tokens, cache, active, temps,
                                   top_ks, top_ps, keys, ring, rps):
                def sample_fn(logits, state, emit_pos, act):
                    keys, ring = state
                    toks, keys, ring = sample_step_batched(
                        logits, keys, temps, top_ks, top_ps, ring=ring,
                        rp=rps, emit_pos=emit_pos, active=act)
                    return toks, (keys, ring)

                kwargs: dict = dict(num_steps=K, sample_fn=sample_fn,
                                    sample_state=(keys, ring),
                                    stop_ids=stop_ids, active=active,
                                    pages=-(-kv_window // self.page_size))
                moe = None
                if routed:
                    (toks_all, _, next_tokens, cache, _, (keys, ring),
                     moe) = model.decode_fused_touched(
                        params, config, tokens, cache, mesh, **kwargs)
                elif self._looped:
                    (toks_all, _, next_tokens, cache, _, (keys, ring),
                     moe) = model.decode_fused_exit(
                        params, config, tokens, cache, mesh, **kwargs)
                else:
                    (toks_all, _, next_tokens, cache, _,
                     (keys, ring)) = model.decode_fused(
                        params, config, tokens, cache, mesh, **kwargs)
                return (_with_moe(toks_all, moe), next_tokens, cache, keys,
                        ring)
            return jax.jit(decode_fused_steps, donate_argnums=(1, 2, 7, 8))

        self._make_decode_fused = _make_decode_fused
        self._decode_fused_programs: dict[tuple[int, int], object] = {}

        def _make_spec(kv_window: int):
            """Speculative tick: one verify forward over [cur, draft_0..,
            draft_{K-1}] per row + exact acceptance + length advance, all
            fused. Host reads back 2×B int32 (accepted, correction)."""
            from ..models.sampling import spec_verify_batched

            def spec_verify(params, tokens, drafts, max_acc, cache, active,
                            temps, top_ks, top_ps, keys, ring, rps):
                K = tokens.shape[1] - 1
                lengths_pre = cache.lengths
                S = tokens.shape[1]
                pages = min(-(-(kv_window + S) // self.page_size),
                            cache.max_pages_per_row)
                logits, cache = model.verify_step_paged(
                    params, config, tokens, cache, mesh, pages=pages)
                accepted, correction, keys = spec_verify_batched(
                    logits.astype(jnp.float32), drafts, keys, temps,
                    top_ks, top_ps, max_acc, ring=ring, rp=rps,
                    ctx_len=lengths_pre)
                inc = jnp.where(active, accepted + 1, 0)
                cache = cache._replace(
                    lengths=cache.lengths + inc.astype(cache.lengths.dtype))
                # Emitted tokens (accepted drafts + correction) enter the
                # penalty ring at their context positions; the rest drop.
                B = accepted.shape[0]
                # emitted[i] is the token AFTER input i -> context
                # position lengths_pre + i + 1.
                pos = (lengths_pre[:, None] + 1 + jnp.arange(K + 1)) % _RING
                emit_ok = ((jnp.arange(K + 1)[None, :] <= accepted[:, None])
                           & active[:, None])
                idx = jnp.where(emit_ok, pos, _RING)
                emitted = jnp.where(
                    jnp.arange(K + 1)[None, :] < accepted[:, None],
                    jnp.concatenate([drafts,
                                     jnp.zeros((B, 1), jnp.int32)], axis=1),
                    correction[:, None])
                ring = ring.at[jnp.arange(B)[:, None], idx].set(
                    emitted, mode="drop")
                next_tokens = jnp.where(active[:, None],
                                        correction[:, None], tokens[:, :1])
                return accepted, correction, next_tokens, cache, keys, ring
            return jax.jit(spec_verify, donate_argnums=(4, 9, 10))

        self._make_spec = _make_spec
        self._spec_programs: dict[int, object] = {}

        def _make_spec_tree(kv_window: int):
            """Tree-speculation tick: ONE verify forward over the [B,N]
            node tree (tree-topology mask, per-node depths for RoPE),
            exact tree acceptance, sibling-kv compaction, and length
            advance, all fused. Host reads back 3×B int32 (accepted,
            used_sib, correction)."""
            from ..models.sampling import spec_verify_tree

            def spec_tree_verify(params, tokens, depths, anc, drafts,
                                 sib_tok, sib_node, max_acc, cache, active,
                                 temps, top_ks, top_ps, keys, ring, rps):
                B, N = tokens.shape
                K = drafts.shape[1]
                lengths_pre = cache.lengths
                pages = min(-(-(kv_window + N) // self.page_size),
                            cache.max_pages_per_row)
                logits, cache = model.verify_tree_paged(
                    params, config, tokens, depths, anc, cache, mesh,
                    pages=pages)
                accepted, used_sib, correction, keys = spec_verify_tree(
                    logits.astype(jnp.float32), drafts, sib_tok,
                    sib_node, keys, temps, top_ks, top_ps, max_acc,
                    ring=ring, rp=rps, ctx_len=lengths_pre)
                # Sibling kv compaction: an accepted sibling's kv lives
                # at its NODE slot (lengths + sib_node); move it onto
                # the accepted-path slot (lengths + accepted, i.e. the
                # slot right after the accepted main prefix) BEFORE
                # lengths advance over it. Rows that used no sibling
                # self-copy harmlessly (src == dst). The sibling node
                # index is always > accepted, so the vacated slot stays
                # stale-beyond-length — rejected-branch containment.
                sel = jnp.clip(accepted - 1, 0, K - 1)[:, None]
                sn = jnp.take_along_axis(sib_node, sel, axis=1)[:, 0]
                st = jnp.take_along_axis(sib_tok, sel, axis=1)[:, 0]
                move = active & (used_sib > 0)
                dst = lengths_pre + accepted
                src = jnp.where(move, lengths_pre + sn, dst)
                cache = copy_slot(cache, src, dst)
                inc = jnp.where(active, accepted + 1, 0)
                cache = cache._replace(
                    lengths=cache.lengths
                    + inc.astype(cache.lengths.dtype))
                # Emitted tokens enter the penalty ring at their context
                # positions — the linear tick's rule, except a used
                # sibling replaces the main draft at the rejected
                # position (index accepted-1).
                ar = jnp.arange(K + 1)[None, :]
                pos = (lengths_pre[:, None] + 1 + ar) % _RING
                emit_ok = (ar <= accepted[:, None]) & active[:, None]
                idx = jnp.where(emit_ok, pos, _RING)
                emitted = jnp.where(
                    ar < accepted[:, None],
                    jnp.concatenate(
                        [drafts, jnp.zeros((B, 1), jnp.int32)], axis=1),
                    correction[:, None])
                emitted = jnp.where(
                    (used_sib > 0)[:, None]
                    & (ar == (accepted - 1)[:, None]),
                    st[:, None], emitted)
                ring = ring.at[jnp.arange(B)[:, None], idx].set(
                    emitted, mode="drop")
                next_tokens = jnp.where(active[:, None],
                                        correction[:, None],
                                        tokens[:, :1])
                return (accepted, used_sib, correction, next_tokens,
                        cache, keys, ring)
            return jax.jit(spec_tree_verify, donate_argnums=(8, 13, 14))

        self._make_spec_tree = _make_spec_tree
        self._spec_tree_programs: dict[int, object] = {}

        def _make_wake(kv_window: int, S: int):
            """Session-wake admission program (multi-tier KV): ONE fused
            dispatch re-opens waking sessions — install each waking
            row's page table and length ATOMICALLY (the chunked-
            admission splice discipline: a half-woken row never looks
            live), run the suffix tokens through a verify-shaped
            multi-position forward that attends the session's existing
            pool KV at its DYNAMIC length (the decisive difference from
            the prefix-cache programs, which bake the prefix length into
            the compiled shape — sessions have arbitrary, growing
            lengths, so they must be data, not shape), sample each
            waking row's first token from its last suffix position, and
            install the sampling state. Non-waking rows (mask off) pass
            every buffer through unchanged; their verify writes land
            beyond their trusted lengths or in the garbage page — the
            overwrite-before-trust invariant, same as a spec tick.

            ``packed`` is the admission buffer (_admit_layout) at R = B:
            tokens [B,S] right-padded suffixes; ints[:4] = suffix
            lens (0 = not waking) / session lengths / seeds / top_k;
            floats [3,B] = temp/top_p/repeat_penalty; rings [B,_RING]
            prompt-tail penalty windows; tables [B,mppr] = each waking
            row's FULL page map (the session's kept pages plus
            freshly-allocated growth pages)."""
            def kv_wake(params, packed, cache, keys, next_tokens, temps,
                        top_ks, top_ps, ring, rps):
                tokens, ints, floats, rings, tables = _admit_unpack(
                    packed, cache.max_pages_per_row)
                suf, start = ints[0], ints[1]
                mask = suf > 0
                lengths = jnp.where(mask, start, cache.lengths).astype(
                    cache.lengths.dtype)
                table = jnp.where(mask[:, None], tables.astype(jnp.int32),
                                  cache.page_table)
                cache = cache._replace(page_table=table, lengths=lengths)
                pages = min(-(-(kv_window + S) // self.page_size),
                            cache.max_pages_per_row)
                logits, cache = model.verify_step_paged(
                    params, config, tokens, cache, mesh, pages=pages,
                    last_idx=jnp.clip(suf - 1, 0, S - 1))
                inc = jnp.where(mask, suf, 0)
                cache = cache._replace(
                    lengths=cache.lengths + inc.astype(cache.lengths.dtype))
                B = tokens.shape[0]
                last = logits[:, 0, :]                           # [B,V]
                row_keys = jax.vmap(jax.random.PRNGKey)(ints[2])
                toks, row_keys = sample_batched(last, row_keys, floats[0],
                                                ints[3], floats[1],
                                                ring=rings, rp=floats[2])
                rings2 = rings.at[jnp.arange(B),
                                  (start + suf) % _RING].set(toks)
                m1 = mask[:, None]
                keys = jnp.where(m1, row_keys, keys)
                next_tokens = jnp.where(m1, toks[:, None], next_tokens)
                temps = jnp.where(mask, floats[0], temps)
                top_ks = jnp.where(mask, ints[3], top_ks)
                top_ps = jnp.where(mask, floats[1], top_ps)
                ring = jnp.where(m1, rings2, ring)
                rps = jnp.where(mask, floats[2], rps)
                return (toks, cache, keys, next_tokens, temps, top_ks,
                        top_ps, ring, rps)
            return jax.jit(kv_wake,
                           donate_argnums=(2, 3, 4, 5, 6, 7, 8, 9))

        self._make_wake = _make_wake
        self._wake_programs: dict[tuple[int, int], object] = {}
        # (window, S) wake shapes that have EXECUTED (the jit wrappers
        # compile on first call — a live-stream wake through an unrun
        # shape would stall every stream for the compile, so unwarmed
        # shapes demote to cold admission instead; _chunk_shapes_run's
        # discipline).
        self._wake_shapes_run: set[tuple] = set()  # owned-by: _loop

        def _moe_valid(ints, off: int, width: int):
            """What a routed model's ``_counted`` prefill counts over
            (models/mixtral.py ``valid``): the real prompt positions of
            the ``width`` positions from suffix offset ``off`` — below
            the entry's (suffix) length, in an entry that carries a
            request (a dummy entry's row is the sentinel ``num_slots``).
            Also what decides, for every family, whether a ladder's
            chunk runs at all (``_make_prefill_chunk_program._fwd``)."""
            pos = off + jnp.arange(width)[None, :]
            real = (ints[1] < self.num_slots)[:, None]
            return (pos < ints[0][:, None]) & real

        def _prefill_first_token(params, tokens, ints, floats, rings):
            """Shared admission prologue: batched prefill of R prompts +
            each row's first sampled token.

            The arguments are the packed buffer's parts (_admit_unpack:
            ``ints`` rows 0-3 = lens/rows/seeds/top_k, ``floats`` [3,R] =
            temperature/top_p/repeat_penalty, ``rings`` [R,_RING] =
            prompt-tail penalty windows)."""
            R, S = tokens.shape
            lens, seeds = ints[0], ints[2]
            chunk_temps, chunk_tps = floats[0], floats[1]
            small = KVCache.create(config, R, S, dtype=self._dtype)
            # last_only: the full [R,S,V] logits would materialise an
            # R*S x vocab f32 temp (3.9 GB at 8B dims, 64x128 chunk) and
            # pay S x the lm_head FLOPs for positions nobody samples.
            moe = None
            if routed:
                logits, small, moe = model.prefill_counted(
                    params, config, tokens, lens, small,
                    _moe_valid(ints, 0, S), mesh, last_only=True)
            else:
                logits, small = model.prefill(params, config, tokens, lens,
                                              small, mesh, last_only=True)
            last = logits[:, 0, :]                                    # [R,V]
            row_keys = jax.vmap(jax.random.PRNGKey)(seeds)
            toks, row_keys = sample_batched(last, row_keys, chunk_temps,
                                            ints[3], chunk_tps,
                                            ring=rings, rp=floats[2])
            # The first token joins each row's penalty window at its
            # context position.
            rings = rings.at[jnp.arange(R), lens % _RING].set(toks)
            return small, toks, row_keys, rings, moe

        def _install_rows(rows, row_keys, toks, ints, floats, rings, keys,
                          next_tokens, temps, top_ks, top_ps, ring, rps):
            """Vectorized per-row sampling-state installs. Padding entries
            carry an out-of-range row sentinel (num_slots) and are dropped;
            real rows are unique, so the scatters are order-independent."""
            keys = keys.at[rows].set(row_keys, mode="drop")
            next_tokens = next_tokens.at[rows, 0].set(toks, mode="drop")
            temps = temps.at[rows].set(floats[0], mode="drop")
            top_ks = top_ks.at[rows].set(ints[3], mode="drop")
            top_ps = top_ps.at[rows].set(floats[1], mode="drop")
            ring = ring.at[rows].set(rings, mode="drop")
            rps = rps.at[rows].set(floats[2], mode="drop")
            return keys, next_tokens, temps, top_ks, top_ps, ring, rps

        def _put_state(cache, small, rows):
            """A hybrid model's recurrent state at the end of the rows'
            prompts (``small.state``) into their slots' rows of the
            pool, whole rows: a reused slot inherits nothing. Dummy
            entries (row sentinel ``num_slots``) write the garbage row.
            Nothing for a model whose past is its pages."""
            if small.state is None:
                return cache
            return cache._replace(state=write_rows(cache.state, small.state,
                                                   rows))

        def _seed_state(small, ps):
            """``small`` starting from a prefix entry's state snapshot
            ``ps`` (None for a model without recurrent state)."""
            if ps is None:
                return small
            if isinstance(ps, IndexedPast):
                # An indexed model's entry: its index keys behind the
                # prefix's K and V, its state snapshot where it has one.
                R, P = small.lengths.shape[0], ps.idx.shape[1]
                small = small._replace(idx=small.idx.at[:, :, :P].set(
                    jnp.broadcast_to(ps.idx[:, None],
                                     (ps.idx.shape[0], R) + ps.idx.shape[1:])))
                ps = ps.state
                if ps is None:
                    return small
            return small._replace(
                state=from_snapshot(ps, small.lengths.shape[0]))

        def prefill_admit_paged(params, packed, cache, keys, next_tokens,
                                temps, top_ks, top_ps, ring, rps):
            """Prefill R prompts together, splice each row's kv into the
            page pool, and sample each row's first token. R comes from a
            two-size ladder and S is the prompt bucket — two compiled
            programs per bucket. The chunk's kv goes through the rows'
            page maps in ONE scatter (ops/paged_kv.write_prefill_batch;
            R sequential scatters cost ~8x the TTFT). Padding entries
            carry an all-zero table (writes land in garbage page 0) and
            the out-of-range row sentinel ``num_slots`` (installs
            dropped). ``packed``: the admission's five host arrays in
            one buffer (_admit_layout)."""
            tokens, ints, floats, rings, tables = _admit_unpack(
                packed, cache.max_pages_per_row)
            lens, rows = ints[0], ints[1]
            small, toks, row_keys, rings, moe = _prefill_first_token(
                params, tokens, ints, floats, rings)
            cache = write_prefill_batch(cache, small.k, small.v, rows, lens,
                                        tables, small.idx)
            cache = _put_state(cache, small, rows)
            (keys, next_tokens, temps, top_ks, top_ps, ring,
             rps) = _install_rows(rows, row_keys, toks, ints, floats, rings,
                                  keys, next_tokens, temps, top_ks, top_ps,
                                  ring, rps)
            return (_with_moe(toks, moe), cache, keys, next_tokens, temps,
                    top_ks, top_ps, ring, rps)

        def _prefill_first_token_prefix(params, pk, pv, ps, tokens, ints,
                                        floats, rings):
            """Continuation-prefill admission prologue for prefix-cached
            prompts: the cached prefix KV ([L,P,Hkv,D], computed once by
            register_prefix) is broadcast into every chunk row's small
            cache, then ONLY the suffix tokens run the forward — at
            positions P..P+S with a P-offset causal mask (the same
            continuation shape the speculative verify path uses), so
            admission compute scales with the suffix, not the prompt.

            ``ints`` row 4 is read here and not by the plain prologue:
            [0]=suffix lens, [4]=total lens (prefix + suffix — the context length
            installed in the big cache and the penalty-ring position of
            the first sampled token)."""
            R, S = tokens.shape
            P = pk.shape[1]
            suf_lens, seeds, total_lens = ints[0], ints[2], ints[4]
            small = KVCache.create(config, R, P + S, dtype=self._dtype)
            k0 = jnp.broadcast_to(pk[:, None], (pk.shape[0], R) + pk.shape[1:])
            v0 = jnp.broadcast_to(pv[:, None], (pv.shape[0], R) + pv.shape[1:])
            small = small._replace(k=small.k.at[:, :, :P].set(k0),
                                   v=small.v.at[:, :, :P].set(v0))
            small = _seed_state(small, ps)
            positions = jnp.broadcast_to(P + jnp.arange(S)[None, :], (R, S))
            mask = causal_mask(S, P + S, P)
            moe = None
            if routed:
                logits, small, moe = model.forward_counted(
                    params, config, tokens, positions, small, mask,
                    _moe_valid(ints, 0, S), mesh, last_idx=suf_lens - 1)
            else:
                logits, small = model.forward(params, config, tokens,
                                              positions, small, mask, mesh,
                                              last_idx=suf_lens - 1)
            last = logits[:, 0, :]
            row_keys = jax.vmap(jax.random.PRNGKey)(seeds)
            toks, row_keys = sample_batched(last, row_keys, floats[0],
                                            ints[3], floats[1],
                                            ring=rings, rp=floats[2])
            rings = rings.at[jnp.arange(R), total_lens % _RING].set(toks)
            return small, toks, row_keys, rings, moe

        def prefill_admit_paged_prefix(params, pk, pv, ps, packed, cache,
                                       keys, next_tokens, temps, top_ks,
                                       top_ps, ring, rps):
            """prefill_admit_paged for a chunk sharing one cached prefix:
            the combined [prefix + suffix] KV (the small cache, P+S wide)
            splices into each row's own pages through the one-scatter
            batch path, and lengths = total (copy-based sharing — rows
            own their prefix copy, so release/containment invariants are
            untouched)."""
            tokens, ints, floats, rings, tables = _admit_unpack(
                packed, cache.max_pages_per_row)
            rows, total_lens = ints[1], ints[4]
            small, toks, row_keys, rings, moe = _prefill_first_token_prefix(
                params, pk, pv, ps, tokens, ints, floats, rings)
            cache = write_prefill_batch(cache, small.k, small.v, rows,
                                        total_lens, tables, small.idx)
            cache = _put_state(cache, small, rows)
            (keys, next_tokens, temps, top_ks, top_ps, ring,
             rps) = _install_rows(rows, row_keys, toks, ints, floats, rings,
                                  keys, next_tokens, temps, top_ks, top_ps,
                                  ring, rps)
            return (_with_moe(toks, moe), cache, keys, next_tokens, temps,
                    top_ks, top_ps, ring, rps)

        self._admit_j = jax.jit(prefill_admit_paged,
                                donate_argnums=(2, 3, 4, 5, 6, 7, 8, 9))
        self._admit_prefix_j = jax.jit(
            prefill_admit_paged_prefix,
            donate_argnums=(5, 6, 7, 8, 9, 10, 11, 12))

        def kv_zero_row(cache, row):
            return set_row_table(
                cache, row,
                jnp.zeros((cache.page_table.shape[1],), jnp.int32))

        # Row release: zero the table (writes re-route to the garbage
        # page) BEFORE its pages return to the allocator — a stale
        # parked row must never scatter into a re-allocated page.
        self._zero_row_j = jax.jit(kv_zero_row, donate_argnums=(0,))

        # Multi-tier KV copy programs: the park gather and wake scatter
        # move a session's raw pool words (int8 + head-major scales
        # included) in ONE dispatch each; jit re-specializes per padded
        # page-count bucket automatically (callers pad the page list to
        # a power of two so the compile cache stays small).
        # Wrapped only to carry the kind in the program's name.
        def kv_gather_pages(cache, pages):
            return gather_pages(cache, pages)

        def kv_scatter_pages(cache, pages, *payload):
            return scatter_pages(cache, pages, *payload)

        # graftcheck: nodonate park gather READS the live pool; the resident buffer must outlive the copy
        self._gather_pages_j = jax.jit(kv_gather_pages)
        self._scatter_pages_j = jax.jit(kv_scatter_pages,
                                        donate_argnums=(0,))

        def _make_prefill_chunk_program(P0: int, S: int, OFF: int, C: int):
            """ONE continuation-prefill chunk program of the chunked
            admission ladder (static key: prefix length P0, suffix
            bucket S, chunk offset OFF; width C = prefill_chunk, which
            divides S — a non-multiple bucket, i.e. the max_seq-capped
            top one, admits single-shot instead). Three shapes of one
            family:

            - first (OFF == 0) creates the device carry (small cache
              [L,R,P0+S] + [R,V] logits) and broadcasts the shared
              prefix into it;
            - every chunk runs the continuation forward
              (models/llama.prefill_chunk — full-width mask, the
              bit-identity rule), folds the rows whose LAST prompt
              position falls in this chunk into the carried logits, and
              splices the chunk's KV into the big cache incrementally
              (rows' live lengths/tables stay uninstalled, so
              half-prefilled rows never look live and parked-row
              garbage writes cannot touch the accumulating KV);
            - final (OFF + C == S) samples each row's first token from
              the carried logits (the exact _prefill_first_token tail)
              and installs lengths/tables/sampling state atomically.

            A row's live page_table row stays zeroed from release until
            the final install, so a parked row's garbage decode writes
            keep landing in page 0 meanwhile."""
            if S % C or not 0 <= OFF < S:
                raise ValueError(
                    f"chunk ladder must divide the bucket: S={S} C={C} "
                    f"OFF={OFF} (a non-multiple bucket admits single-shot)")
            first, final = OFF == 0, OFF + C == S
            W = P0 + S
            base = P0 + OFF

            def _parts(packed, cache):
                """The admission buffer's parts, ``tokens`` cut to this
                chunk's columns: every chunk of a ladder takes the one
                buffer its admission uploaded."""
                tokens, *rest = _admit_unpack(packed,
                                              cache.max_pages_per_row)
                return (tokens[:, OFF: OFF + C], *rest)

            def _fwd(params, tokens, ints, carry, logits_c):
                """The chunk's forward, folded into the carried logits.

                A ``mid`` or ``final`` chunk with no real position in
                any row computes nothing (``lax.cond``) and hands the
                carry and the carried logits back as it took them. A
                ladder runs every chunk of its power-of-two bucket, so
                a 9 K prompt brings six such chunks of sixteen, and they
                lie at the ladder's highest offsets, where a page
                layer's attention is longest. Its queries are padding,
                what they would write behind the prompt is read by no
                real query, a decode step overwrites a slot before it
                trusts it, every recurrent or windowed layer moves its
                state by the chunk's count of ``valid`` positions (none
                here), and the logits fold in only for rows whose last
                position lies in the chunk.

                Padding is padding in every family, so the test is the
                scheduler's own arithmetic over the admission buffer
                (:func:`_moe_valid`) and no model's: a chunk with one
                real position runs the program it always ran. The
                first chunk always holds one and takes no ``cond``."""
                valid = _moe_valid(ints, OFF, C)

                def run(carry, logits_c):
                    # A routed model's carried logits travel with its
                    # drop count so far: (logits [R,V], stats [2]).
                    suf_lens = ints[0]
                    local_last = suf_lens - 1 - OFF
                    last_idx = jnp.clip(local_last, 0, C - 1)
                    if routed:
                        logits_c, moe_c = logits_c
                        logits, carry, moe = model.prefill_chunk_counted(
                            params, config, tokens, carry, base, valid,
                            mesh, last_idx=last_idx)
                    else:
                        logits, carry = model.prefill_chunk(
                            params, config, tokens, carry, base, mesh,
                            last_idx=last_idx)
                    keep = (local_last >= 0) & (local_last < C)
                    logits_c = jnp.where(keep[:, None], logits[:, 0, :],
                                         logits_c)
                    if routed:
                        logits_c = (logits_c, moe_c + moe)
                    return carry, logits_c

                if first:
                    return run(carry, logits_c)
                return _unless_padding(jnp.any(valid), run, carry, logits_c)

            def _splice(cache, carry, ints, tables):
                rows = ints[1]
                lo = 0 if first else base   # first chunk carries the prefix
                cache = write_prefill_chunk(
                    cache, carry.k[:, :, lo: base + C],
                    carry.v[:, :, lo: base + C], tables, lo,
                    None if carry.idx is None
                    else carry.idx[:, :, lo: base + C])
                if final:
                    table = cache.page_table.at[rows].set(
                        tables.astype(jnp.int32), mode="drop")
                    lengths = cache.lengths.at[rows].set(
                        ints[4].astype(cache.lengths.dtype), mode="drop")
                    cache = cache._replace(page_table=table,
                                           lengths=lengths)
                    cache = _put_state(cache, carry, rows)
                return cache

            if first:
                def prefill_chunk_first(params, *args):
                    if P0:
                        pk, pv, ps, packed, cache = args
                    else:
                        pk = pv = ps = None
                        packed, cache = args
                    tokens, ints, _, _, tables = _parts(packed, cache)
                    R = tokens.shape[0]
                    carry = KVCache.create(config, R, W, dtype=self._dtype)
                    if P0:
                        k0 = jnp.broadcast_to(
                            pk[:, None], (pk.shape[0], R) + pk.shape[1:])
                        v0 = jnp.broadcast_to(
                            pv[:, None], (pv.shape[0], R) + pv.shape[1:])
                        carry = carry._replace(
                            k=carry.k.at[:, :, :P0].set(k0),
                            v=carry.v.at[:, :, :P0].set(v0))
                        carry = _seed_state(carry, ps)
                    carry, logits_c = _fwd(params, tokens, ints, carry,
                                           self._chunk_logits0(R))
                    cache = _splice(cache, carry, ints, tables)
                    return carry, logits_c, cache
                # donate the big cache (always the last argument)
                return jax.jit(prefill_chunk_first,
                               donate_argnums=(5 if P0 else 2,))

            if not final:
                def prefill_chunk_mid(params, packed, carry, logits_c,
                                      cache):
                    tokens, ints, _, _, tables = _parts(packed, cache)
                    carry, logits_c = _fwd(params, tokens, ints, carry,
                                           logits_c)
                    cache = _splice(cache, carry, ints, tables)
                    return carry, logits_c, cache
                return jax.jit(prefill_chunk_mid, donate_argnums=(2, 3, 4))

            def prefill_chunk_final(params, packed, carry, logits_c, cache,
                                    keys, next_tokens, temps, top_ks, top_ps,
                                    ring, rps):
                tokens, ints, floats, rings, tables = _parts(packed, cache)
                carry, logits_c = _fwd(params, tokens, ints, carry,
                                       logits_c)
                moe = None
                if routed:
                    logits_c, moe = logits_c
                R = tokens.shape[0]
                seeds, total_lens = ints[2], ints[4]
                row_keys = jax.vmap(jax.random.PRNGKey)(seeds)
                toks, row_keys = sample_batched(logits_c, row_keys,
                                                floats[0], ints[3],
                                                floats[1], ring=rings,
                                                rp=floats[2])
                rings = rings.at[jnp.arange(R),
                                 total_lens % _RING].set(toks)
                cache = _splice(cache, carry, ints, tables)
                (keys, next_tokens, temps, top_ks, top_ps, ring,
                 rps) = _install_rows(ints[1], row_keys, toks, ints,
                                      floats, rings, keys, next_tokens,
                                      temps, top_ks, top_ps, ring, rps)
                out = (_with_moe(toks, moe), cache, keys, next_tokens,
                       temps, top_ks, top_ps, ring, rps)
                return out + (carry,) if in_place else out
            # The carry kv/logits die here but have no same-shaped output
            # to alias into — donating them only trips XLA's unusable-
            # donation warning, so they are freed by refcount instead;
            # except a LARGE carry, which is handed back so that it can be
            # donated (_CARRY_IN_PLACE_BYTES; _dispatch_prefill_chunk
            # drops it).
            in_place = W * self._carry_token_bytes > _CARRY_IN_PLACE_BYTES
            if in_place:
                return jax.jit(prefill_chunk_final,
                               donate_argnums=(2, 4, 5, 6, 7, 8, 9, 10, 11))
            return jax.jit(prefill_chunk_final,
                           donate_argnums=(4, 5, 6, 7, 8, 9, 10, 11))

        self._make_prefill_chunk_program = _make_prefill_chunk_program
        self._prefill_chunk_programs: dict[tuple[int, int, int], object] = {}
        # (P0, S, off, C, R) shapes that have actually executed (jit
        # wrappers above compile per batch width R on first call).
        self._chunk_shapes_run: set[tuple] = set()  # owned-by: _loop

        def prefill_build_prefix(params, toks):
            """Prefill one prefix ([1,P]) and strip the batch axis —
            the register_prefix / promotion builder."""
            P = toks.shape[1]
            cache = KVCache.create(config, 1, P, dtype=self._dtype)
            lens = jnp.full((1,), P, jnp.int32)
            if routed:
                _, cache, moe = model.prefill_counted(
                    params, config, toks, lens, cache,
                    jnp.ones((1, P), bool), mesh)
                past = (None if cache.state is None
                        else snapshot(cache.state))
                if cache.idx is not None:
                    past = IndexedPast(cache.idx[:, 0], past)
                if past is not None:
                    return cache.k[:, 0], cache.v[:, 0], moe, past
                return cache.k[:, 0], cache.v[:, 0], moe
            _, cache = model.prefill(params, config, toks, lens, cache, mesh)
            return cache.k[:, 0], cache.v[:, 0]

        self._build_prefix_j = jax.jit(prefill_build_prefix)

        # Process start (as the OS records it) until this scheduler is
        # built: interpreter, imports, weights, pool.
        self._boot_load_s = process_age_s() or 0.0
        self._thread = threading.Thread(target=self._loop, daemon=True,
                                        name="batch-scheduler")
        self._thread.start()

    # -- shared-prefix KV cache ----------------------------------------------

    def _registered_prefix_len(self, text: str, quiet: bool = False) -> int:
        """Cached-entry length for a registered template (0 = won't
        cache): the full token head minus ONE — match() requires a
        proper prefix (>= 1 suffix token must prefill; its logits seed
        sampling), so a full-length entry would never serve a prompt
        that IS the template verbatim (a real workload: the same
        question re-asked, or a fixed prompt benched repeatedly).
        Shared by register_prefix and warmup's job planning so the two
        cannot drift."""
        ids = self.tokenizer.encode(text, add_bos=True)
        if len(ids) < _MIN_REGISTER_PREFIX:
            if not quiet:
                log.warning(
                    "prefix_text %r encodes to %d tokens — below the "
                    "%d-token minimum, not cached (caching would save "
                    "almost nothing)",
                    text[:40], len(ids), _MIN_REGISTER_PREFIX)
            return 0
        P = len(ids) - 1
        if P + _MIN_BUCKET > self.max_seq:
            # The admission guard rejects any prefix whose length plus
            # the smallest suffix bucket overruns max_seq — building the
            # entry would burn a prefill + an LRU slot on KV no request
            # can ever use.
            if not quiet:
                log.warning(
                    "prefix_text %r encodes to %d tokens — too long to "
                    "ever admit under max_seq=%d, not cached",
                    text[:40], len(ids), self.max_seq)
            return 0
        return P

    def register_prefix(self, text: str) -> int:
        """Cache the KV of ``text``'s token head at its EXACT length
        (minus one — see _registered_prefix_len). Registered templates
        are not grain-bounded the way auto-promoted heads are: the
        operator names finitely many templates and warmup compiles their
        admission shapes up front, so exact lengths add no unbounded
        compiles — and grain-snapping silently dropped real-tokenizer
        templates shorter than the smallest grain (the co-pilot template
        is ~18 llama-BPE tokens vs a 64-token ladder floor, so the
        advertised default caching never engaged on real checkpoints).
        Returns the cached prefix length in tokens (0 = not cached,
        logged). Called from warmup (before traffic) or the scheduler
        thread (promotion); the store itself is thread-safe."""
        if self._prefix is None:
            return 0
        P = self._registered_prefix_len(text)
        if P <= 0:
            return 0
        ids = self.tokenizer.encode(text, add_bos=True)
        return self._register_prefix_ids(ids[:P])

    def _chunk_logits0(self, R: int):
        """What a chunk ladder carries from chunk to chunk beside the
        KV, at its start: the rows' last-position logits, and for a
        routed model the drop count so far."""
        logits = jnp.zeros((R, self.config.vocab_size), jnp.float32)
        if self._counted:
            return logits, jnp.zeros((len(self._moe_prefill),), jnp.int32)
        return logits

    def _count_moe(self, stats, dispatches: int) -> None:
        """A prefill's counts, summed on the device over its routed
        layers and over the ``dispatches`` that carried a request (the
        chunks of a ladder share one vector)."""
        n = dict(zip(self._moe_prefill, map(int, stats)))
        self._n_moe_assigned += n["assigned"]
        self._n_moe_dropped += n["dropped"]
        self._n_moe_prefill_rows += n.get("rows", 0)
        if "routed" in n:
            self._n_moe_routed_pairs += n["routed"]
            self._n_moe_local_pairs += n["assigned"]
            self._n_moe_prefill_layers += (dispatches
                                           * self.config.routed_layers)

    def _build_prefix_kv(self, ids) -> tuple:
        """Prefix KV for ``ids`` — reads only immutable state (params +
        the jitted builder), so it is safe on the promotion worker
        thread too."""
        built = self._build_prefix_j(
            self._params,  # graftcheck: sync-ok host token ids, upload not readback
            jnp.asarray(np.asarray(ids, np.int32)[None, :]))
        if self._counted:
            # A build has no first token to ride on: its drop count
            # waits, on the device, for the next admission's readback.
            self._moe_unread.append(built[2])
        # Third: a hybrid model's state at the prefix's end, else None.
        return built[0], built[1], (built[3] if len(built) > 3 else None)

    def _install_prefix(self, ids, k, v, note: str = "",
                        state=None) -> None:
        """Store insert + log (scheduler thread only — single writer)."""
        self._prefix.put(PrefixEntry(ids=tuple(ids), k=k, v=v, state=state))
        log.info("cached prefix KV: %d tokens (%d entr%s%s)", len(ids),
                 len(self._prefix),
                 "y" if len(self._prefix) == 1 else "ies", note)

    def _register_prefix_ids(self, ids: list[int]) -> int:
        k, v, state = self._build_prefix_kv(ids)
        self._install_prefix(ids, k, v, state=state)
        return len(ids)

    def _decode_for(self, window: int):
        """Jitted decode program for a static attention-read window
        (compiled once per power-of-two window)."""
        p = self._decode_programs.get(window)
        if p is None:
            p = self._make_decode(window)
            self._decode_programs[window] = p
        return p

    def _spec_for(self, window: int):
        p = self._spec_programs.get(window)
        if p is None:
            p = self._make_spec(window)
            self._spec_programs[window] = p
        return p

    def _spec_tree_for(self, window: int):
        p = self._spec_tree_programs.get(window)
        if p is None:
            p = self._make_spec_tree(window)
            self._spec_tree_programs[window] = p
        return p

    def _tree_base(self) -> tuple[np.ndarray, np.ndarray]:
        """Static per-(N, K) host base of the node tree: depths and
        ancestor sets for the main chain (node 0 = pending token, nodes
        1..K = the linear draft), sibling slots zeroed (depth 0,
        self-only ancestry) until a tick budgets them. Cached — the
        spec tick copies it per dispatch."""
        if self._tree_base_np is None:
            N, K = self.spec_tree_nodes, self.spec_k
            depths = np.zeros((N,), np.int32)
            depths[: K + 1] = np.arange(K + 1, dtype=np.int32)
            anc = np.zeros((N, N), bool)
            for i in range(K + 1):
                anc[i, : i + 1] = True
            for s in range(K + 1, N):
                anc[s, s] = True
            self._tree_base_np = (depths, anc)
        return self._tree_base_np

    def _decode_fused_for(self, window: int, K: int):
        p = self._decode_fused_programs.get((window, K))
        if p is None:
            p = self._make_decode_fused(window, K)
            self._decode_fused_programs[(window, K)] = p
        return p

    def _wake_for(self, window: int, S: int):
        p = self._wake_programs.get((window, S))
        if p is None:
            p = self._make_wake(window, S)
            self._wake_programs[(window, S)] = p
        return p

    def _prefill_chunk_for(self, P0: int, S: int, off: int, C: int):
        """Jitted continuation-prefill chunk program (compiled once per
        (prefix length, suffix bucket, offset, chunk width) — warmup
        walks the whole ladder so none compiles mid-serving). ``C`` is
        the caller's chunk width, NOT self.prefill_chunk: an in-flight
        carry snapshots its width at admission, so a runtime toggle of
        prefill_chunk can never mismatch a half-prefilled admission
        against a differently-shaped program."""
        key = (P0, S, off, C)
        p = self._prefill_chunk_programs.get(key)
        if p is None:
            p = self._make_prefill_chunk_program(P0, S, off, C)
            self._prefill_chunk_programs[key] = p
        return p

    @property
    def _fuse_ladder(self) -> tuple[int, ...]:
        """Compiled fused-K sizes: powers of two up to decode_fuse_max
        (plus the cap itself) — the ramp climbs this ladder, so the
        compile cache holds a handful of fused programs per window, not
        one per possible K."""
        ks, k = [], 2
        while k < self.decode_fuse_max:
            ks.append(k)
            k *= 2
        if self.decode_fuse_max > 1:
            ks.append(self.decode_fuse_max)
        return tuple(ks)

    def _choose_fuse_k(self, inflight: int) -> int:
        """Adaptive fused-K for this tick. Collapses to 1 whenever
        fusing could overrun a budget THIS tick:

        - any active row within K tokens of its ``max_new`` or KV
          budget — the device must never write a slot past a row's
          allocation, and ``inflight`` unprocessed pipelined steps count
          against the headroom (device length runs ahead of the host's
          ctx_len mirror by up to that many slots);
        - admissions pending while chunking is DISABLED or cannot cover
          every bucket (``max_seq % prefill_chunk != 0``: the
          max_seq-capped top bucket admits single-shot whole-bucket, so
          a pending admission may put an unbounded prefill after this
          tick, and a K-step tick would also push its TTFT back K-1
          steps — conservative: power-of-two buckets in that config
          lose the ramp-under-backlog win, but the bounded-stall
          guarantee comes first). With chunking covering all buckets
          (default), pending admissions do NOT collapse K: every
          admission dispatch is already bounded to one chunk's compute,
          so fusion keeps amortising host dispatch while the backlog
          drains — the pre-chunking rule degraded decode to K=1 for the
          entire drain;

        otherwise K doubles along the compiled ladder up to
        ``decode_fuse_max``, so a stream that just admitted ramps
        1 -> 2 -> 4 instead of jumping straight to a long fused tick.
        The decision table is pinned by tests/test_fused_decode.py.
        """
        kmax = self.decode_fuse_max
        if kmax <= 1:
            return 1
        C = self.prefill_chunk   # one read: togglable at runtime
        if ((not C or self.max_seq % C)
                and (self._admit_carry or self._waiting
                     or not self._admit_q.empty())):
            self._fuse_ramp = 1
            return 1
        cap = kmax
        for s in self._slots:
            if s is None:
                continue
            cap = min(cap,
                      s.max_new - len(s.ids) - inflight,
                      s.ctx_budget - s.ctx_len - inflight)
            if cap < 2:
                self._fuse_ramp = 1
                return 1
        k = 1
        target = min(cap, self._fuse_ramp * 2)
        for cand in self._fuse_ladder:
            if cand <= target:
                k = cand
        self._fuse_ramp = max(k, 1)
        return max(k, 1)

    def _chunk_ladder_ready(self, P0: int, S: int, R: int) -> bool:
        """True when every continuation-chunk program of the (P0, S)
        ladder has already EXECUTED at batch width R — the precondition
        for chunked admission while live streams exist (an unwarmed
        ladder would compile serially on the loop thread, stalling
        every decode). Checked against the executed-shape set, not the
        jit-wrapper cache: a wrapper registered by an earlier admission
        at a different R would still pay ceil(S/C) fresh XLA compiles
        at this R."""
        C = self.prefill_chunk
        return all((P0, S, off, C, R) in self._chunk_shapes_run
                   for off in range(0, S, C))

    def _admit_widths(self, footprint: int) -> tuple[int, ...]:
        """Widths (rows R, ascending) of the admission programs for a
        per-row token footprint: the suffix bucket plus any broadcast
        prefix (the small cache is [L, R, P+S, ...], so both count).
        Warm-up compiles exactly these, the promotion worker builds
        exactly these, and _admit_width picks among them, so an
        admission never meets a width nobody compiled.

        Default: 1, and 2 where two rows of this footprint stay inside
        _ADMIT_PAIR_TOKENS and _ADMIT_PAIR_BYTES (the comments there say
        why no wider): a
        burst of n requests runs ceil(n/2) pair dispatches. A fixed
        ``admit_chunk`` is the ONLY width, narrowed where the bucket
        would pass the HBM budget."""
        if self.admit_chunk:
            return (min(self.admit_chunk,
                        _pow2_floor(_ADMIT_TOKEN_BUDGET // footprint)),)
        if (self.num_slots > 1 and 2 * footprint <= _ADMIT_PAIR_TOKENS
                and 2 * footprint * self._carry_token_bytes
                <= _ADMIT_PAIR_BYTES):
            return (1, 2)
        return (1,)

    def _admit_width(self, n: int, footprint: int) -> int:
        """Width of the dispatch for ``n`` requests collected together:
        the narrowest program that holds them all, else the widest (the
        rest of the group follows in further dispatches)."""
        widths = self._admit_widths(footprint)
        return next((w for w in widths if w >= n), widths[-1])

    def _window(self, extra: int = 0) -> int:
        """Smallest power-of-two (>= 128, <= max_seq) attention window
        covering every active row's context + the slot(s) being written
        (``extra`` > 0: the speculative tick writes K extra candidates).
        For an indexed model this bounds what the INDEXER scores and
        what the masked walk reads; its attention attends ``index_topk``
        rows of it."""
        need = 1 + extra + max(s.ctx_len for s in self._slots if s is not None)
        w = min(128, self.max_seq)
        while w < need:
            w *= 2
        return min(w, self.max_seq)

    def warmup(self, prompt_buckets: tuple[int, ...] = (128, 256),
               windows: Optional[tuple[int, ...]] = None,
               prefix_texts: tuple[str, ...] = (),
               timeout_s: float = 1800.0) -> None:
        """Pre-compile the serving programs (first compile is tens of
        seconds on TPU — it must not land on real requests' TTFT): the
        admit programs of every prompt bucket at each width admission
        can choose (_admit_widths: 1 row and 2 rows a bucket,
        with their chunk ladders and prefix splices —
        _admission_shapes), one decode (and spec) program per attention
        window.

        Warmup dispatches the REAL programs on the LIVE device state with
        all-padding inputs — a no-op by the same invariants serving rests
        on (padding rows carry the out-of-range sentinel so installs
        drop; inactive decode rows never advance and their writes land
        beyond trusted lengths / in the garbage page). This matters for
        memory: the earlier throwaway-buffer approach allocated a second
        full KV pool during warmup, which at long max_seq was the
        difference between fitting in HBM and OOMing before the first
        request.

        Because it touches live buffers, the work runs ON the scheduler
        thread — split into ONE queued job per compiled program, so live
        decode ticks and admissions interleave between compiles instead
        of freezing for the whole ladder. This wrapper blocks until every
        job completes and re-raises the first error, from any thread."""
        if self._closed.is_set():
            raise RuntimeError("scheduler is stopped")
        # /readyz gating: once a warmup has STARTED, the scheduler
        # reports not-ready until it completes (uncompiled programs mean
        # tens-of-seconds TTFT on TPU — a load balancer must not route
        # here yet). A scheduler that never warms is ready immediately.
        self.note_warmup_pending()
        buckets = sorted({_bucket(b, self.max_seq) for b in prompt_buckets})
        if windows is None:
            # The whole ladder up to max_seq: any window left uncompiled
            # would lazily compile mid-serving the first time a context
            # grows into it, stalling every active stream for the compile.
            w, ws = min(128, self.max_seq), set()
            while True:
                ws.add(w)
                if w >= self.max_seq:
                    break
                w *= 2
            windows = tuple(sorted(ws))
        else:
            # Caller-supplied windows clamp to the serving budget (which
            # is itself capped by the model's max_seq_len): a wider
            # window would walk past the KV allocation.
            windows = tuple(sorted({min(w, self.max_seq) for w in windows}))

        def _admit_steps(S: int, R: int, P0: int = 0,
                         synthetic: bool = False) -> list:
            """Warmup jobs for one (prefix, suffix-bucket, chunk-width)
            admission shape: the single-shot program when the bucket
            fits one prefill chunk (or is not a chunk multiple — the
            max_seq-capped top bucket, which admits single-shot), else
            the WHOLE continuation-chunk ladder (one job per offset —
            the chunked path never runs the single-shot program for
            that bucket, and a lazy chunk compile mid-admission would
            stall every live stream).
            Prefix entries are looked up at RUN time, after the
            registration jobs queued ahead have populated the store."""
            C = self.prefill_chunk
            if C and S > C and S % C == 0:
                return [
                    (lambda S=S, R=R, off=off, P0=P0:
                     self._warm_prefill_chunk(S, R, off, prefix_len=P0,
                                              synthetic=synthetic))
                    for off in range(0, S, C)]
            if P0 or synthetic:
                return [lambda P0=P0, S=S, R=R:
                        self._warm_prefix_combo(P0, S, R,
                                                synthetic=synthetic)]
            return [lambda S=S, R=R: self._admit_chunk([], [], S, R)]

        steps = []
        n_chunk_jobs = 0

        def _extend_admit(jobs: list) -> None:
            """Queue one admission shape's warmup jobs, counting the
            continuation-ladder ones (>1 job = a chunk ladder) for the
            `warmup compiled:` line the verify script greps."""
            nonlocal n_chunk_jobs
            if len(jobs) > 1:
                n_chunk_jobs += len(jobs)
            steps.extend(jobs)

        # Shared-prefix programs: register the known templates (builds
        # their KV — one prefill compile per distinct P) before the
        # splice programs that look their entries up at run time. The P
        # set is known before the register jobs run: already-cached
        # lengths plus the exact token length of each template.
        plens: set[int] = set()
        if self._prefix is not None:
            plens.update(self._prefix.lengths())
        for text in prefix_texts:
            steps.append(lambda t=text: self.register_prefix(t))
            n = self._registered_prefix_len(text, quiet=True)
            if self._prefix is not None and n > 0:
                plens.add(n)
        for P, S, R, synthetic in self._admission_shapes(buckets, plens):
            _extend_admit(_admit_steps(S, R, P0=P, synthetic=synthetic))
        for w in windows:
            steps.append(lambda w=w: self._warm_window(w))
        if self._tier is not None:
            # Session-wake programs compile per (window, suffix bucket):
            # warm the cross product so a wake under live traffic never
            # compiles mid-serving (unwarmed shapes demote to cold
            # admission — correct, but forfeits the wake win exactly
            # when the session economics matter).
            for S in buckets:
                if S > _WAKE_MAX_SUFFIX:
                    continue
                for w in windows:
                    steps.append(lambda w=w, S=S: self._warm_wake(w, S))
        if self._draft_model is not None:
            # Drafter programs (steady-state draft shape per window +
            # the admission-prefill feed shapes) ride the same one-job-
            # per-program queue, so a mid-traffic warmup interleaves
            # drafter compiles with live ticks too.
            steps.extend(self._draft_model.warm(buckets, windows))
        steps.append(self._warm_zero_row)
        # One-shot device-step measurement for the wall/device gauges —
        # after the windows compiled, before traffic.
        steps.append(self._probe_device_step)
        # Admission rounds short prompts UP to the smallest warmed bucket
        # (_serving_bucket) — recorded only after every program compiled.
        def _record():
            self._warmed_buckets = buckets
            # Long-window kernel ladder: name which warmed windows baked
            # in the multi-chunk flash-append kernel (W >= min_w on TPU
            # — ops/paged_attention._flash_append_policy). The windows
            # loop above compiled BOTH sides of the boundary, so a live
            # batch promoting from a gather window into a kernel window
            # mid-serving never compiles over active streams.
            flash_note = ""
            min_w = self._paged_flash_min_w
            kernel_ws = [w for w in windows if min_w and w >= min_w]
            if kernel_ws:
                flash_note = (f", flash-append kernel at windows "
                              f"{kernel_ws} (min_w {min_w})")
            log.info("warmup compiled: admit widths by bucket %s, decode "
                     "windows %s, prefill chunk %d (%d continuation "
                     "programs)%s",
                     {S: self._admit_widths(S) for S in buckets},
                     windows, self.prefill_chunk, n_chunk_jobs, flash_note)
        steps.append(_record)
        # Drain the dispatch queue at the end: warmup executions are
        # async — without a readback the first real request queues
        # behind all of them.
        # graftcheck: sync-ok,lock-ok intentional drain, runs as a queued _WarmupJob ON the scheduler thread
        steps.append(lambda: np.asarray(self._cache.lengths[:1]))

        def _warmup_finished():
            # Admission deadlines guard CAPACITY, not boot: requests that
            # arrive while warmup still compiles (an 8B boot is minutes of
            # compiles even with the persistent cache) start their
            # deadline clock here, not at arrival (see _expired).
            self._warmup_done_at = time.monotonic()
            self._boot_warmup_s = self._warmup_done_at - t_warm
            self._boot_compile_s = compile_clock().seconds
            log.info("warmup finished: %d jobs in %.1f s (process: %.1f s "
                     "of compilation and cache retrieval so far)",
                     len(steps), self._boot_warmup_s, self._boot_compile_s)
        self._warmup_done_at = None
        t_warm = time.monotonic()
        steps.append(_warmup_finished)

        # A failed job voids the warmup (_WarmupJob.void) and the
        # failure is TERMINAL — recorded for /readyz and the entry point
        # (serve/api.py exits non-zero on it). A program the compiler
        # refuses must not leave a live process answering 503-warming
        # for ever.
        void = threading.Event()
        jobs = [_WarmupJob(fn, void) for fn in steps]
        for j in jobs:
            self._admit_q.put(j)
        deadline = time.monotonic() + timeout_s
        try:
            for j in jobs:
                while not j.done.wait(timeout=1.0):
                    if self._closed.is_set() and not self._thread.is_alive():
                        raise RuntimeError("scheduler stopped during warmup")
                    if time.monotonic() > deadline:
                        raise TimeoutError(
                            f"warmup did not finish within {timeout_s}s")
                if j.err is not None:
                    raise j.err
        except BaseException as e:
            void.set()
            self.warmup_error = f"{type(e).__name__}: {e}"
            raise

    def _admission_shapes(self, buckets: list[int],
                          plens: set[int]) -> list[tuple]:
        """The admission surface warm-up compiles, as (P, S, R,
        synthetic): every suffix bucket S at each width _admit_widths
        allows, without a prefix (P = 0) and behind each cached or
        registered prefix length in ``plens`` — so neither a lone
        request nor a burst, template hit or miss, compiles
        mid-serving. Then the grain pre-warm: auto-promoted prefixes
        always land on the grain ladder, so compiling each grain's
        splice program for the SMALLEST suffix bucket now (synthetic
        zero entries — only shapes matter to the compile cache) means
        a hot template promoted mid-traffic admits through a warm
        program. Bounded: grains x 1 bucket x widths."""
        shapes = [(P, S, R, False)
                  for P in [0] + sorted(plens) for S in buckets
                  if P + S <= self.max_seq
                  for R in self._admit_widths(P + S)]
        if self._prefix is not None and buckets:
            S = buckets[0]
            shapes += [(P, S, R, True)
                       for P in self._prefix.grain_ladder
                       if P not in plens and P + S <= self.max_seq
                       for R in self._admit_widths(P + S)]
        return shapes

    def _build_promotion(self) -> None:
        """Hand one queued prefix promotion to the build worker
        (scheduler thread only). The worker computes the prefix KV AND
        ahead-of-time compiles the splice programs the new prefix will
        admit through, both off the serving loop; _drain_promotions
        integrates the results. The admission-shape combos and the
        live-state shape skeletons are snapshotted HERE, on the
        scheduler thread — metadata-only reads, but _warmed_buckets /
        _chunk_shapes_run / the buffer trees are loop-owned."""
        self._last_promote_tick = self._n_decode_ticks
        head = self._promote_q.pop(0)
        if self._promote_worker is None:
            self._promote_worker = threading.Thread(
                target=self._promotion_worker, daemon=True,
                name="prefix-promote")
            self._promote_worker.start()
        self._promote_pending.add(head)
        self._promote_work.put((head, self._promotion_combos(len(head)),
                                self._promotion_structs()))

    def _promotion_combos(self, P: int) -> list[tuple]:
        """Admission shapes a fresh prefix of length ``P`` can serve
        through, mirroring warmup()'s prefix sub-ladder: one
        (S, R, C, offs) per (warmed suffix bucket, admit width) — offs
        is the continuation-chunk offset ladder for chunked buckets,
        None for single-shot. Shapes already compiled (a prior
        promotion at the same grain, or the warmup grain pre-warm's
        ladder recorded in _chunk_shapes_run) are skipped."""
        C = self.prefill_chunk
        combos: list[tuple] = []
        for S in (getattr(self, "_warmed_buckets", None) or ()):
            if P + S > self.max_seq:
                continue
            for R in self._admit_widths(P + S):
                if C and S > C and S % C == 0:
                    offs = tuple(
                        off for off in range(0, S, C)
                        if (P, S, off, C, R) not in self._chunk_shapes_run
                        and (P, S, off, C, R) not in self._prefill_chunk_aot)
                    if offs:
                        combos.append((S, R, C, offs))
                elif (P, S, R) not in self._admit_prefix_aot:
                    combos.append((S, R, C, None))
        return combos

    def _promotion_structs(self) -> dict:
        """Shape/dtype skeletons of the live serving state, captured on
        the scheduler thread (metadata only — no device reads, no
        buffer references escape to the worker beyond structs) so the
        promotion worker can lower admission programs against exactly
        the shapes/placements the loop will execute them with."""
        def _sds(x):
            return jax.ShapeDtypeStruct(jnp.shape(x), x.dtype,
                                        sharding=getattr(x, "sharding",
                                                         None))
        if self._params_struct is None:
            # Params are immutable for the scheduler's lifetime.
            self._params_struct = jax.tree.map(_sds, self._params)
        return {
            "params": self._params_struct,
            "cache": jax.tree.map(_sds, self._cache),
            "sample": jax.tree.map(_sds, (
                self._keys, self._next_dev, self._temps_dev,
                self._top_ks_dev, self._top_ps_dev, self._ring_dev,
                self._rps_dev)),
            "mppr": self._cache.max_pages_per_row,
        }

    def _compile_promotion_aot(self, P: int, k, v, state,
                               combos: list[tuple],
                               structs: dict) -> tuple[dict, dict]:
        """AOT-compile (lower + compile — never execute) the splice
        programs for a promoted prefix of length ``P``. Runs on the
        promotion worker thread: tracing and XLA compilation consume
        only shape skeletons, so the donated live buffers the programs
        will eventually run against are never touched off-loop; the
        scheduler thread calls the returned executables with the real
        arrays exactly as it would the jit wrappers."""
        params_s, cache_s, sample_s = (structs["params"], structs["cache"],
                                       structs["sample"])
        ks = jax.ShapeDtypeStruct(k.shape, k.dtype)
        vs = jax.ShapeDtypeStruct(v.shape, v.dtype)
        ss = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                          state)
        aot_admit: dict[tuple, object] = {}
        aot_chunks: dict[tuple, object] = {}
        for S, R, C, offs in combos:
            # The packed admission buffer (_admit_layout), whole for
            # every program of the bucket.
            packed = jax.ShapeDtypeStruct(
                (R, _admit_layout(structs["mppr"])[-1] + S), jnp.int32,
                sharding=self._packed_sharding)
            if offs is None:
                args = [params_s, ks, vs, ss, packed, cache_s, *sample_s]
                aot_admit[(P, S, R)] = (
                    self._admit_prefix_j.lower(*args).compile())
                continue
            carry_s = jax.eval_shape(
                lambda R=R, W=P + S: KVCache.create(self.config, R, W,
                                                    dtype=self._dtype))
            logits_s = jax.eval_shape(lambda R=R: self._chunk_logits0(R))
            for off in offs:
                prog = self._make_prefill_chunk_program(P, S, off, C)
                if off == 0:
                    args = [params_s, ks, vs, ss, packed, cache_s]
                elif off + C < S:
                    args = [params_s, packed, carry_s, logits_s, cache_s]
                else:
                    args = [params_s, packed, carry_s, logits_s, cache_s,
                            *sample_s]
                aot_chunks[(P, S, off, C, R)] = (
                    prog.lower(*args).compile())
        return aot_admit, aot_chunks

    def _promotion_worker(self) -> None:
        """Daemon: builds promotion prefix KV — and AOT-compiles the
        admission programs that will splice it — off the scheduler
        thread. Touches ONLY immutable state (params, the jitted
        builder — jit call caches are thread-safe) plus the shape
        skeletons snapshotted by _build_promotion; results go back
        through _promote_done for the scheduler thread to install."""
        while True:
            item = self._promote_work.get()
            if item is None or self._closed.is_set():
                return
            head, combos, structs = item
            try:
                # Failpoint: a failed promotion build is dropped (it is
                # an optimization) — serving must be untouched.
                failpoint("serve.scheduler.promote")
                k, v, state = self._build_prefix_kv(head)
                aot_admit, aot_chunks = self._compile_promotion_aot(
                    len(head), k, v, state, combos, structs)
                self._promote_done.put((head, k, v, state, aot_admit,
                                        aot_chunks))
            except Exception:   # noqa: BLE001 — promotion is optional
                log.exception("prefix promotion build failed")
                self._promote_done.put((head, None, None, None, {}, {}))

    def _drain_promotions(self) -> None:
        """Install finished promotion builds (scheduler thread only —
        keeps the store and the AOT tables single-writer). The worker's
        executables merge BEFORE the entry goes live: the very next
        admission may hit the new prefix, and the contract is that it
        dispatches an already-compiled program."""
        while True:
            try:
                (head, k, v, state, aot_admit,
                 aot_chunks) = self._promote_done.get_nowait()
            except queue.Empty:
                return
            self._promote_pending.discard(head)
            if k is None:
                continue
            self._admit_prefix_aot.update(aot_admit)
            self._prefill_chunk_aot.update(aot_chunks)
            self._install_prefix(
                head, k, v, state=state,
                note=(f", promoted off-thread, "
                      f"{len(aot_admit) + len(aot_chunks)} AOT programs"))

    def _zero_prefix_entry(self, P: int) -> PrefixEntry:
        """A prefix entry of the right shapes and no content (the grain
        pre-warm: the compile cache keys on shapes only)."""
        c = self.config
        lead = (c.cache_layers, P, c.cache_kv_heads)
        state = (snapshot(StatePool.create(c, 1, self._dtype))
                 if c.state_layers else None)
        if c.is_indexed:
            state = IndexedPast(jnp.zeros(
                (c.cache_layers, P, 1, c.cache_idx_dim), self._dtype), state)
        return PrefixEntry(ids=tuple(range(P)),
                           k=jnp.zeros(lead + (c.cache_k_dim,), self._dtype),
                           v=jnp.zeros(lead + (c.cache_v_dim,), self._dtype),
                           state=state)

    def _warm_prefix_combo(self, P: int, S: int, R: int,
                           synthetic: bool = False) -> None:
        """Compile+run ONE prefix-admission program (one queued warmup
        job per program, so mid-traffic warmups interleave with live
        ticks between compiles instead of stalling for a whole
        sub-ladder). The entry is looked up at run time — registration
        jobs queued ahead of this one have populated the store.
        ``synthetic``: no entry exists yet (grain pre-warm) — run the
        program against a zeros entry of the right SHAPES, which is all
        the compile cache keys on; auto-promoted prefixes are
        grain-snapped, so their first real admission then hits a warm
        program instead of compiling mid-burst (measured ~5 s stall for
        every in-flight stream)."""
        entry = next((e for e in self._prefix.snapshot()
                      if e.length == P), None)
        if P + S > self.max_seq:
            return
        if entry is None:
            if not synthetic:
                return
            entry = self._zero_prefix_entry(P)
        self._admit_chunk([], [], S, R, warm_prefix=entry)

    # graftcheck: runs-on _loop
    def _warm_prefill_chunk(self, S: int, R: int, off: int,
                            prefix_len: int = 0,
                            synthetic: bool = False) -> None:
        """Compile+run ONE continuation-prefill chunk program as a
        padding no-op on the live cache (one queued warmup job per
        program, exactly like the admit/window jobs, so mid-traffic
        warmups interleave with live ticks). Offsets past the first run
        against a throwaway zero carry — the compile cache keys on
        shapes only. ``prefix_len`` > 0 warms the prefix-offset ladder:
        the entry is looked up at run time (registration jobs queued
        ahead have populated the store); ``synthetic`` fabricates a
        zeros entry of the right shapes (grain pre-warm)."""
        entry = None
        if prefix_len:
            entry = next((e for e in self._prefix.snapshot()
                          if e.length == prefix_len), None)
            if entry is None:
                if not synthetic:
                    return
                entry = self._zero_prefix_entry(prefix_len)
        if prefix_len + S > self.max_seq:
            return
        C = self.prefill_chunk
        packed = self._admit_upload(
            self._admit_host_arrays([], [], S, R, entry), live=False)
        if off == 0:
            kv = logits = None
        else:
            kv = KVCache.create(self.config, R, prefix_len + S,
                                dtype=self._dtype)
            logits = self._chunk_logits0(R)
        self._dispatch_prefill_chunk(prefix_len, S, off, C, packed, kv,
                                     logits, entry)

    # graftcheck: runs-on _loop
    def _warm_window(self, w: int) -> None:
        """Compile+run the decode (and spec) program for one window on
        live state as a parked-row no-op. The programs split every row's
        PRNG key unconditionally, so live rows' keys are restored after —
        a mid-traffic warmup must not perturb seeded requests' outputs.

        Each window's program bakes in its attention impl at trace time
        (gather below the model's flash boundary, the
        multi-chunk flash-append kernel at and above it on TPU), so
        running this across the default whole ladder up to max_seq
        warms the kernel's Mosaic compiles at every long-window bucket
        — window promotion under live traffic is always a cache hit,
        on either side of the gather/kernel boundary."""
        B = self.num_slots
        # graftcheck: sync-ok host bool list, no device readback
        live = np.array([s is not None for s in self._slots], bool)
        keys_before = (self._keys + 0) if live.any() else None   # copy:
        inactive = jnp.zeros((B,), bool)                         # donated
        (_, self._next_dev, self._cache, self._keys,
         self._ring_dev) = self._decode_for(w)(
            self._params, self._next_dev, self._cache, inactive,
            self._temps_dev, self._top_ks_dev, self._top_ps_dev,
            self._keys, self._ring_dev, self._rps_dev)
        if self.spec_k:
            K = self.spec_k
            # Feed live pending tokens as the verify window's first
            # column: the spec program returns next_tokens =
            # where(active, correction, tokens[:, :1]) and active is
            # all-False here, so _next_dev round-trips instead of being
            # clobbered with zeros for rows admitted before a
            # background warmup finishes.
            warm_tokens = jnp.concatenate(
                [self._next_dev, jnp.zeros((B, K), jnp.int32)], axis=1)
            (_, _, self._next_dev, self._cache, self._keys,
             self._ring_dev) = self._spec_for(w)(
                self._params, warm_tokens,
                jnp.zeros((B, K), jnp.int32),
                jnp.zeros((B,), jnp.int32), self._cache, inactive,
                self._temps_dev, self._top_ks_dev, self._top_ps_dev,
                self._keys, self._ring_dev, self._rps_dev)
        if self.spec_k and self.spec_tree_nodes:
            K, N = self.spec_k, self.spec_tree_nodes
            depths_b, anc_b = self._tree_base()
            warm_tokens = jnp.concatenate(
                [self._next_dev, jnp.zeros((B, N - 1), jnp.int32)],
                axis=1)
            (_, _, _, self._next_dev, self._cache, self._keys,
             self._ring_dev) = self._spec_tree_for(w)(
                self._params, warm_tokens,
                jnp.asarray(np.broadcast_to(depths_b, (B, N)).copy()),
                jnp.asarray(np.broadcast_to(anc_b, (B, N, N)).copy()),
                jnp.zeros((B, K), jnp.int32),
                jnp.full((B, K), -1, jnp.int32),
                jnp.full((B, K), -1, jnp.int32),
                jnp.zeros((B,), jnp.int32), self._cache, inactive,
                self._temps_dev, self._top_ks_dev, self._top_ps_dev,
                self._keys, self._ring_dev, self._rps_dev)
        if self.decode_fuse_max > 1:
            # Fused-K programs for this window: the ramp's whole ladder,
            # so the first fused tick after warmup never compiles
            # mid-serving (a lazy scan compile would stall every live
            # stream exactly like a lazy decode compile would).
            for K in self._fuse_ladder:
                (_, self._next_dev, self._cache, self._keys,
                 self._ring_dev) = self._decode_fused_for(w, K)(
                    self._params, self._next_dev, self._cache, inactive,
                    self._temps_dev, self._top_ks_dev, self._top_ps_dev,
                    self._keys, self._ring_dev, self._rps_dev)
        if keys_before is not None:
            self._keys = jnp.where(jnp.asarray(live)[:, None],
                                   keys_before, self._keys)

    # graftcheck: runs-on _loop
    def _warm_wake(self, w: int, S: int) -> None:
        """Compile+run one session-wake program as an all-masked-off
        no-op on live state. Non-waking rows pass every buffer through
        unchanged (keys included — no restore dance needed, unlike
        _warm_window), and the verify writes land beyond trusted
        lengths / in the garbage page."""
        if w < S:
            return   # dispatch never picks w < start + S
        buf, _ = _admit_buffer(self.num_slots, S,
                               self._cache.max_pages_per_row,
                               self.config.vocab_size)
        (_, self._cache, self._keys, self._next_dev, self._temps_dev,
         self._top_ks_dev, self._top_ps_dev, self._ring_dev,
         self._rps_dev) = self._wake_for(w, S)(
            self._params, self._admit_upload(buf, live=False),
            self._cache, self._keys, self._next_dev, self._temps_dev,
            self._top_ks_dev, self._top_ps_dev, self._ring_dev,
            self._rps_dev)
        self._wake_shapes_run.add((w, S))

    # graftcheck: runs-on _loop
    def _probe_device_step(self) -> None:
        """Measure the device decode step once, at warmup's tail: a
        two-point solve over parked-row no-op ticks of the smallest
        window (wall(N) = N*step + one readback; the solve cancels the
        constant), run on the live buffers through the REAL decode
        program. Feeds the ``decode_device_ms`` gauge so /metrics can
        show the wall/device decomposition (``decode_wall_ms`` tracks
        the serving loop live). Keys are restored afterwards, exactly
        like _warm_window — the probe must not perturb seeded streams."""
        B = self.num_slots
        # graftcheck: sync-ok host bool list, no device readback
        live = np.array([s is not None for s in self._slots], bool)
        keys_before = (self._keys + 0) if live.any() else None
        inactive = jnp.zeros((B,), bool)
        decode_j = self._decode_for(min(128, self.max_seq))

        def loop(n: int) -> float:
            t = time.monotonic()
            toks = None
            for _ in range(n):
                (toks, self._next_dev, self._cache, self._keys,
                 self._ring_dev) = decode_j(
                    self._params, self._next_dev, self._cache, inactive,
                    self._temps_dev, self._top_ks_dev, self._top_ps_dev,
                    self._keys, self._ring_dev, self._rps_dev)
            np.asarray(toks)  # graftcheck: sync-ok the probe IS the forced sync
            return (time.monotonic() - t) / n

        loop(1)                                  # warm dispatch path
        n1, n2 = 4, 12
        w1, w2 = loop(n1), loop(n2)
        d = (n2 * w2 - n1 * w1) / (n2 - n1)
        self._decode_device_ms = round(
            (d if d > 0.05 * w2 else w2) * 1e3, 4)
        if keys_before is not None:
            self._keys = jnp.where(jnp.asarray(live)[:, None],
                                   keys_before, self._keys)

    # graftcheck: runs-on _loop
    def _warm_zero_row(self) -> None:
        # The row-release program (_zero_row_j) otherwise compiles on
        # the first request's release — inside a later request's TTFT.
        # Zero a FREE row only: warmup may run mid-traffic (background
        # warmup after serving started), and zeroing a live row's
        # table would reroute its context reads to the garbage page.
        # A free row's table is already zero, so this is a no-op
        # re-zero. All rows busy: skip (compiles lazily on first
        # release — rare, bounded cost).
        free_row = next((i for i, s in enumerate(self._slots)
                         if s is None), None)
        if free_row is not None:
            self._cache = self._zero_row_j(
                self._cache, jnp.asarray(free_row, jnp.int32))

    def _reset_device_state(self) -> None:
        B = self.num_slots
        self._alloc = PageAllocator(self.num_pages, self.page_size)
        self._cache = PagedKVCache.create(
            self.config, B, self.num_pages, self.page_size,
            max_pages_per_row=-(-self.max_seq // self.page_size),
            dtype=self._dtype, quantized=self.kv_quant, mesh=self.mesh)
        self._next_dev = jnp.zeros((B, 1), jnp.int32)
        self._keys = jnp.zeros((B, 2), jnp.uint32)
        # Per-row sampling options live on device; admission scatters them
        # so decode ticks upload nothing but the active mask.
        self._temps_dev = jnp.zeros((B,), jnp.float32)
        self._top_ks_dev = jnp.zeros((B,), jnp.int32)
        self._top_ps_dev = jnp.ones((B,), jnp.float32)
        # Repeat-penalty state: per-row recent-token ring (sentinel
        # vocab_size = empty slot) + penalty factor (1.0 = off).
        self._ring_dev = jnp.full((B, _RING), self.config.vocab_size,
                                  jnp.int32)
        self._rps_dev = jnp.ones((B,), jnp.float32)
        self._active_host: tuple = ()
        self._active_dev = jnp.zeros((B,), bool)
        st = self._cache.state
        self._state_pool_bytes = st.nbytes if st is not None else 0
        self._state_row_bytes = st.row_bytes if st is not None else 0
        self._ring_position_bytes = (st.ring_position_bytes
                                     if st is not None else 0)
        # One position of ONE layer in the page pool, scales included.
        c = self._cache
        self._page_token_bytes = sum(
            int(a.nbytes) for a in (c.k, c.v)) // (
                c.k.shape[0] * c.num_pages * c.page_size)
        if c.quantized:
            self._page_token_bytes += 2 * 4 * c.k.shape[3]
        # One position's index key in ONE indexed layer, as the pool
        # holds it and a step reads it (a row of whole lane tiles).
        self._index_token_bytes = (
            0 if c.idx is None
            else c.idx.shape[-1] * c.idx.dtype.itemsize)

    # -- client side (HTTP threads) ------------------------------------------

    def note_warmup_pending(self) -> None:
        """Flip /readyz to not-ready NOW, atomically (both flags before
        any other warmup work — a readiness poll landing between 'started'
        and 'done nulled' must never read ready). Called at warmup()'s
        entry, and by callers that DEFER the warmup to a background
        thread (serve/engine.py) so the thread-spawn gap is covered
        too."""
        self._warmup_done_at = None
        self._warmup_started = True

    @property
    def ready(self) -> bool:
        """Readiness (distinct from liveness): the loop thread is up AND
        any started warmup has completed — /readyz gates on this, so a
        load balancer never routes traffic at a scheduler whose first
        compiles would land on real requests' TTFT. A scheduler that
        never warms is ready as soon as its thread runs."""
        if self._closed.is_set() or not self._thread.is_alive():
            return False
        if self._draining.is_set():
            return False
        return not self._warmup_started or self._warmup_done_at is not None

    def drain(self) -> None:
        """Enter draining: in-flight streams finish normally, but new
        submits fast-fail with :class:`OverloadError` (503 at the HTTP
        front) and ``ready`` reports False so any balancer scraping
        /readyz routes new sessions away. Reversible via
        :meth:`undrain` — nothing is torn down."""
        self._draining.set()

    def undrain(self) -> None:
        self._draining.clear()

    @property
    def draining(self) -> bool:
        return self._draining.is_set()

    def _queue_depth(self) -> int:
        with self._depth_mu:
            return self._queued_requests

    def submit(self, req: GenerateRequest,
               stats: Optional[RequestStats] = None) -> Iterator[str]:
        """Enqueue a request; yield text deltas until completion. Closing
        the iterator early (client gone) cancels the request.

        Runs the overload check EAGERLY (this is a plain function
        returning a generator, not itself a generator): at queue_max
        pending requests the caller gets :class:`OverloadError` in
        microseconds — well-formed backpressure — instead of a slot that
        waits out the queue deadline. Admission/enqueue also happens
        here, so arrival order is the submit() call order."""
        if self._closed.is_set():
            raise RuntimeError("scheduler is stopped")
        if self._draining.is_set():
            # Draining is deliberate, bounded-duration backpressure: a
            # client (or a router that somehow raced the drain) gets the
            # same well-formed 503 + Retry-After contract as overload.
            with self._depth_mu:
                self._n_shed += 1
            raise OverloadError("server is draining; retry elsewhere",
                                retry_after_s=5.0)
        if self.queue_max:
            with self._depth_mu:
                if self._queued_requests >= self.queue_max:
                    self._n_shed += 1
                    shed = True
                else:
                    self._queued_requests += 1
                    shed = False
            if shed:
                raise OverloadError(
                    f"server at capacity: {self.queue_max} requests "
                    "already queued; retry later")
            on_depart = self._note_depart
        else:
            on_depart = None
        opts = req.options
        seed = opts.seed if opts.seed is not None else time.monotonic_ns()
        slot = _Slot(req=req, stats=stats, out_q=queue.Queue(),
                     seed=int(seed) % (2 ** 31), on_depart=on_depart)
        self._admit_q.put(slot)
        if self._closed.is_set():
            # stop() may have drained the queue between our closed-check and
            # the put; finish defensively so the consumer can never hang (a
            # duplicate None from stop()'s own drain is harmless).
            slot.finish()
        return _SlotStream(self._consume(slot), slot)

    def _note_depart(self) -> None:
        with self._depth_mu:
            self._queued_requests -= 1

    def _consume(self, slot: _Slot) -> Iterator[str]:
        try:
            while True:
                item = slot.out_q.get()
                if item is None:
                    if slot.error is not None:
                        raise RuntimeError(slot.error)
                    return
                delta, put_t = item
                slot.handoff_s += time.monotonic() - put_t
                slot.handoff_n += 1
                # Burst drain: a fused K-step tick (or a speculative
                # tick) lands several deltas at once — coalesce whatever
                # is already queued into ONE yield so the HTTP front
                # writes one NDJSON chunk per burst instead of K
                # per-token chunks (K syscalls + K JSON records per
                # tick otherwise; latency is untouched because only
                # immediately-available deltas are merged).
                parts = [delta]
                done = False
                while True:
                    try:
                        nxt = slot.out_q.get_nowait()
                    except queue.Empty:
                        break
                    if nxt is None:
                        done = True
                        break
                    parts.append(nxt[0])
                    slot.handoff_s += time.monotonic() - nxt[1]
                    slot.handoff_n += 1
                yield "".join(parts)
                if done:
                    if slot.error is not None:
                        raise RuntimeError(slot.error)
                    return
        finally:
            slot.cancelled.set()
            # Once a stream, however it ends (finished, failed, closed
            # by a client that left): its hand-off joins the counters.
            with self._depth_mu:
                self._handoff_s += slot.handoff_s
                self._n_deltas += slot.handoff_n

    # graftcheck: lock-ok drains scheduler-owned state only AFTER _thread.join — the owner is gone
    def stop(self) -> None:
        self._closed.set()
        self._admit_q.put(None)    # wake the loop if parked
        self._promote_work.put(None)   # wake the promotion worker
        self._thread.join(timeout=10.0)
        # Unblock every consumer: in-flight slots and never-admitted
        # requests would otherwise hang forever on out_q.get().
        for i, s in enumerate(self._slots):
            if s is not None:
                s.finish()
                self._slots[i] = None
        for s in self._waiting:
            s.finish()
        self._waiting = []
        for s in self._admit_carry:
            s.finish()
        self._admit_carry = []
        pc, self._prefill_carry = self._prefill_carry, None
        if pc is not None:
            for s in pc.chunk:
                s.finish()
        while True:
            try:
                s = self._admit_q.get_nowait()
            except queue.Empty:
                break
            if isinstance(s, _WarmupJob):
                # Waiter unblocks AND sees the failure — returning
                # success for a warmup that never ran would hide
                # uncompiled serving programs.
                s.err = RuntimeError("scheduler stopped before warmup ran")
                s.done.set()
            elif s is not None:
                s.finish()

    # -- scheduler thread ----------------------------------------------------

    def _loop(self) -> None:
        """Serving loop with one-tick pipelining: tick N+1 is dispatched
        BEFORE tick N's tokens are read back, so the
        device->host readback of N overlaps N+1's device compute instead
        of serialising with it. The device carries its own next-token
        feed (_next_dev), so the host's one-tick lag only delays
        streaming/stop detection by one tick; a stopped row decodes one
        extra token whose write the release path already tolerates (it
        lands beyond the trusted length or in the garbage page).
        Speculative ticks stay synchronous — drafting needs the current
        ids — and flush the pipeline first."""
        pending: Optional[tuple] = None   # (toks_dev, snapshot, K)
        while not self._closed.is_set():
            it_start = time.monotonic()
            self._loop_beat = it_start
            self._loop_iter += 1
            phase = self._phase
            phase.mark_iteration()
            warm0 = phase.inclusive("warmup")
            try:
                # The outermost mark: what the iteration does between the
                # marks inside it is the phase "other".
                with phase("other"):
                    self._drain_stall_reset()
                    self._drain_park_all()
                    # Admission inside the same recovery envelope as decode: an
                    # unexpected admission-path error must fail requests and
                    # reset, never kill the scheduler thread (which would leave
                    # every future submit() hanging on a dead queue).
                    # "plan" is whatever _admit_pending does outside the
                    # parts marked inside it: reservations, matching,
                    # grouping, widths.
                    with phase("admit"), phase("plan"):
                        self._admit_pending(block=not self._any_active()
                                            and pending is None
                                            and self._prefill_carry is None)
                    if (self._waiting and self._prefill_carry is None
                            and any(s is None for s in self._slots)):
                        # Admission has run and a request still waits for
                        # pages beside a free row (a ladder in progress
                        # holds rows that are not in _slots yet, and
                        # admits nothing: not counted).
                        self._n_page_starved_iters += 1
                    if self._closed.is_set():
                        return
                    if self._prefix is not None:
                        self._drain_promotions()
                    self._tier_sweep()
                    if self._prefill_carry is not None:
                        # Chunked admission in progress: ONE continuation
                        # chunk per loop iteration — the decode tick below
                        # runs between chunks, so live streams stall at
                        # most one chunk's compute per iteration (the
                        # bounded-stall contract).
                        self._prefill_step()
                    if not self._any_active():
                        # No live decodes: the stall gauge must not bridge
                        # this gap — a cold admission after idle time would
                        # otherwise book the whole idle stretch as
                        # decode_stall_ms (it stalled nobody).
                        self._ledger.rest()
                        if pending is not None:
                            self._process_tick(*pending)
                            pending = None
                        elif self._promote_q and self._prefill_carry is None:
                            # Idle: build one deferred prefix promotion
                            # (compile + prefill happen with no live streams
                            # to stall).
                            self._build_promotion()
                        continue
                    # Flush the pipeline for a speculative tick only when one
                    # can actually run this tick (drafting needs current ids)
                    # — while the acceptance throttle has EVERY source backed
                    # off, plain ticks keep their pipelining.
                    if self.spec_k and not self._sources:
                        self._ensure_sources()   # spec_k toggled 0 -> K
                    spec_allowed = (self._spec_sources_allowed()
                                    if self.spec_k else {})
                    spec_now = bool(self.spec_k) and any(spec_allowed.values())
                    if spec_now:
                        if pending is not None:
                            self._process_tick(*pending)
                            pending = None
                        if not self._any_active():
                            continue
                        with phase("decode_dispatch"):
                            # Its reads and per-row work mark their own
                            # phases inside; what is left is the dispatch.
                            spec_done = self._spec_tick(spec_allowed)
                        if spec_done:
                            continue
                    # Fused K-step ticks ride the same one-tick-deep pipeline
                    # as plain ones: tick t+1 (up to K steps) is enqueued
                    # BEFORE tick t's K-token burst is drained, so the
                    # readback/stream work overlaps device compute. K=1 while
                    # speculation is live this iteration (a fused tick would
                    # emit K tokens with no draft chance).
                    with phase("decode_dispatch"):
                        new = self._dispatch_tick(
                            allow_fuse=not spec_now,
                            inflight=pending[2] if pending is not None else 0)
                    if pending is not None:
                        self._process_tick(*pending)
                    pending = new
                    if (self._promote_q and self._n_decode_ticks
                            - self._last_promote_tick > _PROMOTE_EVERY_TICKS):
                        # Sustained load never goes idle — without this, hot
                        # templates would never get their prefix built
                        # exactly when it pays most. One bounded stall per
                        # build, amortised over hundreds of ticks.
                        self._build_promotion()
            except Exception:   # noqa: BLE001 — fail requests, keep serving
                log.exception("decode tick failed; failing in-flight requests")
                pending = None
                self._fail_all_and_reset()
            finally:
                self._loop_s += time.monotonic() - it_start
                self._watchdog(it_start,
                               phase.inclusive("warmup") - warm0)

    # graftcheck: runs-on _loop
    def _watchdog(self, it_start: float, warm_s: float = 0.0) -> None:
        """Loop-iteration watchdog: an iteration past the budget (a
        mid-serving compile, a wedged device call, a host stall) updates
        the ``loop_stall_ms`` max gauge and logs ONCE per stall episode
        — enter and recover each log one line, never one per iteration
        (a minutes-long warmup would otherwise spam hundreds). Blocked-
        idle iterations cap at the admission poll timeout (~0.2 s), so
        idleness never reads as a stall. Nor does warm-up: until the
        scheduler is ready, the ``warm_s`` seconds this iteration spent
        inside warm-up jobs (every cold compile is one) are not the
        loop's, and an iteration that is over budget only because of
        them enters no episode, dumps nothing and leaves the gauges at
        0 = never stalled. Once ready, a job's time counts like any
        other (a background warm-up then stalls live streams).

        A compile heard during an iteration that began after a warm-up
        had finished (the process's compile clock counted on: a program
        the warm-up list missed, a promotion's build) leaves a
        ``compile`` event in the flight ring first, so the dump of a
        stalled iteration names it."""
        clock = compile_clock()
        heard, heard_s = self._compiles_heard
        if clock.events != heard:
            now_s = clock.seconds
            if self._warmup_done_at and self._warmup_done_at < it_start:
                self._flight.note("compile", self._loop_iter,
                                  n=clock.events - heard,
                                  seconds=round(now_s - heard_s, 3))
            self._compiles_heard = (clock.events, now_s)
        budget = self.loop_budget_ms
        if not budget:
            return
        dur_ms = (time.monotonic() - it_start) * 1e3
        if warm_s and self._warmup_done_at is None:
            dur_ms -= warm_s * 1e3
        if dur_ms > budget:
            if dur_ms > self._loop_stall_ms:
                self._loop_stall_ms = dur_ms
            # Last-episode gauge (round 15): re-stamped every over-
            # budget iteration, so after recovery it holds the LAST
            # episode's wall instead of the all-time max the
            # ``loop_stall_ms`` high-water series keeps.
            self._loop_stall_last_ms = dur_ms
            if not self._loop_stalled:
                self._loop_stalled = True
                log.warning("scheduler loop iteration took %.0f ms "
                            "(budget %.0f ms)", dur_ms, budget)
                # Flight-recorder dump at episode ENTRY: the ring still
                # holds the events of the iteration that stalled — the
                # stall marker shares its ``it`` with the event that
                # caused it, which is the whole diagnosis.
                self._flight.note("stall_enter", self._loop_iter,
                                  over_ms=round(dur_ms, 1),
                                  budget_ms=self.loop_budget_ms,
                                  phase=self._phase.slowest)
                try:
                    path = self._flight.dump("watchdog_stall")
                    log.warning("flight recorder dumped to %s", path)
                except OSError as e:
                    log.warning("flight-recorder dump failed: %s", e)
        elif self._loop_stalled:
            self._loop_stalled = False
            self._flight.note("stall_recover", self._loop_iter,
                              last_ms=round(dur_ms, 1))
            log.info("scheduler loop recovered (last iteration %.0f ms)",
                     dur_ms)

    # graftcheck: lock-ok advisory gauge — torn reads of the loop-owned float are harmless for /metrics
    def _live_loop_stall_ms(self) -> float:
        """Completed-iteration max (``_loop_stall_ms``) folded with the
        in-flight iteration's age when over budget — readable from any
        thread, so a permanently wedged iteration is visible on /metrics
        WHILE it is wedged."""
        stall = self._loop_stall_ms
        beat, budget = self._loop_beat, self.loop_budget_ms
        # A cleanly stopped scheduler's stale beat is not a stall; a
        # DEAD loop thread on a live scheduler very much is.
        if (beat is not None and budget and not self._closed.is_set()
                and not (self._warm_iter == self._loop_iter
                         and self._warmup_done_at is None)):
            cur = (time.monotonic() - beat) * 1e3
            if cur > budget:
                stall = max(stall, cur)
        return stall

    def _any_active(self) -> bool:
        return any(s is not None for s in self._slots)

    def _free_rows(self) -> list[int]:
        return [i for i, s in enumerate(self._slots) if s is None]

    def _collect_pending(self, limit: int, block: bool) -> list[_Slot]:
        """Pull up to ``limit`` admittable requests off the queue; tokenize
        and budget them host-side. Blocks only when the batch is empty."""
        out: list[_Slot] = []
        while len(out) < limit:
            try:
                # Once the first request is in hand, keep draining through a
                # short arrival gap (3 ms): a concurrent burst lands in ONE
                # big-chunk admission instead of fragmenting into serial
                # small chunks; a lone request pays at most the gap.
                if block and not out:
                    # Nothing live and nothing pending: the one place
                    # the loop waits for work.
                    with self._phase("idle"):
                        slot = self._admit_q.get(timeout=0.2)
                elif out:
                    # A wait by design, not work: its own part, so that
                    # "collect" off the CPU is a wait nobody asked for.
                    with self._phase("gap"):
                        slot = self._admit_q.get(timeout=0.003)
                else:
                    slot = self._admit_q.get_nowait()
            except queue.Empty:
                break
            if isinstance(slot, _WarmupJob):
                # One job per admission round: warmup is split into one
                # job per compiled program precisely so decode ticks and
                # admissions run in between — draining them all here
                # would stall every live stream for the whole ladder.
                self._warm_iter = self._loop_iter
                self._n_warmup_jobs += 1
                with self._phase("warmup"):
                    slot.run()
                break
            if slot is None or self._closed.is_set():
                if slot is not None:
                    # Already dequeued: stop()'s drain can no longer see it,
                    # so finish it here or its consumer hangs forever.
                    slot.finish()
                break
            if slot.cancelled.is_set():
                slot.depart()        # consumer gone before admission
                continue
            if self._expired(slot):
                continue
            # Shared Ollama admission contract (context prepend/BOS rules,
            # num_ctx clamp, tail truncation, num_predict<=0 semantics) —
            # backend.normalize_request, one copy for every engine. An
            # out-of-vocab context id must fail THIS request cleanly, not
            # corrupt logits (XLA clamps silently) or blow up the whole
            # admission chunk it gets batched into.
            # (NB: must not shadow ``limit`` — doing so once made a >limit
            # burst over-collect past the free rows and crash admission.)
            try:
                ids, slot.max_new, ctx_limit = normalize_request(
                    self.tokenizer, self.config.vocab_size, self.max_seq,
                    slot.req, min_bucket=_MIN_BUCKET)
            except ValueError as e:
                slot.fail(str(e))
                continue
            slot.prompt_ids = ids
            slot.ctx_budget = ctx_limit
            if slot.stats is not None:
                slot.stats.prompt_tokens = len(ids)
            if self._prefix is not None:
                # Auto-promotion: a prompt head seen promote_after times
                # becomes a cached prefix. Building one costs a prefill
                # dispatch plus (on TPU) possible compiles — seconds that
                # must NOT land inside this request's admission, so the
                # build is deferred to an idle tick (_loop). Bounded,
                # deduped queue: promotion is an optimization, dropping
                # one under pressure is free.
                head = self._prefix.observe(ids)
                if (head is not None and len(self._promote_q) < 8
                        # A QUEUED (or in-flight) longer head covers this
                        # one the same way a built entry would (match()
                        # takes the longest) — building the shorter
                        # grain too would be pure compile/prefill waste.
                        and not any(len(q) >= len(head)
                                    and q[: len(head)] == head
                                    for q in list(self._promote_q)
                                    + list(self._promote_pending))):
                    self._promote_q.append(head)
            out.append(slot)
        return out

    def _serving_bucket(self, prompt_len: int) -> int:
        """Admission bucket for a prompt: the power-of-two bucket, rounded
        UP to the smallest warmup-compiled bucket that fits (compiling a
        fresh small-bucket program mid-serving would stall every stream
        for tens of seconds on TPU). Prompts longer than every warmed
        bucket keep their own bucket and compile lazily (logged)."""
        b = _bucket(prompt_len, self.max_seq)
        warmed = getattr(self, "_warmed_buckets", None)
        if warmed:
            for w in warmed:
                if w >= b:
                    return w
            log.info("prompt bucket %d exceeds warmed buckets %s; compiling "
                     "lazily", b, warmed)
        return b

    def _expired(self, slot: _Slot) -> bool:
        """Fail a request that outlived the admission deadline (it never
        reached a row; the client has almost certainly given up)."""
        if self.queue_timeout_s is None:
            return False
        done_at = getattr(self, "_warmup_done_at", 0.0)
        if done_at is None:
            return False          # warmup still compiling: boot, not load
        age = time.monotonic() - max(slot.req.arrival_time, done_at)
        if age <= self.queue_timeout_s:
            return False
        log.warning("request waited %.1fs for admission (deadline %.1fs); "
                    "failing it", age, self.queue_timeout_s)
        slot.fail(f"not admitted within {self.queue_timeout_s:.0f}s "
                  "(server at capacity)")
        self._n_expired += 1
        return True

    def reset_decode_stall(self, timeout_s: float = 30.0) -> None:
        """Zero the decode_stall_ms max gauge (and its timestamp), so a
        phased workload can attribute the max decode-tick gap to its OWN
        phase instead of reading a lifetime max. The gauge is _loop-owned, so
        the reset executes ON the scheduler thread — via an event the
        loop services at the top of EVERY iteration, not a queued
        admission job: the admit queue only drains when admission can
        run, so a job would starve (and this call would time out) behind
        a full batch of long generations or an in-flight prefill carry.
        Returns once the loop has performed the reset."""
        if self._closed.is_set():
            raise RuntimeError("scheduler is stopped")
        self._stall_reset_ack.clear()
        self._stall_reset_req.set()
        if not self._stall_reset_ack.wait(timeout=timeout_s):
            raise TimeoutError("reset_decode_stall: scheduler loop did "
                               "not service the reset")

    # graftcheck: runs-on _loop
    def _drain_stall_reset(self) -> None:
        """Service a pending reset_decode_stall handshake (scheduler
        thread, every loop iteration — even when admission cannot
        run)."""
        if self._stall_reset_req.is_set():
            self._stall_reset_req.clear()
            self._ledger.reset_stall()
            self._stall_reset_ack.set()

    def park_all(self, timeout_s: float = 30.0,
                 key: Optional[str] = None) -> None:
        """Park RESIDENT sessions to host RAM (HTTP threads; the
        migration pre-step — a parked payload is the only exportable
        form). ``key`` limits the park to ONE session (the per-key
        export path must not demote every other live conversation to a
        wake it never needed); None parks everything (the drain path).
        Resident pages are device state only the scheduler loop may
        gather, so this is the same event handshake as
        :meth:`reset_decode_stall`: the loop services it at the top of
        every iteration, even mid-backlog. No-op without a tier. Returns
        once the loop has ack'd."""
        if self._tier is None:
            return
        if self._closed.is_set():
            raise RuntimeError("scheduler is stopped")
        self._park_all_key = key
        self._park_all_ack.clear()
        self._park_all_req.set()
        if not self._park_all_ack.wait(timeout=timeout_s):
            raise TimeoutError("park_all: scheduler loop did not service "
                               "the park request")

    # graftcheck: runs-on _loop
    def _drain_park_all(self) -> None:
        """Service a pending park_all handshake (scheduler thread). The
        ack sets in a finally so a park failure — which rides the loop's
        recovery envelope — can never strand the HTTP caller on an
        un-ack'd event."""
        if not self._park_all_req.is_set():
            return
        self._park_all_req.clear()
        key = self._park_all_key
        try:
            if self._tier is not None:
                for sess in self._tier.park_candidates(force=True):
                    if key is None or sess.key == key:
                        self._park_session(sess)
        finally:
            self._park_all_ack.set()

    # -- live session migration (serve/router.py over /admin/session) --------
    # List/export/forget/import run on HTTP threads: they touch only the
    # tier index and immutable parked host payloads, never device
    # buffers (export of a resident session parks it first through the
    # park_all handshake above).

    def session_list(self) -> Optional[dict]:
        """{key: meta} of open sessions, or None when tiering is off
        (the front answers 501 so the router skips this replica)."""
        if self._tier is None:
            return None
        return self._tier.sessions_meta()

    def session_export(self, key: str) -> Optional[bytes]:
        """Serialized session payload for a peer replica, or None when
        unknown. A still-resident session is parked first (the loop owns
        that copy); the session is retained either way — the router
        forgets it on the destination's ack, never before."""
        if self._tier is None:
            return None
        meta = self._tier.sessions_meta().get(key)
        if meta is None:
            return None
        if not meta["parked"]:
            self.park_all(key=key)      # only THIS session demotes
        return self._tier.export_payload(key)

    def session_import(self, data: bytes):
        """Install a peer replica's exported session (parked tier).
        Returns the adopted SessionKV, or None on a malformed payload,
        a geometry/dtype mismatch with this engine's pool, or a fresher
        resident local copy. The next prompt extending the session's
        tokens wakes it through the ordinary verify-shaped wake
        admission — byte-identical to never having migrated."""
        if self._tier is None:
            return None
        failpoint("serve.kv_tier.import")
        from .kv_tier import deserialize_session
        sess = deserialize_session(data)
        if sess is None or not self._session_payload_compatible(sess):
            return None
        if not self._tier.adopt(sess):
            log.info("session %s import skipped: a resident local copy "
                     "is fresher", sess.key)
            return None
        return sess

    def session_forget(self, key: str) -> Optional[bool]:
        """Migration ack: drop the (parked) source copy. None = no tier;
        False = unknown key or still resident."""
        if self._tier is None:
            return None
        return self._tier.forget(key)

    # -- disaggregated prefill (serve/disagg.py round 14) --------------------

    def prefill_park(self, req: GenerateRequest,
                     timeout_s: float = 10.0) -> Optional[dict]:
        """Run this request's prefill WITHOUT sampling its first real
        token, retaining the KV as an exportable session — the prefill
        side of the prefill→decode handoff (serve/disagg.py).

        The prompt is normalized EXACTLY like the real admission
        (context prepend, BOS rule, num_ctx clamp, tail truncation —
        the decode replica normalizes the same request to the same
        ids), then a one-token throwaway generation runs over
        ``ids[:-1]``: the retained session is "prompt + all generated
        but the last" = ``ids[:-1]`` precisely, so the destination's
        wake admission forwards the final prompt token and samples the
        conversation's FIRST real token there, as the first draw of its
        own per-request seeded RNG — byte-identical to a
        never-disaggregated run. The throwaway token is discarded here
        and its sample never touches the real request's RNG.

        Returns ``{"key", "len", "parked"}``, or None when this request
        cannot ride the handoff (no tier, prompt too short to leave a
        suffix token, anonymous below the HEAD_GRAIN index grain, or
        the prefill itself failed — the caller routes the request
        un-disaggregated). OverloadError propagates: a saturated
        prefill replica sheds exactly like any admission."""
        if self._tier is None:
            return None
        from .kv_tier import HEAD_GRAIN, head_key
        try:
            ids, _, _ = normalize_request(
                self.tokenizer, self.config.vocab_size, self.max_seq,
                req, min_bucket=_MIN_BUCKET)
        except ValueError:
            return None
        if len(ids) < 2:
            return None             # no suffix token would remain
        if req.session:
            key = f"sid:{req.session}"
        elif len(ids) - 1 >= HEAD_GRAIN:
            # The shared anonymous index derivation — the throwaway's
            # prompt ids share the head (ids[:-1][:HEAD_GRAIN] ==
            # ids[:HEAD_GRAIN] because len(ids)-1 >= HEAD_GRAIN here),
            # so the retained session gets exactly this key.
            key = head_key(ids)
        else:
            return None             # anonymous and unindexable
        throwaway = GenerateRequest(
            prompt="", model=req.model,
            options=GenerateOptions(max_tokens=1, temperature=0.0,
                                    seed=1, num_ctx=req.options.num_ctx),
            context=tuple(ids[:-1]), session=req.session)
        try:
            for _ in self.submit(throwaway):
                pass
        except OverloadError:
            raise
        except RuntimeError as e:
            log.warning("disagg prefill failed (%s); the request runs "
                        "un-disaggregated", e)
            return None
        # Retention runs on the scheduler loop as the slot finishes —
        # AFTER the stream above closes. Bounded wait, not an event
        # handshake: the tier index is the single source of truth and
        # the loop is already obligated to finish the slot. The wait is
        # satisfied only by the FRESH retention (length exactly
        # len(ids)-1): a pre-existing session under the same key (a
        # prior turn whose affinity entry aged out of the router's LRU)
        # must not be exported as if it were this prefill — the
        # follow-up would ride a stale payload and re-prefill the delta
        # as admission work on the decode side.
        want_len = len(ids) - 1
        deadline = time.monotonic() + timeout_s
        while time.monotonic() < deadline:
            meta = self._tier.sessions_meta().get(key)
            if meta is not None and meta["len"] == want_len:
                return {"key": key, "len": meta["len"],
                        "parked": meta["parked"]}
            time.sleep(0.01)
        log.warning("disagg prefill for %s finished but the fresh "
                    "session (len %d) never appeared in the tier index",
                    key, want_len)
        return None

    def _session_payload_compatible(self, sess) -> bool:
        """May this imported payload scatter into OUR pool? Shape/dtype
        checks against the live cache — replicas in a fleet are
        identical by construction (the router's assumption), but a
        mis-aimed import from a differently-configured engine must
        reject cleanly, not crash the wake dispatch. Reads only shape
        metadata (valid even across the loop's donation rebinds)."""
        try:
            arrays, span = sess.host
            k = arrays[0]
            if len(arrays) != 4 or sess.length > self.max_seq:
                return False
            if any(t < 0 or t >= self.config.vocab_size
                   for t in sess.tokens):
                return False
            cache_k = self._cache.k
            if (k.shape[0] != cache_k.shape[0]
                    or k.shape[2:] != cache_k.shape[2:]
                    or str(k.dtype) != str(cache_k.dtype)):
                return False
            if (arrays[2] is not None) != bool(self.kv_quant):
                return False
            if span > k.shape[1] or span > self._cache.max_pages_per_row:
                return False
            if -(-sess.length // self.page_size) > span:
                return False
            return True
        except Exception:   # noqa: BLE001 — incompatible payloads reject
            return False

    # -- grafttrace (obs/): span store wiring + the flight surface -----------

    def set_trace_store(self, store) -> None:
        """Install the owning server's span store (obs/trace.py). One
        atomic reference assignment at wiring time, before traffic —
        the loop reads the reference per use, so None stays "off"."""
        self._trace = store

    # graftcheck: lock-ok advisory read — the loop-iteration int tags tier events best-effort; a torn int read is impossible
    def _tier_event(self, kind: str, **meta) -> None:
        """KVTier observer -> flight ring (park/wake/adopt/forget/evict
        — adopt/forget arrive from HTTP threads, hence the advisory
        iteration read)."""
        self._flight.note(f"tier_{kind}", self._loop_iter, **meta)

    def flight_snapshot(self) -> list:
        """The event ring, oldest first (GET /admin/trace surface)."""
        return self._flight.snapshot()

    def flight_dump(self, reason: str = "on_demand") -> str:
        """Dump the ring to its JSON file; returns the path (the
        POST /admin/trace/dump surface)."""
        return self._flight.dump(reason)

    # graftcheck: lock-ok advisory gauges — torn reads of loop-owned ints are harmless for /metrics
    def metrics_snapshot(self) -> dict[str, float]:
        """Serving-plane gauges/counters for the /metrics endpoint (read
        from any thread; values are monotonically-written ints and
        len()s, so torn reads are harmless)."""
        ph, led, clock = self._phase, self._ledger, compile_clock()
        out = {
            "serve_batch_occupancy": sum(s is not None for s in self._slots),
            "serve_batch_slots": self.num_slots,
            # Per-model weight stream (stamped at build): stored bytes of
            # the fused tree, labeled with the quantization mode — the
            # decode-step bandwidth denominator, and the operator's
            # check that SERVE_QUANT actually halved the footprint.
            f'model_weight_bytes{{quant="{self._quant_mode or "bf16"}"}}':
                self._weight_bytes,
            "serve_queue_depth": (self._admit_q.qsize() + len(self._waiting)
                                  + len(self._admit_carry)),
            "serve_admitted_total": self._n_admitted,
            "serve_decode_ticks_total": self._n_decode_ticks,
            "serve_queue_expired_total": self._n_expired,
            # Overload shedding (queue_max): requests fast-failed with
            # OverloadError/503 at submit instead of burning the queue
            # deadline. 0 on a healthy deployment; a nonzero RATE is the
            # capacity alarm.
            "requests_shed_total": self._n_shed,
            # Draining (replica-router drain hook): 1 while this
            # scheduler refuses new sessions so a balancer can retire
            # the replica gracefully; in-flight streams still finish.
            "serve_draining": int(self._draining.is_set()),
            # Loop watchdog (loop_budget_ms): max over-budget iteration
            # wall observed — including the CURRENT iteration if it is
            # already past budget (a hung device call must show up in
            # the gauge while it hangs, not after it ends). 0 = never
            # stalled.
            "loop_stall_ms": round(self._live_loop_stall_ms(), 3),
            # Last COMPLETE stall episode's over-budget wall (round 15):
            # unlike the high-water max above, this one re-stamps per
            # episode — after recovery it stops growing, so a dashboard
            # can tell "stalling now" from "stalled once at boot".
            "loop_stall_last_ms": round(self._loop_stall_last_ms, 3),
            # Flight-recorder dumps written (watchdog stall, reset, or
            # /admin/trace/dump) — a nonzero rate is the incident alarm.
            "serve_flight_dumps_total": self._flight.dumps_total(),
            # Fused multi-step decode (decode_fuse_max): dispatches that
            # fused K>1 steps, total fused steps, and the realized mean K
            # over every decode dispatch — the lever that closes the
            # wall/device gap, so its engagement is first-class.
            "decode_fused_ticks_total": self._n_fused_ticks,
            "decode_fused_steps_total": self._n_fused_steps,
            # Realized K over NON-speculative decode dispatches: spec
            # ticks have no fused-K and counting them would dilute the
            # mean below 1 on spec-enabled deployments (reading as
            # "fusion disengaged" when it is not).
            "decode_fused_mean_k": round(
                self._n_decode_steps
                / max(1, self._n_decode_ticks - self._n_spec_ticks), 3),
            # Wall vs device decode step: wall is the live p50 of
            # steady-state per-step dispatch intervals; device is the
            # warmup probe's two-point solve (_probe_device_step).
            "decode_wall_ms": round(
                led.wall_hist.percentile(50) or 0.0, 4),
            "decode_device_ms": self._decode_device_ms,
            # Chunked prefill (SERVE_PREFILL_CHUNK): continuation-chunk
            # dispatches, the max decode-tick gap attributable to
            # admission (bounded by one chunk's compute when chunking is
            # on — the stall the tentpole bounds), and client-perceived
            # inter-token latency percentiles.
            "prefill_chunks_total": self._n_prefill_chunks,
            # ... and those of them whose offset lay at or past every
            # row's suffix length: dispatched (the ladder runs its whole
            # bucket) and computing nothing.
            "serve_prefill_chunks_padded_total":
                self._n_prefill_chunks_padded,
            "decode_stall_ms": round(led.stall_ms, 3),
            "inter_token_p50_ms": round(
                self._tbt_hist.percentile(50) or 0.0, 4),
            "inter_token_p95_ms": round(
                self._tbt_hist.percentile(95) or 0.0, 4),
            # Loop phases (obs/phase.py): the loop thread's wall by
            # phase, as self times, so the eight add up to
            # serve_loop_seconds_total ("other" is the iteration outside
            # every other mark). A phase's seconds hold its parts'
            # (below): they mean what they meant before there were
            # parts. Window differences of these are the benchmark's
            # host_ms_per_step, device_wait_share and admit_host_ms.
            "serve_loop_idle_seconds_total": ph.total("idle"),
            "serve_loop_admit_seconds_total": ph.total("admit"),
            "serve_loop_prefill_chunk_seconds_total":
                ph.total("prefill_chunk"),
            "serve_loop_decode_dispatch_seconds_total":
                ph.total("decode_dispatch"),
            "serve_loop_readback_seconds_total": ph.total("readback"),
            "serve_loop_stream_seconds_total": ph.total("stream"),
            "serve_loop_warmup_seconds_total": ph.total("warmup"),
            "serve_loop_other_seconds_total": ph.total("other"),
            # The thread's CPU seconds (time.thread_time), read in one
            # iteration of obs/phase.CPU_EVERY, and the wall seconds of
            # those same marks: 1 - cpu / cpu_wall is the share of a
            # phase the thread was off the CPU, which in a phase that
            # is Python on the host is a wait for the interpreter lock
            # or for the OS (loop_offcpu_share).
            "serve_loop_idle_cpu_seconds_total": ph.cpu_total("idle"),
            "serve_loop_idle_cpu_wall_seconds_total":
                ph.cpu_wall_total("idle"),
            "serve_loop_admit_cpu_seconds_total": ph.cpu_total("admit"),
            "serve_loop_admit_cpu_wall_seconds_total":
                ph.cpu_wall_total("admit"),
            "serve_loop_prefill_chunk_cpu_seconds_total":
                ph.cpu_total("prefill_chunk"),
            "serve_loop_prefill_chunk_cpu_wall_seconds_total":
                ph.cpu_wall_total("prefill_chunk"),
            "serve_loop_decode_dispatch_cpu_seconds_total":
                ph.cpu_total("decode_dispatch"),
            "serve_loop_decode_dispatch_cpu_wall_seconds_total":
                ph.cpu_wall_total("decode_dispatch"),
            "serve_loop_readback_cpu_seconds_total": ph.cpu_total("readback"),
            "serve_loop_readback_cpu_wall_seconds_total":
                ph.cpu_wall_total("readback"),
            "serve_loop_stream_cpu_seconds_total": ph.cpu_total("stream"),
            "serve_loop_stream_cpu_wall_seconds_total":
                ph.cpu_wall_total("stream"),
            "serve_loop_warmup_cpu_seconds_total": ph.cpu_total("warmup"),
            "serve_loop_warmup_cpu_wall_seconds_total":
                ph.cpu_wall_total("warmup"),
            "serve_loop_other_cpu_seconds_total": ph.cpu_total("other"),
            "serve_loop_other_cpu_wall_seconds_total":
                ph.cpu_wall_total("other"),
            # The parts of a phase (obs/phase.PARTS_OF): self wall
            # seconds, self CPU seconds and marks of each, so that "ms a
            # launch" is a ratio of two counters (launch_ms).
            "serve_loop_admit_collect_seconds_total":
                ph.seconds("admit.collect"),
            "serve_loop_admit_collect_cpu_seconds_total":
                ph.cpu("admit.collect"),
            "serve_loop_admit_collect_cpu_wall_seconds_total":
                ph.cpu_wall("admit.collect"),
            "serve_loop_admit_collect_marks_total": ph.marks("admit.collect"),
            "serve_loop_admit_gap_seconds_total":
                ph.seconds("admit.gap"),
            "serve_loop_admit_gap_cpu_seconds_total":
                ph.cpu("admit.gap"),
            "serve_loop_admit_gap_cpu_wall_seconds_total":
                ph.cpu_wall("admit.gap"),
            "serve_loop_admit_gap_marks_total": ph.marks("admit.gap"),
            "serve_loop_admit_plan_seconds_total":
                ph.seconds("admit.plan"),
            "serve_loop_admit_plan_cpu_seconds_total":
                ph.cpu("admit.plan"),
            "serve_loop_admit_plan_cpu_wall_seconds_total":
                ph.cpu_wall("admit.plan"),
            "serve_loop_admit_plan_marks_total": ph.marks("admit.plan"),
            "serve_loop_admit_build_seconds_total":
                ph.seconds("admit.build"),
            "serve_loop_admit_build_cpu_seconds_total":
                ph.cpu("admit.build"),
            "serve_loop_admit_build_cpu_wall_seconds_total":
                ph.cpu_wall("admit.build"),
            "serve_loop_admit_build_marks_total": ph.marks("admit.build"),
            "serve_loop_admit_upload_seconds_total":
                ph.seconds("admit.upload"),
            "serve_loop_admit_upload_cpu_seconds_total":
                ph.cpu("admit.upload"),
            "serve_loop_admit_upload_cpu_wall_seconds_total":
                ph.cpu_wall("admit.upload"),
            "serve_loop_admit_upload_marks_total": ph.marks("admit.upload"),
            "serve_loop_admit_launch_seconds_total":
                ph.seconds("admit.launch"),
            "serve_loop_admit_launch_cpu_seconds_total":
                ph.cpu("admit.launch"),
            "serve_loop_admit_launch_cpu_wall_seconds_total":
                ph.cpu_wall("admit.launch"),
            "serve_loop_admit_launch_marks_total": ph.marks("admit.launch"),
            "serve_loop_prefill_chunk_build_seconds_total":
                ph.seconds("prefill_chunk.build"),
            "serve_loop_prefill_chunk_build_cpu_seconds_total":
                ph.cpu("prefill_chunk.build"),
            "serve_loop_prefill_chunk_build_cpu_wall_seconds_total":
                ph.cpu_wall("prefill_chunk.build"),
            "serve_loop_prefill_chunk_build_marks_total":
                ph.marks("prefill_chunk.build"),
            "serve_loop_prefill_chunk_upload_seconds_total":
                ph.seconds("prefill_chunk.upload"),
            "serve_loop_prefill_chunk_upload_cpu_seconds_total":
                ph.cpu("prefill_chunk.upload"),
            "serve_loop_prefill_chunk_upload_cpu_wall_seconds_total":
                ph.cpu_wall("prefill_chunk.upload"),
            "serve_loop_prefill_chunk_upload_marks_total":
                ph.marks("prefill_chunk.upload"),
            "serve_loop_prefill_chunk_launch_seconds_total":
                ph.seconds("prefill_chunk.launch"),
            "serve_loop_prefill_chunk_launch_cpu_seconds_total":
                ph.cpu("prefill_chunk.launch"),
            "serve_loop_prefill_chunk_launch_cpu_wall_seconds_total":
                ph.cpu_wall("prefill_chunk.launch"),
            "serve_loop_prefill_chunk_launch_marks_total":
                ph.marks("prefill_chunk.launch"),
            "serve_loop_decode_dispatch_upload_seconds_total":
                ph.seconds("decode_dispatch.upload"),
            "serve_loop_decode_dispatch_upload_cpu_seconds_total":
                ph.cpu("decode_dispatch.upload"),
            "serve_loop_decode_dispatch_upload_cpu_wall_seconds_total":
                ph.cpu_wall("decode_dispatch.upload"),
            "serve_loop_decode_dispatch_upload_marks_total":
                ph.marks("decode_dispatch.upload"),
            "serve_loop_decode_dispatch_launch_seconds_total":
                ph.seconds("decode_dispatch.launch"),
            "serve_loop_decode_dispatch_launch_cpu_seconds_total":
                ph.cpu("decode_dispatch.launch"),
            "serve_loop_decode_dispatch_launch_cpu_wall_seconds_total":
                ph.cpu_wall("decode_dispatch.launch"),
            "serve_loop_decode_dispatch_launch_marks_total":
                ph.marks("decode_dispatch.launch"),
            "serve_loop_stream_launch_seconds_total":
                ph.seconds("stream.launch"),
            "serve_loop_stream_launch_cpu_seconds_total":
                ph.cpu("stream.launch"),
            "serve_loop_stream_launch_cpu_wall_seconds_total":
                ph.cpu_wall("stream.launch"),
            "serve_loop_stream_launch_marks_total": ph.marks("stream.launch"),
            # Launches that feed the device, by kind, and those that
            # found the last launch's output already there: the device
            # had run dry (_note_launch; launch_starved_share).
            "serve_launch_admit_total": self._n_launch["admit"],
            "serve_launch_admit_starved_total":
                self._n_starved["admit"],
            "serve_launch_prefill_chunk_total": self._n_launch["prefill_chunk"],
            "serve_launch_prefill_chunk_starved_total":
                self._n_starved["prefill_chunk"],
            "serve_launch_decode_total": self._n_launch["decode"],
            "serve_launch_decode_starved_total":
                self._n_starved["decode"],
            # The loop's hand-off to the HTTP threads: seconds from a
            # delta's put to its dequeue, summed over the streams that
            # have ended, and their deltas (stream_handoff_ms).
            "serve_stream_handoff_seconds_total": self._handoff_s,
            "serve_stream_deltas_total": self._n_deltas,
            "serve_loop_seconds_total": self._loop_s,
            "serve_loop_iterations_total": self._loop_iter,
            "serve_page_starved_iterations_total":
                self._n_page_starved_iters,
            # Counts at the dispatch sites: admissions started (with
            # serve_admitted_total: requests per admission) and the
            # rows of their programs (1 - admitted / rows: the share
            # of rows that were dummy entries), prompt positions that
            # had to be computed against the positions the padded
            # programs computed, live rows x steps over the decode
            # dispatches.
            "serve_admit_batches_total": self._n_admit_batches,
            # Host-to-device transfers the admission path issued: one
            # an admission, none for a ladder's chunks (over batches +
            # prefill_chunks_total: the transfers a dispatch).
            "serve_admit_uploads_total": self._n_admit_uploads,
            "serve_admit_pair_dispatches_total":
                self._n_admit_pair_dispatches,
            "serve_admit_rows_padded_total": self._n_admit_rows_padded,
            "serve_prefill_tokens_total": self._n_prefill_tokens,
            "serve_prefill_tokens_padded_total": self._n_prefill_padded,
            "serve_prefill_context_pairs_total": self._n_prefill_pairs,
            # Routed models: (token, expert) pairs the prefill programs
            # routed for real prompt positions, and those their
            # capacity buckets dropped (0 and 0 for a dense model).
            "serve_moe_assignments_total": self._n_moe_assigned,
            "serve_moe_dropped_total": self._n_moe_dropped,
            "serve_decode_row_steps_total": self._n_decode_row_steps,
            "serve_decode_sort_dispatches_total":
                self._n_decode_sort_dispatches,
            "serve_attn_context_tokens_total": self._n_attn_ctx_tokens,
            "serve_attn_chunks_total": self._n_attn_chunks,
            "serve_attn_chunks_walked_total": self._n_attn_chunks_walked,
            # The interval ledger (obs/intervals.py): every decode
            # dispatch interval under 0.25 s, with the steps of the
            # dispatch it waited for, booked to clean or to the dearest
            # admission work noted in its iteration or the two before.
            "serve_decode_clean_seconds_total": led.seconds[CLEAN],
            "serve_decode_clean_steps_total": led.steps[CLEAN],
            "serve_decode_clean_intervals_total": led.intervals[CLEAN],
            "serve_decode_cut_chunk_seconds_total": led.seconds[CHUNK],
            "serve_decode_cut_chunk_steps_total": led.steps[CHUNK],
            "serve_decode_cut_padded_seconds_total": led.seconds[PADDED],
            "serve_decode_cut_padded_steps_total": led.steps[PADDED],
            "serve_decode_cut_admit_seconds_total": led.seconds[ADMIT],
            "serve_decode_cut_admit_steps_total": led.steps[ADMIT],
            # Boot, set once: process start (the OS's record) until
            # this scheduler was built; warmup() entry to its last job;
            # the seconds of compilation and cache retrieval JAX
            # reported in this process until then; warm-up jobs run.
            "serve_boot_load_seconds": round(self._boot_load_s, 3),
            "serve_boot_warmup_seconds": round(self._boot_warmup_s, 3),
            "serve_boot_compile_seconds": round(self._boot_compile_s, 3),
            "serve_boot_programs_total": self._n_warmup_jobs,
            # ... and the same clock's running totals, which go on
            # after ready: a window's difference is the compilation
            # that landed on serving (0 where warm-up covered every
            # program), and the count says how many programs it was.
            "serve_compile_seconds_total": round(clock.seconds, 3),
            "serve_compiles_total": clock.events,
        }
        if self.config.is_moe:
            # Routed models only: of the layers x experts each decode
            # step could have streamed (slots), those a live row reached
            # (touched); the others' weights were not read.
            out["serve_moe_decode_experts_touched_total"] = \
                self._n_moe_decode_touched
            out["serve_moe_decode_expert_slots_total"] = \
                self._n_moe_decode_slots
        if self.config.is_moe and "rows" in self._moe_prefill:
            # A model whose prefills go sorted onto tiles (a dropless
            # Mixtral-family model; the held-range and hybrid families):
            # the rows their expert matmuls ran over (filled tiles x
            # rows a tile), beside serve_moe_assignments_total, the
            # pairs they were for (of a held range, those routed to a
            # held expert): rows / pairs is what the tiles' padding
            # costs.
            out["serve_moe_prefill_rows_total"] = self._n_moe_prefill_rows
        if self.config.router_width > self.config.num_experts:
            # A share of the experts is held here: pairs routed (prefill
            # and decode), and those routed to a held expert.
            out["serve_moe_routed_pairs_total"] = self._n_moe_routed_pairs
            out["serve_moe_local_pairs_total"] = self._n_moe_local_pairs
            # Routed layers of the prefill dispatches that carried a
            # request.
            out["serve_moe_prefill_layers_total"] = \
                self._n_moe_prefill_layers
        if self.config.conv_layers:
            # Recurrent state, or convolution windows alone, beside the
            # pages (ops/state_pool.py): the pool's bytes, the slots
            # holding a live row's state, and what the decode dispatches
            # moved of it.
            out["serve_state_pool_bytes"] = self._state_pool_bytes
            out["serve_state_rows_in_use"] = sum(
                s is not None for s in self._slots)
            out["serve_state_bytes_total"] = self._n_state_bytes
            out["serve_state_row_steps_total"] = self._n_state_row_steps
            out["serve_state_row_steps_live_total"] = \
                self._n_state_row_steps_live
            out["serve_state_snapshots_total"] = self._n_state_snapshots
            # What the prefix store holds as state snapshots (an entry's
            # is one row's state and window whatever its length).
            out["serve_prefix_state_bytes"] = (
                self._prefix.state_nbytes if self._prefix is not None
                else 0)
        if self.config.window_layers:
            out["serve_window_bytes_total"] = self._n_window_bytes
        if self._shared_kv_readers:
            out["serve_shared_kv_bytes_total"] = self._n_shared_kv_bytes
        if self._page_kv_layers:
            out["serve_page_kv_bytes_total"] = self._n_page_kv_bytes
        if self._looped:
            out["serve_loop_passes_total"] = self._n_loop_passes
            out["serve_loop_weight_bytes_total"] = self._n_loop_weight_bytes
            for t, m in enumerate(self._loop_exit_mass):
                out[f'serve_loop_exit_mass_total{{pass="{t}"}}'] = m
        if self.config.is_indexed:
            out["serve_index_kv_bytes_total"] = self._n_index_kv_bytes
            out["serve_sparse_selected_total"] = self._n_sparse_selected
            out["serve_sparse_context_total"] = self._n_sparse_context
        if self.spec_k:
            out["serve_spec_accepted_total"] = self._n_spec_accepted
            # Back-compat aggregate: the most optimistic source (the
            # one that keeps speculation ticking).
            out["serve_spec_accept_ema"] = round(
                max(self._spec_ema.values(), default=0.0), 4)
            # Per-draft-source series (ngram | model): proposed/accepted
            # draft-token counters, the realized acceptance rate, and
            # each source's throttle EMA — the observability that shows
            # WHICH source is earning its verify cost per workload.
            for s in self._sources:
                n = s.name
                prop = self._n_spec_proposed_src[n]
                acc = self._n_spec_accepted_src[n]
                out[f'serve_spec_proposed_total{{source="{n}"}}'] = prop
                out[f'serve_spec_accepted_total{{source="{n}"}}'] = acc
                out[f'serve_spec_accept_rate{{source="{n}"}}'] = (
                    round(acc / prop, 4) if prop else 0.0)
                out[f'serve_spec_accept_ema{{source="{n}"}}'] = round(
                    self._spec_ema[n], 4)
                # Accepted tokens per verify dispatch that THIS source
                # drafted into — the lever tree speculation moves
                # (more accepted per dispatch at the same verify
                # budget), so it is first-class per source.
                disp = self._n_spec_dispatch_src.get(n, 0)
                out[f'serve_spec_accepted_per_dispatch{{source="{n}"}}'] = (
                    round(acc / disp, 3) if disp else 0.0)
            # Aggregate accepted-per-dispatch across all spec ticks.
            out["serve_spec_accepted_per_dispatch"] = round(
                self._n_spec_accepted / max(1, self._n_spec_ticks), 3)
            if self.spec_tree_nodes:
                # Tree speculation: total node positions verified
                # (root + drafts + siblings over drafted rows) and the
                # mean accepted PATH length (root included, so a
                # zero-acceptance tick still walked 1 node).
                out["serve_spec_tree_nodes_total"] = (
                    self._n_spec_tree_nodes)
                out["serve_spec_tree_accepted_path_len"] = round(
                    1 + self._n_spec_tree_accepted
                    / max(1, self._n_spec_tree_rows), 3)
        if self._prefix is not None:
            out["serve_prefix_entries"] = len(self._prefix)
            out["serve_prefix_admits_total"] = self._n_prefix_admits
            out["serve_prefix_tokens_saved_total"] = self._n_prefix_tokens
            # Store-level hit/miss/eviction counters (the store tracked
            # hits internally for LRU long before exporting anything —
            # now the fleet can see prefix efficacy per replica and in
            # the router's unsuffixed totals).
            out["prefix_hits_total"] = self._prefix.hits_total
            out["prefix_misses_total"] = self._prefix.misses_total
            out["prefix_evictions_total"] = self._prefix.evictions_total
            out["prefix_bytes"] = self._prefix.nbytes
        if self._tier is not None:
            res, parked = self._tier.counts()
            # Multi-tier KV: open = resident (pages held in HBM) +
            # parked (host-RAM copy). The whole point of the tier is
            # that open_sessions is bounded by SERVE_KV_HOST_GB, not
            # by the page pool.
            out["kv_resident_sessions"] = res
            out["kv_parked_sessions"] = parked
            out["kv_open_sessions"] = res + parked
            # One locked snapshot (KVTier.stats) instead of seven bare
            # cross-object reads: consistent values on the wire, and no
            # reliance on this function's advisory suppression for
            # another object's guarded state under runtime lockcheck.
            st = self._tier.stats()
            out["kv_host_bytes"] = st["host_bytes"]
            out["kv_parked_total"] = st["parked_total"]
            out["kv_waked_total"] = st["waked_total"]
            out["kv_wake_cold_total"] = st["wake_cold_total"]
            out["kv_wake_tokens_saved_total"] = st["wake_tokens_total"]
            out["kv_evicted_total"] = st["evicted_total"]
            out["kv_pages_freed_total"] = st["pages_freed_total"]
            out["kv_wake_p50_ms"] = round(
                self._wake_hist.percentile(50) or 0.0, 3)
            out["kv_wake_p95_ms"] = round(
                self._wake_hist.percentile(95) or 0.0, 3)
        out["serve_kv_free_pages"] = self._alloc.free_pages
        out["serve_kv_total_pages"] = self.num_pages - 1
        # The gather->flash-append promotion boundary (0 = the kernel
        # cannot engage: the CPU, a mesh, a pool Mosaic refuses, a
        # latent pool): operators correlating a step-time knee at a
        # window boundary read the value the compiled ladder baked in,
        # fixed at construction.
        out["paged_flash_min_w"] = self._paged_flash_min_w
        return out

    def _log_kernels(self) -> None:
        """One boot line naming which Pallas kernels this engine's
        programs dispatch, and for each one that is off, what XLA path
        serves instead and why. Every gate reads the one platform probe
        (utils/device.py)."""
        from ..models.quant import kernel_wanted
        from ..ops.paged_attention import flash_append_blocked
        from ..utils.device import on_tpu, pallas_interpret, platform
        sharded = self.mesh is not None
        why_off = ("not on a TPU" if not on_tpu() else
                   "mesh-sharded operands (pallas_call cannot consume "
                   "them)" if sharded else None)
        # kernel -> None (live) | reason it is off
        off = {"prefill-flash": why_off}
        if self._quant_mode:
            off[f"qmm-{self._quant_mode}"] = (
                None if kernel_wanted() else why_off or "forced to XLA")
        if self.config.is_latent:
            off["mla-prefill"] = off["mla-decode"] = why_off
            off["flash-append"] = "a latent pool: mla-decode reads it"
        else:
            off["flash-append"] = flash_append_blocked(
                sharded, *self._flash_pool_row(self.config, self.kv_quant))
        if self.config.ssm_layers:
            shape = "x".join(map(str, self.config.ssm_state_shape))
            off["ssm-decode"] = (
                None if self._state_kernel else
                "Mamba-1 hands in its own step" if self.config.mamba1_inner
                else why_off or f"a state of {shape} does not tile (head_dim "
                "% 8, state_size % 128)")
        log.info("kernels on %s: %s; XLA instead of: %s; flash-append "
                 "min_w %d; pallas interpret %s", platform(),
                 ", ".join(k for k, why in off.items() if not why) or "none",
                 "; ".join(f"{k} ({why})" for k, why in off.items() if why)
                 or "none",
                 self._paged_flash_min_w, pallas_interpret())

    def _log_pool(self) -> None:
        """One boot line with the pool's geometry as the device holds
        it: what a token costs a layer (per-head K and V, or a latent
        model's one shared row), the scales, pages, and the bytes."""
        c, cache = self.config, self._cache
        per_layer = c.cache_kv_heads * (c.cache_k_dim + c.cache_v_dim)
        item = cache.k.dtype.itemsize
        total = sum(int(a.size) * a.dtype.itemsize for a in (
            cache.k, cache.v, cache.k_scale, cache.v_scale)
            if a is not None)
        kind = (f"latent (MLA): 1 head x ({c.cache_k_dim} latent + "
                f"{c.qk_rope_head_dim} rotated key in {c.cache_v_dim} "
                "lanes)" if c.is_latent else
                f"{c.cache_kv_heads} kv heads x 2 x {c.cache_k_dim}")
        log.info("KV pool: %s, %s%s; %d layers x %d bytes a token; %d pages "
                 "x %d tokens, %d rows x %d pages a row; %.3f GB",
                 kind, cache.k.dtype.name,
                 ", a float32 scale a token a head for each" if
                 cache.quantized else "", c.cache_layers, per_layer * item,
                 self.num_pages, self.page_size, self.num_slots,
                 cache.max_pages_per_row, total / 1e9)
        if cache.idx is not None:
            log.info("index keys: %d layers x %d pages x %d tokens x %d head "
                     "x %d lanes %s (%d numbers a key) beside K and V under "
                     "the same page table; %d bytes a token a layer, %.3f "
                     "GB; each query reads the %d keys its indexer picks",
                     *cache.idx.shape, cache.idx.dtype.name,
                     c.index_head_dim, self._index_token_bytes,
                     cache.idx.nbytes / 1e9, c.index_topk)
        st = cache.state
        if st is not None and st.ssm.shape[0]:
            log.info("state pool: %d Mamba layers x %d rows (%d slots and "
                     "a garbage row) x (%s float32 state + %s %s window); "
                     "%.3f MB a row, %.3f GB",
                     st.ssm.shape[0], st.ssm.shape[1], self.num_slots,
                     "x".join(map(str, st.ssm.shape[2:])),
                     "x".join(map(str, st.conv.shape[2:])),
                     st.conv.dtype.name, st.row_bytes / 1e6,
                     (st.nbytes - st.ring_nbytes) / 1e9)
        if st is not None and st.conv.shape[0] and not st.ssm.shape[0]:
            log.info("convolution windows: %d layers x %d rows (%d slots "
                     "and a garbage row) x %s %s, no recurrent state; "
                     "%.3f MB a row, %.3f MB",
                     st.conv.shape[0], st.rows, self.num_slots,
                     "x".join(map(str, st.conv.shape[2:])),
                     st.conv.dtype.name, st.row_bytes / 1e6,
                     st.conv.nbytes / 1e6)
        if st is not None and st.win_k is not None:
            log.info("window rings: %d layers x %d rows x %d positions "
                     "x %dx%d %s%s; %d bytes a position a layer, %.3f GB",
                     st.win_k.shape[0], st.rows, st.win_k.shape[3],
                     st.win_k.shape[2], st.win_k.shape[4],
                     st.win_k.dtype.name,
                     ", a float32 scale a position a head for each"
                     if st.win_ks is not None else "",
                     st.ring_position_bytes, st.ring_nbytes / 1e9)

    @staticmethod
    def _flash_pool_row(config, kv_quant: bool) -> tuple:
        """(lanes of the pool's row, rows of an int8 pool | 0): what
        ops/paged_attention.flash_append_blocked asks of a pool. The row
        is a head, or a PAIR of heads at a head of 64
        (``ModelConfig.kv_paired``: four pairs x 128 take the kernel
        where eight heads x 64 could not)."""
        if config.kv_paired:
            return (config.cache_k_dim,
                    config.cache_kv_heads if kv_quant else 0)
        return config.head_dim, config.num_kv_heads if kv_quant else 0

    @staticmethod
    def _flash_min_w(config, mesh, kv_quant: bool = False) -> int:
        """Window threshold at which this process's paged decode
        programs dispatch the multi-chunk flash-append kernel instead of
        the gather path: 0 = cannot engage (CPU, a mesh-sharded pool, a
        head_dim or an int8 pool Mosaic refuses), else one window for
        every pool geometry. One source of truth:
        ops/paged_attention.effective_flash_min_w, next to the dispatch
        policy itself."""
        from ..ops.paged_attention import effective_flash_min_w
        if config.is_latent:
            # A latent pool is read by its own kernel at every window
            # (ops/mla_attention.py); this policy is the per-head pools'.
            return 0
        return effective_flash_min_w(
            mesh is not None,
            *BatchScheduler._flash_pool_row(config, kv_quant))

    def _try_reserve(self, slot: _Slot) -> bool:
        """Claim the slot's page budget (prompt + generation
        room + the next-write slot). All-or-nothing; False = pool pressure,
        the request waits."""
        need = self._alloc.pages_for(len(slot.prompt_ids) + slot.max_new + 1)
        need = min(need, self._cache.max_pages_per_row)
        pages = self._alloc.alloc(need)
        if pages is None and self._tier is not None:
            # Page-pool pressure: resident sessions are the reclaimable
            # tier — park them to host RAM and retry before making the
            # request wait (idle KV must never block admissions).
            self._reclaim_pages(need)
            pages = self._alloc.alloc(need)
        if pages is None:
            return False
        slot.pages = pages
        slot.ctx_budget = min(need * self.page_size, self.max_seq)
        return True

    def _wait_or_fail(self, slot: _Slot) -> None:
        """Queue a page-starved request for retry — unless it could never
        fit even an empty pool (misconfigured pool), which fails fast."""
        need = self._alloc.pages_for(len(slot.prompt_ids) + slot.max_new + 1)
        if need > self.num_pages - 1:
            log.warning("request needs %d pages but the pool only has %d; "
                        "failing it", need, self.num_pages - 1)
            slot.fail(f"request needs {need} KV pages; the pool has "
                      f"{self.num_pages - 1}")
        else:
            self._waiting.append(slot)

    def _admit_pending(self, block: bool) -> None:
        """Admit pending requests into free rows: group by prompt bucket,
        prefill each group in chunks as wide as the requests they carry
        (one fused dispatch per chunk, _admit_width). Paged mode first
        retries page-starved waiters (FIFO), then pulls fresh requests
        while pages and rows last.

        While decode is active, at most ONE chunk is admitted per call
        (the rest carries to the next loop iteration), so a multi-chunk
        burst cannot stall every live stream behind back-to-back
        prefills — chunked-prefill interleaving."""
        if self._prefill_carry is not None:
            # A half-prefilled chunk owns its rows (they are not in
            # _slots until the final chunk installs them) and admission
            # is strictly ordered — everything else queues behind it in
            # _admit_carry until the carry drains.
            return
        free = self._free_rows()
        if not free:
            return
        # Install finished off-thread promotion builds BEFORE matching:
        # the loop may have been parked inside this call's blocking
        # collect when the build finished, and the burst that woke it
        # must see the new entry (draining only back in the loop would
        # make the whole first burst miss the prefix it paid to build).
        if self._prefix is not None:
            self._drain_promotions()
        had_active = len(free) < self.num_slots   # live streams to protect
        pending: list[_Slot] = []
        # Session wakes (multi-tier KV): slots whose prompt extends an
        # open session's tokens, grouped by suffix bucket. Classified
        # wherever a slot has no page reservation yet (fresh arrivals
        # and carried wake remnants); slots that already reserved cold
        # pages keep their reservation.
        wakes: dict[int, list[_Slot]] = {}

        def _classify(s: _Slot) -> bool:
            if self._tier is None or s.pages is not None or self._waiting:
                return False
            S = self._wake_candidate(s)
            if S is None:
                return False
            wakes.setdefault(S, []).append(s)
            return True

        for s in self._admit_carry:           # prepared last round
            if s.cancelled.is_set() or s.done or self._expired(s):
                s.depart()                    # no longer queued, any path
                s.wake_dev = None
                if s.pages:                   # never installed in a table
                    self._alloc.free(s.pages)
                    s.pages = None
                continue
            if _classify(s):
                continue
            if s.pages is None:
                # A carried wake remnant whose session vanished since
                # last round: it needs a cold reservation like any
                # fresh request (same FIFO discipline vs waiters).
                if self._waiting or not self._try_reserve(s):
                    self._wait_or_fail(s)
                else:
                    pending.append(s)
            else:
                pending.append(s)
        self._admit_carry = []
        if self._waiting:
            still: list[_Slot] = []
            for s in self._waiting:
                if s.cancelled.is_set():
                    s.depart()
                    continue
                if self._expired(s):
                    continue
                # Strict FIFO: the first waiter that can't reserve blocks
                # everyone behind it (otherwise smaller later requests leap
                # a large one forever and it starves).
                if (not still and len(pending) < len(free)
                        and self._try_reserve(s)):
                    pending.append(s)
                else:
                    still.append(s)
            self._waiting = still
        room = len(free) - len(pending) - sum(len(g) for g in wakes.values())
        if room > 0:
            with self._phase("collect"):
                fresh = self._collect_pending(
                    room, block and not pending and not wakes
                    and not self._waiting)
            for s in fresh:
                if _classify(s):
                    continue
                # Strict FIFO vs page-starved waiters: once anything is
                # waiting for pages, fresh requests queue *behind* it —
                # a stream of small requests must not bypass (and so
                # indefinitely starve) a large waiter. _wait_or_fail
                # still fail-fasts never-fits requests, which must not
                # become permanent head-of-line blockers.
                if self._waiting or not self._try_reserve(s):
                    self._wait_or_fail(s)
                else:
                    pending.append(s)
        if not pending and not wakes:
            return
        if wakes and pending and had_active and self._wake_rr_cold:
            # Fairness rotation: the previous contended round put a wake
            # ahead of carried cold admissions — this round the cold
            # chunk goes first and the wakes wait in the carry (they
            # re-classify next round; the rotation bounds a sustained
            # wake stream's head-of-line hold on cold requests to
            # alternate rounds instead of their whole queue deadline).
            self._wake_rr_cold = False
            self._admit_carry = [x for S in sorted(wakes)
                                 for x in wakes[S]]
            wakes = {}
        # Session wakes dispatch FIRST: each suffix bucket is one fused
        # dispatch (table/length install + suffix forward + first-token
        # sample, all in-program — the atomic-install discipline). With
        # live streams at most ONE wake dispatch runs per round and
        # everything behind it carries — the same bounded-stall rule
        # chunked admission established.
        one_wake = False
        carry_tail: list[_Slot] = []
        wake_keys = sorted(wakes)
        for wi, S in enumerate(wake_keys):
            group = wakes[S]
            if (had_active and one_wake) or not free:
                carry_tail.extend(group)
                continue
            batch = group[: len(free)]
            carry_tail.extend(group[len(batch):])
            rows = [free.pop(0) for _ in range(len(batch))]
            try:
                demoted, unused = self._admit_wake(batch, rows, S)
            except Exception:   # noqa: BLE001
                log.exception("wake admission failed for %d request(s)",
                              len(batch))
                # Same wholesale-abort rationale as the chunk path:
                # tables/pages may be half-installed.
                for s in (batch + carry_tail
                          + [x for S2 in wake_keys[wi + 1:]
                             for x in wakes[S2]] + pending):
                    s.fail("internal error: admission failed")
                self._fail_all_and_reset()
                return
            one_wake = True
            free.extend(unused)
            for s in demoted:
                # Session vanished between match and claim (replaced /
                # evicted / taken by an earlier duplicate) or its page
                # reservation failed: cold-admit this same round.
                if self._waiting or not self._try_reserve(s):
                    self._wait_or_fail(s)
                else:
                    pending.append(s)
        if carry_tail or (had_active and one_wake):
            rest = carry_tail + pending
            if rest:
                self._admit_carry = rest + self._admit_carry
            if one_wake and pending:
                # Cold work waited behind this wake: next contended
                # round rotates priority (see _wake_rr_cold).
                self._wake_rr_cold = True
            return
        if not pending:
            return
        # Group by (cached prefix, prompt bucket): a chunk's rows must
        # share one prefill program — and, with prefix caching, one prefix
        # entry (its KV is one broadcast operand). The bucket covers only
        # the suffix for prefix-matched slots.
        by_bucket: dict[tuple, list[_Slot]] = {}
        for s in pending:
            if self._prefix is not None and not s.prefix_checked:
                s.prefix = self._prefix.match(s.prompt_ids)
                s.prefix_checked = True
                if s.prefix is not None:
                    # The spliced admission cache is P + suffix-bucket
                    # wide; a near-max_seq prompt whose suffix bucket
                    # rounds past the budget must take the plain path.
                    sb = self._serving_bucket(
                        len(s.prompt_ids) - s.prefix.length)
                    if s.prefix.length + sb > self.max_seq:
                        s.prefix = None
            plen = s.prefix.length if s.prefix is not None else 0
            key = (s.prefix.ids if s.prefix is not None else (),
                   self._serving_bucket(len(s.prompt_ids) - plen))
            by_bucket.setdefault(key, []).append(s)
        groups = sorted(by_bucket.items())
        for gi, ((pkey, S), group) in enumerate(groups):
            while group:
                # The dispatch is as wide as what it carries: a lone
                # request runs the 1-row program, a burst the bucket's
                # 2-row one, several times over if the group is larger
                # (both warmed: _admit_widths). A prefix-cached group
                # counts its broadcast prefix in the footprint too.
                R = self._admit_width(len(group), S + len(pkey))
                chunk = group[:R]
                group = group[R:]
                rows = [free.pop(0) for _ in range(len(chunk))]
                # One read of the runtime-togglable budget: condition and
                # carry snapshot must see the SAME value (a mid-expression
                # flip could divide by zero or build a mis-shaped carry).
                C = self.prefill_chunk
                try:
                    if (C and S > C and S % C == 0
                            and (not had_active
                                 or self._chunk_ladder_ready(len(pkey), S,
                                                             R))):
                        # Chunked admission: install the carry (the loop
                        # dispatches one chunk per iteration, decode
                        # ticks in between) and stash every remaining
                        # request behind it — admission is strictly
                        # ordered, so nothing leapfrogs a half-prefilled
                        # chunk. An UNWARMED ladder with live streams
                        # falls through to single-shot instead (output-
                        # identical by contract): lazily compiling
                        # ceil(S/C) chunk programs back-to-back on this
                        # thread would stall every live decode for the
                        # whole ladder — strictly worse than the one
                        # whole-bucket compile it replaced, i.e. the
                        # exact stall class chunking exists to remove.
                        # With no live streams the ladder compiles (and
                        # is cached) with nobody to stall.
                        self._start_prefill_carry(chunk, rows, S, R, C)
                        # Append (not assign): deferred wake slots from
                        # the fairness rotation may already sit in the
                        # carry and must not be dropped.
                        self._admit_carry = group + [
                            x for _, g in groups[gi + 1:] for x in g
                        ] + self._admit_carry
                        return
                    self._admit_chunk(chunk, rows, S, R)
                    if had_active and (group or gi + 1 < len(groups)):
                        # Live streams existed before this round and more
                        # chunks remain: carry them so decode ticks run
                        # in between (bounded stalls per burst).
                        self._admit_carry = group + [
                            x for _, g in groups[gi + 1:] for x in g
                        ] + self._admit_carry
                        return
                except Exception:   # noqa: BLE001
                    log.exception("admission failed for %d request(s)",
                                  len(chunk))
                    self._prefill_carry = None
                    # The chunk's pages may already be installed in row
                    # tables (the failure can postdate the device call),
                    # and every not-yet-admitted slot holds pages from
                    # the allocator about to be reset — abort the whole
                    # round wholesale rather than risk freeing pages a
                    # live table still points at / double-allocating.
                    for s in chunk + group + [x for _, g in groups[gi + 1:]
                                              for x in g]:
                        s.fail("internal error: admission failed")
                    self._fail_all_and_reset()
                    return

    # graftcheck: runs-on _loop
    def _note_launch(self, kind: str) -> None:
        """First thing inside the ``launch`` mark of a program that
        feeds the device (an admission, a chunk, a decode dispatch): has
        the last such launch's output arrived already, so that the
        device has nothing of the loop's left to run? One non-blocking
        read of an array's state."""
        self._n_launch[kind] += 1
        last = self._last_out
        if last is None or last.is_ready():
            self._n_starved[kind] += 1

    def _admit_chunk(self, chunk: list[_Slot], rows: list[int], S: int,
                     R: int,
                     warm_prefix: Optional[PrefixEntry] = None) -> None:
        """One fused dispatch: batched prefill of ``chunk`` + kv splice into
        ``rows`` + first-token sample per row.

        The program shape is (R, S) with R the narrowest of the bucket's
        widths that holds the chunk (_admit_widths: 1 row and 2 rows —
        two programs per prompt bucket). A chunk
        shorter than R is padded with dummy entries whose row index is
        the out-of-range sentinel ``num_slots`` — every install of
        theirs is scatter-dropped. ``serve_admit_rows_padded_total``
        sums R over the dispatches; ``serve_admitted_total`` over it is
        the share of rows that carried a request.

        A prefix-cached chunk (every slot carries the same
        ``slot.prefix``; _admit_pending groups by entry) uploads only the
        suffix tokens: S is the *suffix* bucket, ``ints[4]`` holds the
        total (prefix+suffix) lengths, and the prefix-variant
        program broadcasts the cached KV instead of recomputing it.

        An EMPTY chunk is the warmup path: all R entries are padding, so
        the dispatch compiles-and-runs the exact serving program as a
        device no-op (``warm_prefix`` selects the prefix variant).

        The host's five arrays go up as ONE packed buffer
        (_admit_host_arrays, _admit_upload) and the program takes them
        apart: the dispatch enters the runtime twice, an upload and a
        launch."""
        # Failpoint: an injected admission fault must fail THIS chunk's
        # requests cleanly (the _admit_pending recovery envelope) and
        # leave the loop serving — the contract tests/test_failpoints.py
        # drives. (Warmup jobs route through here too; arming during
        # warmup fails that warmup job, surfaced by warmup()'s re-raise.)
        failpoint("serve.scheduler.admit")
        t_admit = time.monotonic()
        for s in chunk:
            s.admit_t = t_admit
        prefix = chunk[0].prefix if chunk else warm_prefix
        P = prefix.length if prefix is not None else 0
        with self._phase("build"):
            packed = self._admit_host_arrays(chunk, rows, S, R, prefix)
        self._ledger.cut(ADMIT)
        if chunk:       # warm-up's all-padding dispatches do not count
            self._n_loop_passes += self.config.ut_steps
            self._n_admit_batches += 1
            self._n_admit_pair_dispatches += len(chunk) > 1
            self._n_admit_rows_padded += R
            self._n_prefill_tokens += sum(len(s.prompt_ids) - P
                                          for s in chunk)
            self._n_prefill_pairs += sum(
                _causal_pairs(P, len(s.prompt_ids)) for s in chunk)
            self._n_prefill_padded += R * S

        if prefix is not None:
            self._n_prefix_admits += len(chunk)
            self._n_prefix_tokens += P * len(chunk)
            if prefix.state is not None:
                self._n_state_snapshots += len(chunk)
            # A promotion-built AOT executable (exact (P, S, R) match)
            # dispatches ahead of the jit wrapper — same signature, but
            # compiled on the worker thread instead of here.
            prog = self._admit_prefix_aot.get((P, S, R),
                                              self._admit_prefix_j)
            pre = (prefix.k, prefix.v, prefix.state)
        else:
            # Padding entries keep an all-zero table: their prefill writes
            # land in garbage page 0 (their table/length installs are
            # dropped via the row sentinel).
            prog, pre = self._admit_j, ()
        packed = self._admit_upload(packed, live=bool(chunk))
        # Every entry, a dummy too, writes a whole row of the state pool
        # (0 bytes for a model without recurrent state).
        with self._phase("launch", state_bytes=R * self._state_row_bytes):
            self._note_launch("admit")
            (toks_dev, self._cache, self._keys, self._next_dev,
             self._temps_dev, self._top_ks_dev, self._top_ps_dev,
             self._ring_dev, self._rps_dev) = prog(
                self._params, *pre, packed, self._cache, self._keys,
                self._next_dev, self._temps_dev, self._top_ks_dev,
                self._top_ps_dev, self._ring_dev, self._rps_dev)
        self._last_out = toks_dev
        self._install_admitted(chunk, rows, toks_dev)

    def _admit_host_arrays(self, chunk: list[_Slot], rows: list[int],
                           S: int, R: int,
                           prefix: Optional[PrefixEntry]) -> "np.ndarray":
        """The host's side of one admission chunk — shared by the
        single-shot programs and the chunked-prefill carry, so the two
        admission paths cannot drift: ONE packed buffer (_admit_layout)
        holding tokens [R,S], ints [5,R] = lens/rows/seeds/top_k/
        total-lens, floats [3,R], rings [R,_RING], tables [R,mppr],
        filled through its views; the programs without a prefix do not
        read ``ints[4]``.

        The requests take the FIRST entries and the dummy entries
        follow: a routed MLP's capacity buckets fill in entry order
        (models/mixtral.moe_mlp), and the dummy entries, all token 0,
        agree on their two experts in every layer — ahead of the
        requests they filled those buckets and a quarter of the real
        tokens' assignments were dropped (PERF.md §6, PR 24)."""
        P = prefix.length if prefix is not None else 0
        packed, (tokens, ints, floats, rings, tables) = _admit_buffer(
            R, S, self._cache.max_pages_per_row, self.config.vocab_size)
        ints[0] = 1                                 # padding: 1-token prompt
        ints[1] = self.num_slots                    # padding: dropped rows
        ints[4] = P + 1
        for r, (slot, row) in enumerate(zip(chunk, rows)):
            suffix = slot.prompt_ids[P:]
            tokens[r, : len(suffix)] = suffix
            o = slot.req.options
            ints[:4, r] = (len(suffix), row, slot.seed, o.top_k)
            ints[4, r] = len(slot.prompt_ids)
            floats[:, r] = (o.temperature, o.top_p, o.repeat_penalty)
            # Penalty window: prompt tokens at their context position mod
            # _RING (later positions overwrite earlier — last-64 window).
            # Prefix-cached rows still seed from the FULL prompt: the ring
            # is host-built state, independent of which KV was recomputed.
            if o.repeat_penalty != 1.0:
                start = max(0, len(slot.prompt_ids) - _RING)
                for p_i in range(start, len(slot.prompt_ids)):
                    rings[r, p_i % _RING] = slot.prompt_ids[p_i]
        for r, slot in enumerate(chunk):
            tables[r, : len(slot.pages)] = slot.pages
        return packed

    def _admit_upload(self, packed: "np.ndarray", live: bool = True):
        """The admission path's one transfer: the packed host buffer to
        the device, or under a mesh to every device of it, committed,
        so that a ladder's later chunks find it where they run and no
        launch places it again. ``live`` is False for warm-up's
        dispatches, which ``serve_admit_uploads_total`` leaves out as
        ``serve_admit_batches_total`` does."""
        with self._phase("upload"):
            if live:
                self._n_admit_uploads += 1
            return jax.device_put(packed, self._packed_sharding)

    def _install_admitted(self, chunk: list[_Slot], rows: list[int],
                          toks_dev,
                          ladder: Optional[_PrefillCarry] = None) -> None:
        """Admission epilogue shared by the single-shot program and the
        final prefill chunk (of ``ladder``): read the first tokens back,
        install the slots, stream/stop-check each first token."""
        dispatches = ladder.S // ladder.C if ladder is not None else 1
        with self._phase("readback", rows=len(chunk)):
            # graftcheck: sync-ok intentional: R int32 first tokens, TTFT depends on it
            first_toks = np.asarray(toks_dev)
            if self._counted:
                # The prefill's drop count rides behind the first tokens
                # (_with_moe); prefix builds left theirs waiting.
                # Warm-up's all-padding dispatches carry no request.
                self._count_moe(first_toks[-len(self._moe_prefill):],
                                dispatches if chunk else 0)
                while self._moe_unread:
                    # graftcheck: sync-ok 2 int32 of a build that ended before this admission was dispatched
                    built = np.asarray(self._moe_unread.popleft())
                    self._count_moe(built, 1)
        with self._phase("stream"):
            # Draft-source admission BEFORE the install loop (a row that
            # finishes on its very first token releases inside the loop, and
            # release must never precede its own admit): n-gram builds its
            # prompt index per row; the model drafter prefills every row's
            # prompt in one batched dispatch — async, no readback, so it
            # overlaps the first-token streaming below and whatever target
            # work the loop does next (the PR 3 chunk ladder included).
            # Gated on the runtime-togglable spec_k: with speculation
            # off, no drafter dispatches may run — sources late-bind at
            # the next draft_batch instead (the model drafter's catch-up
            # feed covers rows admitted while off).
            if self.spec_k and self._sources and chunk:
                ctxs = {row: slot.prompt_ids
                        for slot, row in zip(chunk, rows)}
                rws = [row for _, row in zip(chunk, rows)]
                for s in self._sources:
                    pf = getattr(s, "prefill", None)
                    if pf is not None:
                        pf(rws, ctxs)
                    else:
                        for r in rws:
                            s.admit(r, ctxs[r])

            now = time.monotonic()
            self._n_admitted += len(chunk)
            if chunk:
                self._flight.note("admit", self._loop_iter, n=len(chunk))
            tr = self._trace
            # A ladder's side of a traced request, in the interval
            # ledger's words: the chunks that ran their forward, those
            # past every row's prompt, its bucket, the requests it
            # carried.
            laddered = ({} if ladder is None or tr is None else
                        {"chunks": dispatches - ladder.padded,
                         "padded": ladder.padded, "bucket": ladder.S,
                         "shared": len(chunk)})
            for i, (slot, row) in enumerate(zip(chunk, rows)):
                slot.depart()                # reached a batch row: not queued
                if slot.stats is not None:
                    slot.stats.ttft_s = now - slot.req.arrival_time
                if tr is not None and slot.req.trace_sampled:
                    # Pre-first-token wall, split at the admission dispatch:
                    # queue wait (arrival -> dispatch) vs prefill compute
                    # (dispatch -> install, chunk readback included).
                    t_admit = slot.admit_t or now
                    tr.add(slot.req.trace_id, "sched.queue_wait",
                           slot.req.arrival_time,
                           t_admit - slot.req.arrival_time)
                    tr.add(slot.req.trace_id, "sched.prefill", t_admit,
                           now - t_admit, tokens=len(slot.prompt_ids),
                           row=row, **laddered,
                           # only a model with recurrent state says so
                           **({"state_bytes": self._state_row_bytes}
                              if self._state_row_bytes else {}))
                    slot.cut0 = self._ledger.totals()
                slot.ctx_len = len(slot.prompt_ids)
                # last_emit_t stays 0 until _append_token below sets it: the
                # first token's latency is TTFT, not an inter-token gap — a
                # pre-set stamp would log a fake ~0 ms TBT sample per request.
                self._slots[row] = slot
                if not self._append_token(slot, row, int(first_toks[i])):
                    # finished on the very first token (eos / limits)
                    self._release(row)

    def _start_prefill_carry(self, chunk: list[_Slot], rows: list[int],
                             S: int, R: int, C: int) -> None:
        """Begin a chunked admission: build the host arrays once and
        install the carry. Dispatch happens exclusively in _loop — one
        chunk per iteration (_prefill_step), decode ticks in between —
        so an admission can never put two chunk dispatches back-to-back
        ahead of a decode tick (the bounded-stall contract). ``C`` is
        the caller's already-validated read of prefill_chunk, NOT
        re-read here — the runtime toggle must not land between the
        divisibility check and this snapshot."""
        prefix = chunk[0].prefix if chunk else None
        t_admit = time.monotonic()
        for s in chunk:
            s.admit_t = t_admit
        with self._phase("build"):
            packed = self._admit_host_arrays(chunk, rows, S, R, prefix)
        # The ladder's one upload: every chunk takes this buffer.
        packed = self._admit_upload(packed)
        # The padded positions are counted chunk by chunk (_prefill_step).
        P = prefix.length if prefix is not None else 0
        self._n_admit_batches += 1
        self._n_admit_rows_padded += R
        self._n_prefill_tokens += sum(len(s.prompt_ids) - P for s in chunk)
        self._n_prefill_pairs += sum(
            _causal_pairs(P, len(s.prompt_ids)) for s in chunk)
        self._prefill_carry = _PrefillCarry(
            chunk=chunk, rows=rows, S=S, off=0, C=C,
            prefix=prefix, kv=None, logits=None, packed=packed)

    def _prefill_step(self) -> None:
        """Dispatch ONE continuation-prefill chunk of the in-progress
        admission. At most one chunk runs per loop iteration, so a long
        prompt's admission stalls live decodes by one chunk's compute,
        never the whole prompt's prefill; the final chunk samples the
        first tokens and installs the rows (TTFT lands there)."""
        failpoint("serve.scheduler.admit")   # chunked-admission leg of the site
        pc = self._prefill_carry
        C = pc.C    # the carry's own width — see _PrefillCarry.C
        P0 = pc.prefix.length if pc.prefix is not None else 0
        off = pc.off
        R = pc.packed.shape[0]
        # Past every row's suffix: the program's own test, on the host.
        padded = all(off >= len(s.prompt_ids) - P0 for s in pc.chunk)
        self._n_prefill_chunks += 1
        self._n_prefill_chunks_padded += padded
        if not padded:      # a wholly padded chunk computes nothing
            self._n_loop_passes += self.config.ut_steps
        self._n_admit_pair_dispatches += len(pc.chunk) > 1
        self._n_prefill_padded += R * C
        pc.padded += padded
        self._ledger.cut(PADDED if padded else CHUNK)
        self._flight.note("prefill_chunk", self._loop_iter,
                          off=off, C=C, S=pc.S, n=len(pc.chunk))
        # The ladder's last chunk installs its rows in the state pool.
        installs = R * self._state_row_bytes if off + C == pc.S else 0
        with self._phase("prefill_chunk", R=R, S=pc.S, C=C, off=off,
                         padded=int(padded), state_bytes=installs):
            kv, logits, toks_dev = self._dispatch_prefill_chunk(
                P0, pc.S, off, C, pc.packed, pc.kv, pc.logits, pc.prefix)
            if toks_dev is None:
                pc.kv, pc.logits, pc.off = kv, logits, off + C
                return
            self._prefill_carry = None
            if pc.prefix is not None:
                self._n_prefix_admits += len(pc.chunk)
                self._n_prefix_tokens += P0 * len(pc.chunk)
                if pc.prefix.state is not None:
                    self._n_state_snapshots += len(pc.chunk)
            self._install_admitted(pc.chunk, pc.rows, toks_dev, ladder=pc)

    def _dispatch_prefill_chunk(self, P0: int, S: int, off: int, C: int,
                                packed, kv, logits, prefix) -> tuple:
        """Run one continuation-chunk program (live admission and warmup
        share this dispatch, so argument order cannot drift from the
        compiled signatures). ``C``: the chunk width — the carry's
        snapshot for live admissions, self.prefill_chunk for warmup.
        ``packed``: the admission's buffer, on the device since its
        admission; nothing is uploaded here.
        Returns (carry_kv, carry_logits, None) for a non-final chunk and
        (None, None, first_tokens_dev) for the final one."""
        first, final = off == 0, off + C == S
        shape_key = (P0, S, off, C, packed.shape[0])
        # Promotion-built AOT executables (keyed by the full R-specific
        # shape) dispatch ahead of the per-(P0,S,off,C) jit wrappers.
        prog = self._prefill_chunk_aot.get(shape_key)
        if prog is None:
            prog = self._prefill_chunk_for(P0, S, off, C)
        toks_dev = None
        with self._phase("launch"):
            self._note_launch("prefill_chunk")
            if first:
                pre = (prefix.k, prefix.v, prefix.state) if P0 else ()
                kv, logits, self._cache = prog(self._params, *pre, packed,
                                               self._cache)
            elif not final:
                kv, logits, self._cache = prog(self._params, packed, kv,
                                               logits, self._cache)
            else:
                # (a large carry comes back behind them, donated so that
                # the chunk wrote it in place, and is dropped here)
                (toks_dev, self._cache, self._keys, self._next_dev,
                 self._temps_dev, self._top_ks_dev, self._top_ps_dev,
                 self._ring_dev, self._rps_dev) = prog(
                    self._params, packed, kv, logits,
                    self._cache, self._keys, self._next_dev,
                    self._temps_dev, self._top_ks_dev, self._top_ps_dev,
                    self._ring_dev, self._rps_dev)[:9]
        self._chunk_shapes_run.add(shape_key)
        if toks_dev is not None:
            self._last_out = toks_dev
            return None, None, toks_dev
        # The carry's logits: donated to the next chunk alone, after it
        # has looked (for a routed model the drop count rides beside).
        self._last_out = jax.tree.leaves(logits)[0]
        return kv, logits, None

    def _dispatch_tick(self, allow_fuse: bool = True,
                       inflight: int = 0) -> tuple:
        """Dispatch one batched decode tick (async — returns without a
        readback): K=1 plain step, or a fused K-step scan when
        _choose_fuse_k allows (``allow_fuse`` is False on iterations
        where speculation could run — a fused tick would emit K tokens
        with no draft opportunity). ``inflight``: steps of the still-
        unprocessed pipelined tick, counted against every budget.
        Returns (toks_dev [B] or [K,B], flat with the expert count
        behind it for a routed model (_with_moe), snapshot of the rows it
        decoded for, K); _process_tick consumes it, one tick later under
        pipelining."""
        # Flight event BEFORE the failpoint/device dispatch: if this
        # very dispatch wedges (the armed-delay stall test), the ring's
        # last event names it at the iteration the stall marker carries.
        self._flight.note("dispatch", self._loop_iter,
                          inflight=inflight)
        # Failpoint: an injected dispatch fault rides the loop's recovery
        # envelope (_fail_all_and_reset) — in-flight requests fail with a
        # well-formed error, the next request serves oracle-exact.
        failpoint("serve.scheduler.dispatch")
        K = self._choose_fuse_k(inflight) if allow_fuse else 1
        if K != self._last_fuse_k:
            # Fuse-K decisions are sparse relative to ticks — record
            # the FLIPS, not every tick, or K=4 steady state would
            # evict everything else from the ring.
            self._flight.note("fuse_k", self._loop_iter, k=K)
            self._last_fuse_k = K
        self._n_decode_ticks += 1
        self._n_decode_steps += K
        if K > 1:
            self._n_fused_ticks += 1
            self._n_fused_steps += K
        self._ledger.note(time.monotonic(), K)
        active = tuple(s is not None for s in self._slots)
        live_steps = sum(active) * K
        self._n_decode_row_steps += live_steps
        # The sampler's own test, on the host: a live row that is not
        # greedy takes the step through the candidate sort
        # (models/sampling.sample_batched). An upper bound for a fused
        # dispatch, whose sampling row may stop before its last step.
        self._n_decode_sort_dispatches += any(
            s is not None and not s.req.options.temperature <= 0.0
            for s in self._slots)
        if self._cache.state is not None:
            # The kernel reads and writes a live row's state and nothing
            # of the others'; XLA's update is one program over every
            # slot's row, and a row not live comes back as it was.
            moved = (live_steps if self._state_kernel
                     else self.num_slots * K)
            self._n_state_row_steps += moved
            self._n_state_row_steps_live += live_steps
            self._n_state_bytes += 2 * moved * self._state_row_bytes
        # Step j of the K reads each live row's ctx_len + j cached rows.
        ctx_tokens = K * sum(
            s.ctx_len + inflight for s in self._slots
            if s is not None) + sum(active) * K * (K - 1) // 2
        self._n_attn_ctx_tokens += ctx_tokens
        if self._ring_position_bytes:
            W = self.config.sliding_window
            self._n_window_bytes += (
                self.config.window_layers * self._ring_position_bytes
                * sum(min(s.ctx_len + inflight + j + 1, W)
                      for s in self._slots if s is not None
                      for j in range(K)))
        self._n_shared_kv_bytes += (self._shared_kv_readers * ctx_tokens
                                    * self._page_token_bytes)
        if self.config.is_indexed:
            # Step j of the K scores a row's ctx_len + j cached keys and
            # its own, and attends the index_topk it selects (all of them
            # while there are no more). The masked walk still READS every
            # page of the row (ops/paged_attention.py says why), so the
            # page bytes below count the context, as every model's do.
            topk, n = self.config.index_topk, self._page_kv_layers
            in_ctx = ctx_tokens + live_steps
            self._n_index_kv_bytes += n * in_ctx * self._index_token_bytes
            self._n_sparse_selected += n * sum(
                min(s.ctx_len + inflight + j + 1, topk)
                for s in self._slots if s is not None for j in range(K))
            self._n_sparse_context += n * in_ctx
        self._n_page_kv_bytes += (self._page_kv_layers * ctx_tokens
                                  * self._page_token_bytes)
        if self._looped:
            self._n_loop_passes += K * self.config.ut_steps
            self._n_loop_weight_bytes += (K * self.config.ut_steps
                                          * self._stack_bytes)
        if active != self._active_host:
            # Re-upload the mask only when the active set changed (it only
            # moves on admission/finish — not per tick).
            self._active_host = active
            with self._phase("upload"):
                # graftcheck: sync-ok host tuple -> device upload, not a readback
                self._active_dev = jnp.asarray(np.array(active, bool))
        # extra: under pipelining a row's device length can run up to
        # ``inflight`` slots ahead of the host's ctx_len, and this tick
        # writes K more slots — the deepest attended position is
        # ctx_len + inflight + K - 1 (floor 1 keeps K=1 selection
        # identical to the pre-fusion program ladder).
        decode_w = self._window(extra=max(1, inflight + K - 1))
        if 0 < self._paged_flash_min_w <= decode_w:
            self._note_attn_chunks(decode_w, K, inflight)
        if K == 1:
            decode_j = self._decode_for(decode_w)
        else:
            decode_j = self._decode_fused_for(decode_w, K)
        with self._phase("launch"):
            self._note_launch("decode")
            (toks_dev, self._next_dev, self._cache, self._keys,
             self._ring_dev) = decode_j(
                self._params, self._next_dev, self._cache, self._active_dev,
                self._temps_dev, self._top_ks_dev, self._top_ps_dev,
                self._keys, self._ring_dev, self._rps_dev)
        self._last_out = toks_dev
        return toks_dev, list(self._slots), K

    def _note_attn_chunks(self, window: int, K: int, inflight: int) -> None:
        """Count what a decode dispatch at ``window`` asks of the
        flash-append kernel (ops/paged_attention.py), a layer: its grid
        is rows x chunks of the window at every one of the K steps, and
        a program fetches and folds its chunk only where the chunk
        starts inside its row's context (step j of the K sees ctx_len +
        inflight + j cached rows; a free row holds none). Host
        arithmetic on what the loop holds, with the kernel's own chunk
        size."""
        ps = self.page_size
        pages = -(-window // ps)
        chunk_pages = flash_append_chunk_pages(
            self.config.kv_dim, self._cache.k.dtype.itemsize, ps, pages)
        n_chunks = -(-pages // chunk_pages)
        ct = chunk_pages * ps
        self._n_attn_chunks += len(self._slots) * n_chunks * K
        self._n_attn_chunks_walked += sum(
            min(n_chunks, -(-(s.ctx_len + inflight + j) // ct))
            for s in self._slots if s is not None for j in range(K))

    def _process_tick(self, toks_dev, snapshot: list, K: int = 1) -> None:
        """Host half of a decode tick: read the sampled tokens back and
        run per-row bookkeeping for the rows captured at dispatch time.
        Fused ticks drain a [K, B] burst — each row consumes its tokens
        in order and stops at the first finisher (EOS parked the row
        in-scan at exactly that point, so later burst positions of a
        finished row are garbage by construction). Rows finished/released
        since dispatch (their slot.done is set) are skipped — their
        in-flight tokens are discarded, and the writes they made sit
        beyond the trusted length by the overwrite-before-trust
        invariant."""
        # Failpoint: the engine's token readback (device -> host). A
        # fault here (a device reset) hits the same loop
        # recovery envelope as a dispatch fault.
        with self._phase("readback", K=K):
            failpoint("serve.engine.readback")
            # graftcheck: sync-ok intentional: [B] or [K,B] int32, the tick's readback
            toks = np.asarray(toks_dev)
        if self._counted:
            # The experts the dispatch touched ride behind its tokens
            # (_with_moe).
            w = self._moe_w
            self._n_moe_decode_touched += int(toks[-w])
            self._n_moe_decode_slots += int(toks[1 - w])
            if w > 2:
                self._n_moe_routed_pairs += int(toks[2 - w])
                self._n_moe_local_pairs += int(toks[3 - w])
            toks = toks[:-w]
        elif self._looped:
            # The dispatch's exit mass rides behind its tokens, a pass an
            # entry, as float32 bits (_with_moe).
            T = self.config.ut_steps
            for t, m in enumerate(toks[-T:].view(np.float32)):
                self._loop_exit_mass[t] += float(m)
            toks = toks[:-T]
        toks = toks.reshape(K, -1)
        with self._phase("stream"):
            for row, slot in enumerate(snapshot):
                # Identity check, not just done/None: the row may have
                # been released AND re-admitted since dispatch — acting
                # on it now (e.g. the cancelled branch's release) would
                # evict the NEW occupant.
                if (slot is None or slot.done
                        or self._slots[row] is not slot):
                    continue
                if slot.cancelled.is_set():
                    self._release(row)
                    continue
                for k in range(toks.shape[0]):
                    slot.ctx_len += 1  # decode wrote this row's next kv slot
                    if not self._append_token(slot, row, int(toks[k, row])):
                        self._release(row)
                        break

    def _ensure_sources(self) -> None:
        """Build the draft-source list (and per-source throttle/counter
        state) the first time speculation is on. Called at construction
        and from _loop, so a scheduler built with spec_k=0 whose spec_k
        is later toggled >0 still speculates (n-gram only: a drafter's
        K is baked in at ITS construction, so it cannot be conjured by
        a toggle — it is validated and attached only when the scheduler
        is built with spec_k>0)."""
        if self._sources or not self.spec_k:
            return
        from ..utils.draft import NGramSource
        srcs = [NGramSource(self.spec_k)]
        if self._draft_model is not None:
            srcs.append(self._draft_model)
        for s in srcs:
            # Per-source state BEFORE the source becomes visible: a
            # concurrent /metrics scrape iterates _sources and indexes
            # these dicts, so appending first would open a KeyError
            # window during a runtime 0 -> K toggle.
            self._spec_ema[s.name] = _SPEC_EMA_SEED
            self._spec_cooldown[s.name] = 0
            self._n_spec_proposed_src[s.name] = 0
            self._n_spec_accepted_src[s.name] = 0
            self._n_spec_dispatch_src[s.name] = 0
            self._sources.append(s)

    # graftcheck: runs-on _loop
    def _spec_sources_allowed(self) -> dict[str, bool]:
        """Per-source acceptance-collapse throttle: a source whose EMA
        sits below the floor proposes only every Nth tick (a successful
        probe lifts its EMA and re-enables it per-tick); sources above
        the floor always may. Mutates the per-source probe counters —
        call once per loop iteration, BEFORE the pipeline flush, so
        iterations where every source is throttled keep their one-tick
        pipelining. Per-source on purpose: a cold n-gram index on
        free-form output must not starve model drafting (and a cold
        model must not stop quoting workloads' free n-gram wins)."""
        out: dict[str, bool] = {}
        for s in self._sources:
            if self._spec_ema[s.name] >= _SPEC_EMA_FLOOR:
                out[s.name] = True
            else:
                self._spec_cooldown[s.name] += 1
                out[s.name] = not (self._spec_cooldown[s.name]
                                   % _SPEC_PROBE_EVERY)
        return out

    def _spec_tick(self, allowed: dict[str, bool]) -> bool:
        """Speculative decode tick over the hybrid draft sources.
        Returns False (caller falls back to the plain tick) when no
        active row has a usable draft — the verify program computes K+1
        positions for every row, so it only pays off when something is
        drafted.

        Draft phase, priority order (``allowed`` gates each source —
        the per-source EMA throttle): the n-gram index proposes first
        (host-side, ~free when it hits); rows it misses go to the
        resident draft model, which proposes K greedy tokens in one
        batched drafter dispatch (serve/draft_model.py). Verify phase:
        the device verifies [cur, drafts...] in one target forward,
        accepts an exactly-distributed prefix
        (models/sampling.spec_verify_batched — both sources propose
        point-mass drafts, so the acceptance math is exact for either),
        advances lengths by accepted+1, and hands back (accepted,
        correction) — 2×B int32. Rejected drafts' kv slots are
        stale-beyond-length (free rollback, target AND drafter — the
        drafter rewinds via observe()); near-budget rows cap acceptance
        via max_acc so trusted slots never pass their budget.

        Tree mode (``spec_tree_nodes`` = N > 0): the verify window
        widens from K+1 to N node positions. Nodes 0..K are the linear
        chain exactly as above; nodes K+1..N-1 are SIBLING leaves — the
        drafter's second-choice token at its least-certain main-chain
        positions (top-1/top-2 logit gap < ``spec_tree_gap``), so the
        one position most likely to be rejected carries a ready-scored
        alternative. Verify is still ONE forward (tree-topology mask,
        per-node depths); acceptance walks the main chain and, at the
        first rejection, may hop to that position's sibling
        (models/sampling.spec_verify_tree — exact, and bit-identical
        to linear under greedy). An accepted sibling's kv slot is
        compacted onto the accepted path inside the same dispatch;
        rejected branches stay stale-beyond-length like rejected
        drafts. Sources observe their MAIN-CHAIN accepted prefix only
        (a used sibling diverges from the drafter's fed state)."""
        K = self.spec_k
        N = self.spec_tree_nodes
        tree = bool(N)
        B = self.num_slots
        tokens = np.zeros((B, N if tree else K + 1), np.int32)
        drafts = np.zeros((B, K), np.int32)
        max_acc = np.zeros((B,), np.int32)
        if tree:
            depth_b, anc_b = self._tree_base()
            depths = np.broadcast_to(depth_b, (B, N)).copy()
            anc = np.broadcast_to(anc_b, (B, N, N)).copy()
            sib_tok = np.full((B, K), -1, np.int32)
            sib_node = np.full((B, K), -1, np.int32)
        budgets: dict[int, int] = {}
        # Contexts as UNCONCATENATED (prompt_ids, ids) reference pairs —
        # the DraftSource contract — so a spec tick copies no per-row
        # context; sources slice only the suffix they need.
        ctxs: dict[int, tuple] = {}
        remaining: list[int] = []
        for row, slot in enumerate(self._slots):
            if slot is None:
                continue
            # Live slots always hold >= 1 generated token (admission
            # appends the first or releases the row).
            tokens[row, 0] = slot.ids[-1]
            budget = slot.ctx_budget - 2 - slot.ctx_len
            if budget < 1:
                continue        # cannot accept anything — don't draft
            budgets[row] = budget
            ctxs[row] = (slot.prompt_ids, slot.ids)
            remaining.append(row)
        # row -> (source name, main chain, second choices, gaps) — first
        # source to propose wins. Non-tree ticks carry empty sec/gap.
        proposals: dict[int, tuple[str, list[int], list[int],
                                   list[float]]] = {}
        consulted: list[str] = []
        for s in self._sources:
            if not remaining or not allowed.get(s.name):
                continue
            consulted.append(s.name)
            if tree:
                got_t = s.draft_tree_batch(remaining, ctxs)
                for row in remaining:
                    t = got_t.get(row)
                    if t and t[0]:
                        d, sec, gap = t
                        proposals[row] = (s.name, list(d[:K]),
                                          list(sec[:K]), list(gap[:K]))
            else:
                got = s.draft_batch(remaining, ctxs)
                for row in remaining:
                    d = got.get(row)
                    if d:
                        proposals[row] = (s.name, list(d[:K]), [], [])
            remaining = [r for r in remaining if r not in proposals]
        # A consulted source that proposed NOTHING decays like a
        # zero-acceptance tick: an unthrottled source is what keeps the
        # spec path flushing the one-tick decode pipeline each
        # iteration, so "never proposes" must back off to probes
        # exactly like "never accepted" (a free-form stream under
        # n-gram-only speculation otherwise ran unpipelined forever).
        for name in consulted:
            if not any(src == name for src, *_ in proposals.values()):
                self._spec_ema[name] *= (1 - _SPEC_EMA_ZERO_ALPHA)
        if not proposals:
            return False
        src_rows: dict[str, list[int]] = {s.name: [] for s in self._sources}
        for row, (src, d, sec, gap) in proposals.items():
            src_rows[src].append(row)
            self._n_spec_proposed_src[src] += len(d)
            drafts[row, : len(d)] = d
            tokens[row, 1: 1 + len(d)] = d
            max_acc[row] = min(len(d), budgets[row])
            if tree:
                n_sib = 0
                # Sibling write-validity guard: node slots K+1..N-1
                # write kv at lengths + node; past the row's cache
                # capacity those writes are dropped (garbage page /
                # mode="drop"), and compacting a dropped slot would
                # copy stale kv — so near-capacity rows run the tick
                # as a plain linear chain.
                if (self._slots[row] is not None
                        and self._slots[row].ctx_len + N + 2
                        <= self.max_seq):
                    sites = [j for j in range(min(len(d), len(sec),
                                                  len(gap)))
                             if gap[j] < self.spec_tree_gap
                             and sec[j] != d[j]]
                    for j in sites[: N - K - 1]:
                        node = K + 1 + n_sib
                        tokens[row, node] = sec[j]
                        depths[row, node] = j + 1
                        anc[row, node, : j + 1] = True
                        sib_tok[row, j] = sec[j]
                        sib_node[row, j] = node
                        n_sib += 1
                self._n_spec_tree_rows += 1
                self._n_spec_tree_nodes += 1 + len(d) + n_sib

        self._n_decode_ticks += 1
        self._n_spec_ticks += 1
        # One step for every live row, as Observations.decode_steps
        # counts a speculative dispatch.
        self._n_decode_row_steps += sum(
            s is not None for s in self._slots)
        # A spec tick emits tokens like a decode tick: book any pending
        # admission gap against it (the chunk's compute delayed THIS
        # tick's emissions too), then restart the interval. K = 0: spec
        # wall is not decode-step wall.
        self._ledger.note(time.monotonic(), 0)
        active = tuple(s is not None for s in self._slots)
        if active != self._active_host:
            self._active_host = active
            with self._phase("upload"):
                # graftcheck: sync-ok host tuple -> device upload, not a readback
                self._active_dev = jnp.asarray(np.array(active, bool))
        for name, rows_d in src_rows.items():
            if rows_d:
                self._n_spec_dispatch_src[name] = (
                    self._n_spec_dispatch_src.get(name, 0) + 1)
        if tree:
            spec_j = self._spec_tree_for(self._window(extra=N - 1))
            with self._phase("upload"):
                up = [jnp.asarray(a) for a in (tokens, depths, anc, drafts,
                                               sib_tok, sib_node, max_acc)]
            with self._phase("launch"):
                self._note_launch("decode")
                (accepted, used_sib, correction, self._next_dev,
                 self._cache, self._keys, self._ring_dev) = spec_j(
                    self._params, *up, self._cache, self._active_dev,
                    self._temps_dev, self._top_ks_dev, self._top_ps_dev,
                    self._keys, self._ring_dev, self._rps_dev)
            with self._phase("readback"):
                used = np.asarray(used_sib)  # graftcheck: sync-ok 3xB int32 verify readback
        else:
            spec_j = self._spec_for(self._window(extra=K))
            with self._phase("upload"):
                up = [jnp.asarray(a) for a in (tokens, drafts, max_acc)]
            with self._phase("launch"):
                self._note_launch("decode")
                (accepted, correction, self._next_dev, self._cache,
                 self._keys, self._ring_dev) = spec_j(
                    self._params, *up, self._cache, self._active_dev,
                    self._temps_dev, self._top_ks_dev, self._top_ps_dev,
                    self._keys, self._ring_dev, self._rps_dev)
            used = np.zeros((B,), np.int32)
        self._last_out = accepted
        with self._phase("readback"):
            acc = np.asarray(accepted)  # graftcheck: sync-ok 2xB int32 verify readback
            corr = np.asarray(correction)  # graftcheck: sync-ok same dispatch, already synced
        with self._phase("stream"):
            # Per-source EMA update over the rows THAT source drafted this
            # tick (a source is judged on its own proposals only — the old
            # all-active-rows denominator let undrafted rows dilute the
            # signal). Zero-acceptance ticks decay fast (_SPEC_EMA_ZERO_
            # ALPHA) so a never-accepting workload stops paying verify
            # forwards within a few ticks. Sources also roll back their
            # state to the last accepted position here (the model drafter's
            # KV rewind — observe()).
            for s in self._sources:
                rows_s = src_rows.get(s.name) or []
                if not rows_s:
                    continue
                n_acc = sum(int(acc[r]) for r in rows_s)
                self._n_spec_accepted_src[s.name] += n_acc
                tick_acc = n_acc / len(rows_s)
                alpha = (_SPEC_EMA_ZERO_ALPHA if n_acc == 0
                         else _SPEC_EMA_ALPHA)
                ema = (1 - alpha) * self._spec_ema[s.name] + alpha * tick_acc
                if tick_acc >= _SPEC_EMA_FLOOR:
                    # Probe recovery: a deeply-decayed EMA (long dry spell)
                    # would need several good probes x _SPEC_PROBE_EVERY
                    # ticks to climb back over the floor — one probe whose
                    # acceptance already clears it is the recovery signal,
                    # so re-enable immediately.
                    ema = max(ema, _SPEC_EMA_SEED)
                self._spec_ema[s.name] = ema
                for r in rows_s:
                    # MAIN-CHAIN accepted prefix only: a used sibling's
                    # token diverges from what this source fed itself, so
                    # the drafter must rewind to just before it (the EMA
                    # above still credits the full acceptance).
                    s.observe(r, int(acc[r]) - int(used[r]))
            for row, slot in enumerate(self._slots):
                if slot is None:
                    continue
                if slot.cancelled.is_set():
                    self._release(row)
                    continue
                a = int(acc[row])
                self._n_spec_accepted += a
                if tree and row in proposals:
                    self._n_spec_tree_accepted += a
                if int(used[row]):
                    # Position a-1 accepted the SIBLING token, not the main
                    # draft; the correction then comes from the sibling
                    # node's own logits.
                    a0 = a - 1
                    emitted = ([int(t) for t in drafts[row, :a0]]
                               + [int(sib_tok[row, a0])] + [int(corr[row])])
                else:
                    emitted = ([int(t) for t in drafts[row, :a]]
                               + [int(corr[row])])
                for t in emitted:
                    slot.ctx_len += 1    # per token, mirroring the plain tick
                    if not self._append_token(slot, row, t):
                        self._release(row)
                        break
        return True

    def _append_token(self, slot: _Slot, row: int, tok: int) -> bool:
        """Record one sampled token; stream its text. Returns False when the
        request is finished (eos, stop string, length/context limits)."""
        now = time.monotonic()
        if slot.last_emit_t:
            # Client-perceived inter-token gap (TBT): tokens inside one
            # fused/spec burst land together (~0 ms), the burst boundary
            # carries the dispatch interval plus any admission stall —
            # exactly what the p95 must expose.
            self._tbt_hist.observe((now - slot.last_emit_t) * 1e3)
        slot.last_emit_t = now
        if tok in self._stop_ids:
            self._flush_text(slot, final=True)
            slot.finish()
            return False
        slot.ids.append(tok)
        for s in self._sources:
            # n-gram: extend the row's index. Model drafter: no-op here
            # (its KV catches up lazily at the next draft dispatch).
            s.append(row, tok)
        if slot.stats is not None:
            slot.stats.completion_tokens = len(slot.ids)
        stop_hit = self._flush_text(slot)
        if stop_hit:
            slot.finish()
            return False
        if len(slot.ids) >= slot.max_new:
            self._flush_text(slot, final=True)
            slot.finish()
            return False
        # Context full: the next decode step would write slot ctx_len,
        # which must stay < the slot's admitted page budget (host mirror
        # avoids a device sync).
        if slot.ctx_len + 1 >= slot.ctx_budget:
            self._flush_text(slot, final=True)
            slot.finish()
            return False
        return True

    def _flush_text(self, slot: _Slot, final: bool = False) -> bool:
        """Incremental detokenisation + streaming.

        Decodes only the ids not yet folded into ``slot.text`` (amortised
        O(1) per token — never the whole history), holding back a trailing
        partial UTF-8 sequence (surfaces as U+FFFD) until completed. Also
        holds back any text suffix that is a prefix of a stop string, so a
        stop straddling a token boundary never leaks its prefix to the
        client. Returns True when a stop string matched (text past it is
        dropped, matching Ollama)."""
        pending = self.tokenizer.decode(slot.ids[slot.decoded_upto:])
        if pending:
            if not final and pending.endswith("�"):
                return False    # wait for the rest of the multibyte char
            slot.text += pending
            slot.decoded_upto = len(slot.ids)

        stops = [s for s in slot.req.options.stop if s]
        max_stop = max((len(s) for s in stops), default=0)
        for s in stops:
            # Overlap window: a match can start up to len(s)-1 chars before
            # the newly decoded region; never earlier (holdback below
            # guarantees streamed text cannot already contain a prefix).
            idx = slot.text.find(s, max(0, slot.streamed - len(s) + 1))
            if idx >= 0:
                slot.push(slot.text[slot.streamed: idx])
                slot.text = slot.text[:idx]
                slot.streamed = idx
                return True
        emit_to = len(slot.text)
        if not final and stops:
            # Longest suffix of text that is a proper prefix of any stop
            # string stays buffered until disambiguated.
            for k in range(min(max_stop - 1, len(slot.text)), 0, -1):
                suffix = slot.text[-k:]
                if any(s.startswith(suffix) for s in stops):
                    emit_to = len(slot.text) - k
                    break
        if emit_to > slot.streamed:
            slot.push(slot.text[slot.streamed: emit_to])
            slot.streamed = emit_to
        return False

    def _fail_all_and_reset(self) -> None:
        """Error-path recovery: fail every in-flight request and rebuild the
        device state and the page allocator from scratch.
        Wholesale by design — selective recovery here risks leaking pages
        (slots cleared without ``_alloc.free``) or leaving a stale row
        table aimed at pages the allocator has handed to a new request,
        whose KV a parked row's per-step garbage scatter would then
        corrupt. All compiled programs key on shapes, which don't change,
        so the only cost is re-allocating the buffers."""
        self._flight.note("reset", self._loop_iter,
                          failed=sum(s is not None for s in self._slots))
        try:
            path = self._flight.dump("fail_all_and_reset")
            log.warning("flight recorder dumped to %s", path)
        except OSError as e:
            log.warning("flight-recorder dump failed: %s", e)
        for i, s in enumerate(self._slots):
            if s is not None:
                s.fail("internal error: serving state was reset")
                self._slots[i] = None
        for s in self._admit_carry:
            # Their reserved pages came from the allocator being rebuilt —
            # freeing them into the NEW allocator would duplicate ids.
            s.pages = None
            s.fail("internal error: serving state was reset")
        self._admit_carry = []
        pc, self._prefill_carry = self._prefill_carry, None
        if pc is not None:
            # Half-prefilled rows were never installed in _slots; their
            # pages also belong to the allocator being rebuilt.
            for s in pc.chunk:
                s.pages = None
                s.fail("internal error: serving state was reset")
        for s in self._sources:
            # The drafter's donated cache may have been consumed by the
            # same failed call; its per-row state maps dead rows either
            # way — rebuild alongside the target state.
            s.reset()
        if self._tier is not None:
            # Resident sessions' pages are ids into the allocator being
            # rebuilt, over pool content being re-zeroed — drop them.
            # Parked payloads live on host and survive the reset.
            self._tier.reset_resident()
        self._reset_device_state()

    # -- multi-tier KV: session park / wake (serve/kv_tier.py) ---------------

    def _session_key(self, slot: _Slot) -> Optional[str]:
        """Stable key for the conversation this slot belongs to: the
        client's explicit session id (api front: ``X-Session-Id`` header
        / ``session`` body field — the router's affinity id, so a
        session's KV and its routing home coincide), else a hash of the
        prompt's first HEAD_GRAIN token ids (context continuation names
        no session, but a follow-up's prompt head is verbatim the prior
        turn's — so the derived key matches across turns). None = too
        short to index and anonymous: not worth retaining."""
        sid = getattr(slot.req, "session", "")
        if sid:
            return f"sid:{sid}"
        from .kv_tier import head_key
        # graftcheck: sync-ok host token ids -> bytes for hashing, no device readback
        return head_key(slot.prompt_ids)

    # graftcheck: runs-on _loop
    def _retain_session(self, slot: _Slot, row: int) -> bool:
        """Keep a finished request's KV open as a session instead of
        freeing it. Returns True when the row's cleanup (table zero +
        page ownership) was fully handled here — the caller skips the
        legacy free path. The trusted content is tokens[0:ctx_len]
        (prompt + all generated but the last; the final emitted token's
        KV was never written), spanning ceil(ctx_len / page_size)
        pages; trailing growth pages return to the pool. An in-flight
        pipelined tick may still garbage-write past ctx_len through the
        pre-zero table — those writes land beyond the trusted region
        (kept tail page slack), in a trimmed page that any re-user
        fully overwrites AFTER the in-flight tick by dispatch order,
        or in garbage page 0. All contained."""
        key = self._session_key(slot)
        if key is None or slot.ctx_len <= 0:
            return False
        toks = (list(slot.prompt_ids) + list(slot.ids))[: slot.ctx_len]
        if len(toks) < slot.ctx_len:
            return False          # host mirror out of sync — don't trust
        from .kv_tier import SessionKV
        if not slot.pages:
            return False
        keep = min(len(slot.pages), self._alloc.pages_for(slot.ctx_len))
        kept, extra = slot.pages[:keep], slot.pages[keep:]
        try:
            with self._phase("launch"):
                self._cache = self._zero_row_j(
                    self._cache, jnp.asarray(row, jnp.int32))
        except Exception:   # noqa: BLE001 — same contract as _release
            log.exception("row-table zero failed; resetting")
            self._fail_all_and_reset()
            return True
        if extra:
            self._alloc.free(extra)
        slot.pages = None
        old = self._tier.take(key)
        if old is not None:
            self._recycle_session(old)
        self._tier.insert(SessionKV(key=key, tokens=tuple(toks),
                                    length=slot.ctx_len, pages=kept))
        self._tier_enforce()
        return True

    def _recycle_session(self, sess) -> None:
        """Return a replaced session's resident pages to the allocator
        (parked payloads are plain host arrays — refcount frees them)."""
        if sess.pages:
            self._alloc.free(sess.pages)
            sess.pages = None

    # graftcheck: runs-on _loop
    def _park_session(self, sess) -> None:
        """Demote one resident session to a host-RAM copy: ONE gather
        dispatch of the raw pool words (int8 + head-major scales
        included), one readback, pages back to the allocator.
        Wake re-uploads the same bits, so a parked-then-resumed greedy
        stream is byte-identical to one that never left HBM."""
        sess = self._tier.take(sess.key)
        if sess is None or not sess.pages:
            return
        pages, n = sess.pages, len(sess.pages)
        P2 = 1 << max(0, n - 1).bit_length()    # pow2 shape bucket
        padded = pages + [0] * (P2 - n)
        with self._phase("launch"):
            out = self._gather_pages_j(self._cache,
                                       jnp.asarray(padded, jnp.int32))
        with self._phase("readback"):
            # graftcheck: sync-ok the park IS the host copy — one readback per parked session
            payload = tuple(None if a is None else np.asarray(a)
                            for a in out)
        self._alloc.free(pages)
        from .kv_tier import SessionKV
        self._tier.insert(SessionKV(
            key=sess.key, tokens=sess.tokens, length=sess.length,
            host=(payload, n),
            nbytes=sum(a.nbytes for a in payload if a is not None),
            last_used=sess.last_used))
        self._tier.note_parked(pages_freed=n)
        self._tier_enforce()

    # graftcheck: runs-on _loop
    def _reclaim_pages(self, need: int) -> None:
        """Page-pool pressure: park resident sessions (LRU first) until
        ``need`` pages are free or none remain — idle sessions' HBM
        turns into admission room instead of blocking requests."""
        for sess in self._tier.park_candidates(force=True):
            if self._alloc.free_pages >= need:
                return
            self._park_session(sess)

    # graftcheck: runs-on _loop
    def _tier_enforce(self) -> None:
        """Apply the tier policies after an insert: the host byte
        budget (cost = bytes x recency over parked sessions) and the
        session index cap (plain LRU). Resident victims' pages return
        to the allocator; parked victims just drop (their follow-up
        cold-admits — tiering is invisible in outputs)."""
        for sess in self._tier.host_victims():
            self._tier.drop(sess)
        for sess in self._tier.overflow_victims():
            pages = self._tier.drop(sess)
            if pages:
                self._alloc.free(pages)

    # graftcheck: runs-on _loop
    def _tier_sweep(self) -> None:
        """Idle parking: at most one park per ~250 ms loop pass (each
        is a gather dispatch + readback — a bounded stall, amortised
        the way promotion builds are)."""
        if self._tier is None:
            return
        now = time.monotonic()
        if now - self._last_tier_sweep < 0.25:
            return
        self._last_tier_sweep = now
        cands = self._tier.park_candidates(now=now)
        if cands:
            self._park_session(cands[0])

    def _wake_window(self, S: int, start: int) -> int:
        """Attention window for a wake dispatch: covers every live
        row's context plus the deepest waking session's start + S
        suffix slots (the wake forward's query j attends positions
        <= start + j)."""
        deepest = max((s.ctx_len for s in self._slots if s is not None),
                      default=0)
        need = max(deepest + 1, start + S)
        w = min(128, self.max_seq)
        while w < need:
            w *= 2
        return min(w, self.max_seq)

    # graftcheck: runs-on _loop
    def _wake_candidate(self, slot: _Slot) -> Optional[int]:
        """Suffix bucket S when ``slot`` can wake an open session, else
        None (cold admission). Peeks only — _admit_wake claims the
        session when the dispatch actually happens. For parked sessions
        this also starts the host->device payload transfer NOW
        (device_put is async), so the copy flies while any admission
        work queued ahead — a chunked-prefill ladder included — runs."""
        sess = self._tier.lookup(self._session_key(slot) or "",
                                 slot.prompt_ids)
        if sess is None:
            return None
        S = self._serving_bucket(len(slot.prompt_ids) - sess.length)
        if sess.length + S > self.max_seq or S > _WAKE_MAX_SUFFIX:
            return None
        if self._any_active():
            w = self._wake_window(S, sess.length)
            if (w, S) not in self._wake_shapes_run:
                return None   # a lazy compile would stall live streams
        slot.wake_key = sess.key
        if sess.parked:
            if slot.wake_dev is None or slot.wake_dev[0] is not sess:
                # (Re)start the async H2D prefetch — a stamp mismatch
                # means the session was replaced/re-parked since the
                # last match and the old payload is stale.
                slot.wake_dev = (sess, tuple(
                    None if a is None else jnp.asarray(a)
                    for a in sess.host[0]))
        else:
            slot.wake_dev = None
        return S

    # graftcheck: runs-on _loop
    def _wake_install_kv(self, slot: _Slot, row: int, sess,
                         tables: "np.ndarray") -> bool:
        """Wake KV placement: reserve the row's full page budget,
        scatter a parked payload into the first pages (one dispatch —
        the prefetched device arrays land here), and point the host
        table at session pages + growth pages in logical order. False =
        reservation failed even after parking others; the session goes
        back untouched and the request cold-admits."""
        need = self._alloc.pages_for(len(slot.prompt_ids)
                                     + slot.max_new + 1)
        need = min(need, self._cache.max_pages_per_row)
        if sess.parked:
            arrays, n = sess.host
            need = max(need, n)
            pages = self._alloc.alloc(need)
            if pages is None:
                self._reclaim_pages(need)
                pages = self._alloc.alloc(need)
            if pages is None:
                self._tier.insert(sess)
                slot.wake_dev = None     # demote must not pin the copy
                return False
            # The prefetched payload is only usable if it came from THIS
            # session object — a replaced/re-parked session's bytes (and
            # possibly shapes) differ.
            dev = None
            if slot.wake_dev is not None and slot.wake_dev[0] is sess:
                dev = slot.wake_dev[1]
            slot.wake_dev = None
            if dev is None:
                dev = tuple(None if a is None else jnp.asarray(a)
                            for a in arrays)
            P2 = arrays[0].shape[1]
            padded = pages[:n] + [0] * (P2 - n)
            with self._phase("launch"):
                self._cache = self._scatter_pages_j(
                    self._cache, jnp.asarray(padded, jnp.int32),
                    dev[0], dev[1], dev[2], dev[3])
        else:
            extra = need - len(sess.pages)
            if extra > 0:
                more = self._alloc.alloc(extra)
                if more is None:
                    self._reclaim_pages(extra)
                    more = self._alloc.alloc(extra)
                if more is None:
                    self._tier.insert(sess)
                    slot.wake_dev = None
                    return False
                pages = sess.pages + more
            else:
                pages = sess.pages
            sess.pages = None          # ownership moves to the slot
        slot.pages = pages
        slot.ctx_budget = min(len(pages) * self.page_size, self.max_seq)
        tables[row, : len(pages)] = pages
        return True

    # graftcheck: runs-on _loop
    def _admit_wake(self, chunk: list[_Slot], rows: list[int],
                    S: int) -> tuple[list[_Slot], list[int]]:
        """One fused wake dispatch for up to len(chunk) sessions sharing
        a suffix bucket: claim each session, place its KV (resident
        pages re-enter the new row's table; parked payloads scatter
        back in one dispatch), then the wake program installs
        tables/lengths ATOMICALLY with the suffix forward and the
        first-token sample. Returns (demoted, unused_rows): slots whose
        session vanished since matching or whose reservation failed —
        the caller cold-admits them this same round."""
        failpoint("serve.scheduler.admit")
        t0 = time.monotonic()
        B = self.num_slots
        demoted: list[_Slot] = []
        unused: list[int] = []
        claimed: list[tuple[_Slot, int, object]] = []
        for slot, row in zip(chunk, rows):
            sess = self._tier.claim(slot.wake_key or "", slot.prompt_ids)
            slot.wake_key = None
            if sess is None:
                slot.wake_dev = None
                demoted.append(slot)
                unused.append(row)
                continue
            claimed.append((slot, row, sess))
        if not claimed:
            return demoted, unused
        w = self._wake_window(S, max(s.length for _, _, s in claimed))
        if self._any_active() and (w, S) not in self._wake_shapes_run:
            # The batched window outgrew the per-slot estimate (another
            # waking session is deeper): compiling now would stall live
            # streams — put everything back and cold-admit.
            for slot, row, sess in claimed:
                self._tier.insert(sess)
                slot.wake_dev = None
                demoted.append(slot)
                unused.append(row)
            return demoted, unused
        packed, (tokens, ints, floats, rings, tables) = _admit_buffer(
            B, S, self._cache.max_pages_per_row, self.config.vocab_size)
        live: list[tuple[_Slot, int]] = []
        for slot, row, sess in claimed:
            if not self._wake_install_kv(slot, row, sess, tables):
                demoted.append(slot)
                unused.append(row)
                continue
            suffix = slot.prompt_ids[sess.length:]
            o = slot.req.options
            tokens[row, : len(suffix)] = suffix
            ints[:4, row] = (len(suffix), sess.length, slot.seed, o.top_k)
            floats[:, row] = (o.temperature, o.top_p, o.repeat_penalty)
            if o.repeat_penalty != 1.0:
                start_i = max(0, len(slot.prompt_ids) - _RING)
                for p_i in range(start_i, len(slot.prompt_ids)):
                    rings[row, p_i % _RING] = slot.prompt_ids[p_i]
            live.append((slot, row))
        if not live:
            return demoted, unused
        self._ledger.cut(ADMIT)
        # A wake is an admission whose program runs every row at the
        # suffix bucket: B x S positions for the waking rows' suffixes.
        self._n_loop_passes += self.config.ut_steps
        self._n_admit_batches += 1
        self._n_admit_rows_padded += B
        self._n_prefill_tokens += sum(int(ints[0, row]) for _, row in live)
        self._n_prefill_pairs += sum(
            _causal_pairs(int(ints[1, row]),
                          int(ints[1, row]) + int(ints[0, row]))
            for _, row in live)
        self._n_prefill_padded += B * S
        prog = self._wake_for(w, S)
        packed = self._admit_upload(packed)
        with self._phase("launch"):
            self._note_launch("admit")
            (toks_dev, self._cache, self._keys, self._next_dev,
             self._temps_dev, self._top_ks_dev, self._top_ps_dev,
             self._ring_dev, self._rps_dev) = prog(
                self._params, packed, self._cache, self._keys, self._next_dev,
                self._temps_dev, self._top_ks_dev, self._top_ps_dev,
                self._ring_dev, self._rps_dev)
        self._last_out = toks_dev
        self._wake_shapes_run.add((w, S))
        with self._phase("readback", rows=len(live)):
            # graftcheck: sync-ok B int32 first tokens — wake TTFT depends on it
            first_toks = np.asarray(toks_dev)
        with self._phase("stream"):
            # Draft-source admission before the install loop (same ordering
            # contract as _install_admitted: release never precedes admit).
            if self.spec_k and self._sources:
                ctxs = {row: slot.prompt_ids for slot, row in live}
                rws = [row for _, row in live]
                for s in self._sources:
                    pf = getattr(s, "prefill", None)
                    if pf is not None:
                        pf(rws, ctxs)
                    else:
                        for r in rws:
                            s.admit(r, ctxs[r])
            now = time.monotonic()
            wake_ms = (now - t0) * 1e3
            self._n_admitted += len(live)
            # Prompt tokens whose prefill the wake skipped (everything but
            # the new turn's suffix) — the compute-saved counter.
            self._tier.note_waked(
                len(live),
                tokens_saved=sum(int(ints[1, row]) for _, row in live))
            tr = self._trace
            for slot, row in live:
                self._wake_hist.observe(wake_ms)
                slot.depart()
                if slot.stats is not None:
                    slot.stats.ttft_s = now - slot.req.arrival_time
                if tr is not None and slot.req.trace_sampled:
                    tr.add(slot.req.trace_id, "sched.queue_wait",
                           slot.req.arrival_time, t0 - slot.req.arrival_time)
                    tr.add(slot.req.trace_id, "sched.wake", t0, now - t0,
                           tokens_saved=int(ints[1, row]), row=row)
                    slot.cut0 = self._ledger.totals()
                slot.ctx_len = len(slot.prompt_ids)
                self._slots[row] = slot
                if not self._append_token(slot, row, int(first_toks[row])):
                    self._release(row)
        return demoted, unused

    def _release(self, row: int) -> None:
        """Free a row (finish() has already been queued where a consumer is
        still listening; cancelled consumers are gone). The row's page
        table is zeroed on device BEFORE returning its pages to the
        allocator — a stale parked row keeps scattering per-step garbage,
        which must land in the garbage page, never a re-allocated one."""
        slot = self._slots[row]
        self._slots[row] = None
        if (slot is not None and self._trace is not None
                and slot.req.trace_sampled and slot.stats is not None
                and slot.stats.ttft_s is not None):
            # Decode phase: first token -> release (per-tick gaps are
            # the inter_token_ms histogram's job; the span carries the
            # request's share of the decode wall), and beside it the
            # request's own differences of the interval ledger: the
            # decode steps booked while it decoded, those in episodes
            # admission work opened, that work's dispatches by class,
            # and as the sibling span's duration the SUM of those
            # episodes' intervals (not one stretch of time: it shares
            # sched.decode's start and never passes its length). The
            # ledger books an interval two dispatches after its work
            # was queued, so the differences are off by at most two
            # intervals at each end (obs/intervals.py).
            t_first = slot.req.arrival_time + slot.stats.ttft_s
            wall = time.monotonic() - t_first
            end = self._ledger.totals()
            cut_s, steps, steps_cut, chunks, padded, admits = (
                b - a for a, b in zip(slot.cut0 or end, end))
            self._trace.add(slot.req.trace_id, "sched.decode", t_first,
                            wall, tokens=len(slot.ids), row=row,
                            steps=steps, steps_cut=steps_cut, chunks=chunks,
                            padded=padded, admits=admits,
                            # A looped model's span also says how many
                            # passes over the stack its steps were.
                            **({"passes": steps * self.config.ut_steps}
                               if self._looped else {}))
            self._trace.add(slot.req.trace_id, "sched.decode.cut", t_first,
                            min(cut_s, wall))
        for s in self._sources:
            s.release(row)
        if slot is not None and self._tier is not None:
            if self._retain_session(slot, row):
                return
        if slot is not None and slot.pages:
            try:
                with self._phase("launch"):
                    self._cache = self._zero_row_j(
                        self._cache, jnp.asarray(row, jnp.int32))
            except Exception:   # noqa: BLE001
                # Whether or not the donated cache survived, the row's
                # table was not provably zeroed, so its pages can't go
                # back to the allocator — reset wholesale (leak-free).
                log.exception("row-table zero failed; resetting")
                self._fail_all_and_reset()
                return
            self._alloc.free(slot.pages)
            slot.pages = None
