"""Token sampling: greedy, temperature, top-k, top-p.

Three implementations of the same semantics:

- :func:`sample` — jit-friendly JAX, f32 logits [B, vocab] -> ids [B], one
  shared option set for the whole batch. Used by the reference generation
  loops (models/generate.py).
- :func:`sample_batched` — jit-friendly JAX with **per-row** options and
  per-row PRNG keys. Used inside the continuous-batching scheduler's fused
  decode step (serve/scheduler.py), where every batch row belongs to a
  different request: sampling on-device shrinks the per-tick device->host
  transfer from the full [B, vocab] logits to B int32 tokens.
- :func:`sample_np` — host-side numpy over a single row; the hermetic
  reference oracle for the device samplers' filtering semantics.

The option set mirrors what the Ollama contract exposes via ``options``
(serve/backend.py GenerateOptions), so server-side sampling is a drop-in
for what the reference delegated to Ollama.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from .layers import NEG_INF


def greedy(logits: jax.Array) -> jax.Array:
    return jnp.argmax(logits, axis=-1).astype(jnp.int32)


def _apply_top_k(logits: jax.Array, top_k: int) -> jax.Array:
    if top_k <= 0:
        return logits
    k = min(top_k, logits.shape[-1])
    kth = jax.lax.top_k(logits, k)[0][..., -1:]
    return jnp.where(logits < kth, NEG_INF, logits)


def _apply_top_p(logits: jax.Array, top_p: float) -> jax.Array:
    if top_p >= 1.0:
        return logits
    sorted_logits = jnp.sort(logits, axis=-1)[..., ::-1]
    probs = jax.nn.softmax(sorted_logits, axis=-1)
    cum = jnp.cumsum(probs, axis=-1)
    # Keep the smallest prefix with cumulative prob >= top_p (always >= 1
    # tok — the explicit set makes that hold even for top_p <= 0).
    keep = cum - probs < top_p
    keep = keep.at[..., 0].set(True)
    threshold = jnp.min(jnp.where(keep, sorted_logits, jnp.inf), axis=-1,
                        keepdims=True)
    return jnp.where(logits < threshold, NEG_INF, logits)


def sample(logits: jax.Array, key: jax.Array, temperature: float = 0.0,
           top_k: int = 0, top_p: float = 1.0) -> jax.Array:
    """Sample next tokens. temperature<=0 means greedy (matching Ollama's
    deterministic mode)."""
    if temperature <= 0.0:
        return greedy(logits)
    logits = logits / jnp.asarray(temperature, logits.dtype)
    logits = _apply_top_k(logits, top_k)
    logits = _apply_top_p(logits, top_p)
    return jax.random.categorical(key, logits, axis=-1).astype(jnp.int32)


def apply_repeat_penalty(logits: jax.Array, ring: jax.Array,
                         rp: jax.Array) -> jax.Array:
    """Ollama-style repetition penalty over a recent-token ring.

    logits: [B,V]; ring: [B,R] recent token ids (entries >= V are empty
    slots and drop out of the scatter); rp: [B] penalty (1.0 = identity).
    Tokens present in the ring have positive logits divided by rp and
    negative logits multiplied by rp — Ollama/CTRL semantics. Must run
    BEFORE top-k/top-p: the penalty reorders candidates."""
    B, V = logits.shape
    mask = jnp.zeros((B, V), bool).at[
        jnp.arange(B)[:, None], ring].set(True, mode="drop")
    rp = rp[:, None]
    pen = jnp.where(logits > 0, logits / rp, logits * rp)
    return jnp.where(mask, pen, logits)


def _warp(sorted_logits: jax.Array, temperature: jax.Array,
          top_k: jax.Array, top_p: jax.Array) -> jax.Array:
    """Shared per-row warping over a descending top-c candidate axis:
    temperature, then top-k, then top-p on the renormalised distribution
    (the filter order of :func:`sample` / :func:`sample_np`). temperature/
    top_k/top_p are [B] and broadcast over any middle axes of
    ``sorted_logits`` [B, ..., C]. Returns warped probabilities.

    One implementation on purpose: :func:`sample_batched` (the decode
    tick) and :func:`spec_verify_batched` (speculative acceptance) MUST
    warp identically or speculative sampling stops matching sequential
    sampling's distribution."""
    extra = sorted_logits.ndim - 2
    def bx(v):          # [B] -> [B, 1..., 1] matching sorted_logits
        return v.reshape(v.shape[0], *([1] * extra), 1)
    C = sorted_logits.shape[-1]
    ranks = jnp.arange(C)
    keep_k = (bx(top_k) <= 0) | (ranks < bx(top_k))
    temp = jnp.maximum(bx(temperature), 1e-6)
    k_masked = jnp.where(keep_k, sorted_logits / temp, NEG_INF)
    probs = jax.nn.softmax(k_masked, axis=-1)
    cum = jnp.cumsum(probs, axis=-1)
    keep_p = (bx(top_p) >= 1.0) | ((cum - probs) < bx(top_p))
    keep = (keep_k & keep_p).at[..., 0].set(True)     # never empty
    return jax.nn.softmax(jnp.where(keep, sorted_logits / temp, NEG_INF),
                          axis=-1)


def sample_batched(logits: jax.Array, keys: jax.Array, temperature: jax.Array,
                   top_k: jax.Array, top_p: jax.Array,
                   top_c: int = 64, ring: Optional[jax.Array] = None,
                   rp: Optional[jax.Array] = None,
                   live: Optional[jax.Array] = None
                   ) -> tuple[jax.Array, jax.Array]:
    """Per-row sampling: logits [B,V] f32, keys [B,2] (one PRNG key per
    row), temperature/top_k/top_p [B]. Returns (tokens [B] int32,
    advanced keys [B,2]).

    Same filters as :func:`sample` / :func:`sample_np`, vectorised over
    per-row option values: temperature<=0 is greedy; top_k<=0 disables
    top-k; top_p>=1 disables top-p; top_p<=0 degrades to top-1.

    Runs inside the fused decode step, so it must be cheap on the hot
    path: candidates are truncated to the ``top_c`` highest logits via
    ``lax.top_k`` instead of a full-vocab sort (a 32×128k argsort costs
    more than the whole decode step on TPU). Exact when the vocab fits in
    ``top_c`` or the caller's top_k is <= top_c; otherwise the (numerically
    negligible) tail mass past the top-64 candidates is dropped — the
    standard TPU-serving truncation. Two minor divergences from sample_np:
    per-row dynamic k keeps exactly k tokens (ties at the k-th value break
    by sort order), and sampling never leaves the top-``top_c`` set.

    Even ``lax.top_k`` is the dearest thing here (1.30 ms of a 14.8 ms
    step at a vocabulary of 200 K), and a greedy row throws its result
    away, so the candidates are sorted, warped and drawn from under ONE
    ``lax.cond``, taken where some row samples; a step of greedy rows
    takes the argmax and nothing else. ``live`` [B] bool names the rows
    the predicate reads (None: every row): a released slot keeps its
    last request's temperature on the device until an admission writes
    over it, so the decode step hands in its ``active`` mask; an
    admission's rows are all real or padding, whose temperature is 0
    (serve/scheduler._admit_buffer). The penalty, the argmax and the
    key split stay outside the ``cond``: keys advance the same whichever
    branch ran, and a live row's token is the same too (a row not live
    gets the argmax where nobody samples, and nobody reads it).
    """
    B, V = logits.shape
    if ring is not None:
        logits = apply_repeat_penalty(logits, ring, rp)
    split = jax.vmap(lambda k: jax.random.split(k, 2))(keys)   # [B,2,2]
    new_keys, subs = split[:, 0], split[:, 1]
    greedy_row = temperature <= 0.0
    greedy_tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)

    def sampled_or_greedy(logits, subs):
        sorted_logits, order = jax.lax.top_k(logits, min(top_c, V))  # [B,C]
        wprobs = _warp(sorted_logits, temperature, top_k, top_p)
        choice = jax.vmap(jax.random.categorical)(
            subs, jnp.where(wprobs > 0, jnp.log(wprobs), NEG_INF))  # ranks
        sampled = jnp.take_along_axis(order, choice[:, None], axis=-1)[:, 0]
        return jnp.where(greedy_row, greedy_tok, sampled).astype(jnp.int32)

    samples = ~greedy_row if live is None else live & ~greedy_row
    tok = jax.lax.cond(jnp.any(samples), sampled_or_greedy,
                       lambda logits, subs: greedy_tok, logits, subs)
    return tok, new_keys


def sample_step_batched(logits: jax.Array, keys: jax.Array,
                        temperature: jax.Array, top_k: jax.Array,
                        top_p: jax.Array, *, ring: jax.Array, rp: jax.Array,
                        emit_pos: jax.Array, active: jax.Array,
                        top_c: int = 64
                        ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """One decode tick's sample + penalty-ring update, scan-carry shaped.

    The fused multi-step decode path (models/llama.decode_fused) carries
    (keys, ring) through a ``lax.scan`` and the plain one-step decode
    program applies the identical ops once — both MUST route through this
    single implementation, or the fused path's bit-identity-to-K-plain-
    ticks contract (serve/scheduler.py) silently breaks the first time
    one copy drifts.

    logits: [B,V] f32; keys/temperature/top_k/top_p/rp: [B] per-row
    state; ring: [B,R] recent-token penalty window; emit_pos: [B]
    absolute context position of the emitted token (pre-advance lengths
    + 1 — the caller computes it BEFORE the decode step advances
    lengths); active: [B] — parked rows' ring writes drop via the
    out-of-range column sentinel, and their key still splits (the same
    unconditional split the plain program always did, so fused and
    plain key streams agree row-for-row); only an active row's
    temperature can ask :func:`sample_batched` for the candidate sort.

    Returns (tokens [B] int32, advanced keys [B,2], updated ring [B,R]).
    """
    toks, keys = sample_batched(logits, keys, temperature, top_k, top_p,
                                top_c=top_c, ring=ring, rp=rp, live=active)
    B, R = ring.shape
    idx = jnp.where(active, emit_pos % R, R)
    ring = ring.at[jnp.arange(B), idx].set(toks, mode="drop")
    return toks, keys, ring


def spec_verify_batched(logits: jax.Array, drafts: jax.Array,
                        keys: jax.Array, temperature: jax.Array,
                        top_k: jax.Array, top_p: jax.Array,
                        max_accept: jax.Array,
                        top_c: int = 64, ring: Optional[jax.Array] = None,
                        rp: Optional[jax.Array] = None,
                        ctx_len: Optional[jax.Array] = None
                        ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Speculative-decoding acceptance over one verify pass.

    logits: [B,S,V] f32 from models.llama.verify_step (position j is the
    model's distribution AFTER input j); drafts: [B,S-1] proposed tokens
    (the inputs at positions 1..S-1); keys/temperature/top_k/top_p: [B]
    per-row sampling state (serve/scheduler.py); max_accept: [B] budget
    cap (0..S-1).

    The draft distribution q is a point mass (prompt-lookup drafting), so
    exact speculative sampling reduces to: accept draft_j with
    probability p_warped(draft_j); on first rejection sample the
    replacement from p with the draft token removed and renormalised; if
    every draft is accepted, sample the bonus token from the final
    position's distribution unmodified. Greedy rows (temperature<=0)
    accept while draft == argmax and correct with the argmax — bit-exact
    with the sequential greedy loop. The warped distribution (same
    temperature/top-k/top-p filters and the same ``top_c`` truncation as
    :func:`sample_batched`) is what acceptance and residual sampling use,
    so the emitted stream is distributed exactly as sequential sampling.

    Returns (accepted [B] int32 in [0, S-1], correction [B] int32 — the
    token at stream position ``accepted`` —, advanced keys [B,2]).
    """
    B, S, V = logits.shape
    K = S - 1
    if ring is not None:
        # Per-position recent window with exact SLIDING semantics:
        # sequential sampling at stream position j penalises the last
        # ``Rw`` tokens of (context + drafts[:j]) — each hypothetical
        # draft both ENTERS the window and EVICTS the oldest ring token
        # (the one at ring slot (ctx_len + i) % Rw, which holds context
        # position ctx_len + i - Rw). Occurrence COUNTS (not set union)
        # make eviction correct when a token also occurs elsewhere in
        # the window. ``ctx_len`` [B]: context length before this tick's
        # input token's position (the scheduler's pre-advance lengths).
        Rw = ring.shape[1]
        in_cnt = jnp.zeros((B, V), jnp.float32).at[
            jnp.arange(B)[:, None], ring].add(1.0, mode="drop")
        cnt = jnp.broadcast_to(in_cnt[:, None], (B, S, V))
        if K > 0:
            shifts = jnp.arange(1, K + 1)[None, :]              # [1,K]
            ev_slots = (ctx_len[:, None] + shifts) % Rw         # [B,K]
            ev = jnp.take_along_axis(ring, ev_slots, axis=1)    # [B,K]
            zero = jnp.zeros((B, 1, V), jnp.float32)
            # one_hot of the empty-slot sentinel (>= V) is all-zero, so
            # not-yet-full rings evict nothing.
            ev_pref = jnp.concatenate(
                [zero, jnp.cumsum(jax.nn.one_hot(ev, V,
                                                 dtype=jnp.float32), 1)], 1)
            dr_pref = jnp.concatenate(
                [zero, jnp.cumsum(jax.nn.one_hot(drafts, V,
                                                 dtype=jnp.float32), 1)], 1)
            cnt = cnt - ev_pref + dr_pref                       # [B,S,V]
        member = cnt > 0.5
        rp_b = rp[:, None, None]
        pen = jnp.where(logits > 0, logits / rp_b, logits * rp_b)
        logits = jnp.where(member, pen, logits)
    C = min(top_c, V)
    flat = logits.reshape(B * S, V)
    sorted_logits, order = jax.lax.top_k(flat, C)          # [B*S,C]
    sorted_logits = sorted_logits.reshape(B, S, C)
    order = order.reshape(B, S, C)
    wprobs = _warp(sorted_logits, temperature, top_k, top_p)  # [B,S,C]

    # Per-row keys -> carried key + one dedicated correction key + one
    # acceptance-uniform key per draft position. The correction key MUST
    # be distinct from the rejecting position's uniform key: reusing it
    # correlates the rejection event with the resample and skews the
    # residual distribution.
    split = jax.vmap(lambda k: jax.random.split(k, K + 2))(keys)  # [B,K+2,2]
    new_keys, corr_key, subs = split[:, 0], split[:, 1], split[:, 2:]

    greedy_row = (temperature <= 0.0)[:, None]                    # [B,1]
    argmax_tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)    # [B,S]

    # Acceptance per draft position j (draft_j is scored by logits[:, j]).
    dmatch = order[:, :K] == drafts[:, :, None]                   # [B,K,C]
    p_draft = jnp.sum(jnp.where(dmatch, wprobs[:, :K], 0.0), -1)  # [B,K]
    u = jax.vmap(jax.vmap(jax.random.uniform))(subs)              # [B,K]
    ok = jnp.where(greedy_row, drafts == argmax_tok[:, :K], u < p_draft)
    ok &= jnp.arange(K)[None, :] < max_accept[:, None]
    accepted = jnp.sum(jnp.cumprod(ok.astype(jnp.int32), axis=1), axis=1)

    # Correction at stream position `accepted`. The residual (draft token
    # removed, renormalised) applies ONLY when the stop was a
    # *probabilistic* rejection (accepted < max_accept: the accept test
    # actually ran and failed there). A stop forced by the budget cap —
    # including the zero-filled drafts of undrafted rows (max_accept=0) —
    # or the all-accepted bonus position was never tested, so its token
    # samples from the unmodified warped distribution: removing an
    # untested token would skew the stream (and can zero out a top_k=1
    # row's whole distribution).
    j = accepted[:, None, None]                                   # [B,1,1]
    probs_j = jnp.take_along_axis(wprobs, j, axis=1)[:, 0]        # [B,C]
    order_j = jnp.take_along_axis(order, j, axis=1)[:, 0]         # [B,C]
    prob_rejected = accepted < jnp.minimum(max_accept, K)
    # Rejected-draft token of this position (only defined when accepted<K).
    dr = jnp.take_along_axis(drafts, jnp.minimum(accepted, K - 1)[:, None],
                             axis=1)[:, 0] if K > 0 else jnp.zeros(
                                 (B,), jnp.int32)
    drop = (order_j == dr[:, None]) & prob_rejected[:, None]
    resid = jnp.where(drop, 0.0, probs_j)
    resid = resid / jnp.maximum(resid.sum(-1, keepdims=True), 1e-20)
    choice = jax.vmap(jax.random.categorical)(
        corr_key, jnp.where(resid > 0, jnp.log(resid), NEG_INF))
    sampled = jnp.take_along_axis(order_j, choice[:, None], -1)[:, 0]
    g_corr = jnp.take_along_axis(argmax_tok, accepted[:, None], -1)[:, 0]
    correction = jnp.where(greedy_row[:, 0], g_corr, sampled).astype(jnp.int32)
    return accepted.astype(jnp.int32), correction, new_keys


def spec_verify_tree(logits: jax.Array, drafts: jax.Array,
                     sib_tok: jax.Array, sib_node: jax.Array,
                     keys: jax.Array, temperature: jax.Array,
                     top_k: jax.Array, top_p: jax.Array,
                     max_accept: jax.Array,
                     top_c: int = 64, ring: Optional[jax.Array] = None,
                     rp: Optional[jax.Array] = None,
                     ctx_len: Optional[jax.Array] = None
                     ) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Tree-speculation acceptance: linear main chain + top-2 sibling
    LEAVES, one verify dispatch (serve/scheduler.py tree spec tick).

    logits: [B,N,V] f32 from models.llama.verify_tree — node 0 is the
    root (current token), nodes 1..K the main greedy draft chain, nodes
    K+1..N-1 sibling leaves; node j's row is the model's distribution
    AFTER consuming node j's token along its ancestor path. drafts:
    [B,K] main-chain tokens (inputs at nodes 1..K). sib_tok/sib_node:
    [B,K] — the drafter's second-choice token for main position j and
    the tree node index it occupies (-1 = no sibling budgeted there).

    Main-chain acceptance is EXACTLY :func:`spec_verify_batched` over
    logits[:, :K+1]. At the first probabilistic rejection a0, the
    sibling at that position (if any) gets one more exact multi-round
    test: greedy rows accept it iff it IS the argmax (in which case the
    correction comes from the sibling node's own distribution — bit-
    identical to what the next sequential tick would emit); sampled
    rows accept it with the exact residual probability
    p(sib)/(1 - p(draft)) (point-mass proposals, sib != draft by top-2
    distinctness), and on acceptance the correction samples from the
    sibling node's own warped distribution unmodified. If the sibling
    also rejects, the correction resamples from position a0 with BOTH
    the draft and the sibling removed and renormalised — still the
    exact residual. Repeat-penalty counts follow the accepted path
    (context + drafts[:a0] [+ sib]), reusing the linear eviction/draft
    prefix algebra.

    NOTE: the carried key stream differs from :func:`spec_verify_batched`
    (one extra sibling-uniform split), so sampled streams tree-on vs
    tree-off are differently-random but identically-distributed; greedy
    streams are bit-identical.

    Returns (accepted [B] int32 — total accepted tokens INCLUDING a
    used sibling —, used_sib [B] int32 0/1, correction [B] int32,
    advanced keys [B,2]).
    """
    B, N, V = logits.shape
    K = drafts.shape[1]
    main = logits[:, :K + 1]
    ev_pref = dr_pref = None
    if ring is not None:
        # Identical sliding-window algebra to spec_verify_batched over
        # the main chain; ev_pref/dr_pref are kept for the sibling leg.
        Rw = ring.shape[1]
        in_cnt = jnp.zeros((B, V), jnp.float32).at[
            jnp.arange(B)[:, None], ring].add(1.0, mode="drop")
        cnt = jnp.broadcast_to(in_cnt[:, None], (B, K + 1, V))
        shifts = jnp.arange(1, K + 1)[None, :]
        ev_slots = (ctx_len[:, None] + shifts) % Rw
        ev = jnp.take_along_axis(ring, ev_slots, axis=1)
        zero = jnp.zeros((B, 1, V), jnp.float32)
        ev_pref = jnp.concatenate(
            [zero, jnp.cumsum(jax.nn.one_hot(ev, V, dtype=jnp.float32),
                              1)], 1)
        dr_pref = jnp.concatenate(
            [zero, jnp.cumsum(jax.nn.one_hot(drafts, V,
                                             dtype=jnp.float32), 1)], 1)
        cnt = cnt - ev_pref + dr_pref
        member = cnt > 0.5
        rp_b = rp[:, None, None]
        pen = jnp.where(main > 0, main / rp_b, main * rp_b)
        main = jnp.where(member, pen, main)
    C = min(top_c, V)
    flat = main.reshape(B * (K + 1), V)
    sorted_logits, order = jax.lax.top_k(flat, C)
    sorted_logits = sorted_logits.reshape(B, K + 1, C)
    order = order.reshape(B, K + 1, C)
    wprobs = _warp(sorted_logits, temperature, top_k, top_p)

    # carried key + correction key + sibling-uniform key + K acceptance
    # uniforms (one extra split vs the linear path).
    split = jax.vmap(lambda k: jax.random.split(k, K + 3))(keys)
    new_keys, corr_key, sib_key = split[:, 0], split[:, 1], split[:, 2]
    subs = split[:, 3:]

    greedy_row = (temperature <= 0.0)[:, None]
    argmax_tok = jnp.argmax(main, axis=-1).astype(jnp.int32)      # [B,K+1]

    dmatch = order[:, :K] == drafts[:, :, None]
    p_draft = jnp.sum(jnp.where(dmatch, wprobs[:, :K], 0.0), -1)  # [B,K]
    u = jax.vmap(jax.vmap(jax.random.uniform))(subs)
    ok = jnp.where(greedy_row, drafts == argmax_tok[:, :K], u < p_draft)
    ok &= jnp.arange(K)[None, :] < max_accept[:, None]
    a0 = jnp.sum(jnp.cumprod(ok.astype(jnp.int32), axis=1), axis=1)

    j = a0[:, None, None]
    probs_j = jnp.take_along_axis(wprobs, j, axis=1)[:, 0]        # [B,C]
    order_j = jnp.take_along_axis(order, j, axis=1)[:, 0]         # [B,C]
    prob_rejected = a0 < jnp.minimum(max_accept, K)
    m = jnp.minimum(a0, K - 1)[:, None]
    dr = jnp.take_along_axis(drafts, m, axis=1)[:, 0]
    st = jnp.take_along_axis(sib_tok, m, axis=1)[:, 0]
    sn = jnp.take_along_axis(sib_node, m, axis=1)[:, 0]
    has_sib = prob_rejected & (sn >= 0)

    # Sibling test — exact residual round. Greedy: the sibling is usable
    # iff it IS the penalised argmax at the rejected position (then the
    # emitted token equals what linear's correction would have been, and
    # we gain its follow-up from the sibling node's own logits).
    g_tok = jnp.take_along_axis(argmax_tok, a0[:, None], -1)[:, 0]
    p_rej = jnp.take_along_axis(p_draft, m, axis=1)[:, 0]
    p_sib = jnp.sum(jnp.where(order_j == st[:, None], probs_j, 0.0), -1)
    ratio = jnp.minimum(p_sib / jnp.maximum(1.0 - p_rej, 1e-20), 1.0)
    u_sib = jax.vmap(jax.random.uniform)(sib_key)
    sib_ok = jnp.where(greedy_row[:, 0], st == g_tok, u_sib < ratio)
    used_sib = has_sib & sib_ok

    # Sibling node's own distribution (the correction after accepting
    # the sibling): penalty counts for the path context + drafts[:a0] +
    # [sib] reuse the main chain's eviction/draft prefixes (a0+1 <= K
    # whenever has_sib, so the gathers stay in range).
    sib_logits = jnp.take_along_axis(
        logits, jnp.clip(sn, 0, N - 1)[:, None, None], axis=1)[:, 0]
    if ring is not None:
        a1 = jnp.minimum(a0 + 1, K)[:, None, None]
        ev_s = jnp.take_along_axis(ev_pref, a1, axis=1)[:, 0]     # [B,V]
        dr_s = jnp.take_along_axis(dr_pref, a0[:, None, None],
                                   axis=1)[:, 0]
        cnt_s = (in_cnt - ev_s + dr_s
                 + jax.nn.one_hot(st, V, dtype=jnp.float32))
        rp_c = rp[:, None]
        pen_s = jnp.where(sib_logits > 0, sib_logits / rp_c,
                          sib_logits * rp_c)
        sib_logits = jnp.where(cnt_s > 0.5, pen_s, sib_logits)
    sorted_sib, order_sib = jax.lax.top_k(sib_logits, C)
    wprobs_sib = _warp(sorted_sib, temperature, top_k, top_p)

    # Correction. Not-used-sib: linear residual at a0 with the draft
    # removed (when probabilistically rejected) and the sibling ALSO
    # removed when it was tested and failed. Used-sib: the sibling
    # node's warped distribution, unmodified (nothing was tested there).
    drop = ((order_j == dr[:, None]) & prob_rejected[:, None]
            | (order_j == st[:, None]) & (has_sib & ~sib_ok)[:, None])
    resid = jnp.where(drop, 0.0, probs_j)
    probs_f = jnp.where(used_sib[:, None], wprobs_sib, resid)
    order_f = jnp.where(used_sib[:, None], order_sib, order_j)
    probs_f = probs_f / jnp.maximum(probs_f.sum(-1, keepdims=True), 1e-20)
    choice = jax.vmap(jax.random.categorical)(
        corr_key, jnp.where(probs_f > 0, jnp.log(probs_f), NEG_INF))
    sampled = jnp.take_along_axis(order_f, choice[:, None], -1)[:, 0]
    g_corr = jnp.where(used_sib,
                       jnp.argmax(sib_logits, axis=-1).astype(jnp.int32),
                       g_tok)
    correction = jnp.where(greedy_row[:, 0], g_corr,
                           sampled).astype(jnp.int32)
    accepted = a0 + used_sib.astype(jnp.int32)
    return (accepted.astype(jnp.int32), used_sib.astype(jnp.int32),
            correction, new_keys)


def sample_np(logits: np.ndarray, rng: np.random.Generator,
              temperature: float = 0.0, top_k: int = 0,
              top_p: float = 1.0, recent=None,
              repeat_penalty: float = 1.0) -> int:
    """Numpy twin of :func:`sample` for one row of logits [vocab].

    Same filtering semantics: temperature<=0 is greedy; top-k keeps the k
    highest logits (ties at the k-th value survive, like lax.top_k's
    threshold compare); top-p keeps the smallest probability-sorted prefix
    whose cumulative mass reaches top_p (always at least one token).
    ``recent``/``repeat_penalty`` mirror :func:`apply_repeat_penalty`.
    """
    # float64 throughout: Generator.choice checks sum(p)==1 to float64
    # tolerance, which float32 softmax fails at real vocab sizes (~128k).
    logits = np.asarray(logits, np.float64)
    if recent is not None and repeat_penalty != 1.0:
        for t in set(int(x) for x in recent):
            if 0 <= t < logits.shape[-1]:
                logits[t] = (logits[t] / repeat_penalty if logits[t] > 0
                             else logits[t] * repeat_penalty)
    if temperature <= 0.0:
        return int(np.argmax(logits))
    logits = logits / max(temperature, 1e-6)
    if top_k > 0:
        k = min(top_k, logits.shape[-1])
        kth = np.sort(logits)[-k]
        logits = np.where(logits < kth, NEG_INF, logits)
    if top_p < 1.0:
        order = np.argsort(logits)[::-1]
        sorted_logits = logits[order]
        probs = _softmax_np(sorted_logits)
        cum = np.cumsum(probs)
        keep = (cum - probs) < top_p
        # top_p <= 0 keeps nothing under the strict compare; degrade to
        # top-1 like the JAX twin (threshold=inf keeps only the max).
        threshold = (sorted_logits[keep].min() if keep.any()
                     else sorted_logits[0])
        logits = np.where(logits < threshold, NEG_INF, logits)
    probs = _softmax_np(logits)
    return int(rng.choice(logits.shape[-1], p=probs))


def _softmax_np(x: np.ndarray) -> np.ndarray:
    e = np.exp(x - x.max())
    p = e / e.sum()
    # Renormalise exactly — np.random choice requires sum(p) == 1.
    return p / p.sum()
