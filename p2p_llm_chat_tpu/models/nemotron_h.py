"""Hybrid decoders, walked by a pattern of layer kinds: the Nemotron-H
block of NVIDIA-Nemotron-3-Super-120B-A12B (Mamba-2 / attention /
LatentMoE), the SambaY stack of Phi-4-mini-flash-reasoning (Mamba-1 /
window attention / one full attention layer whose pages the upper half
reads / gated memory units, each followed by a dense gated MLP), the
Mellum 2 stack (three window layers to one full layer, GQA rotated by
two tables, each followed by a routed layer of thin experts) and the
LFM2 stack (gated short convolutions, three to one GQA layer at a head
of 64, a dense MLP behind the first two and a biased-sigmoid routed
layer behind the others) and the Keye-VL-2.0 language stack (GQA whose
queries read only the keys a learned indexer picks, a routed layer of
thin experts behind each).
``models.family_for`` picks this
module for a configuration with a ``hybrid_pattern``; the functional
surface is the other families' (init_params, prefill, prefill_chunk,
decode_step_paged, decode_fused, their ``_counted`` / ``_touched``
forms), so the scheduler serves it through the same programs.

**A layer is ONE mixer**: ``x <- x + mixer(RMSNorm(x))``, the mixer named
by the layer's letter in ``hybrid_pattern``:

- ``M``, Mamba-2 (``d = mamba_num_heads x mamba_head_dim``, ``G =
  ssm_groups``, ``N = ssm_state_size``): ``[z | xBC | dt] = u W_in``
  (widths d | d + 2GN | heads); ``xBC <- silu(conv(xBC) + b)``, a causal
  depthwise convolution over the last ``conv_kernel`` positions; split
  into ``x`` [heads, head_dim], ``B``, ``C`` [G, N]; ``dt <-
  softplus(dt + dt_bias)`` and ``A = -exp(A_log)`` in float32; the
  recurrence of ops/state_pool.py over a float32 state; ``y <- y + D x``;
  the gated norm, gate first: ``RMSNorm_grouped(y silu(z); G groups) w``;
  ``out = y W_out``.
- ``*``, attention: GQA, no biases, causal. ``attn_rope`` False: no
  rotary embedding (position comes from the recurrent layers). True
  (Mellum): q and k rotated over the whole head (rotate-half) by
  ``rope_scaling``'s table, YaRN with its factor on cos and sin; the
  pages hold rotated keys.
- ``w`` without ``attn_diff`` (Mellum): the same GQA over the query's
  own position and the ``sliding_window - 1`` before it, q and k
  rotated by the PLAIN table (layers.rope_table: a model has two); K
  and V live in a ring a row, rotated as they were written, ``[G, W,
  D]`` a row with the KV heads apart.
- ``E`` without ``moe_latent_size`` (Mellum): ``p = softmax(x W_r)`` in
  float32 over all experts, the ``num_experts_per_tok`` largest kept
  and divided by their sum (``moe_renormalize``); ``out = sum_e p_e
  (silu(x Wg_e) * (x Wu_e)) Wd_e``. No shared expert.
- ``E`` with ``moe_selection_bias`` and no latent (LFM2): ``s =
  sigmoid(x W_r)`` in float32 over all experts; the
  ``num_experts_per_tok`` largest of ``s + router_bias`` chosen, weighed
  by their unbiased ``s`` over ``(their sum + moe_renorm_eps)`` times
  ``routed_scaling_factor``; SwiGLU experts on the hidden state, all
  held, no shared expert.
- ``c``, a gated short convolution (LFM2): ``[B | C | x] = u W_in`` (H
  -> 3H, that order); ``z = B * x``; ``y_t = sum_j w_j z_{t-(K-1)+j}``
  over the position and the ``conv_kernel - 1`` before it (``w_{K-1}``
  on the current one, zeros before position 0, no bias, no activation);
  ``out = (C * y) W_out``. Its only past is ``z`` of a row's last
  ``conv_kernel - 1`` positions.
- ``*`` with ``qk_norm_head`` (LFM2): an RMSNorm over each head's
  numbers, one weight vector for q and one for k a layer, before the
  rotation. At a head of 64 the carry and the pool keep the KV heads in
  PAIRS (``ModelConfig.kv_paired``), and decode reads a pair's row with
  its queries zero-extended onto their own half
  (ops/paged_attention.paged_attention_append_paired).
- ``s``, attention with an indexer (Keye-VL-2.0; DeepSeek-V3.2's
  "lightning indexer" over GQA; ``u`` the layer's normed input): q, k, v
  as ``*`` with ``qk_norm_head`` and the plain rotation. Beside them
  ``[qI | kI | w] = u W_idx`` (``index_heads`` x ``index_head_dim`` |
  ``index_head_dim`` | ``index_heads``); ``kI <- LayerNorm(kI)`` (weight
  and bias); qI and kI rotated by a plain table of ``rope_theta`` over
  their ``index_head_dim`` numbers. For ``s <= t``: ``I[t, s] = sum_h
  w[t, h] relu(qI[t, h] . kI[s])`` in float32; ``S_t`` = the ``min(t +
  1, index_topk)`` positions of largest ``I[t, .]``, ties to the lower
  position, ONE selection a token for all query heads; ``o[t, h] =
  softmax over S_t of (q[t, h] . k[s, g(h)] / sqrt(D)) v[s, g(h)]``. Its
  per-token past is K, V AND ``kI`` (``KVCache.idx``,
  ``PagedKVCache.idx``: the index key lies in the layer's pages). A
  chunk of at most ``index_topk`` positions from 0, and a decode window
  that holds no more, select everything and run the ``*`` kind's code.
  Prefill scores a chunk's queries against all keys before them (the
  carry's and the chunk's own), finds each query's ``index_topk``-th
  score by bisection (ops/paged_attention.select_mask) and runs the
  dense attention under that mask; decode scores the row's window of
  index keys, selects the same way and hands the mask to the decode
  attention that is there
  (ops/paged_attention.paged_attention_select_append).
- ``E``, LatentMoE: ``s = sigmoid(x W_r)`` in float32 over ALL
  ``router_width`` experts; the top-k of ``s + router_bias`` chosen,
  weighed ``routed_scaling_factor x s / (sum of the chosen s + 1e-20)``;
  ``l = x W_fc1`` (hidden -> latent); expert e is ``relu(l U_e)^2 D_e``
  in the latent; ``routed = (sum over chosen AND held e) W_fc2``;
  ``shared = relu(x U_s)^2 D_s``; ``out = routed + shared``.

All three kinds of ``E`` go through models/pangu._routed_local, the one
dispatch of this family and the latent-attention one: a prefill's pairs
sorted by expert into tiles (models/moe_tiles.routed_tiles; for a held
range of a wider router the pairs routed elsewhere take no row), a
decode step's into buckets that hold every row.

The SambaY kinds (``ModelConfig.norm_kind`` "layer": every norm below
is a biased LayerNorm; ``attn_diff``: every attention is the
differential form of ops/diff_attention.py, biased projections, no
positional encoding):

- ``1``, Mamba-1 (``d = mamba1_inner``, ``N = mamba1_state``): ``[x | z]
  = u W_in``; ``x <- silu(conv(x) + b)``; ``[dt_r | B | C] = x W_x``;
  ``dt = softplus(dt_r W_dt + b_dt)``, ``A = -exp(A_log)`` float32; the
  recurrence of ops/state_pool.ssm1_step over a float32 state [N, d];
  ``m = y + D x``; ``out = (m silu(z)) W_out``. ``Y`` is the same layer
  that also PUBLISHES ``m`` (before the ``z`` gate): a value of the step
  the ``g`` layers above read, not a cache.
- ``w``, attention over the query's own position and the
  ``sliding_window - 1`` before it: K and V live in a ring a row in the
  state pool (ops/state_pool.py), never in pages.
- ``*`` with ``attn_diff``: causal over the whole context, from pages;
  the only layer that owns any.
- ``x``, cross attention: a query projection only; K and V are those of
  the ``*`` layer below it, read from its pages where they lie.
- ``g``, gated memory unit: ``out = (silu(u W_in) * m) W_out`` with ``m``
  the publishing layer's output at the SAME position. No state.
- ``-``, a dense gated MLP: ``[g | u] = x W_gu``; ``out = (silu(g) u)
  W_mlp_down``, at ``dense_intermediate_size`` where a pattern has
  routed layers of another width beside it (LFM2), else at
  ``intermediate_size``. A published layer of this family is its mixer
  and ``-``.

The Granite 4.0-H stack is ``M``, ``*`` and ``-`` alone, a ``-`` behind
every mixer, with four scalars (``ModelConfig``; each default leaves a
program the text it was): ``h0 = embedding_multiplier x E[token]``;
every residual add is ``h + residual_multiplier x out``, a mixer's and
an MLP's alike (:func:`_run_stack`); the softmax scale is
``attention_multiplier`` where the kernels take ``1 / sqrt(head_dim)``,
folded into the queries (:func:`_qkv`: the prefill attention, the paired
flash-append kernel and the gather path read the same q); the logits
are divided by ``logits_scaling`` (:func:`_logits`), so everything that
samples or counts sees scaled logits.

**Kinds of per-row past.** The ``*`` layers' K and V are pages
(ops/paged_kv.py; ``ModelConfig.cache_layers`` of them, each read by
its own layer, and by the ``x`` layers above it where there are any;
an ``s`` layer's pages hold its index key too);
the ``w`` layers' are rings in the state pool; a ``c`` layer's
convolution window is a row of the pool's ``conv`` with no ``ssm`` row
behind it; the ``M``
layers' state and convolution window are rows of a
:class:`~..ops.state_pool.StatePool` that rides in the cache objects'
``state`` leaf: per entry in a prefill's ``KVCache`` (zero for a fresh
prompt, the carry of a chunk ladder, a prefix entry's snapshot), per
slot in the scheduler's ``PagedKVCache``. Prefill programs mask with
``valid`` ([B,S] bool, a row's real positions, a prefix of the row):
padding has ``dt`` = 0 and stays out of the window, so a padded row's
state is its unpadded run's.

**The stack** is a stacked parameter tree a kind (``mamba``, ``moe``,
``attn``; ``mamba1``, ``cross``, ``gmu``, ``mlp``) walked by the
pattern: runs of equal groups of two or four letters between ``*``
layers are one ``lax.scan`` each (:func:`_plan`), so a program holds a
few layer bodies and not one a layer. ``*`` and ``Y`` publish to the
layers above them and stay outside those scans; where nothing reads
what they publish and the pattern is a whole number of periods (Mellum),
the periods are one scan around that walk (:func:`_rounds`); else the
pattern is cut before every ``*``, and equal neighbours, or neighbours
that differ only in how often a group of two letters repeats, are one
scan (LFM2: behind a dense head six rounds of ``*``, three or two
``Ec`` and an ``E``; :func:`_segments`).

Single chip only; speculation and session parking would need the state
(a ring, a convolution window) rolled back or carried and are refused at
boot
(serve/scheduler.py).
"""

from __future__ import annotations

import functools
import itertools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from ..ops import diff_attention, state_pool
from ..ops.diff_attention import FlatKeys, Keys
from ..ops.state_pool import StatePool
from ..parallel.sharding import LogicalRules, DEFAULT_RULES
from .configs import ModelConfig
from .layers import (DEFAULT_COMPUTE_DTYPE, FLASH_KV_CHUNK, NEG_INF,
                     apply_rope, attend_gqa_auto, causal_mask, rms_norm,
                     rope_table)
from .llama import KVCache, _layer_view
from .pangu import (STATS_WIDTH, _normal, _routed_local, no_stats,
                    prefill_stats, streamed_stack)
from .quant import mm

no_touched = no_stats
__all__ = ["STATS_WIDTH", "no_stats", "no_touched", "prefill_stats"]


# -- the pattern --------------------------------------------------------------

# letter -> its parameter tree; letters of one tree are indexed together.
TREES = {"M": "mamba", "E": "moe", "*": "attn", "w": "attn",
         "1": "mamba1", "Y": "mamba1", "x": "cross", "g": "gmu", "-": "mlp",
         "c": "conv", "s": "attn"}


@functools.cache
def _plan(pattern: str) -> tuple:
    """The walk of ``pattern``: a tuple of (letters, repeat, how many of
    each letter came before this step). Between ``*`` layers a run of
    equal groups of two letters, else of four, is one step with
    ``repeat`` > 1 (one scan); everything else is a step of one layer.
    ``*`` and ``Y`` hand values to the layers above them through the
    trace and stay outside every scan."""
    at = dict.fromkeys(TREES, 0)
    steps = []

    def emit(letters: str, n: int) -> None:
        steps.append((letters, n, dict(at)))
        for ch in letters:
            at[ch] += n

    i = 0
    while i < len(pattern):
        for size in (2, 4):
            group = pattern[i: i + size]
            n = 0
            if len(group) == size and not set(group) & {"*", "Y"} \
                    and len(set(group)) > 1:
                while pattern[i + size * n: i + size * (n + 1)] == group:
                    n += 1
            if n >= 2:
                break
        if n >= 2:
            emit(group, n)
            i += size * n
        else:
            emit(pattern[i], 1)
            i += 1
    return tuple(steps)


@functools.cache
def _rounds(pattern: str) -> tuple:
    """(period, rounds): ``pattern`` as ``rounds`` copies of its shortest
    period, where a whole period can be one scan's body: it holds a ``*``
    (a pattern without one is scanned by its groups already) and nothing
    in it hands a value up the trace (no ``Y``, ``x`` or ``g``). Then a
    program holds one period's layer bodies and not every period's: for
    Mellum's four a third of the HLO and half the time to compile, in
    each of the 52 chunk programs a boot compiles (PERF.md section 6,
    PR 40). (pattern, 1) otherwise."""
    if "*" in pattern and not set(pattern) & set("Yxg"):
        for size in range(2, len(pattern) // 2 + 1):
            rounds, rest = divmod(len(pattern), size)
            if not rest and pattern == pattern[:size] * rounds:
                return pattern[:size], rounds
    return pattern, 1


def _varied(a: str, b: str) -> Optional[tuple]:
    """(head, group, tail) where two stretches that open with ``*`` are
    both ``head + group * m + tail``, with a group of two letters and
    different ``m`` >= 1; None where they are not."""
    s, t = sorted((a, b), key=len)
    d = len(t) - len(s)
    if a[:1] + b[:1] != "**" or not d or d % 2:
        return None
    for h in range(1, len(s) - 1):
        g = s[h: h + 2]
        if t[:h + d] == s[:h] + g * (d // 2) and t[h + d:] == s[h:]:
            m = 1
            while s[h + 2 * m: h + 2 * m + 2] == g:
                m += 1
            return s[:h], g, s[h + 2 * m:]
    return None


def _copies(piece: str, parts: tuple) -> int:
    """``m`` where ``piece`` is head + group * m + tail, else 0."""
    head, group, tail = parts
    m, rest = divmod(len(piece) - len(head) - len(tail), 2)
    return m if not rest and m > 0 \
        and piece == head + group * m + tail else 0


@functools.cache
def _segments(pattern: str) -> tuple:
    """The pattern as stretches ``(letters, rounds)`` in order: ``rounds``
    > 1 is one scan over that many copies of ``letters``, 1 is walked as
    it stands. Whole periods (:func:`_rounds`) are one stretch. Else,
    under :func:`_rounds`'s conditions, the pattern is cut before every
    ``*`` and equal neighbours are one scan; neighbours that differ only
    in how often a group of two letters repeats (:func:`_varied`) are one
    scan too, ``((head, group, tail), (m of each round, ...))``, the group
    a loop of as many turns as the round has copies. LFM2 behind its two
    dense layers: ``(("*", "Ec", "E"), (3, 3, 3, 3, 2, 2))``, so a program
    holds one attention body and two routed ones, not six and twenty-two
    (compilation in a cold boot: 498 s with the tail unrolled, 389-411 s
    with the tail a scan of its own, 358 s so, PERF.md section 6, PR 45).
    Stretches with no such
    neighbour are walked together, as they stand (Nemotron's cut: no two
    of its ``*`` stretches are alike, and its programs are the text they
    were)."""
    period, rounds = _rounds(pattern)
    if rounds > 1 or "*" not in pattern or set(pattern) & set("Yxg"):
        return ((period, rounds),)
    cut = [0] + [i for i, ch in enumerate(pattern) if ch == "*" and i]
    runs: list = []
    for piece, same in itertools.groupby(
            pattern[i:j] for i, j in zip(cut, cut[1:] + [None])):
        n = len(list(same))
        last, many = runs[-1] if runs else ("", 0)
        if isinstance(last, tuple) and _copies(piece, last):
            runs[-1] = (last, many + (_copies(piece, last),) * n)
        elif isinstance(last, str) and _varied(last, piece):
            parts = _varied(last, piece)
            runs[-1] = (parts, (_copies(last, parts),) * many
                        + (_copies(piece, parts),) * n)
        else:
            runs.append((piece, n))
    out: list = []
    for letters, n in runs:
        if n == 1 and out and out[-1][1] == 1:
            out[-1] = (out[-1][0] + letters, 1)
        else:
            out.append((letters, n))
    return tuple(out)


# letter -> the layers it shares a per-row past with: a Mamba layer's row
# in the state pool, a window layer's ring, a ``*`` layer's page layer.
PASTS = {**{ch: ch for ch in TREES}, "Y": "1", "s": "*"}


def _index(at: dict, letters: str, j: int, group: dict):
    """Of the layer at place ``j`` of ``letters``, in round 0 of a step
    that starts at counts ``at``: its index among the layers ``group``
    puts it with (TREES: its parameter tree; PASTS: its per-row past),
    and how many of them a round holds."""
    mine = group[letters[j]]
    same = [group[ch] == mine for ch in letters]
    return (sum(n for ch, n in at.items() if group[ch] == mine)
            + sum(same[:j]), sum(same))


def published_layer(pattern: str, step: int) -> int:
    """The published layer a step of the walk belongs to: ``-`` steps
    are the second half of the layer their mixer opened."""
    return sum(ch != "-" for ch in pattern[:step + 1]) - 1 \
        if "-" in pattern else step


# -- parameters ---------------------------------------------------------------

def _dims(config: ModelConfig) -> dict:
    """Per-layer matmul leaves of each tree (without the layer axis)."""
    H = config.hidden_size
    d, nh = config.mamba_inner, config.mamba_num_heads
    Lw = config.moe_latent_size
    F, Fs = config.intermediate_size, (config.shared_intermediate_size
                                       or config.intermediate_size)
    Fd = config.dense_intermediate_size or F
    NE = config.num_experts
    d1, N1, R1 = (config.mamba1_inner, config.mamba1_state,
                  config.mamba1_dt_rank)
    return {
        "mamba": {"w_in": (H, d + config.conv_dim + nh), "w_out": (d, H)},
        "attn": {"wqkv": (H, config.q_dim + 2 * config.kv_dim),
                 "wo": (config.q_dim, H),
                 # [qI | kI | w] of an indexed layer.
                 **({"w_idx": (H, (config.index_heads + 1)
                               * config.index_head_dim
                               + config.index_heads)}
                    if config.is_indexed else {})},
        "moe": {"w_fc1": (H, Lw), "w_fc2": (Lw, H),
                "w_up_s": (H, Fs), "w_down_s": (Fs, H),
                "w_up_e": (NE, Lw, F), "w_down": (NE, F, Lw)} if Lw else
               {"wgu_e": (NE, H, 2 * F), "w_down": (NE, F, H)},
        "mamba1": {"w_in": (H, 2 * d1), "w_x": (d1, R1 + 2 * N1),
                   "w_dt": (R1, d1), "w_out": (d1, H)},
        "cross": {"wq": (H, config.q_dim), "wo": (config.q_dim, H)},
        "gmu": {"w_in": (H, d1), "w_out": (d1, H)},
        "mlp": {"w_gu": (H, 2 * Fd), "w_mlp_down": (Fd, H)},
        "conv": {"w_in": (H, 3 * H), "w_out": (H, H)},
    }


def _init_scale(name: str, shape: tuple, config: ModelConfig) -> float:
    """Standard deviation a random matmul leaf is drawn with: the scaled
    normal's ``fan_in ** -0.5``; the experts' down-projections a
    ``routed_scaling_factor``-th of it, so that the routed sum (whose
    weights add up to that factor, 5) comes out as large as one expert's
    output and not five times it, as training would have left it."""
    std = shape[-2] ** -0.5
    if name == "w_down":
        std /= config.routed_scaling_factor
    return std


def _counts(config: ModelConfig) -> dict:
    """Layers of each parameter tree the pattern uses."""
    n: dict = {}
    for ch in config.hybrid_pattern:
        n[TREES[ch]] = n.get(TREES[ch], 0) + 1
    return n


def _uniform(k, shape, lo, hi, dtype=jnp.float32):
    return jax.random.uniform(k, shape, jnp.float32, lo, hi).astype(dtype)


def _small_leaves(config: ModelConfig, key: jax.Array, dtype) -> dict:
    """Everything that is not a matmul weight. Norms are drawn from
    [0.5, 1.5) and ``D``, the convolution's bias and the router's
    selection bias away from their neutral values, so that a model
    without one of them cannot pass a comparison (pangu._norm_leaves).
    ``dt_bias`` and ``A_log`` span what the published initialiser does:
    time steps of 0.001 to 0.1 and decays ``A`` of 1 to 16. Likewise the
    SambaY kinds' LayerNorm biases, attention biases, sub-norms and
    lambda vectors (wide enough that ``lam - lam0`` is a tenth or more
    in most layers)."""
    n = _counts(config)
    H, nh = config.hidden_size, config.mamba_num_heads
    # The first three trees draw from the stream they always did (their
    # weights, and the reference limits read on them, stay what they
    # were); the later kinds from one of their own.
    ks = iter(jax.random.split(key, 16))
    layer_norm = config.norm_kind == "layer"

    def norm(L: int, name: str = "norm") -> dict:
        out = {name: _uniform(next(ks), (L, H), 0.5, 1.5, dtype)}
        if layer_norm:
            out[name + "_b"] = _normal(next(ks), (L, H), 0.2, dtype)
        return out

    def dt_bias(shape):
        dt = jnp.exp(_uniform(next(ks), shape, jnp.log(1e-3),
                              jnp.log(1e-1)))
        return dt + jnp.log(-jnp.expm1(-dt))    # softplus(dt_bias) = dt

    def diff_leaves(L: int, steps: list) -> dict:
        """Biases, lambda vectors and sub-norm of ``L`` attention layers
        at the walk's ``steps``."""
        D = config.head_dim
        wide = (0.3 / D ** 0.5) ** 0.5
        out = {"bo": _normal(next(ks), (L, H), 0.2, dtype),
               "sub_w": _uniform(next(ks), (L, 2 * D), 0.5, 1.5, dtype),
               "lam0": jnp.asarray(
                   [diff_attention.lam0_of(published_layer(
                       config.hybrid_pattern, j)) for j in steps],
                   jnp.float32)}
        for name in ("lq1", "lk1", "lq2", "lk2"):
            out[name] = _normal(next(ks), (L, D), wide, jnp.float32)
        return out

    small: dict = {}
    if "mamba" in n:
        Lm = n["mamba"]
        dtb = dt_bias((Lm, nh))
        small["mamba"] = {
            **norm(Lm),
            "conv_w": _normal(next(ks), (Lm, config.conv_kernel,
                                         config.conv_dim), 0.5, dtype),
            "conv_b": _normal(next(ks), (Lm, config.conv_dim), 0.5, dtype),
            "dt_bias": dtb,
            "A_log": jnp.log(_uniform(next(ks), (Lm, nh), 1.0, 16.0)),
            "D": _uniform(next(ks), (Lm, nh), 0.5, 1.5),
            "gnorm": _uniform(next(ks), (Lm, config.mamba_inner), 0.5, 1.5,
                              dtype),
        }
    if "attn" in n:
        La = n["attn"]
        small["attn"] = norm(La)
    if "moe" in n:
        Le = n["moe"]
        small["moe"] = {
            **norm(Le),
            # float32, as the published router is.
            "router": _normal(next(ks), (Le, H, config.router_width),
                              H ** -0.5, jnp.float32),
        }
        if config.moe_selection_bias:
            small["moe"]["router_bias"] = _uniform(
                next(ks), (Le, config.router_width), -0.2, 0.2)
    ks = iter(jax.random.split(jax.random.fold_in(key, 1), 64))
    if "attn" in n and config.attn_diff:
        steps = [j for j, ch in enumerate(config.hybrid_pattern)
                 if TREES[ch] == "attn"]
        small["attn"].update(
            diff_leaves(n["attn"], steps),
            bqkv=_normal(next(ks), (n["attn"], config.q_dim
                                    + 2 * config.kv_dim), 0.2, dtype))
    if "mamba1" in n:
        L1, d1, N1 = n["mamba1"], config.mamba1_inner, config.mamba1_state
        small["mamba1"] = {
            **norm(L1),
            "conv_w": _normal(next(ks), (L1, config.conv_kernel, d1), 0.5,
                              dtype),
            "conv_b": _normal(next(ks), (L1, d1), 0.5, dtype),
            "dt_bias": dt_bias((L1, d1)),
            # [N, d]: the state's layout (ops/state_pool.py).
            "A_log": jnp.log(_uniform(next(ks), (L1, N1, d1), 1.0, 16.0)),
            "D": _uniform(next(ks), (L1, d1), 0.5, 1.5),
        }
    if "cross" in n:
        Lx = n["cross"]
        steps = [j for j, ch in enumerate(config.hybrid_pattern)
                 if ch == "x"]
        small["cross"] = {**norm(Lx), **diff_leaves(Lx, steps),
                          "bq": _normal(next(ks), (Lx, config.q_dim), 0.2,
                                        dtype)}
    for tree in ("gmu", "mlp"):
        if tree in n:
            small[tree] = norm(n[tree])
    if "conv" in n:
        small["conv"] = {
            **norm(n["conv"]),
            "conv_w": _normal(next(ks), (n["conv"], config.conv_kernel, H),
                              0.5, dtype)}
    if "attn" in n and config.qk_norm_head:
        for name in ("q_norm", "k_norm"):
            small["attn"][name] = _uniform(
                next(ks), (n["attn"], config.head_dim), 0.5, 1.5, dtype)
    if "attn" in n and config.is_indexed:
        Di = config.index_head_dim
        small["attn"].update(
            ki_norm=_uniform(next(ks), (n["attn"], Di), 0.5, 1.5, dtype),
            ki_norm_b=_normal(next(ks), (n["attn"], Di), 0.2, dtype))
    return small


def _build(config: ModelConfig, key: jax.Array, dtype, stack, head,
           tied) -> dict:
    """The parameter tree both initialisers return (pangu._build)."""
    n = _counts(config)
    latent_moe = (config.moe_selection_bias
                  and config.mlp_activation == "relu2"
                  and config.moe_latent_size)
    plain_moe = not (config.moe_selection_bias or config.moe_latent_size
                     or config.num_shared_experts
                     or config.mlp_activation != "silu"
                     or config.router_width != config.num_experts)
    biased_moe = (config.moe_selection_bias
                  and config.moe_scoring == "sigmoid"
                  and config.mlp_activation == "silu"
                  and not (config.moe_latent_size
                           or config.num_shared_experts)
                  and config.router_width == config.num_experts)
    if "moe" in n and not (latent_moe or plain_moe or biased_moe):
        raise ValueError(f"{config.name}: the hybrid family's routed layer "
                         "is a LatentMoE (selection bias, relu2 experts in "
                         "a latent) or a plain one (SwiGLU experts on the "
                         "hidden state, all held, no shared expert), its "
                         "scores a softmax, or sigmoids with a selection "
                         "bias")
    if "conv" in n and {"mamba", "mamba1"} & set(n):
        raise ValueError(f"{config.name}: short convolutions and Mamba "
                         "layers would share the state pool's conv rows "
                         "at two widths")
    if config.attn_diff and config.attn_rope:
        raise ValueError(f"{config.name}: differential attention is "
                         "served without rotary embedding")
    if config.attn_diff and config.attention_multiplier:
        raise ValueError(f"{config.name}: attention_multiplier scales the "
                         "queries of plain GQA; differential attention "
                         "takes 1 / sqrt(head_dim)")
    if config.is_indexed and (
            config.kv_paired or config.attn_diff or not config.attn_rope
            or not (config.index_heads and config.index_head_dim
                    and config.index_topk)):
        raise ValueError(f"{config.name}: an indexed layer is rotated GQA "
                         "over a per-head pool, with index_heads, "
                         "index_head_dim and index_topk set")
    if {"cross", "attn"} <= set(n) and not config.attn_diff:
        raise ValueError(f"{config.name}: cross layers read a "
                         "differential-attention layer's pages")
    H = config.hidden_size
    k_embed, k_head, k_small, k_stack = jax.random.split(key, 4)
    small = _small_leaves(config, k_small, dtype)
    dims = _dims(config)
    params = {"embed": _normal(k_embed, (config.vocab_size, H), 1.0, dtype),
              "final_norm": jnp.ones((H,), dtype)}
    if config.norm_kind == "layer":
        k_fn, k_fb = jax.random.split(jax.random.fold_in(k_small, 1))
        params.update(final_norm=_uniform(k_fn, (H,), 0.5, 1.5, dtype),
                      final_norm_b=_normal(k_fb, (H,), 0.2, dtype))
    # A tied head is the embedding transposed: a copy in the layout (and,
    # in an int8 tree, the precision) the head's matmul reads.
    params["lm_head"] = (tied(params["embed"]) if config.tie_embeddings
                         else head(k_head, (H, config.vocab_size)))
    for i, tree in enumerate(("mamba", "attn", "moe", "mamba1", "cross",
                              "gmu", "mlp", "conv")):
        if tree in n:
            params[tree] = {**stack(jax.random.fold_in(k_stack, i), n[tree],
                                    dims[tree]), **small[tree]}
    return params


def init_params(config: ModelConfig, key: jax.Array,
                dtype=DEFAULT_COMPUTE_DTYPE) -> dict:
    """Random init (scaled normal)."""
    def stack(k, L, dims):
        return {name: _normal(jax.random.fold_in(k, i), (L, *shape),
                              _init_scale(name, shape, config), dtype)
                for i, (name, shape) in enumerate(dims.items())}

    return _build(config, key, dtype, stack, lambda k, shape: _normal(
        k, shape, shape[0] ** -0.5, dtype), lambda embed: embed.T)


def init_params_quantized(config: ModelConfig, key: jax.Array,
                          dtype=DEFAULT_COMPUTE_DTYPE,
                          quant: str = "int8") -> dict:
    """Random init streamed straight into the int8 tree, one leaf of one
    layer (one expert of it) at a time (pangu.streamed_stack)."""
    from .quant import quantize

    if quant != "int8":
        raise ValueError(f"{config.name}: the hybrid family serves int8 or "
                         f"plain weights, not {quant!r}")

    def leaf(k, shape, name=""):
        return quantize(_normal(k, shape, _init_scale(name, shape, config),
                                dtype))

    return _build(config, key, dtype, streamed_stack(leaf, quant), leaf,
                  jax.jit(lambda embed: quantize(embed.T)))


def fuse_params(params: dict, tp: int = 1, mesh: Optional[Mesh] = None,
                **_) -> dict:
    """The tree is born fused (wqkv)."""
    return params


def param_axes(config: ModelConfig) -> dict:
    """Everything replicated: the family serves on one chip."""
    shapes = jax.eval_shape(lambda: init_params(config,
                                                jax.random.PRNGKey(0)))
    axes = jax.tree.map(lambda a: (None,) * a.ndim, shapes)
    axes.update(embed=("vocab", "embed"), final_norm=("embed",),
                lm_head=("embed", "vocab"))
    return axes


# -- the mixers ---------------------------------------------------------------

def _norm(h, lp: dict, config: ModelConfig, name: str = "norm"):
    """The pre-norm of a layer: RMSNorm, or a biased LayerNorm."""
    if config.norm_kind != "layer":
        return rms_norm(h, lp[name], config.rms_norm_eps)
    x = h.astype(jnp.float32)
    x = x - jnp.mean(x, axis=-1, keepdims=True)
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                          + config.rms_norm_eps)
    return (x * lp[name].astype(jnp.float32)
            + lp[name + "_b"].astype(jnp.float32)).astype(h.dtype)


def _mamba_split(config: ModelConfig, lp: dict):
    """``split(conv_out, dt_raw) -> (x, dt, A, Bm, Cm)``: what
    ops/state_pool reads of the convolved channels and the raw time
    step."""
    d, nh, P = (config.mamba_inner, config.mamba_num_heads,
                config.mamba_head_dim)
    G, N = config.ssm_groups, config.ssm_state_size
    A = -jnp.exp(lp["A_log"].astype(jnp.float32))

    def split(conv_out, dt_raw):
        lead = conv_out.shape[:-1]
        xbc = jax.nn.silu(conv_out)
        x = xbc[..., :d].reshape(*lead, nh, P)
        Bm = xbc[..., d: d + G * N].reshape(*lead, G, N)
        Cm = xbc[..., d + G * N:].reshape(*lead, G, N)
        dt = jax.nn.softplus(dt_raw.astype(jnp.float32)
                             + lp["dt_bias"].astype(jnp.float32))
        return x, dt, A, Bm, Cm

    return split


def _mamba_out(y, x, z, lp, config: ModelConfig, dtype):
    """``y + D x``, the gated grouped norm (gate first) and ``W_out``.
    y, x [..., heads, head_dim] float32; z [..., d]."""
    G = config.ssm_groups
    lead = y.shape[:-2]
    y = y + lp["D"].astype(jnp.float32)[:, None] * x.astype(jnp.float32)
    y = y.reshape(*lead, -1) * jax.nn.silu(z.astype(jnp.float32))
    g = y.reshape(*lead, G, -1)
    g = g * jax.lax.rsqrt(jnp.mean(g * g, axis=-1, keepdims=True)
                          + config.rms_norm_eps)
    y = g.reshape(*lead, -1) * lp["gnorm"].astype(jnp.float32)
    return mm(y.astype(dtype), lp["w_out"])


def _mamba_in(h, lp, config: ModelConfig):
    d = config.mamba_inner
    u = rms_norm(h, lp["norm"], config.rms_norm_eps)
    zxd = mm(u, lp["w_in"])
    return (zxd[..., :d], zxd[..., d: d + config.conv_dim],
            zxd[..., d + config.conv_dim:])


def _mamba_prefill(h, lp, config: ModelConfig, state: StatePool, layer,
                   valid: jax.Array):
    """h [B,S,H] behind the carried state of Mamba layer ``layer`` of
    ``state`` ([L_m, B, ...]). Returns (out [B,S,H], state)."""
    z, xbc, dt_raw = _mamba_in(h, lp, config)
    S_in = jax.lax.dynamic_index_in_dim(state.ssm, layer, 0, False)
    win = jax.lax.dynamic_index_in_dim(state.conv, layer, 0, False)
    lengths = jnp.sum(valid, axis=1)
    conv_out, win = state_pool.conv_scan(xbc, win, lengths, lp["conv_w"],
                                         lp["conv_b"])
    x, dt, A, Bm, Cm = _mamba_split(config, lp)(conv_out, dt_raw)
    dt = jnp.where(valid[..., None], dt, 0.0)
    y, S_out = state_pool.ssd_scan(x, dt, A, Bm, Cm, S_in, config.ssm_chunk)
    state = StatePool(
        ssm=jax.lax.dynamic_update_index_in_dim(state.ssm, S_out, layer, 0),
        conv=jax.lax.dynamic_update_index_in_dim(
            state.conv, win.astype(state.conv.dtype), layer, 0))
    return _mamba_out(y, x, z, lp, config, h.dtype), state


def _mamba_decode(h, lp, config: ModelConfig, pool: StatePool, layer,
                  live: jax.Array):
    """h [B,1,H]: one step of Mamba layer ``layer`` over the pool's first
    B rows. Returns (out [B,1,H], pool)."""
    z, xbc, dt_raw = _mamba_in(h[:, 0], lp, config)
    split = _mamba_split(config, lp)
    y, x, pool = state_pool.decode_update(
        pool, layer, live, xbc, lp["conv_w"], lp["conv_b"],
        lambda conv_out: split(conv_out, dt_raw))
    return _mamba_out(y, x, z, lp, config, h.dtype)[:, None], pool


def _qkv(h, lp, config: ModelConfig, positions=None, window: bool = False):
    """q [B,S,Hq,D], k, v [B,S,Hkv,D] of a GQA layer. ``positions``
    ([B|1,S]): where ``attn_rope``, q and k come back rotated by the
    layer kind's table (``window``: the plain one), behind the per-head
    RMSNorm where the model has one (``qk_norm_head``); q carries the
    softmax scale where the model states one (``attention_multiplier``)."""
    B, S, _ = h.shape
    qkv = mm(rms_norm(h, lp["norm"], config.rms_norm_eps), lp["wqkv"])
    Q, KV = config.q_dim, config.kv_dim
    q = qkv[..., :Q].reshape(B, S, config.num_heads, config.head_dim)
    k = qkv[..., Q: Q + KV].reshape(B, S, config.num_kv_heads,
                                    config.head_dim)
    v = qkv[..., Q + KV:].reshape(B, S, config.num_kv_heads,
                                  config.head_dim)
    if config.qk_norm_head:
        q = rms_norm(q, lp["q_norm"], config.rms_norm_eps)
        k = rms_norm(k, lp["k_norm"], config.rms_norm_eps)
    if config.attn_rope:
        inv_freq, factor = rope_table(config, window)
        q = apply_rope(q, positions, inv_freq, factor)
        k = apply_rope(k, positions, inv_freq, factor)
    if config.attention_multiplier:
        # Every reader of q divides q.k by sqrt(head_dim): the factor
        # that makes that product ``attention_multiplier`` (Granite's
        # 1/64 at a head of 64: an eighth, exact in bf16).
        q = q * jnp.asarray(
            config.attention_multiplier * config.head_dim ** 0.5, q.dtype)
    return q, k, v


def _chunk_positions(offset: int, S: int) -> jax.Array:
    return (offset + jnp.arange(S, dtype=jnp.int32))[None, :]


def _attn_prefill(h, lp, config: ModelConfig, ck, cv, layer: int,
                  offset: int):
    """llama._block's attention against the dense carry: the chunk's K
    and V land at slots offset..offset+S of cache layer ``layer`` and the
    chunk attends the carry under the offset causal mask, as far as its
    own last position: nothing lies past it yet, and the chunk ladder of
    a 16 K bucket would score sixteen times what it keeps. The cut is to
    the next whole chunk of the flash scan (layers.FLASH_KV_CHUNK), the
    widths that scan takes; a carry no wider than one is read whole, as
    it always was."""
    B, S, _ = h.shape
    q, k, v = _qkv(h, lp, config, _chunk_positions(offset, S))
    zero = jnp.zeros((), jnp.int32)
    at = (jnp.asarray(layer, jnp.int32), zero,
          jnp.asarray(offset, jnp.int32), zero, zero)
    # The carry has the pool's geometry: KV heads in pairs at a head of
    # 64 (ModelConfig.kv_paired), a reshape either way.
    paired = config.kv_paired
    if paired:
        k, v = _rows(k, config), _rows(v, config)
    ck = jax.lax.dynamic_update_slice(ck, k[None].astype(ck.dtype), at)
    cv = jax.lax.dynamic_update_slice(cv, v[None].astype(cv.dtype), at)
    T = min(ck.shape[2],
            -(-(offset + S) // FLASH_KV_CHUNK) * FLASH_KV_CHUNK)
    keys, vals = ck[layer][:, :T], cv[layer][:, :T]
    if paired:
        keys, vals = _heads(keys, config), _heads(vals, config)
    attn = attend_gqa_auto(q, keys, vals, causal_mask(S, T, offset),
                           causal0_len=S if offset == 0 else None)
    return mm(attn.reshape(B, S, config.q_dim), lp["wo"]), ck, cv


def _attn_decode(h, lp, config: ModelConfig, cache, layer: int, pages: int):
    from ..ops.paged_attention import (paged_attention_append,
                                       paged_attention_append_paired)
    B = h.shape[0]
    q, k, v = _qkv(h, lp, config, cache.lengths[:, None])
    if config.kv_paired:
        attn = paged_attention_append_paired(
            q[:, 0], k[:, 0], v[:, 0], cache, cache.lengths, layer,
            pages=pages)
        return (mm(attn.reshape(B, 1, config.q_dim), lp["wo"]),
                _rows(k[:, 0], config), _rows(v[:, 0], config))
    attn = paged_attention_append(q[:, 0], k[:, 0], v[:, 0], cache,
                                  cache.lengths, layer, pages=pages)
    return mm(attn.reshape(B, 1, config.q_dim), lp["wo"]), k[:, 0], v[:, 0]


def _index_proj(h, lp, config: ModelConfig, positions):
    """An indexed layer's (qI [B,S,Hi,Dp], kI [B,S,Dp], w [B,S,Hi]) of
    its normed input: ``[qI | kI | w] = u W_idx``, kI through a
    LayerNorm, qI and kI rotated (rotate-half over their Di numbers, a
    plain table of ``rope_theta``), both in rows of Dp =
    ``cache_idx_dim`` lanes, zeros past Di."""
    B, S, _ = h.shape
    Hi, Di = config.index_heads, config.index_head_dim
    qkw = mm(rms_norm(h, lp["norm"], config.rms_norm_eps), lp["w_idx"])
    qi = qkw[..., : Hi * Di].reshape(B, S, Hi, Di)
    ki = qkw[..., Hi * Di: (Hi + 1) * Di].astype(jnp.float32)
    ki = ki - jnp.mean(ki, axis=-1, keepdims=True)
    ki = ki * jax.lax.rsqrt(jnp.mean(ki * ki, axis=-1, keepdims=True)
                            + config.rms_norm_eps)
    ki = (ki * lp["ki_norm"].astype(jnp.float32)
          + lp["ki_norm_b"].astype(jnp.float32)).astype(h.dtype)
    inv_freq = 1.0 / (config.rope_theta ** (
        jnp.arange(0, Di, 2, dtype=jnp.float32) / Di))
    qi = apply_rope(qi, positions, inv_freq)
    ki = apply_rope(ki[:, :, None], positions, inv_freq)[:, :, 0]
    # In the caches' lanes (ModelConfig.cache_idx_dim): the key's row
    # zero-padded, the queries zero-extended, the dots what they were.
    lanes = ((0, 0),) * 2 + ((0, config.cache_idx_dim - Di),)
    return (jnp.pad(qi, lanes[:1] + lanes), jnp.pad(ki, lanes),
            qkw[..., (Hi + 1) * Di:])


def _sel_attn_prefill(h, lp, config: ModelConfig, ck, cv, ci, layer,
                      offset: int):
    """:func:`_attn_prefill` for an indexed layer: the chunk's index keys
    land in ``ci`` beside K and V, and from the chunk that passes
    ``index_topk`` positions on each query attends only the keys its
    index scores select among all before it (the carry's, from earlier
    chunks, and the chunk's own), under the causal mask: the dense
    attention under the selection as a mask. Returns (out, ck, cv, ci,
    the mask [B,S,T] or None where everything is selected)."""
    from ..ops.paged_attention import (select_attention_chunk,
                                       index_scores, select_attention_fits,
                                       select_mask)
    B, S, _ = h.shape
    pos = _chunk_positions(offset, S)
    q, k, v = _qkv(h, lp, config, pos)
    qi, ki, wi = _index_proj(h, lp, config, pos)
    zero = jnp.zeros((), jnp.int32)
    at = (jnp.asarray(layer, jnp.int32), zero,
          jnp.asarray(offset, jnp.int32), zero, zero)
    ck = jax.lax.dynamic_update_slice(ck, k[None].astype(ck.dtype), at)
    cv = jax.lax.dynamic_update_slice(cv, v[None].astype(cv.dtype), at)
    ci = jax.lax.dynamic_update_slice(
        ci, ki[None, :, :, None].astype(ci.dtype), at)
    T = min(ck.shape[2],
            -(-(offset + S) // FLASH_KV_CHUNK) * FLASH_KV_CHUNK)
    keys, vals = ck[layer][:, :T], cv[layer][:, :T]
    causal = causal_mask(S, T, offset)
    mask = None
    if offset + S <= config.index_topk:
        attn = attend_gqa_auto(q, keys, vals, causal,
                               causal0_len=S if offset == 0 else None)
    else:
        scores = index_scores(qi, wi, ci[layer][:, :T, 0].astype(qi.dtype))
        mask = select_mask(scores, causal[0], config.index_topk)
        if select_attention_fits(S, T, config.head_dim):
            attn = select_attention_chunk(q, keys, vals, mask,
                                          offset=offset)
        else:
            attn = attend_gqa_auto(q, keys, vals, mask[:, None])
    return mm(attn.reshape(B, S, config.q_dim), lp["wo"]), ck, cv, ci, mask


def _sel_attn_decode(h, lp, config: ModelConfig, cache, layer, pages: int,
                     kept: bool = False):
    """One step of an indexed layer. Returns (out [B,1,H], k, v [B,Hkv,D],
    kI [B,1,Dp] of the step, and with ``kept`` the positions read [B,
    index_topk], -1 where a row has fewer; else None)."""
    from ..ops.paged_attention import (kept_positions,
                                       paged_attention_select_append)
    B = h.shape[0]
    pos = cache.lengths[:, None]
    q, k, v = _qkv(h, lp, config, pos)
    qi, ki, wi = _index_proj(h, lp, config, pos)
    attn, keep = paged_attention_select_append(
        q[:, 0], k[:, 0], v[:, 0], qi[:, 0], wi[:, 0], ki[:, 0], cache,
        cache.lengths, layer, pages=pages, topk=config.index_topk)
    return (mm(attn.reshape(B, 1, config.q_dim), lp["wo"]), k[:, 0],
            v[:, 0], ki,
            kept_positions(keep, config.index_topk) if kept else None)


def _gqa_window_prefill(h, lp, config: ModelConfig, state: StatePool, layer,
                        offset: int, valid: jax.Array):
    """:func:`_window_prefill` in the plain GQA form: the chunk's rotated
    queries over the carried ring (rotated when it was written) and the
    chunk's own rotated keys, one softmax over both."""
    B, S, _ = h.shape
    W = config.sliding_window
    q, k, v = _qkv(h, lp, config, _chunk_positions(offset, S), window=True)
    rk = jax.lax.dynamic_index_in_dim(state.win_k, layer, 0, False)
    rv = jax.lax.dynamic_index_in_dim(state.win_v, layer, 0, False)
    in_ring, in_chunk = state_pool.ring_chunk_masks(S, W, offset)
    keys, vals, mask = k, v, in_chunk
    if offset:      # a fresh prompt's ring is empty
        keys = jnp.concatenate([jnp.swapaxes(rk, 1, 2).astype(k.dtype), k], 1)
        vals = jnp.concatenate([jnp.swapaxes(rv, 1, 2).astype(v.dtype), v], 1)
        mask = jnp.concatenate([in_ring, in_chunk], axis=1)
    attn = attend_gqa_auto(q, keys, vals, mask[None, None],
                           causal0_len=S if not offset and S <= W else None)
    lengths = jnp.sum(valid, axis=1)
    state = state._replace(
        win_k=jax.lax.dynamic_update_index_in_dim(
            state.win_k, state_pool.ring_after_chunk(rk, k, offset, lengths),
            layer, 0),
        win_v=jax.lax.dynamic_update_index_in_dim(
            state.win_v, state_pool.ring_after_chunk(rv, v, offset, lengths),
            layer, 0))
    return mm(attn.reshape(B, S, config.q_dim), lp["wo"]), state


def _gqa_window_decode(h, lp, config: ModelConfig, pool: StatePool, layer,
                       live: jax.Array, lengths: jax.Array):
    """:func:`_window_decode` in the plain GQA form: query head ``j``
    reads KV head ``j // rep`` of the ring where it lies, [B,G,W,D]; an
    int8 ring's scales fold outside the dots, as ops/paged_attention's
    do."""
    f32 = jnp.float32
    B, W, D = h.shape[0], config.sliding_window, config.head_dim
    G = config.num_kv_heads
    q, k, v = _qkv(h, lp, config, lengths[:, None], window=True)
    pool = state_pool.ring_decode_write(pool, layer, live, lengths, k[:, 0],
                                        v[:, 0])
    rk, rv, ks, vs = state_pool.ring_read(pool, layer, B)
    qg = q[:, 0].reshape(B, G, config.num_heads // G, D)
    sc = jnp.einsum("bgrd,bgwd->bgrw", qg, rk.astype(qg.dtype),
                    preferred_element_type=f32) / jnp.sqrt(D).astype(f32)
    if ks is not None:
        sc = sc * ks[:, :, None, :]
    seen = jnp.arange(W)[None, :] <= lengths[:, None]
    probs = jax.nn.softmax(jnp.where(seen[:, None, None, :], sc, NEG_INF),
                           axis=-1)
    if vs is not None:
        probs = probs * vs[:, :, None, :]
    attn = jnp.einsum("bgrw,bgwd->bgrd", probs.astype(qg.dtype),
                      rv.astype(qg.dtype), preferred_element_type=f32)
    return mm(attn.astype(h.dtype).reshape(B, 1, config.q_dim),
              lp["wo"]), pool


def _conv_in(u, lp, config: ModelConfig):
    """(z, C): the convolution's gated input ``B * x`` and the gate on
    its output, of ``[B | C | x] = u W_in``."""
    H = config.hidden_size
    bcx = mm(rms_norm(u, lp["norm"], config.rms_norm_eps), lp["w_in"])
    return bcx[..., :H] * bcx[..., 2 * H:], bcx[..., H: 2 * H]


def _conv_prefill(h, lp, config: ModelConfig, state: StatePool, layer,
                  valid: jax.Array):
    """h [B,S,H] through short-convolution layer ``layer`` behind the
    carried window ([L_c, B, K-1, H]; zeros before position 0). No bias,
    no activation: ``out = (C * conv(B * x)) W_out``. Returns (out,
    state)."""
    z, gate = _conv_in(h, lp, config)
    win = jax.lax.dynamic_index_in_dim(state.conv, layer, 0, False)
    y, win = state_pool.conv_scan(z, win, jnp.sum(valid, axis=1),
                                  lp["conv_w"], None)
    state = state._replace(conv=jax.lax.dynamic_update_index_in_dim(
        state.conv, win.astype(state.conv.dtype), layer, 0))
    return mm(gate * y.astype(h.dtype), lp["w_out"]), state


def _conv_decode(h, lp, config: ModelConfig, pool: StatePool, layer,
                 live: jax.Array):
    """h [B,1,H]: one step over the pool's first B rows. Returns (out
    [B,1,H], pool)."""
    z, gate = _conv_in(h[:, 0], lp, config)
    y, pool = state_pool.conv_update(pool, layer, live, z, lp["conv_w"])
    return mm(gate * y.astype(h.dtype), lp["w_out"])[:, None], pool


# -- the SambaY kinds ---------------------------------------------------------

def _mamba1_in(h, lp, config: ModelConfig):
    d = config.mamba1_inner
    xz = mm(_norm(h, lp, config), lp["w_in"])
    return xz[..., :d], xz[..., d:]


def _mamba1_split(config: ModelConfig, lp: dict, dtype):
    """``split(conv_out) -> (x, dt, A, Bm, Cm)`` in ssm1_step's shapes:
    the time step and B, C are projections of the CONVOLVED channels."""
    R, N = config.mamba1_dt_rank, config.mamba1_state
    A = -jnp.exp(lp["A_log"].astype(jnp.float32))            # [N, d]

    def split(conv_out):
        x = jax.nn.silu(conv_out).astype(dtype)
        dbc = mm(x, lp["w_x"])
        dt = jax.nn.softplus(mm(dbc[..., :R], lp["w_dt"]).astype(jnp.float32)
                             + lp["dt_bias"].astype(jnp.float32))
        return x, dt, A, dbc[..., R: R + N], dbc[..., R + N:]

    return split


def _mamba1_out(y, x, z, lp, dtype):
    """(out, m): ``m = y + D x`` is what a publishing layer hands up,
    before the ``z`` gate."""
    m = y + lp["D"].astype(jnp.float32) * x.astype(jnp.float32)
    out = mm((m * jax.nn.silu(z.astype(jnp.float32))).astype(dtype),
             lp["w_out"])
    return out, m.astype(dtype)


def _mamba1_prefill(h, lp, config: ModelConfig, state: StatePool, layer,
                    valid: jax.Array):
    """h [B,S,H] behind the carried state of Mamba-1 layer ``layer``.
    Returns (out [B,S,H], m [B,S,d], state)."""
    x, z = _mamba1_in(h, lp, config)
    S_in = jax.lax.dynamic_index_in_dim(state.ssm, layer, 0, False)
    win = jax.lax.dynamic_index_in_dim(state.conv, layer, 0, False)
    conv_out, win = state_pool.conv_scan(x, win, jnp.sum(valid, axis=1),
                                         lp["conv_w"], lp["conv_b"])
    x, dt, A, Bm, Cm = _mamba1_split(config, lp, h.dtype)(conv_out)
    dt = jnp.where(valid[..., None], dt, 0.0)
    y, S_out = state_pool.ssm1_scan(x, dt, A, Bm, Cm, S_in)
    state = state._replace(
        ssm=jax.lax.dynamic_update_index_in_dim(state.ssm, S_out, layer, 0),
        conv=jax.lax.dynamic_update_index_in_dim(
            state.conv, win.astype(state.conv.dtype), layer, 0))
    return (*_mamba1_out(y, x, z, lp, h.dtype), state)


def _mamba1_decode(h, lp, config: ModelConfig, pool: StatePool, layer,
                   live: jax.Array):
    """h [B,1,H]: one step over the pool's first B rows. Returns (out
    [B,1,H], m [B,1,d], pool)."""
    x, z = _mamba1_in(h[:, 0], lp, config)
    y, x, pool = state_pool.decode_update(
        pool, layer, live, x, lp["conv_w"], lp["conv_b"],
        _mamba1_split(config, lp, h.dtype), step=state_pool.ssm1_step)
    out, m = _mamba1_out(y, x, z, lp, h.dtype)
    return out[:, None], m[:, None], pool


def _diff_qkv(h, lp, config: ModelConfig):
    B, S, _ = h.shape
    qkv = mm(_norm(h, lp, config), lp["wqkv"]) + lp["bqkv"]
    Q, KV = config.q_dim, config.kv_dim
    return (qkv[..., :Q].reshape(B, S, config.num_heads, config.head_dim),
            qkv[..., Q: Q + KV].reshape(B, S, config.num_kv_heads,
                                        config.head_dim),
            qkv[..., Q + KV:].reshape(B, S, config.num_kv_heads,
                                      config.head_dim))


def _diff_q(h, lp, config: ModelConfig):
    B, S, _ = h.shape
    q = mm(_norm(h, lp, config), lp["wq"]) + lp["bq"]
    return q.reshape(B, S, config.num_heads, config.head_dim)


def _diff_out(q, parts: list, lp, config: ModelConfig):
    """The differential form over ``parts`` and the output projection:
    a chunk's queries [B,S,Hq,D] over :class:`Keys`, or one decode query
    a row [B,1,Hq,D] over :class:`FlatKeys`, the caches' own layout."""
    lam0 = lp["lam0"]
    lam = diff_attention.lam_of(lp, lam0)
    if isinstance(parts[0], FlatKeys):
        o = diff_attention.attend_decode(q[:, 0], parts, lam, lam0,
                                         lp["sub_w"],
                                         config.rms_norm_eps)[:, None]
    else:
        o = diff_attention.attend(q, parts, lam, lam0, lp["sub_w"],
                                  config.rms_norm_eps)
    return mm(o, lp["wo"]) + lp["bo"]


def _rows(x, config: ModelConfig):
    """[..., Hkv, D] -> [..., 1, Hkv x D]: the caches' geometry, every
    KV head of a position side by side."""
    return x.reshape(*x.shape[:-2], config.cache_kv_heads,
                     config.cache_k_dim)


def _heads(x, config: ModelConfig):
    """[..., 1, Hkv x D] -> [..., Hkv, D]."""
    return x.reshape(*x.shape[:-2], config.num_kv_heads, config.head_dim)


def _window_prefill(h, lp, config: ModelConfig, state: StatePool, layer,
                    offset: int, valid: jax.Array):
    """A chunk through window layer ``layer`` (its index among the
    window layers): the queries read the carried ring and the chunk's
    own keys, and the ring comes back holding each row's last real
    positions. Returns (out [B,S,H], state)."""
    if not config.attn_diff:
        return _gqa_window_prefill(h, lp, config, state, layer, offset,
                                   valid)
    S = h.shape[1]
    q, k, v = _diff_qkv(h, lp, config)
    rk = jax.lax.dynamic_index_in_dim(state.win_k, layer, 0, False)
    rv = jax.lax.dynamic_index_in_dim(state.win_v, layer, 0, False)
    in_ring, in_chunk = state_pool.ring_chunk_masks(
        S, config.sliding_window, offset)
    parts = [Keys(k, v, in_chunk[None])]
    if offset:      # a fresh prompt's ring is empty
        parts.insert(0, Keys(_heads(jnp.swapaxes(rk, 1, 2), config),
                             _heads(jnp.swapaxes(rv, 1, 2), config),
                             in_ring[None]))
    out = _diff_out(q, parts, lp, config)
    lengths = jnp.sum(valid, axis=1)
    state = state._replace(
        win_k=jax.lax.dynamic_update_index_in_dim(
            state.win_k, state_pool.ring_after_chunk(
                rk, _rows(k, config), offset, lengths), layer, 0),
        win_v=jax.lax.dynamic_update_index_in_dim(
            state.win_v, state_pool.ring_after_chunk(
                rv, _rows(v, config), offset, lengths), layer, 0))
    return out, state


def _window_decode(h, lp, config: ModelConfig, pool: StatePool, layer,
                   live: jax.Array, lengths: jax.Array):
    """One step of window layer ``layer``: the token's K and V go into
    slot ``lengths mod W`` of each live row's ring, then the query reads
    the ring's first ``min(lengths + 1, W)`` slots."""
    if not config.attn_diff:
        return _gqa_window_decode(h, lp, config, pool, layer, live, lengths)
    B, W = h.shape[0], config.sliding_window
    q, k, v = _diff_qkv(h, lp, config)
    pool = state_pool.ring_decode_write(
        pool, layer, live, lengths, _rows(k[:, 0], config),
        _rows(v[:, 0], config))
    ring = [a if a is None else a[:, 0]
            for a in state_pool.ring_read(pool, layer, B)]
    mask = jnp.arange(W)[None, :] <= lengths[:, None]
    return _diff_out(q, [FlatKeys(*ring[:2], mask, *ring[2:])], lp,
                     config), pool


def _diff_attn_prefill(q, lp, config: ModelConfig, ck, cv, layer: int,
                       offset: int):
    """Queries at positions offset.. against cache layer ``layer`` of
    the dense carry, causal over its whole width."""
    mask = causal_mask(q.shape[1], ck.shape[2], offset)[0]
    return _diff_out(q, [Keys(_heads(ck[layer], config),
                              _heads(cv[layer], config), mask)], lp, config)


def _pool_keys(cache, layer: int, pages: int, k_cur, v_cur) -> list:
    """What a decode query of the ``*`` layer, or of a cross layer above
    it, reads: the layer's window of the page pool, gathered ONCE a step
    for all of them, and this step's own K and V (not in the pool
    yet)."""
    from ..ops.paged_attention import gather_window
    k, v, ks, vs = gather_window(cache, layer, pages=pages)
    mask = jnp.arange(k.shape[1])[None, :] < cache.lengths[:, None]
    if ks is not None:
        ks, vs = ks[:, 0], vs[:, 0]
    return [FlatKeys(k[:, :, 0], v[:, :, 0], mask, ks, vs),
            FlatKeys(k_cur, v_cur, jnp.ones((1, 1), bool))]


def _gmu(h, lp, config: ModelConfig, m):
    g = jax.nn.silu(mm(_norm(h, lp, config), lp["w_in"]))
    return mm(g * m, lp["w_out"])


def _mlp(h, lp, config: ModelConfig):
    gu = mm(_norm(h, lp, config), lp["w_gu"])
    F = config.dense_intermediate_size or config.intermediate_size
    return mm(jax.nn.silu(gu[..., :F]) * gu[..., F:], lp["w_mlp_down"])


def _relu2_mlp(x, w_up, w_down):
    return mm(jnp.square(jax.nn.relu(mm(x, w_up))), w_down)


def _moe(h, lp, config: ModelConfig, counted, live, chosen: bool = False):
    """(out [B,S,H], stats int32 [4]) and, where ``chosen``, third the
    experts the router kept (int32 [B*S,k])."""
    x = rms_norm(h, lp["norm"], config.rms_norm_eps)
    if not config.moe_latent_size:
        return _routed_local(x, lp, config, counted, live, chosen=chosen)
    latent = mm(x, lp["w_fc1"])
    routed, *rest = _routed_local(x, lp, config, counted, live, latent,
                                  chosen)
    return (mm(routed, lp["w_fc2"])
            + _relu2_mlp(x, lp["w_up_s"], lp["w_down_s"])), *rest


# -- the stack ----------------------------------------------------------------

def _run_stack(params: dict, config: ModelConfig, h: jax.Array, ops: dict,
               counted, live, carry, chosen: bool = False):
    """Walk the pattern. ``ops[letter](h, lp, k, carry) -> (out, carry)``
    is the mode's (prefill's, decode's) mixer of that kind, ``lp`` the
    layer's view of its tree and ``k`` its index among the layers that
    share its per-row past: a Mamba layer's in the state pool, a window
    layer's among the rings, a ``*`` layer's among the page layers (a
    tracer inside a scan; ``*`` is scanned only with its whole stretch,
    :func:`_segments`, ``Y`` never, and they get a Python int otherwise).
    ``E`` and ``-`` keep nothing and are run here. Returns (h, carry,
    stats); with ``chosen`` the stats are a pair, the counts and the
    experts every routed layer's router kept (int32 [``E`` layers, B, S,
    k]: what a reference replays to tell a wrong layer from a near tie
    decided the other way, benchmark/architectures/lfm2.py)."""
    def add(h, out):
        """The residual add, ``residual_multiplier`` on what a layer's
        half put out (1: the add alone)."""
        if config.residual_multiplier != 1.0:
            out = out * jnp.asarray(config.residual_multiplier, out.dtype)
        return h + out

    def moe_step(h, lp, k, carry, stats):
        out, st, *top_i = _moe(h, lp, config, counted, live, chosen)
        if not chosen:
            return add(h, out), carry, stats + st
        (top_i,), (counts, kept) = top_i, stats
        return add(h, out), carry, (
            counts + st, jax.lax.dynamic_update_index_in_dim(
                kept, top_i.reshape(kept.shape[1:]), k, 0))

    def mlp_step(h, lp, k, carry, stats):
        return add(h, _mlp(h, lp, config)), carry, stats

    def kept(op):
        def step(h, lp, k, carry, stats):
            out, carry = op(h, lp, k, carry)
            return add(h, out), carry, stats
        return step

    steps = {"E": moe_step, "-": mlp_step,
             **{ch: kept(op) for ch, op in ops.items()}}

    def run(ch, h, idx, k, carry, stats):
        return steps[ch](h, _layer_view(params[TREES[ch]], idx), k, carry,
                         stats)

    def walk(state, r, segment, before, round_letters=None, more=None):
        """One stretch of the pattern behind the layers ``before``:
        ``segment`` as it stands (``r`` None), or its ``r``-th copy (a
        tracer inside the scan over a stretch's periods). A round of
        varied length (:func:`varied`) is walked in parts: a round's
        fixed letters are ``round_letters``, and ``more(count)`` is the
        layers its repeated group has put before this part."""
        # Layers of a kind (a tree, a past) in some letters: a Python int.
        def count(ch, group, letters):
            return sum(group[c] == group[ch] for c in letters)

        def place(x, ch, group):
            if before:
                x = x + count(ch, group, before)
            if r is None:
                return x
            x = x + r * count(ch, group, round_letters or segment)
            if more is None:
                return x
            return x + more(lambda letters: count(ch, group, letters))

        h, carry, stats = state
        for letters, n, at in _plan(segment):
            if n == 1:
                idx, _ = _index(at, letters, 0, TREES)
                k, _ = _index(at, letters, 0, PASTS)
                if letters not in "*Y":
                    k = jnp.asarray(k, jnp.int32)
                h, carry, stats = run(
                    letters, h, place(jnp.asarray(idx, jnp.int32), letters,
                                      TREES), place(k, letters, PASTS),
                    carry, stats)
            else:
                def body(state, i, letters=letters, at=at):
                    h, carry, stats = state
                    for j, ch in enumerate(letters):
                        (t0, tn), (k0, kn) = (_index(at, letters, j, TREES),
                                              _index(at, letters, j, PASTS))
                        h, carry, stats = run(
                            ch, h,
                            place(i + t0 if tn == 1 else i * tn + t0, ch,
                                  TREES),
                            place(i + k0 if kn == 1 else i * kn + k0, ch,
                                  PASTS), carry, stats)
                    return (h, carry, stats), None

                (h, carry, stats), _ = jax.lax.scan(
                    body, (h, carry, stats), jnp.arange(n, dtype=jnp.int32))
        return h, carry, stats

    def varied(state, parts, copies, before):
        """One scan over rounds of ``head + group * m + tail`` with ``m``
        = ``copies`` of each round: the group a loop of ``m`` turns."""
        head, group, tail = parts
        fixed = head + tail
        earlier = jnp.asarray([sum(copies[:i]) for i in range(len(copies))],
                              jnp.int32)
        copies = jnp.asarray(copies, jnp.int32)

        def one(state, r):
            m, done = copies[r], earlier[r]
            state = walk(state, r, head, before, fixed,
                         lambda count: done * count(group))
            state = jax.lax.fori_loop(
                0, m, lambda j, state: walk(
                    state, r, group, before + head, fixed,
                    lambda count: (done + j) * count(group)), state)
            if tail:
                state = walk(state, r, tail, before + head, fixed,
                             lambda count: (done + m) * count(group))
            return state, None

        return jax.lax.scan(one, state,
                            jnp.arange(len(copies), dtype=jnp.int32))[0]

    stats = no_stats()
    if chosen:
        stats = (stats, jnp.zeros(
            (config.hybrid_pattern.count("E"), *h.shape[:2],
             config.num_experts_per_tok), jnp.int32))
    state, before = (h, carry, stats), ""
    for letters, rounds in _segments(config.hybrid_pattern):
        if rounds == 1:
            state = walk(state, None, letters, before)
        elif isinstance(rounds, tuple):
            state = varied(state, letters, rounds, before)
            head, group, tail = letters
            before += "".join(head + group * m + tail for m in rounds)
            continue
        else:
            state = jax.lax.scan(
                lambda state, r, letters=letters, before=before: (
                    walk(state, r, letters, before), None),
                state, jnp.arange(rounds, dtype=jnp.int32))[0]
        before += letters * rounds
    return state


def _logits(params, config, h, last_idx):
    if last_idx is not None:
        h = jnp.take_along_axis(h, last_idx[:, None, None].astype(jnp.int32),
                                axis=1)
    h = _norm(h, params, config, "final_norm")
    logits = mm(h, params["lm_head"]).astype(jnp.float32)
    if config.logits_scaling != 1.0:
        logits = logits / config.logits_scaling
    return logits


def _embed(params, config, tokens):
    """``E[token]``, times ``embedding_multiplier`` where the model
    states one."""
    h = params["embed"][tokens]
    if config.embedding_multiplier != 1.0:
        h = h * jnp.asarray(config.embedding_multiplier, h.dtype)
    return h


def _refuse_mesh(mesh) -> None:
    if mesh is not None:
        raise ValueError("the hybrid family serves on one chip: its "
                         "recurrent state is not laid out over a mesh")


def _forward(params: dict, config: ModelConfig, tokens: jax.Array,
             cache: KVCache, offset: int, valid: Optional[jax.Array],
             last_idx: Optional[jax.Array], hidden: bool = False,
             chosen: bool = False):
    """Tokens [B,S] at positions offset..offset+S behind the carry
    ``cache``: K and V of the context in its slots below ``offset``, the
    recurrent state at position ``offset`` in ``cache.state``. ``valid``
    [B,S]: a row's real positions (a prefix of it; None = all). Returns
    (logits | hidden states, cache, stats); ``chosen``:
    :func:`_run_stack`'s pair, and third, for an indexed model, what every
    ``s`` layer's queries selected."""
    B, S = tokens.shape
    if valid is None:
        valid = jnp.ones((B, S), bool)
    h = _embed(params, config, tokens)
    # What ``Y`` and ``*`` hand to the layers above them in this step.
    published: dict = {}

    # The carry: K, V and the state; behind them, for an indexed model,
    # its index keys and (``chosen``) every ``s`` layer's selection.
    def mamba(h, lp, layer, carry):
        ck, cv, state, *more = carry
        out, state = _mamba_prefill(h, lp, config, state, layer, valid)
        return out, (ck, cv, state, *more)

    def mamba1(h, lp, layer, carry, publish=False):
        ck, cv, state, *more = carry
        out, m, state = _mamba1_prefill(h, lp, config, state, layer, valid)
        if publish:
            published["m"] = m
        return out, (ck, cv, state, *more)

    def window(h, lp, layer, carry):
        ck, cv, state, *more = carry
        out, state = _window_prefill(h, lp, config, state, layer, offset,
                                     valid)
        return out, (ck, cv, state, *more)

    def conv(h, lp, layer, carry):
        ck, cv, state, *more = carry
        out, state = _conv_prefill(h, lp, config, state, layer, valid)
        return out, (ck, cv, state, *more)

    def selected(h, lp, layer, carry):
        ck, cv, state, ci, kept = carry
        out, ck, cv, ci, mask = _sel_attn_prefill(h, lp, config, ck, cv, ci,
                                                  layer, offset)
        if kept is not None:
            if mask is None:
                mask = jnp.broadcast_to(
                    causal_mask(S, kept.shape[-1], offset)[0],
                    kept.shape[1:])
            kept = jax.lax.dynamic_update_index_in_dim(
                kept, jnp.pad(mask, ((0, 0), (0, 0),
                                     (0, kept.shape[-1] - mask.shape[-1]))),
                layer, 0)
        return out, (ck, cv, state, ci, kept)

    def attn(h, lp, layer, carry):
        ck, cv, state, *more = carry
        if not config.attn_diff:
            out, ck, cv = _attn_prefill(h, lp, config, ck, cv, layer, offset)
            return out, (ck, cv, state, *more)
        q, k, v = _diff_qkv(h, lp, config)
        zero = jnp.zeros((), jnp.int32)
        at = (jnp.asarray(layer, jnp.int32), zero,
              jnp.asarray(offset, jnp.int32), zero, zero)
        ck = jax.lax.dynamic_update_slice(
            ck, _rows(k, config)[None].astype(ck.dtype), at)
        cv = jax.lax.dynamic_update_slice(
            cv, _rows(v, config)[None].astype(cv.dtype), at)
        published["kv_layer"] = layer
        return _diff_attn_prefill(q, lp, config, ck, cv, layer,
                                  offset), (ck, cv, state, *more)

    def cross(h, lp, _, carry):
        ck, cv, *_ = carry
        return _diff_attn_prefill(_diff_q(h, lp, config), lp, config, ck, cv,
                                  published["kv_layer"], offset), carry

    ops = {"M": mamba, "1": mamba1, "w": window, "*": attn, "x": cross,
           "c": conv, "s": selected,
           "Y": functools.partial(mamba1, publish=True),
           "g": lambda h, lp, _, carry: (_gmu(h, lp, config,
                                              published["m"]), carry)}
    carry = (cache.k, cache.v, cache.state)
    if config.is_indexed:
        # ``chosen``: every ``s`` layer's selection too, as a mask over
        # the carry's width ([page layers, B, S, width] bool).
        carry += (cache.idx, jnp.zeros(
            (config.cache_layers, B, S, cache.k.shape[2]), bool)
            if chosen else None)
    h, (ck, cv, state, *more), stats = _run_stack(
        params, config, h, ops, valid, None, carry, chosen)
    cache = KVCache(ck, cv, cache.lengths, state, more[0] if more else None)
    if chosen and more:
        stats = (*stats, more[1])
    if hidden:
        return _norm(h, params, config, "final_norm"), cache, stats
    return _logits(params, config, h, last_idx), cache, stats


def forward_counted(params: dict, config: ModelConfig, tokens: jax.Array,
                    positions: jax.Array, cache: KVCache, mask,
                    valid: jax.Array, mesh: Optional[Mesh] = None,
                    rules: LogicalRules = DEFAULT_RULES,
                    last_idx: Optional[jax.Array] = None, **_):
    """The other families' ``forward_counted`` for the one use the
    scheduler has (pangu.forward_counted): tokens at the LAST S slots of
    the carry behind a cached prefix, whose K and V are in the carry's
    first slots and whose state snapshot is ``cache.state``."""
    _refuse_mesh(mesh)
    offset = cache.k.shape[2] - tokens.shape[1]
    return _forward(params, config, tokens, cache, offset, valid, last_idx)


def forward(params: dict, config: ModelConfig, tokens: jax.Array,
            positions: jax.Array, cache: KVCache, mask,
            mesh: Optional[Mesh] = None,
            rules: LogicalRules = DEFAULT_RULES,
            last_idx: Optional[jax.Array] = None, **_):
    return forward_counted(params, config, tokens, positions, cache, mask,
                           None, mesh, rules, last_idx)[:2]


def _valid_from(prompt_lens: jax.Array, S: int) -> jax.Array:
    return jnp.arange(S)[None, :] < prompt_lens[:, None]


def prefill_counted(params: dict, config: ModelConfig, tokens: jax.Array,
                    prompt_lens: jax.Array, cache: KVCache,
                    valid: Optional[jax.Array],
                    mesh: Optional[Mesh] = None,
                    rules: LogicalRules = DEFAULT_RULES,
                    last_only: bool = False, **_):
    """llama.prefill's contract (right-padded prompts from position 0),
    and third the counts over ``valid``. Positions at or past a row's
    ``prompt_lens`` never move its state, whatever ``valid`` says."""
    _refuse_mesh(mesh)
    real = _valid_from(prompt_lens, tokens.shape[1])
    logits, cache, stats = _forward(
        params, config, tokens, cache, 0,
        real if valid is None else real & valid,
        prompt_lens - 1 if last_only else None)
    return (logits, cache._replace(lengths=prompt_lens.astype(jnp.int32)),
            stats)


def prefill(params: dict, config: ModelConfig, tokens: jax.Array,
            prompt_lens: jax.Array, cache: KVCache,
            mesh: Optional[Mesh] = None,
            rules: LogicalRules = DEFAULT_RULES,
            last_only: bool = False, **_):
    return prefill_counted(params, config, tokens, prompt_lens, cache, None,
                           mesh, rules, last_only)[:2]


def prefill_chunk_counted(params: dict, config: ModelConfig,
                          tokens: jax.Array, cache: KVCache, offset: int,
                          valid: Optional[jax.Array],
                          mesh: Optional[Mesh] = None,
                          rules: LogicalRules = DEFAULT_RULES,
                          last_idx: Optional[jax.Array] = None,
                          chosen: bool = False, **_):
    """llama.prefill_chunk's contract (C tokens a row at positions
    offset..offset+C, resuming from ``cache``; lengths untouched): the
    recurrent layers resume from ``cache.state`` and hand the state at
    the chunk's end (at each row's last ``valid`` position) back in
    it. ``chosen``: :func:`_run_stack`'s. A chunk with no ``valid``
    position moves no state and writes what no real query reads; the
    scheduler's chunk program does not run it at all (serve/scheduler.py
    ``_make_prefill_chunk_program._fwd``)."""
    _refuse_mesh(mesh)
    return _forward(params, config, tokens, cache, int(offset), valid,
                    last_idx, chosen=chosen)


def prefill_chunk(params: dict, config: ModelConfig, tokens: jax.Array,
                  cache: KVCache, offset: int,
                  mesh: Optional[Mesh] = None,
                  rules: LogicalRules = DEFAULT_RULES,
                  last_idx: Optional[jax.Array] = None, **_):
    return prefill_chunk_counted(params, config, tokens, cache, offset,
                                 None, mesh, rules, last_idx)[:2]


def embed_pooled(params: dict, config: ModelConfig, tokens: jax.Array,
                 lens: jax.Array, mesh: Optional[Mesh] = None,
                 rules: LogicalRules = DEFAULT_RULES) -> jax.Array:
    """llama.embed_pooled over this family's trunk."""
    B, S = tokens.shape
    cache = KVCache.create(config, B, S, dtype=params["embed"].dtype)
    valid = _valid_from(lens, S)
    h, _, _ = _forward(params, config, tokens, cache, 0, valid, None,
                       hidden=True)
    h = h.astype(jnp.float32)
    w = valid.astype(jnp.float32)
    pooled = (h * w[:, :, None]).sum(axis=1) / jnp.maximum(
        lens[:, None].astype(jnp.float32), 1.0)
    norm = jnp.linalg.norm(pooled, axis=-1, keepdims=True)
    return pooled / jnp.maximum(norm, 1e-9)


# -- decode -------------------------------------------------------------------

def decode_step_paged_touched(params: dict, config: ModelConfig,
                              tokens: jax.Array, cache,
                              mesh: Optional[Mesh] = None,
                              rules: LogicalRules = DEFAULT_RULES,
                              active: Optional[jax.Array] = None,
                              *, pages: int, chosen: bool = False):
    """One autoregressive step over both pools (llama.decode_step_paged's
    contract: tokens [B,1]; parked rows hold position, write their K and
    V to the garbage page and keep their state bit for bit). Returns
    (logits [B,1,V], cache with lengths advanced where active, counts
    int32 [4] over the live rows; with ``chosen``, :func:`_run_stack`'s
    pair and, third, the positions every ``s`` layer read: int32 [page
    layers, B, index_topk], -1 where a row has fewer)."""
    from ..ops.paged_kv import write_decode_burst
    _refuse_mesh(mesh)
    B = tokens.shape[0]
    h = _embed(params, config, tokens)
    live = jnp.ones((B,), bool) if active is None else active
    # ``*`` layers inside a scan over periods (_segments); an ``s``
    # layer is scanned with its neighbour (_plan).
    scanned = config.is_indexed or any(
        n != 1 for _, n in _segments(config.hybrid_pattern))

    published: dict = {}

    def mamba(h, lp, layer, carry):
        pool, kv = carry
        out, pool = _mamba_decode(h, lp, config, pool, layer, live)
        return out, (pool, kv)

    def mamba1(h, lp, layer, carry, publish=False):
        pool, kv = carry
        out, m, pool = _mamba1_decode(h, lp, config, pool, layer, live)
        if publish:
            published["m"] = m
        return out, (pool, kv)

    def window(h, lp, layer, carry):
        pool, kv = carry
        out, pool = _window_decode(h, lp, config, pool, layer, live,
                                   cache.lengths)
        return out, (pool, kv)

    def conv(h, lp, layer, carry):
        pool, kv = carry
        out, pool = _conv_decode(h, lp, config, pool, layer, live)
        return out, (pool, kv)

    def attn(h, lp, layer, carry):
        pool, kv = carry
        if not config.attn_diff:
            out, k, v = _attn_decode(h, lp, config, cache, layer, pages)
            if scanned:     # a scan's carry cannot grow: one slot a layer
                kv = tuple(jax.lax.dynamic_update_index_in_dim(
                    a, x.astype(a.dtype), layer, 0)
                    for a, x in zip(kv, (k, v))) + kv[2:]
                return out, (pool, kv)
            return out, (pool, kv + ((k, v),))
        q, k, v = _diff_qkv(h, lp, config)
        k, v = _rows(k[:, 0], config), _rows(v[:, 0], config)
        published["keys"] = _pool_keys(cache, layer, pages, k, v)
        return (_diff_out(q, published["keys"], lp, config),
                (pool, kv + ((k, v),)))

    def cross(h, lp, _, carry):
        return _diff_out(_diff_q(h, lp, config), published["keys"], lp,
                         config), carry

    def selected(h, lp, layer, carry):
        pool, kv = carry
        out, *new = _sel_attn_decode(h, lp, config, cache, layer, pages,
                                     chosen)
        return out, (pool, tuple(
            a if a is None else jax.lax.dynamic_update_index_in_dim(
                a, x.astype(a.dtype), layer, 0) for a, x in zip(kv, new)))

    ops = {"M": mamba, "1": mamba1, "w": window, "*": attn, "x": cross,
           "c": conv, "s": selected,
           "Y": functools.partial(mamba1, publish=True),
           "g": lambda h, lp, _, carry: (_gmu(h, lp, config,
                                              published["m"]), carry)}
    kv = ()
    if scanned:
        kv = (jnp.zeros((config.cache_layers, B, config.cache_kv_heads,
                         config.cache_k_dim), h.dtype),) * 2
    if config.is_indexed:
        # Behind K and V: the step's index keys, and (``chosen``) the
        # positions every ``s`` layer read.
        kv += (jnp.zeros((config.cache_layers, B, 1, config.cache_idx_dim),
                         h.dtype),
               jnp.full((config.cache_layers, B, config.index_topk), -1,
                        jnp.int32) if chosen else None)
    h, (pool, kv), stats = _run_stack(params, config, h, ops, None, live,
                                      (cache.state, kv), chosen)
    k_all, v_all, *more = kv if scanned else (
        jnp.stack([k for k, _ in kv]), jnp.stack([v for _, v in kv]))
    cache = write_decode_burst(cache._replace(state=pool), k_all, v_all,
                               live.astype(jnp.int32), *more[:1])
    if chosen and more:
        stats = (*stats, more[1])
    return _logits(params, config, h, None), cache, stats


def decode_step_paged(params: dict, config: ModelConfig, tokens: jax.Array,
                      cache, mesh: Optional[Mesh] = None,
                      rules: LogicalRules = DEFAULT_RULES,
                      active: Optional[jax.Array] = None, *, pages: int):
    return decode_step_paged_touched(params, config, tokens, cache, mesh,
                                     rules, active, pages=pages)[:2]


def decode_fused_touched(params: dict, config: ModelConfig,
                         tokens: jax.Array, cache,
                         mesh: Optional[Mesh] = None,
                         rules: LogicalRules = DEFAULT_RULES,
                         active: Optional[jax.Array] = None, *,
                         num_steps: int, sample_fn, sample_state, stop_ids,
                         kv_window: Optional[int] = None,
                         pages: Optional[int] = None):
    """llama.decode_fused_aux over this family's paged step, the counts
    summed over the steps."""
    from .llama import decode_fused_aux
    if pages is None:
        raise ValueError("the hybrid family decodes from the paged pool "
                         "only")

    def step_fn(params, config, tokens, cache, mesh, rules, aux, *, active,
                pages):
        logits, cache, st = decode_step_paged_touched(
            params, config, tokens, cache, mesh, rules, active, pages=pages)
        return logits, cache, aux + st

    return decode_fused_aux(params, config, tokens, cache, step_fn,
                            no_stats(), mesh, rules, active,
                            num_steps=num_steps, sample_fn=sample_fn,
                            sample_state=sample_state, stop_ids=stop_ids,
                            pages=pages)


def decode_fused(params: dict, config: ModelConfig, tokens: jax.Array,
                 cache, mesh: Optional[Mesh] = None,
                 rules: LogicalRules = DEFAULT_RULES,
                 active: Optional[jax.Array] = None, **kw):
    return decode_fused_touched(params, config, tokens, cache, mesh, rules,
                                active, **kw)[:-1]
