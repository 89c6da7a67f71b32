"""The SambaY family's architecture file (Phi-4-mini-flash-reasoning,
``model_type: phi4flash``, arXiv:2507.06607): a decoder whose lower half
alternates Mamba-1 and window attention, one full attention layer whose K
and V the whole upper half reads, and an upper half that alternates gated
memory units and cross attention. The contract is in
benchmark/manifest.py's docstring.

**The layers, as :func:`forward` computes them** (float32,
``jax.default_matmul_precision("highest")``; ``h`` [T, H]; ``L`` published
layers, ``M = L / 2``). Every layer ``l`` is two residual steps behind
biased LayerNorms (eps ``layer_norm_eps``): ``h <- h + mixer_l(LN1(h))``,
then ``h <- h + (silu(g) * u) W_2`` with ``[g | u] = LN2(h) W_1``. Logits
are ``LN_f(h) E^T``, ``E`` the embedding (tied). No positional encoding.
The mixer by ``l``:

- even, ``l <= M``: Mamba-1. ``[x | z] = u W_in``; ``x_t <- silu(sum_j w_j
  x_{t-3+j} + b_c)`` (zeros before the first); ``[dt_r | B | C] = x W_x``;
  ``dt = softplus(dt_r W_dt + b_dt)``; ``A = -exp(A_log)`` [d, N]; **the
  sequential recurrence, a position at a time**: ``S_t = exp(dt_t (outer)
  A) * S_{t-1} + (dt_t * x_t) (outer) B_t``, ``S`` [d, N] from zero; ``m_t
  = S_t C_t + D * x_t``; ``out = (m * silu(z)) W_out``. Layer ``M`` also
  publishes ``m``.
- odd, ``l < M``: window attention: ``[q | k | v] = u W_qkv + b``; a query
  reads its own position and the ``sliding_window - 1`` before it.
- ``l = M + 1``: full causal attention, the same form.
- even, ``l > M``: gated memory unit, ``out = (silu(u W_in) * m) W_out``
  with ``m`` layer ``M``'s at the same position.
- odd, ``l > M + 1``: cross attention: ``q = u W_q + b``; K and V are
  layer ``M + 1``'s, full causal.
- Every attention is differential: q as [pairs, 2, D], k and v as [kv
  pairs, 2, D], query pair ``p`` reads KV pair ``g = p // (pairs / kv
  pairs)``; ``a_s = softmax(q_{p,s} k_{g,s}^T / sqrt(D))``, ``V_g =
  [v_{g,0} | v_{g,1}]``; ``o_p = a_0 V_g - lam a_1 V_g``, ``lam =
  exp(lq1 . lk1) - exp(lq2 . lk2) + lam0``, ``lam0 = 0.8 - 0.6 exp(-0.3
  l)``; ``o_p <- RMSNorm_2D(o_p) * w_sub * (1 - lam0)``; biased output
  projection.

Departures from the released model (the configuration file's ``assumed``
says where each item comes from): none known; the released modelling file
is not at hand and where it differs it is right.

No kernels, no cache, no ring, no pages, no chunking; nothing is shared
with the program (``position_errors`` is benchmark/reference.py's).

**The check's two samples.** The harness hands 2 x (128 + 8) tokens, which
never leave one window. So :func:`system_logits` and :func:`forward` both
derive from them ONE long sequence (:func:`long_tokens`: 6 chunks and 11
sixteenths of a seventh, then 8 decode steps; 1,712 + 8 tokens at the
cell's 256-token chunk: three windows and a third, a padded last chunk),
which the system takes through its chunk ladder, the install into ring,
page pool and state pool, and decode steps; the reference as one sequence.
:func:`compare` holds both samples to the median limit, the long one's
first Mamba layer to the state limit, and the long one to the window's
edge (below).

Also here, JAX-free, what a step must move and a prompt must compute
(:func:`decode_step_bytes`, :func:`prefill_flops`), and the bytes of the
two new parts of the account (:func:`window_position_bytes`,
:func:`page_token_bytes`). No kernel was written for this family (PERF.md
section 6, PR 38), so there is no ``_cost`` function.

Readers run in the parent of a run, which never imports JAX: this module
imports it inside the functions only the child calls.
"""

from __future__ import annotations

import functools
import math
from typing import Callable, NamedTuple

# The three limits, each from two kinds of reading on a v5e at the
# published widths, int8 weights, int8 rings and page pool (a scale a
# position), float32 state (tools/check_reference_limit.py; my chip runs,
# PR 38; PERF.md section 6 has every number). The sound program on sample
# seeds 53, 1, 2, 3, 4, 5; the same system logits against the reference
# changed into each wrong model of :func:`wrong_models`, seed 53.
#
# TOL_MEDIAN, on the median position error of the logits
# (reference.position_errors), of the harness's sample and of the long one
# alike. Sound: 2.69-2.71% and 2.50% (maxima 3.1-3.3% and 2.9-3.1%: a dense
# model, nothing flips, so the tail sits on the median; the long sample's
# decode positions read an int8 ring and pool and come out no worse). The
# limit is one and a half times the largest, reference.py's rule. The
# wrong models read, on the worse of the two samples: the cross layers on
# the last window layer's K and V 20.7%, lam0 of the next layer 36.1%, m
# after the gate 33.7%, a window of none at all 49.7% (long sample only;
# the harness's 136 tokens never leave a window), every matrix at int4
# 78.8%, lam = 0 80.0%, no sub-norm 89.5%, RMSNorm for LayerNorm 88.2%,
# rotary applied 86.7%, no (1 - lam0) 93.6%, own value head 108%, m
# without D x 110%: 5 to 27 times the limit.
#
# TOL_STATE, on the first Mamba layer's final state of the long sample
# (after the chunk ladder and the decode steps, read back from the state
# pool) against the reference's, over the quarter of its numbers that
# forget slowest, relative, in the Frobenius norm: the limit that fails a
# state kept in bfloat16, which the logits cannot see (2.70% and 2.58%
# against the sound 2.69% and 2.50%). Sound 0.19-0.22%; the reference with
# a bfloat16 state against the same system 5.17%. Between the two, 4.5
# times the largest sound reading and a fifth of the wrong one.
#
# TOL_EDGE, on where the system stands between the reference and the
# reference with a window one position narrower or wider, on the long
# sample: the projection of (system - reference) on (neighbour -
# reference), as a share of the latter's length; 0 for a system that is
# the reference, 1 for one that is the neighbour. One key of 512 moves a
# window layer's output by a fraction of a percent, far inside the
# rounding the median allows (a window of 511 reads 3.6% on the long
# sample's median and 2.69% on the harness's), but the rounding is not
# ALONG that direction: over 200,064 logits x 437 positions its
# projection averages out. Sound 0.0001-0.0024; a window of 511 0.9992.
# Half way is the limit.
TOL_MEDIAN = 0.041
TOL_STATE = 0.01
TOL_EDGE = 0.5

LONG_DECODE = 8
LONG_STRIDE = 4         # prefill positions of the long sample compared
ROPE_THETA = 10000.0    # of the wrong model that applies rotary embedding


# -- the configuration --------------------------------------------------------

def layer_kinds(cfg: dict) -> list:
    """The mixer of each published layer: ``mamba``, ``window``, ``full``,
    ``gmu`` or ``cross``."""
    L = cfg["num_hidden_layers"]
    M = L // 2
    kinds = []
    for l in range(L):
        if l % 2 == 0:
            kinds.append("mamba" if l <= M else "gmu")
        elif l < M:
            kinds.append("window")
        else:
            kinds.append("full" if l == M + 1 else "cross")
    return kinds


def pattern(cfg: dict) -> str:
    """The program's walk (models/nemotron_h.py): a letter a mixer and
    ``-`` for the MLP behind it; ``Y`` the Mamba layer that publishes."""
    M = cfg["num_hidden_layers"] // 2
    letter = {"mamba": "1", "window": "w", "full": "*", "gmu": "g",
              "cross": "x"}
    return "".join(("Y" if l == M else letter[k]) + "-"
                   for l, k in enumerate(layer_kinds(cfg)))


def mamba_dims(cfg: dict) -> tuple:
    """(channels d, state N, convolution K, time-step rank R)."""
    a = cfg["assumed"]
    d = a["mamba_expand"] * cfg["hidden_size"]
    return (d, a["mamba_d_state"], a["mamba_d_conv"],
            a.get("mamba_dt_rank") or math.ceil(cfg["hidden_size"] / 16))


def head_dim(cfg: dict) -> int:
    return cfg["hidden_size"] // cfg["num_attention_heads"]


def model_config(cfg: dict) -> dict:
    """``ModelConfig``'s keywords from the family's published keys and
    the file's ``assumed`` Mamba widths."""
    d, N, K, R = mamba_dims(cfg)
    return dict(
        name=cfg["name"], vocab_size=cfg["vocab_size"],
        hidden_size=cfg["hidden_size"],
        intermediate_size=cfg["intermediate_size"],
        num_layers=cfg["num_hidden_layers"], hybrid_pattern=pattern(cfg),
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"], head_dim=head_dim(cfg),
        attn_rope=False, attn_bias=True, attn_diff=True, norm_kind="layer",
        sliding_window=cfg["sliding_window"], mamba1_inner=d,
        mamba1_state=N, mamba1_dt_rank=R, conv_kernel=K,
        max_seq_len=cfg["max_position_embeddings"],
        rms_norm_eps=cfg["layer_norm_eps"],
        tie_embeddings=bool(cfg["tie_word_embeddings"]),
        bos_token_id=cfg.get("bos_token_id", 1),
        eos_token_ids=())       # ignore_eos: see the configuration file


class Weights(NamedTuple):
    """What :func:`forward` is handed: float32, one layer at a time."""

    embed: object
    layer: Callable             # l -> {"mixer": {...}, "mlp": {...}}
    final_norm: object
    final_norm_b: object
    lm_head: object             # a float32 [H, V] array, or (int8, scale)


class SystemOut(NamedTuple):
    """What :func:`system_logits` hands :func:`compare`."""

    logits: object              # [B, P+D, V] float32, the harness's sample
    long_logits: object         # [1, n, V]: the long sample's compared ones
    state: object               # [d, N]: Mamba layer 0's after the long one


def engine_weights(sched) -> Weights:
    """The engine's own tree (models/nemotron_h.py: a stacked tree a
    kind), dequantised one layer at a time."""
    import jax
    import jax.numpy as jnp
    params, config = sched._params, sched.config
    f32 = jnp.float32
    walk = config.hybrid_pattern
    tree_of = {"1": "mamba1", "Y": "mamba1", "w": "attn", "*": "attn",
               "g": "gmu", "x": "cross"}

    def plain(leaf, i):
        if hasattr(leaf, "q"):
            return leaf.q[i].astype(f32) * leaf.s[i].astype(f32)
        return leaf[i].astype(f32)

    # The tree is an argument, never a closure (a closure bakes gigabytes
    # of constants into the program).
    @jax.jit
    def _layer(tree, i):
        return {name: plain(leaf, i) for name, leaf in tree.items()}

    def layer_weights(l):
        ch = walk[2 * l]
        tree = tree_of[ch]
        i = sum(tree_of[c] == tree for c in walk[: 2 * l: 2])
        mixer = _layer(params[tree], i)
        if tree == "mamba1":      # the program keeps [N, d]: channels minor
            mixer["A_log"] = mixer["A_log"].T
        return {"mixer": mixer, "mlp": _layer(params["mlp"], l)}

    head = params["lm_head"]
    return Weights(
        embed=params["embed"], layer=layer_weights,
        final_norm=params["final_norm"].astype(f32),
        final_norm_b=params["final_norm_b"].astype(f32),
        lm_head=(head.q, head.s) if hasattr(head, "q") else head.astype(f32))


# -- the mixers ---------------------------------------------------------------

def layer_norm(x, w, b, eps, wrong: str = ""):
    import jax
    import jax.numpy as jnp
    if wrong == "rms_for_layer_norm":
        return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True)
                                 + eps) * w
    x = x - jnp.mean(x, -1, keepdims=True)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w \
        + b


def mamba(u, w, cfg: dict, wrong: str = ""):
    """One sequence through a Mamba-1 mixer by the sequential recurrence.
    u [T, H], normed. Returns (out [T, H], m [T, d], final state [d, N],
    mean dt [d])."""
    import jax
    import jax.numpy as jnp
    T = u.shape[0]
    d, N, K, R = mamba_dims(cfg)
    xz = u @ w["w_in"]
    x, z = xz[:, :d], xz[:, d:]
    padded = jnp.concatenate([jnp.zeros((K - 1, d)), x])
    x = jax.nn.silu(sum(padded[j: j + T] * w["conv_w"][j] for j in range(K))
                    + w["conv_b"])
    dbc = x @ w["w_x"]
    dt = jax.nn.softplus(dbc[:, :R] @ w["w_dt"] + w["dt_bias"])     # [T, d]
    Bm, Cm = dbc[:, R: R + N], dbc[:, R + N:]
    A = -jnp.exp(w["A_log"])                                         # [d, N]

    def step(S, inp):
        x_t, dt_t, b_t, c_t = inp
        S = jnp.exp(dt_t[:, None] * A) * S \
            + (dt_t * x_t)[:, None] * b_t[None, :]
        if wrong == "bf16_state":
            # bfloat16's 8 exponent and 7 mantissa bits (a convert there
            # and back is elided under the TPU compiler's
            # allow_excess_precision).
            S = jax.lax.reduce_precision(S, exponent_bits=8,
                                         mantissa_bits=7)
        return S, S @ c_t

    S, y = jax.lax.scan(step, jnp.zeros((d, N), jnp.float32),
                        (x, dt, Bm, Cm))
    m = y if wrong == "m_without_d_skip" else y + w["D"] * x
    gated = m * jax.nn.silu(z)
    return (gated @ w["w_out"],
            gated if wrong == "m_after_gate" else m, S, jnp.mean(dt, axis=0))


def lam0_of(l: int, wrong: str = "") -> float:
    if wrong == "lam0_of_next_layer":
        l += 1
    return 0.8 - 0.6 * math.exp(-0.3 * l)


def diff_attention(q, k, v, w, lam0, cfg: dict, window, wrong: str = ""):
    """q [T, heads, D], k, v [T, kv heads, D] of one sequence: the
    differential form under a causal mask, ``window`` keys wide (0: the
    whole context); ``lam0`` the layer's, by :func:`lam0_of`. Returns
    [T, H] after the output projection."""
    import jax
    import jax.numpy as jnp
    from benchmark.reference import rope
    T, heads, D = q.shape
    kvh = k.shape[1]
    pos = jnp.arange(T)
    if wrong == "rotary_applied":
        q, k = rope(q, pos, ROPE_THETA), rope(k, pos, ROPE_THETA)
    pairs, kv_pairs = heads // 2, kvh // 2
    qp = q.reshape(T, pairs, 2, D)
    g = jnp.arange(pairs) // (pairs // kv_pairs)
    kp = k.reshape(T, kv_pairs, 2, D)[:, g]                 # [T, pairs, 2, D]
    vp = v.reshape(T, kv_pairs, 2, D)[:, g]
    s = jnp.einsum("qpsd,kpsd->psqk", qp, kp) / jnp.sqrt(jnp.float32(D))
    seen = (pos[:, None] >= pos[None, :]) & (
        (pos[:, None] - pos[None, :] < window) | (window == 0))
    a = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
    if wrong == "own_value_head":
        # Each score weighs its own value head only (twice, to keep the
        # width).
        o = jnp.einsum("psqk,kpsd->qpsd", a, vp)
        o = jnp.concatenate([o, o], axis=-1)                # [T, p, 2, 2D]
    else:
        o = jnp.einsum("psqk,kpe->qpse", a, vp.reshape(T, pairs, 2 * D))
    lam = (jnp.exp(jnp.sum(w["lq1"] * w["lk1"]))
           - jnp.exp(jnp.sum(w["lq2"] * w["lk2"])) + lam0)
    if wrong == "lam_zero":
        lam = 0.0
    o = o[:, :, 0] - lam * o[:, :, 1]                       # [T, pairs, 2D]
    if wrong != "no_sub_norm":
        o = o * jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True)
                              + cfg["layer_norm_eps"]) * w["sub_w"]
    if wrong != "no_lam0_factor":
        o = o * (1.0 - lam0)
    return o.reshape(T, pairs * 2 * D) @ w["wo"] + w["bo"]


def qkv(u, w, cfg: dict):
    T = u.shape[0]
    heads, kvh, D = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                     head_dim(cfg))
    x = u @ w["wqkv"] + w["bqkv"]
    return (x[:, : heads * D].reshape(T, heads, D),
            x[:, heads * D: (heads + kvh) * D].reshape(T, kvh, D),
            x[:, (heads + kvh) * D:].reshape(T, kvh, D))


_CFG_KEYS = ("hidden_size", "num_attention_heads", "num_key_value_heads",
             "layer_norm_eps", "num_hidden_layers")


@functools.cache
def _jitted():
    import jax

    def cfg_of(key):
        cfg = dict(key)
        cfg["assumed"] = dict(cfg.pop("_mamba"))
        return cfg

    def ln(h, w, cfg, wrong):
        return layer_norm(h, w["norm"], w["norm_b"], cfg["layer_norm_eps"],
                          wrong)

    # The layer's index and the window are arguments, not constants: one
    # compilation a kind of layer, a shape and a wrong model.
    static = ("wrong", "cfg_key")

    @functools.partial(jax.jit, static_argnames=static)
    def mamba_layer(h, w, *, wrong, cfg_key):
        cfg = cfg_of(cfg_key)
        with jax.default_matmul_precision("highest"):
            out, m, S, dt = jax.vmap(lambda x: mamba(
                ln(x, w, cfg, wrong), w, cfg, wrong))(h)
            return h + out, m, S, dt

    @functools.partial(jax.jit, static_argnames=static)
    def attn_layer(h, w, lam0, window, *, wrong, cfg_key):
        """Window or full attention. Returns (h, k, v)."""
        cfg = cfg_of(cfg_key)
        with jax.default_matmul_precision("highest"):
            def one(x):
                q, k, v = qkv(ln(x, w, cfg, wrong), w, cfg)
                return diff_attention(q, k, v, w, lam0, cfg, window,
                                      wrong), k, v
            out, k, v = jax.vmap(one)(h)
            return h + out, k, v

    @functools.partial(jax.jit, static_argnames=static)
    def cross_layer(h, k, v, w, lam0, *, wrong, cfg_key):
        cfg = cfg_of(cfg_key)
        heads, D = cfg["num_attention_heads"], head_dim(cfg)
        with jax.default_matmul_precision("highest"):
            def one(x, k, v):
                q = (ln(x, w, cfg, wrong) @ w["wq"] + w["bq"]).reshape(
                    -1, heads, D)
                return diff_attention(q, k, v, w, lam0, cfg, 0, wrong)
            return h + jax.vmap(one)(h, k, v)

    @functools.partial(jax.jit, static_argnames=static)
    def gmu_layer(h, m, w, *, wrong, cfg_key):
        cfg = cfg_of(cfg_key)
        with jax.default_matmul_precision("highest"):
            return h + (jax.nn.silu(ln(h, w, cfg, wrong) @ w["w_in"])
                        * m) @ w["w_out"]

    @functools.partial(jax.jit, static_argnames=static)
    def mlp(h, w, *, wrong, cfg_key):
        cfg = cfg_of(cfg_key)
        with jax.default_matmul_precision("highest"):
            gu = ln(h, w, cfg, wrong) @ w["w_gu"]
            F = gu.shape[-1] // 2
            return h + (jax.nn.silu(gu[..., :F]) * gu[..., F:]) \
                @ w["w_mlp_down"]

    @functools.partial(jax.jit, static_argnames=("eps", "wrong"))
    def head(h, norm, norm_b, lm_head, *, eps, wrong):
        """Logits of a block of positions; an int8 head is dequantised
        here, a block at a time."""
        import jax.numpy as jnp
        with jax.default_matmul_precision("highest"):
            if isinstance(lm_head, tuple):
                lm_head = lm_head[0].astype(jnp.float32) * lm_head[1]
            return layer_norm(h, norm, norm_b, eps, wrong) @ lm_head

    return mamba_layer, attn_layer, cross_layer, gmu_layer, mlp, head


def _q4(w):
    import jax.numpy as jnp
    scale = jnp.max(jnp.abs(w), axis=0, keepdims=True) / 7.0
    return jnp.round(w / jnp.where(scale > 0, scale, 1.0)) * scale


_MATS = {"w_in", "w_x", "w_dt", "w_out", "wqkv", "wq", "wo", "w_gu",
         "w_mlp_down"}


def _stack(cfg: dict, tokens, weights: Weights, window: int,
           positions=None) -> tuple:
    """Logits of ``tokens`` [B, T] at ``positions`` (all of them when
    None), with ``window`` keys in the window layers, and layer 0's
    Mamba state and mean time step."""
    import jax
    import jax.numpy as jnp
    mamba_layer, attn_layer, cross_layer, gmu_layer, mlp, head = _jitted()
    wrong = cfg.get("_wrong", "")
    key = tuple((k, cfg[k]) for k in _CFG_KEYS) + (
        ("_mamba", tuple(sorted((k, v) for k, v in cfg["assumed"].items()
                                if k.startswith("mamba_")))),)
    def kw(*heeds):
        """A function is compiled for a wrong model only if it heeds it."""
        return dict(cfg_key=key, wrong=wrong if wrong in heeds
                    or wrong == "rms_for_layer_norm" else "")

    attn_kw = kw("rotary_applied", "lam_zero", "no_sub_norm",
                 "no_lam0_factor", "own_value_head")
    mamba_kw = kw("bf16_state", "m_after_gate", "m_without_d_skip")
    M = cfg["num_hidden_layers"] // 2
    with jax.default_matmul_precision("highest"):
        h = weights.embed[tokens].astype(jnp.float32)
    m = kv = window_kv = state0 = None
    for l, kind in enumerate(layer_kinds(cfg)):
        w = weights.layer(l)
        if wrong == "int4_weights":
            w = {part: {k: _q4(v) if k in _MATS else v
                        for k, v in tree.items()} for part, tree in w.items()}
        mixer = w["mixer"]
        if kind == "mamba":
            h, m_l, S, dt = mamba_layer(h, mixer, **mamba_kw)
            if l == 0:
                state0 = (S, dt)
            if l == M:
                m = m_l
        elif kind == "window":
            h, k, v = attn_layer(h, mixer, lam0_of(l, wrong), window,
                                 **attn_kw)
            window_kv = (k, v)          # the last window layer's, below M
        elif kind == "full":
            h, k, v = attn_layer(h, mixer, lam0_of(l, wrong), 0, **attn_kw)
            kv = window_kv if wrong == "cross_reads_last_window_layer" \
                else (k, v)
        elif kind == "gmu":
            h = gmu_layer(h, m, mixer, **kw())
        else:
            h = cross_layer(h, *kv, mixer, lam0_of(l, wrong), **attn_kw)
        h = mlp(h, w["mlp"], **kw())
    if positions is not None:
        h = h[:, positions]
    # The head a block of positions at a time: 200,064 x T float32 beside
    # a serving model.
    blocks = [head(h[:, i: i + 128], weights.final_norm,
                   weights.final_norm_b, weights.lm_head,
                   eps=cfg["layer_norm_eps"], wrong=kw()["wrong"])
              for i in range(0, h.shape[1], 128)]
    return jnp.concatenate(blocks, axis=1), state0


# -- the long sample ----------------------------------------------------------

def long_shape(chunk: int) -> tuple:
    """(prefill positions, decode steps) of the long sample at a chunk of
    ``chunk``: six chunks and 11/16 of a seventh, which is padded."""
    return 6 * chunk + 11 * chunk // 16, LONG_DECODE


def long_tokens(tokens, vocab: int, chunk: int):
    """The long sample [1, P + D], drawn from a seed the harness's
    tokens give: the same for system and reference, another every
    ``--seed``."""
    import numpy as np
    seed = int(np.asarray(tokens).astype(np.int64).sum()) % (2 ** 31)
    return np.random.default_rng(seed).integers(
        0, vocab, size=(1, sum(long_shape(chunk)))).astype(np.int32)


def long_positions(chunk: int):
    """The long sample's compared positions: every LONG_STRIDE-th of the
    prefill, its last, and every decode step."""
    import numpy as np
    P, D = long_shape(chunk)
    return np.unique(np.concatenate([np.arange(0, P, LONG_STRIDE),
                                     np.arange(P - 1, P + D)]))


def check_chunk(cfg: dict) -> int:
    """The chunk the check's long sample is laid out for: the stack's."""
    return int(cfg.get("stack", {}).get("SERVE_PREFILL_CHUNK", 256))


def forward(cfg: dict, tokens, weights: Weights) -> tuple:
    """Logits [B, T, V] (float32) of ``tokens`` [B, T], every position,
    and the facts ``compare`` reads: the long sample's logits at its
    compared positions, the same with a window one key narrower and one
    wider, and the first Mamba layer's final state there with the
    indices of its slowest quarter. ``cfg["_wrong"]`` (absent in a run)
    names a deliberately wrong model."""
    import jax.numpy as jnp
    W = cfg["sliding_window"]
    wrong = cfg.get("_wrong", "")
    if wrong == "window_one_short":
        W -= 1
    elif wrong == "window_unbounded":
        W = 0
    logits, _ = _stack(cfg, tokens, weights, W)
    chunk = check_chunk(cfg)
    long = jnp.asarray(long_tokens(tokens, cfg["vocab_size"], chunk))
    at = jnp.asarray(long_positions(chunk))
    long_logits, (S, dt) = _stack(cfg, long, weights, W, at)
    facts = {"long_logits": long_logits, "state": S[0], "edges": {}}
    if W:
        for name, w in (("narrower", W - 1), ("wider", W + 1)):
            facts["edges"][name] = _stack(cfg, long, weights, w, at)[0]
    # The state numbers that forget slowest over this sequence (smallest
    # mean dt |A|): where a state kept in fewer bits drifts furthest.
    A = jnp.exp(weights.layer(0)["mixer"]["A_log"])             # [d, N]
    rate = (dt[0][:, None] * A).reshape(-1)
    facts["slow"] = jnp.argsort(rate)[: max(1, rate.size // 4)]
    return logits, facts


WRONG = ("rotary_applied", "lam_zero", "lam0_of_next_layer", "no_sub_norm",
         "no_lam0_factor", "own_value_head", "window_one_short",
         "window_unbounded", "m_after_gate", "m_without_d_skip",
         "cross_reads_last_window_layer", "rms_for_layer_norm",
         "bf16_state", "int4_weights")


def wrong_models(cfg: dict, weights: Weights) -> dict:
    """name -> (cfg, weights) of the wrong models a limit must fail:
    rotary embedding applied; ``lam`` = 0; ``lam0`` of layer ``l + 1``;
    the sub-norm, or the ``(1 - lam0)`` factor, left out; each score
    weighing its own value head only; a window of 511 and none at all;
    ``m`` taken after the ``z`` gate, or without ``D x``; the cross
    layers reading the last window layer's K and V; RMSNorm for
    LayerNorm; a bfloat16 recurrent state (the precision below the
    float32 the configuration states); every matrix rounded to int4 (the
    precision below the int8 the stack states)."""
    return {name: ({**cfg, "_wrong": name}, weights) for name in WRONG}


# -- the system ---------------------------------------------------------------

def system_logits(sched, tokens, n_prefill: int) -> SystemOut:
    """The system's logits through the programs the scheduler serves
    with. Both samples go the way an admission does: ``prefill_chunk``
    a chunk at a time over a dense carry (K and V of the full layer, the
    window layers' rings and the Mamba state in its ``state``), the last
    chunk padded and masked; K and V spliced into a paged pool of the
    scheduler's kind, state and rings into the state pool's rows; then
    decode steps over ring, pages and state. The harness's sample is one
    chunk of its ``n_prefill`` positions; the long one
    (:func:`long_tokens`) is seven of the scheduler's chunk."""
    import jax
    import jax.numpy as jnp
    from p2p_llm_chat_tpu.models.llama import KVCache
    from p2p_llm_chat_tpu.ops.paged_kv import (PagedKVCache,
                                               write_prefill_batch)
    from p2p_llm_chat_tpu.ops.state_pool import write_rows
    model, params, config = sched._model, sched._params, sched.config
    mesh, ps = sched.mesh, sched.page_size

    @functools.partial(jax.jit, static_argnames=("offset",))
    def chunk(params, toks, valid, carry, *, offset):
        logits, carry, _ = model.prefill_chunk_counted(
            params, config, toks, carry, offset, valid, mesh)
        return logits.astype(jnp.float32), carry

    @functools.partial(jax.jit, static_argnames=("per_row",))
    def splice(carry, lens, *, per_row):
        B = lens.shape[0]
        rows = jnp.arange(B, dtype=jnp.int32)
        cache = PagedKVCache.create(config, B, 1 + B * per_row, ps,
                                    max_pages_per_row=per_row,
                                    dtype=sched._dtype,
                                    quantized=sched.kv_quant, mesh=mesh)
        tables = 1 + jnp.arange(B * per_row,
                                dtype=jnp.int32).reshape(B, per_row)
        cache = write_prefill_batch(cache, carry.k, carry.v, rows, lens,
                                    tables)
        return cache._replace(state=write_rows(cache.state, carry.state,
                                               rows))

    @functools.partial(jax.jit, donate_argnums=(2,),
                       static_argnames=("pages",))
    def decode(params, tok, cache, *, pages):
        logits, cache = model.decode_step_paged(params, config, tok, cache,
                                                mesh, pages=pages)
        return logits.astype(jnp.float32), cache

    def drive(tokens, P: int, C: int, keep=None):
        """Logits of ``tokens`` [B, P + D] (at positions ``keep`` when
        given) and the pool after the last step."""
        B, T = tokens.shape
        width = -(-P // C) * C
        pages = 1
        while pages * ps < T + 1:
            pages *= 2
        carry = KVCache.create(config, B, width, dtype=sched._dtype)
        out = []
        for off in range(0, width, C):
            n = min(C, P - off)
            toks = jnp.pad(tokens[:, off: off + n], ((0, 0), (0, C - n)))
            valid = jnp.broadcast_to(jnp.arange(C)[None, :] < n, (B, C))
            logits, carry = chunk(params, toks, valid, carry, offset=off)
            at = range(off, off + n)
            if keep is not None:
                at = [p for p in at if p in keep]
            out.append(logits[:, jnp.asarray([p - off for p in at],
                                             jnp.int32)])
        cache = splice(carry, jnp.full((B,), P, jnp.int32), per_row=pages)
        for t in range(P, T):
            step, cache = decode(params, tokens[:, t: t + 1], cache,
                                 pages=pages)
            if keep is None or t in keep:
                out.append(step)
        return jnp.concatenate(out, axis=1), cache

    logits, _ = drive(tokens, n_prefill, n_prefill)
    C = sched.prefill_chunk
    long = jnp.asarray(long_tokens(tokens, config.vocab_size, C))
    long_logits, cache = drive(long, long_shape(C)[0], C,
                               keep=set(long_positions(C).tolist()))
    return SystemOut(logits=logits, long_logits=long_logits,
                     state=cache.state.ssm[0, 0].T)


def compare(system: SystemOut, reference_logits, facts: dict,
            cfg: dict) -> dict:
    """reference.compare's numbers on the harness's sample under this
    family's limit on the median; the long sample's median under the
    same (``long_median``); the first Mamba layer's final state after the
    long sample against the reference's over its slowest quarter,
    relative, in the Frobenius norm (``state_error``); and how far the
    system stands towards a window one key narrower or wider
    (``window_edge``, the larger of the two projections)."""
    import jax.numpy as jnp
    from benchmark import reference
    out = reference.compare(system.logits, reference_logits, routed=False)
    f32 = jnp.float32
    long_err = reference.position_errors(system.long_logits,
                                         facts["long_logits"]).reshape(-1)
    out["long_median"] = float(jnp.median(long_err))
    out["long_max"] = float(jnp.max(long_err))
    ref_state = facts["state"].astype(f32).reshape(-1)[facts["slow"]]
    sys_state = system.state.astype(f32).reshape(-1)[facts["slow"]]
    out["state_error"] = float(jnp.linalg.norm(sys_state - ref_state)
                               / jnp.linalg.norm(ref_state))
    off = (system.long_logits.astype(f32)
           - facts["long_logits"].astype(f32)).reshape(-1)
    out["window_edge"] = 0.0
    for other in facts["edges"].values():
        step = (other.astype(f32) - facts["long_logits"].astype(f32)
                ).reshape(-1)
        out["window_edge"] = max(out["window_edge"], float(
            jnp.dot(off, step) / jnp.maximum(jnp.dot(step, step), 1e-30)))
    out["ok"] = bool(
        jnp.isfinite(long_err).all() and out["median"] <= TOL_MEDIAN
        and out["long_median"] <= TOL_MEDIAN
        and out["state_error"] <= TOL_STATE
        and out["window_edge"] <= TOL_EDGE)
    out["tolerance"] = {"median": TOL_MEDIAN, "max": None,
                        "long_median": TOL_MEDIAN,
                        "state_error": TOL_STATE, "window_edge": TOL_EDGE}
    return out


# -- what a step must move and a prompt must compute (JAX-free) ---------------

def _q8(n_in: int, n_out: int) -> float:
    """Bytes of an int8 [n_in, n_out] weight with a float32 scale a
    column (benchmark/roofline.py's count)."""
    return n_in * n_out + 4 * n_out


def layer_counts(cfg: dict) -> dict:
    kinds = layer_kinds(cfg)
    return {k: kinds.count(k) for k in ("mamba", "window", "full", "gmu",
                                        "cross")}


def layer_shapes(cfg: dict) -> dict:
    """[in, out] of every matrix of each kind of layer, as published."""
    H, F = cfg["hidden_size"], cfg["intermediate_size"]
    d, N, _, R = mamba_dims(cfg)
    Q = cfg["num_attention_heads"] * head_dim(cfg)
    KV = cfg["num_key_value_heads"] * head_dim(cfg)
    attn = [(H, Q + 2 * KV), (Q, H)]
    return {"mlp": [(H, 2 * F), (F, H)],
            "mamba": [(H, 2 * d), (d, R + 2 * N), (R, d), (d, H)],
            "window": attn, "full": attn,
            "gmu": [(H, d), (d, H)], "cross": [(H, Q), (Q, H)]}


def state_row_bytes(cfg: dict) -> float:
    """One row of ONE Mamba layer in the state pool: the float32 state
    and the bf16 convolution window."""
    d, N, K, _ = mamba_dims(cfg)
    return 4.0 * d * N + 2.0 * (K - 1) * d


def page_token_bytes(cfg: dict) -> float:
    """One position of the full layer in the int8 page pool, or of a
    window layer in its int8 ring: K and V of every KV head side by side
    as one row each, and a float32 scale a row."""
    return 2.0 * (cfg["num_key_value_heads"] * head_dim(cfg) + 4)


window_position_bytes = page_token_bytes


def decode_step_bytes(cfg: dict, rows: float, context: float) -> float:
    """Bytes one decode step has to move: every matrix once (int8, the
    head an int8 copy of the tied embedding); the rows' embeddings in
    bf16; each live row's Mamba state and window in every Mamba layer,
    read AND written; each row's last ``min(context, window)`` positions
    in every window layer's ring; and each row's context in the full
    layer's pages once for the full layer and once for each cross layer
    (eight readers of one pool)."""
    n = layer_counts(cfg)
    shapes = layer_shapes(cfg)
    H = cfg["hidden_size"]
    weights = sum(cfg["num_hidden_layers"] * _q8(*s) for s in shapes["mlp"])
    weights += sum(n[k] * _q8(*s) for k in n for s in shapes[k])
    ring = (n["window"] * rows * min(context, cfg["sliding_window"])
            * window_position_bytes(cfg))
    pages = (n["full"] + n["cross"]) * rows * context * page_token_bytes(cfg)
    state = n["mamba"] * 2.0 * rows * state_row_bytes(cfg)
    return (weights + _q8(H, cfg["vocab_size"]) + rows * 2 * H
            + ring + pages + state)


def prefill_flops(cfg: dict, tokens: float, context_pairs: float) -> float:
    """FLOPs the prompt positions require: two a parameter a token for
    every matrix; the recurrence (a state update and a read of d x N
    each, two operations a number) and the convolution in every Mamba
    layer; and the attention's pairs, the causal ones in the full and the
    cross layers (which in this program run for every position of a
    prompt), and in the window layers no more than ``sliding_window`` a
    token (approximated by the causal pairs capped at window x tokens).
    The head runs for one position a request and is left out."""
    n = layer_counts(cfg)
    shapes = layer_shapes(cfg)
    d, N, K, _ = mamba_dims(cfg)
    per_token = 2.0 * cfg["num_hidden_layers"] * sum(
        a * b for a, b in shapes["mlp"])
    per_token += 2.0 * sum(n[k] * a * b for k in n for a, b in shapes[k])
    per_token += n["mamba"] * (4.0 * d * N + 2.0 * K * d)
    pair = 4.0 * cfg["num_attention_heads"] * head_dim(cfg)
    window_pairs = min(context_pairs, cfg["sliding_window"] * tokens)
    return (tokens * per_token
            + (n["full"] + n["cross"]) * context_pairs * pair
            + n["window"] * window_pairs * pair)
