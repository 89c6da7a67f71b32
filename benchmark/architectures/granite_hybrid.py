"""The Granite 4.0-H family's architecture file (granite-4.0-h-micro,
``model_type: granitemoehybrid`` with ``num_local_experts`` 0): a decoder
whose every layer is a mixer AND a dense SwiGLU MLP, the mixer Mamba-2 in
nine layers of ten and attention without positional encoding in the
tenth, with four scalars of the family's muP parameterisation. The
contract is in benchmark/manifest.py's docstring.

**The layers, as :func:`forward` computes them** (float32,
``jax.default_matmul_precision("highest")``; ``h`` [T, H]; every norm an
RMSNorm at ``rms_norm_eps`` with a learned weight; no bias anywhere but
the convolution's):

- ``h0 = embedding_multiplier x E[token]``.
- Layer ``l``: ``h <- h + residual_multiplier x mixer_l(RMSNorm(h))``,
  then ``h <- h + residual_multiplier x MLP(RMSNorm(h))`` with ``MLP(u) =
  (silu(u Wg) * (u Wu)) Wd`` at ``shared_intermediate_size``.
- ``layer_types[l] == "mamba"`` (``d = mamba_n_heads x mamba_d_head``,
  ``G = mamba_n_groups``, ``N = mamba_d_state``): ``[z | xBC | dt] = u
  W_in`` (widths d | d + 2GN | heads). ``xBC_t <- silu(sum_j w_j
  xBC_{t-3+j} + b)``, j over the ``mamba_d_conv`` = 4 last positions
  (zeros before the first). Split ``x`` [heads, head_dim], ``B``, ``C``
  [G, N]; head h reads group ``h // (heads / G)``. ``dt <- softplus(dt +
  dt_bias)``, ``A = -exp(A_log)``. **The sequential recurrence, one
  position at a time**: ``S_t = exp(dt_t A) S_{t-1} + dt_t x_t (outer)
  B_t``; ``y_t = S_t C_t + D x_t``; ``S`` [heads, head_dim, N] float32
  from zero. (The program prefills in the chunked form at blocks of
  ``mamba_chunk_size`` and decodes a step at a time from a state pool; it
  has to agree with this.) Then the gated norm, gate first: ``y <-
  RMSNorm(y silu(z); G groups) w``; ``out = y W_out``.
- ``"attention"``: q ``num_attention_heads`` x D, k and v
  ``num_key_value_heads`` x D (``D = hidden_size / num_attention_heads``),
  causal, ``softmax(q k^T x attention_multiplier) v``, NO positional
  encoding (``position_embedding_type: "nope"``), ``out = o W_o``.
- ``logits = RMSNorm_f(h) E^T / logits_scaling`` (the head is the
  embedding transposed; the program serves an int8 copy of it and the
  reference reads that copy).

No kernels, no cache, no batching, no chunked scan; nothing is shared
with the program (``rms_norm`` and ``position_errors`` are
benchmark/reference.py's).

**The check's two samples.** The harness hands 2 x (128 + 8) tokens: two
64-token chunks (the second starts from the carried state and window).
:func:`system_logits` and :func:`forward` both derive from them ONE long
sequence besides (:func:`long_tokens`: 4 chunks and 11 sixteenths of a
fifth of the stack's chunk, then 8 decode steps: 1,200 + 8 tokens at
256), which the system admits as a request that hits a prefix entry (the
first chunk kept as an entry's K, V and state snapshot, a carry seeded
from it, the chunk ladder behind it with a padded last chunk). All three
rows are installed in the scheduler's own pool, at its ``num_slots``
rows (first, middle and last slot, the others parked), and decode
together under the live mask through the window's programs: the fused
scan at ``decode_fuse_max`` steps and at half of it, then plain steps,
at a window of 2,048 tokens, where the paired flash-append kernel reads
the pages on the chip. The reference takes each sample as one sequence.
:func:`compare` holds both samples to the limits on the median and the
maximum, the long one's first Mamba layer to the state's, and the long
one to the softmax scale's edge (below).

Also here, JAX-free, what a step must move and a prompt must compute
(:func:`decode_step_bytes`, :func:`prefill_flops`), held to hand
arithmetic in tests/benchmark/test_benchmark_granite.py. No kernel was
written for this family (PERF.md section 6, PR 55), so there is no
``_cost`` function.

Readers run in the parent of a run, which never imports JAX: this module
imports it inside the functions only the child calls.
"""

from __future__ import annotations

import functools
from typing import Callable, NamedTuple

# The four limits, each from two kinds of reading on a v5e at the
# published widths, 40 layers, int8 weights, the int8 page pool and the
# float32 state pool, through :func:`system_logits` as it stands (the
# scheduler's pool at 64 rows, the fused scans, the prefix snapshot):
# tools/check_reference_limit.py --seeds 53,1,2,3 --wrong-seeds 53,1,2,3
# --rest-wrong bf16_state; my chip run, PR 55, call R1; PERF.md section
# 6 has every number, and those of the check's first form (call 1),
# which the logits' readings repeat to the third digit. The sound
# program on four sample seeds; the same system logits against the
# reference changed into each wrong model of :func:`wrong_models`, seed
# 53 (the sample a run checks), and the bfloat16 state on all four.
#
# TOL_MEDIAN and TOL_MAX, on the median and the largest position error of
# the logits (reference.position_errors), of the harness's sample and of
# the long one alike. A dense model: nothing routes, so nothing flips and
# the tail sits on the median. Sound: median 1.486-1.491% and 1.095-1.099%
# (the long sample), maximum 1.60-1.63% and 1.16-1.19%: forty layers of
# bf16 rounding, damped by a residual factor of 0.22 onto a stream the
# embedding opened at x 12. The wrong models, harness's sample / long
# one, median: every matrix at int4 3.90 / 3.71%, the norm before the
# gate 4.54 / 4.42%, no D skip 6.04 / 5.59%, the gated norm a head at a
# time 6.46 / 6.37%, no convolution bias 6.88 / 6.97%, a residual factor
# of 1 43.3 / 43.1%, undivided logits 87.5 / 87.5%, an unscaled embedding
# 91.8 / 91.3%; maximum: int4 4.17 / 3.94%, the others above it. The
# limits are 1.6 and 1.7 times the largest sound reading and 65% and 71%
# of the smallest wrong one.
#
# TOL_STATE, on the first Mamba layer's final state of the long sample
# (after the chunk ladder and the decode steps, read back from the state
# pool) against the reference's, over the quarter of the heads that
# forget slowest, relative, in the Frobenius norm: the limit that fails a
# state kept in bfloat16 (the precision below the float32 the
# configuration states), which the logits cannot see (1.490 / 1.099%
# against the sound 1.490 / 1.097%). Sound 0.372-0.431% over the four
# seeds (0.431% on the run's own; the check's first form, a pool of one
# row, read 0.385-0.655%); the reference with a bfloat16 state against
# the same system 1.840, 1.652, 1.639 and 1.824% on the four. Between
# the two: 2.6 times the largest sound reading and 67% of the smallest
# wrong one.
#
# TOL_EDGE, on where the system stands between the reference and the
# reference with the softmax scale ``1 / sqrt(head_dim)``, on the long
# sample: the projection of (system - reference) on (neighbour -
# reference), as a share of the latter's length; 0 for a system that is
# the reference, 1 for one that is the neighbour. Four attention layers
# of eighty residual steps, each x 0.22 onto a stream the embedding
# opened at x 12, move the logits by less than the rounding the median
# allows (a scale of 1/8 reads 1.61 / 1.13% at the median and 2.06 /
# 1.63% at the maximum: inside every other limit), but the rounding is
# not ALONG that direction: over 100,352 logits x 160 positions its
# projection averages out (benchmark/architectures/phi4flash.py holds
# its window's width the same way). Sound -0.040 to -0.017; a scale of
# 1/8 1.020. Half way is the limit.
TOL_MEDIAN = 0.024
TOL_MAX = 0.028
TOL_STATE = 0.011
TOL_EDGE = 0.5

# Positions the system prefills a chunk at a time on the harness's
# sample, so that the second chunk starts from the carried state.
REF_CHUNK = 64
LONG_DECODE = 8
LONG_STRIDE = 8         # prefill positions of the long sample compared


# -- the configuration --------------------------------------------------------

def pattern(cfg: dict) -> str:
    """The program's walk (models/nemotron_h.py): a letter a mixer and
    ``-`` for the MLP behind it."""
    letter = {"mamba": "M", "attention": "*"}
    kinds = cfg["layer_types"]
    if len(kinds) != cfg["num_hidden_layers"]:
        raise ValueError(f"layer_types names {len(kinds)} layers for "
                         f"{cfg['num_hidden_layers']}")
    return "".join(letter[k] + "-" for k in kinds)


def head_dim(cfg: dict) -> int:
    return cfg["hidden_size"] // cfg["num_attention_heads"]


def model_config(cfg: dict) -> dict:
    """``ModelConfig``'s keywords from the family's published keys."""
    if cfg["num_local_experts"] or cfg["position_embedding_type"] != "nope":
        raise ValueError("this file describes the family's dense members "
                         "without positional encoding")
    return dict(
        name=cfg["name"], vocab_size=cfg["vocab_size"],
        hidden_size=cfg["hidden_size"],
        intermediate_size=cfg["shared_intermediate_size"],
        num_layers=cfg["num_hidden_layers"], hybrid_pattern=pattern(cfg),
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"], head_dim=head_dim(cfg),
        attn_rope=False,
        mamba_num_heads=cfg["mamba_n_heads"],
        mamba_head_dim=cfg["mamba_d_head"],
        ssm_state_size=cfg["mamba_d_state"],
        ssm_groups=cfg["mamba_n_groups"], conv_kernel=cfg["mamba_d_conv"],
        ssm_chunk=cfg["mamba_chunk_size"],
        max_seq_len=cfg["max_position_embeddings"],
        rope_theta=float(cfg["rope_theta"]), rope_scaling=None,
        rms_norm_eps=cfg["rms_norm_eps"],
        tie_embeddings=bool(cfg["tie_word_embeddings"]),
        embedding_multiplier=float(cfg["embedding_multiplier"]),
        residual_multiplier=float(cfg["residual_multiplier"]),
        attention_multiplier=float(cfg["attention_multiplier"]),
        logits_scaling=float(cfg["logits_scaling"]),
        bos_token_id=cfg.get("bos_token_id", 1),
        eos_token_ids=())       # ignore_eos: see the configuration file


class Weights(NamedTuple):
    """What :func:`forward` is handed: float32, one layer at a time."""

    embed: object
    layer: Callable             # l -> {"mixer": {...}, "mlp": {...}}
    final_norm: object
    lm_head: object             # a float32 [H, V] array, or (int8, scale)


class SystemOut(NamedTuple):
    """What :func:`system_logits` hands :func:`compare`."""

    logits: object              # [B, P+D, V] float32, the harness's sample
    long_logits: object         # [1, n, V]: the long sample's compared ones
    state: object               # [heads, head_dim, N]: Mamba layer 0's
    #                             after the long sample


def engine_weights(sched) -> Weights:
    """The engine's own tree (models/nemotron_h.py: a stacked tree a
    kind), dequantised one layer at a time."""
    import jax
    import jax.numpy as jnp
    params, config = sched._params, sched.config
    f32 = jnp.float32
    walk = config.hybrid_pattern
    tree_of = {"M": "mamba", "*": "attn"}

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
        tree = tree_of[walk[2 * l]]
        i = sum(tree_of[c] == tree for c in walk[: 2 * l: 2])
        return {"mixer": _layer(params[tree], i),
                "mlp": _layer(params["mlp"], l)}

    head = params["lm_head"]
    return Weights(
        embed=params["embed"], layer=layer_weights,
        final_norm=params["final_norm"].astype(f32),
        lm_head=(head.q, head.s) if hasattr(head, "q") else head.astype(f32))


# -- the two mixers and the MLP -----------------------------------------------

def mamba(u, w, cfg: dict, wrong: str = ""):
    """One sequence through a Mamba-2 mixer by the sequential recurrence.
    u [T, H], already normed. Returns (out [T, H], final state [heads,
    head_dim, N], the indices of its slowest quarter of the heads)."""
    import jax
    import jax.numpy as jnp
    T = u.shape[0]
    nh, P = cfg["mamba_n_heads"], cfg["mamba_d_head"]
    G, N, K = cfg["mamba_n_groups"], cfg["mamba_d_state"], cfg["mamba_d_conv"]
    d = nh * P
    zxd = u @ w["w_in"]
    z, xbc, dt = (zxd[:, :d], zxd[:, d: 2 * d + 2 * G * N],
                  zxd[:, 2 * d + 2 * G * N:])
    padded = jnp.concatenate([jnp.zeros((K - 1, xbc.shape[1])), xbc])
    conv = sum(padded[j: j + T] * w["conv_w"][j] for j in range(K))
    if wrong != "no_conv_bias":
        conv = conv + w["conv_b"]
    xbc = jax.nn.silu(conv)
    x = xbc[:, :d].reshape(T, nh, P)
    Bm = jnp.repeat(xbc[:, d: d + G * N].reshape(T, G, N), nh // G, axis=1)
    Cm = jnp.repeat(xbc[:, d + G * N:].reshape(T, G, N), nh // G, axis=1)
    dt = jax.nn.softplus(dt + w["dt_bias"])                     # [T, nh]
    A = -jnp.exp(w["A_log"])

    def step(S, inp):
        x_t, b_t, c_t, dt_t = inp
        S = (jnp.exp(dt_t * A)[:, None, None] * S
             + (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :])
        if wrong == "bf16_state":
            # bfloat16's 8 exponent and 7 mantissa bits (a convert there
            # and back is elided under the TPU compiler's
            # allow_excess_precision).
            S = jax.lax.reduce_precision(S, exponent_bits=8,
                                         mantissa_bits=7)
        return S, jnp.einsum("hpn,hn->hp", S, c_t)

    S, y = jax.lax.scan(step, jnp.zeros((nh, P, N), jnp.float32),
                        (x, Bm, Cm, dt))
    # The heads whose state forgets slowest over this sequence (smallest
    # mean dt |A|): where a state kept in fewer bits drifts furthest and
    # the inputs' own rounding averages out most (compare's state limit).
    slow = jnp.argsort(jnp.mean(dt, axis=0) * -A)[: max(1, nh // 4)]
    if wrong != "no_d_skip":
        y = y + w["D"][:, None] * x
    y = y.reshape(T, d)

    def grouped_norm(v, groups):
        g = v.reshape(T, groups, -1)
        g = g * jax.lax.rsqrt(jnp.mean(g * g, axis=-1, keepdims=True)
                              + cfg["rms_norm_eps"])
        return g.reshape(T, d) * w["gnorm"]

    if wrong == "norm_before_gate":
        y = grouped_norm(y, G) * jax.nn.silu(z)
    elif wrong == "norm_by_head":
        y = grouped_norm(y * jax.nn.silu(z), nh)
    else:
        y = grouped_norm(y * jax.nn.silu(z), G)
    return y @ w["w_out"], S, slow


def attention(u, w, cfg: dict, wrong: str = ""):
    """Causal grouped-query attention of one sequence, no positional
    encoding, the softmax scale ``attention_multiplier``. u [T, H]."""
    import jax
    import jax.numpy as jnp
    T = u.shape[0]
    heads, kvh = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    D = cfg["hidden_size"] // heads
    qkv = u @ w["wqkv"]
    q = qkv[:, : heads * D].reshape(T, heads, D)
    k = qkv[:, heads * D: (heads + kvh) * D].reshape(T, kvh, D)
    v = qkv[:, (heads + kvh) * D:].reshape(T, kvh, D)
    pos = jnp.arange(T)
    k = jnp.repeat(k, heads // kvh, axis=1)
    v = jnp.repeat(v, heads // kvh, axis=1)
    scale = (1.0 / jnp.sqrt(jnp.float32(D)) if wrong == "scale_rsqrt_d"
             else cfg["attention_multiplier"])
    s = jnp.einsum("qhd,khd->hqk", q, k) * scale
    s = jnp.where(pos[None, :, None] >= pos[None, None, :], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("hqk,khd->qhd", p, v).reshape(T, heads * D) @ w["wo"]


_CFG_KEYS = ("mamba_n_heads", "mamba_d_head", "mamba_n_groups",
             "mamba_d_state", "mamba_d_conv", "rms_norm_eps", "hidden_size",
             "num_attention_heads", "num_key_value_heads",
             "shared_intermediate_size", "attention_multiplier",
             "residual_multiplier", "logits_scaling")


@functools.cache
def _jitted():
    import jax
    import jax.numpy as jnp
    from benchmark.reference import rms_norm

    def residual(cfg, wrong):
        return 1.0 if wrong == "residual_one" else cfg["residual_multiplier"]

    @functools.partial(jax.jit, static_argnames=("wrong", "cfg_key"))
    def mamba_layer(h, w, *, wrong, cfg_key):
        cfg = dict(cfg_key)
        with jax.default_matmul_precision("highest"):
            out, S, slow = jax.vmap(lambda x: mamba(
                rms_norm(x, w["norm"], cfg["rms_norm_eps"]), w, cfg,
                wrong))(h)
            return h + residual(cfg, wrong) * out, S, slow

    @functools.partial(jax.jit, static_argnames=("wrong", "cfg_key"))
    def attn_layer(h, w, *, wrong, cfg_key):
        cfg = dict(cfg_key)
        with jax.default_matmul_precision("highest"):
            out = jax.vmap(lambda x: attention(
                rms_norm(x, w["norm"], cfg["rms_norm_eps"]), w, cfg,
                wrong))(h)
            return h + residual(cfg, wrong) * out

    @functools.partial(jax.jit, static_argnames=("wrong", "cfg_key"))
    def mlp(h, w, *, wrong, cfg_key):
        cfg = dict(cfg_key)
        F = cfg["shared_intermediate_size"]
        with jax.default_matmul_precision("highest"):
            gu = rms_norm(h, w["norm"], cfg["rms_norm_eps"]) @ w["w_gu"]
            out = (jax.nn.silu(gu[..., :F]) * gu[..., F:]) @ w["w_mlp_down"]
            return h + residual(cfg, wrong) * out

    @functools.partial(jax.jit, static_argnames=("wrong", "cfg_key"))
    def head(h, final_norm, lm_head, *, wrong, cfg_key):
        """The logits of a block of positions. An int8 head (q, scale) is
        read as it lies: ``(x q) * scale`` is ``x (q * scale)`` with the
        scale a column, and no float32 copy of 100,352 x 2,048 stands
        beside a serving model."""
        cfg = dict(cfg_key)
        with jax.default_matmul_precision("highest"):
            x = rms_norm(h, final_norm, cfg["rms_norm_eps"])
            if isinstance(lm_head, tuple):
                q, s = lm_head
                logits = (x @ q.astype(jnp.float32)) * s.reshape(-1)
            else:
                logits = x @ lm_head
            if wrong != "logits_undivided":
                logits = logits / cfg["logits_scaling"]
            return logits

    return mamba_layer, attn_layer, mlp, head


def _q4(w):
    import jax.numpy as jnp
    scale = jnp.max(jnp.abs(w), axis=0, keepdims=True) / 7.0
    return jnp.round(w / jnp.where(scale > 0, scale, 1.0)) * scale


_MATS = {"w_in", "w_out", "wqkv", "wo", "w_gu", "w_mlp_down"}
_MAMBA_WRONG = ("bf16_state", "no_d_skip", "no_conv_bias",
                "norm_before_gate", "norm_by_head")
_ATTN_WRONG = ("scale_rsqrt_d",)


def _stack(cfg: dict, tokens, weights: Weights, positions=None) -> tuple:
    """Logits of ``tokens`` [B, T] at ``positions`` (all of them when
    None), and the first Mamba layer's final state with the indices of
    its slowest heads. A layer at a time, each waited for: run ahead, the
    host parks every layer it has dequantised on the chip (40 x 0.3 GB)."""
    import jax
    import jax.numpy as jnp
    mamba_layer, attn_layer, mlp, head = _jitted()
    wrong = cfg.get("_wrong", "")
    key = tuple((k, cfg[k]) for k in _CFG_KEYS)

    def kw(*heeds):
        """A function is compiled for a wrong model only if it heeds it."""
        return dict(cfg_key=key, wrong=wrong if wrong in heeds
                    or wrong == "residual_one" else "")

    with jax.default_matmul_precision("highest"):
        h = weights.embed[tokens].astype(jnp.float32)
        if wrong != "embedding_unscaled":
            h = h * cfg["embedding_multiplier"]
    state0 = None
    for l, kind in enumerate(cfg["layer_types"]):
        w = weights.layer(l)
        if wrong == "int4_weights":
            w = {part: {k: _q4(v) if k in _MATS else v
                        for k, v in tree.items()} for part, tree in w.items()}
        if kind == "mamba":
            h, S, slow = mamba_layer(h, w["mixer"], **kw(*_MAMBA_WRONG))
            if state0 is None:
                state0 = (S, slow)
        else:
            h = attn_layer(h, w["mixer"], **kw(*_ATTN_WRONG))
        h = jax.block_until_ready(mlp(h, w["mlp"], **kw()))
    if positions is not None:
        h = h[:, positions]
    # The head a block of positions at a time: 100,352 x T float32 beside
    # a serving model.
    head_kw = dict(cfg_key=key,
                   wrong=wrong if wrong == "logits_undivided" else "")
    blocks = [head(h[:, i: i + 128], weights.final_norm, weights.lm_head,
                   **head_kw) for i in range(0, h.shape[1], 128)]
    return jnp.concatenate(blocks, axis=1), state0


# -- the long sample ----------------------------------------------------------

def long_shape(chunk: int) -> tuple:
    """(prefill positions, decode steps) of the long sample at a chunk of
    ``chunk``: four chunks and 11/16 of a fifth, which is padded."""
    return 4 * chunk + 11 * chunk // 16, LONG_DECODE


def long_tokens(tokens, vocab: int, chunk: int):
    """The long sample [1, P + D], drawn from a seed the harness's
    tokens give: the same for system and reference, another every
    sample seed."""
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
    compared positions, the same with the softmax scale ``1 /
    sqrt(head_dim)``, and the first Mamba layer's final state there
    ([heads, head_dim, N]) with the indices of its slowest quarter of the
    heads. ``cfg["_wrong"]`` (absent in a run) names a deliberately wrong
    model."""
    import jax.numpy as jnp
    logits, _ = _stack(cfg, tokens, weights)
    chunk = check_chunk(cfg)
    long = jnp.asarray(long_tokens(tokens, cfg["vocab_size"], chunk))
    at = jnp.asarray(long_positions(chunk))
    long_logits, (S, slow) = _stack(cfg, long, weights, at)
    # The neighbour across the softmax scale's edge: the same model with
    # the other scale (the stated one, where this model is the wrong one).
    other = "" if cfg.get("_wrong") == "scale_rsqrt_d" else "scale_rsqrt_d"
    edge, _ = _stack({**cfg, "_wrong": other}, long, weights, at)
    return logits, {"long_logits": long_logits, "state": S[0],
                    "slow_heads": slow[0], "scale_edge": edge}


WRONG = ("bf16_state", "scale_rsqrt_d", "residual_one",
         "embedding_unscaled", "logits_undivided", "no_d_skip",
         "no_conv_bias", "norm_before_gate", "norm_by_head",
         "int4_weights")


def wrong_models(cfg: dict, weights: Weights) -> dict:
    """name -> (cfg, weights) of the wrong models a limit must fail: a
    state kept in bfloat16 (the precision below the float32 the
    configuration states); each of the four scalars dropped (a softmax
    scale of ``1 / sqrt(head_dim)``, a residual factor of 1, the
    embedding unscaled, the logits undivided); no ``D`` skip, no
    convolution bias, the norm before the gate, the gated norm a head at
    a time; every matrix rounded to int4 (the precision below the int8
    the stack states). Not in the list: rotary embedding applied. At a
    softmax scale of 1/64 the attention is close to a plain average of
    the values, and a rotation of q and k moves the logits by a few
    thousandths (0.3% at test size), inside every limit; the Nemotron
    cell's check, whose scale is ``1 / sqrt(head_dim)``, fails it at 7.6%
    through the same ``attn_rope`` switch."""
    return {name: ({**cfg, "_wrong": name}, weights) for name in WRONG}


# -- the system ---------------------------------------------------------------

def system_logits(sched, tokens, n_prefill: int) -> SystemOut:
    """The system's logits through the programs the cell's window runs,
    at the sizes it runs them.

    Both samples are admitted as an admission is. The harness's sample
    goes through ``prefill_chunk_counted`` in two chunks of REF_CHUNK
    over a dense carry (K and V of the attention layers, the Mamba state
    and window in its ``state``). The long one (:func:`long_tokens`)
    takes the way of a request that hits a prefix entry, as every request
    of the cell does: its first chunk is prefilled whole and kept as an
    entry is (K, V and ``state_pool.snapshot`` of the state at its end),
    a fresh carry is seeded from that (``from_snapshot``), and the rest
    follows in the scheduler's chunks behind it, the last padded and
    masked.

    Then ALL rows decode together in the SCHEDULER'S OWN POOL, at its
    ``num_slots`` rows: the first, a middle and the last slot, the other
    slots parked. A second pool of that size does not fit beside the
    first (``f32[36, 65, 64, 64, 128]`` is 4.9 GB of the chip's 16), so
    the check borrows the one the window will use: the engine is idle
    between its warm-up and the window, the pages come from the
    scheduler's allocator and go back to it, and the rows are released as
    a finished request's are. The steps are the window's three decode
    programs in turn: llama.decode_fused_aux (the scan
    ``jit_decode_fused_steps`` is) over the family's
    ``decode_step_paged_touched`` at ``decode_fuse_max`` steps and at
    half of that, then the plain step, each under the live mask and at
    the window the long row needs (2,048 tokens, where the paired
    flash-append kernel reads the pages on the chip)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from p2p_llm_chat_tpu.models.llama import KVCache, decode_fused_aux
    from p2p_llm_chat_tpu.ops.paged_kv import write_prefill_batch
    from p2p_llm_chat_tpu.ops.state_pool import (from_snapshot, snapshot,
                                                 write_rows)
    model, params, config = sched._model, sched._params, sched.config
    mesh, ps = sched.mesh, sched.page_size
    f32 = jnp.float32
    tokens = jnp.asarray(tokens)
    B, T = tokens.shape
    D = T - n_prefill
    C = sched.prefill_chunk
    long = jnp.asarray(long_tokens(tokens, config.vocab_size, C))
    PL, DL = long_shape(C)
    slots, fuse = sched.num_slots, sched.decode_fuse_max
    if slots < B + 1 or D != DL:
        raise ValueError(f"the check decodes {B + 1} rows of {DL} steps in "
                         f"one batch: {slots} slots, {D} steps")
    if any(s is not None for s in sched._slots):
        raise ValueError("the check decodes in the scheduler's own pool: "
                         "no request may be live")
    rows = np.round(np.linspace(0, slots - 1, B + 1)).astype(np.int32)
    pages = 1
    while pages * ps < PL + DL + 1:
        pages *= 2

    @functools.partial(jax.jit, static_argnames=("offset", "keep"))
    def chunk(params, toks, valid, carry, *, offset, keep):
        logits, carry, _ = model.prefill_chunk_counted(
            params, config, toks, carry, offset, valid, mesh)
        return logits[:, jnp.asarray(keep, jnp.int32)].astype(f32), carry

    def ladder(tokens, carry, start: int, P: int, C: int, keep=None):
        """``tokens`` [R, >= P] from position ``start`` to ``P`` in chunks
        of ``C`` over ``carry``: the logits (at positions ``keep`` when
        given) and the carry."""
        out = []
        for off in range(start, P, C):
            n = min(C, P - off)
            toks = jnp.pad(tokens[:, off: off + n], ((0, 0), (0, C - n)))
            valid = jnp.broadcast_to(jnp.arange(C)[None, :] < n, toks.shape)
            at = tuple(p - off for p in range(off, off + n)
                       if keep is None or p in keep)
            logits, carry = chunk(params, toks, valid, carry, offset=off,
                                  keep=at)
            out.append(logits)
        return out, carry

    @functools.partial(jax.jit, static_argnames=("keep",))
    def build_prefix(params, toks, *, keep):
        """One prefix [1, P0] prefilled whole and kept as an entry is
        (serve/scheduler.py ``prefill_build_prefix``)."""
        P0 = toks.shape[1]
        lens = jnp.full((1,), P0, jnp.int32)
        logits, cache, _ = model.prefill_counted(
            params, config, toks, lens,
            KVCache.create(config, 1, P0, dtype=sched._dtype),
            jnp.ones((1, P0), bool), mesh)
        return (logits[:, jnp.asarray(keep, jnp.int32)].astype(f32),
                cache.k[:, 0], cache.v[:, 0], snapshot(cache.state))

    @functools.partial(jax.jit, static_argnames=("width",))
    def seed(pk, pv, snap, *, width):
        """A suffix's carry behind the entry (``prefill_chunk_first``)."""
        P0 = pk.shape[1]
        carry = KVCache.create(config, 1, width, dtype=sched._dtype)
        return carry._replace(
            k=carry.k.at[:, :, :P0].set(pk[:, None]),
            v=carry.v.at[:, :, :P0].set(pv[:, None]),
            state=from_snapshot(snap, 1))

    @functools.partial(jax.jit, donate_argnums=(0,))
    def install(cache, carry, long_carry, tables):
        for c, at, n, table in ((carry, rows[:B], n_prefill, tables[:B]),
                                (long_carry, rows[B:], PL, tables[B:])):
            at = jnp.asarray(at)
            cache = write_prefill_batch(
                cache, c.k, c.v, at, jnp.full(at.shape, n, jnp.int32), table)
            cache = cache._replace(state=write_rows(cache.state, c.state,
                                                    at))
        return cache

    live = jnp.zeros((slots,), bool).at[rows].set(True)

    @functools.partial(jax.jit, donate_argnums=(2,),
                       static_argnames=("steps",))
    def decode_fused(params, feed, cache, script, *, steps):
        """``steps`` fused steps from the input tokens ``feed`` [slots,
        1]; ``script`` [steps, slots]: the sample's token after each."""
        def step(params, config, toks, cache, mesh, rules, aux, *, active,
                 pages):
            i, logits_at = aux
            logits, cache, _ = model.decode_step_paged_touched(
                params, config, toks, cache, mesh, rules, active,
                pages=pages)
            return logits, cache, (
                i + 1, logits_at.at[i].set(logits[rows, 0].astype(f32)))

        def sample(logits, i, emit_pos, act):
            return script[i], i + 1

        aux = (jnp.zeros((), jnp.int32),
               jnp.zeros((steps, B + 1, config.vocab_size), f32))
        _, _, _, cache, _, _, (_, logits_at) = decode_fused_aux(
            params, config, feed, cache, step, aux, mesh, active=live,
            num_steps=steps, sample_fn=sample,
            sample_state=jnp.zeros((), jnp.int32), stop_ids=(), pages=pages)
        return cache, logits_at

    @functools.partial(jax.jit, donate_argnums=(2,))
    def decode_plain(params, feed, cache):
        logits, cache, _ = model.decode_step_paged_touched(
            params, config, feed, cache, mesh, active=live, pages=pages)
        return cache, logits[rows, 0].astype(f32)[None]

    at = long_positions(C)
    long_keep = frozenset(at[at < PL].tolist())
    out, carry = ladder(
        tokens, KVCache.create(config, B, n_prefill, dtype=sched._dtype), 0,
        n_prefill, REF_CHUNK if n_prefill % REF_CHUNK == 0 else n_prefill)
    head, pk, pv, snap = build_prefix(
        params, long[:, :C], keep=tuple(p for p in range(C)
                                        if p in long_keep))
    long_out, long_carry = ladder(
        long, seed(pk, pv, snap, width=-(-PL // C) * C), C, PL, C,
        keep=long_keep)
    # What each slot is fed at each step, and a row of zeros behind the
    # last: the sampler's answer to a step is the next step's input.
    feed = np.zeros((D + 1, slots), np.int32)
    feed[:D, rows[:B]] = np.asarray(tokens[:, n_prefill:]).T
    feed[:D, rows[B]] = np.asarray(long[0, PL:])
    feed = jnp.asarray(feed)
    # The window's programs in turn: the longest scan, the half, then
    # plain steps.
    plan, left = [], D
    for k in (fuse, fuse // 2):
        if 1 < k <= left:
            plan.append(k)
            left -= k
    plan += [1] * left
    need = [-(-(n_prefill + D + 1) // ps)] * B + [-(-(PL + DL + 1) // ps)]
    held = sched._alloc.alloc(sum(need))
    if held is None:
        raise ValueError(f"the check needs {sum(need)} free pages")
    tables = np.zeros((B + 1, sched._cache.max_pages_per_row), np.int32)
    taken = iter(held)
    for r, n in enumerate(need):
        tables[r, :n] = [next(taken) for _ in range(n)]
    steps_logits, t = [], 0
    try:
        sched._cache = install(sched._cache, carry, long_carry,
                               jnp.asarray(tables))
        for n in plan:
            if n > 1:
                sched._cache, logits_at = decode_fused(
                    params, feed[t][:, None], sched._cache,
                    feed[t + 1: t + 1 + n], steps=n)
            else:
                sched._cache, logits_at = decode_plain(
                    params, feed[t][:, None], sched._cache)
            steps_logits.append(jnp.swapaxes(logits_at, 0, 1))  # [B+1,n,V]
            t += n
        state = jnp.copy(sched._cache.state.ssm[0, int(rows[B])])
    finally:
        for row in rows:
            sched._cache = sched._zero_row_j(sched._cache,
                                             jnp.asarray(row, jnp.int32))
        sched._alloc.free(held)
    steps_logits = jnp.concatenate(steps_logits, axis=1)
    return SystemOut(
        logits=jnp.concatenate([*out, steps_logits[:B]], axis=1),
        long_logits=jnp.concatenate([head, *long_out, steps_logits[B:]],
                                    axis=1),
        state=state)


def compare(system: SystemOut, reference_logits, facts: dict,
            cfg: dict) -> dict:
    """reference.compare's numbers on the harness's sample under this
    family's limits on the median and the maximum; the long sample's
    under the same (``long_median``, ``long_max``); and the first Mamba
    layer's final state after the long sample against the reference's
    over the slowest quarter of the heads, relative, in the Frobenius
    norm (``state_error``); and how far the system stands towards the
    softmax scale ``1 / sqrt(head_dim)`` (``scale_edge``)."""
    import jax.numpy as jnp
    from benchmark import reference
    out = reference.compare(system.logits, reference_logits, routed=False)
    f32 = jnp.float32
    long_err = reference.position_errors(system.long_logits,
                                         facts["long_logits"]).reshape(-1)
    out["long_median"] = float(jnp.median(long_err))
    out["long_max"] = float(jnp.max(long_err))
    slow = facts["slow_heads"]
    ref_state = facts["state"].astype(f32)[slow]
    sys_state = system.state.astype(f32)[slow]
    out["state_error"] = float(jnp.linalg.norm(sys_state - ref_state)
                               / jnp.linalg.norm(ref_state))
    off = (system.long_logits.astype(f32)
           - facts["long_logits"].astype(f32)).reshape(-1)
    step = (facts["scale_edge"].astype(f32)
            - facts["long_logits"].astype(f32)).reshape(-1)
    out["scale_edge"] = float(
        jnp.dot(off, step) / jnp.maximum(jnp.dot(step, step), 1e-30))
    out["ok"] = bool(
        jnp.isfinite(long_err).all() and out["positions"]
        and out["median"] <= TOL_MEDIAN and out["max"] <= TOL_MAX
        and out["long_median"] <= TOL_MEDIAN and out["long_max"] <= TOL_MAX
        and out["state_error"] <= TOL_STATE
        and out["scale_edge"] <= TOL_EDGE)
    out["tolerance"] = {"median": TOL_MEDIAN, "max": TOL_MAX,
                        "long_median": TOL_MEDIAN, "long_max": TOL_MAX,
                        "state_error": TOL_STATE, "scale_edge": TOL_EDGE}
    return out


# -- what a step must move and a prompt must compute (JAX-free) ---------------

def _q8(n_in: int, n_out: int) -> float:
    """Bytes of an int8 [n_in, n_out] weight with a float32 scale a
    column (benchmark/roofline.py's count)."""
    return n_in * n_out + 4 * n_out


def _conv_dim(cfg: dict) -> int:
    return (cfg["mamba_n_heads"] * cfg["mamba_d_head"]
            + 2 * cfg["mamba_n_groups"] * cfg["mamba_d_state"])


def layer_counts(cfg: dict) -> dict:
    kinds = cfg["layer_types"]
    return {k: kinds.count(k) for k in ("mamba", "attention")}


def layer_shapes(cfg: dict) -> dict:
    """[in, out] of every matrix of each half of a layer, as published."""
    H, F = cfg["hidden_size"], cfg["shared_intermediate_size"]
    d = cfg["mamba_n_heads"] * cfg["mamba_d_head"]
    Q = cfg["num_attention_heads"] * head_dim(cfg)
    KV = cfg["num_key_value_heads"] * head_dim(cfg)
    return {"mlp": [(H, 2 * F), (F, H)],
            "mamba": [(H, d + _conv_dim(cfg) + cfg["mamba_n_heads"]),
                      (d, H)],
            "attention": [(H, Q + 2 * KV), (Q, H)]}


def parameter_count(cfg: dict) -> int:
    """Matrix parameters of the whole model, the tied embedding once."""
    n, shapes = layer_counts(cfg), layer_shapes(cfg)
    per = {k: sum(a * b for a, b in shapes[k]) for k in shapes}
    return (cfg["num_hidden_layers"] * per["mlp"]
            + sum(n[k] * per[k] for k in n)
            + cfg["vocab_size"] * cfg["hidden_size"])


def state_row_bytes(cfg: dict) -> float:
    """One row of ONE Mamba layer in the state pool: the float32 state
    and the bf16 convolution window."""
    return (4.0 * cfg["mamba_n_heads"] * cfg["mamba_d_head"]
            * cfg["mamba_d_state"]
            + 2.0 * (cfg["mamba_d_conv"] - 1) * _conv_dim(cfg))


def page_token_bytes(cfg: dict) -> float:
    """One token of one attention layer in the int8 page pool: K and V of
    every KV head, the heads in pairs of 128 lanes with a float32 scale a
    pair."""
    kvh = cfg["num_key_value_heads"]
    return 2.0 * (kvh * head_dim(cfg) + 4 * (kvh // 2))


def decode_step_bytes(cfg: dict, rows: float, context: float) -> float:
    """Bytes one decode step has to move: every matrix once (int8, the
    head an int8 copy of the tied embedding); the rows' embeddings in
    bf16; each live row's state and window in every Mamba layer, read
    AND written; and each row's cached K and V in the attention layers."""
    n, shapes = layer_counts(cfg), layer_shapes(cfg)
    H = cfg["hidden_size"]
    weights = sum(cfg["num_hidden_layers"] * _q8(*s) for s in shapes["mlp"])
    weights += sum(n[k] * _q8(*s) for k in n for s in shapes[k])
    state = n["mamba"] * 2.0 * rows * state_row_bytes(cfg)
    pages = n["attention"] * rows * context * page_token_bytes(cfg)
    return (weights + _q8(H, cfg["vocab_size"]) + rows * 2 * H
            + state + pages)


def prefill_flops(cfg: dict, tokens: float, context_pairs: float) -> float:
    """FLOPs the prompt positions require: two a parameter a token for
    every matrix; the recurrence itself (a state update and a read of
    heads x head_dim x N each, two operations a number) and the
    convolution in every Mamba layer; and the attention's causal pairs in
    its layers (a score and a value over head_dim for every query head).
    The chunked form's extra products are its own choice and are not
    counted; the head runs for one position a request and is left out."""
    n, shapes = layer_counts(cfg), layer_shapes(cfg)
    per_token = 2.0 * cfg["num_hidden_layers"] * sum(
        a * b for a, b in shapes["mlp"])
    per_token += 2.0 * sum(n[k] * a * b for k in n for a, b in shapes[k])
    per_token += n["mamba"] * (
        4.0 * cfg["mamba_n_heads"] * cfg["mamba_d_head"]
        * cfg["mamba_d_state"] + 2.0 * cfg["mamba_d_conv"] * _conv_dim(cfg))
    pair = 4.0 * cfg["num_attention_heads"] * head_dim(cfg)
    return tokens * per_token + n["attention"] * context_pairs * pair
