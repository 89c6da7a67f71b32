"""The Mellum 2 family's architecture file (Mellum2-12B-A2.5B-Instruct,
``model_type: mellum``): a decoder of GQA attention layers, three over a
window to one over the whole context, rotated by two rotary tables, each
followed by a routed layer of many thin experts. The contract is in
benchmark/manifest.py's docstring.

**The layers, as :func:`forward` computes them** (float32,
``jax.default_matmul_precision("highest")``; ``h`` [T, d]; RMSNorm eps
``rms_norm_eps``; no biases). ``h_0 = E[tokens]``. Layer ``l``:

- ``a = RMSNorm(h; w1)``; ``[q | k | v] = a W_qkv`` (``num_attention_heads``
  query and ``num_key_value_heads`` KV heads x ``head_dim``); q and k
  rotated over the whole head, pairs (i, i + head_dim/2), by the table of
  the layer's kind; scores ``q . k / sqrt(head_dim)``, query head ``j``
  against KV head ``j // (heads / KV heads)``; softmax over the allowed
  keys; ``h <- h + (softmax v) W_o``. Allowed: causal; in a
  ``sliding_attention`` layer only the query's own position and the
  ``sliding_window - 1`` before it.
- **Two tables** (:func:`rope_table`, from ``rope_parameters``).
  ``sliding_attention``: ``inv_freq_i = theta^(-2i/D)``, cos and sin as
  they are. ``full_attention``: YaRN: ``c(n) = D ln(original / (2 pi n)) /
  (2 ln theta)``, ``low = floor(c(beta_fast))``, ``high =
  ceil(c(beta_slow))``, clamped to [0, D - 1]; ``ramp_i = clip((i - low) /
  (high - low), 0, 1)``; ``inv_freq_i = (1 - ramp_i) theta^(-2i/D) + ramp_i
  theta^(-2i/D) / factor``; cos and sin both multiplied by
  ``attention_factor``, so a full layer's scores carry its square.
- ``b = RMSNorm(h; w2)``; ``p = softmax(b W_r)`` over all ``num_experts``;
  the ``num_experts_per_tok`` largest kept and divided by their sum
  (``norm_topk_prob``); ``h <- h + sum_e p_e (silu(b Wg_e) * (b Wu_e))
  Wd_e``, every expert computed for every token and weighed (0 where it
  was not kept). No shared expert, no dense layer, nothing dropped.
- ``logits = RMSNorm(h; w_f) W_h``, untied.

Departures from the released model (the configuration file's ``assumed``
says where each item comes from): none known; the released modelling file
is not at hand and where it differs it is right. The config's
``intermediate_size`` is used by no layer (``mlp_layer_types`` is
``sparse`` throughout); the "MTP head" of the model card is not in the
config and is not computed.

No kernels, no cache, no ring, no pages, no chunking; attention runs a
block of queries at a time so that a 3,800-token sequence fits beside a
serving model; nothing is imported from the program (``rms_norm`` and
``position_errors`` are benchmark/reference.py's).

**The check's two samples.** The harness hands 2 x (128 + 8) tokens, which
never leave one window. So :func:`system_logits` and :func:`forward` both
derive from them ONE long sequence (:func:`long_tokens`: whole chunks and
11 sixteenths of another, at least 3.32 windows, then 8 decode steps: 3,776
+ 8 tokens at the cell's chunk of 1,024, 3,456 + 8 at 2,048), which the
system takes
through its chunk ladder, the install into rings and pages, and decode
steps; the reference as one sequence. :func:`compare` holds both samples
to the median limit and the long one to the window's edge (below).

Also here, JAX-free, what a step must move and a prompt must compute
(:func:`decode_step_bytes`, :func:`prefill_flops`), and the bytes of a
ring position and of a page token (:func:`window_position_bytes`,
:func:`page_token_bytes`). No kernel was written for this family (PERF.md
section 6, PR 40), so there is no ``_cost`` function.

Readers run in the parent of a run, which never imports JAX: this module
imports it inside the functions only the child calls.
"""

from __future__ import annotations

import functools
import math
from typing import Callable, NamedTuple

# The two limits, each from two kinds of reading on a v5e at the published
# widths, 16 layers, int8 weights, int8 rings and page pool
# (tools/check_reference_limit.py; my chip runs, PR 40; PERF.md section 6
# has every number). The sound program on sample seeds 53, 1, 2, 3, 4, 5;
# the same system logits against the reference changed into each wrong
# model of :func:`wrong_models`, seed 53; read at the cell's chunk of
# 1,024 (a long sample of 3,776 + 8), and again at 2,048, where the sweep
# stood before the review, on seeds 53, 1, 2 (3,456 + 8): sound 3.87-4.10%
# and 4.20-4.57%, edges 0.013-0.045, every wrong model within half a point
# of the readings below (windows of 1,023 and 1,025: edges of 1.06 and
# 1.12). The harness's seed at 1,024 on the final tree: 4.07% / 4.55%,
# edge 0.058 (call 7).
#
# TOL_MEDIAN, on the median position error of the logits
# (reference.position_errors), of the harness's sample and of the long one
# alike; the median alone, as for every routed family: where the 8th and
# 9th router probabilities tie within bf16's rounding (the smallest
# margin of a sample reads 5e-7 to 6e-5), system and reference keep
# different experts and the position is legitimately off. Sound: 3.24-
# 4.20% on the harness's sample and 4.00-4.61% on the long one (90th
# percentiles 6.1-7.2% and 9.0-10.1%, maxima 9.2-11.2% and 16.5-21.8%:
# sixteen layers of bf16 rounding, a full layer's scores carrying the
# factor's square, 1.63, and one of a token's eight experts flipped here
# and there). Wrong, on the better of the two samples: the factor applied
# once 9.1%, the factor dropped 12.9%, the plain table on the full layers
# 13.1%, the kept weights not renormalised 27.6%, YaRN on the window
# layers 33.0%, every matrix at int4 47.4%; a window layer reading its
# whole context 34.5% on the long sample (the harness's 136 tokens never
# leave a window: 4.07%, the sound reading). The limit lies between the
# two: 1.41 times the largest sound reading and 0.72 of the smallest
# wrong one.
#
# TOL_EDGE, on where the system stands between the reference and the
# reference with a window one key narrower or wider, on the long sample's
# positions past the first window: the projection of (system - reference)
# on (neighbour - reference), as a share of the latter's length; 0 for a
# system that is the reference, 1 for one that is the neighbour. One key
# of 1,024 moves a window layer's output by a fraction of a percent, far
# inside the rounding the median allows, but the rounding is not ALONG
# that direction. The neighbours keep the reference's own choice of
# experts (``_stack``'s ``kept``): a token whose 8th and 9th probabilities
# nearly tie flips under ANY small change, the system's rounding and a
# narrower window alike, and a neighbour free to flip would share those
# flips with the system. Sound 0.000-0.036; a window of 1,023 reads 1.03
# and one of 1,025 1.14 (their medians 4.07% and 4.5%, inside any limit
# rounding allows: the edge limit is what holds the window to the key).
# Half way is the limit.
TOL_MEDIAN = 0.065
TOL_EDGE = 0.5

LONG_DECODE = 8
LONG_STRIDE = 8         # prefill positions of the long sample compared
LONG_WINDOWS = 3.32     # its prefill is at least this many windows
EDGE_SPAN = 16          # positions compared densely past each wrap
QUERY_BLOCK = 512       # queries a block of the reference's attention


# -- the configuration --------------------------------------------------------

def layer_kinds(cfg: dict) -> list:
    """``window`` or ``full`` for each published layer."""
    kinds = {"sliding_attention": "window", "full_attention": "full"}
    types = cfg["layer_types"]
    if len(types) != cfg["num_hidden_layers"] or any(
            t != "sparse" for t in cfg["mlp_layer_types"]) or len(
                cfg["mlp_layer_types"]) != len(types):
        raise ValueError("layer_types / mlp_layer_types do not describe "
                         f"{cfg['num_hidden_layers']} sparse layers")
    return [kinds[t] for t in types]


def pattern(cfg: dict) -> str:
    """The program's walk (models/nemotron_h.py): ``w`` or ``*`` for a
    layer's attention and ``E`` for the routed layer behind it."""
    return "".join({"window": "w", "full": "*"}[k] + "E"
                   for k in layer_kinds(cfg))


def yarn_ramp(rp: dict, D: int) -> tuple:
    """(low, high) of the blend's ramp over the D / 2 frequencies."""
    def turns(n: float) -> float:
        return (D * math.log(rp["original_max_position_embeddings"]
                             / (n * 2 * math.pi))
                / (2 * math.log(rp["rope_theta"])))
    return (max(math.floor(turns(rp["beta_fast"])), 0),
            min(math.ceil(turns(rp["beta_slow"])), D - 1))


def rope_table(cfg: dict, kind: str) -> tuple:
    """(inverse frequencies, a list of head_dim / 2; the factor on cos and
    sin) of a ``window`` or ``full`` layer, from ``rope_parameters``."""
    rp = cfg["rope_parameters"][{"window": "sliding_attention",
                                 "full": "full_attention"}[kind]]
    D = cfg["head_dim"]
    plain = [rp["rope_theta"] ** (-2.0 * i / D) for i in range(D // 2)]
    if rp["rope_type"] == "default":
        return plain, 1.0
    if rp["rope_type"] != "yarn":
        raise ValueError(f"rope_type {rp['rope_type']!r}")
    low, high = yarn_ramp(rp, D)
    span = max(high - low, 1e-3)
    ramp = [min(max((i - low) / span, 0.0), 1.0) for i in range(D // 2)]
    return ([(1 - r) * f + r * f / rp["factor"]
             for r, f in zip(ramp, plain)],
            rp.get("attention_factor")
            or 0.1 * math.log(rp["factor"]) + 1.0)


def model_config(cfg: dict) -> dict:
    """``ModelConfig``'s keywords from the family's published keys."""
    from p2p_llm_chat_tpu.models.configs import RopeScaling
    rp = cfg["rope_parameters"]
    full, window = rp["full_attention"], rp["sliding_attention"]
    if window["rope_type"] != "default" or full["rope_type"] != "yarn" \
            or window["rope_theta"] != full["rope_theta"]:
        raise ValueError("the program rotates window layers by the plain "
                         "table and full layers by YaRN, of one theta")
    return dict(
        name=cfg["name"], vocab_size=cfg["vocab_size"],
        hidden_size=cfg["hidden_size"],
        intermediate_size=cfg["moe_intermediate_size"],
        num_layers=2 * cfg["num_hidden_layers"], hybrid_pattern=pattern(cfg),
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        sliding_window=cfg["sliding_window"],
        max_seq_len=cfg["max_position_embeddings"],
        rope_theta=float(full["rope_theta"]),
        rope_scaling=RopeScaling(
            kind="yarn", factor=float(full["factor"]),
            original_max_position=full["original_max_position_embeddings"],
            beta_fast=float(full["beta_fast"]),
            beta_slow=float(full["beta_slow"]),
            attention_factor=float(full.get("attention_factor") or 0.0)),
        rms_norm_eps=cfg["rms_norm_eps"],
        tie_embeddings=bool(cfg["tie_word_embeddings"]),
        num_experts=cfg["num_experts"],
        num_experts_per_tok=cfg["num_experts_per_tok"],
        moe_renormalize=bool(cfg["norm_topk_prob"]),
        bos_token_id=cfg.get("bos_token_id", 1),
        eos_token_ids=())       # ignore_eos: see the configuration file


class Weights(NamedTuple):
    """What :func:`forward` is handed: float32, one layer (one expert) at
    a time."""

    embed: object
    layer: Callable     # l -> {"attn": {norm, wqkv, wo}, "moe": {norm, router}}
    expert: Callable    # (l, e) -> (W_gate|up [d, 2F], W_down [F, d])
    final_norm: object
    lm_head: object     # a float32 [d, V] array, or (int8, scale)


class SystemOut(NamedTuple):
    """What :func:`system_logits` hands :func:`compare`."""

    logits: object              # [B, P+D, V] float32, the harness's sample
    long_logits: object         # [1, n, V]: the long sample's compared ones


def engine_weights(sched) -> Weights:
    """The engine's own tree (models/nemotron_h.py: a stacked tree a
    kind), dequantised one layer (one expert) at a time."""
    import jax
    import jax.numpy as jnp
    params = sched._params
    f32 = jnp.float32
    experts = ("wgu_e", "w_down")

    def plain(leaf, *at):
        if hasattr(leaf, "q"):
            return leaf.q[at].astype(f32) * leaf.s[at].astype(f32)
        return leaf[at].astype(f32)

    # The tree is an argument, never a closure (a closure bakes gigabytes
    # of constants into the program).
    @jax.jit
    def _layer(tree, i):
        return {name: plain(leaf, i) for name, leaf in tree.items()}

    @jax.jit
    def _expert(wgu, wd, i, e):
        return plain(wgu, i, e), plain(wd, i, e)

    def layer_weights(l):
        return {"attn": _layer(params["attn"], l),
                "moe": _layer({k: v for k, v in params["moe"].items()
                               if k not in experts}, l)}

    head = params["lm_head"]
    return Weights(
        embed=params["embed"], layer=layer_weights,
        expert=lambda l, e: _expert(*(params["moe"][k] for k in experts),
                                    l, e),
        final_norm=params["final_norm"].astype(f32),
        lm_head=(head.q, head.s) if hasattr(head, "q") else head.astype(f32))


# -- the layers ---------------------------------------------------------------

def rotate(x, pos, inv_freq, factor):
    """x [T, heads, D] at positions ``pos`` [T]: pairs (i, i + D/2), cos
    and sin times ``factor``."""
    import jax.numpy as jnp
    half = x.shape[-1] // 2
    ang = pos.astype(jnp.float32)[:, None] * inv_freq            # [T, D/2]
    cos = (jnp.cos(ang) * factor)[:, None, :]
    sin = (jnp.sin(ang) * factor)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(u, w, cfg: dict, table, window):
    """One sequence through an attention layer. u [T, d], normed;
    ``table`` (inverse frequencies, the factor on q's cos and sin, the
    factor on k's); ``window`` keys a query reads (0: the whole
    context). A block of queries at a time."""
    import jax
    import jax.numpy as jnp
    T = u.shape[0]
    heads, kvh, D = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                     cfg["head_dim"])
    inv_freq, fq, fk = table
    qkv = u @ w["wqkv"]
    pos = jnp.arange(T)
    q = rotate(qkv[:, : heads * D].reshape(T, heads, D), pos, inv_freq, fq)
    k = rotate(qkv[:, heads * D: (heads + kvh) * D].reshape(T, kvh, D),
               pos, inv_freq, fk)
    v = qkv[:, (heads + kvh) * D:].reshape(T, kvh, D)
    k = jnp.repeat(k, heads // kvh, axis=1)
    v = jnp.repeat(v, heads // kvh, axis=1)
    block = min(QUERY_BLOCK, T)
    pad = -T % block

    def some(args):
        qb, pb = args                                   # [block, heads, D]
        s = jnp.einsum("qhd,khd->hqk", qb, k) / jnp.sqrt(jnp.float32(D))
        seen = (pb[:, None] >= pos[None, :]) & (
            (pb[:, None] - pos[None, :] < window) | (window == 0))
        return jnp.einsum("hqk,khd->qhd",
                          jax.nn.softmax(jnp.where(seen, s, -jnp.inf), -1),
                          v)

    o = jax.lax.map(some, (
        jnp.pad(q, ((0, pad), (0, 0), (0, 0))).reshape(-1, block, heads, D),
        jnp.arange(T + pad).reshape(-1, block)))
    return o.reshape(-1, heads * D)[:T] @ w["wo"]


def route(x, router, top_k: int, kept=None, wrong: str = ""):
    """[T, NE] weights: softmax over all experts, the ``top_k`` largest
    kept (or the experts ``kept`` [T, top_k] names) and divided by their
    sum, zero elsewhere; the kept experts' indices; and each token's
    margin between its k-th and (k+1)-th probability, relative to the
    k-th."""
    import jax
    import jax.numpy as jnp
    probs = jax.nn.softmax(x @ router, axis=-1)
    top_p, top_i = jax.lax.top_k(probs, top_k + 1)
    margin = (top_p[:, top_k - 1] - top_p[:, top_k]) / top_p[:, top_k - 1]
    if kept is None:
        kept = top_i[:, :top_k]
    w = jnp.take_along_axis(probs, kept, axis=-1)
    if wrong != "weights_not_renormalised":
        w = w / jnp.sum(w, -1, keepdims=True)
    weights = jnp.zeros_like(probs).at[
        jnp.arange(x.shape[0])[:, None], kept].set(w)
    return weights, kept, margin


_CFG_KEYS = ("num_attention_heads", "num_key_value_heads", "head_dim",
             "rms_norm_eps", "num_experts_per_tok")


@functools.cache
def _jitted():
    import jax
    import jax.numpy as jnp
    from benchmark.reference import rms_norm, swiglu

    @functools.partial(jax.jit, static_argnames=("cfg_key",))
    def attn_layer(h, w, table, window, *, cfg_key):
        cfg = dict(cfg_key)
        with jax.default_matmul_precision("highest"):
            return h + jax.vmap(lambda x: attention(
                rms_norm(x, w["norm"], cfg["rms_norm_eps"]), w, cfg, table,
                window))(h)

    @functools.partial(jax.jit, static_argnames=("cfg_key", "wrong"))
    def moe_open(h, w, kept, *, cfg_key, wrong):
        """(normed tokens [B*T, d], routing weights, kept experts,
        margins)."""
        cfg = dict(cfg_key)
        with jax.default_matmul_precision("highest"):
            x = rms_norm(h, w["norm"], cfg["rms_norm_eps"]).reshape(
                -1, h.shape[-1])
            return (x, *route(x, w["router"], cfg["num_experts_per_tok"],
                              kept, wrong))

    @jax.jit
    def expert_add(acc, x, weight_col, wgu, wd):
        with jax.default_matmul_precision("highest"):
            F = wd.shape[0]
            return acc + weight_col[:, None] * swiglu(x, wgu[:, :F],
                                                      wgu[:, F:], wd)

    @functools.partial(jax.jit, static_argnames=("eps",))
    def head(h, norm, lm_head, *, eps):
        """Logits of a block of positions; an int8 head is dequantised
        here, a block at a time."""
        with jax.default_matmul_precision("highest"):
            if isinstance(lm_head, tuple):
                lm_head = lm_head[0].astype(jnp.float32) * lm_head[1]
            return rms_norm(h, norm, eps) @ lm_head

    return attn_layer, moe_open, expert_add, head


def _q4(w):
    import jax.numpy as jnp
    scale = jnp.max(jnp.abs(w), axis=0, keepdims=True) / 7.0
    return jnp.round(w / jnp.where(scale > 0, scale, 1.0)) * scale


def _tables(cfg: dict, wrong: str) -> dict:
    """kind -> (inverse frequencies, factor on q, factor on k), as the
    wrong model ``wrong`` has them."""
    import jax.numpy as jnp
    of = {"window": "full" if wrong == "yarn_on_window_layers" else "window",
          "full": "window" if wrong == "plain_on_full_layers" else "full"}
    out = {}
    for kind, table in of.items():
        inv_freq, f = rope_table(cfg, table)
        fq = fk = f
        if wrong == "factor_dropped":
            fq = fk = 1.0
        elif wrong == "factor_once":    # the scores carry f, not its square
            fk = 1.0
        out[kind] = (jnp.asarray(inv_freq, jnp.float32), fq, fk)
    return out


def _stack(cfg: dict, tokens, weights: Weights, window: int,
           positions=None, kept=None) -> tuple:
    """Logits of ``tokens`` [B, T] at ``positions`` (all of them when
    None) with ``window`` keys in the window layers (0: all), each routed
    layer's kept experts ([B*T, k]; ``kept``: a list of them to route by
    instead of the largest), and each token's smallest margin."""
    import jax
    import jax.numpy as jnp
    attn_layer, moe_open, expert_add, head = _jitted()
    wrong = cfg.get("_wrong", "")
    key = tuple((k, cfg[k]) for k in _CFG_KEYS)
    tables = _tables(cfg, wrong)
    with jax.default_matmul_precision("highest"):
        h = weights.embed[tokens].astype(jnp.float32)
    chosen, min_margin = [], None
    for l, kind in enumerate(layer_kinds(cfg)):
        w = weights.layer(l)
        if wrong == "int4_weights":
            w = {**w, "attn": {k: _q4(v) if k in ("wqkv", "wo") else v
                               for k, v in w["attn"].items()}}
        h = attn_layer(h, w["attn"], tables[kind],
                       window if kind == "window" else 0, cfg_key=key)
        x, routing, top_i, margin = moe_open(
            h, w["moe"], None if kept is None else kept[l], cfg_key=key,
            wrong=wrong if wrong == "weights_not_renormalised" else "")
        chosen.append(top_i)
        min_margin = margin if min_margin is None else jnp.minimum(
            min_margin, margin)
        acc = jnp.zeros_like(x)
        for e in range(cfg["num_experts"]):
            mats = weights.expert(l, e)
            if wrong == "int4_weights":
                mats = tuple(_q4(m) for m in mats)
            acc = expert_add(acc, x, routing[:, e], *mats)
        h = h + acc.reshape(h.shape)
    if positions is not None:
        h = h[:, positions]
    # The head a block of positions at a time: 98,304 x T float32 beside a
    # serving model.
    blocks = [head(h[:, i: i + 128], weights.final_norm, weights.lm_head,
                   eps=cfg["rms_norm_eps"])
              for i in range(0, h.shape[1], 128)]
    return jnp.concatenate(blocks, axis=1), chosen, min_margin


# -- the long sample ----------------------------------------------------------

def long_shape(chunk: int, window: int) -> tuple:
    """(prefill positions, decode steps) of the long sample at a chunk of
    ``chunk``: whole chunks and 11/16 of another, which is padded, the
    fewest that make LONG_WINDOWS windows."""
    part = 11 * chunk // 16
    n = max(1, math.ceil((LONG_WINDOWS * window - part) / chunk))
    return n * chunk + part, LONG_DECODE


def long_tokens(tokens, vocab: int, chunk: int, window: int):
    """The long sample [1, P + D], drawn from a seed the harness's tokens
    give: the same for system and reference, another every ``--seed``."""
    import numpy as np
    seed = int(np.asarray(tokens).astype(np.int64).sum()) % (2 ** 31)
    return np.random.default_rng(seed).integers(
        0, vocab, size=(1, sum(long_shape(chunk, window)))).astype(np.int32)


def long_positions(chunk: int, window: int):
    """The long sample's compared positions: every LONG_STRIDE-th of the
    prefill, EDGE_SPAN positions from each multiple of the window (just
    past the first window, just past each wrap of a ring), the prefill's
    last, and every decode step."""
    import numpy as np
    P, D = long_shape(chunk, window)
    edges = [np.arange(w, min(w + EDGE_SPAN, P))
             for w in range(window, P, window)]
    return np.unique(np.concatenate([np.arange(0, P, LONG_STRIDE), *edges,
                                     np.arange(P - 1, P + D)]))


def check_chunk(cfg: dict) -> int:
    """The chunk the check's long sample is laid out for: the stack's."""
    return int(cfg.get("stack", {}).get("SERVE_PREFILL_CHUNK", 256))


def forward(cfg: dict, tokens, weights: Weights) -> tuple:
    """Logits [B, T, V] (float32) of ``tokens`` [B, T], every position,
    and the facts ``compare`` reads: the long sample's logits at its
    compared positions, the same with a window one key narrower and one
    wider under the same choice of experts, and each sample's smallest
    routing margin a token. ``cfg["_wrong"]`` (absent in a run) names a
    deliberately wrong model."""
    import jax.numpy as jnp
    W = cfg["sliding_window"]
    wrong = cfg.get("_wrong", "")
    W = {"window_one_short": W - 1, "window_one_long": W + 1,
         "window_unbounded": 0}.get(wrong, W)
    logits, _, margin = _stack(cfg, tokens, weights, W)
    chunk = check_chunk(cfg)
    window = cfg["sliding_window"]
    long = jnp.asarray(long_tokens(tokens, cfg["vocab_size"], chunk, window))
    at = jnp.asarray(long_positions(chunk, window))
    long_logits, kept, long_margin = _stack(cfg, long, weights, W, at)
    facts = {"long_logits": long_logits, "edges": {}, "min_margin": margin,
             "long_min_margin": long_margin,
             "past_window": jnp.asarray(long_positions(chunk, window)
                                        >= window)}
    if W:
        for name, w in (("narrower", W - 1), ("wider", W + 1)):
            facts["edges"][name] = _stack(cfg, long, weights, w, at, kept)[0]
    return logits, facts


# Top-8 taken before the softmax is NOT here: with the kept weights
# divided by their sum, exp(l_i) / sum over the kept of exp(l_j) is the
# same number whichever comes first, so no limit can or should tell the
# two apart (PERF.md section 6, PR 40).
WRONG = ("yarn_on_window_layers", "plain_on_full_layers", "factor_dropped",
         "factor_once", "window_one_short", "window_one_long",
         "window_unbounded", "weights_not_renormalised", "int4_weights")


def wrong_models(cfg: dict, weights: Weights) -> dict:
    """name -> (cfg, weights) of the wrong models a limit must fail: YaRN
    on the window layers; the plain table on the full layers; the factor
    on cos and sin dropped; the factor applied once (on q alone) instead
    of squared; a window of 1,023 and of 1,025; a window layer reading
    its whole context; the kept router weights not divided by their sum;
    every matrix rounded to int4 (the precision below the int8 the stack
    states)."""
    return {name: ({**cfg, "_wrong": name}, weights) for name in WRONG}


# -- the system ---------------------------------------------------------------

def system_logits(sched, tokens, n_prefill: int) -> SystemOut:
    """The system's logits through the programs the scheduler serves
    with. Both samples go the way an admission does: ``prefill_chunk`` a
    chunk at a time over a dense carry (K and V of the full layers, the
    window layers' rings in its ``state``), the last chunk padded and
    masked; K and V spliced into a paged pool of the scheduler's kind,
    rings into the state pool's rows; then decode steps over rings and
    pages. The harness's sample is one chunk of its ``n_prefill``
    positions; the long one (:func:`long_tokens`) is several of the
    scheduler's chunk."""
    import jax
    import jax.numpy as jnp
    from p2p_llm_chat_tpu.models.llama import KVCache
    from p2p_llm_chat_tpu.ops.paged_kv import (PagedKVCache,
                                               write_prefill_batch)
    from p2p_llm_chat_tpu.ops.state_pool import write_rows
    model, params, config = sched._model, sched._params, sched.config
    mesh, ps = sched.mesh, sched.page_size

    @functools.partial(jax.jit, static_argnames=("offset", "keep"))
    def chunk(params, toks, valid, carry, *, offset, keep):
        logits, carry, _ = model.prefill_chunk_counted(
            params, config, toks, carry, offset, valid, mesh)
        return logits[:, jnp.asarray(keep, jnp.int32)].astype(
            jnp.float32), carry

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
        given)."""
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
            at = tuple(p - off for p in range(off, off + n)
                       if keep is None or p in keep)
            logits, carry = chunk(params, toks, valid, carry, offset=off,
                                  keep=at)
            out.append(logits)
        cache = splice(carry, jnp.full((B,), P, jnp.int32), per_row=pages)
        for t in range(P, T):
            step, cache = decode(params, tokens[:, t: t + 1], cache,
                                 pages=pages)
            if keep is None or t in keep:
                out.append(step)
        return jnp.concatenate(out, axis=1)

    logits = drive(tokens, n_prefill, n_prefill)
    C, W = sched.prefill_chunk, config.sliding_window
    long = jnp.asarray(long_tokens(tokens, config.vocab_size, C, W))
    return SystemOut(logits=logits, long_logits=drive(
        long, long_shape(C, W)[0], C,
        keep=set(long_positions(C, W).tolist())))


def compare(system: SystemOut, reference_logits, facts: dict,
            cfg: dict) -> dict:
    """reference.compare's numbers on the harness's sample under this
    family's limit on the median; the long sample's median under the
    same (``long_median``); and how far the system stands towards a
    window one key narrower or wider, over the long sample's positions
    past the first window (``window_edge``, the larger of the two
    projections)."""
    import jax.numpy as jnp
    from benchmark import reference
    f32 = jnp.float32
    out = reference.compare(system.logits, reference_logits, routed=True)
    long_err = reference.position_errors(system.long_logits,
                                         facts["long_logits"]).reshape(-1)
    out["long_median"] = float(jnp.median(long_err))
    out["long_p90"] = float(jnp.percentile(long_err, 90))
    out["long_max"] = float(jnp.max(long_err))
    out["min_margin"] = float(jnp.min(facts["min_margin"]))
    past = facts["past_window"]
    off = (system.long_logits.astype(f32)
           - facts["long_logits"].astype(f32))[:, past].reshape(-1)
    out["window_edge"] = 0.0
    for other in facts["edges"].values():
        step = (other.astype(f32)
                - facts["long_logits"].astype(f32))[:, past].reshape(-1)
        out["window_edge"] = max(out["window_edge"], float(
            jnp.dot(off, step) / jnp.maximum(jnp.dot(step, step), 1e-30)))
    out["ok"] = bool(
        jnp.isfinite(long_err).all() and out["median"] <= TOL_MEDIAN
        and out["long_median"] <= TOL_MEDIAN
        and out["window_edge"] <= TOL_EDGE)
    out["tolerance"] = {"median": TOL_MEDIAN, "max": None,
                        "long_median": TOL_MEDIAN, "window_edge": TOL_EDGE}
    return out


# -- what a step must move and a prompt must compute (JAX-free) ---------------

def _q8(n_in: int, n_out: int) -> float:
    """Bytes of an int8 [n_in, n_out] weight with a float32 scale a
    column (benchmark/roofline.py's count)."""
    return n_in * n_out + 4 * n_out


def layer_counts(cfg: dict) -> dict:
    kinds = layer_kinds(cfg)
    return {k: kinds.count(k) for k in ("window", "full")}


def layer_shapes(cfg: dict) -> dict:
    """[in, out] of every matrix of a layer's attention and of ONE of its
    experts, as published."""
    H, F, D = (cfg["hidden_size"], cfg["moe_intermediate_size"],
               cfg["head_dim"])
    Q, KV = cfg["num_attention_heads"] * D, cfg["num_key_value_heads"] * D
    return {"attn": [(H, Q + 2 * KV), (Q, H)],
            "expert": [(H, 2 * F), (F, H)]}


def page_token_bytes(cfg: dict) -> float:
    """One position of ONE full layer in the int8 page pool, or of ONE
    window layer in its int8 ring: K and V of every KV head and a float32
    scale a head for each."""
    return 2.0 * cfg["num_key_value_heads"] * (cfg["head_dim"] + 4)


window_position_bytes = page_token_bytes


def decode_step_bytes(cfg: dict, rows: float, context: float) -> float:
    """Bytes one decode step has to move: every attention matrix once and
    the router in float32; the experts a step reaches (all of them once
    ``rows x num_experts_per_tok`` passes the count, else that many);
    the head in int8; the rows' embeddings in bf16; each live row's last
    ``min(context, sliding_window)`` positions in every window layer's
    ring; and each row's context in every full layer's pages, each read
    by its own layer."""
    n = layer_counts(cfg)
    shapes = layer_shapes(cfg)
    H, L, NE = cfg["hidden_size"], cfg["num_hidden_layers"], \
        cfg["num_experts"]
    reached = min(NE, rows * cfg["num_experts_per_tok"])
    weights = L * (sum(_q8(*s) for s in shapes["attn"]) + 4.0 * H * NE
                   + reached * sum(_q8(*s) for s in shapes["expert"]))
    ring = (n["window"] * rows * min(context, cfg["sliding_window"])
            * window_position_bytes(cfg))
    pages = n["full"] * rows * context * page_token_bytes(cfg)
    return (weights + _q8(H, cfg["vocab_size"]) + rows * 2 * H + ring
            + pages)


def prefill_flops(cfg: dict, tokens: float, context_pairs: float) -> float:
    """FLOPs the prompt positions require: two a parameter a token for
    the attention matrices, the router and the ``num_experts_per_tok``
    experts a token reaches; and the attention's pairs, the causal ones
    in the full layers and no more than ``sliding_window`` a token in the
    window layers (the causal pairs capped at window x tokens). The head
    runs for one position a request and is left out."""
    n = layer_counts(cfg)
    shapes = layer_shapes(cfg)
    per_token = 2.0 * cfg["num_hidden_layers"] * (
        sum(a * b for a, b in shapes["attn"])
        + cfg["hidden_size"] * cfg["num_experts"]
        + cfg["num_experts_per_tok"] * sum(a * b
                                           for a, b in shapes["expert"]))
    pair = 4.0 * cfg["num_attention_heads"] * cfg["head_dim"]
    window_pairs = min(context_pairs, cfg["sliding_window"] * tokens)
    return (tokens * per_token + n["full"] * context_pairs * pair
            + n["window"] * window_pairs * pair)
