"""The LFM2 family's architecture file (LFM2-8B-A1B, ``model_type:
lfm2_moe``): a decoder whose layers are an *operator* and a
*feed-forward*, the operator a gated short convolution in eighteen layers
of twenty-four and grouped-query attention in six, the feed-forward a
dense SwiGLU in the first two and a routed layer of 32 SwiGLU experts
chosen by a biased sigmoid in the other twenty-two. The contract is in
benchmark/manifest.py's docstring.

**The layers, as :func:`forward` computes them** (float32,
``jax.default_matmul_precision("highest")``; ``h`` [T, d]; RMSNorm eps
``norm_eps``; no biases anywhere). ``h_0 = E[tokens]``. Layer ``l``:

- ``u = RMSNorm(h; w_op)``, then by ``layer_types[l]``:
  ``conv``: ``[B | C | x] = u W_in`` (d -> 3d, that order); ``z = B * x``;
  ``y_t = sum_{j=0..K-1} w_j * z_{t-(K-1)+j}`` with ``K = conv_L_cache`` =
  3 (a causal depthwise convolution over the position and the two before
  it, ``w_{K-1}`` on the current one, zeros before position 0, no bias,
  no activation), computed as K shifted products over the whole sequence;
  ``h <- h + (C * y) W_out``.
  ``full_attention``: ``[q | k | v] = u W_qkv`` (``num_attention_heads``
  query and ``num_key_value_heads`` KV heads x 64); RMSNorm over each
  head's 64 numbers, one weight vector for q and one for k a layer,
  shared by the heads; q and k rotated over the whole head, pairs (i, i +
  32), theta ``rope_theta``, no scaling; scores ``q . k / 8``, query head
  ``j`` against KV head ``j // 4``, causal softmax over the whole
  sequence; ``h <- h + (softmax v) W_o``.
- ``b = RMSNorm(h; w_ff)``, then: layers below ``num_dense_layers``:
  ``h <- h + (silu(b W_1) * (b W_3)) W_2`` at ``intermediate_size``. The
  others: ``s = sigmoid(b W_r)`` over all ``num_experts``; the
  ``num_experts_per_tok`` largest of ``s + bias`` chosen
  (``use_expert_bias``); their weights the UNBIASED ``s`` of the chosen
  over ``(their sum + 1e-6)`` (``norm_topk_prob``) times
  ``routed_scaling_factor``; ``h <- h + sum_e w_e (silu(b W1_e) * (b
  W3_e)) W2_e`` at ``moe_intermediate_size``, every expert computed for
  every token and weighed (0 where it was not chosen). No shared expert,
  nothing dropped.
- ``logits = RMSNorm(h; w_f) E^T``: the release's ``embedding_norm`` is
  the output norm, and the head is the embedding transposed.

Departures from the released model (the configuration file's ``assumed``
says where each item comes from): none known; the released modelling file
is not at hand and where it differs it is right.

No kernels, no cache, no window rows, no pages, no chunking; attention
runs a block of queries at a time so that a 3,800-token sequence fits
beside a serving model; nothing is imported from the program
(``rms_norm``, ``swiglu`` and ``position_errors`` are
benchmark/reference.py's).

**The check's two samples.** The harness hands 2 x (128 + 8) tokens. So
:func:`system_logits` and :func:`forward` both derive from them ONE long
sequence (:func:`long_tokens`: whole chunks and 11 sixteenths of another,
at least 3,500 positions, then 8 decode steps: 3,776 + 8 tokens at a
chunk of 1,024, 3,936 + 8 at 512), which the system takes through its
chunk ladder (the last chunk padded and masked), the install into
convolution rows and pages, and decode steps; the reference as one
sequence, replaying the routers' choices the system made
(:func:`route`). :func:`compare` holds both samples' medians, the decode
worst positions (the decode steps and the chunk starts among them) and
the routers' choices each to a limit (:data:`TOL_MEDIAN` and the comment
above it).

Also here, JAX-free, what a step must move and a prompt must compute
(:func:`decode_step_bytes`, :func:`prefill_flops`), the bytes of a page
token (:func:`page_token_bytes`) and what the flash-append kernel has to
read and multiply for the six page layers (:func:`flash_append_cost`).

Readers run in the parent of a run, which never imports JAX: this module
imports it inside the functions only the child calls.
"""

from __future__ import annotations

import functools
import math
from typing import Callable, NamedTuple

# The three limits, each from two kinds of reading on a v5e at the
# published widths, 24 layers, int8 weights, int8 page pool, bf16
# convolution rows (tools/check_reference_limit.py; my chip runs, PR 45,
# call 6; harness's sample of 2 x (128 + 8) / long sample of 3,776 + 8;
# sample seeds 53, 1, 2, 3 for the sound program, 53 for each wrong model
# of :func:`wrong_models` against the same system logits).
#
# Why the system's routing is REPLAYED in the reference before anything
# is held (:func:`route`): this router keeps the 4 largest of 32 sigmoid
# scores PLUS a bias and weighs them by the scores WITHOUT it, so the
# expert at the edge of the choice is no small weight, a quarter of the
# layer's output on average, and under random weights a quarter of all
# (token, routed layer) pairs have a 4th and 5th biased score within 0.01
# (``close_calls`` 24.3-24.6%). bf16's rounding of the hidden state the
# router reads decides 8.1-8.2% of the pairs the other way (``flips``),
# each legitimately a quarter of a layer off, and with the rule's own
# choices all through the reference the sound program's median position
# error read 19.6-24.6% (PR 45's first readings, call 2) where every other
# family's reads 2-6%. With the system's choices replayed the same
# program reads 4.2-4.5% at the median and 5.3% at its WORST position of
# the 761 compared: the flips were the whole of it, and every position can be held.
#
# TOL_MEDIAN, on the median position error of the logits
# (reference.position_errors), of each sample. Sound: 0.0424 / 0.0445,
# 0.0424 / 0.0446, 0.0420 / 0.0446, 0.0424 / 0.0447. Wrong: the biased
# scores kept as weights 0.1089 / 0.1125; QK-norm over the whole
# projection 0.1136 / 0.0543 and left out 0.2385 / 0.0731 (a long
# context's softmax averages more keys and forgives a norm more: the
# harness's short sample holds them, and TOL_MAX both); every matrix at
# int4, the precision below the stack's int8, 1.064 / 1.063; a window of
# one 1.223 / 1.228, of three 1.223 / 1.225; the taps reversed 1.346 /
# 1.350; the ``C`` gate left out 1.368 / 1.371. The limit is one and a
# half times the largest sound reading (reference.py's rule) and 0.62 of
# the smallest wrong one it is there for (0.1089).
#
# TOL_MAX, on the WORST position of each sample, which a replay makes
# worth holding: the 16 + 8 decode steps (the fused scan at the cell's 32
# slots through the flash-append kernel, windows and pages) and the 9
# positions that open a chunk behind a carried window are each held by
# it, and ``decode_max`` / ``starts_max`` say what they read alone. Sound:
# ``max`` 0.0480 / 0.0525, 0.0486 / 0.0525, 0.0479 / 0.0522, 0.0483 /
# 0.0518 (``decode_max`` 0.0452-0.0464, ``starts_max`` 0.0472-0.0497).
# Wrong, and passing TOL_MEDIAN: the window dropped between chunks
# (``carry_dropped``: medians 0.0424 / 0.0448, ``long_max`` =
# ``starts_max`` 1.325, ``decode_max`` 0.0459); the window never written
# by decode (``decode_window_stale``: medians 0.0426 / 0.0446, ``max``
# 1.371, ``decode_max`` 1.386, ``starts_max`` 0.0497). Of the others the
# smallest worst position is the biased weights' 0.1385 / 0.1429 and the
# whole-projection norm's 0.1758 / 0.1408. The limit is 1.7 times the
# largest sound reading and 0.65 of the smallest wrong one (the toy-size
# rehearsal on the CPU, tests/benchmark/test_benchmark_lfm2.py, reads
# 0.081 at a decode step: narrow layers round coarser).
#
# TOL_FLIPS, on the share of (token, routed layer) pairs of both samples
# where the rule's own choice, made on the reference's hidden state, is
# not the system's. Sound: 0.0812, 0.0819, 0.0820, 0.0808. Wrong: no bias
# in the choice 0.929 (its logits are the sound program's to the digit
# under a replay: only this limit sees it). 1.46 times the largest sound
# reading, an eighth of the wrong one.
#
# What no limit here can tell from the sound program, and says so: the
# router's products in bfloat16 (``router_bf16``: flips 0.0819 against
# the sound 0.0812 on the same logits, every other number equal to the
# fourth digit). The hidden state the system's router reads is bf16
# already and carries the rounding of every layer below it; a second
# rounding of the products moves a choice only where the first already
# could. The router's float32 is held where it can be seen, in the
# lowered program (tests/test_lfm2_parity.py:
# test_the_routers_products_are_float32_at_highest_in_the_program).
TOL_MEDIAN = 0.067
TOL_MAX = 0.09
TOL_FLIPS = 0.12

LONG_DECODE = 8
LONG_STRIDE = 8         # prefill positions of the long sample compared
LONG_MIN = 3500         # its prefill is at least this many positions
QUERY_BLOCK = 512       # queries a block of the reference's attention


# -- the configuration --------------------------------------------------------

def layer_kinds(cfg: dict) -> list:
    """``conv`` or ``attn`` for each published layer."""
    kinds = {"conv": "conv", "full_attention": "attn"}
    types = cfg["layer_types"]
    if len(types) != cfg["num_hidden_layers"]:
        raise ValueError("layer_types does not describe "
                         f"{cfg['num_hidden_layers']} layers")
    return [kinds[t] for t in types]


def pattern(cfg: dict) -> str:
    """The program's walk (models/nemotron_h.py): ``c`` or ``*`` for a
    layer's operator, then ``-`` (dense) or ``E`` (routed) for its
    feed-forward."""
    return "".join({"conv": "c", "attn": "*"}[k]
                   + ("-" if l < cfg["num_dense_layers"] else "E")
                   for l, k in enumerate(layer_kinds(cfg)))


def head_dim(cfg: dict) -> int:
    return cfg.get("head_dim") or (cfg["hidden_size"]
                                   // cfg["num_attention_heads"])


def model_config(cfg: dict) -> dict:
    """``ModelConfig``'s keywords from the family's published keys."""
    if cfg["conv_bias"] or not cfg["use_expert_bias"]:
        raise ValueError("the program's short convolution has no bias and "
                         "its sigmoid router a selection bias")
    return dict(
        name=cfg["name"], vocab_size=cfg["vocab_size"],
        hidden_size=cfg["hidden_size"],
        intermediate_size=cfg["moe_intermediate_size"],
        dense_intermediate_size=cfg["intermediate_size"],
        num_layers=2 * cfg["num_hidden_layers"], hybrid_pattern=pattern(cfg),
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"], head_dim=head_dim(cfg),
        max_seq_len=cfg["max_position_embeddings"],
        rope_theta=float(cfg["rope_theta"]), rms_norm_eps=cfg["norm_eps"],
        tie_embeddings=True, conv_kernel=cfg["conv_L_cache"],
        qk_norm_head=True, num_experts=cfg["num_experts"],
        num_experts_per_tok=cfg["num_experts_per_tok"],
        moe_scoring="sigmoid", moe_selection_bias=True,
        moe_renormalize=bool(cfg["norm_topk_prob"]), moe_renorm_eps=1e-6,
        routed_scaling_factor=float(cfg["routed_scaling_factor"]),
        bos_token_id=cfg.get("bos_token_id", 1),
        eos_token_ids=())       # ignore_eos: see the configuration file


class Weights(NamedTuple):
    """What :func:`forward` is handed: float32, one layer (one expert) at
    a time."""

    embed: object
    layer: Callable     # l -> {"op": {...}, "ff": {...}} of the layer's kinds
    expert: Callable    # (l, e) -> (W_1|3 [d, 2F], W_2 [F, d])
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
    pat = sched.config.hybrid_pattern
    ops, ffs = pat[0::2], pat[1::2]

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
        op = {"c": "conv", "*": "attn"}[ops[l]]
        ff = {"-": "mlp", "E": "moe"}[ffs[l]]
        return {"op": _layer(params[op], ops[:l].count(ops[l])),
                "ff": _layer({k: v for k, v in params[ff].items()
                              if k not in experts}, ffs[:l].count(ffs[l]))}

    head = params["lm_head"]
    return Weights(
        embed=params["embed"], layer=layer_weights,
        expert=lambda l, e: _expert(*(params["moe"][k] for k in experts),
                                    ffs[:l].count("E"), e),
        final_norm=params["final_norm"].astype(f32),
        lm_head=(head.q, head.s) if hasattr(head, "q") else head.astype(f32))


# -- the layers ---------------------------------------------------------------

def rotate(x, pos, theta: float):
    """x [T, heads, D] at positions ``pos`` [T]: pairs (i, i + D/2)."""
    import jax.numpy as jnp
    D = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, D, 2, dtype=jnp.float32) / D))
    ang = pos.astype(jnp.float32)[:, None] * inv                 # [T, D/2]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : D // 2], x[..., D // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def short_conv(u, w, cfg: dict, wrong: str = ""):
    """One sequence through a gated short convolution. u [T, d], normed;
    ``w``: w_in [d, 3d], conv_w [K, d], w_out [d, d]. Two of the wrong
    models are a serving fault's and act where the window is CARRIED:
    ``carry_dropped`` reads zeros before every chunk of ``_chunk``
    positions (an admission that forgets the window between chunks), and
    ``decode_window_stale`` reads, from position ``_decode_from`` on, the
    window as the prefill left it (a decode step that reads its row and
    never writes it)."""
    import jax.numpy as jnp
    T, d = u.shape
    bcx = u @ w["w_in"]
    B, C, x = bcx[:, :d], bcx[:, d: 2 * d], bcx[:, 2 * d:]
    z = B * x
    taps = w["conv_w"]
    if wrong == "taps_reversed":
        taps = taps[::-1]
    elif wrong == "window_of_one":          # the oldest position unread
        taps = taps[1:]
    elif wrong == "window_of_three":        # one position further back
        taps = jnp.concatenate([taps[:1], taps], axis=0)
    K = taps.shape[0]
    padded = jnp.concatenate([jnp.zeros((K - 1, d), z.dtype), z], axis=0)
    pos = jnp.arange(T)[:, None]

    def past(j):
        """z at the position K - 1 - j before each one."""
        back = K - 1 - j
        if wrong == "carry_dropped" and back:
            return jnp.where(pos % cfg["_chunk"] >= back,
                             padded[j: j + T], 0.0)
        if wrong == "decode_window_stale" and back:
            P = cfg["_decode_from"]
            frozen = padded[jnp.clip(P - back + K - 1, 0, T + K - 2)]
            return jnp.where(pos >= P, frozen, padded[j: j + T])
        return padded[j: j + T]

    y = sum(taps[j] * past(j) for j in range(K))
    if wrong != "c_gate_left_out":
        y = C * y
    return y @ w["w_out"]


def attention(u, w, cfg: dict, wrong: str = ""):
    """One sequence through an attention layer. u [T, d], normed. A block
    of queries at a time."""
    import jax
    import jax.numpy as jnp
    from benchmark.reference import rms_norm
    T = u.shape[0]
    heads, kvh, D = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                     head_dim(cfg))
    eps = cfg["norm_eps"]
    qkv = u @ w["wqkv"]
    q, k = qkv[:, : heads * D], qkv[:, heads * D: (heads + kvh) * D]
    v = qkv[:, (heads + kvh) * D:].reshape(T, kvh, D)
    if wrong == "qk_norm_whole_projection":
        # One RMS over all the heads' numbers, the head's weights tiled.
        q = rms_norm(q, jnp.tile(w["q_norm"], heads), eps)
        k = rms_norm(k, jnp.tile(w["k_norm"], kvh), eps)
    q, k = q.reshape(T, heads, D), k.reshape(T, kvh, D)
    if wrong not in ("qk_norm_whole_projection", "qk_norm_left_out"):
        q = rms_norm(q, w["q_norm"], eps)
        k = rms_norm(k, w["k_norm"], eps)
    pos = jnp.arange(T)
    q = rotate(q, pos, cfg["rope_theta"])
    k = rotate(k, pos, cfg["rope_theta"])
    k = jnp.repeat(k, heads // kvh, axis=1)
    v = jnp.repeat(v, heads // kvh, axis=1)
    block = min(QUERY_BLOCK, T)
    pad = -T % block

    def some(args):
        qb, pb = args                                   # [block, heads, D]
        s = jnp.einsum("qhd,khd->hqk", qb, k) / jnp.sqrt(jnp.float32(D))
        seen = pb[:, None] >= pos[None, :]
        return jnp.einsum("hqk,khd->qhd",
                          jax.nn.softmax(jnp.where(seen, s, -jnp.inf), -1),
                          v)

    o = jax.lax.map(some, (
        jnp.pad(q, ((0, pad), (0, 0), (0, 0))).reshape(-1, block, heads, D),
        jnp.arange(T + pad).reshape(-1, block)))
    return o.reshape(-1, heads * D)[:T] @ w["wo"]


def route(x, router, bias, top_k: int, scaling: float, wrong: str = "",
          chosen=None):
    """[T, NE] weights: float32 sigmoid scores over all experts, the
    ``top_k`` largest of score + bias chosen, weighed by their unbiased
    scores over (their sum + 1e-6), times ``scaling``; zero elsewhere.
    Also the rule's own choice, each token's margin between its k-th and
    (k+1)-th biased score, and ``flipped`` [T].

    ``chosen`` ([T, top_k], the system's choice on these tokens) given:
    the rule still makes its own choice, ``flipped`` says where the two
    differ as sets, and the WEIGHTS are of the system's experts, by this
    rule's scores: the replay. A near tie decided the other way then
    costs the difference of two nearly equal scores and not a quarter of
    the layer, and a choice made by another rule shows in ``flipped``."""
    import jax
    import jax.numpy as jnp
    if wrong == "router_bf16":
        x, router = x.astype(jnp.bfloat16), router.astype(jnp.bfloat16)
    scores = jax.nn.sigmoid((x @ router).astype(jnp.float32))
    biased = scores if wrong == "no_expert_bias" else scores + bias
    top_b, top_i = jax.lax.top_k(biased, top_k + 1)
    margin = top_b[:, top_k - 1] - top_b[:, top_k]
    kept = top_i[:, :top_k]
    use = kept if chosen is None else chosen
    flipped = jnp.any(jnp.sort(kept, -1) != jnp.sort(use, -1), axis=-1)
    w = jnp.take_along_axis(
        biased if wrong == "biased_scores_as_weights" else scores, use,
        axis=-1)
    w = scaling * w / (jnp.sum(w, -1, keepdims=True) + 1e-6)
    weights = jnp.zeros_like(scores).at[
        jnp.arange(x.shape[0])[:, None], use].set(w)
    return weights, kept, margin, flipped


# -- compiling ahead ----------------------------------------------------------
#
# The chip's compiler takes 6-13 s over ONE float32 dot under "highest",
# whatever its shape, and 6-8 s over a chunk program of the model: the
# reference's fourteen programs 101 s one after another and the check 132
# s of a cold run's set-up against 38 s from the compile cache (compiled
# here for a described v5e; my chip runs, PR 45, call 7). None of them
# waits for another's result, and XLA compiles outside the GIL: so both
# sides of the check hand their programs to threads before the first is
# called, and call what the threads compiled: 58 s of a cold set-up, 35-38
# from the cache, the readings the same to the last digit (call 9).

class _Ahead:
    """Programs compiled ahead of their first call, on the TPU in
    threads. ``add`` starts one compilation of a jitted ``fn`` at the
    shapes of ``args`` (arrays or ``ShapeDtypeStruct``s) and the static
    ``statics``; ``call`` runs what was compiled for such arguments, or
    ``fn`` itself where nothing was (a wrong model's variant, a shape not
    foreseen). Off the TPU ``add`` compiles at once, in the caller's
    thread: a test size compiles in a moment, and the tests' processes,
    six at a time, are not given a pool of compiler threads each."""

    def __init__(self) -> None:
        import os
        from concurrent.futures import ThreadPoolExecutor
        import jax
        self._pool = jax.default_backend() == "tpu" and ThreadPoolExecutor(
            max(1, (os.cpu_count() or 2) // 2),
            thread_name_prefix="lfm2-compile")
        self._compiled: dict = {}       # key -> () -> the compiled program

    @staticmethod
    def _key(name, args, statics) -> tuple:
        import jax
        return (name, tuple((tuple(a.shape), str(a.dtype))
                            for a in jax.tree.leaves(args)),
                jax.tree.structure(args), tuple(sorted(statics.items())))

    def add(self, name: str, fn, *args, **statics) -> None:
        key = self._key(name, args, statics)
        if key in self._compiled:
            return
        if self._pool:
            self._compiled[key] = self._pool.submit(
                lambda: fn.lower(*args, **statics).compile()).result
        else:
            program = fn.lower(*args, **statics).compile()
            self._compiled[key] = lambda: program

    def call(self, name: str, fn, *args, **statics):
        ahead = self._compiled.get(self._key(name, args, statics))
        if ahead is None:
            return fn(*args, **statics)
        return ahead()(*args)


_CFG_KEYS = ("num_attention_heads", "num_key_value_heads", "hidden_size",
             "norm_eps", "num_experts_per_tok", "rope_theta",
             "routed_scaling_factor")


@functools.cache
def _jitted():
    import jax
    import jax.numpy as jnp
    from benchmark.reference import rms_norm, swiglu

    @functools.partial(jax.jit, static_argnames=("cfg_key", "kind", "wrong"))
    def op_layer(h, w, *, cfg_key, kind, wrong):
        cfg = dict(cfg_key)
        mixer = short_conv if kind == "conv" else attention
        with jax.default_matmul_precision("highest"):
            return h + jax.vmap(lambda x: mixer(
                rms_norm(x, w["norm"], cfg["norm_eps"]), w, cfg, wrong))(h)

    @functools.partial(jax.jit, static_argnames=("eps",))
    def dense_ff(h, w, *, eps):
        with jax.default_matmul_precision("highest"):
            F = w["w_mlp_down"].shape[0]
            return h + swiglu(rms_norm(h, w["norm"], eps), w["w_gu"][:, :F],
                              w["w_gu"][:, F:], w["w_mlp_down"])

    @functools.partial(jax.jit, static_argnames=("cfg_key", "wrong"))
    def moe_open(h, w, chosen, *, cfg_key, wrong):
        """(normed tokens [B*T, d], then :func:`route`'s four)."""
        cfg = dict(cfg_key)
        with jax.default_matmul_precision("highest"):
            x = rms_norm(h, w["norm"], cfg["norm_eps"]).reshape(
                -1, h.shape[-1])
            return (x, *route(x, w["router"], w["router_bias"],
                              cfg["num_experts_per_tok"],
                              cfg["routed_scaling_factor"], wrong, chosen))

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

    return op_layer, dense_ff, moe_open, expert_add, head


def _q4(w):
    import jax.numpy as jnp
    scale = jnp.max(jnp.abs(w), axis=0, keepdims=True) / 7.0
    return jnp.round(w / jnp.where(scale > 0, scale, 1.0)) * scale


_MATRICES = ("w_in", "w_out", "wqkv", "wo", "w_gu", "w_mlp_down")
_ROUTING_WRONG = ("no_expert_bias", "biased_scores_as_weights",
                  "router_bf16")


_REFERENCE: list = []       # the reference's one :class:`_Ahead`


def _reference() -> "_Ahead":
    if not _REFERENCE:
        _REFERENCE.append(_Ahead())
    return _REFERENCE[0]


def _cfg_key(cfg: dict, decode_from: int) -> tuple:
    return (*((k, cfg[k]) for k in _CFG_KEYS), ("head_dim", head_dim(cfg)),
            ("_chunk", check_chunk(cfg)), ("_decode_from", decode_from))


def _head_blocks(n: int) -> list:
    """The head a block of positions at a time: 65,536 x T float32 beside
    a serving model."""
    return [(i, min(i + 128, n)) for i in range(0, n, 128)]


def _stack_ahead(cfg: dict, weights: Weights, shape: tuple, n_out: int,
                 replayed: bool, decode_from: int) -> None:
    """Start compiling what :func:`_stack` will call for ``shape`` [B, T]
    tokens, ``n_out`` positions' logits and a replay or none
    (:class:`_Ahead`); the sound model's programs only."""
    import jax
    import jax.numpy as jnp
    op_layer, dense_ff, moe_open, expert_add, head = _jitted()
    ahead, key, eps = _reference(), _cfg_key(cfg, decode_from), cfg["norm_eps"]
    B, T = shape
    d, f32 = cfg["hidden_size"], jnp.float32
    h = jax.ShapeDtypeStruct((B, T, d), f32)
    x = jax.ShapeDtypeStruct((B * T, d), f32)
    kinds = layer_kinds(cfg)
    for kind in sorted(set(kinds)):
        ahead.add("op_layer", op_layer, h, weights.layer(
            kinds.index(kind))["op"], cfg_key=key, kind=kind, wrong="")
    if cfg["num_dense_layers"]:
        ahead.add("dense_ff", dense_ff, h, weights.layer(0)["ff"], eps=eps)
    if cfg["num_dense_layers"] < len(kinds):
        l = cfg["num_dense_layers"]
        chosen = jax.ShapeDtypeStruct(
            (B * T, cfg["num_experts_per_tok"]), jnp.int32)
        ahead.add("moe_open", moe_open, h, weights.layer(l)["ff"],
                  chosen if replayed else None, cfg_key=key, wrong="")
        ahead.add("expert_add", expert_add, x, x,
                  jax.ShapeDtypeStruct((B * T,), f32), *weights.expert(l, 0))
    for n in {j - i for i, j in _head_blocks(n_out)}:
        ahead.add("head", head, jax.ShapeDtypeStruct((B, n, d), f32),
                  weights.final_norm, weights.lm_head, eps=eps)


def _stack(cfg: dict, tokens, weights: Weights, positions=None,
           chosen=None, decode_from: int = 0) -> tuple:
    """Logits of ``tokens`` [B, T] at ``positions`` (all of them when
    None), and of every routed layer in order each token's margin and
    whether the rule's choice differs from ``chosen``'s ([routed layers,
    B * T, k], the system's, which is then replayed: :func:`route`; None:
    the rule's own choice all through, and nothing differs).
    ``decode_from``: the first position the system took as a decode step,
    for the wrong model that is a decode fault."""
    import jax
    import jax.numpy as jnp
    op_layer, dense_ff, moe_open, expert_add, head = _jitted()
    call = _reference().call
    wrong = cfg.get("_wrong", "")
    key = _cfg_key(cfg, decode_from)
    eps = cfg["norm_eps"]
    with jax.default_matmul_precision("highest"):
        h = weights.embed[tokens].astype(jnp.float32)
    margins, flips = [], []
    for l, kind in enumerate(layer_kinds(cfg)):
        w = weights.layer(l)
        if wrong == "int4_weights":
            w = {part: {k: _q4(v) if k in _MATRICES else v
                        for k, v in leaves.items()}
                 for part, leaves in w.items()}
        h = call("op_layer", op_layer, h, w["op"], cfg_key=key, kind=kind,
                 wrong=wrong if wrong not in _ROUTING_WRONG else "")
        if l < cfg["num_dense_layers"]:
            h = call("dense_ff", dense_ff, h, w["ff"], eps=eps)
            continue
        x, routing, _, margin, flipped = call(
            "moe_open", moe_open, h, w["ff"],
            None if chosen is None else chosen[len(margins)],
            cfg_key=key, wrong=wrong if wrong in _ROUTING_WRONG else "")
        margins.append(margin)
        flips.append(flipped)
        acc = jnp.zeros_like(x)
        for e in range(cfg["num_experts"]):
            mats = weights.expert(l, e)
            if wrong == "int4_weights":
                mats = tuple(_q4(m) for m in mats)
            acc = call("expert_add", expert_add, acc, x, routing[:, e],
                       *mats)
        h = h + acc.reshape(h.shape)
    if positions is not None:
        h = h[:, positions]
    blocks = [call("head", head, h[:, i: j], weights.final_norm,
                   weights.lm_head, eps=eps)
              for i, j in _head_blocks(h.shape[1])]
    return (jnp.concatenate(blocks, axis=1),
            {"margin": jnp.stack(margins), "flipped": jnp.stack(flips)})


# -- the long sample ----------------------------------------------------------

def long_shape(chunk: int) -> tuple:
    """(prefill positions, decode steps) of the long sample at a chunk of
    ``chunk``: whole chunks and 11/16 of another, which is padded, the
    fewest that make LONG_MIN positions."""
    part = 11 * chunk // 16
    n = max(1, math.ceil((LONG_MIN - part) / chunk))
    return n * chunk + part, LONG_DECODE


def long_tokens(tokens, vocab: int, chunk: int):
    """The long sample [1, P + D], drawn from a seed the harness's tokens
    give: the same for system and reference, another every ``--seed``."""
    import numpy as np
    seed = int(np.asarray(tokens).astype(np.int64).sum()) % (2 ** 31)
    return np.random.default_rng(seed).integers(
        0, vocab, size=(1, sum(long_shape(chunk)))).astype(np.int32)


def long_positions(chunk: int):
    """The long sample's compared positions: every LONG_STRIDE-th of the
    prefill, the first three of every chunk (where the convolution reads
    the carried window), the prefill's last, and every decode step."""
    import numpy as np
    P, D = long_shape(chunk)
    starts = [np.arange(c, min(c + 3, P)) for c in range(0, P, chunk)]
    return np.unique(np.concatenate([np.arange(0, P, LONG_STRIDE), *starts,
                                     np.arange(P - 1, P + D)]))


def check_chunk(cfg: dict) -> int:
    """The chunk the check's long sample is laid out for: the stack's."""
    return int(cfg.get("stack", {}).get("SERVE_PREFILL_CHUNK", 256))


# What :func:`system_logits` last read of the system's routing, for
# :func:`forward` to replay on the same tokens: the harness calls the one,
# then ``forward(cfg, tokens, weights)``, and hands nothing across
# (benchmark/serve_cell.reference_check), so this file does. {the tokens'
# digest: (n_prefill, chosen [routed layers, B * T, k] of the harness's
# sample, of the long one)}; one entry, the last.
_CHOSEN: dict = {}


def _digest(tokens) -> bytes:
    import numpy as np
    return np.asarray(tokens).astype(np.int64).tobytes()


def forward(cfg: dict, tokens, weights: Weights) -> tuple:
    """Logits [B, T, V] (float32) of ``tokens`` [B, T], every position,
    and the facts ``compare`` reads: the long sample's logits at its
    compared positions, and of both samples every routed layer's margins
    and whether the rule's choice is the system's. Where
    :func:`system_logits` ran on these tokens its choices are replayed
    (:func:`route`; ``replayed``); else the rule's own choice is taken
    all through and the last LONG_DECODE positions count as decode steps
    (the harness's sample has as many). ``cfg["_wrong"]`` (absent in a
    run) names a deliberately wrong model."""
    import jax.numpy as jnp
    n_prefill, chosen, long_chosen = _CHOSEN.get(
        _digest(tokens), (tokens.shape[1] - LONG_DECODE, None, None))
    chunk = check_chunk(cfg)
    long = jnp.asarray(long_tokens(tokens, cfg["vocab_size"], chunk))
    at = long_positions(chunk)
    if not cfg.get("_wrong"):
        _stack_ahead(cfg, weights, tokens.shape, tokens.shape[1],
                     chosen is not None, n_prefill)
        _stack_ahead(cfg, weights, long.shape, len(at),
                     long_chosen is not None, long_shape(chunk)[0])
    logits, routing = _stack(cfg, tokens, weights, None, chosen, n_prefill)
    long_logits, long_routing = _stack(
        cfg, long, weights, jnp.asarray(at), long_chosen,
        long_shape(chunk)[0])
    return logits, {"long_logits": long_logits, "replayed": chosen is not None,
                    **routing,
                    **{"long_" + k: v for k, v in long_routing.items()}}


# The readings are beside :data:`TOL_MEDIAN`.
WRONG = ("no_expert_bias", "biased_scores_as_weights", "taps_reversed",
         "window_of_one", "window_of_three", "c_gate_left_out",
         "qk_norm_left_out", "qk_norm_whole_projection", "carry_dropped",
         "decode_window_stale", "int4_weights", "router_bf16")


def wrong_models(cfg: dict, weights: Weights) -> dict:
    """name -> (cfg, weights) of the wrong models the limits must fail: the
    experts chosen by the unbiased scores; the biased scores kept as
    weights; the convolution's taps in reverse; a convolution over two
    positions (a window of one) and over four (a window of three, the
    oldest tap twice); the ``C`` gate left out; the per-head QK-norm left
    out, and taken over the whole projection; the window dropped between
    chunks, and left unwritten by decode (:func:`short_conv`); every
    matrix rounded to int4 (the precision below the int8 the stack
    states). And last the router's products in bfloat16 (the precision
    below the float32 the configuration states for it), which no limit
    here CAN fail (the comment above :data:`TOL_MEDIAN` says why) and
    whose readings are taken all the same."""
    return {name: ({**cfg, "_wrong": name}, weights) for name in WRONG}


# -- the system ---------------------------------------------------------------

def system_logits(sched, tokens, n_prefill: int) -> SystemOut:
    """The system's logits through the programs the scheduler serves
    with, at the sizes it serves them. Both samples are admitted as an
    admission is: ``prefill_chunk_counted`` a chunk at a time over a dense
    carry (K and V of the attention layers, the convolution layers'
    windows in its ``state``), the last chunk padded and masked: the
    harness's sample as one chunk of its ``n_prefill`` positions, the long
    one (:func:`long_tokens`) as several of the scheduler's chunk. Then
    ALL their rows are installed in ONE pool of the scheduler's kind and
    of ``num_slots`` rows (K and V spliced into pages, windows into the
    state pool's rows; the first, a middle and the last slot, the other
    slots parked), and decode together: llama.decode_fused_aux, the scan
    ``jit_decode_fused_steps`` is, over the family's
    ``decode_step_paged_touched``, ``decode_fuse_max`` steps a dispatch,
    at the window the long row needs (64 pages of 64: past the
    flash-append boundary, so the short rows too are read by the kernel
    the cell's traffic runs, beside a row thirty times their length). The
    sampler hands back the sample's next token. Every program also hands
    out the experts its routers kept (``chosen``), left in
    :data:`_CHOSEN` for :func:`forward`."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from p2p_llm_chat_tpu.models.llama import KVCache, decode_fused_aux
    from p2p_llm_chat_tpu.ops.paged_kv import (PagedKVCache,
                                               write_prefill_batch)
    from p2p_llm_chat_tpu.ops.state_pool import write_rows
    model, params, config = sched._model, sched._params, sched.config
    mesh, ps = sched.mesh, sched.page_size
    f32 = jnp.float32
    B, T = tokens.shape
    D = T - n_prefill
    C = sched.prefill_chunk
    long = jnp.asarray(long_tokens(tokens, config.vocab_size, C))
    PL, DL = long_shape(C)
    slots, fuse = sched.num_slots, sched.decode_fuse_max
    if slots < B + 1 or D != DL:
        raise ValueError(f"the check decodes {B + 1} rows of {DL} steps in "
                         f"one batch: {slots} slots, {D} steps")
    rows = np.round(np.linspace(0, slots - 1, B + 1)).astype(np.int32)
    pages = 1
    while pages * ps < PL + DL + 1:
        pages *= 2

    @functools.partial(jax.jit, static_argnames=("offset", "keep"))
    def chunk(params, toks, valid, carry, *, offset, keep):
        logits, carry, (_, kept) = model.prefill_chunk_counted(
            params, config, toks, carry, offset, valid, mesh, chosen=True)
        return logits[:, jnp.asarray(keep, jnp.int32)].astype(f32), carry, \
            kept

    def ladder(P: int, C: int, keep=None) -> list:
        """(offset, positions in the chunk, of them kept) of each chunk
        of ``C`` that ``P`` positions take."""
        return [(off, min(C, P - off),
                 tuple(p - off for p in range(off, min(off + C, P))
                       if keep is None or p in keep))
                for off in range(0, -(-P // C) * C, C)]

    def blank(B: int, P: int, C: int):
        return KVCache.create(config, B, -(-P // C) * C, dtype=sched._dtype)

    def admit(tokens, P: int, C: int, keep=None):
        """``tokens`` [B, >= P] through chunks of ``C``: the logits (at
        positions ``keep`` when given), the carry, and the routers'
        choices [routed layers, B, P, k]."""
        carry = blank(tokens.shape[0], P, C)
        out, chosen = [], []
        for off, n, at in ladder(P, C, keep):
            toks = jnp.pad(tokens[:, off: off + n], ((0, 0), (0, C - n)))
            valid = jnp.broadcast_to(jnp.arange(C)[None, :] < n, toks.shape)
            logits, carry, kept = ahead.call(
                "chunk", chunk, params, toks, valid, carry, offset=off,
                keep=at)
            out.append(logits)
            chosen.append(kept[:, :, :n])
        return out, carry, jnp.concatenate(chosen, axis=2)

    @jax.jit
    def install(carry, long_carry):
        cache = PagedKVCache.create(config, slots, 1 + (B + 1) * pages, ps,
                                    max_pages_per_row=pages,
                                    dtype=sched._dtype,
                                    quantized=sched.kv_quant, mesh=mesh)
        tables = 1 + jnp.arange((B + 1) * pages,
                                dtype=jnp.int32).reshape(B + 1, pages)
        for c, at, n, table in ((carry, rows[:B], n_prefill, tables[:B]),
                                (long_carry, rows[B:], PL, tables[B:])):
            at = jnp.asarray(at)
            cache = write_prefill_batch(
                cache, c.k, c.v, at, jnp.full(at.shape, n, jnp.int32), table)
            cache = cache._replace(state=write_rows(cache.state, c.state,
                                                    at))
        return cache

    @functools.partial(jax.jit, donate_argnums=(2,),
                       static_argnames=("steps",))
    def decode(params, feed, cache, script, *, steps):
        """``steps`` fused steps from the input tokens ``feed`` [slots,
        1]; ``script`` [steps, slots]: the sample's token after each."""
        live = jnp.zeros((slots,), bool).at[rows].set(True)

        def step(params, config, toks, cache, mesh, rules, aux, *, active,
                 pages):
            i, logits_at, kept_at = aux
            logits, cache, (_, kept) = model.decode_step_paged_touched(
                params, config, toks, cache, mesh, rules, active,
                pages=pages, chosen=True)
            return logits, cache, (
                i + 1, logits_at.at[i].set(logits[rows, 0].astype(f32)),
                kept_at.at[i].set(kept[:, rows, 0]))

        def sample(logits, i, emit_pos, act):
            return script[i], i + 1

        aux = (jnp.zeros((), jnp.int32),
               jnp.zeros((steps, B + 1, config.vocab_size), f32),
               jnp.zeros((steps, config.hybrid_pattern.count("E"), B + 1,
                          config.num_experts_per_tok), jnp.int32))
        _, _, _, cache, _, _, (_, logits_at, kept_at) = decode_fused_aux(
            params, config, feed, cache, step, aux, mesh, active=live,
            num_steps=steps, sample_fn=sample,
            sample_state=jnp.zeros((), jnp.int32), stop_ids=(), pages=pages)
        return cache, logits_at, kept_at

    at = long_positions(C)
    long_keep = set(at[at < PL].tolist())
    # Every program of the check, to the threads (:class:`_Ahead`) before
    # the first is called: two ladders, the install, the fused decode.
    ahead = _Ahead()
    shape = jax.ShapeDtypeStruct
    carries = []
    for rows_, P, width, keep in ((B, n_prefill, n_prefill, None),
                                  (1, PL, C, long_keep)):
        carries.append(jax.eval_shape(lambda: blank(rows_, P, width)))
        for off, _, kept_at in ladder(P, width, keep):
            ahead.add("chunk", chunk, params,
                      shape((rows_, width), jnp.int32),
                      shape((rows_, width), bool), carries[-1], offset=off,
                      keep=kept_at)
    ahead.add("install", install, *carries)
    pool = jax.eval_shape(install, *carries)
    for n in {min(fuse, D - t) for t in range(0, D, fuse)}:
        ahead.add("decode", decode, params, shape((slots, 1), jnp.int32),
                  pool, shape((n, slots), jnp.int32), steps=n)
    out, carry, chosen = admit(tokens, n_prefill, n_prefill)
    long_out, long_carry, long_chosen = admit(long, PL, C, keep=long_keep)
    cache = ahead.call("install", install, carry, long_carry)
    # What each slot is fed at each step, and a row of zeros behind the
    # last: the sampler's answer to a step is the next step's input.
    feed = np.zeros((D + 1, slots), np.int32)
    feed[:D, rows[:B]] = np.asarray(tokens[:, n_prefill:]).T
    feed[:D, rows[B]] = np.asarray(long[0, PL:])
    feed = jnp.asarray(feed)
    steps_logits, steps_chosen = [], []
    for t in range(0, D, fuse):
        n = min(fuse, D - t)
        cache, logits_at, kept_at = ahead.call(
            "decode", decode, params, feed[t][:, None], cache,
            feed[t + 1: t + 1 + n], steps=n)
        steps_logits.append(jnp.swapaxes(logits_at, 0, 1))      # [B+1,n,V]
        steps_chosen.append(jnp.transpose(kept_at, (1, 2, 0, 3)))
    steps_logits = jnp.concatenate(steps_logits, axis=1)
    steps_chosen = jnp.concatenate(steps_chosen, axis=2)        # [E,B+1,D,k]

    def flat(chosen):
        return chosen.reshape(chosen.shape[0], -1, chosen.shape[-1])

    _CHOSEN.clear()
    _CHOSEN[_digest(tokens)] = (
        n_prefill,
        flat(jnp.concatenate([chosen, steps_chosen[:, :B]], axis=2)),
        flat(jnp.concatenate([long_chosen, steps_chosen[:, B:]], axis=2)))
    return SystemOut(
        logits=jnp.concatenate([*out, steps_logits[:B]], axis=1),
        long_logits=jnp.concatenate([*long_out, steps_logits[B:]], axis=1))


def compare(system: SystemOut, reference_logits, facts: dict,
            cfg: dict) -> dict:
    """reference.compare's numbers on the harness's sample, the long
    sample's beside them (``long_*``), the worst of the decode steps and
    of the positions that open a chunk behind a carried window
    (``decode_max``, ``starts_max``: which positions a failed ``max`` is
    about), the routers' (``flips``: the share of (token, routed layer)
    pairs where the rule's choice is not the system's), and the verdict:
    both medians, both worst positions and the flips each under its
    limit (:data:`TOL_MEDIAN` and the comment above it), on logits that
    were replayed."""
    import jax.numpy as jnp
    import numpy as np
    from benchmark import reference
    out = reference.compare(system.logits, reference_logits, routed=True)
    P, chunk = facts["n_prefill"], check_chunk(cfg)
    err = reference.position_errors(system.logits, reference_logits)
    long_err = reference.position_errors(system.long_logits,
                                         facts["long_logits"]).reshape(-1)
    at, PL = long_positions(chunk), long_shape(chunk)[0]
    decode = jnp.concatenate([err[:, P:].reshape(-1),
                              long_err[np.flatnonzero(at >= PL)]])
    # Behind the first chunk: where the window read is a carried one.
    starts = long_err[np.flatnonzero(
        (at >= chunk) & (at < PL) & (at % chunk < cfg["conv_L_cache"]))]
    flipped = jnp.concatenate([facts["flipped"].reshape(-1),
                               facts["long_flipped"].reshape(-1)])
    margin = jnp.concatenate([facts["margin"].reshape(-1),
                              facts["long_margin"].reshape(-1)])
    out.update(
        long_median=float(jnp.median(long_err)),
        long_p90=float(jnp.percentile(long_err, 90)),
        long_max=float(jnp.max(long_err)),
        decode_median=float(jnp.median(decode)),
        decode_max=float(jnp.max(decode)),
        starts_max=float(jnp.max(starts)) if starts.size else 0.0,
        replayed=bool(facts["replayed"]),
        flips=float(jnp.mean(flipped)),
        # Pairs whose k-th and next biased scores lie within 0.01: where
        # a flip is likely, for the record.
        close_calls=float(jnp.mean(margin < 0.01)),
        min_margin=float(jnp.min(margin)))
    limits = {"median": TOL_MEDIAN, "long_median": TOL_MEDIAN,
              "max": TOL_MAX, "long_max": TOL_MAX, "flips": TOL_FLIPS}
    out["ok"] = bool(
        facts["replayed"] and jnp.isfinite(err).all()
        and jnp.isfinite(long_err).all()
        and all(out[name] <= limit for name, limit in limits.items()))
    out["tolerance"] = limits
    return out


# -- what a step must move and a prompt must compute (JAX-free) ---------------

def _q8(n_in: int, n_out: int) -> float:
    """Bytes of an int8 [n_in, n_out] weight with a float32 scale a
    column (benchmark/roofline.py's count)."""
    return n_in * n_out + 4 * n_out


def layer_counts(cfg: dict) -> dict:
    kinds = layer_kinds(cfg)
    nd = cfg["num_dense_layers"]
    return {"conv": kinds.count("conv"), "attn": kinds.count("attn"),
            "dense": nd, "routed": len(kinds) - nd}


def layer_shapes(cfg: dict) -> dict:
    """[in, out] of every matrix of each kind of operator and
    feed-forward (of ONE expert), as published."""
    H, F, Fd, D = (cfg["hidden_size"], cfg["moe_intermediate_size"],
                   cfg["intermediate_size"], head_dim(cfg))
    Q, KV = cfg["num_attention_heads"] * D, cfg["num_key_value_heads"] * D
    return {"conv": [(H, 3 * H), (H, H)],
            "attn": [(H, Q + 2 * KV), (Q, H)],
            "dense": [(H, 2 * Fd), (Fd, H)],
            "expert": [(H, 2 * F), (F, H)]}


def parameter_count(cfg: dict) -> tuple:
    """(all, active a token) parameters, the tied head counted once."""
    n, shapes = layer_counts(cfg), layer_shapes(cfg)
    H, NE, k = (cfg["hidden_size"], cfg["num_experts"],
                cfg["num_experts_per_tok"])

    def size(kind):
        return sum(a * b for a, b in shapes[kind])

    norms = 2 * H * cfg["num_hidden_layers"] + H
    fixed = (n["conv"] * (size("conv") + cfg["conv_L_cache"] * H)
             + n["attn"] * (size("attn") + 2 * head_dim(cfg))
             + n["dense"] * size("dense") + n["routed"] * (H * NE + NE)
             + cfg["vocab_size"] * H + norms)
    return (fixed + n["routed"] * NE * size("expert"),
            fixed + n["routed"] * k * size("expert"))


def page_token_bytes(cfg: dict) -> float:
    """One position of ONE attention layer in the int8 page pool: K and V
    of every KV head, and a float32 scale for each PAIR of heads (the
    pool keeps its KV heads in pairs, 128 numbers a row)."""
    kvh = cfg["num_key_value_heads"]
    return 2.0 * (kvh * head_dim(cfg) + 4 * (kvh // 2))


def window_row_bytes(cfg: dict) -> float:
    """One row's convolution windows over all conv layers, bf16."""
    return (2.0 * layer_counts(cfg)["conv"] * (cfg["conv_L_cache"] - 1)
            * cfg["hidden_size"])


def decode_step_bytes(cfg: dict, rows: float, context: float) -> float:
    """Bytes one decode step has to move: every operator's and dense
    feed-forward's matrices once, the taps, the routers in float32; the
    experts a step reaches (all of them once ``rows x
    num_experts_per_tok`` passes the count, else that many); the head in
    int8; the rows' embeddings in bf16; each live row's convolution
    windows read and written; and each row's context in every attention
    layer's pages.

    The experts are an UPPER bound: which of them a step's rows reach is
    the router's to say, and under this configuration's seeded weights 25
    rows reach about a fifth (``moe_touched_share`` 18.2% over a traced
    window and its drain, and a fused step takes 8.3 ms on the device
    where this count would need 11.1 at the chip's 819 GB/s: PERF.md
    section 7(xviii)). The reader of ``decode_bw_util_family`` hands over
    rows and a context and no count of experts reached, so the cell is
    not on that metric's list; ``page_step_share`` and
    ``state_step_share``, which divide by this, under-read by as much as
    it over-counts."""
    n, shapes = layer_counts(cfg), layer_shapes(cfg)
    H, NE = cfg["hidden_size"], cfg["num_experts"]
    reached = min(NE, rows * cfg["num_experts_per_tok"])

    def size(kind):
        return sum(_q8(*s) for s in shapes[kind])

    weights = (n["conv"] * (size("conv") + 2.0 * cfg["conv_L_cache"] * H)
               + n["attn"] * size("attn") + n["dense"] * size("dense")
               + n["routed"] * (4.0 * H * NE + reached * size("expert")))
    return (weights + _q8(H, cfg["vocab_size"]) + rows * 2 * H
            + 2.0 * rows * window_row_bytes(cfg)
            + n["attn"] * rows * context * page_token_bytes(cfg))


def prefill_flops(cfg: dict, tokens: float, context_pairs: float) -> float:
    """FLOPs the prompt positions require: two a parameter a token for
    every operator's and feed-forward's matrices, the router and the
    ``num_experts_per_tok`` experts a token reaches, the convolution's
    taps and gates; and the attention layers' causal pairs. The head runs
    for one position a request and is left out."""
    n, shapes = layer_counts(cfg), layer_shapes(cfg)
    H = cfg["hidden_size"]

    def size(kind):
        return sum(a * b for a, b in shapes[kind])

    per_token = 2.0 * (
        n["conv"] * (size("conv") + (cfg["conv_L_cache"] + 1) * H)
        + n["attn"] * size("attn") + n["dense"] * size("dense")
        + n["routed"] * (H * cfg["num_experts"]
                         + cfg["num_experts_per_tok"] * size("expert")))
    pair = 4.0 * cfg["num_attention_heads"] * head_dim(cfg)
    return tokens * per_token + n["attn"] * context_pairs * pair


def flash_append_cost(cfg: dict, rows: float, context: float) -> tuple:
    """(FLOPs, bytes) of ONE decode step's attention over the pages, all
    attention layers, as ops/paged_attention's flash-append kernel does it
    on the paired pool: every cached position of every live row read once
    (K, V and their scales), and each query's dot with a PAIR's 128-wide
    row, scores and values alike: twice the head's own arithmetic."""
    n = layer_counts(cfg)["attn"]
    pair_row = 2 * head_dim(cfg)
    flops = n * rows * context * 4.0 * cfg["num_attention_heads"] * pair_row
    return flops, n * rows * context * page_token_bytes(cfg)
