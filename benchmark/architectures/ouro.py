"""The Ouro family's architecture file (Ouro-2.6B, ``model_type: ouro``;
"Scaling Latent Reasoning via Looped Language Models", arXiv:2510.25741):
a dense decoder whose whole stack of layers is walked ``total_ut_steps``
times for every token with ONE set of weights. The contract is in
benchmark/manifest.py's docstring.

**The model, as :func:`forward` computes it** (float32,
``jax.default_matmul_precision("highest")``; ``N`` an RMSNorm with its own
learned weight, eps ``rms_norm_eps``; no biases but the gate's). The
configuration file's ``assumed`` says which lines come from the family's
released modelling file and not from ``config.json``::

    h = E[tokens]                                 (no scaling)
    for t in 0 .. total_ut_steps - 1:
      for l in 0 .. num_hidden_layers - 1:
        a = Attn_l(N1_l(h))      plain multi-head attention, q and k rotated
                                 (rotate-half, rope_theta, position p),
                                 causal over THIS pass's keys
        h = h + N2_l(a W_o)      the norm on the branch's OUTPUT
        m = (silu(N3_l(h) W_g) * (N3_l(h) W_u)) W_d
        h = h + N4_l(m)
      h = N_f(h)                 after EVERY pass; enters pass t + 1
      g_t = sigmoid(h . w_gate + b_gate)
    logits = h W_head            the last pass's, always (threshold 1)
    exit pdf: p_t = g_t prod_{s<t} (1 - g_s), the last pass what is left

No cache, no kernels, nothing imported from the program: a pass is a
Python loop over the layers, each layer one jitted call on weights the
caller dequantises a layer at a time, so the reference fits beside a
serving model (2 x 136 positions: 192 layer calls).

**Two samples.** The harness's 2 x (128 + 8) tokens stay below every
boundary of the cell's own traffic: their decode window is 256, where the
gather form serves, and their prefill is one causal forward. So
:func:`system_logits` and :func:`forward` both derive from them ONE long
sequence (:func:`long_tokens`: two whole chunks and 11 sixteenths of a
third, 688 + 8 at the cell's chunk of 256), which the system admits as
the scheduler's ladder admits a prompt (``prefill_chunk`` over a donated
carry, the last chunk padded, each chunk spliced into the pool) and
decodes beside the short rows at the 1,024 window, where the flash-append
kernel reads the pages: the programs the cell's window runs.

Also here, JAX-free, what a step must move and a prompt must compute
(:func:`decode_step_bytes`, :func:`prefill_flops`, :func:`stack_bytes`,
:func:`page_token_bytes`). Readers run in the parent of a run, which
never imports JAX: this module imports it inside the functions only the
child calls.
"""

from __future__ import annotations

import functools
from typing import Callable, NamedTuple

# The limits, each between two kinds of reading on a v5e at the published
# widths (48 layers x 4 passes, int8 weights, int8 page pool;
# tools/check_reference_limit.py; my chip run, PR 53, call E; the
# harness's sample of 2 x (128 + 8) and the long sample of 688 + 8; sample
# seeds 53, 1, 2, 3 for the sound program, 53 and 1 for each wrong model
# of :func:`wrong_models` against the same system logits). PERF.md section
# 6, PR 53, has every reading.
#
# A looped stack under RANDOM weights amplifies a rounding. Every branch
# hands the residual stream a unit-RMS vector (its output norm), a SwiGLU
# of random projections doubles the angular variance of what it is given,
# and the final norm resets the stream's scale before each pass: an error
# grows about 2.7 times a pass. bf16's rounding in the first pass is thus
# a quarter of the logits' spread after the fourth, in a program that
# computes the reference's function to 4e-5 where its activations are
# float32 (48 layers x 4 passes at a width of 256 on the CPU: 3.1% after
# one pass, 8.6% after two, 46% after four in bfloat16; 4.0e-5 in float32:
# PERF.md). A trained stack is no such amplifier, and a serving system in
# bf16 cannot read closer to float32 than this on these weights; so the
# limits here are loose where Mistral's are tight, and every wrong model
# still reads three to six times the sound program.
#
# TOL_MEDIAN, on the median position error of the logits
# (reference.position_errors): ``median``, the harness's sample, prefill
# positions (one causal forward) and decode steps together; and
# ``long_median``, the long sample's compared prefill positions, which the
# chunk ladder computed (three chunks of 256 over a donated carry, the
# last padded). Sound: 0.246, 0.294, 0.280, 0.202 and 0.184, 0.257, 0.212,
# 0.256. Wrong: no rotation 0.748-0.809 and 0.891-0.909; three passes
# 1.170-1.188 and 1.171-1.206; int4 weights (the precision below the stack's int8),
# the pre-norms alone, the final norm once and one pass 1.354-1.422 in
# both (two unrelated logit vectors read 1.41). The limit is 1.53 times
# the largest sound reading and 0.60 of the smallest wrong one. The
# passes sharing one cache layer read 0.247 and 0.184 here: their prefill
# IS the sound program's, and the next limit holds them.
#
# TOL_DECODE, on ``decode_max`` and ``long_decode_max``: the worst of the
# 2 x 8 decode steps of the harness's sample and of the long row's 8, all
# three rows together in the fused scan at the cell's 32 slots and the
# 1,024 window (the flash-append kernel from W 512: the long row reads
# 689-696 positions of each of its OWN 192 cache layers, the short rows
# 129-136 beside it). Sound: 0.250, 0.293, 0.284, 0.197 and 0.168, 0.240,
# 0.197, 0.243. Wrong: the passes sharing one cache layer a weight layer
# (the paper's last-pass reuse) 0.747-0.758 and 0.703-0.744; every other
# wrong model 0.857-1.443 and 0.951-1.433. 1.54 times the largest sound
# reading, 0.64 of the smallest wrong one.
#
# TOL_EXIT, on ``exit_max``: the largest absolute difference between the
# system's exit pdf and the reference's, over every prefill position and
# pass of the harness's sample, and over the decode steps' mass (the pdf
# summed over the three rows, as the scheduler's counter sums it over live
# rows) a row. Sound: 0.026, 0.045, 0.052, 0.028. Wrong: the shared cache
# 0.211, no rotation 0.277, the final norm once 0.324, three passes 0.377,
# int4 0.407, the pre-norms alone 0.696, one pass 0.894. 2.3 times the
# largest sound reading, 0.57 of the smallest wrong one.
#
# The worst position of all (``max``, and ``long_max`` of the long
# sample's prefill) is reported and not held: the amplifier's tail swings
# with the sample (0.372, 0.511, 0.427, 0.316; 0.307, 0.544, 0.332, 0.533).
TOL_MEDIAN = 0.45
TOL_DECODE = 0.45
TOL_EXIT = 0.12

REF_PREFILL = 128       # benchmark/serve_cell.py's; ``_n_prefill`` overrides


# -- the configuration --------------------------------------------------------

def model_config(cfg: dict) -> dict:
    """``ModelConfig``'s keywords from the published field names. The
    published ``early_exit_threshold`` is 1: every token runs every pass
    and the logits are the last pass's. A row that leaves the loop early
    is not built (ROADMAP.md, Reach), so another threshold is refused by
    name and not served as if it were 1."""
    from benchmark.manifest import ManifestError
    if cfg.get("early_exit_threshold", 1) != 1:
        raise ManifestError(
            f"{cfg.get('name')}: early_exit_threshold "
            f"{cfg['early_exit_threshold']} asks for rows that leave the "
            f"loop before pass {cfg.get('total_ut_steps')}; the program "
            f"runs every pass for every token (threshold 1) and has no "
            f"path that exits early")
    heads = cfg["num_attention_heads"]
    return dict(
        name=cfg["name"], vocab_size=cfg["vocab_size"],
        hidden_size=cfg["hidden_size"],
        intermediate_size=cfg["intermediate_size"],
        num_layers=cfg["num_hidden_layers"], num_heads=heads,
        num_kv_heads=cfg["num_key_value_heads"],
        head_dim=cfg.get("head_dim") or cfg["hidden_size"] // heads,
        max_seq_len=cfg["max_position_embeddings"],
        rope_theta=float(cfg["rope_theta"]), rope_scaling=None,
        rms_norm_eps=cfg["rms_norm_eps"],
        tie_embeddings=bool(cfg.get("tie_word_embeddings", False)),
        sandwich_norm=True, ut_steps=cfg["total_ut_steps"],
        bos_token_id=cfg.get("bos_token_id", 1),
        eos_token_ids=())       # ignore_eos: see the configuration file


class Weights(NamedTuple):
    """What :func:`forward` is handed: float32, one layer at a time."""

    embed: object
    layer: Callable             # l -> dict of the layer's weights
    final_norm: object
    gate_w: object              # [H]
    gate_b: object              # []
    lm_head: object


class SystemOut(NamedTuple):
    logits: object              # [B, P + D, V]
    exit_pdf: object            # [B, P, T]: the prefill positions'
    exit_mass: object           # [D, T]: each decode step's, over the rows
    long_logits: object         # [1, n, V]: the long sample's compared ones


_NORMS = ("attn_norm", "attn_out_norm", "mlp_norm", "mlp_out_norm")
_MATRICES = ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down")


def engine_weights(sched) -> Weights:
    """The engine's own int8 tree, dequantised one layer at a time (int8
    x float32 scale is exact in float32). One chip: the fused leaves are
    the plain ``[q | k | v]`` and ``[gate | up]`` (a looped model is
    refused under a mesh)."""
    import jax
    import jax.numpy as jnp
    params, config = sched._params, sched.config
    layers = params["layers"]
    Q, KV, E = config.q_dim, config.kv_dim, config.intermediate_size
    f32 = jnp.float32

    def deq(w):
        return (w.q.astype(f32) * w.s.astype(f32) if hasattr(w, "q")
                else w.astype(f32))

    # The tree is an argument, never a closure (a closure bakes the
    # weights into the program as constants).
    @jax.jit
    def _layer_weights(layers, layer):
        take = lambda a: jax.lax.dynamic_index_in_dim(a, layer, 0, False)
        one = {k: jax.tree.map(take, layers[k]) for k in layers}
        qkv, gu = deq(one["wqkv"]), deq(one["wgu"])
        w = {k: one[k].astype(f32) for k in _NORMS}
        w.update(wq=qkv[:, :Q], wk=qkv[:, Q: Q + KV], wv=qkv[:, Q + KV:],
                 wo=deq(one["wo"]), w_gate=gu[:, :E], w_up=gu[:, E:],
                 w_down=deq(one["w_down"]))
        return w

    return Weights(
        embed=params["embed"], layer=lambda l: _layer_weights(layers, l),
        final_norm=params["final_norm"].astype(f32),
        gate_w=params["exit_gate_w"].astype(f32)[:, 0],
        gate_b=params["exit_gate_b"].astype(f32)[0],
        lm_head=deq(params["lm_head"]))


# -- the plain reference ------------------------------------------------------

def _q4(w):
    """``w`` [in, out] rounded to 4 signed bits a column (absmax scale):
    the precision below the int8 the stack states."""
    import jax.numpy as jnp
    scale = jnp.max(jnp.abs(w), axis=0, keepdims=True) / 7.0
    return jnp.round(w / jnp.where(scale > 0, scale, 1.0)) * scale


@functools.lru_cache(maxsize=None)
def _jitted(heads: int, kvh: int, d: int, theta: float, eps: float,
            rotate: bool, sandwich: bool):
    """The reference's jitted pieces for one geometry."""
    import jax
    import jax.numpy as jnp
    from benchmark.reference import rms_norm, rope

    def attend(q, k, v, q_pos, k_pos):
        """One sequence. q [Tq, heads, d] at positions ``q_pos``; k, v
        [Tk, kvh, d] at ``k_pos``; a query sees the keys at or before
        it."""
        k = jnp.repeat(k, heads // kvh, axis=1)
        v = jnp.repeat(v, heads // kvh, axis=1)
        s = jnp.einsum("qhd,khd->hqk", q, k) / jnp.sqrt(jnp.float32(d))
        s = jnp.where(q_pos[None, :, None] >= k_pos[None, None, :], s,
                      -jnp.inf)
        return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), v)

    @jax.jit
    def layer(h, w, pos, past_k, past_v, past_pos):
        """h [B, T, H] at positions ``pos`` [T]; ``past_*`` the K and V
        (and positions) the queries see beside this call's own ([B, 0,
        ..] for none). Returns (h, this call's k, v)."""
        with jax.default_matmul_precision("highest"):
            B, T, _ = h.shape
            x = rms_norm(h, w["attn_norm"], eps)
            q = (x @ w["wq"]).reshape(B, T, heads, d)
            k = (x @ w["wk"]).reshape(B, T, kvh, d)
            v = (x @ w["wv"]).reshape(B, T, kvh, d)
            if rotate:
                q = jax.vmap(lambda a: rope(a, pos, theta))(q)
                k = jax.vmap(lambda a: rope(a, pos, theta))(k)
            k_all = jnp.concatenate([past_k, k], axis=1)
            v_all = jnp.concatenate([past_v, v], axis=1)
            k_pos = jnp.concatenate([past_pos, pos])
            a = jax.vmap(lambda q, k, v: attend(q, k, v, pos, k_pos))(
                q, k_all, v_all).reshape(B, T, heads * d) @ w["wo"]
            if sandwich:
                a = rms_norm(a, w["attn_out_norm"], eps)
            h = h + a
            x = rms_norm(h, w["mlp_norm"], eps)
            m = (jax.nn.silu(x @ w["w_gate"]) * (x @ w["w_up"])) @ w["w_down"]
            if sandwich:
                m = rms_norm(m, w["mlp_out_norm"], eps)
            return h + m, k, v

    @jax.jit
    def after_pass(h, final_norm, gate_w, gate_b):
        with jax.default_matmul_precision("highest"):
            n = rms_norm(h, final_norm, eps)
            return n, jax.nn.sigmoid(n @ gate_w + gate_b)

    @jax.jit
    def head(h, lm_head):
        with jax.default_matmul_precision("highest"):
            return h @ lm_head

    return layer, after_pass, head


def exit_pdf(gates):
    """[T, ...] gates -> [..., T]: p_t = g_t prod_{s<t} (1 - g_s), the
    last pass what is left."""
    import jax.numpy as jnp
    out, stay = [], jnp.ones_like(gates[0])
    for t in range(gates.shape[0] - 1):
        out.append(gates[t] * stay)
        stay = stay * (1.0 - gates[t])
    return jnp.stack(out + [stay], axis=-1)


# -- the long sample ----------------------------------------------------------

LONG_DECODE = 8
LONG_STRIDE = 8         # prefill positions of the long sample compared


def check_chunk(cfg: dict) -> int:
    """The chunk the check's long sample is laid out for: the stack's."""
    return int(cfg.get("stack", {}).get("SERVE_PREFILL_CHUNK", 256))


def long_shape(chunk: int) -> tuple:
    """(prefill positions, decode steps) of the long sample at a chunk of
    ``chunk``: two whole chunks and 11/16 of a third, which is padded
    (688 + 8 at the cell's 256: past the flash-append boundary of a pool
    of 16 heads x 128, 512, in the 1,024 window, where the cell's decode
    runs; the dense carry of a longer row does not fit beside the cell's
    pool and the check's own)."""
    return 2 * chunk + 11 * chunk // 16, LONG_DECODE


def long_tokens(tokens, vocab: int, chunk: int):
    """The long sample [1, P + D], drawn from a seed the harness's tokens
    give: the same for system and reference, another every ``--seed``."""
    import numpy as np
    seed = int(np.asarray(tokens).astype(np.int64).sum()) % (2 ** 31)
    return np.random.default_rng(seed).integers(
        0, vocab, size=(1, sum(long_shape(chunk)))).astype(np.int32)


def long_positions(chunk: int):
    """The long sample's compared positions: every LONG_STRIDE-th of the
    prefill, the first of every chunk, the prefill's last, and every
    decode step."""
    import numpy as np
    P, D = long_shape(chunk)
    return np.unique(np.concatenate([np.arange(0, P, LONG_STRIDE),
                                     np.arange(0, P, chunk),
                                     np.arange(P - 1, P + D)]))


def _stack(cfg: dict, tokens, weights: Weights, n_prefill: int, at=None):
    """(logits [B, n, V] at positions ``at`` (None: every one), exit pdf
    [B, T, passes]) of ``tokens`` [B, T]. ``n_prefill`` is where the
    sample's decode steps begin, which only ``shared_cache`` reads."""
    import jax
    import jax.numpy as jnp
    wrong = cfg.get("_wrong", "")
    heads, kvh = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    d = cfg.get("head_dim") or cfg["hidden_size"] // heads
    L = cfg["num_hidden_layers"]
    passes = {"one_pass": 1, "three_passes": 3}.get(
        wrong, cfg["total_ut_steps"])
    layer, after_pass, head = _jitted(
        heads, kvh, d, float(cfg["rope_theta"]), cfg["rms_norm_eps"],
        wrong != "no_rotation", wrong != "pre_norms_only")

    def layer_weights(l):
        w = weights.layer(l)
        if wrong == "int4_weights":
            w = {k: _q4(v) if k in _MATRICES else v for k, v in w.items()}
        return w

    B, T = tokens.shape
    f32 = jnp.float32
    with jax.default_matmul_precision("highest"):
        h = weights.embed[tokens].astype(f32)
    none = (jnp.zeros((B, 0, kvh, d), f32), jnp.zeros((B, 0, kvh, d), f32),
            jnp.zeros((0,), jnp.int32))
    # ``shared_cache``: the paper's last-pass reuse. The passes of a
    # DECODE step read, for the positions before it, the K and V the LAST
    # pass left (one cache layer a weight layer, written by pass after
    # pass), and their own for the step's position. The prefill positions
    # run as the sound model's.
    P = T if wrong != "shared_cache" else min(T, n_prefill)
    pos = jnp.arange(P, dtype=jnp.int32)
    gates, last_k, last_v = [], [None] * L, [None] * L
    hp = h[:, :P]
    for t in range(passes):
        for l in range(L):
            hp, k, v = layer(hp, layer_weights(l), pos, *none)
            # One layer's float32 weights on the chip at a time: the host
            # runs far ahead of the device otherwise, and every layer it
            # has dequantised (206 MB at the published widths) waits in
            # memory beside the serving model's pool (my chip run, PR 53,
            # call G: 15.73 GB of the chip's 15.75 at the peak).
            hp.block_until_ready()
            if P < T and t == passes - 1:
                last_k[l], last_v[l] = k, v
        n, g = after_pass(hp, weights.final_norm, weights.gate_w,
                          weights.gate_b)
        gates.append(g)
        if wrong != "final_norm_once":
            hp = n
    out_h = [n]
    gates = [jnp.stack(gates)]                          # [passes, B, P]
    for p in range(P, T):
        hd = h[:, p: p + 1]
        here = jnp.asarray([p], jnp.int32)
        step_gates = []
        for t in range(passes):
            for l in range(L):
                hd, k, v = layer(hd, layer_weights(l), here, last_k[l],
                                 last_v[l], jnp.arange(p, dtype=jnp.int32))
                hd.block_until_ready()
                if t == passes - 1:
                    last_k[l] = jnp.concatenate([last_k[l], k], axis=1)
                    last_v[l] = jnp.concatenate([last_v[l], v], axis=1)
            hd, g = after_pass(hd, weights.final_norm, weights.gate_w,
                               weights.gate_b)
            step_gates.append(g)
        out_h.append(hd)
        gates.append(jnp.stack(step_gates))
    gates = jnp.concatenate(gates, axis=2)              # [passes, B, T]
    pdf = exit_pdf(gates)
    if pdf.shape[-1] < cfg["total_ut_steps"]:
        pdf = jnp.pad(pdf, ((0, 0), (0, 0),
                            (0, cfg["total_ut_steps"] - pdf.shape[-1])))
    out_h = jnp.concatenate(out_h, axis=1)
    if at is not None:
        out_h = out_h[:, jnp.asarray(at)]
    return head(out_h, weights.lm_head), pdf


def forward(cfg: dict, tokens, weights: Weights) -> tuple:
    """Logits [B, T, V] (float32) of ``tokens`` [B, T], every position,
    and ``facts``: ``exit_pdf`` [B, T, passes], and of the long sample
    (:func:`long_tokens`) its logits at its compared positions
    (``long_logits`` [1, n, V]) and its decode steps' pdf
    (``long_exit_pdf`` [1, D, passes]). ``cfg["_wrong"]`` names a wrong
    model of :func:`wrong_models`."""
    import jax.numpy as jnp
    logits, pdf = _stack(cfg, tokens, weights,
                         cfg.get("_n_prefill", REF_PREFILL))
    chunk = check_chunk(cfg)
    PL = long_shape(chunk)[0]
    long = jnp.asarray(long_tokens(tokens, cfg["vocab_size"], chunk))
    long_logits, long_pdf = _stack(cfg, long, weights, PL,
                                   long_positions(chunk))
    return logits, {"exit_pdf": pdf, "long_logits": long_logits,
                    "long_exit_pdf": long_pdf[:, PL:]}


WRONG = ("one_pass", "three_passes", "shared_cache", "final_norm_once",
         "pre_norms_only", "no_rotation", "int4_weights")


def wrong_models(cfg: dict, weights: Weights) -> dict:
    """name -> (cfg, weights) of the reference as each wrong model, every
    one of which :func:`compare` must fail: the stack walked once, or
    three times; the passes sharing ONE cache layer a weight layer (the
    paper's last-pass reuse for decoding: a different function from the
    released default, kept here so that no later change slips into it);
    the final norm once at the end and not after every pass; the
    pre-norms alone (no norm on either branch's output); no rotation;
    and every matrix rounded to int4, the precision below the int8 the
    stack states."""
    return {name: ({**cfg, "_wrong": name}, weights) for name in WRONG}


# -- the system ---------------------------------------------------------------

def system_logits(sched, tokens, n_prefill: int) -> SystemOut:
    """The system's logits and exit distribution through the model
    functions the scheduler serves with, on its tree, at the sizes the
    cell's traffic runs them.

    The harness's sample, its first ``n_prefill`` positions: one causal
    forward over a dense cache of ``cache_layers`` layers
    (``forward_exit``: ``prefill`` with the pdf beside the logits),
    spliced into a paged pool of the scheduler's kind and of
    ``num_slots`` rows (the first and a middle slot, the others parked).

    The long sample (:func:`long_tokens`), as the scheduler's ladder
    admits a prompt (``_make_prefill_chunk_program``): ``prefill_chunk``
    a chunk of ``sched.prefill_chunk`` at a time over a dense carry, the
    last chunk padded; the first program creates the carry, every later
    one takes it donated, runs under the ladder's ``cond`` on "some
    position is real" and hands it back, the form the ladder's LAST
    chunk has for a carry of this size (``_CARRY_IN_PLACE_BYTES``); every
    chunk splices its K and V into the pool (``write_prefill_chunk``) and
    the last installs the row's table and length. The last slot.

    Then ALL the rows decode together through llama.decode_fused_aux, the
    scan ``jit_decode_fused_steps`` is, over ``decode_step_paged_exit``,
    ``decode_fuse_max`` steps a dispatch, at the window the long row
    needs (16 pages of 64: past the flash-append boundary, so the short
    rows too are read by the kernel the cell's decode runs, beside a row
    five times their length). The sampler hands back the sample's next
    token, and a step leaves its logits and its exit mass (the pdf summed
    over the live rows, as the scheduler counts it)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from p2p_llm_chat_tpu.models.layers import causal_mask
    from p2p_llm_chat_tpu.models.llama import KVCache, decode_fused_aux
    from p2p_llm_chat_tpu.ops.paged_kv import (PagedKVCache,
                                               write_prefill_batch,
                                               write_prefill_chunk)
    model, params, config = sched._model, sched._params, sched.config
    mesh, ps = sched.mesh, sched.page_size
    f32 = jnp.float32
    B, T = tokens.shape
    P, D = n_prefill, T - n_prefill
    C = sched.prefill_chunk
    long = jnp.asarray(long_tokens(tokens, config.vocab_size, C))
    PL, DL = long_shape(C)
    slots, fuse = sched.num_slots, sched.decode_fuse_max
    if slots < B + 1 or D != DL:
        raise ValueError(f"the check decodes {B + 1} rows of {DL} steps in "
                         f"one batch: {slots} slots, {D} steps")
    rows = np.round(np.linspace(0, slots - 1, B + 1)).astype(np.int32)
    pages = 1
    while pages * ps < max(T, PL + DL) + 1:
        pages *= 2
    # A row holds the pages its positions need, as a reservation does; the
    # rest of its table is the garbage page.
    held = [-(-(T + 1) // ps)] * B + [-(-(PL + DL + 1) // ps)]
    tables = np.zeros((B + 1, pages), np.int32)
    for r, n in enumerate(held):
        tables[r, :n] = 1 + sum(held[:r]) + np.arange(n)
    tables = jnp.asarray(tables)
    W = -(-PL // C) * C                 # the long row's carry

    @jax.jit
    def prefill(params, toks):
        small = KVCache.create(config, B, P, dtype=sched._dtype)
        positions = jnp.broadcast_to(jnp.arange(P)[None, :], (B, P))
        logits, small, pdf = model.forward_exit(
            params, config, toks, positions, small, causal_mask(P, P, 0),
            mesh, causal0=True)
        cache = PagedKVCache.create(config, slots, 1 + sum(held), ps,
                                    max_pages_per_row=pages,
                                    dtype=sched._dtype,
                                    quantized=sched.kv_quant, mesh=mesh)
        cache = write_prefill_batch(cache, small.k, small.v,
                                    jnp.asarray(rows[:B]),
                                    jnp.full((B,), P, jnp.int32), tables[:B])
        return logits.astype(f32), pdf, cache

    def ladder_chunk(params, toks, real, carry, cache, offset, keep):
        """The long row's chunk at ``offset`` (``real``: some position of
        it is one): (carry, its logits at the chunk's positions ``keep``,
        the pool with the chunk's K and V)."""
        def run(carry, kept):
            logits, carry = model.prefill_chunk(params, config, toks, carry,
                                                offset, mesh)
            return carry, logits[:, jnp.asarray(keep, jnp.int32)].astype(f32)

        kept = jnp.zeros((1, len(keep), config.vocab_size), f32)
        if carry is None:
            carry, kept = run(KVCache.create(config, 1, W,
                                             dtype=sched._dtype), kept)
        else:
            carry, kept = jax.lax.cond(real, run, lambda *c: c, carry, kept)
        cache = write_prefill_chunk(
            cache, carry.k[:, :, offset: offset + C],
            carry.v[:, :, offset: offset + C], tables[B:], offset)
        if offset + C == W:
            cache = cache._replace(
                page_table=cache.page_table.at[rows[B]].set(tables[B]),
                lengths=cache.lengths.at[rows[B]].set(PL))
        return carry, kept, cache

    @functools.partial(jax.jit, donate_argnums=(2,),
                       static_argnames=("keep",))
    def first(params, toks, cache, *, keep):
        return ladder_chunk(params, toks, True, None, cache, 0, keep)

    @functools.partial(jax.jit, donate_argnums=(3, 4),
                       static_argnames=("offset", "keep"))
    def later(params, toks, real, carry, cache, *, offset, keep):
        return ladder_chunk(params, toks, real, carry, cache, offset, keep)

    @functools.partial(jax.jit, donate_argnums=(2,),
                       static_argnames=("steps",))
    def decode(params, feed, cache, script, *, steps):
        """``steps`` fused steps from the input tokens ``feed`` [slots,
        1]; ``script`` [steps, slots]: the sample's token after each."""
        live = jnp.zeros((slots,), bool).at[rows].set(True)

        def step(params, config, toks, cache, mesh, rules, aux, *, active,
                 pages):
            i, logits_at, mass_at = aux
            logits, cache, mass = model.decode_step_paged_exit(
                params, config, toks, cache, mesh, rules, None, active,
                pages=pages)
            return logits, cache, (
                i + 1, logits_at.at[i].set(logits[rows, 0].astype(f32)),
                mass_at.at[i].set(mass))

        def sample(logits, i, emit_pos, act):
            return script[i], i + 1

        aux = (jnp.zeros((), jnp.int32),
               jnp.zeros((steps, B + 1, config.vocab_size), f32),
               jnp.zeros((steps, config.ut_steps), f32))
        _, _, _, cache, _, _, (_, logits_at, mass_at) = decode_fused_aux(
            params, config, feed, cache, step, aux, mesh, active=live,
            num_steps=steps, sample_fn=sample,
            sample_state=jnp.zeros((), jnp.int32), stop_ids=(), pages=pages)
        return cache, logits_at, mass_at

    logits, pdf, cache = prefill(params, tokens[:, :P])
    at = long_positions(C)
    carry, long_out = None, []
    for off in range(0, W, C):
        n = min(C, PL - off)
        toks = jnp.pad(long[:, off: off + n], ((0, 0), (0, C - n)))
        keep = tuple(int(p) - off for p in at if off <= p < off + n)
        if carry is None:
            carry, kept, cache = first(params, toks, cache, keep=keep)
        else:
            carry, kept, cache = later(params, toks, n > 0, carry, cache,
                                       offset=off, keep=keep)
        long_out.append(kept)
    del carry       # handed back by the last chunk, and dropped as there
    # What each slot is fed at each step, and a row of zeros behind the
    # last: the sampler's answer to a step is the next step's input.
    feed = np.zeros((D + 1, slots), np.int32)
    feed[:D, rows[:B]] = np.asarray(tokens[:, P:]).T
    feed[:D, rows[B]] = np.asarray(long[0, PL:])
    feed = jnp.asarray(feed)
    out, mass = [], []
    for t in range(0, D, fuse):
        n = min(fuse, D - t)
        cache, logits_at, mass_at = decode(
            params, feed[t][:, None], cache, feed[t + 1: t + 1 + n], steps=n)
        out.append(jnp.swapaxes(logits_at, 0, 1))           # [B + 1, n, V]
        mass.append(mass_at)
    out = jnp.concatenate(out, axis=1)
    return SystemOut(logits=jnp.concatenate([logits, out[:B]], axis=1),
                     exit_pdf=pdf, exit_mass=jnp.concatenate(mass, axis=0),
                     long_logits=jnp.concatenate([*long_out, out[B:]],
                                                 axis=1))


def compare(system: SystemOut, reference_logits, facts: dict,
            cfg: dict) -> dict:
    """reference.compare's numbers over the harness's sample (prefill
    positions, then the decode steps), the worst of its decode steps
    (``decode_max``), the long sample's beside them (``long_median`` over
    its compared prefill positions, which the chunk ladder computed;
    ``long_decode_max``, the worst of its decode steps, which the
    flash-append kernel read at the cell's window), and ``exit_max``: the
    largest absolute difference of the exit distribution, the prefill
    positions' pdf and the decode steps' mass a row; the verdict: each
    under its limit (:data:`TOL_MEDIAN` and the comment above it)."""
    import jax.numpy as jnp
    import numpy as np
    from benchmark import reference
    out = reference.compare(system.logits, reference_logits, routed=False)
    P, chunk = facts["n_prefill"], check_chunk(cfg)
    err = reference.position_errors(system.logits, reference_logits)
    long_err = reference.position_errors(
        system.long_logits, facts["long_logits"]).reshape(-1)
    at, PL = long_positions(chunk), long_shape(chunk)[0]
    ref_pdf = facts["exit_pdf"]
    B = ref_pdf.shape[0] + 1
    ref_mass = jnp.sum(ref_pdf[:, P:], axis=0) + facts["long_exit_pdf"][0]
    off = [jnp.max(jnp.abs(system.exit_pdf - ref_pdf[:, :P])),
           jnp.max(jnp.abs(system.exit_mass - ref_mass)) / B]
    out.update(
        decode_max=float(jnp.max(err[:, P:])),
        long_median=float(jnp.median(long_err[np.flatnonzero(at < PL)])),
        long_max=float(jnp.max(long_err[np.flatnonzero(at < PL)])),
        long_decode_max=float(jnp.max(long_err[np.flatnonzero(at >= PL)])),
        exit_max=float(jnp.max(jnp.stack(off))),
        exit_mean=[float(x) for x in
                   jnp.mean(ref_pdf.reshape(-1, ref_pdf.shape[-1]), 0)])
    limits = {"median": TOL_MEDIAN, "long_median": TOL_MEDIAN,
              "decode_max": TOL_DECODE, "long_decode_max": TOL_DECODE,
              "exit_max": TOL_EXIT}
    out["ok"] = bool(jnp.isfinite(err).all() and jnp.isfinite(long_err).all()
                     and all(out[k] <= v for k, v in limits.items()))
    out["tolerance"] = limits
    return out


# -- what a step must move and a prompt must compute (JAX-free) ---------------

def _q8(n_in: int, n_out: int) -> float:
    """Bytes of an int8 [n_in, n_out] weight with a float32 scale a
    column (benchmark/roofline.py's count)."""
    return n_in * n_out + 4 * n_out


def layer_shapes(cfg: dict) -> list:
    """[in, out] of every matrix of a layer, fused as served."""
    H, E = cfg["hidden_size"], cfg["intermediate_size"]
    D = cfg.get("head_dim") or H // cfg["num_attention_heads"]
    Q, KV = cfg["num_attention_heads"] * D, cfg["num_key_value_heads"] * D
    return [(H, Q + 2 * KV), (Q, H), (H, 2 * E), (E, H)]


def stack_bytes(cfg: dict) -> float:
    """The stack's stored bytes, ONE pass's read: every layer's int8
    matrices with their scales and its four bf16 norm vectors."""
    return cfg["num_hidden_layers"] * (
        sum(_q8(*s) for s in layer_shapes(cfg)) + 4 * 2 * cfg["hidden_size"])


def page_token_bytes(cfg: dict) -> float:
    """One position in the int8 page pool over ALL its cache layers
    (``num_hidden_layers x total_ut_steps``): K and V of every KV head and
    a float32 scale a head for each. 811,008 for Ouro-2.6B."""
    D = cfg.get("head_dim") or cfg["hidden_size"] // cfg["num_attention_heads"]
    return (cfg["num_hidden_layers"] * cfg["total_ut_steps"]
            * 2.0 * cfg["num_key_value_heads"] * (D + 4))


def decode_step_bytes(cfg: dict, rows: float, context: float) -> float:
    """Bytes one decode step has to move: the stack once a PASS (it does
    not stay on the chip between passes), the head in int8, the rows'
    embeddings in bf16, and each row's context in every cache layer's
    pages, a pass's layers read by that pass alone."""
    H = cfg["hidden_size"]
    return (cfg["total_ut_steps"] * stack_bytes(cfg)
            + _q8(H, cfg["vocab_size"]) + rows * 2 * H
            + rows * context * page_token_bytes(cfg))


def prefill_flops(cfg: dict, tokens: float, context_pairs: float) -> float:
    """FLOPs the prompt positions require: two a parameter a token a
    PASS for the stack's matrices, and the attention's causal pairs in
    every cache layer (q.k and p.v: four a head dimension a pair). The
    head runs for one position a request and is left out."""
    passes, L = cfg["total_ut_steps"], cfg["num_hidden_layers"]
    D = cfg.get("head_dim") or cfg["hidden_size"] // cfg["num_attention_heads"]
    per_token = 2.0 * passes * L * sum(a * b for a, b in layer_shapes(cfg))
    pair = 4.0 * cfg["num_attention_heads"] * D
    return tokens * per_token + passes * L * context_pairs * pair
