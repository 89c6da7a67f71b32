"""Peaks of the chip, and the bytes and operations a step needs. JAX-free.

Every function takes the configuration file's own keys (the published
``config.json`` names) and the stack's storage types, and computes from
shapes what the *algorithm* has to move or do: int8 weights with one
float32 scale per output channel, an int8 KV cache with one float32
scale per (token, kv head), bf16 embeddings and router. What the program
actually moves may be more (it streams every expert, touched or not);
that difference is the point of dividing by these.

Copied idea: bench.py's DEVICE_PEAKS keyed by device_kind; the table is
peaks.json, with the bf16 and int8 peaks added.
"""

from __future__ import annotations

import json
import os

_HERE = os.path.dirname(os.path.abspath(__file__))


def peaks_for(device_kind: str, path: str = os.path.join(_HERE, "peaks.json")
              ) -> dict:
    with open(path) as f:
        table = json.load(f)
    row = table.get(device_kind)
    if not isinstance(row, dict):
        raise KeyError(
            f"no peaks for device_kind {device_kind!r} in {path}; add a row "
            f"with its source (have {[k for k in table if k[0] != '_']})")
    return row


def dims(cfg: dict) -> dict:
    H = cfg["hidden_size"]
    heads = cfg["num_attention_heads"]
    d = cfg.get("head_dim") or H // heads
    return {"H": H, "E": cfg["intermediate_size"],
            "L": cfg["num_hidden_layers"], "V": cfg["vocab_size"],
            "q": heads * d, "kv": cfg["num_key_value_heads"] * d,
            "kv_heads": cfg["num_key_value_heads"], "d": d,
            "NE": cfg.get("num_local_experts", 0),
            "k": cfg.get("num_experts_per_tok", 0)}


def _q8(n_in: int, n_out: int) -> float:
    """Bytes of an int8 [n_in, n_out] weight with a float32 scale per
    output channel."""
    return n_in * n_out + 4 * n_out


def attn_weight_bytes(cfg: dict) -> float:
    m = dims(cfg)
    return _q8(m["H"], m["q"] + 2 * m["kv"]) + _q8(m["q"], m["H"])


def mlp_weight_bytes(cfg: dict) -> float:
    """One SwiGLU (one expert of an MoE layer): gate, up, down."""
    m = dims(cfg)
    return _q8(m["H"], 2 * m["E"]) + _q8(m["E"], m["H"])


def experts_touched(rows: float, top_k: int, n_experts: int) -> float:
    """Expected number of distinct experts that ``rows`` tokens reach
    when each picks ``top_k`` of ``n_experts`` uniformly."""
    if not n_experts:
        return 0.0
    return n_experts * (1.0 - (1.0 - top_k / n_experts) ** rows)


def layer_weight_bytes(cfg: dict, rows: float | None = None) -> float:
    """Weight bytes of one layer; with ``rows``, only the experts that
    many tokens touch (all of them when ``rows`` is None)."""
    m = dims(cfg)
    norms = 2 * m["H"] * 2
    if not m["NE"]:
        return attn_weight_bytes(cfg) + mlp_weight_bytes(cfg) + norms
    n = (m["NE"] if rows is None
         else experts_touched(rows, m["k"], m["NE"]))
    router = m["H"] * m["NE"] * 2
    return attn_weight_bytes(cfg) + n * mlp_weight_bytes(cfg) + router + norms


def head_bytes(cfg: dict) -> float:
    m = dims(cfg)
    return _q8(m["H"], m["V"])


def embed_bytes(cfg: dict) -> float:
    m = dims(cfg)
    return m["V"] * m["H"] * 2


def model_weight_bytes(cfg: dict) -> float:
    """Everything resident: layers, head, bf16 embeddings, final norm."""
    m = dims(cfg)
    return (m["L"] * layer_weight_bytes(cfg) + head_bytes(cfg)
            + embed_bytes(cfg) + m["H"] * 2)


def kv_bytes_per_token(cfg: dict) -> float:
    """int8 keys and values of one token in every layer, with a float32
    scale per (layer, k or v, kv head)."""
    m = dims(cfg)
    return m["L"] * 2 * (m["kv"] + 4 * m["kv_heads"])


def kv_pool_bytes(cfg: dict, rows: int, max_seq: int,
                  padded: bool = False) -> float:
    """A pool of ``rows`` x ``max_seq`` tokens (or pages x page size).
    ``padded``: as the program stores it, each page's scales padded from
    the page size to a full tile of 128 lanes."""
    m = dims(cfg)
    extra = (m["L"] * 2 * 4 * m["kv_heads"]) if padded else 0
    return rows * max_seq * (kv_bytes_per_token(cfg) + extra)


def decode_step_bytes(cfg: dict, rows: float, context: float) -> float:
    """Bytes one decode step must read: the layers' weights (the experts
    touched), the head, the live rows' embeddings and their KV."""
    m = dims(cfg)
    return (m["L"] * layer_weight_bytes(cfg, rows) + head_bytes(cfg)
            + rows * m["H"] * 2 + rows * context * kv_bytes_per_token(cfg))


def _token_matmul_flops(cfg: dict) -> float:
    """Multiply-adds x 2 of one token through one layer's matmuls."""
    m = dims(cfg)
    attn = 2 * m["H"] * (m["q"] + 2 * m["kv"]) + 2 * m["q"] * m["H"]
    mlp = 2 * 3 * m["H"] * m["E"]
    if m["NE"]:
        return attn + m["k"] * mlp + 2 * m["H"] * m["NE"]
    return attn + mlp


def decode_step_flops(cfg: dict, rows: float, context: float) -> float:
    m = dims(cfg)
    attn = 4 * context * m["q"]            # q.k and p.v over the context
    return rows * (m["L"] * (_token_matmul_flops(cfg) + attn)
                   + 2 * m["H"] * m["V"])


def prefill_chunk_flops(cfg: dict, tokens: int, context: float) -> float:
    """``tokens`` prompt tokens of one row through every layer, each
    attending ``context`` earlier tokens on average, plus one head row."""
    m = dims(cfg)
    return (tokens * m["L"] * (_token_matmul_flops(cfg)
                               + 4 * context * m["q"])
            + 2 * m["H"] * m["V"])


def prefill_chunk_bytes(cfg: dict, tokens: int, context: float) -> float:
    """Weights once (every expert: a chunk of hundreds of tokens reaches
    all), the earlier context's KV once, the chunk's own KV written."""
    m = dims(cfg)
    return (m["L"] * layer_weight_bytes(cfg) + head_bytes(cfg)
            + (context + tokens) * kv_bytes_per_token(cfg)
            + tokens * m["H"] * 2)


def least_seconds(flops: float, nbytes: float, peaks: dict,
                  int8: bool = False) -> tuple:
    """(seconds, "compute" | "memory"): the roofline bound and which
    side sets it."""
    rate = peaks["int8_ops_per_s" if int8 else "bf16_flops_per_s"]
    tc, tm = flops / rate, nbytes / peaks["hbm_bytes_per_s"]
    return (tc, "compute") if tc >= tm else (tm, "memory")
