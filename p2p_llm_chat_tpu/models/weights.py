"""Checkpoint loading: HF-format safetensors -> our stacked param trees.

The reference pulls model weights out-of-tree via ``ollama pull``
(README.md:62-70); the in-tree equivalent reads HuggingFace-layout
checkpoints (config.json + *.safetensors) from local disk and materialises
them directly into (optionally sharded) ``jax.Array``s.

Key transforms vs the HF torch layout:
- torch ``nn.Linear`` stores ``[out, in]`` and computes ``x @ W.T``; we
  store ``[in, out]`` — so every projection is transposed on load.
- per-layer tensors are stacked along a leading ``num_layers`` axis to
  match the lax.scan decoder (models/llama.py).
- with a mesh, each stacked tensor is device_put with its logical-axis
  sharding, so a 70B checkpoint never needs to fit on one chip
  (BASELINE.json config 4).
"""

from __future__ import annotations

import functools
import json
import os
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding

from ..utils.log import get_logger
from ..parallel.sharding import LogicalRules, DEFAULT_RULES, spec_for
from .configs import CONFIGS, ModelConfig, RopeScaling

log = get_logger("weights")


# -- HF name mapping ----------------------------------------------------------

def _dense_layer_map(i: int) -> dict[str, tuple[str, bool]]:
    """our layer key -> (HF tensor name, transpose?)."""
    p = f"model.layers.{i}"
    return {
        "attn_norm": (f"{p}.input_layernorm.weight", False),
        "wq": (f"{p}.self_attn.q_proj.weight", True),
        "wk": (f"{p}.self_attn.k_proj.weight", True),
        "wv": (f"{p}.self_attn.v_proj.weight", True),
        "wo": (f"{p}.self_attn.o_proj.weight", True),
        "mlp_norm": (f"{p}.post_attention_layernorm.weight", False),
        "w_gate": (f"{p}.mlp.gate_proj.weight", True),
        "w_up": (f"{p}.mlp.up_proj.weight", True),
        "w_down": (f"{p}.mlp.down_proj.weight", True),
    }


def _moe_layer_map(i: int, num_experts: int) -> dict[str, Any]:
    """Mixtral layout: experts w1 (gate), w3 (up), w2 (down) + router gate."""
    p = f"model.layers.{i}"
    m: dict[str, Any] = {
        "attn_norm": (f"{p}.input_layernorm.weight", False),
        "wq": (f"{p}.self_attn.q_proj.weight", True),
        "wk": (f"{p}.self_attn.k_proj.weight", True),
        "wv": (f"{p}.self_attn.v_proj.weight", True),
        "wo": (f"{p}.self_attn.o_proj.weight", True),
        "mlp_norm": (f"{p}.post_attention_layernorm.weight", False),
        "router": (f"{p}.block_sparse_moe.gate.weight", True),
        "w_gate": [(f"{p}.block_sparse_moe.experts.{e}.w1.weight", True)
                   for e in range(num_experts)],
        "w_up": [(f"{p}.block_sparse_moe.experts.{e}.w3.weight", True)
                 for e in range(num_experts)],
        "w_down": [(f"{p}.block_sparse_moe.experts.{e}.w2.weight", True)
                   for e in range(num_experts)],
    }
    return m


def _olmoe_layer_map(i: int, num_experts: int) -> dict[str, Any]:
    """OLMoE layout: the dense names for attention and norms, the two
    whole-projection QK-norm vectors, ``mlp.gate`` for the router and
    ``mlp.experts.N.{gate,up,down}_proj`` for the experts."""
    p = f"model.layers.{i}"
    m: dict[str, Any] = {k: v for k, v in _dense_layer_map(i).items()
                         if k not in ("w_gate", "w_up", "w_down")}
    m["q_norm"] = (f"{p}.self_attn.q_norm.weight", False)
    m["k_norm"] = (f"{p}.self_attn.k_norm.weight", False)
    m["router"] = (f"{p}.mlp.gate.weight", True)
    for key, proj in (("w_gate", "gate_proj"), ("w_up", "up_proj"),
                      ("w_down", "down_proj")):
        m[key] = [(f"{p}.mlp.experts.{e}.{proj}.weight", True)
                  for e in range(num_experts)]
    return m


def _sandwich_layer_map(i: int) -> dict[str, tuple[str, bool]]:
    """Ouro's layout: the dense names, and the two norms on each branch's
    OUTPUT (``input_layernorm_2`` behind the attention,
    ``post_attention_layernorm_2`` behind the MLP)."""
    p = f"model.layers.{i}"
    return {**_dense_layer_map(i),
            "attn_out_norm": (f"{p}.input_layernorm_2.weight", False),
            "mlp_out_norm": (f"{p}.post_attention_layernorm_2.weight",
                             False)}


# A looped stack's exit gate (``model.early_exit_gate``, a Linear hidden
# -> 1 with bias): our leaf -> (HF tensor name, transpose?).
_EXIT_GATE_MAP = {
    "exit_gate_w": ("model.early_exit_gate.weight", True),
    "exit_gate_b": ("model.early_exit_gate.bias", False),
}


def _layer_map(config: ModelConfig, i: int) -> dict[str, Any]:
    """Layer i's name map for the configuration's family. The two routed
    layouts are told apart by QK-norm: Mixtral (``block_sparse_moe``,
    w1/w2/w3) has none, OLMoE (``mlp.experts``, *_proj) has it."""
    if not config.is_moe:
        return (_sandwich_layer_map(i) if config.sandwich_norm
                else _dense_layer_map(i))
    if config.qk_norm_whole:
        return _olmoe_layer_map(i, config.num_experts)
    return _moe_layer_map(i, config.num_experts)


def convert_hf_state_dict(state: dict[str, np.ndarray], config: ModelConfig,
                          dtype=jnp.bfloat16) -> dict:
    """Convert a flat HF state dict (numpy arrays) into our stacked tree.
    Test-oracle path (used by the parity tests); load_checkpoint below is
    the production path over safetensors files."""
    def get(name: str, transpose: bool) -> np.ndarray:
        t = state[name]
        return np.ascontiguousarray(t.T) if transpose else t

    L = config.num_layers
    layers: dict[str, Any] = {}
    maps = [_layer_map(config, i) for i in range(L)]
    for key in maps[0]:
        per_layer = []
        for i in range(L):
            spec = maps[i][key]
            if isinstance(spec, list):   # per-expert stack
                per_layer.append(np.stack([get(n, t) for n, t in spec]))
            else:
                per_layer.append(get(*spec))
        layers[key] = jnp.asarray(np.stack(per_layer), dtype)

    params: dict[str, Any] = {
        "embed": jnp.asarray(state["model.embed_tokens.weight"], dtype),
        "layers": layers,
        "final_norm": jnp.asarray(state["model.norm.weight"], dtype),
    }
    if config.ut_steps > 1:
        for key, spec in _EXIT_GATE_MAP.items():
            params[key] = jnp.asarray(get(*spec), dtype)
    if not config.tie_embeddings:
        params["lm_head"] = jnp.asarray(
            np.ascontiguousarray(state["lm_head.weight"].T), dtype)
    return params


# -- safetensors checkpoint directory loading --------------------------------

def config_from_hf_json(path: str) -> ModelConfig:
    """Derive a ModelConfig from an HF config.json (llama, mixtral and
    olmoe families). Mixtral publishes ``num_local_experts`` and always
    renormalises the kept router weights; OLMoE publishes ``num_experts``
    and ``norm_topk_prob``, and its ``model_type`` says that q and k
    carry a whole-projection RMSNorm."""
    with open(path) as f:
        hf = json.load(f)
    arch = (hf.get("architectures") or ["LlamaForCausalLM"])[0]
    rope_scaling = None
    rs = hf.get("rope_scaling")
    if rs and rs.get("rope_type", rs.get("type")) == "llama3":
        rope_scaling = RopeScaling(
            factor=float(rs.get("factor", 8.0)),
            low_freq_factor=float(rs.get("low_freq_factor", 1.0)),
            high_freq_factor=float(rs.get("high_freq_factor", 4.0)),
            original_max_position=int(rs.get("original_max_position_embeddings", 8192)),
        )
    num_heads = int(hf["num_attention_heads"])
    if hf.get("early_exit_threshold", 1) != 1:
        # A looped model (Ouro) whose rows leave the loop early: the
        # program runs every pass for every token and has no path that
        # exits (ROADMAP.md, Reach); serving it as if the threshold were
        # 1 would be another model under this one's name.
        raise ValueError(
            f"{path}: early_exit_threshold {hf['early_exit_threshold']} "
            "is below 1; only the last pass's logits (threshold 1) are "
            "served")
    eos = hf.get("eos_token_id", 2)
    eos_ids = tuple(eos) if isinstance(eos, list) else (int(eos),)
    return ModelConfig(
        name=hf.get("_name_or_path", "hf-model"),
        vocab_size=int(hf["vocab_size"]),
        hidden_size=int(hf["hidden_size"]),
        intermediate_size=int(hf["intermediate_size"]),
        num_layers=int(hf["num_hidden_layers"]),
        num_heads=num_heads,
        num_kv_heads=int(hf.get("num_key_value_heads", num_heads)),
        # Mixtral configs carry an explicit ``"head_dim": null``.
        head_dim=int(hf.get("head_dim") or hf["hidden_size"] // num_heads),
        max_seq_len=int(hf.get("max_position_embeddings", 8192)),
        rope_theta=float(hf.get("rope_theta", 10000.0)),
        rope_scaling=rope_scaling,
        rms_norm_eps=float(hf.get("rms_norm_eps", 1e-5)),
        tie_embeddings=bool(hf.get("tie_word_embeddings", False)),
        num_experts=int(hf.get("num_local_experts")
                        or hf.get("num_experts") or 0),
        num_experts_per_tok=int(hf.get("num_experts_per_tok", 0)),
        moe_renormalize=bool(hf.get("norm_topk_prob", True)),
        qk_norm_whole=hf.get("model_type") == "olmoe",
        # A looped stack: walked ``total_ut_steps`` times. Four norms a
        # layer where the config says so (``sandwich_norm``, openPangu's
        # key); Ouro's config.json has no key for its four, so there the
        # ``model_type`` says it, as it says OLMoE's QK-norm above.
        ut_steps=int(hf.get("total_ut_steps") or 1),
        sandwich_norm=bool(hf.get("sandwich_norm",
                                  hf.get("model_type") == "ouro")),
        bos_token_id=int(hf.get("bos_token_id") or 1),
        eos_token_ids=eos_ids,
    )


def _reverse_name_map(config: ModelConfig) -> dict[str, tuple]:
    """HF tensor name -> (leaf key path, layer index or None, expert index
    or None, transpose?) for every per-layer tensor, plus the top-level
    names. Derived from the same forward maps the batch loader uses, so
    the two loaders cannot drift."""
    out: dict[str, tuple] = {
        "model.embed_tokens.weight": (("embed",), None, None, False),
        "model.norm.weight": (("final_norm",), None, None, False),
    }
    if not config.tie_embeddings:
        out["lm_head.weight"] = (("lm_head",), None, None, True)
    if config.ut_steps > 1:
        for key, (name, tr) in _EXIT_GATE_MAP.items():
            out[name] = ((key,), None, None, tr)
    for i in range(config.num_layers):
        for key, spec in _layer_map(config, i).items():
            if isinstance(spec, list):
                for e, (name, tr) in enumerate(spec):
                    out[name] = (("layers", key), i, e, tr)
            else:
                name, tr = spec
                out[name] = (("layers", key), i, None, tr)
    return out


def _iter_hf_tensors(ckpt_dir: str, config: ModelConfig):
    """Yield ``(leaf_path, layer, expert, np_tensor)`` for every mapped
    tensor across the dir's safetensors shards, transpose already applied
    (host RAM holds one tensor at a time). Shared by the streaming and
    streamed-int8 loaders so the shard walk / name map / missing-tensor
    accounting cannot drift between them. Raises FileNotFoundError with
    no shards; KeyError when mapped tensors are absent (zeros where
    weights should be = garbage logits with no error — fail loudly)."""
    from safetensors import safe_open

    name_map = _reverse_name_map(config)
    missing = set(name_map)
    shards = sorted(f for f in os.listdir(ckpt_dir)
                    if f.endswith(".safetensors"))
    if not shards:
        raise FileNotFoundError(f"no .safetensors files in {ckpt_dir}")
    for shard in shards:
        with safe_open(os.path.join(ckpt_dir, shard),
                       framework="numpy") as f:
            for name in f.keys():
                entry = name_map.get(name)
                if entry is None:
                    continue
                path, layer, expert, transpose = entry
                t = f.get_tensor(name)
                if transpose:
                    t = np.ascontiguousarray(t.T)
                missing.discard(name)
                yield path, layer, expert, t
        log.info("streamed shard %s (%d/%d tensors placed)", shard,
                 len(name_map) - len(missing), len(name_map))
    if missing:
        raise KeyError(
            f"checkpoint {ckpt_dir} is missing {len(missing)} expected "
            f"tensor(s), e.g. {sorted(missing)[:3]} — truncated download "
            "or wrong config?")


def load_checkpoint_streaming(ckpt_dir: str,
                              config: Optional[ModelConfig] = None,
                              mesh: Optional[Mesh] = None,
                              rules: LogicalRules = DEFAULT_RULES,
                              dtype=jnp.bfloat16,
                              ) -> tuple[dict, ModelConfig]:
    """Memory-bounded checkpoint load: host RAM holds ONE tensor at a
    time; the stacked tree lives on device (sharded when a mesh is given)
    from the start.

    The batch loader (:func:`load_checkpoint`) materialises the whole HF
    state dict in host numpy before stacking — ~140 GB for llama3.1-70B
    bf16, the memory-fit hard part SURVEY.md §7 names. Here every leaf is
    pre-allocated on device (zeros, with its logical sharding) and each
    safetensors tensor is spliced into its (layer[, expert]) slice via a
    donated ``dynamic_update_index_in_dim`` — one compiled splice program
    per leaf shape, reused across layers, so host peak stays at the
    largest single tensor and device memory at the final tree size.
    """
    from . import family_for

    if config is None:
        config = config_from_hf_json(os.path.join(ckpt_dir, "config.json"))
    family = family_for(config)
    axes = family.param_axes(config)

    def sharding(path_axes):
        if mesh is None:
            return None
        return NamedSharding(mesh, spec_for(path_axes, rules))

    abstract = jax.eval_shape(
        lambda: family.init_params(config, jax.random.PRNGKey(0),
                                   dtype=dtype))
    params = jax.tree.map(
        lambda a, ax: jnp.zeros(a.shape, a.dtype, device=sharding(ax)),
        abstract, axes,
        is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct))

    # One donated splice program per (leaf shape, index arity).
    @functools.partial(jax.jit, donate_argnums=(0,), static_argnums=(3,))
    def splice(full, t, idx, two_level):
        if two_level:
            return jax.lax.dynamic_update_slice(
                full, t[None, None], (idx[0], idx[1]) + (0,) * t.ndim)
        return jax.lax.dynamic_update_index_in_dim(full, t, idx[0], 0)

    def get_leaf(path):
        node = params
        for p in path:
            node = node[p]
        return node

    def set_leaf(path, value):
        node = params
        for p in path[:-1]:
            node = node[p]
        node[path[-1]] = value

    for path, layer, expert, t in _iter_hf_tensors(ckpt_dir, config):
        leaf = get_leaf(path)
        if layer is None:
            set_leaf(path, jax.device_put(
                jnp.asarray(t, dtype),
                leaf.sharding if mesh is not None else None))
        else:
            idx = (jnp.asarray(layer, jnp.int32),
                   jnp.asarray(0 if expert is None else expert,
                               jnp.int32))
            set_leaf(path, splice(leaf, jnp.asarray(t, dtype),
                                  idx, expert is not None))
    log.info("loaded %s (streaming): %.2fB params", config.name,
             sum(x.size for x in jax.tree.leaves(params)) / 1e9)
    return params, config


def load_checkpoint(ckpt_dir: str, config: Optional[ModelConfig] = None,
                    mesh: Optional[Mesh] = None,
                    rules: LogicalRules = DEFAULT_RULES,
                    dtype=jnp.bfloat16,
                    param_axes_fn: Optional[Callable[[ModelConfig], dict]] = None,
                    ) -> tuple[dict, ModelConfig]:
    """Load an HF-layout checkpoint directory into a (sharded) param tree.

    Reads every ``*.safetensors`` shard, converts/stacks, and — when a mesh
    is given — places each tensor with its logical sharding so per-host
    memory stays bounded by the shard size, not the model size.
    """
    from safetensors import safe_open

    if config is None:
        config = config_from_hf_json(os.path.join(ckpt_dir, "config.json"))

    state: dict[str, np.ndarray] = {}
    shards = sorted(f for f in os.listdir(ckpt_dir) if f.endswith(".safetensors"))
    if not shards:
        raise FileNotFoundError(f"no .safetensors files in {ckpt_dir}")
    for shard in shards:
        with safe_open(os.path.join(ckpt_dir, shard), framework="numpy") as f:
            for name in f.keys():
                state[name] = f.get_tensor(name)
        log.info("read shard %s (%d tensors total)", shard, len(state))

    params = convert_hf_state_dict(state, config, dtype)
    if mesh is not None:
        if param_axes_fn is None:
            from . import family_for
            param_axes_fn = family_for(config).param_axes
        axes = param_axes_fn(config)
        params = jax.tree.map(
            lambda x, a: jax.device_put(x, NamedSharding(mesh, spec_for(a, rules))),
            params, axes,
            is_leaf=lambda x: isinstance(x, jax.Array),
        )
    log.info("loaded %s: %.2fB params", config.name,
             sum(x.size for x in jax.tree.leaves(params)) / 1e9)
    return params, config


class UnsupportedForQuantizedLoad(ValueError):
    """The checkpoint's family is outside load_checkpoint_quantized's
    scope — callers fall back to the standard paths. A dedicated type so
    fallbacks cannot swallow REAL load errors (corrupt shards etc.),
    which must propagate."""


# Fields that determine whether a caller-supplied config names the SAME
# MODEL as a checkpoint: every tensor-shape-bearing field (plus the
# registry name, native checkpoints only — see _check_config_identity).
# Deliberately excluded: max_seq_len, rope_theta/rope_scaling, eps,
# token-id defaults, moe_capacity_factor — serving/runtime knobs that
# registry bumps legitimately change without re-saving weights (e.g. the
# bench-1b max_seq_len 2048 -> 16384 bump for long-context rows, which
# the old whole-dataclass equality would have rejected for every
# pre-existing native checkpoint).
_CONFIG_IDENTITY_FIELDS = (
    "vocab_size", "hidden_size", "intermediate_size", "num_layers",
    "num_heads", "num_kv_heads", "head_dim", "tie_embeddings",
    "num_experts", "num_experts_per_tok", "moe_renormalize", "qk_norm_whole",
    "sandwich_norm", "ut_steps",
)


def _check_config_identity(supplied: ModelConfig, stored: ModelConfig,
                           ckpt_dir: str, check_name: bool = True) -> None:
    """Raise unless ``supplied`` names the same model as the checkpoint's
    own ``stored`` config — identity-relevant fields only (see
    _CONFIG_IDENTITY_FIELDS). On agreement the SUPPLIED config wins:
    honoring its benign (non-shape) field bumps is the point.

    ``check_name``: native checkpoints carry the registry name they were
    saved under, so name disagreement means a different model; HF dirs
    derive ``name`` from config.json's ``_name_or_path`` (or the literal
    "hf-model"), which can NEVER equal a registry name — the HF branch
    passes False and lets the shape fields alone establish identity."""
    fields = _CONFIG_IDENTITY_FIELDS + (("name",) if check_name else ())
    bad = [f for f in fields if getattr(supplied, f) != getattr(stored, f)]
    if bad:
        raise ValueError(
            f"config mismatch: caller passed {supplied.name!r} but the "
            f"checkpoint at {ckpt_dir} carries {stored.name!r} "
            f"(differing identity fields: {', '.join(bad)})")


def load_checkpoint_quantized(ckpt_dir: str,
                              config: Optional[ModelConfig] = None,
                              quant: str = "int8",
                              ) -> tuple[dict, ModelConfig]:
    """Single-chip big-model load: stream a checkpoint (HF safetensors or
    native Orbax) straight into the FUSED quantized stacked tree — the
    bf16 device tree never exists. ``quant``: ``int8`` (per-channel) or
    ``int4`` (group-wise packed nibbles — half the int8 stream again;
    ~3.8 GB for the 8B trunk).

    Why: ``load_checkpoint`` + ``quantize_params`` peaks at the full bf16
    model on the chip (~16 GB for llama3.1-8B — does not fit a 16 GB
    v5e), even though the int8 model (~8.6 GB) plus an int8 KV pool does.
    This is the checkpoint-path twin of ``llama.init_params_quantized``
    (which solved the same problem for random init): per layer, the host
    tensors are quantized host-side and spliced into donated stacked int8
    buffers in ``fuse_params``' wqkv/wgu layout — quantize-then-fuse
    equivalence holds exactly (per-output-channel scales concatenate with
    their columns).

    Weights round through bf16 (the serving compute dtype) before
    quantization, so the result is BIT-IDENTICAL to load-at-bf16 ->
    quantize_params -> fuse_params (pinned by tests for both formats
    and both precisions — the host numpy quantizers below mirror
    quant.quantize / quant.quantize4's exact IEEE f32 ops).
    For f32-SAVED native checkpoints the old single-chip path would have
    quantized unrounded f32 — that path cannot fit big models anyway, and
    all in-tree saves default to bf16.

    MoE (mixtral-family) checkpoints stream the same way: attention
    fuses to wqkv exactly like dense, and the per-expert ffn leaves
    quantize into the fused ``wgu_e`` [L,NE,H,2F] + ``w_down``
    [L,NE,F,H] stacks (mixtral.moe_mlp's single-einsum layout); the
    router stays bf16 (tiny, f32 routing math). Unknown families raise
    :class:`UnsupportedForQuantizedLoad`. Tied-embedding configs return
    no ``lm_head`` leaf (forward uses ``embed.T``, kept bf16).
    """
    from . import family_for, llama, mixtral
    from .checkpoint import is_native_checkpoint, peek_config
    from .checkpoint import load_checkpoint as load_native
    from .quant import QTensor, QTensor4, stream_bufs

    if quant not in ("int8", "int4"):
        raise ValueError(f"quant must be int8|int4, got {quant!r}")
    dtype = jnp.bfloat16

    # Family gate FIRST — from metadata alone. Checking after the tensor
    # reads would load a rejected multi-GB checkpoint in full, only for
    # the engine to re-load it through the standard path.
    native = is_native_checkpoint(ckpt_dir)
    if config is None:
        config = (peek_config(ckpt_dir) if native else
                  config_from_hf_json(os.path.join(ckpt_dir, "config.json")))
    else:
        # A caller-supplied config must name the same MODEL as the
        # checkpoint — identity fields only, so benign registry bumps
        # (max_seq_len, rope knobs) survive pre-existing checkpoints.
        # Applied to BOTH branches: the HF path used to skip the check
        # entirely (silently trusting the caller), the native one used
        # whole-dataclass equality (rejecting every benign bump).
        stored = (peek_config(ckpt_dir) if native else
                  config_from_hf_json(os.path.join(ckpt_dir,
                                                   "config.json")))
        _check_config_identity(config, stored, ckpt_dir, check_name=native)
    family = family_for(config)
    if family not in (llama, mixtral):
        raise UnsupportedForQuantizedLoad(
            "load_checkpoint_quantized covers the llama and mixtral "
            f"families; {config.name} keeps the standard load paths")
    moe = config.is_moe
    # Norm vectors stay in the compute dtype, one row a layer.
    norm_keys = ("attn_norm", "mlp_norm") + (
        ("q_norm", "k_norm") if config.qk_norm_whole else ()) + (
        ("attn_out_norm", "mlp_out_norm") if config.sandwich_norm else ())
    layer_keys = norm_keys + ("wq", "wk", "wv", "wo", "w_gate", "w_up",
                              "w_down") + (("router",) if moe else ())

    # -- per-layer host-tensor iterator -------------------------------------
    if native:
        cpu = jax.devices("cpu")[0]
        host_params, loaded_cfg = load_native(ckpt_dir, device=cpu)
        # Identity agreement with the caller's config was checked above
        # (relaxed to _CONFIG_IDENTITY_FIELDS — ADVICE r4's consistency
        # point, minus the whole-dataclass equality that rejected benign
        # runtime-field bumps); re-verify against the ACTUALLY-loaded
        # config in case peek and load ever disagree. The supplied
        # config stays authoritative for non-identity fields.
        _check_config_identity(config, loaded_cfg, ckpt_dir)

        def layer_host(li: int) -> dict[str, np.ndarray]:
            lp = host_params["layers"]
            return {k: np.asarray(lp[k][li]) for k in layer_keys}

        def top_host() -> dict[str, np.ndarray]:
            out = {"embed": np.asarray(host_params["embed"]),
                   "final_norm": np.asarray(host_params["final_norm"])}
            for k in ("lm_head", *_EXIT_GATE_MAP):
                if k in host_params:
                    out[k] = np.asarray(host_params[k])
            return out
    else:
        host_params = None

        def _read_all() -> tuple[dict, dict]:
            """One pass over the shards (shared iterator), grouped per
            layer. Host peak is the full tree for HF dirs read this way —
            acceptable (host RAM >> HBM); the DEVICE peak is what this
            loader bounds. Per-expert tensors stack into [NE, ...] host
            arrays in expert order."""
            per_layer: dict[int, dict] = {}
            top: dict[str, np.ndarray] = {}
            for path, layer, expert, t in _iter_hf_tensors(ckpt_dir,
                                                           config):
                if layer is None:
                    top[path[-1]] = t
                elif expert is None:
                    per_layer.setdefault(layer, {})[path[-1]] = t
                else:
                    per_layer.setdefault(layer, {}).setdefault(
                        path[-1], {})[expert] = t
            for lt in per_layer.values():
                for k, v in lt.items():
                    if isinstance(v, dict):
                        lt[k] = np.stack([v[e] for e in range(len(v))])
            return per_layer, top

        _layers_np, _top_np = _read_all()

        def layer_host(li: int) -> dict[str, np.ndarray]:
            return _layers_np[li]

        def top_host() -> dict[str, np.ndarray]:
            return _top_np

    # -- per-layer host quantize + donated device splice --------------------
    # Quantization happens in HOST numpy, mirroring quant.quantize's exact
    # IEEE f32 ops (abs-max / 127 per output column, round-half-even) —
    # in-jit quantization may fuse the divide/round and drift +-1 from the
    # eager quantize_params path, breaking the bit-identity contract.
    L, H = config.num_layers, config.hidden_size
    E, NE = config.intermediate_size, config.num_experts
    if moe:
        dims: dict[str, tuple] = {
            "wqkv": (H, config.q_dim + 2 * config.kv_dim),
            "wo": (config.q_dim, H),
            "wgu_e": (NE, H, 2 * E),
            "w_down": (NE, E, H),
        }
    else:
        dims = {
            "wqkv": (H, config.q_dim + 2 * config.kv_dim),
            "wo": (config.q_dim, H),
            "wgu": (H, 2 * E),
            "w_down": (E, H),
        }
    bufs = {name: stream_bufs(L, shape, quant)
            for name, shape in dims.items()}

    import ml_dtypes

    def _bf16_round(w: np.ndarray) -> np.ndarray:
        # Round through bf16 first: the reference path (load bf16 tree,
        # then quantize_params) sees bf16-rounded weights, and HF shards
        # are often f32 — skipping the rounding would drift the scales.
        return np.asarray(w).astype(ml_dtypes.bfloat16).astype(np.float32)

    def _host_quant8(w: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
        # axis=-2 is the contraction axis for 2-D projections and the
        # [NE, H, F] expert stacks alike (quant.quantize's axis).
        wf = _bf16_round(w)
        amax = np.abs(wf).max(axis=-2, keepdims=True)
        s = np.where(amax > 0, amax / 127.0, 1.0).astype(np.float32)
        q = np.clip(np.round(wf / s), -127, 127).astype(np.int8)
        return q, s

    def _host_quant4(w: np.ndarray, group: int) -> tuple[np.ndarray,
                                                         np.ndarray]:
        # quant.quantize4's exact math in host numpy: group-wise abs-max
        # / 7, round-half-even, clip to [-7, 7], split-half nibble pack
        # (quant.pack4's layout; the uint8 view IS the explicit wrap).
        wf = _bf16_round(w)
        K = wf.shape[-2]
        ng = K // group
        g = wf.reshape(*wf.shape[:-2], ng, group, wf.shape[-1])
        amax = np.abs(g).max(axis=-2, keepdims=True)
        s = np.where(amax > 0, amax / 7.0, 1.0).astype(np.float32)
        qv = np.clip(np.round(g / s), -7, 7).astype(np.int32)
        qv = qv.reshape(*wf.shape[:-2], K, wf.shape[-1])
        lo = qv[..., :K // 2, :] + 8
        hi = qv[..., K // 2:, :] + 8
        q = (lo | (hi << 4)).astype(np.uint8).view(np.int8)
        return q, np.squeeze(s, -2)

    def host_quant(w: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
        # Per-leaf precision mirrors quant._quantize_leaf via the SAME
        # group chooser (per-layer leaves: dense 2-D, expert stacks
        # 3-D — matching _quantize_leaf's streaming-loop default).
        from .quant import _int4_group
        group = (_int4_group(w.shape[-2], w.ndim >= 3)
                 if quant == "int4" else None)
        if group is not None:
            return _host_quant4(w, group)
        return _host_quant8(w)

    @functools.partial(jax.jit, donate_argnums=(0,))
    def splice_layer(bufs, qs, layer):
        out = dict(bufs)
        for name, (q, s) in qs.items():
            out[name] = type(bufs[name])(q=bufs[name].q.at[layer].set(q),
                                         s=bufs[name].s.at[layer].set(s))
        return out

    norms: dict = {k: [] for k in norm_keys}
    routers = np.zeros((L, H, NE), np.float32) if moe else None
    for li in range(L):
        lt = layer_host(li)
        for k in norm_keys:
            norms[k].append(lt[k].astype(np.float32))
        fused = {
            "wqkv": np.concatenate(
                [lt["wq"], lt["wk"], lt["wv"]], axis=1),
            "wo": lt["wo"],
        }
        if moe:
            routers[li] = lt["router"].astype(np.float32)
            # Per-expert gate|up columns concatenate on the out axis —
            # scales concatenate with them (fused-quantize equivalence).
            fused["wgu_e"] = np.concatenate(
                [lt["w_gate"], lt["w_up"]], axis=-1)
            fused["w_down"] = lt["w_down"]
        else:
            fused["wgu"] = np.concatenate(
                [lt["w_gate"], lt["w_up"]], axis=1)
            fused["w_down"] = lt["w_down"]
        qs = {}
        for name, w in fused.items():
            q, s = host_quant(w)
            qs[name] = (jnp.asarray(q), jnp.asarray(s))
        bufs = splice_layer(bufs, qs, jnp.asarray(li))

    top = top_host()
    layers: dict = {
        **{k: jnp.asarray(np.stack(v), dtype) for k, v in norms.items()},
        **bufs,
    }
    if moe:
        layers["router"] = jnp.asarray(routers, dtype)
    params: dict = {
        "embed": jnp.asarray(top["embed"], dtype),
        "layers": layers,
        "final_norm": jnp.asarray(top["final_norm"], dtype),
    }
    if config.ut_steps > 1:
        for k in _EXIT_GATE_MAP:
            params[k] = jnp.asarray(top[k], dtype)
    if not config.tie_embeddings:
        # Host-side too: a device quantize of the 8B lm_head would spike
        # ~3 GB of bf16-upload + f32 temp on a chip already holding the
        # quantized tree (the same spike removed from synth.py's quote
        # head). The class mirrors host_quant's per-leaf precision
        # choice (quant._quantize_leaf's predicate).
        head = top["lm_head"]
        from .quant import _int4_group
        cls = (QTensor4 if (quant == "int4"
                            and _int4_group(head.shape[-2], False))
               else QTensor)
        q, s = host_quant(head)
        params["lm_head"] = cls(q=jnp.asarray(q), s=jnp.asarray(s))
    jax.block_until_ready(params)
    del host_params
    from .quant import quant_mode
    mode = quant_mode(params) or "int8"
    n_logical = sum(
        (2 * x.q.size if isinstance(x, QTensor4) else x.size)
        for x in jax.tree.leaves(
            params, is_leaf=lambda v: isinstance(v, QTensor4)))
    log.info("loaded %s quantized+fused (streaming, single-chip): "
             "%.2fB params %s", config.name, n_logical / 1e9, mode)
    return params, config
