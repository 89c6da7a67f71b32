"""Native checkpoint save/resume (Orbax) for the serving stack.

SURVEY.md §5 "checkpoint/resume": the reference holds everything in
memory and regenerates identity per run (go/cmd/node/main.go:293-299,
README.md:134 lists persistence as future work); weights come out-of-tree
via ``ollama pull``. This module is the in-tree TPU-native equivalent for
the model side: params persist as an Orbax checkpoint — sharded,
async-friendly, restorable *directly onto a device mesh* so a 70B tree
restores shard-by-shard without ever materialising on one host.

Two formats live under ``CKPT_DIR`` (serve/engine.py auto-detects):
- HF-layout safetensors (models/weights.py) — interop with published
  llama/Mixtral checkpoints;
- this native format (``native_meta.json`` + Orbax tree) — fast resume of
  a tree we already converted/sharded once, at device-native dtypes.

Quantized (QTensor) trees are saved as-is is NOT supported: quantization
is cheap and deterministic (models/quant.py), so save the bf16 tree and
re-quantize after restore — one code path, no int8 serialization quirks.
"""

from __future__ import annotations

import json
import os
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding

from ..utils.log import get_logger
from ..parallel.sharding import LogicalRules, DEFAULT_RULES, spec_for
from .configs import CONFIGS, ModelConfig
from .quant import QTensor, QTensor4

log = get_logger("checkpoint")

_META = "native_meta.json"
_TREE = "params"


def is_native_checkpoint(ckpt_dir: str) -> bool:
    return os.path.exists(os.path.join(ckpt_dir, _META))


def peek_config(ckpt_dir: str) -> ModelConfig:
    """Resolve a native checkpoint's config from its metadata alone — no
    tensor reads (callers that gate on model family must decide BEFORE
    paying a multi-GB restore)."""
    with open(os.path.join(ckpt_dir, _META)) as f:
        meta = json.load(f)
    if meta["config"] not in CONFIGS:
        raise ValueError(f"unknown config {meta['config']!r} in {ckpt_dir}")
    return CONFIGS[meta["config"]]


def save_checkpoint(ckpt_dir: str, params: dict, config: ModelConfig) -> None:
    """Persist a param tree + config. The tree must be unquantized (see
    module docstring); sharded arrays are gathered/written per-shard by
    Orbax."""
    import orbax.checkpoint as ocp

    if any(isinstance(x, (QTensor, QTensor4)) for x in jax.tree.leaves(
            params, is_leaf=lambda x: isinstance(x, (QTensor, QTensor4)))):
        raise ValueError("save the bf16 tree and re-quantize after restore "
                         "(models/checkpoint.py docstring)")
    ckpt_dir = os.path.abspath(ckpt_dir)
    os.makedirs(ckpt_dir, exist_ok=True)
    dtype = jax.tree.leaves(params)[0].dtype
    with ocp.StandardCheckpointer() as ckptr:
        ckptr.save(os.path.join(ckpt_dir, _TREE), params, force=True)
    # Meta is written LAST: its presence is the completeness marker
    # (is_native_checkpoint, cache-reuse checks) — writing it first
    # would make an interrupted multi-GB save look like a valid
    # checkpoint forever after.
    with open(os.path.join(ckpt_dir, _META), "w") as f:
        json.dump({"config": config.name, "dtype": str(dtype)}, f)
    log.info("saved %s (%s) to %s", config.name, dtype, ckpt_dir)


def load_checkpoint(ckpt_dir: str, mesh: Optional[Mesh] = None,
                    rules: LogicalRules = DEFAULT_RULES,
                    device=None) -> tuple[dict, ModelConfig]:
    """Restore a native checkpoint, placing each leaf with its logical
    sharding when a mesh is given — Orbax reads straight into the sharded
    buffers, so host memory never holds the full tree."""
    import orbax.checkpoint as ocp

    from . import family_for

    ckpt_dir = os.path.abspath(ckpt_dir)
    with open(os.path.join(ckpt_dir, _META)) as f:
        meta = json.load(f)
    if meta["config"] not in CONFIGS:
        raise ValueError(f"unknown config {meta['config']!r} in {ckpt_dir}")
    config = CONFIGS[meta["config"]]
    family = family_for(config)
    dtype = jnp.dtype(meta["dtype"])

    abstract = jax.eval_shape(
        lambda: family.init_params(config, jax.random.PRNGKey(0),
                                   dtype=dtype))
    if mesh is not None:
        axes = family.param_axes(config)
        abstract = jax.tree.map(
            lambda a, ax: jax.ShapeDtypeStruct(
                a.shape, a.dtype,
                sharding=NamedSharding(mesh, spec_for(ax, rules))),
            abstract, axes,
            is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct))
    else:
        # orbax 0.11.32 restores a bare ShapeDtypeStruct too, but from
        # the sharding file the SAVING topology wrote (it warns that is
        # unsafe on another topology). A concrete sharding on every leaf
        # pins the target instead, and is what lets ``device`` choose it
        # — weights.load_checkpoint_quantized restores to a CPU device
        # so a 16 GB bf16 tree never touches a 16 GB chip.
        single = jax.sharding.SingleDeviceSharding(
            device if device is not None else jax.devices()[0])
        abstract = jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                           sharding=single),
            abstract,
            is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct))
    with ocp.StandardCheckpointer() as ckptr:
        params = ckptr.restore(os.path.join(ckpt_dir, _TREE), abstract)
    log.info("restored %s (%s) from %s%s", config.name, dtype, ckpt_dir,
             f" onto mesh {dict(mesh.shape)}" if mesh is not None else "")
    return params, config
