"""JAX model definitions for the TPU serving stack.

The reference delegates all modelling to Ollama (SURVEY.md §1 L4); these are
the in-tree replacements mandated by BASELINE.json's configs: the llama
family (3.1-8B / 3.1-70B and smaller test sizes) and Mixtral-8x7B MoE.

Design (TPU-first, not a port of any torch code):

- pure-functional: params are nested dicts of ``jax.Array``; forward passes
  are plain jitted functions. No framework Module state.
- layers are *stacked* along a leading ``num_layers`` axis and the decoder
  runs as one ``lax.scan`` — O(1) XLA graph size in depth, fast compiles
  for 32-80 layer models.
- every parameter/activation has a logical-axis annotation
  (parallel/sharding.py) so the same code runs single-chip, tensor-parallel
  or expert-parallel by switching the mesh.
- compute in bfloat16 on the MXU, reductions/norms in float32.
"""

from .configs import ModelConfig, CONFIGS, get_config
from . import llama


def family_for(config: ModelConfig):
    """The model module (llama, mixtral, pangu or nemotron_h) implementing this config.

    Both families expose the same functional surface — init_params,
    param_axes, prefill, decode_step (identical signatures and KVCache
    contract) — so the serving stack (serve/scheduler.py, serve/engine.py)
    and the driver dryrun dispatch on ``config.is_moe`` alone.
    """
    if config.is_latent:
        from . import pangu
        return pangu
    if config.is_hybrid:
        from . import nemotron_h
        return nemotron_h
    if config.is_moe:
        from . import mixtral
        return mixtral
    return llama


__all__ = ["ModelConfig", "CONFIGS", "get_config", "llama", "family_for"]
