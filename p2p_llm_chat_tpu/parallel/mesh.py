"""Device-mesh construction.

Canonical mesh axes for the whole framework (scoped by BASELINE.json's
configs — TP for 70B over ICI, EP for Mixtral, DP/batching, and sequence/
pipeline axes so context and pipeline parallelism can attach, per
SURVEY.md §2):

- ``dp``: data parallel (replicated weights, sharded batch)
- ``pp``: pipeline parallel (layer stack sharded into stages —
  parallel/pipeline.py)
- ``tp``: tensor parallel (sharded heads / mlp / vocab)
- ``ep``: expert parallel (sharded experts; reuses tp chips for dense parts)
- ``sp``: sequence/context parallel (ring attention shards)

A mesh never needs all axes > 1; size-1 axes cost nothing under XLA's
partitioner, so every program is written against the full 5-axis mesh.
"""

from __future__ import annotations

from dataclasses import dataclass

import jax
import numpy as np
from jax.sharding import Mesh

AXES = ("dp", "pp", "ep", "sp", "tp")


@dataclass(frozen=True)
class MeshConfig:
    dp: int = 1
    pp: int = 1
    ep: int = 1
    sp: int = 1
    tp: int = 1

    @property
    def size(self) -> int:
        return self.dp * self.pp * self.ep * self.sp * self.tp

    @classmethod
    def for_devices(cls, n: int, tp: int | None = None) -> "MeshConfig":
        """Default layout: everything tensor-parallel (the decode-serving
        sweet spot on a single slice — weights sharded, batch replicated)."""
        return cls(tp=n if tp is None else tp,
                   dp=1 if tp is None else n // tp)


def make_mesh(cfg: MeshConfig, devices: list | None = None) -> Mesh:
    """Build a Mesh with the canonical axis order.

    Axis order matters for ICI locality: ``tp`` is innermost so
    tensor-parallel collectives (the per-layer latency-critical ones) ride
    neighbouring chips; ``dp`` is outermost (least-frequent comms).

    When the mesh covers every visible device, device assignment goes
    through ``mesh_utils.create_device_mesh``, which matches the logical
    axes onto the slice's physical ICI topology (ring/torus orderings)
    instead of flat enumeration order — measurably better collective
    bandwidth on real 2D-torus slices, identical behavior on CPU.
    """
    shape = (cfg.dp, cfg.pp, cfg.ep, cfg.sp, cfg.tp)
    devs = devices if devices is not None else jax.devices()
    if cfg.size > len(devs):
        raise ValueError(f"mesh needs {cfg.size} devices, have {len(devs)}")
    if devices is None and cfg.size == len(devs):
        # A topology the helper cannot map raises: falling back to flat
        # enumeration order would be a silent placement decision on
        # real chips.
        from jax.experimental import mesh_utils
        return Mesh(mesh_utils.create_device_mesh(shape), AXES)
    return Mesh(np.array(devs[: cfg.size]).reshape(shape), AXES)


def local_mesh(tp: int | None = None) -> Mesh:
    """Mesh over all locally visible devices (single-host path)."""
    n = len(jax.devices())
    return make_mesh(MeshConfig.for_devices(n, tp=tp))
