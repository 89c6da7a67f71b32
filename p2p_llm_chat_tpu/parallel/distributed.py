"""Multi-host (DCN) distributed runtime entry points.

The reference's only "distributed backend" is point-to-point chat streams
(SURVEY.md §5: no NCCL/MPI/Gloo anywhere); the TPU-native equivalent is
XLA collectives — ICI within a slice, DCN between hosts — driven entirely
by device meshes. This module is the multi-host glue:

- :func:`init_distributed` brings a process into the JAX distributed
  runtime (coordinator handshake, global device visibility). After it,
  ``jax.devices()`` spans every host and the regular ``make_mesh`` /
  ``shard_map`` programs run unchanged — XLA routes collectives over ICI
  inside a slice and DCN across slices.
- :func:`multihost_mesh` builds the hybrid mesh for that world: the
  slower DCN axis carries the replication-style parallelism (``dp`` —
  gradient/batch-level, least-frequent comms) while tp/ep/sp stay inside
  a slice on ICI, the layout the bandwidth hierarchy demands.

Env surface (cluster-launcher friendly, same env-first style as the rest
of the framework): ``JAX_COORDINATOR`` (host:port of process 0),
``JAX_NUM_PROCESSES``, ``JAX_PROCESS_ID``. On Cloud TPU pods
``jax.distributed.initialize()`` auto-discovers all three; the envs are
for bare-metal/manual launches.

Single-host fallback: with no coordinator configured this is a no-op and
everything runs on the local devices — the same code path the tests and
the single-chip bench use.
"""

from __future__ import annotations

import os
from typing import Optional

import jax
from jax.sharding import Mesh

from ..utils.log import get_logger
from .mesh import AXES, MeshConfig, make_mesh

log = get_logger("parallel.distributed")


def init_distributed(coordinator: Optional[str] = None,
                     num_processes: Optional[int] = None,
                     process_id: Optional[int] = None) -> bool:
    """Join the multi-host runtime; returns True when distributed mode is
    active. No-op (False) when neither args nor env configure a
    coordinator and the platform can't auto-discover one."""
    coordinator = coordinator or os.environ.get("JAX_COORDINATOR")
    n = num_processes if num_processes is not None else int(
        os.environ.get("JAX_NUM_PROCESSES", "0") or 0)
    pid = process_id if process_id is not None else int(
        os.environ.get("JAX_PROCESS_ID", "-1"))
    if coordinator is None and n == 0:
        return False
    # CPU-pinned processes (the two-process tests) need no set-up: jax
    # 0.9.0's jax_cpu_collectives_implementation already defaults to gloo.
    jax.distributed.initialize(
        coordinator_address=coordinator,
        num_processes=n or None,
        process_id=pid if pid >= 0 else None,
    )
    log.info("distributed runtime up: process %d/%d, %d global devices",
             jax.process_index(), jax.process_count(), len(jax.devices()))
    return True


def multihost_mesh(cfg: MeshConfig) -> Mesh:
    """Mesh over the global (multi-host) device set with the DCN/ICI
    split: ``dp`` spans hosts over DCN; pp/ep/sp/tp stay slice-local on
    ICI. ``cfg.size`` must equal the global device count and ``cfg.dp``
    must be a multiple of the process count (whole slices per replica).
    """
    n_proc = jax.process_count()
    if n_proc == 1:
        return make_mesh(cfg)
    devices = jax.devices()
    if cfg.size != len(devices):
        raise ValueError(f"mesh size {cfg.size} != global device count "
                         f"{len(devices)}")
    # Key the DCN layout on the SLICE topology, not the process count: a
    # slice can span several hosts (its devices are all on one ICI
    # fabric), so slices — not processes — are the unit a dp replica
    # must not straddle. Genuinely multi-slice pods go through the
    # hybrid builder, and an error from it (or a dp that doesn't divide
    # the slice count) is a real misconfiguration that must surface —
    # silently substituting an ICI-oblivious placement would bury a
    # severe interconnect performance cliff. Everything else — non-TPU
    # platforms, the forced-host test path (every CPU device reports
    # slice 0), a single multi-host slice — has no DCN hop to lay out,
    # and takes the process-grouped reshape.
    slice_ids = {getattr(d, "slice_index", None) for d in devices}
    if None not in slice_ids and len(slice_ids) > 1:
        n_slices = len(slice_ids)
        if cfg.dp % n_slices:
            raise ValueError(
                f"dp={cfg.dp} must be a multiple of slice count "
                f"{n_slices} (DCN carries dp; a replica cannot straddle "
                "a slice boundary)")
        from jax.experimental import mesh_utils
        ici = (cfg.dp // n_slices, cfg.pp, cfg.ep, cfg.sp, cfg.tp)
        dcn = (n_slices, 1, 1, 1, 1)
        arr = mesh_utils.create_hybrid_device_mesh(ici, dcn)
    else:
        # Group by process manually: dp outermost over sorted process
        # blocks — each process's devices fill whole dp rows, so a
        # replica never straddles a host.
        if cfg.dp % n_proc:
            raise ValueError(
                f"dp={cfg.dp} must be a multiple of process count "
                f"{n_proc} (a replica cannot straddle a host boundary)")
        import numpy as np
        log.warning(
            "single-slice or non-TPU device topology (%d slice ids over "
            "%d processes): building a process-grouped mesh instead of "
            "the ICI/DCN hybrid layout",
            len(slice_ids - {None}) or 1, n_proc)
        devs = sorted(devices, key=lambda d: (d.process_index, d.id))
        arr = np.array(devs).reshape(cfg.dp, cfg.pp, cfg.ep, cfg.sp,
                                     cfg.tp)
    return Mesh(arr, AXES)
