"""One TPU chip per serving process — decided by parents that stay off JAX.

A chip belongs to one process at a time: a parent that has touched JAX
holds it, and a second process that finds no free chip comes up on the
CPU unless something stops it (serve/engine.py does, through
utils/device.require_tpu). So the processes that START serving replicas
— the dev launcher (start_all.py) and the router's autoscale spawner
(serve/router.py) — never import JAX. They count the host's chips from
the device files libtpu opens and confine each child to one chip with
libtpu's own environment variables (established on a four-chip v5e
host: four concurrent children, one device each).
"""

from __future__ import annotations

import glob
import os
import threading


def cpu_pinned() -> bool:
    """The operator pinned JAX to the CPU (``JAX_PLATFORMS=cpu`` — what
    the test suite exports): TPU-backend replicas then run on the CPU on
    purpose and need no chip."""
    first = os.environ.get("JAX_PLATFORMS", "").split(",")[0]
    return first.strip().lower() == "cpu"


def count_chips() -> int:
    """TPU chips on this host: ``/dev/vfio/<n>`` (v5e and newer) or
    ``/dev/accel<n>`` device files."""
    vfio = [p for p in glob.glob("/dev/vfio/*")
            if os.path.basename(p).isdigit()]
    return len(vfio) or len(glob.glob("/dev/accel[0-9]*"))


def chip_env(index: int) -> dict[str, str]:
    """Environment that confines a child process to chip ``index`` (its
    position among the host's chips, 0-based)."""
    return {"TPU_VISIBLE_CHIPS": str(index),
            "TPU_CHIPS_PER_PROCESS_BOUNDS": "1,1,1",
            "TPU_PROCESS_BOUNDS": "1,1,1"}


class ChipPool:
    """Chip indices a spawner may hand out, one per live child."""

    def __init__(self, chips: list[int]) -> None:
        self._mu = threading.Lock()
        self._free = sorted(chips)      # guarded-by: _mu

    def take(self) -> int | None:
        with self._mu:
            return self._free.pop(0) if self._free else None

    def give(self, chip: int) -> None:
        with self._mu:
            self._free.append(chip)
            self._free.sort()
