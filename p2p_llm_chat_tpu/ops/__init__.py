"""Pallas TPU kernels and the paged KV-cache machinery.

The north-star serving path (BASELINE.json; SURVEY.md §7 stage 4) replaces
the dense ``[L, B, max_seq, Hkv, D]`` cache — whose HBM footprint reserves
``max_seq`` slots for every batch row — with a paged pool: fixed-size pages
allocated per request for its *actual* context budget, addressed through a
page table, laid out token-major so pages read/write as contiguous blocks.
Decode attention over the pool (ops/paged_attention.py) attends before
the step's k/v is written and has two implementations chosen by window
and pool geometry: a page-granular XLA gather at short windows and a
Pallas flash-append kernel walking scalar-prefetched page-table indices
at long ones — either way HBM reads scale with live context, never with
allocation.

Modules:
- :mod:`.paged_kv` — PagedKVCache pytree, host-side page allocator, and the
  pure-JAX page write/gather ops.
- :mod:`.paged_attention` — paged decode attention
  (``paged_attention_append``, ``paged_attention_verify_append``,
  ``gather_window``; a jnp reference oracle, and ``interpret=True`` on
  the kernel for hardware-free tests, per SURVEY.md §4).
- :mod:`.quant_mm` — Pallas w8a16 matmul streaming int8 weights through
  VMEM dequant (models/quant.py's decode path; XLA alone materialises a
  bf16 weight copy, defeating the bandwidth win).
"""

from .paged_kv import PagedKVCache, PageAllocator
from .paged_attention import paged_attention_reference
from .quant_mm import quant_matmul

__all__ = ["PagedKVCache", "PageAllocator", "paged_attention_reference",
           "quant_matmul"]
