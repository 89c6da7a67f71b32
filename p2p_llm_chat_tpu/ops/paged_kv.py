"""Paged KV cache: page pool, page table, allocator, and write ops.

Replaces the dense cache's per-row ``max_seq`` reservation (models/llama.py
KVCache) with fixed-size pages drawn from a shared pool, so HBM holds the
sum of live context budgets instead of ``num_slots x max_seq``. The pool
layout is **token-major within a page**:

    k/v: [L, num_pages, page_size, Hkv, D]

— one token's kv is a contiguous ``[Hkv, D]`` window and one page is a
contiguous ``[page_size, Hkv, D]`` block, exactly the dense cache's slot
order. That makes the decode write a dense-shaped scatter, the admission
splice a transpose-free reshape, and a whole-page gather a contiguous
block read (ops/paged_attention.py's gather path) — measured ~10x
faster end-to-end than the earlier head-major layout, whose strided
windows made XLA scatters and per-(head,page) kernel programs dominate
the decode tick. Page 0 is a permanent garbage bin: padded prefill slots
and parked decode rows write there, so masked writes never need a branch
(the overwrite-before-trust invariant of the dense path becomes a
write-to-trash invariant here).

All device-side state is a pytree (works as a jit carry / donated arg);
the allocator is host-side bookkeeping owned by the scheduler thread.
"""

from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from ..models.configs import ModelConfig


class PagedKVCache(NamedTuple):
    """k/v: [L, num_pages, page_size, Hkv, D]; page_table: [B, max_pages]
    (physical page id per logical page; unused entries MUST hold 0 — the
    garbage page — so kernel-side fetches of dead pages stay in bounds);
    lengths: [B] live tokens per row.

    A latent-attention model's pool (models/pangu.py) has the same
    leaves under the same page table and write ops: one head, ``k`` =
    the normed latent row [.., 1, kv_lora_rank] and ``v`` = the shared
    rotated key [.., 1, rope dim padded to 128 lanes], each with its own
    scale a token when int8 (``ModelConfig.cache_*``); nothing per head.

    Quantized pool (``create(..., quantized=True)``): k/v store int8 with
    per-(layer, slot, kv-head) float32 scales ``k_scale``/``v_scale``,
    stored HEAD-MAJOR as ``[L, num_pages, Hkv, page_size]`` — symmetric
    over the head_dim axis, the same scheme models/quant.py uses over
    matmul contractions. Decode attention is KV-bandwidth-bound, so int8
    halves the dominant read (measured ~0.3 ms off a B=32 bench-1b step
    on v5e) and doubles how much context one pool holds; the scales fold
    into k/v at the in-register dequant, so the MXU still consumes the
    int8 stream directly. bf16 pools keep scale = None.

    Why head-major: the flash-append kernel
    (ops/paged_attention._flash_append_kernel_body) DMAs one page's
    scales as contiguous ``[page_size]`` lane vectors a kv-head and folds
    them into the VMEM dequant — with Hkv (= 8) as the minor dim that
    slice is strided 8 ways, a shape Mosaic cannot form. ``k_scale_view``/
    ``v_scale_view`` return the logical [L, N, ps, Hkv] order for
    oracles/tests.

    What head-major does NOT settle is how the scales are written. The
    slot is the lane (minor) dimension, and a scatter that indexes it
    (``arr.at[:, phys, :, slot].set``) makes XLA's TPU backend copy the
    whole array into a layout with the indexed dimensions major (``Hkv``
    or ``L`` padded to 128 lanes), scatter there and copy it back: four
    whole-array copies and 0.3-1.1 GB of temporaries a decode step, 1-2
    ms at the benchmark's pools (PERF.md §6, PR 29; earlier notes blamed
    the fused scan's while carry). The rule for a write path: **index
    pages, never lanes** — gather the ``[Hkv, ps_pad]`` tiles of the
    pages written, replace lanes with ``jnp.where``, scatter the tiles on
    the page dimension (:func:`_scatter_scale_tiles`); that compiles to
    an in-place update of the donated array. The decode write and the
    chunk ladder's splices follow it (tests/test_pool_write_layout.py);
    the lane scatters left (:func:`write_decode_multi_all_layers`,
    :func:`copy_slot`, :func:`write_prefill`, :func:`write_prefill_row`:
    speculation and the one-shot prefills; :func:`write_decode`, which
    only tests call) are correct and pay the relayout.
    """

    k: jax.Array
    v: jax.Array
    page_table: jax.Array
    lengths: jax.Array
    k_scale: Optional[jax.Array] = None
    v_scale: Optional[jax.Array] = None
    # A hybrid model's recurrent state beside the pages, indexed by slot
    # (ops/state_pool.StatePool, ``batch`` + 1 rows: the last is the
    # garbage row); ``L`` above is then its attention layers alone.
    state: Optional[object] = None

    @property
    def page_size(self) -> int:
        return self.k.shape[2]

    @property
    def num_pages(self) -> int:
        return self.k.shape[1]

    @property
    def quantized(self) -> bool:
        return self.k_scale is not None

    @property
    def max_pages_per_row(self) -> int:
        return self.page_table.shape[1]

    @property
    def k_scale_view(self) -> jax.Array:
        """k_scale in logical [L, N, page_size, Hkv] order (transposed,
        lane-padding sliced off the head-major storage)."""
        return self.k_scale[..., : self.page_size].transpose(0, 1, 3, 2)

    @property
    def v_scale_view(self) -> jax.Array:
        return self.v_scale[..., : self.page_size].transpose(0, 1, 3, 2)

    @classmethod
    def create(cls, config: ModelConfig, batch: int, num_pages: int,
               page_size: int, max_pages_per_row: Optional[int] = None,
               dtype=jnp.bfloat16, quantized: bool = False,
               mesh=None) -> "PagedKVCache":
        lead = (config.cache_layers, num_pages, page_size,
                config.cache_kv_heads)
        shape = lead + (config.cache_k_dim,)
        vshape = lead + (config.cache_v_dim,)
        if max_pages_per_row is None:
            max_pages_per_row = num_pages
        if quantized:
            # Minor dim padded to a full 128-lane tile: Mosaic DMAs of a
            # [Hkv, ps] scale page must be lane-aligned (ps = 64 is half
            # a tile). Slots past page_size are never written or read.
            ps_pad = -(-page_size // 128) * 128
            sshape = (config.cache_layers, num_pages,
                      config.cache_kv_heads, ps_pad)
            cache = cls(
                k=jnp.zeros(shape, jnp.int8), v=jnp.zeros(vshape, jnp.int8),
                page_table=jnp.zeros((batch, max_pages_per_row), jnp.int32),
                lengths=jnp.zeros((batch,), jnp.int32),
                k_scale=jnp.zeros(sshape, jnp.float32),
                v_scale=jnp.zeros(sshape, jnp.float32),
            )
        else:
            cache = cls(
                k=jnp.zeros(shape, dtype), v=jnp.zeros(vshape, dtype),
                page_table=jnp.zeros((batch, max_pages_per_row), jnp.int32),
                lengths=jnp.zeros((batch,), jnp.int32),
            )
        if config.state_layers:
            from .state_pool import StatePool
            cache = cache._replace(
                state=StatePool.create(config, batch + 1, dtype,
                                       quantized=quantized))
        if mesh is not None:
            cache = shard_cache(cache, mesh)
        return cache


def shard_cache(cache: PagedKVCache, mesh,
                tp_axis: str = "tp") -> PagedKVCache:
    """Shard the pool over kv heads (tp) — the memory-fit half of the
    tensor-parallel serving story: without it every chip holds the FULL
    pool and TP cannot serve contexts one chip's HBM can't. k/v shard
    dim 3 (Hkv of [L, N, ps, Hkv, D]); the head-major
    scale arrays shard dim 2; page_table/lengths replicate (host-written
    per tick). Falls back to replication when Hkv doesn't divide tp
    (tiny test configs — same policy as parallel/sharding.constrain)."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    if tp_axis not in mesh.shape:
        return cache
    t = mesh.shape[tp_axis]
    hkv = cache.k.shape[3]
    ax = tp_axis if t > 1 and hkv % t == 0 else None

    def put(arr, spec):
        return jax.device_put(arr, NamedSharding(mesh, spec))

    rep = P()
    out = cache._replace(
        k=put(cache.k, P(None, None, None, ax)),
        v=put(cache.v, P(None, None, None, ax)),
        page_table=put(cache.page_table, rep),
        lengths=put(cache.lengths, rep),
    )
    if cache.quantized:
        out = out._replace(
            k_scale=put(cache.k_scale, P(None, None, ax)),
            v_scale=put(cache.v_scale, P(None, None, ax)))
    return out


def quant_kv(x: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Symmetric int8 over the trailing head_dim axis: x [..., Hkv, D] ->
    (int8 [..., Hkv, D], f32 scale [..., Hkv]). (bf16 scales were tried
    to shrink the scale arrays' whole-array copies — a lane-indexed
    scatter's relayout, not the while carry: PagedKVCache's docstring —
    and the bf16 scale GATHER is ~5x slower than f32's on v5e and
    regressed the step: f32 stays.)"""
    xf = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(xf), axis=-1)
    s = jnp.where(amax > 0, amax / 127.0, 1.0)
    q = jnp.clip(jnp.round(xf / s[..., None]), -127, 127).astype(jnp.int8)
    return q, s


class PageAllocator:
    """Host-side free-list over physical pages 1..num_pages-1 (page 0 is
    the shared garbage bin and is never handed out). Owned by the
    scheduler thread; no locking needed there (SURVEY.md §5 single-thread
    scheduler discipline)."""

    def __init__(self, num_pages: int, page_size: int) -> None:
        if num_pages < 2:
            raise ValueError("need >= 2 pages (page 0 is reserved)")
        self.page_size = page_size
        self.num_pages = num_pages
        self._free: list[int] = list(range(num_pages - 1, 0, -1))

    @property
    def free_pages(self) -> int:
        return len(self._free)

    def pages_for(self, tokens: int) -> int:
        """Pages needed to hold ``tokens`` slots."""
        return max(1, -(-tokens // self.page_size))

    def alloc(self, n: int) -> Optional[list[int]]:
        """n physical pages, or None if the pool can't satisfy it (caller
        backpressures — the request waits, nothing is partially held)."""
        if n <= 0:
            raise ValueError(f"alloc({n}): need a positive page count")
        if n > len(self._free):
            return None
        taken = self._free[-n:]
        del self._free[-n:]
        return taken

    def free(self, pages: list[int]) -> None:
        for p in pages:
            if not 0 < p < self.num_pages:
                raise ValueError(f"freeing invalid page {p}")
        self._free.extend(pages)


# -- device-side write ops (pure JAX; used inside jitted serving programs) ----

def _scatter_kv(cache: PagedKVCache, new_k: jax.Array, new_v: jax.Array,
                scatter, sscatter=None) -> PagedKVCache:
    """Apply ``scatter(pool_array, update)`` to k and v — quantizing the
    updates (and scattering their scales via ``sscatter``, the
    head-major [L, N, Hkv, ps] twin of the pool index expression) when
    the pool is int8. Centralises the only difference between the bf16
    and quantized write paths."""
    if not cache.quantized:
        return cache._replace(k=scatter(cache.k, new_k),
                              v=scatter(cache.v, new_v))
    qk, sk = quant_kv(new_k)
    qv, sv = quant_kv(new_v)
    return cache._replace(
        k=scatter(cache.k, qk), v=scatter(cache.v, qv),
        k_scale=sscatter(cache.k_scale, sk),
        v_scale=sscatter(cache.v_scale, sv))


def _scatter_scale_tiles(arr: jax.Array, phys: jax.Array, lanes: jax.Array,
                         vals: jax.Array) -> jax.Array:
    """Store scales into ``arr`` [L, N, Hkv, ps_pad] as WHOLE page tiles:
    gather the ``[L, Hkv, ps_pad]`` tiles of pages ``phys`` [T], replace
    the lanes ``lanes`` [T, ps_pad] (bool) marks with ``vals``
    ([L, T, Hkv, ps_pad], or anything that broadcasts to it), write every
    other lane back as it was read, and scatter the tiles on the page
    dimension alone: the floats a lane-indexed
    ``arr.at[:, phys, :, slot].set`` stores, without its relayout of the
    whole array (PagedKVCache: "index pages, never lanes"). Pages in
    ``phys`` must be distinct apart from garbage page 0, whose tile then
    holds one of its writers' (garbage by contract); an out-of-range
    page is dropped."""
    tiles = jnp.where(lanes[None, :, None, :], vals, arr[:, phys])
    return arr.at[:, phys].set(tiles, mode="drop")


def write_prefill(cache: PagedKVCache, layer_k: jax.Array, layer_v: jax.Array,
                  rows: jax.Array, lens: jax.Array) -> PagedKVCache:
    """Splice a dense prefill chunk's KV into the pool.

    layer_k/v: [L, R, S, Hkv, D] (the small dense cache a prefill chunk
    produced — serve/scheduler.py admission path); rows: [R] target batch
    rows; lens: [R] valid tokens per chunk row. Positions past ``lens`` are
    routed to garbage page 0 slot 0; valid positions go to the page/slot
    the row's page table maps them to. The row's page_table entries must
    already be set (set_row_table).
    """
    L, R, S, Hkv, D = layer_k.shape
    ps = cache.page_size
    pos = jnp.arange(S)[None, :]                       # [1,S]
    valid = pos < lens[:, None]                        # [R,S]
    logical = pos // ps                                # [1,S] -> broadcast [R,S]
    logical = jnp.broadcast_to(logical, (R, S))
    phys = jnp.take_along_axis(cache.page_table[rows], logical, axis=1)  # [R,S]
    phys = jnp.where(valid, phys, 0)
    slot = jnp.where(valid, jnp.broadcast_to(pos % ps, (R, S)), 0)

    # [L,R,S,Hkv,D] -> scatter at (layer, phys, slot). The advanced
    # indices (phys, slot) are adjacent dims, so the update keeps the
    # array order: [L, R, S, Hkv, D] — no axis shuffling.
    cache = _scatter_kv(cache, layer_k, layer_v,
                        lambda arr, upd: arr.at[:, phys, slot].set(
                            upd, mode="drop"),
                        # head-major scale target; non-adjacent advanced
                        # indices (dims 1, 3) move to the front: update
                        # [R, S, L, Hkv]
                        lambda arr, upd: arr.at[:, phys, :, slot].set(
                            upd.transpose(1, 2, 0, 3), mode="drop"))
    lengths = cache.lengths.at[rows].set(lens.astype(cache.lengths.dtype))
    return cache._replace(lengths=lengths)


def write_prefill_batch(cache: PagedKVCache, chunk_k: jax.Array,
                        chunk_v: jax.Array, rows: jax.Array,
                        lens: jax.Array, tables: jax.Array) -> PagedKVCache:
    """Splice a whole admission chunk's prefill KV into the pool in ONE
    page-granular scatter (serve/scheduler.py hot path).

    Two rejected designs, for the record: R sequential per-row scatters
    made paged admission ~8x slower than dense, and a single *per-token*
    scatter (R*S indices, each a strided [L,Hkv,D] window) barely helped —
    TPU scatters want few indices with large contiguous windows. Here the
    unit is the pool's own page: each (row, logical page) copies one
    [L,<=page_size,Hkv,D] block, so a 32-request x 128-token chunk is 64
    window-copies instead of 4096 strided ones — and with the token-major
    pool layout the chunk->page reshape is free (no transpose).

    chunk_k/v: [L, R, S, Hkv, D] for any S (smaller than one page writes a
    partial leading tile; non-page-aligned S pads the last tile — padded
    slots land past ``lens`` or in garbage page 0, never attended); rows:
    [R] target batch rows, padding entries set to an out-of-range sentinel
    (>= B) so their table/length installs drop; lens: [R] valid tokens;
    tables: [R, max_pages_per_row] physical page ids, zero-padded past
    each row's allocation (and all-zero for padding entries).

    Ordering safety: real rows' allocated pages are disjoint and real row
    indices unique, so the only duplicate scatter index is garbage page 0
    — whose content is garbage by contract either way. Slots past a row's
    ``lens`` inside an *allocated* page receive stale prefill values;
    they are never attended (length-masked) and decode overwrites slot
    ``lengths[b]`` before trusting it — the overwrite-before-trust
    invariant. Logical pages past the allocation land in page 0.
    """
    L, R, S, Hkv, D = chunk_k.shape
    P, ps_eff = _page_tiling(S, cache.page_size)
    phys = tables[:, :P].reshape(R * P).astype(jnp.int32)
    cache = _tile_scatter(cache, chunk_k, chunk_v, phys, P, ps_eff)
    table = cache.page_table.at[rows].set(tables.astype(jnp.int32),
                                          mode="drop")
    lengths = cache.lengths.at[rows].set(lens.astype(cache.lengths.dtype),
                                         mode="drop")
    return cache._replace(page_table=table, lengths=lengths)


def _page_tiling(S: int, ps: int) -> tuple[int, int]:
    """(page tiles P, effective tile width): a sub-page span is one
    partial leading tile; otherwise ceil(S/ps) full-width tiles (the
    last padded by _tile_scatter when S doesn't page-align)."""
    return (1, S) if S < ps else (-(-S // ps), ps)


def _tile_scatter(cache: PagedKVCache, chunk_k: jax.Array,
                  chunk_v: jax.Array, phys: jax.Array, P: int,
                  ps_eff: int) -> PagedKVCache:
    """The page-tile window scatter shared by write_prefill_batch and
    write_prefill_chunk's aligned path: one [L,<=page_size,Hkv,D] copy
    per (row, logical page), ``phys`` [R*P] the physical page per tile.
    Tables/lengths are NOT touched — callers own that install."""
    L, R, S = chunk_k.shape[:3]
    ps = cache.page_size

    # [L,R,S,...] -> [L, R*P, ps_eff, ...]: one pool page per (row,
    # logical page) — a pure reshape under the token-major layout (pads
    # the last tile first when S doesn't page-align).
    def tiles(x):
        if S % ps and S >= ps:
            pad = [(0, 0)] * x.ndim
            pad[2] = (0, P * ps - S)
            x = jnp.pad(x, pad)
        return x.reshape(L, R * P, ps_eff, *x.shape[3:])

    def values(arr, upd):
        if cache.quantized and arr.shape[3:] == (4, 128):
            # An int8 pool of FOUR KV heads x 128: the TPU compiler packs
            # a token's four heads into one sublane row, takes the whole
            # pool through a token-minor layout for a page-window scatter
            # and copies it back (two 1 GB copies of each of K and V a
            # write in Mellum's cell: PERF.md section 6, PR 40; 2, 8 and
            # 16 heads x 128 are written where they lie). The same tiles
            # indexed (page, slot) a token keep the pool's layout, as
            # write_prefill_chunk's mid-page path does.
            return arr.at[:, phys[:, None], jnp.arange(ps_eff)[None, :]].set(
                tiles(upd), mode="drop")
        return arr.at[:, phys, :ps_eff].set(tiles(upd), mode="drop")

    return _scatter_kv(cache, chunk_k, chunk_v, values,
                       lambda arr, upd: arr.at[:, phys, :, :ps_eff].set(
                           tiles(upd).transpose(0, 1, 3, 2), mode="drop"))


def write_prefill_chunk(cache: PagedKVCache, chunk_k: jax.Array,
                        chunk_v: jax.Array, tables: jax.Array,
                        start: int) -> PagedKVCache:
    """Splice ONE continuation-prefill chunk into the pool — the
    incremental unit of chunked admission (serve/scheduler.py): each
    chunk of a long prompt lands in the pool as it is computed, so the
    final chunk's dispatch splices C tokens, not the whole prompt.

    chunk_k/v: [L, R, C, Hkv, D] covering token positions
    ``start .. start+C`` of each row; tables: [R, max_pages_per_row]
    physical page ids (zero-padded past each row's allocation; all-zero
    for padding entries). Deliberately installs NEITHER tables NOR
    lengths — the scheduler routes every chunk through the ``tables``
    operand and installs the row state atomically with the FINAL chunk,
    so a half-prefilled row never looks live to the decode loop (its
    live page_table row stays zeroed and parked-row garbage writes keep
    landing in page 0 while the chunks accumulate).

    A page-aligned ``start`` (the plain chunk ladder — chunk budgets are
    power-of-two and >= the default page size) takes
    :func:`write_prefill_batch`'s page-tile scatter shifted by
    ``start // page_size``; an unaligned start (a prefix-offset chunk —
    the broadcast prefix shifts every boundary by the registered prefix
    length — or a sub-page chunk budget) falls back to a per-token
    scatter of the values, and lands an int8 pool's scales per page
    tile (:func:`_scatter_scale_tiles`). Positions past a row's
    allocation hit zero table entries
    (or the width clamp) and land in garbage page 0 — the containment
    write_prefill_batch documents."""
    L, R, C, Hkv, D = chunk_k.shape
    ps = cache.page_size
    def span_pages(P):
        """[R*P] physical pages of logical pages start//ps .. +P-1 per
        row; past the table's width: garbage page 0."""
        lp = start // ps + jnp.arange(P)               # logical pages
        idx = jnp.minimum(lp, tables.shape[1] - 1)
        phys = jnp.where((lp < tables.shape[1])[None, :],
                         tables.astype(jnp.int32)[:, idx], 0)
        return phys.reshape(R * P)

    if start % ps == 0:
        P, ps_eff = _page_tiling(C, ps)
        return _tile_scatter(cache, chunk_k, chunk_v, span_pages(P), P,
                             ps_eff)
    # Mid-page start: per-token indices (write_prefill's shape) with the
    # chunk's position offset; slower than page tiles but only the
    # prefix-offset chunks pay it.
    pos = start + jnp.arange(C)                        # [C]
    logical = pos // ps
    safe = jnp.minimum(logical, tables.shape[1] - 1)
    phys = jnp.take_along_axis(tables.astype(jnp.int32),
                               jnp.broadcast_to(safe[None, :], (R, C)),
                               axis=1)                 # [R,C]
    phys = jnp.where((logical < tables.shape[1])[None, :], phys, 0)
    slot = jnp.broadcast_to((pos % ps)[None, :], (R, C))

    def scale_tiles(arr, upd):                         # upd [L, R, C, Hkv]
        # The scales go per PAGE, not per token (several tokens of the
        # chunk share a page): each (row, page) tile of the static page
        # span start//ps .. (start+C-1)//ps takes the chunk's scales on
        # the lanes whose position falls inside start..start+C and keeps
        # the rest.
        p0 = start // ps
        P = (start + C - 1) // ps - p0 + 1
        lead, ps_pad = start - p0 * ps, arr.shape[3]
        lane = jnp.arange(ps_pad)[None, :]
        tpos = (p0 + jnp.arange(P))[:, None] * ps + lane   # [P, ps_pad]
        lanes = (lane < ps) & (tpos >= start) & (tpos < start + C)
        upd = jnp.pad(upd, ((0, 0), (0, 0),
                            (lead, P * ps - lead - C), (0, 0)))
        upd = upd.reshape(L, R * P, ps, Hkv).transpose(0, 1, 3, 2)
        upd = jnp.pad(upd, ((0, 0),) * 3 + ((0, ps_pad - ps),))
        return _scatter_scale_tiles(arr, span_pages(P),
                                    jnp.tile(lanes, (R, 1)), upd)

    return _scatter_kv(cache, chunk_k, chunk_v,
                       lambda arr, upd: arr.at[:, phys, slot].set(
                           upd, mode="drop"),
                       scale_tiles)


def write_prefill_row(cache: PagedKVCache, row_k: jax.Array,
                      row_v: jax.Array, row: jax.Array, length: jax.Array,
                      table_row: jax.Array) -> PagedKVCache:
    """Splice ONE request's prefill KV into the pool and install its page
    map — the admission-program unit (serve/scheduler.py unrolls R of
    these sequentially, so later real entries overwrite earlier padding
    entries deterministically; padding entries pass an all-zero
    ``table_row`` so their writes land in garbage page 0).

    row_k/v: [L, S, Hkv, D]; row: scalar target batch row; length: scalar
    valid tokens; table_row: [max_pages_per_row] physical page ids.
    """
    L, S, Hkv, D = row_k.shape
    ps = cache.page_size
    pos = jnp.arange(S)
    valid = pos < length
    phys = jnp.where(valid, table_row[pos // ps], 0)   # [S]
    slot = jnp.where(valid, pos % ps, 0)
    # cache.k: [L, N, ps, Hkv, D]; adjacent advanced indices (phys, slot)
    # keep the update in array order: [L, S, Hkv, D] = row_k as-is.
    cache = _scatter_kv(cache, row_k, row_v,
                        lambda arr, upd: arr.at[:, phys, slot].set(upd),
                        # update [S, L, Hkv] (advanced dims 1, 3 -> front)
                        lambda arr, upd: arr.at[:, phys, :, slot].set(
                            upd.transpose(1, 0, 2)))
    table = cache.page_table.at[row].set(table_row.astype(jnp.int32))
    lengths = cache.lengths.at[row].set(length.astype(cache.lengths.dtype))
    return cache._replace(page_table=table, lengths=lengths)


def write_decode(cache: PagedKVCache, layer: jax.Array, k: jax.Array,
                 v: jax.Array) -> PagedKVCache:
    """Write one decode step's k/v for every row into its current slot,
    one layer. No serving program calls this (a step's layers land
    together, :func:`write_decode_burst`): tests use it to build the pool
    a reference attends, with the current token written in.

    k/v: [B, Hkv, D]; row b writes page ``page_table[b, lengths[b]//ps]``
    slot ``lengths[b] % ps`` of ``layer``. Parked rows (whose length the
    caller will not advance) overwrite the same slot next step — and their
    page-table entry for a never-grown row is 0, the garbage bin.
    """
    B = k.shape[0]
    ps = cache.page_size
    logical = cache.lengths // ps                      # [B]
    phys = jnp.take_along_axis(cache.page_table, logical[:, None],
                               axis=1)[:, 0]           # [B]
    slot = cache.lengths % ps
    return _scatter_kv(cache, k, v,
                       lambda arr, upd: arr.at[layer, phys, slot].set(
                           upd, mode="drop"),
                       # layer-sliced target [N, Hkv, ps]; advanced dims
                       # 0, 2 -> update [B, Hkv] as-is
                       lambda arr, upd: arr.at[layer, phys, :, slot].set(
                           upd, mode="drop"))


def write_decode_all_layers(cache: PagedKVCache, k_all: jax.Array,
                            v_all: jax.Array) -> PagedKVCache:
    """Write one decode step's k/v for EVERY layer in one scatter.

    k_all/v_all: [L, B, Hkv, D] (the decode scan's stacked per-layer
    outputs). Row b writes page ``page_table[b, lengths[b]//ps]`` slot
    ``lengths[b] % ps`` across all L layers — one [B]-indexed scatter
    with [L, Hkv, D] windows instead of L scatters with [Hkv, D]
    windows (models/llama.decode_step_paged pairs this with
    ops/paged_attention.paged_attention_append, which folds the current
    token into attention before it lands in the pool). Same garbage-page
    routing as :func:`write_decode`.
    """
    ps = cache.page_size
    logical = cache.lengths // ps                      # [B]
    phys = jnp.take_along_axis(cache.page_table, logical[:, None],
                               axis=1)[:, 0]           # [B]
    slot = cache.lengths % ps
    # Advanced indices (phys, slot) sit on adjacent dims, so the update
    # keeps array order: [L, B, Hkv, D]. The scales [L, B, Hkv] land as
    # page tiles with lane ``slot`` replaced (live rows' current pages
    # are disjoint; parked rows share garbage page 0).
    if cache.k.shape[3] == 1:
        # One head (a latent pool): XLA lays the array out with the SLOT
        # on the sublanes (int8 packs four slots a word). A slot-indexed
        # scatter over all layers then relayouts the whole pool to put
        # the layers there instead and copies it back (1.5 GB moved a
        # step at the benchmark's pool); so does a dynamic-update-slice
        # a row; and ONE gather of the rows' whole pages over all layers
        # is split in two lane halves by first slicing the WHOLE pool
        # into them (1.2 GB a step; the served trace, PERF.md section 6,
        # PR 30). A layer at a time, the rows' whole pages with one slot
        # replaced (the scales' rule) are small enough to gather as they
        # are and scatter back in place.
        at = (jnp.arange(ps)[None, :] == slot[:, None])[:, :, None, None]

        def put(arr, upd):
            for layer in range(arr.shape[0]):
                tiles = jnp.where(at, upd[layer, :, None], arr[layer, phys])
                arr = arr.at[layer, phys].set(tiles, mode="drop")
            return arr
    else:
        def put(arr, upd):
            return arr.at[:, phys, slot].set(upd, mode="drop")
    return _scatter_kv(cache, k_all, v_all, put,
                       lambda arr, upd: _scatter_scale_tiles(
                           arr, phys,
                           jnp.arange(arr.shape[3])[None, :] == slot[:, None],
                           upd[..., None]))


def write_decode_burst(cache: PagedKVCache, k_all: jax.Array,
                       v_all: jax.Array, inc: jax.Array) -> PagedKVCache:
    """Land one decode step for the whole stack and advance: scatter
    every layer's k/v at each row's current slot
    (:func:`write_decode_all_layers`) and bump ``lengths`` by ``inc``
    ([B] int32 — the active mask; parked rows hold position so their
    next write overwrites the same slot).

    This is the per-step mutation both the plain decode tick and the
    fused multi-step scan body (models/llama.decode_fused — K of these
    back to back inside one dispatch) run, kept as ONE function so the
    write/advance ordering cannot drift between the paths: the advance
    must follow the scatter, or a fused step would write its token one
    slot deep and the K-fused-ticks ≡ K-plain-ticks contract breaks.

    Rejected alternative, for the record: carrying the fused tick's K
    tokens in-register and landing them ONCE via
    :func:`write_decode_multi_all_layers` (the spec-verify multi-token
    append) would save K-1 pool scatters — but on int8 pools the later
    steps would then attend EARLIER same-tick tokens at full precision
    where sequential ticks read them back quantized, so fused output
    would drift from plain ticks on logit ties (the exact caveat
    verify_append documents for drafts). Bit-identity outranks the
    scatter savings; the dispatch overhead fusion targets is host-side
    anyway.
    """
    cache = write_decode_all_layers(cache, k_all, v_all)
    return cache._replace(lengths=cache.lengths + inc)


def _multi_write_indices(cache: PagedKVCache,
                         S: int) -> tuple[jax.Array, jax.Array]:
    """(phys, slot) [B,S] for S consecutive candidate positions per row.
    Positions past the table's width go to garbage page 0 — clamping
    them onto the last real page would wrap their slot index into
    TRUSTED kv (observed: a fully-allocated row near its budget had
    early slots of its last page overwritten by draft positions).
    Shared by every multi-position write so the containment logic has
    exactly one copy."""
    ps = cache.page_size
    pos = cache.lengths[:, None] + jnp.arange(S)[None, :]      # [B,S]
    logical = pos // ps
    safe = jnp.minimum(logical, cache.max_pages_per_row - 1)
    phys = jnp.take_along_axis(cache.page_table, safe, axis=1)     # [B,S]
    phys = jnp.where(logical < cache.max_pages_per_row, phys, 0)
    return phys, pos % ps


def write_decode_multi_all_layers(cache: PagedKVCache, k_all: jax.Array,
                                  v_all: jax.Array) -> PagedKVCache:
    """Write S candidate slots per row for EVERY layer in one scatter —
    :func:`write_decode_all_layers`'s speculative-verify generalisation.
    k_all/v_all: [L, B, S, Hkv, D]; row b's position j goes to page
    ``page_table[b, (lengths[b]+j) // ps]`` slot ``(lengths[b]+j) % ps``.
    Positions past the row's page allocation hit table entries that are 0
    by contract — the garbage page — so near-budget rows' untrusted draft
    writes are naturally contained (see _multi_write_indices)."""
    phys, slot = _multi_write_indices(cache, k_all.shape[2])
    return _scatter_kv(cache, k_all, v_all,
                       lambda arr, upd: arr.at[:, phys, slot].set(
                           upd, mode="drop"),
                       # update [B, S, L, Hkv] (advanced dims 1, 3 front)
                       lambda arr, upd: arr.at[:, phys, :, slot].set(
                           upd.transpose(1, 2, 0, 3), mode="drop"))


def copy_slot(cache: PagedKVCache, src_pos: jax.Array,
              dst_pos: jax.Array) -> PagedKVCache:
    """Move ONE kv slot per row (all layers) from absolute position
    ``src_pos[b]`` to ``dst_pos[b]`` — the tree-speculation sibling
    compaction (serve/scheduler.py tree spec tick): an accepted sibling
    leaf's kv, written at its node slot, is copied onto the accepted-
    path slot before lengths advance over it. Raw pool words move
    (int8 values + their head-major scales together), so the copy is
    exact — never a requantize. Rows with ``src_pos == dst_pos``
    self-copy harmlessly; positions past a row's table width route to
    garbage page 0 both ways (same containment as
    :func:`_multi_write_indices`).
    """
    ps = cache.page_size

    def indices(pos):                                  # [B] -> (phys, slot)
        logical = pos // ps
        safe = jnp.minimum(logical, cache.max_pages_per_row - 1)
        phys = jnp.take_along_axis(cache.page_table, safe[:, None],
                                   axis=1)[:, 0]
        phys = jnp.where(logical < cache.max_pages_per_row, phys, 0)
        return phys.astype(jnp.int32), (pos % ps).astype(jnp.int32)

    sp, so = indices(src_pos)
    dp, do = indices(dst_pos)
    out = cache._replace(
        k=cache.k.at[:, dp, do].set(cache.k[:, sp, so]),
        v=cache.v.at[:, dp, do].set(cache.v[:, sp, so]))
    if cache.quantized:
        # Head-major scales [L,N,Hkv,ps_pad]: the batch indices sit on
        # non-adjacent dims, so index every axis explicitly to keep the
        # gather/scatter in [L,B,Hkv] array order.
        L, _, Hkv, _ = cache.k_scale.shape
        li = jnp.arange(L)[:, None, None]
        hi = jnp.arange(Hkv)[None, None, :]
        src_ix = (li, sp[None, :, None], hi, so[None, :, None])
        dst_ix = (li, dp[None, :, None], hi, do[None, :, None])
        out = out._replace(
            k_scale=cache.k_scale.at[dst_ix].set(cache.k_scale[src_ix]),
            v_scale=cache.v_scale.at[dst_ix].set(cache.v_scale[src_ix]))
    return out


# -- page-set extract / inject (KV tiering, serve/kv_tier.py) -----------------

def gather_pages(cache: PagedKVCache, pages: jax.Array) -> tuple:
    """Pull a page set's content out of the pool in ONE gather per array
    — the device half of parking a session's KV to host RAM (the caller
    jits this, reads the result back with a single sync, and frees the
    physical pages).

    pages: [P] physical page ids (pad with 0 — the garbage page — to a
    power-of-two bucket so the compile cache stays small; padded lanes
    carry garbage the caller ignores). Returns (k [L,P,ps,Hkv,D],
    v [L,P,ps,Hkv,D], k_scale, v_scale) with the scale pair None for
    bf16 pools and the head-major [L,P,Hkv,ps_pad] storage layout for
    int8 — the raw pool bits, NOT a dequant: park/wake must round-trip
    the exact int8+scale words so a resumed session attends bit-identical
    KV to one that never left HBM.
    """
    k = cache.k[:, pages]
    v = cache.v[:, pages]
    if not cache.quantized:
        return k, v, None, None
    return k, v, cache.k_scale[:, pages], cache.v_scale[:, pages]


def scatter_pages(cache: PagedKVCache, pages: jax.Array, k: jax.Array,
                  v: jax.Array, k_scale: Optional[jax.Array] = None,
                  v_scale: Optional[jax.Array] = None) -> PagedKVCache:
    """Land a parked page set back into the pool in ONE scatter per
    array — the device half of waking a session from host RAM. Inverse
    of :func:`gather_pages`: the payload is raw pool words (int8 +
    head-major scales included), so wake is a copy, never a requantize.

    pages: [P] freshly-allocated physical ids, padded with 0 to the
    payload's bucket — duplicate 0 entries scatter garbage into the
    garbage page, which holds garbage by contract. The caller installs
    the waking row's table/lengths separately (atomically with its
    suffix prefill — the chunked-admission splice discipline); this
    touches pool content only.
    """
    cache = cache._replace(k=cache.k.at[:, pages].set(k),
                           v=cache.v.at[:, pages].set(v))
    if k_scale is not None:        # payload structure — static under jit
        cache = cache._replace(
            k_scale=cache.k_scale.at[:, pages].set(k_scale),
            v_scale=cache.v_scale.at[:, pages].set(v_scale))
    return cache


def set_row_table(cache: PagedKVCache, row: int | jax.Array,
                  pages: jax.Array) -> PagedKVCache:
    """Install a row's page map (host-allocated physical ids, padded with
    0s to max_pages_per_row) and reset its length to 0."""
    table = cache.page_table.at[row].set(pages.astype(jnp.int32))
    return cache._replace(page_table=table,
                          lengths=cache.lengths.at[row].set(0))


def gather_dense(cache: PagedKVCache, layer: int, max_seq: int,
                 ) -> tuple[jax.Array, jax.Array]:
    """Materialise one layer back to dense [B, max_seq, Hkv, D] (test
    oracle / debugging only — defeats the point in production). Returns
    the POOL dtype for bf16 pools and float32 (full-precision dequant)
    for quantized pools — callers mixing it with bf16 tensors must cast
    explicitly; the f32 return is deliberate so oracles compare at the
    dequant's native precision."""
    ps = cache.page_size
    pos = jnp.arange(max_seq)
    logical = pos // ps                                # [max_seq]
    B = cache.page_table.shape[0]
    phys = cache.page_table[:, logical]                # [B, max_seq]
    slot = jnp.broadcast_to(pos % ps, (B, max_seq))
    k = cache.k[layer][phys, slot]                     # [B, max_seq, Hkv, D]
    v = cache.v[layer][phys, slot]
    if cache.quantized:
        k = (k.astype(jnp.float32)
             * cache.k_scale_view[layer][phys, slot][..., None])
        v = (v.astype(jnp.float32)
             * cache.v_scale_view[layer][phys, slot][..., None])
    return k, v
