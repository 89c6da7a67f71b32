"""The int8 pool's hot writes update the scale arrays in place, off the
chip: ``write_decode_burst`` and the unaligned ``write_prefill_chunk``,
donated, compiled for a described v5e chip at the pool shape of
``mixtral-8x7b-v0.1-l6`` (the TPU's compiler is installed here; nothing
runs). A scatter that indexes the scales' lane (slot) dimension makes
the compiler copy the whole ``f32[L, pages, Hkv, 128]`` array into a
layout with the indexed dimensions major and back, four copies and half
a gigabyte of temporaries a call (PERF.md §6, PR 29); the rule is
"index pages, never lanes" (ops/paged_kv.py), and this file holds the
two writes every cell runs to it.

Everything that touches the topology sits inside fixtures, as the
on-chip-measurement guide says: only the worker that runs this file
loads the TPU's library.
"""

import re

import pytest

# The pool of benchmark/configs/mixtral-8x7b-v0.1-l6.json: 6 layers,
# 1,024 pages and the garbage page, 64 slots a page, 8 kv heads x 128,
# 32 rows of at most 2,048 tokens.
L, PAGES, PS, HKV, D = 6, 1025, 64, 8, 128
ROWS, PER_ROW = 32, 32
SCALE_SHAPE = rf"f32\[{L},{PAGES},{HKV},128\]"
TEMP_LIMIT = 8 * 1024 * 1024


@pytest.fixture(scope="module")
def topo():
    import os
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no compiler here: skip
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def described(topo):
    """shape, dtype -> a ShapeDtypeStruct on the described chip."""
    import jax
    from jax.sharding import SingleDeviceSharding
    one_chip = SingleDeviceSharding(topo.devices[0])
    return lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                     sharding=one_chip)


@pytest.fixture()
def pool(described):
    import jax.numpy as jnp
    from p2p_llm_chat_tpu.ops.paged_kv import PagedKVCache
    return PagedKVCache(
        k=described((L, PAGES, PS, HKV, D), jnp.int8),
        v=described((L, PAGES, PS, HKV, D), jnp.int8),
        page_table=described((ROWS, PER_ROW), jnp.int32),
        lengths=described((ROWS,), jnp.int32),
        k_scale=described((L, PAGES, HKV, 128), jnp.float32),
        v_scale=described((L, PAGES, HKV, 128), jnp.float32))


@pytest.fixture()
def no_cache():
    """The persistent cache cannot hold what it cannot read back."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", True)
    compilation_cache.reset_cache()


def _assert_in_place(compiled):
    copies = [line.strip()[:200] for line in compiled.as_text().splitlines()
              if re.search(rf"= {SCALE_SHAPE}\S* copy(-start)?\(", line)]
    assert not copies, "the scale array is copied whole:\n" + "\n".join(copies)
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < TEMP_LIMIT, f"{temp} bytes of temporaries"


def test_decode_write_updates_the_scales_in_place(pool, described, no_cache):
    import jax
    import jax.numpy as jnp
    from p2p_llm_chat_tpu.ops.paged_kv import write_decode_burst
    kv = described((L, ROWS, HKV, D), jnp.bfloat16)
    inc = described((ROWS,), jnp.int32)
    _assert_in_place(jax.jit(write_decode_burst, donate_argnums=(0,)).lower(
        pool, kv, kv, inc).compile())


@pytest.mark.parametrize("start", [88, 88 + 256], ids=["mid", "final"])
def test_unaligned_chunk_splice_updates_the_scales_in_place(
        start, pool, described, no_cache):
    """A 1 x 256 chunk of the ladder behind the registered 88-token
    template head: every ``prefill_chunk_mid`` / ``_final`` splice."""
    import jax
    import jax.numpy as jnp
    from p2p_llm_chat_tpu.ops.paged_kv import write_prefill_chunk
    kv = described((L, 1, 256, HKV, D), jnp.bfloat16)
    tables = described((1, PER_ROW), jnp.int32)

    def splice(cache, k, v, tables):
        return write_prefill_chunk(cache, k, v, tables, start)

    _assert_in_place(jax.jit(splice, donate_argnums=(0,)).lower(
        pool, kv, kv, tables).compile())


@pytest.mark.parametrize("heads", [4, 8])
def test_a_first_chunks_page_tiles_leave_the_values_where_they_lie(
        heads, described, no_cache):
    """The page-ALIGNED splice (a ladder's first chunk, with the 88-token
    head in front; every single-shot admission) at the pool shape of
    ``mellum2-12b-a2.5b-instruct-l16``, four KV heads x 128 in int8: around
    a page-window scatter the compiler takes such a pool through a
    token-minor layout and back, two copies of each of K and V and a
    gigabyte of temporaries a write at the cell's 8,193 pages (PERF.md
    §6, PR 40), so ``_tile_scatter`` indexes it a token at a time. Eight
    heads, the other cells' shape, are written where they lie by the
    window scatter."""
    import jax
    import jax.numpy as jnp
    from p2p_llm_chat_tpu.ops.paged_kv import (PagedKVCache,
                                               write_prefill_chunk)
    layers = 4
    pool = PagedKVCache(
        k=described((layers, PAGES, PS, heads, D), jnp.int8),
        v=described((layers, PAGES, PS, heads, D), jnp.int8),
        page_table=described((ROWS, PER_ROW), jnp.int32),
        lengths=described((ROWS,), jnp.int32),
        k_scale=described((layers, PAGES, heads, 128), jnp.float32),
        v_scale=described((layers, PAGES, heads, 128), jnp.float32))
    kv = described((layers, 1, 88 + 1024, heads, D), jnp.bfloat16)
    tables = described((1, PER_ROW), jnp.int32)

    def splice(cache, k, v, tables):
        return write_prefill_chunk(cache, k, v, tables, 0)

    compiled = jax.jit(splice, donate_argnums=(0,)).lower(
        pool, kv, kv, tables).compile()
    values = rf"s8\[{layers},{PAGES},{PS},{heads},{D}\]"
    copies = [line.strip()[:200] for line in compiled.as_text().splitlines()
              if re.search(rf"= {values}\S* copy\(", line)]
    assert not copies, "the pool is copied whole:\n" + "\n".join(copies)
    assert compiled.memory_analysis().temp_size_in_bytes < TEMP_LIMIT


# The expert matmuls the benchmark's cells dispatch (H, O, experts; the
# sweep tool's list), at the decode bucket and at the widest prefill tile: the stripe
# `pick_expert_bo` gives must be one Mosaic accepts at its default
# scoped limit, compiled ALONE with x read from HBM (inside a model's
# program XLA may hand the kernel an x that already lies in VMEM, which
# takes no pipeline buffers: that is how 128 rows x 14336 -> 4096 at 256
# columns ran in the Mixtral cell while this compile refused it with
# "scoped allocation 17.53M, limit 16.00M"; ROADMAP S5, PERF.md §6 PR 47).
def _cell_expert_shapes():
    from tools.check_quant_kernel import CELL_SHAPES
    return [pytest.param(H, O, NE, id=label.replace(" ", "-"))
            for label, H, O, NE, _ in CELL_SHAPES]


@pytest.mark.parametrize("rows", [32, 128])
@pytest.mark.parametrize("H,O,NE", _cell_expert_shapes())
def test_the_expert_stripe_the_rule_picks_compiles_for_the_chip(
        rows, H, O, NE, described, no_cache):
    import jax
    import jax.numpy as jnp
    from p2p_llm_chat_tpu.ops import quant_mm as qmm
    assert qmm.pick_expert_bo(rows, H, O, 2) is not None
    # The suite's "highest" is a float32 request the MXU's bf16 dot does
    # not take; the server runs at the default.
    with jax.default_matmul_precision("default"):
        jax.jit(qmm.quant_matmul_experts_stacked).lower(
            described((NE, rows, H), jnp.bfloat16),
            described((2, NE, H, O), jnp.int8),
            described((2, NE, 1, O), jnp.float32),
            described((), jnp.int32), described((NE,), jnp.int32)).compile()


def test_mosaic_refuses_what_the_expert_account_refuses(described, no_cache):
    """The other side, at the shape the account was calibrated on: the
    dense search's 256 columns at 128 rows x 14336 -> 4096 is refused by
    the compiler with the figure the account reads (17.53M <= account)."""
    import functools
    import jax
    import jax.numpy as jnp
    from p2p_llm_chat_tpu.ops import quant_mm as qmm
    assert not qmm.expert_bo_fits(128, 14336, 4096, 256, 2)
    with jax.default_matmul_precision("default"), \
            pytest.raises(Exception, match=r"17\.53M and limit 16\.00M"):
        jax.jit(functools.partial(qmm.quant_matmul_experts_stacked,
                                  bo=256)).lower(
            described((8, 128, 14336), jnp.bfloat16),
            described((2, 8, 14336, 4096), jnp.int8),
            described((2, 8, 1, 4096), jnp.float32),
            described((), jnp.int32), described((8,), jnp.int32)).compile()


# The state pool of benchmark/configs/nemotron-3-super-120b-a12b-l22e128:
# ten Mamba-2 layers, 32 slots and the garbage row, 128 heads x 64 x 128
# float32 in 8 groups; a decode step moves 32 rows. And that of
# benchmark/configs/granite-4.0-h-micro.json: 36 layers, 64 slots and the
# garbage row, 64 heads x 64 x 128 in ONE group; a step moves 64 rows.
SSM_POOLS = {"nemotron": ((10, 33, 128, 64, 128), 8, 32),
             "granite": ((36, 65, 64, 64, 128), 1, 64)}
CONV_K = 4


@pytest.mark.parametrize("which", sorted(SSM_POOLS))
def test_the_decode_kernel_updates_the_state_pool_where_it_lies(
        described, no_cache, monkeypatch, which):
    """``decode_update`` on the chip's path (the platform probe steered
    here: nothing runs), two layers' calls in one donated program, as a
    decode program's walk makes them: Mosaic takes the head block
    ``pick_head_block`` gives at its default scoped limit, the kernel is
    in the program, and no instruction the shape of the pool is a copy
    (PERF.md §6, PR 48)."""
    import jax
    import jax.numpy as jnp
    from p2p_llm_chat_tpu.ops import state_pool
    from p2p_llm_chat_tpu.ops.state_pool import StatePool
    SSM_SHAPE, G, B = SSM_POOLS[which]
    _, _, H, P, N = SSM_SHAPE
    hb = state_pool.pick_head_block(H, P, N, G)
    assert hb and H % hb == 0
    assert state_pool.ssm_kernel_vmem_bytes(hb, P, N) < 16 * 1024 * 1024
    monkeypatch.setattr(state_pool, "on_tpu", lambda: True)
    C = H * P + 2 * G * N

    def step(pool, live, xbc, dt_raw, w, b):
        def split(out):
            x = jax.nn.silu(out)
            return (x[:, :H * P].reshape(B, H, P),
                    jax.nn.softplus(dt_raw), -jnp.ones((H,), jnp.float32),
                    x[:, H * P: H * P + G * N].reshape(B, G, N),
                    x[:, H * P + G * N:].reshape(B, G, N))
        ys = []
        for layer in (3, 7):
            y, _, pool = state_pool.decode_update(
                pool, jnp.asarray(layer, jnp.int32), live, xbc, w, b, split)
            ys.append(y)
        return ys, pool

    pool = StatePool(
        ssm=described(SSM_SHAPE, jnp.float32),
        conv=described(SSM_SHAPE[:2] + (CONV_K - 1, C), jnp.bfloat16))
    compiled = jax.jit(step, donate_argnums=(0,)).lower(
        pool, described((B,), jnp.bool_), described((B, C), jnp.bfloat16),
        described((B, H), jnp.float32), described((CONV_K, C), jnp.bfloat16),
        described((C,), jnp.bfloat16)).compile()
    text = compiled.as_text()
    assert text.count("ssm_decode_step") >= 2, "the kernel is not there"
    shape = r"f32\[" + ",".join(map(str, SSM_SHAPE)) + r"\]"
    copies = [line.strip()[:200] for line in text.splitlines()
              if re.search(rf"= {shape}\S* copy(-start)?\(", line)]
    assert not copies, "the state pool is copied whole:\n" + "\n".join(copies)
    assert "input_output_alias" in text
    assert compiled.memory_analysis().temp_size_in_bytes < TEMP_LIMIT


# The pools of benchmark/configs/keye-vl-2.0-30b-a3b-l12.json: 12 layers,
# 3,584 pages and the garbage page, 4 kv heads x 128 in int8, an index key
# a token in a row of 128 bfloat16 lanes; 32 rows of at most 16,384.
KEYE = dict(L=12, pages=3585, hkv=4, heads=32, rows=32, per_row=256)


def _keye_pool(described):
    import jax.numpy as jnp
    from p2p_llm_chat_tpu.ops.paged_kv import PagedKVCache
    L, N, H = KEYE["L"], KEYE["pages"], KEYE["hkv"]
    return PagedKVCache(
        k=described((L, N, PS, H, D), jnp.int8),
        v=described((L, N, PS, H, D), jnp.int8),
        page_table=described((KEYE["rows"], KEYE["per_row"]), jnp.int32),
        lengths=described((KEYE["rows"],), jnp.int32),
        k_scale=described((L, N, H, 128), jnp.float32),
        v_scale=described((L, N, H, 128), jnp.float32),
        idx=described((L, N, PS, 1, 128), jnp.bfloat16))


@pytest.mark.parametrize("masked", [False, True])
def test_the_masked_flash_append_compiles_for_the_chip(masked, described,
                                                       no_cache):
    """The kernel's ``masked`` variant (an indexed layer's selection: a
    mask tile a page on the scales' DMA slots, a fourth prefetched
    scalar) at Keye's geometry and its longest window, beside the variant
    every other cell runs."""
    import jax
    import jax.numpy as jnp
    from p2p_llm_chat_tpu.ops import paged_attention as pa
    pool, B, bf = _keye_pool(described), KEYE["rows"], jnp.bfloat16
    pages = KEYE["per_row"]
    kw = {}
    if masked:
        kw = dict(keep=described((B, pages * PS), jnp.bool_),
                  keep_cur=described((B,), jnp.bool_))
    # The suite's "highest" is a float32 request the MXU's bf16 dot does
    # not take; the server runs at the default.
    with jax.default_matmul_precision("default"):
        text = jax.jit(lambda *a, **k: pa._paged_attention_flash_append(
            *a, pages=pages, quantized=True, **k)).lower(
            described((B, KEYE["heads"], D), bf),
            described((B, KEYE["hkv"], D), bf),
            described((B, KEYE["hkv"], D), bf), pool.k, pool.v,
            pool.k_scale, pool.v_scale, pool.page_table, pool.lengths,
            described((), jnp.int32), **kw).compile().as_text()
    assert text.count("tpu_custom_call") == 1
    assert ("paged_attention_flash_append_masked" in text) == masked


# The per-head pools the flash-append kernel walks at short contexts:
def _flash_append_compiled(described, heads, hkv, rows, window, quantized):
    """(the kernel's chunk in pages, the compiled text) of the
    flash-append kernel alone at one pool geometry and window."""
    import jax
    import jax.numpy as jnp
    from p2p_llm_chat_tpu.ops import paged_attention as pa
    L, bf = 2, jnp.bfloat16
    pages, N = window // PS, rows * 8 + 1
    kv = described((L, N, PS, hkv, D), jnp.int8 if quantized else bf)
    scale = described((L, N, hkv, 128), jnp.float32) if quantized else None
    with jax.default_matmul_precision("default"):
        text = jax.jit(lambda *a: pa._paged_attention_flash_append(
            *a, pages=pages, quantized=quantized)).lower(
            described((rows, heads, D), bf), described((rows, hkv, D), bf),
            described((rows, hkv, D), bf), kv, kv, scale, scale,
            described((rows, pages), jnp.int32),
            described((rows,), jnp.int32),
            described((), jnp.int32)).compile().as_text()
    assert text.count("tpu_custom_call") == 1
    assert "paged_attention_flash_append" in text
    return pa.flash_append_chunk_pages(hkv * D, kv.dtype.itemsize, PS, pages)


# 16 MHA heads (OLMoE, Ouro: 256-token tiles of a 512-token chunk) and
# llama's 8 GQA heads (512 of 1,024), 32 rows, int8 and bf16.
@pytest.mark.parametrize("heads, hkv, window, quantized", [
    (16, 16, 512, True), (16, 16, 1024, True), (32, 8, 1024, True),
    (16, 16, 512, False), (32, 8, 2048, False)])
def test_the_tiled_flash_append_compiles_for_the_chip(
        heads, hkv, window, quantized, described, no_cache):
    from p2p_llm_chat_tpu.ops import paged_attention as pa
    chunk = _flash_append_compiled(described, heads, hkv, ROWS, window,
                                   quantized)
    assert pa.flash_append_tile_pages(hkv * D, 1 if quantized else 2, PS,
                                      chunk) * 2 == chunk


# What PR 56 put on the served path: a window of 256 or 512 tokens is ONE
# chunk a row, shorter than the chunk budget. Mistral's and Mixtral's 8
# GQA heads, the 4 rows x 128 of the paired pools and of the 4-KV-head
# models (at Granite's 64 slots too), 16 MHA heads, int8 and bf16.
@pytest.mark.parametrize("heads, hkv, rows, window, quantized", [
    (32, 8, 32, 256, True), (32, 8, 32, 512, True), (32, 4, 32, 256, True),
    (32, 4, 64, 256, True), (16, 16, 32, 256, True), (32, 8, 32, 256, False),
    (32, 4, 32, 512, False)])
def test_the_flash_append_compiles_for_the_chip_at_a_short_window(
        heads, hkv, rows, window, quantized, described, no_cache):
    from p2p_llm_chat_tpu.ops import paged_attention as pa
    assert pa._flash_append_policy(window)
    assert _flash_append_compiled(described, heads, hkv, rows, window,
                                  quantized) == window // PS


def test_the_selection_kernels_compile_for_the_chip(described, no_cache):
    """The threshold kernel at a decode step's and a chunk's rows, and
    the index-scores kernel at a chunk against the longest carry, for a
    described v5e."""
    import jax
    import jax.numpy as jnp
    from p2p_llm_chat_tpu.ops import paged_attention as pa
    bf = jnp.bfloat16
    with jax.default_matmul_precision("default"):
        for rows in (32, 1024):
            pa._kth_largest_kernel.lower(
                described((rows, 16384), jnp.float32), k=2048).compile()
        text = pa._index_scores_kernel.lower(
            described((1, 1024, 16, 128), bf), described((1, 1024, 16), bf),
            described((1, 16384, 128), bf)).compile().as_text()
    assert text.count("tpu_custom_call") == 1 and "index_scores" in text
    # A chunk's attention under its selection, at the longest carry and
    # at two rows; a decode step's scores where the keys lie.
    with jax.default_matmul_precision("default"):
        for B, T, off in ((1, 16384, 15360), (2, 4096, 2048)):
            pa.select_attention_chunk.lower(
                described((B, 1024, 32, D), bf), described((B, T, 4, D), bf),
                described((B, T, 4, D), bf),
                described((B, 1024, T), jnp.bool_), offset=off).compile()
        pool = _keye_pool(described)
        for pages in (256, 64):
            pa._index_scores_decode_kernel.lower(
                described((32, 16, 128), bf), described((32, 16), bf),
                pool.idx, pool.page_table, pool.lengths,
                described((), jnp.int32), pages=pages).compile()


def test_the_index_keys_decode_write_updates_them_in_place(described,
                                                           no_cache):
    """``write_decode_burst`` with a step's index keys, donated: no
    instruction of the index keys' shape is a copy (a 64-lane row, or a
    slot-indexed scatter over all layers, made the compiled step copy the
    0.4 GB array whole; ops/paged_kv.py's docstring)."""
    import jax
    import jax.numpy as jnp
    from p2p_llm_chat_tpu.ops.paged_kv import write_decode_burst
    pool, B, L, H = (_keye_pool(described), KEYE["rows"], KEYE["L"],
                     KEYE["hkv"])
    compiled = jax.jit(write_decode_burst, donate_argnums=(0,)).lower(
        pool, described((L, B, H, D), jnp.bfloat16),
        described((L, B, H, D), jnp.bfloat16), described((B,), jnp.int32),
        described((L, B, 1, 128), jnp.bfloat16)).compile()
    shape = rf"bf16\[{L},{KEYE['pages']},{PS},(1,)?128\]"
    copies = [line for line in compiled.as_text().splitlines()
              if re.search(rf"= {shape}[^ ]* copy\(", line)]
    assert not copies, copies[:3]
    assert compiled.memory_analysis().temp_size_in_bytes < 64 * 1024 * 1024


# The pool of benchmark/configs/ouro-2.6b.json: 192 cache layers (48
# layers x 4 passes), 193 pages of 64, 16 kv heads x 128, 32 rows.
OURO = dict(L=192, pages=193, hkv=16, rows=32)


def test_a_looped_stacks_decode_write_lands_all_its_passes_in_place(
        described, no_cache):
    """``write_decode_burst`` with a looped stack's step, donated: K and V
    of every (pass, layer) pair stacked [192, 32, 16, 128], as
    models/llama._walk hands them out (an outer scan's ys reshaped: no
    data moves). No instruction of the pool's or the scales' shape is a
    copy (either would be gigabytes a step). The temporaries are the
    rows' scale pages, read, updated and written back: [192, 32, 16, 128]
    float32 is 50 MB, a few of them for K and for V (0.41 GB in all,
    against Mixtral's 6 layers x 8 heads under 8 MB: they grow with the
    cache layers x heads x rows, never with the pool)."""
    import jax
    import jax.numpy as jnp
    from p2p_llm_chat_tpu.ops.paged_kv import PagedKVCache, write_decode_burst
    L, N, H, B = OURO["L"], OURO["pages"], OURO["hkv"], OURO["rows"]
    pool = PagedKVCache(
        k=described((L, N, PS, H, D), jnp.int8),
        v=described((L, N, PS, H, D), jnp.int8),
        page_table=described((B, 32), jnp.int32),
        lengths=described((B,), jnp.int32),
        k_scale=described((L, N, H, 128), jnp.float32),
        v_scale=described((L, N, H, 128), jnp.float32))

    def burst(cache, k, v, inc):
        # [passes, layers, ...] -> [cache_layers, ...], as the walk does.
        flat = lambda a: a.reshape((L,) + a.shape[2:])
        return write_decode_burst(cache, flat(k), flat(v), inc)

    kv = described((4, L // 4, B, H, D), jnp.bfloat16)
    compiled = jax.jit(burst, donate_argnums=(0,)).lower(
        pool, kv, kv, described((B,), jnp.int32)).compile()
    shapes = (rf"f32\[{L},{N},{H},128\]", rf"s8\[{L},{N},{PS},{H},{D}\]")
    copies = [line.strip()[:200] for line in compiled.as_text().splitlines()
              if any(re.search(rf"= {s}\S* copy(-start)?\(", line)
                     for s in shapes)]
    assert not copies, "the pool is copied whole:\n" + "\n".join(copies)
    assert compiled.memory_analysis().temp_size_in_bytes < 512 * 1024 * 1024
