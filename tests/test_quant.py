"""Weight-only int8 + int4 quantization tests (models/quant.py).

Three oracles, applied to both precisions:
- the elementwise bound symmetric rounding guarantees (|w - deq| <= s/2
  per output channel for int8, per GROUP for int4);
- exact agreement between the fused quantized matmul path (mm/q_einsum,
  and the Pallas kernels in interpret mode) and a forward over
  explicitly dequantized weights — same math, so the tolerance is
  float-roundoff only;
- end-to-end sanity vs the unquantized model: logits stay highly
  correlated (int8 cosine > 0.99; int4 > 0.96 — group-wise 4-bit is
  honestly lossier) and greedy decode still matches through the serving
  engine.

The int4 legs additionally pin the split-half nibble packing
(pack4/unpack4 exact round-trip) and the kernel dispatch decisions of
the per-hidden-size autotune table (ops/quant_mm._TILE_TABLE).
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from p2p_llm_chat_tpu.models import llama, mixtral
from p2p_llm_chat_tpu.models.configs import get_config
from p2p_llm_chat_tpu.models.llama import KVCache
from p2p_llm_chat_tpu.models.quant import (QTensor, QTensor4, dequantize,
                                           dequantize4, mm, pack4,
                                           quantize, quantize4,
                                           quantize_params, unpack4)

from solo import Solo, jit_model

pytestmark = pytest.mark.model

CFG = get_config("tiny")
PARAMS = llama.init_params(CFG, jax.random.PRNGKey(0), dtype=jnp.float32)
# The model's entry points, each lowered whole (tests/solo.py): a
# quantized and a dequantized tree are two lowerings of the same function.
prefill = jit_model(llama.prefill, CFG)
decode_step = jit_model(llama.decode_step, CFG)


def dequantize_tree(params):
    def walk(d):
        return {k: (walk(v) if isinstance(v, dict) else
                    dequantize(v, jnp.float32) if isinstance(v, QTensor)
                    else dequantize4(v, jnp.float32)
                    if isinstance(v, QTensor4) else v)
                for k, v in d.items()}
    return walk(params)


def test_roundtrip_error_bound():
    rng = np.random.default_rng(0)
    w = jnp.asarray(rng.normal(size=(64, 48)) * 0.1, jnp.float32)
    qt = quantize(w)
    deq = dequantize(qt, jnp.float32)
    bound = np.asarray(qt.s)[0] / 2 + 1e-7          # per out channel
    assert np.all(np.abs(np.asarray(deq - w)) <= bound[None, :])
    # int8 payload really is int8, scales kept per-channel.
    assert qt.q.dtype == jnp.int8 and qt.s.shape == (1, 48)


def test_zero_channel_is_stable():
    w = jnp.zeros((8, 4), jnp.float32).at[:, 1].set(1.0)
    qt = quantize(w)
    deq = np.asarray(dequantize(qt, jnp.float32))
    np.testing.assert_array_equal(deq[:, 0], 0)     # no NaN / div-by-zero
    np.testing.assert_allclose(deq[:, 1], 1.0, atol=1e-6)


def test_mm_matches_explicit_dequant():
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.normal(size=(5, 64)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(64, 48)), jnp.float32)
    qt = quantize(w)
    got = np.asarray(mm(x, qt))
    ref = np.asarray(x @ dequantize(qt, jnp.float32))
    np.testing.assert_allclose(got, ref, rtol=1e-5, atol=1e-5)


def test_quantized_forward_matches_dequantized_oracle():
    """The fused int8 path through the whole model must equal a plain
    forward over the dequantized weights — quantization error itself
    cancels out of this comparison."""
    qparams = quantize_params(PARAMS)
    dparams = dequantize_tree(qparams)
    tokens = jnp.asarray(
        np.random.default_rng(2).integers(0, CFG.vocab_size, (2, 12)),
        jnp.int32)
    lens = jnp.asarray([12, 9], jnp.int32)
    cache_q = KVCache.create(CFG, 2, 32, jnp.float32)
    cache_d = KVCache.create(CFG, 2, 32, jnp.float32)
    lq, cache_q = prefill(qparams, tokens, lens, cache_q)
    ld, cache_d = prefill(dparams, tokens, lens, cache_d)
    np.testing.assert_allclose(np.asarray(lq), np.asarray(ld),
                               rtol=2e-4, atol=2e-4)
    nxt = jnp.argmax(lq[:, -1], -1).astype(jnp.int32)[:, None]
    for _ in range(3):
        lq, cache_q = decode_step(qparams, nxt, cache_q)
        ld, cache_d = decode_step(dparams, nxt, cache_d)
        np.testing.assert_allclose(np.asarray(lq), np.asarray(ld),
                                   rtol=2e-4, atol=2e-4)
        nxt = jnp.argmax(lq[:, 0], -1).astype(jnp.int32)[:, None]


def test_quantized_close_to_full_precision():
    """Sanity vs the ORIGINAL weights: per-channel int8 keeps the logits
    direction (cosine similarity), not bitwise equality."""
    qparams = quantize_params(PARAMS)
    tokens = jnp.asarray(
        np.random.default_rng(3).integers(0, CFG.vocab_size, (1, 10)),
        jnp.int32)
    lens = jnp.asarray([10], jnp.int32)
    lq, _ = prefill(qparams, tokens, lens,
                    KVCache.create(CFG, 1, 16, jnp.float32))
    lf, _ = prefill(PARAMS, tokens, lens,
                    KVCache.create(CFG, 1, 16, jnp.float32))
    a = np.asarray(lq).reshape(-1)
    b = np.asarray(lf).reshape(-1)
    cos = float(a @ b / (np.linalg.norm(a) * np.linalg.norm(b) + 1e-9))
    assert cos > 0.99, cos


def test_moe_quantized_matches_dequantized_oracle():
    mcfg = get_config("tiny-moe")
    mparams = mixtral.init_params(mcfg, jax.random.PRNGKey(1),
                                  dtype=jnp.float32)
    qparams = quantize_params(mparams)
    dparams = dequantize_tree(qparams)
    tokens = jnp.asarray(
        np.random.default_rng(4).integers(0, mcfg.vocab_size, (2, 8)),
        jnp.int32)
    lens = jnp.asarray([8, 6], jnp.int32)
    moe_prefill = jit_model(mixtral.prefill, mcfg)
    lq, _ = moe_prefill(qparams, tokens, lens,
                        KVCache.create(mcfg, 2, 16, jnp.float32))
    ld, _ = moe_prefill(dparams, tokens, lens,
                        KVCache.create(mcfg, 2, 16, jnp.float32))
    np.testing.assert_allclose(np.asarray(lq), np.asarray(ld),
                               rtol=2e-4, atol=2e-4)


def test_quantized_params_serve_through_engine():
    """QTensor leaves must ride the scheduler's jitted programs (scan,
    donation, scatter installs) end to end: greedy decode through the
    batching engine equals the solo quantized oracle."""
    from p2p_llm_chat_tpu.serve.backend import (GenerateOptions,
                                                GenerateRequest,
                                                RequestStats)
    from p2p_llm_chat_tpu.serve.engine import TPUEngine
    from p2p_llm_chat_tpu.tokenizer import ByteTokenizer

    tok = ByteTokenizer(vocab_size=CFG.vocab_size)
    qparams = quantize_params(PARAMS)
    oracle = Solo(llama, CFG, tok, max_seq=64)

    eng = TPUEngine(qparams, CFG, tok, num_slots=2, max_seq=64)
    try:
        req = GenerateRequest(prompt="quantized serving",
                              options=GenerateOptions(max_tokens=8))
        got = "".join(eng.generate_stream(req, RequestStats()))
        assert got == oracle(qparams, "quantized serving", 8)
    finally:
        eng.stop()


def test_quantize_after_shard_matches_unsharded():
    """quantize_params on tp-sharded weights: the q/s leaves derive their
    shardings from the weight's and the forward still matches the
    single-device quantized oracle."""
    from p2p_llm_chat_tpu.parallel.mesh import MeshConfig, make_mesh
    from p2p_llm_chat_tpu.parallel.sharding import shard_params

    mesh = make_mesh(MeshConfig(tp=4))
    tokens = jnp.asarray(
        np.random.default_rng(5).integers(0, CFG.vocab_size, (2, 8)),
        jnp.int32)
    lens = jnp.asarray([8, 8], jnp.int32)
    ref, _ = prefill(quantize_params(PARAMS), tokens, lens,
                     KVCache.create(CFG, 2, 16, jnp.float32))
    sharded = shard_params(PARAMS, llama.param_axes(CFG), mesh)
    qsharded = quantize_params(sharded)
    got, _ = jit_model(llama.prefill, CFG, mesh=mesh)(
        qsharded, tokens, lens, KVCache.create(CFG, 2, 16, jnp.float32))
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("rows", [3, 8, 32, 160])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_pallas_qmm_matches_xla_path(rows, dtype):
    """The Pallas w8a16 kernel (ops/quant_mm.py — the decode weight
    stream on TPU) must agree with the inline-dequant XLA path, including
    non-multiple-of-8 row counts (padded internally)."""
    from p2p_llm_chat_tpu.ops.quant_mm import quant_matmul

    rng = np.random.default_rng(11)
    H, O = 256, 384
    w = jnp.asarray(rng.normal(size=(H, O)), jnp.float32)
    qw = quantize(w)
    x = jnp.asarray(rng.normal(size=(rows, H)), dtype)
    want = (x @ qw.q.astype(dtype)) * jnp.squeeze(qw.s, -2).astype(dtype)
    got = quant_matmul(x, qw.q, qw.s, interpret=True)
    assert got.dtype == dtype and got.shape == (rows, O)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               atol=5e-2, rtol=5e-2)


def test_pallas_qmm_block_picker():
    from p2p_llm_chat_tpu.ops.quant_mm import pick_block

    assert pick_block(2048) == 1024
    assert pick_block(512) == 512
    assert pick_block(384) == 128
    assert pick_block(100) is None        # mm falls back to the XLA path


def test_init_params_quantized_streams_to_fused_int8():
    """Streaming random init (models/llama.init_params_quantized) yields
    an already-fused int8 tree: fuse_params is a no-op, decode runs, and
    the quantisation error bound holds per leaf (the path that lets the
    8B config fit one 16 GB chip)."""
    import jax
    import jax.numpy as jnp
    from p2p_llm_chat_tpu.models import llama
    from p2p_llm_chat_tpu.models.configs import get_config
    from p2p_llm_chat_tpu.models.quant import QTensor

    cfg = get_config("tiny")
    params = llama.init_params_quantized(cfg, jax.random.PRNGKey(0))
    layers = params["layers"]
    assert set(layers) >= {"wqkv", "wo", "wgu", "w_down"}
    for name in ("wqkv", "wo", "wgu", "w_down"):
        leaf = layers[name]
        assert isinstance(leaf, QTensor) and leaf.q.dtype == jnp.int8
        assert leaf.q.shape[0] == cfg.num_layers
    assert isinstance(params["lm_head"], QTensor)
    assert llama.fuse_params(params) is params or \
        "wqkv" in llama.fuse_params(params)["layers"]

    B, S = 2, 8
    cache = llama.KVCache.create(cfg, B, 32, dtype=params["embed"].dtype)
    toks = jnp.ones((B, S), jnp.int32)
    logits, cache = jit_model(llama.prefill, cfg)(
        params, toks, jnp.full((B,), S, jnp.int32), cache)
    assert logits.shape == (B, S, cfg.vocab_size)
    step, cache = jit_model(llama.decode_step, cfg)(params, toks[:, :1],
                                                    cache)
    assert step.shape == (B, 1, cfg.vocab_size)
    assert bool(jnp.isfinite(step).all())


# ---------------------------------------------------------------------------
# int4 (w4a16, group-wise) — ISSUE 16
# ---------------------------------------------------------------------------


def test_pack4_unpack4_roundtrip_exact():
    """Split-half nibble packing is lossless over the full int4 range,
    including the high-nibble>=8 bytes whose packed value exceeds 127
    (the explicit two's-complement wrap in pack4)."""
    rng = np.random.default_rng(6)
    v = jnp.asarray(rng.integers(-8, 8, size=(64, 48)), jnp.int32)
    p = pack4(v)
    assert p.dtype == jnp.int8 and p.shape == (32, 48)
    np.testing.assert_array_equal(np.asarray(unpack4(p)), np.asarray(v))
    # Byte row i must hold logical rows i (lo nibble) and i + K/2 (hi):
    # the layout contract the Pallas kernel's group-pair walk relies on.
    pb = np.asarray(p).astype(np.uint8)
    np.testing.assert_array_equal((pb & 0xF).astype(np.int32) - 8,
                                  np.asarray(v)[:32])
    np.testing.assert_array_equal((pb >> 4).astype(np.int32) - 8,
                                  np.asarray(v)[32:])


def test_int4_roundtrip_error_bound():
    rng = np.random.default_rng(7)
    w = jnp.asarray(rng.normal(size=(256, 48)) * 0.1, jnp.float32)
    qt = quantize4(w)                                 # group = 128
    assert qt.q.dtype == jnp.int8 and qt.q.shape == (128, 48)
    assert qt.s.shape == (2, 48) and qt.group == 128
    assert qt.shape == (256, 48) and qt.ndim == 2
    deq = np.asarray(dequantize4(qt, jnp.float32))
    bound = np.repeat(np.asarray(qt.s), 128, axis=0) / 2 + 1e-7
    assert np.all(np.abs(deq - np.asarray(w)) <= bound)


def test_int4_group64_and_zero_group_stable():
    """K=192 is not 128-divisible -> group falls back to 64; an all-zero
    group must dequantize to exact zeros (no NaN from a zero amax)."""
    w = jnp.zeros((192, 4), jnp.float32).at[64:128, 1].set(1.0)
    qt = quantize4(w)
    assert qt.group == 64 and qt.s.shape == (3, 4)
    deq = np.asarray(dequantize4(qt, jnp.float32))
    np.testing.assert_array_equal(deq[:64], 0)
    np.testing.assert_allclose(deq[64:128, 1], 1.0, atol=1e-6)
    np.testing.assert_array_equal(deq[128:], 0)


def test_mm4_matches_explicit_dequant():
    rng = np.random.default_rng(8)
    x = jnp.asarray(rng.normal(size=(5, 256)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(256, 48)), jnp.float32)
    qt = quantize4(w)
    got = np.asarray(mm(x, qt))
    ref = np.asarray(x @ dequantize4(qt, jnp.float32))
    np.testing.assert_allclose(got, ref, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("rows", [3, 8, 32])
def test_pallas_qmm4_matches_reference(rows):
    """The w4a16 kernel (interpret mode — hardware-free) vs the
    group-wise dequant reference: identical f32 math, so the tolerance
    is roundoff only (dot-order differences), not quantization error."""
    from p2p_llm_chat_tpu.ops.quant_mm import pick_int4_bo, quant_matmul4

    rng = np.random.default_rng(9)
    H, O = 256, 384                                   # ng=2, G=128
    w = jnp.asarray(rng.normal(size=(H, O)), jnp.float32)
    qt = quantize4(w)
    assert pick_int4_bo(rows, H, O, qt.s.shape[0], 4) is not None
    x = jnp.asarray(rng.normal(size=(rows, H)), jnp.float32)
    got = quant_matmul4(x, qt.q, qt.s, interpret=True)
    want = x @ dequantize4(qt, jnp.float32)
    assert got.dtype == x.dtype and got.shape == (rows, O)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


def test_pallas_qmm4_stacked_matches_reference():
    """The stacked twin reads [L, K/2, O] at a scalar-prefetched layer
    index — every layer must match the per-layer unstacked result."""
    from p2p_llm_chat_tpu.ops.quant_mm import quant_matmul_stacked4

    rng = np.random.default_rng(10)
    L, H, O = 3, 256, 384
    w = jnp.asarray(rng.normal(size=(L, H, O)), jnp.float32)
    qt = quantize4(w)
    x = jnp.asarray(rng.normal(size=(8, H)), jnp.float32)
    for layer in range(L):
        got = quant_matmul_stacked4(x, qt.q, qt.s, layer, interpret=True)
        want = x @ dequantize4(QTensor4(q=qt.q[layer], s=qt.s[layer]),
                               jnp.float32)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-4, atol=1e-4)


def test_qmm_tile_table_dispatch():
    """Pins the per-hidden-size autotune table decisions (ops/quant_mm
    ._TILE_TABLE): hidden=1024 caps the 1D grid's output tile at 256
    (the draft-400m retune — bo=1024 left a 2048-col projection only two
    grid programs and lost ~5% to XLA), hidden=2048 keeps the full 1024
    stripe; and the w4a16 gates (even group count, 128-aligned groups)
    route uncovered shapes to the XLA fallback."""
    from p2p_llm_chat_tpu.ops.quant_mm import _pick_1d_bo, pick_int4_bo

    # The retune this table exists for, shared by both precisions.
    assert _pick_1d_bo(8, 1024, 2048, 2) == 256
    assert _pick_1d_bo(8, 2048, 2048, 2) == 1024
    assert _pick_1d_bo(8, 1024, 2048, 2, stripe_rows=512) == 256  # int4

    # w4a16 coverage gates.
    assert pick_int4_bo(8, 1024, 2048, 8, 2) == 256   # G=128, ng even
    assert pick_int4_bo(8, 1024, 2048, 7, 2) is None  # odd group count
    assert pick_int4_bo(8, 192, 256, 3, 2) is None    # G=64 not lane-wide
    assert pick_int4_bo(8, 1024, 2048, 0, 2) is None  # unquantized guard


@pytest.mark.slow
@pytest.mark.parametrize("rows", [8, 32])
@pytest.mark.parametrize("shape", [(512, 512), (1024, 2048), (2048, 1024)])
def test_pallas_qmm4_shape_matrix(rows, shape):
    """Full-matrix interpret parity at bench-relevant hidden sizes —
    including hidden=1024, where the tile table caps bo (the retune must
    not change the numbers, only the grid)."""
    from p2p_llm_chat_tpu.ops.quant_mm import quant_matmul4

    H, O = shape
    rng = np.random.default_rng(H + O + rows)
    w = jnp.asarray(rng.normal(size=(H, O)), jnp.float32)
    qt = quantize4(w)
    x = jnp.asarray(rng.normal(size=(rows, H)), jnp.float32)
    got = quant_matmul4(x, qt.q, qt.s, interpret=True)
    want = x @ dequantize4(qt, jnp.float32)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


def test_int4_forward_matches_dequantized_oracle():
    """The fused int4 path through the whole model equals a plain
    forward over the group-dequantized weights — quantization error
    cancels out of this comparison, exactly like the int8 oracle."""
    qparams = quantize_params(PARAMS, mode="int4")
    dparams = dequantize_tree(qparams)
    tokens = jnp.asarray(
        np.random.default_rng(12).integers(0, CFG.vocab_size, (2, 12)),
        jnp.int32)
    lens = jnp.asarray([12, 9], jnp.int32)
    cache_q = KVCache.create(CFG, 2, 32, jnp.float32)
    cache_d = KVCache.create(CFG, 2, 32, jnp.float32)
    lq, cache_q = prefill(qparams, tokens, lens, cache_q)
    ld, cache_d = prefill(dparams, tokens, lens, cache_d)
    np.testing.assert_allclose(np.asarray(lq), np.asarray(ld),
                               rtol=2e-4, atol=2e-4)
    nxt = jnp.argmax(lq[:, -1], -1).astype(jnp.int32)[:, None]
    for _ in range(3):
        lq, cache_q = decode_step(qparams, nxt, cache_q)
        ld, cache_d = decode_step(dparams, nxt, cache_d)
        np.testing.assert_allclose(np.asarray(lq), np.asarray(ld),
                                   rtol=2e-4, atol=2e-4)
        nxt = jnp.argmax(lq[:, 0], -1).astype(jnp.int32)[:, None]


def test_int4_close_to_full_precision():
    """Sanity vs the ORIGINAL weights. Group-wise int4 is honestly
    lossier than per-channel int8, so the pinned cosine floor is 0.96
    (int8 pins 0.99; measured 0.967 on tiny, whose K=128 trunk gives
    only ONE group per column — the worst case) — documented in
    docs/serving.md Round-16."""
    qparams = quantize_params(PARAMS, mode="int4")
    tokens = jnp.asarray(
        np.random.default_rng(13).integers(0, CFG.vocab_size, (1, 10)),
        jnp.int32)
    lens = jnp.asarray([10], jnp.int32)
    lq, _ = prefill(qparams, tokens, lens,
                    KVCache.create(CFG, 1, 16, jnp.float32))
    lf, _ = prefill(PARAMS, tokens, lens,
                    KVCache.create(CFG, 1, 16, jnp.float32))
    a = np.asarray(lq).reshape(-1)
    b = np.asarray(lf).reshape(-1)
    cos = float(a @ b / (np.linalg.norm(a) * np.linalg.norm(b) + 1e-9))
    assert cos > 0.96, cos


def test_moe_int4_matches_dequantized_oracle():
    """Mixtral expert stacks quantize group-wise along axis -2 and run
    through the q_einsum dequant path."""
    mcfg = get_config("tiny-moe")
    mparams = mixtral.init_params(mcfg, jax.random.PRNGKey(1),
                                  dtype=jnp.float32)
    qparams = quantize_params(mparams, mode="int4")
    dparams = dequantize_tree(qparams)
    tokens = jnp.asarray(
        np.random.default_rng(14).integers(0, mcfg.vocab_size, (2, 8)),
        jnp.int32)
    lens = jnp.asarray([8, 6], jnp.int32)
    moe_prefill = jit_model(mixtral.prefill, mcfg)
    lq, _ = moe_prefill(qparams, tokens, lens,
                        KVCache.create(mcfg, 2, 16, jnp.float32))
    ld, _ = moe_prefill(dparams, tokens, lens,
                        KVCache.create(mcfg, 2, 16, jnp.float32))
    np.testing.assert_allclose(np.asarray(lq), np.asarray(ld),
                               rtol=2e-4, atol=2e-4)


def test_int4_params_serve_through_engine():
    """QTensor4 leaves must ride the scheduler's jitted programs (scan,
    donation, scatter installs) end to end: greedy decode through the
    batching engine equals the solo int4 oracle."""
    from p2p_llm_chat_tpu.serve.backend import (GenerateOptions,
                                                GenerateRequest,
                                                RequestStats)
    from p2p_llm_chat_tpu.serve.engine import TPUEngine
    from p2p_llm_chat_tpu.tokenizer import ByteTokenizer

    tok = ByteTokenizer(vocab_size=CFG.vocab_size)
    qparams = quantize_params(PARAMS, mode="int4")
    oracle = Solo(llama, CFG, tok, max_seq=64)

    eng = TPUEngine(qparams, CFG, tok, num_slots=2, max_seq=64)
    try:
        req = GenerateRequest(prompt="int4 serving",
                              options=GenerateOptions(max_tokens=8))
        got = "".join(eng.generate_stream(req, RequestStats()))
        assert got == oracle(qparams, "int4 serving", 8)
    finally:
        eng.stop()


def test_init_params_quantized_streams_to_fused_int4():
    """quant='int4' streams straight to a fused QTensor4 tree (packed
    byte rows = half the logical contraction dim) — the path that halves
    the 8B weight trunk again without ever materialising bf16."""
    cfg = get_config("tiny")
    params = llama.init_params_quantized(cfg, jax.random.PRNGKey(0),
                                         quant="int4")
    layers = params["layers"]
    for name in ("wqkv", "wo", "wgu", "w_down"):
        leaf = layers[name]
        assert isinstance(leaf, QTensor4) and leaf.q.dtype == jnp.int8
        assert leaf.q.shape[0] == cfg.num_layers
        assert leaf.q.shape[-2] * 2 == leaf.shape[-2]   # packed rows
    assert isinstance(params["lm_head"], QTensor4)

    B, S = 2, 8
    cache = llama.KVCache.create(cfg, B, 32, dtype=params["embed"].dtype)
    toks = jnp.ones((B, S), jnp.int32)
    logits, cache = jit_model(llama.prefill, cfg)(
        params, toks, jnp.full((B,), S, jnp.int32), cache)
    assert logits.shape == (B, S, cfg.vocab_size)
    step, cache = jit_model(llama.decode_step, cfg)(params, toks[:, :1],
                                                    cache)
    assert bool(jnp.isfinite(step).all())


def test_quant_mode_and_param_bytes():
    """quant_mode labels a tree by its leaves; param_bytes counts STORED
    bytes (int4 packs two weights per byte) — the scheduler's
    model_weight_bytes gauge reads both."""
    from p2p_llm_chat_tpu.models.quant import param_bytes, quant_mode

    assert quant_mode(PARAMS) == ""
    q8 = quantize_params(PARAMS)
    q4 = quantize_params(PARAMS, mode="int4")
    assert quant_mode(q8) == "int8"
    assert quant_mode(q4) == "int4"
    # int4 stores half the int8 payload (+ group scales vs channel
    # scales); with tiny's K=128..256 groups the total must land well
    # under int8's and both under bf16-equivalent f32.
    assert param_bytes(q4) < param_bytes(q8) < param_bytes(PARAMS)
