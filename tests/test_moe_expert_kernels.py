"""MoE expert-kernel and fusion oracles (round 18).

Three layers of the large-MoE trunk, each pinned against the simplest
correct implementation:

- the grouped expert-stripe Pallas kernels (interpret mode) against the
  dequantize-then-einsum oracle, int8 and int4 — including the odd
  group-count half-group walk the round introduced;
- wgu_e fusion on/off through models/mixtral.moe_mlp — fusing gate|up
  into one batched einsum must not change a single bit (the per-column
  dots are identical; only the dispatch count changes);
- the paged decode walk against the dense cache on QUANTIZED MoE
  params — the existing float oracle (tests/test_paged_decode.py)
  composed with the quantized expert trunk the bench actually serves.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from p2p_llm_chat_tpu.models import mixtral
from p2p_llm_chat_tpu.models.configs import get_config
from p2p_llm_chat_tpu.models.quant import (QTensor, dequantize4, quantize4)
from p2p_llm_chat_tpu.ops import quant_mm as qmm

pytestmark = pytest.mark.model


# -- expert-stripe kernels vs dequant einsum ----------------------------------

def _int8_pool(rng, L, NE, H, F):
    q = rng.integers(-127, 128, size=(L, NE, H, F), dtype=np.int8)
    s = (rng.random((L, NE, 1, F), np.float32) * 0.02 + 0.005)
    return jnp.asarray(q), jnp.asarray(s)


@pytest.mark.parametrize("L,NE,C,H,F", [
    (2, 2, 5, 256, 256),        # C=5 exercises the row pad
    # OLMoE-1B-7B's thin experts at NE 64: fused gate|up [2048 -> 2048]
    # and w_down [1024 -> 2048], at a part-full and a full decode bucket.
    (1, 64, 8, 2048, 2048),
    (1, 64, 32, 2048, 2048),
    (1, 64, 8, 1024, 2048),
    (1, 64, 32, 1024, 2048),
])
def test_expert_stacked_int8_matches_dequant_einsum(L, NE, C, H, F):
    rng = np.random.default_rng(0)
    q, s = _int8_pool(rng, L, NE, H, F)
    x = jnp.asarray(rng.standard_normal((NE, C, H)).astype(np.float32))
    assert qmm.pick_expert_bo(C, H, F, x.dtype.itemsize) is not None
    for layer in range(L):
        got = qmm.quant_matmul_experts_stacked(x, q, s, layer,
                                               interpret=True)
        ref = jnp.einsum("ech,ehf->ecf",
                         x, q[layer].astype(x.dtype)) * s[layer]
        # The contraction's length sets the rounding of a float32 sum.
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   atol=1e-4 * H / 256, rtol=1e-4,
                                   err_msg=f"layer {layer}")


@pytest.mark.parametrize("tiles", [False, True], ids=["experts", "tiles"])
@pytest.mark.parametrize("bo", [2688, 896, 384])
def test_expert_stripe_width_leaves_every_column_as_it_was(bo, tiles):
    """Nemotron's LatentMoE up-projection (1024 -> 2688 = 128 x 21) at
    the stripes the widest-divisor rule can give, none a power of two:
    a column's contraction over H, its float32 sum and its scale are the
    same whatever stripe holds it, so the output equals the 128-column
    walk's BIT FOR BIT, with empty experts skipped by ``count`` and with
    ``source`` naming the expert of each tile of a sorted dispatch. x is
    drawn in eighths, so every partial sum is exact in float32 and the
    equality does not lean on how the CPU's dot blocks its contraction
    under the interpreter."""
    L, NE, C, H, O = 2, 4, 16, 1024, 2688
    rng = np.random.default_rng(46)
    q, s = _int8_pool(rng, L, NE, H, O)
    if tiles:
        # Seven tiles over four experts: a run of two, an expert with no
        # tile (2), an empty tile naming the expert before it, as
        # moe_tiles.routed_tiles lays them out.
        source = jnp.asarray([0, 0, 1, 1, 1, 3, 3], jnp.int32)
        count = jnp.asarray([16, 5, 16, 16, 0, 9, 0], jnp.int32)
    else:
        source = None
        count = jnp.asarray([16, 0, 3, 0], jnp.int32)
    n = count.shape[0]
    x = np.round(rng.standard_normal((n, C, H)) * 8).clip(-32, 32) / 8
    x = jnp.asarray(x.astype(np.float32))
    x = x * (jnp.arange(C)[None, :, None] < count[:, None, None])
    for layer in range(L):
        got = qmm.quant_matmul_experts_stacked(
            x, q, s, layer, count, source, bo=bo, interpret=True)
        narrow = qmm.quant_matmul_experts_stacked(
            x, q, s, layer, count, source, bo=128, interpret=True)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(narrow))
        held = jnp.arange(NE) if source is None else source
        ref = jnp.einsum("ech,ehf->ecf", x,
                         q[layer][held].astype(x.dtype)) * s[layer][held]
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   atol=1e-4 * H / 256, rtol=1e-4,
                                   err_msg=f"layer {layer}")
    assert qmm.pick_expert_bo(C, H, O, 2) == 2688


@pytest.mark.parametrize("group,ng_parity", [
    (512, "odd"),     # ng=1: the round-18 half-group walk (G % 256 == 0)
    (256, "even"),    # ng=2: whole-group walk
    (128, "even"),    # ng=4: whole-group walk at the finer grouping
])
def test_expert_stacked_int4_matches_dequant_einsum(group, ng_parity):
    L, NE, C, H, F = 2, 2, 5, 512, 256
    rng = np.random.default_rng(1)
    w = rng.standard_normal((L, NE, H, F)).astype(np.float32)
    qt = quantize4(jnp.asarray(w), group=group)
    ng = qt.s.shape[-2]
    assert (ng % 2 == 1) == (ng_parity == "odd")
    assert qmm.pick_int4_bo(C, H, F, ng, 4) is not None
    x = jnp.asarray(rng.standard_normal((NE, C, H)).astype(np.float32))
    for layer in range(L):
        got = qmm.quant_matmul_experts_stacked4(x, qt.q, qt.s, layer,
                                                interpret=True)
        wl = dequantize4(type(qt)(q=qt.q[layer], s=qt.s[layer]), x.dtype)
        ref = jnp.einsum("ech,ehf->ecf", x, wl)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   atol=1e-4, rtol=1e-4,
                                   err_msg=f"group {group} layer {layer}")


# -- wgu_e fusion bit-identity ------------------------------------------------

@pytest.mark.parametrize("quantized", [False, True])
def test_moe_mlp_wgu_fusion_identity(quantized):
    """moe_mlp(w_gu=gate|up) == moe_mlp(w_gate, w_up) exactly: each
    fused output column runs the same contraction in the same order as
    its unfused twin, and per-output-channel int8 scales concatenate
    with their columns."""
    NE, k, B, S, H, F = 4, 2, 2, 3, 64, 32
    rng = np.random.default_rng(2)
    x = jnp.asarray(rng.standard_normal((B, S, H)).astype(np.float32))
    router = jnp.asarray(rng.standard_normal((H, NE)).astype(np.float32))
    w_gate = rng.standard_normal((NE, H, F)).astype(np.float32)
    w_up = rng.standard_normal((NE, H, F)).astype(np.float32)
    w_down = jnp.asarray(rng.standard_normal((NE, F, H)).astype(np.float32))
    w_gu = np.concatenate([w_gate, w_up], axis=-1)
    if quantized:
        from p2p_llm_chat_tpu.models.quant import quantize
        w_gate, w_up, w_gu = (quantize(jnp.asarray(a))
                              for a in (w_gate, w_up, w_gu))
        # Column-concat commutes with per-output-channel quantization.
        np.testing.assert_array_equal(
            np.asarray(w_gu.q),
            np.concatenate([np.asarray(w_gate.q), np.asarray(w_up.q)],
                           axis=-1))
    else:
        w_gate, w_up, w_gu = (jnp.asarray(a)
                              for a in (w_gate, w_up, w_gu))
    split = mixtral.moe_mlp(x, router, w_gate, w_up, w_down, k)
    fused = mixtral.moe_mlp(x, router, None, None, w_down, k, w_gu=w_gu)
    np.testing.assert_array_equal(np.asarray(split), np.asarray(fused))


# -- paged decode on quantized MoE params -------------------------------------

def test_paged_decode_matches_dense_quantized_moe():
    """The paged walk over a QUANTIZED tiny-moe (the int8 expert trunk +
    wgu_e fusion the bench serves) stays logit-identical to the dense
    cache — quantization changes the weights both paths share, never
    the attention walk."""
    from p2p_llm_chat_tpu.models.llama import KVCache
    from p2p_llm_chat_tpu.ops.paged_kv import (PageAllocator, PagedKVCache,
                                               write_prefill_row)
    PS = 8
    cfg = get_config("tiny-moe")
    params = mixtral.init_params_quantized(cfg, jax.random.PRNGKey(3),
                                           dtype=jnp.float32)
    assert isinstance(params["layers"]["wgu_e"], QTensor)
    prompts_lens = [5, 8, 13]
    B, S = len(prompts_lens), max(prompts_lens)
    max_seq = 64
    rng = np.random.default_rng(0)
    tokens = rng.integers(0, cfg.vocab_size, size=(B, S)).astype(np.int32)
    lens = jnp.asarray(prompts_lens, jnp.int32)

    dense = KVCache.create(cfg, B, max_seq, jnp.float32)
    logits, dense = mixtral.prefill(params, cfg, jnp.asarray(tokens), lens,
                                    dense)
    alloc = PageAllocator(32, PS)
    paged = PagedKVCache.create(cfg, B, 32, PS,
                                max_pages_per_row=max_seq // PS,
                                dtype=jnp.float32)
    for b in range(B):
        pages = alloc.alloc(alloc.pages_for(prompts_lens[b] + 8))
        table = np.zeros((paged.max_pages_per_row,), np.int32)
        table[: len(pages)] = pages
        paged = write_prefill_row(
            paged, dense.k[:, b, :S], dense.v[:, b, :S],
            jnp.asarray(b), jnp.asarray(prompts_lens[b]),
            jnp.asarray(table))

    last = jnp.stack([logits[b, n - 1] for b, n in enumerate(prompts_lens)])
    tok = jnp.argmax(last, axis=-1).astype(jnp.int32)[:, None]
    for step in range(4):
        pages = int(np.ceil((max(prompts_lens) + step + 1) / PS))
        d_logits, dense = mixtral.decode_step(params, cfg, tok, dense)
        p_logits, paged = mixtral.decode_step_paged(params, cfg, tok, paged,
                                                    pages=pages)
        np.testing.assert_allclose(np.asarray(p_logits),
                                   np.asarray(d_logits),
                                   atol=1e-4, rtol=1e-4,
                                   err_msg=f"step {step}")
        tok = jnp.argmax(d_logits[:, 0], axis=-1).astype(jnp.int32)[:, None]
