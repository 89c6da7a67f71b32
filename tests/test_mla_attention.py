"""ops/mla_attention.py: both kernels in interpret mode against their
XLA oracles, and the oracles against attention written out longhand."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from p2p_llm_chat_tpu.models.configs import ModelConfig
from p2p_llm_chat_tpu.ops import mla_attention as mla
from p2p_llm_chat_tpu.ops.paged_kv import PagedKVCache, write_prefill_batch

CFG = ModelConfig(name="mla-kernel-test", vocab_size=64, hidden_size=64,
                  intermediate_size=64, num_layers=2, num_heads=4,
                  num_kv_heads=1, head_dim=192, kv_lora_rank=128,
                  q_lora_rank=32, qk_nope_head_dim=128, qk_rope_head_dim=64,
                  v_head_dim=128)


def rand(key, shape, dtype=jnp.float32):
    return jax.random.normal(jax.random.PRNGKey(key), shape, dtype)


def longhand_prefill(qn, qr, kv, kr, offset, dn, dr, dv):
    B, S, Hq, _ = qn.shape
    W = kv.shape[1]
    kv = np.asarray(kv, np.float64).reshape(B, W, Hq, dn + dv)
    out = np.zeros((B, S, Hq, dv))
    for b in range(B):
        for h in range(Hq):
            for i in range(S):
                n = offset + i + 1
                s = (kv[b, :n, h, :dn] @ np.asarray(qn[b, i, h], np.float64)
                     + np.asarray(kr[b, :n, :dr], np.float64)
                     @ np.asarray(qr[b, i, h], np.float64)) / np.sqrt(dn + dr)
                p = np.exp(s - s.max())
                out[b, i, h] = (p / p.sum()) @ kv[b, :n, h, dn:]
    return out.reshape(B, S, Hq * dv)


@pytest.mark.parametrize("S,offset", [(8, 0), (8, 16), (16, 8)])
def test_prefill_reference_is_causal_attention_with_two_key_parts(S, offset):
    B, Hq, dn, dr, dv = 2, 3, 16, 8, 12
    W = offset + S
    qn, qr = rand(0, (B, S, Hq, dn)), rand(1, (B, S, Hq, dr))
    kv, kr = rand(2, (B, W, Hq * (dn + dv))), rand(3, (B, W, 16))
    got = mla.mla_prefill_reference(qn, qr, kv, kr, offset, dn=dn, dr=dr,
                                    dv=dv)
    want = longhand_prefill(qn, qr, kv, kr, offset, dn, dr, dv)
    np.testing.assert_allclose(np.asarray(got), want, atol=2e-5)


@pytest.mark.parametrize("S,offset", [(128, 0), (256, 256), (256, 768),
                                      (512, 0)])
def test_prefill_kernel_matches_reference(S, offset):
    """q.k 192 wide (128 + 64), v 128: the benchmark's head, at a chunk
    with no context, chunks behind one, and a prompt in one piece with
    key blocks past the diagonal skipped."""
    B, Hq, dn, dr, dv = 1, 4, 128, 64, 128
    W = offset + S
    qn, qr = rand(0, (B, S, Hq, dn)), rand(1, (B, S, Hq, dr))
    kv = rand(2, (B, W, Hq * (dn + dv)))
    kr = jnp.pad(rand(3, (B, W, dr)), ((0, 0), (0, 0), (0, 128 - dr)))
    want = mla.mla_prefill_reference(qn, qr, kv, kr, offset, dn=dn, dr=dr,
                                     dv=dv)
    got = mla.mla_prefill_attention(qn, qr, kv, kr, offset, dn=dn, dr=dr,
                                    dv=dv, interpret=True, impl="kernel")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


def pool_with(quantized, lengths, ps=32, per_row=4, seed=5):
    """A pool holding seeded latents for rows of the given lengths, and
    the float latents that went in."""
    B = len(lengths)
    T = ps * per_row
    c = rand(seed, (CFG.num_layers, B, T, 1, CFG.cache_k_dim))
    r = jnp.pad(rand(seed + 1, (CFG.num_layers, B, T, 1, 64)),
                ((0, 0),) * 4 + ((0, 64),))
    pool = PagedKVCache.create(CFG, B, 1 + B * per_row, ps,
                               max_pages_per_row=per_row, dtype=jnp.float32,
                               quantized=quantized)
    tables = 1 + jnp.arange(B * per_row, dtype=jnp.int32).reshape(B, per_row)
    pool = write_prefill_batch(pool, c, r, jnp.arange(B),
                               jnp.asarray(lengths, jnp.int32), tables)
    return pool, c, r


def test_decode_reference_is_attention_over_the_latent_rows():
    """bf16... float pool: every head scores the same rows with its own
    query, the value is the first ``r`` numbers of the row, the current
    token is one more row."""
    lengths = [37, 5]
    pool, c, r = pool_with(False, lengths)
    B, Hq, R = 2, 4, CFG.cache_k_dim
    ql, qr = rand(7, (B, Hq, R)), jnp.pad(rand(8, (B, Hq, 64)),
                                          ((0, 0), (0, 0), (0, 64)))
    cc, rc = rand(9, (B, R)), jnp.pad(rand(10, (B, 64)), ((0, 0), (0, 64)))
    got = mla.mla_decode_reference(ql, qr, cc, rc, pool, pool.lengths, 1,
                                   pages=4, sm_scale=0.1)
    for b, n in enumerate(lengths):
        rows_c = np.concatenate([np.asarray(c[1, b, :n, 0]),
                                 np.asarray(cc[b])[None]], 0)
        rows_r = np.concatenate([np.asarray(r[1, b, :n, 0]),
                                 np.asarray(rc[b])[None]], 0)
        s = (np.asarray(ql[b]) @ rows_c.T + np.asarray(qr[b]) @ rows_r.T) * 0.1
        p = np.exp(s - s.max(-1, keepdims=True))
        want = (p / p.sum(-1, keepdims=True)) @ rows_c
        np.testing.assert_allclose(np.asarray(got[b]), want, atol=2e-4)


@pytest.mark.parametrize("quantized", [False, True])
@pytest.mark.parametrize("pages", [4, 3, 1])
def test_decode_kernel_matches_reference(quantized, pages):
    """Float and int8 pools; a window of whole chunks, one whose last
    chunk is clamped, and one page; a row shorter than a page, a row
    near the window's end, and a parked row of length 0."""
    ps = 32
    lengths = [min(37, pages * ps - 1), 5, 0]
    pool, _, _ = pool_with(quantized, lengths, ps=ps)
    B, Hq, R = 3, 4, CFG.cache_k_dim
    ql, qr = rand(7, (B, Hq, R)), jnp.pad(rand(8, (B, Hq, 64)),
                                          ((0, 0), (0, 0), (0, 64)))
    cc, rc = rand(9, (B, R)), jnp.pad(rand(10, (B, 64)), ((0, 0), (0, 64)))
    old = mla._DECODE_CHUNK_TOKENS
    mla._DECODE_CHUNK_TOKENS = 64      # two pages a chunk: several chunks
    try:
        got = mla.mla_decode_attention(ql, qr, cc, rc, pool, pool.lengths, 1,
                                       pages=pages, sm_scale=0.1,
                                       interpret=True, impl="kernel")
    finally:
        mla._DECODE_CHUNK_TOKENS = old
    want = mla.mla_decode_reference(ql, qr, cc, rc, pool, pool.lengths, 1,
                                    pages=pages, sm_scale=0.1)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-4)


def test_block_reference_with_one_position_is_the_decode_reference():
    pool, _, _ = pool_with(True, [20, 9])
    B, Hq, R, S = 2, 4, CFG.cache_k_dim, 3
    ql, qr = rand(7, (B, S, Hq, R)), rand(8, (B, S, Hq, 128))
    cb, rb = rand(9, (B, S, R)), rand(10, (B, S, 128))
    blk = mla.mla_block_reference(ql, qr, cb, rb, pool, pool.lengths, 0,
                                  pages=2, sm_scale=0.2)
    one = mla.mla_decode_reference(ql[:, 0], qr[:, 0], cb[:, 0], rb[:, 0],
                                   pool, pool.lengths, 0, pages=2,
                                   sm_scale=0.2)
    np.testing.assert_allclose(np.asarray(blk[:, 0]), np.asarray(one),
                               atol=1e-5)
