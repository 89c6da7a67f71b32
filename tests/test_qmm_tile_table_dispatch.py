"""Decision-matrix tests for the quantized-matmul dispatch gates.

Round 18 moved every int4 coverage decision onto ONE derivation —
ops/quant_mm.int4_stripe_seg, the expert-stripe segment table — and
added the expert-pool (4-D) dispatch to models/quant.q_einsum. These
tests pin the decisions themselves (pure host logic, no kernels), so a
future budget/table tweak that silently flips a production shape from
Pallas to the XLA dequant fallback (or vice versa) fails loudly here
rather than showing up as a bench regression three rounds later.

The shapes named below are the production ones: bench-moe
(H=1024, F=2816) and mixtral-large (H=4096, F=11520 = 45*256 = 90*128)
expert leaves, plus the dense regression shapes the tile table was
measured on.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from p2p_llm_chat_tpu.models import quant
from p2p_llm_chat_tpu.models.quant import (LayerSlice, QTensor, QTensor4,
                                           _int4_group, q_einsum)
from p2p_llm_chat_tpu.ops import quant_mm as qmm


# -- int4_stripe_seg: the single int4 coverage gate ---------------------------

@pytest.mark.parametrize("K,ng,seg", [
    # even group counts walk whole groups (G % 128 == 0)
    (1024, 8, 128),       # dense decode trunk, G=128
    (11520, 90, 128),     # mixtral-large w_down at group 128
    (2816, 22, 128),      # bench-moe w_down, G=128
    (4096, 32, 128),      # mixtral-large wgu_e contraction
    # odd group counts walk half-groups (G % 256 == 0)
    (11520, 45, 128),     # mixtral-large w_down at group 256 -> seg G/2
    (2816, 11, 128),      # bench-moe w_down at group 256 -> seg G/2
    (512, 1, 256),        # single group, odd -> half of G=512
    # rejections: the kernels cannot serve these groupings
    (512, 8, None),       # G=64: even but not lane-aligned
    (1152, 9, None),      # odd at G=128: hi-half straddles scales
    (384, 3, None),       # odd at G=128 (small)
    (1023, 3, None),      # odd K: no packed byte rows
    (1000, 3, None),      # ng does not divide K
    (1024, 0, None),      # no groups
])
def test_int4_stripe_seg_matrix(K, ng, seg):
    assert qmm.int4_stripe_seg(K, ng) == seg


def test_int4_stripe_seg_segment_covers_one_scale_group():
    """Both halves of every segment must land inside a single scale
    group — the invariant _qmm4_body's walk rests on. Checked over the
    full production grid rather than argued once in a comment."""
    for K, ng in [(11520, 45), (11520, 90), (2816, 11), (2816, 22),
                  (4096, 32), (1024, 8), (512, 1)]:
        seg = qmm.int4_stripe_seg(K, ng)
        if seg is None:
            continue
        G = K // ng
        half = K // 2
        for t in range(half // seg):
            lo_rows = (t * seg, (t + 1) * seg - 1)
            hi_rows = (half + t * seg, half + (t + 1) * seg - 1)
            assert lo_rows[0] // G == lo_rows[1] // G, (K, ng, t)
            assert hi_rows[0] // G == hi_rows[1] // G, (K, ng, t)


# -- _int4_group: the grouping chooser the gate must agree with ---------------

@pytest.mark.parametrize("K,expert,group", [
    (11520, True, 256),    # real expert scale: halve the f32 scale rows
    (11520, False, 128),   # dense trunk keeps the finer grouping
    (4096, True, 128),     # expert but below the 8192 floor
    (4096, False, 128),
    (192, False, 64),      # small leaves fall to group 64
    (191, False, None),    # odd K: int8 fallback
])
def test_int4_group_choice(K, expert, group):
    assert _int4_group(K, expert) == group


def test_int4_group_choices_are_kernel_servable():
    """Every grouping _int4_group can emit for a kernel-sized K must be
    one int4_stripe_seg accepts — quantize-time choice and dispatch-time
    gate derive from the same table, so a leaf quantized for the kernel
    can never be silently forced onto the XLA path by its own grouping
    (the round-18 fix: group 256 at K=11520 yields ng=45, odd, which the
    old even-only gate rejected)."""
    for K in (1024, 2816, 4096, 11520, 28672):
        for expert in (False, True):
            G = _int4_group(K, expert)
            if G is None or G == 64:
                continue   # 64 is the declared XLA-only grouping
            assert qmm.int4_stripe_seg(K, K // G) is not None, (K, expert)


# -- block-width picks at the production shapes -------------------------------

def test_tile_table_pinned_entries():
    """The measured per-hidden-size caps (rounds 16-18), depth caps for
    the ONE-matrix grids: the dense and int4 pickers read them through
    `_pick_1d_bo`; since PR 47 the w8a16 expert grid, whose depth comes
    from its experts, does not. A removal or retune shows up here first,
    with the bench row that justified it."""
    assert qmm._TILE_TABLE[1024] == 256     # round-16 dense decode trunk
    assert qmm._TILE_TABLE[2816] == 128     # bench-moe w_down: avoid 1-program grid
    assert qmm._TILE_TABLE[11520] == 256    # mixtral-large w_down, budget-derived


# The expert matmuls the benchmark's cells dispatch, H -> O, with the
# width the rule gives each (tools/check_quant_kernel.py lists them for
# its sweep; PERF.md section 6, PR 47): at the decode bucket (32 rows)
# and at the rows of their prefill tiles (moe_tiles.tile_rows: 16-128).
from tools.check_quant_kernel import CELL_SHAPES  # noqa: E402

_CELL_EXPERT_SHAPES = [(H, O) for _, H, O, _, _ in CELL_SHAPES]
_CELL_WIDTHS = {
    "nemotron up": 2688, "nemotron down": 1024,
    "olmoe down": 2048, "olmoe gate|up": 1024,
    "mellum gate|up": 896, "mellum down": 2304,
    "lfm2 gate|up": 1792, "lfm2 down": 1024,
    "openpangu gate|up": 512, "openpangu down": 1920,
    "mixtral gate|up": 1024, "mixtral down": 256,
}
_CELL_ROWS = (16, 32, 64, 128)


@pytest.mark.parametrize("rows,H,O,bo", [
    # The widest multiple of 128 that divides O under a 4 MiB stripe,
    # where the dense search gave (PR 45): 512, 256 (`_TILE_TABLE`), 256
    # (`_TILE_TABLE[1024]`), 128 (`_TILE_TABLE[2816]`).
    (16, 4096, 23040, 768),    # mixtral-large wgu_e (O = 2F = 30 x 768)
    (16, 11520, 4096, 256),    # mixtral-large w_down: 512 is 5.6 MiB
    (8, 1024, 5632, 2816),     # bench-moe wgu_e (5632 itself is 5.5 MiB)
    (8, 2816, 1024, 1024),     # bench-moe w_down: one 2.75 MiB stripe
    (2048, 11520, 4096, None),  # prefill-class rows: x alone is 3 x 45 MiB
    # mixtral-8x7b (the benchmark's 6-layer cell): wgu_e [4096 -> 2 x
    # 14336] and w_down, at a part-full, a full and a prefill bucket.
    # They keep the dense search's answer by the rule's own arithmetic:
    # 28672 = 2^12 x 7 and 4096 x 1024 IS the 4 MiB stripe (1792 would
    # be 7 MiB); 14336 x 256 is 3.5 MiB and 14336 x 512 is 7.
    (2, 4096, 28672, 1024),
    (32, 4096, 28672, 1024),
    (256, 4096, 28672, 1024),   # the widest bucket a Mixtral cell fills
    # 512 rows: the dense search said 1024 and Mosaic refuses that
    # (scoped 22.36M of 16: x [512, 4096] bf16 three times over is 12);
    # 512 it refuses too (17.36M); 256 compiles (14.73M). No admission
    # makes this bucket (two rows of 256 tokens at the most, 256 rows).
    (512, 4096, 28672, 256),
    (32, 14336, 4096, 256),     # the 4 MiB stripe limit shrinks it
    # 128 rows: the dense search said 256, which Mosaic refuses compiled
    # alone (scoped 17.53M of 16, ROADMAP S5: the figure the account was
    # calibrated on); 128 compiles (10.56M). 256 rows: XLA, as before.
    (128, 14336, 4096, 128),
    (256, 14336, 4096, None),
    # olmoe-1b-7b's thin experts, NE 64 (PERF.md section 6, PR 26 and PR
    # 46: the chip's sweeps at C = 8..512): wgu_e [2048 -> 2 x 1024]
    # and w_down [1024 -> 2048]. The dense search gave 1024 (two
    # programs an expert), which stays: 2048 x 2048 is a 4 MiB expert,
    # over the 3 MiB a whole expert may be in one stripe (the cell's
    # trace read it 7-11% slower at one stripe than at two); and 256
    # (eight programs, under `_TILE_TABLE[1024]`, a cap measured for a
    # dense trunk of two programs), which goes to the whole 2 MiB expert.
    (8, 2048, 2048, 1024),
    (32, 2048, 2048, 1024),
    (64, 2048, 2048, 1024),
    (512, 2048, 2048, 1024),
    (8, 1024, 2048, 2048),
    (32, 1024, 2048, 2048),
    (64, 1024, 2048, 2048),
    (512, 1024, 2048, 2048),
] + [
    # Every cell's shapes at its decode bucket and its tile rows.
    (rows, H, O, _CELL_WIDTHS[label])
    for label, H, O, _, _ in CELL_SHAPES
    for rows in _CELL_ROWS
    if (rows, H, O) != (128, 14336, 4096)
])
def test_pick_expert_bo_matrix(rows, H, O, bo):
    assert qmm.pick_expert_bo(rows, H, O, 2) == bo


@pytest.mark.parametrize("H,O", _CELL_EXPERT_SHAPES)
@pytest.mark.parametrize("rows", _CELL_ROWS + (2, 256, 512))
def test_pick_expert_bo_is_the_widest_divisor_that_fits(rows, H, O):
    """The rule, whole: the result is a multiple of 128, divides O,
    passes the stripe limit, the two-stripes clause and the VMEM
    account, and no wider such divisor does; None only where not even
    128 columns fit. There is no clause by rows beyond the account's own
    terms: on the chip
    (tools/check_quant_kernel.py sweep-cells, 16 to 128 rows, all
    experts touched and the cells' shares of them; PERF.md section 6,
    PR 47) the widest width under the stripe limit is the fastest or
    within 1.5% of it at eleven of the twelve shapes at every row count,
    128 rows included (Nemotron's up projection 1.200 ms at 2688 against
    1.299 at 896; OLMoE's down 0.390 at 2048 against 0.432 at 1024).
    The two-stripes clause gives some of that up at 128 rows, all
    experts touched (Mellum's gate|up 0.486 ms at 896 against 0.446 at
    1792) for what it gains where a decode step leaves experts empty
    (OLMoE's gate|up, 38 of 64 touched: 0.283 ms at 1024 against 0.324
    at 2048). The twelfth shape is openPangu's 7680 -> 4096, where 256
    columns read 0.700 ms against 0.781 at the 512 this rule AND the
    dense search give at 32 rows, and lose at 128 rows: left as it was
    (PERF.md section 7(xix))."""
    fits = [b for b in range(128, O + 1, 128)
            if O % b == 0 and qmm.expert_bo_fits(rows, H, O, b, 2)]
    bo = qmm.pick_expert_bo(rows, H, O, 2)
    if not fits:
        assert bo is None
        return
    assert bo == max(fits)
    assert bo % 128 == 0 and O % bo == 0
    assert H * bo <= qmm._EXPERT_STRIPE_BYTES
    assert bo < O or H * O <= qmm._EXPERT_WHOLE_BYTES
    assert (qmm.expert_vmem_bytes(rows, H, bo, 2)
            <= qmm._EXPERT_VMEM_LIMIT_BYTES)


def test_expert_vmem_account_against_mosaics_own_figures():
    """The account never reads under what Mosaic printed for the same
    program (compiled for a described v5e under a lowered limit, which
    makes it print: PR 47 read these thirteen again that way, to the
    digit; tests/test_pool_write_layout.py compiles the cells' shapes
    at the default one), and stands within 1 MiB of it at the shapes
    that decide a cell's width."""
    M = 2 ** 20
    for rows, H, bo, mosaic in [
            (128, 14336, 256, 17.53),   # ROADMAP S5's refusal
            (32, 14336, 256, 9.94), (32, 4096, 1024, 8.92),
            (256, 4096, 1024, 15.12), (512, 4096, 1024, 22.36),
            (512, 4096, 256, 14.73), (32, 1024, 2688, 6.14),
            (128, 1024, 2688, 7.31), (32, 2048, 2048, 8.55),
            (128, 2048, 2048, 10.53), (128, 7680, 512, 13.36),
            (128, 2048, 1920, 9.96), (512, 2688, 1024, 15.49)]:
        account = qmm.expert_vmem_bytes(rows, H, bo, 2) / M
        assert mosaic <= account <= mosaic + 1.0, (rows, H, bo, account)


@pytest.mark.parametrize("rows,H,O,bo", [
    # mistral-7b-v0.3's decode projections at a full batch: fused qkv,
    # wo, fused gate|up, w_down, the 32768-wide head.
    (32, 4096, 6144, 1024),
    (32, 4096, 4096, 1024),
    (32, 4096, 28672, 1024),
    (32, 14336, 4096, 256),
    (32, 4096, 32768, 1024),
    (32, 4096, 32000, 256),     # mixtral's head: 32000 = 125 x 256
    # olmoe-1b-7b's dense projections: fused qkv (MHA: 3 x 2048), wo, and
    # the 50304-wide head (393 x 128).
    (32, 2048, 6144, 1024),
    (32, 2048, 2048, 1024),
    (32, 2048, 50304, 128),
])
def test_pick_1d_bo_at_the_benchmarks_dense_shapes(rows, H, O, bo):
    """The dense stripe kernel's tile at every decode projection the
    benchmark's three configurations run: a retune of `_TILE_TABLE` or
    of a budget for one of them shows here if it moves another's."""
    assert qmm._pick_1d_bo(rows, H, O, 2) == bo


@pytest.mark.parametrize("rows,H,O,ng,bo", [
    (16, 11520, 4096, 45, 256),   # mixtral-large w_down, group 256 (odd walk)
    (16, 11520, 4096, 90, 256),   # same leaf quantized at group 128
    (16, 4096, 23040, 32, 512),   # mixtral-large wgu_e, group 128
    (8, 2816, 1024, 11, 128),     # bench-moe w_down, group 256 (odd walk)
    (8, 512, 512, 8, None),       # G=64: gate rejects
    (8, 1152, 512, 9, None),      # odd at G=128: gate rejects
])
def test_pick_int4_bo_matrix(rows, H, O, ng, bo):
    assert qmm.pick_int4_bo(rows, H, O, ng, 2) == bo


# -- q_einsum expert-pool dispatch decisions ----------------------------------

def _expert_pool_int8(L=2, NE=2, H=256, F=512, seed=0):
    r = np.random.default_rng(seed)
    q = r.integers(-127, 128, size=(L, NE, H, F), dtype=np.int8)
    s = (r.random((L, NE, 1, F), np.float32) * 0.02 + 0.01)
    return QTensor(q=jnp.asarray(q), s=jnp.asarray(s))


def _expert_pool_int4(L=2, NE=2, H=512, F=512, ng=1, seed=0):
    r = np.random.default_rng(seed)
    q = r.integers(0, 256, size=(L, NE, H // 2, F), dtype=np.uint8)
    s = (r.random((L, NE, ng, F), np.float32) * 0.02 + 0.01)
    return QTensor4(q=jnp.asarray(q.astype(np.int8)), s=jnp.asarray(s))


def _spy(monkeypatch, name):
    """Replace the named ops.quant_mm expert kernel with a recorder that
    returns a correctly-shaped dummy (the dispatch sites re-import from
    the module on every call, so the monkeypatch is what they fetch)."""
    calls = []

    def fake(x, q, s, layer, count=None, **kw):
        calls.append((x.shape, q.shape, int(layer) if np.ndim(layer) == 0
                      else layer, count))
        return jnp.zeros(x.shape[:2] + (q.shape[-1],), x.dtype)

    monkeypatch.setattr(qmm, name, fake)
    return calls


@pytest.fixture
def on_tpu(monkeypatch):
    """Make kernel_wanted() answer True on the CPU test host (the
    decision logic under test is backend-independent)."""
    monkeypatch.setattr(quant, "on_tpu", lambda: True)
    monkeypatch.setattr(quant, "_FORCE_XLA", False)


def test_expert_dispatch_int8_pool_hits_kernel(on_tpu, monkeypatch):
    calls = _spy(monkeypatch, "quant_matmul_experts_stacked")
    w = _expert_pool_int8()
    x = jnp.ones((2, 8, 256), jnp.float32)
    y = q_einsum("ech,ehf->ecf", x, LayerSlice(w, 1))
    assert y.shape == (2, 8, 512)
    assert len(calls) == 1 and calls[0][2] == 1 and calls[0][3] is None
    # The buckets' count of filled slots goes to the kernel as it came.
    count = jnp.asarray([3, 0], jnp.int32)
    q_einsum("ech,ehf->ecf", x, LayerSlice(w, 1), count)
    assert calls[1][3] is count


def test_expert_dispatch_int4_pool_hits_kernel(on_tpu, monkeypatch):
    calls = _spy(monkeypatch, "quant_matmul_experts_stacked4")
    w = _expert_pool_int4()              # H=512, ng=1 -> odd walk, seg 256
    x = jnp.ones((2, 8, 512), jnp.float32)
    count = jnp.asarray([0, 8], jnp.int32)
    y = q_einsum("ech,ehf->ecf", x, LayerSlice(w, 0), count)
    assert y.shape == (2, 8, 512)
    assert len(calls) == 1 and calls[0][2] == 0 and calls[0][3] is count


@pytest.mark.parametrize("reason,spec,xshape", [
    # spec not in the family / x not expert-batched: broadcast-style
    # einsums (one token bucket against every expert) are legal through
    # the eager path but are NOT a per-expert batched matmul.
    ("x is not expert-batched (2-D)", "ch,ehf->ecf", (8, 256)),
    ("prefill-class token count", "ech,ehf->ecf", (2, 513, 256)),
])
def test_expert_dispatch_falls_back(on_tpu, monkeypatch, reason, spec,
                                    xshape):
    calls = _spy(monkeypatch, "quant_matmul_experts_stacked")
    w = _expert_pool_int8(H=256, F=512)
    x = jnp.ones(xshape, jnp.float32)
    y = q_einsum(spec, x, LayerSlice(w, 0))
    assert not calls, reason
    assert y.shape[-1] == 512             # fallback still produced output


def test_expert_dispatch_int4_rejected_grouping_falls_back(on_tpu,
                                                           monkeypatch):
    """A pool whose grouping the stripe table cannot serve (G=64) must
    take the dequant fallback even when the kernel is wanted."""
    calls = _spy(monkeypatch, "quant_matmul_experts_stacked4")
    w = _expert_pool_int4(H=512, ng=8)    # G=64 -> int4_stripe_seg None
    x = jnp.ones((2, 8, 512), jnp.float32)
    y = q_einsum("ech,ehf->ecf", x, LayerSlice(w, 0))
    assert not calls
    assert y.shape == (2, 8, 512)


def test_expert_dispatch_cpu_fallback_matches_eager_slice():
    """On the actual CPU backend (no monkeypatch) the LayerSlice expert
    path must be bit-identical to slicing the layer eagerly and running
    the plain quantized einsum — the pre-round-18 behavior."""
    w = _expert_pool_int8(L=3)
    x = jnp.asarray(np.random.default_rng(1).standard_normal(
        (2, 8, 256)).astype(np.float32))
    for layer in range(3):
        got = q_einsum("ech,ehf->ecf", x, LayerSlice(w, layer))
        ref = q_einsum("ech,ehf->ecf", x, QTensor(q=w.q[layer],
                                                  s=w.s[layer]))
        assert jnp.array_equal(got, ref), layer
