"""Pallas w8a16 + w4a16 matmuls: quantized weights dequantized in VMEM.

Why this kernel exists: XLA on TPU does not stream int8 dot operands —
``x @ q.astype(bf16)`` (and the mixed-dtype ``dot_general``) materialise
a full bf16 copy of the weight in HBM before the matmul, so "int8"
decode read MORE bytes than bf16 (measured on a v5e chip: 22-layer
decode trunk 4.0 ms with the convert vs 2.9 ms plain bf16 — the int8
read + bf16 write + bf16 read round trip). Here each program DMAs an
int8 ``[block_h, block_o]`` weight tile straight into VMEM, converts it
there (VPU, free next to the HBM stream), and feeds the MXU — HBM sees
int8 only, which is the entire point of weight-only quantization for
bandwidth-bound decode (models/quant.py).

Grid ``(O/block_o, H/block_h)`` with the contraction (H) innermost: the
f32 accumulator tile stays resident in VMEM scratch across the H walk
and is scaled (per-output-channel ``s``) once on the last step.

Used by models/quant.mm for small-row calls (decode/verify ticks — the
bandwidth-bound shapes); prefill keeps the XLA path, where the convert
cost is amortised over thousands of rows and the matmul is
compute-bound. ``interpret=True`` runs on CPU for hardware-free parity
tests (tests/test_quant.py).

The w4a16 kernels (:func:`quant_matmul4` / :func:`quant_matmul_stacked4`)
stream the PACKED int4 bytes — HBM weight traffic is half of int8's,
the entire point — and unpack nibbles + fold group-wise scales in VMEM.
They run the 1D whole-contraction grid only, statically unrolled over
SEGMENTS of the split-half packing (models/quant.pack4): each segment of
packed byte rows unpacks one small [seg, bo] tile (a whole-stripe int32
unpack would blow VMEM at 8B dims), runs two [rows, seg] x [seg, bo]
dots, and scales each after its dot — the segment width is chosen so
every dot's logical rows fall inside ONE scale group, which is what
makes scale-after-dot legal per group. Even group counts walk whole
groups (seg = G: packed rows ``[g*G, (g+1)*G)`` are exactly logical
group ``g`` low-nibble and group ``ng/2 + g`` high-nibble); odd group
counts walk HALF-groups (seg = G/2: the hi-nibble half starts at
logical row ng*G/2 — a half-group boundary — so whole-group segments
would straddle two scales, half-group segments never do).
Preconditions (:func:`int4_stripe_seg`): group % 128 == 0 for even
counts, group % 256 == 0 for odd ones (x slices must stay lane-
aligned at the segment width); everything else takes the dequant XLA
fallback in models/quant.mm.

The ``*_experts_stacked`` kernels extend both precisions to the 4-D
MoE expert pools [L, NE, H, O]: grid (NE, O/bo) per layer, each program
DMAing one expert's whole-contraction stripe, so the top-k gathered
expert matmuls of models/mixtral.moe_mlp ride the quantized stream
instead of falling back to an XLA dequant of the full expert stack.
They are handed each bucket's count of filled slots and read nothing for
an empty one (:func:`_expert_weight_map`): decode at a part-full batch
is bound by the experts' bytes, and most experts hold no live token then.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Weight-tile candidates, first divisor wins. All lane-aligned (x128) and
# int8-sublane-aligned (x32). Bigger tiles = fewer program invocations
# (the per-program cost is what erodes the bandwidth win at decode);
# 1024x1024 int8 = 1 MiB of VMEM per tile, comfortably resident.
_BLOCK_CANDIDATES = (1024, 512, 256, 128)

# VMEM budget for ONE whole-contraction weight stripe [H, bo] int8 on the
# 1D-grid path (~16 MB VMEM/core; Mosaic double-buffers the stripe, so the
# working set is 2x this, leaving room for x/out/everything else). Chosen
# so bench-1b's w_down (H=5632) still runs whole-H stripes at bo=512.
# QMM_STRIPE_BUDGET overrides (bytes; 0 forces the 2D grid everywhere).
import os as _os

_STRIPE_BUDGET_BYTES = int(_os.environ.get("QMM_STRIPE_BUDGET",
                                           4 * 1024 * 1024))

# Ceiling for the fully-resident x block of the 1D whole-contraction
# grid (x [rows, H] bf16 + two double-buffered weight stripes must fit
# ~16 MB VMEM). Calls above it use the 2D grid, whose x blocks tile over
# H — hit by 512-row prefill-admission chunks at 8B dims (rows x 14336
# bf16 = 14.7 MB, observed as a compile-time VMEM OOM).
_X_VMEM_BUDGET_BYTES = 6 * 1024 * 1024

# Per-hidden-size output-tile autotune table for the 1D whole-stripe
# grids of ONE matrix. What reads it: _pick_1d_bo, so the dense w8a16
# kernels, the dense w4a16 kernels and the int4 expert kernel
# (pick_int4_bo), identical logical shapes picking identical grids in
# both precisions. What does not, since PR 47: the w8a16 expert-stripe
# kernel (pick_expert_bo). Every entry is a DEPTH cap: a one-matrix grid
# is O / bo programs deep and needs several in flight to overlap a
# stripe's DMA with the dot before it, while the expert grid is
# (NE, O / bo), 8 to 128 times deeper before a stripe is cut at all, and
# measured fastest with the widest stripe that fits (0.306 ms at the 256
# columns of the 1024 cap against 0.204 at the whole 2048 at OLMoE's
# down projection, 32 rows; PERF.md section 6, PRs 26 and 47).
#
# Key = logical contraction dim, value = bo cap. Why it exists: the
# stripe machinery was tuned at hidden=2048 (bench-1b), where bo=1024
# keeps >= 6 programs in flight per matmul; at hidden=1024 (draft-400m)
# the same bo leaves a 2048-col projection only TWO grid programs — too
# shallow for Mosaic to overlap the next stripe's DMA with the current
# dot, recorded as the stacked kernel losing ~5% to forced XLA (ROADMAP
# round-8 MoE note). Capping bo at 256 restores >= 8 programs and the
# double-buffer overlap; tests/test_qmm_tile_table_dispatch.py pins the
# dispatch decision, tools/check_quant_kernel.py measures it on chip.
# Caps only apply when they divide O (else the next smaller candidate
# divisor wins via the normal search).
#
# 2816 and 11520 (round 18) are bench-moe's and mixtral-large's w_down
# contractions, entered for the expert kernels when those still searched
# here: 128 restores 8 programs of an O=1024 projection, 256 pins what
# the 4 MiB stripe budget gives at O=4096. The int4 expert kernel still
# reads them.
_TILE_TABLE = {1024: 256, 2816: 128, 11520: 256}


def _qmm_kernel(x_ref, q_ref, s_ref, o_ref, acc_ref):
    j = pl.program_id(1)
    num_h = pl.num_programs(1)

    @pl.when(j == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    x = x_ref[...]                                 # [rows, bh] bf16
    q = q_ref[...].astype(x.dtype)                 # int8 -> bf16 in VMEM
    acc_ref[:] += jax.lax.dot(x, q, preferred_element_type=jnp.float32)

    @pl.when(j == num_h - 1)
    def _finalise():
        s = s_ref[0].astype(jnp.float32)           # [bo]
        o_ref[...] = (acc_ref[:] * s[None, :]).astype(o_ref.dtype)


def _qmm_kernel_1d(x_ref, q_ref, s_ref, o_ref):
    """Whole-contraction stripe: one program = one [H, bo] weight tile =
    one output tile — no revisits, no scratch accumulator, and ~3x fewer
    program invocations than the 2D grid at decode shapes (measured: the
    per-program fixed cost, not DMA bandwidth, dominated the 2D walk)."""
    x = x_ref[...]                                 # [rows, H] bf16
    q = q_ref[...].astype(x.dtype)                 # int8 -> bf16 in VMEM
    acc = jax.lax.dot(x, q, preferred_element_type=jnp.float32)
    s = s_ref[0].astype(jnp.float32)               # [bo]
    o_ref[...] = (acc * s[None, :]).astype(o_ref.dtype)


def _qmm_kernel_1d_stacked(layer_ref, x_ref, q_ref, s_ref, o_ref):
    """Whole-contraction stripe fetched from the STACKED [L, H, O] weight
    at the scalar-prefetched layer index. This is how the decode scan
    avoids materialising per-layer weight slices: a pallas custom-call
    cannot alias a dynamic-slice view, so feeding it sliced operands made
    XLA copy every layer's int8 weights before the matmul — measured at
    ~1.9 ms of a 3.8 ms bench-1b step (half the step!). With the stacked
    operand the kernel DMAs tiles straight from the scan-invariant pool."""
    x = x_ref[...]                                 # [rows, H] bf16
    q = q_ref[0].astype(x.dtype)                   # [H, bo] int8 -> bf16
    acc = jax.lax.dot(x, q, preferred_element_type=jnp.float32)
    s = s_ref[0, 0].astype(jnp.float32)            # [bo]
    o_ref[...] = (acc * s[None, :]).astype(o_ref.dtype)


def _qmm_kernel_2d_stacked(layer_ref, x_ref, q_ref, s_ref, o_ref, acc_ref):
    j = pl.program_id(1)
    num_h = pl.num_programs(1)

    @pl.when(j == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    x = x_ref[...]                                 # [rows, bh]
    q = q_ref[0].astype(x.dtype)                   # [bh, bo]
    acc_ref[:] += jax.lax.dot(x, q, preferred_element_type=jnp.float32)

    @pl.when(j == num_h - 1)
    def _finalise():
        s = s_ref[0, 0].astype(jnp.float32)        # [bo]
        o_ref[...] = (acc_ref[:] * s[None, :]).astype(o_ref.dtype)


def _qmm4_body(x, pk_rows, s_rows, o_dtype):
    """Shared w4a16 kernel body: x [rp, K]; pk_rows [K/2, bo] packed
    int8; s_rows [ng, bo] f32. Statically unrolled over SEGMENTS of the
    split-half packing: each iteration unpacks ONE [seg, bo] tile to
    int32 (small — a whole-stripe unpack would blow VMEM at K=14336),
    runs two [rp, seg] x [seg, bo] dots and folds each group's scale
    after its dot (legal per group: the segment width divides the group
    so a dot's contraction never crosses a scale boundary — see
    :func:`int4_stripe_seg` for why odd counts need half-group
    segments). Nibble math stays in int32 where & 0xF and the
    arithmetic >> 4 are sign-robust for negative reinterpreted bytes."""
    K = x.shape[1]
    ng = s_rows.shape[0]
    G = K // ng
    seg = int4_stripe_seg(K, ng)
    acc = jnp.zeros((x.shape[0], pk_rows.shape[1]), jnp.float32)
    for t in range((K // 2) // seg):
        pk = pk_rows[t * seg:(t + 1) * seg, :].astype(jnp.int32)
        w_lo = ((pk & 0xF) - 8).astype(x.dtype)
        w_hi = (((pk >> 4) & 0xF) - 8).astype(x.dtype)
        # Logical rows of this segment: low nibbles at t*seg, high
        # nibbles at K/2 + t*seg; both offsets are seg-multiples and seg
        # divides G, so each lies inside exactly one scale group.
        s_lo = s_rows[(t * seg) // G, :].astype(jnp.float32)
        s_hi = s_rows[(K // 2 + t * seg) // G, :].astype(jnp.float32)
        acc += jax.lax.dot(x[:, t * seg:(t + 1) * seg], w_lo,
                           preferred_element_type=jnp.float32) * s_lo[None, :]
        acc += jax.lax.dot(x[:, K // 2 + t * seg:K // 2 + (t + 1) * seg],
                           w_hi,
                           preferred_element_type=jnp.float32) * s_hi[None, :]
    return acc.astype(o_dtype)


def _qmm4_kernel_1d(x_ref, q_ref, s_ref, o_ref):
    """w4a16 whole-contraction stripe: one program = one [K/2, bo] PACKED
    weight tile = one output tile. HBM reads the int4-packed bytes only."""
    o_ref[...] = _qmm4_body(x_ref[...], q_ref[...], s_ref[...], o_ref.dtype)


def _qmm4_kernel_1d_stacked(layer_ref, x_ref, q_ref, s_ref, o_ref):
    """w4a16 stacked twin: the [L, K/2, O] packed pool is read at the
    scalar-prefetched layer index, no per-layer slice materialisation —
    same motivation as _qmm_kernel_1d_stacked."""
    o_ref[...] = _qmm4_body(x_ref[...], q_ref[0], s_ref[0], o_ref.dtype)


def _touched_or_zero(route_ref, o_ref, compute) -> None:
    """Run ``compute`` (which writes this program's output block) for an
    expert whose bucket holds a token; an empty one's block is written
    as zeros, which is what its all-zero bucket would have given, so
    nothing uninitialised reaches a later fusion."""
    touched = route_ref[pl.program_id(0)] > 0
    pl.when(touched)(compute)

    @pl.when(jnp.logical_not(touched))
    def _empty():
        o_ref[...] = jnp.zeros_like(o_ref)


def _qmm_kernel_experts_stacked(layer_ref, route_ref, x_ref, q_ref, s_ref,
                                o_ref):
    """w8a16 expert stripe: one program = one expert's whole-contraction
    [H, bo] tile from the [L, NE, H, O] pool at the scalar-prefetched
    layer — the batched-expert twin of _qmm_kernel_1d_stacked. The
    expert axis is the OUTER grid dim, so the per-expert x block
    [C, H] is fetched once and the O/bo stripe walk streams under it.
    ``route_ref`` (:func:`_expert_route`): an empty expert's programs
    were handed no new stripe and compute nothing."""
    def compute():
        x = x_ref[0]                               # [Cp, H] bf16
        q = q_ref[0, 0].astype(x.dtype)            # [H, bo] int8 -> bf16
        acc = jax.lax.dot(x, q, preferred_element_type=jnp.float32)
        s = s_ref[0, 0, 0].astype(jnp.float32)     # [bo]
        o_ref[0] = (acc * s[None, :]).astype(o_ref.dtype)
    _touched_or_zero(route_ref, o_ref, compute)


def _qmm4_kernel_experts_stacked(layer_ref, route_ref, x_ref, q_ref, s_ref,
                                 o_ref):
    """w4a16 expert stripe over the [L, NE, K/2, O] packed pool — the
    batched-expert twin of _qmm4_kernel_1d_stacked, sharing the
    segment-walk body (and its odd-group support) and the int8 twin's
    skip of an empty expert."""
    def compute():
        o_ref[0] = _qmm4_body(x_ref[0], q_ref[0, 0], s_ref[0, 0],
                              o_ref.dtype)
    _touched_or_zero(route_ref, o_ref, compute)


@functools.partial(jax.jit, static_argnames=("interpret",))
def quant_matmul_stacked(x: jax.Array, q: jax.Array, s: jax.Array,
                         layer: jax.Array, *,
                         interpret: bool = False) -> jax.Array:
    """``x @ dequant(q[layer], s[layer])`` reading the stacked weight
    directly — no per-layer slice copy (see _qmm_kernel_1d_stacked).

    x: [rows, H]; q: [L, H, O] int8; s: [L, 1, O] f32 (the stacked
    models/quant.QTensor layout); layer: scalar int32. Same block
    preconditions as :func:`quant_matmul`.
    """
    rows, H = x.shape
    O = q.shape[2]
    bh, bo = pick_block(H), pick_block(O)
    if bh is None or bo is None:
        raise ValueError(f"no block divides H={H} / O={O}; use the XLA path")
    pad = (-rows) % 8
    if pad:
        x = jnp.pad(x, ((0, pad), (0, 0)))
    rp = rows + pad
    ly = jnp.asarray(layer, jnp.int32).reshape(1)

    bo_1d = _pick_1d_bo(rp, H, O, x.dtype.itemsize)
    if bo_1d is not None:
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(O // bo_1d,),
            in_specs=[
                pl.BlockSpec((rp, H), lambda i, ly: (0, 0)),
                pl.BlockSpec((1, H, bo_1d), lambda i, ly: (ly[0], 0, i)),
                pl.BlockSpec((1, 1, bo_1d), lambda i, ly: (ly[0], 0, i)),
            ],
            out_specs=pl.BlockSpec((rp, bo_1d), lambda i, ly: (0, i)),
        )
        out = pl.pallas_call(
            _qmm_kernel_1d_stacked,
            name="qmm_int8_stacked_1d",
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((rp, O), x.dtype),
            interpret=interpret,
        )(ly, x, q, s)
        return out[:rows] if pad else out

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(O // bo, H // bh),
        in_specs=[
            pl.BlockSpec((rp, bh), lambda i, j, ly: (0, j)),
            pl.BlockSpec((1, bh, bo), lambda i, j, ly: (ly[0], j, i)),
            pl.BlockSpec((1, 1, bo), lambda i, j, ly: (ly[0], 0, i)),
        ],
        out_specs=pl.BlockSpec((rp, bo), lambda i, j, ly: (0, i)),
        scratch_shapes=[pltpu.VMEM((rp, bo), jnp.float32)],
    )
    out = pl.pallas_call(
        _qmm_kernel_2d_stacked,
        name="qmm_int8_stacked_2d",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((rp, O), x.dtype),
        interpret=interpret,
    )(ly, x, q, s)
    return out[:rows] if pad else out


def pick_block(dim: int) -> int | None:
    for b in _BLOCK_CANDIDATES:
        if dim % b == 0:
            return b
    return None


def _pick_1d_bo(rp: int, H: int, O: int, x_itemsize: int,
                stripe_rows: int | None = None) -> int | None:
    """Output-block width for the 1D whole-contraction grid, or None to
    use the 2D grid: x [rp, H] must fit the VMEM x-budget and the
    [stripe_rows, bo] weight stripe the stripe budget (stripe_rows
    defaults to H — int8's byte rows; the int4 path passes H/2, its
    PACKED byte rows). The per-hidden-size _TILE_TABLE caps bo below the
    budget-driven choice where measurement says shallower grids lose to
    XLA. Shared by the stacked and unstacked kernels of both precisions
    so identical shapes always pick identical grids."""
    if rp * H * x_itemsize > _X_VMEM_BUDGET_BYTES:
        return None
    sr = H if stripe_rows is None else stripe_rows
    bo = pick_block(O)
    cap = _TILE_TABLE.get(H)
    if cap is not None and bo is not None and bo > cap and O % cap == 0:
        bo = cap
    while bo is not None and sr * bo > _STRIPE_BUDGET_BYTES:
        bo = next((b for b in _BLOCK_CANDIDATES
                   if b < bo and O % b == 0), None)
    return bo


def int4_stripe_seg(K: int, ng: int) -> int | None:
    """Segment width (in packed byte rows) of the w4a16 stripe walk for
    contraction ``K`` with ``ng`` scale groups, or None if the kernels
    cannot serve the grouping — the single coverage gate every int4
    dispatch decision derives from (the expert-stripe table of the
    round-18 MoE work; pick_int4_bo and _qmm4_body both consult it).

    Even counts walk whole groups: seg = G, needing G % 128 == 0 for
    lane-aligned x slices. Odd counts CANNOT walk whole groups — the
    hi-nibble half starts at logical row ng*G/2, a half-group boundary,
    so a whole-group segment would straddle two scales — they walk
    half-groups instead: seg = G/2, needing G % 256 == 0 to keep the
    half-width slices lane-aligned. G=64 shapes (and odd counts at
    G=128) fall back to the XLA dequant path in models/quant.
    """
    if ng <= 0 or K % ng or K % 2:
        return None
    G = K // ng
    if ng % 2 == 0:
        return G if G % 128 == 0 else None
    return G // 2 if G % 256 == 0 else None


# The expert-stripe kernel's OWN block rule (pick_expert_bo). Its grid is
# (NE, O / bo): the depth a pipeline needs comes from the experts, 8 to
# 128 of them, so the widest stripe that fits is the best one (a program
# costs about 0.25 us whatever it moves: tools/check_quant_kernel.py
# sweep-cells, PERF.md section 6 PR 47).
#
# What "fits" means is ONE sum against Mosaic's scoped VMEM limit, of
# what Mosaic itself allocates for a program of this kernel; every term
# was read off its refusals ("scoped allocation 17.53M, limit 16.00M"
# for 128 rows x 14336 -> 4096 at bo 256). Held against the compiler's
# own figure at 25 shapes compiled for a described v5e under a lowered
# limit and at the 14 widths the chip's sweep saw refused (PR 47): never
# under it, 0.15 to 0.8 MiB over it at the widths the cells' shapes
# get, up to 4 MiB over it where many rows meet a narrow stripe (the
# body then keeps no copy of x):
#   2 x [H, bo] int8           the stripe in its two pipeline buffers
#   2 x [Cp, H] + 1 x [Cp, H]  x in its two buffers, and the copy the
#                              body keeps while the MXU reads it
#   2 x [Cp, bo]               the output block in its two buffers
#   [Cp, 256] float32          the product, which Mosaic holds a column
#                              tile at a time, never whole
#   0.5 MiB                    scale blocks and the relayout of a bucket
#                              of 16 or 32 bf16 rows
# The stripe's bf16 conversion is NOT a term: Mosaic converts on the way
# into the MXU and keeps none of it.
_EXPERT_VMEM_LIMIT_BYTES = 16 * 1024 * 1024
_EXPERT_VMEM_SLACK_BYTES = 512 * 1024
# No stripe above 4 MiB: two of them are half the limit, and the sweep
# found nothing past it (Mixtral's 4096 x 1024 stripes stand at 91-92%
# of the roofline; PERF.md section 6, PRs 27 and 47).
_EXPERT_STRIPE_BYTES = 4 * 1024 * 1024
# An expert of more than 3 MiB is cut into at least two stripes. A decode
# step leaves experts empty, and the stripe of a touched expert behind
# an empty one is fetched in the open: the empty program that starts the
# fetch has no dot to hide it behind, so every such turn costs one
# stripe's DMA, a whole expert's where the stripe is the expert. OLMoE's
# gate|up (4 MiB an expert, about six in ten touched by a decode step)
# read 0.324 ms a layer-step at one stripe and 0.283 at two with 38 of
# 64 touched, level with all 64; Nemotron's 2.6 MiB experts 0.458 at
# one and 0.444 at three with a quarter of them empty, and one stripe
# is 8% the faster at 128 rows (PERF.md section 6, PR 47).
_EXPERT_WHOLE_BYTES = 3 * 1024 * 1024


def expert_vmem_bytes(rows: int, H: int, bo: int, x_itemsize: int) -> int:
    """What one program of the w8a16 expert-stripe kernel holds in VMEM
    at a bucket of ``rows`` rows and a stripe ``[H, bo]``, by the
    account above."""
    cp = rows + ((-rows) % 8)
    return (2 * H * bo + 3 * cp * H * x_itemsize + 2 * cp * bo * x_itemsize
            + cp * min(bo, 256) * 4 + _EXPERT_VMEM_SLACK_BYTES)


def expert_widths(O: int) -> list[int]:
    """The stripe widths the expert grid may take for ``O`` columns:
    every multiple of 128 that divides it, widest first."""
    return [bo for bo in range(O - O % 128, 0, -128) if O % bo == 0]


def expert_bo_fits(rows: int, H: int, O: int, bo: int,
                   x_itemsize: int) -> bool:
    """:func:`pick_expert_bo`'s rule for one width ``bo`` of ``O``
    columns: the stripe limit, no whole expert above
    ``_EXPERT_WHOLE_BYTES``, and the VMEM account."""
    return (H * bo <= _EXPERT_STRIPE_BYTES
            and (bo < O or H * O <= _EXPERT_WHOLE_BYTES)
            and expert_vmem_bytes(rows, H, bo, x_itemsize)
            <= _EXPERT_VMEM_LIMIT_BYTES)


def pick_expert_bo(rows: int, H: int, O: int,
                   x_itemsize: int) -> int | None:
    """Output-block width for the w8a16 expert-stripe kernel, or None ->
    models/quant.q_einsum keeps the XLA dequant path (there is no 2D
    fallback for the expert grid: uncovered shapes are prefill-class
    and XLA's batched einsum is the right tool there anyway).

    The widest multiple of 128 that divides ``O`` and fits
    (:func:`expert_bo_fits`: a stripe of 4 MiB at the most, an expert
    above 3 MiB in two stripes at the least, one VMEM account). A search
    of its own, sharing nothing with :func:`_pick_1d_bo`: the dense grid is ``O / bo`` programs deep and
    wants depth from narrow blocks (``_TILE_TABLE``'s caps), the expert
    grid has its depth from ``NE`` and wants few, wide stripes. So
    neither the four powers of two of ``_BLOCK_CANDIDATES`` nor
    ``_TILE_TABLE`` nor the dense budgets are read here. The int4 expert
    kernel stays on :func:`pick_int4_bo`, the dense search: no cell runs
    int4 experts and nothing here was measured for them."""
    return next((bo for bo in expert_widths(O)
                 if expert_bo_fits(rows, H, O, bo, x_itemsize)), None)


def pick_int4_bo(rows: int, H: int, O: int, ng: int,
                 x_itemsize: int) -> int | None:
    """Output-block width for the w4a16 1D whole-stripe kernel, or None
    -> models/quant.mm takes the dequant XLA fallback. The coverage
    gate is :func:`int4_stripe_seg` (even groups at G % 128 == 0, odd
    at G % 256 == 0 — the round-18 fix: the old even-only gate rejected
    odd expert group counts the segment walk now serves); the block
    width then comes from the shared budget/tile-table search.
    """
    if int4_stripe_seg(H, ng) is None:
        return None
    rp = rows + ((-rows) % 8)
    return _pick_1d_bo(rp, H, O, x_itemsize, stripe_rows=H // 2)


@functools.partial(jax.jit, static_argnames=("interpret",))
def quant_matmul(x: jax.Array, q: jax.Array, s: jax.Array,
                 *, interpret: bool = False) -> jax.Array:
    """``(x @ dequant(q, s))`` with int8-only HBM weight traffic.

    x: [rows, H] (rows padded to a multiple of 8 here if needed);
    q: [H, O] int8; s: [1, O] f32 per-output-channel scales (the
    models/quant.QTensor layout). Returns [rows, O] in x.dtype.
    Caller guarantees H and O are divisible by a block candidate
    (models/quant.mm falls back to the XLA path otherwise).
    """
    rows, H = x.shape
    O = q.shape[1]
    bh, bo = pick_block(H), pick_block(O)
    if bh is None or bo is None:
        raise ValueError(f"no block divides H={H} / O={O}; use the XLA path")
    pad = (-rows) % 8
    if pad:
        x = jnp.pad(x, ((0, pad), (0, 0)))
    rp = rows + pad

    # Prefer the 1D whole-contraction grid: shrink bo until the [H, bo]
    # int8 stripe fits the VMEM budget (keeping bo a divisor of O).
    bo_1d = _pick_1d_bo(rp, H, O, x.dtype.itemsize)
    if bo_1d is not None:
        out = pl.pallas_call(
            _qmm_kernel_1d,
            name="qmm_int8_1d",
            grid=(O // bo_1d,),
            in_specs=[
                pl.BlockSpec((rp, H), lambda i: (0, 0)),
                pl.BlockSpec((H, bo_1d), lambda i: (0, i)),
                pl.BlockSpec((1, bo_1d), lambda i: (0, i)),
            ],
            out_specs=pl.BlockSpec((rp, bo_1d), lambda i: (0, i)),
            out_shape=jax.ShapeDtypeStruct((rp, O), x.dtype),
            interpret=interpret,
        )(x, q, s)
        return out[:rows] if pad else out

    out = pl.pallas_call(
        _qmm_kernel,
        name="qmm_int8_2d",
        grid=(O // bo, H // bh),
        in_specs=[
            pl.BlockSpec((rp, bh), lambda i, j: (0, j)),
            pl.BlockSpec((bh, bo), lambda i, j: (j, i)),
            pl.BlockSpec((1, bo), lambda i, j: (0, i)),
        ],
        out_specs=pl.BlockSpec((rp, bo), lambda i, j: (0, i)),
        scratch_shapes=[pltpu.VMEM((rp, bo), jnp.float32)],
        out_shape=jax.ShapeDtypeStruct((rp, O), x.dtype),
        interpret=interpret,
    )(x, q, s)
    return out[:rows] if pad else out


@functools.partial(jax.jit, static_argnames=("interpret",))
def quant_matmul4(x: jax.Array, q: jax.Array, s: jax.Array,
                  *, interpret: bool = False) -> jax.Array:
    """``x @ dequant4(q, s)`` with int4-PACKED HBM weight traffic.

    x: [rows, H]; q: [H/2, O] int8 packed nibbles (models/quant.pack4's
    split-half layout); s: [ng, O] f32 group scales. Returns [rows, O]
    in x.dtype. Caller guarantees :func:`pick_int4_bo` accepts the shape
    (models/quant.mm falls back to the dequant XLA path otherwise).
    """
    rows, H = x.shape
    O = q.shape[1]
    ng = s.shape[0]
    bo = pick_int4_bo(rows, H, O, ng, x.dtype.itemsize)
    if bo is None:
        raise ValueError(
            f"w4a16 kernel does not cover H={H} O={O} ng={ng}; use the "
            "XLA fallback (models/quant.mm gates on pick_int4_bo)")
    pad = (-rows) % 8
    if pad:
        x = jnp.pad(x, ((0, pad), (0, 0)))
    rp = rows + pad
    out = pl.pallas_call(
        _qmm4_kernel_1d,
        name="qmm_int4_1d",
        grid=(O // bo,),
        in_specs=[
            pl.BlockSpec((rp, H), lambda i: (0, 0)),
            pl.BlockSpec((H // 2, bo), lambda i: (0, i)),
            pl.BlockSpec((ng, bo), lambda i: (0, i)),
        ],
        out_specs=pl.BlockSpec((rp, bo), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((rp, O), x.dtype),
        interpret=interpret,
    )(x, q, s)
    return out[:rows] if pad else out


@functools.partial(jax.jit, static_argnames=("interpret",))
def quant_matmul_stacked4(x: jax.Array, q: jax.Array, s: jax.Array,
                          layer: jax.Array, *,
                          interpret: bool = False) -> jax.Array:
    """``x @ dequant4(q[layer], s[layer])`` reading the stacked packed
    pool directly — the int4 twin of :func:`quant_matmul_stacked`.

    x: [rows, H]; q: [L, H/2, O] int8 packed nibbles; s: [L, ng, O] f32
    group scales (the stacked models/quant.QTensor4 layout); layer:
    scalar int32. Same coverage contract as :func:`quant_matmul4`.
    """
    rows, H = x.shape
    O = q.shape[2]
    ng = s.shape[1]
    bo = pick_int4_bo(rows, H, O, ng, x.dtype.itemsize)
    if bo is None:
        raise ValueError(
            f"w4a16 kernel does not cover H={H} O={O} ng={ng}; use the "
            "XLA fallback (models/quant.mm gates on pick_int4_bo)")
    pad = (-rows) % 8
    if pad:
        x = jnp.pad(x, ((0, pad), (0, 0)))
    rp = rows + pad
    ly = jnp.asarray(layer, jnp.int32).reshape(1)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(O // bo,),
        in_specs=[
            pl.BlockSpec((rp, H), lambda i, ly: (0, 0)),
            pl.BlockSpec((1, H // 2, bo), lambda i, ly: (ly[0], 0, i)),
            pl.BlockSpec((1, ng, bo), lambda i, ly: (ly[0], 0, i)),
        ],
        out_specs=pl.BlockSpec((rp, bo), lambda i, ly: (0, i)),
    )
    out = pl.pallas_call(
        _qmm4_kernel_1d_stacked,
        name="qmm_int4_stacked_1d",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((rp, O), x.dtype),
        interpret=interpret,
    )(ly, x, q, s)
    return out[:rows] if pad else out


def _expert_route(count: jax.Array | None, NE: int) -> jax.Array:
    """The expert kernels' second scalar-prefetch operand, int32 [2*NE]:
    ``count`` (filled slots of each expert's bucket; None = every expert
    is touched) and behind it, for every expert, the expert whose weight
    block its programs name: its own if it is touched, else the nearest
    touched expert before it, else the first touched one (the last expert
    when no bucket holds a token: one stripe is then fetched, unused)."""
    if count is None:
        count = jnp.ones((NE,), jnp.int32)
    count = count.astype(jnp.int32)
    ids = jnp.arange(NE, dtype=jnp.int32)
    before = jax.lax.cummax(jnp.where(count > 0, ids, -1))
    first = jnp.where(jnp.any(count > 0), jnp.argmax(count > 0),
                      NE - 1).astype(jnp.int32)
    return jnp.concatenate([count, jnp.where(before >= 0, before, first)])


def _expert_weight_map(NE: int, stripes: int):
    """Index map of an expert pool's weight (and scale) block on the grid
    ``(NE, stripes)``. A touched expert walks its own stripes. An empty
    one names the block the pipeline already holds, the last stripe of
    the touched expert before it, or the first stripe of the first
    touched expert when none precedes it (:func:`_expert_route`), so no
    DMA is issued for its programs."""
    def index(e, i, ly, route):
        src = route[NE + e]
        held = jnp.where(src < e, stripes - 1, 0)
        return ly[0], src, 0, jnp.where(route[e] > 0, i, held)
    return index


@functools.partial(jax.jit, static_argnames=("bo", "interpret"))
def quant_matmul_experts_stacked(x: jax.Array, q: jax.Array, s: jax.Array,
                                 layer: jax.Array,
                                 count: jax.Array | None = None,
                                 source: jax.Array | None = None, *,
                                 bo: int | None = None,
                                 interpret: bool = False) -> jax.Array:
    """Batched per-expert ``x[e] @ dequant(q[layer, e], s[layer, e])``
    reading the 4-D expert pool directly — the MoE twin of
    :func:`quant_matmul_stacked`, for mixtral's capacity-bucket expert
    matmuls (models/quant.q_einsum dispatches here for decode-shaped
    buckets so the expert trunk streams int8 instead of an XLA dequant
    of the whole [NE, H, O] stack).

    x: [NE, C, H] expert buckets; q: [L, NE, H, O] int8;
    s: [L, NE, 1, O] f32; layer: scalar int32. Returns [NE, C, O].
    Caller guarantees ``pick_expert_bo`` accepts the shape.

    ``count`` ([NE] int32, None = all touched): the filled slots of each
    bucket, which the dispatch knows on its way to them. An expert whose
    count is 0 has an all-zero bucket by the caller's word: its weights
    are not read (:func:`_expert_weight_map`) and its output is zeros.
    Decode at a part-full batch is where that pays: the step's time is
    the experts' bytes, and 2 live rows x top-2 reach at most 4 of 8.

    ``source`` ([NE] int32, None = bucket e reads expert e): the expert
    whose weights each bucket reads, for buckets that are TILES of a
    row-sorted dispatch, several of them one expert's
    (models/moe_tiles.routed_tiles): ``x`` is then [tiles, rows a tile,
    H] and the grid walks tiles. A tile whose count is 0 must name the
    expert of the last filled tile before it.

    ``bo`` (None = :func:`pick_expert_bo`'s): the stripe width, for the
    sweep that measures the rule and the tests that hold every width to
    the same bits; the caller answers for VMEM.
    """
    NE, C, H = x.shape
    O = q.shape[-1]
    if bo is None:
        bo = pick_expert_bo(C, H, O, x.dtype.itemsize)
    if bo is None:
        raise ValueError(
            f"expert w8a16 kernel does not cover C={C} H={H} O={O}; use "
            "the XLA path (models/quant.q_einsum gates on pick_expert_bo)")
    pad = (-C) % 8
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
    cp = C + pad
    ly = jnp.asarray(layer, jnp.int32).reshape(1)
    if source is None:
        route = _expert_route(count, NE)
    else:
        filled = jnp.ones((NE,), jnp.int32) if count is None else count
        route = jnp.concatenate([filled.astype(jnp.int32),
                                 source.astype(jnp.int32)])
    weight_map = _expert_weight_map(NE, O // bo)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(NE, O // bo),
        in_specs=[
            pl.BlockSpec((1, cp, H), lambda e, i, ly, route: (e, 0, 0)),
            pl.BlockSpec((1, 1, H, bo), weight_map),
            pl.BlockSpec((1, 1, 1, bo), weight_map),
        ],
        out_specs=pl.BlockSpec((1, cp, bo),
                               lambda e, i, ly, route: (e, 0, i)),
    )
    out = pl.pallas_call(
        _qmm_kernel_experts_stacked,
        name="qmm_int8_experts_stacked",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((NE, cp, O), x.dtype),
        interpret=interpret,
    )(ly, route, x, q, s)
    return out[:, :C] if pad else out


@functools.partial(jax.jit, static_argnames=("interpret",))
def quant_matmul_experts_stacked4(x: jax.Array, q: jax.Array, s: jax.Array,
                                  layer: jax.Array,
                                  count: jax.Array | None = None, *,
                                  interpret: bool = False) -> jax.Array:
    """int4 twin of :func:`quant_matmul_experts_stacked`: the packed
    [L, NE, H/2, O] expert pool streams at int4-packed bytes, unpacked
    per stripe by the shared segment walk (odd expert group counts
    included — mixtral-large's w_down groups at 256 into ng=45).

    x: [NE, C, H]; q: [L, NE, H/2, O] int8 packed nibbles;
    s: [L, NE, ng, O] f32 group scales; layer: scalar int32; ``count``
    as the int8 twin's (an empty expert is skipped). Returns [NE, C, O].
    Caller guarantees :func:`pick_int4_bo` accepts the per-expert shape.
    """
    NE, C, H = x.shape
    O = q.shape[-1]
    ng = s.shape[-2]
    bo = pick_int4_bo(C, H, O, ng, x.dtype.itemsize)
    if bo is None:
        raise ValueError(
            f"expert w4a16 kernel does not cover C={C} H={H} O={O} "
            f"ng={ng}; use the XLA fallback (models/quant.q_einsum gates "
            "on pick_int4_bo)")
    pad = (-C) % 8
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
    cp = C + pad
    ly = jnp.asarray(layer, jnp.int32).reshape(1)
    weight_map = _expert_weight_map(NE, O // bo)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(NE, O // bo),
        in_specs=[
            pl.BlockSpec((1, cp, H), lambda e, i, ly, route: (e, 0, 0)),
            pl.BlockSpec((1, 1, H // 2, bo), weight_map),
            pl.BlockSpec((1, 1, ng, bo), weight_map),
        ],
        out_specs=pl.BlockSpec((1, cp, bo),
                               lambda e, i, ly, route: (e, 0, i)),
    )
    out = pl.pallas_call(
        _qmm4_kernel_experts_stacked,
        name="qmm_int4_experts_stacked",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((NE, cp, O), x.dtype),
        interpret=interpret,
    )(ly, _expert_route(count, NE), x, q, s)
    return out[:, :C] if pad else out
