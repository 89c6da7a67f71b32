"""Weight-only int8 (w8a16) and int4 (w4a16) quantization for serving.

Decode is HBM-bandwidth-bound: every step streams the full weight set
through the MXU at batch sizes far too small to amortise it (SURVEY.md §6
north-star shapes). Storing matmul weights as int8 with per-output-channel
scales halves that traffic vs bf16 — and is the memory lever that fits
llama3.1-70B on a v5e-8 slice (BASELINE.json config 4; the reference
delegates this entirely to Ollama's quantised GGUF models, README.md:52).

TPU-first shape of the idea:
- **storage**: ``QTensor(q: int8[..., in, out], s: f32[..., 1, out])`` —
  symmetric per-out-channel scales over the contraction axis. A NamedTuple,
  so it is a pytree: it rides ``lax.scan`` over stacked layers, donation,
  and ``jax.sharding`` untouched (q inherits the weight's sharding spec;
  s is tiny and follows the out axis).
- **compute**: decode-shaped calls (few rows — the bandwidth-bound path)
  run a Pallas w8a16 kernel (ops/quant_mm.py) that DMAs int8 tiles into
  VMEM and converts there, so HBM sees int8 only. XLA does NOT do this on
  its own: ``x @ q.astype(bf16)`` materialises a bf16 weight copy in HBM
  first (measured slower than plain bf16 — see ops/quant_mm.py), which is
  also why prefill-shaped calls (thousands of rows, compute-bound,
  convert amortised) keep the plain XLA path. Activations stay bf16
  end-to-end; no activation quantisation, no calibration data needed.
- embeddings and norms stay bf16: the embed gather reads one row per
  token (bandwidth-irrelevant) and norms are numerically sensitive.

Accuracy: per-channel symmetric int8 keeps |w - dequant(w)| <= s/2
elementwise (tests/test_quant.py pins the bound and end-to-end logit
agreement).

int4 (w4a16, :class:`QTensor4`) halves the weight stream AGAIN vs int8
— the 8B decode trunk drops ~7.6 GB -> ~3.8 GB per step. Per-channel
scales lose too much at 4 bits, so scales go **group-wise** along the
contraction axis (AWQ/GPTQ-style, group 128 with a 64 fallback): one f32
scale per (group, out-channel). Two 4-bit values pack per int8 byte in a
split-half layout — byte row ``i`` of ``q[..., K/2, O]`` holds logical
row ``i`` in its low nibble and row ``i + K/2`` in its high nibble, each
stored offset-by-8 in [0, 15] — chosen so a contiguous run of byte rows
is exactly one lo-half group plus one hi-half group and the Pallas
kernel (ops/quant_mm.quant_matmul4) unpacks group-pairs in VMEM without
any cross-row shuffle. Symmetric clip to [-7, 7] (the -8 code is
unused), scale = group-abs-max / 7, so |w - dequant(w)| <= s_g/2 holds
per group exactly like int8's per-channel bound.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from ..utils.device import on_tpu


class QTensor(NamedTuple):
    """int8 weight + f32 per-output-channel scale (contraction axis kept
    as size-1 so ``q * s`` and post-matmul scaling both broadcast)."""

    q: jax.Array
    s: jax.Array

    @property
    def shape(self):
        return self.q.shape

    @property
    def ndim(self) -> int:
        return self.q.ndim


class QTensor4(NamedTuple):
    """Packed int4 weight + f32 group-wise scales.

    ``q``: int8 ``[..., K/2, O]`` — two offset-by-8 nibbles per byte in
    the split-half layout (module docstring). ``s``: f32 ``[..., ng, O]``
    with ``ng = K / group``. No static metadata field: both the logical
    contraction dim (``2 * q.shape[-2]``) and the group size derive from
    the array shapes, so the NamedTuple stays a plain two-leaf pytree
    (scan / donation / sharding safe, exactly like :class:`QTensor`).
    """

    q: jax.Array
    s: jax.Array

    @property
    def shape(self):
        """LOGICAL shape [..., K, O] (not the packed storage shape)."""
        return (*self.q.shape[:-2], 2 * self.q.shape[-2], self.q.shape[-1])

    @property
    def ndim(self) -> int:
        return self.q.ndim

    @property
    def group(self) -> int:
        return 2 * self.q.shape[-2] // self.s.shape[-2]


class LayerSlice(NamedTuple):
    """Deferred per-layer view of a layer-stacked weight ``w[layer]``.

    Why it exists: a decode scan that slices stacked weights (scan xs or
    an explicit dynamic-slice) and feeds them to the Pallas w8a16 kernel
    forces XLA to MATERIALISE the slice — custom-call operands cannot
    alias a slice view — which re-reads and re-writes the entire weight
    set every step (measured: ~1.9 ms of a 3.8 ms bench-1b step).
    Wrapping (stacked weight, layer index) lets :func:`mm` pass the
    scan-invariant stacked array to a layer-indexed kernel
    (ops/quant_mm.quant_matmul_stacked) that DMAs tiles directly; the
    XLA fallback slices lazily, exactly like scan xs would have.

    ``w``: QTensor with q [L, in, out] (plain stacked bf16 arrays are
    sliced eagerly by llama._layer_view instead — XLA fuses those slices
    into their consumers for free); ``layer``: scalar int32 (a scan
    tracer in practice).
    """

    w: object
    layer: jax.Array


def quantize(w: jax.Array, axis: int = -2) -> QTensor:
    """Symmetric int8 quantization with per-channel scales over ``axis``
    (the matmul contraction axis — every channel that feeds one output
    unit shares a scale)."""
    wf = w.astype(jnp.float32)
    amax = jnp.max(jnp.abs(wf), axis=axis, keepdims=True)
    s = jnp.where(amax > 0, amax / 127.0, 1.0)
    q = jnp.clip(jnp.round(wf / s), -127, 127).astype(jnp.int8)
    return QTensor(q=q, s=s)


def dequantize(w: QTensor, dtype=jnp.bfloat16) -> jax.Array:
    return (w.q.astype(jnp.float32) * w.s).astype(dtype)


def pack4(v: jax.Array) -> jax.Array:
    """Pack int values in [-8, 7] (shape ``[..., K, O]``, K even) into
    the split-half int8 nibble layout ``[..., K/2, O]``: byte row ``i``
    = logical row ``i`` (low nibble) | logical row ``i + K/2`` (high),
    each offset by +8 into [0, 15]. The int8 reinterpretation of bytes
    >= 128 wraps explicitly (XLA's out-of-range int8 cast is
    implementation-defined)."""
    K = v.shape[-2]
    if K % 2:
        raise ValueError(f"pack4 needs an even contraction dim, got {K}")
    vi = v.astype(jnp.int32)
    lo = jax.lax.slice_in_dim(vi, 0, K // 2, axis=-2) + 8
    hi = jax.lax.slice_in_dim(vi, K // 2, K, axis=-2) + 8
    b = lo | (hi << 4)                               # [0, 255]
    return jnp.where(b >= 128, b - 256, b).astype(jnp.int8)


def unpack4(p: jax.Array) -> jax.Array:
    """Invert :func:`pack4`: int8 ``[..., K/2, O]`` -> int32 values in
    [-8, 7] at the logical ``[..., K, O]``. Nibble extraction runs in
    int32 where ``& 0xF`` / arithmetic ``>> 4`` are sign-robust for the
    negative reinterpreted bytes."""
    pi = p.astype(jnp.int32)
    lo = (pi & 0xF) - 8
    hi = ((pi >> 4) & 0xF) - 8
    return jnp.concatenate([lo, hi], axis=-2)


def quantize4(w: jax.Array, group: int | None = None) -> QTensor4:
    """Symmetric int4 quantization with group-wise scales over the -2
    (contraction) axis: each run of ``group`` input channels feeding one
    output unit shares an f32 scale = group-abs-max / 7 (clip to
    [-7, 7]; the -8 code stays unused so the bound |w - deq| <= s_g/2
    holds without clipping loss). ``group`` defaults to 128 (the Pallas
    kernel's lane-aligned size) with a 64 fallback for small dims."""
    wf = w.astype(jnp.float32)
    K = wf.shape[-2]
    if group is None:
        group = 128 if K % 128 == 0 else 64
    if K % group or K % 2:
        raise ValueError(f"group {group} must divide even K={K}")
    ng = K // group
    g = wf.reshape(*wf.shape[:-2], ng, group, wf.shape[-1])
    amax = jnp.max(jnp.abs(g), axis=-2, keepdims=True)
    s = jnp.where(amax > 0, amax / 7.0, 1.0)         # [..., ng, 1, O]
    qv = jnp.clip(jnp.round(g / s), -7, 7).astype(jnp.int32)
    qv = qv.reshape(*wf.shape[:-2], K, wf.shape[-1])
    return QTensor4(q=pack4(qv), s=jnp.squeeze(s, -2))


def dequantize4(w: QTensor4, dtype=jnp.bfloat16) -> jax.Array:
    v = unpack4(w.q).astype(jnp.float32)             # [..., K, O]
    ng = w.s.shape[-2]
    K = v.shape[-2]
    g = v.reshape(*v.shape[:-2], ng, K // ng, v.shape[-1])
    out = g * w.s[..., :, None, :]
    return out.reshape(v.shape).astype(dtype)


# Row threshold for the Pallas w8a16 path: decode/verify ticks sit far
# below it; prefill chunks far above (where XLA's matmul is the right
# tool and the convert cost is amortised).
_KERNEL_MAX_ROWS = 512
_FORCE_XLA = False


def set_mm_impl(impl: str) -> None:
    """``xla`` forces the inline-dequant path everywhere; ``auto`` (the
    default) lets decode-shaped calls use the Pallas kernel. The serve
    engine forces ``xla`` under tensor parallelism: pallas_call cannot
    consume mesh-sharded operands without a shard_map wrapper (the
    kernel's TP integration is future work — the XLA path shards fine)."""
    global _FORCE_XLA
    if impl not in ("auto", "xla"):
        raise ValueError(f"impl must be auto|xla, got {impl!r}")
    _FORCE_XLA = impl == "xla"


def kernel_wanted() -> bool:
    """Whether decode-shaped quantized matmuls dispatch the Pallas
    kernels: on the TPU (utils/device.py, the one platform probe) unless
    a mesh forced the XLA path (:func:`set_mm_impl`)."""
    return on_tpu() and not _FORCE_XLA


def _deq_once(q: jax.Array, s: jax.Array, dtype) -> jax.Array:
    """Materialised one-shot dequant for prefill-shaped dots.

    ``x @ q.astype(bf16)`` lets XLA fuse the convert INTO the dot, which
    re-reads (and re-converts) the whole int8 weight once per M-tile of
    the output — measured 23.5 ms for ONE bench-1b wgu prefill matmul
    whose FLOP bound is ~1.3 ms (B=2 S=2048: 32 M-tiles x 23 MB weight
    re-read per layer). The optimization barrier forces the dequant to
    materialise once, and the standard dot emitter then streams the bf16
    weight at matmul speed."""
    return jax.lax.optimization_barrier(dequantize(QTensor(q, s), dtype))


def _deq4_once(w: QTensor4, dtype) -> jax.Array:
    """Int4 twin of :func:`_deq_once`: materialise the group-dequantized
    bf16 weight exactly once behind an optimization barrier so
    prefill-shaped dots stream it at matmul speed instead of re-running
    the unpack+scale per M-tile."""
    return jax.lax.optimization_barrier(dequantize4(w, dtype))


def mm(x: jax.Array, w) -> jax.Array:
    """``x @ w`` for a plain array or a :class:`QTensor`.

    Quantized weights: decode-shaped calls (<= _KERNEL_MAX_ROWS rows, 2D
    weight, kernel-friendly dims, TPU backend) go through the Pallas
    w8a16 kernel so HBM reads int8 only; prefill-shaped calls
    dequantize ONCE behind an optimization barrier (see _deq_once) and
    run a plain bf16 dot. Both scale per output channel."""
    if isinstance(w, LayerSlice):
        lead, H = x.shape[:-1], x.shape[-1]
        rows = 1
        for d in lead:
            rows *= d
        inner, layer = w.w, w.layer
        if isinstance(inner, QTensor):
            if (inner.q.ndim == 3 and rows <= _KERNEL_MAX_ROWS
                    and kernel_wanted()):
                from ..ops.quant_mm import pick_block, quant_matmul_stacked
                if pick_block(H) and pick_block(inner.q.shape[2]):
                    y = quant_matmul_stacked(x.reshape(rows, H), inner.q,
                                             inner.s, layer)
                    return y.reshape(*lead, inner.q.shape[2])
            inner = QTensor(
                q=jax.lax.dynamic_index_in_dim(inner.q, layer, 0, False),
                s=jax.lax.dynamic_index_in_dim(inner.s, layer, 0, False))
            return mm(x, inner)
        if isinstance(inner, QTensor4):
            if (inner.q.ndim == 3 and rows <= _KERNEL_MAX_ROWS
                    and kernel_wanted()):
                from ..ops.quant_mm import (pick_int4_bo,
                                            quant_matmul_stacked4)
                if pick_int4_bo(rows, H, inner.q.shape[-1],
                                inner.s.shape[-2], x.dtype.itemsize):
                    y = quant_matmul_stacked4(x.reshape(rows, H), inner.q,
                                              inner.s, layer)
                    return y.reshape(*lead, inner.q.shape[-1])
            inner = QTensor4(
                q=jax.lax.dynamic_index_in_dim(inner.q, layer, 0, False),
                s=jax.lax.dynamic_index_in_dim(inner.s, layer, 0, False))
            return mm(x, inner)
        raise TypeError("LayerSlice wraps stacked QTensors only; slice "
                        "plain stacked arrays eagerly (llama._layer_view)")
    if isinstance(w, QTensor4):
        lead, H = x.shape[:-1], x.shape[-1]
        rows = 1
        for d in lead:
            rows *= d
        O = w.q.shape[-1]
        if w.q.ndim == 2 and rows <= _KERNEL_MAX_ROWS and kernel_wanted():
            from ..ops.quant_mm import pick_int4_bo, quant_matmul4
            if pick_int4_bo(rows, H, O, w.s.shape[-2], x.dtype.itemsize):
                y = quant_matmul4(x.reshape(rows, H), w.q, w.s)
                return y.reshape(*lead, O)
        if rows > _KERNEL_MAX_ROWS and w.q.ndim == 2:
            return x @ _deq4_once(w, x.dtype)
        # Group-wise scales vary along the contraction axis, so there is
        # no scale-after-dot inline form like int8's; small uncovered
        # shapes dequantize inline (one M-tile, XLA fuses it).
        return x @ dequantize4(w, x.dtype)
    if isinstance(w, QTensor):
        lead, H = x.shape[:-1], x.shape[-1]
        rows = 1
        for d in lead:
            rows *= d
        if w.q.ndim == 2 and rows <= _KERNEL_MAX_ROWS and kernel_wanted():
            from ..ops.quant_mm import pick_block, quant_matmul
            if pick_block(H) and pick_block(w.q.shape[1]):
                y = quant_matmul(x.reshape(rows, H), w.q, w.s)
                return y.reshape(*lead, w.q.shape[1])
        if rows > _KERNEL_MAX_ROWS and w.q.ndim == 2:
            return x @ _deq_once(w.q, w.s, x.dtype)
        return (x @ w.q.astype(x.dtype)) * jnp.squeeze(w.s, -2).astype(x.dtype)
    return x @ w


# Expert einsum specs that are exactly a batched per-expert matmul
# x[e] @ w[e] (contraction at w's -2, out axis last) — the two forms
# models/mixtral.moe_mlp emits and the only ones the expert-stripe
# Pallas kernels serve.
_EXPERT_MM_SPECS = frozenset({"ech,ehf->ecf", "ecf,efh->ech"})


def q_einsum(spec: str, x: jax.Array, w, count=None,
             source=None) -> jax.Array:
    """``einsum(spec, x, w)`` for plain or quantized ``w``. The spec's
    contraction over ``w`` must be its -2 axis (the quantize() axis) and
    the output must end with ``w``'s out axis — true for every expert
    einsum in models/mixtral.py (``ech,ehf->ecf`` / ``ecf,efh->ech``).

    A :class:`LayerSlice` wrapping a layer-stacked 4-D expert pool
    (llama._layer_view defers those exactly like the dense projections)
    dispatches decode-shaped batched-matmul specs to the expert-stripe
    Pallas kernels (ops/quant_mm.quant_matmul_experts_stacked[4]) so the
    expert trunk streams quantized bytes from the scan-invariant pool —
    the eager fallback slices the layer out and recurses, which is
    bit-identical to what _layer_view did before the kernels existed.

    ``count`` ([NE] int32, the filled slots of each expert's bucket
    ``x[e]``; None = unknown): handed to the expert-stripe kernels, which
    read no weights for an expert whose count is 0. Every XLA path
    ignores it: an empty bucket's zeros give zeros there at full cost.

    ``source`` ([x.shape[0]] int32; None = bucket e reads expert e): the
    expert each bucket of ``x`` reads, where the buckets are tiles of a
    row-sorted dispatch (ops/quant_mm.quant_matmul_experts_stacked says
    how the kernel takes it; the XLA paths gather the tiles' weights)."""
    if isinstance(w, LayerSlice):
        inner, layer = w.w, w.layer
        if not isinstance(inner, (QTensor, QTensor4)):
            raise TypeError("LayerSlice wraps stacked QTensors only")
        if (inner.q.ndim == 4 and x.ndim == 3 and spec in _EXPERT_MM_SPECS
                and x.shape[1] <= _KERNEL_MAX_ROWS and kernel_wanted()):
            C, H = x.shape[1], x.shape[2]
            O = inner.q.shape[-1]
            if isinstance(inner, QTensor):
                from ..ops.quant_mm import (pick_expert_bo,
                                            quant_matmul_experts_stacked)
                if pick_expert_bo(C, H, O, x.dtype.itemsize):
                    return quant_matmul_experts_stacked(
                        x, inner.q, inner.s, layer, count, source=source)
            elif source is None:    # the int4 kernel walks experts only
                from ..ops.quant_mm import (pick_int4_bo,
                                            quant_matmul_experts_stacked4)
                if pick_int4_bo(C, H, O, inner.s.shape[-2],
                                x.dtype.itemsize):
                    return quant_matmul_experts_stacked4(x, inner.q,
                                                         inner.s, layer,
                                                         count)
        inner = type(inner)(
            q=jax.lax.dynamic_index_in_dim(inner.q, layer, 0, False),
            s=jax.lax.dynamic_index_in_dim(inner.s, layer, 0, False))
        return q_einsum(spec, x, inner, source=source)
    if source is not None:
        w = jax.tree.map(lambda a: a[source], w)
    if isinstance(w, QTensor):
        y = jnp.einsum(spec, x, w.q.astype(x.dtype))
        return y * w.s.astype(x.dtype)       # s: [..., 1, out] broadcasts
    if isinstance(w, QTensor4):
        # Group scales vary along the contracted axis: no post-einsum
        # scale fold exists, so the expert einsums dequantize first
        # (compute-bound expert batches — the convert amortises).
        return jnp.einsum(spec, x, _deq4_once(w, x.dtype))
    return jnp.einsum(spec, x, w)


# Matmul weight leaves (llama + mixtral families; models/llama.py and
# models/mixtral.py init_params). All store the contraction at axis -2.
_QUANT_LEAVES = frozenset({
    "wq", "wk", "wv", "wo",            # attention projections
    "wqkv", "wgu", "wgu_e",            # fused forms (llama.fuse_params)
    "w_gate", "w_up", "w_down",        # SwiGLU / expert FFNs
    "lm_head",                         # output projection
    # latent attention and the shared expert (models/pangu.py)
    "wqkva", "wqb", "wkvb", "wgu_s", "w_down_s",
    # the hybrid family (models/nemotron_h.py): Mamba's projections, the
    # latent projections, the ungated shared expert and experts
    "w_in", "w_out", "w_fc1", "w_fc2", "w_up_s", "w_up_e",
})


def _int4_group(K: int, expert: bool) -> int | None:
    """Group size for an int4 leaf with contraction ``K``, or None ->
    the leaf keeps int8. Dense leaves group at 128 (the lane-aligned
    kernel size) with a 64 fallback, as ever. Expert-stacked leaves
    (``expert=True``, ndim >= 3) with a large 256-divisible contraction
    group at 256 instead: at real expert scale the f32 scale rows are no
    longer negligible (mixtral-large w_down: ng=90 at group 128 -> 35 MB
    of scales halved to ng=45), and the segment-walk kernels serve the
    odd count that results (ops/quant_mm.int4_stripe_seg — G=256 is
    exactly the odd-count alignment bar)."""
    if K % 2:
        return None
    if expert and K >= 8192 and K % 256 == 0:
        return 256
    if K % 128 == 0:
        return 128
    if K % 64 == 0:
        return 64
    return None


def _quantize_leaf(v: jax.Array, mode: str, expert: bool | None = None):
    """One matmul weight leaf at ``mode``. int4 needs a group (see
    :func:`_int4_group`) dividing the even contraction dim; leaves whose
    dims cannot group (odd / sub-64 contraction — tiny test heads) fall
    back to per-channel int8 so a mixed tree still serves. ``expert``
    defaults to ``v.ndim >= 3`` — right for the PER-LAYER leaves the
    streaming init/load loops pass (dense 2-D, expert stacks 3-D);
    :func:`quantize_params` walks LAYER-stacked trees and passes it
    explicitly (dense 3-D there)."""
    if mode == "int4":
        if expert is None:
            expert = v.ndim >= 3
        group = _int4_group(v.shape[-2], expert)
        if group is not None:
            return quantize4(v, group=group)
    return quantize(v)


def stream_bufs(L: int, shape: tuple, mode: str):
    """Zero stacked quantized buffers ``[L, *shape]`` matching
    :func:`_quantize_leaf`'s precision choice for this shape — the
    donated per-layer streaming loops (llama/mixtral
    ``init_params_quantized``, weights.load_checkpoint_quantized) splice
    layer slices into these so the bf16 tree never materialises."""
    K, O = shape[-2], shape[-1]
    group = _int4_group(K, len(shape) >= 3) if mode == "int4" else None
    if group is not None:
        return QTensor4(
            q=jnp.zeros((L, *shape[:-2], K // 2, O), jnp.int8),
            s=jnp.zeros((L, *shape[:-2], K // group, O), jnp.float32))
    return QTensor(q=jnp.zeros((L, *shape), jnp.int8),
                   s=jnp.zeros((L, *shape[:-2], 1, O), jnp.float32))


def quantize_params(params: dict, mesh=None, mode: str = "int8") -> dict:
    """Quantize every matmul weight leaf of a model param tree in place of
    its bf16 array (embed/norms/router stay as-is). ``mode``: ``int8``
    (per-output-channel scales) or ``int4`` (group-wise — see
    :func:`quantize4`; ungroupable leaves keep int8). Works on sharded
    params too — quantize *after* ``shard_params`` so q/s derive their
    shardings from the weight's, and pass that ``mesh`` here: the Pallas
    decode-matmul kernels cannot consume mesh-sharded operands (no
    shard_map wrapper yet), so a mesh forces the XLA path process-wide
    rather than leaving the guard to each construction site."""
    if mode not in ("int8", "int4"):
        raise ValueError(f"mode must be int8|int4, got {mode!r}")
    if mesh is not None:
        set_mm_impl("xla")

    def walk(d: dict) -> dict:
        out = {}
        for k, v in d.items():
            if isinstance(v, dict):
                out[k] = walk(v)
            elif k in _QUANT_LEAVES:
                # Leaves here carry the leading layer axis: dense
                # projections are 3-D, expert stacks 4-D.
                out[k] = _quantize_leaf(v, mode, expert=v.ndim >= 4)
            else:
                out[k] = v
        return out
    return walk(params)


def _is_qleaf(x) -> bool:
    return isinstance(x, (QTensor, QTensor4))


def is_quantized(params: dict) -> bool:
    return any(_is_qleaf(x)
               for x in jax.tree.leaves(params, is_leaf=_is_qleaf))


def quant_mode(params: dict) -> str:
    """``"int4"`` if any leaf is a QTensor4, ``"int8"`` if any is a
    QTensor, else ``""`` (bf16) — the label serving stamps on logs and
    the ``model_weight_bytes{quant=}`` metric."""
    leaves = jax.tree.leaves(params, is_leaf=_is_qleaf)
    if any(isinstance(x, QTensor4) for x in leaves):
        return "int4"
    if any(isinstance(x, QTensor) for x in leaves):
        return "int8"
    return ""


def param_bytes(params: dict) -> int:
    """Actual stored bytes of the tree (int4 packed bytes count as
    stored, i.e. half a byte per logical weight) — the weight-stream
    size a decode step reads from HBM."""
    return sum(x.nbytes for x in jax.tree.leaves(params))
