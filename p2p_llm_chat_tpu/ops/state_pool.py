"""Recurrent state beside the page pool: what a Mamba layer keeps for
each row, whatever the row's length, the ring a window-attention layer
keeps of its last ``sliding_window`` positions, and the programs over
them.

A row of a layer is a float32 state ``ssm`` [heads, head_dim, state_size]
and the last ``conv_kernel - 1`` inputs of the layer's causal depthwise
convolution, ``conv`` [conv_kernel - 1, conv_dim], in the activations'
dtype:

    ssm:  [L_m, rows, heads, head_dim, state_size]   float32
    conv: [L_m, rows, conv_kernel - 1, conv_dim]

A gated short convolution (``c`` of a hybrid pattern, LFM2) keeps the
convolution's window and nothing else: ``conv`` [L_c, rows, conv_kernel -
1, hidden_size] holds the last values of its gated input ``B * x``, and
``ssm`` has no layer ([0, rows]). :func:`conv_update` is its decode step;
its convolution has no bias and no activation behind it.

The same :class:`StatePool` serves in two places. In the scheduler's
pool (``PagedKVCache.state``) it is indexed by SLOT and has one row
more than there are slots: the last is a garbage row, as page 0 is the
page pool's garbage page; an admission's dummy entries (row sentinel
``num_slots``) write there. In a prefill's small carry
(``KVCache.state``) it has a row an entry and no garbage row: the state
a chunk ladder hands from chunk to chunk, and what a prefix entry
snapshots.

Every operation on the scheduler's pool is in place on a donated buffer
(tests/test_state_pool.py reads the optimised HLO for a copy):

- :func:`decode_update`: one layer's step for the first ``B`` rows; the
  rows not named live keep their state bit for bit. On the TPU Mamba-2's
  is :func:`ssm_decode_kernel`, which reads and writes a live row's
  state once and touches no other; everywhere else, and for Mamba-1, one
  XLA program over the ``B`` rows with a select.
- :func:`write_rows`: a prefill's or a chunk ladder's final state into
  named rows. It overwrites the whole row, so a slot that is freed and
  reused inherits nothing.
- :func:`snapshot` / :func:`from_snapshot`: a prefix entry's state out
  of a one-row carry, and into every row of a fresh one.

**Mamba-1** (``ModelConfig.mamba1_inner`` channels ``d``, ``N`` state
numbers a channel) keeps ``ssm`` [L_m, rows, N, d]: the channels are the
minor dimension, because a minor dimension of 16 would be padded to a
128-lane tile and the pool would move eight times its bytes. Its decay
is one number a channel AND state number a token, so nothing of
:func:`ssd_scan` applies; :func:`ssm1_scan` carries the state through
the positions one at a time and :func:`ssm1_step` is one of them:

    S_t = exp(dt_t (outer) A) * S_{t-1} + (dt_t x_t) (outer) B_t
    y_t = S_t C_t

**Window rings** (``ModelConfig.window_layers`` > 0). A window layer
reads its query's own position and the ``W - 1`` before it, so a row
keeps ``W`` positions of K and V a layer, whatever its length: position
``p`` lives in slot ``p mod W`` (no positional encoding is applied to
the keys, and a softmax does not care in which order it sums):

    win_k, win_v: [L_w, rows, G, W, C]
    win_ks, win_vs: [L_w, rows, G, W]   float32, int8 pools only

with ``G x C`` the cache's geometry (``ModelConfig.cache_kv_heads`` rows
of ``cache_k_dim`` numbers a position: for differential attention a PAIR
of KV heads side by side, 128 numbers at a head of 64). The head comes
before the position so that a row's ``[W, C]`` block of one head is what
a dot contracts, as it lies: the positions on sublanes, ``C`` on lanes
(with the position before the head the compiler transposed the whole
ring every step). In the scheduler's pool they are int8 with a scale a
position a head when the page pool is (ops/paged_kv.quant_kv); in a
prefill's carry they are the activations' dtype and :func:`write_rows`
quantises them.
:func:`ring_decode_write` lands a step's K and V in each live row's
slot (the others write the garbage row); :func:`ring_after_chunk` is the
ring a chunk leaves behind; the masks say which slots a query may read.

The Mamba-2 recurrence (``A`` < 0 a head, ``dt`` > 0 a head and token, ``B`` and
``C`` shared by ``heads // groups`` heads):

    S_t = exp(dt_t A) S_{t-1} + dt_t x_t (outer) B_t      y_t = S_t C_t

:func:`ssm_step` is one step of it; :func:`ssd_scan` the same sum over a
block of positions in the chunked (SSD) form: inside a block of
``chunk`` positions the pairwise decays form a [chunk, chunk] lower
triangle and everything is matmuls; between blocks the state is
carried by a short sequential scan. A position whose ``dt`` is 0
neither decays nor feeds the state: that is how padding is masked.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..models.configs import ModelConfig
from ..utils.device import on_tpu
from .quant_mm import _expert_route


class StatePool(NamedTuple):
    ssm: jax.Array
    conv: jax.Array
    win_k: Optional[jax.Array] = None
    win_v: Optional[jax.Array] = None
    win_ks: Optional[jax.Array] = None
    win_vs: Optional[jax.Array] = None

    @classmethod
    def create(cls, config: ModelConfig, rows: int, dtype,
               quantized: bool = False) -> "StatePool":
        pool = cls(
            ssm=jnp.zeros((config.ssm_layers, rows) + config.ssm_state_shape,
                          jnp.float32),
            conv=jnp.zeros((config.conv_layers, rows, config.conv_kernel - 1,
                            config.conv_dim), dtype))
        Lw, W = config.window_layers, config.sliding_window
        if not Lw:
            return pool
        shape = (Lw, rows, config.cache_kv_heads, W, config.cache_k_dim)
        if not quantized:
            return pool._replace(win_k=jnp.zeros(shape, dtype),
                                 win_v=jnp.zeros(shape, dtype))
        scales = shape[:-1]
        return pool._replace(
            win_k=jnp.zeros(shape, jnp.int8), win_v=jnp.zeros(shape, jnp.int8),
            win_ks=jnp.zeros(scales, jnp.float32),
            win_vs=jnp.zeros(scales, jnp.float32))

    @property
    def rows(self) -> int:
        return self.ssm.shape[1]

    @property
    def ring_nbytes(self) -> int:
        return sum(int(a.nbytes) for a in self[2:] if a is not None)

    @property
    def nbytes(self) -> int:
        return int(self.ssm.nbytes) + int(self.conv.nbytes) \
            + self.ring_nbytes

    @property
    def row_bytes(self) -> int:
        """Bytes of ONE row's recurrent state over all layers (state and
        convolution window): what a decode step reads and writes."""
        return (int(self.ssm.nbytes) + int(self.conv.nbytes)) // self.rows

    @property
    def ring_position_bytes(self) -> int:
        """Bytes of ONE position of one row in ONE window layer's ring (K
        and V, with their scales)."""
        if self.win_k is None:
            return 0
        return self.ring_nbytes // (self.win_k.shape[0] * self.rows
                                    * self.win_k.shape[3])


def _by_head(g: jax.Array, heads: int) -> jax.Array:
    """[..., groups, N] -> [..., heads, N]: head h reads group
    ``h // (heads // groups)``."""
    return jnp.repeat(g, heads // g.shape[-2], axis=-2)


def ssm_step(S: jax.Array, x: jax.Array, dt: jax.Array, A: jax.Array,
             Bm: jax.Array, Cm: jax.Array) -> tuple:
    """One position. S [B,H,P,N] float32; x [B,H,P]; dt [B,H] float32;
    A [H]; Bm, Cm [B,G,N]. Returns (y [B,H,P] float32, S_new)."""
    H = S.shape[1]
    f32 = jnp.float32
    decay = jnp.exp(dt * A)                                   # [B,H]
    Bh = _by_head(Bm.astype(f32), H)                          # [B,H,N]
    Ch = _by_head(Cm.astype(f32), H)
    dtx = dt[..., None] * x.astype(f32)                       # [B,H,P]
    S_new = S * decay[..., None, None] + dtx[..., None] * Bh[:, :, None, :]
    y = jnp.sum(S_new * Ch[:, :, None, :], axis=-1)
    return y, S_new


def conv_step(window: jax.Array, xbc: jax.Array, w: jax.Array,
              b: Optional[jax.Array]) -> tuple:
    """One position of the causal depthwise convolution. window
    [B,K-1,C] (oldest first); xbc [B,C]; w [K,C] (w[K-1] weighs the
    current input); b [C], or None for a convolution without a bias.
    Returns (out [B,C] float32, new window)."""
    f32 = jnp.float32
    full = jnp.concatenate([window, xbc[:, None].astype(window.dtype)],
                           axis=1)                            # [B,K,C]
    out = jnp.sum(full.astype(f32) * w.astype(f32)[None], axis=1)
    if b is not None:
        out = out + b.astype(f32)
    return out, full[:, 1:]


def conv_scan(xbc: jax.Array, window: jax.Array, lengths: jax.Array,
              w: jax.Array, b: Optional[jax.Array]) -> tuple:
    """The convolution over S positions behind ``window``. xbc [B,S,C];
    window [B,K-1,C]; lengths [B]: a row's first ``lengths`` positions
    are real; b as :func:`conv_step`'s. Returns (out [B,S,C] float32, the
    window after each row's last REAL position: the carried one where it
    has none)."""
    f32 = jnp.float32
    K = w.shape[0]
    S = xbc.shape[1]
    ext = jnp.concatenate([window, xbc.astype(window.dtype)], axis=1)
    out = 0.0 if b is None else b.astype(f32)[None, None]
    for j in range(K):
        out = out + ext[:, j: j + S].astype(f32) * w[j].astype(f32)
    at = lengths.astype(jnp.int32)[:, None] + jnp.arange(K - 1)[None, :]
    return out, jnp.take_along_axis(ext, at[:, :, None], axis=1)


def ssd_scan(x: jax.Array, dt: jax.Array, A: jax.Array, Bm: jax.Array,
             Cm: jax.Array, S_in: jax.Array, chunk: int) -> tuple:
    """The recurrence over S positions in the chunked form. x [B,S,H,P];
    dt [B,S,H] float32, 0 at a position that is not real; A [H]; Bm, Cm
    [B,S,G,N]; S_in [B,H,P,N] float32. Returns (y [B,S,H,P] float32,
    S_out). Equal, as a sum, to S calls of :func:`ssm_step`
    (tests/test_state_pool.py)."""
    f32 = jnp.float32
    Bsz, S, H, P = x.shape
    Q = min(chunk, S)
    pad = -S % Q
    if pad:
        # dt = 0 behind the end: the state does not move there.
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        Bm = jnp.pad(Bm, ((0, 0), (0, pad), (0, 0), (0, 0)))
        Cm = jnp.pad(Cm, ((0, 0), (0, pad), (0, 0), (0, 0)))
    nc = (S + pad) // Q
    G = Bm.shape[2]
    rep = H // G
    x = x.astype(f32).reshape(Bsz, nc, Q, G, rep, P)
    dt = dt.reshape(Bsz, nc, Q, G, rep)
    Bm = Bm.astype(f32).reshape(Bsz, nc, Q, G, -1)
    Cm = Cm.astype(f32).reshape(Bsz, nc, Q, G, -1)
    a = dt * A.reshape(G, rep)                                # <= 0
    cum = jnp.cumsum(a, axis=2)                               # [B,nc,Q,G,r]
    dtx = dt[..., None] * x                                   # [B,nc,Q,G,r,P]
    # Inside a block: y_t += sum_{s<=t} exp(cum_t - cum_s) (C_t.B_s) dtx_s
    cb = jnp.einsum("bcqgn,bcsgn->bcqsg", Cm, Bm)
    tri = jnp.tril(jnp.ones((Q, Q), bool))[None, None, :, :, None, None]
    seg = cum[:, :, :, None] - cum[:, :, None, :]             # [B,nc,Q,Q,G,r]
    L = jnp.where(tri, jnp.exp(jnp.where(tri, seg, 0.0)), 0.0)
    y = jnp.einsum("bcqsgr,bcsgrp->bcqgrp", L * cb[..., None], dtx)
    # What each block adds to the state at its end.
    to_end = jnp.exp(cum[:, :, -1:] - cum)                    # [B,nc,Q,G,r]
    add = jnp.einsum("bcsgrp,bcsgn->bcgrpn", dtx * to_end[..., None], Bm)
    block_decay = jnp.exp(cum[:, :, -1])                      # [B,nc,G,r]

    def carry(S_prev, blk):
        d, inc = blk
        return S_prev * d[..., None, None] + inc, S_prev

    S0 = S_in.reshape(Bsz, G, rep, P, -1)
    S_out, S_before = jax.lax.scan(
        carry, S0, (jnp.moveaxis(block_decay, 1, 0),
                    jnp.moveaxis(add, 1, 0)))
    S_before = jnp.moveaxis(S_before, 0, 1)                   # [B,nc,G,r,P,N]
    # The state a block starts from, seen from inside it.
    y = y + jnp.einsum("bcqgn,bcgrpn->bcqgrp", Cm, S_before) \
        * jnp.exp(cum)[..., None]
    y = y.reshape(Bsz, nc * Q, H, P)[:, :S]
    return y, S_out.reshape(S_in.shape)


def ssm1_step(S: jax.Array, x: jax.Array, dt: jax.Array, A: jax.Array,
              Bm: jax.Array, Cm: jax.Array) -> tuple:
    """One position of Mamba-1. S [B,N,d] float32; x [B,d]; dt [B,d]
    float32 (0: the state does not move); A [N,d] (< 0); Bm, Cm [B,N].
    Returns (y [B,d] float32, S_new)."""
    f32 = jnp.float32
    decay = jnp.exp(dt[:, None, :] * A[None])                 # [B,N,d]
    dtx = dt * x.astype(f32)                                  # [B,d]
    S_new = decay * S + Bm.astype(f32)[:, :, None] * dtx[:, None, :]
    y = jnp.sum(S_new * Cm.astype(f32)[:, :, None], axis=1)
    return y, S_new


def ssm1_scan(x: jax.Array, dt: jax.Array, A: jax.Array, Bm: jax.Array,
              Cm: jax.Array, S_in: jax.Array, unroll: int = 8) -> tuple:
    """Mamba-1 over S positions: x, dt [B,S,d]; Bm, Cm [B,S,N]; S_in
    [B,N,d] float32. The state is carried a position at a time (its
    decay differs by channel and state number, so no block of positions
    is a matmul), ``unroll`` positions a loop iteration. Returns (y
    [B,S,d] float32, S_out)."""
    def step(S, inp):
        y, S = ssm1_step(S, *inp[:2], A, *inp[2:])
        return S, y

    S_out, y = jax.lax.scan(
        step, S_in, tuple(jnp.moveaxis(a, 1, 0) for a in (x, dt, Bm, Cm)),
        unroll=min(unroll, x.shape[1]))
    return jnp.moveaxis(y, 0, 1), S_out


# -- the Mamba-2 decode step as one kernel ------------------------------------
#
# XLA compiles :func:`ssm_step` behind a dynamic_slice and in front of a
# dynamic_update_slice as two programs a layer: one reads the state,
# recomputes ``S_new`` and reduces it to ``y``; the other reads the state
# again, recomputes and writes. Three passes over the state where the
# recurrence needs two (PERF.md section 6, PR 48). The kernel below
# reads a block of the pool once, writes it back in place and hands out
# ``y`` of the same pass; a row that is not live moves nothing.

# What a program may hold in VMEM. The chip's sweep (tools/
# check_state_kernel.py sweep, PERF.md section 6, PR 48) reads 592 GB/s
# of the moved bytes at 16 heads of Nemotron's [64, 128] a block, 606 at
# 32 (4.2 MiB by the account below) and 606 at 64: the widest block under
# 6 MiB is where the plateau starts, well inside Mosaic's 16 MiB.
_SSM_VMEM_BYTES = 6 * 1024 * 1024


def ssm_kernel_vmem_bytes(hb: int, P: int, N: int) -> int:
    """What one program of the decode kernel holds in VMEM at a block of
    ``hb`` heads: the state block it reads and the one it writes, each in
    the pipeline's two buffers; two blocks each of the columns (``hb``
    lanes in tiles of 128) and of ``y`` (one row in a tile of 8); ``B``
    and ``C`` at 8 groups. The body keeps one head in registers. Mosaic
    allocates 16.16 MiB where this says 16.58 (128 heads of [64, 128])."""
    lanes = hb + (-hb) % 128
    return (4 * hb * P * N + 2 * P * lanes + 2 * 8 * hb * P + 4 * 8 * N) * 4


def head_blocks(H: int, groups: int) -> list[int]:
    """The head blocks the decode kernel's grid may take: the divisors of
    ``H`` that hold whole groups or divide one (so that one ``B`` and
    ``C`` serves a run of the block's heads), widest first."""
    rep = H // groups
    return [hb for hb in range(H, 0, -1)
            if H % hb == 0 and (hb % rep == 0 or rep % hb == 0)]


def pick_head_block(H: int, P: int, N: int, groups: int) -> int | None:
    """Heads a program of the decode kernel: the widest of
    :func:`head_blocks` that :func:`ssm_kernel_vmem_bytes` keeps within
    ``_SSM_VMEM_BYTES``. None where not even one head's state does, or
    its minor dimensions do not tile."""
    if P % 8 or N % 128:
        return None
    return next((hb for hb in head_blocks(H, groups)
                 if ssm_kernel_vmem_bytes(hb, P, N) <= _SSM_VMEM_BYTES), None)


def ssm_kernel_covers(state_shape: tuple) -> bool:
    """Whether :func:`decode_update` runs Mamba-2's step as the kernel,
    for a row's state ``[H, P, N]``: on the TPU (utils/device.py, the one
    platform probe), where the state tiles and one head's fits (a block
    of one head is within every grouping's reach, so the grouping is not
    asked). Read once at a boot too: the scheduler counts the rows a step
    moves by it."""
    H, P, N = state_shape
    return on_tpu() and pick_head_block(H, P, N, H) is not None


def _ssm_decode_kernel(layer_ref, route_ref, decay_ref, s_ref, cols_ref,
                       b_ref, c_ref, s_out_ref, y_ref, *, rep: int):
    """One program = ``hb`` heads of one row of the pool's layer
    ``layer_ref[0]``: ``S <- S * decay + dtx (outer) B`` written to the
    block it was read from, and ``y = S C`` of what was written, a group
    of heads a product: ``C [1, N] . S [heads x P, N]^T`` on the MXU at
    float32 (the lane reduction of the same sum held the kernel to 513
    GB/s of its bytes where this reads 606; PERF.md section 6, PR 48).
    ``route_ref`` (quant_mm._expert_route over the live mask): a row that
    is not live was handed the block the pipeline already holds, computes
    nothing and leaves that block as it is; its ``y`` is zeros."""
    r, j = pl.program_id(0), pl.program_id(1)
    hb, P, N = s_ref.shape[2:]
    H = hb * pl.num_programs(1)
    per = min(hb, rep)                  # heads of the block that share a group
    live = route_ref[r] > 0

    @pl.when(live)
    def _step():
        for h0 in range(0, hb, per):
            g = (j * hb + h0) // rep
            Bv = b_ref[0, pl.ds(g, 1), :]
            for h in range(h0, h0 + per):
                s_out_ref[0, 0, h] = (
                    s_ref[0, 0, h] * decay_ref[r * H + j * hb + h]
                    + cols_ref[0, 0, :, h: h + 1] * Bv)
            y = jax.lax.dot_general(
                jnp.broadcast_to(c_ref[0, pl.ds(g, 1), :], (8, N)),
                s_out_ref[0, 0, h0: h0 + per].reshape(per * P, N),
                (((1,), (1,)), ((), ())),
                precision=jax.lax.Precision.HIGHEST,
                preferred_element_type=jnp.float32)
            y_ref[0, 0, :, h0 * P: (h0 + per) * P] = y[:1]

    @pl.when(jnp.logical_not(live))
    def _skip():
        y_ref[...] = jnp.zeros_like(y_ref)

    # No row before this one has written the output block the pipeline
    # holds: it goes back as it came unless a live row fills it first.
    @pl.when(jnp.logical_not(live) & (r == 0) & (j == 0))
    def _keep():
        s_out_ref[...] = s_ref[...]


@functools.partial(jax.jit, static_argnames=("hb", "interpret"))
def ssm_decode_kernel(ssm: jax.Array, layer: jax.Array, live: jax.Array,
                      x: jax.Array, dt: jax.Array, A: jax.Array,
                      Bm: jax.Array, Cm: jax.Array, *,
                      hb: int | None = None,
                      interpret: bool = False) -> tuple:
    """:func:`ssm_step` for the first B rows of layer ``layer`` of the
    pool's ``ssm`` [L_m, rows, H, P, N], in place: one pass over the live
    rows' state. x [B,H,P]; dt [B,H] float32; A [H]; Bm, Cm [B,G,N];
    live [B] bool. Returns (y [B,H,P] float32, zeros for a row that is
    not live; ssm, the other rows and layers untouched). ``hb`` (None =
    :func:`pick_head_block`'s): heads a program, for the sweep that
    measures the rule and the tests that hold every block to the same
    answer. All arithmetic is float32, as :func:`ssm_step`'s."""
    _, _, H, P, N = ssm.shape
    B, G = x.shape[0], Bm.shape[-2]
    f32 = jnp.float32
    if hb is None:
        hb = pick_head_block(H, P, N, G)
    nblk = H // hb
    decay = jnp.exp(dt * A).reshape(B * H)
    # dtx as columns: a head's [P] lies along the state's sublanes.
    cols = jnp.swapaxes(
        (dt[..., None] * x.astype(f32)).reshape(B, nblk, hb, P), 2, 3)
    route = _expert_route(live.astype(jnp.int32), B)

    def row_map(r, j, ly, route):
        src = route[B + r]
        return src, jnp.where(route[r] > 0, j,
                              jnp.where(src < r, nblk - 1, 0))

    def state_map(r, j, ly, route):
        src, blk = row_map(r, j, ly, route)
        return ly[0], src, blk, 0, 0

    def col_map(r, j, ly, route):
        return (*row_map(r, j, ly, route), 0, 0)

    def group_map(r, j, ly, route):
        return route[B + r], 0, 0

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, nblk),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, 1, hb, P, N), state_map),
            pl.BlockSpec((1, 1, P, hb), col_map),
            pl.BlockSpec((1, G, N), group_map),
            pl.BlockSpec((1, G, N), group_map),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, hb, P, N), state_map),
            pl.BlockSpec((1, 1, 1, hb * P),
                         lambda r, j, ly, route: (r, j, 0, 0)),
        ],
    )
    ssm, y = pl.pallas_call(
        functools.partial(_ssm_decode_kernel, rep=H // G),
        name="ssm_decode_step",
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(ssm.shape, f32),
                   jax.ShapeDtypeStruct((B, nblk, 1, hb * P), f32)],
        input_output_aliases={3: 0},
        interpret=interpret,
    )(jnp.asarray(layer, jnp.int32).reshape(1), route, decay, ssm, cols,
      Bm.astype(f32), Cm.astype(f32))
    return y.reshape(B, H, P), ssm


def _window_update(pool: StatePool, layer: jax.Array, live: jax.Array,
                   z: jax.Array, conv_w: jax.Array,
                   conv_b: Optional[jax.Array]) -> tuple:
    """One position of layer ``layer``'s convolution for the pool's first
    B rows: (out [B,C] float32, ``pool.conv`` with the live rows' windows
    moved on, in place)."""
    B = z.shape[0]
    zero = jnp.zeros((), jnp.int32)
    at = (jnp.asarray(layer, jnp.int32), zero, zero, zero)
    win = jax.lax.dynamic_slice(pool.conv, at,
                                (1, B) + pool.conv.shape[2:])[0]
    out, win_new = conv_step(win, z, conv_w, conv_b)
    win_new = jnp.where(live[:, None, None], win_new, win)
    return out, jax.lax.dynamic_update_slice(pool.conv, win_new[None], at)


def decode_update(pool: StatePool, layer: jax.Array, live: jax.Array,
                  xbc: jax.Array, conv_w: jax.Array, conv_b: jax.Array,
                  split, step=ssm_step) -> tuple:
    """One decode step of Mamba layer ``layer`` for the pool's first B
    rows, in place. ``xbc`` [B,C]: the convolution's new input;
    ``split(conv_out [B,C] float32) -> (x, dt, A, Bm, Cm)``: the model's
    reading of the convolved channels, in the shapes ``step`` takes
    (:func:`ssm_step`, or :func:`ssm1_step` for Mamba-1). ``live`` [B]
    bool: the other rows' state and window come back bit for bit.
    Returns (y float32, x, pool). Where :func:`ssm_kernel_covers` says
    so, Mamba-2's state goes through :func:`ssm_decode_kernel` and the
    ``y`` of a row that is not live is zeros; the convolution's window,
    a thousandth of the bytes, stays XLA's."""
    if step is ssm_step and ssm_kernel_covers(pool.ssm.shape[2:]):
        out, conv = _window_update(pool, layer, live, xbc, conv_w, conv_b)
        x, dt, A, Bm, Cm = split(out)
        y, ssm = ssm_decode_kernel(pool.ssm, layer, live, x, dt, A, Bm, Cm)
        return y, x, pool._replace(ssm=ssm, conv=conv)
    B = xbc.shape[0]
    zero = jnp.zeros((), jnp.int32)
    layer = jnp.asarray(layer, jnp.int32)
    tail = (zero,) * (pool.ssm.ndim - 2)
    S = jax.lax.dynamic_slice(
        pool.ssm, (layer, zero) + tail, (1, B) + pool.ssm.shape[2:])[0]
    win = jax.lax.dynamic_slice(
        pool.conv, (layer, zero, zero, zero),
        (1, B) + pool.conv.shape[2:])[0]
    out, win_new = conv_step(win, xbc, conv_w, conv_b)
    x, dt, A, Bm, Cm = split(out)
    y, S_new = step(S, x, dt, A, Bm, Cm)
    S_new = jnp.where(live.reshape((B,) + (1,) * (S.ndim - 1)), S_new, S)
    win_new = jnp.where(live[:, None, None], win_new, win)
    return y, x, pool._replace(
        ssm=jax.lax.dynamic_update_slice(
            pool.ssm, S_new[None], (layer, zero) + tail),
        conv=jax.lax.dynamic_update_slice(
            pool.conv, win_new[None], (layer, zero, zero, zero)))


def conv_update(pool: StatePool, layer: jax.Array, live: jax.Array,
                z: jax.Array, conv_w: jax.Array) -> tuple:
    """One decode step of short-convolution layer ``layer`` for the
    pool's first B rows, in place: :func:`decode_update` without a
    recurrence behind the convolution. ``z`` [B,C]: the convolution's new
    input. Rows not ``live`` keep their window bit for bit. Returns (out
    [B,C] float32, pool)."""
    out, conv = _window_update(pool, layer, live, z, conv_w, None)
    return out, pool._replace(conv=conv)


# -- window rings -------------------------------------------------------------

def ring_chunk_masks(S: int, W: int, offset: int) -> tuple:
    """What a chunk of S queries at positions offset..offset+S of a
    window layer may read: (of the carried ring [S,W], of the chunk's own
    keys [S,S]). Slot j of the ring holds the newest position below
    ``offset`` that is j mod W; query i reads positions above ``offset +
    i - W``, its own the last."""
    i = jnp.arange(S)[:, None]
    held = offset - 1 - (offset - 1 - jnp.arange(W)[None, :]) % W
    ring = (held >= 0) & (held > offset + i - W)
    j = jnp.arange(S)[None, :]
    return ring, (j <= i) & (i - j < W)


def ring_after_chunk(ring: jax.Array, new: jax.Array, offset: int,
                     lengths: jax.Array) -> jax.Array:
    """The ring [B,G,W,C] after a chunk ``new`` [B,S,G,C] whose first
    ``lengths`` [B] positions are real, at positions offset..: each slot
    takes the newest real position of its residue, or keeps what it
    held. Padding is never written."""
    W, S = ring.shape[2], new.shape[1]
    last = offset + lengths.astype(jnp.int32)[:, None] - 1       # [B,1]
    newest = last - (last - jnp.arange(W)[None, :]) % W          # [B,W]
    idx = jnp.clip(newest - offset, 0, S - 1)
    taken = jnp.take_along_axis(new, idx[:, :, None, None], axis=1)
    return jnp.where((newest >= offset)[:, None, :, None],
                     jnp.swapaxes(taken, 1, 2).astype(ring.dtype), ring)


def ring_decode_write(pool: StatePool, layer: jax.Array, live: jax.Array,
                      lengths: jax.Array, k: jax.Array,
                      v: jax.Array) -> StatePool:
    """A decode step's K and V ([B,G,C], position ``lengths`` [B] of
    each row) into window layer ``layer``'s ring, in place. Rows not
    live write the garbage row (the pool's last) and keep theirs bit for
    bit."""
    B, G, _ = k.shape
    W = pool.win_k.shape[3]
    layer = jnp.asarray(layer, jnp.int32)
    rows = jnp.where(live, jnp.arange(B, dtype=jnp.int32),
                     pool.rows - 1)[:, None]
    slot = (lengths.astype(jnp.int32) % W)[:, None]
    heads = jnp.arange(G, dtype=jnp.int32)[None, :]

    def put(ring, new):
        return ring.at[layer, rows, heads, slot].set(new.astype(ring.dtype))

    if pool.win_ks is None:
        return pool._replace(win_k=put(pool.win_k, k),
                             win_v=put(pool.win_v, v))
    from .paged_kv import quant_kv
    (kq, ks), (vq, vs) = quant_kv(k), quant_kv(v)
    zero = jnp.zeros((), jnp.int32)
    at = (jnp.arange(W)[None, None, :] == slot[:, :, None]) \
        & live[:, None, None]

    def put_scale(scales, s):
        # The slot is the lane dimension: replace lanes of the layer's
        # [B, G, W] block, never scatter into them (paged_kv's rule).
        old = jax.lax.dynamic_slice(scales, (layer, zero, zero, zero),
                                    (1, B) + scales.shape[2:])[0]
        return jax.lax.dynamic_update_slice(
            scales, jnp.where(at, s[:, :, None], old)[None],
            (layer, zero, zero, zero))

    return pool._replace(
        win_k=put(pool.win_k, kq), win_v=put(pool.win_v, vq),
        win_ks=put_scale(pool.win_ks, ks), win_vs=put_scale(pool.win_vs, vs))


def ring_read(pool: StatePool, layer: jax.Array, B: int) -> tuple:
    """Window layer ``layer``'s ring of the pool's first B rows: (k
    [B,G,W,C], v, k scales [B,G,W] | None, v scales)."""
    layer = jnp.asarray(layer, jnp.int32)
    zero = jnp.zeros((), jnp.int32)

    def rows(a):
        if a is None:
            return None
        return jax.lax.dynamic_slice(
            a, (layer, zero) + (zero,) * (a.ndim - 2),
            (1, B) + a.shape[2:])[0]

    return tuple(rows(a) for a in pool[2:])


def write_rows(pool: StatePool, state: StatePool,
               rows: jax.Array) -> StatePool:
    """``state`` ([L, R, ...], a prefill's carry) into rows ``rows`` [R]
    of ``pool``, whole rows (rings too, quantised here where the pool's
    are int8). An admission's dummy entries name the garbage row (the
    row sentinel ``num_slots`` is its index)."""
    rows = rows.astype(jnp.int32)
    if pool.win_ks is not None:
        from .paged_kv import quant_kv
        (kq, ks), (vq, vs) = quant_kv(state.win_k), quant_kv(state.win_v)
        state = state._replace(win_k=kq, win_v=vq, win_ks=ks, win_vs=vs)
    return StatePool(*(
        None if a is None else a.at[:, rows].set(b.astype(a.dtype),
                                                 mode="drop")
        for a, b in zip(pool, state)))


def snapshot(state: StatePool, row: int = 0) -> StatePool:
    """One row of a carry, without the row axis ([L, ...]): what a
    prefix entry keeps beside its K and V."""
    return StatePool(*(None if a is None else a[:, row] for a in state))


def from_snapshot(snap: StatePool, rows: int) -> StatePool:
    """A carry of ``rows`` rows that all start from ``snap``."""
    def rep(a):
        return jnp.broadcast_to(a[:, None], (a.shape[0], rows) + a.shape[1:])
    return StatePool(*(None if a is None else rep(a) for a in snap))
