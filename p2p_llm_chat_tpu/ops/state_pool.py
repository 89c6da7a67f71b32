"""Recurrent state beside the page pool: what a Mamba-2 layer keeps for
each row, whatever the row's length, and the programs over it.

A row of a layer is a float32 state ``ssm`` [heads, head_dim, state_size]
and the last ``conv_kernel - 1`` inputs of the layer's causal depthwise
convolution, ``conv`` [conv_kernel - 1, conv_dim], in the activations'
dtype:

    ssm:  [L_m, rows, heads, head_dim, state_size]   float32
    conv: [L_m, rows, conv_kernel - 1, conv_dim]

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
  rows not named live keep their state bit for bit.
- :func:`write_rows`: a prefill's or a chunk ladder's final state into
  named rows. It overwrites the whole row, so a slot that is freed and
  reused inherits nothing.
- :func:`snapshot` / :func:`from_snapshot`: a prefix entry's state out
  of a one-row carry, and into every row of a fresh one.

The recurrence (``A`` < 0 a head, ``dt`` > 0 a head and token, ``B`` and
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

from typing import NamedTuple

import jax
import jax.numpy as jnp

from ..models.configs import ModelConfig


class StatePool(NamedTuple):
    ssm: jax.Array
    conv: jax.Array

    @classmethod
    def create(cls, config: ModelConfig, rows: int, dtype) -> "StatePool":
        L = config.ssm_layers
        return cls(
            ssm=jnp.zeros((L, rows, config.mamba_num_heads,
                           config.mamba_head_dim, config.ssm_state_size),
                          jnp.float32),
            conv=jnp.zeros((L, rows, config.conv_kernel - 1,
                            config.conv_dim), dtype))

    @property
    def nbytes(self) -> int:
        return int(self.ssm.nbytes) + int(self.conv.nbytes)

    @property
    def row_bytes(self) -> int:
        """Bytes of ONE row over all layers (state and window)."""
        return self.nbytes // self.ssm.shape[1]


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
              b: jax.Array) -> tuple:
    """One position of the causal depthwise convolution. window
    [B,K-1,C] (oldest first); xbc [B,C]; w [K,C] (w[K-1] weighs the
    current input); b [C]. Returns (out [B,C] float32, new window)."""
    f32 = jnp.float32
    full = jnp.concatenate([window, xbc[:, None].astype(window.dtype)],
                           axis=1)                            # [B,K,C]
    out = jnp.sum(full.astype(f32) * w.astype(f32)[None], axis=1) \
        + b.astype(f32)
    return out, full[:, 1:]


def conv_scan(xbc: jax.Array, window: jax.Array, lengths: jax.Array,
              w: jax.Array, b: jax.Array) -> tuple:
    """The convolution over S positions behind ``window``. xbc [B,S,C];
    window [B,K-1,C]; lengths [B]: a row's first ``lengths`` positions
    are real. Returns (out [B,S,C] float32, the window after each row's
    last REAL position: the carried one where it has none)."""
    f32 = jnp.float32
    K = w.shape[0]
    S = xbc.shape[1]
    ext = jnp.concatenate([window, xbc.astype(window.dtype)], axis=1)
    out = b.astype(f32)[None, None]
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


def decode_update(pool: StatePool, layer: jax.Array, live: jax.Array,
                  xbc: jax.Array, conv_w: jax.Array, conv_b: jax.Array,
                  split) -> tuple:
    """One decode step of Mamba layer ``layer`` for the pool's first B
    rows, in place. ``xbc`` [B,C]: the convolution's new input;
    ``split(conv_out [B,C] float32) -> (x [B,H,P], dt [B,H], A [H], Bm
    [B,G,N], Cm [B,G,N])``: the model's reading of the convolved
    channels. ``live`` [B] bool: the other rows' state and window come
    back bit for bit. Returns (y [B,H,P] float32, x, pool)."""
    B = xbc.shape[0]
    zero = jnp.zeros((), jnp.int32)
    layer = jnp.asarray(layer, jnp.int32)
    S = jax.lax.dynamic_slice(
        pool.ssm, (layer, zero, zero, zero, zero),
        (1, B) + pool.ssm.shape[2:])[0]
    win = jax.lax.dynamic_slice(
        pool.conv, (layer, zero, zero, zero),
        (1, B) + pool.conv.shape[2:])[0]
    out, win_new = conv_step(win, xbc, conv_w, conv_b)
    x, dt, A, Bm, Cm = split(out)
    y, S_new = ssm_step(S, x, dt, A, Bm, Cm)
    S_new = jnp.where(live[:, None, None, None], S_new, S)
    win_new = jnp.where(live[:, None, None], win_new, win)
    return y, x, StatePool(
        ssm=jax.lax.dynamic_update_slice(
            pool.ssm, S_new[None], (layer, zero, zero, zero, zero)),
        conv=jax.lax.dynamic_update_slice(
            pool.conv, win_new[None], (layer, zero, zero, zero)))


def write_rows(pool: StatePool, state: StatePool,
               rows: jax.Array) -> StatePool:
    """``state`` ([L_m, R, ...], a prefill's carry) into rows ``rows``
    [R] of ``pool``, whole rows. An admission's dummy entries name the
    garbage row (the row sentinel ``num_slots`` is its index)."""
    rows = rows.astype(jnp.int32)
    return StatePool(ssm=pool.ssm.at[:, rows].set(state.ssm, mode="drop"),
                     conv=pool.conv.at[:, rows].set(
                         state.conv.astype(pool.conv.dtype), mode="drop"))


def snapshot(state: StatePool, row: int = 0) -> StatePool:
    """One row of a carry, without the row axis ([L_m, ...]): what a
    prefix entry keeps beside its K and V."""
    return StatePool(ssm=state.ssm[:, row], conv=state.conv[:, row])


def from_snapshot(snap: StatePool, rows: int) -> StatePool:
    """A carry of ``rows`` rows that all start from ``snap``."""
    def rep(a):
        return jnp.broadcast_to(a[:, None], (a.shape[0], rows) + a.shape[1:])
    return StatePool(ssm=rep(snap.ssm), conv=rep(snap.conv))
