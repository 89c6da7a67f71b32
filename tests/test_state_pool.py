"""ops/state_pool.py at the op: the chunked (SSD) scan is the sequential
recurrence in float32, whatever the length and the block; padding
neither moves the state nor enters the convolution's window; the pool's
writes name whole rows; a decode update leaves rows that are not live
bit-equal; the programs over the pool update it in place; and the
Mamba-2 decode kernel (interpret mode) is ``ssm_step`` for the live rows
and touches nothing else."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from p2p_llm_chat_tpu.models.configs import get_config
from p2p_llm_chat_tpu.ops import state_pool
from p2p_llm_chat_tpu.ops.state_pool import StatePool
from tools.check_state_kernel import xla_update

CFG = get_config("tiny-nemotron-h")
H, P, G, N = 8, 16, 2, 16


def draws(B, S, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    x = jax.random.normal(ks[0], (B, S, H, P), jnp.float32)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, H)) - 2.0)
    A = -jnp.exp(jax.random.uniform(ks[2], (H,), minval=0.0, maxval=2.5))
    Bm = jax.random.normal(ks[3], (B, S, G, N), jnp.float32)
    Cm = jax.random.normal(ks[4], (B, S, G, N), jnp.float32)
    S0 = jax.random.normal(ks[5], (B, H, P, N), jnp.float32)
    return x, dt, A, Bm, Cm, S0


def sequential(x, dt, A, Bm, Cm, S0):
    ys, S = [], S0
    for t in range(x.shape[1]):
        y, S = state_pool.ssm_step(S, x[:, t], dt[:, t], A, Bm[:, t],
                                   Cm[:, t])
        ys.append(y)
    return jnp.stack(ys, axis=1), S


@pytest.mark.parametrize("S,chunk", [(1, 16), (7, 16), (16, 16), (37, 16),
                                     (48, 16), (50, 128), (33, 8)])
def test_chunked_scan_is_the_sequential_recurrence(S, chunk):
    x, dt, A, Bm, Cm, S0 = draws(2, S, seed=S)
    y_seq, S_seq = sequential(x, dt, A, Bm, Cm, S0)
    y, S_out = state_pool.ssd_scan(x, dt, A, Bm, Cm, S0, chunk)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_seq),
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(S_out), np.asarray(S_seq),
                               rtol=2e-4, atol=2e-4)


def test_a_scan_in_pieces_is_the_scan_in_one():
    """State handed from piece to piece, at edges that are no multiple
    of the block."""
    x, dt, A, Bm, Cm, S0 = draws(2, 45, seed=3)
    y_all, S_all = state_pool.ssd_scan(x, dt, A, Bm, Cm, S0, 16)
    S, ys = S0, []
    for lo, hi in ((0, 13), (13, 30), (30, 45)):
        y, S = state_pool.ssd_scan(x[:, lo:hi], dt[:, lo:hi], A,
                                   Bm[:, lo:hi], Cm[:, lo:hi], S, 16)
        ys.append(y)
    np.testing.assert_allclose(np.asarray(jnp.concatenate(ys, 1)),
                               np.asarray(y_all), rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(S), np.asarray(S_all),
                               rtol=2e-4, atol=2e-4)


def test_positions_with_dt_zero_do_not_move_the_state():
    x, dt, A, Bm, Cm, S0 = draws(2, 40, seed=5)
    lens = jnp.asarray([23, 40])
    real = jnp.arange(40)[None, :] < lens[:, None]
    _, S_pad = state_pool.ssd_scan(x, jnp.where(real[..., None], dt, 0.0),
                                   A, Bm, Cm, S0, 16)
    _, S_row0 = state_pool.ssd_scan(x[:1, :23], dt[:1, :23], A, Bm[:1, :23],
                                    Cm[:1, :23], S0[:1], 16)
    np.testing.assert_allclose(np.asarray(S_pad[0]), np.asarray(S_row0[0]),
                               rtol=2e-4, atol=2e-4)


def test_convolution_scan_is_its_steps_and_its_window_skips_padding():
    K, C, B, S = 4, 12, 3, 9
    ks = jax.random.split(jax.random.PRNGKey(1), 4)
    xbc = jax.random.normal(ks[0], (B, S, C), jnp.float32)
    win0 = jax.random.normal(ks[1], (B, K - 1, C), jnp.float32)
    w = jax.random.normal(ks[2], (K, C), jnp.float32)
    b = jax.random.normal(ks[3], (C,), jnp.float32)
    lens = jnp.asarray([9, 4, 0])
    out, win = state_pool.conv_scan(xbc, win0, lens, w, b)
    wins, outs, cur = [], [], win0
    for t in range(S):
        o, cur = state_pool.conv_step(cur, xbc[:, t], w, b)
        outs.append(o)
        wins.append(cur)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(jnp.stack(outs, 1)), rtol=1e-5,
                               atol=1e-5)
    # The window after each row's last REAL position.
    assert np.array_equal(np.asarray(win[0]), np.asarray(wins[8][0]))
    assert np.array_equal(np.asarray(win[1]), np.asarray(wins[3][1]))
    assert np.array_equal(np.asarray(win[2]), np.asarray(win0[2]))


def filled_pool(rows, seed=0):
    pool = StatePool.create(CFG, rows, jnp.float32)
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
    return StatePool(ssm=jax.random.normal(k1, pool.ssm.shape, jnp.float32),
                     conv=jax.random.normal(k2, pool.conv.shape, jnp.float32))


def test_write_rows_overwrites_whole_rows_and_dummies_hit_the_garbage_row():
    slots = 4
    pool = filled_pool(slots + 1)
    state = filled_pool(3, seed=9)
    # Entries for slots 2 and 0, and a dummy (row sentinel = num_slots).
    rows = jnp.asarray([2, 0, slots])
    out = state_pool.write_rows(pool, state, rows)
    for got, src, old in ((out.ssm, state.ssm, pool.ssm),
                          (out.conv, state.conv, pool.conv)):
        got, src, old = map(np.asarray, (got, src, old))
        assert np.array_equal(got[:, 2], src[:, 0])
        assert np.array_equal(got[:, 0], src[:, 1])
        assert np.array_equal(got[:, slots], src[:, 2])
        assert np.array_equal(got[:, 1], old[:, 1])
        assert np.array_equal(got[:, 3], old[:, 3])


def test_snapshot_round_trip_seeds_every_row():
    state = filled_pool(1, seed=2)
    snap = state_pool.snapshot(state)
    assert snap.ssm.shape == state.ssm.shape[:1] + state.ssm.shape[2:]
    back = state_pool.from_snapshot(snap, 3)
    for r in range(3):
        assert np.array_equal(np.asarray(back.ssm[:, r]),
                              np.asarray(state.ssm[:, 0]))
        assert np.array_equal(np.asarray(back.conv[:, r]),
                              np.asarray(state.conv[:, 0]))


def _step_program():
    d = CFG.mamba_inner
    A = -jnp.ones((H,), jnp.float32)

    def split(conv_out):
        lead = conv_out.shape[:-1]
        return (conv_out[..., :d].reshape(*lead, H, P),
                jnp.full(lead + (H,), 0.1, jnp.float32), A,
                conv_out[..., d: d + G * N].reshape(*lead, G, N),
                conv_out[..., d + G * N:].reshape(*lead, G, N))

    def step(pool, live, xbc, w, b):
        for layer in range(2):
            _, _, pool = state_pool.decode_update(
                pool, jnp.asarray(layer, jnp.int32), live, xbc, w, b, split)
        return pool

    return step


def test_decode_update_leaves_rows_that_are_not_live_bit_equal():
    B = 3
    pool = filled_pool(B + 1, seed=4)
    xbc = jax.random.normal(jax.random.PRNGKey(8), (B, CFG.conv_dim))
    w = jnp.ones((CFG.conv_kernel, CFG.conv_dim), jnp.float32)
    b = jnp.zeros((CFG.conv_dim,), jnp.float32)
    live = jnp.asarray([True, False, True])
    out = jax.jit(_step_program())(pool, live, xbc, w, b)
    for new, old in ((out.ssm, pool.ssm), (out.conv, pool.conv)):
        new, old = np.asarray(new), np.asarray(old)
        assert np.array_equal(new[:2, 1], old[:2, 1])       # not live
        assert np.array_equal(new[:2, B], old[:2, B])       # garbage row
        assert np.array_equal(new[2:], old[2:])             # other layers
        assert not np.array_equal(new[:2, 0], old[:2, 0])
        assert not np.array_equal(new[:2, 2], old[:2, 2])


# A pool that tiles as the kernel's predicate asks (head_dim % 8,
# state_size % 128): 8 heads in 2 groups of 4, 4 rows and the garbage row.
KL, KROWS, KH, KP, KG, KN = 3, 5, 8, 8, 2, 128


def _kernel_draws(B, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    return (jax.random.normal(ks[0], (KL, KROWS, KH, KP, KN), jnp.float32),
            jax.random.normal(ks[1], (B, KH, KP), jnp.float32),
            jax.nn.softplus(jax.random.normal(ks[2], (B, KH)) - 2.0),
            -jnp.exp(jax.random.uniform(ks[3], (KH,), minval=0.0,
                                        maxval=2.5)),
            jax.random.normal(ks[4], (B, KG, KN), jnp.float32),
            jax.random.normal(ks[5], (B, KG, KN), jnp.float32))


@pytest.mark.parametrize("live,layer,hb", [
    ((1, 1, 1, 1), 0, None),     # all rows live
    ((1, 0, 1, 0), 0, None),     # some not
    ((0, 1, 1, 0), 0, None),     # the first row not live
    ((0, 0, 0, 0), 0, None),     # none live
    ((0, 0, 0, 1), 1, None),     # only the last, in a layer that is not 0
    ((1, 0, 1), 2, None),        # B smaller than the pool's rows
    ((1, 0, 1, 1), 1, 2),        # a head block smaller than a group
    ((0, 1, 0, 1), 1, 4),        # a head block of one of several groups
    ((1, 1, 0, 1), 2, 8),        # a head block of every group
], ids=["all-live", "some-live", "first-dead", "none-live", "last-only",
        "B-under-rows", "hb-under-group", "hb-one-group", "hb-all-groups"])
def test_decode_kernel_is_the_step_for_live_rows_and_moves_nothing_else(
        live, layer, hb):
    """``ssm_decode_kernel`` against ``ssm_step`` + ``where(live, ...)`` +
    ``dynamic_update_slice``: ``y`` and the live rows' state within
    float32 rounding; every other row, the garbage row and every other
    layer bit-equal; ``y`` of a row that is not live is zeros."""
    B = len(live)
    ssm, x, dt, A, Bm, Cm = _kernel_draws(B, seed=layer)
    live = jnp.asarray(live, bool)
    y, got = state_pool.ssm_decode_kernel(ssm, layer, live, x, dt, A, Bm, Cm,
                                          hb=hb, interpret=True)
    y_ref, ref = xla_update(ssm, layer, live, x, dt, A, Bm, Cm)
    y, got, y_ref, ref, old, lv = map(np.asarray,
                                      (y, got, y_ref, ref, ssm, live))
    others = [i for i in range(KL) if i != layer]
    assert np.array_equal(got[others], old[others])          # other layers
    assert np.array_equal(got[layer, B:], old[layer, B:])    # garbage row
    assert np.array_equal(got[layer, :B][~lv], old[layer, :B][~lv])
    assert not np.any(y[~lv])
    np.testing.assert_allclose(got[layer, :B][lv], ref[layer, :B][lv],
                               rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(y[lv], y_ref[lv], rtol=1e-5, atol=1e-4)
    if lv.any():
        assert not np.array_equal(got[layer, :B][lv], old[layer, :B][lv])


def test_head_block_holds_whole_groups_or_divides_one_and_fits():
    """``pick_head_block``: the widest block under the VMEM account, by
    the shape alone; nothing where the state's minor dimensions do not
    tile (the test-size models, Mamba-1's [N, d])."""
    pick, limit = state_pool.pick_head_block, state_pool._SSM_VMEM_BYTES
    account = state_pool.ssm_kernel_vmem_bytes
    for H, P, N, G in [(128, 64, 128, 8), (128, 64, 128, 1), (8, 8, 128, 2),
                       (96, 64, 128, 8), (24, 128, 256, 8), (64, 64, 128, 64)]:
        hb = pick(H, P, N, G)
        rep = H // G
        assert H % hb == 0 and (hb % rep == 0 or rep % hb == 0), (H, G, hb)
        assert 4 * hb * P * N * 4 < account(hb, P, N) <= limit, (hb, P, N)
        wider = [w for w in state_pool.head_blocks(H, G) if w > hb]
        assert all(account(w, P, N) > limit for w in wider), (hb, wider)
    assert pick(128, 64, 128, 8) == 32         # Nemotron's: 4.2 MiB
    assert pick(8, 16, 16, 2) is None          # tiny-nemotron-h
    assert pick(8, 12, 128, 2) is None
    assert not state_pool.ssm_kernel_covers((128, 64, 128))   # not on a TPU


def test_decode_update_hands_mamba2_to_the_kernel_where_it_covers(
        monkeypatch):
    """On a TPU, at a state that tiles, ``decode_update`` with
    ``ssm_step`` is the convolution's window in XLA and the kernel for
    the state; Mamba-1's step keeps XLA's program whatever the platform.
    The platform probe is steered here, and the kernel interpreted."""
    B, K = 4, 4
    C = KH * KP + 2 * KG * KN
    ssm, _, dt, A, _, _ = _kernel_draws(B, seed=3)
    ks = jax.random.split(jax.random.PRNGKey(5), 3)
    pool = StatePool(ssm=ssm, conv=jax.random.normal(
        ks[0], (KL, KROWS, K - 1, C), jnp.float32))
    xbc = jax.random.normal(ks[1], (B, C), jnp.float32)
    w = jax.random.normal(ks[2], (K, C), jnp.float32)
    live = jnp.asarray([True, False, True, True])

    def split(out):
        d = KH * KP
        return (out[:, :d].reshape(B, KH, KP), dt, A,
                out[:, d: d + KG * KN].reshape(B, KG, KN),
                out[:, d + KG * KN:].reshape(B, KG, KN))

    def update():
        return state_pool.decode_update(pool, jnp.asarray(1), live, xbc, w,
                                        None, split)

    y_ref, x_ref, ref = update()
    called = []

    def interpreted(*args):
        called.append(args[0].shape)
        return kernel(*args, interpret=True)

    kernel = state_pool.ssm_decode_kernel
    monkeypatch.setattr(state_pool, "on_tpu", lambda: True)
    monkeypatch.setattr(state_pool, "ssm_decode_kernel", interpreted)
    assert state_pool.ssm_kernel_covers((KH, KP, KN))
    assert not state_pool.ssm_kernel_covers((8, 16, 16))
    y, x, got = update()
    assert called == [ssm.shape]
    lv = np.asarray(live)
    assert np.array_equal(np.asarray(x), np.asarray(x_ref))
    assert np.array_equal(np.asarray(got.conv), np.asarray(ref.conv))
    np.testing.assert_allclose(np.asarray(y)[lv], np.asarray(y_ref)[lv],
                               rtol=1e-5, atol=1e-4)
    np.testing.assert_allclose(np.asarray(got.ssm), np.asarray(ref.ssm),
                               rtol=1e-6, atol=1e-6)
    assert np.array_equal(np.asarray(got.ssm)[1, 1], np.asarray(ssm)[1, 1])
    # Mamba-1 hands in its own step: no kernel, on any platform.
    pool1 = StatePool(ssm=jnp.zeros((2, 3, 16, 128), jnp.float32),
                      conv=jnp.zeros((2, 3, K - 1, 128), jnp.float32))
    state_pool.decode_update(
        pool1, jnp.asarray(0), jnp.ones((2,), bool), jnp.ones((2, 128)),
        jnp.ones((K, 128)), None,
        lambda out: (out, out, -jnp.ones((16, 128)), out[:, :16],
                     out[:, :16]), step=state_pool.ssm1_step)
    assert called == [ssm.shape]


def _kernel_program():
    def step(pool, live, x, dt, A, Bm, Cm):
        for layer in range(2):
            _, ssm = state_pool.ssm_decode_kernel(
                pool.ssm, jnp.asarray(layer, jnp.int32), live, x, dt, A, Bm,
                Cm, interpret=True)
            pool = pool._replace(ssm=ssm)
        return pool

    return step


@pytest.mark.parametrize("program", ["decode_update", "write_rows",
                                     "decode_kernel"])
def test_pool_programs_alias_the_donated_pool(program):
    """The compiled program's output pool IS its input pool's buffer
    (``input_output_alias`` in the optimised module). Whether the TPU
    compiler also keeps every instruction in place is read off the
    served programs' optimised HLO on the chip
    (tools/check_pool_copies.py) and, for the decode kernel, off a
    compile for a described chip (tests/test_pool_write_layout.py): the
    CPU compiler's copies say nothing about it."""
    B = 3
    pool = StatePool.create(CFG, B + 1, jnp.float32)
    if program == "decode_update":
        fn = jax.jit(_step_program(), donate_argnums=(0,))
        args = (pool, jnp.ones((B,), bool), jnp.zeros((B, CFG.conv_dim)),
                jnp.ones((CFG.conv_kernel, CFG.conv_dim)),
                jnp.zeros((CFG.conv_dim,)))
    elif program == "decode_kernel":
        ssm, *inp = _kernel_draws(B)
        fn = jax.jit(_kernel_program(), donate_argnums=(0,))
        args = (pool._replace(ssm=ssm), jnp.ones((B,), bool), *inp)
    else:
        fn = jax.jit(state_pool.write_rows, donate_argnums=(0,))
        args = (pool, StatePool.create(CFG, 2, jnp.float32),
                jnp.asarray([1, B]))
    text = fn.lower(*args).compile().as_text()
    assert "input_output_alias" in text
