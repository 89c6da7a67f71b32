"""A dropless routed prefill without the buckets: the sorted-tile
dispatch, one in the tree, for every family's prefill on one device
(models/pangu._routed_local's prefill half: openPangu's held range,
Nemotron's LatentMoE and Mellum's routed layers;
models/mixtral._moe_tiles: OLMoE's dropless prefill).

Each family keeps its own router and hands over what it chose
(``top_w``, ``top_i``), which pairs are to be computed (``takes``: a
real position's, and of a held range of a wider router those routed to
an expert held here), the rows its experts read (the hidden state, or
the latent of a family whose experts live in one) and its experts'
feed-forward (:func:`swiglu_experts`, :func:`relu2_experts`); the
bookkeeping here is the same for all: the (token, expert) pairs laid
out SORTED by expert, each expert's run padded to whole tiles of
:func:`tile_rows` rows, the expert-stripe kernel walking tiles and
reading each tile's expert (quant.q_einsum's ``source``). Rows come to
their tiles by ONE gather and go back by one; no row is scattered, and
a pair that is not taken is sent nowhere and gets 0.
"""

from __future__ import annotations

from typing import Callable, Optional

import jax
import jax.numpy as jnp

from .quant import q_einsum


def tile_rows(pairs: int, experts: int, width: Optional[int] = None) -> int:
    """Rows a tile of :func:`routed_tiles`, from the dispatch's shapes
    alone. In pairs an expert: the mean run (``pairs / experts``)
    rounded down to a power of two of 8 to 128 (a tile is one MXU pass
    of one expert's weights; smaller tiles pad less and fetch the
    weights more often), which is all the rule was when the hybrid
    family alone used it (PR 40), and since PR 42 TWICE that where it is
    under the number of experts.

    Why twice: runs scatter about the mean, so a tile of the mean run
    splits about half of them in two, and a run's second tile reads its
    expert's weights again; under 128 rows an expert the dispatch is
    bound by that stream. Measured at ONE shape, 2,048 pairs over 64
    experts (OLMoE's one-row admission): 21.7 ms a dispatch at 32 rows,
    19.3 at 64, 21.5 at 128, whose padding rows the gathers around the
    kernel pay for (PERF.md section 6, PR 42).

    Why only under the number of experts: that condition is a fence, not
    physics (by the arithmetic above twice the mean run should pay at
    every mean under 128). It leaves the rule as it was at the shapes
    where twice was not measured and something holds the programs in
    place: 8 experts (the test sizes tests/test_program_hashes.py pins;
    Mixtral's 176 MB experts, which take no tiles), and mean runs of 64
    and up at 64 experts (Mellum's chunk, (8,192, 64) -> 128, whose
    cell must not move; OLMoE's pair, (4,096, 64) -> 64, where 128 read
    alike). PERF.md section 7 has the plain rule as an open question.

    At 64 experts top-8, by tokens a dispatch (the parent's rule in
    brackets): 16-64 tokens 16 (8), 128 tokens 32 (16), 256 tokens 64
    (32), 512 tokens 64 (64), 1,024 and up 128 (128). So every program
    of OLMoE's AND of Mellum's under 512 tokens a dispatch lowers to
    other tiles than the parent's rule gave; of those only 256 tokens
    was measured, and Mellum's cell drives none of them.

    ``width`` (PR 43): the experts the router scores, where ``experts``
    is a range of them held here (None, or no wider: all are held, and
    nothing above changes). The rule then reads the pairs the held
    experts EXPECT, ``pairs x experts / width``, and gives no fewer
    than 64 rows. Why a floor: the layout is sized for every selection
    being local (``pairs / rows + experts`` tiles) while a quarter or a
    sixteenth of them are, so most tiles are empty, and the kernel's
    grid steps over an empty tile as over a filled one (21-23 steps a
    tile at the two benchmark configurations' widths, about 0.16 us a
    step): small tiles buy little padding with many steps, and under
    hot experts (the rule under the benchmark's weights) they read a
    long run's expert again and again. Why not 128: the rows of a filled
    tile are multiplied whether real or not, and at 128 that shows.
    Measured with tools/check_admit_pair.py, a one-row 256-token
    dispatch / a two-row one, ms (PERF.md section 6, PR 43): Nemotron
    (128 of 512 experts, top-22; the buckets it left 40.6 / 75.8) 33.1 /
    49.9 at 16 and 32 rows (this rule without its floor), 30.8 / 49.9
    at 32, **29.4 / 47.4 at 64**, 29.3 / 49.8 at 64 and 128
    (``tile_rows(pairs, experts)``, all selections counted), 34.7 / 50.1
    at 128; openPangu (16 of 256, top-8; 18.9 / 35.7) 22.4 / 41.5 at
    16, 20.2 / 37.4 at 32, **20.1 / 35.6 at 64**, 22.0 / 36.3 at 128.
    Above a mean expected run of 64 (a dispatch of 2,048 tokens there,
    4,096 here) the rule's own 128 takes over; nothing was measured
    there."""
    held = width is not None and width > experts
    if held:
        pairs = pairs * experts // width
    rows = 8
    while rows < 128 and rows * 2 * experts <= pairs:
        rows *= 2
    rows = min(2 * rows, 128) if rows < experts else rows
    return max(rows, 64) if held else rows


def swiglu_experts(xin: jax.Array, count: Optional[jax.Array],
                   source: Optional[jax.Array], w_gu, w_down,
                   w_gate=None, w_up=None) -> jax.Array:
    """The experts' SwiGLU over buckets or tiles ``xin`` [N,C,H] ->
    [N,C,H]: ``w_gu`` ([NE,H,2F], gate|up fused) or, where it is None,
    ``w_gate`` and ``w_up``; ``count`` and ``source`` as
    quant.q_einsum takes them."""
    if w_gu is not None:
        gu = q_einsum("ech,ehf->ecf", xin, w_gu, count, source)
        F = gu.shape[-1] // 2
        g = jax.nn.silu(gu[..., :F])
        u = gu[..., F:]
    else:
        g = jax.nn.silu(q_einsum("ech,ehf->ecf", xin, w_gate, count, source))
        u = q_einsum("ech,ehf->ecf", xin, w_up, count, source)
    return q_einsum("ecf,efh->ech", g * u, w_down, count, source)


def relu2_experts(xin: jax.Array, count: Optional[jax.Array],
                  source: Optional[jax.Array], w_up, w_down) -> jax.Array:
    """Ungated ``relu(.)^2`` experts over buckets or tiles, as
    :func:`swiglu_experts`: ``w_up`` [NE,H,F], ``w_down`` [NE,F,H]."""
    up = q_einsum("ech,ehf->ecf", xin, w_up, count, source)
    return q_einsum("ecf,efh->ech", jnp.square(jax.nn.relu(up)), w_down,
                    count, source)


def routed_tiles(xt: jax.Array, top_w: jax.Array, top_i: jax.Array,
                 takes: jax.Array, experts: int, ffn: Callable,
                 width: Optional[int] = None) -> tuple[jax.Array, jax.Array]:
    """The routed sum of ``xt`` [T,H] over the pairs its router chose:
    ``top_w``, ``top_i`` [T,k] (weight and expert of each selection) and
    ``takes`` [T,k] bool (the pairs to compute: a real position's;
    the others get 0). ``ffn(xin [tiles,tm,H], count [tiles], source
    [tiles]) -> [tiles,tm,H]`` is the experts' feed-forward over tiles
    (:func:`swiglu_experts` or :func:`relu2_experts` with the family's
    weights bound). ``width`` is :func:`tile_rows`': the experts the
    router scores where ``experts`` is a held range of them (a pair
    past it must come with ``takes`` False). The layout is sized for
    every one of the ``T x k`` selections being taken, whatever share
    of them is expected.

    The experts' matmuls run over ``pairs + experts x tm`` rows at the
    most where all-T buckets run ``experts x T`` (eight times what 64
    experts top-8 need: PERF.md section 6, PRs 40 and 42).

    Returns (out float32 [T,H]: each row's selections summed by their
    weights in selection order, tiles int32 [experts]: the tiles each
    expert's run filled, so ``sum(tiles) x tile_rows`` rows were
    multiplied)."""
    T, H = xt.shape
    NE, k = experts, top_i.shape[-1]
    P = T * k
    tm = tile_rows(P, NE, width)
    NT = -(-P // tm) + NE               # tiles: every run's last is part full
    expert = jnp.where(takes, top_i, NE).reshape(P)      # NE: sent nowhere
    flat = jax.nn.one_hot(expert, NE, dtype=jnp.int32)   # [P, NE]
    sent = jnp.sum(flat, axis=0)                         # [NE]
    slot = jnp.sum(flat * (jnp.cumsum(flat, axis=0) - flat), axis=-1)
    tiles = -(-sent // tm)
    tile_end = jnp.cumsum(tiles)
    tile_start = tile_end - tiles
    run_start = jnp.cumsum(sent) - sent                  # in sorted order
    order = jnp.argsort(expert, stable=True)             # pairs by expert
    # Of every tile: its expert, and how many of its rows are filled.
    t = jnp.arange(NT, dtype=jnp.int32)
    used = t < tile_end[-1]
    # (a count of the runs that end at or before it: no search loop)
    source = jnp.minimum(jnp.sum(tile_end[None, :] <= t[:, None], axis=1),
                         NE - 1).astype(jnp.int32)
    source = jnp.where(used, source, source[jnp.maximum(tile_end[-1] - 1,
                                                        0)])
    first = (t - tile_start[source]) * tm                # rank of its row 0
    count = jnp.where(used, jnp.clip(sent[source] - first, 0, tm), 0)
    # Of every row of the layout: the pair it holds.
    rank = first[:, None] + jnp.arange(tm, dtype=jnp.int32)[None, :]
    held = used[:, None] & (rank < sent[source][:, None])
    pair = order[jnp.clip(run_start[source][:, None] + rank, 0, P - 1)]
    xin = jnp.where(held[..., None], xt[pair // k], 0).astype(xt.dtype)
    y = ffn(xin, count, source)
    # Back to the tokens: pair p sits at its expert's run, its slot on.
    at = jnp.where(expert < NE,
                   tile_start[jnp.minimum(expert, NE - 1)] * tm + slot, 0)
    got = y.reshape(NT * tm, H)[at].reshape(T, k, H)
    out = jnp.sum(got.astype(jnp.float32)
                  * jnp.where(takes, top_w, 0.0)[..., None], axis=1)
    return out, tiles
