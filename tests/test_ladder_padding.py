"""A ladder's wholly padded chunks compute nothing, in every family.

A chunked admission runs every chunk of its power-of-two bucket. The
``mid`` and ``final`` chunk programs (serve/scheduler.py
``_make_prefill_chunk_program._fwd``) run their forward under one
``lax.cond`` on whether the chunk holds a real position of any row, a
test of the admission buffer and of no model. Here, through the
scheduler's own programs on the CPU, for the registered test size of
every family that ladders:

- a prompt that ends in chunk 2 of a 4-chunk bucket gives the same
  first token, the same next 8 decoded tokens, the same ``lengths``,
  page table and state pool rows, bit for bit, as the same ladder with
  the ``cond`` forced true, and its padded chunks leave the carry as
  they took it;
- beside a long row the forward runs wherever that row has a position;
- a dummy entry (row ``num_slots``) never makes a chunk real.

And on the host: ``serve_prefill_chunks_padded_total`` counts the
dispatches whose offset lay at or past every row's suffix length.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from p2p_llm_chat_tpu.models import family_for
from p2p_llm_chat_tpu.models.configs import get_config
from p2p_llm_chat_tpu.serve import scheduler as sched_mod
from p2p_llm_chat_tpu.serve.backend import (GenerateOptions, GenerateRequest,
                                            RequestStats)
from p2p_llm_chat_tpu.serve.scheduler import BatchScheduler
from p2p_llm_chat_tpu.tokenizer import ByteTokenizer

from solo import ladder, on_loop

FAMILIES = ("tiny", "tiny-moe", "tiny-olmoe", "tiny-pangu",
            "tiny-nemotron-h", "tiny-phi4flash", "tiny-mellum2", "tiny-lfm2",
            "tiny-keye")
C, S, R = 32, 128, 2            # a bucket of four chunks, two entries
SHORT, LONG = 40, 100           # end in chunk 2 and in chunk 4
WINDOW, PAGE, NEW = 256, 16, 8
# case -> the prompt lengths its ladder carries
CASES = {"short-alone": (SHORT,), "short-and-long": (SHORT, LONG),
         "dummies": ()}


def _host(tree):
    return [np.asarray(a) for a in jax.tree.leaves(tree)]


class _Ladders:
    """One scheduler of a family, and its ladders as the loop would
    dispatch them, chunk by chunk, with the ``cond`` as it is and
    forced true."""

    def __init__(self, family: str) -> None:
        cfg = get_config(family)
        params = family_for(cfg).init_params_quantized(
            cfg, jax.random.PRNGKey(4), dtype=jnp.float32)
        self.sched = BatchScheduler(
            params, cfg, ByteTokenizer(vocab_size=cfg.vocab_size),
            num_slots=4, max_seq=WINDOW, page_size=PAGE, kv_quant=True,
            decode_fuse_max=1, prefill_chunk=C)
        self.programs = {False: {}, True: {}}
        self.ran: dict = {}

    def run(self, case: str, forced: bool) -> dict:
        if (case, forced) not in self.ran:
            with pytest.MonkeyPatch.context() as mp:
                if forced:
                    mp.setattr(sched_mod, "_unless_padding",
                               lambda real, run, *carried: run(*carried))
                self.ran[case, forced] = on_loop(
                    self.sched, lambda: self._ladder(CASES[case], forced))
        return self.ran[case, forced]

    def _ladder(self, lens: tuple, forced: bool) -> dict:
        sched = self.sched
        sched._prefill_chunk_programs = self.programs[forced]
        rng = np.random.default_rng(11)
        rows, carries, first = ladder(
            sched, [rng.integers(3, sched.config.vocab_size, n)
                    for n in lens], S, R)
        out = {"carries": [_host(c) for c in carries], "first": first,
               "lengths": np.asarray(sched._cache.lengths)[rows],
               "tables": np.asarray(sched._cache.page_table)[rows],
               "state": [a[:, rows] if a.ndim > 1 else a
                         for a in _host(sched._cache.state)]}
        active = jnp.asarray([r in rows for r in range(sched.num_slots)])
        decoded = []
        for _ in range(NEW if rows else 0):
            (got, sched._next_dev, sched._cache, sched._keys,
             sched._ring_dev) = sched._decode_for(WINDOW)(
                sched._params, sched._next_dev, sched._cache, active,
                sched._temps_dev, sched._top_ks_dev, sched._top_ps_dev,
                sched._keys, sched._ring_dev, sched._rps_dev)
            decoded.append(np.asarray(got)[:sched.num_slots][rows])
        out["decoded"] = np.asarray(decoded)
        for row in rows:                       # the next ladder starts clean
            sched._cache = sched._zero_row_j(sched._cache, row)
        return out


@pytest.fixture(scope="module")
def ladders():
    """One family's scheduler at a time: the cases run family by
    family, and a family's scheduler stops when the next is asked for."""
    live: dict = {}

    def of(family: str) -> _Ladders:
        if family not in live:
            for held in live.values():
                held.sched.stop()
            live.clear()
            live[family] = _Ladders(family)
        return live[family]

    yield of
    for held in live.values():
        held.sched.stop()


def _same(a: list, b: list) -> bool:
    return len(a) == len(b) and all(
        np.array_equal(x, y) for x, y in zip(a, b))


@pytest.mark.parametrize("case", CASES)
@pytest.mark.parametrize("family", FAMILIES)
def test_a_padded_chunk_computes_nothing_and_changes_no_answer(
        ladders, family, case):
    held = ladders(family)
    got, want = held.run(case, False), held.run(case, True)
    after_first, after_2, after_3 = got["carries"]
    if case == "dummies":
        # What warm-up dispatches: every entry the sentinel row, its
        # one-token prompt no real position. Neither chunk behind the
        # first runs; forced, they would have.
        assert _same(after_first, after_2) and _same(after_2, after_3)
        assert not _same(want["carries"][0], want["carries"][1])
        return
    for key in ("first", "decoded", "lengths", "tables"):
        np.testing.assert_array_equal(got[key], want[key], err_msg=key)
    assert _same(got["state"], want["state"])
    assert got["lengths"].tolist() == list(CASES[case])
    assert got["decoded"].shape == (NEW, len(CASES[case]))
    # Chunk 2 holds the short row's end: it runs.
    assert not _same(after_first, after_2)
    if case == "short-alone":
        # Chunk 3 is padding alone, the dummy entry behind the request
        # included: the carry and the carried logits as they came.
        assert _same(after_2, after_3)
        assert not _same(want["carries"][1], want["carries"][2])
    else:
        # Chunk 3 holds positions of the long row alone: it runs, and
        # what it leaves is what the forced ladder leaves.
        assert not _same(after_2, after_3)
        assert _same(after_3, want["carries"][2])


# -- the host's count ----------------------------------------------------------

def test_padded_dispatches_are_counted_where_they_are_dispatched():
    """A prompt of 3 chunks in a bucket of 8 (the one bucket a warm-up
    compiled: a prompt takes the smallest warmed bucket that fits): the
    ladder dispatches all eight, and five of them lay past the prompt."""
    cfg = get_config("tiny")
    params = family_for(cfg).init_params(cfg, jax.random.PRNGKey(0),
                                         dtype=jnp.float32)
    tok = ByteTokenizer(vocab_size=cfg.vocab_size)
    sched = BatchScheduler(params, cfg, tok, num_slots=2, max_seq=256,
                           prefill_chunk=32)
    sched._warmed_buckets = [256]
    try:
        prompt = "p" * 75
        n = len(tok.encode(prompt, add_bos=True))
        assert 2 * 32 < n <= 3 * 32
        req = GenerateRequest(prompt=prompt, options=GenerateOptions(
            max_tokens=2, temperature=0.0))
        "".join(sched.submit(req, RequestStats()))
        snap = sched.metrics_snapshot()
        assert snap["prefill_chunks_total"] == 8
        assert snap["serve_prefill_chunks_padded_total"] == 5
        # The positions dispatched keep their meaning: all eight chunks.
        assert snap["serve_prefill_tokens_padded_total"] == 8 * 32
    finally:
        sched.stop()
