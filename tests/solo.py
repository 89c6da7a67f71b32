"""The solo greedy loop the engine tests hold the scheduler to, jitted.

Any request served through the batched, paged, chunked, fused serving
path must stream exactly the tokens a batch-of-one prefill + decode loop
produces for the same prompt. That loop is here once, for every family:
one-shot prefill of the unpadded prompt, then greedy decode steps to a
stop id, either on the model layer's dense cache or with K/V (and the
state, rings or latents a family keeps) spliced into a one-row paged pool.

The family's own ``prefill`` and ``decode_step[_paged]`` are what runs,
unchanged, under ``jax.jit`` with the config closed over: a prompt length
costs one program and a decode step one cached call. Called eagerly they
dispatch every primitive of the forward as a program of its own, again
for every prompt length, which was half of tier-1's wall (ROADMAP D14).

A family's engine test brings one :class:`Solo` (its arguments are what
differs between families), not a copy of this loop.
"""

import threading

import numpy as np

import jax
import jax.numpy as jnp

from p2p_llm_chat_tpu.models.llama import KVCache
from p2p_llm_chat_tpu.ops import state_pool
from p2p_llm_chat_tpu.ops.paged_kv import PagedKVCache, write_prefill_batch
from p2p_llm_chat_tpu.serve import scheduler as sched_mod
from p2p_llm_chat_tpu.serve.backend import (GenerateOptions, GenerateRequest,
                                            RequestStats)

PAGE = 16


def generate(engine, prompt, max_tokens=12, **opts):
    """One request through ``engine``: (text, stats)."""
    stats = RequestStats()
    req = GenerateRequest(prompt=prompt, options=GenerateOptions(
        max_tokens=max_tokens, **opts))
    return "".join(engine.generate_stream(req, stats)), stats


def on_loop(sched, fn):
    """``fn()`` on ``sched``'s own thread, which owns the device buffers
    (the road a warm-up job takes)."""
    box = []
    job = sched_mod._WarmupJob(lambda: box.append(fn()), threading.Event())
    sched._admit_q.put(job)
    assert job.done.wait(600)
    if job.err is not None:
        raise job.err
    return box[0]


def ladder(sched, prompts, S, R):
    """The chunk ladder of ``prompts`` (lists of ids; entry ``i`` takes
    row ``i + 1`` and a row's worth of pages, dummy entries fill up to
    ``R``) through ``sched``'s own chunk programs, dispatched one by
    one as the loop dispatches them. Call it on the loop
    (:func:`on_loop`). Returns (the rows, the carry and carried logits
    behind each chunk but the last as host arrays, the first tokens)."""
    per_row = sched._cache.max_pages_per_row
    slots = []
    for i, ids in enumerate(prompts):
        slot = sched_mod._Slot(
            req=GenerateRequest(prompt="", options=GenerateOptions(
                temperature=0.0)),
            stats=None, out_q=None, seed=i)
        slot.prompt_ids = list(ids)
        slot.pages = list(range(1 + i * per_row, 1 + (i + 1) * per_row))
        slots.append(slot)
    rows = list(range(1, 1 + len(slots)))
    packed = sched._admit_upload(
        sched._admit_host_arrays(slots, rows, S, R, None), live=False)
    kv = logits = toks = None
    carries = []
    for off in range(0, S, sched.prefill_chunk):
        kv, logits, toks = sched._dispatch_prefill_chunk(
            0, S, off, sched.prefill_chunk, packed, kv, logits, None)
        if toks is None:
            carries.append((jax.tree.map(np.asarray, kv),
                            jax.tree.map(np.asarray, logits)))
    return rows, carries, np.asarray(toks)[:len(rows)]


def greedy(last, seen):
    return int(last.argmax())


class Solo:
    """The loop for one (family module, config, tokenizer).

    ``pool``: None runs the model layer's dense cache of ``max_seq``
    rows; "int8" or "float" splices the prefill's cache (sized to the
    prompt) into a one-row paged pool of ``max_seq`` rows in pages of 16
    and decodes through ``decode_step_paged`` — the exact reference of an
    engine on such a pool: the rounding is per (slot, kv-head), so it
    does not depend on what else is in the batch. ``last_only``: the
    prefill computes the last position's logits alone. ``dtype``: of the
    caches (a float pool, the state pool)."""

    def __init__(self, family, config, tok, *, pool=None, max_seq=128,
                 last_only=False, dtype=jnp.float32):
        assert pool in (None, "int8", "float"), pool
        self.family, self.config, self.tok = family, config, tok
        self.pool, self.max_seq, self.dtype = pool, max_seq, dtype
        self.last_only = last_only
        self.stop_ids = set(config.eos_token_ids) | {tok.eos_id}
        self._start = jax.jit(self._start_impl)
        self._step = jax.jit(self._step_impl)

    def _start_impl(self, params, ids):
        """ids [1, n] -> (the last position's logits, the decode cache)."""
        n = ids.shape[1]
        lens = jnp.asarray([n])
        cache = KVCache.create(
            self.config, 1, self.max_seq if self.pool is None else n,
            self.dtype)
        logits, cache = self.family.prefill(
            params, self.config, ids, lens, cache,
            **({"last_only": True} if self.last_only else {}))
        if self.pool is not None:
            pages = self.max_seq // PAGE
            pool = PagedKVCache.create(
                self.config, 1, pages + 1, PAGE, max_pages_per_row=pages,
                dtype=self.dtype, quantized=self.pool == "int8")
            state = cache.state
            cache = write_prefill_batch(
                pool, cache.k, cache.v, jnp.arange(1), lens,
                1 + jnp.arange(pages, dtype=jnp.int32)[None], cache.idx)
            if state is not None:
                cache = cache._replace(state=state_pool.write_rows(
                    cache.state, state, jnp.asarray([0])))
        return logits[0, 0 if self.last_only else n - 1], cache

    def _step_impl(self, params, token, cache):
        if self.pool is None:
            logits, cache = self.family.decode_step(
                params, self.config, token, cache)
        else:
            logits, cache = self.family.decode_step_paged(
                params, self.config, token, cache,
                pages=self.max_seq // PAGE)
        return logits[0, 0], cache

    def tokens(self, params, ids, max_new, pick=greedy):
        """Token ids the loop emits after ``ids``. ``pick(logits, seen)``
        chooses each one from the float32 logits and the ids so far
        (prompt and output); the default is the argmax."""
        last, cache = self._start(params, jnp.asarray([ids]))
        seen, out = list(ids), []
        for _ in range(max_new):
            t = pick(np.asarray(last, np.float32), seen)
            if t in self.stop_ids:
                break
            out.append(t)
            seen.append(t)
            last, cache = self._step(params, jnp.asarray([[t]]), cache)
        return out

    def __call__(self, params, prompt, max_new, pick=greedy):
        """The text an engine must stream for ``prompt``."""
        ids = self.tok.encode(prompt, add_bos=True)
        return self.tok.decode(self.tokens(params, ids, max_new, pick))


def jit_model(fn, config, **static):
    """A whole-model entry point ``fn(params, config, *arrays, **static)``
    as one program, the config and the keyword arguments closed over: for
    a test that calls one outside a :class:`Solo`. The function is not
    rewritten, only lowered whole, so a comparison of two entry points
    stays a comparison of two lowerings."""
    return jax.jit(lambda params, *arrays: fn(params, config, *arrays,
                                              **static))
