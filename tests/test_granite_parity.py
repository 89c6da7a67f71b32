"""models/nemotron_h.py as the Granite 4.0-H stack (``M``, ``*`` and ``-``
with four scalars) against the plain reference of
benchmark/architectures/granite_hybrid.py (float32, the Mamba layer as
the sequential recurrence one position at a time, no cache, no kernels),
at test size on seeded random weights: int8 weights dequantise exactly,
so under float32 activations what is left is arithmetic order, and with
an int8 pool the cache's rounding.

The full forward; a chunk ladder with carried state = one piece; a
prefix hit that starts from a snapshot; prefill then decode through both
pools, plain and fused; one padded admission program = each row's
unpadded run; each of the four scalars dropped from the PROGRAM fails the
comparison; every wrong model of the reference fails ``compare``; and the
walk of the published 80-letter pattern and of a 2-round cut of it."""

import os
import sys
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark import manifest, reference  # noqa: E402
from p2p_llm_chat_tpu.models import nemotron_h  # noqa: E402
from p2p_llm_chat_tpu.models.configs import (GRANITE_H_PERIOD,  # noqa: E402
                                             get_config)
from p2p_llm_chat_tpu.models.llama import KVCache  # noqa: E402
from p2p_llm_chat_tpu.ops import state_pool  # noqa: E402
from p2p_llm_chat_tpu.ops.paged_kv import (PageAllocator,  # noqa: E402
                                           PagedKVCache, set_row_table,
                                           write_prefill_batch)

from solo import jit_model  # noqa: E402

CFG = get_config("tiny-granite-h")
# The published key names of the same model, as the reference reads them.
KEYS = {"name": "tiny-granite-h", "hidden_size": 256, "vocab_size": 512,
        "num_hidden_layers": 8,
        "layer_types": ["mamba", "mamba", "attention", "mamba"] * 2,
        "intermediate_size": 192, "shared_intermediate_size": 192,
        "num_local_experts": 0, "num_experts_per_tok": 0,
        "position_embedding_type": "nope",
        "mamba_n_heads": 4, "mamba_d_head": 16, "mamba_n_groups": 1,
        "mamba_d_state": 16, "mamba_d_conv": 4, "mamba_chunk_size": 16,
        "num_attention_heads": 4, "num_key_value_heads": 2,
        "rope_theta": 10000.0, "rms_norm_eps": 1e-5,
        "embedding_multiplier": 6.0, "residual_multiplier": 0.3,
        "attention_multiplier": 0.03125, "logits_scaling": 4.0,
        "max_position_embeddings": 256, "tie_word_embeddings": True,
        # The check's long sample at this size: 4 x 16 + 11 positions.
        "stack": {"SERVE_PREFILL_CHUNK": "16"}}
B, P, D = 2, 40, 8
PS, PER_ROW = 16, 4
prefill = jit_model(nemotron_h.prefill, CFG)
prefill_last = jit_model(nemotron_h.prefill, CFG, last_only=True)
decode_step = jit_model(nemotron_h.decode_step_paged, CFG, pages=PER_ROW)


@pytest.fixture(scope="module")
def arch():
    return manifest.load_architecture(os.path.join(ROOT, "benchmark"),
                                      "granite_hybrid")


SLOTS = 7


def fake_sched(params, config=CFG, **kw):
    """What the architecture file reads of a scheduler: the programs'
    arguments, and the idle pool of ``SLOTS`` rows that the check decodes
    in, with its allocator and the row-release program."""
    pool = PagedKVCache.create(config, SLOTS, 64, PS, max_pages_per_row=8,
                               dtype=jnp.float32, quantized=True)
    return types.SimpleNamespace(
        _params=params, config=config, mesh=None, _model=nemotron_h,
        _dtype=jnp.float32, page_size=PS, kv_quant=True, prefill_chunk=16,
        num_slots=SLOTS, decode_fuse_max=4, _slots=[None] * SLOTS,
        _cache=pool, _alloc=PageAllocator(64, PS),
        _zero_row_j=jax.jit(lambda c, row: set_row_table(
            c, row, jnp.zeros((c.page_table.shape[1],), jnp.int32))), **kw)


@pytest.fixture(scope="module")
def setup(arch):
    params = nemotron_h.init_params_quantized(CFG, jax.random.PRNGKey(7),
                                              dtype=jnp.float32)
    tokens = jnp.asarray(np.random.default_rng(0).integers(
        0, CFG.vocab_size, (B, P + D)), jnp.int32)
    weights = arch.engine_weights(fake_sched(params))
    ref, facts = arch.forward(KEYS, tokens, weights)
    return params, tokens, ref, facts, weights


def pools_from(carry, quantized, lens=None):
    pool = PagedKVCache.create(CFG, B, 1 + B * PER_ROW, PS,
                               max_pages_per_row=PER_ROW, dtype=jnp.float32,
                               quantized=quantized)
    tables = 1 + jnp.arange(B * PER_ROW, dtype=jnp.int32).reshape(B, PER_ROW)
    lens = jnp.full((B,), P, jnp.int32) if lens is None else lens
    pool = write_prefill_batch(pool, carry.k, carry.v, jnp.arange(B), lens,
                               tables)
    return pool._replace(state=state_pool.write_rows(
        pool.state, carry.state, jnp.arange(B)))


def one_shot(params, tokens, n=P):
    cache = KVCache.create(CFG, B, n, dtype=jnp.float32)
    return prefill(params, tokens[:, :n], jnp.full((B,), n, jnp.int32),
                   cache)


def worst(a, b):
    return float(jnp.max(reference.position_errors(a, b)))


def close(a, b, tol=2e-3):
    assert worst(a, b) < tol, worst(a, b)


def test_model_config_from_the_published_keys(arch):
    kw = arch.model_config(KEYS)
    built = CFG.with_(**{k: v for k, v in kw.items()
                         if k not in ("eos_token_ids", "bos_token_id")})
    assert built == CFG
    assert CFG.kv_paired and CFG.cache_layers == 2 and CFG.ssm_layers == 6
    for key in ("embedding_multiplier", "residual_multiplier",
                "attention_multiplier", "logits_scaling"):
        assert kw[key] == KEYS[key]


def test_the_named_preset_is_the_benchmarks_configuration(arch):
    """``granite-4.0-h-micro`` as models/configs.py registers it is what
    the benchmark's file builds through the architecture file."""
    import json
    from p2p_llm_chat_tpu.models.configs import ModelConfig
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "granite-4.0-h-micro.json")) as f:
        cfg = json.load(f)
    kw = arch.model_config(cfg)
    preset = get_config("granite-4.0-h-micro")
    assert ModelConfig(**{**kw, "bos_token_id": preset.bos_token_id,
                          "eos_token_ids": preset.eos_token_ids}) == preset
    assert preset.hybrid_pattern == GRANITE_H_PERIOD * 4
    assert [i for i, ch in enumerate(preset.hybrid_pattern[::2])
            if ch == "*"] == [5, 15, 25, 35]


def test_full_forward_is_the_reference(setup):
    params, tokens, ref, _, _ = setup
    logits, _ = one_shot(params, tokens, P + D)
    close(logits, ref)


@pytest.mark.parametrize("edges", [(0, 40), (0, 13, 40), (0, 24, 31, 40),
                                   (0, 16, 32, 40)])
def test_chunk_ladder_with_carry_is_one_piece(setup, edges):
    """Prompt length 40 and chunk edges that are no multiple of the SSD
    block (16): logits, K/V, state and window."""
    params, tokens, ref, _, _ = setup
    _, whole = one_shot(params, tokens)
    carry = KVCache.create(CFG, B, P, dtype=jnp.float32)
    out = []
    for lo, hi in zip(edges, edges[1:]):
        logits, carry = jit_model(nemotron_h.prefill_chunk, CFG, offset=lo)(
            params, tokens[:, lo:hi], carry)
        out.append(logits)
    close(jnp.concatenate(out, axis=1), ref[:, :P])
    for got, want in ((carry.k, whole.k), (carry.state.ssm, whole.state.ssm),
                      (carry.state.conv, whole.state.conv)):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-3, atol=1e-4)


def test_prefix_hit_with_a_snapshot_is_the_whole_prompt(setup):
    """The scheduler's prefix admission: a 16-token head prefilled alone,
    its K and V and a SNAPSHOT of its state kept (a prefix entry); two
    suffixes then start from the snapshot at the carry's last slots
    (``forward_counted``) and must read what the whole prompt reads."""
    params, tokens, _, _, _ = setup
    H = 16
    head = jnp.broadcast_to(tokens[:1, :H], (B, H))
    whole = tokens.at[:, :H].set(head)
    want, _ = one_shot(params, whole)
    entry = KVCache.create(CFG, 1, H, dtype=jnp.float32)
    _, entry = prefill(params, head[:1], jnp.asarray([H], jnp.int32), entry)
    snap = state_pool.snapshot(entry.state)
    assert snap.ssm.shape == (CFG.ssm_layers,) + CFG.ssm_state_shape
    carry = KVCache.create(CFG, B, P, dtype=jnp.float32)
    carry = carry._replace(
        k=carry.k.at[:, :, :H].set(entry.k), v=carry.v.at[:, :, :H].set(
            entry.v), state=state_pool.from_snapshot(snap, B))
    valid = jnp.ones((B, P - H), bool)
    logits, carry, _ = jit_model(nemotron_h.forward_counted, CFG)(
        params, whole[:, H:P], None, carry, None, valid)
    close(logits, want[:, H:])
    _, full = one_shot(params, whole)
    np.testing.assert_allclose(np.asarray(carry.state.ssm),
                               np.asarray(full.state.ssm), rtol=1e-3,
                               atol=1e-4)


def test_padded_admission_is_each_rows_unpadded_run(setup):
    """One program over rows of different lengths, padded to a bucket of
    64 and to three rows (the third a dummy entry): each row's state and
    window equal its own unpadded run's, and the dummy's stay zero."""
    params, tokens, _, _, _ = setup
    lens = jnp.asarray([23, 40, 1], jnp.int32)
    padded = jnp.zeros((3, 64), jnp.int32).at[:2, :P].set(tokens[:, :P])
    padded = padded.at[0, 23:].set(7)       # junk behind row 0's prompt
    valid = (jnp.arange(64)[None, :] < lens[:, None]) & jnp.asarray(
        [True, True, False])[:, None]
    cache = KVCache.create(CFG, 3, 64, dtype=jnp.float32)
    logits, cache, _ = jit_model(nemotron_h.prefill_counted, CFG,
                                 last_only=True)(
        params, padded, lens, cache, valid)
    for row, n in ((0, 23), (1, 40)):
        solo = KVCache.create(CFG, 1, n, dtype=jnp.float32)
        want, solo = prefill_last(params, tokens[row: row + 1, :n],
                                  jnp.asarray([n]), solo)
        close(logits[row: row + 1], want)
        np.testing.assert_allclose(np.asarray(cache.state.ssm[:, row]),
                                   np.asarray(solo.state.ssm[:, 0]),
                                   rtol=1e-3, atol=1e-4)
    assert not np.asarray(cache.state.ssm[:, 2]).any()
    assert not np.asarray(cache.state.conv[:, 2]).any()


@pytest.mark.parametrize("quantized,tol", [(False, 2e-3), (True, 0.05)])
def test_decode_through_both_pools_is_the_reference(setup, quantized, tol):
    params, tokens, ref, _, _ = setup
    _, carry = one_shot(params, tokens)
    pool = pools_from(carry, quantized)
    out = []
    for t in range(P, P + D):
        logits, pool = decode_step(params, tokens[:, t: t + 1], pool)
        out.append(logits)
    close(jnp.concatenate(out, axis=1), ref[:, P:], tol)
    assert list(np.asarray(pool.lengths)) == [P + D] * B


def test_fused_decode_is_the_plain_steps(setup):
    params, tokens, _, _, _ = setup
    _, carry = one_shot(params, tokens)

    def greedy(logits, state, emit_pos, act):
        return jnp.argmax(logits, -1).astype(jnp.int32), state

    plain, toks, tok = pools_from(carry, True), [], tokens[:, P: P + 1]
    for _ in range(4):
        logits, plain = decode_step(params, tok, plain)
        tok = jnp.argmax(logits[:, 0], -1).astype(jnp.int32)[:, None]
        toks.append(tok[:, 0])
    fused = jit_model(
        nemotron_h.decode_fused, CFG, num_steps=4, sample_fn=greedy,
        sample_state=(), stop_ids=(), pages=PER_ROW)(
            params, tokens[:, P: P + 1], pools_from(carry, True))
    assert np.array_equal(np.asarray(fused[0]), np.asarray(jnp.stack(toks)))
    np.testing.assert_allclose(np.asarray(fused[3].state.ssm),
                               np.asarray(plain.state.ssm), rtol=1e-5,
                               atol=1e-6)


def test_system_logits_and_compare_pass_the_sound_program(setup, arch):
    params, tokens, ref, facts, _ = setup
    sched = fake_sched(params)
    system = arch.system_logits(sched, tokens, P)
    assert system.logits.shape == ref.shape
    assert system.long_logits.shape == facts["long_logits"].shape
    got = arch.compare(system, ref, {**facts, "n_prefill": P}, KEYS)
    assert got["ok"], got
    assert got["state_error"] < 1e-4 and got["median"] < 0.02
    assert got["long_median"] < 0.02
    # The scheduler's pool is handed back as a finished request leaves
    # it: every page free, no row holding a table or a length; the rows
    # the check took (first, middle, last) hold the state it left.
    assert sched._alloc.free_pages == 63
    assert not np.asarray(sched._cache.page_table).any()
    ssm = np.asarray(sched._cache.state.ssm)
    moved = [r for r in range(SLOTS) if ssm[:, r].any()]
    assert moved == [0, 3, 6]
    np.testing.assert_array_equal(ssm[0, 6], np.asarray(system.state))


def test_the_check_sees_a_fault_of_the_many_row_programs(setup, arch,
                                                         monkeypatch):
    """What the check exists for: a step that moves a PARKED row's state
    into a live one (a wrong mask, a garbage row at ``num_slots``) fails
    it, as does a live request in the pool it would borrow."""
    params, tokens, ref, facts, _ = setup
    step = nemotron_h.decode_step_paged_touched

    def shifted(params, config, toks, cache, *a, **kw):
        logits, cache, stats = step(params, config, toks, cache, *a, **kw)
        return logits, cache._replace(state=cache.state._replace(
            ssm=jnp.roll(cache.state.ssm, 1, axis=1))), stats
    monkeypatch.setattr(nemotron_h, "decode_step_paged_touched", shifted)
    got = arch.compare(arch.system_logits(fake_sched(params), tokens, P),
                       ref, {**facts, "n_prefill": P}, KEYS)
    assert not got["ok"], got
    monkeypatch.undo()
    busy = fake_sched(params)
    busy._slots[2] = object()
    with pytest.raises(ValueError, match="no request may be live"):
        arch.system_logits(busy, tokens, P)


# Each scalar dropped from the PROGRAM (its neutral value in the
# configuration), one at a time, and a softmax scale of 1 / sqrt(64):
# prefill and paged decode both leave the reference, which the sound
# program keeps to 2e-3. The softmax scale moves two attention layers of
# sixteen residual steps, so it moves the logits least (2.4% here); the
# check holds it by its own edge (the test behind this one).
DROPPED = {"embedding_multiplier": 1.0, "residual_multiplier": 1.0,
           "attention_multiplier": 0.0, "logits_scaling": 1.0}
MOVES = {"attention_multiplier": 0.01}


@pytest.mark.parametrize("key", sorted(DROPPED))
def test_a_program_without_one_scalar_fails(setup, key):
    params, tokens, ref, _, _ = setup
    assert getattr(CFG, key) != DROPPED[key]
    cfg = CFG.with_(**{key: DROPPED[key]})
    cache = KVCache.create(cfg, B, P, dtype=jnp.float32)
    logits, _ = jit_model(nemotron_h.prefill, cfg)(
        params, tokens[:, :P], jnp.full((B,), P, jnp.int32), cache)
    sound, _ = one_shot(params, tokens)
    assert worst(sound, ref[:, :P]) < 2e-3
    assert worst(logits, ref[:, :P]) > MOVES.get(key, 0.05), key
    # The decode step of the program without it, from the SOUND carry.
    _, carry = one_shot(params, tokens)
    step, _ = jit_model(nemotron_h.decode_step_paged, cfg, pages=PER_ROW)(
        params, tokens[:, P: P + 1], pools_from(carry, False))
    good, _ = decode_step(params, tokens[:, P: P + 1],
                          pools_from(carry, False))
    assert worst(good, ref[:, P: P + 1]) < 2e-3
    assert worst(step, ref[:, P: P + 1]) > MOVES.get(key, 0.05), key


def test_a_softmax_scale_of_rsqrt_head_dim_stands_across_the_edge(setup,
                                                                  arch):
    """The check's own verdict on a program that serves ``1 / sqrt(64)``:
    it stands on the neighbour (``scale_edge`` 1), the sound one on the
    reference (0), whatever the rounding between them."""
    params, tokens, ref, facts, _ = setup
    sound = arch.compare(arch.system_logits(fake_sched(params), tokens, P),
                         ref, {**facts, "n_prefill": P}, KEYS)
    assert sound["ok"] and abs(sound["scale_edge"]) < 0.01
    cfg = CFG.with_(attention_multiplier=0.0)
    got = arch.compare(
        arch.system_logits(fake_sched(params, cfg), tokens, P), ref,
        {**facts, "n_prefill": P}, KEYS)
    assert not got["ok"] and abs(got["scale_edge"] - 1) < 0.01, got


def test_neutral_scalars_leave_the_program_text_alone():
    """The defaults are identities that are not even traced: another
    family's program is the text it was (tests/test_program_hashes.py
    pins the hashes; this pins the reason)."""
    cfg = get_config("tiny-nemotron-h")
    assert (cfg.embedding_multiplier, cfg.residual_multiplier,
            cfg.attention_multiplier, cfg.logits_scaling) == (1.0, 1.0,
                                                              0.0, 1.0)
    params = jax.eval_shape(lambda: nemotron_h.init_params(
        cfg, jax.random.PRNGKey(0), dtype=jnp.float32))
    cache = jax.eval_shape(lambda: KVCache.create(cfg, 1, 16,
                                                  dtype=jnp.float32))
    text = jax.jit(lambda p, t, c: nemotron_h.prefill(
        p, cfg, t, jnp.asarray([16]), c)).lower(
            params, jax.ShapeDtypeStruct((1, 16), jnp.int32),
            cache).as_text()
    scaled = jax.jit(lambda p, t, c: nemotron_h.prefill(
        p, cfg.with_(residual_multiplier=0.5), t, jnp.asarray([16]),
        c)).lower(params, jax.ShapeDtypeStruct((1, 16), jnp.int32),
                  cache).as_text()
    assert text != scaled
    assert "5.000000e-01" in scaled and "5.000000e-01" not in text


@pytest.mark.parametrize("name", [
    "bf16_state", "scale_rsqrt_d", "residual_one", "embedding_unscaled",
    "logits_undivided", "no_d_skip", "no_conv_bias", "norm_before_gate",
    "norm_by_head", "int4_weights"])
def test_every_wrong_model_fails_compare(setup, arch, name):
    params, tokens, ref, facts, weights = setup
    system = arch.SystemOut(logits=ref, long_logits=facts["long_logits"],
                            state=facts["state"])
    sound = arch.compare(system, ref, {**facts, "n_prefill": P}, KEYS)
    assert sound["ok"] and sound["state_error"] < 1e-6 \
        and sound["scale_edge"] == 0
    wcfg, w = arch.wrong_models(KEYS, weights)[name]
    wrong_ref, wfacts = arch.forward(wcfg, tokens, w)
    got = arch.compare(system, wrong_ref, {**wfacts, "n_prefill": P}, KEYS)
    if name == "bf16_state":
        # The limit on the state is the one that sees it: set on the chip
        # over 1,208 positions and 16 slow heads (the architecture file
        # has the readings); over this test's 83 positions and one head
        # the drift is smaller, and far above the sound program's.
        assert got["state_error"] > 5e-4, got["state_error"]
        assert got["long_median"] < arch.TOL_MEDIAN   # the logits cannot
        return
    assert not got["ok"], got


def test_the_published_pattern_is_one_scan_over_four_periods():
    full = GRANITE_H_PERIOD * 4
    assert len(full) == 80 and full.count("M") == 36 \
        and full.count("*") == 4 and full.count("-") == 40
    assert nemotron_h._rounds(full) == (GRANITE_H_PERIOD, 4)
    assert nemotron_h._segments(full) == ((GRANITE_H_PERIOD, 4),)
    # A 2-round cut of it is the same body, walked twice.
    assert nemotron_h._segments(GRANITE_H_PERIOD * 2) == (
        (GRANITE_H_PERIOD, 2),)
    # Inside a period, the runs of ``M-`` between the attention layers
    # are scans of their own: five before, four behind (the MLP behind
    # the attention layer opens the second run's groups).
    assert [(letters, n) for letters, n, _ in
            nemotron_h._plan(GRANITE_H_PERIOD)] == [
                ("M-", 5), ("*", 1), ("-M", 4), ("-", 1)]
    # The test size walks the same three kinds of step.
    assert nemotron_h._segments(CFG.hybrid_pattern) == (("M-M-*-M-", 2),)


def test_a_two_round_cut_of_the_published_pattern_is_the_reference(arch):
    """Twenty published layers at test widths: the walk indexes 18 Mamba
    layers, 2 page layers and 20 MLPs as the published pattern does, a
    scan over two periods."""
    cfg = CFG.with_(name="tiny-granite-h-2r", num_layers=20,
                    hybrid_pattern=GRANITE_H_PERIOD * 2)
    keys = {**KEYS, "name": cfg.name, "num_hidden_layers": 20,
            "layer_types": (["mamba"] * 5 + ["attention"]
                            + ["mamba"] * 4) * 2}
    assert arch.pattern(keys) == cfg.hybrid_pattern
    params = nemotron_h.init_params_quantized(cfg, jax.random.PRNGKey(3),
                                              dtype=jnp.float32)
    tokens = jnp.asarray(np.random.default_rng(1).integers(
        0, cfg.vocab_size, (1, 24)), jnp.int32)
    ref, _ = arch._stack(keys, tokens,
                         arch.engine_weights(fake_sched(params, cfg)))
    cache = KVCache.create(cfg, 1, 24, dtype=jnp.float32)
    logits, _ = jit_model(nemotron_h.prefill, cfg)(
        params, tokens, jnp.asarray([24], jnp.int32), cache)
    close(logits, ref)
