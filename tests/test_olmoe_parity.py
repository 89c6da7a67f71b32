"""OLMoE's block on the normal path against its plain reference.

``tiny-olmoe`` (MHA, whole-projection QK-norm, 8 experts top-4 without
renormalisation) runs prefill, then cached decode, through the program's
own functions: the dense ``KVCache`` and the paged int8-free
``PagedKVCache``; float32, bf16 and int8 trees; fused and unfused
``wqkv``; ``tp`` 1 and 2. Every position's logits are compared with the
full forward pass of benchmark/architectures/olmoe.py (float32, no
cache, no kernels), which is handed the same weights. All weights are
random, the two norm vectors too, so that a norm left out cannot pass;
the three wrong models (a renormalised router, no ``q_norm``, no
``k_norm``) each fail the comparison the right one passes.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import manifest, reference
from benchmark.architectures import mistral
from p2p_llm_chat_tpu.models import family_for, mixtral
from p2p_llm_chat_tpu.models.configs import get_config
from p2p_llm_chat_tpu.models.llama import KVCache, fuse_tp_for
from p2p_llm_chat_tpu.models.quant import quantize_params
from p2p_llm_chat_tpu.ops.paged_kv import PagedKVCache, write_prefill_batch
from p2p_llm_chat_tpu.parallel.mesh import MeshConfig, make_mesh
from p2p_llm_chat_tpu.parallel.sharding import shard_params

from solo import jit_model

CFG = get_config("tiny-olmoe")
OLMOE = manifest.load_architecture(manifest.REPO + "/benchmark", "olmoe")
# The published key names of tiny-olmoe, as a configuration file has them.
PUBLISHED = {
    "name": "tiny-olmoe", "model_type": "olmoe", "norm_topk_prob": False,
    "hidden_size": CFG.hidden_size,
    "intermediate_size": CFG.intermediate_size,
    "num_hidden_layers": CFG.num_layers,
    "num_attention_heads": CFG.num_heads,
    "num_key_value_heads": CFG.num_kv_heads, "vocab_size": CFG.vocab_size,
    "max_position_embeddings": CFG.max_seq_len,
    "rope_theta": CFG.rope_theta, "rms_norm_eps": CFG.rms_norm_eps,
    "num_experts": CFG.num_experts,
    "num_experts_per_tok": CFG.num_experts_per_tok,
}
B, P, D = 2, 24, 6          # sequences, prefill tokens, decode steps
# Median position error (reference.position_errors) the right model stays
# under: float32 against float32 differs by summation order alone; a bf16
# or int8 tree (the reference is handed the same dequantised weights)
# adds bf16's rounding of activations, well inside the benchmark's own
# limit (reference.TOL_MEDIAN, 4.5%), which every wrong model exceeds:
# at this size by 5.7% (renormalised router) and far more (a norm left
# out); the right one, in float32, stays 400 times below it.
TOL = {"float32": 1e-4, "bfloat16": reference.TOL_MEDIAN,
       "int8": reference.TOL_MEDIAN}
WRONG_AT_LEAST = reference.TOL_MEDIAN


def test_registered_as_published_and_routed_through_mixtral():
    big = get_config("olmoe-1b-7b")
    assert family_for(big) is mixtral and family_for(CFG) is mixtral
    assert (big.num_layers, big.hidden_size, big.num_heads,
            big.num_kv_heads, big.head_dim) == (16, 2048, 16, 16, 128)
    assert (big.num_experts, big.num_experts_per_tok,
            big.intermediate_size, big.vocab_size) == (64, 8, 1024, 50304)
    assert big.qk_norm_whole and not big.moe_renormalize
    # Dropless, as published (models/configs.py says what a factor lost).
    assert big.moe_capacity_factor is None and not big.tie_embeddings
    assert serve_config(PUBLISHED) == CFG.with_(
        name="tiny-olmoe", bos_token_id=1, eos_token_ids=(),
        moe_capacity_factor=None)
    # Every configuration registered before keeps both defaults.
    for name in ("tiny", "tiny-moe", "mixtral-8x7b", "llama3.1-8b"):
        old = get_config(name)
        assert old.moe_renormalize and not old.qk_norm_whole


def serve_config(published: dict):
    from benchmark import serve_cell
    return serve_cell.model_config({**published, "architecture": "olmoe"})


def random_params(dtype):
    """Every leaf random, the two norm vectors too (the initialiser
    draws them from [0.5, 1.5): ones would be nearly the identity)."""
    params = mixtral.init_params(CFG, jax.random.PRNGKey(5), dtype=dtype)
    for name in ("q_norm", "k_norm"):
        assert float(jnp.std(params["layers"][name].astype(
            jnp.float32))) > 0.2
    return params


def _deq(w, *at):
    if hasattr(w, "q"):
        return w.q[at].astype(jnp.float32) * w.s[at].astype(jnp.float32)
    return w[at].astype(jnp.float32)


def reference_weights(params, tp: int = 1):
    """What olmoe.forward is handed, read from any of the trees this
    file serves from: plain or quantised leaves, ``wqkv`` / ``wgu_e``
    fused (under ``tp`` device blocks) or not."""
    L = params["layers"]
    Q, KV, F = CFG.q_dim, CFG.kv_dim, CFG.intermediate_size

    def layer(l):
        w = {k: _deq(L[k], l) for k in ("attn_norm", "mlp_norm", "q_norm",
                                        "k_norm", "wo", "router")}
        if "wqkv" in L:
            w["wq"], w["wk"], w["wv"] = mistral.unfuse(
                _deq(L["wqkv"], l), (Q, KV, KV), tp)
        else:
            w.update({k: _deq(L[k], l) for k in ("wq", "wk", "wv")})
        return w

    def expert(l, e):
        if "wgu_e" in L:
            gate, up = mistral.unfuse(_deq(L["wgu_e"], l, e), (F, F), 1)
        else:
            gate, up = _deq(L["w_gate"], l, e), _deq(L["w_up"], l, e)
        return gate, up, _deq(L["w_down"], l, e)

    head = params["lm_head"]
    return mistral.Weights(
        embed=params["embed"], layer=layer, expert=expert,
        final_norm=params["final_norm"].astype(jnp.float32),
        lm_head=_deq(head, ...))


def system_logits(params, config, tokens, paged: bool, mesh=None):
    """Prefill of the first P tokens, then D cached decode steps, through
    the program's own functions. [B, P + D, V] float32."""
    dtype = params["embed"].dtype
    lens = jnp.full((B,), P, jnp.int32)
    small = KVCache.create(config, B, P if paged else P + D, dtype=dtype)
    logits, small = jit_model(mixtral.prefill, config, mesh=mesh)(
        params, tokens[:, :P], lens, small)
    out = [logits.astype(jnp.float32)]
    if paged:
        ps, per_row = 8, 4
        cache = PagedKVCache.create(config, B, 1 + B * per_row, ps,
                                    max_pages_per_row=per_row, dtype=dtype,
                                    mesh=mesh)
        tables = 1 + jnp.arange(B * per_row,
                                dtype=jnp.int32).reshape(B, per_row)
        cache = write_prefill_batch(cache, small.k, small.v,
                                    jnp.arange(B, dtype=jnp.int32), lens,
                                    tables)
    else:
        cache = small
    decode = (jit_model(mixtral.decode_step_paged, config, mesh=mesh, pages=4)
              if paged else jit_model(mixtral.decode_step, config, mesh=mesh))
    for t in range(P, P + D):
        step, cache = decode(params, tokens[:, t:t + 1], cache)
        out.append(step.astype(jnp.float32))
    return jnp.concatenate(out, axis=1)


def tokens_for(seed: int = 11):
    return jnp.asarray(np.random.default_rng(seed).integers(
        0, CFG.vocab_size, size=(B, P + D)), jnp.int32)


def median_error(system, ref) -> float:
    return float(jnp.median(reference.position_errors(system, ref)))


@pytest.mark.parametrize("paged", [False, True], ids=["dense", "paged"])
@pytest.mark.parametrize("tree,fused", [
    ("float32", False), ("float32", True), ("bfloat16", True),
    ("int8", True), ("int8", False)])
def test_prefill_then_cached_decode_agrees_with_the_reference(tree, fused,
                                                              paged):
    params = random_params(jnp.float32 if tree == "float32"
                           else jnp.bfloat16)
    if tree == "int8":
        params = quantize_params(params, mode="int8")
    if fused:
        params = mixtral.fuse_params(params)
        assert "wqkv" in params["layers"] and "wgu_e" in params["layers"]
    assert {"q_norm", "k_norm"} <= set(params["layers"])
    tokens = tokens_for()
    system = system_logits(params, CFG, tokens, paged)
    ref, facts = OLMOE.forward(PUBLISHED, tokens, reference_weights(params))
    err = median_error(system, ref)
    assert err <= TOL[tree], (tree, fused, paged, err)
    assert facts["min_margin"].shape == (B * (P + D),)
    assert len(facts["routing"]) == CFG.num_layers


def _attn_one_norm(skipped):
    """``llama._attn_qkv`` for an unfused tree, written out, with the
    norm named ``skipped`` left out (``None``: the right model)."""
    from p2p_llm_chat_tpu.models.layers import apply_rope, rms_norm
    from p2p_llm_chat_tpu.models.quant import mm

    def attn_qkv(h, lp, config, inv_freq, positions, mesh, rules):
        rows, S, _ = h.shape
        x = rms_norm(h, lp["attn_norm"], config.rms_norm_eps)
        q, k, v = (mm(x, lp[n]) for n in ("wq", "wk", "wv"))
        if skipped != "q_norm":
            q = rms_norm(q, lp["q_norm"], config.rms_norm_eps)
        if skipped != "k_norm":
            k = rms_norm(k, lp["k_norm"], config.rms_norm_eps)
        heads = lambda a, n: a.reshape(rows, S, n, config.head_dim)
        return (apply_rope(heads(q, config.num_heads), positions, inv_freq),
                apply_rope(heads(k, config.num_kv_heads), positions,
                           inv_freq),
                heads(v, config.num_kv_heads))
    return attn_qkv


@pytest.mark.parametrize("wrong", ["renormalised router", "no q_norm",
                                   "no k_norm", None])
def test_a_wrong_model_fails_the_comparison(wrong, monkeypatch):
    """The comparison the right model passes at 1e-4 is failed by a
    program that renormalises the kept router weights, or leaves either
    norm out. The last case is the control: the written-out attention
    with nothing left out is the right model."""
    from p2p_llm_chat_tpu.models import llama
    params = random_params(jnp.float32)
    tokens = tokens_for()
    ref, _ = OLMOE.forward(PUBLISHED, tokens, reference_weights(params))
    config = CFG
    if wrong == "renormalised router":
        config = CFG.with_(moe_renormalize=True)
    else:
        monkeypatch.setattr(llama, "_attn_qkv", _attn_one_norm(
            wrong and wrong.removeprefix("no ")))
    err = median_error(system_logits(params, config, tokens, paged=False),
                       ref)
    if wrong is None:
        assert err <= TOL["float32"], err
    else:
        assert err >= WRONG_AT_LEAST, (wrong, err)


@pytest.mark.parametrize("paged", [False, True], ids=["dense", "paged"])
def test_two_devices_agree_with_the_reference(paged):
    """``tp`` 2 on two virtual devices: the fused ``wqkv`` is interleaved
    by device, the norm vectors are sharded with the heads, and the norm's
    mean over all heads is a reduction across the two."""
    mesh = make_mesh(MeshConfig(tp=2), devices=jax.devices()[:2])
    assert fuse_tp_for(CFG, mesh) == 2
    plain = random_params(jnp.float32)
    sharded = shard_params(plain, mixtral.param_axes(CFG), mesh)
    assert sharded["layers"]["q_norm"].sharding.spec[-1] == "tp"
    fused = mixtral.fuse_params(sharded, tp=2, mesh=mesh)
    tokens = tokens_for()
    system = system_logits(fused, CFG, tokens, paged, mesh)
    ref, _ = OLMOE.forward(PUBLISHED, tokens, reference_weights(plain))
    assert median_error(system, ref) <= TOL["float32"]
    # The benchmark's own reader undoes the same layout.
    weights = reference_weights(fused, tp=2)
    np.testing.assert_array_equal(
        np.asarray(weights.layer(1)["wk"]),
        np.asarray(plain["layers"]["wk"][1], np.float32))
