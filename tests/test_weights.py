"""Coverage for the production checkpoint path: weights.load_checkpoint.

The in-memory ``convert_hf_state_dict`` oracle alone would leave the
safetensors-directory path serving actually uses uncovered. These tests write tiny HF-layout checkpoints
(config.json + sharded ``*.safetensors``) to disk with
``safetensors.numpy.save_file`` and require ``load_checkpoint`` to
reproduce the convert-path tree exactly — dense and MoE, unsharded and
mesh-sharded (the multi-chip 70B path, BASELINE.json config 4).
"""

import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from p2p_llm_chat_tpu.models.weights import (config_from_hf_json,
                                             convert_hf_state_dict,
                                             load_checkpoint)

torch = pytest.importorskip("torch")
transformers = pytest.importorskip("transformers")
safetensors_numpy = pytest.importorskip("safetensors.numpy")

pytestmark = pytest.mark.model


def _np_state(model) -> dict[str, np.ndarray]:
    return {k: v.float().numpy() for k, v in model.state_dict().items()}


def _write_ckpt(tmp_path, model, n_shards: int = 2) -> str:
    """Write an HF-layout checkpoint dir: config.json + sharded safetensors."""
    model.config.architectures = [type(model).__name__]
    model.config.to_json_file(os.path.join(tmp_path, "config.json"))
    names = sorted(_np_state(model))
    state = _np_state(model)
    per = (len(names) + n_shards - 1) // n_shards
    for s in range(n_shards):
        chunk = {n: state[n] for n in names[s * per:(s + 1) * per]}
        if chunk:
            safetensors_numpy.save_file(
                chunk, os.path.join(
                    tmp_path, f"model-{s + 1:05d}-of-{n_shards:05d}.safetensors"))
    return str(tmp_path)


def _tiny_llama(tie=False):
    from tests.test_llama_parity import make_hf_model
    return make_hf_model(tie=tie)


def _assert_trees_equal(got, want):
    jax.tree.map(
        lambda g, w: np.testing.assert_array_equal(np.asarray(g), np.asarray(w)),
        got, want)


def test_load_checkpoint_dense_matches_convert(tmp_path):
    model, cfg = _tiny_llama()
    ckpt = _write_ckpt(tmp_path, model)
    params, loaded_cfg = load_checkpoint(ckpt, dtype=jnp.float32)

    # Config derived from config.json matches the parity config's geometry.
    for f in ("vocab_size", "hidden_size", "intermediate_size", "num_layers",
              "num_heads", "num_kv_heads", "head_dim", "tie_embeddings"):
        assert getattr(loaded_cfg, f) == getattr(cfg, f), f

    want = convert_hf_state_dict(_np_state(model), cfg, dtype=jnp.float32)
    _assert_trees_equal(params, want)


def test_load_checkpoint_tied_embeddings(tmp_path):
    model, cfg = _tiny_llama(tie=True)
    ckpt = _write_ckpt(tmp_path, model, n_shards=1)
    params, loaded_cfg = load_checkpoint(ckpt, dtype=jnp.float32)
    assert loaded_cfg.tie_embeddings
    assert "lm_head" not in params


def test_load_checkpoint_sharded_mesh(tmp_path):
    """Mesh-sharded load (the 70B path): every leaf lands with a
    NamedSharding and the values equal the single-device load. Also
    regression-covers ADVICE round-1 high: a dense-config mesh load must
    not require models/mixtral."""
    from jax.sharding import NamedSharding
    from p2p_llm_chat_tpu.parallel.mesh import MeshConfig, make_mesh

    model, cfg = _tiny_llama()
    ckpt = _write_ckpt(tmp_path, model)
    mesh = make_mesh(MeshConfig(tp=2))
    sharded, _ = load_checkpoint(ckpt, mesh=mesh, dtype=jnp.float32)
    plain, _ = load_checkpoint(ckpt, dtype=jnp.float32)

    for leaf in jax.tree.leaves(sharded):
        assert isinstance(leaf.sharding, NamedSharding)
    _assert_trees_equal(sharded, plain)


def test_load_checkpoint_moe(tmp_path):
    from tests.test_mixtral_parity import make_hf_model as make_moe

    model, cfg = make_moe()
    ckpt = _write_ckpt(tmp_path, model, n_shards=3)
    params, loaded_cfg = load_checkpoint(ckpt, dtype=jnp.float32)
    assert loaded_cfg.is_moe
    assert loaded_cfg.num_experts == cfg.num_experts
    assert loaded_cfg.num_experts_per_tok == cfg.num_experts_per_tok

    want = convert_hf_state_dict(_np_state(model), cfg, dtype=jnp.float32)
    _assert_trees_equal(params, want)
    # Per-expert stacking: [L, E, in, out].
    assert params["layers"]["w_gate"].shape[:2] == (cfg.num_layers,
                                                    cfg.num_experts)


def test_load_checkpoint_empty_dir_raises(tmp_path):
    with open(os.path.join(tmp_path, "config.json"), "w") as f:
        json.dump({"vocab_size": 8, "hidden_size": 8, "intermediate_size": 16,
                   "num_hidden_layers": 1, "num_attention_heads": 2}, f)
    with pytest.raises(FileNotFoundError):
        load_checkpoint(str(tmp_path))


def test_config_from_hf_json_llama3_rope_and_eos_list(tmp_path):
    hf = {
        "architectures": ["LlamaForCausalLM"],
        "vocab_size": 128256, "hidden_size": 4096,
        "intermediate_size": 14336, "num_hidden_layers": 32,
        "num_attention_heads": 32, "num_key_value_heads": 8,
        "max_position_embeddings": 131072, "rope_theta": 500000.0,
        "rms_norm_eps": 1e-5, "tie_word_embeddings": False,
        "bos_token_id": 128000, "eos_token_id": [128001, 128008, 128009],
        "rope_scaling": {"rope_type": "llama3", "factor": 8.0,
                         "low_freq_factor": 1.0, "high_freq_factor": 4.0,
                         "original_max_position_embeddings": 8192},
    }
    path = os.path.join(tmp_path, "config.json")
    with open(path, "w") as f:
        json.dump(hf, f)
    cfg = config_from_hf_json(path)
    assert cfg.rope_scaling is not None
    assert cfg.rope_scaling.factor == 8.0
    assert cfg.rope_scaling.original_max_position == 8192
    assert cfg.eos_token_ids == (128001, 128008, 128009)
    assert cfg.num_kv_heads == 8
    assert cfg.head_dim == 128
    assert not cfg.is_moe


def test_streaming_loader_matches_batch_loader(tmp_path):
    """The memory-bounded streaming loader (one host tensor at a time,
    device-resident tree) must produce exactly the batch loader's tree —
    dense, tied, MoE, and mesh-sharded."""
    from p2p_llm_chat_tpu.models.weights import load_checkpoint_streaming
    from p2p_llm_chat_tpu.parallel.mesh import MeshConfig, make_mesh

    model, cfg = _tiny_llama()
    ckpt = _write_ckpt(tmp_path, model)
    want, _ = load_checkpoint(ckpt, dtype=jnp.float32)
    got, got_cfg = load_checkpoint_streaming(ckpt, dtype=jnp.float32)
    assert got_cfg.num_layers == cfg.num_layers
    _assert_trees_equal(got, want)

    mesh = make_mesh(MeshConfig(tp=2))
    got_sharded, _ = load_checkpoint_streaming(ckpt, mesh=mesh,
                                               dtype=jnp.float32)
    from jax.sharding import NamedSharding
    for leaf in jax.tree.leaves(got_sharded):
        assert isinstance(leaf.sharding, NamedSharding)
    _assert_trees_equal(got_sharded, want)


def test_streaming_loader_moe(tmp_path):
    from p2p_llm_chat_tpu.models.weights import load_checkpoint_streaming
    from tests.test_mixtral_parity import make_hf_model as make_moe

    model, cfg = make_moe()
    ckpt = _write_ckpt(tmp_path, model, n_shards=3)
    want, _ = load_checkpoint(ckpt, dtype=jnp.float32)
    got, _ = load_checkpoint_streaming(ckpt, dtype=jnp.float32)
    _assert_trees_equal(got, want)


def test_streaming_loader_tied_embeddings(tmp_path):
    from p2p_llm_chat_tpu.models.weights import load_checkpoint_streaming

    model, cfg = _tiny_llama(tie=True)
    ckpt = _write_ckpt(tmp_path, model, n_shards=1)
    want, _ = load_checkpoint(ckpt, dtype=jnp.float32)
    got, _ = load_checkpoint_streaming(ckpt, dtype=jnp.float32)
    assert "lm_head" not in got
    _assert_trees_equal(got, want)


def test_load_checkpoint_quantized_hf_matches_quantize_then_fuse(tmp_path):
    """The single-chip streamed int8 loader must produce EXACTLY
    fuse_params(quantize_params(load_checkpoint(...))) — quantization is
    deterministic and per-output-channel scales concatenate with their
    columns, so the trees are bit-identical."""
    from p2p_llm_chat_tpu.models import llama
    from p2p_llm_chat_tpu.models.quant import quantize_params
    from p2p_llm_chat_tpu.models.weights import load_checkpoint_quantized

    model, cfg = _tiny_llama()
    ckpt = _write_ckpt(tmp_path, model)
    got, got_cfg = load_checkpoint_quantized(ckpt)
    for f in ("vocab_size", "hidden_size", "intermediate_size",
              "num_layers", "num_heads", "num_kv_heads", "tie_embeddings"):
        assert getattr(got_cfg, f) == getattr(cfg, f), f

    base, _ = load_checkpoint(ckpt)         # bf16 (default dtype)
    want = llama.fuse_params(quantize_params(base))
    _assert_trees_equal(got, want)

    # HF-branch config identity: a caller-supplied REGISTRY config whose
    # shapes match must be honored even though its name can never equal
    # the HF-derived one (_name_or_path / "hf-model") — shape fields
    # alone establish identity there. A shape disagreement still rejects.
    supplied = got_cfg.with_(name="my-registry-tag",
                             max_seq_len=got_cfg.max_seq_len * 2)
    got2, got2_cfg = load_checkpoint_quantized(ckpt, config=supplied)
    assert got2_cfg.name == "my-registry-tag"
    assert got2_cfg.max_seq_len == got_cfg.max_seq_len * 2
    _assert_trees_equal(got2, want)
    with pytest.raises(ValueError, match="identity"):
        load_checkpoint_quantized(
            ckpt, config=got_cfg.with_(num_layers=got_cfg.num_layers + 1))


def test_load_checkpoint_quantized_native_matches(tmp_path):
    """Same equivalence through a native Orbax checkpoint (the e2e quote
    checkpoints and any natively-saved model take this path)."""
    import jax as _jax
    import jax.numpy as _jnp

    from p2p_llm_chat_tpu.models import llama
    from p2p_llm_chat_tpu.models.checkpoint import save_checkpoint
    from p2p_llm_chat_tpu.models.configs import get_config
    from p2p_llm_chat_tpu.models.quant import quantize_params
    from p2p_llm_chat_tpu.models.weights import load_checkpoint_quantized

    cfg = get_config("tiny")
    params = llama.init_params(cfg, _jax.random.PRNGKey(3),
                               dtype=_jnp.bfloat16)
    ckpt = str(tmp_path / "native")
    save_checkpoint(ckpt, params, cfg)

    got, got_cfg = load_checkpoint_quantized(ckpt)
    assert got_cfg.name == "tiny"
    want = llama.fuse_params(quantize_params(params))
    _assert_trees_equal(got, want)

    # Config agreement is relaxed to IDENTITY fields (name + tensor
    # shapes): a benign runtime-field bump — the registry raising a
    # config's max_seq_len — must not orphan pre-existing checkpoints,
    # and the caller's bumped value must win.
    bumped = cfg.with_(max_seq_len=cfg.max_seq_len * 2)
    got2, got2_cfg = load_checkpoint_quantized(ckpt, config=bumped)
    assert got2_cfg.max_seq_len == cfg.max_seq_len * 2
    _assert_trees_equal(got2, want)
    # A shape-bearing field disagreement is a DIFFERENT model: reject.
    with pytest.raises(ValueError, match="identity"):
        load_checkpoint_quantized(
            ckpt, config=cfg.with_(num_kv_heads=cfg.num_kv_heads * 2))


def test_load_checkpoint_quantized_moe_matches_quantize_then_fuse(tmp_path):
    """Round-4 verdict #3: the streamed int8 loader now covers the MoE
    family. Must produce EXACTLY
    fuse_params(quantize_params(load_checkpoint(...))) — the same
    bit-identity contract the dense path carries, with the per-expert
    gate|up fused into wgu_e [L,NE,H,2F]."""
    from tests.test_mixtral_parity import make_hf_model as make_moe
    from p2p_llm_chat_tpu.models import mixtral
    from p2p_llm_chat_tpu.models.quant import quantize_params
    from p2p_llm_chat_tpu.models.weights import load_checkpoint_quantized

    model, cfg = make_moe()
    ckpt = _write_ckpt(tmp_path, model, n_shards=3)
    got, got_cfg = load_checkpoint_quantized(ckpt)
    assert got_cfg.is_moe and got_cfg.num_experts == cfg.num_experts

    base, _ = load_checkpoint(ckpt)         # bf16 (default dtype)
    want = mixtral.fuse_params(quantize_params(base))
    assert "wgu_e" in want["layers"]        # expert fusion engaged
    assert want["layers"]["wgu_e"].q.shape == (
        cfg.num_layers, cfg.num_experts, cfg.hidden_size,
        2 * cfg.intermediate_size)
    _assert_trees_equal(got, want)


def test_load_checkpoint_quantized_int4_matches(tmp_path):
    """Round-16: the streamed loader's w4a16 branch. Both checkpoint
    flavors (HF safetensors and native Orbax) must produce EXACTLY
    fuse_params(quantize_params(load_checkpoint(...), mode="int4")) —
    group-wise quantization is deterministic and nibble packing is a
    pure bit permutation, so the trees are bit-identical."""
    import jax as _jax
    import jax.numpy as _jnp

    from p2p_llm_chat_tpu.models import llama
    from p2p_llm_chat_tpu.models.checkpoint import save_checkpoint
    from p2p_llm_chat_tpu.models.configs import get_config
    from p2p_llm_chat_tpu.models.quant import QTensor4, quantize_params
    from p2p_llm_chat_tpu.models.weights import load_checkpoint_quantized

    # HF branch.
    model, cfg = _tiny_llama()
    ckpt = _write_ckpt(tmp_path, model)
    got, got_cfg = load_checkpoint_quantized(ckpt, quant="int4")
    assert got_cfg.hidden_size == cfg.hidden_size
    base, _ = load_checkpoint(ckpt)         # bf16 (default dtype)
    want = llama.fuse_params(quantize_params(base, mode="int4"))
    assert any(isinstance(v, QTensor4) for v in want["layers"].values())
    _assert_trees_equal(got, want)

    # Native Orbax branch.
    ncfg = get_config("tiny")
    params = llama.init_params(ncfg, _jax.random.PRNGKey(7),
                               dtype=_jnp.bfloat16)
    nckpt = str(tmp_path / "native-int4")
    save_checkpoint(nckpt, params, ncfg)
    ngot, ngot_cfg = load_checkpoint_quantized(nckpt, quant="int4")
    assert ngot_cfg.name == "tiny"
    nwant = llama.fuse_params(quantize_params(params, mode="int4"))
    _assert_trees_equal(ngot, nwant)


def test_load_checkpoint_quantized_moe_native_matches(tmp_path):
    """Same MoE equivalence through a native Orbax checkpoint."""
    import jax as _jax
    import jax.numpy as _jnp

    from p2p_llm_chat_tpu.models import mixtral
    from p2p_llm_chat_tpu.models.checkpoint import save_checkpoint
    from p2p_llm_chat_tpu.models.configs import get_config
    from p2p_llm_chat_tpu.models.quant import quantize_params
    from p2p_llm_chat_tpu.models.weights import load_checkpoint_quantized

    cfg = get_config("tiny-moe")
    params = mixtral.init_params(cfg, _jax.random.PRNGKey(5),
                                 dtype=_jnp.bfloat16)
    ckpt = str(tmp_path / "native-moe")
    save_checkpoint(ckpt, params, cfg)

    got, got_cfg = load_checkpoint_quantized(ckpt)
    assert got_cfg.is_moe
    want = mixtral.fuse_params(quantize_params(params))
    _assert_trees_equal(got, want)




# -- OLMoE: its own tensor names, num_experts / norm_topk_prob, QK-norm -------

def _tiny_olmoe():
    """A seeded ``transformers`` OLMoE at a toy size (MHA, 8 experts
    top-4, ``norm_topk_prob: false``), its q_norm / k_norm weights made
    random so that a norm the loader dropped could not pass."""
    from p2p_llm_chat_tpu.models.configs import ModelConfig
    hf_cfg = transformers.OlmoeConfig(
        vocab_size=128, hidden_size=64, intermediate_size=32,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=4,
        num_experts=8, num_experts_per_tok=4, norm_topk_prob=False,
        max_position_embeddings=256, rope_theta=10000.0, rms_norm_eps=1e-5,
        attention_dropout=0.0, pad_token_id=1, eos_token_id=2)
    torch.manual_seed(0)
    model = transformers.OlmoeForCausalLM(hf_cfg).eval()
    with torch.no_grad():
        for name, p in model.named_parameters():
            if name.endswith(("q_norm.weight", "k_norm.weight")):
                p.copy_(0.5 + torch.rand_like(p))
    ours = ModelConfig(
        name="tiny-olmoe-parity", vocab_size=128, hidden_size=64,
        intermediate_size=32, num_layers=2, num_heads=4, num_kv_heads=4,
        head_dim=16, max_seq_len=256, rope_theta=10000.0,
        num_experts=8, num_experts_per_tok=4, moe_renormalize=False,
        qk_norm_whole=True, bos_token_id=1, eos_token_ids=(2,))
    return model, ours


def test_olmoe_checkpoint_names_config_and_logits(tmp_path):
    """config.json's ``num_experts``, ``norm_topk_prob`` and ``model_type:
    olmoe`` reach the ModelConfig; ``self_attn.q_norm / k_norm``,
    ``mlp.gate`` and ``mlp.experts.N.{gate,up,down}_proj`` reach their
    leaves; and the loaded tree, through models/mixtral.py, gives the
    ``transformers`` model's own logits."""
    from p2p_llm_chat_tpu.models import family_for, mixtral
    from p2p_llm_chat_tpu.models.llama import KVCache
    model, cfg = _tiny_olmoe()
    ckpt = _write_ckpt(tmp_path, model, n_shards=3)
    with open(os.path.join(ckpt, "config.json")) as f:
        published = json.load(f)
    assert published["model_type"] == "olmoe"
    assert "num_local_experts" not in published
    loaded_cfg = config_from_hf_json(os.path.join(ckpt, "config.json"))
    assert (loaded_cfg.num_experts, loaded_cfg.num_experts_per_tok) == (8, 4)
    assert loaded_cfg.qk_norm_whole and not loaded_cfg.moe_renormalize
    assert family_for(loaded_cfg) is mixtral

    params, _ = load_checkpoint(ckpt, dtype=jnp.float32)
    want = convert_hf_state_dict(_np_state(model), cfg, dtype=jnp.float32)
    _assert_trees_equal(params, want)
    state = _np_state(model)
    L = params["layers"]
    assert L["q_norm"].shape == (2, 64) and L["k_norm"].shape == (2, 64)
    np.testing.assert_array_equal(
        np.asarray(L["k_norm"][1]),
        state["model.layers.1.self_attn.k_norm.weight"])
    np.testing.assert_array_equal(
        np.asarray(L["router"][0]), state["model.layers.0.mlp.gate.weight"].T)
    np.testing.assert_array_equal(
        np.asarray(L["w_down"][1, 5]),
        state["model.layers.1.mlp.experts.5.down_proj.weight"].T)

    tokens = np.random.default_rng(0).integers(
        0, cfg.vocab_size, size=(2, 12)).astype(np.int32)
    with torch.no_grad():
        ref = model(torch.from_numpy(tokens).long()).logits.float().numpy()
    cache = KVCache.create(cfg, batch=2, max_seq=32, dtype=jnp.float32)
    ours, _ = mixtral.prefill(params, loaded_cfg, jnp.asarray(tokens),
                              jnp.array([12, 12]), cache)
    np.testing.assert_allclose(np.asarray(ours), ref, atol=5e-3, rtol=2e-2)
    # A Mixtral-style reading of the same weights is a different model.
    wrong, _ = mixtral.prefill(
        params, loaded_cfg.with_(moe_renormalize=True), jnp.asarray(tokens),
        jnp.array([12, 12]),
        KVCache.create(cfg, batch=2, max_seq=32, dtype=jnp.float32))
    assert np.abs(np.asarray(wrong) - ref).max() > 10 * np.abs(
        np.asarray(ours) - ref).max()


def test_olmoe_streamed_int8_load_matches_quantize_then_fuse(tmp_path):
    """The streamed int8 loader carries q_norm and k_norm beside the
    other norm vectors, and the identity check compares the two fields a
    checkpoint's arithmetic depends on."""
    from p2p_llm_chat_tpu.models import mixtral
    from p2p_llm_chat_tpu.models.quant import quantize_params
    from p2p_llm_chat_tpu.models.weights import load_checkpoint_quantized

    model, cfg = _tiny_olmoe()
    ckpt = _write_ckpt(tmp_path, model, n_shards=2)
    got, got_cfg = load_checkpoint_quantized(ckpt)
    assert got_cfg.qk_norm_whole and not got_cfg.moe_renormalize
    base, _ = load_checkpoint(ckpt)
    want = mixtral.fuse_params(quantize_params(base))
    assert {"q_norm", "k_norm", "wgu_e"} <= set(want["layers"])
    _assert_trees_equal(got, want)
    for field in ("moe_renormalize", "qk_norm_whole"):
        with pytest.raises(ValueError, match=field):
            load_checkpoint_quantized(
                ckpt, config=cfg.with_(**{field: not getattr(cfg, field)}))
