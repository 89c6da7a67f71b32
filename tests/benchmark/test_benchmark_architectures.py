"""The harness takes a family it has not seen, and a tree laid out under a
mesh, as new files: an architecture file written here into a temporary
root reaches the program and judges it; the Mistral family's reader of
the engine's tree gives the unfused weights back whatever the mesh; two
``chips: 4`` cells run whole on four virtual CPU devices.
"""

import json
import os
import time
import types

import pytest

from rehearsal_files import (ROOT, on_cpu, run_args,  # noqa: F401
                             tiny, write_benchmark)

from benchmark import manifest, run  # noqa: E402

# What a model PR for a second family would bring: a file of its own
# that maps the family's published keys (``num_experts``, as OLMoE
# publishes it, where Mixtral says ``num_local_experts``), reads the
# engine's tree, and holds the family's plain reference. The program
# serves this toy family through the block it has, so the tree is laid
# out as Mixtral's and that reader is borrowed; the reference is
# written out here, and with ROPE = False it is a wrong one.
OTHER_FAMILY = '''\
"""A family the harness has not seen (written by a test)."""
import os

import jax
import jax.numpy as jnp

from benchmark import manifest, reference

ROPE = True
_mistral = manifest.load_architecture(
    os.path.join(manifest.REPO, "benchmark"), "mistral")
engine_weights = _mistral.engine_weights


def _as_mistral(cfg):
    return dict(cfg, num_local_experts=cfg["num_experts"])


def model_config(cfg):
    return _mistral.model_config(_as_mistral(cfg))


def attention(x, w, cfg):
    T = x.shape[0]
    heads, kvh = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    d, pos = cfg["head_dim"], jnp.arange(T)
    turn = reference.rope if ROPE else (lambda x, pos, theta: x)
    q = turn((x @ w["wq"]).reshape(T, heads, d), pos, cfg["rope_theta"])
    k = turn((x @ w["wk"]).reshape(T, kvh, d), pos, cfg["rope_theta"])
    v = (x @ w["wv"]).reshape(T, kvh, d)
    k, v = (jnp.repeat(a, heads // kvh, axis=1) for a in (k, v))
    s = jnp.einsum("qhd,khd->hqk", q, k) / jnp.sqrt(jnp.float32(d))
    s = jnp.where(pos[None, :, None] >= pos[None, None, :], s, -jnp.inf)
    o = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), v)
    return o.reshape(T, heads * d) @ w["wo"]


def forward(cfg, tokens, weights):
    eps, top_k = cfg["rms_norm_eps"], cfg["num_experts_per_tok"]
    B, T = tokens.shape
    facts = {"min_margin": jnp.full((B * T,), jnp.inf), "routing": []}
    with jax.default_matmul_precision("highest"):
        h = weights.embed[tokens].astype(jnp.float32)
        for layer in range(cfg["num_hidden_layers"]):
            w = weights.layer(layer)
            h = h + jax.vmap(lambda x: attention(
                reference.rms_norm(x, w["attn_norm"], eps), w, cfg))(h)
            x = reference.rms_norm(h, w["mlp_norm"], eps).reshape(B * T, -1)
            routed, margin = reference.route(x, w["router"], top_k)
            facts["routing"].append(routed)
            facts["min_margin"] = jnp.minimum(facts["min_margin"], margin)
            for e in range(cfg["num_experts"]):
                h = h + (routed[:, e:e + 1] * reference.swiglu(
                    x, *weights.expert(layer, e))).reshape(h.shape)
        logits = reference.rms_norm(h, weights.final_norm, eps) \\
            @ weights.lm_head
    return logits, facts


def compare(system, reference_logits, facts, cfg):
    return _mistral.compare(system, reference_logits, facts,
                            _as_mistral(cfg))
'''


def _other(name: str, architecture: str) -> dict:
    cfg = tiny(name, experts=4, architecture=architecture)
    cfg["num_experts"] = cfg.pop("num_local_experts")
    return cfg


@pytest.fixture(scope="module")
def other_root(tmp_path_factory):
    """New files only: nothing of the real benchmark but the readers."""
    return write_benchmark(
        tmp_path_factory.mktemp("other"),
        [_other("tiny-other", "other"),
         _other("tiny-other-no-rope", "other-no-rope")],
        copied=("layer_metrics",),
        architectures={"other": OTHER_FAMILY,
                       "other-no-rope": OTHER_FAMILY.replace(
                           "ROPE = True", "ROPE = False")})


@pytest.fixture(scope="module")
def mesh_root(tmp_path_factory):
    """Widths that four devices divide (4 heads, 4 kv heads, 256), so
    that ``fuse_tp_for`` answers 4 and the fused columns interleave."""
    return write_benchmark(
        tmp_path_factory.mktemp("mesh"),
        [tiny("tiny-dense", num_key_value_heads=4),
         tiny("tiny-routed", experts=4, num_key_value_heads=4)], chips=4)


def _whole_run(cell, root, out, capsys, seconds=4.0):
    last = run.run_cell(run_args(cell, 0, seconds), time.monotonic(),
                        data_root=root, out_root=str(out))
    earlier = [json.loads(x) for x in capsys.readouterr().out.splitlines()]
    ref = next(x["reference"] for x in earlier if "reference" in x)
    return last, ref, earlier


def test_a_second_family_is_new_files_only(other_root, on_cpu, tmp_path,
                                           capsys):
    cell = manifest.load_cell("tiny-other.tiny-open", other_root)
    assert "num_local_experts" not in cell.config
    assert os.listdir(os.path.join(cell.root, "architectures")) \
        and not os.path.exists(os.path.join(cell.root, "architectures",
                                            "mistral.py"))
    last, ref, earlier = _whole_run(cell.name, other_root, tmp_path, capsys)
    assert ref["ok"] and ref["capacity"] == 256, ref
    assert 0 < ref["median"] <= ref["tolerance"]["median"]
    assert last["correct"] and last["failed"] == 0, earlier
    assert last["attempted"] >= 10


def test_the_configurations_own_reference_judges_it(other_root, on_cpu,
                                                    tmp_path, capsys):
    """The same program under a file whose reference leaves the rotary
    embedding out: not correct, with the reference's numbers in the
    fault."""
    last, ref, earlier = _whole_run("tiny-other-no-rope.tiny-open",
                                    other_root, tmp_path, capsys, 2.0)
    assert not ref["ok"] and ref["median"] > 5 * ref["tolerance"]["median"]
    assert not last["correct"]
    (fault,) = next(x["faults"] for x in earlier if "faults" in x)
    assert "disagrees with the reference" in fault
    assert repr(ref["median"]) in fault and "tolerance" in fault


@pytest.mark.parametrize("cell", ["tiny-dense.tiny-open",
                                  "tiny-routed.tiny-open"])
def test_four_chip_cell_on_four_virtual_devices(cell, mesh_root, on_cpu,
                                                tmp_path, capsys):
    last, ref, earlier = _whole_run(cell, mesh_root, tmp_path, capsys)
    assert ref["ok"], ref
    assert last["correct"] and last["failed"] == 0, earlier
    assert last["device"]["count"] == 4
    with open(os.path.join(str(tmp_path), "benchmark",
                           f"{cell}.seed7.trace0", "server.log")) as f:
        log = f.read()
    # SERVE_TP=4 on four devices: the scheduler fused under a mesh of
    # four, the tree is interleaved by device, and the check followed.
    assert "count=4" in log and "SERVE_TP is unset" not in log, log[-2000:]


# -- the reader of the engine's tree ------------------------------------------

@pytest.mark.parametrize("experts", [0, 4])
@pytest.mark.parametrize("tp", [1, 2, 4])
def test_engine_weights_undo_the_fusion(tp, experts):
    """``wq, wk, wv, w_gate, w_up`` read back from a tree fused under
    ``tp`` devices equal the unfused originals, element for element."""
    import jax
    import numpy as np
    from benchmark import serve_cell
    from p2p_llm_chat_tpu.models import family_for
    from p2p_llm_chat_tpu.models.llama import fuse_tp_for
    from p2p_llm_chat_tpu.models.quant import quantize_params
    from p2p_llm_chat_tpu.parallel.mesh import MeshConfig, make_mesh
    cfg = tiny("t", experts=experts, num_key_value_heads=4)
    config = serve_cell.model_config(cfg)
    model = family_for(config)
    mesh = (make_mesh(MeshConfig(tp=tp), devices=jax.devices()[:tp])
            if tp > 1 else None)
    plain = quantize_params(model.init_params(config, jax.random.PRNGKey(3)),
                            mode="int8")
    assert fuse_tp_for(config, mesh) == tp
    fused = model.fuse_params(plain, tp=tp, mesh=mesh)
    assert "wq" not in fused["layers"] and "wqkv" in fused["layers"]
    assert ("wgu_e" in fused["layers"]) == (bool(experts) and tp == 1)
    arch = manifest.load_architecture(os.path.join(ROOT, "benchmark"))
    weights = arch.engine_weights(types.SimpleNamespace(
        _params=fused, config=config, mesh=mesh))
    deq = lambda w, *at: np.asarray(w.q[at], np.float32) * np.asarray(
        w.s[at], np.float32)
    L = plain["layers"]
    if tp > 1:      # the layout under a mesh is not the plain [q | k | v]
        assert not np.array_equal(
            np.asarray(fused["layers"]["wqkv"].q[..., :config.q_dim]),
            np.asarray(L["wq"].q))
    for layer in range(config.num_layers):
        got = weights.layer(layer)
        for name in ("wq", "wk", "wv", "wo"):
            np.testing.assert_array_equal(np.asarray(got[name]),
                                          deq(L[name], layer), name)
        for e in range(experts):
            for name, w in zip(("w_gate", "w_up", "w_down"),
                               weights.expert(layer, e)):
                np.testing.assert_array_equal(np.asarray(w),
                                              deq(L[name], layer, e), name)
        if not experts:
            for name in ("w_gate", "w_up", "w_down"):
                np.testing.assert_array_equal(np.asarray(got[name]),
                                              deq(L[name], layer), name)


# -- what a faulty architecture file is told ----------------------------------

def test_unknown_keyword_names_the_keyword_and_the_file(tmp_path):
    from benchmark import serve_cell
    arch_dir = tmp_path / "architectures"
    arch_dir.mkdir()
    (arch_dir / "normed.py").write_text(
        "from benchmark import manifest\n"
        "m = manifest.load_architecture(manifest.REPO + '/benchmark')\n"
        "engine_weights, forward, compare = "
        "m.engine_weights, m.forward, m.compare\n"
        "def model_config(cfg):\n"
        "    return dict(m.model_config(cfg), qk_norm=True)\n")
    cfg = tiny("t", architecture="normed")
    with pytest.raises(manifest.ManifestError) as e:
        serve_cell.model_config(cfg, root=str(tmp_path))
    assert "qk_norm" in str(e.value)
    assert str(arch_dir / "normed.py") in str(e.value)


def test_missing_or_partial_architecture_file_is_named(tmp_path):
    from benchmark import serve_cell
    with pytest.raises(manifest.ManifestError) as e:
        serve_cell.model_config(tiny("t", architecture="absent"),
                                root=str(tmp_path))
    assert os.path.join(str(tmp_path), "architectures", "absent.py") \
        in str(e.value)
    (tmp_path / "architectures").mkdir()
    (tmp_path / "architectures" / "half.py").write_text(
        "def model_config(cfg):\n    return {}\n")
    with pytest.raises(manifest.ManifestError, match=r"half\.py defines no "
                                                     r"engine_weights\(\)"):
        manifest.load_architecture(str(tmp_path), "half")
    # A configuration that names no architecture is of the Mistral family.
    assert serve_cell.model_config(tiny("t")).num_kv_heads == 2
