"""Rehearsal of the real sizes, off the chip: the decode program of both
configurations, at the cell's batch and pool, compiled for a described
v5e chip with the Pallas kernels in (the TPU's compiler is installed
here; nothing runs). What the compiler refuses here costs no chip time:
a kernel it cannot lower, a program that does not fit 16 GB.

All of it in this one file and inside fixtures, as the on-chip-measurement
guide says: only the worker that runs this file loads the TPU's library.
"""

import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

# What the chip's compiler allows a program: 15.75 GiB of the 16, less
# the 258 MiB it reserves (its own message, PR 22's first chip run).
HBM_BYTES = (15.75 * 1024 - 258) * 1024 * 1024


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no compiler here: skip
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture()
def as_on_tpu(monkeypatch):
    """The program asks utils.device which kernels to dispatch; this
    process runs on the CPU, so the test answers for the described chip.
    The persistent cache cannot hold what it cannot read back."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache
    from p2p_llm_chat_tpu.utils import device
    monkeypatch.setattr(device, "platform", lambda: "tpu")
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", True)
    compilation_cache.reset_cache()


@pytest.mark.parametrize("name,window", [
    ("mistral-7b-v0.3", 512), ("mixtral-8x7b-v0.1-l6", 512)])
def test_decode_program_compiles_and_fits(name, window, one_chip, as_on_tpu):
    import jax
    import jax.numpy as jnp
    from benchmark import roofline, serve_cell
    from p2p_llm_chat_tpu.models import family_for
    from p2p_llm_chat_tpu.ops.paged_kv import PagedKVCache
    with open(os.path.join(ROOT, "benchmark", "configs",
                           name + ".json")) as f:
        cfg = json.load(f)
    config = serve_cell.model_config(cfg)
    model = family_for(config)
    slots = int(cfg["stack"]["SERVE_SLOTS"])
    pages = int(cfg["stack"]["SERVE_PAGES"])
    ps = int(cfg["stack"]["SERVE_PAGE_SIZE"])
    per_row = int(cfg["stack"]["SERVE_MAX_SEQ"]) // ps

    def described(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one_chip), tree)

    params = described(jax.eval_shape(
        lambda k: model.init_params_quantized(config, k, quant="int8"),
        jax.random.PRNGKey(0)))
    cache = described(jax.eval_shape(lambda: PagedKVCache.create(
        config, slots, pages, ps, max_pages_per_row=per_row,
        quantized=True)))
    tokens = jax.ShapeDtypeStruct((slots, 1), jnp.int32, sharding=one_chip)

    def step(params, tokens, cache):
        return model.decode_step_paged(params, config, tokens, cache,
                                       pages=window // ps)

    # tests/conftest.py asks for "highest" matmul precision everywhere;
    # the server on the chip runs with JAX's default, and Mosaic refuses
    # a bf16 kernel matmul at float32 precision.
    with jax.default_matmul_precision("default"):
        compiled = jax.jit(step, donate_argnums=(2,)).lower(
            params, tokens, cache).compile()
    assert "tpu_custom_call" in compiled.as_text(), \
        "the decode step holds no Pallas kernel"
    mem = compiled.memory_analysis()
    resident = mem.argument_size_in_bytes + mem.temp_size_in_bytes
    assert resident < HBM_BYTES, (name, resident)
    # The resident arguments are what roofline.py says the model and the
    # pool weigh (the pool's scales are stored padded to 128 lanes).
    want = (roofline.model_weight_bytes(cfg)
            + roofline.kv_pool_bytes(cfg, pages, ps, padded=True))
    assert mem.argument_size_in_bytes == pytest.approx(want, rel=0.02)


@pytest.mark.parametrize("name", ["mistral-7b-v0.3", "mixtral-8x7b-v0.1-l6"])
def test_admit_prefill_compiles_and_fits(name, one_chip, as_on_tpu):
    """The admission's prefill at its largest footprint (the scheduler's
    budget of 16,384 tokens a dispatch: 32 rows of a 512-token bucket):
    its temporaries must fit beside the weights and the pool. This is
    the program that did not fit beside a pool of 32 x 2048 tokens on
    the chip (PERF.md, Findings, PR 22)."""
    import jax
    import jax.numpy as jnp
    from benchmark import roofline, serve_cell
    from p2p_llm_chat_tpu.models import family_for
    from p2p_llm_chat_tpu.models.llama import KVCache
    with open(os.path.join(ROOT, "benchmark", "configs",
                           name + ".json")) as f:
        cfg = json.load(f)
    config = serve_cell.model_config(cfg)
    model = family_for(config)
    R, S = 32, 512
    params = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        jax.eval_shape(
            lambda k: model.init_params_quantized(config, k, quant="int8"),
            jax.random.PRNGKey(0)))
    tokens = jax.ShapeDtypeStruct((R, S), jnp.int32, sharding=one_chip)
    lens = jax.ShapeDtypeStruct((R,), jnp.int32, sharding=one_chip)

    def admit(params, tokens, lens):
        small = KVCache.create(config, R, S)
        return model.prefill(params, config, tokens, lens, small, None,
                             last_only=True)

    with jax.default_matmul_precision("default"):
        compiled = jax.jit(admit).lower(params, tokens, lens).compile()
    mem = compiled.memory_analysis()
    pool = roofline.kv_pool_bytes(
        cfg, int(cfg["stack"]["SERVE_PAGES"]),
        int(cfg["stack"]["SERVE_PAGE_SIZE"]), padded=True)
    total = (mem.argument_size_in_bytes + mem.temp_size_in_bytes
             + mem.output_size_in_bytes + pool)
    print(name, "admit 32x512:", mem.argument_size_in_bytes / 2**30,
          mem.temp_size_in_bytes / 2**30, mem.output_size_in_bytes / 2**30,
          "pool", pool / 2**30, "total GiB", total / 2**30)
    assert total < HBM_BYTES, (name, total)
