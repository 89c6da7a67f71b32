"""Bring-up contracts that need no device: where the compile cache
lands, which KV layout a boot gets, and that the chip smoke fails —
fast, naming the device — on a machine with no chip."""

import json
import os
import subprocess
import sys
import time

import jax
import pytest

from p2p_llm_chat_tpu.utils import jax_cache
from p2p_llm_chat_tpu.utils.chips import count_chips

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def config_updates(monkeypatch):
    seen = {}
    monkeypatch.setattr(jax.config, "update",
                        lambda key, value: seen.__setitem__(key, value))
    return seen


def test_cache_dir_from_the_environment_is_never_set_in_code(
        monkeypatch, tmp_path, config_updates):
    placed = str(tmp_path / "placed-from-outside")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", placed)
    assert jax_cache.enable_persistent_cache() == placed
    assert "jax_compilation_cache_dir" not in config_updates
    assert os.path.isdir(placed)


def test_cache_dir_defaults_to_the_checkout(monkeypatch, config_updates):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    want = os.path.join(ROOT, ".jax_cache")
    assert jax_cache.enable_persistent_cache() == want
    assert config_updates["jax_compilation_cache_dir"] == want


def test_cache_dir_that_cannot_be_created_is_an_error(
        monkeypatch, tmp_path, config_updates):
    (tmp_path / "file").write_text("not a directory")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR",
                       str(tmp_path / "file" / "cache"))
    with pytest.raises(OSError):
        jax_cache.enable_persistent_cache()


@pytest.mark.skipif(count_chips() > 0, reason="this host has a TPU chip")
@pytest.mark.parametrize("serve_kv", [None, "paged", "dense"])
def test_boot_serves_the_paged_pool_and_refuses_any_other_layout(
        serve_kv, monkeypatch):
    """SERVE_KV is no longer an option: unset and ``paged`` (which
    deployments still export) boot the same pool, anything else ends
    the boot with the reason."""
    from p2p_llm_chat_tpu.ops.paged_kv import PagedKVCache
    from p2p_llm_chat_tpu.serve.engine import build_engine_from_env
    monkeypatch.delenv("SERVE_KV", raising=False)
    if serve_kv is not None:
        monkeypatch.setenv("SERVE_KV", serve_kv)
    for key, value in (("MODEL_CONFIG", "tiny"), ("SERVE_SLOTS", "2"),
                       ("SERVE_MAX_SEQ", "128"), ("SERVE_PAGE_SIZE", "16"),
                       ("SERVE_WARMUP", "0")):
        monkeypatch.setenv(key, value)
    if serve_kv == "dense":
        with pytest.raises(SystemExit, match="dense serving was removed "
                                             "in PR 28"):
            build_engine_from_env()
        return
    eng = build_engine_from_env()
    try:
        sched = eng.scheduler
        assert isinstance(sched._cache, PagedKVCache)
        assert sched.num_pages == 2 * (128 // 16) + 1
    finally:
        eng.stop()


def test_chip_smoke_fails_fast_without_a_chip():
    """The smoke forces its server onto the TPU (it must not inherit
    this suite's JAX_PLATFORMS=cpu and pass on the CPU): with no chip
    the boot dies, and the smoke exits non-zero with the reason."""
    t0 = time.monotonic()
    r = subprocess.run([sys.executable, os.path.join(ROOT, "chip_smoke.py")],
                       capture_output=True, text=True, timeout=120)
    assert r.returncode != 0
    assert time.monotonic() - t0 < 60
    assert "needs a TPU" in r.stderr, r.stderr[-2000:]
    last = r.stdout.strip().splitlines()[-1]
    with pytest.raises(ValueError):     # no result line on a failure
        json.loads(last)
