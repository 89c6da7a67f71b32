"""Test config: force JAX onto a virtual 8-device CPU mesh.

Per SURVEY.md §4 — same model code under jax.sharding runs on CPU with a
faked device count; real-TPU paths are exercised by chip_smoke.py and the
tools/check_*_kernel.py scripts instead. Must run before jax is imported
anywhere.

The platform is pinned twice on purpose: the JAX_PLATFORMS env var covers
the subprocesses tests spawn (and is what serve/engine.py accepts as the
operator's explicit CPU pin), ``jax.config.update`` covers this process
even when a caller exported something else.
"""

import os
import sys
import time

# Env vars still set for any subprocesses tests spawn.
os.environ["JAX_PLATFORMS"] = "cpu"
# Hermetic networking: node daemons must not probe the CI host's real
# gateway for NAT-PMP during tests (test_natpmp.py opts back in against
# a fake gateway explicitly).
os.environ.setdefault("NATPMP", "0")
# Containers without the `cryptography` package: opt the p2p plane into
# the explicit INSECURE stdlib dev fallback (p2p/devcrypto.py) so the
# whole p2p suite RUNS here instead of dying at collection — the suites
# test protocol logic, not the crypto library, and the shim preserves
# the functional contracts (tamper -> InvalidSignature, peer-id
# round-trips, commutative key agreement). Where cryptography exists
# the flag is inert: the real imports win.
try:
    import importlib.util as _ilu
    if _ilu.find_spec("cryptography") is None:
        os.environ.setdefault("P2P_DEV_CRYPTO", "1")
except Exception:   # noqa: BLE001 — probing only
    pass
os.environ.setdefault("JAX_DEFAULT_MATMUL_PRECISION", "highest")
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_default_matmul_precision", "highest")
# Persistent compilation cache: the model suites compile hundreds of
# small programs; caching them across test processes cuts wall time
# dramatically on small hosts (first full run pays, reruns reuse). The
# production helper decides the directory — JAX_COMPILATION_CACHE_DIR
# when the environment sets it, else the fixed <checkout>/.jax_cache —
# so tests, in-process engine builds and spawned servers share one.
from p2p_llm_chat_tpu.utils.jax_cache import (  # noqa: E402
    enable_persistent_cache)

enable_persistent_cache()
# XLA:CPU's async dispatch runs eager ops on a background thread; with
# the serving suites' heavy buffer donation it has produced sporadic
# heap-corruption segfaults in long multi-suite processes (three crash
# dumps, each detonating at a different later XLA entry point).
# Synchronous dispatch removes that class of races on the test platform;
# TPU execution is unaffected.
jax.config.update("jax_cpu_enable_async_dispatch", False)

assert jax.devices()[0].platform == "cpu", (
    f"tests must run on CPU, got {jax.devices()}")

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# Runtime guarded-by enforcement (tools/graftcheck/lockcheck.py): under
# GRAFTCHECK_LOCKCHECK=1 every class-level `# guarded-by:` attribute in
# the serving + chat planes is rewritten into a descriptor asserting
# the named lock is held by the current thread — the annotations the
# static analyzer reads become executable assertions exercised by the
# threaded suites. (Module-level globals carrying the comment, e.g.
# utils/backoff._retries_total, are documentation only in both worlds —
# the grammar is class-scoped; docs/static-analysis.md §lockcheck.)
# (ci.sh full runs test_router/test_kv_tier/test_loadgen/test_stress
# this way). Must run here, before any test module builds a scheduler,
# router, or driver instance — pre-existing instances would keep their
# state under the un-mangled attribute names.
if os.environ.get("GRAFTCHECK_LOCKCHECK") == "1":
    from tools.graftcheck import lockcheck as _lockcheck
    _lockcheck.install(root=os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))))


# Model-heavy modules get the `model` marker automatically, so the
# chat-plane suite stays sub-minute: `pytest -m "not model"`.
_MODEL_TEST_MODULES = {"test_llama_parity", "test_engine", "test_sampling",
                       "test_pipeline", "test_checkpoint", "test_quant", "test_spec", "test_stress",
                       "test_mixtral_parity", "test_sharding", "test_ops",
                       "test_weights", "test_prefix", "test_embed",
                       "test_serve_tp", "test_fused_decode",
                       "test_chunked_prefill"}

import pytest  # noqa: E402


def pytest_runtest_logreport(report):
    if report.when == "call" and os.environ.get("DEBUG_MAPS"):
        import threading
        print(f" [maps={_maps()} threads={threading.active_count()}]",
              file=sys.stderr, flush=True)


# The model suites compile hundreds of XLA:CPU executables in one pytest
# process; each loaded executable holds multiple mmap regions, and the
# process was measured hitting vm.max_map_count (default 65530) —
# at which point the NEXT executable load dies with SIGSEGV/SIGABRT
# inside XLA (observed as "random" late-suite segfaults; DEBUG_MAPS=1
# prints the per-test map count). Two defenses:
#
# 1. drop every cached executable between test modules — modules build
#    their own engines/programs anyway, and the persistent compilation
#    cache (above) makes re-loads cheap — and inside a module once it
#    has come two thirds of the way to the limit (tests/test_engine.py
#    alone ends at 61-65 K regions: PR 42 met the limit there);
# 2. where permitted (root), raise the kernel limit outright.

_MAPS_HIGH = 44_000


def _maps() -> int:
    try:
        with open("/proc/self/maps") as f:
            return sum(1 for _ in f)
    except OSError:
        return 0


def pytest_runtest_teardown(item, nextitem):
    if (nextitem is None or item.module is not nextitem.module
            or _maps() > _MAPS_HIGH):
        import gc
        import jax as _jax
        # clear_caches() walks a weakref set that any still-settling
        # background thread (scheduler/redelivery workers from the
        # module just torn down) can mutate mid-iteration, raising
        # "Set changed size during iteration" — which fails THIS test's
        # teardown and the NEXT test's setup as collateral. The clear
        # is memory hygiene, not a correctness gate: retry once, then
        # let the next boundary pick it up.
        for _ in range(2):
            try:
                _jax.clear_caches()
                break
            except RuntimeError:
                time.sleep(0.1)
        gc.collect()


def _raise_map_count(target: int = 1_048_576) -> None:
    """Opt-in (PYTEST_RAISE_MAP_COUNT=1): writing a machine-global
    kernel tunable as a pytest side effect is too invasive to do
    silently — defense 1 suffices on its own; this is the backstop for
    operators who want headroom (e.g. running many suites in one
    process) and are prepared to change host state."""
    if os.environ.get("PYTEST_RAISE_MAP_COUNT") != "1":
        return
    try:
        with open("/proc/sys/vm/max_map_count") as f:
            current = int(f.read().strip())
        if current < target:
            with open("/proc/sys/vm/max_map_count", "w") as f:
                f.write(str(target))
            print(f"conftest: raised vm.max_map_count {current} -> {target}",
                  file=sys.stderr)
    except (OSError, ValueError):
        pass    # not privileged: defense 1 still applies


_raise_map_count()


# Tier-2 modules, auto-marked `slow`: exactly the set ci.sh's fast gate
# excludes from the generic sweep (exhaustive HF-parity matrices, the
# chaos/stress suite, TP-sharded serving, the prefix-cache matrix, and
# the chunked-prefill parity file — which ci.sh instead runs in its own
# dedicated single-device-CPU invocation, the only topology where its
# exact model-level asserts execute rather than skip). The tier-1 gate
# runs `-m "not slow"` under a hard timeout; before these marks existed
# the gate ran the slow matrices first (alphabetical order) and was
# killed mid-suite — ~100 later tests (sampling, serve_api, spec,
# weights, the fused-decode parity matrix) never executed at all, which
# is strictly less correctness coverage per gate run than deselecting
# the tier-2 suites and finishing. ci.sh `full` still runs everything.
_SLOW_TEST_MODULES = {"test_llama_parity", "test_mixtral_parity",
                      "test_prefix", "test_serve_tp", "test_stress",
                      "test_chunked_prefill"}


def pytest_collection_modifyitems(config, items):
    for item in items:
        if item.module.__name__ in _MODEL_TEST_MODULES:
            item.add_marker(pytest.mark.model)
        if item.module.__name__ in _SLOW_TEST_MODULES:
            item.add_marker(pytest.mark.slow)
