"""graftcheck: in-tree static analysis for the jax_graft serving stack.

Three bug classes sink a threaded JAX serving stack, and all three are
invisible to generic linters:

- **trace-safety**: a host sync (``np.asarray``, ``.item()``,
  ``block_until_ready``) or a Python branch on a tracer inside code
  reachable from a ``jax.jit``/``lax.scan`` entry point — the exact
  family of silent hot-path regressions behind the 36% wall/device gap
  PR 1 closed.
- **lock-discipline**: shared mutable attributes in the threaded
  serving/P2P planes accessed outside their declared lock
  (``# guarded-by: <lock>``) or off their owning thread
  (``# owned-by: <entry>``).
- **env-flag hygiene**: ``SERVE_*``/``FAIL_*`` reads that bypass
  ``utils/env.py`` or are missing from the docs flag table.

Run: ``python -m tools.graftcheck p2p_llm_chat_tpu/`` (see
docs/static-analysis.md for the analyzer catalog, annotation syntax and
suppression policy).
"""

from .core import Config, Finding, run_paths  # noqa: F401
