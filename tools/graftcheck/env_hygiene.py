"""Env-flag hygiene analyzer.

Every ``SERVE_*``/``FAIL_*``
(config.env_prefixes) environment read must:

- go through the typed helpers in ``utils/env.py`` (``env_or``,
  ``env_int``, ``env_float``, ``env_bool``, plus ``env_opt`` for the
  flags whose documented OFF spelling is the empty string) — a raw
  ``os.environ`` read
  bypasses the empty-string-is-unset contract the whole stack relies on
  (``env-hygiene/raw-read``, tag ``env-ok``);
- appear in the docs flag table (config.docs_files, default
  ``docs/serving.md``) so every operator-visible knob is discoverable
  (``env-hygiene/undocumented``, tag ``env-ok``).

Writes (``os.environ[K] = v``, ``setdefault``) are out of scope — tests
and launchers legitimately *set* flags.
"""

from __future__ import annotations

import ast
import os
import re

from .core import Config, Finding, SourceFile, str_const

_HELPERS = {"env_or", "env_int", "env_float", "env_bool", "env_opt"}


def _env_read_key(node: ast.Call) -> str | None:
    """Literal key of an os.environ.get / os.getenv read, else None."""
    f = node.func
    if isinstance(f, ast.Attribute):
        # os.environ.get("K"), environ.get("K")
        if f.attr == "get" and isinstance(f.value, ast.Attribute) \
                and f.value.attr == "environ":
            return str_const(node.args[0]) if node.args else None
        if f.attr == "get" and isinstance(f.value, ast.Name) \
                and f.value.id == "environ":
            return str_const(node.args[0]) if node.args else None
        # os.getenv("K")
        if f.attr == "getenv":
            return str_const(node.args[0]) if node.args else None
    elif isinstance(f, ast.Name) and f.id == "getenv":
        return str_const(node.args[0]) if node.args else None
    return None


def analyze(files: list[SourceFile], config: Config) -> list[Finding]:
    findings: list[Finding] = []
    docs_text = ""
    for rel in config.docs_files:
        path = os.path.join(config.root, rel)
        try:
            with open(path, encoding="utf-8") as fh:
                docs_text += fh.read()
        except OSError:
            pass
    flags_seen: list[tuple[SourceFile, int, str]] = []

    for sf in files:
        norm = sf.path.replace("\\", "/")
        is_env_module = norm.endswith(config.env_module)
        for node in ast.walk(sf.tree):
            if not isinstance(node, ast.Call):
                continue
            key = _env_read_key(node)
            if key is not None and key.startswith(config.env_prefixes):
                if not is_env_module:
                    findings.append(Finding(
                        sf.path, node.lineno, "env-hygiene/raw-read",
                        "env-ok",
                        f"`{key}` read via os.environ — use the typed "
                        "helpers in utils/env.py (env_or/env_int/"
                        "env_float/env_bool)"))
                flags_seen.append((sf, node.lineno, key))
                continue
            # env_or("K", ...) and friends, however imported
            fname = node.func.attr if isinstance(node.func, ast.Attribute) \
                else (node.func.id if isinstance(node.func, ast.Name)
                      else "")
            if fname in _HELPERS and node.args:
                key = str_const(node.args[0])
                if key is not None and key.startswith(config.env_prefixes):
                    flags_seen.append((sf, node.lineno, key))
            # Subscript read: os.environ["K"] (load context only)
        for node in ast.walk(sf.tree):
            if (isinstance(node, ast.Subscript)
                    and isinstance(node.ctx, ast.Load)
                    and isinstance(node.value, ast.Attribute)
                    and node.value.attr == "environ"):
                key = str_const(node.slice)
                if key is not None and key.startswith(config.env_prefixes):
                    if not sf.path.replace("\\", "/").endswith(
                            config.env_module):
                        findings.append(Finding(
                            sf.path, node.lineno, "env-hygiene/raw-read",
                            "env-ok",
                            f"`{key}` read via os.environ[...] — use the "
                            "typed helpers in utils/env.py"))
                    flags_seen.append((sf, node.lineno, key))

    if docs_text:
        # Exact backticked tokens only: a raw substring test would let
        # `SERVE_MAX` ride on the documented `SERVE_MAX_SEQ`.
        documented = set(re.findall(r"`([A-Z][A-Z0-9_]*)`", docs_text))
        reported: set[str] = set()
        for sf, line, key in flags_seen:
            if key in reported or key in documented:
                continue
            reported.add(key)
            findings.append(Finding(
                sf.path, line, "env-hygiene/undocumented", "env-ok",
                f"flag `{key}` is read here but missing from the docs "
                f"flag table ({', '.join(config.docs_files)})"))
    return findings
