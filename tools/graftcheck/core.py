"""Shared graftcheck machinery: file model, suppressions, runner.

Suppression/annotation comment grammar (one comment, N tags):

    # graftcheck: <tag>[,<tag>...] <reason>

A finding is suppressed when a matching tag with a non-empty reason
appears on the finding's line, the line above it, or the ``def`` line of
the enclosing function (function-level suppressions cover e.g. a whole
``stop()`` that legitimately touches scheduler-owned state after the
thread join). A graftcheck comment with no reason string is itself a
finding (``suppression`` rule): the policy is that every suppression
says *why* the flagged pattern is safe.

Structural annotations (consumed by individual analyzers, same comment
channel):

    self._store = {}          # guarded-by: _store_mu
    self._slots = [...]       # owned-by: _loop
    def _warm_window(self, w):  # graftcheck: runs-on _loop
"""

from __future__ import annotations

import ast
import io
import os
import re
import tokenize
from dataclasses import dataclass, field
from typing import Iterable, Optional

_GRAFT_RE = re.compile(r"#\s*graftcheck:\s*([a-z0-9_,\-]+)\s*(.*)")
_GUARDED_RE = re.compile(r"#\s*guarded-by:\s*([A-Za-z_][A-Za-z0-9_]*)")
_OWNED_RE = re.compile(r"#\s*owned-by:\s*([A-Za-z_][A-Za-z0-9_]*)")
_RUNS_ON_RE = re.compile(r"#\s*graftcheck:\s*runs-on\s+([A-Za-z_][A-Za-z0-9_]*)")


@dataclass(frozen=True)
class Finding:
    path: str
    line: int
    rule: str        # e.g. "trace-safety/host-sync"
    tag: str         # suppression tag, e.g. "sync-ok"
    message: str

    def render(self) -> str:
        return f"{self.path}:{self.line}: [{self.rule}] {self.message}"


@dataclass
class Config:
    """Knobs shared by the analyzers (defaults match this repo)."""

    env_prefixes: tuple[str, ...] = ("SERVE_", "FAIL_", "LOADGEN_",
                                     "P2P_", "TRACE_", "DIR_")
    env_module: str = "utils/env.py"           # the one blessed reader
    docs_files: tuple[str, ...] = ("docs/serving.md",)
    pytest_ini: str = "pytest.ini"
    # Modules where EVERY forced host sync must be annotated sync-ok —
    # the serving hot path, where an unannounced sync is a latency bug.
    hot_sync_modules: tuple[str, ...] = (
        "serve/scheduler.py", "serve/engine.py", "serve/multihost.py")
    # Directories whose locks are latency fences: a blocking call under
    # a held lock there is a plane-wide stall (blocking analyzer).
    hot_lock_dirs: tuple[str, ...] = ("serve/", "p2p/", "loadgen/", "obs/")
    # Metrics contract (metrics_contract analyzer): the name grammar
    # every in-tree series follows, the docs that list series for
    # operators, and the dirs whose string literals count as consumer
    # references (the router's aggregation tables live under serve/).
    metric_prefixes: tuple[str, ...] = (
        "serve_", "kv_", "prefix_", "router_", "decode_", "inter_token_",
        "failpoint_", "retry_", "requests_", "loop_", "prefill_", "model_",
        "p2p_", "directory_")
    metric_suffixes: tuple[str, ...] = (
        "_total", "_seconds", "_ms", "_bytes", "_sessions", "_pages",
        "_depth", "_slots", "_occupancy", "_requests", "_entries")
    metrics_docs: tuple[str, ...] = ("docs/serving.md",)
    metrics_consumer_dirs: tuple[str, ...] = ("serve/",)
    # Donation safety (donation analyzer): modules on the decode hot
    # path where a carried cache/pool jit argument left undonated is a
    # silent HBM-copy-per-tick — there it must either be donated or
    # carry an explicit `# graftcheck: nodonate <reason>`.
    donate_hot_modules: tuple[str, ...] = (
        "serve/scheduler.py", "serve/engine.py", "serve/multihost.py",
        "serve/draft_model.py")
    donate_carry_params: tuple[str, ...] = ("cache", "pool")
    # Failpoint-site contract (failpoint_contract analyzer): the
    # registry module + tuple name, the docs catalog carrying the
    # marked site table, the site-name grammar prefixes a spec literal
    # must be registered under (scratch test sites use other prefixes),
    # and where arming evidence lives.
    failpoints_module: str = "utils/failpoints.py"
    failpoint_registry: str = "KNOWN_SITES"
    failpoint_prefixes: tuple[str, ...] = ("serve.", "p2p.")
    failpoint_docs: tuple[str, ...] = ("docs/robustness.md",)
    failpoint_test_dirs: tuple[str, ...] = ("tests",)
    failpoint_ci_files: tuple[str, ...] = ("ci.sh",)
    # HTTP wire contract (http_contract analyzer): the serve/chat front
    # modules the 503/NDJSON/proxy-header disciplines apply to, the
    # fronts whose route tables are a documented operator contract, and
    # the docs file carrying the marked endpoint catalog.
    http_modules: tuple[str, ...] = ("serve/", "loadgen/", "ui.py",
                                     "node.py")
    endpoint_modules: tuple[str, ...] = ("serve/api.py", "serve/router.py",
                                         "ui.py", "node.py", "directory.py")
    endpoint_docs: tuple[str, ...] = ("docs/serving.md",)
    # Source set for cross-file analyses (lock-order class models and
    # declarations, metrics export sites): resolved against the FULL
    # package tree even when only a few files were selected, so a
    # partial run (`python -m tools.graftcheck serve/scheduler.py`)
    # never false-fails on a contract whose other half lives in an
    # unselected file.
    package_dirs: tuple[str, ...] = ("p2p_llm_chat_tpu",)
    root: str = "."


class SourceFile:
    """One parsed Python file plus its comment/annotation side tables."""

    def __init__(self, path: str, text: str) -> None:
        self.path = path
        self.text = text
        self.tree = ast.parse(text, filename=path)
        # line -> full comment text (including the leading '#')
        self.comments: dict[int, str] = {}
        # lines whose comment stands alone (nothing but whitespace before
        # it) — structural annotations only look UP to these, so a
        # trailing `# guarded-by:` on line N can't bleed onto the
        # unrelated assignment on line N+1 (e.g. the lock itself).
        self.own_line_comments: set[int] = set()
        try:
            for tok in tokenize.generate_tokens(io.StringIO(text).readline):
                if tok.type == tokenize.COMMENT:
                    row, col = tok.start
                    self.comments[row] = tok.string
                    if not tok.line[:col].strip():
                        self.own_line_comments.add(row)
        except tokenize.TokenizeError:
            pass
        # line -> {tag: reason}
        self.suppressions: dict[int, dict[str, str]] = {}
        self.bad_suppressions: list[int] = []
        for line, comment in self.comments.items():
            m = _GRAFT_RE.search(comment)
            if not m:
                continue
            tags = [t for t in m.group(1).split(",") if t]
            reason = m.group(2).strip()
            if tags == ["runs-on"]:
                continue             # structural, parsed via runs_on()
            if not reason:
                self.bad_suppressions.append(line)
                continue
            self.suppressions.setdefault(line, {}).update(
                {t: reason for t in tags})
        # def-lineno set (for function-level suppression lookup)
        self._def_lines: list[tuple[int, int, int]] = []   # (start, end, defline)
        # statement spans, for trailing-comment suppression scoping
        self._stmt_spans: list[tuple[int, int]] = []
        for node in ast.walk(self.tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                end = getattr(node, "end_lineno", node.lineno)
                self._def_lines.append((node.lineno, end, node.lineno))
            if isinstance(node, ast.stmt):
                self._stmt_spans.append(
                    (node.lineno, getattr(node, "end_lineno", node.lineno)))

    def comment_on(self, line: int) -> str:
        return self.comments.get(line, "")

    def _structural(self, line: int, regex: re.Pattern) -> Optional[str]:
        """Same-line trailing comment, or an own-line comment just above
        (a trailing comment on the PREVIOUS statement never applies)."""
        m = regex.search(self.comments.get(line, ""))
        if m:
            return m.group(1)
        if line - 1 in self.own_line_comments:
            m = regex.search(self.comments.get(line - 1, ""))
            if m:
                return m.group(1)
        return None

    def guarded_by(self, line: int) -> Optional[str]:
        return self._structural(line, _GUARDED_RE)

    def owned_by(self, line: int) -> Optional[str]:
        return self._structural(line, _OWNED_RE)

    def runs_on(self, def_line: int) -> Optional[str]:
        for ln in (def_line, def_line - 1):
            m = _RUNS_ON_RE.search(self.comments.get(ln, ""))
            if m:
                return m.group(1)
        return None

    def _same_statement(self, line: int, other: int) -> bool:
        """True when ``line`` and ``other`` fall inside one statement —
        the tightest statement span containing ``line`` also covers
        ``other``. Scopes trailing-comment suppressions: a trailing
        comment mid-way through a multi-line call suppresses findings
        on that call's later physical lines, but a trailing comment on
        a *separate previous statement* must not leak onto this one."""
        best = None
        for start, end in self._stmt_spans:
            if start <= line <= end:
                if best is None or start > best[0]:
                    best = (start, end)
        return best is not None and best[0] <= other <= best[1]

    def suppressed(self, line: int, tag: str) -> bool:
        if tag in self.suppressions.get(line, {}):
            return True
        # Line above: an own-line comment always applies; a TRAILING
        # comment applies only from inside the same (multi-line)
        # statement, never from the statement before.
        if tag in self.suppressions.get(line - 1, {}):
            if (line - 1 in self.own_line_comments
                    or self._same_statement(line, line - 1)):
                return True
        # Function-level: the def line of the tightest enclosing function.
        best = None
        for start, end, defline in self._def_lines:
            if start <= line <= end:
                if best is None or start > best[0]:
                    best = (start, end, defline)
        if best is not None:
            for ln in (best[2], best[2] - 1):
                if tag in self.suppressions.get(ln, {}):
                    return True
        return False


def load_files(paths: Iterable[str]) -> tuple[list[SourceFile], list[Finding]]:
    """Collect .py files under ``paths`` (files or directories)."""
    files: list[SourceFile] = []
    findings: list[Finding] = []
    seen: set[str] = set()
    for p in paths:
        if os.path.isfile(p):
            candidates = [p]
        elif not os.path.isdir(p):
            # A typo'd target must be a loud usage error, not a silent
            # 0-file 'clean' run that neuters the CI gate.
            raise ValueError(f"no such file or directory: {p}")
        else:
            candidates = []
            for dirpath, dirnames, filenames in os.walk(p):
                dirnames[:] = [d for d in dirnames
                               if d not in ("__pycache__", ".git",
                                            "testdata", ".jax_cache")]
                candidates.extend(os.path.join(dirpath, f)
                                  for f in sorted(filenames)
                                  if f.endswith(".py"))
        for c in sorted(candidates):
            c = os.path.normpath(c)
            if c in seen:
                continue
            seen.add(c)
            try:
                with open(c, encoding="utf-8") as fh:
                    text = fh.read()
            except OSError as e:
                findings.append(Finding(c, 0, "io/read", "io-ok",
                                        f"unreadable: {e}"))
                continue
            try:
                files.append(SourceFile(c, text))
            except SyntaxError as e:
                findings.append(Finding(c, e.lineno or 0, "io/syntax",
                                        "io-ok", f"syntax error: {e.msg}"))
    return files, findings


def apply_suppressions(files: list[SourceFile],
                       findings: list[Finding]) -> list[Finding]:
    by_path = {f.path: f for f in files}
    out = []
    for fi in findings:
        sf = by_path.get(fi.path)
        if sf is not None and sf.suppressed(fi.line, fi.tag):
            continue
        out.append(fi)
    # Reason-less graftcheck comments are findings of their own.
    for sf in files:
        for line in sf.bad_suppressions:
            out.append(Finding(
                sf.path, line, "suppression/no-reason", "suppression-ok",
                "graftcheck suppression without a reason string — every "
                "suppression must say why the pattern is safe"))
    return out


def run_paths(paths: Iterable[str], config: Optional[Config] = None,
              select: Optional[Iterable[str]] = None) -> list[Finding]:
    """Load files and run the selected analyzers (default: all)."""
    from . import (blocking, donation, env_hygiene, failpoint_contract,
                   http_contract, lock_discipline, lock_order, markers,
                   metrics_contract, stream_close, trace_safety)

    config = config or Config()
    analyzers = {
        "trace": trace_safety.analyze,
        "lock": lock_discipline.analyze,
        "env": env_hygiene.analyze,
        "markers": markers.analyze,
        "order": lock_order.analyze,
        "blocking": blocking.analyze,
        "metrics": metrics_contract.analyze,
        "streams": stream_close.analyze,
        "donation": donation.analyze,
        "failpoints": failpoint_contract.analyze,
        "http": http_contract.analyze,
    }
    names = list(select) if select else list(analyzers)
    unknown = [n for n in names if n not in analyzers]
    if unknown:
        raise ValueError(f"unknown analyzer(s): {', '.join(unknown)} "
                         f"(have: {', '.join(analyzers)})")
    files, findings = load_files(paths)
    for name in names:
        findings.extend(analyzers[name](files, config))
    findings = apply_suppressions(files, findings)
    findings.sort(key=lambda f: (f.path, f.line, f.rule))
    return findings


_TREE_CACHE: dict[tuple, list[SourceFile]] = {}


def load_package_tree(config: Config,
                      covered: frozenset = frozenset(),
                      dirs: Optional[tuple[str, ...]] = None,
                      ) -> list[SourceFile]:
    """The full package source set (config.package_dirs under
    config.root, or an analyzer-supplied ``dirs`` tuple — the failpoint
    contract resolves against package + test dirs), cached per
    (root, dirs) — the resolution context for cross-file analyzers on
    partial runs. Missing dirs (fixture roots) yield an empty tree,
    which degrades those analyzers to the analyzed-set-only behavior
    the fixture tests pin. ``covered`` paths the caller already parsed
    short-circuit the load when they span the whole tree (the CI full
    run — the union would discard these parses anyway)."""
    dirs = dirs if dirs is not None else config.package_dirs
    paths = [p for p in (os.path.join(config.root, d)
                         for d in dirs)
             if os.path.isdir(p)]
    # Key on each file's (path, mtime, size) so a long-lived process
    # (fixture tests rewriting sources, a future watch mode) never
    # resolves against a stale first-load tree; listing + stat is cheap
    # next to re-parsing.
    sig = []
    for p in paths:
        for dirpath, dirnames, filenames in os.walk(p):
            dirnames[:] = [d for d in dirnames
                           if d not in ("__pycache__", ".git",
                                        "testdata", ".jax_cache")]
            for f in sorted(filenames):
                if f.endswith(".py"):
                    fp = os.path.join(dirpath, f)
                    try:
                        st = os.stat(fp)
                        sig.append((fp, st.st_mtime_ns, st.st_size))
                    except OSError:
                        continue
    if sig and all(os.path.normpath(fp) in covered
                   for fp, _, _ in sig):
        return []
    key = (os.path.abspath(config.root), dirs, tuple(sig))
    if key not in _TREE_CACHE:
        # A handful of live trees per process: the package tree and the
        # package+tests tree coexist in one run, and fixture tests cycle
        # a few roots — evict oldest-first past that.
        while len(_TREE_CACHE) >= 4:
            _TREE_CACHE.pop(next(iter(_TREE_CACHE)))
        files, _ = load_files(paths)
        _TREE_CACHE[key] = files
    return _TREE_CACHE[key]


def resolution_files(files: list[SourceFile],
                     config: Config,
                     dirs: Optional[tuple[str, ...]] = None,
                     ) -> list[SourceFile]:
    """Analyzed set ∪ package tree, analyzed objects taking precedence
    (so node-identity side tables built during scanning stay consistent
    with the objects other passes walk)."""
    covered = frozenset(sf.path for sf in files)
    union = {sf.path: sf
             for sf in load_package_tree(config, covered, dirs)}
    union.update({sf.path: sf for sf in files})
    return list(union.values())


# -- small shared AST helpers -------------------------------------------------

LOCK_CTORS = {"Lock", "RLock", "Condition", "Semaphore", "BoundedSemaphore"}
REENTRANT_LOCK_CTORS = {"RLock"}


def walk_class_scope(cls: ast.ClassDef):
    """Like ``ast.walk(cls)`` over the class body, but without
    descending into nested ClassDefs — a nested class's ``self.<attr>``
    assigns belong to the nested class, not the enclosing one (it gets
    its own model/lock set from the outer ClassDef scan)."""
    stack = list(ast.iter_child_nodes(cls))
    while stack:
        node = stack.pop()
        if isinstance(node, ast.ClassDef):
            continue
        yield node
        stack.extend(ast.iter_child_nodes(node))


def walk_function_scope(fn: ast.AST):
    """Like ``ast.walk`` over a function's body, but without descending
    into nested defs/lambdas — those run later, on whatever thread
    calls them, so what they acquire is not what their definer
    acquires (the lock-discipline scoping rule)."""
    stack = list(ast.iter_child_nodes(fn))
    while stack:
        node = stack.pop()
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                             ast.Lambda)):
            continue
        yield node
        stack.extend(ast.iter_child_nodes(node))


def self_attr(node: ast.AST) -> Optional[str]:
    """'x' for a bare ``self.x`` attribute node; None otherwise."""
    if (isinstance(node, ast.Attribute)
            and isinstance(node.value, ast.Name)
            and node.value.id == "self"):
        return node.attr
    return None


def lock_ctor(value: ast.AST) -> Optional[bool]:
    """True/False = a threading lock constructor call (True when a
    second same-thread acquire is legal); None = not one."""
    if not isinstance(value, ast.Call):
        return None
    base = dotted_name(value.func).rsplit(".", 1)[-1]
    if base not in LOCK_CTORS:
        return None
    if base in REENTRANT_LOCK_CTORS:
        return True
    if base == "Condition":
        # Condition() wraps an RLock by default; Condition(lock) has
        # the wrapped lock's reentrancy.
        if not value.args:
            return True
        return bool(lock_ctor(value.args[0]))
    if base in ("Semaphore", "BoundedSemaphore"):
        # An initial count > 1 means a second same-thread acquire just
        # takes another permit — not a self-deadlock. Default is 1,
        # which does block.
        count = None
        if value.args:
            count = value.args[0]
        for kw in value.keywords:
            if kw.arg == "value":
                count = kw.value
        return (isinstance(count, ast.Constant)
                and isinstance(count.value, int) and count.value > 1)
    return False


def dotted_name(node: ast.AST) -> str:
    """'jax.lax.scan' for nested Attribute/Name chains; '' otherwise."""
    parts: list[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return ""


def iter_functions(tree: ast.AST):
    """Yield every (Async)FunctionDef/Lambda with its parent chain."""
    def walk(node, chain):
        for child in ast.iter_child_nodes(node):
            if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef,
                                  ast.Lambda)):
                yield child, chain
                yield from walk(child, chain + [child])
            else:
                yield from walk(child, chain)
    yield from walk(tree, [])


def str_const(node: ast.AST) -> Optional[str]:
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return node.value
    return None
