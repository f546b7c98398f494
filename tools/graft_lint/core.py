"""Source model, findings, suppressions, and the pass engine.

A pass is a callable taking (SourceFile, repo_root) and yielding
Findings (file passes), or taking (repo_root,) alone (project passes —
GL105, which scans a configured emission root independent of the CLI
paths so a run over one sub-package still validates every span
against the catalog).
"""
from __future__ import annotations

import ast
import os
import re
from typing import Dict, Iterable, List, Optional, Sequence, Set, Tuple

SEVERITIES = ("error", "warning")

# `# graft-lint: ok[GL102] reason` — suppress named rules on the line
# (or, when the comment is a whole line, on the next line). A bare
# `# graft-lint: ok — reason` suppresses every rule at that site.
_SUPPRESS_RE = re.compile(
    r"#\s*graft-lint:\s*ok(?:\[(?P<rules>[A-Z0-9,\s]+)\])?")


class Finding:
    """One rule violation, anchored to file:line."""

    __slots__ = ("rule", "severity", "path", "line", "col", "message",
                 "hint", "code", "occ")

    def __init__(self, rule: str, severity: str, path: str, line: int,
                 col: int, message: str, hint: str = "",
                 code: str = ""):
        assert severity in SEVERITIES, severity
        self.rule = rule
        self.severity = severity
        self.path = path.replace(os.sep, "/")
        self.line = line
        self.col = col
        self.message = message
        self.hint = hint
        self.code = code
        self.occ = 0  # n-th finding with the same (rule, path, code);
        #               assigned by run_passes — the line-number-free
        #               part of the baseline fingerprint

    def key(self) -> Tuple[str, str, str, int]:
        return (self.rule, self.path, self.code, self.occ)

    def as_dict(self) -> dict:
        return {"rule": self.rule, "severity": self.severity,
                "path": self.path, "line": self.line, "col": self.col,
                "message": self.message, "hint": self.hint,
                "code": self.code, "occ": self.occ}

    def render(self) -> str:
        loc = f"{self.path}:{self.line}:{self.col}"
        out = f"{loc}: {self.rule} {self.severity}: {self.message}"
        if self.hint:
            out += f"\n    hint: {self.hint}"
        if self.code:
            out += f"\n    >>> {self.code}"
        return out


class SourceFile:
    """One parsed Python file: text, lines, AST, suppression map."""

    def __init__(self, abspath: str, repo_root: str):
        self.abspath = abspath
        self.relpath = os.path.relpath(abspath, repo_root).replace(
            os.sep, "/")
        with open(abspath, "r", encoding="utf-8", errors="replace") as f:
            self.text = f.read()
        self.lines = self.text.splitlines()
        self.tree: Optional[ast.AST] = None
        self.parse_error: Optional[SyntaxError] = None
        try:
            self.tree = ast.parse(self.text, filename=self.relpath)
        except SyntaxError as e:  # surfaced as a GL001 finding
            self.parse_error = e
        # line -> set of suppressed rule ids ({"*"} = all)
        self.suppress: Dict[int, Set[str]] = {}
        for i, line in enumerate(self.lines, start=1):
            m = _SUPPRESS_RE.search(line)
            if not m:
                continue
            rules = m.group("rules")
            ids = ({r.strip() for r in rules.split(",") if r.strip()}
                   if rules else {"*"})
            target = i
            if line.lstrip().startswith("#"):
                # comment-only sanction: applies to the next code line
                # (skipping the rest of the comment block)
                j = i + 1
                while j <= len(self.lines) and (
                        not self.lines[j - 1].strip()
                        or self.lines[j - 1].lstrip().startswith("#")):
                    j += 1
                target = j
            self.suppress.setdefault(target, set()).update(ids)

    def suppressed(self, rule: str, line: int) -> bool:
        ids = self.suppress.get(line)
        return bool(ids) and ("*" in ids or rule in ids)

    def line_text(self, line: int) -> str:
        if 1 <= line <= len(self.lines):
            return self.lines[line - 1].strip()
        return ""

    def finding(self, rule: str, severity: str, node: ast.AST,
                message: str, hint: str = "") -> Finding:
        line = getattr(node, "lineno", 1)
        col = getattr(node, "col_offset", 0)
        return Finding(rule, severity, self.relpath, line, col, message,
                       hint, code=self.line_text(line))


# ---------------------------------------------------------------------------
# small AST helpers shared by the passes
# ---------------------------------------------------------------------------

def dotted(node: ast.AST) -> str:
    """Best-effort dotted name of an expression: `jax.jit`,
    `self._lock`, `functools.partial` — "" when not a name chain."""
    parts: List[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return ""


def call_target(call: ast.Call) -> str:
    """Dotted name of a call's callee ("" for computed callees)."""
    return dotted(call.func)


def terminal_name(node: ast.AST) -> str:
    """Last attribute segment of a name chain (`a.b.c` -> "c")."""
    if isinstance(node, ast.Attribute):
        return node.attr
    if isinstance(node, ast.Name):
        return node.id
    return ""


def kwarg(call: ast.Call, name: str) -> Optional[ast.expr]:
    for kw in call.keywords:
        if kw.arg == name:
            return kw.value
    return None


def is_jax_jit(node: ast.AST) -> bool:
    """True for `jax.jit` / `jit` / `pjit` name chains."""
    d = dotted(node)
    return d in ("jax.jit", "jit", "pjit", "jax.pjit") or \
        d.endswith(".jit") or d.endswith(".pjit")


def partial_of_jit(call: ast.Call) -> bool:
    """`functools.partial(jax.jit, ...)`."""
    if dotted(call.func) in ("functools.partial", "partial") and call.args:
        return is_jax_jit(call.args[0])
    return False


def walk_functions(tree: ast.AST) -> Iterable[Tuple[str, ast.AST]]:
    """Yield (qualname, FunctionDef|AsyncFunctionDef) for every function
    in the module, with class nesting in the qualname."""

    def _walk(node, prefix):
        for child in ast.iter_child_nodes(node):
            if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef)):
                q = f"{prefix}{child.name}"
                yield q, child
                yield from _walk(child, q + ".")
            elif isinstance(child, ast.ClassDef):
                yield from _walk(child, f"{prefix}{child.name}.")
            else:
                yield from _walk(child, prefix)

    yield from _walk(tree, "")


def iter_py_files(paths: Sequence[str], repo_root: str) -> List[str]:
    """Expand CLI paths (files or directories) to .py files."""
    out: List[str] = []
    seen: Set[str] = set()
    for p in paths:
        if not os.path.isabs(p):
            # CWD-relative wins when it exists (invocations from inside
            # the repo); otherwise resolve against the repo root (CI
            # calling from elsewhere with repo-relative paths)
            p = os.path.abspath(p) if os.path.exists(p) \
                else os.path.join(repo_root, p)
        if os.path.isfile(p) and p.endswith(".py"):
            candidates = [p]
        elif os.path.isdir(p):
            candidates = []
            for root, dirs, files in os.walk(p):
                dirs[:] = [d for d in dirs
                           if d not in ("__pycache__", ".git")]
                candidates.extend(os.path.join(root, f)
                                  for f in sorted(files)
                                  if f.endswith(".py"))
        else:
            candidates = []
        for c in candidates:
            c = os.path.abspath(c)
            if c not in seen:
                seen.add(c)
                out.append(c)
    return out


# ---------------------------------------------------------------------------
# engine
# ---------------------------------------------------------------------------

def run_passes(paths: Sequence[str], repo_root: str,
               rules: Optional[Set[str]] = None,
               docs_override: Optional[dict] = None) -> List[Finding]:
    """Run every registered pass over `paths`; returns findings sorted
    by (path, line, rule) with occurrence indices assigned and inline
    suppressions already removed. `rules` filters to a subset of rule
    ids; `docs_override` lets tests point GL105 at fixture docs/roots.
    """
    from . import passes as _passes

    files = [SourceFile(p, repo_root)
             for p in iter_py_files(paths, repo_root)]
    findings: List[Finding] = []
    srcs: List[SourceFile] = []
    for sf in files:
        if sf.parse_error is not None:
            e = sf.parse_error
            findings.append(Finding(
                "GL001", "error", sf.relpath, e.lineno or 1, 0,
                f"syntax error: {e.msg}"))
            continue
        srcs.append(sf)

    for rule_id, fn in _passes.FILE_PASSES:
        if rules and rule_id not in rules:
            continue
        for sf in srcs:
            findings.extend(fn(sf, repo_root))
    # already-parsed files, so project passes (GL105 re-scans its own
    # emission roots) don't read+parse the same tree a second time
    file_cache = {sf.abspath: sf for sf in srcs}
    for rule_id, fn in _passes.PROJECT_PASSES:
        if rules and rule_id not in rules:
            continue
        findings.extend(fn(repo_root, docs_override, file_cache))

    # inline suppressions. Project passes (GL105) anchor findings in
    # files OUTSIDE the CLI path set (the rest of the package under a
    # `graft_lint.py paddle_tpu/serving` run), so parse those on demand — a
    # sanction comment must work no matter which paths were passed.
    by_path = {sf.relpath: sf for sf in srcs}
    kept = []
    for f in findings:
        sf = by_path.get(f.path)
        if sf is None and f.path.endswith(".py"):
            ab = os.path.join(repo_root, f.path)
            if os.path.isfile(ab):
                sf = by_path[f.path] = SourceFile(ab, repo_root)
        if sf is not None and sf.parse_error is None and \
                sf.suppressed(f.rule, f.line):
            continue
        kept.append(f)

    kept.sort(key=lambda f: (f.path, f.line, f.col, f.rule))
    occ_count: Dict[Tuple[str, str, str], int] = {}
    for f in kept:
        k = (f.rule, f.path, f.code)
        f.occ = occ_count.get(k, 0)
        occ_count[k] = f.occ + 1
    return kept
