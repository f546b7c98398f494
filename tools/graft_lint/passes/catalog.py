"""GL105 — telemetry-catalog consistency.

Every metric / span / flag name EMITTED in code must appear in the
docs catalogs, and every catalog entry must still have an emission
site — the catalog can never silently drift again (it did: PR 6/7/8
each hand-repaired entries).

Code side (AST over config.EMISSION_ROOTS — paddle_tpu/, independent
of the CLI paths):
- `counter("...")` / `gauge("...")` / `histogram("...")` first-arg
  string literals (module helpers and registry methods alike);
- `span("...")` / `start_span("...")` / `traced("...")` literals,
  and the tick helper's `tick("...")` / `.stage("...")` (stage spans
  of a hot loop, observability.tracing); f-string names
  (`f"comm.{op}"`) become wildcard prefixes;
- `define_flag("name", ...)` — the FLAGS_* registry.

Docs side:
- backticked dotted names under config.CATALOG_PREFIXES in
  config.CATALOG_DOCS (template entries like `comm.<op>` become
  wildcard prefixes);
- `FLAGS_<name>` tokens anywhere under config.FLAG_DOC_ROOTS.

Both directions are checked; docstrings never count as emissions (the
quickstart examples in observability/__init__ stay out), and only
names under the known domain prefixes participate.
"""
from __future__ import annotations

import ast
import os
import re
from typing import Dict, List, Optional, Tuple

from .. import config
from ..core import Finding, SourceFile, iter_py_files, terminal_name

_METRIC_FNS = {"counter", "gauge", "histogram"}
_SPAN_FNS = {"span", "start_span", "traced", "tick", "stage"}

_BACKTICK_RE = re.compile(r"`([^`\s]+)`")
_FLAG_RE = re.compile(r"FLAGS_([a-z][a-z0-9_]*)")
_NAME_RE = re.compile(r"^[a-z][a-z0-9_]*(\.[a-z0-9_<>{}*]+)+$")

_HINT_DOCS = ("add the name to the metric/span catalog in "
              "docs/OBSERVABILITY.md (robustness.* entries live in its "
              "Robustness table; see docs/STATIC_ANALYSIS.md)")
_HINT_CODE = ("the catalog entry has no remaining emission site: "
              "delete it from the docs, or restore the emission")


class _Emission:
    __slots__ = ("name", "kind", "path", "line", "pattern",
                 "docs_checked")

    def __init__(self, name, kind, path, line, pattern=None,
                 docs_checked=True):
        self.name = name          # display form (f-strings: comm.{...})
        self.kind = kind          # "metric" | "span" | "flag"
        self.path = path
        self.line = line
        # compiled regex for f-string emissions (f"comm.{op}" ->
        # ^comm\..+$, f"{p}.grad_norm" -> ^.+\.grad_norm$); None for
        # plain literals
        self.pattern = pattern
        # False = only used to satisfy doc entries, never reported as
        # undocumented (leading-dynamic f-strings whose domain prefix
        # can't be determined statically)
        self.docs_checked = docs_checked


def _in_prefixes(name: str) -> bool:
    return name.split(".", 1)[0] in config.CATALOG_PREFIXES


def _metric_or_span_kind(fn_name: str):
    """Classify a callee name: aliased helpers count too
    (`_obs_histogram`, `obs.counter`, `Gauge(...)` constructors)."""
    tail = fn_name.lstrip("_").split("_")[-1].lower()
    if tail in _METRIC_FNS or fn_name in ("Counter", "Gauge",
                                          "Histogram"):
        return "metric"
    if fn_name.lstrip("_") in _SPAN_FNS:
        return "span"
    return None


def _collect_emissions(repo_root: str, roots, file_cache=None
                       ) -> Tuple[List[_Emission], List[_Emission]]:
    """(metric/span emissions, flag definitions). `file_cache` maps
    abspath -> already-parsed SourceFile (the engine's file-pass set)
    so the default run doesn't parse the same tree twice."""
    emissions: List[_Emission] = []
    flags: List[_Emission] = []
    files = iter_py_files(list(roots), repo_root)
    for path in files:
        sf = (file_cache or {}).get(path) or SourceFile(path, repo_root)
        if sf.tree is None:
            continue
        for node in ast.walk(sf.tree):
            if not isinstance(node, ast.Call) or not node.args:
                continue
            fn = terminal_name(node.func)
            arg = node.args[0]
            if fn == "define_flag" and isinstance(arg, ast.Constant) \
                    and isinstance(arg.value, str):
                flags.append(_Emission(arg.value, "flag", sf.relpath,
                                       node.lineno))
                continue
            kind = _metric_or_span_kind(fn)
            if kind is None:
                continue
            if isinstance(arg, ast.Constant) and \
                    isinstance(arg.value, str):
                name = arg.value
                if _NAME_RE.match(name) and _in_prefixes(name):
                    emissions.append(_Emission(name, kind, sf.relpath,
                                               node.lineno))
            elif isinstance(arg, ast.JoinedStr):
                # constant parts joined by ".+": f"comm.{op}" matches
                # every comm.* entry, f"{p}.grad_norm" every
                # *.grad_norm entry
                parts = [re.escape(str(p.value))
                         if isinstance(p, ast.Constant) else ".+"
                         for p in arg.values]
                disp = "".join(str(p.value)
                               if isinstance(p, ast.Constant) else "{*}"
                               for p in arg.values)
                body = "".join(parts)
                if not body.strip(".+"):
                    continue  # fully dynamic: nothing to check
                first = arg.values[0]
                if isinstance(first, ast.Constant):
                    # same domain filter as literal names: out-of-scope
                    # prefixes (myapp.*) don't participate at all
                    if not _in_prefixes(str(first.value)):
                        continue
                    docs_checked = True
                else:
                    # leading-dynamic ({p}.grad_norm): the domain can't
                    # be determined — usable to satisfy doc entries,
                    # never reported as undocumented
                    docs_checked = False
                emissions.append(_Emission(
                    disp, kind, sf.relpath, node.lineno,
                    pattern=re.compile(f"^{body}$"),
                    docs_checked=docs_checked))
    return emissions, flags


def _collect_doc_names(repo_root: str, docs) -> Dict[str, Tuple[str, int,
                                                                bool]]:
    """{name: (docfile, line, is_template)} for backticked catalog
    names; template entries (`comm.<op>`) keyed by their prefix."""
    out: Dict[str, Tuple[str, int, bool]] = {}
    for rel in docs:
        path = os.path.join(repo_root, rel)
        if not os.path.isfile(path):
            continue
        with open(path, "r", encoding="utf-8") as f:
            for i, line in enumerate(f, start=1):
                for tok in _BACKTICK_RE.findall(line):
                    if "/" in tok or tok.endswith((".py", ".md",
                                                   ".json", ".jsonl")):
                        continue
                    if not _NAME_RE.match(tok):
                        continue
                    if not _in_prefixes(tok):
                        continue
                    if any(c in tok for c in "<{*"):
                        prefix = re.split(r"[<{*]", tok)[0]
                        out.setdefault(prefix, (rel, i, True))
                    else:
                        out.setdefault(tok, (rel, i, False))
    return out


def _collect_doc_flags(repo_root: str, roots) -> Dict[str, Tuple[str,
                                                                 int]]:
    out: Dict[str, Tuple[str, int]] = {}
    md_files: List[str] = []
    for rel in roots:
        path = os.path.join(repo_root, rel)
        if os.path.isfile(path):
            md_files.append(path)
        elif os.path.isdir(path):
            for root, _, files in os.walk(path):
                md_files.extend(os.path.join(root, f)
                                for f in sorted(files)
                                if f.endswith(".md"))
    for path in md_files:
        rel = os.path.relpath(path, repo_root).replace(os.sep, "/")
        with open(path, "r", encoding="utf-8") as f:
            for i, line in enumerate(f, start=1):
                for name in _FLAG_RE.findall(line):
                    out.setdefault(name, (rel, i))
    return out


def check(repo_root: str, overrides: Optional[dict] = None,
          file_cache: Optional[dict] = None) -> List[Finding]:
    cfg = {
        "emission_roots": config.EMISSION_ROOTS,
        "catalog_docs": config.CATALOG_DOCS,
        "flag_doc_roots": config.FLAG_DOC_ROOTS,
    }
    if overrides:
        cfg.update(overrides)
    emissions, flags = _collect_emissions(repo_root,
                                          cfg["emission_roots"],
                                          file_cache)
    doc_names = _collect_doc_names(repo_root, cfg["catalog_docs"])
    doc_flags = _collect_doc_flags(repo_root, cfg["flag_doc_roots"])
    findings: List[Finding] = []

    templates = [n for n, (_, _, t) in doc_names.items() if t]

    def _documented(e: _Emission) -> bool:
        if e.pattern is not None:
            # f-string emission: catalogued when any doc entry (or
            # template prefix) matches the pattern
            return any(e.pattern.match(n) for n in doc_names) or \
                any(e.pattern.match(t + "x") for t in templates)
        if e.name in doc_names:
            return True
        return any(e.name.startswith(t) for t in templates)

    # code -> docs
    reported = set()
    for e in emissions:
        if not e.docs_checked or _documented(e):
            continue
        key = (e.name, e.path, e.line)
        if key in reported:
            continue
        reported.add(key)
        findings.append(Finding(
            "GL105", "error", e.path, e.line, 0,
            f"{e.kind} {e.name!r} is emitted here but missing from the "
            f"docs catalogs ({', '.join(cfg['catalog_docs'])})",
            _HINT_DOCS))

    # docs -> code
    emitted_exact = {e.name for e in emissions if e.pattern is None}
    emitted_pats = [e.pattern for e in emissions if e.pattern is not None]
    for name, (doc, line, is_template) in sorted(doc_names.items()):
        if is_template:
            ok = any(n.startswith(name) for n in emitted_exact) or \
                any(p.match(name + "x") for p in emitted_pats)
        else:
            ok = name in emitted_exact or \
                any(n.startswith(name + ".") for n in emitted_exact) \
                or any(p.match(name) for p in emitted_pats)
        if not ok:
            findings.append(Finding(
                "GL105", "error", doc, line, 0,
                f"catalog entry {name!r} has no emission site in "
                f"{', '.join(cfg['emission_roots'])}", _HINT_CODE))

    # flags: code -> docs
    defined = {f.name: f for f in flags}
    for name, e in sorted(defined.items()):
        if name not in doc_flags:
            findings.append(Finding(
                "GL105", "error", e.path, e.line, 0,
                f"flag FLAGS_{name} is defined but undocumented under "
                f"{', '.join(cfg['flag_doc_roots'])}",
                "add it to the flag catalog (docs/OBSERVABILITY.md "
                "debug-flags section or the subsystem doc)"))
    # flags: docs -> code
    for name, (doc, line) in sorted(doc_flags.items()):
        if name not in defined:
            findings.append(Finding(
                "GL105", "error", doc, line, 0,
                f"docs reference FLAGS_{name} but no define_flag("
                f"{name!r}) exists", _HINT_CODE))
    return findings
