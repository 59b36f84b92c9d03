"""Framework core: shared walker, findings model, suppression grammar.

Every pass consumes a :class:`ProjectIndex` — each file is read and
parsed exactly once per run, however many passes look at it — and
returns :class:`Finding`s.  The runner applies the suppression grammar
and times each pass (the per-pass wall time rides in ``--json`` so
tier-1 can assert the whole sweep stays under budget).

Suppression grammar (one true spelling, one legacy alias):

    # analysis-ok(<pass>): <reason>
    # analysis-ok(<pass>, <pass2>): <reason>     (one line, two passes)
    # blocking-ok: <reason>                      (alias for async_blocking)

The comment lives on the finding line or the line above; the reason is
mandatory — an annotation that doesn't say WHY the hazard is acceptable
is itself a finding waiting to happen, so a bare marker suppresses
nothing.
"""
from __future__ import annotations

import ast
import os
import re
import time
from dataclasses import dataclass, field
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

#: default analysis scope: the whole product tree.
DEFAULT_ROOTS: Tuple[str, ...] = ("yugabyte_db_tpu",)

_SUPPRESS_RE = re.compile(
    r"analysis-ok\(\s*([\w*]+(?:\s*,\s*[\w*]+)*)\s*\)\s*:\s*(\S)")
#: legacy alias kept so every pre-framework `blocking-ok:` annotation
#: (and tests/test_check_blocking.py) keeps working unmodified.
_ALIASES = {"async_blocking": re.compile(r"blocking-ok\s*:\s*(\S)")}


@dataclass
class Finding:
    """One hazard: file:line + pass id + message + fix hint.

    ``detail`` is the machine-usable core of the finding (e.g. the
    offending call's dotted name) — the check_blocking shim and tests
    key on it without parsing the prose."""

    path: str          # repo-relative
    line: int
    pass_id: str
    message: str
    detail: str = ""
    hint: str = ""

    def to_dict(self) -> dict:
        return {"path": self.path, "line": self.line, "pass": self.pass_id,
                "message": self.message, "detail": self.detail,
                "hint": self.hint}

    def format(self) -> str:
        h = f"  [fix: {self.hint}]" if self.hint else ""
        return f"{self.path}:{self.line}: [{self.pass_id}] {self.message}{h}"


@dataclass
class ModuleInfo:
    """One parsed source file, shared by every pass."""

    path: str                     # absolute
    rel: str                      # repo-relative (the Finding.path form)
    source: str
    lines: List[str]
    tree: Optional[ast.Module]    # None on syntax error
    parse_error: Optional[str] = None
    #: (mtime_ns, size) at read time — the facts-cache key; None for
    #: overlay content (staged bytes have no stable on-disk identity)
    stat_key: Optional[Tuple[int, int]] = None


class ProjectIndex:
    """Parse-once file index over the analysis roots.

    ``modules()`` walks the roots; ``module(rel)`` parses any repo file
    on demand.  ``call_graph()`` lazily builds the shared
    interprocedural graph; with ``cache_dir`` set, its per-file
    extraction facts persist across runs keyed on (path, mtime, size)
    so a repeat run re-walks only changed files."""

    def __init__(self, base: str, roots: Sequence[str] = DEFAULT_ROOTS,
                 overlay: Optional[Dict[str, str]] = None,
                 cache_dir: Optional[str] = None):
        self.base = os.path.abspath(base)
        self.roots = tuple(roots)
        #: rel path -> source text that REPLACES the on-disk file (the
        #: pre-commit hook overlays staged INDEX content so a partially
        #: staged file is checked against the bytes being committed)
        self.overlay = dict(overlay or {})
        self.cache_dir = cache_dir
        self._cache: Dict[str, Optional[ModuleInfo]] = {}
        self._modules: Optional[List[ModuleInfo]] = None
        self._graph = None

    def module(self, rel: str) -> Optional[ModuleInfo]:
        if rel in self._cache:
            return self._cache[rel]
        path = os.path.join(self.base, rel)
        mi: Optional[ModuleInfo] = None
        stat_key = None
        if rel in self.overlay:
            src = self.overlay[rel]
        else:
            try:
                # stat BEFORE read: if a writer lands between the two,
                # the key describes the older content and the next run
                # simply misses — the reverse order could persist facts
                # of the old bytes under the new key, a permanently
                # stale cache entry
                st = os.stat(path)
                stat_key = (st.st_mtime_ns, st.st_size)
                with open(path, encoding="utf-8") as f:
                    src = f.read()
            except OSError:
                self._cache[rel] = None
                return None
        try:
            tree = ast.parse(src, filename=path)
            err = None
        except SyntaxError as e:
            tree, err = None, str(e)
        mi = ModuleInfo(path=path, rel=rel, source=src,
                        lines=src.splitlines(), tree=tree, parse_error=err,
                        stat_key=stat_key)
        self._cache[rel] = mi
        return mi

    def call_graph(self):
        """The shared interprocedural call graph (built once per run,
        however many passes consume it)."""
        if self._graph is None:
            from .callgraph import build_graph
            self._graph = build_graph(self)
        return self._graph

    def modules(self) -> List[ModuleInfo]:
        # every pass calls this; the tree walk is memoized alongside
        # the per-file parses (one run = one traversal, many readers)
        if self._modules is not None:
            return self._modules
        out: List[ModuleInfo] = []
        for root in self.roots:
            rootp = os.path.join(self.base, root)
            if os.path.isfile(rootp) and rootp.endswith(".py"):
                mi = self.module(os.path.relpath(rootp, self.base))
                if mi is not None:
                    out.append(mi)
                continue
            for dirpath, dirs, files in os.walk(rootp):
                dirs[:] = sorted(d for d in dirs if d != "__pycache__")
                for fn in sorted(files):
                    if not fn.endswith(".py"):
                        continue
                    rel = os.path.relpath(os.path.join(dirpath, fn),
                                          self.base)
                    mi = self.module(rel)
                    if mi is not None:
                        out.append(mi)
        self._modules = out
        return out


class AnalysisPass:
    """Base class: subclasses set ``id``/``title``/``hint`` and
    implement ``run(index) -> [Finding]`` returning RAW findings — the
    runner applies suppression, so a pass never needs to know the
    grammar."""

    id: str = ""
    title: str = ""
    hint: str = ""

    def run(self, index: ProjectIndex) -> List[Finding]:
        raise NotImplementedError

    def finding(self, module: ModuleInfo, line: int, message: str,
                detail: str = "", hint: Optional[str] = None) -> Finding:
        return Finding(path=module.rel, line=line, pass_id=self.id,
                       message=message, detail=detail,
                       hint=self.hint if hint is None else hint)


# --- suppression ----------------------------------------------------------

def _line_suppresses(text: str, pass_id: str) -> bool:
    m = _SUPPRESS_RE.search(text)
    if m:
        ids = {p.strip() for p in m.group(1).split(",")}
        if pass_id in ids or "*" in ids:
            return True
    alias = _ALIASES.get(pass_id)
    return bool(alias and alias.search(text))


def is_suppressed(module: ModuleInfo, line: int, pass_id: str) -> bool:
    """True when the finding line or the line above carries a matching
    annotation (both spots allowed: long lines push the comment up)."""
    here = module.lines[line - 1] if 0 < line <= len(module.lines) else ""
    above = module.lines[line - 2] if line >= 2 else ""
    return (_line_suppresses(here, pass_id)
            or _line_suppresses(above, pass_id))


# --- shared AST helpers ---------------------------------------------------

def call_name(node: ast.Call) -> str:
    """Dotted name of a call target ('time.sleep', 'open', ...)."""
    f = node.func
    parts: List[str] = []
    while isinstance(f, ast.Attribute):
        parts.append(f.attr)
        f = f.value
    if isinstance(f, ast.Name):
        parts.append(f.id)
    return ".".join(reversed(parts))


_LOCKISH_RE = re.compile(r"(?:^|_)(?:lock|mutex|mu|rlock)s?$",
                         re.IGNORECASE)


def is_lockish(expr: ast.expr) -> bool:
    """Does a `with` context expression look like a lock?  Terminal
    name matching (self._lock, peer.apply_lock, LOCK, threading.Lock())
    — deliberately name-based: the analyzer runs without imports."""
    e = expr
    if isinstance(e, ast.Call):
        name = call_name(e)
        if name.endswith(("Lock", "RLock", "Condition", "Semaphore")):
            return True
        e = e.func
    if isinstance(e, ast.Attribute):
        return bool(_LOCKISH_RE.search(e.attr))
    if isinstance(e, ast.Name):
        return bool(_LOCKISH_RE.search(e.id))
    return False


def terminal_attr(expr: ast.expr) -> Optional[str]:
    """`self.tablet.flush` -> 'flush'; bare `flush` -> 'flush'."""
    if isinstance(expr, ast.Attribute):
        return expr.attr
    if isinstance(expr, ast.Name):
        return expr.id
    return None


# --- runner ---------------------------------------------------------------

def run_analysis(index: ProjectIndex,
                 passes: Iterable[AnalysisPass]) -> dict:
    """Run passes over the index; returns the report dict that is also
    the ``--json`` schema:

    {"passes": [{"id", "title", "findings": N, "suppressed": N,
                 "wall_ms": F}],
     "findings": [finding dicts...],          # unsuppressed only
     "suppressions": {pass_id: N},            # held to baseline.json
     "total_findings": N, "total_suppressed": N, "wall_ms": F,
     "parse_errors": [{"path", "error"}]}
    """
    report: dict = {"passes": [], "findings": [], "suppressions": {},
                    "parse_errors": []}
    seen_errors = set()
    total_ms = 0.0
    for p in passes:
        t0 = time.perf_counter()
        raw = p.run(index)
        kept: List[Finding] = []
        nsup = 0
        for f in raw:
            mod = index.module(f.path)
            if mod is not None and is_suppressed(mod, f.line, f.pass_id):
                nsup += 1
            else:
                kept.append(f)
        wall_ms = (time.perf_counter() - t0) * 1e3
        total_ms += wall_ms
        kept.sort(key=lambda f: (f.path, f.line))
        report["passes"].append({
            "id": p.id, "title": p.title, "findings": len(kept),
            "suppressed": nsup, "wall_ms": round(wall_ms, 2)})
        report["suppressions"][p.id] = nsup
        report["findings"].extend(f.to_dict() for f in kept)
    for rel, mi in index._cache.items():
        if mi is not None and mi.parse_error and rel not in seen_errors:
            seen_errors.add(rel)
            report["parse_errors"].append({"path": rel,
                                           "error": mi.parse_error})
    report["total_findings"] = len(report["findings"])
    report["total_suppressed"] = sum(report["suppressions"].values())
    report["wall_ms"] = round(total_ms, 2)
    if index._graph is not None:
        report["callgraph"] = dict(index._graph.stats)
    return report
