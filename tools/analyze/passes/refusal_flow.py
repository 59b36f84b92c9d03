"""Pass: refusal-flow soundness — typed refusals must reach a typed
handler, never a broad ``except`` that swallows them.

The fast paths refuse work they cannot do exactly by RAISING a typed
refusal (BypassIneligible, DocIneligible, JoinIneligible,
MatviewIneligible, ...).  The contract is that every
refusal propagates to a dispatcher that catches the TYPE and routes the
request to the interpreted / CPU fallback.  A broad ``except
Exception:`` between the raise and that dispatcher launders the refusal
into "handled": the fast path silently returns garbage or caches a
wrong eligibility verdict, and the fallback never runs.  The raise and
the offending handler are usually several calls apart, so no lexical
pass can see the pair; this one follows the propagation
interprocedurally.

How it works:

1. REFUSAL CLASSES — every exception class defined in a module named
   ``errors.py``, every class named ``*Ineligible`` anywhere, and any
   class marked ``# analysis: refusal-class`` on its ``class`` line or
   the line above (for typed refusals that live outside an errors
   module, e.g. KeySuffixError).  Each class's catch-name set is its
   own name plus every ancestor name in its bases chain (project bases
   recursively, stdlib bases like ValueError by name) — so ``except
   ValueError`` legitimately catches KeySuffixError.
2. ESCAPE SETS — a memoized interprocedural walk computes, per def,
   the set of refusal classes that can propagate OUT of it: direct
   ``raise Refusal(...)`` statements plus calls whose resolved callee
   has a non-empty escape set, minus anything caught inside the def.
   Cycles and unresolvable calls under-approximate to empty
   (documented limit: no false positives from them).
3. HANDLER WALK — at each source point the enclosing ``try`` handlers
   are consulted innermost-out, in handler order, exactly like the
   interpreter would: a handler naming the refusal (or an ancestor)
   handles it; a BROAD handler (bare / ``Exception`` /
   ``BaseException``, including inside tuples) is the decision point —
   if its body re-raises (any ``raise``) the refusal propagates past;
   if its body mentions a refusal class name (the
   ``isinstance``-and-route shape) it counts as explicit handling;
   otherwise it is a FINDING at the handler line.
4. TASK-CANCEL SUB-RULE — ``task.cancel()`` without the
   cancel-until-done drain loses the cancellation entirely when it
   races an in-flight completion (bpo-37658), which is the same
   lost-control-flow shape at the event-loop level.  In async defs a
   bare ``.cancel()`` on a task-ish receiver (name contains "task",
   or assigned from ``create_task``/``ensure_future``, or iterating a
   task-named collection) is flagged unless it sits inside a
   ``while ... .done()`` drain loop.  Route new sites through
   ``yugabyte_db_tpu.utils.tasks.cancel_and_drain``.

Suppression anchors at the reported handler / cancel line:
``# analysis-ok(refusal_flow): <reason>``.
"""
from __future__ import annotations

import ast
from typing import Dict, FrozenSet, List, Optional, Set, Tuple

from ..core import (AnalysisPass, Finding, ModuleInfo, ProjectIndex,
                    call_name)

#: names that make a handler "broad" rather than typed
_BROAD = frozenset({"Exception", "BaseException"})
#: base names stripped from catch sets (catching these is broad, not typed)
_NEVER_TYPED = frozenset({"Exception", "BaseException", "object"})

_REFUSAL_MARK = "# analysis: refusal-class"


def _terminal(expr: ast.expr) -> Optional[str]:
    """Last dotted component of a Name/Attribute chain, else None."""
    while isinstance(expr, ast.Attribute):
        return expr.attr
    if isinstance(expr, ast.Name):
        return expr.id
    return None


def _handler_names(h: ast.ExceptHandler) -> List[str]:
    """Terminal class names a handler catches; [] for a bare except."""
    t = h.type
    if t is None:
        return []
    elts = t.elts if isinstance(t, ast.Tuple) else [t]
    out = []
    for e in elts:
        n = _terminal(e)
        if n is not None:
            out.append(n)
    return out


class _Source:
    """Witness for one refusal entering a def: the raise itself or the
    call that lets it in."""
    __slots__ = ("line", "what")

    def __init__(self, line: int, what: str):
        self.line = line
        self.what = what


class RefusalFlowPass(AnalysisPass):
    id = "refusal_flow"
    title = "typed refusal swallowed by a broad except"
    hint = ("catch the refusal type explicitly (route to the fallback) "
            "before any broad except, or re-raise from the broad "
            "handler; for .cancel() use utils.tasks.cancel_and_drain")

    def run(self, index: ProjectIndex) -> List[Finding]:
        graph = index.call_graph()
        from ..callgraph import iter_defs

        refusals = self._discover(index, graph)
        self._catch: Dict[str, FrozenSet[str]] = {
            name: self._catch_names(graph, rel, qual)
            for name, (rel, qual) in refusals.items()}
        self._names: FrozenSet[str] = frozenset(refusals)

        #: def key -> (module, qual, ast node)
        self._defs: Dict[str, Tuple[ModuleInfo, str, ast.AST]] = {}
        for mod in index.modules():
            if mod.tree is None:
                continue
            for qual, _cls, node in iter_defs(mod.tree):
                self._defs[graph.key(mod.rel, qual)] = (mod, qual, node)

        self._graph = graph
        self._esc: Dict[str, FrozenSet[str]] = {}
        self._busy: Set[str] = set()
        #: (rel, handler line) -> (module, {refusal names}, witness)
        self._hits: Dict[Tuple[str, int],
                         Tuple[ModuleInfo, Set[str], _Source]] = {}

        for key in sorted(self._defs):
            self._escape(key)

        out: List[Finding] = []
        for (rel, line) in sorted(self._hits):
            mod, names, w = self._hits[(rel, line)]
            nm = ", ".join(sorted(names))
            out.append(self.finding(
                mod, line,
                f"broad except swallows typed refusal(s) {nm} "
                f"(reaches here from line {w.line}: {w.what}) without "
                "re-raising or routing to the fallback",
                detail=",".join(sorted(names))))
        out.extend(self._cancel_findings())
        return out

    # --- refusal-class discovery ------------------------------------------
    def _discover(self, index: ProjectIndex, graph,
                  ) -> Dict[str, Tuple[str, str]]:
        """name -> (rel, cls_qual) of every refusal class."""
        found: Dict[str, Tuple[str, str]] = {}
        for mod in index.modules():
            if mod.tree is None:
                continue
            f = graph.facts.get(mod.rel)
            if f is None:
                continue
            is_errors_mod = mod.rel.endswith("errors.py")
            for cq in f["classes"]:
                name = cq.split(".")[-1]
                if is_errors_mod or name.endswith("Ineligible"):
                    found.setdefault(name, (mod.rel, cq))
            # marker-declared refusals outside errors modules
            if _REFUSAL_MARK not in mod.source:
                continue
            for node in ast.walk(mod.tree):
                if not isinstance(node, ast.ClassDef):
                    continue
                ln = node.lineno - 1          # 0-based
                here = mod.lines[ln] if ln < len(mod.lines) else ""
                above = mod.lines[ln - 1] if ln > 0 else ""
                if (_REFUSAL_MARK in here or _REFUSAL_MARK in above):
                    found.setdefault(node.name, (mod.rel, node.name))
        return found

    def _catch_names(self, graph, rel: str, cls_qual: str,
                     ) -> FrozenSet[str]:
        """Own name + every ancestor name: any of these in an except
        clause catches this refusal (minus the broad names)."""
        names: Set[str] = set()
        work = [(rel, cls_qual)]
        seen: Set[Tuple[str, str]] = set()
        while work:
            r, q = work.pop()
            if (r, q) in seen or len(seen) > 64:
                continue
            seen.add((r, q))
            names.add(q.split(".")[-1])
            c = graph.class_fact(r, q)
            if c is None:
                continue
            for b in c["bases"]:
                hit = graph.resolve_class(r, b)
                if hit is not None:
                    work.append(hit)
                else:
                    t = b.split(".")[-1]
                    if t:
                        names.add(t)
        return frozenset(names - _NEVER_TYPED)

    # --- escape sets + handler findings -----------------------------------
    def _escape(self, key: str) -> FrozenSet[str]:
        if key in self._esc:
            return self._esc[key]
        if key in self._busy:
            return frozenset()          # cycle: under-approximate
        ent = self._defs.get(key)
        if ent is None:
            return frozenset()
        self._busy.add(key)
        mod, qual, node = ent

        # fast path: a def with no raise and no except can only pass
        # its callees' escapes straight through — no AST walk needed
        # (the resolved edges come from the shared facts)
        end = getattr(node, "end_lineno", None) or node.lineno
        seg = "\n".join(mod.lines[node.lineno - 1:end])
        if "raise" not in seg and "except" not in seg:
            esc: Set[str] = set()
            for _line, _text, tgt in self._graph.edges(key):
                if tgt is not None and tgt != key:
                    esc |= self._escape(tgt)
            self._busy.discard(key)
            res = frozenset(esc)
            self._esc[key] = res
            return res

        escapes: Set[str] = set()

        def refusal_of(exc: Optional[ast.expr]) -> Optional[str]:
            if exc is None:
                return None
            tgt = exc.func if isinstance(exc, ast.Call) else exc
            n = _terminal(tgt)
            return n if n in self._names else None

        def propagate(names: Set[str], w: _Source,
                      tries: Tuple[ast.Try, ...]) -> None:
            live = set(names)
            for t in reversed(tries):
                if not live:
                    return
                for h in t.handlers:
                    hnames = _handler_names(h)
                    broad = (not hnames) or bool(set(hnames) & _BROAD)
                    typed_hit = {r for r in live
                                 if set(hnames) & self._catch[r]}
                    live -= typed_hit            # typed catch: handled
                    if not live:
                        return
                    if not broad:
                        continue
                    # broad handler reached with refusals still live
                    if self._reraises(h):
                        break                    # propagates past this try
                    if self._mentions_refusal(h, live):
                        return                   # isinstance-routed: handled
                    k = (mod.rel, h.lineno)
                    prev = self._hits.get(k)
                    if prev is None:
                        self._hits[k] = (mod, set(live), w)
                    else:
                        prev[1].update(live)
                    return                       # swallowed here
            escapes.update(live)

        def walk(n: ast.AST, tries: Tuple[ast.Try, ...]) -> None:
            if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef,
                              ast.ClassDef, ast.Lambda)):
                return
            if isinstance(n, ast.Try):
                for s in n.body:
                    walk(s, tries + (n,))
                # handlers / else / finally are NOT covered by this
                # try's own handlers
                for h in n.handlers:
                    for s in h.body:
                        walk(s, tries)
                for s in n.orelse:
                    walk(s, tries)
                for s in n.finalbody:
                    walk(s, tries)
                return
            if isinstance(n, ast.Raise):
                r = refusal_of(n.exc)
                if r is not None:
                    propagate({r}, _Source(n.lineno, f"raise {r}"),
                              tries)
            elif isinstance(n, ast.Call):
                text = call_name(n)
                if text:
                    tgt = self._graph.resolve(mod.rel, qual, text)
                    if tgt is not None and tgt != key:
                        esc = self._escape(tgt)
                        if esc:
                            propagate(set(esc),
                                      _Source(n.lineno, f"{text}()"),
                                      tries)
            for c in ast.iter_child_nodes(n):
                walk(c, tries)

        for stmt in node.body:
            walk(stmt, ())
        self._busy.discard(key)
        res = frozenset(escapes)
        self._esc[key] = res
        return res

    @staticmethod
    def _reraises(h: ast.ExceptHandler) -> bool:
        """Any raise in the handler body (bare re-raise, re-raise of
        the bound name, or a translation raise) means the handler does
        not silently swallow."""
        for n in ast.walk(h):
            if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef,
                              ast.Lambda)):
                continue
            if isinstance(n, ast.Raise):
                return True
        return False

    def _mentions_refusal(self, h: ast.ExceptHandler,
                          live: Set[str]) -> bool:
        """Handler body references a live refusal's catch name — the
        ``isinstance(e, Refusal)``-and-route shape counts as typed
        handling."""
        wanted: Set[str] = set()
        for r in live:
            wanted |= self._catch[r]
        for n in ast.walk(h):
            if isinstance(n, ast.Name) and n.id in wanted:
                return True
            if isinstance(n, ast.Attribute) and n.attr in wanted:
                return True
        return False

    # --- task-cancel sub-rule ---------------------------------------------
    def _cancel_findings(self) -> List[Finding]:
        out: List[Finding] = []
        for key in sorted(self._defs):
            mod, qual, node = self._defs[key]
            if not isinstance(node, ast.AsyncFunctionDef):
                continue
            end = getattr(node, "end_lineno", None) or node.lineno
            if ".cancel(" not in "\n".join(
                    mod.lines[node.lineno - 1:end]):
                continue
            taskish = self._taskish_locals(node)

            def is_taskish(recv: ast.expr) -> bool:
                term = _terminal(recv)
                if term is None:
                    return False
                if "task" in term.lower():
                    return True
                return isinstance(recv, ast.Name) and recv.id in taskish

            def walk(n: ast.AST, in_drain: bool) -> None:
                if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef,
                                  ast.ClassDef, ast.Lambda)):
                    return
                if isinstance(n, ast.While):
                    drains = ".done()" in ast.unparse(n.test)
                    for c in ast.iter_child_nodes(n):
                        walk(c, in_drain or drains)
                    return
                if (isinstance(n, ast.Call)
                        and isinstance(n.func, ast.Attribute)
                        and n.func.attr == "cancel"
                        and not n.args and not in_drain
                        and is_taskish(n.func.value)):
                    recv = ast.unparse(n.func.value)
                    out.append(self.finding(
                        mod, n.lineno,
                        f"bare {recv}.cancel() can lose the "
                        "cancellation when it races completion "
                        "(bpo-37658) — the task may keep running "
                        "after shutdown",
                        detail=f"{recv}.cancel"))
                for c in ast.iter_child_nodes(n):
                    walk(c, in_drain)

            for stmt in node.body:
                walk(stmt, False)
        return out

    @staticmethod
    def _taskish_locals(node: ast.AsyncFunctionDef) -> Set[str]:
        """Local names bound to tasks: assigned from create_task /
        ensure_future, or iterating a task-named collection."""
        names: Set[str] = set()
        for n in ast.walk(node):
            if isinstance(n, (ast.FunctionDef, ast.Lambda)):
                continue
            if (isinstance(n, ast.Assign)
                    and isinstance(n.value, ast.Call)):
                cn = call_name(n.value)
                if cn and (cn.endswith("create_task")
                           or cn.endswith("ensure_future")):
                    for t in n.targets:
                        if isinstance(t, ast.Name):
                            names.add(t.id)
            if isinstance(n, (ast.For, ast.AsyncFor)):
                it = ast.unparse(n.iter)
                if "task" in it.lower() and isinstance(n.target,
                                                       ast.Name):
                    names.add(n.target.id)
        return names


PASS = RefusalFlowPass()
