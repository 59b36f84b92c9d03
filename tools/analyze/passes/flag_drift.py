"""Pass: drift between flag definitions (utils/flags.py) and use.

Four mechanical drift shapes:

1. DEFINED, NEVER READ — a ``DEFINE``/``DEFINE_RUNTIME`` whose name no
   product code ever ``flags.get``s: dead
   operator surface that lies about being a knob.
2. READ, NEVER DEFINED — ``flags.get("name")`` of a name no DEFINE
   creates: a KeyError waiting for that code path.
3. DUPLICATE DEFINITION with a different default (``define`` returns
   the first registration, so the second default silently loses).
4. DOC DEFAULT MISMATCH — a ``(default X)`` claim in the flag's help
   text or the repo docs (COVERAGE.md / ANALYSIS.md / README.md) that
   disagrees with the actual default.

Dynamic reads through f-strings (``flags.get(f"sched_{lane}_depth")``)
are matched as regexes against the defined names; fully dynamic reads
(``flags.get(var)`` in the CLI's hot-flag tooling) are ignored — they
can't prove a specific flag is wired.  Reads in tests/ don't count: a
flag only a test touches is not wired into the product.
"""
from __future__ import annotations

import ast
import os
import re
from typing import Dict, List, Optional, Set, Tuple

from ..core import AnalysisPass, Finding, ModuleInfo, ProjectIndex, call_name

FLAGS_MODULE = os.path.join("yugabyte_db_tpu", "utils", "flags.py")
_DEFINE_FUNCS = {"DEFINE", "DEFINE_RUNTIME", "define_flag",
                 "REGISTRY.define", "flags.DEFINE", "flags.DEFINE_RUNTIME"}
_AUTO_FUNCS = {"DEFINE_AUTO", "flags.DEFINE_AUTO"}
_READ_METHODS = {"get", "on_change"}
_DOC_GLOBS = ("COVERAGE.md", "ANALYSIS.md", "README.md")
_DOC_DEFAULT_RE = r"`?%s`?\s*\(default[:\s]+([^)]+)\)"
# matches "default 5", "default: 5", "(default 5)", "default=5",
# "defaults to 9", "default is True" — the claimed value must LOOK like
# a value (number/bool/None/quoted) so prose like "the default backend"
# never false-positives
_HELP_DEFAULT_RE = re.compile(
    r"\bdefaults?\s*(?:(?:is|to)\s+)?[:=]?\s*"
    r"(-?[0-9][\w.\-]*|True|False|None|'[^']+'|\"[^\"]+\")",
    re.IGNORECASE)


def _literal(node: ast.expr):
    """Best-effort literal value; None when not statically evaluable
    (e.g. `16 * 1024 * 1024` — those skip the doc-mismatch check)."""
    try:
        return ast.literal_eval(node)
    except (ValueError, SyntaxError):
        return None


def _fstring_regex(node: ast.JoinedStr) -> Optional[str]:
    parts = []
    for v in node.values:
        if isinstance(v, ast.Constant):
            parts.append(re.escape(str(v.value)))
        else:
            parts.append(".*")
    return "^" + "".join(parts) + "$"


class FlagDriftPass(AnalysisPass):
    id = "flag_drift"
    title = "flag definition/use drift"
    hint = ("wire the flag, delete it, or annotate the DEFINE with "
            "`# analysis-ok(flag_drift): <reason>` if it is reserved")

    def run(self, index: ProjectIndex) -> List[Finding]:
        out: List[Finding] = []
        defs, autos = self._collect_definitions(index, out)
        reads, regexes = self._collect_reads(index, out, set(defs))
        for rx in regexes:
            pat = re.compile(rx)
            reads.update(n for n in defs if pat.match(n))
        # indirection fallback: a flag name appearing as ANY string
        # literal in product code (e.g. a `fraction_flag="..."` param
        # default that later reaches flags.get) counts as read — a
        # truly dead flag's name appears nowhere outside its DEFINE.
        unread = {n for n in defs if n not in reads}
        if unread:
            for mod in index.modules():
                if mod.tree is None or mod.rel == FLAGS_MODULE:
                    continue
                for node in ast.walk(mod.tree):
                    if isinstance(node, ast.Constant) \
                            and isinstance(node.value, str) \
                            and node.value in unread \
                            and mod.rel != defs[node.value][0].rel:
                        reads.add(node.value)
                        unread.discard(node.value)
                if not unread:
                    break
        for name, (mod, line, _default, _help) in sorted(defs.items()):
            if name not in reads and name not in autos:
                out.append(self.finding(
                    mod, line,
                    f"flag `{name}` is defined but never read by "
                    f"product code",
                    detail=name))
        self._check_doc_defaults(index, defs, out)
        return out

    # --- definitions ------------------------------------------------------
    def _collect_definitions(self, index: ProjectIndex,
                             out: List[Finding]):
        defs: Dict[str, Tuple[ModuleInfo, int, object, str]] = {}
        autos: Set[str] = set()
        for mod in index.modules():
            if mod.tree is None:
                continue
            for node in ast.walk(mod.tree):
                if not isinstance(node, ast.Call):
                    continue
                fname = call_name(node)
                is_def = fname in _DEFINE_FUNCS
                is_auto = fname in _AUTO_FUNCS
                if not (is_def or is_auto):
                    continue
                if not (node.args and isinstance(node.args[0], ast.Constant)
                        and isinstance(node.args[0].value, str)):
                    continue
                name = node.args[0].value
                default = (_literal(node.args[1])
                           if len(node.args) > 1 else None)
                help_txt = ""
                if len(node.args) > 2 and isinstance(node.args[2],
                                                     ast.Constant):
                    help_txt = str(node.args[2].value)
                for kw in node.keywords:
                    if kw.arg == "help" and isinstance(kw.value,
                                                       ast.Constant):
                        help_txt = str(kw.value.value)
                if is_auto:
                    autos.add(name)     # read via auto_flags()/promotion
                if name in defs:
                    prev = defs[name]
                    if default is not None and prev[2] is not None \
                            and prev[2] != default:
                        out.append(self.finding(
                            mod, node.lineno,
                            f"flag `{name}` re-defined with a different "
                            f"default ({default!r} vs {prev[2]!r} at "
                            f"{prev[0].rel}:{prev[1]}) — define() keeps "
                            f"the FIRST registration, this default "
                            f"silently loses",
                            detail=name,
                            hint="one DEFINE per flag; share it"))
                    continue
                defs[name] = (mod, node.lineno, default, help_txt)
        return defs, autos

    # --- reads ------------------------------------------------------------
    @staticmethod
    def _flag_aliases(mod: ModuleInfo) -> Set[str]:
        """Names the flags module is bound to in this module (`flags`,
        `_flags`, ...) — keeps dict-typed locals called `flags` from
        polluting the read scan."""
        aliases: Set[str] = set()
        for node in ast.walk(mod.tree):
            if isinstance(node, ast.ImportFrom):
                for a in node.names:
                    if a.name == "flags":
                        aliases.add(a.asname or a.name)
            elif isinstance(node, ast.Import):
                for a in node.names:
                    if a.name.endswith(".flags") or a.name == "flags":
                        aliases.add((a.asname or a.name).split(".")[0])
        return aliases

    def _collect_reads(self, index: ProjectIndex, out: List[Finding],
                       defined: Set[str]):
        reads: Set[str] = set()
        regexes: Set[str] = set()
        for mod in index.modules():
            if mod.tree is None or mod.rel == FLAGS_MODULE:
                continue
            aliases = self._flag_aliases(mod)
            if not aliases:
                continue
            for node in ast.walk(mod.tree):
                if not (isinstance(node, ast.Call)
                        and isinstance(node.func, ast.Attribute)
                        and node.func.attr in (_READ_METHODS | {"set",
                                                                "reset"})
                        and node.args):
                    continue
                recv = node.func.value
                recv_name = recv.id if isinstance(recv, ast.Name) else ""
                if recv_name not in aliases and not (
                        isinstance(recv, ast.Attribute)
                        and recv.attr == "REGISTRY"):
                    continue
                arg = node.args[0]
                is_read = node.func.attr in _READ_METHODS
                if isinstance(arg, ast.Constant) \
                        and isinstance(arg.value, str):
                    if is_read:
                        reads.add(arg.value)
                    if arg.value not in defined:
                        out.append(self.finding(
                            mod, node.lineno,
                            f"flag `{arg.value}` is "
                            f"{'read' if is_read else 'set'} here but "
                            f"never defined in utils/flags.py",
                            detail=arg.value,
                            hint="DEFINE it (or fix the typo)"))
                elif isinstance(arg, ast.JoinedStr) and is_read:
                    rx = _fstring_regex(arg)
                    if rx:
                        regexes.add(rx)
                # fully dynamic reads (Name arg) prove nothing; skip
        # set_flag("x", v) module-level helper calls
        for mod in index.modules():
            if mod.tree is None or mod.rel == FLAGS_MODULE:
                continue
            for node in ast.walk(mod.tree):
                if isinstance(node, ast.Call) \
                        and call_name(node).split(".")[-1] == "set_flag" \
                        and node.args \
                        and isinstance(node.args[0], ast.Constant) \
                        and isinstance(node.args[0].value, str) \
                        and node.args[0].value not in defined:
                    out.append(self.finding(
                        mod, node.lineno,
                        f"flag `{node.args[0].value}` is set here but "
                        f"never defined in utils/flags.py",
                        detail=node.args[0].value,
                        hint="DEFINE it (or fix the typo)"))
        return reads, regexes

    # --- doc defaults -----------------------------------------------------
    def _check_doc_defaults(self, index: ProjectIndex, defs,
                            out: List[Finding]) -> None:
        docs: List[Tuple[str, List[str]]] = []
        for fn in _DOC_GLOBS:
            path = os.path.join(index.base, fn)
            if os.path.isfile(path):
                with open(path, encoding="utf-8") as f:
                    docs.append((fn, f.read().splitlines()))
        for name, (mod, line, default, help_txt) in sorted(defs.items()):
            if default is None:
                continue
            claims: List[Tuple[str, int, str]] = []
            m = _HELP_DEFAULT_RE.search(help_txt)
            if m:
                claims.append((mod.rel, line, m.group(1)))
            rx = re.compile(_DOC_DEFAULT_RE % re.escape(name))
            for fn, lines in docs:
                for i, text in enumerate(lines, 1):
                    dm = rx.search(text)
                    if dm:
                        claims.append((fn, i, dm.group(1).strip()))
            for src, src_line, claim in claims:
                if not self._claim_matches(claim, default):
                    out.append(self.finding(
                        mod, line,
                        f"flag `{name}` default is {default!r} but "
                        f"{src}:{src_line} documents default "
                        f"`{claim}`",
                        detail=name,
                        hint="fix whichever side is wrong"))

    @staticmethod
    def _claim_matches(claim: str, default) -> bool:
        c = claim.strip().strip("`'\"")
        if c == str(default):
            return True
        try:
            return ast.literal_eval(c) == default
        except (ValueError, SyntaxError):
            return c.lower() == str(default).lower()


PASS = FlagDriftPass()
