"""Pass: numeric-exactness contract — SUM/COUNT stay exact int64, and
zone-map float bounds are only consumed through the f32-widened
envelope.

The aggregate contract (ops/scan.py): SUM and COUNT are EXACT —
integer lanes accumulate in int64, float lanes quantize to int64
fixed-point first; floats never accumulate in float32 (5e8 rows of
1.0 in f32 saturates at 2**24 and silently stops counting).  Zone-map
bounds are stored as float32 minima/maxima of possibly-float64 data,
so a consumer comparing them EXACTLY can prune a block that actually
contains matching rows — every consumer must go through the
``_f32_widen`` one-ulp-outward envelope in ops/scan.py.  And
constant-table compilation (``compile_expr``) is positional: a second
compile in the same def without an explicit ``offset=`` re-reads the
FIRST expression's constants (the PR-12 consts-offset regression).

Rules (all taint-local to one def; under-approximate on missing
evidence — no finding without a dtype witness):

- R1 ``sum-dtype``: ``jnp.sum(x)`` / ``segment_sum(x, ...)`` with no
  ``dtype=`` where ``x``'s local assignment evidence shows a narrow
  integer/bool dtype (int8/16/32, bool) and never int64 — the
  accumulator inherits the narrow dtype and overflows.
- R2 ``zone-envelope``: an attribute read of ``.zmap`` in any module
  other than the envelope implementation (ops/scan.py) and the
  builders (storage/columnar.py, docstore/pushdown.py) — raw bounds
  must not leak past the widened envelope.
- R3 ``float-accumulator``: summing a value whose evidence shows an
  int/bool source cast through float32 (``x.astype(jnp.float32)``
  then summed) — exact counts silently become saturating f32 adds.
- R4 ``consts-offset``: a def calling ``compile_expr`` two or more
  times where any call after the first omits ``offset=`` — the
  second expression reads the first's constant table.

Suppress at the reported line:
``# analysis-ok(numeric_exactness): <reason>``.
"""
from __future__ import annotations

import ast
from typing import Dict, List, Set

from ..core import AnalysisPass, Finding, ProjectIndex, call_name

_NARROW_INT = frozenset({"int8", "int16", "int32", "bool_", "bool"})
_INTISH = _NARROW_INT | {"int64"}
_DTYPE_TOKENS = _INTISH | {"float32", "float64"}

#: modules allowed to touch raw .zmap bounds (envelope impl + builders)
_ZMAP_OK = ("ops/scan.py", "storage/columnar.py",
            "docstore/pushdown.py")

def _dtype_tokens(text: str) -> Set[str]:
    return {t for t in _DTYPE_TOKENS if t in text}


def _is_sum_call(n: ast.Call) -> bool:
    # jnp.sum / jax.numpy.sum / *.segment_sum — NOT np.sum (numpy
    # already accumulates integers in platform int64)
    cn = call_name(n)
    if cn.endswith("segment_sum"):
        return True
    return (cn.split(".")[-1] == "sum"
            and (cn.startswith("jnp.") or cn.startswith("jax.")))


class NumericExactnessPass(AnalysisPass):
    id = "numeric_exactness"
    title = "exact-aggregate / zone-envelope numeric contract violation"
    hint = ("accumulate in int64 (dtype=jnp.int64, or quantize floats "
            "to int64 fixed-point); consume zone-map bounds through "
            "ops/scan.py's _f32_widen envelope; pass offset= to every "
            "compile_expr after the first")

    def run(self, index: ProjectIndex) -> List[Finding]:
        from ..callgraph import iter_defs
        out: List[Finding] = []
        for mod in index.modules():
            if mod.tree is None:
                continue
            # token gates: the rules only ever fire on source that
            # mentions these — skip the AST walks everywhere else
            if ".zmap" in mod.source and not mod.rel.endswith(_ZMAP_OK):
                for n in ast.walk(mod.tree):
                    if (isinstance(n, ast.Attribute)
                            and n.attr == "zmap"):
                        out.append(self.finding(
                            mod, n.lineno,
                            "raw zone-map bounds read outside the "
                            "f32-widen envelope — float32 block "
                            "min/max compared exactly can prune "
                            "blocks that contain matching rows",
                            detail="zone-envelope"))
            if not any(t in mod.source for t in
                       ("jnp.", "jax.", "segment_sum", "compile_expr")):
                continue
            for qual, _cls, node in iter_defs(mod.tree):
                self._check_def(mod, qual, node, out)
        return out

    def _check_def(self, mod, qual: str, node, out: List[Finding],
                   ) -> None:
        #: local name -> dtype tokens seen in its assignments
        evidence: Dict[str, Set[str]] = {}
        sums: List[ast.Call] = []
        compiles: List[ast.Call] = []

        def _own_nodes(root):
            """Source-order walk that stays out of nested defs —
            iter_defs hands those to their own _check_def, and the
            evidence map chains assignments in program order."""
            for n in ast.iter_child_nodes(root):
                if isinstance(n, (ast.FunctionDef,
                                  ast.AsyncFunctionDef, ast.Lambda)):
                    continue
                yield n
                yield from _own_nodes(n)

        for n in _own_nodes(node):
            if isinstance(n, ast.Assign) and len(n.targets) == 1 \
                    and isinstance(n.targets[0], ast.Name):
                name = n.targets[0].id
                rhs = ast.unparse(n.value)
                toks = _dtype_tokens(rhs)
                # a cast chains its source's evidence: y =
                # x.astype(jnp.float32) keeps x's int taint on y
                for m in ast.walk(n.value):
                    if isinstance(m, ast.Name) and m.id in evidence:
                        toks |= evidence[m.id]
                if toks:
                    evidence.setdefault(name, set()).update(toks)
            elif isinstance(n, ast.Call):
                if _is_sum_call(n) and n.args:
                    sums.append(n)
                if call_name(n).split(".")[-1] == "compile_expr":
                    compiles.append(n)

        for n in sums:
            if any(kw.arg == "dtype" for kw in n.keywords):
                continue
            arg = n.args[0]
            text = ast.unparse(arg)
            toks = set(_dtype_tokens(text))
            for m in ast.walk(arg):
                if isinstance(m, ast.Name) and m.id in evidence:
                    toks |= evidence[m.id]
            narrow = toks & _NARROW_INT
            if narrow and "int64" not in toks and "float32" not in toks:
                out.append(self.finding(
                    mod, n.lineno,
                    f"sum over {'/'.join(sorted(narrow))}-evidenced "
                    f"value without dtype= — the accumulator "
                    "inherits the narrow dtype and overflows "
                    "(contract: exact int64)",
                    detail="sum-dtype"))
            elif "float32" in toks and toks & _INTISH:
                out.append(self.finding(
                    mod, n.lineno,
                    "int/bool value cast through float32 then "
                    "summed — exact counts become saturating f32 "
                    "adds above 2**24 (contract: exact int64, "
                    "quantize floats to fixed-point)",
                    detail="float-accumulator"))

        if len(compiles) >= 2:
            ordered = sorted(compiles, key=lambda c: (c.lineno,
                                                      c.col_offset))
            for c in ordered[1:]:
                if not any(kw.arg == "offset" for kw in c.keywords):
                    out.append(self.finding(
                        mod, c.lineno,
                        f"compile_expr call after the first in "
                        f"{qual} without offset= — it re-reads the "
                        "first expression's constant table (the "
                        "consts-offset regression)",
                        detail="consts-offset"))


PASS = NumericExactnessPass()
