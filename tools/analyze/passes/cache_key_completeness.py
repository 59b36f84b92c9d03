"""Pass: cache-key completeness — every runtime input a keyed
computation reads must be represented in its cache key.

The device caches (DeviceBlockCache batches, kernel-signature memo
dicts) key compiled/built artifacts by structural signature.  A runtime
input that AFFECTS the cached value but is MISSING from the key makes a
warm cache serve a stale artifact after the input changes — the PR-9
regression class (``device_float_dtype`` changed, batch cache kept
serving float32 batches).  The read of the input and the construction
of the key live in different functions, so only an interprocedural
check can pair them.

How it works, per REGISTRY entry (one entry per key constructor):

1. KEY TEXT — the key constructor's key-building source: for a def
   whose name mentions ``key``/``sig`` the whole def; otherwise the
   key argument of every ``*cache*.<method>(...)`` call plus the
   right-hand side of every assignment to a ``*key*``/``*sig*`` name.
   ``key_helpers`` (dedicated key-constructor defs whose result is
   embedded, e.g. ``_batch_cache_key`` under the chunk keys) extend
   the key text.
2. FLAG CLOSURE — every ``flags.get("<literal>")`` transitively
   reachable from the entry's ``roots`` (the defs that COMPUTE the
   cached value) via the call graph.  Each reached flag must appear as
   a literal in the key text or carry an ``allow`` reason in the
   registry (e.g. "captured via prune_sig") — else a finding at the
   key constructor, with the witness call chain to the read.
3. MUST-MENTION — structural key components that are easy to drop in
   a refactor (``prune_sig``, ``dict_sig``, ``chunk_rows``, ...) are
   pinned as registry substrings; key text losing one is a finding.
4. STALENESS — a registry entry whose def no longer exists is itself
   a finding, so the registry cannot rot silently.

The registry is intentionally explicit: adding a new keyed cache means
adding an entry here (tests enforce the known constructors stay
registered).  Suppress at the key constructor's def line:
``# analysis-ok(cache_key_completeness): <reason>``.
"""
from __future__ import annotations

import ast
from typing import Dict, List, Optional, Sequence, Set, Tuple

from ..core import AnalysisPass, Finding, ProjectIndex, call_name

_OPS = "yugabyte_db_tpu/ops"
_DOCDB = "yugabyte_db_tpu/docdb"

#: one entry per key constructor; see the module docstring for fields
REGISTRY: Tuple[dict, ...] = (
    {
        "key_builder": (f"{_DOCDB}/operations.py",
                        "DocReadOperation._batch_cache_key"),
        "roots": [(f"{_OPS}/device_batch.py", "build_batch")],
        "key_helpers": [],
        "allow": {},
        "must_mention": [
            ("write_generation", "batch must rebuild after writes"),
            ("device_float_dtype", "PR-9 regression: runtime dtype "
                                   "switch must re-key the batch"),
        ],
    },
    {
        "key_builder": (f"{_DOCDB}/mesh_read.py", "MeshReader._cache_key"),
        "roots": [("yugabyte_db_tpu/parallel/distributed_scan.py",
                   "build_sharded_batch")],
        "key_helpers": [],
        "allow": {},
        "must_mention": [
            ("write_generation", "batch must rebuild after writes"),
            ("device_float_dtype", "runtime dtype switch must re-key "
                                   "the sharded batch too"),
        ],
    },
    {
        "key_builder": (f"{_OPS}/stream_scan.py",
                        "streaming_scan_aggregate.build"),
        "roots": [(f"{_OPS}/stream_scan.py",
                   "streaming_scan_aggregate.build")],
        "key_helpers": [(f"{_DOCDB}/operations.py",
                         "DocReadOperation._batch_cache_key")],
        "allow": {},
        "must_mention": [
            ("cache_key", "caller prefix (carries the batch key)"),
            ("chunk_rows", "runtime streaming_chunk_rows re-plan"),
            ("bucket", "pow2 pad bucket is part of batch shape"),
            ("prune_sig", "zone-pruned chunk list identity"),
            ("dict_sig", "dictionary plan identity"),
        ],
    },
    {
        "key_builder": (f"{_OPS}/stream_scan.py",
                        "streaming_scan_filter.build"),
        "roots": [(f"{_OPS}/stream_scan.py",
                   "streaming_scan_filter.build")],
        "key_helpers": [(f"{_DOCDB}/operations.py",
                         "DocReadOperation._batch_cache_key")],
        "allow": {},
        "must_mention": [
            ("cache_key", "caller prefix (carries the batch key)"),
            ("chunk_rows", "runtime streaming_chunk_rows re-plan"),
            ("bucket", "pow2 pad bucket is part of batch shape"),
            ("prune_sig", "zone-pruned chunk list identity"),
            ("dict_sig", "dictionary plan identity"),
        ],
    },
    {
        "key_builder": (f"{_OPS}/plan_fusion.py",
                        "streaming_plan_aggregate.build"),
        "roots": [(f"{_OPS}/plan_fusion.py",
                   "streaming_plan_aggregate.build")],
        "key_helpers": [(f"{_DOCDB}/operations.py",
                         "DocReadOperation._batch_cache_key")],
        "allow": {},
        "must_mention": [
            ("cache_key", "caller prefix (carries the batch key)"),
            ("chunk_rows", "runtime streaming_chunk_rows re-plan"),
            ("bucket", "pow2 pad bucket is part of batch shape"),
        ],
    },
    {
        "key_builder": (f"{_OPS}/plan_fusion.py",
                        "monolithic_plan_aggregate"),
        "roots": [(f"{_OPS}/plan_fusion.py",
                   "monolithic_plan_aggregate")],
        "key_helpers": [(f"{_DOCDB}/operations.py",
                         "DocReadOperation._batch_cache_key")],
        "allow": {
            "zone_map_pruning": "captured via prune_key ('zp', "
                                "kept_idx) — the pruned block-list "
                                "identity, finer than the flag bit",
            "join_max_build_slots": "join runtime is rebuilt every "
                                    "call OUTSIDE the cached lambda — "
                                    "only build_batch(kept) is keyed",
            "multi_join_max_stages": "stage-count gate raises a typed "
                                     "JoinIneligible BEFORE any cache "
                                     "touch; runtimes are rebuilt "
                                     "every call outside the cached "
                                     "lambda",
        },
        "must_mention": [
            ("prune_key", "zone-pruned block list identity"),
        ],
    },
    {
        # one signature for both scan kernels (the mesh kernel prefixes
        # its mesh's identity)
        "key_builder": (f"{_OPS}/scan.py", "prepare_launch"),
        "roots": [(f"{_OPS}/scan.py", "ScanKernel.run"),
                  ("yugabyte_db_tpu/parallel/distributed_scan.py",
                   "DistributedScanKernel.run")],
        "key_helpers": [],
        "allow": {
            "scan_group_strategy": "resolved value `strategy` is a "
                                   "signature component (finer: "
                                   "auto's resolution is keyed)",
        },
        "must_mention": [
            ("strategy", "grouped-path choice bakes into the kernel"),
            ("col_sig", "column dtype/shape identity"),
            ("mvcc_mode", "visibility mode changes the kernel body"),
            ("static_sums", "const-folded sum lanes change the body"),
            ("padded_rows", "pow2 pad bucket is a compile-time shape"),
        ],
    },
    {
        "key_builder": (f"{_OPS}/plan_fusion.py", "FusedPlanKernel.run"),
        "roots": [(f"{_OPS}/plan_fusion.py", "FusedPlanKernel.run")],
        "key_helpers": [],
        "allow": {
            "scan_group_strategy": "resolved value `strategy` is a "
                                   "signature component",
        },
        "must_mention": [
            ("strategy", "grouped-path choice bakes into the kernel"),
            ("col_sig", "column dtype/shape identity"),
            ("join_shape", "build-side shape identity"),
            ("build_buckets", "per-STAGE pow2 build buckets — a "
                              "multi-join chain must re-key when any "
                              "one stage crosses a table bucket"),
            ("dict_sig", "per-stage dict-coded payload lanes — which "
                         "lanes carry codes changes rewrite/decode "
                         "semantics downstream"),
            ("mvcc_mode", "visibility mode changes the kernel body"),
            ("static_sums", "const-folded sum lanes change the body"),
            ("padded_rows", "pow2 pad bucket is a compile-time shape"),
        ],
    },
)

_KEYISH = ("key", "sig")


def _keyish_name(name: str) -> bool:
    low = name.lower()
    return any(k in low for k in _KEYISH)


class CacheKeyCompletenessPass(AnalysisPass):
    id = "cache_key_completeness"
    title = "cache key missing a runtime input of the keyed computation"
    hint = ("add the input (or a derived signature of it) to the cache "
            "key, or record an allow reason in the pass registry "
            "explaining which key component already captures it")

    def __init__(self, registry: Optional[Sequence[dict]] = None):
        #: overridable so fixture tests can run synthetic registries
        self.registry: Tuple[dict, ...] = tuple(
            REGISTRY if registry is None else registry)

    def run(self, index: ProjectIndex) -> List[Finding]:
        graph = index.call_graph()
        out: List[Finding] = []

        #: per-def flags.get("<literal>") reads, for summarize()
        flag_reads: Dict[str, Dict[str, int]] = {}

        def direct(key: str) -> Dict[str, int]:
            if key in flag_reads:
                return flag_reads[key]
            d = graph.def_fact(key)
            got: Dict[str, int] = {}
            if d is not None:
                rel, qual = graph.split(key)
                mod = index.module(rel)
                node = self._def_node(index, graph, rel, qual)
                if mod is not None and node is not None:
                    for n in ast.walk(node):
                        if (isinstance(n, ast.Call)
                                and call_name(n).endswith("flags.get")
                                and n.args
                                and isinstance(n.args[0], ast.Constant)
                                and isinstance(n.args[0].value, str)):
                            got.setdefault(n.args[0].value, n.lineno)
            flag_reads[key] = got
            return got

        def follow(key: str) -> bool:
            return True

        for ent in self.registry:
            rel, qual = ent["key_builder"]
            mod = index.module(rel)
            node = self._def_node(index, graph, rel, qual)
            if mod is None or node is None:
                anchor = index.module(rel) or index.modules()[0]
                out.append(self.finding(
                    anchor, 1,
                    f"stale cache-key registry entry: def {qual!r} "
                    f"not found in {rel} — update the "
                    "cache_key_completeness registry",
                    detail=f"{rel}::{qual}"))
                continue

            key_text = self._key_text(qual, node)
            for hrel, hqual in ent["key_helpers"]:
                hnode = self._def_node(index, graph, hrel, hqual)
                if hnode is not None:
                    key_text += "\n" + ast.unparse(hnode)

            # 3. must-mention structural components
            for needle, why in ent["must_mention"]:
                if needle not in key_text:
                    out.append(self.finding(
                        mod, node.lineno,
                        f"cache key for {qual} lost its "
                        f"{needle!r} component ({why})",
                        detail=f"{qual}:{needle}"))

            # 2. flag closure over the keyed computation
            for rrel, rqual in ent["roots"]:
                rkey = graph.key(rrel, rqual)
                summ = graph.summarize(rkey, self.id, direct, follow)
                for flag in sorted(summ):
                    if flag in ent["allow"]:
                        continue
                    if f'"{flag}"' in key_text or \
                            f"'{flag}'" in key_text:
                        continue
                    steps = graph.chain(rkey, flag, self.id,
                                        direct, follow)
                    via = " -> ".join(
                        f"{q} ({r}:{ln})" for r, q, ln in steps)
                    out.append(self.finding(
                        mod, node.lineno,
                        f"keyed computation under {qual} reads flag "
                        f"{flag!r} (via {via or rqual}) but the cache "
                        "key never includes it — a runtime flag flip "
                        "serves stale cached results",
                        detail=f"{qual}:{flag}"))
        return out

    # ------------------------------------------------------------------
    @staticmethod
    def _def_node(index: ProjectIndex, graph, rel: str,
                  qual: str) -> Optional[ast.AST]:
        mod = index.module(rel)
        if mod is None or mod.tree is None:
            return None
        from ..callgraph import iter_defs
        for q, _cls, node in iter_defs(mod.tree):
            if q == qual:
                return node
        return None

    @staticmethod
    def _key_text(qual: str, node: ast.AST) -> str:
        """The key-building source of a def (see module docstring)."""
        name = qual.split(".")[-1]
        if _keyish_name(name):
            return ast.unparse(node)
        parts: List[str] = []
        # nested closures' key expressions count too: the chunk keys
        # are built inside `build` closures
        for n in ast.walk(node):
            if (isinstance(n, ast.Call)
                    and isinstance(n.func, ast.Attribute)
                    and n.args
                    and "cache" in ast.unparse(n.func.value).lower()):
                parts.append(ast.unparse(n.args[0]))
            if isinstance(n, ast.Assign):
                names: Set[str] = {
                    t.id for t in n.targets if isinstance(t, ast.Name)}
                if any(_keyish_name(x) for x in names):
                    parts.append(ast.unparse(n.value))
        return "\n".join(parts)


PASS = CacheKeyCompletenessPass()
