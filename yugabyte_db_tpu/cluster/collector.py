"""Cross-process trace assembly + wait-state attribution.

The harness side of the observability layer (CLUSTER.md): every server
process serves ``rpc_tracez`` — a pid+timestamp-stamped dump of its
sampled spans and ASH wait-state histograms.  This module stitches
those dumps into per-trace span TREES (one user write becomes one tree
spanning client, leader and follower processes) and turns per-round
ASH deltas into p99 attribution labels (`cluster_p99_attribution` in
the bench JSON): every round whose p99 exceeds the spread gate gets
its dominant wait state, so a tail spike explains itself instead of
being "flush-pause luck".

Layering: pure data — talks to servers only through a supervisor's
``call`` (duck-typed), never imports server internals.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Sequence

#: canonical wait-state -> attribution category.  The bench labels an
#: over-spread round with the CATEGORY (flush/fsync/queue/compile/
#: device/lock/cpu/scan) so thresholds and dashboards stay stable even
#: as the state table grows.
WAIT_CATEGORIES = {
    "Flush_SstWrite": "flush",
    "Flush_MemtableBackpressure": "flush",
    "WAL_Fsync": "fsync",
    "Catalog_Fsync": "fsync",
    "SchedQueue_Wait": "queue",
    "Raft_Replicate": "queue",
    "Raft_ApplyWait": "queue",
    "SafeTime_Wait": "lock",
    "LeaderLease_Wait": "lock",
    "Lock_Wait": "lock",
    "Device_Compile": "compile",
    "Device_BlockUntilReady": "device",
    "Compaction_Run": "flush",
    "Bypass_Scan": "scan",
    "OnCpu_Read": "cpu",
    "OnCpu_WriteApply": "cpu",
}


def classify_wait_state(state: str) -> str:
    return WAIT_CATEGORIES.get(state, "other")


async def collect_cluster_tracez(sup, timeout: float = 10.0
                                 ) -> List[dict]:
    """One rpc_tracez dump per ALIVE process in the cluster (tservers,
    masters and drivers all serve the same method on their role
    service).  `sup` is a ClusterSupervisor (duck-typed: ``procs``
    name->proc with ``.role``/``.alive()``, plus ``call``)."""
    dumps: List[dict] = []
    for name, proc in sorted(sup.procs.items()):
        if not proc.alive():
            continue
        service = getattr(proc, "role", "tserver")
        try:
            d = await sup.call(name, service, "tracez", {},
                               timeout=timeout)
        except Exception:   # noqa: BLE001 — a dead/draining process
            continue        # just drops out of the stitch
        d["process"] = name
        dumps.append(d)
    return dumps


def _nodes(dumps: Sequence[dict]) -> List[dict]:
    out = []
    for d in dumps:
        for key in ("spans", "active"):
            for s in d.get(key, ()):
                n = dict(s)
                n["pid"] = d.get("pid")
                n["process"] = d.get("process")
                n["children"] = []
                out.append(n)
    return out


def stitch(dumps: Sequence[dict]) -> Dict[int, dict]:
    """Assemble span trees across process dumps.

    Returns {trace_id: {"roots": [span trees], "span_count": N,
    "pids": [...]}} — a span whose parent is missing from every dump
    (sampled out of the ring, or an unsampled ancestor) becomes a root
    of its own subtree rather than being dropped."""
    nodes = _nodes(dumps)
    by_span: Dict[int, dict] = {}
    for n in nodes:
        # later dumps win on span_id collision (same span active+recent)
        prev = by_span.get(n["span_id"])
        if prev is None or (n.get("finished") and not prev.get("finished")):
            by_span[n["span_id"]] = n
    traces: Dict[int, dict] = {}
    for n in by_span.values():
        t = traces.setdefault(
            n["trace_id"], {"roots": [], "span_count": 0, "pids": set()})
        t["span_count"] += 1
        t["pids"].add(n["pid"])
        parent = by_span.get(n["parent_id"])
        if parent is not None and parent is not n:
            parent["children"].append(n)
        else:
            t["roots"].append(n)
    for t in traces.values():
        t["pids"] = sorted(p for p in t["pids"] if p is not None)
        for r in t["roots"]:
            _sort_tree(r)
    return traces


def _sort_tree(node: dict) -> None:
    node["children"].sort(key=lambda c: c.get("start_unix", 0.0))
    for c in node["children"]:
        _sort_tree(c)


def tree_names(tree: dict) -> List[str]:
    """Flattened span names of one stitched tree (assertion helper)."""
    out = [tree.get("name", "")]
    for c in tree.get("children", ()):
        out.extend(tree_names(c))
    return out


def render_tree(tree: dict, indent: int = 0) -> str:
    """Human-readable one-tree dump (debugging aid)."""
    line = (" " * indent +
            f"{tree.get('name')} [{tree.get('duration_ms')}ms "
            f"pid={tree.get('pid')}]")
    return "\n".join([line] + [render_tree(c, indent + 2)
                               for c in tree.get("children", ())])


# --- ASH attribution -------------------------------------------------------

def merge_ash_cumulative(dumps: Sequence[dict]) -> Dict[str, int]:
    """Sum the monotonic per-state tallies across process dumps (the
    diffable counters — the windowed histograms don't subtract
    cleanly across round boundaries)."""
    out: Dict[str, int] = {}
    for d in dumps:
        for state, n in (d.get("ash", {}) or {}).get(
                "cumulative", {}).items():
            out[state] = out.get(state, 0) + int(n)
    return out


def ash_delta(pre: Dict[str, int], post: Dict[str, int]
              ) -> Dict[str, int]:
    return {s: post.get(s, 0) - pre.get(s, 0)
            for s in post if post.get(s, 0) > pre.get(s, 0)}


def dominant_wait(delta: Dict[str, int],
                  exclude_cpu: bool = True) -> Optional[str]:
    """The wait state that accumulated the most sampler ticks in this
    window.  On-CPU buckets are excluded first (a p99 spike blamed on
    "was running" explains nothing) but win as fallback — on a 2-core
    box pure CPU contention is an honest answer."""
    if not delta:
        return None
    blocked = {s: n for s, n in delta.items()
               if not exclude_cpu or classify_wait_state(s) != "cpu"}
    pool = blocked or delta
    return max(pool.items(), key=lambda kv: kv[1])[0]


def attribute_rounds(rounds: Sequence[dict],
                     spread_gate: float = 3.0) -> dict:
    """Label bench rounds with their dominant wait state.

    ``rounds``: [{"tag", "p99_ms", "wait_delta": {state: ticks}}].
    Every round whose p99 exceeds ``spread_gate`` x the median p99 is
    flagged ``over_spread`` and labeled with its dominant wait state +
    category — the `cluster_p99_attribution` block in the bench JSON.
    """
    p99s = sorted(r.get("p99_ms", 0.0) for r in rounds)
    median = p99s[len(p99s) // 2] if p99s else 0.0
    out_rounds = []
    over = []
    for r in rounds:
        delta = r.get("wait_delta") or {}
        dom = dominant_wait(delta)
        top = sorted(delta.items(), key=lambda kv: -kv[1])[:3]
        is_over = median > 0 and r.get("p99_ms", 0.0) > spread_gate * median
        entry = {
            "tag": r.get("tag"),
            "p99_ms": r.get("p99_ms"),
            "over_spread": is_over,
            "dominant_wait": dom,
            "category": classify_wait_state(dom) if dom else None,
            "top_waits": top,
        }
        out_rounds.append(entry)
        if is_over:
            over.append(entry["tag"])
    return {"spread_gate": spread_gate,
            "median_p99_ms": round(median, 2),
            "over_spread_rounds": over,
            "rounds": out_rounds}
