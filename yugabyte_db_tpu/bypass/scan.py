"""Keyless v2 SST-direct scan engine.

Opens a pinned snapshot's SST files directly (fresh readers over the
leased paths — never the store's own reader list, and with NO
key_builder bound, so a key-matrix rebuild is structurally impossible:
there is no thunk to fire) and streams their columnar blocks through
the shared pow2-bucket chunk pipeline (ops/stream_scan.py).  The v2
format's promise finally cashes out here: eligibility, zone-map
pruning, chunk-safety and SST-run ordering all read only the stored
boundary keys (k0/k1), so an all-v2 tablet scans end-to-end with ZERO
key-matrix rebuilds (``KEY_REBUILD_STATS`` asserts it in tests).

Eligibility is typed (errors.py): anything the engine cannot serve
exactly — hash groups, varlen-only columns, non-chunk-safe block
sequences, kernel-incompatible expressions — raises BypassIneligible
and the caller falls back to the RPC path.  What IS served is
byte-identical to the RPC scan path at the same read point: the same
zone-prune gate, the same chunk plan and shared bucket, the same
kernel and combine rules, and the same monolithic twin under
``min_chunks`` (the near-data prefilter preserves this bit-for-bit —
see bypass/prefilter.py).
"""
from __future__ import annotations

from typing import List, Optional, Sequence, Tuple

import numpy as np

from ..ops.device_batch import bucket_rows, build_batch
from ..ops.grouped_scan import DictGroupSpec
from ..ops.scan import AggSpec, HashGroupSpec, ScanKernel, _expand_avg
from ..ops.stream_scan import (LAST_STREAM_STATS, chunk_safe_mvcc,
                               streaming_scan_aggregate)
from ..storage.columnar import KEY_REBUILD_STATS, ColumnarBlock
from ..storage.sst import SstReader
from ..utils import flags
from .errors import (REASON_COLUMN_NOT_FIXED, REASON_DOC_OFF,
                     REASON_DOC_SHAPE, REASON_EXPR_SHAPE,
                     REASON_GROUPED_OFF, REASON_HASH_GROUP,
                     REASON_JOIN_OFF, REASON_JOIN_SHAPE,
                     REASON_NO_COLUMNAR, REASON_NOT_AGGREGATE,
                     REASON_NOT_CHUNK_SAFE, REASON_SLOT_OVERFLOW,
                     BypassIneligible)
from .prefilter import make_prefilter


def open_snapshot_readers(snap) -> List[SstReader]:
    """Fresh SstReaders over a snapshot's leased paths.  No row_decoder
    and — deliberately — no key_builder: the keyless scanner has no
    lazy-rebuild path to fall into."""
    return [SstReader(p, row_decoder=None, key_builder=None)
            for p in snap.sst_paths]


def collect_keyless_blocks(readers: Sequence[SstReader]
                           ) -> Tuple[List[ColumnarBlock], dict]:
    """All columnar blocks of the snapshot, as ONE candidate sorted
    run: per-SST block runs are ordered by their first stored boundary
    key (newest-first install order is irrelevant for a disjoint set;
    interleaved/overlapping runs are caught by the chunk-safety check
    downstream, which this ordering deliberately feeds)."""
    runs: List[List[ColumnarBlock]] = []
    keyless = 0
    total = 0
    for r in readers:
        run: List[ColumnarBlock] = []
        for i in range(r.num_blocks()):
            cb = r.read_columnar(i)
            if cb is None:
                raise BypassIneligible(
                    REASON_NO_COLUMNAR,
                    f"{r.path}: block {i} has no columnar sidecar")
            total += 1
            if cb._keys is None:
                keyless += 1
            run.append(cb)
        if run:
            runs.append(run)

    def run_key(run: List[ColumnarBlock]) -> bytes:
        k0, _ = run[0].boundary_keys(materialize=False)
        return k0 if k0 is not None else b""

    runs.sort(key=run_key)
    blocks = [b for run in runs for b in run]
    return blocks, {"blocks": total, "keyless_blocks": keyless,
                    "ssts": len(readers)}


def bypass_scan_aggregate(
        blocks: Sequence[ColumnarBlock],
        where: Optional[tuple], aggs: Sequence[AggSpec],
        group, read_ht: int,
        kernel: Optional[ScanKernel] = None,
        chunk_rows: Optional[int] = None,
        prefilter_enabled: Optional[bool] = None,
        min_chunks: int = 3,
        grouped_out: Optional[dict] = None
        ) -> Tuple[tuple, np.ndarray, dict]:
    """Aggregate `blocks` at `read_ht` without touching the tserver.
    Returns (agg_values, counts, stats); raises BypassIneligible with a
    typed reason for every shape the engine cannot serve exactly.

    A :class:`DictGroupSpec` group serves KEYLESSLY too: string group
    columns ride as dictionary codes (stored v2 dict lanes or the
    per-block byte-level unique — row strings never decode), the
    grouped kernel aggregates into slot arrays, and the caller receives
    COMPACTED per-shard partials — ``grouped_out['group_values']``
    carries the decoded string keys aligned with the returned counts,
    ready for the shared group-keyed combine.  Slot overflow raises
    ``REASON_SLOT_OVERFLOW`` (the RPC path's interpreted GROUP BY
    serves the over-cardinality set)."""
    if not aggs:
        raise BypassIneligible(REASON_NOT_AGGREGATE)
    if isinstance(group, HashGroupSpec):
        raise BypassIneligible(REASON_HASH_GROUP)
    dict_group = isinstance(group, DictGroupSpec)
    if dict_group and not flags.get("grouped_pushdown_enabled"):
        raise BypassIneligible(REASON_GROUPED_OFF)
    # doc-path shapes rewrite onto shredded virtual lanes FIRST — the
    # keyless scanner then serves them like any derived column (the
    # shredded lanes need no key matrix, so zero key rebuilds hold)
    from ..docstore import pushdown as _doc
    if _doc.exprs_have_doc(where, aggs):
        if not flags.get("doc_shred_enabled"):
            raise BypassIneligible(REASON_DOC_OFF)
        from ..docstore.errors import DocIneligible
        try:
            where, aggs, _refs, blocks = _doc.prepare_doc_scan(
                where, aggs, blocks)
        except DocIneligible as e:
            raise BypassIneligible(
                REASON_DOC_SHAPE,
                e.reason + (f": {e.detail}" if e.detail else ""))
    from ..ops.expr import device_compatible, referenced_columns
    if where is not None and not device_compatible(where):
        raise BypassIneligible(REASON_EXPR_SHAPE, "where")
    for a in aggs:
        if a.expr is not None and not device_compatible(a.expr):
            raise BypassIneligible(REASON_EXPR_SHAPE, "aggregate expr")
    needed: set = set()
    if where is not None:
        referenced_columns(where, needed)
    for a in aggs:
        if a.expr is not None:
            referenced_columns(a.expr, needed)
    if dict_group:
        needed.update(group.cols)
    elif group is not None:
        needed.update(cid for cid, _, _ in group.cols)
    for b in blocks:
        for cid in needed:
            # varlen (string) columns are servable too: they ride as
            # dictionary codes (string predicates compare as integers,
            # DictGroupSpec keys aggregate as code strides); columns
            # with no columnar form at all stay typed-ineligible
            if not (cid in b.fixed or cid in b.pk or cid in b.varlen):
                raise BypassIneligible(
                    REASON_COLUMN_NOT_FIXED, f"column {cid}")
    # the ONE structural gate: every doc key lives wholly inside one
    # block of one globally-sorted disjoint unique-key run, proven from
    # stored boundary keys alone
    if not chunk_safe_mvcc(blocks):
        raise BypassIneligible(REASON_NOT_CHUNK_SAFE)
    if prefilter_enabled is None:
        prefilter_enabled = flags.get("bypass_prefilter_enabled")
    if kernel is None:
        from ..docdb.operations import _SHARED_KERNEL
        kernel = _SHARED_KERNEL
    rebuilds0 = KEY_REBUILD_STATS["rebuilds"]
    cols_sorted = sorted(needed)
    expanded = tuple(_expand_avg(aggs))
    minmax = [i for i, a in enumerate(expanded)
              if a.op in ("min", "max")]
    aggs_run = expanded + tuple(AggSpec("count", expanded[i].expr)
                                for i in minmax)
    # the near-data prefilter compacts blocks through the fused
    # FIXED-lane gather — compacted pseudo-blocks carry no varlen
    # lanes, so a scan whose columns ride as dictionary codes (string
    # predicates, DictGroupSpec group keys) must run unfiltered; the
    # streaming path makes the same call (compacted blocks would have
    # no dictionary remap entries)
    rides_codes = any(
        not all(cid in b.fixed or cid in b.pk for b in blocks)
        for cid in cols_sorted)
    pf = (make_prefilter(where, cols_sorted)
          if prefilter_enabled and not rides_codes else None)
    stats: dict = {}
    gout: Optional[dict] = {} if dict_group else None
    dict_out: dict = {}
    got = streaming_scan_aggregate(
        blocks, cols_sorted, where, aggs_run, group, read_ht,
        kernel=kernel, chunk_rows=chunk_rows, prefilter=pf,
        min_chunks=min_chunks, grouped_out=gout, dict_out=dict_out)
    group_dicts = None
    if got is None:
        got = _monolithic_twin(blocks, cols_sorted, where, aggs_run,
                               group, read_ht, kernel, pf,
                               dict_out=dict_out)
        if dict_group:
            got, group_dicts = got
        stats["path"] = "monolithic"
    else:
        if dict_group:
            if gout.get("spill"):
                raise BypassIneligible(
                    REASON_SLOT_OVERFLOW,
                    f"{gout['spill']} rows past "
                    f"{gout['num_slots']} slots")
            group_dicts = gout["dicts"]
        stats["path"] = "streaming"
        stats.update(LAST_STREAM_STATS)
    outs, counts = got
    from ..docdb.operations import _nullify_minmax, dict_minmax_decode
    outs = _nullify_minmax(expanded, minmax, outs)
    # dict-code MIN/MAX decode happens PER SHARD, before the session's
    # cross-shard combine — each shard merged its own dictionary, so
    # codes must never leave the shard
    outs = dict_minmax_decode(expanded, outs,
                              dict_out.get("dicts") or {})
    if dict_group:
        from ..ops.grouped_scan import decode_slot_groups
        outs, counts, gvals = decode_slot_groups(
            group, group_dicts, outs, counts)
        if grouped_out is not None:
            grouped_out["group_values"] = gvals
    stats["key_rebuilds"] = KEY_REBUILD_STATS["rebuilds"] - rebuilds0
    if pf is not None:
        from .prefilter import LAST_PREFILTER_STATS
        stats.setdefault("prefilter_rows_in",
                         LAST_PREFILTER_STATS["rows_in"])
        stats.setdefault("prefilter_rows_kept",
                         LAST_PREFILTER_STATS["rows_kept"])
    return outs, np.asarray(counts), stats


def bypass_plan_aggregate(
        blocks: Sequence[ColumnarBlock],
        where: Optional[tuple], aggs: Sequence[AggSpec],
        group, read_ht: int, join_wire,
        chunk_rows: Optional[int] = None,
        min_chunks: int = 3,
        grouped_out: Optional[dict] = None
        ) -> Tuple[tuple, np.ndarray, dict]:
    """Fused-plan (FK-equijoin) aggregate over a pinned snapshot —
    the bypass route of ops/plan_fusion.py.  The probe scan streams
    keylessly exactly like bypass_scan_aggregate (same chunk-safety
    gate, same shared bucket); the build side probes inside the fused
    program.  Raises BypassIneligible with a typed reason for every
    shape the engine cannot serve exactly; ``REASON_JOIN_SHAPE``
    carries the ops/join_scan typed reason in its detail."""
    from ..ops.join_scan import BUILD_COL_BASE, JoinIneligible
    from ..ops.plan_fusion import (default_plan_kernel,
                                   monolithic_plan_aggregate,
                                   streaming_plan_aggregate)
    if not aggs:
        raise BypassIneligible(REASON_NOT_AGGREGATE)
    if isinstance(group, HashGroupSpec):
        raise BypassIneligible(REASON_HASH_GROUP)
    if not flags.get("join_pushdown_enabled"):
        raise BypassIneligible(REASON_JOIN_OFF)
    dict_group = isinstance(group, DictGroupSpec)
    if dict_group and not flags.get("grouped_pushdown_enabled"):
        raise BypassIneligible(REASON_GROUPED_OFF)
    from ..ops.expr import device_compatible, referenced_columns
    if where is not None and not device_compatible(where):
        raise BypassIneligible(REASON_EXPR_SHAPE, "where")
    for a in aggs:
        if a.expr is not None and not device_compatible(a.expr):
            raise BypassIneligible(REASON_EXPR_SHAPE, "aggregate expr")
    needed: set = set()
    if where is not None:
        referenced_columns(where, needed)
    for a in aggs:
        if a.expr is not None:
            referenced_columns(a.expr, needed)
    if dict_group:
        needed.update(group.cols)
    elif group is not None:
        needed.update(cid for cid, _, _ in group.cols)
    needed = {c for c in needed if c < BUILD_COL_BASE}
    # multi-stage chains: only REAL probe-table columns gather from
    # blocks — a chain stage's probe lane is an earlier stage's payload
    # (>= BUILD_COL_BASE) and materializes inside the fused program
    from ..ops.join_scan import normalize_join
    for w in normalize_join(join_wire):
        if w.probe_col < BUILD_COL_BASE:
            needed.add(w.probe_col)
    for b in blocks:
        for cid in needed:
            if not (cid in b.fixed or cid in b.pk or cid in b.varlen):
                raise BypassIneligible(
                    REASON_COLUMN_NOT_FIXED, f"column {cid}")
    if not chunk_safe_mvcc(blocks):
        raise BypassIneligible(REASON_NOT_CHUNK_SAFE)
    kernel = default_plan_kernel()
    rebuilds0 = KEY_REBUILD_STATS["rebuilds"]
    cols_sorted = sorted(needed)
    expanded = tuple(_expand_avg(aggs))
    minmax = [i for i, a in enumerate(expanded)
              if a.op in ("min", "max")]
    aggs_run = expanded + tuple(AggSpec("count", expanded[i].expr)
                                for i in minmax)
    stats: dict = {}
    gout: Optional[dict] = {} if dict_group else None
    from ..docdb.operations import DocReadOperation
    try:
        got = streaming_plan_aggregate(
            blocks, cols_sorted, where, aggs_run, group, read_ht,
            join_wire, kernel=kernel, chunk_rows=chunk_rows,
            min_chunks=min_chunks, grouped_out=gout)
        if got is None:
            try:
                got = monolithic_plan_aggregate(
                    blocks, cols_sorted, where, aggs_run, group,
                    read_ht, join_wire, kernel=kernel,
                    grouped_out=gout)
            except KeyError as e:
                raise BypassIneligible(REASON_COLUMN_NOT_FIXED, str(e))
            stats["path"] = "monolithic"
        else:
            stats["path"] = "streaming"
    except JoinIneligible as e:
        raise BypassIneligible(REASON_JOIN_SHAPE, e.reason)
    except DocReadOperation._Unrewritable:
        raise BypassIneligible(
            REASON_EXPR_SHAPE,
            "string column outside a rewritable predicate shape")
    if dict_group and gout.get("spill"):
        raise BypassIneligible(
            REASON_SLOT_OVERFLOW,
            f"{gout['spill']} rows past {gout['num_slots']} slots")
    outs, counts = got
    from ..docdb.operations import _nullify_minmax
    outs = _nullify_minmax(expanded, minmax, outs)
    if dict_group:
        from ..ops.grouped_scan import decode_slot_groups
        outs, counts, gvals = decode_slot_groups(
            group, gout["dicts"], outs, counts)
        if grouped_out is not None:
            grouped_out["group_values"] = gvals
    stats["key_rebuilds"] = KEY_REBUILD_STATS["rebuilds"] - rebuilds0
    from ..ops.plan_fusion import LAST_PLAN_STATS
    # keep the session-scoped key_rebuilds (it covers block collection
    # too, not just the chunk pipeline)
    stats.update({k: v for k, v in LAST_PLAN_STATS.items()
                  if k not in ("path", "key_rebuilds")})
    return outs, np.asarray(counts), stats


def _monolithic_twin(blocks, cols_sorted, where, aggs_run, group,
                     read_ht, kernel, pf, dict_out: dict = None):
    """The under-min_chunks shape, mirroring the RPC monolithic
    aggregate path bit-for-bit (zone-prune gate, single bucket over the
    kept rows, string predicates rewritten against the batch
    dictionaries; the blocks are proved one version a key, so no row
    versions are linked and the mask is the `visible` one) so bypass
    results stay byte-identical whichever shape the row count picks.
    Dict-grouped scans return ``((outs, counts), batch dictionaries)``
    — the caller decodes slots through the same dictionaries the group
    ids were encoded with."""
    from ..ops.scan import zone_prune_blocks
    kept = list(blocks)
    if where is not None and flags.get("zone_map_pruning"):
        # bypass blocks are always chunk-safe (the caller verified), so
        # pruning is unconditionally sound here
        kept, _ = zone_prune_blocks(kept, where)
    try:
        if pf is not None:
            batch = build_batch(
                pf(kept), cols_sorted,
                pad_to=bucket_rows(max(sum(b.n for b in kept), 1)),
                bounds_blocks=kept)
        else:
            batch = build_batch(kept, cols_sorted)
    except KeyError as e:
        # build_batch's documented fall-back contract: a varlen column
        # that can't dictionary-encode (binary / non-UTF8 payloads)
        # raises KeyError — typed here so client routing falls back to
        # the RPC path instead of crashing.  Scoped to the batch build
        # alone: a KeyError from kernel dispatch below would be a real
        # bug and must propagate, not masquerade as ineligibility.
        raise BypassIneligible(REASON_COLUMN_NOT_FIXED, str(e))
    if dict_out is not None:
        dict_out["dicts"] = batch.dicts
    if batch.dicts and (where is not None
                        or any(a.expr is not None for a in aggs_run)):
        from ..docdb.operations import DocReadOperation
        try:
            where, aggs_run = DocReadOperation.rewrite_where_and_aggs(
                where, aggs_run, batch.dicts)
        except DocReadOperation._Unrewritable:
            raise BypassIneligible(
                REASON_EXPR_SHAPE, "string column outside a "
                "rewritable predicate shape")
    if isinstance(group, DictGroupSpec):
        from ..ops.grouped_scan import domain_product
        if any(c not in batch.dicts for c in group.cols):
            raise BypassIneligible(
                REASON_COLUMN_NOT_FIXED,
                "group column has no dictionary form")
        if domain_product(group, batch.dicts) >= 2 ** 31:
            raise BypassIneligible(
                REASON_SLOT_OVERFLOW,
                "group domain product exceeds 2^31 (group id would "
                "wrap)")
        outs, counts, _, spill = kernel.run(batch, where, aggs_run,
                                            group, read_ht)
        if int(spill) > 0:
            raise BypassIneligible(
                REASON_SLOT_OVERFLOW, f"{int(spill)} rows spilled")
        return (outs, counts), batch.dicts
    outs, counts, _ = kernel.run(batch, where, aggs_run, group, read_ht)
    return outs, counts
