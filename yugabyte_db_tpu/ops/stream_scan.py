"""Streaming pow2-chunk scan — cold scans without whole-batch
materialization.

The monolithic cold scan pays decode + concat + pad + device_put for
EVERY row before the first kernel byte executes, and its padded bucket
can overshoot the true row count by up to 2x (6M rows pad to 8M).  Here
the block list is cut into chunks of consecutive whole blocks
(~``streaming_chunk_rows`` rows, padded to ONE shared pow2 bucket), and
a :class:`storage.pipeline.StreamPipeline` overlaps chunk k+1's batch
formation (fused native copy, GIL-released) with chunk k's kernel
execution.  Each chunk hits the SAME kernel-cache signature — one
compile serves the whole stream — and chunk batches land in the device
cache individually, so a warm re-scan re-dispatches cached chunks with
zero host work.

Aggregate partials combine host-side with the same rules the
distributed layer uses (sum/count add — int64 partials stay exact —
min/max take elementwise extremes); per-chunk static SUM scales rescale
before combining, so chunk boundaries never change the documented
accumulation contract.

MVCC correctness bounds what may stream: with a read point set, a doc
key's versions must not span a chunk boundary.  ``chunk_safe_mvcc``
proves the sufficient condition — every block carries a keys matrix,
is internally unique, and consecutive blocks' boundary DOC KEYS differ
— which holds exactly for the bulk-load / post-compaction single-SST
shape the cold-scan benchmarks measure.  Everything else (overlapping
SSTs, memtable overlays, hash-grouped or dictionary-column scans)
falls back to the monolithic path; ``streaming_scan_enabled=False``
forces it, keeping the honest r05 baseline reproducible.
"""
from __future__ import annotations

import dataclasses
from typing import List, Optional, Sequence, Tuple

import numpy as np

from ..storage.columnar import ColumnarBlock
from ..storage.pipeline import StreamPipeline
from ..utils import flags
from ..utils.hybrid_time import ENCODED_SIZE
from .device_batch import bucket_rows, build_batch
from .grouped_scan import (LAST_GROUPED_STATS, DictGroupSpec,
                           dict_cols_needed, domain_product,
                           make_dict_plan, resolve_group)
from .scan import AggSpec, HashGroupSpec, ScanKernel, _expand_avg

_HT_SUFFIX = ENCODED_SIZE + 1   # DocHybridTime suffix + kHybridTime marker

#: stats of the most recent streaming scan (informational only)
LAST_STREAM_STATS: dict = {}


def plan_chunks(blocks: Sequence[ColumnarBlock],
                chunk_rows: int) -> List[List[ColumnarBlock]]:
    """Cut the block list into runs of consecutive WHOLE blocks of
    ~chunk_rows rows (block granularity keeps every array a zero-copy
    view until the fused fill)."""
    chunks: List[List[ColumnarBlock]] = []
    cur: List[ColumnarBlock] = []
    rows = 0
    for b in blocks:
        cur.append(b)
        rows += b.n
        if rows >= chunk_rows:
            chunks.append(cur)
            cur, rows = [], 0
    if cur:
        chunks.append(cur)
    return chunks


def chunk_safe_mvcc(blocks: Sequence[ColumnarBlock]) -> bool:
    """True when chunking at any block boundary preserves MVCC
    semantics: all blocks are internally unique-keyed, carry boundary
    keys, and no doc key straddles two consecutive blocks — so the
    newest-visible-version choice never needs to see two chunks.

    Only BOUNDARY keys are consulted (``boundary_keys`` with
    ``materialize=False``), so v2 keyless blocks prove safety from
    their stored k0/k1 without EVER materializing the derived key
    matrix: a block that has neither an inline matrix nor stored
    boundary keys is simply declared unsafe (the monolithic path
    serves it) rather than paying a whole-block rebuild inside an
    eligibility check."""
    prev_last: Optional[bytes] = None
    for b in blocks:
        if not b.unique_keys or b.n == 0:
            return False
        first, last = b.boundary_keys(materialize=False)
        if first is None or last is None or len(first) <= _HT_SUFFIX:
            return False
        # boundary doc keys must be STRICTLY ascending across the whole
        # block sequence: that proves the blocks are one globally-sorted
        # disjoint run (a second overlapping SST — or a memtable overlay
        # — breaks monotonicity at its first block and fails here)
        first_dk = first[:-_HT_SUFFIX]
        if prev_last is not None and prev_last >= first_dk:
            return False
        prev_last = last[:-_HT_SUFFIX]
    return True


def _combine(aggs: Tuple[AggSpec, ...], acc: Optional[list],
             new: Sequence) -> list:
    if acc is None:
        return [np.asarray(o) for o in new]
    for i, a in enumerate(aggs):
        if a.op in ("sum", "count"):
            acc[i] = acc[i] + np.asarray(new[i])
        elif a.op == "min":
            acc[i] = np.minimum(acc[i], np.asarray(new[i]))
        elif a.op == "max":
            acc[i] = np.maximum(acc[i], np.asarray(new[i]))
        else:   # pragma: no cover — _expand_avg leaves only these four
            raise ValueError(a.op)
    return acc


def group_domain_ok(group, dicts) -> bool:
    """Shared guard for every dict-group route (streamed, fused-plan,
    bypass): all group columns must carry a scan-global dictionary and
    the slot-id arithmetic must not wrap int32 (the kernel's gid lane).
    Non-dict groups pass trivially."""
    if not isinstance(group, DictGroupSpec):
        return True
    if any(c not in dicts for c in group.cols):
        return False
    return domain_product(group, dicts) < 2 ** 31


def _kept_plan(plans, key, blocks, make):
    """The scan-global dictionary plan for `key`, made once per store
    contents: `plans` is the mapping the caller keeps with those
    contents (`docdb.operations.StoreFacts.plans`), so the same key
    means the same blocks in the same order.  A plan is kept by block
    position and handed out keyed by this read's block objects — an
    SST's block cache may have decoded a block anew since the plan was
    made.  `plans` None: made for this scan alone."""
    if plans is None:
        return make()
    if key not in plans:
        plan = make()
        plans[key] = (plan, plan and {
            cid: [by_block[id(b)] for b in blocks]
            for cid, by_block in plan.codes.items()})
        return plan
    plan, by_position = plans[key]
    if plan is None:
        return None     # these blocks do not dictionary-encode
    return dataclasses.replace(
        plan, merge_s=0.0,
        codes={cid: {id(b): c for b, c in zip(blocks, lanes)}
               for cid, lanes in by_position.items()})


def _plan_dict_columns(blocks, columns, where, aggs, group, plans=None):
    """Scan-global dictionary planning + string-predicate rewrite for a
    streamed scan.  Returns ``(plan, where, aggs, ok)``: plan is None
    when no column needs dictionary form; ok=False means the scan can't
    stream (no columnar/dictionary form, over-wide group domain, or a
    string column used outside a rewritable predicate shape).
    `plans`: see :func:`_kept_plan`."""
    dcids = dict_cols_needed(blocks, columns)
    if dcids is None:
        return None, where, aggs, False
    if isinstance(group, DictGroupSpec):
        for cid in group.cols:
            if not all(cid in b.varlen for b in blocks):
                return None, where, aggs, False
        dcids = sorted(set(dcids) | set(group.cols))
    if not dcids:
        return None, where, aggs, True
    plan = _kept_plan(plans, tuple(dcids), blocks,
                      lambda: make_dict_plan(blocks, dcids))
    if plan is None:
        return None, where, aggs, False
    if not group_domain_ok(group, plan.dicts):
        return None, where, aggs, False     # gid arithmetic would wrap
    from ..docdb.operations import DocReadOperation
    try:
        where, aggs = DocReadOperation.rewrite_where_and_aggs(
            where, aggs, plan.dicts)
    except DocReadOperation._Unrewritable:
        return None, where, aggs, False
    return plan, where, aggs, True


def _cannot_stream(blocks, group, read_ht, chunk_safe):
    """The refusals of a streamed scan that need no dictionary plan, so
    that none is made for a scan the monolithic batch will serve:
    ``(refused, chunk_safe)``.  `chunk_safe`: the caller's proof over
    `blocks` (kept with the store's contents), None = prove it here."""
    if isinstance(group, HashGroupSpec):
        return True, chunk_safe
    if isinstance(group, DictGroupSpec) \
            and not flags.get("grouped_pushdown_enabled"):
        return True, chunk_safe
    if chunk_safe is None:
        chunk_safe = chunk_safe_mvcc(blocks)
    return read_ht is not None and not chunk_safe, chunk_safe


def streaming_scan_aggregate(
        blocks: Sequence[ColumnarBlock], columns: Sequence[int],
        where: Optional[tuple], aggs: Sequence[AggSpec],
        group=None, read_ht: Optional[int] = None,
        kernel: Optional[ScanKernel] = None,
        chunk_rows: Optional[int] = None,
        cache=None, cache_key: Optional[tuple] = None,
        min_chunks: int = 3, prefilter=None,
        grouped_out: Optional[dict] = None,
        dict_out: Optional[dict] = None,
        chunk_safe: Optional[bool] = None,
        plans: Optional[dict] = None):
    """Chunked scan-aggregate over `blocks`.

    Returns ``(agg_values, counts)`` — the shapes of
    ``ScanKernel.run(...)[:2]`` — or None when the scan isn't
    streamable (caller uses the monolithic batch):
      - HashGroupSpec (per-chunk group sets can't combine densely),
      - a needed column with no columnar/dictionary form, or a string
        column used outside a rewritable predicate shape,
      - a DictGroupSpec while ``grouped_pushdown_enabled`` is off,
      - a read point over blocks that aren't provably chunk-safe,
      - fewer than `min_chunks` chunks (at 2 marginal chunks the
        per-chunk dispatch overhead measured SLOWER than monolithic on
        the 2-core box; the win needs real depth to amortize).

    String columns stream through the scan-global dictionary plan
    (ops/grouped_scan.make_dict_plan): one merged dictionary for the
    whole scan, per-chunk codes remapped into it at batch formation, so
    string predicates run as integer compares and a
    :class:`DictGroupSpec` GROUP BY aggregates densely into shared slot
    arrays that combine across chunks by plain addition/extremes.  For
    a dict-grouped scan the caller passes ``grouped_out`` (a dict) and
    receives ``{"spill": total spilled rows, "dicts": the scan-global
    dictionaries, "num_slots": slot bucket}`` — nonzero spill means the
    slot budget overflowed and the results MUST be discarded for the
    interpreted path.

    `cache`/`cache_key`: optional DeviceBlockCache — chunk batches land
    under ``cache_key + ("chunk", i)`` so a warm re-scan re-dispatches
    device-resident chunks with zero batch formation.  The scan-global
    dictionary identity is part of the chunk key: two scans whose
    merged dictionaries differ can never share a cached batch of
    remapped codes.

    `prefilter`: optional callable(chunk blocks) -> compacted blocks —
    the bypass reader's near-data pre-filter drops provably-unmatched
    rows before batch formation.  The batch still pads to the shared
    UNFILTERED bucket and takes its dtype policy + static-scale bounds
    from the unfiltered chunk (``bounds_blocks``), so results stay
    byte-identical to the unfiltered scan; mutually exclusive with the
    device cache (a one-shot snapshot scan has no warm re-scan to
    serve) and with the dictionary plan (compacted blocks have no
    remap entries).

    `chunk_safe`, `plans`: what the caller keeps with the store's
    contents (:func:`_cannot_stream`, :func:`_kept_plan`).
    """
    refused, chunk_safe = _cannot_stream(blocks, group, read_ht,
                                         chunk_safe)
    if refused:
        return None
    dict_group = isinstance(group, DictGroupSpec)
    plan, where, aggs, ok = _plan_dict_columns(blocks, columns, where,
                                               aggs, group, plans)
    if not ok or (dict_group and plan is None):
        return None
    if plan is not None:
        prefilter = None    # compacted blocks have no remap entries
        if dict_out is not None:
            # the scan-global dictionaries the returned partials were
            # coded in — callers decode dict-code MIN/MAX results
            # through them (docdb.operations.dict_minmax_decode)
            dict_out["dicts"] = plan.dicts
    # zone-map pruning: skip whole blocks whose v2 min/max maps prove
    # the WHERE can't match, BEFORE any batch formation. Safe exactly
    # when each doc key lives in one block (chunk_safe over the FULL
    # list — a pruned block can then never hide a newer version of a
    # surviving key); with no read point every row stands alone and
    # pruning is unconditionally safe.
    pruned = 0
    kept_idx = None
    if where is not None and flags.get("zone_map_pruning") \
            and (read_ht is None or chunk_safe):
        from .scan import zone_prune_blocks
        kept, kept_idx = zone_prune_blocks(blocks, where)
        pruned = len(blocks) - len(kept)
        if pruned:
            blocks = kept
    chunk_rows = chunk_rows or int(flags.get("streaming_chunk_rows"))
    chunks = plan_chunks(blocks, chunk_rows)
    if len(chunks) < min_chunks and not pruned:
        # min_chunks guards the unpruned case only (2 marginal chunks
        # measured slower than one monolithic batch); once zone maps
        # dropped blocks, streaming the small remainder beats falling
        # back to the monolithic path, which would rebuild it anyway
        return None
    kernel = kernel or _default_kernel()
    aggs = tuple(_expand_avg(aggs))
    cols_sorted = sorted(columns)
    # one shared pow2 bucket: every full chunk reuses one kernel-cache
    # signature (the last, short chunk pads up to the same bucket)
    bucket = bucket_rows(max(max(sum(b.n for b in c) for c in chunks), 1))

    # pruning changes which blocks land in which chunk, so the kept-set
    # INDICES are part of the device-cache identity — a batch cached
    # under one predicate's prune must never serve another predicate's
    prune_sig = ("zp", kept_idx) if pruned else ()
    # ... and the scan-global dictionary identity too: a batch of codes
    # remapped under one merged dictionary must never serve a scan that
    # merged a different one (same store key, different dict contents —
    # e.g. plans built over different block subsets)
    dict_sig = (("dict",) + plan.identity) if plan is not None else ()

    pf_stats = {"rows_in": 0, "rows_kept": 0}

    def build(item):
        ci, chunk = item
        if prefilter is not None:
            kept_blocks = prefilter(chunk)
            pf_stats["rows_in"] += sum(b.n for b in chunk)
            pf_stats["rows_kept"] += sum(b.n for b in kept_blocks)
            return build_batch(kept_blocks, cols_sorted, pad_to=bucket,
                               bounds_blocks=chunk)
        if cache is not None and cache_key is not None:
            # the chunk plan (target rows + bucket) is part of the key:
            # a runtime streaming_chunk_rows change re-plans the chunks,
            # and batches cached under the OLD plan must never serve the
            # new one (rows would double-count); stale entries LRU out
            return cache.get_or_build(
                cache_key + ("chunk", chunk_rows, bucket, ci)
                + prune_sig + dict_sig,
                lambda: build_batch(chunk, cols_sorted, pad_to=bucket,
                                    dict_plan=plan))
        return build_batch(chunk, cols_sorted, pad_to=bucket,
                           dict_plan=plan)

    pipe = StreamPipeline([build], depth=2, name="stream-scan")
    acc = None
    counts_acc = None
    spill_acc = 0
    kernel_s = 0.0
    combine_s = 0.0
    import time

    from ..storage.columnar import KEY_REBUILD_STATS
    rebuilds0 = KEY_REBUILD_STATS["rebuilds"]
    for batch in pipe.run(enumerate(chunks)):
        t0 = time.perf_counter()
        if dict_group:
            outs, counts, _, spill = kernel.run(batch, where, aggs,
                                                group, read_ht)
            spill_acc += int(spill)
        else:
            outs, counts, _ = kernel.run(batch, where, aggs, group,
                                         read_ht)
        kernel_s += time.perf_counter() - t0
        t0 = time.perf_counter()
        acc = _combine(aggs, acc, outs)
        counts_acc = (np.asarray(counts) if counts_acc is None
                      else counts_acc + np.asarray(counts))
        combine_s += time.perf_counter() - t0
    LAST_STREAM_STATS.clear()
    LAST_STREAM_STATS.update({
        "chunks": len(chunks), "bucket_rows": bucket,
        "zone_blocks_pruned": pruned,
        "zone_blocks_total": len(blocks) + pruned,
        # lazy key-matrix rebuilds paid DURING this scan — the keyless
        # v2 contract is that this stays 0 (tests assert it)
        "key_rebuilds": KEY_REBUILD_STATS["rebuilds"] - rebuilds0,
        "prefilter_rows_in": pf_stats["rows_in"],
        "prefilter_rows_kept": pf_stats["rows_kept"],
        "build_s": round(pipe.stage_s[0], 4),
        "kernel_s": round(kernel_s, 4),
        "combine_s": round(combine_s, 4),
        "consumer_wait_s": round(pipe.wait_s, 4)})
    if dict_group:
        resolved, _ = resolve_group(group, plan.dicts)
        occupied = int(np.count_nonzero(
            np.asarray(counts_acc)[:resolved.num_slots - 1])) \
            if counts_acc is not None else 0
        LAST_GROUPED_STATS.clear()
        LAST_GROUPED_STATS.update({
            "path": "streaming", "num_slots": resolved.num_slots,
            "slots_occupied": occupied, "spilled_rows": spill_acc,
            "dict_merge_s": round(plan.merge_s, 4),
            "kernel_s": round(kernel_s, 4),
            "combine_s": round(combine_s, 4)})
        if grouped_out is not None:
            # plan + post-prune block list ride along so the caller's
            # partial-spill merge can replay the device's group ids
            # host-side (the codes ARE the plan's remapped codes)
            grouped_out.update(spill=spill_acc, dicts=plan.dicts,
                               num_slots=resolved.num_slots,
                               plan=plan, blocks=list(blocks))
    elif plan is not None:
        LAST_STREAM_STATS["dict_merge_s"] = round(plan.merge_s, 4)
    return tuple(acc), counts_acc


def streaming_scan_filter(
        blocks: Sequence[ColumnarBlock], columns: Sequence[int],
        where: Optional[tuple], read_ht: Optional[int],
        materialize, limit: Optional[int] = None,
        kernel: Optional[ScanKernel] = None,
        chunk_rows: Optional[int] = None,
        cache=None, cache_key: Optional[tuple] = None,
        min_chunks: int = 2,
        chunk_safe: Optional[bool] = None,
        plans: Optional[dict] = None):
    """Streamed filter-pushdown ROW path (ROADMAP operator-frontier
    rung (a)): per-chunk WHERE masks compute on device while the next
    chunk's batch forms on the pipeline thread; matching rows
    materialize host-side per chunk through ``materialize(chunk_blocks,
    local_indices) -> rows`` (the caller owns projection/row shape).

    Returns the accumulated row list, or None when the scan can't
    stream (same eligibility as the aggregate path; with a read point
    the block sequence must be chunk-safe so the newest-visible-version
    choice never spans chunks).  String predicates stream through the
    scan-global dictionary plan exactly like the aggregate path.
    ``limit``: stop dispatching once this many rows matched — the
    pipeline closes early, which is the row-path win the monolithic
    batch can't have.  `chunk_safe`, `plans`: as
    :func:`streaming_scan_aggregate`."""
    refused, chunk_safe = _cannot_stream(blocks, None, read_ht,
                                         chunk_safe)
    if refused:
        return None
    plan, where, _, ok = _plan_dict_columns(blocks, columns, where,
                                            (), None, plans)
    if not ok:
        return None
    pruned = 0
    kept_idx = None
    if where is not None and flags.get("zone_map_pruning") \
            and (read_ht is None or chunk_safe):
        from .scan import zone_prune_blocks
        kept, kept_idx = zone_prune_blocks(blocks, where)
        pruned = len(blocks) - len(kept)
        if pruned:
            blocks = kept
    chunk_rows = chunk_rows or int(flags.get("streaming_chunk_rows"))
    chunks = plan_chunks(blocks, chunk_rows)
    if len(chunks) < min_chunks and not pruned:
        return None
    kernel = kernel or _default_kernel()
    cols_sorted = sorted(columns)
    bucket = bucket_rows(max(max(sum(b.n for b in c) for c in chunks), 1))
    prune_sig = ("zp", kept_idx) if pruned else ()
    dict_sig = (("dict",) + plan.identity) if plan is not None else ()

    def build(item):
        ci, chunk = item
        if cache is not None and cache_key is not None:
            return cache.get_or_build(
                cache_key + ("chunk", chunk_rows, bucket, ci)
                + prune_sig + dict_sig,
                lambda: build_batch(chunk, cols_sorted, pad_to=bucket,
                                    dict_plan=plan)), chunk
        return build_batch(chunk, cols_sorted, pad_to=bucket,
                           dict_plan=plan), chunk

    pipe = StreamPipeline([build], depth=2, name="stream-rows")
    rows: list = []
    kernel_s = 0.0
    import time
    from ..storage.columnar import KEY_REBUILD_STATS
    rebuilds0 = KEY_REBUILD_STATS["rebuilds"]
    chunks_run = 0
    run = pipe.run(enumerate(chunks))
    try:
        for batch, chunk in run:
            t0 = time.perf_counter()
            _, _, mask = kernel.run(batch, where, (), None, read_ht)
            kernel_s += time.perf_counter() - t0
            sel = np.nonzero(np.asarray(mask))[0]
            chunks_run += 1
            if limit is not None and len(rows) + len(sel) > limit:
                sel = sel[:limit - len(rows)]
            rows.extend(materialize(chunk, sel))
            if limit is not None and len(rows) >= limit:
                break
    finally:
        close = getattr(run, "close", None)
        if close is not None:
            close()     # early exit: tear the pipeline down cleanly
    LAST_STREAM_STATS.clear()
    LAST_STREAM_STATS.update({
        "chunks": len(chunks), "chunks_run": chunks_run,
        "bucket_rows": bucket, "rows_out": len(rows),
        "zone_blocks_pruned": pruned,
        "zone_blocks_total": len(blocks) + pruned,
        "key_rebuilds": KEY_REBUILD_STATS["rebuilds"] - rebuilds0,
        "prefilter_rows_in": 0, "prefilter_rows_kept": 0,
        "build_s": round(pipe.stage_s[0], 4),
        "kernel_s": round(kernel_s, 4),
        "consumer_wait_s": round(pipe.wait_s, 4)})
    return rows


def _default_kernel() -> ScanKernel:
    from .scan import _DEFAULT_KERNEL
    return _DEFAULT_KERNEL
