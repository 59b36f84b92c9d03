"""The TPU scan/filter/aggregate kernel — THE hot path.

Replaces the reference's row-at-a-time scan loop
(reference: src/yb/docdb/pgsql_operation.cc:2790-2877 ExecuteScalar,
EvalAggregate :3153, PopulateAggregate :3163) with whole-batch columnar
kernels:

- WHERE predicates compile via ops/expr.py and fuse with the masked
  aggregates into one XLA program (VPU elementwise + MXU matmul for
  grouped aggregation via one-hot matrices).
- MVCC visibility (hybrid-time <= read point, tombstones) is a vector
  mask, and stays one elementwise pass when a batch may contain several
  versions of a key: the batch then carries `next_ht`, the write time
  of each row's next newer version (ops/device_batch.py link_versions,
  computed once on the host when the batch is built), and a version is
  the newest visible one exactly when `ht <= read_ht < next_ht` — the
  job IntentAwareIterator+DocRowwiseIterator do with seeks (reference:
  src/yb/docdb/doc_rowwise_iterator.cc:687).  No scan sorts for MVCC;
  `lex_order` (ops/lexsort.py) is left to HashGroupSpec below and to
  the compaction merge.
- Kernels are cached by structural signature (expr shape, agg list,
  group spec, padded size, dtypes) — literals are runtime arguments, so
  re-running with different constants does NOT recompile (the
  schema-version-keyed kernel cache SURVEY.md §7 calls for).

Aggregate partials come back in combinable form (sum/count/min/max) so
the parallel layer can `lax.psum` them across a tablet mesh axis.

Accumulation contract (SQL SUM must not drift with the device it runs
on — reference semantics: exact PG numerics in EvalAggregate,
src/yb/docdb/pgsql_operation.cc:3153):
- SUM/COUNT accumulate EXACTLY in int64. Integer (and integer-valued)
  columns sum exactly end-to-end. Float values are deterministically
  quantized to int64 fixed point — scale s = 2^k chosen so
  n_rows * bound * s < 2^62 cannot overflow — then summed exactly and
  rescaled on the host in f64. The only error is per-row: the f32
  device representation of the value itself (<= 2^-24 relative; f64 on
  CPU backends; a float64 lane on the TPU is the float32 pair the chip
  computes with, `device_batch.Pair`, <= 2^-48) plus quantization
  <= 0.5 granule/row. For a FIXED
  device dtype and quantization scale the result is order-independent —
  accumulation order (MXU vs VPU vs psum tree) can never change it;
  error bounds do not grow with row count. Results may still differ at
  the per-row-representation level between backends with different
  device dtypes (f64 CPU vs f32 TPU) or between partitionings that
  derive different scales.
- The scale is STATIC when host-side column stats can bound the
  aggregate expression (ops/expr.expr_bound over DeviceBatch.col_bounds
  — the common case): it arrives as a runtime scalar, so quantization
  fuses into the predicate pass with no device max-reduction and no
  second lane (this is what recovered the r03 Q1/Q6 regression). SUMs
  over unboundable expressions or degenerate magnitudes fall back to
  the DYNAMIC per-batch scale (in-kernel max-reduce) with a float
  fallback lane for Inf/NaN propagation.
- Grouped-SUM absolute error is <= 0.5 * n_g granules at the
  batch-global granule (set by the batch-wide bound). A group whose own
  values are many decades smaller than the batch bound sees that
  ABSOLUTE error floor — negligible in batch terms, but potentially
  visible relative to that group's own small sum. The dynamic path's
  fallback lane picks the independently-summed float lane for such
  small-|q| groups; the static path accepts the documented absolute
  bound in exchange for single-pass speed.
- MIN/MAX carry the value dtype (no accumulation error by nature).
- Distributed: static scales derive from GLOBAL column bounds, so int64
  partials psum exactly over ICI with no pre-collective; dynamic scales
  pmax-combine max|v| across shards first.
"""
from __future__ import annotations

import asyncio
import math
import threading
from dataclasses import dataclass, field
from typing import Dict, List, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .device_batch import (WORD_MAX, DeviceBatch, Pair, join, lane_sig,
                           launch_leaves, words)
from .expr import collect_constants, compile_expr, expr_signature
from .grouped_scan import (DictGroupSpec, ResolvedDictGroup,
                           grouped_reduce, resolve_group)
from .lexsort import lex_order
from ..utils import trace as _trace


@dataclass(frozen=True)
class AggSpec:
    """One aggregate target: op in sum|count|min|max|avg; expr None means
    COUNT(*)."""
    op: str
    expr: Optional[tuple] = None

    def signature(self) -> tuple:
        return (self.op, expr_signature(self.expr) if self.expr else None)


@dataclass(frozen=True)
class GroupSpec:
    """GROUP BY over small-domain columns (dictionary/categorical encoded):
    cols = ((col_id, domain_size, offset), ...). Group id =
    sum((col - offset) * stride); total groups = prod(domains).
    Large/unbounded domains use HashGroupSpec instead."""
    cols: Tuple[Tuple[int, int, int], ...]

    @property
    def num_groups(self) -> int:
        g = 1
        for _, d, _ in self.cols:
            g *= d
        return g


@dataclass(frozen=True)
class HashGroupSpec:
    """GROUP BY over ARBITRARY-domain fixed-width columns: device sort
    by the group-key tuple + segment aggregation. Needs no pre-declared
    domains or ANALYZE stats (reference: unconditional aggregate
    pushdown, pgsql_operation.cc:3153-3163). `max_groups` caps the
    per-batch distinct-group count — the kernel reports the true count
    and the caller falls back to CPU grouping when it overflows.
    NULL group values are excluded, matching GroupSpec's device path."""
    cols: Tuple[int, ...]
    max_groups: int = 4096


# sums over <= this many groups MAY unroll into per-group masked tree
# reductions (pure VPU code); larger group counts always use segment_sum
_UNROLL_G = 16

# scale sentinel meaning "integer-exact result, do not rescale"
_NOSCALE = jnp.float32(0.0)

# a grouped scan of a longer lane runs its body over row tiles of this
# length and adds the partials.  Chosen on the chip (PERF.md §5, PR 37:
# a 16,777,216-row shard, Q1 by a 16-slot dictionary group, ms a
# statement on the host): whole 20.6, tiles of 1,048,576 18.0,
# 2,097,152 17.3, 4,194,304 16.9, 8,388,608 18.2 — a tile's mask and
# per-group intermediates stay in the chip's vector memory between the
# body's fusions, and every loop step pays the fusions' fixed costs.
_TILE_ROWS = 4_194_304


def _group_strategy() -> str:
    """Reduction strategy for small-G grouped aggregates. CPU XLA does
    not fuse G unrolled masked reductions into one pass (measured ~7x
    slower on TPC-H Q1), so CPU uses scatter-add segment_sum; TPU keeps
    the unrolled VPU reductions (scatter is the slow op there)."""
    from ..utils import flags as _flags
    s = _flags.get("scan_group_strategy")
    if s == "auto":
        return "segment" if jax.default_backend() == "cpu" else "unroll"
    return s


def _scale_for(bound: float, n_total: int):
    """Static fixed-point scale 2^k for a float SUM whose per-row values
    are bounded by `bound` (host-side interval arithmetic over column
    stats): k = floor(61 - log2 n - log2 bound) makes n_total rows of
    |v|<=bound sum to < 2^61 in int64 with no possible overflow (one
    spare bit vs 2^62 absorbs f32 rounding of v itself). Returns an f32
    scale (powers of two are exact in f32; the kernel casts to the value
    dtype), or None when the magnitude regime can't quantize — the
    caller then uses the dynamic in-kernel scale with its degenerate
    fallbacks."""
    if not np.isfinite(bound):
        return None
    if bound <= 0.0:
        return np.float32(1.0)      # all values are exactly 0
    k = np.floor(61.0 - np.log2(max(n_total, 1)) - np.log2(bound))
    if k < -120.0 or k > 120.0:     # out of f32-exp / int64 range
        return None
    return np.float32(2.0 ** k)


def _sum_prep_static(v, m, scale):
    """Static-scale twin of _sum_prep: the scale is a host-derived
    runtime scalar, so quantization fuses into the predicate pass —
    no device max-reduction, no float fallback lane. Returns (q int64,
    scale) with q zero outside the mask."""
    if jnp.issubdtype(v.dtype, jnp.integer) or v.dtype == jnp.bool_:
        return jnp.where(m, v.astype(jnp.int64), 0), _NOSCALE
    vm = jnp.where(m, v, 0)
    q = jnp.rint(vm * scale.astype(vm.dtype)).astype(jnp.int64)
    return q, scale


def _sum_prep(v, m, n_total: int, axis_names: Tuple[str, ...] = ()):
    """Per-row SUM input -> (q int64 [0 outside mask], scale, fsum).

    Integer/bool values pass through exactly (scale sentinel 0.0,
    fsum unused). Float values quantize to int64 fixed point with a
    per-batch dynamic scale s = 2^k, k = floor(62 - log2(n_total) -
    log2(max|v|)), which makes every downstream int64 accumulation
    exact and overflow-free (sum <= n_total * max|v| * s <= 2^62). In
    the distributed kernel `axis_names` pmax-combines max|v| so all
    shards agree on s and the int64 partials can psum.

    Degenerate inputs — non-finite values, or magnitudes where the
    exponent would leave the dtype's exp2 range (possible for f64
    columns past ~1e51 and for sub-1e-30 maxima) — can't quantize:
    there the returned scale is NaN, q is zeroed, and the THIRD return
    (the masked per-row values) lets the caller produce a plain float
    fallback sum with the same grouping, which propagates Inf/NaN the
    way PG's float8 SUM does (accumulation drift only in this
    degenerate regime)."""
    if jnp.issubdtype(v.dtype, jnp.integer) or v.dtype == jnp.bool_:
        return jnp.where(m, v.astype(jnp.int64), 0), _NOSCALE, None
    vm = jnp.where(m, v, 0)
    vmax = jnp.max(jnp.abs(vm))
    for ax in axis_names:
        vmax = jax.lax.pmax(vmax, ax)
    safe = jnp.maximum(vmax, jnp.asarray(1e-30, vm.dtype))
    k = jnp.floor(62.0 - float(np.log2(max(n_total, 1))) - jnp.log2(safe))
    # clip to the dtype's exp2 range; a BINDING clip (or Inf/NaN input)
    # means quantization can't represent the data -> fall back to fsum
    lo, hi = (-120.0, 120.0) if vm.dtype == jnp.float32 \
        else (-1000.0, 1000.0)
    kc = jnp.clip(k, lo, hi)
    ok = jnp.isfinite(vmax) & (k == kc)
    s = jnp.exp2(kc).astype(vm.dtype)
    q = jnp.where(ok, jnp.rint(vm * s).astype(jnp.int64), 0)
    s = jnp.where(ok, s, jnp.asarray(np.nan, s.dtype))
    return q, s, vm


def _grouped_sum(q, gid, G: int, strategy: str = "unroll"):
    """Per-group sums in q's dtype (exact for the int64 fixed-point
    lane; also builds the float fallback lane); q must already be 0
    outside the row mask (so invalid rows are additive no-ops whatever
    their gid)."""
    if strategy == "unroll" and G <= _UNROLL_G:
        return jnp.stack([jnp.sum(jnp.where(gid == g, q, 0))
                          for g in range(G)])
    return jax.ops.segment_sum(q, gid, G)


def _grouped_extreme(v, m, gid, G: int, is_min: bool,
                     strategy: str = "unroll"):
    sentinel = _type_max(v) if is_min else _type_min(v)
    masked = jnp.where(m, v, sentinel)
    if strategy == "unroll" and G <= _UNROLL_G:
        red = jnp.min if is_min else jnp.max
        return jnp.stack([red(jnp.where(gid == g, masked, sentinel))
                          for g in range(G)])
    seg = jax.ops.segment_min if is_min else jax.ops.segment_max
    return seg(masked, gid, G)


def mvcc_lanes(batch, read_ht):
    """(mvcc_mode, (ht, next_ht, tombstone)) a batch is served with at
    `read_ht`, decided by what the batch carries: no read point or no
    MVCC lanes -> 'none'; a `next_ht` lane (the batch may hold several
    versions of a key) -> 'linked'; else 'visible'.  A lane the mode
    does not read is None, so no launch builds a placeholder for it."""
    if read_ht is None or batch.ht is None:
        return "none", (None, None, None)
    if batch.next_ht is None:
        return "visible", (batch.ht, None, batch.tombstone)
    return "linked", (batch.ht, batch.next_ht, batch.tombstone)


def visibility_mask(mvcc_mode: str, valid, ht, next_ht, tombstone,
                    read_ht):
    """The MVCC row mask — THE one implementation shared by the scan
    kernel, the fused plan kernel (ops/plan_fusion.py) and the
    distributed kernel; elementwise and in block order in every mode.
    mvcc_mode: 'none' (valid only), 'visible' (ht filter, keys proved
    single-version), 'linked' (newest visible version of each key: the
    row is visible and its next newer version, `next_ht`, is not — the
    all-ones sentinel says it has none, and keeps `read_ht` = MAX,
    "latest", selecting the newest).
    Times compare as their 32-bit words, high word first (`words`): a
    batch holds `ht` and `next_ht` as `Pair`s, and `read_ht` arrives as
    its two words (`unpack_scalars`) or, in the fused plan kernel, as one
    uint64 host value split as a scalar — no 64-bit lane in the
    program."""
    if mvcc_mode == "none":
        return valid
    read = words(read_ht)

    def le(a):          # a <= read_ht
        return (a.hi < read.hi) | ((a.hi == read.hi) & (a.lo <= read.lo))
    ht = words(ht)
    mask = valid & le(ht) & jnp.logical_not(tombstone)
    if mvcc_mode == "visible":
        return mask
    nxt = words(next_ht)
    newest = (nxt.hi == WORD_MAX) & (nxt.lo == WORD_MAX)    # HT_NONE
    return mask & (newest | jnp.logical_not(le(nxt)))


def masked_aggregate(group, agg_fns, prep, cols, nulls, consts, mask,
                     domains, sum_scales, n_total: int,
                     strategy: str):
    """Aggregate the masked rows — the traceable group/agg tail shared
    by the scan kernel and the fused plan kernel, so the two programs
    cannot drift.  Handles ResolvedDictGroup (dict-code strides into a
    pow2 slot bucket, via grouped_reduce), dense GroupSpec, and the
    ungrouped scalar path; HashGroupSpec stays a scan-kernel-only shape
    (its sort machinery has no fused-plan use).  Return shapes match
    the historical _build_kernel contract."""
    import jax.numpy as jnp
    if isinstance(group, ResolvedDictGroup):
        # dict-key grouped aggregation (ops/grouped_scan.py): dense
        # stride encoding of scan-global dictionary codes, pow2 slot
        # bucket, spill-slot overflow detection
        with jax.named_scope("dict_group_reduce"):
            return grouped_reduce(group, agg_fns, prep, cols, nulls,
                                  consts, mask, domains, sum_scales,
                                  strategy)
    if group is None:
        out, scales = [], []
        for i, (op, f) in enumerate(agg_fns):
            if f is None:
                out.append(jnp.sum(mask, dtype=jnp.int64))
                scales.append(_NOSCALE)
                continue
            v, vn = f(cols, nulls, consts)
            m = mask if vn is None else mask & jnp.logical_not(vn)
            if op == "count":
                out.append(jnp.sum(m, dtype=jnp.int64))
                scales.append(_NOSCALE)
            elif op == "sum":
                q, s, vm = prep(i, v, m, n_total, sum_scales)
                out.append(jnp.sum(q))
                scales.append(s if vm is None else (s, jnp.sum(vm)))
            elif op == "min":
                out.append(jnp.min(jnp.where(m, v, _type_max(v))))
                scales.append(_NOSCALE)
            elif op == "max":
                out.append(jnp.max(jnp.where(m, v, _type_min(v))))
                scales.append(_NOSCALE)
            else:
                raise ValueError(op)
        return (tuple(out), tuple(scales),
                jnp.sum(mask, dtype=jnp.int64), mask)

    # grouped over declared domains: dense group id + exact int64
    # per-group reductions (small G unrolls into VPU tree sums;
    # larger G uses segment_sum — still exact int64).
    # Rows with NULL in any group column are excluded (the device
    # group-id encoding has no NULL slot; PG's NULL group stays on
    # the CPU fallback path).
    gid = None
    stride = 1
    for cid, domain, offset in group.cols:
        gn = nulls.get(cid)
        if gn is not None:
            mask = mask & jnp.logical_not(gn)
        c = cols[cid].astype(jnp.int32) - offset
        c = jnp.clip(c, 0, domain - 1)
        gid = c * stride if gid is None else gid + c * stride
        stride *= domain
    G = group.num_groups
    out, scales = [], []
    for i, (op, f) in enumerate(agg_fns):
        if f is None:
            out.append(_grouped_sum(mask.astype(jnp.int64), gid, G,
                                    strategy))
            scales.append(_NOSCALE)
            continue
        v, vn = f(cols, nulls, consts)
        m = mask if vn is None else mask & jnp.logical_not(vn)
        if op == "count":
            out.append(_grouped_sum(m.astype(jnp.int64), gid, G,
                                    strategy))
            scales.append(_NOSCALE)
        elif op == "sum":
            q, s, vm = prep(i, v, m, n_total, sum_scales)
            out.append(_grouped_sum(q, gid, G, strategy))
            scales.append(
                s if vm is None
                else (s, _grouped_sum(vm, gid, G, strategy)))
        elif op == "min":
            out.append(_grouped_extreme(v, m, gid, G, True, strategy))
            scales.append(_NOSCALE)
        elif op == "max":
            out.append(_grouped_extreme(v, m, gid, G, False, strategy))
            scales.append(_NOSCALE)
        else:
            raise ValueError(op)
    group_counts = _grouped_sum(mask.astype(jnp.int64), gid, G,
                                strategy)
    return tuple(out), tuple(scales), group_counts, mask


def tile_count(n: int, group, aggs, static_sums, tile_rows=None) -> int:
    """How many row tiles the kernel runs a lane of `n` rows in — what
    `_build_kernel` traces and what `launch` tags, from what both see.
    More than one only for a grouped scan whose tile partials add
    exactly: a GroupSpec or ResolvedDictGroup (an ungrouped body reads
    each lane once in a few fusions and has nothing to keep near: the
    loop only costs it; HashGroupSpec sorts the whole lane) with every
    SUM static-scale (int64 fixed point at a host-derived scale, or an
    integer lane; a dynamic-scale SUM takes a max over all rows
    first).  Buckets are powers of two, so a longer lane is a
    multiple of the tile."""
    tile_rows = tile_rows or _TILE_ROWS
    if n <= tile_rows or n % tile_rows or group is None \
            or isinstance(group, HashGroupSpec):
        return 1
    if not all(s for a, s in zip(aggs, static_sums)
               if a.op == "sum" and a.expr is not None):
        return 1
    return n // tile_rows


def _build_kernel(where_node, agg_specs: Tuple[AggSpec, ...],
                  group: Optional[GroupSpec], mvcc_mode: str,
                  axis_names: Tuple[str, ...] = (),
                  row_multiplier: int = 1,
                  static_sums: Tuple[bool, ...] = (),
                  strategy: str = "unroll",
                  tile_rows: Optional[int] = None):
    """mvcc_mode: 'none' | 'visible' | 'linked' (see visibility_mask);
    the kernel takes the lanes `mvcc_lanes` hands out for that mode.

    Returns a traceable fn whose result is
      (agg_outs, agg_scales, counts, mask[, gvals, n_groups])
    where each float SUM out is an exact int64 accumulation to be divided
    by its scale host-side (scale 0.0 = integer-exact, keep as int64).
    `axis_names`/`row_multiplier` let the distributed kernel agree on
    quantization scales across `row_multiplier` mesh shards.

    `static_sums[i]` marks SUM aggregates whose fixed-point scale is
    host-derived from column stats (expr_bound) and arrives as the
    runtime arg `sum_scales[i]` — the fast path: quantization fuses
    into the predicate pass with no device max-reduce and no float
    fallback lane. Non-static SUMs keep the dynamic in-kernel scale
    with its degenerate-magnitude fallbacks.

    A lane of more than `tile_rows` rows (default `_TILE_ROWS`) runs in
    `tile_count` row tiles inside one device loop, the tiles' partials
    added; one tile is the program without a loop.

    Once `fn` is traced, `fn.scaled_by_host` holds the indices of the
    SUMs whose scale is the host's `sum_scales[i]` (the others' non-tuple
    scale is `_NOSCALE`)."""
    # the kernel's consts list concatenates WHERE constants first, then
    # each aggregate expression's, in AggSpec order — every compile
    # lands at its cumulative offset so the slots can never collide
    # (they DID collide before the fused-plan work: an aggregate
    # expression's literal read the WHERE's first constant whenever
    # both carried any)
    from .expr import const_count
    off = const_count(where_node) if where_node is not None else 0
    where_fn = compile_expr(where_node) if where_node is not None else None
    agg_fns = []
    for a in agg_specs:
        if a.expr is None:
            agg_fns.append((a.op, None))
        else:
            agg_fns.append((a.op, compile_expr(a.expr, offset=off)))
            off += const_count(a.expr)
    static_sums = static_sums or (False,) * len(agg_fns)
    # the SUMs whose int64 result is divided by the scale the host gave
    # (a static scale over a float value; over an integer lane the scale
    # is `_NOSCALE`): noted as the program is traced, so that a launch
    # need not read back a scale the host already holds
    scaled_by_host = set()

    def _prep(i, v, m, n_total, sum_scales):
        if static_sums[i]:
            q, s = _sum_prep_static(v, m, sum_scales[i])
            if s is not _NOSCALE:
                scaled_by_host.add(i)
            return q, s, None
        return _sum_prep(v, m, n_total, axis_names)

    def body(cols, nulls, consts, valid, ht, next_ht, tombstone, read_ht,
             sum_scales, group_domains, n_total):
        """The scan of one run of rows — a whole lane or one tile of
        it; `n_total` is what the SUM scales were sized for.  A column
        that arrives as a `Pair` is joined here, on the run's rows."""
        cols = {cid: join(v) for cid, v in cols.items()}
        mask = visibility_mask(mvcc_mode, valid, ht, next_ht, tombstone,
                               read_ht)
        if where_fn is not None:
            wv, wn = where_fn(cols, nulls, consts)
            mask = mask & wv
            if wn is not None:
                mask = mask & jnp.logical_not(wn)

        if isinstance(group, HashGroupSpec):
            # exclude NULL group values (same rule as the dict path)
            for cid in group.cols:
                gn = nulls.get(cid)
                if gn is not None:
                    mask = mask & jnp.logical_not(gn)
            G = group.max_groups
            inv = jnp.logical_not(mask).astype(jnp.uint8)
            gcols = [cols[cid] for cid in group.cols]
            perm = lex_order((inv, *gcols))
            g_s = [g[perm] for g in gcols]
            valid_s = inv[perm] == 0
            changed = g_s[0][1:] != g_s[0][:-1]
            for g in g_s[1:]:
                changed = changed | (g[1:] != g[:-1])
            first = valid_s & jnp.concatenate(
                [jnp.array([True]), changed])
            n_groups = jnp.sum(first, dtype=jnp.int32)
            seg = jnp.clip(jnp.cumsum(first) - 1, 0, G - 1)
            out, scales = [], []
            for i, (op, f) in enumerate(agg_fns):
                if f is None:
                    out.append(jax.ops.segment_sum(
                        valid_s.astype(jnp.int64), seg, G))
                    scales.append(_NOSCALE)
                    continue
                v, vn = f(cols, nulls, consts)
                v_s = v[perm]
                m = valid_s if vn is None else valid_s & \
                    jnp.logical_not(vn)[perm]
                if op == "count":
                    out.append(jax.ops.segment_sum(
                        m.astype(jnp.int64), seg, G))
                    scales.append(_NOSCALE)
                elif op == "sum":
                    q, s, vm = _prep(i, v_s, m, n_total, sum_scales)
                    out.append(jax.ops.segment_sum(q, seg, G))
                    scales.append(
                        s if vm is None
                        else (s, jax.ops.segment_sum(vm, seg, G)))
                elif op == "min":
                    out.append(jax.ops.segment_min(
                        jnp.where(m, v_s, _type_max(v)), seg, G))
                    scales.append(_NOSCALE)
                elif op == "max":
                    out.append(jax.ops.segment_max(
                        jnp.where(m, v_s, _type_min(v)), seg, G))
                    scales.append(_NOSCALE)
                else:
                    raise ValueError(op)
            counts = jax.ops.segment_sum(valid_s.astype(jnp.int64),
                                         seg, G)
            # group-key values: within a segment every group col is
            # constant; min over the segment (invalid rows masked to
            # +inf/max) recovers it
            gvals = tuple(
                jax.ops.segment_min(
                    jnp.where(valid_s, g, _type_max(g)), seg, G)
                for g in g_s)
            return (tuple(out), tuple(scales), counts, mask, gvals,
                    n_groups)

        return masked_aggregate(group, agg_fns, _prep, cols, nulls,
                                consts, mask, group_domains, sum_scales,
                                n_total, strategy)

    # how a tile's partial joins the ones before it: sums and counts add
    # (int64, exactly), an extreme is order-free
    joins = [jnp.minimum if op == "min" and f is not None
             else jnp.maximum if op == "max" and f is not None
             else jnp.add for op, f in agg_fns]

    def fn(cols, nulls, consts, valid, ht, next_ht, tombstone, read_ht,
           sum_scales, group_domains=()):
        n = valid.shape[0]
        n_total = n * row_multiplier
        tiles = tile_count(n, group, agg_specs, static_sums, tile_rows)
        lanes = (cols, nulls, valid, ht, next_ht, tombstone)

        def run(lanes):
            c, nl, *rest = lanes
            return body(c, nl, consts, *rest, read_ht, sum_scales,
                        group_domains, n_total)
        if tiles == 1:
            return run(lanes)

        # one device loop over the lane's tiles (`dynamic_slice` of the
        # lanes as they are: a [tiles, rows] view is a copy under the
        # chip's layouts; a lane the mode does not read is None, no
        # leaf).  The carry starts from each partial's identity — 0, or
        # the type's sentinel for an extreme: what `body` answers on no
        # rows — and holds the lane's row mask, which a caller that
        # does not read it (the mesh) leaves for the compiler to drop.
        rows = n // tiles
        tile = lambda i: jax.tree_util.tree_map(
            lambda a: jax.lax.dynamic_slice_in_dim(a, i * rows, rows), lanes)
        like = jax.eval_shape(lambda: run(tile(0)))
        zero = lambda x: jnp.zeros(x.shape, x.dtype)

        def identity(join, x):
            if join is jnp.add:
                return zero(x)
            sentinel = _type_max if join is jnp.minimum else _type_min
            return jnp.full(x.shape, sentinel(x), x.dtype)
        start = (tuple(map(identity, joins, like[0])),
                 jax.tree_util.tree_map(zero, like[1]), zero(like[2]),
                 tuple(map(zero, like[4:])), jnp.zeros(n, jnp.bool_))

        def step(i, carry):
            acc, _, acc_counts, acc_spilled, mask = carry
            outs, scales, counts, tile_mask, *spilled = run(tile(i))
            # a static scale is the same every tile: the last one stays
            return (tuple(j(a, o) for j, a, o in zip(joins, acc, outs)),
                    scales, acc_counts + counts,
                    tuple(a + x for a, x in zip(acc_spilled, spilled)),
                    jax.lax.dynamic_update_slice_in_dim(
                        mask, tile_mask, i * rows, 0))
        outs, scales, counts, spilled, mask = jax.lax.fori_loop(
            0, tiles, step, start)
        return (outs, scales, counts, mask, *spilled)

    fn.scaled_by_host = scaled_by_host
    return fn


def _rescale_outs(raw_outs, raw_scales):
    """Host-side: divide int64 fixed-point sums by their scale (f64).
    Scale entries are: the 0.0 sentinel (integer-exact result, stays
    int64); a bare nonzero scale (static host-derived fixed point:
    divide); or a (scale, float_fallback) pair from the dynamic path —
    NaN scale there means quantization was impossible (Inf/NaN or
    out-of-range magnitudes) and the plain float sum is the answer."""
    final = []
    for q, s in zip(raw_outs, raw_scales):
        if isinstance(s, tuple):
            sv = float(s[0])
            fb = np.asarray(s[1], np.float64)
            if np.isnan(sv):
                final.append(fb)
                continue
            qv = np.asarray(q)
            r = qv.astype(np.float64) / sv
            # Per-(group) lane choice by worst-case error bound: the
            # quantized lane's absolute error is <= 0.5*n_g granules,
            # the float lane's is <= n_g*eps*sum|v|. For |q| granules
            # of signal the quantized bound wins iff |q| >= 0.5/eps.
            # Below that — e.g. a small-magnitude group under a scale
            # set by a 15-decades-larger group elsewhere in the batch —
            # the independently-summed float lane is more accurate
            # (PG parity: each group's sum reflects its own values).
            eps = 2.0 ** -24 if np.asarray(s[1]).dtype == np.float32 \
                else 2.0 ** -53
            use_q = np.abs(qv) >= 0.5 / eps
            final.append(np.where(use_q, r, fb) if r.ndim
                         else (r if use_q else fb))
        else:
            sv = float(np.asarray(s))
            if sv == 0.0:
                final.append(np.asarray(q))       # integer-exact
            else:
                final.append(np.asarray(q).astype(np.float64) / sv)
    return tuple(final)


def _type_max(v):
    if jnp.issubdtype(v.dtype, jnp.integer):
        return jnp.iinfo(v.dtype).max
    return jnp.inf


def _type_min(v):
    if jnp.issubdtype(v.dtype, jnp.integer):
        return jnp.iinfo(v.dtype).min
    return -jnp.inf


# ---------------------------------------------------------------------------
# What crosses between host and device a launch: the runtime scalars as
# one host vector per element kind in, what the host reads as one array
# per element kind out.  Every host value a jitted call places, and every
# array a read-back brings and releases, costs the launch's thread time
# (PERF.md §5).
# ---------------------------------------------------------------------------

def _literal_slot(c) -> str:
    """Where a literal rides into the program: 'i' in the int64 vector or
    'f' in the float64 vector — a Python int or float, which the program
    makes weakly typed again, so that a compare runs in the lane's own
    type as it would with the scalar itself — or 'a' as an argument of
    its own, in the type it has: a `dictlut` table, a numpy scalar, a
    bool."""
    if type(c) is int and -2 ** 63 <= c < 2 ** 63:
        return "i"
    return "f" if type(c) is float else "a"


def _weak(x):
    """`x` weakly typed, as the Python scalar it was on the host: an
    operand of another type then converts it, not the other way (JAX has
    no public spelling of this conversion)."""
    from jax._src.lax.lax import _convert_element_type
    return _convert_element_type(x, x.dtype, weak_type=True)


def unpack_scalars(arrays, scalars, lits, group, static_sums):
    """Inside a program: the runtime scalars `prepare_launch` packed, as
    the program `_build_kernel` makes takes them — (consts, read_ht,
    sum_scales, domains).  `scalars` is (ints[, floats]): ints holds
    `read_ht`'s two 32-bit words, the dictionary sizes, then the integer
    literals; floats the static SUM scales, then the float literals;
    `arrays` the literals that ride alone; `lits` a slot a literal
    (`_literal_slot`)."""
    ints = scalars[0]
    floats = scalars[1] if len(scalars) > 1 else None
    read_ht = Pair(ints[0].astype(jnp.uint32), ints[1].astype(jnp.uint32))
    n_domains = len(group.cols) if isinstance(group, ResolvedDictGroup) \
        else 0
    domains = ints[2:2 + n_domains].astype(jnp.int32) if n_domains else ()
    at = {"i": 2 + n_domains, "f": 0}
    sum_scales = []
    for static in static_sums:
        sum_scales.append(floats[at["f"]].astype(jnp.float32)
                          if static else None)
        at["f"] += static
    consts, alone = [], iter(arrays)
    for slot in lits:
        if slot == "a":
            consts.append(next(alone))
            continue
        consts.append(_weak((ints if slot == "i" else floats)[at[slot]]))
        at[slot] += 1
    return consts, read_ht, sum_scales, domains


def _wide(dtype) -> np.dtype:
    """The element kind a result of `dtype` crosses as: an integer or a
    bool as int64, a float as float64 — each exact — and a kind no wider
    one holds exactly (uint64) as itself."""
    dtype = np.dtype(dtype)
    if dtype.kind == "f":
        return np.dtype(np.float64)
    if dtype.kind in "biu" and dtype != np.uint64:
        return np.dtype(np.int64)
    return dtype


class ResultLayout:
    """How a program's result crosses to the host: one vector per
    element kind (`_wide`), whose layout — each leaf's vector, offset,
    shape and dtype, and how the SUMs' scales are found — is recorded
    when the program is traced (`pack`) and read after each call
    (`unpack`, numpy views and slices of what the one `device_get`
    brought)."""

    def __init__(self):
        self.value = None

    def pack(self, fn, outs, scales, counts, rest):
        """Inside a program: what the host reads of the result of `fn`
        (a `_build_kernel` program) — its outs, counts and `rest` (a
        hash group's values and count, a dictionary group's spill
        count), and of its scales only a dynamic SUM's (scale, float
        fallback): a static scale is the host's, a `_NOSCALE` none."""
        kinds = tuple("dynamic" if isinstance(s, tuple)
                      else "host" if i in fn.scaled_by_host else "exact"
                      for i, s in enumerate(scales))
        dynamic = tuple(s for s in scales if isinstance(s, tuple))
        leaves, tree = jax.tree_util.tree_flatten(
            (tuple(outs), dynamic, counts, tuple(rest)))
        vectors: Dict[np.dtype, list] = {}
        entries = []
        for x in map(jnp.asarray, leaves):
            kind = _wide(x.dtype)
            parts = vectors.setdefault(kind, [])
            entries.append((list(vectors).index(kind),
                            sum(p.size for p in parts), x.shape, x.dtype))
            parts.append(x.reshape(-1).astype(kind))
        self.value = (tree, tuple(entries), kinds)
        return tuple(jnp.concatenate(p) for p in vectors.values())

    def unpack(self, vectors, host_scales):
        """On the host: (outs rescaled, counts, rest) of the read-back
        `vectors`, the static scales from `host_scales`."""
        tree, entries, kinds = self.value
        outs, dynamic, counts, rest = jax.tree_util.tree_unflatten(tree, [
            vectors[v][at:at + math.prod(shape)].reshape(shape)
            .astype(dtype, copy=False)
            for v, at, shape, dtype in entries])
        dynamic = iter(dynamic)
        scales = [next(dynamic) if k == "dynamic"
                  else host_scales[i] if k == "host" else 0.0
                  for i, k in enumerate(kinds)]
        return _rescale_outs(outs, scales), counts, rest


def scan_program(where, aggs, group, mvcc_mode, static_sums, strategy,
                 lits):
    """(program, layout): the one-device program a `prepare_launch` key
    names, traceable, and the `ResultLayout` its result is read by.  It
    takes `prepare_launch`'s argument list and returns (the packed
    result, the row mask) — the mask only where the launch has no
    aggregates (a filter: `scan_filter`, the streamed filter route),
    else None: no aggregate launch keeps a mask lane on the device."""
    raw = _build_kernel(where, aggs, group, mvcc_mode,
                        static_sums=static_sums, strategy=strategy)
    layout = ResultLayout()

    def program(cols, nulls, arrays, valid, ht, next_ht, tombstone,
                scalars):
        consts, read_ht, sum_scales, domains = unpack_scalars(
            arrays, scalars, lits, group, static_sums)
        outs, scales, counts, mask, *rest = raw(
            cols, nulls, consts, valid, ht, next_ht, tombstone, read_ht,
            sum_scales, domains)
        return (layout.pack(raw, outs, scales, counts, rest),
                None if aggs else mask)
    # a stable program name: a kept trace's "XLA Modules" line reads
    # jit_scan_linked..., not jit_program
    program.__name__ = program.__qualname__ = "_".join(
        ["scan", mvcc_mode] + ([type(group).__name__.lower()]
                               if group is not None else []))
    return program, layout


class ScanKernel:
    """Signature-keyed cache of jitted scan kernels."""

    def __init__(self):
        self._cache: Dict[tuple, object] = {}
        self.compiles = 0
        # `run` is called from the threads that serve reads' launches
        # beside the event loop (tablet/tablet.py serve_read) as well as
        # from the loop: one program a signature whoever asks first
        self._lock = threading.Lock()

    def _get(self, sig, where, aggs, group, mvcc_mode, static_sums,
             strategy, lits):
        """The jitted program `sig` names; its `ResultLayout` is kept
        beside it (`layout`)."""
        with self._lock:
            got = self._cache.get(sig)
            if got is None:
                program, layout = scan_program(
                    where, aggs, group, mvcc_mode, static_sums, strategy,
                    lits)
                got = self._cache[sig] = (jax.jit(program), layout)
                self.compiles += 1
            return got[0]

    def layout(self, sig) -> "ResultLayout":
        return self._cache[sig][1]

    def run(self, batch: DeviceBatch,
            where: Optional[tuple] = None,
            aggs: Sequence[AggSpec] = (),
            group: Optional[GroupSpec] = None,
            read_ht: Optional[int] = None):
        """Returns (agg_results tuple, count_or_group_counts, mask).
        HashGroupSpec adds (group_values, n_groups); DictGroupSpec adds
        a trailing spill count (nonzero = slot overflow, the caller
        must revert to the interpreted GROUP BY).  Everything but the
        mask is a host value (`launch`); the mask is a device array
        where the launch has no aggregates (a filter), else None.  It
        reads the batch, which nothing changes once it is built, and
        this kernel's own program cache under its lock — nothing of a
        store — so a served read calls it on a thread beside the event
        loop.  What it does before the dispatch is the `launch.prepare`
        span."""
        with _trace.TRACES.span("launch.prepare", child_only=True,
                                cpu=True):
            job = prepare_launch(batch, where, aggs, group, read_ht)
            # (two threads that both find no program both wait for the
            # one compile, and both say so)
            compiled = job.sig not in self._cache
            fn = self._get(job.sig, *job.key)
        return launch(fn, self.layout(job.sig), job, batch, compiled,
                      mask=True)


class Launch(NamedTuple):
    """What one launch is made of (`prepare_launch`).

    - key = (where, aggs, group, mvcc_mode, static_sums, strategy, lits),
      what a kernel's `_get` builds the program from: AVG expanded, a
      DictGroupSpec resolved against the batch's scan-global
      dictionaries (the pow2 slot bucket is static; KeyError = a group
      column with no dictionary, the caller falls back), and where each
      literal rides (`_literal_slot`);
    - sig, the structural signature that names the program in a cache;
    - args, the jitted call's argument list: (cols, nulls, the literals
      that ride alone, valid, ht, next_ht, tombstone, scalars);
    - scales, the static SUM scales the host rescales the result with
      (one float32 an aggregate, 0.0 where not static)."""
    sig: tuple
    key: tuple
    args: tuple
    scales: np.ndarray


def prepare_launch(batch, where, aggs, group, read_ht,
                   n_total=None) -> Launch:
    """What one launch is made of, for either kernel — a `DeviceBatch`
    here, a `ShardedBatch` in parallel/distributed_scan.py, whose SUMs
    run over `n_total` = rows x shards.  Every runtime scalar is packed
    into ONE host vector per element kind, placed by the one jitted call
    and by no program of its own (`unpack_scalars` takes them apart in
    the program): an int64 vector of `read_ht`'s two 32-bit words (all
    ones = latest), the dictionary sizes and the integer literals, and —
    where there is any — a float64 vector of the static SUM scales and
    the float literals.  Array constants (`dictlut` tables) ride as
    numpy arrays of their own.  Other literals, read points, bounds or
    dictionary sizes never recompile; a literal's kind (and the type of
    one that rides alone) is part of the signature."""
    aggs = tuple(_expand_avg(aggs))
    mvcc_mode, lanes = mvcc_lanes(batch, read_ht)
    consts: List = []
    if where is not None:
        collect_constants(where, consts)
    for a in aggs:
        if a.expr is not None:
            collect_constants(a.expr, consts)
    read_ht = 0xFFFFFFFFFFFFFFFF if read_ht is None else int(read_ht)
    ints = [read_ht >> 32, read_ht & 0xFFFFFFFF]
    if isinstance(group, DictGroupSpec):
        group, sizes = resolve_group(group, batch.dicts)
        ints.extend(sizes)
    col_sig = tuple(sorted(
        (cid, lane_sig(v)) for cid, v in batch.cols.items()))
    static_sums, scales = _static_scales(
        aggs, batch.col_bounds, n_total or batch.padded_rows, batch.cols)
    floats = [float(s) for s, static in zip(scales, static_sums) if static]
    lits = tuple(map(_literal_slot, consts))
    alone = []
    for c, slot in zip(consts, lits):
        (ints if slot == "i" else floats if slot == "f" else alone).append(c)
    strategy = _group_strategy()
    sig = (
        expr_signature(where) if where is not None else None,
        tuple(a.signature() for a in aggs),
        (type(group).__name__, group.cols,
         getattr(group, "max_groups", getattr(group, "num_slots", None)))
        if group else None,
        mvcc_mode, batch.padded_rows, col_sig, static_sums, strategy, lits,
        # a literal that rides alone is traced in its own type: so is the
        # program's result, whose layout the program's name must fix
        tuple((str(np.asarray(c).dtype), np.shape(c)) for c in alone),
    )
    scalars = (np.asarray(ints, np.int64),) + (
        (np.asarray(floats, np.float64),) if floats else ())
    args = (batch.cols, batch.nulls, alone, batch.valid, *lanes, scalars)
    return Launch(sig, (where, aggs, group, mvcc_mode, static_sums,
                        strategy, lits), args, scales)


def launch(fn, layout: ResultLayout, job: Launch, batch, compiled: bool,
           mask: bool, tags=()):
    """Dispatch `fn(*job.args)`, the program `job.key` names (the
    `device.scan` span: tag `host_args` = how many host values the call
    placed, `wide_lanes` = how many 64-bit arrays it was given — each
    one the chip splits over the whole lane first, 0 with a batch's
    `Pair`s — `tiles` = how many row tiles the program runs a lane in,
    1 = whole) and read its result back in ONE transfer (`device.wait`:
    tag `reads`; `result_leaves` = the arrays it brought, one an element
    kind), `tags` on both spans.
    `fn` returns (the packed result, the row mask or None): `layout`
    unpacks the result, the fixed-point sums are rescaled on the host
    (static scales from `job.scales`) and the caller gets (outs,
    counts[, mask], ...) — all host values but the row mask, which stays
    a device array (only the filter launches return one); `mask` says
    whether the caller's result has the mask's place.
    The read-back is the first host read of the result, so this is
    where the host waits for the device (`Device_BlockUntilReady` for
    ASH); a sampled span waits for every output first, so it holds the
    whole wait whatever the transfer covers."""
    _, aggs, group, mvcc_mode, static_sums, _, _ = job.key
    scan_tags = tags
    if _trace.sampled():
        # counted before the span opens: its wall and CPU time are the
        # dispatch's alone
        host_args, wide = launch_leaves(job.args)
        scan_tags = (("host_args", host_args), ("wide_lanes", wide),
                     ("tiles", tile_count(batch.padded_rows, group, aggs,
                                          static_sums)), *tags)
    with _trace.device_span("scan", signature=job.sig, compiled=compiled,
                            bucket=batch.padded_rows, rows=batch.n_rows,
                            mvcc=mvcc_mode, tags=scan_tags):
        packed, on_device = fn(*job.args)
    with _trace.wait_status("Device_BlockUntilReady",
                            component="device"), \
            _trace.TRACES.span("device.wait", child_only=True) as sp:
        if sp.sampled:
            sp.set_tag("thread", _thread_kind())
            sp.set_tag("reads", 1)
            sp.set_tag("result_leaves", len(packed))
            for k, v in tags:
                sp.set_tag(k, v)
            jax.block_until_ready(packed)
        outs, counts, rest = layout.unpack(jax.device_get(packed),
                                           job.scales)
        # the result's device buffers go here, inside the read-back: their
        # release is a cost of the launch (~0.75 ms on a CPU backend)
        del packed
    return (outs, counts, *((on_device,) if mask else ()), *rest)


def _thread_kind() -> str:
    """`loop` on a thread that runs an asyncio event loop (the wait
    blocks every task of that loop), else `executor`."""
    try:
        asyncio.get_running_loop()
        return "loop"
    except RuntimeError:
        return "executor"


def _static_scales(aggs: Sequence[AggSpec],
                   col_bounds: Dict[int, Tuple[float, float]],
                   n_total: int, cols=None):
    """Per-agg static fixed-point scales from host column stats.
    Returns (static_flags, scales) — scales is ONE float32 vector on the
    host, an entry an aggregate (0.0 for a non-static one): the static
    ones ride into the program as runtime values (`prepare_launch`), so
    changing data bounds never recompiles the kernel, and the host
    rescales the result with them.
    `cols` (col_id -> device array) supplies dtypes: expressions
    touching f32 columns cap every intermediate interval at the f32
    finite range, since an f32 product can overflow to Inf on device
    even when the final bound is small and the static path has no Inf
    fallback lane."""
    from .expr import expr_bound, referenced_columns
    flags_, scales = [], []
    for a in aggs:
        s = None
        if a.op == "sum" and a.expr is not None and col_bounds:
            # f32 cap applies whenever the device may EVALUATE the
            # expression in f32: any f32 column, or a non-CPU backend
            # (TPU has no f64, so even int-column exprs mixed with
            # float constants compute in f32 there)
            mag = 1.0e306
            if jax.default_backend() != "cpu" or (
                    cols is not None and any(
                        str(getattr(cols.get(c), "dtype", "")) == "float32"
                        for c in referenced_columns(a.expr))):
                mag = 3.0e38
            b = expr_bound(a.expr, col_bounds, mag_limit=mag)
            if b is not None:
                s = _scale_for(max(abs(b[0]), abs(b[1])), n_total)
        flags_.append(s is not None)
        scales.append(0.0 if s is None else s)
    return tuple(flags_), np.asarray(scales, np.float32)


def _expand_avg(aggs: Sequence[AggSpec]) -> List[AggSpec]:
    """AVG(e) -> SUM(e), COUNT(e); recombined by the caller/result layer."""
    out = []
    for a in aggs:
        if a.op == "avg":
            out.append(AggSpec("sum", a.expr))
            out.append(AggSpec("count", a.expr))
        else:
            out.append(a)
    return out


# ---------------------------------------------------------------------------
# Cross-shard partial combine — THE one implementation of "sum/count
# add, min/max take None-aware elementwise extremes" shared by the
# client's RPC fan-out (client/client.py _combine) and the bypass
# session's host combine, so the two paths cannot drift apart.
# ---------------------------------------------------------------------------

def _scalar_of(x):
    """Python scalar from a 0-d array / numpy scalar / plain value."""
    if isinstance(x, (np.ndarray, np.generic)):
        return x.item()
    return x


def _mm2(x, y, op):
    """None-aware scalar min/max (SQL: NULL is the identity)."""
    if x is None:
        return y
    if y is None:
        return x
    return min(x, y) if op == "min" else max(x, y)


def merge_minmax(a, b, op):
    """None-aware elementwise min/max over scalars or per-group arrays
    (SQL semantics: NULL is the identity, never the answer over a
    non-empty input set)."""
    av, bv = np.asarray(a), np.asarray(b)
    if av.ndim == 0:
        return np.asarray(_mm2(av.item(), bv.item(), op))
    if av.dtype != object and bv.dtype != object:
        return np.minimum(av, bv) if op == "min" else np.maximum(av, bv)
    out = np.empty(av.shape, object)
    for i in range(av.shape[0]):
        out[i] = _mm2(_scalar_of(av[i]), _scalar_of(bv[i]), op)
    return out


def agg_is_none(x) -> bool:
    """A whole-shard NULL aggregate (empty tablet min/max)."""
    return x is None or (isinstance(x, np.ndarray) and x.dtype == object
                         and x.shape == () and x.item() is None)


def combine_agg_partials(expanded_aggs: Sequence[AggSpec],
                         parts: Sequence[Sequence],
                         counts_parts: Sequence):
    """Combine per-shard (agg_values, group_counts) partials in shard
    order: sum/count add, min/max merge via :func:`merge_minmax` with
    None as the identity.  `expanded_aggs` must already be
    avg-expanded; returns (tuple of combined values, combined counts
    or None)."""
    total = None
    counts = None
    for vals, cnts in zip(parts, counts_parts):
        vals = [np.asarray(v) for v in vals]
        if total is None:
            total = vals
            counts = np.asarray(cnts) if cnts is not None else None
            continue
        for i, a in enumerate(expanded_aggs):
            if a.op in ("sum", "count"):
                total[i] = total[i] + vals[i]
            elif agg_is_none(vals[i]):
                pass
            elif agg_is_none(total[i]):
                total[i] = vals[i]
            else:
                total[i] = merge_minmax(total[i], vals[i], a.op)
        if counts is not None:
            counts = counts + np.asarray(cnts)
    return (tuple(total) if total is not None else ()), counts


def combine_grouped_partials(expanded_aggs: Sequence[AggSpec],
                             parts: Sequence[tuple]):
    """Group-KEYED partial merge — THE one implementation shared by the
    client's RPC hash/dict-grouped fan-out combine, the bypass
    session's host combine, and any path whose per-shard group slots
    don't align (each shard merges its own dictionary, so slot i means
    different keys on different shards).

    ``parts``: per-shard ``(agg_values, counts, group_values)`` with
    compacted present-group arrays (group_values = one array per group
    column, aligned with counts). Returns ``(agg_values, counts,
    group_values)`` merged by key in first-seen shard order: sum/count
    add, min/max merge via :func:`merge_minmax` with None as the
    identity."""
    merged: Dict[tuple, list] = {}
    for vals, cnts, gvals in parts:
        if cnts is None:
            continue
        counts = np.asarray(cnts)
        gv = [np.asarray(g) for g in (gvals or ())]
        vv = [np.asarray(v) for v in vals]
        for g in range(len(counts)):
            if counts[g] == 0:
                continue
            # object (string) arrays index to plain str — only numpy
            # scalars need .item() unwrapping into hashable python
            key = tuple(x[g].item() if isinstance(x[g], np.generic)
                        else x[g] for x in gv)
            st = merged.get(key)
            if st is None:
                merged[key] = [[v[g] for v in vv], int(counts[g])]
                continue
            for i, a in enumerate(expanded_aggs):
                if a.op in ("sum", "count"):
                    st[0][i] = st[0][i] + vv[i][g]
                else:
                    st[0][i] = _mm2(_scalar_of(st[0][i]),
                                    _scalar_of(vv[i][g]), a.op)
            st[1] += int(counts[g])
    keys = list(merged)
    outs = tuple(np.asarray([merged[k][0][i] for k in keys])
                 for i in range(len(expanded_aggs)))
    counts = np.asarray([merged[k][1] for k in keys], np.int64)
    gvals = tuple(np.asarray([k[j] for k in keys])
                  for j in range(len(keys[0]) if keys else 0))
    return outs, counts, gvals


def _keyed_partials(part):
    """Keyed dict view of one (agg_values, counts, group_values)
    partial: group key tuple -> [agg scalars, count]."""
    vals, cnts, gvals = part
    out: Dict[tuple, list] = {}
    if cnts is None:
        return out
    counts = np.asarray(cnts)
    gv = [np.asarray(g) for g in (gvals or ())]
    vv = [np.asarray(v) for v in vals]
    for g in range(len(counts)):
        if counts[g] == 0:
            continue
        key = tuple(x[g].item() if isinstance(x[g], np.generic)
                    else x[g] for x in gv)
        out[key] = [[_scalar_of(v[g]) for v in vv], int(counts[g])]
    return out


def retract_grouped_partials(expanded_aggs: Sequence[AggSpec],
                             base: tuple, delta: tuple):
    """Retraction-safe inverse of :func:`combine_grouped_partials` for
    the incremental-matview fold (matview/): subtract a keyed grouped
    delta (retracted rows, pre-aggregated per group) from a base
    partial set.

    SUM/COUNT retract exactly — the lanes are exact int64 per this
    module's contract, so subtraction is the true inverse of the
    combine's addition. MIN/MAX have no algebraic inverse: a retracted
    value that CHALLENGES the surviving extremum (<= it for min, >= it
    for max) is reported as a dirty slot instead of being guessed at;
    the caller re-establishes those slots with a bounded, counted
    per-group re-scan. Groups whose row count reaches zero are dropped
    (their min/max slots are never dirty: there is nothing left to
    re-establish).

    ``base``/``delta``: ``(agg_values, counts, group_values)`` keyed
    triples in combine_grouped_partials' compacted shape. Returns
    ``(triple, dirty)`` where ``dirty`` is ``[(group_key, agg_index)]``
    for min/max slots needing a re-scan (their surviving value is the
    unretracted one, kept verbatim until the caller repairs it).
    Raises ValueError when the delta retracts a group or count the
    base never contained — that is a maintainer consistency bug, not a
    recoverable state."""
    merged = _keyed_partials(base)
    dirty: List[tuple] = []
    for key, (dvals, dcnt) in _keyed_partials(delta).items():
        st = merged.get(key)
        if st is None:
            raise ValueError(
                f"retract of unknown group {key!r}")
        if dcnt > st[1]:
            raise ValueError(
                f"retract of {dcnt} rows from group {key!r} "
                f"holding {st[1]}")
        st[1] -= dcnt
        if st[1] == 0:
            del merged[key]
            continue
        for i, a in enumerate(expanded_aggs):
            if a.op in ("sum", "count"):
                st[0][i] = _scalar_of(st[0][i]) - _scalar_of(dvals[i])
                continue
            dv = _scalar_of(dvals[i])
            bv = _scalar_of(st[0][i])
            if dv is None:
                continue             # NULL contributions never held a slot
            if bv is None or (dv <= bv if a.op == "min" else dv >= bv):
                dirty.append((key, i))
    keys = list(merged)
    outs = tuple(np.asarray([merged[k][0][i] for k in keys])
                 for i in range(len(expanded_aggs)))
    counts = np.asarray([merged[k][1] for k in keys], np.int64)
    gvals = tuple(np.asarray([k[j] for k in keys])
                  for j in range(len(keys[0]) if keys else 0))
    return (outs, counts, gvals), dirty


# ---------------------------------------------------------------------------
# Zone-map block pruning (v2 SST blocks carry per-block min/max maps)
# ---------------------------------------------------------------------------

def _f32_widen(lo, hi):
    """Widen a float interval to cover f32 re-rounding: the device may
    evaluate the column (and predicate constants) in float32, where a
    value just below a boundary can round ONTO it — e.g. f64
    0.0499999999 becomes f32(0.05) and satisfies `>= 0.05`. One f32 ulp
    outward on each end covers every such crossing; on f64 backends the
    widening merely forfeits a sliver of pruning."""
    lo_w = float(np.nextafter(np.float32(lo), np.float32(-np.inf)))
    hi_w = float(np.nextafter(np.float32(hi), np.float32(np.inf)))
    return (min(lo, lo_w), max(hi, hi_w))


def _zone_interval(node, zmap):
    """Conservative (lo, hi) interval of an expression over a block
    described by its zone map, or None when unboundable. Integer lanes
    stay exact python ints (no float roundoff at int64 block
    boundaries); float lanes widen to the f32 envelope (_f32_widen)
    because the kernel may evaluate them in the device float dtype."""
    kind = node[0]
    if kind == "col":
        b = zmap.get(node[1])
        if b is not None and (isinstance(b[0], float)
                              or isinstance(b[1], float)):
            return _f32_widen(b[0], b[1])
        return b
    if kind == "const":
        v = node[1]
        if isinstance(v, bool) or not isinstance(v, (int, float)):
            return None
        if isinstance(v, float):
            # the kernel may round the constant itself to f32: an exact
            # zone bound equal to the f32-rounded constant must still
            # count as overlapping
            return _f32_widen(v, v)
        return (v, v)
    if kind == "arith":
        lb = _zone_interval(node[2], zmap)
        rb = _zone_interval(node[3], zmap)
        if lb is None or rb is None:
            return None
        op = node[1]
        if op == "add":
            out = (lb[0] + rb[0], lb[1] + rb[1])
        elif op == "sub":
            out = (lb[0] - rb[1], lb[1] - rb[0])
        elif op == "mul":
            prods = (lb[0] * rb[0], lb[0] * rb[1],
                     lb[1] * rb[0], lb[1] * rb[1])
            out = (min(prods), max(prods))
        else:
            return None
        if isinstance(out[0], float) or isinstance(out[1], float):
            out = _f32_widen(out[0], out[1])   # per-op device rounding
        return out
    return None


def zone_maybe_match(where, zmap) -> bool:
    """Conservative zone-map test: False ONLY when the block's value
    ranges PROVE no row can satisfy `where` — then the whole block can
    skip batch formation. True on anything unprovable (missing zone
    map entries, string predicates, NOT, unsupported shapes).

    NULL semantics line up with the kernel: zone maps cover non-null
    values only and a NULL comparison never matches, so a block pruned
    on its non-null range cannot hide a NULL row that would have
    matched."""
    if not zmap:
        return True
    kind = where[0]
    if kind == "and":
        return all(zone_maybe_match(c, zmap) for c in where[1:])
    if kind == "or":
        return any(zone_maybe_match(c, zmap) for c in where[1:])
    if kind == "between":
        return (zone_maybe_match(("cmp", "ge", where[1], where[2]), zmap)
                and zone_maybe_match(("cmp", "le", where[1], where[3]),
                                     zmap))
    if kind == "in":
        x, vals = where[1], where[2]
        b = _zone_interval(x, zmap)
        if b is None:
            return True
        return any(isinstance(v, (int, float)) and not isinstance(v, bool)
                   and b[0] <= v <= b[1] for v in vals) or not vals
    if kind == "cmp":
        op = where[1]
        lb = _zone_interval(where[2], zmap)
        rb = _zone_interval(where[3], zmap)
        if lb is None or rb is None:
            return True
        if op == "lt":
            return lb[0] < rb[1]
        if op == "le":
            return lb[0] <= rb[1]
        if op == "gt":
            return lb[1] > rb[0]
        if op == "ge":
            return lb[1] >= rb[0]
        if op == "eq":
            return lb[0] <= rb[1] and lb[1] >= rb[0]
        if op == "ne":
            return not (lb[0] == lb[1] == rb[0] == rb[1])
        return True
    return True


def zone_prune_blocks(blocks, where):
    """Split `blocks` into (kept_blocks, kept_indices) by their zone
    maps — indices are positions in the input list, the stable prune
    identity device-cache keys embed (two predicates pruning different
    sets must never share a cached batch). Never returns an empty kept
    list: aggregates/filters still need one (non-matching) block to
    keep result shapes and NULL semantics on the device path, so the
    cheapest block survives as the representative when everything
    proves unmatchable."""
    if where is None:
        return list(blocks), tuple(range(len(blocks)))
    kept_idx = [i for i, b in enumerate(blocks)
                if getattr(b, "zmap", None) is None
                or zone_maybe_match(where, b.zmap)]
    if not kept_idx and blocks:
        kept_idx = [min(range(len(blocks)), key=lambda i: blocks[i].n)]
    return [blocks[i] for i in kept_idx], tuple(kept_idx)


_DEFAULT_KERNEL = ScanKernel()


def scan_aggregate(batch: DeviceBatch, where=None, aggs=(), group=None,
                   read_ht=None):
    return _DEFAULT_KERNEL.run(batch, where, aggs, group, read_ht)


def scan_filter(batch: DeviceBatch, where=None, read_ht=None):
    """Filter-only scan: returns (mask ndarray, match_count)."""
    _, count, mask = _DEFAULT_KERNEL.run(batch, where, (), None, read_ht)
    return mask, count
