"""Device grouped aggregation over dictionary-encoded group keys.

TPC-H Q1's GROUP BY (l_returnflag, l_linestatus) is the shape: group
keys are low-cardinality STRINGS. The monolithic kernel could already
group over declared integer domains (GroupSpec) or sort arbitrary
numeric keys (HashGroupSpec), but string keys fell back to the
interpreted row-at-a-time path — and nothing grouped could stream. This
module closes the gap (ROADMAP operator-frontier rungs (b)+(d)):

- :class:`DictGroupSpec` — GROUP BY over dictionary-encoded (string)
  columns. On device the group id is a dense stride encoding of the
  columns' scan-global dictionary codes; strides are RUNTIME scalars
  derived from the dictionary sizes, so dictionary growth never changes
  the kernel signature while it stays inside one pow2 slot bucket.
- :func:`grouped_reduce` — the traceable segment-sum/min/max reduction
  the scan kernel (ops/scan.py) dispatches to for DictGroupSpec: one
  scatter-add pass into a pow2 group-slot bucket, one reserved SPILL
  slot catching rows whose group id exceeds the budget. A nonzero
  spill count reverts the whole scan to the interpreted GROUP BY — the
  bounded slot-overflow fallback, detected on device, decided on host.
- :func:`make_dict_plan` — the per-chunk dictionary merge: per-block
  dictionaries (ColumnarBlock.dict_varlen — stored v2 dict lanes or a
  one-time byte-level unique) union into ONE scan-global dictionary
  (lane_codec.merge_dicts) and each block's local codes translate
  through an int32 remap table. Row strings are never decoded; the
  same plan lets string equality/IN/LIKE predicates run on device as
  integer compares over global codes.
- :func:`grouped_aggregate_cpu` — the numpy CPU twin, replaying the
  kernel's exact accumulation contract (static int64 fixed-point SUM
  scales included) so parity tests can demand bitwise equality on f64
  backends.

Compile accounting matches ops/compaction.py: pow2 row chunks (the
streaming pipeline's shared bucket) x pow2 slot buckets mean one
compile serves a whole scan (`ScanKernel.compiles`; each launch is a
`device.scan` span).
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from ..storage import lane_codec
from ..storage.columnar import ColumnarBlock

#: accounting + stage split of the most recent grouped scan
#: (informational only)
LAST_GROUPED_STATS: dict = {}

#: process-wide grouped-kernel fallbacks: spill_fallbacks counts slot
#: overflows served by a full interpreted re-scan, spill_merges those
#: served by the partial-spill merge instead
GROUPED_STATS = {"spill_fallbacks": 0, "spill_merges": 0}

#: slot budgets are powers of two in this band — small enough that a
#: Q1-shaped 8-slot kernel stays pure VPU code, large enough for a
#: 4096-group cardinality sweep
_MIN_SLOTS = 4
_MAX_SLOTS_HARD = 1 << 20


@dataclass(frozen=True)
class DictGroupSpec:
    """GROUP BY over dictionary-encoded (string) columns.

    ``cols``: column ids; each must be servable as dictionary CODES on
    device (DeviceBatch.dicts carries the scan-global dictionaries).
    ``max_slots``: group-slot budget (rounded up to a power of two, one
    slot reserved for overflow spill). The device result is exact when
    the spill count is zero; otherwise the caller falls back to the
    interpreted GROUP BY."""
    cols: Tuple[int, ...]
    max_slots: int = 4096


@dataclass(frozen=True)
class ResolvedDictGroup:
    """Kernel-facing resolution of a DictGroupSpec: the pow2 slot count
    is static (part of the kernel signature); the per-column dictionary
    DOMAIN sizes arrive as runtime scalars so dictionary growth inside
    one slot bucket never recompiles."""
    cols: Tuple[int, ...]
    num_slots: int


def slot_bucket(needed: int, max_slots: int) -> int:
    """Smallest pow2 slot count >= needed (incl. the spill slot),
    clamped to [\\_MIN_SLOTS, pow2(max_slots)]."""
    cap = _MIN_SLOTS
    limit = min(max(int(max_slots), _MIN_SLOTS), _MAX_SLOTS_HARD)
    while cap < limit:
        cap <<= 1
    s = _MIN_SLOTS
    while s < needed and s < cap:
        s <<= 1
    return s


def resolve_group(spec: DictGroupSpec,
                  dicts: Dict[int, np.ndarray]
                  ) -> Tuple[ResolvedDictGroup, Tuple[int, ...]]:
    """(ResolvedDictGroup, domains) for a scan whose scan-global
    dictionaries are `dicts`. Raises KeyError when a group column has
    no dictionary (caller falls back)."""
    domains = tuple(max(len(dicts[c]), 1) for c in spec.cols)
    prod = 1
    for d in domains:
        prod *= d
    return (ResolvedDictGroup(spec.cols,
                              slot_bucket(prod + 1, spec.max_slots)),
            domains)


def domain_product(spec: DictGroupSpec,
                   dicts: Dict[int, np.ndarray]) -> int:
    prod = 1
    for c in spec.cols:
        prod *= max(len(dicts[c]), 1)
    return prod


# ---------------------------------------------------------------------------
# The traceable reduction (called from ops/scan.py _build_kernel)
# ---------------------------------------------------------------------------

def grouped_reduce(group: ResolvedDictGroup, agg_fns, prep,
                   cols, nulls, consts, mask, domains, sum_scales,
                   strategy: str):
    """Segment-sum/min/max over the dense dictionary-code group id.

    ``domains`` are traced int32 scalars (dictionary sizes); the group
    id is ``sum(code_i * stride_i)`` with strides derived from them at
    trace time as runtime arithmetic — NEVER Python control flow over a
    traced value (the jit_hazards contract: the traced group count must
    not leak into Python `if`/`while`). Rows whose id lands at or past
    the reserved spill slot scatter INTO it; the spill count comes back
    as an output for the host to act on.

    Returns (outs, scales, counts, mask, spilled) mirroring the
    GroupSpec path plus the spill count."""
    import jax.numpy as jnp

    from .scan import (_NOSCALE, _grouped_extreme, _grouped_sum,
                       _type_max, _type_min)
    for cid in group.cols:
        gn = nulls.get(cid)
        if gn is not None:
            # NULL group values are excluded (same rule as GroupSpec)
            mask = mask & jnp.logical_not(gn)
    gid = None
    stride = jnp.int64(1)
    for cid, dom in zip(group.cols, domains):
        c = cols[cid].astype(jnp.int64)
        gid = c * stride if gid is None else gid + c * stride
        stride = stride * dom.astype(jnp.int64)
    S = group.num_slots                     # static pow2 (signature)
    spill_slot = S - 1
    in_range = gid < spill_slot
    spilled = jnp.sum(mask & jnp.logical_not(in_range),
                      dtype=jnp.int64)
    gid_c = jnp.where(mask & in_range, gid,
                      spill_slot).astype(jnp.int32)
    n_total = mask.shape[0]
    out, scales = [], []
    for i, (op, f) in enumerate(agg_fns):
        if f is None:
            out.append(_grouped_sum(mask.astype(jnp.int64), gid_c, S,
                                    strategy))
            scales.append(_NOSCALE)
            continue
        v, vn = f(cols, nulls, consts)
        m = mask if vn is None else mask & jnp.logical_not(vn)
        if op == "count":
            out.append(_grouped_sum(m.astype(jnp.int64), gid_c, S,
                                    strategy))
            scales.append(_NOSCALE)
        elif op == "sum":
            q, s, vm = prep(i, v, m, n_total, sum_scales)
            out.append(_grouped_sum(q, gid_c, S, strategy))
            scales.append(s if vm is None
                          else (s, _grouped_sum(vm, gid_c, S, strategy)))
        elif op == "min":
            out.append(_grouped_extreme(v, m, gid_c, S, True, strategy))
            scales.append(_NOSCALE)
        elif op == "max":
            out.append(_grouped_extreme(v, m, gid_c, S, False, strategy))
            scales.append(_NOSCALE)
        else:
            raise ValueError(op)
    counts = _grouped_sum(mask.astype(jnp.int64), gid_c, S, strategy)
    return tuple(out), tuple(scales), counts, mask, spilled


# ---------------------------------------------------------------------------
# Scan-global dictionary plan (the per-chunk dictionary merge)
# ---------------------------------------------------------------------------

@dataclass
class DictPlan:
    """Scan-global dictionaries + per-block remapped codes for a fixed
    block list. ``identity`` is the content identity the device-cache
    key embeds — two scans that merged different dictionaries can never
    share a cached batch of codes (the remap would lie)."""
    dicts: Dict[int, np.ndarray]                 # cid -> sorted uniq (str)
    codes: Dict[int, Dict[int, np.ndarray]]      # cid -> {id(block): int32}
    identity: tuple = ()
    merge_s: float = 0.0

    def block_codes(self, cid: int, block) -> np.ndarray:
        return self.codes[cid][id(block)]


def make_dict_plan(blocks: Sequence[ColumnarBlock],
                   cids: Sequence[int],
                   max_card: int = 1 << 16) -> Optional[DictPlan]:
    """Merge per-block dictionaries for `cids` into scan-global ones
    and remap every block's local codes. None when any (block, column)
    can't dictionary-encode — the caller falls back to the legacy
    decode path / interpreter. Row strings are never decoded here.
    Emits a per-scan ``device.dict_plan`` telemetry span (the host
    stage that feeds the grouped kernel) when a sampled trace is
    ambient."""
    from ..utils import trace as _trace
    with _trace.device_span("dict_plan",
                            signature=tuple(sorted(cids)),
                            rows=sum(b.n for b in blocks)) as sp:
        plan = _make_dict_plan(blocks, cids, max_card)
        if sp is not None:
            sp.set_tag("eligible", plan is not None)
        return plan


def _make_dict_plan(blocks: Sequence[ColumnarBlock],
                    cids: Sequence[int],
                    max_card: int = 1 << 16) -> Optional[DictPlan]:
    t0 = time.perf_counter()
    dicts: Dict[int, np.ndarray] = {}
    codes: Dict[int, Dict[int, np.ndarray]] = {}
    ident = []
    for cid in sorted(cids):
        per_block = []
        for b in blocks:
            got = b.dict_varlen(cid, max_card=max_card)
            if got is None:
                return None
            per_block.append(got)
        global_uniq, remaps = lane_codec.merge_dicts(
            [u for u, _ in per_block])
        if len(global_uniq) > max_card:
            return None
        dicts[cid] = global_uniq
        codes[cid] = {
            id(b): (remap[local] if len(remap) else
                    np.zeros(b.n, np.int32))
            for b, (_, local), remap in zip(blocks, per_block, remaps)}
        ident.append((cid,) + lane_codec.dict_identity(global_uniq))
    return DictPlan(dicts=dicts, codes=codes, identity=tuple(ident),
                    merge_s=time.perf_counter() - t0)


def dict_cols_needed(blocks: Sequence[ColumnarBlock],
                     columns: Sequence[int]) -> Optional[List[int]]:
    """Columns of `columns` that are varlen in any block (must ride as
    dictionary codes), or None when some column is neither fixed/pk nor
    varlen everywhere (no columnar form at all)."""
    out: List[int] = []
    for cid in columns:
        if all(cid in b.fixed or cid in b.pk for b in blocks):
            continue
        if all(cid in b.varlen for b in blocks):
            out.append(cid)
        else:
            return None
    return out


# ---------------------------------------------------------------------------
# Host-side slot decode + spill handling
# ---------------------------------------------------------------------------

def decode_slot_groups(spec: DictGroupSpec,
                       dicts: Dict[int, np.ndarray],
                       outs: Sequence[np.ndarray],
                       counts: np.ndarray
                       ) -> Tuple[tuple, np.ndarray, tuple]:
    """Compact dense slot arrays to the PRESENT groups and decode each
    slot id back to its string key values through the scan-global
    dictionaries: (agg_values, counts, group_values) in slot order.
    The FIRST group column has stride 1 (varies fastest), so slot
    order sorts primarily by the LAST column's dictionary order.
    Group order is NOT part of the contract — every consumer keys by
    group values (combine_grouped_partials, SQL projection, tests)."""
    counts = np.asarray(counts)
    domains = [max(len(dicts[c]), 1) for c in spec.cols]
    prod = 1
    for d in domains:
        prod *= d
    present = np.nonzero(counts[:min(prod, len(counts))])[0]
    gvals = []
    rem = present.copy()
    for cid, dom in zip(spec.cols, domains):
        code = rem % dom
        rem = rem // dom
        gvals.append(np.asarray(dicts[cid], object)[code])
    outs_c = tuple(np.asarray(o)[present] for o in outs)
    return outs_c, counts[present], tuple(gvals)


# ---------------------------------------------------------------------------
# CPU twin — numpy replay of the kernel's accumulation contract
# ---------------------------------------------------------------------------

def grouped_aggregate_cpu(blocks: Sequence[ColumnarBlock],
                          columns: Sequence[int],
                          where: Optional[tuple],
                          aggs: Sequence,
                          spec: DictGroupSpec,
                          read_ht: Optional[int] = None,
                          plan: Optional[DictPlan] = None):
    """Numpy twin of the device dict-grouped scan: same scan-global
    dictionary plan, same dense slot encoding, same static int64
    fixed-point SUM quantization (ops/scan.py accumulation contract) —
    so on an f64 backend the twin is BITWISE equal to the kernel, and
    parity tests can assert it. Returns (outs, counts, spilled) in
    dense slot form (decode via decode_slot_groups)."""
    from .cpu_scan import eval_expr_np
    from .scan import _expand_avg, _scale_for
    aggs = tuple(_expand_avg(aggs))
    dcids = dict_cols_needed(blocks, columns)
    if plan is None:
        if dcids is None:
            raise ValueError("columns lack columnar form")
        plan = make_dict_plan(blocks, set(dcids) | set(spec.cols))
        if plan is None:
            raise ValueError("not dictionary-encodable")
    cols: Dict[int, np.ndarray] = {}
    nulls: Dict[int, np.ndarray] = {}
    bounds: Dict[int, Tuple[float, float]] = {}
    for cid in set(columns) | set(spec.cols):
        if cid in plan.dicts:
            cols[cid] = np.concatenate(
                [plan.block_codes(cid, b) for b in blocks])
            nulls[cid] = np.concatenate(
                [np.asarray(b.varlen[cid][2], bool) for b in blocks])
            continue
        parts, nparts = [], []
        for b in blocks:
            if cid in b.fixed:
                v, m = b.fixed[cid]
                parts.append(v)
                nparts.append(m)
            else:
                parts.append(b.pk[cid])
                nparts.append(np.zeros(b.n, bool))
        arr = np.concatenate(parts)
        # mirror the device batch's f64->int32 conversion policy so
        # integer-valued f64 columns aggregate exactly, like on device
        from .device_batch import f64_conversion
        conv = f64_conversion(parts) if arr.dtype == np.float64 else None
        if conv is not None:
            arr = arr.astype(conv)
        cols[cid] = arr
        nulls[cid] = np.concatenate(nparts)
        if arr.dtype.kind in "fiu" and len(arr):
            bounds[cid] = (float(arr.min()), float(arr.max()))
    n = len(next(iter(cols.values())))
    mask = np.ones(n, bool)
    if read_ht is not None:
        ht = np.concatenate([b.ht for b in blocks])
        tomb = np.concatenate([b.tombstone for b in blocks])
        mask &= (ht <= np.uint64(read_ht)) & ~tomb
    if where is not None:
        wv, wn = eval_expr_np(where, cols, nulls)
        mask &= wv
        if wn is not None:
            mask &= ~wn
    resolved, domains = resolve_group(spec, plan.dicts)
    for cid in spec.cols:
        mask &= ~nulls[cid]
    gid = np.zeros(n, np.int64)
    stride = 1
    for cid, dom in zip(spec.cols, domains):
        gid += cols[cid].astype(np.int64) * stride
        stride *= dom
    S = resolved.num_slots
    spill_slot = S - 1
    in_range = gid < spill_slot
    spilled = int(np.sum(mask & ~in_range))
    gid_c = np.where(mask & in_range, gid, spill_slot).astype(np.int64)
    outs = []
    from .expr import expr_bound

    def _exact_count(m):
        return np.bincount(gid_c[m], minlength=S).astype(np.int64)

    def _exact_sum(q):
        qs = np.zeros(S, np.int64)
        np.add.at(qs, gid_c, q)
        return qs

    for a in aggs:
        if a.expr is None:
            outs.append(_exact_count(mask))
            continue
        v, vn = eval_expr_np(a.expr, cols, nulls)
        m = mask if vn is None else mask & ~vn
        if a.op == "count":
            outs.append(_exact_count(m))
        elif a.op == "sum":
            if np.issubdtype(np.asarray(v).dtype, np.integer) or \
                    np.asarray(v).dtype == np.bool_:
                outs.append(_exact_sum(
                    np.where(m, v, 0).astype(np.int64)))
                continue
            b = expr_bound(a.expr, bounds) if bounds else None
            s = (_scale_for(max(abs(b[0]), abs(b[1])), n)
                 if b is not None else None)
            if s is not None:
                # the kernel's static fixed-point lane, replayed
                q = np.rint(np.where(m, v, 0) * np.float64(s)
                            ).astype(np.int64)
                outs.append(_exact_sum(q).astype(np.float64) / float(s))
            else:
                outs.append(np.bincount(gid_c,
                                        weights=np.where(m, v, 0),
                                        minlength=S))
        elif a.op in ("min", "max"):
            sent = (np.inf if a.op == "min" else -np.inf) \
                if np.asarray(v).dtype.kind == "f" else \
                (np.iinfo(np.asarray(v).dtype).max if a.op == "min"
                 else np.iinfo(np.asarray(v).dtype).min)
            arr = np.full(S, sent, np.asarray(v).dtype)
            red = np.minimum if a.op == "min" else np.maximum
            getattr(red, "at")(arr, gid_c[m], np.asarray(v)[m])
            outs.append(arr)
        else:
            raise ValueError(a.op)
    counts = np.bincount(gid_c[mask], minlength=S).astype(np.int64)
    return tuple(outs), counts, spilled


def retract_grouped_cpu(aggs, vals, counts, delta_vals, delta_counts):
    """Dense-slot numpy twin of ops/scan.py
    :func:`~yugabyte_db_tpu.ops.scan.retract_grouped_partials`: both
    operands are slot-ALIGNED arrays (slot i means the same group in
    base and delta — the kernel-side layout, unlike the keyed triples
    the client combine passes around). SUM/COUNT lanes subtract
    exactly; MIN/MAX lanes cannot un-aggregate, so the twin returns a
    per-(agg, slot) dirty mask marking every slot whose retracted
    extremum challenges the surviving value (== the keyed version's
    dirty list; the caller re-scans those slots). Slots whose row
    count reaches zero clear to identity and are never dirty.

    ``aggs`` must already be avg-expanded. Returns
    ``(outs, new_counts, dirty)`` with ``dirty`` of shape
    ``[len(aggs), slots]`` (bool)."""
    counts = np.asarray(counts, np.int64)
    dcounts = np.asarray(delta_counts, np.int64)
    if np.any(dcounts > counts):
        raise ValueError("retract of more rows than a slot holds")
    new_counts = counts - dcounts
    alive = new_counts > 0
    outs = []
    dirty = np.zeros((len(aggs), len(counts)), bool)
    for i, a in enumerate(aggs):
        v = np.asarray(vals[i])
        dv = np.asarray(delta_vals[i])
        if a.op in ("sum", "count"):
            outs.append(np.where(alive, v - dv, np.zeros_like(v)))
            continue
        # min/max: a delta extremum at/past the base extremum means the
        # surviving value may be stale — the kernel sentinel (inf /
        # dtype extreme) is the empty-delta identity and never fires
        challenge = (dv <= v) if a.op == "min" else (dv >= v)
        dirty[i] = alive & (dcounts > 0) & challenge
        outs.append(v.copy())
    return tuple(outs), new_counts, dirty
