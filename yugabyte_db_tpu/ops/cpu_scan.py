"""Vectorized numpy CPU scan — the honest CPU baseline.

A fair stand-in for the reference's C++ scan loop
(reference: src/yb/docdb/pgsql_operation.cc:2790): whole-column numpy
evaluation over the same columnar blocks the TPU path reads, so a
comparison against it measures TPU-vs-CPU execution, not
Python-vs-compiled overhead. (The row-at-a-time interpreter in
docdb/operations.py is the semantics reference, not the baseline.)
"""
from __future__ import annotations

from typing import Dict, Optional, Sequence, Tuple

import numpy as np

from .scan import AggSpec, GroupSpec, _expand_avg
from ..storage.columnar import ColumnarBlock


def eval_expr_np(node: tuple, cols: Dict[int, np.ndarray],
                 nulls: Dict[int, np.ndarray]):
    """Returns (values ndarray, null_mask ndarray|None)."""
    kind = node[0]
    if kind == "col":
        return cols[node[1]], nulls.get(node[1])
    if kind == "const":
        return node[1], None
    if kind == "cmp":
        l, ln = eval_expr_np(node[2], cols, nulls)
        r, rn = eval_expr_np(node[3], cols, nulls)
        op = {"lt": np.less, "le": np.less_equal, "gt": np.greater,
              "ge": np.greater_equal, "eq": np.equal,
              "ne": np.not_equal}[node[1]]
        return op(l, r), _or(ln, rn)
    if kind == "arith":
        l, ln = eval_expr_np(node[2], cols, nulls)
        r, rn = eval_expr_np(node[3], cols, nulls)
        op = {"add": np.add, "sub": np.subtract, "mul": np.multiply,
              "div": np.divide}[node[1]]
        return op(l, r), _or(ln, rn)
    if kind == "and":
        l, ln = eval_expr_np(node[1], cols, nulls)
        r, rn = eval_expr_np(node[2], cols, nulls)
        return np.logical_and(l, r), _or(ln, rn)
    if kind == "or":
        l, ln = eval_expr_np(node[1], cols, nulls)
        r, rn = eval_expr_np(node[2], cols, nulls)
        return np.logical_or(l, r), _or(ln, rn)
    if kind == "not":
        v, n = eval_expr_np(node[1], cols, nulls)
        return np.logical_not(v), n
    if kind == "between":
        x, xn = eval_expr_np(node[1], cols, nulls)
        lo, lon = eval_expr_np(node[2], cols, nulls)
        hi, hin = eval_expr_np(node[3], cols, nulls)
        return (x >= lo) & (x <= hi), _or(_or(xn, lon), hin)
    if kind == "in":
        x, xn = eval_expr_np(node[1], cols, nulls)
        acc = np.zeros(np.shape(x), bool)
        for v in node[2]:
            acc |= (x == v)
        return acc, xn
    if kind == "isnull":
        _, xn = eval_expr_np(node[1], cols, nulls)
        return (xn if xn is not None else np.zeros(1, bool)), None
    raise ValueError(kind)


def _or(a, b):
    if a is None:
        return b
    if b is None:
        return a
    return a | b


def cpu_scan_aggregate(blocks: Sequence[ColumnarBlock],
                       columns: Sequence[int],
                       where: Optional[tuple] = None,
                       aggs: Sequence[AggSpec] = (),
                       group: Optional[GroupSpec] = None,
                       read_ht: Optional[int] = None):
    """Numpy twin of ops.scan.scan_aggregate over raw columnar blocks
    (unique-keys sources only — the baseline scenario)."""
    aggs = tuple(_expand_avg(aggs))
    cols: Dict[int, np.ndarray] = {}
    nulls: Dict[int, np.ndarray] = {}
    for cid in columns:
        parts, nparts = [], []
        for b in blocks:
            if cid in b.fixed:
                v, m = b.fixed[cid]
                parts.append(v)
                nparts.append(m)
            else:
                parts.append(b.pk[cid])
                nparts.append(np.zeros(b.n, bool))
        cols[cid] = np.concatenate(parts)
        nulls[cid] = np.concatenate(nparts)
    mask = np.ones(len(next(iter(cols.values()))), bool)
    if read_ht is not None:
        ht = np.concatenate([b.ht for b in blocks])
        tomb = np.concatenate([b.tombstone for b in blocks])
        mask &= (ht <= read_ht) & ~tomb
    if where is not None:
        wv, wn = eval_expr_np(where, cols, nulls)
        mask &= wv
        if wn is not None:
            mask &= ~wn
    outs = []
    if group is None:
        for a in aggs:
            if a.expr is None:
                outs.append(np.int64(mask.sum()))
                continue
            v, vn = eval_expr_np(a.expr, cols, nulls)
            m = mask if vn is None else mask & ~vn
            if a.op == "count":
                outs.append(np.int64(m.sum()))
            elif a.op == "sum":
                outs.append(np.where(m, v, 0).sum())
            elif a.op == "min":
                outs.append(v[m].min() if m.any() else np.inf)
            elif a.op == "max":
                outs.append(v[m].max() if m.any() else -np.inf)
        return tuple(outs), np.int64(mask.sum())
    gid = None
    stride = 1
    for cid, domain, offset in group.cols:
        gn = nulls.get(cid)
        if gn is not None:
            mask &= ~gn
        c = np.clip(cols[cid].astype(np.int64) - offset, 0, domain - 1)
        gid = c * stride if gid is None else gid + c * stride
        stride *= domain
    G = group.num_groups
    for a in aggs:
        if a.expr is None:
            outs.append(np.bincount(gid, weights=mask, minlength=G
                                    ).astype(np.int64))
            continue
        v, vn = eval_expr_np(a.expr, cols, nulls)
        m = mask if vn is None else mask & ~vn
        if a.op == "count":
            outs.append(np.bincount(gid, weights=m, minlength=G
                                    ).astype(np.int64))
        elif a.op == "sum":
            outs.append(np.bincount(gid, weights=np.where(m, v, 0),
                                    minlength=G))
        elif a.op in ("min", "max"):
            arr = np.full(G, np.inf if a.op == "min" else -np.inf)
            red = np.minimum if a.op == "min" else np.maximum
            getattr(red, "at")(arr, gid[m], v[m])
            outs.append(arr)
    counts = np.bincount(gid, weights=mask, minlength=G).astype(np.int64)
    return tuple(outs), counts
