"""DocDB read/write operations — the tablet-level request executors.

Analogs of the reference's PgsqlReadOperation / PgsqlWriteOperation
(reference: src/yb/docdb/pgsql_operation.cc:2225 Execute, :1633 write
path, scan loop :2790-2877). Both the SQL and CQL front ends compile to
these requests; they cross the wire in msgpack (the PgsqlReadRequestPB
analog, reference: src/yb/common/pgsql_protocol.proto:430-565).

The read executor is where the TPU pushdown boundary lives: aggregate /
filter scans over enough rows route to the columnar scan kernels
(ops/scan.py) when `tpu_pushdown_enabled` is set, with row-at-a-time CPU
execution as both the small-scan path and the correctness reference —
exactly the two-backend structure the reference's
`yb_enable_tpu_pushdown` GUC plan describes (BASELINE.json north star).
"""
from __future__ import annotations

from dataclasses import dataclass, field
from functools import partial
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from ..dockv.key_encoding import ValueType
from ..dockv.value import PrimitiveValue, ValueKind, unwrap_ttl
from ..ops.device_batch import batch_bytes, build_batch
from ..ops.grouped_scan import DictGroupSpec
from ..ops.scan import AggSpec, GroupSpec, HashGroupSpec, ScanKernel
from ..ops.stream_scan import chunk_safe_mvcc
from ..storage.columnar import ColumnarBlock, fnv64_bytes
from ..storage.lsm import LsmStore, WriteBatch
from ..utils import flags, metrics
from ..utils import trace as _trace
from ..utils.hybrid_time import ENCODED_SIZE, DocHybridTime, HybridTime
from .hotpath import load as _hot_mod
from .table_codec import TableCodec

_HT_SUFFIX = ENCODED_SIZE + 1


# --------------------------------------------------------------------------
# Requests (wire format objects)
# --------------------------------------------------------------------------
@dataclass
class RowOp:
    # 'upsert' | 'delete' | 'insert' — 'insert' is insert-if-absent:
    # the write path rejects it with DUPLICATE_KEY when a live row
    # already exists at the key (the conflict-causing op unique indexes
    # are built from; reference: unique-index insertion through
    # yb_lsm.c:233-366 where the index doc key IS the indexed value)
    kind: str
    row: Dict[str, object]         # full row for upsert; PK columns for delete
    ttl_ms: Optional[int] = None   # row TTL (None = forever)


@dataclass
class WriteRequest:
    table_id: str
    ops: List[RowOp] = field(default_factory=list)
    # xCluster: preserve the SOURCE universe's commit HT on target
    # writes so safe-time reads see a consistent cut (reference:
    # external hybrid time in docdb / xcluster_write_interface)
    external_ht: int | None = None
    # catalog-version fence: the CLIENT's cached schema version; the
    # serving tablet rejects a mismatch before replicating, so a
    # session holding a pre-ALTER schema can never write through it
    # (reference: catalog version checks + YsqlBackendsManager,
    # src/yb/master/ysql_backends_manager.cc). None = unfenced
    # (internal paths, WAL replay)
    schema_version: int | None = None


@dataclass
class WriteResponse:
    rows_affected: int = 0


@dataclass
class ReadRequest:
    table_id: str
    columns: Tuple[str, ...] = ()            # projection (empty = all)
    where: Optional[tuple] = None            # expr AST over column IDS
    aggregates: Tuple[AggSpec, ...] = ()     # aggregate pushdown
    group_by: Optional[GroupSpec] = None
    # FK-equijoin pushdown: the (small, pre-filtered) build side ships
    # WITH the request — ONE ops/join_scan.JoinWire or an ordered
    # sequence of them (multi-join chains/stars: N probe stages, probed
    # in order inside one fused program) — keys + payload columns,
    # referenced from `aggregates`/`group_by` at ids >= BUILD_COL_BASE.
    # Aggregate requests only; `where` stays a probe-side predicate
    # (build-side filters are applied by the sender before shipping
    # the build rows).
    join: Optional[object] = None
    # server-side window pushdown: a sorted-scan spec
    # (ops/window_scan.WindowWire) for ROW requests — the tablet sorts
    # its visible post-WHERE rows by (partition, order) and attaches
    # the window values via the segment-scan kernels; ineligible
    # shapes serve plain rows with a typed reason and the client tier
    # recomputes bit-identically
    window: Optional[object] = None
    pk_eq: Optional[Dict[str, object]] = None  # full-PK point lookup
    pk_prefix: Optional[Dict[str, object]] = None  # hash-cols prefix scan
    limit: Optional[int] = None
    paging_state: Optional[bytes] = None      # resume key (exclusive)
    read_ht: Optional[int] = None             # read point (HybridTime.value)
    # True when the SERVER picked read_ht from its clock: only such reads
    # are subject to uncertainty-window restarts (explicit snapshot /
    # time-travel read points never restart)
    server_assigned_read_ht: bool = False
    # 'strong' = leader + lease; 'follower' = consistent-prefix read from
    # any replica (reference: follower reads / consistent prefix,
    # tserver/read_query.cc consistency levels)
    consistency: str = "strong"


@dataclass
class ReadResponse:
    rows: List[Dict[str, object]] = field(default_factory=list)
    agg_values: Optional[tuple] = None        # scalars or per-group arrays
    group_counts: Optional[object] = None
    # hash-grouped results: per-group key values, aligned with
    # group_counts / agg_values (order matches the HashGroupSpec cols)
    group_values: Optional[tuple] = None
    paging_state: Optional[bytes] = None
    backend: str = "cpu"                      # which path executed
    # window pushdown outcome: True when `rows` already carry the
    # request's window values (computed tablet-side); on refusal the
    # typed reason rides back so the caller can tally it
    window_served: bool = False
    window_reason: Optional[str] = None


# --------------------------------------------------------------------------
# CPU expression interpreter (correctness reference / small scans)
# --------------------------------------------------------------------------
_IN_SET_CACHE: Dict[int, tuple] = {}


def _pg_text(v) -> str:
    """Text form for string functions/||: SQL-style, not Python repr
    (True -> 'true', Decimal prints plainly)."""
    if isinstance(v, bool):
        return "true" if v else "false"
    return v if isinstance(v, str) else str(v)


def _pg_mod(l, r):
    """PG %/mod(): truncates toward zero (Python's % floors)."""
    if isinstance(l, int) and isinstance(r, int):
        m = abs(l) % abs(r)
        return -m if l < 0 else m
    from decimal import Decimal
    return Decimal(str(l)) % Decimal(str(r))


def _as_array(v):
    """Array value: a Python list, or the JSON-text form arrays/CQL
    collections are stored as. None for NULL / non-array."""
    if v is None or isinstance(v, list):
        return v
    if isinstance(v, (str, bytes)):
        import json as _json
        try:
            out = _json.loads(v)
        except (ValueError, TypeError):
            return None
        return out if isinstance(out, list) else None
    return None


_TRUNC_FIELDS = ("year", "month", "day", "hour", "minute", "second",
                 "week")


def _date_trunc(unit: str, micros):
    """date_trunc('<unit>', ts_micros) -> micros at the truncation."""
    if micros is None:
        return None
    from datetime import datetime, timedelta, timezone
    dt = datetime.fromtimestamp(micros / 1e6, tz=timezone.utc)
    unit = unit.lower()
    if unit not in _TRUNC_FIELDS:
        raise ValueError(f"date_trunc unit {unit!r}")
    if unit == "week":
        dt = (dt - timedelta(days=dt.weekday())).replace(
            hour=0, minute=0, second=0, microsecond=0)
    elif unit == "year":
        dt = dt.replace(month=1, day=1, hour=0, minute=0, second=0,
                        microsecond=0)
    elif unit == "month":
        dt = dt.replace(day=1, hour=0, minute=0, second=0,
                        microsecond=0)
    elif unit == "day":
        dt = dt.replace(hour=0, minute=0, second=0, microsecond=0)
    elif unit == "hour":
        dt = dt.replace(minute=0, second=0, microsecond=0)
    elif unit == "minute":
        dt = dt.replace(second=0, microsecond=0)
    else:                                  # second
        dt = dt.replace(microsecond=0)
    return int(dt.timestamp() * 1_000_000)


def _extract_field(field: str, micros):
    """EXTRACT(<field> FROM ts_micros) (reference: PG timestamp_part)."""
    if micros is None:
        return None
    from datetime import datetime, timezone
    dt = datetime.fromtimestamp(micros / 1e6, tz=timezone.utc)
    f = field.lower()
    if f == "epoch":
        return micros / 1e6
    if f == "year":
        return dt.year
    if f == "month":
        return dt.month
    if f == "day":
        return dt.day
    if f == "hour":
        return dt.hour
    if f == "minute":
        return dt.minute
    if f == "second":
        return dt.second + dt.microsecond / 1e6
    if f == "dow":
        return (dt.weekday() + 1) % 7      # PG: Sunday = 0
    if f == "doy":
        return dt.timetuple().tm_yday
    if f == "week":
        return dt.isocalendar()[1]
    raise ValueError(f"EXTRACT field {field!r}")


def eval_expr_py(node: tuple, row: Dict[int, object]):
    """Evaluate the pushdown AST over one row ({col_id: value}); returns
    value or None for SQL NULL."""
    kind = node[0]
    if kind == "col":
        return row.get(node[1])
    if kind == "case":
        n = node[1]
        for i in range(n):
            if eval_expr_py(node[2 + 2 * i], row) is True:
                return eval_expr_py(node[3 + 2 * i], row)
        return eval_expr_py(node[2 + 2 * n], row)
    if kind == "const":
        return node[1]
    if kind == "cmp":
        l = eval_expr_py(node[2], row)
        r = eval_expr_py(node[3], row)
        if l is None or r is None:
            return None
        return {"lt": l < r, "le": l <= r, "gt": l > r, "ge": l >= r,
                "eq": l == r, "ne": l != r}[node[1]]
    if kind == "arith":
        l = eval_expr_py(node[2], row)
        r = eval_expr_py(node[3], row)
        if l is None or r is None:
            return None
        if node[1] == "concat":
            # PG ||: text concat, array||array, array||elem, elem||array
            if isinstance(l, list) or isinstance(r, list):
                al, ar = _as_array(l), _as_array(r)
                if al is not None and ar is not None:
                    return al + ar
                if al is not None:
                    return al + [r]
                return [l] + ar
            return _pg_text(l) + _pg_text(r)
        # Decimal refuses mixed arithmetic with float: promote the
        # other operand (comparisons already allow the mix)
        from decimal import Decimal
        if isinstance(l, Decimal) != isinstance(r, Decimal):
            if isinstance(l, Decimal):
                r = Decimal(str(r))
            else:
                l = Decimal(str(l))
        # dispatch lazily: an eager dict literal would evaluate EVERY
        # op (div-by-zero on add, str-minus-str on concat, ...)
        op = node[1]
        if op == "add":
            return l + r
        if op == "sub":
            return l - r
        if op == "mul":
            return l * r
        if op == "div":
            return l / r
        if op == "mod":
            return _pg_mod(l, r)
        raise ValueError(op)
    if kind == "and":
        l = eval_expr_py(node[1], row)
        r = eval_expr_py(node[2], row)
        if l is False or r is False:
            return False
        if l is None or r is None:
            return None
        return l and r
    if kind == "or":
        l = eval_expr_py(node[1], row)
        r = eval_expr_py(node[2], row)
        if l is True or r is True:
            return True
        if l is None or r is None:
            return None
        return l or r
    if kind == "not":
        v = eval_expr_py(node[1], row)
        return None if v is None else not v
    if kind == "between":
        x = eval_expr_py(node[1], row)
        lo = eval_expr_py(node[2], row)
        hi = eval_expr_py(node[3], row)
        if x is None or lo is None or hi is None:
            return None
        return lo <= x <= hi
    if kind == "in":
        x = eval_expr_py(node[1], row)
        if x is None:
            return None
        vals = node[2]
        if len(vals) > 32:
            # large lists (IN-subquery results): one set build per node,
            # O(1) membership per row; the entry keeps a strong ref to
            # the node so its id stays valid for the cache's lifetime
            ent = _IN_SET_CACHE.get(id(node))
            if ent is None or ent[0] is not node:
                if len(_IN_SET_CACHE) > 128:
                    _IN_SET_CACHE.clear()
                ent = (node, set(vals))
                _IN_SET_CACHE[id(node)] = ent
            if x in ent[1]:
                return True
            # SQL 3VL: x IN (..., NULL) is UNKNOWN on a non-match —
            # which matters under NOT IN (PG returns zero rows)
            return None if None in ent[1] else False
        if x in vals:
            return True
        return None if any(v is None for v in vals) else False
    if kind == "isnull":
        return eval_expr_py(node[1], row) is None
    if kind == "isdistinct":
        a = eval_expr_py(node[1], row)
        b = eval_expr_py(node[2], row)
        # null-safe: NULL is not distinct from NULL (never returns NULL)
        if a is None or b is None:
            return (a is None) != (b is None)
        return a != b
    if kind in ("like", "ilike"):
        import re as _re
        v = eval_expr_py(node[1], row)
        if v is None:
            return None
        pat = "^" + _re.escape(node[2]).replace("%", ".*").replace(
            "_", ".") + "$"
        # note: escape() escaped % and _ as literals? re.escape leaves %
        # and _ unescaped in Python 3.7+, so the replace above is correct
        return _re.match(pat, str(v),
                         _re.IGNORECASE if kind == "ilike" else 0) \
            is not None
    if kind == "array":
        # ARRAY[...] with non-constant elements; NULL elements kept
        return [eval_expr_py(a, row) for a in node[1:]]
    if kind == "anyall":
        # ('anyall', 'any'|'all', cmpop, lhs, arr) — PG x <op> ANY/ALL
        # with SQL three-valued semantics over NULL elements
        lhs = eval_expr_py(node[3], row)
        arr = _as_array(eval_expr_py(node[4], row))
        if lhs is None or arr is None:
            return None
        import operator as _op
        cmp = {"lt": _op.lt, "le": _op.le, "gt": _op.gt, "ge": _op.ge,
               "eq": _op.eq, "ne": _op.ne}[node[2]]
        saw_null = False
        for e in arr:
            if e is None:
                saw_null = True
                continue
            hit = cmp(lhs, e)
            if node[1] == "any" and hit:
                return True
            if node[1] == "all" and not hit:
                return False
        if saw_null:
            return None
        return node[1] == "all"
    if kind == "fn":
        # scalar functions, row-wise on the CPU path (reference: the
        # ybgate-linked PG function library, docdb/docdb_pgapi.cc)
        name = node[1]
        if name == "now":
            # normally constant-folded at bind time; name-evaluated
            # contexts (CTE rows, join residuals) land here
            import time as _time
            return int(_time.time() * 1_000_000)
        args = [eval_expr_py(a, row) for a in node[2:]]
        if name == "coalesce":
            for a in args:
                if a is not None:
                    return a
            return None
        if name == "array_prepend":
            # PG prepends a NULL element rather than returning NULL
            arr = _as_array(args[1])
            return None if arr is None else [args[0]] + arr
        if name == "array_append":
            # the appended ELEMENT may be SQL NULL
            arr = _as_array(args[0])
            return None if arr is None else arr + [args[1]]
        if name == "concat":
            # PG concat() skips NULLs (unlike ||)
            return "".join(_pg_text(a) for a in args if a is not None)
        if name == "nullif":
            if args[0] is None:
                return None
            return None if args[0] == args[1] else args[0]
        if name in ("greatest", "least"):
            vals = [a for a in args if a is not None]
            if not vals:
                return None
            return max(vals) if name == "greatest" else min(vals)
        if any(a is None for a in args):
            return None          # strict functions: NULL in -> NULL out
        a0 = args[0] if args else None
        if name == "abs":
            return abs(a0)
        if name == "round":
            # PG rounds half AWAY from zero; Python round() is
            # half-to-even
            from decimal import ROUND_HALF_UP, Decimal
            nd = int(args[1]) if len(args) > 1 and args[1] is not None \
                else 0
            q = Decimal(1).scaleb(-nd)
            r = Decimal(str(a0)).quantize(q, ROUND_HALF_UP)
            if isinstance(a0, Decimal):
                return r
            return float(r) if isinstance(a0, float) and nd > 0 \
                else float(r) if isinstance(a0, float) else int(r)
        if name == "floor":
            import math
            return math.floor(a0)
        if name == "ceil":
            import math
            return math.ceil(a0)
        if name == "upper":
            return str(a0).upper()
        if name == "lower":
            return str(a0).lower()
        if name == "length":
            return len(a0)
        if name == "cast_numeric":
            from decimal import Decimal
            return a0 if isinstance(a0, Decimal) else Decimal(str(a0))
        if name in ("cast_bigint", "cast_int", "cast_integer",
                    "cast_int8", "cast_int4", "cast_smallint"):
            if isinstance(a0, int):
                return a0          # never round-trip int64 through f64
            from decimal import ROUND_HALF_UP, Decimal
            return int(Decimal(str(a0)).to_integral_value(ROUND_HALF_UP))
        if name in ("cast_double", "cast_float8", "cast_float",
                    "cast_real", "cast_float4"):
            return float(a0)
        if name in ("cast_text", "cast_varchar", "cast_string"):
            return str(a0)
        if name in ("substr", "substring"):
            st = int(args[1])
            ln = int(args[2]) if len(args) > 2 and args[2] is not None \
                else None
            sv = _pg_text(a0)
            # PG: 1-based; start may be <= 0 (consumes length)
            begin = st - 1
            end = None if ln is None else begin + ln
            begin = max(begin, 0)
            if end is not None and end < begin:
                end = begin
            return sv[begin:end]
        if name == "replace":
            return _pg_text(a0).replace(_pg_text(args[1]),
                                        _pg_text(args[2]))
        if name == "trim":
            return _pg_text(a0).strip(
                _pg_text(args[1]) if len(args) > 1 else None)
        if name == "ltrim":
            return _pg_text(a0).lstrip(
                _pg_text(args[1]) if len(args) > 1 else None)
        if name == "rtrim":
            return _pg_text(a0).rstrip(
                _pg_text(args[1]) if len(args) > 1 else None)
        if name == "strpos":
            return _pg_text(a0).find(_pg_text(args[1])) + 1
        if name == "left":
            n_ = int(args[1])
            sv = _pg_text(a0)
            return sv[:n_] if n_ >= 0 else sv[:len(sv) + n_]
        if name == "right":
            n_ = int(args[1])
            sv = _pg_text(a0)
            if n_ == 0:
                return ""
            # n < 0: all but the first |n| characters (PG semantics)
            return sv[-n_:] if n_ > 0 else sv[abs(n_):]
        if name == "lpad":
            sv, width = _pg_text(a0), int(args[1])
            fill = _pg_text(args[2]) if len(args) > 2 else " "
            if len(sv) >= width:
                return sv[:width]
            pad = (fill * width)[:width - len(sv)]
            return pad + sv
        if name == "rpad":
            sv, width = _pg_text(a0), int(args[1])
            fill = _pg_text(args[2]) if len(args) > 2 else " "
            if len(sv) >= width:
                return sv[:width]
            return sv + (fill * width)[:width - len(sv)]
        if name == "split_part":
            parts = _pg_text(a0).split(_pg_text(args[1]))
            i_ = int(args[2])
            return parts[i_ - 1] if 1 <= i_ <= len(parts) else ""
        if name == "starts_with":
            return _pg_text(a0).startswith(_pg_text(args[1]))
        if name == "initcap":
            import re as _re2
            return _re2.sub(r"[A-Za-z0-9]+",
                            lambda m: m.group(0).capitalize(),
                            _pg_text(a0))
        if name == "reverse":
            return _pg_text(a0)[::-1]
        if name == "subscript":
            # PG arrays are 1-based; out-of-bounds -> NULL
            arr = _as_array(a0)
            idx = args[1]
            if arr is None or idx is None:
                return None
            i = int(idx)
            return arr[i - 1] if 1 <= i <= len(arr) else None
        if name in ("array_length", "cardinality"):
            arr = _as_array(a0)
            if arr is None:
                return None
            if name == "array_length" and len(args) > 1 \
                    and args[1] not in (None, 1):
                return None     # 1-D arrays only
            return len(arr) if arr else (0 if name == "cardinality"
                                         else None)
        if name == "array_position":
            arr = _as_array(a0)
            if arr is None:
                return None
            try:
                return arr.index(args[1]) + 1
            except ValueError:
                return None
        if name == "trunc":
            from decimal import ROUND_DOWN, Decimal
            nd = int(args[1]) if len(args) > 1 and args[1] is not None \
                else 0
            q = Decimal(1).scaleb(-nd)
            r = Decimal(str(a0)).quantize(q, ROUND_DOWN)
            if isinstance(a0, Decimal):
                return r
            return float(r) if isinstance(a0, float) else int(r)
        if name == "sqrt":
            import math
            return math.sqrt(a0)
        if name == "power":
            from decimal import Decimal
            if isinstance(a0, Decimal) or isinstance(args[1], Decimal):
                return Decimal(str(a0)) ** Decimal(str(args[1]))
            return a0 ** args[1]
        if name == "mod":
            if args[1] is None:
                return None
            return _pg_mod(a0, args[1])
        if name == "date_trunc":
            return _date_trunc(str(a0), args[1])
        if name.startswith("extract_"):
            return _extract_field(name[len("extract_"):], a0)
        raise ValueError(f"unknown function {name}")
    if kind == "json":
        # ('json', 'text'|'value', expr, key) — PG ->> / -> semantics
        import json as _json
        v = eval_expr_py(node[2], row)
        if v is None:
            return None
        try:
            obj = _json.loads(v) if isinstance(v, (str, bytes)) else v
        except (ValueError, TypeError):
            return None
        key = node[3]
        if isinstance(obj, dict):
            out = obj.get(key)
        elif isinstance(obj, list) and isinstance(key, int):
            out = obj[key] if -len(obj) <= key < len(obj) else None
        else:
            return None
        if out is None:
            return None
        if node[1] == "text":
            return out if isinstance(out, str) else _json.dumps(out)
        return out if isinstance(out, (str, bytes)) else _json.dumps(out)
    raise ValueError(f"unknown node {kind}")


# --------------------------------------------------------------------------
# Write operation
# --------------------------------------------------------------------------
class DocWriteOperation:
    """Converts row ops into a KV WriteBatch at apply time (the hybrid
    time is assigned when the Raft operation is applied — reference:
    tablet/tablet.cc ApplyRowOperations)."""

    def __init__(self, codec: TableCodec, request: WriteRequest):
        self.codec = codec
        self.request = request

    def apply(self, ht: HybridTime, op_id=None) -> Tuple[WriteBatch, int]:
        batch = WriteBatch(op_id=op_id)
        wid = 0
        from ..dockv.value import wrap_ttl
        for op in self.request.ops:
            dht = DocHybridTime(ht, wid)
            if op.kind in ("upsert", "insert"):
                # 'insert' duplicates were rejected on the leader before
                # replication; at apply it writes like an upsert
                k, v = self.codec.encode_write(op.row, dht)
                if op.ttl_ms:
                    expire = ht.add_micros(op.ttl_ms * 1000).value
                    v = wrap_ttl(v, expire)
            elif op.kind == "delete":
                k, v = self.codec.encode_delete(op.row, dht)
            else:
                raise ValueError(op.kind)
            batch.put(k, v)
            wid += 1
        return batch, len(self.request.ops)


# --------------------------------------------------------------------------
# Read operation
# --------------------------------------------------------------------------
_POINT_TYPES = ("int32", "int64", "timestamp", "string")
_RANGE_TYPES = ("int32", "int64", "timestamp")
_MAX_SKIP_SEGMENTS = 4096


def extract_scan_options(where, range_cols):
    """Multi-column skip-scan options (reference: hybrid/ScanChoices,
    docdb/hybrid_scan_choices.cc): walk the conjuncts of `where` and,
    following range-PK column order, collect per-column target sets —
    point sets from =/IN on the leading columns, then one optional
    numeric interval on the next column. Returns
    (point_lists, interval, residual):
      point_lists: [(ColumnSchema, sorted values)] for leading columns
      interval:    (ColumnSchema, lo, hi) inclusive (either end None)
                   or None
      residual:    conjuncts NOT consumed by the bounds (re-checked
                   row-wise), or None
    Point lists enumerate in sorted order so the segment scan preserves
    encoded-pk order (ORDER BY stays pushdown-compatible)."""
    conjuncts = []

    def flatten(n):
        if n[0] == "and":
            flatten(n[1])
            flatten(n[2])
        else:
            conjuncts.append(n)

    if where is not None:
        flatten(where)

    def col_of(n):
        # (col, const) comparisons only, either operand order
        if n[0] == "cmp":
            if n[2][0] == "col" and n[3][0] == "const":
                return n[2][1], n[1], n[3][1]
            if n[3][0] == "col" and n[2][0] == "const":
                flip = {"lt": "gt", "le": "ge", "gt": "lt", "ge": "le",
                        "eq": "eq", "ne": "ne"}
                return n[3][1], flip[n[1]], n[2][1]
        return None

    def norm_point(col, v):
        """A point value an =/IN target on `col` can actually hit, or
        None. Non-integral numerics can never equal an integer column
        (consumed as provably-false, NOT truncated); type mismatches
        are rejected so the conjunct stays residual."""
        if col.type == "string":
            return v if isinstance(v, str) else None
        if isinstance(v, bool) or not isinstance(v, (int, float)):
            return None
        if isinstance(v, float):
            return int(v) if float(v).is_integer() else None
        return v

    used = set()
    point_lists = []
    interval = None
    import math
    for col in range_cols:
        pts = None
        lo = hi = None
        for i, n in enumerate(conjuncts):
            if i in used:
                continue
            if n[0] == "in" and n[1] == ("col", col.id) \
                    and col.type in _POINT_TYPES:
                if not all(isinstance(v, (int, float, str))
                           and not isinstance(v, bool)
                           for v in n[2] if v is not None):
                    continue       # untypeable list: stays residual
                vals = {p for v in n[2] if v is not None
                        for p in [norm_point(col, v)] if p is not None}
                pts = vals if pts is None else pts & vals
                used.add(i)
                continue
            c = col_of(n)
            if c is None or c[0] != col.id:
                continue
            op, v = c[1], c[2]
            if op == "eq" and col.type in _POINT_TYPES:
                if col.type != "string" and not isinstance(
                        v, (int, float)) or isinstance(v, bool):
                    continue       # untypeable: stays residual
                if col.type == "string" and not isinstance(v, str):
                    continue
                p = norm_point(col, v)
                new = {p} if p is not None else set()
                pts = new if pts is None else pts & new
                used.add(i)
            elif col.type in _RANGE_TYPES and op in ("ge", "gt") \
                    and isinstance(v, (int, float)) \
                    and not isinstance(v, bool):
                # integer column: k >= 4.5 means k >= 5; k > 4.5 too
                b = math.ceil(v) if op == "ge" else math.floor(v) + 1
                lo = b if lo is None else max(lo, b)
                used.add(i)
            elif col.type in _RANGE_TYPES and op in ("le", "lt") \
                    and isinstance(v, (int, float)) \
                    and not isinstance(v, bool):
                b = math.floor(v) if op == "le" else math.ceil(v) - 1
                hi = b if hi is None else min(hi, b)
                used.add(i)
        if n_between := [i for i, n in enumerate(conjuncts)
                         if i not in used and n[0] == "between"
                         and n[1] == ("col", col.id)
                         and n[2][0] == "const" and n[3][0] == "const"
                         and col.type in _RANGE_TYPES
                         and all(isinstance(n[j][1], (int, float))
                                 and not isinstance(n[j][1], bool)
                                 for j in (2, 3))]:
            for i in n_between:
                n = conjuncts[i]
                blo, bhi = math.ceil(n[2][1]), math.floor(n[3][1])
                lo = blo if lo is None else max(lo, blo)
                hi = bhi if hi is None else min(hi, bhi)
                used.add(i)
        if pts is not None:
            if lo is not None or hi is not None:
                pts = {p for p in pts
                       if (lo is None or p >= lo)
                       and (hi is None or p <= hi)}
            point_lists.append((col, sorted(pts)))
            continue
        if lo is not None or hi is not None:
            interval = (col, lo, hi)
        break       # first non-point column ends the enumerable prefix
    residual = [n for i, n in enumerate(conjuncts) if i not in used]
    if not residual:
        res = None
    else:
        res = residual[0]
        for r in residual[1:]:
            res = ("and", res, r)
    return point_lists, interval, res


def classify_scan_options(schema, partition_kind: str, where):
    """Shared skip-scan eligibility + shape, used by BOTH execution
    (_scan_segments) and EXPLAIN so the reported plan can never drift
    from what runs. Returns (kind, point_lists, interval, residual,
    nseg) with kind in:
      "seq"   — plain scan, residual = the original where
      "empty" — provably-empty target set
      "skip"  — enumerable point segments (nseg of them)
      "range" — leading-interval bounds only
    """
    if partition_kind != "range" or where is None or \
            any(c.sort_desc for c in schema.key_columns):
        return ("seq", None, None, where, 0)
    point_lists, interval, residual = extract_scan_options(
        where, schema.key_columns)
    if not point_lists and interval is None:
        return ("seq", None, None, where, 0)
    total = 1
    for _c, vals in point_lists:
        total *= len(vals)
        if total > _MAX_SKIP_SEGMENTS:
            # too many combinations to enumerate: full scan +
            # row-wise filter (no silent cap on correctness)
            return ("seq", None, None, where, 0)
    if point_lists and total == 0:
        return ("empty", point_lists, interval, residual, 0)
    return ("skip" if point_lists else "range",
            point_lists, interval, residual, total)


_SKEW_WINDOW = [None]
flags.REGISTRY.on_change(
    "max_clock_skew_ms", lambda v: _SKEW_WINDOW.__setitem__(0, None))


def _skew_window_ht() -> int:
    # cached: read on every point lookup, flag changes are rare
    w = _SKEW_WINDOW[0]
    if w is None:
        w = _SKEW_WINDOW[0] = flags.get("max_clock_skew_ms") * 1000 << 12
    return w


def _nullify_minmax(expanded, minmax, outs):
    """SQL NULL semantics for MIN/MAX over zero qualifying inputs: the
    kernel returns a dtype sentinel there, so each min/max aggregate ran
    with a hidden companion COUNT appended after `expanded`; zero-count
    results become None host-side (the CPU twin returns None too).
    Shared by the monolithic and streaming aggregate paths."""
    outs = [np.asarray(o) for o in outs]
    base, extras = outs[:len(expanded)], outs[len(expanded):]
    for j, i in enumerate(minmax):
        cnt = extras[j]
        v = base[i]
        if v.ndim == 0:
            base[i] = (np.asarray(None, object)
                       if int(cnt) == 0 else v)
        else:
            obj = v.astype(object)
            obj[np.asarray(cnt) == 0] = None
            base[i] = obj
    return tuple(base)


def dict_minmax_decode(expanded, outs, dicts):
    """Decode dict-code MIN/MAX aggregate results back into strings —
    the host half of the aggregate-over-string-payload pushdown
    (ROADMAP fused-plan item (d)): the kernel min/maxes the CODES lane
    of a dictionary column (code order == string order for the sorted
    dictionary), and each surviving code maps through the scan-global
    payload dictionary here, BEFORE any cross-shard combine (per-shard
    dictionaries differ, so codes must never leave the shard).

    ``expanded`` aligns with ``outs``; only entries whose expr is a
    bare column with a dictionary entry decode.  Out-of-range codes
    (pre-nullify kernel sentinels for zero-input groups) and None
    (post-nullify) map to None.  Shared by the monolithic, streaming,
    spill-merge and bypass routes."""
    if not dicts:
        return tuple(outs)
    outs = list(outs)
    for i, a in enumerate(expanded):
        if i >= len(outs) or a.op not in ("min", "max"):
            continue
        e = a.expr
        if not (isinstance(e, (tuple, list)) and e and e[0] == "col"
                and e[1] in dicts):
            continue
        d = dicts[e[1]]

        def dec(x, _d=d):
            if x is None:
                return None
            c = int(x)
            return str(_d[c]) if 0 <= c < len(_d) else None

        v = np.asarray(outs[i])
        if v.ndim == 0:
            outs[i] = np.asarray(dec(v.item()), object)
        else:
            obj = v.astype(object)
            for g in range(len(obj)):
                obj[g] = dec(obj[g])
            outs[i] = obj
    return tuple(outs)


class ReadRestartError(Exception):
    """Internal: a record inside the clock-uncertainty window was seen;
    the read must restart at restart_ht (reference: read restarts in
    tserver/read_query.cc / transactional reads design)."""

    def __init__(self, restart_ht: int):
        super().__init__(f"read restart at {restart_ht}")
        self.restart_ht = restart_ht


@dataclass
class StoreFacts:
    """What a device read has to know about a store's blocks beyond its
    cached batch, made in one pass over them and kept with the store
    (`LsmStore.read_facts`) for as long as its contents stand: `key` is
    the SST set and the write generation, as `_batch_cache_key` names
    them, so a write, a flush or a compaction makes the next read take
    the facts anew and a stale one cannot be reached."""
    key: tuple
    #: the newest write time over every collected block (SSTs and the
    #: memtable overlay): a read at or above it has an empty
    #: uncertainty window
    max_ht: int
    #: `chunk_safe_mvcc` over the whole block list: one version a key,
    #: every key inside one block
    chunk_safe: bool
    #: table id -> the streaming route's kept dictionary plans, by
    #: dictionary-column set (`ops.stream_scan._kept_plan`)
    plans: Dict[str, dict] = field(default_factory=dict)


def run_steps(steps):
    """Drive a read written as steps (`DocReadOperation.execute_steps`)
    on the calling thread: every launch it yields is called here."""
    try:
        call = next(steps)
        while True:
            try:
                got = call()
            except Exception as e:   # noqa: BLE001 — the read's to see
                call = steps.throw(e)
            else:
                call = steps.send(got)
    except StopIteration as done:
        return done.value


class DocReadOperation:
    """Executes a ReadRequest against one tablet's stores."""

    def __init__(self, codec: TableCodec, store: LsmStore,
                 scan_kernel: Optional[ScanKernel] = None,
                 device_cache=None, owner: str = ""):
        self.codec = codec
        self.store = store
        self.kernel = scan_kernel or _SHARED_KERNEL
        self.device_cache = device_cache
        # `/metrics` of the server that owns the tablet (`owner`)
        ent = metrics.REGISTRY.entity("server", owner or "docdb")
        self._m_facts_hits = ent.counter("store_facts_hits")
        self._m_facts_misses = ent.counter("store_facts_misses")

    # ---- point lookup ----------------------------------------------------
    def _mem_best(self, prefix: bytes, read_ht: int, restart_hi, mems):
        """Newest visible memtable version of one doc key as a
        (ht, write_id, key, value, None, None) tuple, or None."""
        plen = len(prefix)
        kht = ValueType.kHybridTime
        best = None
        for m in mems:
            if not m.may_contain_row(prefix):
                continue    # O(1) negative guard: most probes on
                #             read-heavy workloads miss the memtable
            for k, v in m.seek(prefix):
                if not k.startswith(prefix) or k[plen] != kht:
                    break
                dht = DocHybridTime.decode_desc(k[-ENCODED_SIZE:])
                ht = dht.ht.value
                if ht > read_ht:
                    if restart_hi is not None and ht <= restart_hi:
                        # concurrent write inside the uncertainty
                        # window: the writer's clock may be ahead
                        raise ReadRestartError(ht)
                    continue
                if best is None or (ht, dht.write_id) > best[:2]:
                    best = (ht, dht.write_id, k, v, None, None)
                break
        return best

    def _find_best(self, prefix: bytes, read_ht: int, restart_hi,
                   mems, ssts, keys_only: bool = False):
        """Newest visible version tuple (ht, write_id, key, value,
        block, pos) of one doc key across the snapshot, or None.
        `keys_only`: as `SstReader.point_find`."""
        best = self._mem_best(prefix, read_ht, restart_hi, mems)
        h = fnv64_bytes(prefix)
        for r in ssts:
            if not r.may_contain_hash(h):
                continue
            found = r.point_find(prefix, read_ht, restart_hi, keys_only)
            if found is None:
                continue
            if found[0] == "restart":
                raise ReadRestartError(found[1])
            c = found[1:]
            if best is None or c[:2] > best[:2]:
                best = c
        return best

    def _decode_best(self, best, read_ht: int):
        _, _, k, v, cb, pos = best
        if cb is not None:
            # columnar winner: direct single-row decode (no TTL wrapper
            # possible — TTL'd blocks never get a columnar sidecar)
            return self.codec.decode_block_row(cb, pos, k)
        v, expire = unwrap_ttl(v)
        if expire is not None and expire <= read_ht:
            return None
        return self.codec.decode_row(k, v)

    def _native_best(self, prefixes: List[bytes], ssts, read_ht: int,
                     restart_hi, want_cols=None):
        """Cross-SST merge of PointReader.find_many results: one C call
        per SST does bloom+bisect+MVCC-walk+extract for the whole key
        list. Returns (best, slow) where best[i] is the winning
        (ht, wid, row dict|None-for-tombstone) and slow is the set of
        key indices needing the per-key Python path (non-columnar
        blocks) — or None when any SST lacks a native reader."""
        readers = []
        for r in ssts:
            pr = r.point_reader(self.codec)
            if pr is None:
                return None
            readers.append(pr)
        n = len(prefixes)
        best: List = [None] * n
        slow: set = set()
        rh = -1 if restart_hi is None else restart_hi
        for pr in readers:
            for i, got in enumerate(pr.find_many(prefixes, read_ht, rh,
                                                 want_cols)):
                if got is None:
                    continue
                if got is NotImplemented:
                    slow.add(i)
                    continue
                if isinstance(got, int):
                    raise ReadRestartError(got)
                b = best[i]
                if b is None or got[:2] > b[:2]:
                    best[i] = got
        return best, slow

    def get_row(self, pk_row: Dict[str, object], read_ht: int,
                allow_restart: bool = False
                ) -> Optional[Dict[str, object]]:
        """Newest visible version across memtable + SSTs, using per-SST
        bloom filters and the native fused whole-SST lookup (reference:
        DocDBTableReader point-get over BlockBasedTable::Get). A
        non-empty memtable contributes its candidate via a cheap seek
        merged against the native SST result — mixed read/write
        workloads keep the C path for the expensive part."""
        prefix = self.codec.doc_key_prefix(pk_row)
        restart_hi = (read_ht + _skew_window_ht()
                      if allow_restart else None)
        mems, ssts = self.store.read_snapshot()
        got = self._native_best([prefix], ssts, read_ht, restart_hi)
        if got is not None:
            best, slow = got
            if not slow:
                mb = self._mem_best(prefix, read_ht, restart_hi, mems)
                nb = best[0]
                if mb is not None and (nb is None or mb[:2] > nb[:2]):
                    return self._decode_best(mb, read_ht)
                return nb[2] if nb is not None else None
        best = self._find_best(prefix, read_ht, restart_hi, mems, ssts)
        if best is None:
            return None
        return self._decode_best(best, read_ht)

    def key_is_live(self, pk_row: Dict[str, object], read_ht: int,
                    allow_restart: bool = False) -> bool:
        """`get_row(pk_row, read_ht) is not None` for a caller that
        wants no row — an INSERT's uniqueness gate.  Each SST's bloom
        filter first, then only the block the key would sit in, decoded
        without its value columns: a key that is new to the tablet costs
        no block at all but for the filter's false positives, where
        `get_row` builds a point reader over every block of every SST
        of the tablet the first time it is asked."""
        prefix = self.codec.doc_key_prefix(pk_row)
        restart_hi = read_ht + _skew_window_ht() if allow_restart else None
        mems, ssts = self.store.read_snapshot()
        best = self._find_best(prefix, read_ht, restart_hi, mems, ssts,
                               keys_only=True)
        if best is None:
            return False
        cb, pos = best[4], best[5]
        if cb is not None:
            # (a block with TTL'd rows has no columnar form)
            return not cb.tombstone[pos]
        return self._decode_best(best, read_ht) is not None

    def multi_get(self, pk_rows: Sequence[Dict[str, object]],
                  read_ht: int, allow_restart: bool = False,
                  columns=None) -> List[Optional[Dict[str, object]]]:
        """Batched point lookups: one snapshot, one restart window, one
        result list — the server-side batching seam concurrent sessions
        share (reference analog: operation buffering in pggate,
        src/yb/yql/pggate/pg_operation_buffer.cc, and MultiGet-style
        batched reads). The whole batch runs in ONE C call per SST
        (PointReader.find_many: bloom + block bisect + MVCC walk + row
        materialization); only keys touching non-columnar blocks or
        non-empty memtables take the per-key Python path."""
        restart_hi = (read_ht + _skew_window_ht()
                      if allow_restart else None)
        prefix_of = self.codec.doc_key_prefix
        prefixes = [prefix_of(r) for r in pk_rows]
        # C-side projection: rows materialize with ONLY these columns
        # (short range scans would otherwise decode 10 payload strings
        # per row just for the caller to drop them); memtable/slow-path
        # rows stay full and the caller's projection normalizes
        want = tuple(columns) if columns else None
        return self._multi_get_prefixes(prefixes, read_ht, restart_hi,
                                        want)

    def _multi_get_prefixes(self, prefixes: List[bytes], read_ht: int,
                            restart_hi, want=None
                            ) -> List[Optional[Dict[str, object]]]:
        mems, ssts = self.store.read_snapshot()
        n = len(prefixes)
        got = self._native_best(prefixes, ssts, read_ht, restart_hi,
                                want)
        if got is None:
            best: List = [None] * n
            slow = set(range(n))
        else:
            best, slow = got
        mem_active = [m for m in mems if not m.empty()]
        # direct prefix-set membership beats a method call per
        # (key, memtable) pair; a foreign-layout memtable disables the
        # shortcut and probes unconditionally
        mem_guarded = [m for m in mem_active if not m._foreign_layout]
        probe_all = len(mem_guarded) != len(mem_active)
        mem_sets = [m._row_prefixes for m in mem_guarded]
        if len(mem_sets) == 1:
            # the common steady state: one active memtable — a plain
            # set-membership beats an any() genexpr per key
            ms0 = mem_sets[0]
            mem_sets = None
        else:
            ms0 = None
        out: List[Optional[Dict[str, object]]] = []
        for i in range(n):
            if i in slow:
                f = self._find_best(prefixes[i], read_ht, restart_hi,
                                    mems, ssts)
                out.append(None if f is None
                           else self._decode_best(f, read_ht))
                continue
            b = best[i]
            if mem_active:
                p = prefixes[i]
                if probe_all or (p in ms0 if ms0 is not None
                                 else any(p in ms for ms in mem_sets)):
                    mb = self._mem_best(p, read_ht, restart_hi,
                                        mem_active)
                    if mb is not None and (b is None or mb[:2] > b[:2]):
                        out.append(self._decode_best(mb, read_ht))
                        continue
            out.append(b[2] if b is not None else None)
        return out

    def _enumerated_multi_get(self, hot, spec, keys, read_ht: int,
                              want, allow_restart: bool
                              ) -> List[Optional[Dict[str, object]]]:
        """Per-key path for enumerated scans: inline single-int key
        encoding (one native call per key, no per-key dict/genexpr
        wrapping) feeding the batched prefix MultiGet."""
        restart_hi = (read_ht + _skew_window_ht()
                      if allow_restart else None)
        enc = hot.encode_doc_key
        prefixes = [enc(spec, (int(k),)) for k in keys]
        return self._multi_get_prefixes(prefixes, read_ht, restart_hi,
                                        want)

    def _range_read_fused(self, hot, spec, keys: range, read_ht: int,
                          want, allow_restart: bool
                          ) -> List[Optional[Dict[str, object]]]:
        """Contiguous-int-key MultiGet through ONE C call
        (ybtpu_hot.range_read): key encode + per-SST bloom/bisect/MVCC
        walk + cross-SST merge + memtable-guard probe all happen below
        the interpreter; only keys the C side flags (memtable hit,
        non-columnar block, read restart) surface for per-key Python
        handling. Mirrors _multi_get_prefixes semantics exactly —
        falls back to it when the snapshot shape disqualifies the
        fused path (reader-less SST, multiple or foreign-layout
        memtables)."""
        restart_hi = (read_ht + _skew_window_ht()
                      if allow_restart else None)
        mems, ssts = self.store.read_snapshot()

        def fallback():
            return self._enumerated_multi_get(hot, spec, keys, read_ht,
                                              want, allow_restart)

        readers = []
        for r in ssts:
            pr = r.point_reader(self.codec)
            if pr is None:
                return fallback()
            readers.append(pr)
        mem_active = [m for m in mems if not m.empty()]
        if any(m._foreign_layout for m in mem_active) \
                or len(mem_active) > 1:
            return fallback()
        ms0 = mem_active[0]._row_prefixes if mem_active else None
        rh = -1 if restart_hi is None else restart_hi
        res = hot.range_read(spec, keys.start, keys.stop - 1,
                             tuple(readers), read_ht, rh, want, ms0)
        out: List[Optional[Dict[str, object]]] = []
        for item in res:
            if type(item) is not tuple:
                out.append(item)       # final row dict | None
                continue
            p, got = item
            if got is NotImplemented:
                f = self._find_best(p, read_ht, restart_hi, mems, ssts)
                out.append(None if f is None
                           else self._decode_best(f, read_ht))
                continue
            if isinstance(got, int):
                raise ReadRestartError(got)
            # memtable-guard hit: merge the memtable candidate against
            # the native winner by (commit ht, write id)
            mb = self._mem_best(p, read_ht, restart_hi, mem_active)
            if mb is not None and (got is None or mb[:2] > got[:2]):
                out.append(self._decode_best(mb, read_ht))
            else:
                out.append(got[2] if got is not None else None)
        return out

    # ---- scans -----------------------------------------------------------
    def execute(self, req: ReadRequest) -> ReadResponse:
        """`execute_steps`, every launch made on the calling thread."""
        return run_steps(self.execute_steps(req))

    def execute_steps(self, req: ReadRequest):
        """The read as a generator of steps.  Each launch it makes it
        yields as a call of no arguments — `ScanKernel.run` over the
        batch it holds: dispatch, wait for the device, read-back, which
        touch nothing of a store — and takes that call's result back;
        its value is the response.  Whoever drives it chooses the thread
        the launch runs and waits on: `run_steps` the calling one,
        `tablet/tablet.py serve_read` one beside the event loop.  Every
        structure of the store is read between the yields, on the
        driver's thread.  Other reads of the tablet may run while one is
        suspended; what it resumes with (batch, blocks, read time) it
        holds itself."""
        # one tablet's share of a read, as a span: block collection,
        # batch formation, the kernel's dispatch and the wait for its
        # result are its children; `route` is the path that served
        with _trace.TRACES.span("docdb.read", child_only=True) as sp:
            if req.server_assigned_read_ht:
                for _attempt in range(3):
                    try:
                        return (yield from self._execute_once_steps(req))
                    except ReadRestartError as e:
                        req.read_ht = e.restart_ht
                        sp.count("restarts")
            # explicit read points never restart; after 3 bumps serve at
            # the last restart point without further bumps
            return (yield from self._execute_once_steps(
                req, allow_restart=False))

    @staticmethod
    def _served(route: str, resp: ReadResponse) -> ReadResponse:
        """Name the route that served on the `docdb.read` span; the
        first name wins (`streaming` inside `tpu_aggregate`)."""
        sp = _trace.current_span()
        if sp.sampled and "route" not in sp.tags:
            sp.set_tag("route", route)
        return resp

    def _execute_once_steps(self, req: ReadRequest,
                            allow_restart: bool = True):
        # restarts engage only on server-assigned read points; the
        # answer is this request's, handed down as an argument: other
        # reads of the tablet run while this one is suspended in a yield
        allow_restart = bool(allow_restart and req.server_assigned_read_ht)
        if req.pk_eq is not None:
            read_ht = req.read_ht if req.read_ht is not None else _MAX_HT
            row = self.get_row(req.pk_eq, read_ht, allow_restart)
            rows = [self._project(row, req.columns)] if row is not None else []
            return self._served("point",
                                ReadResponse(rows=rows, backend="cpu"))
        if req.pk_prefix is not None:
            return self._served("prefix", self._prefix_scan(req))
        if req.join is not None and req.aggregates:
            return self._served(
                "join", self._execute_join_aggregate(req, allow_restart))
        if (not req.aggregates and req.where is not None
                and req.paging_state is None):
            got = self._hash_enumerated_read(req, allow_restart)
            if got is not None:
                return self._served("hash_enumerated",
                                    self._serve_window(req, got))
        if req.aggregates and self._tpu_eligible(req):
            resp = yield from self._execute_tpu_aggregate_steps(
                req, allow_restart)
            if resp is not None:
                return self._served("tpu_aggregate", resp)
        if (not req.aggregates and req.where is not None
                and req.paging_state is None and self._tpu_eligible(req)):
            resp = yield from self._execute_tpu_filter_steps(req)
            if resp is not None:
                return self._served("tpu_filter",
                                    self._serve_window(req, resp))
        return self._served("cpu", self._serve_window(
            req, self._execute_cpu(req, allow_restart)))

    def _serve_window(self, req: ReadRequest,
                      resp: ReadResponse) -> ReadResponse:
        """Server-side window pushdown boundary: a row response whose
        request carries a WindowWire gets its window values attached
        HERE, over the tablet's own visible post-WHERE rows
        (ops/window_scan.serve_window_rows — the same sort codes and
        segment-scan kernels the executor's device hook runs, so the
        served values are bitwise what the client tier would compute).
        Every refusal is typed on the response (window_reason) and the
        rows serve plain — the executor recomputes bit-identically,
        never silently."""
        if req.window is None or req.aggregates:
            return resp
        from ..ops.window_scan import (REASON_WINDOW_OFF,
                                       REASON_WINDOW_PAGED,
                                       WINDOW_STATS, WindowIneligible,
                                       serve_window_rows)
        try:
            if not flags.get("window_server_pushdown_enabled"):
                raise WindowIneligible(REASON_WINDOW_OFF)
            if req.paging_state is not None or req.limit is not None \
                    or resp.paging_state is not None:
                # a paged/limited scan serves a row SUBSET: window
                # frames need every partition row, so those shapes
                # always recompute above
                raise WindowIneligible(REASON_WINDOW_PAGED)
            serve_window_rows(req.window, resp.rows)
        except WindowIneligible as e:
            WINDOW_STATS["fallbacks"] += 1
            resp.window_reason = e.reason
            return resp
        resp.window_served = True
        return resp

    def _prefix_scan(self, req: ReadRequest) -> ReadResponse:
        """All visible rows whose doc key starts with the hash prefix
        (secondary-index lookup path)."""
        read_ht = req.read_ht if req.read_ht is not None else _MAX_HT
        prefix = self.codec.hash_prefix(req.pk_prefix)
        rows_out: List[Dict[str, object]] = []
        cur_prefix = None
        chosen = False
        from ..dockv.value import unwrap_ttl
        for k, v in self.store.iterate(lower=prefix):
            if not k.startswith(prefix):
                break
            marker = len(k) - _HT_SUFFIX
            p = k[:marker]
            if p != cur_prefix:
                cur_prefix = p
                chosen = False
            if chosen:
                continue
            dht = DocHybridTime.decode_desc(k[-ENCODED_SIZE:])
            if dht.ht.value > read_ht:
                continue
            chosen = True
            v, expire = unwrap_ttl(v)
            if expire is not None and expire <= read_ht:
                continue
            if v[0] == ValueKind.kTombstone:
                continue
            row = self.codec.decode_row(k, v)
            if row is not None:
                rows_out.append(self._project(row, req.columns))
                if req.limit is not None and len(rows_out) >= req.limit:
                    break
        return ReadResponse(rows=rows_out, backend="cpu")

    def _hash_enumerated_read(self, req: ReadRequest,
                              allow_restart: bool):
        """Short-range scans on a single-INTEGER-hash-PK table become
        batched point gets: hash sharding cannot seek key ranges, but a
        small enumerable target set (BETWEEN span, IN list, =) IS a
        MultiGet — the YCSB-E shape (reference: point segments in
        docdb/hybrid_scan_choices.cc; rocksdb MultiGet). Returns a
        ReadResponse or None when the shape doesn't apply."""
        schema = self.codec.info.schema
        kcs = schema.key_columns
        if (len(kcs) != 1 or kcs[0].type not in ("int32", "int64")
                or self.codec.info.partition_schema.kind != "hash"):
            return None
        w = req.where
        if (w is not None and w[0] == "between" and w[1][0] == "col"
                and w[1][1] == kcs[0].id and w[2][0] == "const"
                and w[3][0] == "const"
                and type(w[2][1]) is int and type(w[3][1]) is int):
            # the hot shape (YCSB-E: BETWEEN k AND k+9 on the int PK)
            # skips the generic conjunct walk entirely
            point_lists, interval, residual = \
                None, (kcs[0], w[2][1], w[3][1]), None
        else:
            point_lists, interval, residual = extract_scan_options(
                req.where, kcs)
        # constants outside the column's width can never match a stored
        # key (and would overflow the key encoder) — clamp/drop them,
        # matching what the row-wise filter would return
        kmin, kmax = ((-2**31, 2**31 - 1) if kcs[0].type == "int32"
                      else (-2**63, 2**63 - 1))
        if point_lists:
            keys = [k for k in point_lists[0][1] if kmin <= k <= kmax]
        elif interval is not None and interval[1] is not None \
                and interval[2] is not None:
            lo = max(int(interval[1]), kmin)
            hi = min(int(interval[2]), kmax)
            if hi - lo + 1 > flags.get("hash_scan_enumerate_max"):
                return None
            keys = range(lo, hi + 1)
        else:
            return None
        if len(keys) > flags.get("hash_scan_enumerate_max"):
            return None
        name = kcs[0].name
        read_ht = req.read_ht if req.read_ht is not None else _MAX_HT
        # residual predicates need their referenced columns too — only
        # project in C when the bounds consumed the whole WHERE
        want = tuple(req.columns) if (req.columns and residual is None) \
            else None
        hot = _hot_mod()
        spec = getattr(self.codec, "_key_spec", None)
        if (hot is not None and spec is not None
                and isinstance(keys, range) and keys
                and len(keys) < 1_000_000
                and hasattr(hot, "range_read")):
            rows = self._range_read_fused(hot, spec, keys, read_ht, want,
                                          allow_restart)
        elif hot is not None and spec is not None:
            rows = self._enumerated_multi_get(hot, spec, keys, read_ht,
                                              want, allow_restart)
        else:
            rows = self.multi_get([{name: int(k)} for k in keys],
                                  read_ht, allow_restart=allow_restart,
                                  columns=want)
        by_id = {c.name: c.id for c in schema.columns}
        out = []
        nwant = len(want) if want else -1
        for r in rows:
            if r is None:
                continue
            if residual is not None:
                idrow = {by_id[n]: v for n, v in r.items()}
                if eval_expr_py(residual, idrow) is not True:
                    continue
            # rows the native reader projected are already final;
            # memtable/slow-path rows are full and still need the cut
            out.append(r if len(r) == nwant
                       else self._project(r, req.columns))
            if req.limit is not None and len(out) >= req.limit:
                break
        return ReadResponse(rows=out, backend="cpu")

    def _tpu_eligible(self, req: ReadRequest) -> bool:
        if not flags.get("tpu_pushdown_enabled"):
            return False
        from ..ops.expr import device_compatible
        compatible = device_compatible
        json_cols = set(getattr(self.codec, "shred_cols", ()))
        if json_cols and flags.get("doc_shred_enabled"):
            # doc-path shapes MAY rewrite onto shredded lanes — judge
            # the rest of the expression with doc shapes neutralized
            # (the block-level rewrite still falls back typed when a
            # path turns out unshredded/heterogeneous)
            from ..docstore.pushdown import doc_compatible

            def compatible(n, _jc=json_cols):
                return doc_compatible(n, _jc)
        if req.where is not None and not compatible(req.where):
            return False
        for a in req.aggregates:
            if a.expr is not None and not compatible(a.expr):
                return False
        approx_rows = sum(r.num_entries for r in self.store.ssts)
        return approx_rows >= flags.get("tpu_min_rows_for_pushdown")

    def _maybe_doc_rewrite(self, req: ReadRequest, blocks):
        """Doc-path pushdown (docstore/): when the request references
        JSON paths, rewrite them onto shredded virtual lanes (blocks
        mutated in place by attach_shredded) and return a request in
        vcid space.  Returns `req` unchanged when no doc shapes are
        present; None when the shapes can't be served bit-identically
        (typed fallback recorded — caller takes the interpreted
        path)."""
        json_cols = set(getattr(self.codec, "shred_cols", ()))
        if not json_cols:
            return req
        from ..docstore import pushdown as _doc
        if not _doc.exprs_have_doc(req.where, req.aggregates):
            return req
        from ..docstore.errors import REASON_OFF, DocIneligible
        if not flags.get("doc_shred_enabled"):
            _doc.record_fallback(REASON_OFF)
            return None
        try:
            where, aggs, _refs, attached = _doc.prepare_doc_scan(
                req.where, req.aggregates, blocks, json_cols)
        except DocIneligible as e:
            _doc.record_fallback(e.reason)
            return None
        # the attached lanes live on scan-lifetime CLONES — splice them
        # into the caller's list so the shared cached originals (also
        # read by compaction/point reads) stay untouched
        blocks[:] = attached
        from dataclasses import replace
        return replace(req, where=where, aggregates=aggs)

    def _collect_blocks(self, columns=None
                        ) -> Optional[List[ColumnarBlock]]:
        """All columnar blocks across SSTs + a block built from memtable
        contents; None if any source can't provide columnar form.
        `columns`: the value-column ids the caller will read — the SST
        blocks are then projected to them (`SstReader.projected_block`),
        decoded for this caller alone and not through the SST's block
        cache, which a table larger than it only churns."""
        with _trace.TRACES.span("docdb.collect_blocks",
                                child_only=True) as sp:
            sp.set_tag("step", "collect")
            sp.set_tag("ssts", len(self.store.ssts))
            blocks: List[ColumnarBlock] = []
            for r in self.store.ssts:
                for i in range(r.num_blocks()):
                    cb = (r.columnar_block(i) if columns is None
                          else r.projected_block(i, columns))
                    if cb is None:
                        return None
                    blocks.append(cb)
            mem_entries = list(self.store._mem.iterate())
            for m in self.store._frozen:
                mem_entries += list(m.iterate())
            if mem_entries:
                mem_entries.sort()
                cb = self.codec.columnar_builder(mem_entries)
                if cb is None:
                    return None
                cb.unique_keys = False  # overlaps SSTs in general
                blocks.append(cb)
            if len(self.store.ssts) > 1 or (mem_entries
                                            and self.store.ssts):
                for b in blocks:
                    b.unique_keys = b.unique_keys and len(blocks) == 1
            sp.set_tag("blocks", len(blocks))
            return blocks

    def _collect_with_facts(self):
        """``(blocks, facts)`` for a device read: the store's whole block
        list and the :class:`StoreFacts` of its contents — looked up
        where nothing was written since they were made (`facts` = `hit`
        on the `docdb.read` span), made in one pass over the blocks
        otherwise.  ``(None, None)`` without blocks."""
        store = self.store
        # named BEFORE the blocks are collected: a write that lands in
        # between leaves facts of newer contents under the older name,
        # which no later read asks for — never older facts under a
        # newer name
        key = (tuple(r.path for r in store.ssts), store.write_generation())
        blocks = self._collect_blocks()
        if not blocks:
            return None, None
        facts = store.read_facts
        hit = facts is not None and facts.key == key
        if not hit:
            facts = store.read_facts = StoreFacts(
                key, max((int(b.ht.max()) for b in blocks if b.n),
                         default=0),
                chunk_safe_mvcc(blocks))
        (self._m_facts_hits if hit else self._m_facts_misses).increment()
        sp = _trace.current_span()
        if sp.sampled:
            sp.set_tag("facts", "hit" if hit else "miss")
        return blocks, facts

    # --- string predicates on device (dictionary rewrite) -----------------
    class _Unrewritable(Exception):
        pass

    @classmethod
    def rewrite_where_and_aggs(cls, where, aggs, dicts,
                               allow_dict_minmax: bool = True):
        """Apply :meth:`_rewrite_strings` to a WHERE node and every
        AggSpec expr in one shot — ``(where, aggs)`` in dictionary-code
        space.  THE one rewrite entry shared by the monolithic device
        path, the streaming dictionary plan and the bypass twin, so the
        three routes cannot drift.  Raises ``_Unrewritable``; callers
        pick their fallback (device paths return None, bypass raises a
        typed reason).

        ``allow_dict_minmax``: MIN/MAX/COUNT over a bare dictionary
        (string) column pass through as-is — the kernel aggregates the
        CODES lane (sorted dictionary: code order IS string order) and
        the caller decodes the winning code back through the
        scan-global dictionary (:func:`dict_minmax_decode`).  Routes
        with no decode step (the fused plan kernel) pass False and
        keep the historical typed refusal."""
        if where is not None:
            where = cls._rewrite_strings(where, dicts)
        out = []
        for a in aggs:
            e = a.expr
            if e is None:
                out.append(a)
                continue
            if allow_dict_minmax and a.op in ("min", "max", "count") \
                    and isinstance(e, (tuple, list)) and e \
                    and e[0] == "col" and e[1] in dicts:
                out.append(a)          # codes lane serves it directly
                continue
            out.append(AggSpec(a.op, cls._rewrite_strings(e, dicts)))
        return where, tuple(out)

    @classmethod
    def _rewrite_strings(cls, node, dicts):
        """Translate string predicates into dictionary-code space so
        they run in the device kernel (SURVEY §7 hard-part 3; reference:
        varlen handling in dockv/schema_packing.h + pushdown eval).
        The per-batch dictionary is SORTED, so ordering predicates map
        to code ranges; equality/IN map to exact codes; LIKE (and any
        other string function) evaluates host-side over the dictionary
        into a boolean LUT the kernel gathers. Raises _Unrewritable
        when a string column is used outside these shapes."""
        import bisect
        kind = node[0]

        def is_dict_col(x):
            return (isinstance(x, (tuple, list)) and x
                    and x[0] == "col" and x[1] in dicts)

        def is_const_str(x):
            return (isinstance(x, (tuple, list)) and x
                    and x[0] == "const" and isinstance(x[1], str))

        if kind == "cmp":
            op, l, r = node[1], node[2], node[3]
            if is_dict_col(l) and is_const_str(r):
                d = dicts[l[1]]
                v = r[1]
                if op in ("eq", "ne"):
                    i = bisect.bisect_left(d, v)
                    code = i if i < len(d) and d[i] == v else -1
                    return ("cmp", op, l, ("const", code))
                if op == "lt":
                    return ("cmp", "lt", l,
                            ("const", bisect.bisect_left(d, v)))
                if op == "le":
                    return ("cmp", "lt", l,
                            ("const", bisect.bisect_right(d, v)))
                if op == "gt":
                    return ("cmp", "ge", l,
                            ("const", bisect.bisect_right(d, v)))
                if op == "ge":
                    return ("cmp", "ge", l,
                            ("const", bisect.bisect_left(d, v)))
            if is_dict_col(r) and is_const_str(l):
                flip = {"lt": "gt", "le": "ge", "gt": "lt", "ge": "le",
                        "eq": "eq", "ne": "ne"}
                return cls._rewrite_strings(
                    ("cmp", flip[op], r, l), dicts)
            if is_dict_col(l) or is_dict_col(r):
                raise cls._Unrewritable(node)
            # neither side is directly a string column: still recurse —
            # a nested expr may contain one (and must then fail or
            # rewrite), falling through to the generic walk below
        elif kind == "between":
            x, lo, hi = node[1], node[2], node[3]
            if is_dict_col(x):
                if not (is_const_str(lo) and is_const_str(hi)):
                    raise cls._Unrewritable(node)
                return ("and",
                        cls._rewrite_strings(("cmp", "ge", x, lo), dicts),
                        cls._rewrite_strings(("cmp", "le", x, hi), dicts))
        elif kind == "in":
            x, vals = node[1], node[2]
            if is_dict_col(x):
                d = dicts[x[1]]
                codes = []
                for v in vals:
                    if not isinstance(v, str):
                        raise cls._Unrewritable(node)
                    i = bisect.bisect_left(d, v)
                    codes.append(int(i) if i < len(d) and d[i] == v
                                 else -1)
                return ("in", x, codes)
            # generic walk must not treat the VALUES list as a node
            return ("in", cls._rewrite_strings(x, dicts), vals)
        if kind in ("like", "ilike"):
            x, pattern = node[1], node[2]
            if not is_dict_col(x):
                raise cls._Unrewritable(node)
            import re as _re
            pat = _re.compile(
                "^" + _re.escape(pattern).replace("%", ".*")
                .replace("_", ".") + "$",
                _re.IGNORECASE if kind == "ilike" else 0)
            d = dicts[x[1]]
            lut = [1 if pat.match(s) else 0 for s in d]
            return ("dictlut", x, lut)
        if kind == "isnull":
            x = node[1]
            if is_dict_col(x):
                # null-mask read only — codes are never compared, so
                # IS NULL over a dictionary column needs no rewrite
                return node
        if kind == "col" and node[1] in dicts:
            # a bare string column outside a rewritable predicate
            raise cls._Unrewritable(node)
        if kind in ("const",):
            return node
        out = [kind]
        for c in node[1:]:
            if isinstance(c, (tuple, list)) and c and \
                    isinstance(c[0], str):
                out.append(cls._rewrite_strings(c, dicts))
            else:
                out.append(c)
        return tuple(out)

    def _batch_cache_key(self, needed) -> tuple:
        """THE device-cache key for batches over this store's current
        contents. Every flag that affects batch formation must be in
        here: device_float_dtype is runtime-settable and baked into the
        batch dtype at build time. Shared by the monolithic and
        streaming paths (the streaming path appends its chunk plan), so
        a new formation-affecting flag is added in exactly one place."""
        return (id(self.store), tuple(sorted(needed)),
                tuple(r.path for r in self.store.ssts),
                self.store.write_generation(),
                flags.get("device_float_dtype"))

    def _cached_batch(self, blocks, needed, extra: tuple,
                      facts: StoreFacts):
        """Build (or fetch from the device cache) the columnar batch for
        `needed` columns. `extra` extends the cache key — the zone-map
        prune signature rides here so a batch built from one predicate's
        pruned block set never serves another predicate.
        Unless the store's whole block list is proved one version a key
        (`facts.chunk_safe`: it is not with several SSTs or a memtable
        overlay), the batch links its row versions when it is built
        (`next_ht`) and is served `linked`; a property of the store's
        contents, which the key already names (SST paths, write
        generation)."""
        miss = False

        def build():
            nonlocal miss
            miss = True
            return build_batch(blocks, sorted(needed),
                               multi_version=not facts.chunk_safe)

        with _trace.TRACES.span("docdb.batch", child_only=True) as sp:
            if self.device_cache is None:
                batch = build()
            else:
                batch = self.device_cache.get_or_build(
                    self._batch_cache_key(needed) + extra, build)
            if sp.sampled:
                sp.set_tag("cache", "miss" if miss else "hit")
                sp.set_tag("rows", batch.n_rows)
                sp.set_tag("bytes", batch_bytes(batch))
            return batch

    def _zone_prune(self, blocks, where, read_ht, chunk_safe: bool):
        """Zone-map block pruning for the monolithic pushdown paths:
        (kept_blocks, cache_key_extra). MVCC-gated exactly like the
        streaming path — pruning is only sound when every doc key lives
        wholly inside one block (`chunk_safe`: the proof over the FULL
        list, `StoreFacts.chunk_safe`), since dropping a block may
        otherwise unmask an older version of a key that survives
        elsewhere."""
        if where is None or not flags.get("zone_map_pruning"):
            return blocks, ()
        # the second step of `docdb.collect_blocks`: which of the
        # collected blocks the batch is formed from
        with _trace.TRACES.span("docdb.collect_blocks",
                                child_only=True) as sp:
            sp.set_tag("step", "zone_prune")
            sp.set_tag("blocks", len(blocks))
            sp.set_tag("pruned", 0)
            # a read point ALWAYS flows into the kernel's MVCC selection
            # in these paths (even _MAX_HT), so the chunk-safety proof is
            # unconditionally required before dropping any block
            if read_ht is not None and not chunk_safe:
                return blocks, ()
            from ..ops.scan import zone_prune_blocks
            kept, kept_idx = zone_prune_blocks(blocks, where)
            if len(kept) == len(blocks):
                return blocks, ()
            sp.set_tag("pruned", len(blocks) - len(kept))
            return kept, ("zp", kept_idx)

    def _try_streaming_aggregate(self, req: ReadRequest, blocks, needed,
                                 read_ht: int, facts: StoreFacts,
                                 allow_restart: bool):
        """Chunked pipelined aggregate (ops/stream_scan.py) for scans it
        can serve exactly; None falls through to the monolithic batch.
        Hash grouping and MVCC-unsafe block sequences are rejected
        inside streaming_scan_aggregate; string (dictionary) columns —
        predicates and DictGroupSpec group keys — stream through the
        scan-global dictionary plan.  Returns ``_SPILLED`` when a
        dict-grouped scan overflowed its slot budget: the monolithic
        batch would spill identically (same dictionaries, same slot
        bucket), so the caller must go STRAIGHT to the interpreted
        GROUP BY instead of paying a second full device pass."""
        if not flags.get("streaming_scan_enabled"):
            return None
        from ..ops.stream_scan import streaming_scan_aggregate
        from ..ops.scan import _expand_avg
        cache = self.device_cache
        key = (self._batch_cache_key(needed)
               if cache is not None else None)
        expanded = tuple(_expand_avg(req.aggregates))
        minmax = [i for i, a in enumerate(expanded)
                  if a.op in ("min", "max")]
        aggs_run = expanded + tuple(AggSpec("count", expanded[i].expr)
                                    for i in minmax)
        dict_group = isinstance(req.group_by, DictGroupSpec)
        grouped_out: Optional[dict] = {} if dict_group else None
        dict_out: dict = {}
        got = streaming_scan_aggregate(
            blocks, sorted(needed), req.where, aggs_run, req.group_by,
            read_ht, kernel=self.kernel, cache=cache, cache_key=key,
            grouped_out=grouped_out, dict_out=dict_out,
            chunk_safe=facts.chunk_safe, plans=self._kept_plans(facts))
        if got is None:
            return None
        if dict_group and grouped_out.get("spill"):
            # slot overflow: slots BELOW the spill slot still hold exact
            # per-group partials (every in-range row scattered to its own
            # slot regardless of the overflow) — only the spill slot
            # aggregated an unknown mix.  The partial-spill merge keeps
            # the hot device partials and re-aggregates just the spilled
            # rows on the interpreted tail; when it can't run, revert to
            # the full interpreted re-scan as before.
            from ..ops.grouped_scan import GROUPED_STATS
            if flags.get("grouped_spill_merge_enabled"):
                # restart window over the FULL pre-prune block list,
                # exactly like the normal streamed path and the
                # interpreted re-scan — a zone-pruned block's
                # ambiguous-HT rows must keep forcing the restart
                self._check_restart_window(blocks, read_ht,
                                           allow_restart, facts)
                resp = self._grouped_spill_merge(
                    req, grouped_out, expanded, minmax, aggs_run, got,
                    read_ht)
                if resp is not None:
                    GROUPED_STATS["spill_merges"] += 1
                    return resp
            GROUPED_STATS["spill_fallbacks"] += 1
            return _SPILLED
        # uncertainty-window restart check only once the streaming path
        # is actually serving the read — a scan that falls through to
        # the monolithic/CPU paths keeps their own (possibly narrower)
        # restart behavior, exactly as before this path existed
        self._check_restart_window(blocks, read_ht, allow_restart, facts)
        outs, counts = got
        outs = _nullify_minmax(expanded, minmax, outs)
        outs = dict_minmax_decode(expanded, outs,
                                  dict_out.get("dicts") or {})
        if dict_group:
            from ..ops.grouped_scan import decode_slot_groups
            outs_c, counts_c, gvals = decode_slot_groups(
                req.group_by, grouped_out["dicts"], outs, counts)
            return ReadResponse(agg_values=outs_c,
                                group_counts=counts_c,
                                group_values=gvals, backend="tpu")
        return ReadResponse(agg_values=outs,
                            group_counts=np.asarray(counts),
                            backend="tpu")

    def _grouped_spill_merge(self, req: ReadRequest, gout: dict,
                             expanded, minmax, aggs_run, got,
                             read_ht: int) -> Optional[ReadResponse]:
        """Partial-spill merge (PR-9 named follow-on): device slots
        below the spill slot keep their exact partials; rows whose
        group id landed at/past it re-aggregate on the interpreted
        tail (same WHERE, same MVCC-visible mask — valid because the
        streamed path already proved the blocks chunk-safe, i.e. one
        visible version per doc key); the two partials combine through
        the shared group-keyed combine.  The partials are DISJOINT by
        construction (a group's id is fixed: it is either in range or
        spilled), so the combine is a pure union.  Returns None when
        the merge can't run — caller reverts to the full re-scan."""
        plan = gout.get("plan")
        blocks = gout.get("blocks")
        if plan is None or not blocks:
            return None
        spec = req.group_by
        dicts = gout["dicts"]
        spill_slot = gout["num_slots"] - 1
        outs, counts = got
        counts_hot = np.asarray(counts).copy()
        counts_hot[spill_slot:] = 0
        from ..ops.grouped_scan import decode_slot_groups
        # dict-code MIN/MAX lanes decode to strings BEFORE the combine:
        # the interpreted tail's partials are strings (it min/maxes the
        # actual payload), and codes must never mix with them
        dev_outs = dict_minmax_decode(
            tuple(aggs_run), [np.asarray(o) for o in outs], dicts)
        dev_part = decode_slot_groups(spec, dicts, dev_outs, counts_hot)
        # replay the device's group-id encoding over the SAME remapped
        # codes to find which rows spilled
        gid = None
        gnull = None
        stride = 1
        for cid in spec.cols:
            codes = np.concatenate(
                [plan.block_codes(cid, b) for b in blocks])
            nl = np.concatenate(
                [np.asarray(b.varlen[cid][2], bool) for b in blocks])
            gid = (codes.astype(np.int64) * stride if gid is None
                   else gid + codes.astype(np.int64) * stride)
            gnull = nl if gnull is None else (gnull | nl)
            stride *= max(len(dicts[cid]), 1)
        ht = np.concatenate([b.ht for b in blocks])
        tomb = np.concatenate([b.tombstone for b in blocks])
        vis = (ht <= np.uint64(read_ht)) & ~tomb
        sel = np.flatnonzero(vis & ~gnull & (gid >= spill_slot))
        return self._spill_merge_tail(req, blocks, sel, aggs_run,
                                      expanded, minmax, dev_part)

    def _spill_merge_tail(self, req: ReadRequest, blocks, sel,
                          aggs_run, expanded, minmax, dev_part
                          ) -> Optional[ReadResponse]:
        """Shared spill-merge tail (streamed AND monolithic routes):
        gather the spilled rows from the columnar blocks, re-aggregate
        them on the interpreted fold (same WHERE), and union with the
        exact device partials through the group-keyed combine.  The
        partials are DISJOINT by construction (a group's id is fixed:
        either in range or spilled).  None when the gather can't run —
        caller reverts to the full interpreted re-scan."""
        spec = req.group_by
        schema = self.codec.schema
        from ..ops.expr import referenced_columns
        needed = set(spec.cols)
        if req.where is not None:
            referenced_columns(req.where, needed)
        for a in req.aggregates:
            if a.expr is not None:
                referenced_columns(a.expr, needed)
        by_id = {c.id: c for c in schema.columns}
        if any(c not in by_id for c in needed):
            return None
        proj = [by_id[c] for c in sorted(needed)]
        rows = self._gather_rows(blocks, sel, proj)
        if rows is None:
            return None
        aggs_list = list(aggs_run)
        dummy_state = [None] * len(aggs_list)
        group_state: Dict[object, list] = {}
        name_to_id = {c.name: c.id for c in schema.columns}
        for row in rows:
            idrow = {name_to_id[nm]: v for nm, v in row.items()}
            if req.where is not None and \
                    eval_expr_py(req.where, idrow) is not True:
                continue
            _agg_accumulate(aggs_list, dummy_state, group_state, spec,
                            idrow)
        tail = _grouped_cpu_response(aggs_list, group_state, spec)
        from ..ops.scan import combine_grouped_partials
        merged_outs, merged_counts, merged_gvals = \
            combine_grouped_partials(
                tuple(aggs_run),
                [dev_part, (tail.agg_values, tail.group_counts,
                            tail.group_values)])
        # (the caller already ran the restart-window check over the
        # FULL pre-prune block list)
        outs_f = _nullify_minmax(expanded, minmax, merged_outs)
        return ReadResponse(agg_values=outs_f,
                            group_counts=merged_counts,
                            group_values=merged_gvals, backend="tpu")

    def _monolithic_spill_merge(self, req: ReadRequest, gspec, batch,
                                blocks, expanded, minmax, aggs_run,
                                outs, counts, mask
                                ) -> Optional[ReadResponse]:
        """Monolithic twin of the partial-spill merge (ROADMAP TPC-H
        item (c)): the dict-group host codes are ALREADY device lanes
        in ``batch.cols``, and ``mask`` — a filter launch's, at the
        aggregate's read point — folds visibility and WHERE, so the
        spilled row set is mask & (gid >= spill_slot) less the rows
        with a NULL group value (which the grouped kernel leaves out),
        replayed host-side.  Slots below the spill slot keep their
        exact partials; the spilled rows re-aggregate on the shared
        interpreted tail."""
        from ..ops.grouped_scan import decode_slot_groups, resolve_group
        n = batch.n_rows
        try:
            resolved, domains = resolve_group(gspec, batch.dicts)
        except KeyError:
            return None
        spill_slot = resolved.num_slots - 1
        gid = np.zeros(n, np.int64)
        stride = 1
        for cid, dom in zip(gspec.cols, domains):
            if cid not in batch.cols:
                return None
            gid += np.asarray(batch.cols[cid])[:n].astype(np.int64) \
                * stride
            stride *= dom
        counts_hot = np.asarray(counts).copy()
        counts_hot[spill_slot:] = 0
        dev_outs = dict_minmax_decode(
            tuple(aggs_run), [np.asarray(o) for o in outs],
            batch.dicts)
        dev_part = decode_slot_groups(gspec, batch.dicts, dev_outs,
                                      counts_hot)
        spilled = np.asarray(mask)[:n] & (gid >= spill_slot)
        for cid in gspec.cols:
            if batch.nulls.get(cid) is not None:
                spilled &= ~np.asarray(batch.nulls[cid])[:n]
        sel = np.flatnonzero(spilled)
        return self._spill_merge_tail(req, blocks, sel, aggs_run,
                                      expanded, minmax, dev_part)

    def _kept_plans(self, facts: StoreFacts) -> dict:
        return facts.plans.setdefault(self.codec.info.table_id, {})

    def _check_restart_window(self, blocks, read_ht: int,
                              allow_restart: bool,
                              facts: Optional[StoreFacts] = None) -> None:
        """Raise ReadRestartError when any block holds a record inside
        (read_ht, read_ht + skew] — the coarse whole-block uncertainty
        check shared by the monolithic and streaming aggregate paths.
        `facts`: those of the store `blocks` is the whole list of; a
        read at or above its newest write time walks nothing."""
        if not (allow_restart and read_ht != _MAX_HT):
            return
        if facts is not None and facts.max_ht <= read_ht:
            return      # no record newer than the read: none in the window
        self._walk_restart_window(blocks, read_ht)

    @staticmethod
    def _walk_restart_window(blocks, read_ht: int) -> None:
        """Every block's `ht` lane against the window; the restart time
        is the newest record of the first block that holds one."""
        window_hi = read_ht + _skew_window_ht()
        for b in blocks:
            amb = b.ht[(b.ht > np.uint64(read_ht))
                       & (b.ht <= np.uint64(window_hi))]
            if len(amb):
                raise ReadRestartError(int(amb.max()))

    def _execute_tpu_aggregate_steps(self, req: ReadRequest,
                                     allow_restart: bool):
        """Steps (`execute_steps`) to the response, or None where the
        device cannot serve the aggregate."""
        blocks, facts = self._collect_with_facts()
        if not blocks:
            return None
        req = self._maybe_doc_rewrite(req, blocks)
        if req is None:
            return None     # typed doc fallback: interpreted row path
        needed = set()
        from ..ops.expr import referenced_columns
        if req.where is not None:
            referenced_columns(req.where, needed)
        for a in req.aggregates:
            if a.expr is not None:
                referenced_columns(a.expr, needed)
        if isinstance(req.group_by, (HashGroupSpec, DictGroupSpec)):
            needed.update(req.group_by.cols)
        elif req.group_by is not None:
            needed.update(cid for cid, _, _ in req.group_by.cols)
        if isinstance(req.group_by, DictGroupSpec) \
                and not flags.get("grouped_pushdown_enabled"):
            return None     # interpreted GROUP BY (the flag-off path)
        read_ht = req.read_ht if req.read_ht is not None else _MAX_HT
        resp = self._try_streaming_aggregate(req, blocks, needed, read_ht,
                                             facts, allow_restart)
        if resp is _SPILLED:
            return None     # over-cardinality: interpreted GROUP BY
        if resp is not None:
            return self._served("streaming", resp)
        # zone-map pruning ahead of the monolithic batch build; the
        # restart window below still checks the FULL block list (a
        # pruned block's ambiguous-HT rows keep today's restart
        # behavior)
        kept, prune_key = self._zone_prune(blocks, req.where, read_ht,
                                           facts.chunk_safe)
        try:
            batch = self._cached_batch(kept, needed, prune_key, facts)
        except KeyError:
            return None   # some column lacks columnar form → CPU path
        self._check_restart_window(blocks, read_ht, allow_restart, facts)
        return (yield from self.aggregate_on_batch_steps(
            req, batch,
            lambda where, aggs, group: self.kernel.run(
                batch, where, aggs, group, read_ht),
            lambda *partials: self._monolithic_spill_merge(
                req, req.group_by, batch, kept, *partials)))

    @classmethod
    def aggregate_on_batch_steps(cls, req: ReadRequest, batch, run,
                                 on_spill=None):
        """The request's aggregates over one cached batch — a
        `DeviceBatch`, or a `ShardedBatch` that covers several tablets
        (docdb/mesh_read.py) — as steps (`execute_steps`): string shapes
        rewritten into the batch's code space, the kernel launched
        through `run(where, aggs, group)` (what `ScanKernel.run`
        returns; the call is yielded, and its result taken back), the
        result decoded.
        None = a shape the device cannot serve exactly; the caller
        falls back.  `on_spill(expanded, minmax, aggs_run, outs, counts,
        mask)` may serve a dictionary-grouped scan that overflowed its
        slot budget; `mask` is the row mask of a filter launch through
        `run` (the aggregate launch returns none)."""
        where = req.where
        aggregates = req.aggregates
        if where is not None or any(a.expr is not None
                                    for a in aggregates):
            # runs even with no dictionaries: a leftover 'like' (or any
            # string shape the kernel can't compile) must fall back
            try:
                where, aggregates = cls.rewrite_where_and_aggs(
                    where, aggregates, batch.dicts)
            except cls._Unrewritable:
                return None   # string column outside a rewritable shape
        # SQL NULL semantics for MIN/MAX over zero qualifying inputs:
        # the kernel returns a dtype sentinel there, so run a hidden
        # companion COUNT per min/max aggregate and replace sentinel
        # results with None host-side (the CPU twin returns None too)
        from ..ops.scan import _expand_avg
        expanded = tuple(_expand_avg(aggregates))
        minmax = [i for i, a in enumerate(expanded)
                  if a.op in ("min", "max")]
        aggs_run = expanded + tuple(AggSpec("count", expanded[i].expr)
                                    for i in minmax)

        def _nullify(outs):
            return dict_minmax_decode(
                expanded, _nullify_minmax(expanded, minmax, outs),
                batch.dicts)

        if isinstance(req.group_by, HashGroupSpec):
            outs, counts, _, gvals, n_groups = yield partial(
                run, where, aggs_run, req.group_by)
            if int(n_groups) > req.group_by.max_groups:
                return None     # distinct-group overflow: CPU fallback
            return ReadResponse(
                agg_values=_nullify(outs),
                group_counts=np.asarray(counts),
                group_values=tuple(np.asarray(g) for g in gvals),
                backend="tpu")
        if isinstance(req.group_by, DictGroupSpec):
            from ..ops.grouped_scan import (GROUPED_STATS,
                                            decode_slot_groups,
                                            domain_product)
            gspec = req.group_by
            if any(c not in batch.dicts for c in gspec.cols) or \
                    domain_product(gspec, batch.dicts) >= 2 ** 31:
                return None     # no dictionary / gid would wrap: CPU
            outs, counts, _, spill = yield partial(
                run, where, aggs_run, gspec)
            if int(spill) > 0:
                # slot overflow on the MONOLITHIC dict-group route:
                # same partial-spill merge as the streamed path — keep
                # the exact in-range device partials, re-aggregate only
                # the spilled rows on the interpreted fold.  An
                # aggregate launch returns no row mask: a filter launch
                # at the same read point gives the one that folds
                # visibility and WHERE, read back inside its launch.
                if on_spill is not None \
                        and flags.get("grouped_spill_merge_enabled"):
                    mask = yield lambda: np.asarray(
                        run(where, (), None)[2])
                    resp = on_spill(expanded, minmax, aggs_run, outs,
                                    counts, mask)
                    if resp is not None:
                        GROUPED_STATS["spill_merges"] += 1
                        return resp
                GROUPED_STATS["spill_fallbacks"] += 1
                return None     # slot overflow: interpreted GROUP BY
            outs_c, counts_c, gvals = decode_slot_groups(
                gspec, batch.dicts, _nullify(outs), counts)
            return ReadResponse(agg_values=outs_c,
                                group_counts=counts_c,
                                group_values=gvals, backend="tpu")
        outs, counts, _ = yield partial(run, where, aggs_run,
                                        req.group_by)
        return ReadResponse(agg_values=_nullify(outs),
                            group_counts=np.asarray(counts),
                            backend="tpu")

    # ---- FK-equijoin pushdown (ReadRequest.join) -------------------------
    def _join_eligible(self, req: ReadRequest) -> bool:
        if not flags.get("tpu_pushdown_enabled"):
            return False
        from ..ops.expr import device_compatible
        if req.where is not None and not device_compatible(req.where):
            return False
        for a in req.aggregates:
            if a.expr is not None and not device_compatible(a.expr):
                return False
        approx_rows = sum(r.num_entries for r in self.store.ssts)
        return approx_rows >= flags.get("tpu_min_rows_for_pushdown")

    def _execute_join_aggregate(self, req: ReadRequest,
                                allow_restart: bool) -> ReadResponse:
        """Aggregate request with a shipped build side: the fused-plan
        device path (filter -> probe -> gather -> group -> aggregate in
        ONE program, ops/plan_fusion.py) when eligible, the interpreted
        row-at-a-time join otherwise — typed JoinIneligible refusals
        and every device-ineligible shape land on the same interpreted
        path, so the answer never depends on which path ran."""
        from ..ops.join_scan import JOIN_STATS, JoinIneligible
        if flags.get("join_pushdown_enabled") and \
                self._join_eligible(req):
            try:
                resp = self._execute_fused_join(req, allow_restart)
                if resp is not None:
                    return resp
            except JoinIneligible:
                JOIN_STATS["fallbacks"] += 1
        return self._execute_join_cpu(req, allow_restart)

    def _execute_fused_join(self, req: ReadRequest, allow_restart: bool
                            ) -> Optional[ReadResponse]:
        from ..ops.join_scan import BUILD_COL_BASE
        from ..ops.plan_fusion import (default_plan_kernel,
                                       monolithic_plan_aggregate,
                                       streaming_plan_aggregate)
        group = req.group_by
        if isinstance(group, HashGroupSpec):
            return None
        dict_group = isinstance(group, DictGroupSpec)
        if dict_group and not flags.get("grouped_pushdown_enabled"):
            return None
        blocks, facts = self._collect_with_facts()
        if not blocks:
            return None
        from ..ops.expr import referenced_columns
        needed = set()
        if req.where is not None:
            referenced_columns(req.where, needed)
        for a in req.aggregates:
            if a.expr is not None:
                referenced_columns(a.expr, needed)
        if dict_group:
            needed.update(group.cols)
        elif group is not None:
            needed.update(cid for cid, _, _ in group.cols)
        from ..ops.join_scan import normalize_join
        needed = {c for c in needed if c < BUILD_COL_BASE}
        for w in normalize_join(req.join):
            # chain stages probe an EARLIER stage's payload lane
            # (>= BUILD_COL_BASE) — only real probe-table FKs scan
            if w.probe_col < BUILD_COL_BASE:
                needed.add(w.probe_col)
        read_ht = req.read_ht if req.read_ht is not None else _MAX_HT
        from ..ops.scan import _expand_avg
        expanded = tuple(_expand_avg(req.aggregates))
        minmax = [i for i, a in enumerate(expanded)
                  if a.op in ("min", "max")]
        aggs_run = expanded + tuple(AggSpec("count", expanded[i].expr)
                                    for i in minmax)
        kernel = default_plan_kernel()
        cache = self.device_cache
        key = (self._batch_cache_key(needed)
               if cache is not None else None)
        gout: Optional[dict] = {} if dict_group else None
        got = None
        if flags.get("streaming_scan_enabled"):
            got = streaming_plan_aggregate(
                blocks, sorted(needed), req.where, aggs_run, group,
                read_ht, req.join, kernel=kernel, cache=cache,
                cache_key=key, grouped_out=gout)
        if got is None:
            try:
                got = monolithic_plan_aggregate(
                    blocks, sorted(needed), req.where, aggs_run,
                    group, read_ht, req.join, kernel=kernel,
                    cache=cache, cache_key=key, grouped_out=gout)
            except KeyError:
                return None   # probe column lacks columnar form
            except self._Unrewritable:
                return None   # string predicate outside rewrite shapes
        if dict_group and gout.get("spill"):
            from ..ops.grouped_scan import GROUPED_STATS
            GROUPED_STATS["spill_fallbacks"] += 1
            return None       # slot overflow: interpreted join
        self._check_restart_window(blocks, read_ht, allow_restart, facts)
        outs, counts = got
        outs = _nullify_minmax(expanded, minmax, outs)
        if dict_group:
            from ..ops.grouped_scan import decode_slot_groups
            outs_c, counts_c, gvals = decode_slot_groups(
                group, gout["dicts"], outs, counts)
            return ReadResponse(agg_values=outs_c,
                                group_counts=counts_c,
                                group_values=gvals, backend="tpu")
        return ReadResponse(agg_values=outs,
                            group_counts=np.asarray(counts),
                            backend="tpu")

    def _iter_visible_idrows(self, read_ht: int, allow_restart: bool):
        """Newest visible version of every row as a {col_id: value}
        dict — the interpreted scan loop the CPU join path feeds on
        (same MVCC walk as _execute_cpu, minus segments/paging, which
        join requests never carry)."""
        table_prefix = self.codec.scan_prefix()
        name_to_id = {c.name: c.id for c in self.codec.schema.columns}
        cur_prefix = None
        chosen = False
        from ..dockv.value import unwrap_ttl
        for k, v in self.store.iterate(lower=table_prefix or None):
            if table_prefix and not k.startswith(table_prefix):
                break
            marker = len(k) - _HT_SUFFIX
            prefix = k[:marker]
            if prefix != cur_prefix:
                cur_prefix = prefix
                chosen = False
            if chosen:
                continue
            dht = DocHybridTime.decode_desc(k[-ENCODED_SIZE:])
            if dht.ht.value > read_ht:
                if allow_restart and \
                        dht.ht.value <= read_ht + _skew_window_ht():
                    raise ReadRestartError(dht.ht.value)
                continue
            chosen = True
            v, expire = unwrap_ttl(v)
            if expire is not None and expire <= read_ht:
                continue
            if v[0] == ValueKind.kTombstone:
                continue
            row = self.codec.decode_row(k, v)
            if row is None:
                continue
            yield {name_to_id[n]: val for n, val in row.items()}

    def _execute_join_cpu(self, req: ReadRequest,
                          allow_restart: bool) -> ReadResponse:
        """Interpreted FK-equijoin aggregate: row-at-a-time probe scan,
        a Python dict over each stage's shipped build keys, payload
        values merged into the row under their build-column ids, stages
        folded LEFT TO RIGHT (a chain stage probes a payload column an
        earlier stage merged in) — the correctness reference the fused
        plan is tested against and the fallback for every ineligible
        shape, one wire or many."""
        from ..ops.join_scan import normalize_join
        wires = normalize_join(req.join)
        read_ht = req.read_ht if req.read_ht is not None else _MAX_HT
        stages = []
        for wire in wires:
            keys = np.asarray(wire.keys)
            # key -> ALL matching build rows: duplicate build keys (a
            # shape the device path refuses with a typed reason) keep
            # full SQL inner-join semantics here — one output row per
            # matching build row, never a silent last-wins overwrite
            lookup: Dict[object, list] = {}
            for i in range(len(keys)):
                k = keys[i]
                lookup.setdefault(
                    k.item() if isinstance(k, np.generic) else k,
                    []).append(i)
            payload = {}
            for bid, (vals, nls) in wire.payload.items():
                vals = np.asarray(vals)
                nls = (np.asarray(nls, bool) if nls is not None
                       else np.zeros(len(keys), bool))
                payload[bid] = (vals, nls)
            stages.append((wire.probe_col, lookup, payload))
        aggs = list(_expand_avg_cpu(req.aggregates))
        agg_state = [_agg_init(a) for a in aggs]
        group_state: Dict[object, list] = {}

        def fold(idrow, si):
            if si == len(stages):
                _agg_accumulate(aggs, agg_state, group_state,
                                req.group_by, idrow)
                return
            probe_col, lookup, payload = stages[si]
            fk = idrow.get(probe_col)
            if fk is None:
                return                   # NULL FK never matches
            matches = lookup.get(fk)
            if matches is None:
                return                   # dangling FK: inner join drops
            for bi in matches:
                r2 = dict(idrow) if len(matches) > 1 else idrow
                for bid, (vals, nls) in payload.items():
                    bv = vals[bi]
                    r2[bid] = None if nls[bi] else (
                        bv.item() if isinstance(bv, np.generic) else bv)
                fold(r2, si + 1)

        for idrow in self._iter_visible_idrows(read_ht, allow_restart):
            if req.where is not None and \
                    eval_expr_py(req.where, idrow) is not True:
                continue
            fold(idrow, 0)
        if req.group_by is not None:
            return _grouped_cpu_response(aggs, group_state,
                                         req.group_by)
        vals = tuple(_agg_final(a, s) for a, s in zip(aggs, agg_state))
        return ReadResponse(agg_values=vals, backend="cpu",
                            group_counts=None)

    def _execute_tpu_filter_steps(self, req: ReadRequest):
        """Steps (`execute_steps`) of the filter-pushdown row scan: the
        WHERE mask computes on device, matching rows gather host-side
        with vectorized numpy over the columnar blocks (no per-row
        predicate evaluation). Falls back to the CPU row loop when
        columns aren't columnar-capable."""
        blocks, facts = self._collect_with_facts()
        if not blocks:
            return None
        req = self._maybe_doc_rewrite(req, blocks)
        if req is None:
            return None     # typed doc fallback: interpreted row path
        from ..ops.expr import referenced_columns
        needed = set(referenced_columns(req.where))
        schema = self.codec.schema
        proj_cols = ([schema.column_by_name(n) for n in req.columns]
                     if req.columns else list(schema.columns))
        read_ht = req.read_ht if req.read_ht is not None else _MAX_HT
        resp = self._try_streaming_filter(req, blocks, needed,
                                          proj_cols, read_ht, facts)
        if resp is not None:
            return self._served("streaming", resp)
        blocks, prune_key = self._zone_prune(blocks, req.where, read_ht,
                                             facts.chunk_safe)
        try:
            # same device cache as the aggregate path: repeated string-
            # predicate scans must not rebuild dictionaries per query
            batch = self._cached_batch(blocks, needed, prune_key, facts)
        except KeyError:
            return None
        where = req.where
        if where is not None:
            try:
                where = self._rewrite_strings(where, batch.dicts)
            except self._Unrewritable:
                return None
        # the mask's read-back belongs to the launch: it waits for the
        # device as `device.wait` does
        mask = yield lambda: np.asarray(self.kernel.run(
            batch, where, (), None, read_ht)[2])
        sel = np.nonzero(mask)[0]
        if req.limit is not None and len(sel) > req.limit:
            sel = sel[:req.limit]
        rows = self._gather_rows(blocks, sel, proj_cols)
        if rows is None:
            return None   # column unavailable in columnar form
        return ReadResponse(rows=rows, backend="tpu")

    def _try_streaming_filter(self, req: ReadRequest, blocks, needed,
                              proj_cols, read_ht: int, facts: StoreFacts
                              ) -> Optional[ReadResponse]:
        """Streamed filter-pushdown ROW path: per-chunk WHERE masks on
        device overlapped with the next chunk's batch formation, rows
        gathered host-side per chunk (ops/stream_scan.py
        streaming_scan_filter). None falls through to the monolithic
        batch."""
        if not flags.get("streaming_scan_enabled"):
            return None
        # projection availability must hold for EVERY block up front:
        # the per-chunk materializer cannot un-stream rows it already
        # emitted when a later chunk's block lacks a column
        for b in blocks:
            for c in proj_cols:
                if not (c.id in b.fixed or c.id in b.pk
                        or c.id in b.varlen):
                    return None
        from ..ops.stream_scan import streaming_scan_filter
        cache = self.device_cache
        key = (self._batch_cache_key(needed) + ("rows",)
               if cache is not None else None)

        def materialize(chunk_blocks, sel):
            return self._gather_rows(chunk_blocks, sel, proj_cols) or []

        rows = streaming_scan_filter(
            blocks, sorted(needed), req.where, read_ht, materialize,
            limit=req.limit, kernel=self.kernel, cache=cache,
            cache_key=key, chunk_safe=facts.chunk_safe,
            plans=self._kept_plans(facts))
        if rows is None:
            return None
        return ReadResponse(rows=rows, backend="tpu")

    def _gather_rows(self, blocks, sel, proj_cols
                     ) -> Optional[List[Dict[str, object]]]:
        """Materialize selected row indices (positions in the
        concatenated block list) into projected row dicts — vectorized
        per (column, block); shared by the monolithic and streamed
        filter-pushdown row paths. None when a projected column has no
        columnar form."""
        rows: List[Dict[str, object]] = [dict() for _ in range(len(sel))]
        offsets = np.cumsum([0] + [b.n for b in blocks])
        blk_of = np.searchsorted(offsets, sel, side="right") - 1
        local = sel - offsets[blk_of]
        for c in proj_cols:
            for bi, b in enumerate(blocks):
                which = np.nonzero(blk_of == bi)[0]
                if not len(which):
                    continue
                li = local[which]
                if c.id in b.fixed:
                    vals, nulls = b.fixed[c.id]
                    for j, i_ in zip(which, li):
                        rows[j][c.name] = (None if nulls[i_]
                                           else vals[i_].item())
                elif c.id in b.pk:
                    vals = b.pk[c.id]
                    for j, i_ in zip(which, li):
                        rows[j][c.name] = vals[i_].item()
                elif c.id in b.varlen:
                    ends, heap, nulls = b.varlen[c.id]
                    from ..dockv.packed_row import ColumnType as _CT
                    is_text = c.type in (_CT.STRING, _CT.JSON, _CT.DECIMAL)
                    for j, i_ in zip(which, li):
                        if nulls[i_]:
                            rows[j][c.name] = None
                        else:
                            lo = int(ends[i_ - 1]) if i_ else 0
                            raw = heap[lo:int(ends[i_])]
                            rows[j][c.name] = (raw.decode() if is_text
                                               else raw)
                else:
                    return None   # column unavailable in columnar form
        return rows

    def _scan_segments(self, req: ReadRequest):
        """Skip-scan segments for range-sharded tables (reference:
        docdb/scan_choices.cc + hybrid_scan_choices.cc): =/IN target
        sets on the leading range-PK columns enumerate into seek
        segments, an interval on the following column bounds each
        segment. Returns ([(lower, upper_exclusive, prefix)], residual)
        in encoded-key order, or (None, where) when nothing usable —
        the caller then runs one unbounded segment. Each segment's
        `prefix` (may be b"") is required of every key (break past it)."""
        schema = self.codec.schema
        ps = self.codec.info.partition_schema
        kind, point_lists, interval, residual, _n = \
            classify_scan_options(schema, ps.kind, req.where)
        if kind == "seq":
            return None, residual
        if kind == "empty":
            return [], residual
        from itertools import product
        from .table_codec import _KEV_MAKER
        from ..dockv.key_encoding import encode_key_entry
        base = self.codec.scan_prefix()
        segments = []
        combos = product(*[[(c, v) for v in vals]
                           for c, vals in point_lists]) \
            if point_lists else [()]
        for combo in combos:
            prefix = base + b"".join(
                encode_key_entry(_KEV_MAKER[c.type](
                    int(v) if c.type != "string" else v))
                for c, v in combo)
            lower, upper = prefix, prefix + b"\xff"
            if interval is not None:
                c, lo, hi = interval
                maker = _KEV_MAKER[c.type]
                if lo is not None:
                    lower = prefix + encode_key_entry(maker(int(lo)))
                if hi is not None:
                    upper = prefix + encode_key_entry(maker(int(hi) + 1))
            segments.append((lower, upper, prefix))
        segments.sort(key=lambda s: s[0])
        return segments, residual

    def _execute_cpu(self, req: ReadRequest,
                     allow_restart: bool) -> ReadResponse:
        read_ht = req.read_ht if req.read_ht is not None else _MAX_HT
        table_prefix = self.codec.scan_prefix()
        segments, scan_where = self._scan_segments(req)
        if segments is None:
            segments = [(table_prefix or None, None, b"")]
        if req.paging_state:
            # resume: drop segments the cursor already passed, clamp
            # the containing one
            resume = req.paging_state
            segments = [
                (max(lo or b"", resume), up, seg_pre)
                for lo, up, seg_pre in segments
                if up is None or up > resume]
        rows_out: List[Dict[str, object]] = []
        aggs = list(_expand_avg_cpu(req.aggregates))
        agg_state = [_agg_init(a) for a in aggs]
        group_state: Dict[int, list] = {}
        count = 0
        cur_prefix = None
        chosen = False
        name_to_id = {c.name: c.id for c in self.codec.schema.columns}
        for seg_lower, seg_upper, seg_prefix in segments:
            for k, v in self.store.iterate(lower=seg_lower,
                                           upper=seg_upper):
                if table_prefix and not k.startswith(table_prefix):
                    break                  # left this cotable's key space
                if seg_prefix and not k.startswith(seg_prefix):
                    break                  # left this skip-scan segment
                marker = len(k) - _HT_SUFFIX
                prefix = k[:marker]
                if prefix != cur_prefix:
                    cur_prefix = prefix
                    chosen = False
                if chosen:
                    continue
                dht = DocHybridTime.decode_desc(k[-ENCODED_SIZE:])
                if dht.ht.value > read_ht:
                    if allow_restart and \
                            dht.ht.value <= read_ht + _skew_window_ht():
                        raise ReadRestartError(dht.ht.value)
                    continue
                chosen = True   # newest visible version of this doc key
                from ..dockv.value import unwrap_ttl
                v, expire = unwrap_ttl(v)
                if expire is not None and expire <= read_ht:
                    continue    # expired
                if v[0] == ValueKind.kTombstone:
                    continue
                row = self.codec.decode_row(k, v)
                if row is None:
                    continue
                idrow = {name_to_id[n]: val for n, val in row.items()}
                if scan_where is not None:
                    if eval_expr_py(scan_where, idrow) is not True:
                        continue
                if aggs:
                    _agg_accumulate(aggs, agg_state, group_state,
                                    req.group_by, idrow)
                else:
                    rows_out.append(self._project(row, req.columns))
                    count += 1
                    if req.limit is not None and count >= req.limit:
                        return ReadResponse(
                            rows=rows_out, paging_state=prefix + b"\xff",
                            backend="cpu")
        if aggs:
            if req.group_by is not None:
                return _grouped_cpu_response(aggs, group_state, req.group_by)
            vals = tuple(_agg_final(a, s) for a, s in zip(aggs, agg_state))
            return ReadResponse(agg_values=vals, backend="cpu",
                                group_counts=None)
        return ReadResponse(rows=rows_out, backend="cpu")

    def _project(self, row: Dict[str, object], columns: Tuple[str, ...]
                 ) -> Dict[str, object]:
        if not columns:
            return row
        return {c: row.get(c) for c in columns}


_MAX_HT = 0xFFFFFFFFFFFFFFFF - 1
_SHARED_KERNEL = ScanKernel()

#: sentinel from _try_streaming_aggregate: the dict-grouped scan
#: overflowed its slot budget — skip the monolithic device pass (it
#: would spill identically) and serve the interpreted GROUP BY
_SPILLED = object()


def _expand_avg_cpu(aggs):
    for a in aggs:
        if a.op == "avg":
            yield AggSpec("sum", a.expr)
            yield AggSpec("count", a.expr)
        else:
            yield a


def _agg_init(a: AggSpec):
    if a.op in ("sum", "count"):
        return 0
    return None


def _agg_step(a: AggSpec, state, idrow):
    if a.expr is None:
        return (state or 0) + 1
    v = eval_expr_py(a.expr, idrow)
    if v is None:
        return state
    if a.op == "count":
        return (state or 0) + 1
    if a.op == "sum":
        return (state or 0) + v
    if a.op == "min":
        return v if state is None else min(state, v)
    if a.op == "max":
        return v if state is None else max(state, v)
    raise ValueError(a.op)


def _agg_accumulate(aggs, agg_state, group_state, group, idrow):
    if group is None:
        for i, a in enumerate(aggs):
            agg_state[i] = _agg_step(a, agg_state[i], idrow)
        return
    if isinstance(group, (HashGroupSpec, DictGroupSpec)):
        # interpreted GROUP BY keys by value tuple — the slot-overflow
        # and flag-off fallback for DictGroupSpec lands here
        key = tuple(idrow.get(cid) for cid in group.cols)
        if any(v is None for v in key):
            return       # NULL group values are excluded (matches device)
        st = group_state.setdefault(key,
                                    [_agg_init(a) for a in aggs] + [0])
        for i, a in enumerate(aggs):
            st[i] = _agg_step(a, st[i], idrow)
        st[-1] += 1
        return
    gid = 0
    stride = 1
    for cid, domain, offset in group.cols:
        c = idrow.get(cid)
        if c is None:
            return       # NULL group values are excluded (matches device)
        c = int(c) - offset
        gid += max(0, min(c, domain - 1)) * stride
        stride *= domain
    st = group_state.setdefault(gid, [_agg_init(a) for a in aggs] + [0])
    for i, a in enumerate(aggs):
        st[i] = _agg_step(a, st[i], idrow)
    st[-1] += 1


def _agg_final(a: AggSpec, state):
    if a.op in ("sum", "count"):
        return state or 0
    return state


def _grouped_cpu_response(aggs, group_state, group) -> ReadResponse:
    if isinstance(group, (HashGroupSpec, DictGroupSpec)):
        keys = list(group_state)
        G = len(keys)
        outs = []
        for i, a in enumerate(aggs):
            if a.op in ("min", "max"):
                # SQL NULL for a group with zero qualifying inputs
                arr = np.array(
                    [_agg_final(a, group_state[k][i]) for k in keys],
                    object)
            else:
                arr = np.zeros(G,
                               np.float64 if a.op != "count" else np.int64)
                for g, key in enumerate(keys):
                    arr[g] = _agg_final(a, group_state[key][i]) or 0
            outs.append(arr)
        counts = np.asarray([group_state[k][-1] for k in keys], np.int64)
        gvals = tuple(np.asarray([k[j] for k in keys])
                      for j in range(len(group.cols)))
        return ReadResponse(agg_values=tuple(outs), group_counts=counts,
                            group_values=gvals, backend="cpu")
    G = group.num_groups
    outs = []
    for i, a in enumerate(aggs):
        if a.op in ("min", "max"):
            arr = np.full(G, None, object)
            for gid, st in group_state.items():
                arr[gid] = _agg_final(a, st[i])
        else:
            arr = np.zeros(G, np.float64 if a.op != "count" else np.int64)
            for gid, st in group_state.items():
                arr[gid] = _agg_final(a, st[i]) or 0
        outs.append(arr)
    counts = np.zeros(G, np.int64)
    for gid, st in group_state.items():
        counts[gid] = st[-1]
    return ReadResponse(agg_values=tuple(outs), group_counts=counts,
                        backend="cpu")
