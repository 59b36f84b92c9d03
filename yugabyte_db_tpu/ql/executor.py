"""SQL executor: statements -> client calls -> DocDB requests.

The round-1 stand-in for the reference's PG executor + pggate
(reference: src/yb/yql/pggate/pggate.cc ExecSelect :1842, expression
pushdown classification in src/postgres ybplan.c): WHERE clauses and
scalar aggregates push down to tablets (and from there to the TPU scan
kernels); GROUP BY pushes down to the device unconditionally for
numeric group keys — dictionary one-hot matmul when ANALYZE stats bound
the domains, sort + segment aggregation (HashGroupSpec) otherwise —
falling back to client-side hash grouping only for non-numeric keys or
distinct-group overflow.
"""
from __future__ import annotations

import asyncio
import contextlib
from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from ..client import YBClient
from ..docdb.operations import ReadRequest, RowOp, eval_expr_py
from ..rpc.messenger import RpcError
from ..utils import flags
from ..utils.trace import TRACES
from ..docdb.table_codec import TableInfo
from ..dockv.packed_row import ColumnSchema, ColumnType, TableSchema
from ..dockv.partition import PartitionSchema
from ..ops.grouped_scan import DictGroupSpec
from ..ops.scan import AggSpec, GroupSpec, HashGroupSpec
from .parser import (
    AlterTableStmt, AnalyzeStmt, CreateIndexStmt, CreateMatViewStmt,
    CreateSequenceStmt,
    CreateTableStmt, CreateTablespaceStmt, CreateViewStmt, DeleteStmt,
    DropIndexStmt, DropMatViewStmt, DropSequenceStmt, DropTableStmt,
    DropTablespaceStmt, DropViewStmt,
    ExplainStmt, InsertStmt, RefreshMatViewStmt, SelectStmt, SetOpStmt,
    TruncateStmt,
    TxnStmt, UpdateStmt, parse_statement,
)

_TYPE_MAP = {
    "bigint": ColumnType.INT64, "int8": ColumnType.INT64,
    "int": ColumnType.INT32, "integer": ColumnType.INT32,
    "int4": ColumnType.INT32, "smallint": ColumnType.INT32,
    "double": ColumnType.FLOAT64, "float8": ColumnType.FLOAT64,
    "float": ColumnType.FLOAT64, "real": ColumnType.FLOAT32,
    "float4": ColumnType.FLOAT32,
    "text": ColumnType.STRING, "varchar": ColumnType.STRING,
    "string": ColumnType.STRING,
    "bool": ColumnType.BOOL, "boolean": ColumnType.BOOL,
    "timestamp": ColumnType.TIMESTAMP,
    "bytea": ColumnType.BINARY, "blob": ColumnType.BINARY,
    "binary": ColumnType.BINARY,
    "jsonb": ColumnType.JSON, "json": ColumnType.JSON,
    "decimal": ColumnType.DECIMAL, "numeric": ColumnType.DECIMAL,
    "vector": ColumnType.VECTOR,
}


def is_collection_type(typ: str) -> bool:
    """CQL collection (list<..>/set<..>/map<..>) or PG array (t[])."""
    return (typ.split("<", 1)[0] in ("list", "set", "map")
            or typ.endswith("[]"))


def resolve_type(typ: str):
    """Column-type name -> storage ColumnType. CQL collections
    (list<..>/set<..>/map<..>) store as JSON documents — the wire layer
    (cql_server) owns their element typing (reference: collection
    subdocuments in dockv; ours ride the JSON column path)."""
    if is_collection_type(typ):
        return ColumnType.JSON
    return _TYPE_MAP.get(typ)


def parse_vector(text) -> "np.ndarray":
    if isinstance(text, (list, tuple)):
        return np.asarray(text, np.float32)
    return np.asarray(
        [float(x) for x in text.strip().strip("[]").split(",") if x.strip()],
        np.float32)


@dataclass
class SqlResult:
    rows: List[dict]
    status: str = "OK"
    # set when the statement was served from a materialized view's
    # maintained partials (matview/): the read's bounded staleness
    staleness_ms: Optional[float] = None

    def __iter__(self):
        return iter(self.rows)


class SqlSession:
    """One SQL session over a cluster client (a PG-backend analog)."""

    def __init__(self, client: YBClient):
        self.client = client
        # optional per-table column stats enabling device GROUP BY:
        # {table: {column: (domain, offset)}}
        self.stats: Dict[str, Dict[str, Tuple[int, int]]] = {}
        # ANALYZE-recorded row counts: the planner's cardinality
        # estimates (join-order choice, BNL eligibility reporting)
        self.rowcounts: Dict[str, int] = {}
        self._txn = None    # active YBTransaction (BEGIN..COMMIT)
        # materialized CTE rowsets visible to the current statement
        self._cte_rows: Dict[str, List[dict]] = {}
        # per-statement join-side schemas (label -> schema|None), set
        # by _select_join/_explain via _gather_join_schemas
        self._join_schemas: Dict[str, object] = {}
        # the open `sql.plan` span of the SELECT being planned and the
        # stack that ends it (see _select / _scan)
        self._plan = None

    async def execute(self, sql: str) -> SqlResult:
        # the statement is the trace's root: parse, plan, the client's
        # fan-out and every tablet RPC below share its trace_id
        with TRACES.span("sql.execute") as sp:
            with TRACES.span("sql.parse", child_only=True):
                stmt = parse_statement(sql)
            return await self._dispatch(stmt, sp)

    async def execute_script(self, sql: str) -> List[SqlResult]:
        """Multi-statement script: results in statement order
        (reference: the PG simple-query protocol runs whole scripts)."""
        from .parser import parse_script
        out = []
        for s in parse_script(sql):
            with TRACES.span("sql.execute") as sp:
                out.append(await self._dispatch(s, sp))
        return out

    async def _dispatch(self, stmt, sp) -> SqlResult:
        """Run one parsed statement under its `sql.execute` span `sp`
        (tags: the statement kind, rows returned, the stale-schema
        retry)."""
        sp.set_tag("stmt", type(stmt).__name__[:-len("Stmt")].lower())
        try:
            res = await self._dispatch_inner(stmt)
        except KeyError as orig:
            # an unknown column may just be a stale client schema cache
            # (ALTER through another node): binding precedes any write
            # RPC, so a one-shot refresh + retry is side-effect free
            # (reference: catalog-version mismatch retry in pggate)
            table = getattr(stmt, "table", None)
            if table is None or table in self._cte_rows or isinstance(
                    stmt, (CreateTableStmt, DropTableStmt)):
                raise
            try:
                await self.client._table(table, refresh=True)
            except Exception:   # noqa: BLE001 — not a real table (a
                raise orig      # CTE or vtable): keep the original
            sp.set_tag("retried", True)
            res = await self._dispatch_inner(stmt)
        sp.set_tag("rows", len(res.rows))
        return res

    async def _dispatch_inner(self, stmt) -> SqlResult:
        if isinstance(stmt, CreateTableStmt):
            return await self._create(stmt)
        if isinstance(stmt, CreateViewStmt):
            await self.client.create_view(stmt.name, stmt.select_sql,
                                          stmt.or_replace)
            return SqlResult([], "CREATE VIEW")
        if isinstance(stmt, DropViewStmt):
            from ..rpc.messenger import RpcError
            try:
                await self.client.drop_view(stmt.name)
            except RpcError as e:
                if not (stmt.if_exists and e.code == "NOT_FOUND"):
                    raise
            return SqlResult([], "DROP VIEW")
        if isinstance(stmt, CreateMatViewStmt):
            await self.client.matviews().create(self._matview_def(stmt))
            return SqlResult([], "CREATE MATERIALIZED VIEW")
        if isinstance(stmt, DropMatViewStmt):
            from ..matview.errors import MatviewError
            try:
                await self.client.matviews().drop(stmt.name)
            except MatviewError as e:
                from ..matview.errors import MatviewDisabledError
                if not stmt.if_exists \
                        or isinstance(e, MatviewDisabledError):
                    raise
            return SqlResult([], "DROP MATERIALIZED VIEW")
        if isinstance(stmt, RefreshMatViewStmt):
            await self.client.matviews().refresh(stmt.name)
            return SqlResult([], "REFRESH MATERIALIZED VIEW")
        if isinstance(stmt, CreateTablespaceStmt):
            await self.client.create_tablespace(
                stmt.name,
                placement=[{"zone": z, "min_replicas": n}
                           for z, n in stmt.placement],
                preferred_zones=stmt.preferred_zones)
            return SqlResult([], "CREATE TABLESPACE")
        if isinstance(stmt, DropTablespaceStmt):
            await self.client.drop_tablespace(stmt.name)
            return SqlResult([], "DROP TABLESPACE")
        if isinstance(stmt, CreateSequenceStmt):
            await self.client.create_sequence(
                stmt.name, stmt.start, stmt.increment,
                stmt.if_not_exists)
            return SqlResult([], "CREATE SEQUENCE")
        if isinstance(stmt, DropSequenceStmt):
            from ..rpc.messenger import RpcError
            try:
                await self.client.drop_sequence(stmt.name)
            except RpcError as e:
                # IF EXISTS forgives only not-found — a leaderless
                # master etc. must still surface
                if not (stmt.if_exists and e.code == "NOT_FOUND"):
                    raise
            return SqlResult([], "DROP SEQUENCE")
        if isinstance(stmt, DropTableStmt):
            return await self._drop(stmt)
        if isinstance(stmt, DropIndexStmt):
            return await self._drop_index(stmt)
        if isinstance(stmt, InsertStmt):
            return await self._insert(stmt)
        if isinstance(stmt, AlterTableStmt):
            if getattr(stmt, "add_constraints", None) or \
                    getattr(stmt, "drop_constraints", None):
                await self._alter_constraints(stmt)
                if not stmt.add_columns and not stmt.drop_columns:
                    return SqlResult([], "ALTER TABLE")
            adds = []
            for cname, ctype in stmt.add_columns:
                ct = resolve_type(ctype)
                if ct is None:
                    raise ValueError(f"unknown type {ctype}")
                adds.append((cname, ct,
                             ctype if is_collection_type(ctype)
                             else None))
            v = await self.client.alter_table(
                stmt.table, adds, getattr(stmt, "drop_columns", ()))
            return SqlResult([], f"ALTER TABLE (v{v})")
        if isinstance(stmt, TxnStmt):
            return await self._txn_stmt(stmt)
        if isinstance(stmt, CreateIndexStmt):
            ct = await self.client._table(stmt.table)
            col = ct.info.schema.column_by_name(stmt.column)
            if col.type == ColumnType.VECTOR or stmt.method != "lsm":
                from ..vector import available_methods, get_index_cls
                method = (stmt.method if stmt.method != "lsm"
                          else "ivfflat")
                get_index_cls(method)   # unknown USING method -> error
                if len(getattr(stmt, "columns", None) or [1]) > 1:
                    raise ValueError(
                        f"{method} indexes cover exactly one vector "
                        f"column (available ANN methods: "
                        f"{available_methods()})")
                if col.type != ColumnType.VECTOR:
                    raise ValueError(
                        f"USING {method} requires a vector column, "
                        f"got {stmt.column!r}")
                n = await self.client.build_vector_index(
                    stmt.table, stmt.column, stmt.lists,
                    method=method, options=stmt.options)
            else:
                n = await self.client.create_secondary_index(
                    stmt.table, stmt.name,
                    getattr(stmt, "columns", None) or stmt.column,
                    unique=getattr(stmt, "unique", False))
            return SqlResult([], f"CREATE INDEX ({n} rows)")
        if isinstance(stmt, ExplainStmt):
            plan = await self._explain(stmt.inner)
            if not getattr(stmt, "analyze", False):
                return plan
            # EXPLAIN ANALYZE: run the statement for real and append
            # actuals (reference: PG EXPLAIN ANALYZE; DML side effects
            # apply, as in PG)
            import time as _time
            t0 = _time.perf_counter()
            res = await self._dispatch_inner(stmt.inner)
            ms = (_time.perf_counter() - t0) * 1e3
            lines = list(plan.rows)
            lines.append({"QUERY PLAN":
                          f"  Actual rows: {len(res.rows)}"})
            lines.append({"QUERY PLAN":
                          f"Execution Time: {ms:.3f} ms"})
            return SqlResult(lines)
        if isinstance(stmt, AnalyzeStmt):
            return await self._analyze(stmt)
        if isinstance(stmt, TruncateStmt):
            if self._txn is not None:
                raise ValueError(
                    "TRUNCATE cannot run inside a transaction here "
                    "(non-MVCC store drop, like the reference's)")
            self._invalidate_stats(stmt.table)
            await self.client.truncate_table(stmt.table)
            return SqlResult([], "TRUNCATE TABLE")
        if isinstance(stmt, SetOpStmt):
            return await self._set_op(stmt)
        if isinstance(stmt, SelectStmt):
            if stmt.knn is not None:
                return await self._knn_select(stmt)
            return await self._select(stmt)
        if isinstance(stmt, DeleteStmt):
            return await self._delete(stmt)
        if isinstance(stmt, UpdateStmt):
            return await self._update(stmt)
        raise ValueError(f"unhandled statement {stmt}")

    @staticmethod
    def _item_name(stmt: SelectStmt, idx: int) -> str:
        """Output column name for item `idx`: its AS alias, else the
        default derived name (positional, so aliases can never collide
        with or overwrite other projected columns)."""
        alias = getattr(stmt, "aliases", {}).get(idx)
        if alias:
            return alias
        it = stmt.items[idx]
        if it[0] == "window":
            # disambiguate same-function windows so the second can't
            # silently overwrite the first's column
            dups = [j for j, o in enumerate(stmt.items)
                    if o[0] == "window" and o[1] == it[1]]
            return it[1] if len(dups) == 1 else f"{it[1]}_{idx}"
        if it[0] == "col":
            # PG semantics: SELECT a.attname projects as "attname"
            return it[1].split(".", 1)[1] if "." in it[1] else it[1]
        return (_agg_name(it) if it[0] == "agg" else _expr_name(it[1]))

    # max distinct-domain width eligible for device GROUP BY (one-hot
    # matmul columns scale with the domain product)
    _ANALYZE_MAX_DOMAIN = 4096

    async def _analyze(self, stmt: AnalyzeStmt) -> SqlResult:
        """Collect small-domain integer column stats so grouped
        aggregates route to the DEVICE one-hot kernel automatically
        (reference: ANALYZE feeding the PG planner; ours feeds the
        group-pushdown eligibility check). Unlike PG, these stats are
        correctness-bearing for the device kernel (it clips values to
        the recorded domain), so DML on the table invalidates them —
        re-run ANALYZE after loading data. Columns are skipped when
        NULLs exist (the device kernel has no NULL group slot) or when
        values fall outside int32 (the kernel's group dtype)."""
        ct = await self.client._table(stmt.table)
        schema = ct.info.schema
        int_cols = [c for c in schema.columns
                    if c.type in (ColumnType.INT32, ColumnType.INT64)
                    and not c.is_hash_key and not c.is_range_key]
        # ONE scan carries every column's min/max/count + count(*)
        aggs = [AggSpec("count")]
        for c in int_cols:
            aggs += [AggSpec("min", ("col", c.id)),
                     AggSpec("max", ("col", c.id)),
                     AggSpec("count", ("col", c.id))]
        resp = await self.client.scan(stmt.table, ReadRequest(
            "", aggregates=tuple(aggs)))
        total = _scalar(resp.agg_values[0])
        st = {}
        i32 = 2 ** 31 - 1
        for j, c in enumerate(int_cols):
            lo = _scalar(resp.agg_values[1 + 3 * j])
            hi = _scalar(resp.agg_values[2 + 3 * j])
            nn = _scalar(resp.agg_values[3 + 3 * j])
            if lo is None or hi is None:
                continue
            if nn != total:
                continue        # NULLs present: no device NULL group
            lo, hi = int(lo), int(hi)
            if lo < -i32 or hi > i32:
                continue        # outside the kernel's int32 group dtype
            domain = hi - lo + 1
            if 0 < domain <= self._ANALYZE_MAX_DOMAIN:
                st[c.name] = (domain, lo)
        self.stats[stmt.table] = st
        self.rowcounts[stmt.table] = int(total)
        return SqlResult(
            [{"column": k, "domain": d, "offset": o}
             for k, (d, o) in sorted(st.items())],
            f"ANALYZE ({len(st)} columns)")

    # ------------------------------------------------------------------
    async def _explain(self, stmt) -> SqlResult:
        """Plan description without executing (reference: EXPLAIN via
        the PG planner + yb_lsm cost hooks; ours mirrors _select's
        branch order exactly so the reported plan is the executed one)."""
        lines: List[str] = []
        if isinstance(stmt, SetOpStmt):
            label = {"union": "Append" if stmt.all else "HashSetOp Union",
                     "intersect": "HashSetOp Intersect",
                     "except": "HashSetOp Except"}[stmt.op]
            lines.append(label + (" All" if stmt.all and
                                  stmt.op != "union" else ""))
            for side in (stmt.left, stmt.right):
                sub = await self._explain(side)
                lines.extend("  -> " + r["QUERY PLAN"] if i == 0
                             else "     " + r["QUERY PLAN"]
                             for i, r in enumerate(sub.rows))
            if stmt.order_by:
                lines.append(f"Sort: {', '.join(c for c, _ in stmt.order_by)}")
            return SqlResult([{"QUERY PLAN": ln} for ln in lines])
        def _has_subquery(n):
            if not isinstance(n, tuple):
                return False
            if n[0] in ("exists_subquery", "scalar_subquery",
                        "in_subquery"):
                return True
            return any(_has_subquery(c) for c in n
                       if isinstance(c, tuple))
        subplan_note = (isinstance(stmt, SelectStmt)
                        and stmt.where is not None
                        and _has_subquery(stmt.where))
        if isinstance(stmt, SelectStmt) and (
                getattr(stmt, "ctes", None)
                or stmt.table in self._cte_rows):
            lines.append(f"CTE Scan on {stmt.table} "
                         f"(materialized client-side)")
            return SqlResult([{"QUERY PLAN": ln} for ln in lines])
        if isinstance(stmt, SelectStmt):
            ct = await self.client._table(stmt.table)
            schema = ct.info.schema
            agg_items = [it for it in stmt.items if it[0] == "agg"]
            having = getattr(stmt, "having", None)
            if having is not None and not agg_items and not stmt.group_by:
                raise ValueError("HAVING requires aggregates or GROUP BY")
            push_limit = (stmt.limit is not None
                          and not (stmt.order_by or stmt.distinct
                                   or stmt.offset))
            if stmt.knn is not None:
                lines.append(f"kNN Search on {stmt.table} "
                             f"({stmt.knn[0]})")
                lines.append("  -> per-tablet ANN index (registry: "
                             "ivfflat two-stage | hnsw) + re-rank "
                             "(exact device search if no index)")
            elif getattr(stmt, "joins", None):
                import dataclasses
                probe = dataclasses.replace(
                    stmt, joins=list(stmt.joins))
                self._join_schemas, _real = \
                    await self._gather_join_schemas(probe)
                self._maybe_reorder_joins(probe)
                swapped = probe.table != stmt.table
                reordered = (probe.table != stmt.table or
                             [j.table for j in probe.joins]
                             != [j.table for j in stmt.joins])
                pushed = self._join_pushdown(probe)
                for jc in probe.joins:
                    lbl = jc.alias or jc.table
                    sch = self._join_schemas.get(lbl)
                    rcol_ok = False
                    if sch is not None:
                        try:
                            sch.column_by_name(
                                self._split_qual(jc.right_col)[1])
                            rcol_ok = True
                        except Exception:  # noqa: BLE001
                            pass
                    # mirror fetch_inner's ELIGIBILITY exactly; the
                    # runtime key-count fallback is reported as such
                    bnl = (jc.kind in ("inner", "left") and rcol_ok
                           and jc.table not in self._cte_rows)
                    strat = ("Batched Nested Loop (inner IN-key "
                             "batches; hash join past bnl_max_keys "
                             "outer keys)" if bnl else "Hash Join")
                    lines.append(f"{strat} ({jc.kind}) {probe.table} "
                                 f"⋈ {jc.table}")
                if len(stmt.joins) >= 2 and reordered:
                    chain = " -> ".join(
                        [probe.table] + [j.table for j in probe.joins])
                    est = ", ".join(
                        f"{t}={self.rowcounts.get(t)}"
                        for t in [probe.table]
                        + [j.table for j in probe.joins])
                    lines.append(f"  Join order: {chain} "
                                 f"(ANALYZE greedy left-deep: {est})")
                elif swapped:
                    lines.append(f"  Join order: {probe.table} outer "
                                 f"(ANALYZE: "
                                 f"{self.rowcounts.get(probe.table)} "
                                 f"rows < "
                                 f"{self.rowcounts.get(probe.joins[0].table)})")
                for lbl, conjs in sorted(pushed.items()):
                    lines.append(f"  Pushed to {lbl}: {len(conjs)} "
                                 f"predicate(s)")
                if not pushed:
                    lines.append("  Residual WHERE: client-side")
            elif agg_items and not stmt.group_by:
                lines.append(f"Aggregate on {stmt.table} "
                             f"(pushed to tablets; TPU scan kernel "
                             f"when >= tpu_min_rows_for_pushdown)")
                if stmt.where is not None:
                    lines.append("  Filter: pushed to tablets "
                                 "(device mask when columnar)")
                if having is not None:
                    lines.append("  Having: client-side over the "
                                 "single group")
            elif stmt.group_by and (agg_items or having is not None):
                gspec = (self._group_spec(stmt, schema)
                         if agg_items else None)
                if isinstance(gspec, HashGroupSpec):
                    lines.append(
                        f"Grouped Aggregate on {stmt.table} "
                        f"(DEVICE pushdown: sort + segment "
                        f"aggregation, up to {gspec.max_groups} groups)")
                elif gspec is not None:
                    lines.append(
                        f"Grouped Aggregate on {stmt.table} "
                        f"(DEVICE pushdown: one-hot matmul over "
                        f"{gspec.num_groups} groups)")
                else:
                    lines.append(
                        f"Grouped Aggregate on {stmt.table} "
                        f"(client hash grouping over non-numeric "
                        f"group keys)")
                if stmt.where is not None:
                    lines.append("  Filter: pushed to tablets "
                                 "(device mask when columnar)")
                if having is not None:
                    lines.append("  Having: client-side over group rows")
                if stmt.order_by:
                    lines.append("  Order By: client-side sort")
                if stmt.limit is not None:
                    lines.append(f"  Limit {stmt.limit}: client-side")
            else:
                idx = None
                if ct.indexes and stmt.where is not None \
                        and self._txn is None:
                    idx = self._extract_index_eq(stmt.where, ct)
                if idx is not None:
                    lines.append(f"Index Lookup on {stmt.table} "
                                 f"via {idx[0]}")
                    lines.append("  Residual Filter: client-side")
                    if stmt.order_by:
                        lines.append("  Order By: client-side sort")
                    if stmt.limit is not None:
                        lines.append(f"  Limit {stmt.limit}: "
                                     f"client-side")
                else:
                    # the SAME classifier execution uses, so the plan
                    # can never drift from actual behavior
                    from ..docdb.operations import classify_scan_options
                    schema = ct.info.schema
                    kind, _pts, interval, _res, nseg = \
                        classify_scan_options(
                            schema, ct.info.partition_schema.kind,
                            self._bind(stmt.where, schema)
                            if stmt.where is not None else None)
                    if kind == "empty":
                        scan_kind = (f"Skip Scan on {stmt.table} "
                                     f"(empty target set)")
                    elif kind == "skip":
                        scan_kind = (f"Skip Scan on {stmt.table} "
                                     f"({nseg} segments"
                                     + (", range-bounded)"
                                        if interval else ")"))
                    elif kind == "range":
                        scan_kind = (f"Range Scan on {stmt.table} "
                                     f"(pk bounds)")
                    else:
                        scan_kind = f"Seq Scan on {stmt.table}"
                    lines.append(scan_kind)
                    if stmt.where is not None:
                        lines.append("  Filter: pushed to tablets "
                                     "(device mask when columnar)")
                    natural = self._natural_order(ct, stmt.order_by)
                    if stmt.order_by:
                        lines.append(
                            "  Order By: natural range-shard pk order "
                            "(per-tablet merge)" if natural
                            else "  Order By: client-side sort")
                    if stmt.limit is not None:
                        push = (not (stmt.distinct or stmt.offset)
                                and (natural or not stmt.order_by))
                        lines.append(
                            f"  Limit {stmt.limit}: "
                            f"{'pushed down' if push else 'client-side'}")
            if self._is_serializable():
                lines.append("  Locks: SERIALIZABLE row read locks "
                             "on the read set")
        elif isinstance(stmt, (UpdateStmt, DeleteStmt)):
            op = "Update" if isinstance(stmt, UpdateStmt) else "Delete"
            lines.append(f"{op} on {stmt.table}: pk scan + per-row "
                         f"write (txn intents when in a transaction)")
        else:
            lines.append(f"{type(stmt).__name__}: no plan")
        return SqlResult([{"QUERY PLAN": l} for l in lines], "EXPLAIN")

    async def _txn_stmt(self, stmt: TxnStmt) -> SqlResult:
        if stmt.kind == "begin":
            if self._txn is not None:
                raise ValueError("transaction already in progress")
            self._txn = await self.client.transaction(
                getattr(stmt, "isolation", "snapshot")).begin()
            return SqlResult([], "BEGIN")
        if self._txn is None:
            raise ValueError("no transaction in progress")
        if stmt.kind == "savepoint":
            self._txn.savepoint(stmt.name)
            return SqlResult([], "SAVEPOINT")
        if stmt.kind == "rollback_to":
            await self._txn.rollback_to(stmt.name)
            return SqlResult([], "ROLLBACK")
        if stmt.kind == "release":
            self._txn.release_savepoint(stmt.name)
            return SqlResult([], "RELEASE")
        txn, self._txn = self._txn, None
        if stmt.kind == "commit":
            await txn.commit()
            return SqlResult([], "COMMIT")
        await txn.abort()
        return SqlResult([], "ROLLBACK")

    async def _create(self, stmt: CreateTableStmt) -> SqlResult:
        if stmt.if_not_exists:
            names = {t["name"] for t in await self.client.list_tables()}
            if stmt.name in names:
                return SqlResult([], "OK")
        cols = []
        pk = stmt.primary_key
        range_sharded = getattr(stmt, "range_sharded", False)
        serial_cols = []       # (column, owned sequence) to create
        for i, (name, typ) in enumerate(stmt.columns):
            default_seq = None
            if typ in ("serial", "smallserial", "bigserial"):
                ct = (ColumnType.INT64 if typ == "bigserial"
                      else ColumnType.INT32)
                default_seq = f"{stmt.name}_{name}_seq"
                serial_cols.append(default_seq)
            else:
                ct = resolve_type(typ)
            if ct is None:
                raise ValueError(f"unknown type {typ}")
            cols.append(ColumnSchema(
                i, name, ct,
                nullable=name not in getattr(stmt, "not_null", ()),
                is_hash_key=(not range_sharded and name == pk[0]),
                is_range_key=(name in pk if range_sharded
                              else name in pk[1:]),
                sort_desc=name in getattr(stmt, "pk_desc", []),
                ql_type=typ if is_collection_type(typ) else None,
                default_seq=default_seq,
                default_value=getattr(stmt, "defaults", {}).get(name)))
        for seq in serial_cols:
            await self.client.create_sequence(seq, if_not_exists=True)
        schema = TableSchema(columns=tuple(cols), version=1)
        info = TableInfo(
            "", stmt.name, schema,
            PartitionSchema("range", 0) if range_sharded
            else PartitionSchema("hash", 1))
        fks = [{"column": c, "parent_table": pt, "parent_column": pc,
                "on_delete": act}
               for c, pt, pc, act in getattr(stmt, "foreign_keys", [])]
        for fk in fks:
            # the parent column must be its table's PK (our FK-lite
            # scope: existence checks by point get) — validate at DDL
            # time so a typo fails CREATE, not every later INSERT.
            # Self-referential FKs (REFERENCES the table being created,
            # e.g. emp.mgr -> emp.id) validate against the schema in
            # hand: the table doesn't exist yet.
            if fk["parent_table"] == stmt.name:
                pk_names = pk
            else:
                pct = await self.client._table(fk["parent_table"])
                pk_names = [c.name for c in pct.info.schema.key_columns]
            if [fk["parent_column"]] != pk_names:
                raise ValueError(
                    f"REFERENCES {fk['parent_table']}"
                    f"({fk['parent_column']}): referenced column must "
                    f"be the single-column primary key {pk_names}")
        checks = list(getattr(stmt, "checks", []) or [])
        col_names = {n for n, _ in stmt.columns}
        for chk in checks:
            refs: set = set()
            self._collect_names(chk, refs)
            unknown = {self._split_qual(r)[1] for r in refs} - col_names
            if unknown:
                raise ValueError(
                    f"CHECK constraint references unknown column(s) "
                    f"{sorted(unknown)}")
        await self.client.create_table(
            info, num_tablets=stmt.num_tablets,
            replication_factor=stmt.replication_factor,
            tablespace=getattr(stmt, "tablespace", None),
            foreign_keys=fks, checks=checks)
        self._invalidate_fk_children()
        # UNIQUE columns: enforced through unique secondary indexes
        # (the index doc key is the value itself, so duplicates collide
        # — reference: yb_access/yb_lsm.c:233-366)
        for col in getattr(stmt, "unique_cols", []):
            cols = list(col) if isinstance(col, tuple) else [col]
            await self.client.create_secondary_index(
                stmt.name, f"{stmt.name}_{'_'.join(cols)}_key", cols,
                unique=True)
        return SqlResult([], "CREATE TABLE")

    def _invalidate_stats(self, table: str) -> None:
        """Device-group stats are correctness-bearing (the kernel clips
        to the recorded domain): any DML or DDL on the table voids
        them until the next ANALYZE."""
        self.stats.pop(table, None)
        self.rowcounts.pop(table, None)

    async def _drop(self, stmt: DropTableStmt) -> SqlResult:
        self._invalidate_stats(stmt.name)
        self._invalidate_fk_children()
        if stmt.if_exists:
            names = {t["name"] for t in await self.client.list_tables()}
            if stmt.name not in names:
                return SqlResult([], "OK")
        await self.client.drop_table(stmt.name)
        return SqlResult([], "DROP TABLE")

    async def _drop_index(self, stmt: DropIndexStmt) -> SqlResult:
        """One master RPC: the master owns the index registry and
        resolves the base relation itself (PG resolves DROP INDEX by
        relation; client-side resolution would read stale caches)."""
        try:
            await self.client.drop_secondary_index(stmt.name)
        except RpcError as e:
            if stmt.if_exists and e.code == "NOT_FOUND":
                return SqlResult([], "OK")
            raise
        return SqlResult([], "DROP INDEX")

    async def _insert(self, stmt: InsertStmt) -> SqlResult:
        self._invalidate_stats(stmt.table)
        ct = await self.client._table(stmt.table)
        cols = stmt.columns or [c.name for c in ct.info.schema.columns]
        # validate names against the schema up front: an unknown column
        # must raise (→ stale-cache refresh retry in _dispatch), never
        # silently drop the value on the floor at codec time
        for name in cols:
            ct.info.schema.column_by_name(name)   # raises KeyError
        json_cols = {c.name for c in ct.info.schema.columns
                     if c.type == ColumnType.JSON}
        if getattr(stmt, "select", None) is not None:
            # INSERT INTO ... SELECT: run the select, map by POSITION.
            # Unaliased items get unique hidden aliases first so
            # duplicate output names (SELECT k, k) can't collapse in
            # the row dicts; user aliases are kept for ORDER BY refs.
            sub = stmt.select
            if not any(it[0] == "star" for it in sub.items):
                sub.aliases = {
                    i: sub.aliases.get(i, f"__c{i}")
                    for i in range(len(sub.items))}
            res = await self._select(sub)
            stmt = InsertStmt(
                stmt.table, stmt.columns,
                [list(r.values()) for r in res.rows], stmt.ttl_ms)
            if not stmt.rows:
                return SqlResult([], "INSERT 0")
        vec_cols = {c.name for c in ct.info.schema.columns
                    if c.type == ColumnType.VECTOR}
        dec_cols = _decimal_cols(ct.info.schema)
        rows = []
        for vals in stmt.rows:
            if len(vals) != len(cols):
                raise ValueError("column/value count mismatch")
            row = dict(zip(cols, vals))
            for vc in vec_cols & set(row):
                if row[vc] is not None and not isinstance(
                        row[vc], (bytes, bytearray)):
                    row[vc] = parse_vector(row[vc]).tobytes()
            for jc in json_cols & set(row):
                # ARRAY[...] literals arrive as Python lists; JSON
                # columns store text (same shape the CQL collection
                # path writes)
                if isinstance(row[jc], (list, dict)):
                    import json as _json
                    row[jc] = _json.dumps(row[jc])
            from .parser import SeqFuncValue
            for cname, v in list(row.items()):
                if isinstance(v, SeqFuncValue):   # per inserted row
                    row[cname] = (
                        await self.client.sequence_next(v.name)
                        if v.fn == "nextval"
                        else self.client.sequence_current(v.name))
            for c in ct.info.schema.columns:
                if c.name in row:
                    continue
                # omitted columns: serial, then literal DEFAULT
                if getattr(c, "default_seq", None):
                    row[c.name] = await self.client.sequence_next(
                        c.default_seq)
                elif getattr(c, "default_value", None) is not None:
                    row[c.name] = c.default_value
            for c in ct.info.schema.columns:
                if not c.nullable and row.get(c.name) is None:
                    raise ValueError(
                        f"null value in column {c.name!r} violates "
                        f"not-null constraint")
            self._coerce_decimals(dec_cols, row)
            rows.append(row)
        self._check_check_constraints(ct, rows)
        await self._check_foreign_keys(ct, rows)
        oc = getattr(stmt, "on_conflict", None)
        if oc is not None:
            n, written = await self._insert_on_conflict(ct, stmt, rows,
                                                        oc)
        else:
            # PG semantics: plain INSERT is STRICT — an existing PK (or
            # unique value, via the index write path) raises duplicate
            # key instead of silently upserting (reference: PG INSERT
            # through the YB executor; upserts are the explicit
            # ON CONFLICT DO UPDATE form)
            ops = [RowOp("insert", r, ttl_ms=stmt.ttl_ms)
                   for r in rows]
            if self._txn is not None:
                n = await self._txn.write(stmt.table, ops)
            elif len(ops) == 1:
                n = await self.client.write(stmt.table, ops)
            else:
                # statement atomicity without a txn: one fan-out batch
                # could apply some tablets and reject another — write
                # per-TABLET batches sequentially (a tablet batch is
                # atomic server-side: the insert gate rejects it whole)
                # and compensate applied batches on failure (each
                # applied row was verifiably fresh, so deleting it
                # restores the pre-statement state)
                by_tablet: Dict[str, list] = {}
                for op in ops:
                    loc = self.client._tablet_for_key(ct, op.row)
                    by_tablet.setdefault(loc.tablet_id, []).append(op)
                done: list = []
                try:
                    for tops in by_tablet.values():
                        await self.client.write(stmt.table, tops)
                        done.extend(tops)
                except Exception:
                    pk_names = [c.name for c in
                                ct.info.schema.key_columns]
                    for op in reversed(done):
                        try:
                            await self.client.delete(
                                stmt.table,
                                [{k: op.row[k] for k in pk_names}])
                        except Exception:   # noqa: BLE001
                            pass            # best-effort compensation
                    raise
                n = len(done)
            written = rows
        if getattr(stmt, "returning", None):
            return SqlResult(
                self._returning_rows(stmt.returning, written,
                                     ct.info.schema),
                f"INSERT {n}")
        return SqlResult([], f"INSERT {n}")

    async def _insert_on_conflict(self, ct, stmt, rows, oc):
        """INSERT ... ON CONFLICT (reference: PG ON CONFLICT over
        arbiter indexes; the arbiter here is the PK or a unique-indexed
        target column).  Each row tries a strict insert; on
        DUPLICATE_KEY the arbiter is checked — a conflict the target
        does NOT cover re-raises (PG: the arbiter must infer the
        violated constraint) — then DO NOTHING skips the row and
        DO UPDATE applies the SET expressions over the EXISTING row
        with `excluded.col` resolving to the proposed value.  Returns
        (applied_count, final_rows) so RETURNING reports what was
        actually written."""
        from ..rpc.messenger import RpcError
        schema = ct.info.schema
        pk_names = [c.name for c in schema.key_columns]
        target = oc[1]
        if oc[0] == "update" and target is None:
            raise ValueError(
                "ON CONFLICT DO UPDATE requires a conflict target "
                "(column)")

        async def write(ops):
            if self._txn is not None:
                return await self._txn.write(stmt.table, ops)
            return await self.client.write(stmt.table, ops)

        async def get(pk_row):
            if self._txn is not None:
                return await self._txn.get(stmt.table, pk_row)
            return await self.client.get(stmt.table, pk_row)

        applied = 0
        final_rows = []
        for r in rows:
            try:
                await write([RowOp("insert", r, ttl_ms=stmt.ttl_ms)])
                applied += 1
                final_rows.append(r)
                continue
            except RpcError as e:
                if e.code != "DUPLICATE_KEY":
                    raise
                dup_err = e
            kind, existing = await self._conflict_row(ct, r, get)
            if existing is None:
                # the conflicting row vanished between the failed
                # insert and the lookup — retry the insert once
                await write([RowOp("insert", r, ttl_ms=stmt.ttl_ms)])
                applied += 1
                final_rows.append(r)
                continue
            if target is not None and kind != target:
                # the violated constraint is not the declared arbiter
                raise dup_err
            if oc[0] == "nothing":
                continue
            merged = await self._apply_do_update(ct, stmt, r, existing,
                                                 oc[2])
            applied += 1
            final_rows.append(merged)
        return applied, final_rows

    async def _apply_do_update(self, ct, stmt, r, existing, sets):
        """The DO UPDATE arm, with PG's row-lock semantics: the
        conflicting row is locked FOR UPDATE, the SET expressions
        evaluate over its LATEST version, and the write rides the same
        transaction — concurrent `SET v = v + excluded.v` statements
        serialize instead of losing updates.  Autocommit statements
        open an internal single-statement transaction (which also
        makes a PK-moving update's delete+insert atomic); inside an
        explicit txn the row is locked in place."""
        from ..docdb.operations import eval_expr_py as _eval
        schema = ct.info.schema
        pk_names = [c.name for c in schema.key_columns]
        pk_row = {k: existing[k] for k in pk_names}
        own_txn = None
        txn = self._txn
        if txn is None:
            own_txn = txn = await self.client.transaction().begin()
        try:
            locked = await txn.get(stmt.table, pk_row, for_update=True)
            if locked is None:
                locked = dict(existing)   # vanished: treat pre-image
            merged = dict(locked)
            idrow = {c.id: locked.get(c.name) for c in schema.columns}
            for name, e in sets.items():
                schema.column_by_name(name)     # unknown SET target
                e2 = self._subst_excluded(e, r)
                merged[name] = _eval(
                    self._bind(await self._resolve_subqueries(e2),
                               schema), idrow)
            self._check_check_constraints(ct, [merged])
            if any(merged[k] != locked.get(k) for k in pk_names):
                # SET moved the primary key: PG performs the re-keying
                # update — delete the old row, strict-insert the new
                # key (one txn: atomic; a collision there errors)
                await txn.write(stmt.table, [
                    RowOp("delete", pk_row),
                    RowOp("insert", merged, ttl_ms=stmt.ttl_ms)])
            else:
                await txn.write(stmt.table, [
                    RowOp("upsert", merged, ttl_ms=stmt.ttl_ms)])
            if own_txn is not None:
                await own_txn.commit()
            return merged
        except BaseException:
            if own_txn is not None:
                try:
                    await own_txn.abort()
                except Exception:   # noqa: BLE001
                    pass
            raise

    async def _conflict_row(self, ct, row, get):
        """(conflicting column name, existing row|None) for the
        constraint a strict insert collided with: the PK (name = the
        single pk column) or a unique-indexed column.  Inside a
        transaction the conflict may be the txn's OWN uncommitted
        write, which the committed-snapshot index lookup misses — the
        client-side write set is searched too."""
        schema = ct.info.schema
        pk_names = [c.name for c in schema.key_columns]
        if all(n in row for n in pk_names):
            got = await get({n: row[n] for n in pk_names})
            if got is not None:
                return (pk_names[0] if len(pk_names) == 1 else
                        tuple(pk_names)), got
        pend = (self._txn.pending_writes(ct.info.name)
                if self._txn is not None else {})
        for index_name, spec in (ct.indexes or {}).items():
            icols = spec.get("columns") or [spec["column"]]
            col = icols[0]
            if not spec.get("unique") or \
                    any(row.get(c) is None for c in icols):
                continue
            vals = [row[c] for c in icols]
            for op in pend.values():
                if op.kind != "delete" and all(
                        op.row.get(c) == row[c] for c in icols):
                    full = await get({n: op.row[n] for n in pk_names})
                    return col, (full if full is not None
                                 else dict(op.row))
            pks = await self.client.index_lookup(
                ct.info.name, index_name, vals)
            if pks:
                got = await get(pks[0])
                if got is not None:
                    return col, got
        return None, None

    def _subst_excluded(self, node, proposed: dict):
        """Replace excluded.col refs in an ON CONFLICT SET expression
        with the proposed row's value as a constant."""
        if not isinstance(node, tuple):
            return node
        if node[0] == "col" and isinstance(node[1], str) \
                and node[1].lower().startswith("excluded."):
            return ("const", proposed.get(node[1][9:]))
        return tuple(self._subst_excluded(x, proposed)
                     if isinstance(x, tuple) else x for x in node)

    async def _fk_children(self, parent: str):
        """[(child_table, fk_column)] referencing `parent`.  The map
        builds lazily from the catalog once per session and refreshes
        on this session's DDL; FKs created by OTHER sessions after the
        first build are missed until a refresh (documented FK-lite
        scope).  Reference: pg_constraint lookups feeding the PG
        executor's RESTRICT checks."""
        if getattr(self, "_fk_child_map", None) is None:
            m: Dict[str, list] = {}
            from ..rpc.messenger import RpcError as _RpcErr
            for t in await self.client.list_tables():
                name = t["name"]
                if "." in name:
                    continue        # system./schema-qualified vtables
                try:
                    cct = await self.client._table(name)
                except _RpcErr as e:
                    if e.code != "NOT_FOUND":
                        # a transient error must not silently disable
                        # RESTRICT for this child for the whole session
                        self._fk_child_map = None
                        raise
                    continue        # dropped concurrently
                for fk in getattr(cct, "foreign_keys", None) or []:
                    m.setdefault(fk["parent_table"], []).append(
                        (name, fk["column"],
                         fk.get("on_delete") or "restrict"))
            self._fk_child_map = m
        return self._fk_child_map.get(parent, [])

    async def _check_fk_restrict(self, ct, pk_cols, pk_rows,
                                 planned=None,
                                 all_actions: bool = False) -> None:
        """Parent-side RESTRICT: deleting a row still referenced by a
        child FK fails (reference: PG's NO ACTION/RESTRICT through the
        executor; checked via child scans — an index on the FK column
        accelerates it when present, as in PG).  The check sees the
        TRANSACTION's view: children the txn already deleted don't
        count, children it added do; and rows deleted by this SAME
        statement never count as referencing (the self-referential
        DELETE case, matching PG's end-of-statement NO ACTION)."""
        children = await self._fk_children(ct.info.name)
        if not children or len(pk_cols) != 1:
            return
        pk = pk_cols[0]
        stmt_pks = {tuple(r[k] for k in pk_cols) for r in pk_rows}
        values = [r[pk] for r in pk_rows]
        value_set = set(values)
        for child, col, action in children:
            if action in ("cascade", "set null") and not all_actions:
                # handled by the DELETE action plan; an UPDATE re-key
                # passes all_actions=True — ON DELETE actions don't
                # fire for updates, so every child vetoes (ON UPDATE
                # NO ACTION)
                continue
            cct = await self.client._table(child)
            child_pk = [c.name for c in cct.info.schema.key_columns]
            pend = (self._txn.pending_writes(child)
                    if self._txn is not None else {})
            idx_name = next(
                (n for n, spec in (cct.indexes or {}).items()
                 if spec["column"] == col), None)
            # ONE read per child table: indexed point lookups per
            # value (cheap), else a single IN-scan for the whole
            # statement's parent set
            refs = []
            if idx_name is not None:
                for v in values:
                    for p in await self.client.index_lookup(
                            child, idx_name, v):
                        refs.append({**p, col: v})
            else:
                cid = cct.info.schema.column_by_name(col).id
                resp = await self.client.scan(child, ReadRequest(
                    "", columns=tuple({col, *child_pk}),
                    where=("in", ("col", cid), list(values))))
                refs = resp.rows
            committed_pks = set()
            offender = None
            for ref in refs:
                rpk = tuple(ref.get(k) for k in child_pk)
                committed_pks.add(rpk)
                if planned is not None and \
                        rpk in planned.get(child, ()):
                    continue   # the cascade plan deletes this child
                op = pend.get(rpk)
                if op is not None:
                    if op.kind == "delete":
                        continue   # txn already deleted this child
                    # the txn's version supersedes the committed image
                    # (an UPDATE may have re-pointed the FK); a partial
                    # write without the FK column keeps the committed
                    # value
                    ref_v = op.row.get(col, ref.get(col))
                else:
                    ref_v = ref.get(col)
                if ref_v not in value_set:
                    continue
                if child == ct.info.name and rpk in stmt_pks:
                    continue   # being deleted by this statement
                offender = ref_v
                break
            if offender is None:
                # children the txn ADDED (uncommitted, not in the
                # committed scan) also reference
                for p, op in pend.items():
                    if op.kind != "delete" and p not in committed_pks \
                            and op.row.get(col) in value_set \
                            and not (child == ct.info.name
                                     and p in stmt_pks) \
                            and not (planned is not None and
                                     p in planned.get(child, ())):
                        offender = op.row.get(col)
                        break
            if offender is not None:
                raise ValueError(
                    f'update or delete on table "{ct.info.name}" '
                    f'violates foreign key constraint on table '
                    f'"{child}": key ({pk})=({offender}) is still '
                    f'referenced')

    def _invalidate_fk_children(self) -> None:
        self._fk_child_map = None

    async def _fk_referencing(self, child: str, col: str, value_set,
                              full: bool = False) -> Tuple[list, list]:
        """(child_pk_cols, child rows referencing any of value_set) in
        the TRANSACTION's view: committed rows overlaid with the txn's
        pending writes (re-pointed FKs honored, txn-deleted rows
        excluded, txn-added rows included).  `full=True` returns whole
        rows (SET NULL rewrites the row, so every column must ride
        along — upserts are full-row packed writes)."""
        cct = await self.client._table(child)
        child_pk = [c.name for c in cct.info.schema.key_columns]
        pend = (self._txn.pending_writes(child)
                if self._txn is not None else {})
        idx_name = next(
            (n for n, spec in (cct.indexes or {}).items()
             if spec["column"] == col), None)
        if idx_name is not None:
            # indexed point lookups per value beat one IN-scan; the
            # full-row case follows each index hit with a point get
            committed = []
            for v in value_set:
                for p in await self.client.index_lookup(
                        child, idx_name, v):
                    if full:
                        row = await self.client.get(child, p)
                        if row is not None:
                            committed.append(row)
                    else:
                        committed.append({**p, col: v})
        else:
            cid = cct.info.schema.column_by_name(col).id
            resp = await self.client.scan(child, ReadRequest(
                "", columns=() if full
                else tuple({col, *child_pk}),
                where=("in", ("col", cid), list(value_set))))
            committed = resp.rows
        out = []
        committed_pks = set()
        for ref in committed:
            rpk = tuple(ref.get(k) for k in child_pk)
            committed_pks.add(rpk)
            op = pend.get(rpk)
            if op is not None:
                if op.kind == "delete":
                    continue
                ref = {**ref, **op.row}
            if ref.get(col) in value_set:
                out.append(ref)
        for p, op in pend.items():
            if op.kind != "delete" and p not in committed_pks \
                    and op.row.get(col) in value_set:
                out.append(dict(op.row))
        return child_pk, out

    async def _delete_with_fk_actions(self, ct, pk_cols, pk_rows
                                      ) -> int:
        """Parent delete with ON DELETE CASCADE / SET NULL referential
        actions (reference: PG's referential action triggers — ours
        run statement-inline).  Three phases so a RESTRICT veto (or a
        NOT NULL veto on a SET NULL target) ANYWHERE in the action
        tree fires before ANY write lands:
          1. plan — breadth-first over the cascade graph collecting
             child deletes / set-nulls; `planned` (table -> pk set)
             breaks self-referential cycles, and the iteration is a
             worklist, not recursion, so chain depth is unbounded,
          2. check — every visited table's RESTRICT children veto,
             ignoring rows the plan itself deletes,
          3. execute — deepest level first (children before parents),
             the parent delete last, all under ONE statement
             subtransaction inside a txn so a mid-plan failure can't
             commit a half-applied cascade.
        Returns the parent rows_affected."""
        planned: Dict[str, set] = {}
        plan: list = []    # (table, "delete"|"set null", rows, pk_cols)
        setnull_acc: Dict[str, tuple] = {}   # child -> (pk_cols,
        #                                      {pk: merged row image})
        visited: list = []    # (cct, pk_cols, rows) for restrict pass
        planned.setdefault(ct.info.name, set()).update(
            tuple(r[k] for k in pk_cols) for r in pk_rows)
        frontier = [(ct, pk_cols, pk_rows)]
        while frontier:
            nxt = []
            for ct_, pk_cols_, rows_ in frontier:
                visited.append((ct_, pk_cols_, rows_))
                if len(pk_cols_) != 1:
                    continue   # composite-PK FK scope: restrict only
                children = await self._fk_children(ct_.info.name)
                values = {r[pk_cols_[0]] for r in rows_}
                for child, col, action in children:
                    if action not in ("cascade", "set null"):
                        continue   # restrict / no action veto below
                    child_pk, refs = await self._fk_referencing(
                        child, col, values, full=(action == "set null"))
                    refs = [r for r in refs
                            if tuple(r.get(k) for k in child_pk)
                            not in planned.get(child, ())]
                    if not refs:
                        continue
                    cct = await self.client._table(child)
                    if action == "set null":
                        cs = cct.info.schema.column_by_name(col)
                        if not cs.nullable or col in child_pk:
                            raise ValueError(
                                f'null value in column "{col}" of '
                                f'relation "{child}" violates '
                                f'not-null constraint (ON DELETE '
                                f'SET NULL)')
                        # full-row rewrite: upserts pack every value
                        # column, so the whole row must ride along.
                        # Accumulate per (child, pk) — a child with
                        # TWO set-null FKs toward the parent must null
                        # both columns in ONE row image, not restore
                        # one with the other's upsert
                        acc = setnull_acc.setdefault(
                            child, (child_pk, {}))[1]
                        for r in refs:
                            rpk = tuple(r.get(k) for k in child_pk)
                            if rpk in acc:
                                acc[rpk][col] = None
                            else:
                                acc[rpk] = {**r, col: None}
                        continue
                    # mark planned at DISCOVERY time: a same-level
                    # sibling path to the same row must not plan it
                    # twice (diamond fan-in)
                    planned.setdefault(child, set()).update(
                        tuple(r.get(k) for k in child_pk)
                        for r in refs)
                    nxt.append((cct, child_pk, refs))
                    plan.append((child, "delete", [
                        {k: r.get(k) for k in child_pk}
                        for r in refs], child_pk))
            frontier = nxt
        for child, (cpk, acc) in setnull_acc.items():
            plan.append((child, "set null", list(acc.values()), cpk))
        for ct_, pk_cols_, rows_ in visited:
            await self._check_fk_restrict(ct_, pk_cols_, rows_,
                                          planned)
        parent_rows = [{k: r[k] for k in pk_cols} for r in pk_rows]
        writes = list(reversed(plan))      # deepest level first
        writes.append((ct.info.name, "delete", parent_rows, pk_cols))

        async def execute():
            n = 0
            for child, action, rows, cpk in writes:
                if action == "set null":
                    # cascade wins over set-null on the SAME row (a
                    # child with both actions toward one parent): a
                    # planned-deleted row must not resurrect as a
                    # ghost upsert
                    rows = [r for r in rows
                            if tuple(r.get(k) for k in cpk)
                            not in planned.get(child, ())]
                    if not rows:
                        continue
                self._invalidate_stats(child)
                ops = [RowOp("upsert" if action == "set null"
                             else "delete", r) for r in rows]
                if self._txn is not None:
                    m = await self._txn.write(child, ops)
                else:
                    m = await self.client.write(child, ops)
                n = m
            return n      # last write is the parent delete

        if self._txn is None or len(writes) == 1:
            return await execute()
        # one statement subtransaction around the WHOLE cascade + the
        # parent delete (each _txn.write only brackets its own ops)
        sp = f"__fk_{self._txn._next_sub}"
        self._txn.savepoint(sp)
        try:
            n = await execute()
        except Exception:
            try:
                await self._txn.rollback_to(sp)
                self._txn.release_savepoint(sp)
            except Exception:   # noqa: BLE001 — rollback_to aborts
                pass            # the txn itself on failure
            raise
        self._txn.release_savepoint(sp)
        return n

    def _check_check_constraints(self, ct, rows) -> None:
        """CHECK constraints: a row passes unless the expression is
        FALSE (NULL passes, as in PG).  Evaluated name-based per
        written row (reference: CHECK through the PG executor)."""
        for chk in getattr(ct, "checks", None) or []:
            for row in rows:
                if _eval_by_name(chk, row) is False:
                    raise ValueError(
                        f'new row for relation "{ct.info.name}" '
                        f'violates check constraint')

    async def _check_foreign_keys(self, ct, rows) -> None:
        """FK-lite: REFERENCES enforced as an existence check inside
        the writing transaction (reference: FK enforcement through the
        PG executor over YB row locks — we check existence without the
        parent KEY SHARE lock, so a concurrent parent delete can race;
        parent-side RESTRICT is enforced by _check_fk_restrict on
        DELETE)."""
        for fk in getattr(ct, "foreign_keys", None) or []:
            col, parent = fk["column"], fk["parent_table"]
            pcol = fk["parent_column"]
            # self-referential statements: a row may reference another
            # row of the SAME statement (or itself) — PG checks per row
            # as inserted, so sibling pk values count as present
            sibling_pks = ({row.get(pcol) for row in rows}
                           if parent == ct.info.name else ())
            for row in rows:
                v = row.get(col)
                if v is None:
                    continue           # NULL FK is always valid (PG)
                if v in sibling_pks:
                    continue
                if self._txn is not None:
                    found = await self._txn.get(parent, {pcol: v})
                else:
                    found = await self.client.get(parent, {pcol: v})
                if found is None:
                    raise ValueError(
                        f'insert or update on table "{ct.info.name}" '
                        f'violates foreign key constraint: key '
                        f'({col})=({v}) is not present in table '
                        f'"{parent}"')

    # ------------------------------------------------------------------
    def _bind(self, node, schema: TableSchema):
        """Column NAMES -> column IDS in an expression AST."""
        if node is None:
            return None
        kind = node[0]
        if kind == "col":
            c = schema.column_by_name(node[1])
            if c.type == ColumnType.DECIMAL:
                # DECIMAL stores as text: comparisons/arithmetic must
                # run over decimal.Decimal, not lexicographically —
                # wrap the ref so the CPU evaluator converts (device
                # path declines 'fn' nodes and falls back)
                return ("fn", "cast_numeric", ("col", c.id))
            return ("col", c.id)
        if kind == "const":
            return node
        if kind == "fn" and node[1] == "now":
            # statement-stable clock read at bind time (PG: now() is
            # transaction-stable; ours is statement-stable)
            import time as _time
            return ("const", int(_time.time() * 1_000_000))
        if kind == "in":
            return ("in", self._bind(node[1], schema), node[2])
        if kind in ("like", "ilike"):
            return (kind, self._bind(node[1], schema), node[2])
        if kind == "json":
            return ("json", node[1], self._bind(node[2], schema), node[3])
        return (kind,) + tuple(
            self._bind(c, schema) if isinstance(c, tuple) else c
            for c in node[1:])

    def _is_serializable(self) -> bool:
        return (self._txn is not None
                and self._txn.isolation == "serializable")

    async def _lock_read_set(self, table, schema, where, read_ht) -> None:
        """Take SERIALIZABLE row locks on every row matching `where`
        (the SELECT's read set): scan just the pk columns, lock them.
        Row-level only — predicate/phantom locks are out of scope this
        round, matching the row-intent granularity of the reference."""
        pk_names = [c.name for c in schema.key_columns]
        resp = await self.client.scan(table, ReadRequest(
            "", columns=tuple(pk_names), where=where, read_ht=read_ht))
        if resp.rows:
            await self._txn.lock_rows(
                table, [{n: r[n] for n in pk_names} for r in resp.rows])

    async def _correlate(self, sub, outer_schema, outer_names):
        """Detect outer references in a subquery (reference: PG
        correlated subplans — Vars with varlevelsup > 0).  Returns
        (sub', params): sub' has every outer reference in its WHERE
        replaced by an ("outerref", bare_name) placeholder; params is
        the referenced outer column set.  A reference is OUTER when it
        is qualified with the outer table/alias, or bare, absent from
        the inner schema, and present in the outer one."""
        if sub.table is None or sub.table in self._cte_rows \
                or getattr(sub, "joins", None):
            return sub, []
        try:
            inner_schema = (await self.client._table(
                sub.table)).info.schema
        except Exception:   # noqa: BLE001 — vtable etc: no detection
            return sub, []
        inner_cols = {c.name for c in inner_schema.columns}
        outer_cols = {c.name for c in outer_schema.columns}
        # an ALIAS hides the table name inside the subquery (PG): with
        # FROM t t2, a t.x reference is an OUTER reference
        inner_quals = {sub.table_alias or sub.table}
        params: list = []

        def walk(n):
            if not isinstance(n, tuple):
                return n
            if n[0] == "col" and isinstance(n[1], str):
                q, bare = self._split_qual(n[1])
                if q is not None and q in outer_names \
                        and q not in inner_quals:
                    if bare not in params:
                        params.append(bare)
                    return ("outerref", bare)
                if q is None and bare not in inner_cols \
                        and bare in outer_cols:
                    if bare not in params:
                        params.append(bare)
                    return ("outerref", bare)
                return n
            return tuple(walk(c) if isinstance(c, tuple) else c
                         for c in n)

        if sub.where is None:
            return sub, []
        import dataclasses
        new_where = walk(sub.where)
        if not params:
            return sub, []
        return dataclasses.replace(sub, where=new_where), params

    @staticmethod
    def _subst_outerrefs(node, row: dict):
        if not isinstance(node, tuple):
            return node
        if node[0] == "outerref":
            return ("const", row.get(node[1]))
        return tuple(SqlSession._subst_outerrefs(c, row)
                     if isinstance(c, tuple) else c for c in node)

    async def _replace_corr(self, node, row: dict, cache: dict):
        """Replace every correlated marker in an AST with its computed
        plain form for this outer row."""
        if not isinstance(node, tuple):
            return node
        if node[0] == "corr":
            return await self._corr_to_ast(node, row, cache)
        out = []
        for c in node:
            out.append(await self._replace_corr(c, row, cache)
                       if isinstance(c, tuple) else c)
        return tuple(out)

    async def _corr_to_ast(self, corr, row: dict, cache: dict):
        """One correlated marker -> a plain AST for this outer row
        (executing the subquery with the row's values substituted;
        memoized per distinct parameter tuple)."""
        _, kind, sub, params = corr[:4]
        key = (id(corr), tuple(row.get(p) for p in params))
        if key in cache:
            return cache[key]
        import dataclasses
        bound_sub = dataclasses.replace(
            sub, where=self._subst_outerrefs(sub.where, row))
        if kind == "exists":
            bound_sub = dataclasses.replace(bound_sub, limit=1)
            res = await self._select(bound_sub)
            out = ("const", bool(res.rows))
        elif kind == "scalar":
            res = await self._select(bound_sub)
            if len(res.rows) > 1:
                raise ValueError(
                    "scalar subquery produced more than one row")
            v = (next(iter(res.rows[0].values()))
                 if res.rows else None)
            out = ("const", v)
        else:   # "in"
            res = await self._select(bound_sub)
            raw = [next(iter(r.values())) for r in res.rows]
            vals = sorted({v for v in raw if v is not None})
            in_node = ("in", corr[4], vals)
            if any(v is None for v in raw):
                out = ("or", in_node,
                       ("cmp", "eq", ("const", None), ("const", None)))
            else:
                out = in_node
        cache[key] = out
        return out

    async def _eval_corr_conjunct(self, node, row: dict, schema,
                                  cache: dict) -> bool:
        """Evaluate a WHERE conjunct containing correlated markers for
        one outer row."""
        plain = await self._replace_corr(node, row, cache)
        from ..docdb.operations import eval_expr_py
        idrow = {c.id: row.get(c.name) for c in schema.columns}
        return eval_expr_py(self._bind(plain, schema), idrow) is True

    @staticmethod
    def _has_corr(node) -> bool:
        if not isinstance(node, tuple):
            return False
        if node[0] == "corr":
            return True
        return any(SqlSession._has_corr(c) for c in node
                   if isinstance(c, tuple))

    async def _resolve_subqueries(self, node, seq_ok: bool = False,
                                  outer=None):
        """Replace ("in_subquery", expr, SelectStmt) with a plain
        ("in", expr, values) by running the subquery (semi-join via
        materialized value list — the reference plans these as hash
        semi-joins; ours inlines, which also keeps pushdown working).
        With `outer` = (schema, {names}) context, CORRELATED subqueries
        (referencing outer columns) defer to per-row evaluation via
        ("corr", kind, sub, params[, expr]) markers instead of
        executing here.
        seq_ok: nextval()/currval() may resolve here ONLY in
        single-row contexts (FROM-less SELECT) — statement-level
        resolution in a multi-row scan would hand every row the same
        value (PG evaluates per row), so those contexts raise."""
        if not isinstance(node, tuple):
            return node
        if node[0] == "in_subquery":
            sub = node[2]
            if outer is not None:
                sub_c, params = await self._correlate(sub, *outer)
                if params:
                    if len(sub_c.items) != 1 \
                            or sub_c.items[0][0] == "star":
                        raise ValueError(
                            "IN (SELECT ...) must produce exactly one "
                            "column")
                    inner = await self._resolve_subqueries(
                        node[1], seq_ok, outer)
                    return ("corr", "in", sub_c, params, inner)
            # static shape check (deterministic even on empty results)
            if len(sub.items) != 1 or sub.items[0][0] == "star":
                raise ValueError(
                    "IN (SELECT ...) must produce exactly one column")
            res = await self._select(sub)
            raw = [next(iter(r.values())) for r in res.rows]
            vals = sorted({v for v in raw if v is not None})
            inner = await self._resolve_subqueries(node[1])
            in_node = ("in", inner, vals)
            if len(raw) != len([v for v in raw if v is not None]):
                # SQL three-valued IN: a NULL in the list makes a non-
                # match UNKNOWN, not FALSE (matters under NOT IN) —
                # OR with an unknown term models it exactly
                return ("or", in_node,
                        ("cmp", "eq", ("const", None), ("const", None)))
            return in_node
        if node[0] == "fn" and node[1] in ("nextval", "currval"):
            if not seq_ok:
                raise ValueError(
                    f"{node[1]}() is supported in INSERT VALUES, "
                    f"serial column defaults, and single-row SELECT "
                    f"(it would evaluate once per STATEMENT here, "
                    f"not once per row)")
            arg = node[2]
            if arg[0] != "const" or not isinstance(arg[1], str):
                raise ValueError(f"{node[1]}() needs a sequence name")
            if node[1] == "nextval":
                v = await self.client.sequence_next(arg[1])
            else:
                v = self.client.sequence_current(arg[1])
            return ("const", v)
        if node[0] == "exists_subquery":
            if outer is not None:
                sub_c, params = await self._correlate(node[1], *outer)
                if params:
                    return ("corr", "exists", sub_c, params)
            # uncorrelated EXISTS: one probe row decides it
            import dataclasses
            sub = dataclasses.replace(node[1], limit=1)
            res = await self._select(sub)
            return ("const", bool(res.rows))
        if node[0] == "scalar_subquery":
            sub = node[1]
            if len(sub.items) != 1 or sub.items[0][0] == "star":
                raise ValueError(
                    "scalar subquery must produce exactly one column")
            if outer is not None:
                sub_c, params = await self._correlate(sub, *outer)
                if params:
                    return ("corr", "scalar", sub_c, params)
            res = await self._select(sub)
            if len(res.rows) > 1:
                raise ValueError(
                    "scalar subquery produced more than one row")
            v = next(iter(res.rows[0].values())) if res.rows else None
            return ("const", v)
        out = []
        for c in node:
            out.append(await self._resolve_subqueries(c, seq_ok, outer)
                       if isinstance(c, tuple) else c)
        return tuple(out)

    async def _set_op(self, stmt: SetOpStmt) -> SqlResult:
        """UNION/INTERSECT/EXCEPT combine (reference: PG set ops via
        Append/SetOp plan nodes, optimizer/prep/prepunion.c).  Operands
        run through the normal select path; rows combine POSITIONALLY
        with the left operand's column names (PG semantics); a hoisted
        trailing ORDER BY/LIMIT applies to the whole result."""
        if stmt.ctes:
            import dataclasses
            saved = dict(self._cte_rows)
            try:
                for name, sub in stmt.ctes.items():
                    self._cte_rows[name] = (await self._select(sub)).rows
                return await self._set_op(
                    dataclasses.replace(stmt, ctes={}))
            finally:
                self._cte_rows = saved
        left = await self._dispatch_inner(stmt.left)
        right = await self._dispatch_inner(stmt.right)
        names = (list(left.rows[0].keys()) if left.rows
                 else list(right.rows[0].keys()) if right.rows else [])
        if left.rows and right.rows and \
                len(left.rows[0]) != len(right.rows[0]):
            raise ValueError(
                f"each {stmt.op.upper()} query must have the same "
                f"number of columns ({len(left.rows[0])} vs "
                f"{len(right.rows[0])})")

        def freeze(v):
            return tuple(freeze(x) for x in v) if isinstance(v, list) \
                else v

        lt = [tuple(freeze(v) for v in r.values()) for r in left.rows]
        rt = [tuple(freeze(v) for v in r.values()) for r in right.rows]
        if stmt.op == "union":
            if stmt.all:
                out = lt + rt
            else:
                seen, out = set(), []
                for t in lt + rt:
                    if t not in seen:
                        seen.add(t)
                        out.append(t)
        elif stmt.op == "intersect":
            if stmt.all:
                # multiset intersection: keep min(count_l, count_r)
                from collections import Counter
                rc = Counter(rt)
                out = []
                for t in lt:
                    if rc.get(t, 0) > 0:
                        rc[t] -= 1
                        out.append(t)
            else:
                rs, seen, out = set(rt), set(), []
                for t in lt:
                    if t in rs and t not in seen:
                        seen.add(t)
                        out.append(t)
        else:   # except
            if stmt.all:
                from collections import Counter
                rc = Counter(rt)
                out = []
                for t in lt:
                    if rc.get(t, 0) > 0:
                        rc[t] -= 1
                    else:
                        out.append(t)
            else:
                rs, seen, out = set(rt), set(), []
                for t in lt:
                    if t not in rs and t not in seen:
                        seen.add(t)
                        out.append(t)
        rows = [dict(zip(names, t)) for t in out]
        if stmt.order_by:
            # resolve ordinal sentinels positionally against the
            # set-op output columns (PG: ORDER BY 1 = first column)
            stmt.order_by = [
                ((names[int(c[6:])] if c.startswith("__ord:")
                  and int(c[6:]) < len(names) else c), d)
                for c, d in stmt.order_by]
            for col, desc in reversed(stmt.order_by):
                if rows and col not in rows[0]:
                    raise ValueError(
                        f"ORDER BY column {col!r} is not in the "
                        f"set-op output")
                rows.sort(key=lambda r: (r[col] is None, r[col]),
                          reverse=desc)
        if stmt.offset:
            rows = rows[stmt.offset:]
        if stmt.limit is not None:
            rows = rows[: stmt.limit]
        return SqlResult(rows)

    async def _select(self, stmt: SelectStmt) -> SqlResult:
        """A SELECT under its `sql.plan` span: bind, the ANALYZE-driven
        choice of plan shape and the lowering to a `ReadRequest`, from
        entry to the statement's first `client.scan` (`_scan` ends the
        span there, tagged `route`).  A shape that reaches the tablets
        another way (joins, kNN, views) ends it on return."""
        with contextlib.ExitStack() as plan:
            sp = plan.enter_context(TRACES.span("sql.plan",
                                                child_only=True))
            outer, self._plan = self._plan, (plan, sp)
            try:
                return await self._select_planned(stmt)
            finally:
                self._plan = outer

    async def _scan(self, route: str, table: str, req: ReadRequest,
                    **kw):
        """`client.scan` for the SELECT being planned: planning ends
        here, with the plan shape chosen as the span's `route`."""
        if self._plan is not None:
            plan, sp = self._plan
            sp.set_tag("route", route)
            plan.close()
        return await self.client.scan(table, req, **kw)

    async def _select_planned(self, stmt: SelectStmt) -> SqlResult:
        if stmt.order_by and any(
                c.startswith("__ord:") for c, _ in stmt.order_by):
            # ORDER BY <ordinal> / ORDER BY <select-list expression>:
            # the parser encoded the matched item's index; resolve it
            # to the item's output name ONCE, before any consumer.
            # Duplicate output names would make the name-keyed sort
            # read the WRONG item's values — refuse instead.
            all_names = [self._item_name(stmt, i)
                         for i in range(len(stmt.items))]
            resolved = []
            for c, d in stmt.order_by:
                if c.startswith("__ord:"):
                    name = all_names[int(c[6:])]
                    if all_names.count(name) > 1:
                        raise ValueError(
                            f"ORDER BY position refers to output name "
                            f"{name!r} which is duplicated in the "
                            f"select list; alias the columns")
                    c = name
                resolved.append((c, d))
            stmt.order_by = resolved
        if stmt.table is not None and not getattr(stmt, "joins", None):
            # single-table FROM with an alias: SELECT e.name FROM emp e
            # — strip the alias/table qualifier everywhere so binding
            # sees bare schema names
            quals = {q for q in (getattr(stmt, "table_alias", None),
                                 stmt.table) if q}
            _dequalify_stmt(stmt, quals)
        if getattr(stmt, "ctes", None):
            # WITH: materialize each CTE in order (later CTEs and the
            # outer query see earlier ones), scoped to this statement
            import dataclasses
            saved = dict(self._cte_rows)
            try:
                for name, sub in stmt.ctes.items():
                    self._cte_rows[name] = (await self._select(sub)).rows
                return await self._select(
                    dataclasses.replace(stmt, ctes={}))
            finally:
                self._cte_rows = saved
        if (getattr(stmt, "for_update", False)
                or getattr(stmt, "for_share", False)) and (
                getattr(stmt, "joins", None) or stmt.group_by
                or stmt.distinct
                or any(it[0] in ("agg", "window") for it in stmt.items)
                or stmt.knn is not None or stmt.table is None):
            # PG restricts row locking to plain row-returning scans
            raise ValueError(
                "FOR UPDATE/FOR SHARE is not allowed with joins, "
                "aggregates, GROUP BY, DISTINCT, or window functions")
        # outer context for correlated-subquery detection: only plain
        # single-real-table scans support per-row subplan evaluation
        outer = None
        if stmt.table is not None and not getattr(stmt, "joins", None) \
                and stmt.table not in self._cte_rows:
            try:
                outer_schema = (await self.client._table(
                    stmt.table)).info.schema
                outer = (outer_schema,
                         {stmt.table, stmt.table_alias or stmt.table})
            except Exception:   # noqa: BLE001 — vtables etc.
                outer = None
        if stmt.where is not None:
            stmt.where = await self._resolve_subqueries(stmt.where,
                                                        outer=outer)
        for i, it in enumerate(stmt.items):
            if it[0] == "expr":
                stmt.items[i] = ("expr", await self._resolve_subqueries(
                    it[1], seq_ok=stmt.table is None, outer=outer))
        corr_where: list = []
        if stmt.where is not None and self._has_corr(stmt.where):
            # split AND-conjuncts: uncorrelated parts stay pushable,
            # correlated ones evaluate client-side per row (PG:
            # correlated subplans re-execute per outer row)
            stmt.where, corr_where = self._split_conjuncts(stmt.where)
        corr_items = [i for i, it in enumerate(stmt.items)
                      if it[0] == "expr" and self._has_corr(it[1])]
        if (corr_where or corr_items) and (
                stmt.group_by or stmt.distinct
                or any(it[0] in ("agg", "window") for it in stmt.items)):
            raise ValueError(
                "correlated subqueries are supported in plain row "
                "scans (no aggregates/GROUP BY/DISTINCT here yet)")
        if stmt.table is None:
            # FROM-less constant SELECT: one row of evaluated items
            row = {}
            for i, it in enumerate(stmt.items):
                if it[0] != "expr":
                    raise ValueError(
                        "FROM-less SELECT supports expressions only")
                row[self._item_name(stmt, i)] = eval_expr_py(it[1], {})
            return SqlResult([row])
        if getattr(stmt, "series", None) is not None:
            # FROM generate_series(lo, hi[, step]): materialize the set
            # (PG set-returning function; column named by the alias)
            lo, hi, step = stmt.series
            if step == 0:
                raise ValueError("generate_series step cannot be 0")
            name = stmt.table_alias or "generate_series"
            end = hi + (1 if step > 0 else -1)
            rows = [{name: v} for v in range(lo, end, step)]
            if getattr(stmt, "joins", None):
                # joined series: register the rowset like a CTE for the
                # join engine's materialized-table path, scoped to this
                # statement
                saved = self._cte_rows.get(stmt.table)
                self._cte_rows[stmt.table] = rows
                try:
                    return await self._select_join(stmt)
                finally:
                    if saved is None:
                        self._cte_rows.pop(stmt.table, None)
                    else:
                        self._cte_rows[stmt.table] = saved
            return self._rows_select(stmt, rows)
        if getattr(stmt, "joins", None):
            return await self._select_join(stmt)
        if stmt.table in self._cte_rows:
            return self._rows_select(stmt, self._cte_rows[stmt.table])
        from .pg_catalog import is_virtual, rows_for
        if is_virtual(stmt.table):
            # pg_catalog / information_schema: materialized from the
            # live catalog, then the normal row-select machinery
            return self._rows_select(
                stmt, await rows_for(stmt.table, self.client))
        from ..rpc.messenger import RpcError
        try:
            ct = await self.client._table(stmt.table)
        except RpcError as e:
            if e.code != "NOT_FOUND":
                raise
            # maybe a MATERIALIZED view: serve straight from the
            # maintained grouped partials — no scan; the read carries
            # its bounded staleness (matview/)
            mvs = self.client.matviews()
            if await mvs.lookup(stmt.table) is not None:
                mrows, meta = await mvs.read_rows(stmt.table)
                res = self._rows_select(stmt, mrows)
                res.staleness_ms = meta["staleness_ms"]
                return res
            # maybe a VIEW: materialize its body and run the outer
            # query over the rows (same machinery as a CTE table)
            view_sql = await self.client.get_view(stmt.table)
            if view_sql is None:
                raise
            inner = parse_statement(view_sql)
            rows = (await self._select(inner)).rows
            return self._rows_select(stmt, rows)
        schema = ct.info.schema
        read_ht = self._txn.start_ht if self._txn is not None else None
        where = self._bind(stmt.where, schema)
        if self._is_serializable():
            # EVERY select shape (agg, grouped, plain) locks its read
            # set; reads at the pinned start_ht snapshot plus lock-time
            # read validation make the subsequent scan stable
            await self._lock_read_set(stmt.table, schema, where, read_ht)
        agg_items = [it for it in stmt.items if it[0] == "agg"]

        if getattr(stmt, "having", None) is not None \
                and not agg_items and not stmt.group_by:
            raise ValueError("HAVING requires aggregates or GROUP BY")
        if (agg_items or getattr(stmt, "having", None) is not None) \
                and not stmt.group_by:
            refs = self._having_refs(stmt)
            exotic = any(it[1] in ("array_agg", "count_distinct",
                                   "string_agg")
                         for it in agg_items)
            if exotic or (self._txn is not None
                          and self._txn.pending_writes(stmt.table)):
                return await self._scalar_agg_clientside(
                    stmt, ct, where, refs, read_ht)
            aggs = tuple(AggSpec(op, self._bind(e, schema))
                         for _, op, e in agg_items) + \
                tuple(AggSpec(op, self._bind(e, schema))
                      for op, e in refs)
            resp = await self._scan("agg_pushdown", stmt.table, ReadRequest(
                "", where=where, aggregates=aggs, read_ht=read_ht))
            row = self._agg_row(stmt, resp.agg_values)
            row.update(self._hidden_agg_row(
                refs, resp.agg_values, self._projected_slots(stmt)))
            rows = self._having_filter(stmt, [row], refs)
            return SqlResult(rows)

        if stmt.group_by:
            if getattr(stmt, "group_exprs", None):
                # GROUP BY <expression>: synthetic per-row columns —
                # host grouping only; matching select items project
                # the computed value under their PG output name
                self._rewrite_group_expr_items(stmt)
                return await self._grouped_clientside(stmt, ct, where)
            if any(it[1] in ("array_agg", "count_distinct",
                             "string_agg")
                   for it in agg_items) or (
                    self._txn is not None
                    and self._txn.pending_writes(stmt.table)):
                # read-your-own-writes (grouped pushdown results can't
                # be patched row-wise) and host-only aggregates
                # (array_agg) group client-side over the (overlaid)
                # scan
                return await self._grouped_clientside(stmt, ct, where)
            gspec = self._group_spec(stmt, schema) if agg_items else None
            if gspec is not None:
                return await self._grouped_pushdown(stmt, ct, where, gspec)
            return await self._grouped_clientside(stmt, ct, where)

        # index-accelerated equality lookup (reference: index scans via
        # yb_lsm.c index AM) — not when correlated parts remain: the
        # early return would skip their per-row evaluation
        idx_rows = (None if (corr_where or corr_items)
                    else await self._try_index_path(stmt, ct, where))
        if idx_rows is not None:
            rows = [self._project_row(stmt, r, schema) for r in idx_rows]
            return SqlResult(self._order_limit(stmt, rows))

        # plain row scan; LIMIT pushes down only when no client-side
        # reordering/dedup/offset must happen first
        columns = self._needed_columns(stmt, schema)
        natural = self._natural_order(ct, stmt.order_by)
        has_window = any(it[0] == "window" for it in stmt.items)
        for_update = getattr(stmt, "for_update", False) \
            and self._txn is not None
        for_share = (getattr(stmt, "for_share", False)
                     and self._txn is not None
                     # SERIALIZABLE already locks the read set via
                     # _lock_read_set — a second round would be
                     # redundant RPCs
                     and not self._is_serializable())
        push_limit = (stmt.limit
                      if not (stmt.distinct or stmt.offset or has_window
                              or for_update or for_share)
                      and (natural or not stmt.order_by) else None)
        if corr_where:
            # client-side correlated filtering: project the conjuncts'
            # outer columns and never push a limit (rows drop after the
            # scan)
            need: set = set()
            for conj in corr_where:
                self._collect_names(conj, need)
            cols_set = set(columns)
            for n in need:
                bare = self._split_qual(n)[1]
                if bare not in cols_set and any(
                        c.name == bare for c in schema.columns):
                    columns = list(columns) + [bare]
                    cols_set.add(bare)
            push_limit = None
        if for_update or for_share or (
                self._txn is not None
                and self._txn.pending_writes(stmt.table)):
            # the write-set overlay (and FOR UPDATE's per-row locking)
            # needs pk columns to match rows and WHERE columns to
            # re-evaluate merged rows; and a pushed LIMIT would
            # undercount once the overlay drops rows (_order_limit
            # still applies the limit client-side)
            columns = self._overlay_columns(columns, schema, where)
            push_limit = None
        # server-side window pushdown: when every window item lowers to
        # a wire the tablet can serve bit-identically AND no client
        # stage after the scan changes the row set (correlated filters,
        # row locks, txn overlays), ship the window spec with the scan
        # and let the kernel serve the tablet's own rows
        wwire = None
        if has_window and not (corr_where or corr_items or for_update
                               or for_share) \
                and (self._txn is None
                     or not self._txn.pending_writes(stmt.table)):
            wwire = self._window_wire(stmt, schema)
        req = ReadRequest("", columns=tuple(columns), where=where,
                          read_ht=read_ht, limit=push_limit,
                          window=wwire)
        resp = await self._scan("row_scan", stmt.table, req,
                                keep_all=natural)
        base_rows = resp.rows
        if self._txn is not None:
            base_rows = self._overlay_txn_writes(
                stmt.table, schema, where, base_rows)
        if corr_where:
            base_rows = await self._filter_corr_rows(base_rows,
                                                     corr_where, schema)
        if corr_items:
            # correlated scalar subqueries in the select list: compute
            # per outer row, then project as a synthetic column under
            # the item's original output name (eval_expr_py is the
            # module-level import — a local import here would shadow it
            # for the WHOLE function, breaking earlier uses)
            cache_i: dict = {}
            for i in corr_items:
                name = self._item_name(stmt, i)
                key = f"__corr{i}"
                for r in base_rows:
                    ast = await self._replace_corr(
                        stmt.items[i][1], r, cache_i)
                    idrow = {c.id: r.get(c.name)
                             for c in schema.columns}
                    r[key] = eval_expr_py(self._bind(ast, schema),
                                          idrow)
                stmt.aliases[i] = stmt.aliases.get(i, name)
                stmt.items[i] = ("col", key)
        if for_share:
            # SELECT ... FOR SHARE: shared read locks on the matched
            # rows — readers don't block readers, writers wait and a
            # write-after-read conflicts (reference: FOR SHARE row
            # marks as kStrongRead intents)
            pk_names = [c.name for c in schema.key_columns]
            await self._txn.lock_rows(
                stmt.table,
                [{n: r[n] for n in pk_names} for r in base_rows],
                force=True)
        if for_update:
            # SELECT ... FOR UPDATE: lock each matched row exclusively
            # and re-read its LATEST committed version; rows that no
            # longer satisfy the WHERE after the lock drop out — PG's
            # EvalPlanQual recheck (reference: RowMarkType row locks
            # through pggate + docdb intents)
            pk_names = [c.name for c in schema.key_columns]
            locked = []
            for r in base_rows:
                fresh = await self._txn.get(
                    stmt.table, {n: r[n] for n in pk_names},
                    for_update=True)
                if fresh is None:
                    continue
                if where is not None:
                    idrow = {c.id: fresh.get(c.name)
                             for c in schema.columns}
                    if eval_expr_py(where, idrow) is not True:
                        continue
                locked.append(fresh)
            base_rows = locked
        if has_window and not (wwire is not None and resp.window_served):
            # unserved (typed refusal somewhere down the stack, or no
            # wire): the interpreted/device-hook client path computes
            # them — _apply_windows overwrites the out_name keys
            # unconditionally, so a partially-served fan-out can never
            # leak stale per-tablet values
            self._apply_windows(stmt, base_rows)
        rows = [self._project_row(stmt, r, schema) for r in base_rows]
        rows = self._order_limit(stmt, rows)
        return SqlResult(rows)

    @staticmethod
    def _overlay_columns(columns, schema, where):
        """Extend a scan projection with the pk + WHERE columns the
        txn write-set overlay needs (extras drop at projection time)."""
        from ..ops.expr import referenced_columns
        by_id = {c.id: c.name for c in schema.columns}
        need = list(columns)
        for c in schema.key_columns:
            if c.name not in need:
                need.append(c.name)
        if where is not None:
            for cid in referenced_columns(where):
                name = by_id.get(cid)
                if name is not None and name not in need:
                    need.append(name)
        return need

    async def _scalar_agg_clientside(self, stmt, ct, where, refs,
                                     read_ht) -> SqlResult:
        """Scalar aggregates inside a txn with pending writes on the
        table: the device pushdown result can't be patched row-wise, so
        scan the needed columns, overlay the write set, and fold the
        aggregates on the host (reference: pggate flushes buffered ops
        before reads; we overlay instead — same visible semantics)."""
        schema = ct.info.schema
        agg_items = [it for it in stmt.items if it[0] == "agg"]
        needed: set = set()
        for _, op, e in agg_items:
            if e is not None:
                self._collect_names(e, needed)
        for _op, e in refs:
            if e is not None:
                self._collect_names(e, needed)
        cols = self._overlay_columns(sorted(needed), schema, where)
        resp = await self._scan("agg_clientside", stmt.table, ReadRequest(
            "", columns=tuple(cols), where=where, read_ht=read_ht))
        rows = self._overlay_txn_writes(stmt.table, schema, where,
                                        resp.rows)
        bound = [(op, self._bind(e, schema) if e else None)
                 for _, op, e in agg_items] + \
            [(op, self._bind(e, schema) if e else None)
             for op, e in refs]
        st = [_init(op) for op, _ in bound]
        for r in rows:
            idrow = {schema.column_by_name(k).id: v
                     for k, v in r.items()}
            for i, (op, e) in enumerate(bound):
                st[i] = _step(op, e, st[i], idrow)
        # expand into the (avg -> sum, count) slot layout _agg_row /
        # _hidden_agg_row decode
        values: list = []
        for (op, _e), s in zip(bound, st):
            if op == "avg":
                s = s or (0, 0)
                values.extend([s[0] if s[1] else None, s[1]])
            else:
                values.append(_final(op, s))
        row = self._agg_row(stmt, values)
        row.update(self._hidden_agg_row(
            refs, values, self._projected_slots(stmt)))
        return SqlResult(self._having_filter(stmt, [row], refs))

    def _overlay_txn_writes(self, table: str, schema, where, rows):
        """Read-your-own-writes for plain scans inside a transaction:
        the txn's client-side write set replaces/adds/deletes rows over
        the snapshot scan (reference: pggate buffered-operation reads).
        Aggregate and grouped queries route through the client-side
        fold paths, which overlay the same way."""
        if self._txn is None:
            return rows
        pend = self._txn.pending_writes(table)
        if not pend:
            return rows
        from ..docdb.operations import eval_expr_py
        pk_names = [c.name for c in schema.key_columns]

        def keep(r: dict) -> bool:
            if where is None:
                return True
            idrow = {c.id: r.get(c.name) for c in schema.columns}
            return eval_expr_py(where, idrow) is True

        out = []
        seen = set()
        for r in rows:
            pk = tuple(r.get(k) for k in pk_names)
            op = pend.get(pk)
            if op is None:
                out.append(r)
                continue
            seen.add(pk)
            if op.kind == "delete":
                continue
            merged = {**r, **op.row}
            if keep(merged):
                out.append(merged)
        for pk, op in pend.items():
            if pk in seen or op.kind == "delete":
                continue
            if keep(op.row):
                out.append(dict(op.row))
        return out

    async def _try_index_path(self, stmt, ct, where_bound):
        """WHERE col = const (optionally AND residual) with a secondary
        index on col -> index lookup + point gets + residual filter."""
        if not ct.indexes or stmt.where is None or self._txn is not None:
            return None
        eq = self._extract_index_eq(stmt.where, ct)
        if eq is None:
            return None
        index_name, value, residual = eq
        pks = await self.client.index_lookup(stmt.table, index_name, value)
        rows = []
        schema = ct.info.schema
        for pk in pks:
            row = await self.client.get(stmt.table, pk)
            if row is None:
                continue
            if residual is not None:
                idrow = {schema.column_by_name(k).id: v
                         for k, v in row.items()}
                from ..docdb.operations import eval_expr_py
                if eval_expr_py(self._bind(residual, schema),
                                idrow) is not True:
                    continue
            rows.append(row)
        return rows

    def _extract_index_eq(self, node, ct):
        """Match `col = const` or `col = const AND residual`; returns
        (index_name, value, residual_ast|None)."""
        indexed = {spec["column"]: name
                   for name, spec in (ct.indexes or {}).items()}

        def match_eq(n):
            if n[0] == "cmp" and n[1] == "eq":
                l, r = n[2], n[3]
                if l[0] == "col" and r[0] == "const" and l[1] in indexed:
                    return indexed[l[1]], r[1]
                if r[0] == "col" and l[0] == "const" and r[1] in indexed:
                    return indexed[r[1]], l[1]
            return None

        m = match_eq(node)
        if m:
            return m[0], m[1], None
        if node[0] == "and":
            for i, j in ((1, 2), (2, 1)):
                m = match_eq(node[i])
                if m:
                    return m[0], m[1], node[j]
        return None

    @staticmethod
    def _split_qual(name: str):
        return name.split(".", 1) if "." in name else (None, name)

    def _join_pushdown(self, stmt: SelectStmt):
        """Split the WHERE into per-table pushable conjuncts (reference:
        pushdown classification in src/postgres .../ybplan.c). A
        conjunct pushes to table T when every referenced column resolves
        UNIQUELY to T — via a 'T.col' qualifier (alias-aware) or a bare
        name found in exactly one joined real table — and T is not the
        NULL-SUPPLYING side of any outer join (filtering that side
        before the join changes which rows NULL-extend: WHERE sal IS
        NULL over a RIGHT JOIN must see the real match set). Pushed
        conjuncts stay in the residual too: NULL-extended rows must
        still be filtered, and double evaluation of inner rows is
        harmless."""
        lbl0 = stmt.table_alias or stmt.table
        tables = [lbl0] + [j.alias or j.table for j in stmt.joins]
        nullable = set()
        for j in stmt.joins:
            jl = j.alias or j.table
            if j.kind in ("right", "full"):
                nullable.add(lbl0)
                nullable.update(j2.alias or j2.table
                                for j2 in stmt.joins if j2 is not j)
            if j.kind in ("left", "full"):
                nullable.add(jl)
        per_table: Dict[str, list] = {}
        if stmt.where is None:
            return per_table

        def owner_of(names: set) -> Optional[str]:
            owner = None
            for name in names:
                q, bare = self._split_qual(name)
                cands = []
                for t in tables:
                    if q is not None and q != t:
                        continue
                    sch = self._join_schemas.get(t)
                    if sch is None:
                        # CTE/virtual/unknown: cannot prove ownership
                        # of a bare name — only a qualifier decides
                        if q == t:
                            cands.append(t)
                        elif q is None:
                            return None
                        continue
                    try:
                        sch.column_by_name(bare)
                        cands.append(t)
                    except Exception:  # noqa: BLE001 — not this table
                        pass
                if len(cands) != 1:
                    return None
                if owner is None:
                    owner = cands[0]
                elif owner != cands[0]:
                    return None
            return owner

        for c in _conjuncts(stmt.where):
            names: set = set()
            self._collect_names(c, names)
            if not names:
                continue
            owner = owner_of(names)
            if owner is not None and owner not in nullable \
                    and self._join_schemas.get(owner) is not None:
                per_table.setdefault(owner, []).append(
                    _strip_qualifiers(c))
        return per_table

    def _ambiguous_bare_refs(self, stmt: SelectStmt, schemas) -> bool:
        """True when any BARE column reference in the statement exists
        in 2+ of the joined schemas: such a reference resolves to the
        merge-order winner, so ANY reorder could flip the value it
        sees — the written order must stand."""
        names: set = set()
        if stmt.where is not None:
            self._collect_names(stmt.where, names)
        for it in stmt.items:
            if it[0] == "col":
                names.add(it[1])
            elif it[0] in ("expr", "agg") and it[-1] is not None \
                    and isinstance(it[-1], tuple):
                self._collect_names(it[-1], names)
            elif it[0] == "window":
                # ('window', fn, expr|None, partition, worder)
                if it[2] is not None and isinstance(it[2], tuple):
                    self._collect_names(it[2], names)
                names |= set(it[3] or ())
                names |= {n for n, _ in (it[4] or ())}
        names |= {n for n, _ in stmt.order_by}
        names |= set(stmt.group_by)
        for name in names:
            q, bare = self._split_qual(name)
            if q is not None:
                continue
            holders = sum(1 for sch in schemas
                          if any(c.name == bare for c in sch.columns))
            if holders >= 2:
                return True
        return False

    def _maybe_reorder_joins(self, stmt: SelectStmt) -> None:
        """Greedy left-deep join ordering for ALL-INNER equi-join
        chains of 2+ joins (reference: the PG planner's cheapest-path
        ordering over ANALYZE cardinalities + batched-NL costing,
        nodeYbBatchedNestloop.c; yql/pggate/pg_doc_op.h:115-126 for the
        per-hop BNL batch fan-out the order controls).  The smallest
        estimated table becomes the outer; each hop adds the smallest
        remaining table CONNECTED to the placed set (a disconnected
        pick would be a cross join).  Requires ANALYZE counts and
        schemas for every side; single joins keep the swap path."""
        if len(stmt.joins) < 2:
            return self._maybe_swap_join(stmt)
        if any(j.kind != "inner" for j in stmt.joins):
            return
        if any(it[0] == "star" for it in stmt.items):
            return           # SELECT * follows the written order (PG)
        labels = [stmt.table_alias or stmt.table] + \
            [j.alias or j.table for j in stmt.joins]
        real_of = {stmt.table_alias or stmt.table: stmt.table}
        alias_of = {stmt.table_alias or stmt.table: stmt.table_alias}
        for j in stmt.joins:
            real_of[j.alias or j.table] = j.table
            alias_of[j.alias or j.table] = j.alias
        if any(real_of[l] in self._cte_rows for l in labels):
            return
        schemas = {l: (self._join_schemas or {}).get(l) for l in labels}
        if any(s is None for s in schemas.values()):
            return
        counts = {l: self.rowcounts.get(real_of[l]) for l in labels}
        if any(c is None for c in counts.values()):
            return
        if self._ambiguous_bare_refs(stmt, list(schemas.values())):
            return

        def owner_of(col: str, exclude: str):
            """Label owning a (possibly qualified) column reference."""
            q, bare = self._split_qual(col)
            if q is not None:
                return q if q in schemas else None
            holders = [l for l in labels if l != exclude
                       and any(c.name == bare
                               for c in schemas[l].columns)]
            return holders[0] if len(holders) == 1 else None

        # undirected equi-join edges: (label_a, col_a, label_b, col_b)
        edges = []
        for j in stmt.joins:
            jl = j.alias or j.table
            ol = owner_of(j.left_col, exclude=jl)
            if ol is None:
                return       # can't prove which side the key lives on
            edges.append((ol, self._split_qual(j.left_col)[1],
                          jl, self._split_qual(j.right_col)[1]))

        order = [min(labels, key=lambda l: counts[l])]
        new_joins = []
        remaining = list(edges)
        while len(order) < len(labels):
            placed = set(order)
            cands = {}
            for (a, ca, b, cb) in remaining:
                if a in placed and b not in placed:
                    cands.setdefault(b, (a, ca, cb))
                elif b in placed and a not in placed:
                    cands.setdefault(a, (b, cb, ca))
            if not cands:
                return       # disconnected: would need a cross join
            nxt = min(cands, key=lambda l: counts[l])
            anchor, acol, ncol = cands[nxt]
            from .parser import JoinClause
            new_joins.append(JoinClause(
                real_of[nxt], "inner",
                left_col=f"{anchor}.{acol}", right_col=ncol,
                alias=alias_of[nxt] if alias_of[nxt] is not None
                else (nxt if nxt != real_of[nxt] else None)))
            order.append(nxt)
            remaining = [e for e in remaining
                         if not ((e[0] == nxt and e[2] == anchor)
                                 or (e[2] == nxt and e[0] == anchor))]
        if order == labels:
            return           # stats agree with the written order
        base = order[0]
        stmt.table = real_of[base]
        stmt.table_alias = alias_of[base] if alias_of[base] is not None \
            else (base if base != real_of[base] else None)
        stmt.joins = new_joins

    def _maybe_swap_join(self, stmt: SelectStmt) -> None:
        """Cost-based join-order choice for a single INNER equi-join
        (reference: the PG planner's cheapest-path join ordering fed by
        ANALYZE): the SMALLER side should be the OUTER — fewer rows
        fetched eagerly and fewer distinct keys pushed down in BNL
        batches. Uses ANALYZE row counts; without stats for both sides
        the written order stands."""
        if len(stmt.joins) != 1 or stmt.joins[0].kind != "inner":
            return
        if any(it[0] == "star" for it in stmt.items):
            # SELECT * column order follows the WRITTEN table order;
            # a swap would flip it (PG keeps projection order stable
            # regardless of join order)
            return
        jc = stmt.joins[0]
        if stmt.table in self._cte_rows or jc.table in self._cte_rows:
            # a CTE shadowing a base-table name would both hijack the
            # base table's rowcount estimate and dodge the ambiguity
            # guard (no schema) — written order stands
            return
        left_n = self.rowcounts.get(stmt.table)
        right_n = self.rowcounts.get(jc.table)
        if left_n is None or right_n is None or right_n >= left_n:
            return
        schemas = [s for s in (self._join_schemas or {}).values()
                   if s is not None]
        if len(schemas) != 2:
            return     # can't prove the swap is reference-safe
        # a bare column name living in BOTH tables resolves to the
        # merge-order winner; a swap would flip which value an
        # ambiguous reference sees — keep the written order there
        if self._ambiguous_bare_refs(stmt, schemas):
            return
        from .parser import JoinClause
        stmt.table, jc_table = jc.table, stmt.table
        stmt.table_alias, jc_alias = jc.alias, stmt.table_alias
        stmt.joins = [JoinClause(jc_table, "inner", jc.right_col,
                                 jc.left_col, jc_alias)]

    async def _gather_join_schemas(self, stmt):
        """(label -> schema|None, label -> real table name) for every
        side of a join query — label is the alias when given. None
        schema = CTE / virtual / unknown (resolved at fetch time).
        Shared by execution and EXPLAIN so the two can never drift."""
        from .pg_catalog import is_virtual
        pairs = [(stmt.table_alias or stmt.table, stmt.table)] + \
            [(j.alias or j.table, j.table) for j in stmt.joins]
        schemas, real_of = {}, {}
        for label, tname in pairs:
            real_of[label] = tname
            sch = None
            if tname not in self._cte_rows and not is_virtual(tname):
                try:
                    sch = (await self.client._table(tname)).info.schema
                except Exception:  # noqa: BLE001 — resolved at fetch
                    sch = None
            schemas[label] = sch
        return schemas, real_of

    async def _select_join(self, stmt: SelectStmt) -> SqlResult:
        """Joins executed at the client tier, like the reference's PG
        backend over pggate — but with the storage engine doing the
        filtering: single-table WHERE conjuncts push into each side's
        scan, and the inner side of an equi-join fetches by BATCHES of
        join keys pushed down as IN-lists (reference:
        src/postgres/src/backend/executor/nodeYbBatchedNestloop.c)
        instead of materializing the whole table. Falls back to a full
        inner fetch + hash join when the outer key set is large. Join
        order for single inner joins is cost-chosen from ANALYZE row
        counts (_maybe_swap_join)."""
        from ..docdb.operations import eval_expr_py
        from .pg_catalog import is_virtual, rows_for
        if self._is_serializable():
            for tname in [stmt.table] + [j.table for j in stmt.joins]:
                if tname in self._cte_rows or is_virtual(tname):
                    continue   # materialized rows: nothing to lock
                jct = await self.client._table(tname)
                await self._lock_read_set(
                    tname, jct.info.schema, None, self._txn.start_ht)
        self._join_schemas, real_of = \
            await self._gather_join_schemas(stmt)
        self._maybe_reorder_joins(stmt)   # labels survive the reorder
        lbl0 = stmt.table_alias or stmt.table
        pushed = self._join_pushdown(stmt)
        fused = await self._try_fused_join(stmt, pushed, real_of)
        if fused is not None:
            return fused

        # a name bound by the current WITH scope reads the CTE rowset;
        # pg_catalog/information_schema names materialize virtual rows
        async def fetch(label, extra=None):
            table = real_of.get(label, label)
            if table in self._cte_rows:
                return self._cte_rows[table]
            if is_virtual(table):
                return await rows_for(table, self.client)
            sch = self._join_schemas[label]
            node = None
            for c in pushed.get(label, ()):
                node = c if node is None else ("and", node, c)
            if extra is not None:
                node = extra if node is None else ("and", node, extra)
            where = self._bind(node, sch) if node is not None else None
            resp = await self.client.scan(table,
                                          ReadRequest("", where=where))
            return resp.rows

        async def fetch_inner(jc, label, keys):
            """Batched-IN fetch of the join's inner side; None when the
            key set is too large (caller full-scans instead)."""
            if (jc.table in self._cte_rows or is_virtual(jc.table)
                    or self._join_schemas[label] is None):
                return None
            keys = [k for k in keys if k is not None]
            if len(keys) > flags.get("bnl_max_keys"):
                return None
            _, rcol = self._split_qual(jc.right_col)
            try:
                self._join_schemas[label].column_by_name(rcol)
            except Exception:  # noqa: BLE001 — joined on expr/alias
                return None
            batch = flags.get("bnl_batch_size")
            out = []
            for i in range(0, len(keys), batch):
                out.extend(await fetch(
                    label, ("in", ("col", rcol), keys[i:i + batch])))
            return out

        left_rows = await fetch(lbl0)
        # qualify row dicts: {"t.col": v, "col": v (unqualified wins last)}
        def qualify(rows, tname):
            out = []
            for r in rows:
                q = {f"{tname}.{k}": v for k, v in r.items()}
                q.update(r)
                out.append(q)
            return out

        rows = qualify(left_rows, lbl0)
        for jc in stmt.joins:
            jlabel = jc.alias or jc.table
            right_rows = None
            if jc.kind in ("inner", "left"):
                # outer-key batches push down; dedup preserves order
                lkey = self._split_qual(jc.left_col)[1]
                keys = list(dict.fromkeys(
                    lr.get(jc.left_col, lr.get(lkey)) for lr in rows))
                right_rows = await fetch_inner(jc, jlabel, keys)
            if right_rows is None:
                right_rows = await fetch(jlabel)
            right_rows = qualify(right_rows, jlabel)
            # NULL-extension column set: when the (batched) inner fetch
            # returned nothing, the schema still names the columns the
            # outer rows must carry as NULLs
            if right_rows:
                right_cols = set(right_rows[0])
            elif self._join_schemas.get(jlabel) is not None:
                names = [c.name for c in
                         self._join_schemas[jlabel].columns]
                right_cols = {f"{jlabel}.{n}" for n in names} | set(names)
            else:
                right_cols = set()
            # build hash table on the right join key
            _, rcol = self._split_qual(jc.right_col)
            index: Dict[object, list] = {}
            for rr in right_rows:
                index.setdefault(rr.get(jc.right_col, rr.get(rcol)),
                                 []).append(rr)
            joined = []
            matched_right: set = set()
            for lr in rows:
                key = lr.get(jc.left_col,
                             lr.get(self._split_qual(jc.left_col)[1]))
                matches = index.get(key, [])
                if matches:
                    for rr in matches:
                        merged = dict(lr)
                        merged.update(rr)
                        joined.append(merged)
                        matched_right.add(id(rr))
                elif jc.kind in ("left", "full"):
                    merged = dict(lr)
                    for k in right_cols:
                        merged.setdefault(k, None)
                    joined.append(merged)
            if jc.kind in ("right", "full"):
                # unmatched right rows with NULL left columns
                left_keys = set(rows[0]) if rows else set()
                for rr in right_rows:
                    if id(rr) not in matched_right:
                        merged = {k: None for k in left_keys}
                        merged.update(rr)
                        joined.append(merged)
            rows = joined
        # residual WHERE over merged rows (by name, not ids)
        if stmt.where is not None:
            rows = [r for r in rows
                    if _eval_by_name(stmt.where, r) is True]
        if stmt.group_by or any(it[0] == "agg" for it in stmt.items):
            # aggregates over the join result: the materialized-rows
            # engine (same machinery as CTE sources)
            import dataclasses
            sub = dataclasses.replace(stmt, where=None, joins=[],
                                      ctes={})
            return self._rows_select(sub, rows)
        if any(it[0] == "window" for it in stmt.items):
            self._apply_windows(stmt, rows)
        out = []
        for r in rows:
            if any(it[0] == "star" for it in stmt.items):
                out.append({k: v for k, v in r.items() if "." not in k})
                continue
            row = {}
            for i, it in enumerate(stmt.items):
                if it[0] == "col":
                    _, bare = self._split_qual(it[1])
                    alias = getattr(stmt, "aliases", {}).get(i)
                    row[alias or bare] = r.get(it[1], r.get(bare))
                elif it[0] == "window":
                    name = self._item_name(stmt, i)
                    row[name] = r.get(name)
            # carry sort-only columns through the projection so
            # _order_limit can sort by them (it strips them after).
            # A QUALIFIED ref (t.col) always means the table column —
            # never an output alias that happens to share the bare name
            # (PG: aliases are only reachable by their bare name) — so
            # it carries under its qualified key even when an alias
            # shadows the bare one.
            for col, _d in stmt.order_by:
                if col in row:
                    continue
                q, bare = self._split_qual(col)
                if q is None:
                    if bare not in row:
                        row[col] = r.get(col, r.get(bare))
                else:
                    row[col] = r.get(col)
            out.append(row)
        return SqlResult(self._order_limit(stmt, out))

    # --- fused join+group+aggregate pushdown (ops/plan_fusion.py) -------
    class _NoFuse(Exception):
        pass

    async def _try_fused_join(self, stmt: SelectStmt, pushed,
                              real_of) -> Optional[SqlResult]:
        """Historical entry point — now a thin wrapper over the general
        plan-lowering pass (which subsumes the original single-join
        shape as the 1-stage case)."""
        return await self._lower_fused_plan(stmt, pushed, real_of)

    async def _lower_fused_plan(self, stmt: SelectStmt, pushed,
                                real_of) -> Optional[SqlResult]:
        """General plan-lowering pass: an all-INNER FK-equijoin TREE
        (left-deep chain like lineitem⋈orders⋈customer, or a star with
        several dimensions hanging off the probe table) + GROUP BY +
        aggregates lowers to ONE fused plan — each (filtered) build
        side ships as a probe STAGE in an ordered JoinWire sequence
        with the probe-table scan request, and the whole
        filter->probe_1..probe_N->gather->group->aggregate shape runs
        as one device program per tablet (ops/plan_fusion.py), partials
        combining through the ordinary grouped fan-out combine.  A
        chain stage probes an EARLIER stage's payload lane; a star
        stage probes a probe-table column.  Arithmetic-free window
        TAILS over the grouped output ride along client-side on the
        (small) result rows.  The operator-at-a-time client join stays
        the path for every shape this doesn't cover (None return), and
        `plan_fusion_enabled` off restores it wholesale."""
        if not (flags.get("plan_fusion_enabled")
                and flags.get("join_pushdown_enabled")):
            return None
        if not stmt.joins or any(j.kind != "inner" for j in stmt.joins):
            return None
        if len(stmt.joins) > int(flags.get("multi_join_max_stages")):
            return None   # stage budget: the classic client join (the
            #               server would refuse typed anyway — don't
            #               fetch N build sides just to hear it)
        if getattr(stmt, "having", None) is not None \
                or getattr(stmt, "distinct", False) \
                or getattr(stmt, "group_exprs", None):
            return None
        from .pg_catalog import is_virtual
        lbl0 = stmt.table_alias or stmt.table
        build_lbls = [j.alias or j.table for j in stmt.joins]
        labels = [lbl0] + build_lbls
        if len(set(labels)) != len(labels):
            return None   # duplicate labels: ownership can't be proven
        for lbl in labels:
            tname = real_of.get(lbl, lbl)
            if tname in self._cte_rows or is_virtual(tname):
                return None
            if self._txn is not None and self._txn.pending_writes(tname):
                return None   # write-set overlay can't patch partials
            if self._join_schemas.get(lbl) is None:
                return None
        agg_items = [(i, it) for i, it in enumerate(stmt.items)
                     if it[0] == "agg"]
        if not agg_items or any(it[0] not in ("agg", "col", "window")
                                for it in stmt.items):
            return None
        if any(it[1] not in ("sum", "count", "min", "max", "avg")
               for _, it in agg_items):
            return None
        gset = {self._split_qual(g)[1] for g in stmt.group_by}
        for i, it in enumerate(stmt.items):
            if it[0] == "col" and self._split_qual(it[1])[1] not in gset:
                return None
            if it[0] == "window":
                # window TAIL over the grouped output: arithmetic-free
                # heads only, partition/order drawn from the group keys
                # (those are the columns the result rows carry)
                if it[2] is not None:
                    return None
                if getattr(stmt, "aliases", None):
                    return None   # an alias could shadow a ref's key
                refs = set(it[3] or ()) | {n for n, _ in (it[4] or ())}
                if any(self._split_qual(r)[1] not in gset or
                       self._split_qual(r)[0] is not None
                       for r in refs):
                    return None
        # the WHERE must split entirely into single-side conjuncts
        # (cross-table residuals need the materialized join) — the
        # SAME splitter _join_pushdown used, so the totality check
        # counts exactly what was pushed
        if stmt.where is not None:
            total = len(_conjuncts(stmt.where))
            if sum(len(v) for v in pushed.values()) != total:
                return None
        if any(lbl not in labels for lbl in pushed):
            return None

        def _has(sch, bare):
            try:
                return sch.column_by_name(bare)
            except Exception:  # noqa: BLE001 — not this table
                return None

        def side_of(name):
            """(owning label, ColumnSchema) — alias-aware qualified
            refs win; a bare name must live in exactly ONE side."""
            q, bare = self._split_qual(name)
            cands = []
            for lbl in labels:
                if q is not None and q != lbl:
                    continue
                col = _has(self._join_schemas[lbl], bare)
                if col is not None:
                    cands.append((lbl, col))
            return cands[0] if len(cands) == 1 else None

        from ..ops.join_scan import BUILD_COL_BASE, JoinWire
        # ONE payload-id counter across every stage: lanes are a shared
        # namespace inside the fused program (the kernel refuses typed
        # on collisions; a shared counter makes them impossible here)
        payload_ids: Dict[str, Dict[str, int]] = {l: {}
                                                  for l in build_lbls}
        nxt_bid = [BUILD_COL_BASE]
        agg_payload: set = set()

        def lane_of(lbl, name):
            ids = payload_ids[lbl]
            if name not in ids:
                ids[name] = nxt_bid[0]
                nxt_bid[0] += 1
            return ids[name]

        def bind_mixed(n, in_agg=False):
            if not isinstance(n, tuple):
                return n
            if n[0] == "col":
                s = side_of(n[1])
                if s is None:
                    raise self._NoFuse()
                lbl, col = s
                if lbl == lbl0:
                    if col.type == ColumnType.DECIMAL:
                        # mirror _bind: DECIMAL stores as text — wrap
                        # so the (interpreted) evaluator converts; the
                        # device path declines fn nodes and falls back
                        return ("fn", "cast_numeric", ("col", col.id))
                    return ("col", col.id)
                if col.type == ColumnType.DECIMAL:
                    raise self._NoFuse()   # payload can't ship decimals
                if in_agg:
                    agg_payload.add((lbl, col.name))
                return ("col", lane_of(lbl, col.name))
            if n[0] == "const":
                return n
            if n[0] == "fn" and n[1] == "now":
                # mirror _bind: statement-stable clock read, folded at
                # bind time (never per-row on the server)
                import time as _time
                return ("const", int(_time.time() * 1_000_000))
            if n[0] in ("in", "like", "ilike", "dictlut"):
                return (n[0], bind_mixed(n[1], in_agg)) + tuple(n[2:])
            return (n[0],) + tuple(
                bind_mixed(c, in_agg) if isinstance(c, tuple) else c
                for c in n[1:])

        # join KEYS must be exactly representable as int64 or strings —
        # FLOAT64 keys would truncate under int() and silently change
        # which rows match; the classic client join owns float keys
        _keyable = (ColumnType.INT32, ColumnType.INT64,
                    ColumnType.TIMESTAMP, ColumnType.BOOL,
                    ColumnType.STRING)
        try:
            # per-stage key resolution, in the WRITTEN join order: one
            # key column on the NEW build table, the other on the probe
            # table (star stage) or an EARLIER build (chain stage —
            # probes that stage's payload lane)
            stages = []   # (build label, build key col, probe_ref)
            for si, jc in enumerate(stmt.joins):
                jlabel = build_lbls[si]
                s_l, s_r = side_of(jc.left_col), side_of(jc.right_col)
                if s_l is None or s_r is None:
                    return None
                if (s_l[0] == jlabel) == (s_r[0] == jlabel):
                    return None   # both (or neither) on the new build
                (anchor_lbl, anchor_col), (_, build_key) = (
                    (s_l, s_r) if s_r[0] == jlabel else (s_r, s_l))
                if build_key.type not in _keyable:
                    return None
                if anchor_lbl == lbl0:
                    probe_ref = ("p", anchor_col)
                else:
                    if anchor_lbl not in build_lbls[:si]:
                        return None   # anchor must ALREADY be placed
                    if anchor_col.type not in _keyable:
                        return None
                    # the chain anchor becomes a payload lane of the
                    # earlier stage — shipped even when unprojected
                    probe_ref = ("lane", anchor_lbl, anchor_col)
                stages.append((jlabel, build_key, probe_ref))
            aggs = []
            for _i, it in agg_items:
                if it[2] is None:
                    aggs.append(AggSpec("count"))
                else:
                    aggs.append(AggSpec(it[1], bind_mixed(it[2],
                                                          in_agg=True)))
            gcols = []
            for g in stmt.group_by:
                s = side_of(g)
                if s is None or s[1].type != ColumnType.STRING:
                    return None     # dict-group shape: string keys only
                lbl, col = s
                if lbl == lbl0:
                    gcols.append(col.id)
                else:
                    gcols.append(lane_of(lbl, col.name))
            pw = None
            for c in pushed.get(lbl0, ()):
                pw = c if pw is None else ("and", pw, c)
            pwhere = bind_mixed(pw) if pw is not None else None
            # register chain-anchor lanes LAST so expr/group lanes get
            # stable ids whether or not the anchor is also projected
            for jlabel, build_key, probe_ref in stages:
                if probe_ref[0] == "lane":
                    lane_of(probe_ref[1], probe_ref[2].name)
        except self._NoFuse:
            return None
        # payload columns referenced by AGGREGATES must be numeric —
        # string payloads ride as dictionary codes, which only group
        # keys may consume (an aggregate over codes would be garbage)
        _numeric = (ColumnType.INT32, ColumnType.INT64,
                    ColumnType.TIMESTAMP, ColumnType.BOOL,
                    ColumnType.FLOAT64)
        for lbl, name in agg_payload:
            if _has(self._join_schemas[lbl], name).type not in _numeric:
                return None
        # --- fetch + ship the (filtered) build sides ------------------
        # the probe's txn read point applies to every build scan too —
        # a mixed-snapshot join (build at latest, probe at start_ht)
        # could produce a row set no single snapshot contains
        read_ht = self._txn.start_ht if self._txn is not None else None

        async def fetch_build(jlabel, build_key):
            bsch = self._join_schemas[jlabel]
            bw = None
            for c in pushed.get(jlabel, ()):
                bw = c if bw is None else ("and", bw, c)
            bwhere = self._bind(bw, bsch) if bw is not None else None
            bcols = tuple({build_key.name, *payload_ids[jlabel]})
            return await self.client.scan(
                real_of.get(jlabel, jlabel),
                ReadRequest("", columns=bcols, where=bwhere,
                            read_ht=read_ht))

        bresps = await asyncio.gather(
            *[fetch_build(jlabel, build_key)
              for jlabel, build_key, _ in stages])
        wires = []
        for (jlabel, build_key, probe_ref), bresp in zip(stages, bresps):
            bsch = self._join_schemas[jlabel]
            keys, prows = [], []
            for r in bresp.rows:
                k = r.get(build_key.name)
                if k is None:
                    continue          # NULL keys can never inner-match
                keys.append(k)
                prows.append(r)
            if len(set(keys)) != len(keys):
                return None   # duplicate build keys multiply rows: the
                #               materialized client join owns that shape
            if build_key.type == ColumnType.STRING:
                keys_arr = np.asarray(keys, object)
            else:
                keys_arr = np.asarray([int(k) for k in keys], np.int64)
            payload = {}
            for name, bid in payload_ids[jlabel].items():
                col = _has(bsch, name)
                vals = [r.get(name) for r in prows]
                nulls = np.asarray([v is None for v in vals], bool)
                if col.type == ColumnType.STRING:
                    arr = np.asarray([v if v is not None else ""
                                      for v in vals], object)
                elif col.type == ColumnType.FLOAT64:
                    arr = np.asarray([v if v is not None else 0.0
                                      for v in vals], np.float64)
                else:
                    arr = np.asarray([int(v) if v is not None else 0
                                      for v in vals], np.int64)
                payload[bid] = (arr, nulls)
            probe_col = (probe_ref[1].id if probe_ref[0] == "p"
                         else payload_ids[probe_ref[1]][
                             probe_ref[2].name])
            wires.append(JoinWire(probe_col=probe_col, keys=keys_arr,
                                  payload=payload))
        join_arg = wires[0] if len(wires) == 1 else tuple(wires)
        group = DictGroupSpec(
            cols=tuple(gcols),
            max_slots=int(flags.get("grouped_max_slots"))) \
            if gcols else None
        resp = await self.client.scan(
            real_of.get(lbl0, lbl0),
            ReadRequest("", where=pwhere, aggregates=tuple(aggs),
                        group_by=group, read_ht=read_ht, join=join_arg))
        # --- format: mirror of the grouped-pushdown row builder -------
        if group is None:
            rows = [self._agg_row(stmt, list(resp.agg_values or ()))]
            if any(it[0] == "window" for it in stmt.items):
                self._apply_windows(stmt, rows)
            return SqlResult(rows)
        counts = np.asarray(resp.group_counts) \
            if resp.group_counts is not None else np.zeros(0, np.int64)
        gmap = self._group_out_map(stmt)
        rows = []
        for g in np.nonzero(counts)[0]:
            row = {}
            for j, name in enumerate(stmt.group_by):
                v = np.asarray(resp.group_values[j])[g]
                v = v.item() if isinstance(v, np.generic) else v
                self._put_group_value(gmap, row, name, str(v))
            gvals = [np.asarray(v)[g] for v in resp.agg_values]
            row.update(self._agg_row(stmt, gvals))
            rows.append(row)
        if any(it[0] == "window" for it in stmt.items):
            self._apply_windows(stmt, rows)
        return SqlResult(self._order_limit(stmt, rows))

    # --- window functions (client-side; reference: PG WindowAgg) --------
    def _apply_windows(self, stmt: SelectStmt, rows: List[dict]) -> None:
        """Compute window items and attach each value to its row under
        the item's output name. Supports ROW_NUMBER/RANK/DENSE_RANK,
        LAG/LEAD, and SUM/COUNT/MIN/MAX/AVG OVER (PARTITION BY ...
        [ORDER BY ...]); ordered aggregates use PG's default frame
        (RANGE UNBOUNDED PRECEDING .. CURRENT ROW: peers share the
        cumulative value).

        Eligible shapes route through the vectorized segment-scan
        window kernels (ops/window_scan.py, window_pushdown_enabled):
        one np.lexsort replaces the per-partition Python sorts and the
        rank/lag/frame loops become cummax/cumsum scans.  The device
        hook only takes shapes it can answer BIT-identically to this
        Python path (arithmetic-free functions, exact-integer SUM
        lanes, NULL-free partition/order keys) — everything else stays
        here."""
        if flags.get("window_pushdown_enabled") and rows:
            if self._apply_windows_device(stmt, rows):
                return
        import functools
        for i, it in enumerate(stmt.items):
            if it[0] != "window":
                continue
            _, fn, expr, partition, worder, args = it
            name = self._item_name(stmt, i)
            parts: Dict[tuple, List[int]] = {}
            for idx, r in enumerate(rows):
                key = tuple(r.get(c) for c in partition)
                parts.setdefault(key, []).append(idx)

            def cmp_rows(a, b):
                for col, desc in worder:
                    x, y = rows[a].get(col), rows[b].get(col)
                    if x == y:
                        continue
                    if x is None:            # NULLS LAST asc
                        c = 1
                    elif y is None:
                        c = -1
                    else:
                        c = -1 if x < y else 1
                    return -c if desc else c
                return 0

            for idxs in parts.values():
                if worder:
                    idxs = sorted(idxs,
                                  key=functools.cmp_to_key(cmp_rows))
                vals = [(_eval_by_name(expr, rows[j])
                         if expr is not None else None) for j in idxs]
                if fn == "row_number":
                    for n_, j in enumerate(idxs, 1):
                        rows[j][name] = n_
                elif fn in ("rank", "dense_rank"):
                    rank = drank = 0
                    for n_, j in enumerate(idxs):
                        if n_ == 0 or cmp_rows(idxs[n_ - 1], j) != 0:
                            rank = n_ + 1
                            drank += 1
                        rows[j][name] = rank if fn == "rank" else drank
                elif fn in ("lag", "lead"):
                    off = int(args[0]) if args else 1
                    for n_, j in enumerate(idxs):
                        src = n_ - off if fn == "lag" else n_ + off
                        rows[j][name] = (vals[src]
                                         if 0 <= src < len(idxs)
                                         else None)
                elif fn in ("sum", "count", "min", "max", "avg"):
                    if not worder:
                        v = self._window_agg(fn, vals, expr, len(idxs))
                        for j in idxs:
                            rows[j][name] = v
                    else:
                        # cumulative, peers (order-key ties) share
                        k = 0
                        while k < len(idxs):
                            e = k
                            while e + 1 < len(idxs) and \
                                    cmp_rows(idxs[e + 1], idxs[k]) == 0:
                                e += 1
                            v = self._window_agg(
                                fn, vals[:e + 1], expr, e + 1)
                            for j in idxs[k:e + 1]:
                                rows[j][name] = v
                            k = e + 1
                else:
                    raise ValueError(f"unknown window function {fn}")

    def _window_wire(self, stmt: SelectStmt, schema):
        """Lower the statement's window items to a WindowWire the
        tablet can serve (ops/window_scan.serve_window_rows), or None
        when the shape can't ship: the wire carries column NAMES (the
        server's rows are name-keyed), so every reference must be a
        BARE name resolving in the scanned schema, every item must use
        a supported head with a plain-column argument, and ALL items
        must share ONE (partition, order) spec — a multi-spec statement
        would need several sorted passes, which the single-wire request
        shape doesn't model.  Value/key KIND checks stay server-side
        (typed WindowIneligible): the wire is semantically faithful
        regardless, and a refusal costs one flag on the response."""
        if not flags.get("window_server_pushdown_enabled"):
            return None
        from ..ops.window_scan import WindowWire

        def _bare(name):
            q, bare = self._split_qual(name)
            if q is not None:
                return None   # rows key by bare name only
            try:
                schema.column_by_name(bare)
            except Exception:  # noqa: BLE001 — not a table column
                return None
            return bare

        spec = None
        items = []
        for i, it in enumerate(stmt.items):
            if it[0] != "window":
                continue
            _, fn, expr, partition, worder, args = it
            key = (tuple(partition or ()), tuple(worder or ()))
            if spec is None:
                spec = key
            elif spec != key:
                return None
            out = self._item_name(stmt, i)
            if fn in ("row_number", "rank", "dense_rank"):
                if expr is not None:
                    return None
                items.append((fn, 0, None, out))
                continue
            if fn == "count" and expr is None:
                items.append(("count_star", 0, None, out))
                continue
            if not (isinstance(expr, tuple) and len(expr) == 2
                    and expr[0] == "col"):
                return None
            vcol = _bare(expr[1])
            if vcol is None:
                return None
            if fn in ("lag", "lead"):
                off = int(args[0]) if args else 1
                if off < 0:
                    return None
                items.append((fn, off, vcol, out))
            elif fn in ("sum", "count", "min", "max"):
                items.append((fn, 0, vcol, out))
            else:
                return None   # avg needs two lanes + a divide: client
        if not items:
            return None
        partition, worder = spec
        pnames, onames = [], []
        for nm in partition:
            b = _bare(nm)
            if b is None:
                return None
            pnames.append(b)
        for nm, desc in worder:
            b = _bare(nm)
            if b is None:
                return None
            onames.append((b, bool(desc)))
        return WindowWire(partition_by=tuple(pnames),
                          order_by=tuple(onames),
                          items=tuple(items))

    def _apply_windows_device(self, stmt: SelectStmt,
                              rows: List[dict]) -> bool:
        """Kernel route for window items (ops/window_scan.py): ONE
        np.lexsort per (partition, order) spec, then every function is
        a vectorized segment scan.  Takes the statement only when EVERY
        item is eligible for a bit-identical answer (never splits a
        statement across paths): supported function, NULL/NaN-free
        partition+order keys of one orderable type, exact-integer value
        lanes for arithmetic frames.  Returns False untaken."""
        from ..ops.window_scan import default_window_kernel
        witems = [(i, it) for i, it in enumerate(stmt.items)
                  if it[0] == "window"]
        n = len(rows)

        def codes_of(vals):
            kinds = {type(v) for v in vals}
            if kinds <= {int, bool}:
                arr = np.asarray([int(v) for v in vals], np.int64)
            elif kinds <= {int, bool, float}:
                arr = np.asarray([float(v) for v in vals], np.float64)
                if np.isnan(arr).any():
                    return None
            elif kinds == {str}:
                arr = np.asarray(vals)
            else:
                return None
            uniq, codes = np.unique(arr, return_inverse=True)
            return codes.astype(np.int64), len(uniq)

        by_spec: Dict[tuple, list] = {}
        for i, it in witems:
            _, fn, expr, partition, worder, args = it
            by_spec.setdefault(
                (tuple(partition or ()), tuple(worder or ())),
                []).append((i, fn, expr, args))
        plans = []
        for (partition, worder), items in by_spec.items():
            pkeys, okeys = [], []
            for cname in partition:
                vals = [r.get(cname) for r in rows]
                if any(v is None for v in vals):
                    return False
                got = codes_of(vals)
                if got is None:
                    return False
                pkeys.append(got[0])
            for cname, desc in worder:
                vals = [r.get(cname) for r in rows]
                if any(v is None for v in vals):
                    return False
                got = codes_of(vals)
                if got is None:
                    return False
                codes, nu = got
                okeys.append((nu - 1 - codes) if desc else codes)
            ops, values, nulls, metas = [], [], [], []
            for i, fn, expr, args in items:
                name = self._item_name(stmt, i)
                if fn in ("row_number", "rank", "dense_rank"):
                    ops.append((fn,))
                    values.append(None)
                    nulls.append(None)
                elif fn in ("lag", "lead"):
                    off = int(args[0]) if args else 1
                    if expr is None or off < 0:
                        return False
                    vals = [_eval_by_name(expr, r) for r in rows]
                    kinds = {type(v) for v in vals if v is not None}
                    if kinds <= {int}:
                        arr = np.asarray(
                            [0 if v is None else int(v) for v in vals],
                            np.int64)
                    elif kinds <= {int, float}:
                        arr = np.asarray(
                            [0.0 if v is None else float(v)
                             for v in vals], np.float64)
                    else:
                        return False
                    ops.append((fn, off))
                    values.append(arr)
                    nulls.append(np.asarray([v is None for v in vals],
                                            bool))
                elif fn in ("sum", "count", "min", "max"):
                    cum = 1 if worder else 0
                    if expr is None:
                        if fn != "count":
                            return False
                        ops.append(("count_star", cum))
                        values.append(None)
                        nulls.append(None)
                        metas.append((i, fn, name))
                        continue
                    vals = [_eval_by_name(expr, r) for r in rows]
                    kinds = {type(v) for v in vals if v is not None}
                    if fn == "count":
                        arr = np.zeros(n, np.int64)   # mask-only lane
                    elif kinds <= {int, bool}:
                        # exact int64 segment sums/extremes — the ONLY
                        # arithmetic lanes whose kernel answer is
                        # bit-identical to the Python fold
                        arr = np.asarray(
                            [0 if v is None else int(v) for v in vals],
                            np.int64)
                    else:
                        return False
                    ops.append((fn, cum))
                    values.append(arr)
                    nulls.append(np.asarray([v is None for v in vals],
                                            bool))
                else:
                    return False
                metas.append((i, fn, name))
            plans.append((pkeys, okeys, ops, values, nulls, metas))
        kern = default_window_kernel()
        for pkeys, okeys, ops, values, nulls, metas in plans:
            keys = pkeys + okeys
            perm = (np.lexsort(tuple(reversed(keys))) if keys
                    else np.arange(n))
            seg = np.zeros(n, bool)
            if n:
                seg[0] = True
            for kk in pkeys:
                ks = kk[perm]
                seg[1:] |= ks[1:] != ks[:-1]
            peer = np.zeros(n, bool)
            for kk in okeys:
                ks = kk[perm]
                peer[1:] |= ks[1:] != ks[:-1]
            svalues = [None if v is None else v[perm] for v in values]
            snulls = [None if m is None else m[perm] for m in nulls]
            outs = kern.run(ops, seg, peer, svalues, snulls)
            for (ov, om), (_i, _fn, name) in zip(outs, metas):
                is_f = ov.dtype.kind == "f"
                for k in range(n):
                    ri = int(perm[k])
                    rows[ri][name] = (
                        None if om[k] else
                        float(ov[k]) if is_f else int(ov[k]))
        return True

    @staticmethod
    def _window_agg(fn, vals, expr, nrows):
        return _agg_vals(fn, vals, nrows if expr is None else None)

    # --- in-memory SELECT over materialized rows (CTE source) -----------
    def _rows_select(self, stmt: SelectStmt, base_rows: List[dict]
                     ) -> SqlResult:
        """Full client-side execution of a SELECT whose FROM is a
        materialized rowset (a CTE). Same feature surface as the table
        path minus pushdowns."""
        rows = [dict(r) for r in base_rows]
        if stmt.where is not None:
            rows = [r for r in rows
                    if _eval_by_name(stmt.where, r) is True]
        agg_items = [it for it in stmt.items if it[0] == "agg"]
        if agg_items and not stmt.group_by:
            out = {}
            for i, it in enumerate(stmt.items):
                if it[0] == "agg":
                    out[self._item_name(stmt, i)] = \
                        _agg_over_rows(it[1], it[2], rows)
            return SqlResult([out])
        if stmt.group_by:
            gexprs = getattr(stmt, "group_exprs", None) or {}
            if gexprs:
                self._rewrite_group_expr_items(stmt)
                for r in rows:
                    for g, ast in gexprs.items():
                        r[g] = _eval_by_name(ast, r)
            groups: Dict[tuple, List[dict]] = {}
            for r in rows:
                key = tuple(r.get(c) for c in stmt.group_by)
                groups.setdefault(key, []).append(r)
            out_rows = []
            gmap = self._group_out_map(stmt)
            for key, grows in groups.items():
                row = {}
                for gname, gv in zip(stmt.group_by, key):
                    self._put_group_value(gmap, row, gname, gv)
                for i, it in enumerate(stmt.items):
                    if it[0] == "agg":
                        row[self._item_name(stmt, i)] = \
                            _agg_over_rows(it[1], it[2], grows)
                    elif it[0] == "expr":
                        row[self._item_name(stmt, i)] = _eval_by_name(
                            it[1], row)
                if stmt.having is not None:
                    hv = _eval_by_name(
                        _subst_aggrefs(stmt.having, grows), row)
                    if hv is not True:
                        continue
                out_rows.append(row)
            return SqlResult(self._order_limit(stmt, out_rows))
        if any(it[0] == "window" for it in stmt.items):
            self._apply_windows(stmt, rows)
        out = []
        for r in rows:
            if any(it[0] == "star" for it in stmt.items):
                out.append(dict(r))
                continue
            row = {}
            for i, it in enumerate(stmt.items):
                name = self._item_name(stmt, i)
                if it[0] == "col":
                    _, bare = self._split_qual(it[1])
                    row[name] = r.get(it[1], r.get(bare))
                elif it[0] == "window":
                    row[name] = r.get(name)
                elif it[0] == "expr":
                    row[name] = _eval_by_name(it[1], r)
            for col, _ in stmt.order_by:
                if col not in row and col in r:
                    row[col] = r[col]
            out.append(row)
        return SqlResult(self._order_limit(stmt, out))

    @staticmethod
    def _natural_order(ct, order_by) -> bool:
        """True when ORDER BY follows the table's range-shard pk order
        (each tablet already returns rows in encoded-key order, so a
        pushed-down LIMIT per tablet is complete: the global top-N is a
        subset of the per-tablet top-Ns)."""
        if not order_by or ct.info.partition_schema.kind != "range":
            return False
        pk = ct.info.schema.key_columns
        if len(order_by) > len(pk):
            return False
        for (name, desc), col in zip(order_by, pk):
            if name != col.name or desc != bool(col.sort_desc):
                return False
        return True

    def _needed_columns(self, stmt: SelectStmt, schema) -> List[str]:
        if any(it[0] == "star" for it in stmt.items):
            return [c.name for c in schema.columns]
        names = set()
        for it in stmt.items:
            if it[0] == "col":
                names.add(it[1])
            elif it[0] == "expr":
                self._collect_names(it[1], names)
            elif it[0] == "window":
                if it[2] is not None:
                    self._collect_names(it[2], names)
                names.update(it[3])
                names.update(c for c, _ in it[4])
        item_names = {self._item_name(stmt, i)
                      for i in range(len(stmt.items))}
        for col, _ in stmt.order_by:
            # output names (aliases, function names) exist only
            # post-projection — never ask the scan for them
            if col not in item_names:
                names.add(col)
        return sorted(names)

    def _collect_names(self, node, out: set):
        if node[0] == "col":
            out.add(node[1])
            return
        if node[0] == "corr":
            # a correlated marker needs its OUTER parameter columns;
            # the inner SelectStmt's names are another table's
            out.update(node[3])
            if len(node) > 4 and isinstance(node[4], tuple):
                self._collect_names(node[4], out)
            return
        for c in node[1:]:
            if isinstance(c, tuple):
                self._collect_names(c, out)

    def _project_row(self, stmt: SelectStmt, row: dict, schema) -> dict:
        if any(it[0] == "star" for it in stmt.items):
            return row
        out = {}
        for i, it in enumerate(stmt.items):
            if it[0] == "col":
                out[self._item_name(stmt, i)] = row.get(it[1])
            elif it[0] == "window":
                # computed by _apply_windows, attached under the name
                name = self._item_name(stmt, i)
                out[name] = row.get(name)
            elif it[0] == "expr":
                bound = self._bind(it[1], schema)
                # synthetic keys (__corrN carriers etc.) are not schema
                # columns — only real columns feed the evaluator
                known = {c.name: c.id for c in schema.columns}
                idrow = {known[k]: v for k, v in row.items()
                         if k in known}
                out[self._item_name(stmt, i)] = eval_expr_py(bound, idrow)
        # carry ORDER BY source columns through so post-projection sort
        # works even when they're aliased or not projected; _order_limit
        # strips them again
        for col, _ in stmt.order_by:
            if col not in out and col in row:
                out[col] = row[col]
        return out

    def _order_limit(self, stmt: SelectStmt, rows: List[dict]) -> List[dict]:
        if getattr(stmt, "distinct", False):
            star = any(it[0] == "star" for it in stmt.items)
            projected = None if star else {
                self._item_name(stmt, i) for i in range(len(stmt.items))}
            if projected is not None:
                # PG rule: for SELECT DISTINCT, ORDER BY expressions
                # must appear in the select list — otherwise the sort
                # key of a deduplicated row is ill-defined.  An ORDER
                # BY naming the SOURCE column of an aliased item
                # (SELECT a AS x ... ORDER BY a) matches the select
                # list in PG, so source columns count as projected.
                sources = set()
                for it in stmt.items:
                    if it[0] == "col":
                        sources.add(it[1])
                        sources.add(self._split_qual(it[1])[1])
                for col, _d in stmt.order_by:
                    _, bare = self._split_qual(col)
                    if col not in projected and bare not in projected \
                            and col not in sources \
                            and bare not in sources:
                        raise ValueError(
                            "for SELECT DISTINCT, ORDER BY expressions "
                            "must appear in the select list")
            seen = set()
            out = []
            for r in rows:
                # dedup over the PROJECTED columns only: carried
                # sort-only keys must not make equal rows distinct
                key = tuple(sorted(
                    (k, repr(v)) for k, v in r.items()
                    if projected is None or k in projected))
                if key not in seen:
                    seen.add(key)
                    out.append(r)
            rows = out
        for col, desc in reversed(stmt.order_by):
            # a qualified ORDER BY column (t.col) sorts projected rows
            # whose output key is the bare name — fall back to it
            _, bare = self._split_qual(col)

            def _key(r, c=col, b=bare):
                v = r[c] if c in r else r.get(b)
                return (v is None, v)
            rows.sort(key=_key, reverse=desc)
        off = getattr(stmt, "offset", 0)
        if off:
            rows = rows[off:]
        if stmt.limit is not None:
            rows = rows[:stmt.limit]
        # strip sort-only / group-key carried columns from the output
        if not any(it[0] == "star" for it in stmt.items):
            projected = {self._item_name(stmt, i)
                         for i in range(len(stmt.items))}
            rows = [{k: v for k, v in r.items() if k in projected}
                    for r in rows]
        return rows

    def _agg_row(self, stmt: SelectStmt, values) -> dict:
        """Map expanded (avg->sum,count) agg outputs back to named items."""
        out = {}
        vi = 0
        for i, it in enumerate(stmt.items):
            if it[0] != "agg":
                continue
            op = it[1]
            name = self._item_name(stmt, i)
            if op == "avg":
                s = _scalar(values[vi])
                c = _scalar(values[vi + 1])
                import decimal
                if isinstance(s, decimal.Decimal):
                    c = int(c) if c is not None else c
                out[name] = (s / c) if s is not None and c else None
                vi += 2
            else:
                import decimal
                v = _scalar(values[vi])
                # _scalar owns the numeric typing (integer columns stay
                # integral, float inputs stay float); count just forces
                # int for the odd object-dtype escape
                out[name] = (v if v is None
                             or isinstance(v, (decimal.Decimal, list,
                                               str))
                             else
                             int(v) if op in ("count", "count_distinct")
                             else v)
                vi += 1
        return out

    @staticmethod
    def _having_refs(stmt: SelectStmt) -> list:
        """Ordered unique (op, expr) aggregate references in HAVING.
        Each is computed as a HIDDEN extra aggregate ("__h<i>") — never
        resolved by name against the projection, so un-projected or
        name-colliding aggregates still filter correctly."""
        having = getattr(stmt, "having", None)
        refs: list = []
        if having is None:
            return refs

        def walk(n):
            if not isinstance(n, tuple):
                return
            if n[0] == "aggref":
                if (n[1], n[2]) not in refs:
                    refs.append((n[1], n[2]))
                return
            for c in n[1:]:
                walk(c)

        walk(having)
        return refs

    @staticmethod
    def _hidden_agg_row(refs: list, values, vi: int) -> dict:
        """Decode the hidden aggregates' expanded output slots starting
        at `vi` (avg occupies two: sum, count)."""
        out = {}
        for i, (op, _e) in enumerate(refs):
            if op == "avg":
                sv = _scalar(values[vi])
                cv = _scalar(values[vi + 1])
                import decimal
                if isinstance(sv, decimal.Decimal):
                    cv = int(cv) if cv is not None else cv
                out[f"__h{i}"] = (sv / cv) if sv is not None and cv \
                    else None
                vi += 2
            else:
                v = _scalar(values[vi])
                out[f"__h{i}"] = (v if v is None else
                                  int(v) if op == "count" else v)
                vi += 1
        return out

    @staticmethod
    def _projected_slots(stmt: SelectStmt) -> int:
        return sum(2 if it[1] == "avg" else 1
                   for it in stmt.items if it[0] == "agg")

    @staticmethod
    def _having_filter(stmt: SelectStmt, rows: list, refs: list) -> list:
        having = getattr(stmt, "having", None)
        if having is None:
            return rows

        def subst(n):
            if not isinstance(n, tuple):
                return n
            if n[0] == "aggref":
                return ("col", f"__h{refs.index((n[1], n[2]))}")
            return tuple(subst(c) if isinstance(c, tuple) else c
                         for c in n)

        expr = subst(having)
        kept = [r for r in rows if _eval_by_name(expr, r) is True]
        for r in kept:                      # hidden keys never surface
            for i in range(len(refs)):
                r.pop(f"__h{i}", None)
        return kept

    def _matview_def(self, stmt: CreateMatViewStmt):
        """Structured ViewDef from a parsed CREATE MATERIALIZED VIEW —
        the ql/matview seam: matview/ never imports the parser, so the
        statement flattens HERE into name-based ASTs + output names,
        and deeper (type-level) eligibility is decided by
        matview.definition.validate against the live schema."""
        from ..matview.definition import ViewDef
        from ..matview.errors import (REASON_SELECT_SHAPE,
                                      MatviewIneligible)
        sel = stmt.select
        for attr, what in (("joins", "JOIN"), ("order_by", "ORDER BY"),
                           ("group_exprs", "GROUP BY expression"),
                           ("distinct", "DISTINCT")):
            if getattr(sel, attr, None):
                raise MatviewIneligible(REASON_SELECT_SHAPE, what)
        if getattr(sel, "having", None) is not None \
                or getattr(sel, "limit", None) is not None \
                or getattr(sel, "offset", None):
            raise MatviewIneligible(REASON_SELECT_SHAPE,
                                    "HAVING/LIMIT/OFFSET")
        aggs = []
        for i, it in enumerate(sel.items):
            if it[0] == "col":
                bare = self._split_qual(it[1])[1]
                if bare not in sel.group_by:
                    raise MatviewIneligible(
                        REASON_SELECT_SHAPE,
                        f"non-grouped column {it[1]}")
            elif it[0] == "agg":
                aggs.append((it[1], it[2], self._item_name(sel, i)))
            else:
                raise MatviewIneligible(
                    REASON_SELECT_SHAPE,
                    "only group columns and aggregates project")
        return ViewDef(
            name=stmt.name, table=sel.table,
            select_sql=stmt.select_sql,
            group_by=list(sel.group_by), aggs=aggs, where=sel.where,
            group_out=self._group_out_map(sel))

    def _group_spec(self, stmt: SelectStmt, schema):
        """Pushdown group spec: dictionary ids when ANALYZE stats bound
        the domains (cheapest — one-hot matmul on the MXU), otherwise a
        HashGroupSpec so arbitrary-domain numeric group keys STILL push
        down (sort + segment aggregation on device; no stats
        prerequisite — reference: unconditional aggregate pushdown,
        pgsql_operation.cc:3153). All-string keys push down as a
        DictGroupSpec — the dict-key grouped kernel aggregates over
        scan-global dictionary codes with a server-side interpreted
        fallback on slot overflow (ops/grouped_scan.py). Other
        non-numeric keys return None (client-side grouping)."""
        st = self.stats.get(stmt.table, {})
        cols = []
        for name in stmt.group_by:
            if name not in st:
                cols = None
                break
            domain, offset = st[name]
            cols.append((schema.column_by_name(name).id, domain, offset))
        if cols is not None:
            return GroupSpec(cols=tuple(cols))
        try:
            gcols = [schema.column_by_name(n) for n in stmt.group_by]
        except Exception:
            return None
        if all(c.type == ColumnType.STRING for c in gcols) \
                and flags.get("grouped_pushdown_enabled"):
            # Q1's shape: GROUP BY over low-cardinality string columns.
            # The server aggregates dictionary CODES on device; an
            # over-cardinality group set spills and reverts to the
            # server's interpreted GROUP BY — either way the response
            # is compacted (group_values, counts) keyed rows
            return DictGroupSpec(
                cols=tuple(c.id for c in gcols),
                max_slots=int(flags.get("grouped_max_slots")))
        hash_cols = []
        for c in gcols:
            # exact-on-device types only: floats would be rounded to
            # f32 at batch formation, silently merging distinct f64
            # group keys — those stay on exact client-side grouping
            if c.type not in (ColumnType.INT32, ColumnType.INT64,
                              ColumnType.TIMESTAMP, ColumnType.BOOL):
                return None
            hash_cols.append(c.id)
        return HashGroupSpec(cols=tuple(hash_cols))

    def _group_out_map(self, stmt) -> Dict[str, list]:
        """group-by name -> ALL projected output names for it (aliases
        included) — computed once per statement, consumed per group
        row."""
        out: Dict[str, list] = {}
        for gname in stmt.group_by:
            bare = self._split_qual(gname)[1]
            out[gname] = [
                self._item_name(stmt, i)
                for i, it in enumerate(stmt.items)
                if it[0] == "col"
                and self._split_qual(it[1])[1] == bare]
        return out

    @staticmethod
    def _put_group_value(gmap: Dict[str, list], row: dict, gname: str,
                         v) -> None:
        """Store a group-key value under its raw column name (for ORDER
        BY/HAVING references) and EVERY projected output name — `SELECT
        a.owner AS who ... GROUP BY a.owner` must emit a 'who' column,
        and _order_limit strips the non-projected raw duplicate."""
        row[gname] = v
        for name in gmap.get(gname, ()):
            row[name] = v

    async def _grouped_pushdown(self, stmt, ct, where, gspec) -> SqlResult:
        schema = ct.info.schema
        read_ht = self._txn.start_ht if self._txn is not None else None
        agg_items = [it for it in stmt.items if it[0] == "agg"]
        refs = self._having_refs(stmt)
        aggs = tuple(AggSpec(op, self._bind(e, schema))
                     for _, op, e in agg_items) + \
            tuple(AggSpec(op, self._bind(e, schema)) for op, e in refs)
        resp = await self._scan("grouped_pushdown", stmt.table, ReadRequest(
            "", where=where, aggregates=aggs, group_by=gspec,
            read_ht=read_ht))
        counts = np.asarray(resp.group_counts)
        rows = []
        gmap = self._group_out_map(stmt)
        if isinstance(gspec, (HashGroupSpec, DictGroupSpec)):
            # compacted (group_values, counts) keyed rows — hash groups
            # and dict (string-key) groups share the shape; dict group
            # values arrive as strings and project unconverted
            schema_cols = {c.id: c for c in schema.columns}
            for g in np.nonzero(counts)[0]:
                row = {}
                for j, (cid, name) in enumerate(zip(gspec.cols,
                                                    stmt.group_by)):
                    v = np.asarray(resp.group_values[j])[g].item()
                    c = schema_cols[cid]
                    if c.type in (ColumnType.INT32, ColumnType.INT64,
                                  ColumnType.TIMESTAMP):
                        v = int(v)
                    elif c.type == ColumnType.BOOL:
                        v = bool(v)
                    elif c.type == ColumnType.STRING:
                        v = str(v)
                    self._put_group_value(gmap, row, name, v)
                gvals = [np.asarray(v)[g] for v in resp.agg_values]
                row.update(self._agg_row(stmt, gvals))
                row.update(self._hidden_agg_row(
                    refs, gvals, self._projected_slots(stmt)))
                rows.append(row)
            rows = self._having_filter(stmt, rows, refs)
            return SqlResult(self._order_limit(stmt, rows))
        for gid in range(gspec.num_groups):
            if counts[gid] == 0:
                continue
            row = {}
            rem = gid
            for (cid, domain, offset), name in zip(gspec.cols,
                                                   stmt.group_by):
                self._put_group_value(gmap, row, name,
                                      rem % domain + offset)
                rem //= domain
            gvals = [np.asarray(v)[gid] for v in resp.agg_values]
            row.update(self._agg_row(stmt, gvals))
            row.update(self._hidden_agg_row(
                refs, gvals, self._projected_slots(stmt)))
            rows.append(row)
        rows = self._having_filter(stmt, rows, refs)
        return SqlResult(self._order_limit(stmt, rows))

    def _rewrite_group_expr_items(self, stmt) -> None:
        """A select item whose expr EQUALS a GROUP BY expression
        projects the synthetic grouping column under the item's PG
        output name (SELECT upper(g) ... GROUP BY upper(g)); the SAME
        substitution applies inside HAVING, which evaluates over group
        rows where the base columns are gone."""
        gexprs = getattr(stmt, "group_exprs", None) or {}
        if not gexprs:
            return

        def subst(n):
            if not isinstance(n, tuple):
                return n
            for gname, ast in gexprs.items():
                if n == ast:
                    return ("col", gname)
            return tuple(subst(c) if isinstance(c, tuple) else c
                         for c in n)

        for i, it in enumerate(stmt.items):
            if it[0] != "expr":
                continue
            matched = next((g for g, ast in gexprs.items()
                            if it[1] == ast), None)
            if matched is not None:
                stmt.aliases[i] = stmt.aliases.get(
                    i, self._item_name(stmt, i))
                stmt.items[i] = ("col", matched)
            else:
                # expressions BUILT ON the group key (upper(g) || '!')
                # substitute the key and evaluate over the group row
                stmt.items[i] = ("expr", subst(it[1]))
        if getattr(stmt, "having", None) is not None:
            stmt.having = subst(stmt.having)

    async def _grouped_clientside(self, stmt, ct, where) -> SqlResult:
        """Hash grouping over projected rows (arbitrary-domain GROUP BY;
        GROUP BY expressions compute synthetic columns per row)."""
        schema = ct.info.schema
        read_ht = self._txn.start_ht if self._txn is not None else None
        agg_indexed = [(i, it) for i, it in enumerate(stmt.items)
                       if it[0] == "agg"]
        agg_items = [it for _, it in agg_indexed]
        refs = self._having_refs(stmt)
        gexprs = getattr(stmt, "group_exprs", None) or {}
        needed = {g for g in stmt.group_by if g not in gexprs}
        for ast in gexprs.values():
            self._collect_names(ast, needed)
        for _, op, e in agg_items:
            if e is not None:
                self._collect_names(e, needed)
        for _op, e in refs:
            if e is not None:
                self._collect_names(e, needed)
        cols = sorted(needed)
        overlay = (self._txn is not None
                   and self._txn.pending_writes(stmt.table))
        if overlay:
            cols = self._overlay_columns(cols, schema, where)
        resp = await self._scan("grouped_clientside", stmt.table, ReadRequest(
            "", columns=tuple(cols), where=where,
            read_ht=read_ht))
        scan_rows = resp.rows
        if overlay:
            scan_rows = self._overlay_txn_writes(stmt.table, schema,
                                                 where, scan_rows)
        groups: Dict[tuple, list] = {}
        bound = [(op, self._bind(e, schema) if e else None)
                 for _, op, e in agg_items] + \
            [(op, self._bind(e, schema) if e else None)
             for op, e in refs]
        bound_gexprs = {g: self._bind(ast, schema)
                        for g, ast in gexprs.items()}
        known = {c.name: c.id for c in schema.columns}
        for r in scan_rows:
            idrow = {known[k]: v for k, v in r.items() if k in known}
            for g, be in bound_gexprs.items():
                r[g] = eval_expr_py(be, idrow)
            key = tuple(r.get(c) for c in stmt.group_by)
            st = groups.setdefault(key, [_init(op) for op, _ in bound])
            for i, (op, e) in enumerate(bound):
                st[i] = _step(op, e, st[i], idrow)
        rows = []
        gmap = self._group_out_map(stmt)
        for key, st in groups.items():
            row = {}
            for gname, gv in zip(stmt.group_by, key):
                self._put_group_value(gmap, row, gname, gv)
            for j, (idx, it) in enumerate(agg_indexed):
                row[self._item_name(stmt, idx)] = _final(bound[j][0],
                                                         st[j])
            for i2, it2 in enumerate(stmt.items):
                if it2[0] == "expr":
                    # expression over the group key(s): evaluate over
                    # the assembled group row (the key substitution
                    # happened in _rewrite_group_expr_items)
                    row[self._item_name(stmt, i2)] = _eval_by_name(
                        it2[1], row)
            for j in range(len(refs)):
                i = len(agg_items) + j
                row[f"__h{j}"] = _final(bound[i][0], st[i])
            rows.append(row)
        rows = self._having_filter(stmt, rows, refs)
        return SqlResult(self._order_limit(stmt, rows))

    async def _knn_select(self, stmt: SelectStmt) -> SqlResult:
        """pgvector-style: SELECT ... ORDER BY vcol <-> '[..]' LIMIT k
        (reference: PgsqlReadOperation::ExecuteVectorLSMSearch,
        docdb/pgsql_operation.cc:2728)."""
        col, lit = stmt.knn
        k = stmt.limit or 10
        q = parse_vector(lit)
        hits = await self.client.vector_search(stmt.table, col, q, k=k)
        rows = []
        for pk, dist in hits:
            row = await self.client.get(stmt.table, pk)
            if row is None:
                continue
            out = self._project_row(stmt, row,
                                    (await self.client._table(stmt.table)
                                     ).info.schema)
            out["distance"] = dist
            rows.append(out)
        return SqlResult(rows)

    @staticmethod
    def _returning_rows(returning, rows, schema) -> List[dict]:
        """RETURNING projection over the written/deleted row images
        (* follows schema column order, like PG)."""
        if returning == ["*"]:
            returning = [c.name for c in schema.columns]
        return [{c: r.get(c) for c in returning} for r in rows]

    # ------------------------------------------------------------------
    async def _update_from(self, stmt: UpdateStmt) -> SqlResult:
        """UPDATE t SET ... FROM u WHERE ... — SET and WHERE reference
        both tables; evaluation is name-based over the merged row."""
        ct = await self.client._table(stmt.table)
        schema = ct.info.schema
        for name in stmt.sets:
            schema.column_by_name(name)
        pairs = await self._dml_join_rows(
            stmt.table, stmt.from_table, stmt.from_alias, stmt.where)
        if not pairs:
            return SqlResult([], "UPDATE 0")
        dec_cols = _decimal_cols(schema)
        nn_cols = [c.name for c in schema.columns
                   if not c.nullable and c.name in stmt.sets]
        json_cols = {c.name for c in schema.columns
                     if c.type == ColumnType.JSON}
        updated = []
        for tr, merged in pairs:
            nr = dict(tr)
            for name, e in stmt.sets.items():
                if e == ("default",):
                    col = schema.column_by_name(name)
                    if getattr(col, "default_seq", None):
                        raise ValueError(
                            "SET ... = DEFAULT on a serial column is "
                            "not supported (per-row nextval)")
                    nr[name] = getattr(col, "default_value", None)
                else:
                    v = _eval_by_name(e, merged)
                    if name in json_cols and isinstance(v, (list,
                                                            dict)):
                        import json as _json
                        v = _json.dumps(v)
                    nr[name] = v
            self._coerce_decimals(dec_cols, nr)
            for name in nn_cols:
                if nr.get(name) is None:
                    raise ValueError(
                        f"null value in column {name!r} violates "
                        f"not-null constraint")
            updated.append(nr)
        self._check_check_constraints(ct, updated)
        if any(fk["column"] in stmt.sets
               for fk in getattr(ct, "foreign_keys", None) or []):
            await self._check_foreign_keys(ct, updated)
        n = await self._write_update_rows(
            ct, schema, [tr for tr, _ in pairs], updated)
        if getattr(stmt, "returning", None):
            return SqlResult(
                self._returning_rows(stmt.returning, updated, schema),
                f"UPDATE {n}")
        return SqlResult([], f"UPDATE {n}")

    async def _delete_using(self, stmt: DeleteStmt) -> SqlResult:
        """DELETE FROM t USING u WHERE ... (PG delete with a using
        list)."""
        ct = await self.client._table(stmt.table)
        schema = ct.info.schema
        pk_cols = [c.name for c in schema.key_columns]
        pairs = await self._dml_join_rows(
            stmt.table, stmt.using_table, stmt.using_alias, stmt.where)
        if not pairs:
            return SqlResult([], "DELETE 0")
        pre_images = [tr for tr, _ in pairs]
        # plans + restrict-checks the whole referential-action tree
        # (root included) before any write lands, then executes the
        # cascade and the parent delete as one statement
        n = await self._delete_with_fk_actions(ct, pk_cols, pre_images)
        if getattr(stmt, "returning", None):
            return SqlResult(
                self._returning_rows(stmt.returning, pre_images,
                                     schema), f"DELETE {n}")
        return SqlResult([], f"DELETE {n}")

    async def _delete(self, stmt: DeleteStmt) -> SqlResult:
        self._invalidate_stats(stmt.table)
        if getattr(stmt, "using_table", None):
            return await self._delete_using(stmt)
        corr = []
        if stmt.where is not None:
            stmt.where, corr = await self._split_corr_where(
                stmt.table, None, stmt.where)
        ct = await self.client._table(stmt.table)
        schema = ct.info.schema
        pk_cols = [c.name for c in schema.key_columns]
        read_ht = self._txn.start_ht if self._txn is not None else None
        where = self._bind(stmt.where, schema)
        returning = getattr(stmt, "returning", None)
        scan_cols = tuple(pk_cols)
        if returning:
            scan_cols = ()        # full pre-image for the projection
        elif self._txn is not None and \
                self._txn.pending_writes(stmt.table):
            # the overlay re-evaluates WHERE on merged rows: project
            # the WHERE columns too or committed values read as NULL
            scan_cols = tuple(self._overlay_columns(pk_cols, schema,
                                                    where))
        if corr:
            scan_cols = ()     # correlated conjuncts read any column
        resp = await self.client.scan(stmt.table, ReadRequest(
            "", columns=scan_cols, where=where, read_ht=read_ht))
        rows = resp.rows
        if self._txn is not None:
            rows = self._overlay_txn_writes(stmt.table, schema, where,
                                            rows)
        rows = await self._filter_corr_rows(rows, corr, schema)
        pre_images = rows
        # targets include the txn's OWN uncommitted rows (and exclude
        # ones it already deleted)
        rows = [{k: r.get(k) for k in pk_cols} for r in rows]
        if not rows:
            return SqlResult([], "DELETE 0")
        # plans + restrict-checks the whole referential-action tree
        # (root included) before any write lands, then executes the
        # cascade and the parent delete as one statement
        n = await self._delete_with_fk_actions(ct, pk_cols, rows)
        if returning:
            return SqlResult(
                self._returning_rows(returning, pre_images, schema),
                f"DELETE {n}")
        return SqlResult([], f"DELETE {n}")

    @staticmethod
    def _coerce_decimals(dec_cols, row: dict) -> None:
        """DECIMAL stores as text: numeric values (literals, Decimal
        results of INSERT..SELECT arithmetic, UPDATE SET values)
        coerce to their canonical string form before packing."""
        for dc in dec_cols & set(row):
            if row[dc] is not None and not isinstance(row[dc], str):
                row[dc] = str(row[dc])

    @staticmethod
    def _split_conjuncts(resolved):
        """AND-conjunct split: (pushable_where, correlated_conjuncts)."""
        conjs: list = []

        def flatten(n):
            if isinstance(n, tuple) and n[0] == "and":
                flatten(n[1])
                flatten(n[2])
            else:
                conjs.append(n)
        flatten(resolved)
        push = [c for c in conjs if not SqlSession._has_corr(c)]
        corr = [c for c in conjs if SqlSession._has_corr(c)]
        w = None
        for c in push:
            w = c if w is None else ("and", w, c)
        return w, corr

    async def _split_corr_where(self, stmt_table, table_alias, where):
        """(pushable_where, corr_conjuncts) for a DML statement's WHERE
        with possible correlated subqueries — the DML scans all rows
        matching the pushable part and filters the correlated remainder
        client-side (same shape as _select)."""
        try:
            outer_schema = (await self.client._table(
                stmt_table)).info.schema
            outer = (outer_schema, {stmt_table,
                                    table_alias or stmt_table})
        except Exception:   # noqa: BLE001
            outer = None
        resolved = await self._resolve_subqueries(where, outer=outer)
        if not self._has_corr(resolved):
            return resolved, []
        return self._split_conjuncts(resolved)

    async def _filter_corr_rows(self, rows, corr, schema):
        if not corr:
            return rows
        cache: dict = {}
        kept = []
        for r in rows:
            ok = True
            for conj in corr:
                if not await self._eval_corr_conjunct(conj, r, schema,
                                                      cache):
                    ok = False
                    break
            if ok:
                kept.append(r)
        return kept

    async def _dml_join_rows(self, target: str, aux_table: str,
                             aux_alias, where):
        """Matched (target_row, merged_row) pairs for UPDATE..FROM /
        DELETE..USING (reference: PG's join DML plans — ours pushes
        target-only conjuncts into the target scan and runs a
        client-side nested loop over the materialized aux table; the
        FIRST matching aux row wins, matching PG's 'one arbitrary
        match' contract).  `merged_row` carries the target's columns
        (bare + qualified) overlaid with the aux table's (qualified,
        bare only where not clashing) for name-based SET/WHERE
        evaluation.  Scans read at the transaction snapshot with the
        write-set overlaid on BOTH tables (read-your-own-writes)."""
        where = await self._resolve_subqueries(where) \
            if where is not None else None
        if where is not None and self._has_corr(where):
            raise ValueError(
                "correlated subqueries are not supported in join DML "
                "(UPDATE ... FROM / DELETE ... USING)")
        t_ct = await self.client._table(target)
        a_ct = await self.client._table(aux_table)
        read_ht = self._txn.start_ht if self._txn is not None else None
        # push target-only conjuncts into the target scan (a conjunct
        # qualifies when every referenced name resolves in the target
        # and is unqualified-or-target-qualified and NOT an aux column
        # ambiguity)
        t_label = target
        a_label = aux_alias or aux_table
        t_cols = {c.name for c in t_ct.info.schema.columns}
        a_cols = {c.name for c in a_ct.info.schema.columns}
        push_w = None
        client_w = where
        if where is not None:
            conjs: list = []

            def flatten(n):
                if isinstance(n, tuple) and n[0] == "and":
                    flatten(n[1])
                    flatten(n[2])
                else:
                    conjs.append(n)
            flatten(where)

            def target_only(conj):
                names: set = set()
                self._collect_names(conj, names)
                for n in names:
                    q, bare = self._split_qual(n)
                    if q is not None and q != t_label:
                        return False
                    if q is None and (bare not in t_cols
                                      or bare in a_cols):
                        return False
                    if bare not in t_cols:
                        return False
                return True
            pushed = [c for c in conjs if target_only(c)]
            rest = [c for c in conjs if not target_only(c)]
            for c in pushed:
                push_w = c if push_w is None else ("and", push_w, c)
            client_w = None
            for c in rest:
                client_w = c if client_w is None \
                    else ("and", client_w, c)
        bound_push = None
        if push_w is not None:
            quals = {t_label}
            bound_push = self._bind(
                self._strip_quals(push_w, quals), t_ct.info.schema)
        t_rows = (await self.client.scan(
            target, ReadRequest("", where=bound_push,
                                read_ht=read_ht))).rows
        if self._txn is not None:
            t_rows = self._overlay_txn_writes(
                target, t_ct.info.schema, bound_push, t_rows)
        a_rows = (await self.client.scan(
            aux_table, ReadRequest("", read_ht=read_ht))).rows
        if self._txn is not None:
            a_rows = self._overlay_txn_writes(
                aux_table, a_ct.info.schema, None, a_rows)
        out = []
        for tr in t_rows:
            merged_base = {f"{t_label}.{k}": v for k, v in tr.items()}
            merged_base.update(tr)
            for ar in a_rows:
                m = dict(merged_base)
                m.update({f"{a_label}.{k}": v for k, v in ar.items()})
                for k, v in ar.items():
                    if k not in tr:
                        m[k] = v
                if client_w is None or \
                        _eval_by_name(client_w, m) is True:
                    out.append((tr, m))
                    break
        return out

    @staticmethod
    def _strip_quals(node, quals: set):
        """Remove table/alias qualifiers owned by `quals` from column
        refs so schema binding sees bare names."""
        if not isinstance(node, tuple):
            return node
        if node[0] == "col" and isinstance(node[1], str) \
                and "." in node[1]:
            q, bare = node[1].split(".", 1)
            if q in quals:
                return ("col", bare)
            return node
        return tuple(SqlSession._strip_quals(c, quals)
                     if isinstance(c, tuple) else c for c in node)

    async def _update(self, stmt: UpdateStmt) -> SqlResult:
        self._invalidate_stats(stmt.table)
        if getattr(stmt, "from_table", None):
            return await self._update_from(stmt)
        corr = []
        if stmt.where is not None:
            stmt.where, corr = await self._split_corr_where(
                stmt.table, None, stmt.where)
        ct = await self.client._table(stmt.table)
        schema = ct.info.schema
        for name in stmt.sets:
            schema.column_by_name(name)   # raises KeyError when stale
        read_ht = self._txn.start_ht if self._txn is not None else None
        where = self._bind(stmt.where, schema)
        resp = await self.client.scan(stmt.table, ReadRequest(
            "", where=where, read_ht=read_ht))
        rows = resp.rows
        if self._txn is not None:
            rows = self._overlay_txn_writes(stmt.table, schema, where,
                                            rows)
        rows = await self._filter_corr_rows(rows, corr, schema)
        if not rows:
            return SqlResult([], "UPDATE 0")
        # SET targets are full expressions evaluated over the PRE-image
        # of each row (SET a = b, b = a swaps, like PG); subqueries and
        # sequence calls resolve statement-level first
        bound_sets = {}
        for name, e in stmt.sets.items():
            if e == ("default",):
                col = schema.column_by_name(name)
                if getattr(col, "default_seq", None):
                    raise ValueError(
                        "SET ... = DEFAULT on a serial column is not "
                        "supported (per-row nextval)")
                bound_sets[name] = ("const",
                                    getattr(col, "default_value", None))
            else:
                bound_sets[name] = self._bind(
                    await self._resolve_subqueries(e), schema)
        json_cols = {c.name for c in schema.columns
                     if c.type == ColumnType.JSON}
        updated = []
        for r in rows:
            idrow = {schema.column_by_name(k).id: v
                     for k, v in r.items()}
            nr = dict(r)
            for name, e in bound_sets.items():
                v = eval_expr_py(e, idrow)
                if name in json_cols and isinstance(v, (list, dict)):
                    import json as _json
                    v = _json.dumps(v)
                nr[name] = v
            updated.append(nr)
        dec_cols = _decimal_cols(schema)
        nn_cols = [c.name for c in schema.columns
                   if not c.nullable and c.name in stmt.sets]
        for r in updated:
            self._coerce_decimals(dec_cols, r)
            for name in nn_cols:
                if r.get(name) is None:
                    raise ValueError(
                        f"null value in column {name!r} violates "
                        f"not-null constraint")
        self._check_check_constraints(ct, updated)
        if any(fk["column"] in stmt.sets
               for fk in getattr(ct, "foreign_keys", None) or []):
            await self._check_foreign_keys(ct, updated)
        n = await self._write_update_rows(ct, schema, rows, updated)
        if getattr(stmt, "returning", None):
            return SqlResult(
                self._returning_rows(stmt.returning, updated, schema),
                f"UPDATE {n}")
        return SqlResult([], f"UPDATE {n}")

    async def _write_update_rows(self, ct, schema, pre_rows,
                                 updated) -> int:
        """Write an UPDATE's post-images.  A row whose SET moved the
        primary key re-keys like PG: the old key deletes and the new
        key strict-inserts (a collision errors), with deletes batched
        BEFORE inserts so overlapping moves (SET k = k + 1) land; a
        moved key still referenced by a child FK vetoes (ON UPDATE is
        NO ACTION scope)."""
        pk_names = [c.name for c in schema.key_columns]
        moved_old, deletes, inserts, upserts = [], [], [], []
        seen_pks = set()
        for r, nr in zip(pre_rows, updated):
            rpk = tuple(r.get(k) for k in pk_names)
            if rpk in seen_pks:
                # a multi-matching UPDATE ... FROM join lists the same
                # target row once per match; PG applies one of them
                continue
            seen_pks.add(rpk)
            if any(nr.get(k) != r.get(k) for k in pk_names):
                moved_old.append(r)
                deletes.append(RowOp(
                    "delete", {k: r[k] for k in pk_names}))
                inserts.append(RowOp("insert", nr))
            else:
                upserts.append(RowOp("upsert", nr))
        n = len(seen_pks)
        if moved_old and len(pk_names) == 1:
            # end-of-statement NO ACTION: a moved-away key that the
            # SAME statement re-creates (overlapping shift, k = k + 1)
            # is still present afterwards and does not veto
            recreated = {op.row[pk_names[0]] for op in inserts}
            vetoed = [r for r in moved_old
                      if r[pk_names[0]] not in recreated]
            if vetoed:
                await self._check_fk_restrict(
                    ct, pk_names, vetoed, all_actions=True)

        async def run_writes(write):
            for ops in (deletes, inserts, upserts):
                if ops:
                    await write(ct.info.name, ops)

        if not moved_old:
            await run_writes(self._txn.write if self._txn is not None
                             else self.client.write)
            return n
        if self._txn is None:
            # re-keying outside a txn runs under an IMPLICIT one: the
            # delete must not survive a strict-insert collision (PG's
            # statement atomicity — the row would simply vanish)
            own = await self.client.transaction().begin()
            try:
                await run_writes(own.write)
                await own.commit()
            except BaseException:
                try:
                    await own.abort()
                except Exception:   # noqa: BLE001
                    pass
                raise
            return n
        # inside an explicit txn the three batches share one statement
        # subtransaction (each _txn.write only brackets its own ops) —
        # a mid-statement duplicate-key must not leak the delete
        sp = f"__rekey_{self._txn._next_sub}"
        self._txn.savepoint(sp)
        try:
            await run_writes(self._txn.write)
        except Exception:
            try:
                await self._txn.rollback_to(sp)
                self._txn.release_savepoint(sp)
            except Exception:   # noqa: BLE001 — rollback_to aborts
                pass            # the txn itself on failure
            raise
        self._txn.release_savepoint(sp)
        return n


def _decimal_cols(schema) -> set:
    return {c.name for c in schema.columns
            if c.type == ColumnType.DECIMAL}


def _dequalify_name(name: str, quals: set) -> str:
    if isinstance(name, str) and "." in name:
        q, bare = name.split(".", 1)
        if q in quals:
            return bare
    return name


def _dequalify_node(node, quals: set):
    if not isinstance(node, tuple) or not node:
        return node
    if node[0] == "col":
        return ("col", _dequalify_name(node[1], quals))
    return tuple(_dequalify_node(c, quals) if isinstance(c, tuple) else c
                 for c in node)


def _dequalify_stmt(stmt, quals: set) -> None:
    """Strip `alias.`/`table.` qualifiers from every name position of a
    single-table SELECT, in place (the join path keeps qualifiers — it
    resolves them against per-table labels instead)."""
    if stmt.where is not None:
        stmt.where = _dequalify_node(stmt.where, quals)
    if getattr(stmt, "having", None) is not None:
        stmt.having = _dequalify_node(stmt.having, quals)
    for i, it in enumerate(stmt.items):
        if it[0] == "col":
            stmt.items[i] = ("col", _dequalify_name(it[1], quals))
        elif it[0] == "expr":
            stmt.items[i] = ("expr", _dequalify_node(it[1], quals))
        elif it[0] == "agg" and it[2] is not None:
            stmt.items[i] = ("agg", it[1],
                             _dequalify_node(it[2], quals))
    stmt.group_by = [_dequalify_name(n, quals) for n in stmt.group_by]
    if getattr(stmt, "group_exprs", None):
        stmt.group_exprs = {g: _dequalify_node(ast, quals)
                            for g, ast in stmt.group_exprs.items()}
    stmt.order_by = [(_dequalify_name(n, quals), d)
                     for n, d in stmt.order_by]


def _conjuncts(n):
    """Flatten a WHERE tree into its top-level AND conjuncts — THE one
    splitter shared by _join_pushdown and _try_fused_join, so the
    fused path's 'every conjunct was pushed' totality check counts
    exactly what the pushdown classifier saw."""
    if isinstance(n, tuple) and n and n[0] == "and":
        return _conjuncts(n[1]) + _conjuncts(n[2])
    return [n]


def _strip_qualifiers(node):
    """('col', 't.name') -> ('col', 'name') throughout an AST — pushed
    join conjuncts bind against the owning table's schema by bare
    column name."""
    if not isinstance(node, tuple) or not node:
        return node
    if node[0] == "col" and isinstance(node[1], str) and "." in node[1]:
        return ("col", node[1].split(".", 1)[1])
    return tuple(_strip_qualifiers(c) if isinstance(c, tuple) else c
                 for c in node)


def _eval_by_name(node, row: dict):
    """Evaluate the name-based AST over a merged join row."""
    kind = node[0]
    if kind == "col":
        name = node[1]
        bare = name.split(".", 1)[1] if "." in name else name
        return row.get(name, row.get(bare))
    if kind == "const":
        return node[1]
    rebuilt = tuple(
        _eval_wrap(c, row) if isinstance(c, tuple) else c
        for c in node[1:])
    from ..docdb.operations import eval_expr_py
    # translate to id-free eval: replace col nodes with consts
    def subst(n):
        if n[0] == "col":
            return ("const", _eval_by_name(n, row))
        if n[0] in ("in",):
            return ("in", subst(n[1]), n[2])
        if n[0] == "json":
            return ("json", n[1], subst(n[2]), n[3])
        return (n[0],) + tuple(subst(c) if isinstance(c, tuple) else c
                               for c in n[1:])
    return eval_expr_py(subst(node), {})


def _eval_wrap(node, row):
    return node


def _agg_vals(op: str, vals, star_count=None):
    """Shared values-level aggregate (window + CTE paths). star_count
    set = COUNT(*) over that many rows."""
    if op == "count" and star_count is not None:
        return star_count
    vv = [v for v in vals if v is not None]
    if op == "count":
        return len(vv)
    if not vv:
        return None
    if op == "sum":
        return sum(vv)
    if op == "min":
        return min(vv)
    if op == "max":
        return max(vv)
    if op == "avg":
        return sum(vv) / len(vv)
    raise ValueError(op)


def _agg_over_rows(op: str, expr, rows: List[dict]):
    """Client-side aggregate over name-keyed rows (CTE / in-memory)."""
    if op == "count" and expr is None:
        return len(rows)
    if op == "string_agg":
        vals = [_eval_by_name(expr[1], r) for r in rows]
        vals = [str(v) for v in vals if v is not None]
        return expr[2].join(vals) if vals else None
    if op == "count_distinct":
        vals = {v if not isinstance(v, list) else tuple(v)
                for r in rows
                if (v := _eval_by_name(expr, r)) is not None}
        return len(vals)
    return _agg_vals(op, [_eval_by_name(expr, r) for r in rows])


def _subst_aggrefs(node, grows: List[dict]):
    """Replace ("aggref", op, expr) leaves in a HAVING tree with their
    computed value over the group's rows."""
    if not isinstance(node, tuple):
        return node
    if node[0] == "aggref":
        return ("const", _agg_over_rows(node[1], node[2], grows))
    return (node[0],) + tuple(
        _subst_aggrefs(c, grows) if isinstance(c, tuple) else c
        for c in node[1:])


def _expr_name(node) -> str:
    """PG-style output name for an expression item: function calls
    project under the function's name (SELECT upper(t) -> column
    "upper"); anything else keeps the generic name."""
    if isinstance(node, tuple) and node and node[0] == "fn":
        return node[1]
    return "expr"


def _scalar(v):
    """Aggregate output -> python scalar; None passes through (min/max
    over zero rows); lists pass through (array_agg); strings pass
    through (string_agg)."""
    if isinstance(v, (list, str)):
        return v
    a = np.asarray(v)
    if a.dtype == object and a.shape == ():
        return a.item()
    if a.dtype.kind in "US" and a.shape == ():
        # string MIN/MAX served on device (dict-code decode) comes
        # back as a numpy unicode scalar after the wire round-trip
        return str(a.item())
    if np.issubdtype(a.dtype, np.integer):
        # sum/min/max over integer columns stay integral (PG:
        # sum(bigint) -> numeric printed without a fraction)
        return int(a)
    return float(a)


def _agg_name(it) -> str:
    op = it[1]
    e = it[2]
    if e is None:
        return "count"
    if e[0] == "col":
        return f"{op}_{e[1]}"
    return op


def _init(op):
    if op == "array_agg":
        return []
    if op == "count_distinct":
        return set()
    return 0 if op in ("sum", "count") else None


def _sagg_step(expr, state, idrow):
    v = eval_expr_py(expr[1], idrow)
    if v is None:
        return state
    if state is None:
        state = (expr[2], [])
    state[1].append(str(v))
    return state


def _step(op, expr, state, idrow):
    if op == "string_agg":
        return _sagg_step(expr, state, idrow)
    if expr is None:
        return (state or 0) + 1
    v = eval_expr_py(expr, idrow)
    if op == "array_agg":
        state.append(v)     # PG array_agg keeps NULL elements
        return state
    if v is None:
        return state
    if op == "count_distinct":
        state.add(v if not isinstance(v, list) else tuple(v))
        return state
    if op == "count":
        return (state or 0) + 1
    if op == "sum":
        return (state or 0) + v
    if op == "avg":
        s, c = state or (0, 0)
        return (s + v, c + 1)
    if op == "min":
        return v if state is None else min(state, v)
    if op == "max":
        return v if state is None else max(state, v)


def _final(op, state):
    if op == "avg":
        if not state or state[1] == 0:
            return None
        return state[0] / state[1]
    if op == "count_distinct":
        return len(state)
    if op == "string_agg":
        return None if state is None else state[0].join(state[1])
    if op in ("sum", "count"):
        return state or 0
    return state
