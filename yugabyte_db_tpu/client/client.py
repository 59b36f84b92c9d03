"""Cluster client: DDL via master, DML routed to tablet leaders.

Analog of the reference's YBClient + MetaCache + Batcher (reference:
src/yb/client/client.h:331, meta_cache.h:593 LookupTabletByKey,
batcher.h:166 per-tablet op grouping, async_rpc.cc retry-on-NOT_LEADER).
Scans fan out per tablet and combine partial aggregates client-side —
the same combine pggate does (reference: pg_doc_op.h:117).
"""
from __future__ import annotations

import asyncio
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from ..docdb.operations import ReadRequest, ReadResponse, RowOp, WriteRequest
from ..docdb.table_codec import TableCodec, TableInfo
from ..docdb.wire import (
    read_request_to_wire, read_response_from_wire, write_request_to_wire,
)
from ..dockv.partition import Partition
from ..utils import trace as _trace
from ..utils.tasks import cancel_and_drain
# partial-combine rules + scalar unwrap shared with the bypass
# session's host combine (ops/scan.py — one implementation, no drift)
from ..ops.scan import combine_agg_partials
from ..rpc.messenger import Messenger, RpcError


def _overload_backoff_s(e: Exception, attempt: int,
                        cap_s: float = 2.0) -> Optional[float]:
    """Client half of the typed overload contract: a SERVICE_UNAVAILABLE
    shed carrying retry_after_ms becomes a JITTERED EXPONENTIAL backoff
    seeded by the server's own estimate — retries spread out instead of
    stampeding back in lockstep (reference analog: client backoff on
    "server overloaded" responses, async_rpc.cc retry delays)."""
    ra = getattr(e, "retry_after_ms", None)
    if not ra:
        return None
    import random
    base = (ra / 1000.0) * (2 ** min(attempt, 5))
    return min(cap_s, base) * random.uniform(0.5, 1.0)


def _mesh_groups(req: ReadRequest, locations: list) -> tuple:
    """(groups, alone): the tablets of an aggregate scan that go out as
    one `read_tablets` RPC a server — two or more led by a server that
    owns several chips — and those that go one RPC a tablet.  With no
    such server (`tserver_device_chips` 1 everywhere) every tablet is
    alone."""
    if (not req.aggregates or req.join is not None
            or req.paging_state is not None
            or not any(l.chips for l in locations)):
        return [], locations
    by_leader: Dict[str, list] = {}
    for l in locations:
        # the leader where the master has reported one; a tablet's only
        # replica leads it
        leader = l.leader or (l.replicas[0][0] if len(l.replicas) == 1
                              else None)
        if leader is not None and l.chips.get(leader, 1) > 1:
            by_leader.setdefault(leader, []).append(l)
    groups = [g for g in by_leader.values() if len(g) > 1]
    grouped = {id(l) for g in groups for l in g}
    return groups, [l for l in locations if id(l) not in grouped]


@dataclass
class TabletLocation:
    tablet_id: str
    partition: Partition
    replicas: List[Tuple[str, Tuple[str, int]]]   # (ts_uuid, addr)
    leader: Optional[str] = None
    # ts_uuid -> the chips that replica's server owns, where it owns
    # more than one (the master passes on what the server reports)
    chips: Dict[str, int] = field(default_factory=dict)

    def leader_addr(self) -> Optional[Tuple[str, int]]:
        for u, a in self.replicas:
            if u == self.leader:
                return a
        return None


@dataclass
class CachedTable:
    info: TableInfo
    codec: TableCodec
    locations: List[TabletLocation]
    indexes: Dict[str, dict] = None
    # [{column, parent_table, parent_column}] — SQL-layer existence
    # checks on child writes (reference: FK via the PG executor)
    foreign_keys: List[dict] = None
    # CHECK constraint ASTs (name-based), evaluated per written row
    checks: List[tuple] = None


async def build_index_ops(ct, table: str, ops, getter):
    """Index mutations for a batch of base-table ops — the ONE place
    the per-index row shapes live (used by both the non-transactional
    client path and YBTransaction).  `getter(table, pk_row)` reads the
    base row's pre-image.  Returns [(index_name, idx_ops, undo_ops)]:
    undo_ops exactly invert idx_ops (computed here because only this
    function still holds the old row needed to restore a deleted
    entry).

    Shapes (reference: index tables in catalog_manager; unique layout
    yb_access/yb_lsm.c:233-366): non-unique entries key on
    (value, base pk); UNIQUE entries key on the value alone (base pk
    in the row payload) and write as insert-if-absent so duplicates
    collide on the shared doc key."""
    pk_names = [c.name for c in ct.info.schema.key_columns]
    # ONE pre-image fetch per base op (not per index): with N indexes
    # the old shape multiplied point reads (and RPC round trips on the
    # transactional path) by N
    olds = []
    for op in ops:
        pk_row = {n: op.row[n] for n in pk_names if n in op.row}
        olds.append(await getter(table, pk_row) if pk_row else None)
    out = []
    for index_name, spec in ct.indexes.items():
        cols = spec.get("columns") or [spec["column"]]
        unique = spec.get("unique")
        ins_ops: List[RowOp] = []
        del_ops: List[RowOp] = []
        ins_undo: List[RowOp] = []
        del_undo: List[RowOp] = []

        def vals_of(row):
            # non-unique: a row indexes when its FIRST (hash-routing)
            # column is non-NULL — NULL range components encode as
            # kNull, so composite entries with trailing NULLs still
            # serve first-column lookups (PG indexes such rows).
            # UNIQUE: any NULL skips the entry — PG's NULLS-DISTINCT
            # means NULL-bearing tuples never conflict, so they must
            # not occupy a shared doc key (documented approximation:
            # they are not index-servable either).
            vs = tuple(row.get(c) for c in cols)
            if vs[0] is None:
                return None
            if unique and any(v is None for v in vs):
                return None
            return vs

        def entry_key(vs):
            return dict(zip(cols, vs))

        for op, old in zip(ops, olds):
            old_vs = vals_of(old) if old else None
            full_old = old_vs and {
                **entry_key(old_vs),
                **{f"base_{n}": old[n] for n in pk_names}}
            new_vs = (vals_of(op.row)
                      if op.kind in ("upsert", "insert") else None)
            if full_old:
                if op.kind == "delete" or old_vs != new_vs:
                    # unique index keys on the value tuple alone: the
                    # delete targets it; base_* live in the value
                    del_ops.append(RowOp("delete", entry_key(old_vs)
                                         if unique else dict(full_old)))
                    del_undo.append(RowOp("upsert", dict(full_old)))
            if new_vs is not None:
                if old_vs == new_vs:
                    continue   # entry already present for this row
                new_row = {**entry_key(new_vs),
                           **{f"base_{n}": op.row[n] for n in pk_names}}
                # unique: insert-if-absent so a duplicate value tuple
                # collides on the shared doc key and is rejected
                ins_ops.append(RowOp("insert" if unique else "upsert",
                                     new_row))
                ins_undo.append(RowOp("delete", entry_key(new_vs)
                                     if unique else new_row))
        # Batch ordering within one index:
        #   1. inserts of values NOT being handed over (fail-fast on a
        #      real duplicate BEFORE any delete lands — a single mixed
        #      batch splits across index tablets and could apply the
        #      delete while the insert is rejected, un-indexing the old
        #      value),
        #   2. all deletes,
        #   3. "handover" inserts — values this same statement is
        #      RELEASING (a re-keying update moves the value to a new
        #      base pk): they can only succeed after their delete.
        if unique:
            def key_of(o):
                return tuple(o.row.get(c) for c in cols)
            released = {key_of(o) for o in del_ops}
            safe = [i for i, o in enumerate(ins_ops)
                    if key_of(o) not in released]
            hand = [i for i, o in enumerate(ins_ops)
                    if key_of(o) in released]
        else:
            safe, hand = list(range(len(ins_ops))), []
        if safe:
            out.append((index_name, [ins_ops[i] for i in safe],
                        [ins_undo[i] for i in safe]))
        if del_ops:
            out.append((index_name, del_ops, del_undo))
        if hand:
            out.append((index_name, [ins_ops[i] for i in hand],
                        [ins_undo[i] for i in hand]))
    return out


class YBClient:
    def __init__(self, master_addr=None, messenger: Optional[Messenger] = None,
                 master_addrs=None):
        """master_addr: single (host, port), or master_addrs: list of
        them (multi-master HA — calls fail over to the leader)."""
        if master_addrs is None:
            master_addrs = [master_addr]
        self.master_addrs = [tuple(a) for a in master_addrs]
        self.master_addr = self.master_addrs[0]
        self.messenger = messenger or Messenger("client")
        self._tables: Dict[str, CachedTable] = {}     # name -> cache
        self._seq_cache: Dict[str, list] = {}   # sequence -> cached block
        self._seq_last: Dict[str, int] = {}     # sequence -> last nextval
        # analytics bypass: callable(table name) -> local Tablet shard
        # objects of a co-located read replica (None/missing = no local
        # replica, scans stay on the RPC path)
        self._bypass_provider = None
        #: last scan_bypass routing outcome: {"used": bool, "reason":
        #: typed fallback reason | None, "stats": session stats | None}
        self.last_bypass: Dict[str, object] = {
            "used": False, "reason": None, "stats": None}

    async def _master_call(self, method: str, payload, timeout: float = 30.0):
        """Call the leader master, failing over across known masters
        (reference: master leader lookup in client/master_rpc.cc)."""
        last = None
        for attempt in range(10):
            for addr in self.master_addrs:
                try:
                    return await self.messenger.call(
                        addr, "master", method, payload, timeout=timeout)
                except RpcError as e:
                    last = e
                    if e.code in ("LEADER_NOT_READY", "NETWORK_ERROR",
                                  "SERVICE_UNAVAILABLE"):
                        continue
                    raise
                except (asyncio.TimeoutError, OSError) as e:
                    last = e
                    continue
            await asyncio.sleep(_overload_backoff_s(last, attempt)
                                or 0.1 * (attempt + 1))
        raise last or RpcError("no master reachable", "TIMED_OUT")

    # --- DDL --------------------------------------------------------------
    async def create_tablespace(self, name: str, placement=(),
                                preferred_zones=(),
                                or_replace: bool = False) -> None:
        """Named geo-placement policy (reference: YSQL tablespaces,
        master/ysql_tablespace_manager.cc). placement: iterable of
        {"zone": z, "min_replicas": n}."""
        await self._master_call("create_tablespace", {
            "name": name, "placement": list(placement),
            "preferred_zones": list(preferred_zones),
            "or_replace": or_replace})

    async def drop_tablespace(self, name: str) -> None:
        await self._master_call("drop_tablespace", {"name": name})

    async def list_tablespaces(self) -> dict:
        return (await self._master_call("list_tablespaces",
                                        {}))["tablespaces"]

    async def set_placement_info(self, placement=(),
                                 preferred_zones=()) -> None:
        """Universe-wide placement defaults + preferred leader zones."""
        await self._master_call("set_placement_info", {
            "placement": list(placement),
            "preferred_zones": list(preferred_zones)})

    async def create_table(self, info: TableInfo, num_tablets: int = 2,
                           replication_factor: int = 1,
                           tablegroup: Optional[str] = None,
                           split_rows=None,
                           tablespace: Optional[str] = None,
                           foreign_keys=None, checks=None) -> str:
        """split_rows: for range-sharded tables, PK rows whose encoded
        keys become the tablet split points."""
        split_points = None
        if split_rows:
            from ..docdb.table_codec import TableCodec
            codec = TableCodec(info)
            split_points = [
                info.partition_schema.partition_key_for_row(
                    codec.pk_entries(r)).hex() for r in split_rows]
        resp = await self._master_call(
            "create_table",
            {"name": info.name, "table": info.to_wire(),
             "num_tablets": num_tablets,
             "replication_factor": replication_factor,
             "tablegroup": tablegroup, "split_points": split_points,
             "tablespace_name": tablespace,
             "foreign_keys": list(foreign_keys or []),
             "checks": [list(c) for c in (checks or [])]})
        return resp["table_id"]

    async def create_tablegroup(self, name: str,
                                replication_factor: int = 1) -> str:
        resp = await self._master_call(
            "create_tablegroup",
            {"name": name, "replication_factor": replication_factor})
        return resp["tablegroup_id"]

    async def alter_table_add_columns(self, name: str,
                                      add_columns) -> int:
        r = await self._master_call(
            "alter_table", {"table": name,
                            "add_columns": [list(c) for c in add_columns]})
        self._tables.pop(name, None)
        return r["schema_version"]

    async def alter_table_drop_columns(self, name: str,
                                       drop_columns) -> int:
        r = await self._master_call(
            "alter_table", {"table": name,
                            "drop_columns": list(drop_columns)})
        self._tables.pop(name, None)
        return r["schema_version"]

    async def alter_table(self, name: str, add_columns=(),
                          drop_columns=()) -> int:
        """Combined ADD/DROP in ONE schema change (atomic at the
        master; a failed validation leaves nothing half-applied)."""
        r = await self._master_call(
            "alter_table",
            {"table": name,
             "add_columns": [list(c) for c in add_columns],
             "drop_columns": list(drop_columns)})
        self._tables.pop(name, None)
        return r["schema_version"]

    # --- sequences (client-side block cache; reference:
    # tserver/pg_client_session.cc PgSequenceCache) ------------------------
    SEQUENCE_CACHE_SIZE = 50

    async def create_sequence(self, name: str, start: int = 1,
                              increment: int = 1,
                              if_not_exists: bool = False) -> None:
        await self._master_call("create_sequence", {
            "name": name, "start": start, "increment": increment,
            "if_not_exists": if_not_exists})

    async def drop_sequence(self, name: str) -> None:
        await self._master_call("drop_sequence", {"name": name})
        self._seq_cache.pop(name, None)
        self._seq_last.pop(name, None)   # currval dies with the seq

    async def sequence_next(self, name: str) -> int:
        """nextval(): serve from the locally cached block; allocate a
        new block through the master (Raft-committed past the block
        BEFORE use, so failover can only leave gaps, never repeats)."""
        cached = self._seq_cache.get(name)
        if cached:
            v = cached.pop(0)
            self._seq_last[name] = v
            return v
        r = await self._master_call("sequence_alloc", {
            "name": name, "count": self.SEQUENCE_CACHE_SIZE})
        vals = [r["first"] + i * r["increment"]
                for i in range(r["count"])]
        v = vals[0]
        self._seq_cache[name] = vals[1:]
        self._seq_last[name] = v
        return v

    def sequence_current(self, name: str) -> int:
        """currval(): last value THIS session handed out (PG errors if
        nextval was never called in the session)."""
        if name not in self._seq_last:
            raise RpcError(
                f"currval of sequence {name!r} is not yet defined "
                f"in this session", "INVALID_ARGUMENT")
        return self._seq_last[name]

    async def create_view(self, name: str, select_sql: str,
                          or_replace: bool = False) -> None:
        await self._master_call("create_view", {
            "name": name, "select_sql": select_sql,
            "or_replace": or_replace})

    async def drop_view(self, name: str) -> None:
        await self._master_call("drop_view", {"name": name})

    async def get_view(self, name: str) -> Optional[str]:
        """View body SQL, or None. Uncached: views resolve only after a
        table lookup misses, and redefinitions through other nodes must
        be visible."""
        try:
            r = await self._master_call("get_view", {"name": name})
        except RpcError as e:
            if e.code == "NOT_FOUND":
                return None
            raise
        return r["select_sql"]

    # --- materialized views (matview/) ------------------------------------
    def matviews(self):
        """The per-client incremental-matview manager (lazy: the
        subsystem imports only when a matview surface is touched)."""
        if getattr(self, "_matview_mgr", None) is None:
            from ..matview.manager import MatviewManager
            self._matview_mgr = MatviewManager(self)
        return self._matview_mgr

    async def create_matview(self, name: str, viewdef: dict,
                             slot_id: Optional[str] = None,
                             state: Optional[dict] = None) -> None:
        await self._master_call("create_matview", {
            "name": name, "def": viewdef, "slot_id": slot_id,
            "state": state})

    async def get_matview(self, name: str) -> Optional[dict]:
        try:
            r = await self._master_call("get_matview", {"name": name})
        except RpcError as e:
            if e.code == "NOT_FOUND":
                return None
            raise
        return r["matview"]

    async def update_matview(self, name: str, **fields) -> None:
        await self._master_call("update_matview",
                                {"name": name, **fields})

    async def drop_matview(self, name: str) -> None:
        await self._master_call("drop_matview", {"name": name})

    async def list_matviews(self) -> List[str]:
        r = await self._master_call("list_matviews", {})
        return r["matviews"]

    async def drop_table(self, name: str) -> None:
        await self._master_call("drop_table", {"name": name})
        self._tables.pop(name, None)

    async def list_tables(self) -> List[dict]:
        resp = await self._master_call("list_tables", {})
        return resp["tables"]

    # --- MetaCache --------------------------------------------------------
    async def _table(self, name: str, refresh: bool = False) -> CachedTable:
        if not refresh and name in self._tables:
            return self._tables[name]
        resp = await self._master_call("get_table", {"name": name})
        info = TableInfo.from_wire(resp["table"])
        locs = []
        for l in resp["locations"]:
            locs.append(TabletLocation(
                tablet_id=l["tablet_id"],
                partition=Partition(bytes.fromhex(l["partition"][0]),
                                    bytes.fromhex(l["partition"][1])),
                replicas=[(r["ts_uuid"], tuple(r["addr"]))
                          for r in l["replicas"] if r["addr"]],
                leader=l.get("leader"),
                chips={r["ts_uuid"]: r["chips"]
                       for r in l["replicas"] if r.get("chips")}))
        from ..docdb.wire import _expr_from_wire
        cached = CachedTable(info, TableCodec(info), locs,
                             resp.get("indexes") or {},
                             resp.get("foreign_keys") or [],
                             [_expr_from_wire(c)
                              for c in resp.get("checks") or []])
        self._tables[name] = cached
        return cached

    def _tablet_for_key(self, ct: CachedTable, row: dict) -> TabletLocation:
        pk = ct.codec.pk_entries(row)
        part_key = ct.info.partition_schema.partition_key_for_row(pk)
        for loc in ct.locations:
            if loc.partition.contains(part_key):
                return loc
        raise RpcError("no tablet covers key", "NOT_FOUND")

    def _tablet_for_hash_key(self, ct: CachedTable, row: dict
                             ) -> TabletLocation:
        """Route by hash columns only (prefix lookups: the range part of
        the PK is unknown)."""
        schema = ct.info.schema
        nh = ct.info.partition_schema.num_hash_columns
        hash_cols = schema.key_columns[:nh]
        from ..docdb.table_codec import _KEV_MAKER
        entries = [_KEV_MAKER[c.type](row[c.name]) for c in hash_cols]
        part_key = ct.info.partition_schema.partition_key_for_row(entries)
        for loc in ct.locations:
            if loc.partition.contains(part_key):
                return loc
        raise RpcError("no tablet covers key", "NOT_FOUND")

    # --- DML: writes ------------------------------------------------------
    async def write(self, table: str, ops: Sequence[RowOp],
                    external_ht: int | None = None) -> int:
        """Batcher: group ops per tablet, send in parallel, retry on
        leadership changes; a concurrent tablet split re-routes by key
        against fresh locations (upserts/deletes are idempotent).
        Maintains secondary-index tables synchronously (reference:
        transactional index maintenance in pggate; round-1 maintenance
        is non-transactional)."""
        ct0 = await self._table(table)
        index_undo = None
        if ct0.indexes:
            index_undo = await self._maintain_indexes(ct0, table, ops)

        async def go(ct):
            by_tablet: Dict[str, List[RowOp]] = {}
            for op in ops:
                loc = self._tablet_for_key(ct, op.row)
                by_tablet.setdefault(loc.tablet_id, []).append(op)

            async def send(tablet_id: str, tops: List[RowOp]) -> int:
                req = WriteRequest(ct.info.table_id, tops,
                                   external_ht=external_ht,
                                   schema_version=ct.info.schema.version)
                payload = {"tablet_id": tablet_id,
                           "req": write_request_to_wire(req)}
                return (await self._call_leader(
                    ct, tablet_id, "write", payload))["rows_affected"]

            return sum(await asyncio.gather(
                *[send(tid, tops) for tid, tops in by_tablet.items()]))

        # catalog-version fence retries: a concurrent DDL moved the
        # schema — refresh the cached table and re-send; ops that only
        # touch still-live columns succeed, anything referencing a
        # dropped column fails loudly instead of writing through a
        # stale schema. Bounded retries with backoff cover the window
        # where tablets already adopted the new schema but the master's
        # catalog commit (which refresh reads) hasn't landed yet.
        try:
            for attempt in range(4):
                try:
                    return await self._retry_on_split(table, go)
                except RpcError as e:
                    if e.code != "SCHEMA_MISMATCH" or attempt == 3:
                        raise
                    await asyncio.sleep(0.05 * (attempt + 1))
                    ct = await self._table(table, refresh=True)
                    live = {c.name for c in ct.info.schema.columns}
                    for op in ops:
                        gone = set(op.row) - live
                        if gone:
                            raise RpcError(
                                f"column(s) {sorted(gone)} dropped by a "
                                f"concurrent ALTER on {table}",
                                "NOT_FOUND")
        except Exception:
            # base write failed after index maintenance: undo the index
            # entries, or an orphan unique entry would deny the value
            # to every future insert
            if index_undo:
                await self._undo_index_ops(index_undo)
            raise

    async def truncate_table(self, table: str) -> int:
        """TRUNCATE: Raft-replicated per-tablet store drop, fanned out
        to every tablet leader (reference: TRUNCATE through the tablet
        service; non-transactional like the reference's).  Secondary
        indexes truncate with the base table."""
        ct = await self._table(table)

        async def go(ct_):
            # ONE statement hybrid time: the first tablet's leader
            # mints it, the rest apply at the same ht — consumers see
            # one logical truncate, replays stay deterministic
            locs = list(ct_.locations)
            r0 = await self._call_leader(
                ct_, locs[0].tablet_id, "truncate_tablet",
                {"tablet_id": locs[0].tablet_id,
                 "table_id": ct_.info.table_id})
            ht = r0.get("ht")

            async def one(loc):
                await self._call_leader(
                    ct_, loc.tablet_id, "truncate_tablet",
                    {"tablet_id": loc.tablet_id,
                     "table_id": ct_.info.table_id, "ht": ht})
            await asyncio.gather(*[one(l) for l in locs[1:]])
            return len(locs)

        n = await self._retry_on_split(table, go)
        for index_name in (ct.indexes or {}):
            await self.truncate_table(index_name)
        return n

    async def insert(self, table: str, rows: Sequence[dict]) -> int:
        return await self.write(table, [RowOp("upsert", r) for r in rows])

    async def delete(self, table: str, pk_rows: Sequence[dict]) -> int:
        return await self.write(table, [RowOp("delete", r) for r in pk_rows])

    async def _maintain_indexes(self, ct, table: str, ops):
        """Non-transactional maintenance (reference: transactional
        maintenance lives in YBTransaction): index writes go FIRST (a
        unique violation must reject the statement before the base row
        lands); if the base write later fails the caller undoes them
        via the returned compensation ops — otherwise an orphan unique
        entry would permanently deny the value.  A crash between the
        two writes can still leak an entry; the transactional path has
        no such window."""
        undo: List[tuple] = []
        try:
            for index_name, idx_ops, undo_ops in await build_index_ops(
                    ct, table, ops, self.get):
                try:
                    if any(o.kind == "insert" for o in idx_ops):
                        # unique inserts go ONE AT A TIME: a multi-op
                        # batch fans out across index tablets
                        # concurrently, and a duplicate rejection on
                        # one tablet cannot tell us which sibling ops
                        # applied — blanket-undoing the failed batch
                        # could delete the EXISTING owner's entry.
                        # Per-op writes make applied == undone.
                        for o, u in zip(idx_ops, undo_ops):
                            await self.write(index_name, [o])
                            undo.append((index_name, [u]))
                    else:
                        await self.write(index_name, idx_ops)
                        undo.append((index_name, undo_ops))
                except RpcError as e:
                    # a concurrent DROP INDEX removed the index table:
                    # skip the dead index (its undo entries are moot —
                    # compensation writes would hit the same NOT_FOUND
                    # and are swallowed) instead of failing the user's
                    # base write forever off a stale cache
                    if e.code == "NOT_FOUND" and await \
                            self.index_dropped(table, index_name):
                        continue
                    raise
        except Exception:
            # partial failure (e.g. a later unique index rejected a
            # duplicate): undo the entries already written — an orphan
            # entry would point at a base row that never lands (and for
            # unique indexes would deny the value forever)
            await self._undo_index_ops(undo)
            raise
        return undo

    async def _undo_index_ops(self, undo) -> None:
        for index_name, ops in reversed(undo):
            if not ops:
                continue
            try:
                await self.write(index_name, ops)
            except Exception:   # noqa: BLE001 — best-effort compensation
                pass

    async def index_lookup(self, table: str, index_name: str, value
                           ) -> List[dict]:
        """Indexed-equality lookup: prefix-scan the index tablet owning
        the value, return base-table PK rows.  `value` is a scalar for
        single-column indexes or a list/tuple for composite ones (a
        PREFIX of the index columns suffices — the first column routes
        the hash)."""
        ct = await self._table(table)
        spec = ct.indexes[index_name]
        ict = await self._table(spec["index_table"])
        cols = spec.get("columns") or [spec["column"]]
        vals = (list(value) if isinstance(value, (list, tuple))
                else [value])
        prefix = dict(zip(cols, vals))
        loc = self._tablet_for_hash_key(ict, prefix)
        req = ReadRequest(ict.info.table_id, pk_prefix=prefix)
        payload = {"tablet_id": loc.tablet_id,
                   "req": read_request_to_wire(req)}
        resp = read_response_from_wire(
            await self._call_leader(ict, loc.tablet_id, "read", payload))
        return [{n: r[f"base_{n}"] for n in spec["base_pk"]}
                for r in resp.rows]

    async def create_secondary_index(self, table: str, index_name: str,
                                     column, unique: bool = False
                                     ) -> int:
        """Create + backfill (reference: online backfill,
        master/backfill_index.cc — ours quiesces via full scan).  A
        UNIQUE index keys the index table by the indexed value alone,
        so duplicate inserts collide on one doc key and the write
        path's insert-if-absent gate rejects them; the backfill itself
        surfaces pre-existing duplicates as DUPLICATE_KEY."""
        columns = (list(column) if isinstance(column, (list, tuple))
                   else [column])
        await self._master_call(
            "create_secondary_index",
            {"table": table, "index_name": index_name,
             "column": columns[0], "columns": columns,
             "unique": unique},
            timeout=60.0)
        self._tables.pop(table, None)
        ct = await self._table(table)
        pk_names = [c.name for c in ct.info.schema.key_columns]
        resp = await self.scan(table, ReadRequest(
            "", columns=tuple(pk_names + columns)))
        rows = [r for r in resp.rows
                if r.get(columns[0]) is not None
                and (not unique or all(r.get(c) is not None
                                       for c in columns))]
        if rows:
            try:
                await self.write(index_name, [
                    RowOp("insert" if unique else "upsert",
                          {**{c: r[c] for c in columns},
                           **{f"base_{n}": r[n] for n in pk_names}})
                    for r in rows])
            except RpcError:
                # failed backfill (pre-existing duplicates): a
                # half-registered index would miss lookups AND deny
                # values through its insert-if-absent gate — deregister
                # it so the DDL fails cleanly and can be retried
                try:
                    await self._master_call(
                        "drop_secondary_index",
                        {"table": table, "index_name": index_name},
                        timeout=30.0)
                except Exception:   # noqa: BLE001
                    # deregistration itself failed (master failover):
                    # the ORIGINAL duplicate-key error must surface,
                    # not the transport error; re-running the DDL
                    # retries the cleanup
                    pass
                self._tables.pop(table, None)
                raise
        return len(rows)

    async def drop_secondary_index(self, index_name: str,
                                   table: str | None = None) -> None:
        """Deregister + drop a secondary index in ONE master RPC —
        the master owns the index registry and resolves the base
        relation itself (reference: DROP INDEX through master
        DeleteTable on the index relation, catalog_manager.cc)."""
        resp = await self._master_call(
            "drop_secondary_index",
            {"table": table, "index_name": index_name}, timeout=30.0)
        self._tables.pop(resp.get("table") or table, None)
        self._tables.pop(index_name, None)

    async def index_dropped(self, table: str, index_name: str) -> bool:
        """After an index-table write failed NOT_FOUND: was the index
        dropped concurrently by another client?  The refresh heals
        this client's cached index list either way; True means the
        caller should skip maintaining the dead index rather than
        fail the user's base-table write."""
        try:
            ct = await self._table(table, refresh=True)
        except Exception:   # noqa: BLE001 — can't tell; let the
            return False    # original error surface
        return index_name not in (ct.indexes or {})

    # --- DML: reads -------------------------------------------------------
    async def _retry_on_split(self, table: str, fn):
        """Run `fn(ct)` retrying with refreshed locations when a tablet
        splits underneath it (the split parent answers TABLET_SPLIT
        until the catalog routes to its children)."""
        ct = await self._table(table)
        for attempt in range(4):
            try:
                return await fn(ct)
            except RpcError as e:
                if e.code != "TABLET_SPLIT" or attempt == 3:
                    raise
                _trace.current_span().count("retries")
                await asyncio.sleep(0.2 * (attempt + 1))
                ct = await self._table(table, refresh=True)
        raise RpcError("unreachable", "INTERNAL")

    async def get(self, table: str, pk_row: dict) -> Optional[dict]:

        async def go(ct):
            loc = self._tablet_for_key(ct, pk_row)
            req = ReadRequest(ct.info.table_id, pk_eq=pk_row)
            payload = {"tablet_id": loc.tablet_id,
                       "req": read_request_to_wire(req)}
            resp = read_response_from_wire(await self._call_leader(
                ct, loc.tablet_id, "read", payload))
            return resp.rows[0] if resp.rows else None
        return await self._retry_on_split(table, go)

    async def scan(self, table: str, req: ReadRequest,
                   keep_all: bool = False) -> ReadResponse:
        """Fan out to every tablet; combine rows or partial aggregates.
        keep_all: skip the union-level LIMIT trim (callers that sort
        client-side need every tablet's top-N, not the first N of an
        arbitrary tablet order)."""
        # child of the statement's `sql.execute`; a scan sent with no
        # statement above it (DDL backfills, tools) roots its own trace
        with _trace.TRACES.span("client.scan",
                                tags={"retries": 0}) as sp:
            ct = await self._table(table)
            req.table_id = ct.info.table_id

            async def one(loc: TabletLocation, ct2: CachedTable,
                          window=None) -> ReadResponse:
                rows: List[dict] = []
                paging = None
                first: Optional[ReadResponse] = None
                while True:
                    r = ReadRequest(
                        req.table_id, columns=req.columns, where=req.where,
                        aggregates=req.aggregates, group_by=req.group_by,
                        limit=req.limit, paging_state=paging,
                        read_ht=req.read_ht, consistency=req.consistency,
                        join=req.join, window=window)
                    payload = {"tablet_id": loc.tablet_id,
                               "req": read_request_to_wire(r)}
                    resp = read_response_from_wire(await self._call_leader(
                        ct2, loc.tablet_id, "read", payload))
                    if first is None:
                        first = resp
                    rows.extend(resp.rows)
                    if resp.paging_state is None or req.aggregates:
                        break
                    if req.limit is not None and len(rows) >= req.limit:
                        break
                    paging = resp.paging_state
                first.rows = rows
                return first

            async def many(group: List[TabletLocation],
                           ct2: CachedTable) -> List[ReadResponse]:
                """The tablets one multi-chip server leads, as ONE read:
                its answer over all of them (combined on its chips), or
                each tablet's own where it served them one by one.  A
                tablet's rows are in exactly one part either way: if
                the server refuses the call, its tablets are asked one
                by one; a call that timed out is sent again (a cold
                build outlasts the deadline, the retry finds the batch
                cached), as a tablet's own read is."""
                payload = {"tablet_ids": [l.tablet_id for l in group],
                           "req": read_request_to_wire(req)}
                try:
                    got = await self._call_leader(
                        ct2, group[0].tablet_id, "read_tablets", payload)
                except RpcError as e:
                    if e.code == "TABLET_SPLIT":
                        raise
                    return list(await asyncio.gather(
                        *[one(l, ct2) for l in group]))
                if "mesh" in got:
                    return [read_response_from_wire(got["mesh"])]
                return [read_response_from_wire(p) for p in got["parts"]]

            async def go(ct2):
                # the server-side window pushdown only holds on a single
                # tablet (a window spans the whole table); with fan-out > 1
                # per-tablet copies DROP the window so servers don't burn
                # compute on partials the client must redo anyway
                win = req.window if len(ct2.locations) == 1 else None
                sp.set_tag("tablets", len(ct2.locations))
                groups, alone = _mesh_groups(req, ct2.locations)
                got = await asyncio.gather(
                    *[many(g, ct2) for g in groups],
                    *[one(l, ct2, win) for l in alone])
                parts = [p for g in got[:len(groups)] for p in g] \
                    + list(got[len(groups):])
                with _trace.TRACES.span("client.combine", child_only=True,
                                        tags={"parts": len(parts)}):
                    return self._combine(req, parts)
            return await self._retry_on_split(table, go)

    # --- analytics bypass routing ----------------------------------------
    def set_bypass_provider(self, provider) -> None:
        """Register the local-replica provider for scan_bypass:
        callable(table name) -> ordered shard objects (TabletPeer
        preferred — the session then waits on MVCC safe time before
        pinning; bare Tablet works for direct-apply replicas), in the
        order the RPC fan-out visits so combined partials match; or
        None when no local replica exists."""
        self._bypass_provider = provider

    async def scan_bypass(self, table: str,
                          req: ReadRequest) -> ReadResponse:
        """Route an aggregate scan through the SST-direct bypass engine
        (yugabyte_db_tpu/bypass/) when `bypass_reader_enabled` is on
        and a local replica is registered; every refusal — flag off, no
        local tablets, a request shape the engine doesn't serve
        (point/prefix lookups, paging, row scans), typed engine
        ineligibility — falls back to the ordinary RPC scan path,
        recording why in ``last_bypass``.  With the flag off (the
        default) this IS `scan`, byte for byte."""
        from ..utils import flags as _flags
        self.last_bypass = {"used": False, "reason": None, "stats": None}
        if not _flags.get("bypass_reader_enabled"):
            from ..bypass.errors import REASON_FLAG_OFF
            self.last_bypass["reason"] = REASON_FLAG_OFF
            return await self.scan(table, req)
        if (not req.aggregates or req.pk_eq is not None
                or req.pk_prefix is not None
                or req.paging_state is not None):
            # whole-tablet aggregates are the ONLY bypass shape; a
            # keyed/paged/row request must keep its RPC semantics
            self.last_bypass["reason"] = "request_shape"
            return await self.scan(table, req)
        tablets = (self._bypass_provider(table)
                   if self._bypass_provider is not None else None)
        if not tablets:
            self.last_bypass["reason"] = "no_local_replica"
            return await self.scan(table, req)
        from ..bypass import BypassIneligible, BypassSession

        def _run():
            # heavy synchronous pin+scan work; the executor keeps the
            # event loop (and with it every point RPC this client has
            # in flight) unblocked — the isolation the subsystem is for
            gout: dict = {}
            with BypassSession(tablets, read_ht=req.read_ht) as s:
                outs, counts, stats = s.scan_aggregate(
                    req.where, req.aggregates, req.group_by,
                    grouped_out=gout, join=req.join)
                return outs, counts, gout.get("group_values"), stats
        loop = asyncio.get_running_loop()
        try:
            outs, counts, gvals, stats = await loop.run_in_executor(
                None, _run)
        except BypassIneligible as e:
            self.last_bypass["reason"] = e.reason
            return await self.scan(table, req)
        self.last_bypass = {"used": True, "reason": None, "stats": stats}
        return ReadResponse(agg_values=outs, group_counts=counts,
                            group_values=gvals, backend="bypass")

    async def scan_pages(self, table: str, req: ReadRequest,
                         page_size: int = 1000):
        """Streaming scan with DOUBLE-BUFFERED paging: while the caller
        consumes page N, page N+1's RPC is already in flight (reference:
        the prefetching PgDocOp pipeline, pggate/pg_doc_op.cc). Yields
        lists of rows; tablets stream in location order."""
        ct = await self._table(table)
        req.table_id = ct.info.table_id

        async def fetch(loc, paging):
            r = ReadRequest(
                req.table_id, columns=req.columns, where=req.where,
                limit=page_size, paging_state=paging,
                read_ht=req.read_ht, consistency=req.consistency)
            payload = {"tablet_id": loc.tablet_id,
                       "req": read_request_to_wire(r)}
            return read_response_from_wire(await self._call_leader(
                ct, loc.tablet_id, "read", payload))

        nxt = None
        try:
            for loc in ct.locations:
                nxt = asyncio.ensure_future(fetch(loc, None))
                while nxt is not None:
                    resp = await nxt
                    nxt = (asyncio.ensure_future(
                               fetch(loc, resp.paging_state))
                           if resp.paging_state is not None else None)
                    if resp.rows:
                        yield resp.rows
        finally:
            # consumer broke out early: reap the in-flight prefetch
            # (drained, so a response racing the cancel can't leave an
            # unretrieved task behind — bpo-37658)
            await cancel_and_drain(nxt)

    def _combine(self, req: ReadRequest, parts: List[ReadResponse]
                 ) -> ReadResponse:
        if not req.aggregates:
            rows = [r for p in parts for r in p.rows]
            served, reason = False, None
            if req.window is not None:
                served = len(parts) == 1 and parts[0].window_served
                reason = parts[0].window_reason if parts else None
                if not served:
                    # fan-out (or a per-tablet refusal): the parts hold
                    # COMPLETE plain rows, so run the same serving
                    # helper over the union — the helper sorts
                    # internally, no stream merge needed.  Typed
                    # refusal -> the executor's interpreted windows.
                    from ..ops.window_scan import (REASON_WINDOW_PAGED,
                                                   WindowIneligible,
                                                   serve_window_rows)
                    try:
                        if req.limit is not None:
                            raise WindowIneligible(
                                REASON_WINDOW_PAGED, "limit")
                        serve_window_rows(req.window, rows)
                        served, reason = True, None
                    except WindowIneligible as e:
                        served, reason = False, e.reason
            if req.limit is not None:
                rows = rows[:req.limit]
            return ReadResponse(rows=rows,
                                backend=parts[0].backend if parts else "cpu",
                                window_served=served,
                                window_reason=reason)
        from ..ops.grouped_scan import DictGroupSpec
        from ..ops.scan import (HashGroupSpec, _expand_avg,
                                combine_grouped_partials)
        aggs = _expand_avg(req.aggregates)
        if isinstance(req.group_by, (HashGroupSpec, DictGroupSpec)):
            # merge per-tablet grouped partials BY GROUP KEY — slots
            # aren't aligned across tablets (each shard merges its own
            # dictionary / sees its own distinct hash keys).  ONE shared
            # implementation with the bypass host combine (reference
            # analog: pggate's client-side grouped-partial combine).
            outs, counts, gvals = combine_grouped_partials(
                aggs, [(p.agg_values, p.group_counts, p.group_values)
                       for p in parts])
            return ReadResponse(agg_values=outs, group_counts=counts,
                                group_values=gvals,
                                backend=parts[0].backend if parts
                                else "cpu")
        total, counts = combine_agg_partials(
            aggs, [p.agg_values for p in parts],
            [p.group_counts for p in parts])
        return ReadResponse(agg_values=total, group_counts=counts,
                            backend=parts[0].backend if parts else "cpu")

    # --- vector search ------------------------------------------------------
    async def build_vector_index(self, table: str, column: str,
                                 lists: int = 100,
                                 method: str = "ivfflat",
                                 options: Optional[dict] = None) -> int:
        """Build an ANN index (any registry method: ivfflat / hnsw) on
        every tablet of `table`; returns total rows indexed."""
        ct = await self._table(table)
        total = 0
        for loc in ct.locations:
            r = await self._call_leader(ct, loc.tablet_id,
                                        "build_vector_index",
                                        {"tablet_id": loc.tablet_id,
                                         "column": column, "lists": lists,
                                         "method": method,
                                         "options": dict(options or {})})
            total += r["indexed"]
        return total

    async def vector_search(self, table: str, column: str, query,
                            k: int = 10, nprobe: int = 8,
                            ef_search: Optional[int] = None):
        """Distributed kNN: per-tablet top-k, client-side re-rank
        (the RPC twin of parallel/vector.py's all_gather path).
        `nprobe` drives IVF probing, `ef_search` the HNSW beam; each
        tablet falls back to its index's build-time options when a
        knob does not apply."""
        ct = await self._table(table)
        hits = []
        for loc in ct.locations:
            r = await self._call_leader(
                ct, loc.tablet_id, "vector_search",
                {"tablet_id": loc.tablet_id, "column": column,
                 "query": list(map(float, query)), "k": k,
                 "nprobe": nprobe, "ef_search": ef_search})
            hits.extend((pk, d) for pk, d in r["hits"])
        hits.sort(key=lambda h: h[1])
        return hits[:k]

    # --- transactions ------------------------------------------------------
    def transaction(self, isolation: str = "snapshot"):
        from .transaction import YBTransaction
        return YBTransaction(self, isolation=isolation)

    # --- leader routing with retry ---------------------------------------
    async def _call_leader(self, ct: CachedTable, tablet_id: str,
                           method: str, payload, max_tries: int = 8,
                           timeout: float = 10.0):
        loc = next(l for l in ct.locations if l.tablet_id == tablet_id)
        last_err: Optional[Exception] = None
        for attempt in range(max_tries):
            addrs = []
            la = loc.leader_addr()
            if la is not None:
                addrs.append(la)
            addrs += [a for _, a in loc.replicas if a not in addrs]
            overload_s: Optional[float] = None
            for addr in addrs:
                try:
                    return await self.messenger.call(
                        addr, "tserver", method, payload, timeout=timeout)
                except RpcError as e:
                    last_err = e
                    if e.code == "TABLET_SPLIT":
                        # the tablet split under us: the caller must
                        # re-route by key against fresh locations
                        raise
                    _trace.current_span().count("retries")
                    if e.code == "SERVICE_UNAVAILABLE":
                        # typed overload shed: honor the server's
                        # retry_after_ms (jittered exponential) instead
                        # of hammering the next replica immediately —
                        # followers would only answer LEADER_NOT_READY
                        # while adding load the server just asked us
                        # to shed
                        overload_s = _overload_backoff_s(e, attempt)
                        if overload_s is not None:
                            break
                        continue
                    if e.code in ("LEADER_NOT_READY", "LEADER_HAS_NO_LEASE",
                                  "NOT_FOUND", "NETWORK_ERROR"):
                        continue
                    raise
                except (asyncio.TimeoutError, OSError) as e:
                    last_err = e
                    _trace.current_span().count("retries")
                    continue
            if overload_s is not None:
                # pure overload: the leader is alive, just shedding —
                # back off and retry the SAME locations (no refresh:
                # leadership did not move)
                await asyncio.sleep(overload_s)
                continue
            # refresh locations (leadership moved / tablet moved)
            await asyncio.sleep(0.1 * (attempt + 1))
            ct2 = await self._table(ct.info.name, refresh=True)
            loc2 = next((l for l in ct2.locations
                         if l.tablet_id == tablet_id), None)
            if loc2 is None:
                # tablet no longer exists (split finished): re-route
                raise RpcError(f"tablet {tablet_id} was split",
                               "TABLET_SPLIT")
            loc = loc2
        raise last_err or RpcError("exhausted retries", "TIMED_OUT")
