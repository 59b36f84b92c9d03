"""Master: the control plane.

Analog of the reference's yb-master (reference: src/yb/master/ —
CatalogManager catalog_manager.cc:4444 CreateTable, TS registry
ts_manager.cc, heartbeats master_heartbeat_service.cc:403, sys catalog
sys_catalog.cc). The sys catalog persists as an atomically-replaced
JSON snapshot journaled through the same Raft log type used by
tablets; multi-master groups replicate catalog deltas through
`start_consensus` (leader serves DDL, reads gate on term-start
catch-up).
"""
from __future__ import annotations

import asyncio
import json
import os
import time
import uuid as uuidlib
from typing import Dict, List, Optional, Tuple

from ..docdb.table_codec import TableInfo
from ..dockv.packed_row import ColumnSchema, TableSchema
from ..dockv.partition import PartitionSchema
from ..rpc.messenger import Messenger, RpcError
from ..utils import flags
from ..utils.tasks import cancel_and_drain

TS_LIVENESS_S = 3.0


class Master:
    def __init__(self, fs_root: str, uuid: str = "m0"):
        self.fs_root = fs_root
        self.uuid = uuid
        os.makedirs(fs_root, exist_ok=True)
        self.messenger = Messenger(f"master-{uuid}")
        # created lazily on the serving loop (no loop exists yet here)
        self._persist_alock = None
        # sys catalog state (the Raft-replicated state machine)
        self.tables: Dict[str, dict] = {}      # table_id -> entry
        self.tablets: Dict[str, dict] = {}     # tablet_id -> entry
        self.tservers: Dict[str, dict] = {}    # ts_uuid -> {addr, last_hb}
        # catalog-persisted maps below must be initialized BEFORE
        # _load() so the snapshot's values survive __init__ (a later
        # assignment would silently wipe them on standalone restart):
        # table -> {source_master} inbound xCluster replication config
        self.xcluster_replication: Dict[str, dict] = {}
        # slot_id -> slot entry: the cdc_state-table analog for the
        # CDC-SDK consumer API (reference: cdc/cdc_state_table.cc,
        # replication-slot metadata in cdcsdk_virtual_wal.cc)
        self.replication_slots: Dict[str, dict] = {}
        # name -> {"next": int, "increment": int} (reference: PG
        # sequences backed by PgSequenceCache chunks,
        # tserver/pg_client_session.cc sequence ops)
        self.sequences: Dict[str, dict] = {}
        # view name -> SELECT body SQL (persisted verbatim; expanded
        # by the SQL layer at query time — reference: PG pg_views)
        self.views: Dict[str, str] = {}
        # materialized-view name -> {"def": structured ViewDef dict,
        # "slot_id": CDC slot feeding the maintainer, "state": the
        # maintainer's durable fold state (partials + applied LSN +
        # watermark) — persisted BEFORE the slot's confirm_flush so a
        # restarted maintainer resumes exactly-once (matview/)
        self.matviews: Dict[str, dict] = {}
        # tablespace name -> placement policy (reference: YSQL
        # tablespaces as geo-placement policies,
        # master/ysql_tablespace_manager.cc):
        #   {"placement": [{"zone": z, "min_replicas": n}, ...],
        #    "preferred_zones": [z, ...]}
        # the reserved name "cluster" is the universe-wide default
        # (reference: --placement_* flags / set_preferred_zones)
        self.tablespaces: Dict[str, dict] = {}
        self._load()
        self.messenger.register_service("master", self)
        self.messenger.register_service("master-heartbeat", self)
        from .load_balancer import ClusterLoadBalancer
        self.load_balancer = ClusterLoadBalancer(self)
        self._lb_task: Optional[asyncio.Task] = None
        self._running = False
        # table -> replicated-up-to HT for inbound xCluster replication
        self._xcluster_safe_time: Dict[str, int] = {}
        self._xcluster_tasks: Dict[str, object] = {}
        # (ts_uuid, tablet_id) -> first time reported as orphaned
        self._orphan_seen: Dict[Tuple[str, str], float] = {}
        # placements legitimately created ahead of their catalog commit
        # (e.g. a move destination between create_tablet and the
        # replicas update) — the orphan sweep must not touch them
        self._gc_inflight: set = set()
        self._xcluster_reconcile_lock = asyncio.Lock()
        # serializes sequence block allocation: the read-modify-commit
        # spans an await (Raft replicate) and must not interleave
        self._seq_lock = asyncio.Lock()
        self.auto_balance = False   # ticked explicitly or via enable
        # tablet_id -> {"size_bytes", "wal_index", "at", "ops_s"}:
        # leader-reported store size + EWMA write rate differentiated
        # from successive heartbeat wal_index deltas (the auto-split
        # size/traffic triggers read these; volatile, not catalog)
        self._tablet_reports: Dict[str, dict] = {}
        # tablets with an auto-split (or barrier) currently in flight
        self._splitting: set = set()
        # sys-catalog Raft (None = standalone single master, still
        # journals through a local single-peer group once started)
        self.consensus = None

    # --- sys catalog as a Raft group (reference: master/sys_catalog.cc —
    # "master state is stored in a single-tablet Raft group") -------------
    async def start_consensus(self, peers) -> None:
        """peers: [(uuid, (host, port))] including self. Catalog
        mutations replicate through this group; followers apply the same
        deltas, so any elected master serves DDL."""
        from ..consensus import Log, RaftConfig, PeerSpec, RaftConsensus
        cfg = RaftConfig([PeerSpec(u, tuple(a)) for u, a in peers])
        log = Log(os.path.join(self.fs_root, "syscatalog-wal"))
        self.consensus = RaftConsensus(
            "syscatalog", self.uuid, cfg, log, self.messenger,
            self.fs_root, self._apply_catalog_entry)
        # rebuild from scratch on restart: snapshot already loaded; the
        # log re-applies deltas idempotently (puts are last-writer-wins)
        await self.consensus.start()

    async def _apply_catalog_entry(self, entry) -> None:
        import msgpack as _mp
        for op in _mp.unpackb(entry.payload, raw=False):
            kind = op[0]
            if kind == "put_table":
                self.tables[op[1]] = op[2]
            elif kind == "del_table":
                self.tables.pop(op[1], None)
            elif kind == "put_tablet":
                self.tablets[op[1]] = op[2]
            elif kind == "del_tablet":
                self.tablets.pop(op[1], None)
            elif kind == "put_xcluster":
                self.xcluster_replication[op[1]] = op[2]
            elif kind == "del_xcluster":
                self.xcluster_replication.pop(op[1], None)
            elif kind == "put_repl_slot":
                self.replication_slots[op[1]] = op[2]
            elif kind == "del_repl_slot":
                self.replication_slots.pop(op[1], None)
            elif kind == "put_sequence":
                self.sequences[op[1]] = op[2]
            elif kind == "del_sequence":
                self.sequences.pop(op[1], None)
            elif kind == "put_view":
                self.views[op[1]] = op[2]
            elif kind == "del_view":
                self.views.pop(op[1], None)
            elif kind == "put_matview":
                self.matviews[op[1]] = op[2]
            elif kind == "del_matview":
                self.matviews.pop(op[1], None)
            elif kind == "put_tablespace":
                self.tablespaces[op[1]] = op[2]
            elif kind == "del_tablespace":
                self.tablespaces.pop(op[1], None)
        await self._persist_off_loop()

    async def _commit_catalog(self, ops) -> None:
        """Apply catalog deltas through Raft when running replicated;
        direct when standalone."""
        if self.consensus is None:
            import types
            e = types.SimpleNamespace(payload=__import__("msgpack").packb(ops))
            await self._apply_catalog_entry(e)
            return
        import msgpack as _mp
        await self.consensus.replicate("write", _mp.packb(ops))

    def _check_leader(self) -> None:
        if self.consensus is None:
            return
        if not self.consensus.is_leader():
            raise RpcError(
                f"not the leader master "
                f"(hint={self.consensus.leader_hint()})",
                "LEADER_NOT_READY")
        # a freshly-elected leader may not have APPLIED its whole
        # catalog log yet; gate on the TERM-START index (not the live
        # last_index — that would spuriously reject during any
        # in-flight catalog write) (reference: leader_ready gating)
        if self.consensus.last_applied < self.consensus.term_start_index:
            raise RpcError("leader catalog still loading",
                           "LEADER_NOT_READY")

    def is_leader(self) -> bool:
        return self.consensus is None or self.consensus.is_leader()

    # --- persistence (sys catalog snapshot) -------------------------------
    @property
    def _catalog_path(self) -> str:
        return os.path.join(self.fs_root, "sys_catalog.json")

    def _load(self):
        if os.path.exists(self._catalog_path):
            with open(self._catalog_path) as f:
                d = json.load(f)
            self.tables = d["tables"]
            self.tablets = d["tablets"]
            self.xcluster_replication = d.get("xcluster", {})
            self.replication_slots = d.get("repl_slots", {})
            self.sequences = d.get("sequences", {})
            self.views = d.get("views", {})
            self.matviews = d.get("matviews", {})
            self.tablespaces = d.get("tablespaces", {})

    def _dump_catalog(self) -> str:
        """Serialize the catalog ON the loop — the dicts are loop
        state, so snapshotting here (not in the executor) is what
        keeps the bytes internally consistent."""
        return json.dumps({"tables": self.tables, "tablets": self.tablets,
                           "xcluster": self.xcluster_replication,
                           "repl_slots": self.replication_slots,
                           "sequences": self.sequences,
                           "views": self.views,
                           "matviews": self.matviews,
                           "tablespaces": self.tablespaces})

    def _write_catalog(self, data: str) -> None:
        """Durable write (executor target: fsync is a device stall)."""
        from ..utils.trace import wait_status
        tmp = self._catalog_path + ".tmp"
        with open(tmp, "w") as f:
            f.write(data)
            f.flush()
            with wait_status("Catalog_Fsync", component="master"):
                os.fsync(f.fileno())
        os.replace(tmp, self._catalog_path)

    def _persist(self):
        self._write_catalog(self._dump_catalog())

    async def _persist_off_loop(self):
        """Catalog persistence without stalling the loop: snapshot the
        state synchronously, then fsync+rename in the executor.  The
        lock serializes writers (concurrent standalone commits would
        race the shared .tmp path and could land an older snapshot
        over a newer one); there is no suspension point between the
        snapshot and the lock acquire, so write order == apply order."""
        data = self._dump_catalog()
        if self._persist_alock is None:
            self._persist_alock = asyncio.Lock()
        async with self._persist_alock:
            await asyncio.get_running_loop().run_in_executor(
                None, self._write_catalog, data)

    # --- lifecycle --------------------------------------------------------
    async def start(self, host: str = "127.0.0.1", port: int = 0,
                    auto_balance: bool = False):
        await self.messenger.start(host, port)
        self._running = True
        self.auto_balance = auto_balance
        self._lb_task = asyncio.create_task(self._lb_loop())
        return self.messenger.addr

    async def _lb_loop(self):
        """Maintenance loop: LB (when enabled) + snapshot schedules."""
        while self._running:
            if self.auto_balance:
                try:
                    await self.load_balancer.tick()
                except Exception:   # noqa: BLE001 — LB must never die
                    pass
            try:
                await self.tick_snapshot_schedules()
            except Exception:   # noqa: BLE001
                pass
            try:
                await self._ensure_xcluster_replicators()
            except Exception:   # noqa: BLE001
                pass
            if self.is_leader():
                try:
                    await self._gc_hidden_tablets()
                except Exception:   # noqa: BLE001
                    pass
                try:
                    await self._gc_orphan_replicas()
                except Exception:   # noqa: BLE001
                    pass
                try:
                    await self._maybe_auto_split()
                except Exception:   # noqa: BLE001 — the splitter must
                    # never kill the maintenance loop; a failed split
                    # retries when the report crosses the threshold
                    # again
                    pass
            # reports accrete per leader heartbeat (on EVERY master —
            # tservers heartbeat them all); drop entries whose tablet
            # was dropped/split/hidden meanwhile so the dict (and
            # metrics_snapshot) tracks LIVE tablets only
            for tid in list(self._tablet_reports):
                ent = self.tablets.get(tid)
                if ent is None or ent.get("hidden"):
                    self._tablet_reports.pop(tid, None)
            await asyncio.sleep(1.0)

    async def _maybe_auto_split(self) -> Optional[str]:
        """Tablet auto-splitting on size/traffic thresholds (reference:
        the tablet-split manager behind enable_automatic_tablet_
        splitting + tablet_split_low_phase_*): at most ONE split per
        maintenance tick, chosen from leader heartbeat reports — size
        crossing `tablet_split_size_threshold_bytes`, or sustained
        write rate crossing `tablet_split_traffic_threshold_ops_s`
        (EWMA over heartbeat wal_index deltas).  Runs THROUGH
        rpc_split_tablet, i.e. the same Raft-replicated online split +
        replica barrier the manual path uses — under live load, not in
        a quiesced window."""
        if not flags.get("enable_automatic_tablet_splitting"):
            return None
        if self._split_throttled():
            return None
        size_thresh = flags.get("tablet_split_size_threshold_bytes")
        rate_thresh = flags.get("tablet_split_traffic_threshold_ops_s")
        max_tablets = flags.get("tablet_split_max_tablets_per_table")
        for tablet_id, ent in list(self.tablets.items()):
            if ent.get("hidden") or tablet_id in self._splitting:
                continue
            table = self.tables.get(ent.get("table_id"))
            if table is None or \
                    len(table.get("tablets", [])) >= max_tablets:
                continue
            rep = self._tablet_reports.get(tablet_id)
            if rep is None:
                continue
            oversized = rep.get("size_bytes", 0) >= size_thresh
            hot = rate_thresh > 0 and rep.get("ops_s", 0.0) >= rate_thresh
            if not (oversized or hot):
                continue
            self._splitting.add(tablet_id)
            try:
                r = await self.rpc_split_tablet({"tablet_id": tablet_id})
            finally:
                self._splitting.discard(tablet_id)
                self._tablet_reports.pop(tablet_id, None)
            return (f"auto-split {tablet_id} -> {r['left']},{r['right']} "
                    f"({'size' if oversized else 'traffic'})")
        return None

    def _split_throttled(self) -> bool:
        """Drain-aware split throttling (the outstanding_tablet_split_
        limit behavior): auto-splitting pauses while a blacklist drain
        still has replicas to move — every split mid-drain hands the
        rebalancer two fresh children to chase, so the drain never
        converges (measured in the PR-10 cluster harness) — and while
        the in-flight split count sits at the limit.  Manual
        rpc_split_tablet stays available either way."""
        limit = flags.get("outstanding_tablet_split_limit")
        if limit <= 0:
            return False
        if len(self._splitting) >= limit:
            return True
        bl = self.load_balancer.blacklist
        if not bl:
            return False
        for ent in self.tablets.values():
            if ent.get("hidden"):
                continue
            if any(u in bl for u in ent.get("replicas", ())):
                return True             # drain still in flight
        return False

    # --- balancing / placement RPCs ----------------------------------------
    async def rpc_move_replica(self, payload) -> dict:
        ok = await self.load_balancer.move_replica(
            payload["tablet_id"], payload["from"], payload["to"])
        if not ok:
            raise RpcError("move failed", "RUNTIME_ERROR")
        return {"ok": True}

    async def rpc_balance_tick(self, payload) -> dict:
        action = await self.load_balancer.tick()
        return {"action": action}

    async def rpc_blacklist(self, payload) -> dict:
        """Decommission draining (reference: blacklist handling in
        cluster_balance.cc)."""
        self.load_balancer.blacklist.add(payload["ts_uuid"])
        return {"ok": True}

    # --- cross-process control endpoint (cluster/ harness) -----------------
    async def rpc_arm_fault(self, payload) -> dict:
        """Arm fault-injection state in THIS master process (same
        contract as the tserver endpoint — the chaos controller arms
        whichever process it targets)."""
        from ..utils import fault_injection as fi
        return {"status": fi.arm_from_spec(payload or {})}

    async def rpc_fault_status(self, payload) -> dict:
        from ..utils import fault_injection as fi
        return {"status": fi.fault_status()}

    async def rpc_set_flag(self, payload) -> dict:
        """Hot-update a runtime flag on THIS master (mirrors the
        tserver RPC — the supervisor flips control-plane flags like
        enable_automatic_tablet_splitting cross-process with it)."""
        name = payload["name"]
        # unknown flag -> KeyError -> RPC error surface
        old, value = flags.coerce_and_set(name, payload["value"])
        return {"name": name, "old": old, "value": value}

    async def rpc_tracez(self, payload) -> dict:
        """Sampled span dump + ASH histograms for the master process
        (same contract as the tserver's rpc_tracez; CLUSTER.md)."""
        from ..utils import trace as _trace
        out = _trace.TRACES.tracez()
        out["uuid"] = self.uuid
        return out

    async def rpc_metrics_snapshot(self, payload) -> dict:
        from ..utils import fault_injection as fi
        from ..utils import metrics as _metrics
        return {
            "uuid": self.uuid,
            **_metrics.snapshot(),
            "faults": fi.fault_status(),
            "balancer": {"moves_done": self.load_balancer.moves_done,
                         "leader_moves_done":
                             self.load_balancer.leader_moves_done},
            "tablet_reports": {
                tid: {"size_bytes": r.get("size_bytes", 0),
                      "ops_s": round(r.get("ops_s", 0.0), 1)}
                for tid, r in self._tablet_reports.items()},
        }

    async def shutdown(self):
        self._running = False
        await cancel_and_drain(self._lb_task)
        self._lb_task = None
        for ent in self._xcluster_tasks.values():
            await ent.stop()
        self._xcluster_tasks.clear()
        await self.messenger.shutdown()

    # --- web UI path handlers (reference: master-path-handlers.cc) --------
    def web_handlers(self) -> Dict[str, object]:
        """Handlers for StatusWebServer: cluster state as JSON —
        /tables, /tablet-servers, /tablets, /xcluster-safe-time."""
        def tables():
            out = []
            for tid, e in self.tables.items():
                info = e["info"]
                out.append({
                    "table_id": tid, "name": info["name"],
                    "tablets": len(e.get("tablets", [])),
                    "schema_version": info["schema"]["version"],
                    "colocated": bool(e.get("colocated_in")
                                      or e.get("tablegroup")),
                    "indexes": list(e.get("indexes", {})),
                    "snapshots": len(e.get("snapshots", {})),
                    "cdc_streams": len(e.get("cdc_streams", {})),
                })
            return json.dumps(out, indent=1), "application/json"

        def tablet_servers():
            now = time.monotonic()
            out = []
            for u, ts in self.tservers.items():
                out.append({
                    "ts_uuid": u, "addr": list(ts["addr"]),
                    "zone": ts.get("zone"),
                    "alive": now - ts["last_hb"] < TS_LIVENESS_S,
                    "tablets": len(ts.get("tablets", [])),
                    "leaders": sum(1 for t in ts.get("tablets", [])
                                   if t.get("is_leader")),
                })
            return json.dumps(out, indent=1), "application/json"

        def tablets():
            out = []
            for tablet_id, ent in self.tablets.items():
                out.append({
                    "tablet_id": tablet_id, "table_id": ent.get("table_id"),
                    "partition": ent.get("partition"),
                    "leader": ent.get("leader"),
                    "replicas": ent.get("replicas", []),
                })
            return json.dumps(out, indent=1, default=str), "application/json"

        def xcluster():
            return json.dumps(self._xcluster_safe_time,
                              indent=1), "application/json"

        return {"/tables": tables, "/tablet-servers": tablet_servers,
                "/tablets": tablets, "/xcluster-safe-time": xcluster}

    # --- TS registry ------------------------------------------------------
    async def rpc_ts_heartbeat(self, payload) -> dict:
        uuid = payload["ts_uuid"]
        now = time.monotonic()
        self.tservers[uuid] = {
            "addr": tuple(payload["addr"]),
            "last_hb": now,
            "tablets": payload.get("tablets", []),
            "zone": payload.get("zone", "zone-default"),
        }
        if payload.get("device_chips"):
            # a server that owns several chips says so: the client may
            # send it one read for all of a table's tablets it leads
            self.tservers[uuid]["device_chips"] = payload["device_chips"]
        # track leadership reports for client routing; differentiate
        # the LEADER's wal_index across heartbeats into a per-tablet
        # write rate (EWMA — one noisy heartbeat gap must not fake a
        # traffic spike) for the auto-split traffic trigger
        for t in payload.get("tablets", []):
            ent = self.tablets.get(t["tablet_id"])
            if ent is not None and t["is_leader"]:
                ent["leader"] = uuid
                if ent.get("hidden"):
                    # CDC-retained split parent: routed but never a
                    # split candidate — don't re-accrete its report
                    continue
                rep = self._tablet_reports.get(t["tablet_id"])
                ops_s = 0.0
                wi = t.get("wal_index")
                if rep is not None and wi is not None and \
                        rep.get("wal_index") is not None:
                    dt = max(now - rep["at"], 1e-3)
                    inst = max(0, wi - rep["wal_index"]) / dt
                    ops_s = 0.5 * rep.get("ops_s", 0.0) + 0.5 * inst
                self._tablet_reports[t["tablet_id"]] = {
                    "size_bytes": t.get("size_bytes", 0),
                    "wal_index": wi, "at": now, "ops_s": ops_s}
        return {"ok": True, "leader_master": True}

    def live_tservers(self) -> List[str]:
        now = time.monotonic()
        return [u for u, d in self.tservers.items()
                if now - d["last_hb"] < TS_LIVENESS_S]

    async def rpc_list_tservers(self, payload) -> dict:
        return {"tservers": {
            u: {"addr": list(d["addr"]),
                "live": u in self.live_tservers(),
                "num_tablets": len(d.get("tablets", []))}
            for u, d in self.tservers.items()}}

    # --- DDL --------------------------------------------------------------
    async def rpc_create_table(self, payload) -> dict:
        """CreateTable: compute partitions, pick replica sets, create
        tablets on tservers, commit to the catalog (reference:
        catalog_manager.cc:4444)."""
        self._check_leader()
        name = payload["name"]
        if any(t["info"]["name"] == name for t in self.tables.values()):
            raise RpcError(f"table {name} exists", "ALREADY_PRESENT")
        if name in self.matviews:
            # symmetric with rpc_create_matview: a table would shadow
            # the matview in name resolution, making it unreachable
            raise RpcError(f"{name} is a materialized view",
                           "ALREADY_PRESENT")
        num_tablets = payload.get("num_tablets", 2)
        rf = payload.get("replication_factor", 1)
        live = self.live_tservers()
        if len(live) < rf:
            raise RpcError(
                f"need {rf} live tservers, have {len(live)}",
                "SERVICE_UNAVAILABLE")
        table_id = payload.get("table_id") or f"tbl-{uuidlib.uuid4().hex[:12]}"
        info_wire = dict(payload["table"])
        info_wire["table_id"] = table_id
        tspace = payload.get("tablespace_name")
        if tspace and tspace not in self.tablespaces:
            raise RpcError(f"tablespace {tspace} not found", "NOT_FOUND")
        if payload.get("tablegroup"):
            if tspace:
                # a colocated table lives in its tablegroup's tablet —
                # per-table placement cannot apply there (reference: PG
                # rejects TABLESPACE on colocated relations too)
                raise RpcError(
                    "tablespace cannot be combined with a tablegroup",
                    "INVALID_ARGUMENT")
            return await self._create_colocated(payload, table_id, info_wire)
        info = TableInfo.from_wire(info_wire)
        split_points = [bytes.fromhex(h)
                        for h in payload.get("split_points") or []]
        parts = info.partition_schema.create_partitions(
            num_tablets, split_points=split_points or None)
        policy = (self.tablespaces.get(tspace) if tspace
                  else self.tablespaces.get("cluster")) or {}
        tablet_entries = {}
        for i, p in enumerate(parts):
            tablet_id = f"{table_id}-t{i}"
            replicas = self._choose_replicas(
                live, rf, i, placement=policy.get("placement"))
            tablet_entries[tablet_id] = {
                "tablet_id": tablet_id, "table_id": table_id,
                "partition": [p.start.hex(), p.end.hex()],
                "replicas": replicas, "leader": None,
            }
        # create replicas on tservers — shielded from the orphan sweep
        # until the catalog commit below records them (a many-tablet
        # create on slow tservers can outlast any grace window)
        is_status = payload.get("is_status_tablet", False)
        shield = {(u, tid_) for tid_, ent in tablet_entries.items()
                  for u in ent["replicas"]}
        self._gc_inflight |= shield
        try:
            for tablet_id, ent in tablet_entries.items():
                raft_peers = [[u, list(self.tservers[u]["addr"])]
                              for u in ent["replicas"]]
                for u in ent["replicas"]:
                    await self.messenger.call(
                        self.tservers[u]["addr"], "tserver",
                        "create_tablet",
                        {"tablet_id": tablet_id, "table": info_wire,
                         "partition": ent["partition"],
                         "raft_peers": raft_peers,
                         "is_status_tablet": is_status},
                        timeout=10.0)
            tent = {"info": info_wire, "tablets": list(tablet_entries)}
            if tspace:
                tent["tablespace"] = tspace
            if payload.get("foreign_keys"):
                # [{column, parent_table, parent_column}] — enforced by
                # the SQL layer as an existence check in the writing
                # txn (reference: FK enforcement through the PG
                # executor over YB indexes)
                tent["foreign_keys"] = payload["foreign_keys"]
            if payload.get("checks"):
                # CHECK constraint ASTs (wire list form) — evaluated
                # per written row by the SQL layer
                tent["checks"] = payload["checks"]
            ops = [["put_table", table_id, tent]]
            ops += [["put_tablet", tid_, ent]
                    for tid_, ent in tablet_entries.items()]
            await self._commit_catalog(ops)
        finally:
            self._gc_inflight -= shield
        return {"table_id": table_id, "tablets": list(tablet_entries)}

    async def _create_colocated(self, payload, table_id, info_wire) -> dict:
        gid, gent = self._find_tablegroup(payload["tablegroup"])
        if gid is None:
            raise RpcError(f"tablegroup {payload['tablegroup']} not found",
                           "NOT_FOUND")
        cotable = gent.get("next_cotable", 1)
        info_wire["cotable_id"] = cotable
        tablet_id = gent["tablets"][0]
        tent = self.tablets[tablet_id]
        for u in tent["replicas"]:
            ts = self.tservers.get(u)
            if ts:
                await self.messenger.call(
                    ts["addr"], "tserver", "add_table",
                    {"tablet_id": tablet_id, "table": info_wire},
                    timeout=30.0)
        new_gent = dict(gent)
        new_gent["next_cotable"] = cotable + 1
        ops = [["put_table", gid, new_gent],
               ["put_table", table_id,
                {"info": info_wire, "tablets": [tablet_id],
                 "colocated_in": gid}]]
        await self._commit_catalog(ops)
        return {"table_id": table_id, "tablets": [tablet_id]}

    def _choose_replicas(self, live: List[str], rf: int, salt: int,
                         placement: Optional[list] = None) -> List[str]:
        """Zone-spreading, least-loaded placement (reference: placement
        policy handling in cluster_balance.cc/catalog_manager): satisfy
        the policy's per-zone minimums first, then pick one replica per
        zone round-robin before doubling up."""
        chosen: List[str] = []
        used_zones: Dict[str, int] = {}
        candidates = sorted(
            live, key=lambda u: (len(self.tservers[u].get("tablets", [])),
                                 hash((u, salt)) & 0xFFFF))

        def take(best):
            chosen.append(best)
            z = self.tservers[best].get("zone", "z")
            used_zones[z] = used_zones.get(z, 0) + 1
            candidates.remove(best)

        for block in placement or ():
            zone, need = block.get("zone"), block.get("min_replicas", 1)
            for _ in range(need):
                if len(chosen) >= rf:
                    break
                in_zone = [u for u in candidates
                           if self.tservers[u].get("zone") == zone]
                if not in_zone:
                    break        # zone unavailable: best-effort remainder
                take(min(in_zone, key=lambda u: (
                    len(self.tservers[u].get("tablets", [])),
                    hash((u, salt)) & 0xFFFF)))
        while len(chosen) < rf and candidates:
            take(min(candidates, key=lambda u: (
                used_zones.get(self.tservers[u].get("zone", "z"), 0),
                len(self.tservers[u].get("tablets", [])),
                hash((u, salt)) & 0xFFFF)))
        return chosen

    def placement_of(self, table_id: str) -> Optional[dict]:
        """Effective placement policy for a table: its tablespace if
        set, else the universe default ('cluster'), else None."""
        ent = self.tables.get(table_id)
        name = (ent or {}).get("tablespace")
        pol = self.tablespaces.get(name) if name else None
        return pol or self.tablespaces.get("cluster")

    # --- tablespaces / geo-placement (reference:
    # master/ysql_tablespace_manager.cc, set_preferred_zones) ------------
    async def rpc_create_tablespace(self, payload) -> dict:
        self._check_leader()
        name = payload["name"]
        if name in self.tablespaces and not payload.get("or_replace"):
            raise RpcError(f"tablespace {name} exists", "ALREADY_PRESENT")
        pol = {"placement": list(payload.get("placement") or []),
               "preferred_zones": list(payload.get("preferred_zones")
                                       or [])}
        await self._commit_catalog([["put_tablespace", name, pol]])
        return {"name": name}

    async def rpc_drop_tablespace(self, payload) -> dict:
        self._check_leader()
        name = payload["name"]
        if name not in self.tablespaces:
            raise RpcError(f"tablespace {name} not found", "NOT_FOUND")
        used = [e["info"]["name"] for e in self.tables.values()
                if e.get("tablespace") == name]
        if used:
            raise RpcError(f"tablespace {name} in use by {used}",
                           "INVALID_ARGUMENT")
        await self._commit_catalog([["del_tablespace", name]])
        return {"ok": True}

    async def rpc_list_tablespaces(self, payload) -> dict:
        return {"tablespaces": dict(self.tablespaces)}

    async def rpc_set_placement_info(self, payload) -> dict:
        """Universe-wide placement + preferred zones (the reserved
        'cluster' tablespace)."""
        self._check_leader()
        pol = {"placement": list(payload.get("placement") or []),
               "preferred_zones": list(payload.get("preferred_zones")
                                       or [])}
        await self._commit_catalog([["put_tablespace", "cluster", pol]])
        return {"ok": True}

    async def rpc_alter_table(self, payload) -> dict:
        """ADD COLUMN: bump the schema version, replicate the new schema
        to every tablet via their Raft groups, commit to the catalog
        (reference: AlterTable in catalog_manager + ChangeMetadata ops;
        old packed rows keep decoding via retained packings)."""
        self._check_leader()
        name = payload["table"]
        tid = next((t for t, e in self.tables.items()
                    if e["info"]["name"] == name), None)
        if tid is None:
            raise RpcError(f"table {name} not found", "NOT_FOUND")
        ent = self.tables[tid]
        info = TableInfo.from_wire(ent["info"])
        cols = list(info.schema.columns)
        # ids are never reused, even after DROP COLUMN: a recycled id
        # would make old packed rows' values resurface under the new
        # column (reference: ColumnId allocation in catalog_entity_info)
        next_id = 1 + max(
            (c.id for sch in (tuple(info.schema_history) + (info.schema,))
             for c in sch.columns), default=0)
        from ..dockv.packed_row import ColumnSchema as _CS
        for entry in payload.get("add_columns", []):
            cname, ctype = entry[0], entry[1]
            ql = entry[2] if len(entry) > 2 else None
            if any(c.name == cname for c in cols):
                raise RpcError(f"column {cname} exists", "ALREADY_PRESENT")
            cols.append(_CS(next_id, cname, ctype, ql_type=ql))
            next_id += 1
        indexed = set()
        for spec in ent.get("indexes", {}).values():
            indexed.update(spec.get("columns") or [spec.get("column")])

        def _check_cols(node, out):
            if not isinstance(node, (list, tuple)) or not node:
                return
            if node[0] == "col" and isinstance(node[1], str):
                out.add(node[1].split(".", 1)[-1])
                return
            for c in node[1:]:
                _check_cols(c, out)
        check_refs: set = set()
        for chk in ent.get("checks", []):
            _check_cols(chk, check_refs)
        for cname in payload.get("drop_columns", []):
            target = next((c for c in cols if c.name == cname), None)
            if target is None:
                raise RpcError(f"column {cname} not found", "NOT_FOUND")
            if target.is_hash_key or target.is_range_key:
                raise RpcError(f"cannot drop key column {cname}",
                               "INVALID_ARGUMENT")
            if cname in indexed:
                raise RpcError(
                    f"cannot drop column {cname}: a secondary index "
                    f"depends on it (drop the index first)",
                    "INVALID_ARGUMENT")
            if cname in check_refs:
                # a stale CHECK AST would resolve the dropped column to
                # NULL and silently pass every row (PG rejects the DROP
                # without CASCADE)
                raise RpcError(
                    f"cannot drop column {cname}: a CHECK constraint "
                    f"depends on it", "INVALID_ARGUMENT")
            cols.remove(target)
        new_schema = TableSchema(columns=tuple(cols),
                                 version=info.schema.version + 1)
        new_info = TableInfo(tid, name, new_schema, info.partition_schema,
                             cotable_id=info.cotable_id,
                             schema_history=info.schema_history
                             + (info.schema,))
        new_wire = new_info.to_wire()
        for tablet_id in ent["tablets"]:
            tent = self.tablets.get(tablet_id)
            if tent is None:
                continue
            last = None
            for u in ([tent.get("leader")] if tent.get("leader") else [])                     + list(tent["replicas"]):
                ts = self.tservers.get(u)
                if not ts:
                    continue
                try:
                    await self.messenger.call(
                        ts["addr"], "tserver", "alter_table",
                        {"tablet_id": tablet_id, "table": new_wire},
                        timeout=30.0)
                    last = None
                    break
                except (RpcError, asyncio.TimeoutError, OSError) as e:
                    last = e
                    continue
            if last is not None:
                raise RpcError(f"alter failed on {tablet_id}: {last}",
                               "RUNTIME_ERROR")
        new_ent = dict(ent)
        new_ent["info"] = new_wire
        await self._commit_catalog([["put_table", tid, new_ent]])
        return {"schema_version": new_schema.version}

    async def rpc_drop_table(self, payload) -> dict:
        self._check_leader()
        name = payload["name"]
        tid = next((t for t, e in self.tables.items()
                    if e["info"]["name"] == name), None)
        if tid is None:
            raise RpcError(f"table {name} not found", "NOT_FOUND")
        if self.tables[tid].get("colocated_in"):
            # colocated table: the tablet is SHARED with other tables —
            # drop only the catalog entry (cotable-range GC is a round-2
            # compaction job; reference deletes the cotable key range)
            await self._commit_catalog([["del_table", tid]])
            return {"ok": True}
        for tablet_id in self.tables[tid]["tablets"]:
            ent = self.tablets.get(tablet_id)
            if not ent:
                continue
            for u in ent["replicas"]:
                ts = self.tservers.get(u)
                if ts:
                    try:
                        await self.messenger.call(
                            ts["addr"], "tserver", "delete_tablet",
                            {"tablet_id": tablet_id}, timeout=5.0)
                    except (RpcError, asyncio.TimeoutError, OSError):
                        pass
        await self._commit_catalog(
            [["del_table", tid]]
            + [["del_tablet", t] for t in self.tables[tid]["tablets"]])
        return {"ok": True}

    async def rpc_add_table_constraint(self, payload) -> dict:
        """ALTER TABLE ADD CONSTRAINT: append an FK or CHECK to the
        catalog entry (the executor validates existing rows first;
        UNIQUE goes through index creation instead — reference:
        AddForeignKey/AddCheck through catalog_manager AlterTable)."""
        self._check_leader()
        name = payload["table"]
        tid = next((t for t, e in self.tables.items()
                    if e["info"]["name"] == name), None)
        if tid is None:
            raise RpcError(f"table {name} not found", "NOT_FOUND")
        tent = dict(self.tables[tid])
        if payload.get("foreign_key"):
            fks = list(tent.get("foreign_keys", []))
            fks.append(dict(payload["foreign_key"]))
            tent["foreign_keys"] = fks
        if payload.get("check") is not None:
            cks = list(tent.get("checks", []))
            cks.append(payload["check"])
            tent["checks"] = cks
        await self._commit_catalog([["put_table", tid, tent]])
        return {"ok": True}

    async def rpc_drop_table_constraint(self, payload) -> dict:
        """ALTER TABLE DROP CONSTRAINT for FOREIGN KEYs: remove by the
        stored or synthesized ({table}_{column}_fkey) name."""
        self._check_leader()
        name = payload["table"]
        cname = payload["constraint_name"]
        tid = next((t for t, e in self.tables.items()
                    if e["info"]["name"] == name), None)
        if tid is None:
            raise RpcError(f"table {name} not found", "NOT_FOUND")
        tent = dict(self.tables[tid])
        fks = list(tent.get("foreign_keys", []))
        keep = [fk for fk in fks
                if (fk.get("name")
                    or f"{name}_{fk['column']}_fkey") != cname]
        if len(keep) == len(fks):
            raise RpcError(f"constraint {cname} not found",
                           "NOT_FOUND")
        tent["foreign_keys"] = keep
        await self._commit_catalog([["put_table", tid, tent]])
        return {"ok": True}

    # --- lookups ----------------------------------------------------------
    async def rpc_get_tablet_locations(self, payload) -> dict:
        """Tablet-id existence + current replica addresses (the txn
        coordinator arbitrates dead-vs-moved participants with this;
        reference: GetTabletLocations in master_client.proto)."""
        self._check_leader()
        ent = self.tablets.get(payload["tablet_id"])
        if ent is None:
            raise RpcError(f"tablet {payload['tablet_id']} not found",
                           "NOT_FOUND")
        return {"replicas": [list(self.tservers[u]["addr"])
                             for u in ent["replicas"]
                             if u in self.tservers]}

    async def rpc_get_table(self, payload) -> dict:
        self._check_leader()
        name = payload.get("name")
        table_id = payload.get("table_id")
        for tid, e in self.tables.items():
            if tid == table_id or e["info"]["name"] == name:
                return {"table": e["info"],
                        "locations": self._locations(tid),
                        "indexes": e.get("indexes", {}),
                        "foreign_keys": e.get("foreign_keys", []),
                        "checks": e.get("checks", [])}
        raise RpcError(f"table {name or table_id} not found", "NOT_FOUND")

    def _locations(self, table_id: str) -> List[dict]:
        out = []
        for tablet_id in self.tables[table_id]["tablets"]:
            ent = self.tablets[tablet_id]
            out.append({
                "tablet_id": tablet_id,
                "partition": ent["partition"],
                "replicas": [
                    {"ts_uuid": u,
                     "addr": list(self.tservers[u]["addr"])
                     if u in self.tservers else None,
                     **({"chips": self.tservers[u]["device_chips"]}
                        if self.tservers.get(u, {}).get("device_chips")
                        else {})}
                    for u in ent["replicas"]],
                "leader": ent.get("leader"),
            })
        return out

    # --- snapshots / PITR (reference: master/master_snapshot_coordinator.cc)
    async def rpc_create_snapshot(self, payload) -> dict:
        self._check_leader()
        """Cluster-consistent table snapshot: checkpoint every tablet
        (hybrid-time consistency comes from checkpoints capturing a flushed
        image; cross-tablet cut at one HT lands with distributed txn
        integration in a later round)."""
        import uuid as _uuid
        name = payload["table"]
        tid = next((t for t, e in self.tables.items()
                    if e["info"]["name"] == name), None)
        if tid is None:
            raise RpcError(f"table {name} not found", "NOT_FOUND")
        snapshot_id = f"snap-{_uuid.uuid4().hex[:12]}"
        # single-HT cut: every tablet checkpoints AT this hybrid time —
        # tservers merge it into their HLC, wait until all in-flight
        # writes below it are applied, and restore trims anything above
        # it (reference: SysSnapshotEntryPB snapshot_hybrid_time)
        from ..utils.hybrid_time import HybridTime
        snapshot_ht = HybridTime.from_micros(time.time_ns() // 1000).value
        # the cut must dominate every write acked before this request:
        # sample the HLC of every tserver hosting this table and take
        # the max (clock skew / merged-ahead HLCs otherwise leave acked
        # writes above the cut, and restore would trim them)
        hosts = {u for tablet_id in self.tables[tid]["tablets"]
                 for u in self.tablets[tablet_id]["replicas"]}
        for u in hosts:
            ts = self.tservers.get(u)
            if not ts:
                continue
            try:
                r = await self.messenger.call(
                    ts["addr"], "tserver", "server_clock", {}, timeout=5.0)
                snapshot_ht = max(snapshot_ht, r["ht"])
            except (RpcError, asyncio.TimeoutError, OSError):
                pass
        manifest = []
        for tablet_id in self.tables[tid]["tablets"]:
            ent = self.tablets[tablet_id]
            done = False
            for u in ent["replicas"]:
                ts = self.tservers.get(u)
                if not ts:
                    continue
                try:
                    r = await self.messenger.call(
                        ts["addr"], "tserver", "create_snapshot",
                        {"tablet_id": tablet_id,
                         "snapshot_id": snapshot_id,
                         "snapshot_ht": snapshot_ht}, timeout=30.0)
                    manifest.append({"tablet_id": tablet_id, "ts_uuid": u,
                                     "dir": r["dir"],
                                     "partition": ent["partition"]})
                    done = True
                    break
                except RpcError as ex:
                    if ex.code not in ("LEADER_NOT_READY", "NOT_FOUND"):
                        raise      # real failure (e.g. drain TIMED_OUT):
                                   # followers can never succeed anyway
                    continue
                except (asyncio.TimeoutError, OSError):
                    continue
            if not done:
                raise RpcError(f"no leader for {tablet_id}",
                               "SERVICE_UNAVAILABLE")
        ent = dict(self.tables[tid])
        snaps = dict(ent.get("snapshots", {}))
        snaps[snapshot_id] = {"manifest": manifest,
                              "snapshot_ht": snapshot_ht}
        ent["snapshots"] = snaps
        await self._commit_catalog([["put_table", tid, ent]])
        return {"snapshot_id": snapshot_id,
                "tablets": len(manifest)}

    async def rpc_delete_snapshot(self, payload) -> dict:
        """Delete a snapshot: drop tserver checkpoint dirs (best effort,
        tserver delete is idempotent) and remove the catalog entry
        (reference: MasterSnapshotCoordinator::Delete)."""
        self._check_leader()
        snapshot_id = payload["snapshot_id"]
        for tid, e in self.tables.items():
            snap = e.get("snapshots", {}).get(snapshot_id)
            if snap is None:
                continue
            for ent in snap.get("manifest", []):
                ts = self.tservers.get(ent["ts_uuid"])
                if not ts:
                    continue
                try:
                    await self.messenger.call(
                        ts["addr"], "tserver", "delete_snapshot",
                        {"tablet_id": ent["tablet_id"],
                         "snapshot_id": snapshot_id}, timeout=30.0)
                except (RpcError, asyncio.TimeoutError, OSError):
                    pass
            tent = dict(self.tables[tid])
            snaps = dict(tent.get("snapshots", {}))
            snaps.pop(snapshot_id, None)
            tent["snapshots"] = snaps
            await self._commit_catalog([["put_table", tid, tent]])
            return {"ok": True}
        raise RpcError(f"snapshot {snapshot_id} not found", "NOT_FOUND")

    async def rpc_create_snapshot_schedule(self, payload) -> dict:
        """Periodic snapshots with retention (reference:
        SnapshotScheduleState in master_snapshot_coordinator.cc). The
        master loop ticks schedules; restore_snapshot_schedule picks the
        newest snapshot at-or-before a target time (PITR-style)."""
        self._check_leader()
        name = payload["table"]
        tid = next((t for t, e in self.tables.items()
                    if e["info"]["name"] == name), None)
        if tid is None:
            raise RpcError(f"table {name} not found", "NOT_FOUND")
        sched_id = f"sched-{uuidlib.uuid4().hex[:10]}"
        ent = dict(self.tables[tid])
        scheds = dict(ent.get("snapshot_schedules", {}))
        scheds[sched_id] = {
            "interval_s": payload.get("interval_s", 60.0),
            "keep": max(1, int(payload.get("keep", 5))),
            "last_run": 0.0, "snapshots": []}
        ent["snapshot_schedules"] = scheds
        await self._commit_catalog([["put_table", tid, ent]])
        return {"schedule_id": sched_id}

    async def tick_snapshot_schedules(self) -> int:
        """Run due schedules (called from the maintenance loop or tests).
        Returns snapshots taken."""
        if not self.is_leader():
            return 0
        taken = 0
        for tid, e in list(self.tables.items()):
            for sid in list(e.get("snapshot_schedules", {})):
                sc = e["snapshot_schedules"].get(sid, {})
                if time.time() - sc.get("last_run", 0) < sc["interval_s"]:
                    continue
                try:
                    r = await self.rpc_create_snapshot(
                        {"table": e["info"]["name"]})
                except (RpcError, asyncio.TimeoutError, OSError):
                    continue
                # re-fetch AFTER the await: concurrent RPCs (schedule
                # create/delete, other ticks) may have replaced the
                # catalog entry — merge into fresh state, touching only
                # this schedule.
                ent = dict(self.tables.get(tid) or {})
                scheds = dict(ent.get("snapshot_schedules", {}))
                cur = scheds.get(sid)
                if not ent or cur is None:       # dropped concurrently
                    continue
                cur = dict(cur)
                snaps = list(cur.get("snapshots", []))
                snaps.append({"snapshot_id": r["snapshot_id"],
                              "at": time.time()})
                # retention: keep the newest N, delete the rest for real
                cur["snapshots"] = snaps[-cur["keep"]:]
                cur["last_run"] = time.time()
                scheds[sid] = cur
                ent["snapshot_schedules"] = scheds
                await self._commit_catalog([["put_table", tid, ent]])
                taken += 1
                for old in snaps[:-cur["keep"]]:
                    try:
                        await self.rpc_delete_snapshot(
                            {"snapshot_id": old["snapshot_id"]})
                    except (RpcError, asyncio.TimeoutError, OSError):
                        pass
        return taken

    async def rpc_list_snapshot_schedules(self, payload) -> dict:
        """List schedules (optionally for one table) with their retained
        snapshots (reference: yb-admin list_snapshot_schedules)."""
        self._check_leader()
        name = payload.get("table")
        out = {}
        for tid, e in self.tables.items():
            if name and e["info"]["name"] != name:
                continue
            for sid, sc in e.get("snapshot_schedules", {}).items():
                out[sid] = {"table": e["info"]["name"],
                            "interval_s": sc["interval_s"],
                            "keep": sc["keep"],
                            "snapshots": sc.get("snapshots", [])}
        return {"schedules": out}

    async def rpc_restore_snapshot_schedule(self, payload) -> dict:
        """PITR-style: restore the newest scheduled snapshot taken at or
        before `at` (epoch seconds) as a new table."""
        self._check_leader()
        sched_id = payload["schedule_id"]
        at = payload.get("at", time.time())
        for tid, e in self.tables.items():
            sc = e.get("snapshot_schedules", {}).get(sched_id)
            if sc is None:
                continue
            candidates = [x for x in sc.get("snapshots", [])
                          if x["at"] <= at]
            if not candidates:
                raise RpcError("no snapshot at or before the target time",
                               "NOT_FOUND")
            best = max(candidates, key=lambda x: x["at"])
            return await self.rpc_restore_snapshot(
                {"snapshot_id": best["snapshot_id"],
                 "new_name": payload["new_name"]})
        raise RpcError(f"schedule {sched_id} not found", "NOT_FOUND")

    async def rpc_restore_snapshot(self, payload) -> dict:
        """Restore a snapshot as a NEW table (clone-from-snapshot flow)."""
        snapshot_id = payload["snapshot_id"]
        new_name = payload["new_name"]
        src = None
        for tid, e in self.tables.items():
            if snapshot_id in e.get("snapshots", {}):
                src = (tid, e)
                break
        if src is None:
            raise RpcError(f"snapshot {snapshot_id} not found", "NOT_FOUND")
        tid, e = src
        import uuid as _uuid
        new_tid = f"tbl-{_uuid.uuid4().hex[:12]}"
        info_wire = dict(e["info"])
        info_wire["table_id"] = new_tid
        info_wire["name"] = new_name
        manifest = e["snapshots"][snapshot_id]["manifest"]
        # shield the clone's tablets from the orphan sweep until the
        # catalog commit records them
        shield = {(m["ts_uuid"], f"{new_tid}-t{i}")
                  for i, m in enumerate(manifest)}
        self._gc_inflight |= shield
        tablet_entries = {}
        try:
            for i, m in enumerate(manifest):
                child = f"{new_tid}-t{i}"
                u = m["ts_uuid"]
                ts = self.tservers.get(u)
                if ts is None:
                    raise RpcError(
                        f"tserver {u} holding snapshot is gone",
                        "SERVICE_UNAVAILABLE")
                await self.messenger.call(
                    ts["addr"], "tserver", "create_tablet",
                    {"tablet_id": child, "table": info_wire,
                     "partition": m["partition"],
                     "raft_peers": [[u, list(ts["addr"])]],
                     "seed_snapshot_dir": m["dir"],
                     "trim_above_ht": e["snapshots"][snapshot_id].get(
                         "snapshot_ht")}, timeout=30.0)
                tablet_entries[child] = {
                    "tablet_id": child, "table_id": new_tid,
                    "partition": m["partition"], "replicas": [u],
                    "leader": None}
            ops = [["put_table", new_tid,
                    {"info": info_wire, "tablets": list(tablet_entries)}]]
            ops += [["put_tablet", t, e]
                    for t, e in tablet_entries.items()]
            await self._commit_catalog(ops)
        finally:
            self._gc_inflight -= shield
        return {"table_id": new_tid}

    # --- tablet splitting (reference: master/tablet_split_manager.cc) ------
    async def rpc_split_tablet(self, payload) -> dict:
        self._check_leader()
        tablet_id = payload["tablet_id"]
        ent = self.tablets.get(tablet_id)
        if ent is None:
            raise RpcError(f"tablet {tablet_id} not found", "NOT_FOUND")
        table_id = ent["table_id"]
        info_wire = self.tables[table_id]["info"]
        from ..dockv.partition import Partition, split_partition
        p = Partition(bytes.fromhex(ent["partition"][0]),
                      bytes.fromhex(ent["partition"][1]))
        lo, hi = split_partition(p)
        split_key = lo.end.hex()
        left_id, right_id = f"{tablet_id}l", f"{tablet_id}r"
        observers = set(ent.get("observers", []))
        raft_peers = [
            [u, list(self.tservers[u]["addr"])]
            + (["observer"] if u in observers else [])
            for u in ent["replicas"] if u in self.tservers]
        # idempotent retry: children already in the catalog = done
        if left_id in self.tablets and right_id in self.tablets:
            return {"left": left_id, "right": right_id}
        # Raft-replicated SplitOperation through the PARENT's log
        # (reference: tablet/operations/split_operation.cc): online —
        # no quiesce, no catch-up barrier; apply ordering guarantees
        # every replica's children see exactly the pre-split state
        await self.load_balancer._leader_call(
            ent, tablet_id, "split_tablet_raft",
            {"parent_id": tablet_id, "left_id": left_id,
             "right_id": right_id, "split_key": split_key,
             "partition": ent["partition"], "table": info_wire,
             "raft_peers": raft_peers})
        # barrier: wait until every reachable replica applied the split
        # (created its children) before deleting parents — a lagging
        # replica whose parent vanished early would never build them
        deadline = asyncio.get_event_loop().time() + 30.0
        pending = set(ent["replicas"])
        while pending and asyncio.get_event_loop().time() < deadline:
            for u in list(pending):
                ts = self.tservers.get(u)
                if ts is None:
                    pending.discard(u)
                    continue
                try:
                    st = await self.messenger.call(
                        ts["addr"], "tserver", "tablet_status",
                        {"tablet_id": tablet_id}, timeout=5.0)
                    # done = the PARENT finished its split apply (its
                    # split_done flag is written after the child copy
                    # completes) or is already gone
                    if not st.get("exists") or st.get("split_done"):
                        pending.discard(u)
                except (RpcError, asyncio.TimeoutError, OSError):
                    pass   # dead replica: times out of the barrier
            if pending:
                await asyncio.sleep(0.1)
        # a parent covered by a CDC replication slot is HIDDEN, not
        # deleted: its peers keep serving get_changes until every slot
        # has drained past its split marker (reference: CDC-retained
        # split parents — hidden tablets, master retains parents while
        # cdc_state still references them)
        tname = self.tables[table_id]["info"]["name"]

        def _cdc_retains() -> bool:
            # a slot whose state references the parent, or a just-
            # created slot that hasn't persisted its tablet set yet
            # (it may be about to adopt the parent; the GC sweep
            # collects it once the slot's state shows otherwise)
            return any(
                tname in s.get("tables", ())
                and (tablet_id in s.get("state", {}) or not s.get("state"))
                for s in self.replication_slots.values())
        # catalog commit comes FIRST: once the parent leaves the table's
        # tablet list, no new slot can discover it — only then is it
        # safe to destroy replicas
        cdc_retained = _cdc_retains()
        ops = []
        if cdc_retained:
            hid = dict(ent)
            hid["hidden"] = True
            ops.append(["put_tablet", tablet_id, hid])
        for child_id, part in ((left_id, [ent["partition"][0], split_key]),
                               (right_id, [split_key, ent["partition"][1]])):
            ops.append(["put_tablet", child_id, {
                "tablet_id": child_id, "table_id": table_id,
                "partition": part, "replicas": list(ent["replicas"]),
                "observers": sorted(observers),
                "leader": None}])
        if not cdc_retained:
            ops.append(["del_tablet", tablet_id])
        tent = dict(self.tables[table_id])
        tl = [t for t in tent["tablets"] if t != tablet_id]
        tent["tablets"] = tl + [left_id, right_id]
        ops.append(["put_table", table_id, tent])
        await self._commit_catalog(ops)
        if not cdc_retained:
            if _cdc_retains():
                # a slot adopted the parent while the split barrier /
                # catalog commit awaited: flip to hidden instead of
                # destroying the data it needs
                hid = dict(ent)
                hid["hidden"] = True
                await self._commit_catalog(
                    [["put_tablet", tablet_id, hid]])
            else:
                for u in ent["replicas"]:
                    ts = self.tservers.get(u)
                    if ts is None or u in pending:
                        continue  # never delete an unsplit parent
                    try:
                        await self.messenger.call(
                            ts["addr"], "tserver", "delete_tablet",
                            {"tablet_id": tablet_id}, timeout=30.0)
                    except (RpcError, asyncio.TimeoutError, OSError):
                        pass   # replica gone/lagging: already out of
                               # the catalog; disk copy orphaned until
                               # operator cleanup
        return {"left": left_id, "right": right_id}

    # --- CDC stream registry (reference: master cdcsdk_manager.cc,
    # cdc_state_table.cc for checkpoints) ----------------------------------
    async def rpc_setup_xcluster_replication(self, payload) -> dict:
        """Start pulling a table from another universe into THIS one
        (reference: SetupUniverseReplication in catalog_manager_ent /
        xcluster; ours runs the poller inside the target master's
        maintenance loop). Config is catalog-persisted; the leader
        (re)spawns the replicator task."""
        self._check_leader()
        table = payload["table"]
        src_addr = tuple(payload["source_master"])
        # validate up front: unreachable source or missing table must
        # fail the RPC, not retry silently forever
        try:
            r = await self.messenger.call(src_addr, "master",
                                          "list_tables", {}, timeout=10.0)
        except (RpcError, asyncio.TimeoutError, OSError) as e:
            raise RpcError(f"source master {src_addr} unreachable: {e}",
                           "SERVICE_UNAVAILABLE")
        if table not in {t["name"] for t in r["tables"]}:
            raise RpcError(f"table {table} not found on source universe",
                           "NOT_FOUND")
        cfg = {"source_master": list(payload["source_master"]),
               "table": table}
        await self._commit_catalog([["put_xcluster", table, cfg]])
        await self._ensure_xcluster_replicators()
        return {"ok": True}

    async def rpc_drop_xcluster_replication(self, payload) -> dict:
        self._check_leader()
        table = payload["table"]
        await self._commit_catalog([["del_xcluster", table]])
        ent = self._xcluster_tasks.pop(table, None)
        if ent is not None:
            await ent.stop()
        return {"ok": True}

    async def rpc_list_xcluster_replication(self, payload) -> dict:
        self._check_leader()
        return {"replication": dict(self.xcluster_replication),
                "running": sorted(self._xcluster_tasks),
                "safe_time": dict(self._xcluster_safe_time)}

    async def _ensure_xcluster_replicators(self) -> None:
        """Leader-only: reconcile running replicator tasks with the
        configured set (spawns after failover/restart too). Serialized:
        the setup RPC and the maintenance tick both call this, and two
        concurrent passes would double-start a poller."""
        async with self._xcluster_reconcile_lock:
            await self._reconcile_xcluster_locked()

    async def _reconcile_xcluster_locked(self) -> None:
        if not self.is_leader():
            for t, ent in list(self._xcluster_tasks.items()):
                await ent.stop()
                del self._xcluster_tasks[t]
            return
        from ..cdc import XClusterReplicator
        from ..client import YBClient
        for table, cfg in list(self.xcluster_replication.items()):
            if table in self._xcluster_tasks:
                continue
            src = YBClient(tuple(cfg["source_master"]),
                           messenger=self.messenger)
            dst = YBClient(self.messenger.addr, messenger=self.messenger)
            repl = XClusterReplicator(src, dst, table, poll_interval=0.2)
            try:
                await repl.start()
            except Exception:   # noqa: BLE001 — source may be down; retry
                continue        # on the next maintenance tick
            self._xcluster_tasks[table] = repl
        for table in list(self._xcluster_tasks):
            if table not in self.xcluster_replication:
                await self._xcluster_tasks.pop(table).stop()

    async def rpc_set_xcluster_safe_time(self, payload) -> dict:
        """Published by an inbound xCluster replicator: the HT up to
        which this table is fully replicated from its source universe
        (reference: xcluster_safe_time_service.cc). Kept in memory —
        it's a high-frequency watermark, re-published continuously, so
        losing it on failover only delays consistent reads briefly."""
        self._check_leader()
        self._xcluster_safe_time[payload["table"]] = max(
            self._xcluster_safe_time.get(payload["table"], 0),
            int(payload["safe_ht"]))
        return {"ok": True}

    async def rpc_get_xcluster_safe_time(self, payload) -> dict:
        """Safe read time for one table, or the min across all inbound
        xCluster tables when no table is given (cluster-consistent)."""
        self._check_leader()
        name = payload.get("table")
        if name is not None:
            return {"safe_ht": self._xcluster_safe_time.get(name, 0)}
        vals = self._xcluster_safe_time
        return {"safe_ht": min(vals.values()) if vals else 0,
                "tables": dict(vals)}

    async def rpc_create_cdc_stream(self, payload) -> dict:
        self._check_leader()
        name = payload["table"]
        tid = next((t for t, e in self.tables.items()
                    if e["info"]["name"] == name), None)
        if tid is None:
            raise RpcError(f"table {name} not found", "NOT_FOUND")
        stream_id = f"cdc-{uuidlib.uuid4().hex[:12]}"
        ent = dict(self.tables[tid])
        streams = dict(ent.get("cdc_streams", {}))
        streams[stream_id] = {"checkpoints": {}}
        ent["cdc_streams"] = streams
        await self._commit_catalog([["put_table", tid, ent]])
        return {"stream_id": stream_id}

    async def rpc_set_cdc_checkpoint(self, payload) -> dict:
        self._check_leader()
        for tid, e in self.tables.items():
            if payload["stream_id"] in e.get("cdc_streams", {}):
                ent = dict(e)
                streams = dict(ent["cdc_streams"])
                st = dict(streams[payload["stream_id"]])
                cps = dict(st.get("checkpoints", {}))
                cps[payload["tablet_id"]] = payload["index"]
                st["checkpoints"] = cps
                streams[payload["stream_id"]] = st
                ent["cdc_streams"] = streams
                await self._commit_catalog([["put_table", tid, ent]])
                return {"ok": True}
        raise RpcError("stream not found", "NOT_FOUND")

    async def rpc_get_cdc_stream(self, payload) -> dict:
        self._check_leader()
        for tid, e in self.tables.items():
            if payload["stream_id"] in e.get("cdc_streams", {}):
                return {"table": e["info"]["name"],
                        **e["cdc_streams"][payload["stream_id"]]}
        raise RpcError("stream not found", "NOT_FOUND")

    # --- replication slots (CDC-SDK consumer API; reference:
    # cdc_state_table.cc + the slot metadata the virtual WAL keeps in
    # cdcsdk_virtual_wal.cc / CreateReplicationSlot in yb_client) --------
    async def rpc_create_replication_slot(self, payload) -> dict:
        self._check_leader()
        name = payload.get("name") or f"slot-{uuidlib.uuid4().hex[:12]}"
        if name in self.replication_slots:
            raise RpcError(f"slot {name} already exists", "ALREADY_PRESENT")
        tables = list(payload["tables"])
        known = {e["info"]["name"] for e in self.tables.values()}
        missing = [t for t in tables if t not in known]
        if missing:
            raise RpcError(f"tables not found: {missing}", "NOT_FOUND")
        ent = {"tables": tables,
               "state": {},            # tablet_id -> per-tablet state
               "confirmed_lsn": None,  # [commit_ht, txn_key, seq]
               "start_from": payload.get("start_from", "earliest")}
        await self._commit_catalog([["put_repl_slot", name, ent]])
        return {"slot_id": name}

    async def rpc_get_replication_slot(self, payload) -> dict:
        self._check_leader()
        ent = self.replication_slots.get(payload["slot_id"])
        if ent is None:
            raise RpcError("slot not found", "NOT_FOUND")
        return {"slot_id": payload["slot_id"], **ent}

    async def rpc_update_replication_slot(self, payload) -> dict:
        """Persist the consumer's acknowledged position: per-tablet
        checkpoints (already held back below unconfirmed txns by the
        virtual WAL) + the confirmed LSN, atomically."""
        self._check_leader()
        sid = payload["slot_id"]
        if sid not in self.replication_slots:
            raise RpcError("slot not found", "NOT_FOUND")
        ent = dict(self.replication_slots[sid])
        ent["state"] = payload["state"]
        ent["confirmed_lsn"] = payload.get("confirmed_lsn")
        if "decisions" in payload:
            ent["decisions"] = payload["decisions"]
        await self._commit_catalog([["put_repl_slot", sid, ent]])
        return {"ok": True}

    async def rpc_drop_replication_slot(self, payload) -> dict:
        self._check_leader()
        if payload["slot_id"] not in self.replication_slots:
            raise RpcError("slot not found", "NOT_FOUND")
        await self._commit_catalog([["del_repl_slot", payload["slot_id"]]])
        return {"ok": True}

    async def _gc_hidden_tablets(self) -> None:
        """Delete CDC-retained split parents once every slot covering
        their table has drained past the split marker (marked them
        retired) or was dropped (reference: hidden-tablet cleanup in
        catalog manager once no CDC stream retains them). Runs from the
        maintenance loop — NOT inline in the consumer's confirm path,
        where an unreachable tserver would stall every ack."""
        for tid, ent in list(self.tablets.items()):
            if not ent.get("hidden"):
                continue
            tent = self.tables.get(ent["table_id"])
            tname = tent["info"]["name"] if tent else None

            def _slot_needs(s) -> bool:
                # only slots whose persisted state references this
                # parent can replay from it (slots created after the
                # split start at the children); such a slot is finished
                # with it once its restart position reaches the split
                # marker — `retired` alone still holds back below
                # unconfirmed txns a restarted consumer must re-read
                if tname not in s.get("tables", ()):
                    return False
                if not s.get("state"):
                    # just-created slot racing the split: its tablet set
                    # (possibly including this parent) isn't persisted
                    # yet — keep the parent, matching the retention
                    # predicate in rpc_split_tablet
                    return True
                st = s["state"].get(tid)
                if st is None:
                    return False
                return not (st.get("retired")
                            and st.get("checkpoint", 0)
                            >= st.get("split_index", 0))
            still_needed = any(_slot_needs(s)
                               for s in self.replication_slots.values())
            if still_needed:
                continue
            for u in ent["replicas"]:
                ts = self.tservers.get(u)
                if ts is None:
                    continue
                try:
                    await self.messenger.call(
                        ts["addr"], "tserver", "delete_tablet",
                        {"tablet_id": tid}, timeout=5.0)
                except (RpcError, asyncio.TimeoutError, OSError):
                    pass
            await self._commit_catalog([["del_tablet", tid]])

    async def _gc_orphan_replicas(self) -> None:
        """Catalog-driven orphan sweep: a replica a live tserver keeps
        reporting that the catalog does not map to it — a deleted
        table's tablet, a stray split child from an interrupted split,
        a move source whose delete_tablet RPC was lost — is deleted on
        that tserver after a grace period spanning several heartbeats
        (reference: tablet-report reconciliation sending DeleteTablet
        in ProcessTabletReportBatch, master_heartbeat_service.cc:854).
        Leader-only, gated on term-start catalog catch-up so a freshly
        elected leader's half-loaded catalog can't condemn replicas."""
        if self.consensus is not None and \
                self.consensus.last_applied < self.consensus.term_start_index:
            return
        now = time.monotonic()
        grace = float(flags.get("master_orphan_gc_grace_s"))
        live = set(self.live_tservers())
        seen: Dict[Tuple[str, str], float] = self._orphan_seen
        reported = set()
        for u in live:
            d = self.tservers[u]
            for t in d.get("tablets", []):
                tid = t["tablet_id"]
                key = (u, tid)
                reported.add(key)
                ent = self.tablets.get(tid)
                ok = ent is not None and (
                    u in ent.get("replicas", [])
                    or u in ent.get("observers", []))
                # a split child (deterministic "<parent>l"/"<parent>r"
                # id) whose PARENT is still in the catalog is a split
                # in flight — or one interrupted before its catalog
                # commit, which the split retry path re-adopts. Never
                # condemn it; survives leader failover because it needs
                # no leader-local state.
                in_split = (tid[-1:] in ("l", "r")
                            and tid[:-1] in self.tablets)
                if ok or in_split or key in self._gc_inflight:
                    seen.pop(key, None)
                    continue
                first = seen.setdefault(key, now)
                if now - first < grace:
                    continue
                try:
                    await self.messenger.call(
                        d["addr"], "tserver", "delete_tablet",
                        {"tablet_id": tid}, timeout=5.0)
                    seen.pop(key, None)
                except (RpcError, asyncio.TimeoutError, OSError):
                    pass   # keep the aged tracker: retry next sweep
        # forget trackers for replicas no longer reported (deleted, or
        # the catalog re-adopted and then dropped them)
        for key in [k for k in seen if k not in reported]:
            seen.pop(key, None)

    # --- sequences (reference: PG sequence relations; allocation is
    # Raft-replicated in BLOCKS so clients cache locally like
    # PgSequenceCache and a master failover can only leave gaps,
    # never duplicates) ---------------------------------------------------
    async def rpc_create_sequence(self, payload) -> dict:
        self._check_leader()
        name = payload["name"]
        if name in self.sequences:
            if payload.get("if_not_exists"):
                return {"ok": True, "existing": True}
            raise RpcError(f"sequence {name} exists", "ALREADY_PRESENT")
        ent = {"next": int(payload.get("start", 1)),
               "increment": int(payload.get("increment", 1))}
        await self._commit_catalog([["put_sequence", name, ent]])
        return {"ok": True}

    async def rpc_drop_sequence(self, payload) -> dict:
        self._check_leader()
        name = payload["name"]
        if name not in self.sequences:
            raise RpcError(f"sequence {name} not found", "NOT_FOUND")
        await self._commit_catalog([["del_sequence", name]])
        return {"ok": True}

    async def rpc_sequence_alloc(self, payload) -> dict:
        """Allocate a block of `count` values: the commit moves the
        persisted next pointer PAST the block before any value is
        handed out, so crashes/failovers skip numbers, never reuse."""
        self._check_leader()
        name = payload["name"]
        count = max(1, int(payload.get("count", 1)))
        async with self._seq_lock:
            ent = self.sequences.get(name)
            if ent is None:
                raise RpcError(f"sequence {name} not found",
                               "NOT_FOUND")
            first, inc = ent["next"], ent["increment"]
            new = dict(ent, next=first + count * inc)
            await self._commit_catalog([["put_sequence", name, new]])
        return {"first": first, "count": count, "increment": inc}

    async def rpc_create_view(self, payload) -> dict:
        self._check_leader()
        name = payload["name"]
        if name in self.views and not payload.get("or_replace"):
            raise RpcError(f"view {name} exists", "ALREADY_PRESENT")
        if any(t["info"]["name"] == name for t in self.tables.values()):
            raise RpcError(f"{name} is a table", "ALREADY_PRESENT")
        if name in self.matviews:
            raise RpcError(f"{name} is a materialized view",
                           "ALREADY_PRESENT")
        await self._commit_catalog([["put_view", name,
                                     payload["select_sql"]]])
        return {"ok": True}

    async def rpc_drop_view(self, payload) -> dict:
        self._check_leader()
        name = payload["name"]
        if name not in self.views:
            raise RpcError(f"view {name} not found", "NOT_FOUND")
        await self._commit_catalog([["del_view", name]])
        return {"ok": True}

    async def rpc_get_view(self, payload) -> dict:
        sql = self.views.get(payload["name"])
        if sql is None:
            raise RpcError(f"view {payload['name']} not found",
                           "NOT_FOUND")
        return {"select_sql": sql}

    # --- materialized views (matview/; reference: PG pg_matviews +
    # the cdc_state slot metadata those maintainers consume) -------------
    async def rpc_create_matview(self, payload) -> dict:
        self._check_leader()
        name = payload["name"]
        if name in self.matviews:
            raise RpcError(f"materialized view {name} exists",
                           "ALREADY_PRESENT")
        if name in self.views or any(
                t["info"]["name"] == name for t in self.tables.values()):
            raise RpcError(f"{name} is a table or view",
                           "ALREADY_PRESENT")
        ent = {"def": payload["def"],
               "slot_id": payload.get("slot_id"),
               "state": payload.get("state")}
        await self._commit_catalog([["put_matview", name, ent]])
        return {"ok": True}

    async def rpc_get_matview(self, payload) -> dict:
        ent = self.matviews.get(payload["name"])
        if ent is None:
            raise RpcError(
                f"materialized view {payload['name']} not found",
                "NOT_FOUND")
        return {"matview": ent}

    async def rpc_update_matview(self, payload) -> dict:
        """Persist maintainer progress (fold state / slot rebind).
        Callers persist state BEFORE confirm_flush on the slot: a crash
        between the two replays already-applied txns, and the state's
        applied LSN filters them — exactly-once without a second log."""
        self._check_leader()
        name = payload["name"]
        ent = self.matviews.get(name)
        if ent is None:
            raise RpcError(f"materialized view {name} not found",
                           "NOT_FOUND")
        ent = dict(ent)
        for k in ("state", "slot_id", "def"):
            if k in payload:
                ent[k] = payload[k]
        await self._commit_catalog([["put_matview", name, ent]])
        return {"ok": True}

    async def rpc_drop_matview(self, payload) -> dict:
        self._check_leader()
        name = payload["name"]
        if name not in self.matviews:
            raise RpcError(f"materialized view {name} not found",
                           "NOT_FOUND")
        await self._commit_catalog([["del_matview", name]])
        return {"ok": True}

    async def rpc_list_matviews(self, payload) -> dict:
        return {"matviews": sorted(self.matviews)}

    async def rpc_list_replication_slots(self, payload) -> dict:
        self._check_leader()
        return {"slots": sorted(self.replication_slots)}

    # --- AutoFlags (reference: master_auto_flags_manager.cc,
    # architecture/design/auto_flags.md) -----------------------------------
    async def rpc_promote_auto_flags(self, payload) -> dict:
        self._check_leader()
        from ..utils import flags as _flags
        _flags.promote_auto_flags()
        return {"promoted": sorted(_flags.auto_flags())}

    # --- tablegroups / colocated tables -----------------------------------
    async def rpc_create_tablegroup(self, payload) -> dict:
        self._check_leader()
        name = payload["name"]
        rf = payload.get("replication_factor", 1)
        gid = f"tg-{uuidlib.uuid4().hex[:10]}"
        parent_wire = TableInfo(
            gid + ".parent", f"{name}.parent",
            TableSchema(columns=(
                ColumnSchema(0, "k", "string", is_hash_key=True),),
                version=1),
            PartitionSchema("hash", 1)).to_wire()
        live = self.live_tservers()
        if len(live) < rf:
            raise RpcError("not enough tservers", "SERVICE_UNAVAILABLE")
        replicas = self._choose_replicas(live, rf, 0)
        tablet_id = f"{gid}-t0"
        raft_peers = [[u, list(self.tservers[u]["addr"])] for u in replicas]
        shield = {(u, tablet_id) for u in replicas}
        self._gc_inflight |= shield
        try:
            for u in replicas:
                await self.messenger.call(
                    self.tservers[u]["addr"], "tserver", "create_tablet",
                    {"tablet_id": tablet_id, "table": parent_wire,
                     "partition": ["", ""], "raft_peers": raft_peers,
                     "colocated": True}, timeout=30.0)
            ent = {"tablet_id": tablet_id, "table_id": gid,
                   "partition": ["", ""], "replicas": replicas,
                   "leader": None}
            ops = [["put_table", gid, {"info": parent_wire,
                                       "tablets": [tablet_id],
                                       "tablegroup": name,
                                       "next_cotable": 1}],
                   ["put_tablet", tablet_id, ent]]
            await self._commit_catalog(ops)
        finally:
            self._gc_inflight -= shield
        return {"tablegroup_id": gid, "tablet_id": tablet_id}

    def _find_tablegroup(self, name: str):
        for tid, e in self.tables.items():
            if e.get("tablegroup") == name:
                return tid, e
        return None, None

    # --- secondary indexes (reference: index tables in catalog_manager,
    # online backfill master/backfill_index.cc) ---------------------------
    async def rpc_create_secondary_index(self, payload) -> dict:
        """Register an index table mapping indexed column -> base PK.

        The index is itself a normal sharded table (the reference models
        indexes exactly this way); the client maintains it on writes and
        backfills existing rows at creation."""
        base_name = payload["table"]
        index_name = payload["index_name"]
        columns = payload.get("columns") or [payload["column"]]
        tid = next((t for t, e in self.tables.items()
                    if e["info"]["name"] == base_name), None)
        if tid is None:
            raise RpcError(f"table {base_name} not found", "NOT_FOUND")
        base = self.tables[tid]
        base_info = TableInfo.from_wire(base["info"])
        pk_cols = base_info.schema.key_columns
        unique = bool(payload.get("unique"))
        # composite index key: first indexed column hashed, the rest
        # range — the doc key is the FULL value tuple, so a UNIQUE
        # index collides two inserts of one tuple on the same key and
        # the write path's insert-if-absent / txn conflict machinery
        # lets exactly one commit (reference: unique-index key layout
        # in yb_access/yb_lsm.c:233-366 — base PK moves to the value)
        cols = []
        for i, cname in enumerate(columns):
            col = base_info.schema.column_by_name(cname)
            cols.append(ColumnSchema(i, cname, col.type,
                                     is_hash_key=(i == 0),
                                     is_range_key=(i > 0)))
        off = len(columns)
        for i, c in enumerate(pk_cols):
            cols.append(ColumnSchema(off + i, f"base_{c.name}", c.type,
                                     is_range_key=not unique))
        idx_info = TableInfo(
            "", index_name, TableSchema(tuple(cols), 1),
            PartitionSchema("hash", 1))
        resp = await self.rpc_create_table({
            "name": index_name, "table": idx_info.to_wire(),
            "num_tablets": payload.get("num_tablets", 2),
            "replication_factor": payload.get("replication_factor", 1)})
        tent = dict(base)
        idxs = dict(tent.get("indexes", {}))
        idxs[index_name] = {
            "column": columns[0], "columns": list(columns),
            "index_table": index_name,
            "base_pk": [c.name for c in pk_cols], "unique": unique}
        tent["indexes"] = idxs
        await self._commit_catalog([["put_table", tid, tent]])
        return {"index_table_id": resp["table_id"]}

    async def rpc_drop_secondary_index(self, payload) -> dict:
        """Deregister + drop an index table (used by DROP INDEX and by
        the client when a unique backfill fails — a registered index
        with no backfilled entries would both miss lookups and deny
        values via its insert-if-absent gate)."""
        base_name = payload.get("table")
        index_name = payload["index_name"]
        if base_name is not None:
            tid = next((t for t, e in self.tables.items()
                        if e["info"]["name"] == base_name), None)
            if tid is None:
                raise RpcError(f"table {base_name} not found",
                               "NOT_FOUND")
        else:
            # DROP INDEX names only the index: the registry owner (this
            # master) resolves the base relation, like the reference's
            # catalog manager resolving an index relation to its
            # indexed table
            tid = next((t for t, e in self.tables.items()
                        if index_name in (e.get("indexes") or {})),
                       None)
            if tid is None:
                raise RpcError(f"index {index_name} not found",
                               "NOT_FOUND")
        tent = dict(self.tables[tid])
        idxs = dict(tent.get("indexes", {}))
        if index_name not in idxs:
            raise RpcError(f"index {index_name} not found", "NOT_FOUND")
        del idxs[index_name]
        tent["indexes"] = idxs
        await self._commit_catalog([["put_table", tid, tent]])
        try:
            await self.rpc_drop_table({"name": index_name})
        except RpcError:
            pass     # index table already gone: deregistration stands
        return {"ok": True, "table": tent["info"]["name"]}

    async def rpc_get_status_tablet(self, payload) -> dict:
        """Return (creating on demand) the transaction status tablet
        (reference: client-side status-tablet picking,
        client/transaction_pool.cc; system `transactions` table)."""
        self._check_leader()
        name = "system.transactions"
        for tid, e in self.tables.items():
            if e["info"]["name"] == name:
                return {"locations": self._locations(tid)}
        live = self.live_tservers()
        rf = min(3, len(live)) or 1
        info = TableInfo(
            "", name,
            TableSchema(columns=(
                ColumnSchema(0, "txn_id", "string", is_hash_key=True),),
                version=1),
            PartitionSchema("hash", 1))
        resp = await self.rpc_create_table({
            "name": name, "table": info.to_wire(), "num_tablets": 1,
            "replication_factor": rf, "is_status_tablet": True})
        return {"locations": self._locations(resp["table_id"])}

    async def rpc_list_tables(self, payload) -> dict:
        self._check_leader()
        return {"tables": [
            {"table_id": tid, "name": e["info"]["name"],
             "num_tablets": len(e["tablets"])}
            for tid, e in self.tables.items()]}
