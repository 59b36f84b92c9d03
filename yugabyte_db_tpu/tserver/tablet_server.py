"""TabletServer: the data node.

Analog of the reference's yb-tserver (reference:
src/yb/tserver/tablet_server.cc, tablet_service.cc — Read :2769, Write
:2724; ts_tablet_manager.cc for tablet lifecycle; heartbeater.cc for
master heartbeats). Hosts TabletPeers, serves the tablet service RPCs,
persists per-tablet metadata for restart, and heartbeats tablet reports
to the master.
"""
from __future__ import annotations

import asyncio
import inspect
import json
import os
from typing import Dict, List, Optional, Tuple

from ..consensus import PeerSpec, RaftConfig
from ..docdb.mesh_read import MeshIneligible, tablets_in_partition_order
from ..docdb.table_codec import TableInfo
from ..docdb.wire import (
    read_request_from_wire, read_response_to_wire, write_request_from_wire,
)
from ..dockv.partition import Partition
from ..rpc.messenger import (Messenger, RpcError, Sidecars,
                             sidecar_ref)
from ..sched import (Lane, PointReadItem, RequestScheduler, ScanItem,
                     WriteItem, canon, classify_read)
from ..tablet.tablet import ServedReads, Tablet, serve_read
from ..tablet.tablet_peer import TabletPeer
import logging

from ..utils import flags, metrics
from ..utils.fault_injection import TEST_CRASH_POINT
from ..utils.hybrid_time import HybridClock
from ..utils.tasks import cancel_and_drain
from ..utils.trace import ASH, TRACES, wait_status

log = logging.getLogger("ybtpu.tserver")


def _atomic_json(path: str, obj) -> None:
    """Durable metadata write: tmp + fsync + rename, so a crash
    mid-write never leaves a truncated tablet-meta.json the next
    startup would fail to parse.  Sync form for sync callers (raft
    config-persist callbacks run off-loop already); async code must
    use ``_atomic_json_off_loop`` — the fsync is a device stall."""
    _write_atomic_json(path, json.dumps(obj))


def _write_atomic_json(path: str, data: str) -> None:
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        f.write(data)
        f.flush()
        os.fsync(f.fileno())
    os.replace(tmp, path)


async def _atomic_json_off_loop(path: str, obj) -> None:
    """_atomic_json without the loop stall: serialize on the loop (the
    dict is loop state — snapshotting here keeps the bytes consistent
    even if the caller mutates it later), fsync+rename in the
    executor."""
    data = json.dumps(obj)
    await asyncio.get_running_loop().run_in_executor(
        None, _write_atomic_json, path, data)


def _rmtree(path: str) -> None:
    """Executor target: tablet/snapshot dirs can be GBs of SST files —
    an inline rmtree on the event loop stalls every lane's dispatch,
    Raft heartbeats included."""
    import shutil
    shutil.rmtree(path, ignore_errors=True)


def _close_sessions(sessions) -> None:
    """Executor target: release every live bypass session's SST leases
    (graceful-drain path; close is idempotent and must not abort the
    drain)."""
    for s in sessions:
        try:
            s.close()
        except Exception:   # noqa: BLE001 — drain regardless
            pass


_DELETING_MARK = ".deleting-"


async def _rmtree_off_loop(path: str) -> None:
    """Detach `path` from its visible name synchronously (one rename —
    observers that saw the owning state change never see a half-deleted
    tree at the old path), then bulk-delete the tombstone off-loop.
    `_sweep_tombstones` finishes the job at startup for any tombstone a
    crash leaves behind, at any depth under tablets/."""
    import uuid
    tomb = f"{path}{_DELETING_MARK}{uuid.uuid4().hex[:8]}"
    try:
        # analysis-ok(async_blocking): single dir-entry metadata op
        os.rename(path, tomb)
    except FileNotFoundError:
        return
    except OSError:
        tomb = path                 # busy/odd fs: delete in place
    await asyncio.get_running_loop().run_in_executor(None, _rmtree, tomb)


def _sweep_tombstones(root: str) -> None:
    """Executor target: remove every crash-left `.deleting-` tombstone
    under `root`, at any depth — delete-tablet, delete-snapshot and
    install-staging renames can all crash between the rename and the
    off-loop rmtree, leaving `<x>.deleting-yyyy` dirs (hard-linked
    snapshot tombstones would otherwise pin deleted SST data forever)."""
    import shutil
    for dirpath, dirs, _files in os.walk(root):
        doomed = [d for d in dirs if _DELETING_MARK in d]
        for d in doomed:
            shutil.rmtree(os.path.join(dirpath, d), ignore_errors=True)
        dirs[:] = [d for d in dirs if _DELETING_MARK not in d]


def _seed_clone(src: str, dst: str) -> None:
    """Executor target: seed a store dir from a checkpoint.  Copy into
    a unique tmp dir + atomic rename, so a concurrent duplicate
    create_tablet (master RPC retry racing a long copy) can never
    observe — or open the tablet from — a half-copied `dst`: the rename
    loser just discards its tmp (a crash leaves only an ignored tmp
    dir, never a partial `dst`)."""
    import shutil
    import uuid
    if os.path.exists(dst):
        return
    tmp = f"{dst}.seed-{uuid.uuid4().hex[:8]}"
    shutil.copytree(src, tmp)
    try:
        os.rename(tmp, dst)
    except OSError:
        # racer renamed first; its copy is complete — keep theirs
        shutil.rmtree(tmp, ignore_errors=True)


class TabletServer:
    def __init__(self, uuid: str, fs_root: str,
                 master_addrs: Optional[List[Tuple[str, int]]] = None,
                 zone: str = "zone-default"):
        self.uuid = uuid
        self.fs_root = fs_root
        self.zone = zone
        self.master_addrs = master_addrs or []
        os.makedirs(fs_root, exist_ok=True)
        self.messenger = Messenger(f"ts-{uuid}")
        self.clock = HybridClock()
        self.peers: Dict[str, TabletPeer] = {}
        # split parent -> [child ids] (persisted in the parent's meta;
        # routes txn apply/rollback decisions to the children that
        # inherited the parent's in-flight intents)
        self._split_children: Dict[str, list] = {}
        self._hb_task: Optional[asyncio.Task] = None
        self._running = False
        # admission-controlled scheduler between RPC dispatch and
        # tablet execution (sched/): data-path RPCs route through it
        # when `scheduler_enabled` is on; flag off = direct dispatch
        self.scheduler = RequestScheduler(f"ts-{uuid}")
        # this server's reads whose launch is beside the event loop
        self.served_reads = ServedReads(f"ts-{uuid}")
        # edge gate: saturated-lane requests shed at the frame edge,
        # before a dispatch task is even spawned
        self.messenger.overload_probe = self.scheduler.overload_probe
        self.messenger.register_service("tserver", self)
        # live bypass sessions opened by rpc_bypass_scan: tracked so a
        # graceful drain can release their SST leases before the stores
        # close (a crash leaves only unmanifested files the next open
        # sweeps — the lease discipline's crash half)
        self._bypass_sessions: set = set()
        # how late each heartbeat tick woke against its 0.2 s sleep: the
        # event loop's lag (work that blocks the loop delays every task
        # on it, this one included)
        self._m_tick_late = metrics.REGISTRY.entity(
            "server", f"ts-{uuid}").histogram("heartbeat_tick_late_ms")
        # the chips this server owns (`tserver_device_chips`): with more
        # than one, a table's tablets are placed on them and an
        # aggregate read of all of them is one mesh launch
        # (docdb/mesh_read.py); with one, nothing of that exists
        self.mesh_reader = None
        self._cache_capacity_before = None
        chips = int(flags.get("tserver_device_chips"))
        if chips > 1:
            self._own_chips(chips)

    def _own_chips(self, chips: int) -> None:
        import jax
        from ..docdb.mesh_read import MeshReader
        from ..ops.device_batch import chip_capacity
        from ..tablet.tablet import _DEVICE_CACHE
        devices = jax.devices()
        if len(devices) < chips:
            raise RuntimeError(
                f"tserver_device_chips={chips}, JAX has {len(devices)}")
        self.mesh_reader = MeshReader(devices[:chips], _DEVICE_CACHE,
                                      owner=f"ts-{self.uuid}")
        # the cache's capacity is a chip's, from the chip's own memory
        self._cache_capacity_before = _DEVICE_CACHE.capacity
        _DEVICE_CACHE.capacity = chip_capacity(
            devices[:chips], default=_DEVICE_CACHE.capacity)

    # --- lifecycle --------------------------------------------------------
    async def start(self, host: str = "127.0.0.1", port: int = 0):
        await self.messenger.start(host, port)
        await self._open_existing_tablets()
        self._running = True
        if self.master_addrs:
            self._hb_task = asyncio.create_task(self._heartbeat_loop())
        return self.messenger.addr

    async def shutdown(self, graceful: bool = False):
        """Stop the server.  ``graceful`` is the SIGTERM drain contract
        the cluster supervisor relies on (CLUSTER.md): release bypass
        SST leases, flush every tablet's memtables, close WALs — so a
        drained node restarts serving from SSTs with nothing to replay
        and no leaked lease pins.  The default (crash-adjacent) path
        keeps the old behavior: consensus stops, WAL closes, memtables
        are simply lost to replay."""
        self._running = False
        await cancel_and_drain(self._hb_task)
        self._hb_task = None
        # the ASH sampler is process-global: a dead server's provider
        # closures must not keep reporting its retained state forever
        for p in getattr(self, "_ash_providers", ()):
            ASH.unregister(p)
        self._ash_providers = []
        await self.scheduler.shutdown()
        if self._cache_capacity_before is not None:
            # the chips go back as they were taken: this server's
            # batches leave them, and the cache its capacity
            from ..tablet.tablet import _DEVICE_CACHE
            for p in self.peers.values():
                p.tablet.drop_device_state()
            _DEVICE_CACHE.capacity = self._cache_capacity_before
            self._cache_capacity_before = None
            # and the programs compiled for this server's mesh with them:
            # they are named by `id(mesh)`, which a later mesh may reuse
            self.mesh_reader.kernel.forget(self.mesh_reader.mesh)
        if graceful:
            # lease release first: a pinned compaction-victim SST is
            # physically unlinked on the last release, which must
            # happen while the store still owns its manifest
            sessions = list(self._bypass_sessions)
            self._bypass_sessions.clear()
            await asyncio.get_running_loop().run_in_executor(
                None, _close_sessions, sessions)
        for p in self.peers.values():
            if graceful:
                await p.graceful_shutdown()
            else:
                await p.shutdown()
        await self.messenger.shutdown()

    # --- tablet management (TSTabletManager analog) -----------------------
    def _tablet_dir(self, tablet_id: str) -> str:
        return os.path.join(self.fs_root, "tablets", tablet_id)

    async def _open_existing_tablets(self):
        root = os.path.join(self.fs_root, "tablets")
        if not os.path.isdir(root):
            return
        # finish crashed deletes first: a tablet tombstone's meta must
        # NOT resurrect the tablet, and nested snapshot/staging
        # tombstones would pin hard-linked SST data forever
        await asyncio.get_running_loop().run_in_executor(
            None, _sweep_tombstones, root)
        for tablet_id in sorted(os.listdir(root)):
            if _DELETING_MARK in tablet_id:
                continue      # tombstoned mid-startup by a delete RPC
            meta_path = os.path.join(root, tablet_id, "tablet-meta.json")
            if not os.path.exists(meta_path):
                continue
            with open(meta_path) as f:   # blocking-ok: tiny meta, startup
                meta = json.load(f)
            await self._open_tablet(meta)

    @staticmethod
    def _complete_install_swap(tdir: str) -> None:
        """Finish (or clean up after) a snapshot-install swap. The
        marker file is written only once the staged dirs are FULLY
        fetched, and removed only after the swap + cleanup completes —
        so: marker present = staged state is authoritative, roll the
        swap FORWARD deterministically; marker absent = any leftover
        .install dirs are partial fetches, discard them. Either way no
        crash point leaves the replica with an empty store or with a
        stale WAL alongside a newer store (which would fake a commit
        floor / break log index contiguity)."""
        import shutil
        marker = os.path.join(tdir, "install-commit")
        if os.path.exists(marker):
            for s in ("regular", "intents"):
                staged = os.path.join(tdir, f"{s}.install")
                live = os.path.join(tdir, s)
                old = os.path.join(tdir, f"{s}.old")
                if os.path.isdir(staged):
                    shutil.rmtree(old, ignore_errors=True)
                    if os.path.isdir(live):
                        os.rename(live, old)
                    os.rename(staged, live)
            wals = os.path.join(tdir, "wals")
            wals_old = os.path.join(tdir, "wals.old")
            if os.path.isdir(wals):
                shutil.rmtree(wals_old, ignore_errors=True)
                os.rename(wals, wals_old)
            for leftover in ("regular.old", "intents.old", "wals.old"):
                shutil.rmtree(os.path.join(tdir, leftover),
                              ignore_errors=True)
            os.remove(marker)
        else:
            for leftover in ("regular.install", "intents.install"):
                shutil.rmtree(os.path.join(tdir, leftover),
                              ignore_errors=True)

    async def _open_tablet(self, meta: dict) -> TabletPeer:
        info = TableInfo.from_wire(meta["table"])
        tablet_id = meta["tablet_id"]
        # roll forward / clean up any snapshot install a crash cut
        # short — staged stores can be GBs of SSTs, so the rename/
        # rmtree sequence runs in the executor (the swap itself is
        # marker-gated and idempotent, and installs for this tablet
        # are serialized by the _installing guard)
        await asyncio.get_running_loop().run_in_executor(
            None, self._complete_install_swap,
            self._tablet_dir(tablet_id))
        part = Partition(bytes.fromhex(meta["partition"][0]),
                         bytes.fromhex(meta["partition"][1]))
        tablet = Tablet(tablet_id, info, self._tablet_dir(tablet_id),
                        clock=self.clock, partition=part,
                        colocated=meta.get("colocated", False),
                        owner=f"ts-{self.uuid}",
                        served=self.served_reads)
        for tw in meta.get("colocated_tables", []):
            tablet.add_table(TableInfo.from_wire(tw))
        config = RaftConfig([PeerSpec(e[0], tuple(e[1]),
                                      e[2] if len(e) > 2 else "voter")
                             for e in meta["raft_peers"]])
        peer = TabletPeer(tablet, self.uuid, config, self.messenger,
                          clock=self.clock,
                          is_status_tablet=meta.get("is_status_tablet",
                                                    False))

        def persist_config(cfg, tablet_id=tablet_id, meta=meta):
            meta["raft_peers"] = [[p.uuid, list(p.addr), p.role]
                                  for p in cfg.peers]
            _atomic_json(os.path.join(self._tablet_dir(tablet_id),
                                      "tablet-meta.json"), meta)

        peer.consensus.on_config_change = persist_config

        def persist_alter(table_wire, tablet_id=tablet_id, meta=meta):
            if meta["table"].get("table_id") == table_wire.get("table_id"):
                meta["table"] = table_wire
            else:
                meta["colocated_tables"] = [
                    tw if tw.get("table_id") != table_wire.get("table_id")
                    else table_wire
                    for tw in meta.get("colocated_tables", [])]
            _atomic_json(os.path.join(self._tablet_dir(tablet_id),
                                      "tablet-meta.json"), meta)

        peer.on_alter = persist_alter
        peer.on_split = self._apply_split
        peer.split_done = bool(meta.get("split_done"))
        if meta.get("split_children"):
            self._split_children[tablet_id] = list(meta["split_children"])
        # a child's split-complete marker names its parent: rebuild the
        # parent->children decision-routing map even after the parent
        # replica itself was deleted
        mk = os.path.join(self._tablet_dir(tablet_id),
                          "split-complete.json")
        if os.path.exists(mk):
            with open(mk) as f:   # blocking-ok: tiny split marker
                mkd = json.load(f)
            par = mkd.get("parent")
            if par:
                sibs = self._split_children.setdefault(par, [])
                for sib in mkd.get("siblings", [tablet_id]):
                    if sib not in sibs:
                        sibs.append(sib)
        self.peers[tablet_id] = peer
        await peer.start()
        # persisted ANN indexes load + scan-diff here, after the store
        # is open (WAL replay re-commits through Raft and maintains the
        # delta via the normal write path once the state is installed).
        # Executor, not inline: the scan-diff — and the full rebuild a
        # torn payload falls back to — must not stall the event loop
        # (same rationale as rpc_build_vector_index).
        if os.path.isdir(os.path.join(tablet.dir, "vecidx")):
            try:
                await asyncio.get_running_loop().run_in_executor(
                    None, tablet.bootstrap_vector_indexes)
            except Exception:   # noqa: BLE001 — a broken index payload
                # must never keep the tablet from serving; but silence
                # here would make "index quietly gone after restart"
                # undiagnosable
                log.exception("vector index bootstrap failed for %s",
                              tablet_id)
        return peer

    async def rpc_create_tablet(self, payload) -> dict:
        tablet_id = payload["tablet_id"]
        if tablet_id in self.peers:
            return {"ok": True, "existing": True}
        # the body awaits (seed copy / remote-bootstrap fetch), so a
        # master retry can arrive mid-create; a duplicate must WAIT for
        # the first attempt rather than race it into two live peers on
        # one directory (same shape as rpc_install_snapshot's guard,
        # but idempotent: create_tablet's contract is "exists after")
        creating = getattr(self, "_creating", None)
        if creating is None:
            creating = self._creating = set()
        while tablet_id in creating:
            await asyncio.sleep(0.01)
        if tablet_id in self.peers:
            return {"ok": True, "existing": True}
        creating.add(tablet_id)
        try:
            return await self._do_create_tablet(tablet_id, payload)
        finally:
            creating.discard(tablet_id)

    async def _do_create_tablet(self, tablet_id: str, payload) -> dict:
        d = self._tablet_dir(tablet_id)
        os.makedirs(d, exist_ok=True)
        meta = {
            "tablet_id": tablet_id,
            "table": payload["table"],
            "partition": payload["partition"],
            "raft_peers": payload["raft_peers"],
            "is_status_tablet": payload.get("is_status_tablet", False),
            "colocated": payload.get("colocated", False),
            "colocated_tables": [],
        }
        seed = payload.get("seed_snapshot_dir")
        if seed:
            # restore-as-clone: seed the regular store from a checkpoint
            # (a whole tablet's SSTs — copy off-loop; tmp+rename inside
            # _seed_clone keeps a racing duplicate create from seeing a
            # half-copied store)
            await asyncio.get_running_loop().run_in_executor(
                None, _seed_clone, os.path.join(seed, "regular"),
                os.path.join(d, "regular"))
        rb = payload.get("remote_bootstrap")
        if rb:
            # Remote bootstrap (reference: tserver/remote_bootstrap_*.cc):
            # stream the source replica's checkpoint files over RPC, then
            # open the tablet from them; Raft log catch-up covers the tail.
            await self._remote_bootstrap_fetch(
                tuple(rb["addr"]), rb["tablet_id"], rb["snapshot_id"],
                os.path.join(d, "regular"))
        # blocking-ok: tiny metadata file
        with open(os.path.join(d, "tablet-meta.json"), "w") as f:
            json.dump(meta, f)
        peer = await self._open_tablet(meta)
        trim = payload.get("trim_above_ht")
        if seed and trim:
            # restore of a single-HT snapshot: clock-skewed versions
            # above the cut are in the checkpoint; drop them
            peer.tablet.trim_above_ht(trim)
        return {"ok": True}

    async def rpc_delete_tablet(self, payload) -> dict:
        tablet_id = payload["tablet_id"]
        peer = self.peers.pop(tablet_id, None)
        if peer:
            await peer.shutdown()
        await _rmtree_off_loop(self._tablet_dir(tablet_id))
        return {"ok": True}

    # --- data-path RPCs ---------------------------------------------------
    def _peer(self, tablet_id: str) -> TabletPeer:
        peer = self.peers.get(tablet_id)
        if peer is None:
            raise RpcError(f"tablet {tablet_id} not found", "NOT_FOUND")
        return peer

    async def rpc_write(self, payload) -> dict:
        peer = self._peer(payload["tablet_id"])
        req = write_request_from_wire(payload["req"])
        if req.schema_version is not None:
            # catalog-version fence: reject BEFORE replicating (and
            # before any scheduler queueing) so a stale session's write
            # (e.g. into a dropped column) can never reach the WAL; the
            # client refreshes and retries (reference: schema version
            # mismatch checks in tablet_service.cc +
            # ysql_backends_manager.cc)
            cur = peer.tablet.schema_version_of(req.table_id)
            if cur is not None and req.schema_version != cur:
                raise RpcError(
                    f"schema version mismatch for {req.table_id}: "
                    f"request {req.schema_version}, tablet {cur}",
                    "SCHEMA_MISMATCH")
        # sampled span (child of the messenger's server span): the
        # legacy always-on trace() here taxed EVERY write for a dump
        # nobody read; sampling keeps the hot path under the bench's
        # trace-overhead gate while sampled requests get full nesting
        with TRACES.span(f"tserver.write:{payload['tablet_id']}",
                         child_only=True):
            with wait_status("OnCpu_WriteApply", component="tserver"):
                if not self.scheduler.enabled():
                    resp = await peer.write(req)
                    return {"rows_affected": resp.rows_affected}
                cost = 256 + 256 * len(req.ops)
                # group commit merges only writes whose semantics are
                # invariant under merging: same tablet + table + schema
                # fence (the group key), no imported external HT, and
                # no insert-if-absent ops (one duplicate would fail the
                # whole merged batch's innocent neighbors)
                if req.external_ht is None and \
                        all(op.kind != "insert" for op in req.ops):
                    key = (payload["tablet_id"], req.table_id,
                           req.schema_version)
                    return await self.scheduler.submit_grouped(
                        Lane.POINT_WRITE, key, WriteItem(peer, req),
                        cost_bytes=cost)

                async def run():
                    resp = await peer.write(req)
                    return {"rows_affected": resp.rows_affected}
                return await self.scheduler.submit(
                    Lane.POINT_WRITE, run, cost_bytes=cost)

    async def _serve_read(self, peer, tablet_id: str, req_wire) -> dict:
        req = read_request_from_wire(req_wire)
        with TRACES.span(f"tserver.read:{tablet_id}", child_only=True):
            with wait_status("OnCpu_Read", component="tserver"):
                resp = await peer.read(req)
        return read_response_to_wire(resp)

    async def rpc_read(self, payload) -> dict:
        peer = self._peer(payload["tablet_id"])

        async def run():
            return await self._serve_read(peer, payload["tablet_id"],
                                          payload["req"])
        if not self.scheduler.enabled():
            return await run()
        lane = classify_read(payload["req"])
        if lane is Lane.POINT_READ:
            r = payload["req"]
            # batched multi_get eligibility: a plain strong point get
            # with a server-assigned read point and no pushdown — the
            # shape whose group shares one gate + read point + fused
            # engine lookup (projection re-applied per member)
            if (r.get("pk_eq") is not None and not r.get("where")
                    and not r.get("aggregates")
                    and r.get("read_ht") is None
                    and not r.get("paging_state")
                    and r.get("consistency", "strong") == "strong"):
                key = ("pr", payload["tablet_id"], r["table_id"])
                # trace/ASH here: the grouped dispatch never runs run(),
                # so instrumentation must wrap the submit (span covers
                # queue wait + the shared batched execution)
                with TRACES.span(f"tserver.read:{payload['tablet_id']}",
                                 child_only=True):
                    with wait_status("OnCpu_Read", component="tserver"):
                        return await self.scheduler.submit_grouped(
                            Lane.POINT_READ, key, PointReadItem(peer, r),
                            cost_bytes=512)
            return await self.scheduler.submit(Lane.POINT_READ, run,
                                               cost_bytes=512)
        # scan/aggregate: same-signature requests queued together
        # execute ONCE — one batched kernel launch through the
        # signature-keyed ops/scan.py cache — and share the response.
        # The group executes with a read point resolved at dispatch
        # (after every member arrived), so coalescing never serves a
        # member data older than its own arrival; explicit read points
        # are part of the signature (identical snapshot only).
        sig = (payload["tablet_id"], canon(payload["req"]))
        return await self.scheduler.submit_grouped(
            Lane.SCAN, sig, ScanItem(run), cost_bytes=4096)

    async def rpc_read_tablets(self, payload) -> dict:
        """One aggregate read over several tablets of a table that this
        server leads, as the client sends it to a server that owns
        several chips.  `{"mesh": response}` is the answer over all of
        them, combined on the chips by one launch at one read time;
        `{"parts": [response, ...]}` are the tablets' own answers by the
        one-device path, in the order asked, where the mesh does not
        take the read — the client combines those as it combines
        per-tablet RPCs.  Each tablet is in exactly one of the two."""
        tablet_ids = list(payload["tablet_ids"])
        peers = [self._peer(t) for t in tablet_ids]

        async def run():
            if self.mesh_reader is not None:
                try:
                    return {"mesh": read_response_to_wire(
                        await self._mesh_read(peers, payload["req"]))}
                except MeshIneligible:
                    pass
            return {"parts": [await self._serve_read(p, t, payload["req"])
                              for p, t in zip(peers, tablet_ids)]}
        if not self.scheduler.enabled():
            return await run()
        sig = (tuple(tablet_ids), canon(payload["req"]))
        return await self.scheduler.submit_grouped(
            Lane.SCAN, sig, ScanItem(run), cost_bytes=4096)

    async def _mesh_read(self, peers: list, req_wire):
        """The tablets' reads gathered into one launch: every tablet's
        gates, ONE read time for all of them, every tablet's safe-time
        wait (`tserver.mesh_gather`), then the mesh scan.  Restarts as
        `DocReadOperation.execute`: three bumps of a server-assigned
        read time, then a read that does not restart."""
        from ..docdb.operations import ReadRestartError
        reader = self.mesh_reader
        with TRACES.span("tserver.read_tablets", child_only=True), \
                wait_status("OnCpu_Read", component="tserver"):
            with TRACES.span("tserver.mesh_gather", child_only=True,
                             tags={"tablets": len(peers),
                                   "chips": reader.chips,
                                   "fanin": len(peers)}):
                req = read_request_from_wire(req_wire)
                if req.consistency == "follower":
                    raise MeshIneligible("follower_read")
                peers = tablets_in_partition_order(peers)
                for p in peers:
                    p.check_strong_read()
                if req.read_ht is None:
                    req.read_ht = self.clock.now().value
                    req.server_assigned_read_ht = True
                for p in peers:
                    await p.wait_safe_time(req.read_ht)
                ops = [p.tablet.read_op(req.table_id) for p in peers]
            for attempt in range(4):
                try:
                    resp = await serve_read(
                        reader.read_steps(req, ops,
                                          allow_restart=attempt < 3),
                        self.served_reads)
                    break
                except ReadRestartError as e:
                    req.read_ht = e.restart_ht
            for p in peers:
                p.tablet.note_read()
            return resp

    async def rpc_alter_table(self, payload) -> dict:
        peer = self._peer(payload["tablet_id"])
        await peer.alter(payload["table"])
        return {"ok": True}

    async def rpc_add_table(self, payload) -> dict:
        """Add a colocated table to an existing tablet (reference:
        tablegroups, master/ysql_tablegroup_manager.cc)."""
        peer = self._peer(payload["tablet_id"])
        info = TableInfo.from_wire(payload["table"])
        peer.tablet.add_table(info)
        meta_path = os.path.join(self._tablet_dir(payload["tablet_id"]),
                                 "tablet-meta.json")
        with open(meta_path) as f:   # blocking-ok: tiny metadata file
            meta = json.load(f)
        meta.setdefault("colocated_tables", []).append(payload["table"])
        with open(meta_path, "w") as f:   # blocking-ok: tiny metadata file
            json.dump(meta, f)
        return {"ok": True}

    # --- remote bootstrap ----------------------------------------------------
    async def _remote_bootstrap_fetch(self, src_addr, tablet_id: str,
                                      snapshot_id: str, dst_dir: str,
                                      subdir: str = "regular"):
        os.makedirs(dst_dir, exist_ok=True)
        listing = await self.messenger.call(
            src_addr, "tserver", "list_snapshot_files",
            {"tablet_id": tablet_id, "snapshot_id": snapshot_id,
             "subdir": subdir},
            timeout=30.0)
        for name, size in listing["files"]:
            out_path = os.path.join(dst_dir, name)
            # blocking-ok: buffered writes of bounded 4MB chunks
            with open(out_path, "wb") as out:
                offset = 0
                while offset < size:
                    chunk = await self.messenger.call(
                        src_addr, "tserver", "fetch_snapshot_file",
                        {"tablet_id": tablet_id, "snapshot_id": snapshot_id,
                         "name": name, "offset": offset, "subdir": subdir,
                         "length": 4 * 1024 * 1024}, timeout=60.0)
                    out.write(chunk["data"])
                    offset += len(chunk["data"])
                    if not chunk["data"]:
                        break

    async def _fetch_tablet_state(self, src_addr, tablet_id: str,
                                  snapshot_id: str, staging: dict):
        """Fetch both stores of a tablet snapshot into staging dirs:
        {"regular": path, "intents": path}. The intents store may be
        absent in snapshots from older leaders — tolerated."""
        await self._remote_bootstrap_fetch(
            src_addr, tablet_id, snapshot_id, staging["regular"],
            subdir="regular")
        try:
            await self._remote_bootstrap_fetch(
                src_addr, tablet_id, snapshot_id, staging["intents"],
                subdir="intents")
        except RpcError as e:
            if e.code != "NOT_FOUND":
                raise

    async def rpc_install_snapshot(self, payload) -> dict:
        """Install a leader checkpoint over this lagging replica
        (reference: remote bootstrap for followers behind log GC +
        Raft InstallSnapshot semantics). Fetches the leader's snapshot
        files first (the replica keeps serving), then swaps in the new
        stores and wipes the stale WAL — snapshot state covers only
        committed entries, so discarding the local log is Raft-safe.
        Consensus metadata (term, vote) is preserved.

        Crash-safe sequencing (renames only, no delete-then-copy
        window): the WAL is retired FIRST — without a log the replica
        presents as a cleanly bootstrapped node at whatever frontier
        its store holds, so a crash at any later point leaves a state
        the leader simply re-installs over; it can never leave a
        non-empty GC'd WAL next to an empty store (which would fake a
        commit floor) or a log contiguous-append violation."""
        tablet_id = payload["tablet_id"]
        if tablet_id not in self.peers:
            raise RpcError(f"tablet {tablet_id} not found", "NOT_FOUND")
        # serialize installs per tablet: two concurrent fetches would
        # interleave writes into the same staging dirs and could commit
        # a mixed-snapshot store as authoritative
        installing = getattr(self, "_installing", None)
        if installing is None:
            installing = self._installing = set()
        if tablet_id in installing:
            raise RpcError(f"install already running for {tablet_id}",
                           "TRY_AGAIN")
        installing.add(tablet_id)
        try:
            return await self._do_install_snapshot(tablet_id, payload)
        finally:
            installing.discard(tablet_id)

    async def _do_install_snapshot(self, tablet_id: str, payload) -> dict:
        d = self._tablet_dir(tablet_id)
        staging = {s: os.path.join(d, f"{s}.install")
                   for s in ("regular", "intents")}
        for p in staging.values():
            # stale staging from a crashed install can be a full
            # checkpoint's worth of files
            await _rmtree_off_loop(p)
        # fetch while the replica keeps serving
        await self._fetch_tablet_state(
            tuple(payload["src_addr"]), tablet_id,
            payload["snapshot_id"], staging)
        # re-check after the long fetch await: a racing delete (or a
        # second leader's install) may have removed the peer meanwhile
        peer = self.peers.pop(tablet_id, None)
        if peer is None:
            for p in staging.values():
                await _rmtree_off_loop(p)
            raise RpcError(f"tablet {tablet_id} went away during "
                           "snapshot fetch", "NOT_FOUND")
        # blocking-ok: tiny metadata file
        with open(os.path.join(d, "tablet-meta.json")) as f:
            meta = json.load(f)
        await peer.shutdown()
        try:
            # commit point: the marker makes the staged state
            # authoritative; any crash from here rolls FORWARD at the
            # next open (see _complete_install_swap)
            marker = os.path.join(d, "install-commit")
            with open(marker, "w") as f:   # blocking-ok: commit marker
                f.write(payload["snapshot_id"])
                f.flush()
                os.fsync(f.fileno())   # blocking-ok: durable commit point
            # the swap renames/rmtrees whole stores — executor, not loop
            await asyncio.get_running_loop().run_in_executor(
                None, self._complete_install_swap, d)
        finally:
            # reopen no matter what — a failed swap must not leave the
            # tablet unserved until process restart
            await self._open_tablet(meta)
        return {"ok": True}

    def _snapshot_dir(self, tablet_id: str, snapshot_id: str,
                      subdir: str = "regular") -> str:
        return os.path.join(self._tablet_dir(tablet_id), "snapshots",
                            snapshot_id, os.path.basename(subdir))

    async def rpc_list_snapshot_files(self, payload) -> dict:
        d = self._snapshot_dir(payload["tablet_id"], payload["snapshot_id"],
                               payload.get("subdir", "regular"))
        if not os.path.isdir(d):
            raise RpcError("snapshot not found", "NOT_FOUND")
        files = [(n, os.path.getsize(os.path.join(d, n)))
                 for n in sorted(os.listdir(d))]
        return {"files": files}

    async def rpc_fetch_snapshot_file(self, payload):
        d = self._snapshot_dir(payload["tablet_id"], payload["snapshot_id"],
                               payload.get("subdir", "regular"))
        name = os.path.basename(payload["name"])   # no path escapes
        path = os.path.join(d, name)
        if not os.path.isfile(path):
            raise RpcError(f"no such snapshot file {name}", "NOT_FOUND")
        # blocking-ok: bounded 4MB chunk read (remote bootstrap)
        with open(path, "rb") as f:
            f.seek(payload.get("offset", 0))
            data = f.read(payload.get("length", 4 * 1024 * 1024))
        # remote bootstrap streams whole SSTs/WALs: the chunk rides as a
        # raw sidecar, skipping msgpack + per-frame zlib (reference:
        # sidecar-carried data in remote_bootstrap_service.cc)
        return Sidecars({"data": sidecar_ref(0)}, [data])

    # --- membership / leadership --------------------------------------------
    async def rpc_change_config(self, payload) -> dict:
        peer = self._peer(payload["tablet_id"])
        new_peers = [PeerSpec(e[0], tuple(e[1]),
                              e[2] if len(e) > 2 else "voter")
                     for e in payload["peers"]]
        idx = await peer.consensus.change_config(new_peers)
        return {"index": idx}

    async def rpc_wait_catchup(self, payload) -> dict:
        peer = self._peer(payload["tablet_id"])
        if not peer.is_leader():
            raise RpcError("not leader", "LEADER_NOT_READY")
        await peer.consensus.wait_for_catchup(payload["peer_uuid"])
        return {"ok": True}

    async def rpc_leader_stepdown(self, payload) -> dict:
        peer = self._peer(payload["tablet_id"])
        await peer.consensus.step_down(
            transfer_to=payload.get("target_uuid"))
        return {"ok": True}

    async def rpc_server_clock(self, payload) -> dict:
        """Current hybrid time — the master samples every involved
        tserver before picking a snapshot cut HT so the cut dominates
        all previously-acked writes (reference: the hybrid-time
        propagation that backs ReadHybridTime/snapshot selection)."""
        return {"ht": self.clock.now().value}

    # --- snapshots ----------------------------------------------------------
    async def rpc_create_snapshot(self, payload) -> dict:
        """Checkpoint one tablet under snapshots/<id> (reference:
        tablet/tablet_snapshots.cc:186 via hard links)."""
        peer = self._peer(payload["tablet_id"])
        if not peer.is_leader() and payload.get("leader_only", True):
            raise RpcError("not leader", "LEADER_NOT_READY")
        snapshot_ht = payload.get("snapshot_ht")
        if snapshot_ht:
            # single-HT cut: push the local HLC past the cut (future
            # writes land above it), then wait until every in-flight
            # write at-or-below it has been applied so the checkpoint
            # can't miss one
            from ..utils.hybrid_time import HybridTime
            self.clock.update(HybridTime(snapshot_ht))
            deadline = asyncio.get_running_loop().time() + 10.0
            while (peer.xcluster_safe_ht(self.clock.now().value)
                   < snapshot_ht):
                if asyncio.get_running_loop().time() > deadline:
                    raise RpcError("in-flight writes below the snapshot "
                                   "time did not drain", "TIMED_OUT")
                await asyncio.sleep(0.005)
        d = os.path.join(self._tablet_dir(payload["tablet_id"]),
                         "snapshots", payload["snapshot_id"])
        peer.tablet.create_snapshot(d)
        return {"ok": True, "dir": d, "ts_uuid": self.uuid}

    async def rpc_delete_snapshot(self, payload) -> dict:
        """Drop a tablet checkpoint dir (reference: DeleteTabletSnapshot
        in tablet/tablet_snapshots.cc). Idempotent."""
        d = os.path.join(self._tablet_dir(payload["tablet_id"]),
                         "snapshots", payload["snapshot_id"])
        await _rmtree_off_loop(d)
        return {"ok": True}

    async def rpc_split_tablet_raft(self, payload) -> dict:
        """Split via a Raft-replicated SplitOperation through the
        PARENT tablet's own log (reference: tablet/operations/
        split_operation.cc) — online (no quiesce: racing writes simply
        order before or after the split entry) and crash-consistent
        (every replica, and WAL replay after any crash, applies the
        same deterministic child copy at the same log position).
        Idempotent: a retried split of an already-split parent returns
        the same children."""
        parent_id = payload["parent_id"]
        parent = self._peer(parent_id)
        if parent.split_done or payload["left_id"] in self.peers:
            return {"ok": True, "already": True}
        if not parent.is_leader():
            raise RpcError("not leader", "LEADER_NOT_READY")
        if parent.participant._key_holder:
            # in-flight txn intents: their provisional records would
            # need to split too — keep the reference's behavior of
            # retrying after they resolve for the common path (children
            # DO inherit any intents that race in, via the filtered
            # intents copy + recover_from_store)
            raise RpcError("tablet has live transaction intents; retry "
                           "after they resolve", "TRY_AGAIN")
        import msgpack as _mp
        # fence BEFORE the entry: no write may order after the split
        # (writes re-check the fence INSIDE the append lock, so none can
        # slip behind the split entry while we wait for replication)
        parent.split_requested = True
        try:
            await parent.consensus.replicate("split", _mp.packb({
                "left_id": payload["left_id"],
                "right_id": payload["right_id"],
                "split_key": payload["split_key"],
                "partition": payload["partition"],
                "table": payload["table"],
                "raft_peers": payload["raft_peers"],
            }))
        except Exception:
            # lift the fence ONLY if the entry never reached our log
            # (LEADER_NOT_READY / precheck): the tablet would otherwise
            # reject every write forever. An appended-but-uncommitted
            # split entry ANYWHERE above last_applied keeps the fence —
            # it may still commit after us (non-fenced entries like a
            # term noop can sit above it, so scan, don't tail-check).
            pending_split = any(
                e.etype == "split"
                for e in parent.log.entries_from(
                    parent.consensus.last_applied + 1))
            if not pending_split:
                parent.split_requested = False
            raise
        return {"ok": True, "split_index": parent.consensus.last_applied}

    async def _apply_split(self, parent, d) -> None:
        """Raft-apply of a split entry (runs on EVERY replica and on
        WAL replay): create the children and copy the parent's state,
        filtered by the split key. Idempotent — replay with existing
        children is a no-op."""
        parent_id = parent.tablet.tablet_id
        split_key = bytes.fromhex(d["split_key"])
        if parent.split_done:
            return                      # replayed after a COMPLETE split

        # Each child gets a durable "split-complete" marker as the LAST
        # step of its build, BEFORE the parent's split_done flag. On
        # replay, a marked child is a finished copy that may already
        # hold acknowledged post-split writes — it must NOT be torn
        # down; only unmarked (half-built) children are redone.
        def _marker(child_id: str) -> str:
            return os.path.join(self._tablet_dir(child_id),
                                "split-complete.json")

        rebuild = []                    # (side, child_id) still to build
        children = {}                   # child_id -> peer
        for side, child_id in (("left", d["left_id"]),
                               ("right", d["right_id"])):
            if os.path.exists(_marker(child_id)):
                peer = self.peers.get(child_id)
                if peer is None:
                    # blocking-ok: tiny metadata file
                    with open(os.path.join(self._tablet_dir(child_id),
                                           "tablet-meta.json")) as f:
                        peer = await self._open_tablet(json.load(f))
                children[child_id] = peer
                continue
            stale = self.peers.pop(child_id, None)
            if stale is not None:
                await stale.shutdown()
            await _rmtree_off_loop(self._tablet_dir(child_id))
            rebuild.append((side, child_id))
        for side, child_id in rebuild:
            part = d["partition"]
            cpart = ([part[0], d["split_key"]] if side == "left"
                     else [d["split_key"], part[1]])
            meta = {
                "tablet_id": child_id, "table": d["table"],
                "partition": cpart, "raft_peers": d["raft_peers"],
                "is_status_tablet": False,
            }
            cd = self._tablet_dir(child_id)
            os.makedirs(cd, exist_ok=True)
            await _atomic_json_off_loop(
                os.path.join(cd, "tablet-meta.json"), meta)
            peer = await self._open_tablet(meta)
            children[child_id] = peer

        def side_of(k: bytes):
            # partition key = 2-byte hash prefix of the doc key
            pk = k[1:3] if k and k[0] == 0x08 else k[:2]
            return pk < split_key

        # deterministic local copy of parent rows (and in-flight
        # intents — children rebuild participant state from their
        # filtered IntentsDB copies) into the children being built:
        # one pass over the parent stores fills both sides' batches
        from ..storage.lsm import WriteBatch
        want = {cid for _, cid in rebuild}
        reg = {cid: WriteBatch() for cid in want}
        intents = {cid: WriteBatch() for cid in want}
        if want:
            for k, v in parent.tablet.regular.iterate():
                cid = d["left_id"] if side_of(k) else d["right_id"]
                if cid in want:
                    reg[cid].put(k, v)
            for k, v in parent.tablet.intents.iterate():
                cid = d["left_id"] if side_of(k) else d["right_id"]
                if cid in want:
                    intents[cid].put(k, v)
        for cid in want:
            ch = children[cid]
            ch.tablet.regular.apply(reg[cid])
            if intents[cid].entries:
                ch.tablet.intents.apply(intents[cid])
            ch.tablet.flush()
            # crash fidelity seam (real-process harness): die with the
            # child's data copied but its split-complete marker absent —
            # restart must rebuild this child from the replayed entry
            TEST_CRASH_POINT("split:before_marker")
            ch.participant.recover_from_store()
            # siblings recorded so the decision-routing map rebuilds
            # COMPLETELY from any one child (the other may live on a
            # different tserver after a balancer move)
            await _atomic_json_off_loop(_marker(cid), {
                "parent": parent_id,
                "siblings": [d["left_id"], d["right_id"]]})
        # persist the split state so a restarted replica keeps
        # rejecting parent ops even before WAL replay reaches the entry
        meta_path = os.path.join(self._tablet_dir(parent_id),
                                 "tablet-meta.json")
        self._split_children[parent_id] = [d["left_id"], d["right_id"]]
        try:
            with open(meta_path) as f:   # blocking-ok: tiny meta
                pmeta = json.load(f)
            pmeta["split_done"] = True
            pmeta["split_children"] = [d["left_id"], d["right_id"]]
            await _atomic_json_off_loop(meta_path, pmeta)
        except FileNotFoundError:
            pass

    async def rpc_tablet_status(self, payload) -> dict:
        """Cheap per-replica probe used by the master's split barrier."""
        peer = self.peers.get(payload["tablet_id"])
        if peer is None:
            return {"exists": False}
        return {"exists": True, "split_done": peer.split_done,
                "last_applied": peer.consensus.last_applied,
                "is_leader": peer.is_leader()}

    # (master split barrier probes the PARENT's split_done — see
    # master/master.py rpc_split_tablet)

    async def rpc_flush(self, payload) -> dict:
        peer = self._peer(payload["tablet_id"])

        async def run():
            return {"path": peer.tablet.flush()}
        return await self.scheduler.submit(Lane.MAINTENANCE, run)

    async def rpc_compact(self, payload) -> dict:
        peer = self._peer(payload["tablet_id"])

        async def run():
            # executor: the merge must not stall the event loop; the
            # maintenance lane bounds how many run at once
            return {"path": await asyncio.get_running_loop()
                    .run_in_executor(None, peer.tablet.compact)}
        return await self.scheduler.submit(Lane.MAINTENANCE, run)

    # --- transactions -------------------------------------------------------
    async def rpc_txn_write(self, payload) -> dict:
        peer = self._peer(payload["tablet_id"])
        req = write_request_from_wire(payload["req"])
        if req.schema_version is not None:
            cur = peer.tablet.schema_version_of(req.table_id)
            if cur is not None and req.schema_version != cur:
                raise RpcError(
                    f"schema version mismatch for {req.table_id}: "
                    f"request {req.schema_version}, tablet {cur}",
                    "SCHEMA_MISMATCH")

        async def run():
            n = await peer.write_txn(
                req, payload["txn_id"], payload["start_ht"],
                payload.get("status_tablet"),
                payload.get("op_read_hts"), payload.get("sub_id", 0))
            return {"rows_affected": n}
        # TXN lane is admission-only (bounded + sheddable, but every
        # admitted request dispatches immediately): an intent write may
        # wait on a conflicting txn whose apply/rollback arrives as
        # another request — queueing those behind each other in a
        # bounded worker pool could deadlock
        return await self.scheduler.submit(
            Lane.TXN, run, cost_bytes=256 + 256 * len(req.ops))

    async def rpc_truncate_tablet(self, payload) -> dict:
        """Raft-replicated tablet truncate (reference: TruncateRequest
        through the tablet service)."""
        peer = self._peer(payload["tablet_id"])
        ht = await peer.truncate(payload["table_id"],
                                 payload.get("ht"))
        return {"ok": True, "ht": ht}

    async def rpc_txn_rollback_sub(self, payload) -> dict:
        """ROLLBACK TO SAVEPOINT: prune this participant's intents with
        sub_id >= from_sub (reference: RollbackToSubTransaction,
        tserver/pg_client.proto).  Routed through splits like
        apply/rollback — a split parent's in-flight intents were copied
        to its children, so the prune must reach every child or the
        rolled-back writes would commit there."""
        await self._drive_txn_decision(payload["tablet_id"],
                                       "txn_rollback_sub", payload)
        return {"ok": True}

    async def _drive_txn_decision(self, tablet_id: str, method: str,
                                  payload: dict) -> None:
        """Land a txn apply/rollback in the right log(s) through splits:
        a split parent's in-flight intents were copied to its children,
        so the decision must reach EVERY child — local children via
        their leader, remote/follower children by forwarding the same
        RPC to their replicas (children elect leaders independently, so
        the two can live on different tservers). Succeeds only when all
        targets got the decision; mid-split or unreachable → retriable
        (the coordinator re-drives)."""
        peer = self.peers.get(tablet_id)
        if peer is not None:
            if peer.split_requested and not peer.split_done:
                raise RpcError("tablet splitting; retry", "TRY_AGAIN")
            if not peer.split_done:
                if not peer.is_leader():
                    raise RpcError("not leader", "LEADER_NOT_READY")
                if method == "apply_txn":
                    await peer.apply_txn(payload["txn_id"],
                                         payload["commit_ht"])
                elif method == "txn_rollback_sub":
                    await peer.rollback_sub_txn(payload["txn_id"],
                                                payload["from_sub"])
                else:
                    await peer.rollback_txn(payload["txn_id"])
                return
        # split parent (possibly already deleted — the children's
        # split-complete markers rebuild the routing map on restart)
        children = self._split_children.get(tablet_id, [])
        if not children:
            if peer is None:
                raise RpcError(f"tablet {tablet_id} not found",
                               "NOT_FOUND")
            raise RpcError("tablet split; children unknown here",
                           "TRY_AGAIN")
        for cid in children:
            cpeer = self.peers.get(cid)
            if cpeer is not None and cpeer.is_leader():
                await self._drive_txn_decision(cid, method,
                                               {**payload,
                                                "tablet_id": cid})
                continue
            # forward to the child's replicas (its own config if local,
            # else the parent's replica set the child was created on)
            fallback = cpeer if cpeer is not None else peer
            if fallback is None:
                raise RpcError(f"child {cid} unknown here", "TRY_AGAIN")
            addrs = [p.addr for p in fallback.consensus.config.peers]
            delivered = False
            for addr in addrs:
                if addr == self.messenger.addr:
                    continue
                try:
                    await self.messenger.call(
                        addr, "tserver", method,
                        {**payload, "tablet_id": cid}, timeout=5.0)
                    delivered = True
                    break
                except (RpcError, asyncio.TimeoutError, OSError):
                    continue
            if not delivered:
                raise RpcError(f"child {cid} unreachable for {method}",
                               "TRY_AGAIN")

    async def rpc_apply_txn(self, payload) -> dict:
        async def run():
            await self._drive_txn_decision(payload["tablet_id"],
                                           "apply_txn", payload)
            return {"ok": True}
        return await self.scheduler.submit(Lane.TXN, run, cost_bytes=256)

    async def rpc_txn_lock_rows(self, payload) -> dict:
        """Bulk SERIALIZABLE read locks for rows a txn scanned (the SQL
        SELECT read-set; reference: row-level read intents taken by
        serializable reads in docdb)."""
        peer = self._peer(payload["tablet_id"])
        codec = peer.tablet._codec_for(payload.get("table_id", ""))
        keys = [codec.doc_key_prefix(r) for r in payload["rows"]]
        await peer.lock_reads(keys, payload["txn_id"],
                              payload.get("read_ht") or 0,
                              payload.get("status_tablet"))
        return {"locked": len(keys)}

    async def rpc_txn_release_reads(self, payload) -> dict:
        peer = self._peer(payload["tablet_id"])
        if not peer.is_leader():
            raise RpcError("not leader", "LEADER_NOT_READY")
        # replicated: read-lock acquisition goes through Raft, so the
        # release must too — otherwise followers (future leaders)
        # accumulate phantom locks for long-committed readers
        import msgpack as _mp
        await peer.consensus.replicate(
            "txn_read_unlock", _mp.packb({"txn_id": payload["txn_id"]}))
        return {"ok": True}

    async def rpc_rollback_txn(self, payload) -> dict:
        async def run():
            await self._drive_txn_decision(payload["tablet_id"],
                                           "rollback_txn", payload)
            return {"ok": True}
        return await self.scheduler.submit(Lane.TXN, run, cost_bytes=256)

    async def rpc_txn_get(self, payload) -> dict:
        """Point get inside a txn: own-intent overlay, else snapshot read
        at the txn start time. Under SERIALIZABLE the read takes a
        shared read lock first, so later writers conflict (write-skew
        protection)."""
        from ..docdb.operations import ReadRequest
        peer = self._peer(payload["tablet_id"])
        lock_ht = None
        if payload.get("for_update"):
            # locking read: claim the key exclusively (waiting out the
            # current holder), then read the LATEST committed version —
            # the reference's SELECT ... FOR UPDATE / READ COMMITTED
            # statement-read shape
            codec = peer.tablet._codec_for(payload.get("table_id", ""))
            key = codec.doc_key_prefix(payload["pk_row"])
            lock_ht = await peer.lock_for_update(
                [key], payload["txn_id"], payload.get("read_ht") or 0,
                payload.get("status_tablet"))
        elif payload.get("serializable"):
            codec = peer.tablet._codec_for(payload.get("table_id", ""))
            key = codec.doc_key_prefix(payload["pk_row"])
            await peer.lock_reads([key], payload["txn_id"],
                                  payload.get("read_ht") or 0,
                                  payload.get("status_tablet"))
        own = peer.read_own_intent(payload["txn_id"], payload["pk_row"],
                                   payload.get("table_id", ""))
        if own is not None:
            kind, row = own[0], own[1]
            if kind == "delete":
                return {"row": None, "from_intent": True,
                        **({"lock_ht": lock_ht} if lock_ht else {})}
            return {"row": row, "from_intent": True,
                    **({"lock_ht": lock_ht} if lock_ht else {})}
        req = ReadRequest(payload.get("table_id", ""),
                          pk_eq=payload["pk_row"],
                          read_ht=lock_ht or payload.get("read_ht"))
        resp = await peer.read(req)
        return {"row": resp.rows[0] if resp.rows else None,
                **({"lock_ht": lock_ht} if lock_ht else {})}

    # coordinator RPCs (valid on the caught-up status tablet leader)
    def _coordinator(self, tablet_id: str):
        peer = self._peer(tablet_id)
        if peer.coordinator is None:
            raise RpcError(f"{tablet_id} is not a status tablet",
                           "INVALID_ARGUMENT")
        if self.master_addrs and not peer.coordinator.master_addrs:
            # dead-participant arbitration needs the tablet registry
            # owner (covers every peer-creation site: create,
            # bootstrap, remote bootstrap)
            peer.coordinator.master_addrs = list(self.master_addrs)
        if not peer.is_leader():
            raise RpcError("not leader", "LEADER_NOT_READY")
        # A just-elected leader that hasn't applied its predecessors'
        # entries yet would answer "unknown txn" = ABORTED for a
        # COMMITTED transaction — participants would then roll back
        # committed intents (atomicity violation). Gate on the term-
        # opening noop being applied (reference: status answered only
        # by the caught-up status-tablet leader; same gate the master
        # catalog reads use).
        c = peer.consensus
        if c.last_applied < c.term_start_index:
            raise RpcError(
                f"leader not caught up (applied={c.last_applied} "
                f"term_start={c.term_start_index})", "LEADER_NOT_READY")
        return peer.coordinator

    async def rpc_txn_begin(self, payload) -> dict:
        return await self._coordinator(payload["tablet_id"]).begin(payload)

    async def rpc_txn_commit(self, payload) -> dict:
        return await self._coordinator(payload["tablet_id"]).commit(payload)

    async def rpc_txn_abort(self, payload) -> dict:
        return await self._coordinator(payload["tablet_id"]).abort(payload)

    async def rpc_txn_report_waits(self, payload) -> dict:
        """Participant-reported wait-for edges feeding the probe-based
        deadlock detector (reference: docdb/deadlock_detector.cc)."""
        return await self._coordinator(
            payload["tablet_id"]).report_waits(payload)

    async def rpc_txn_probe(self, payload) -> dict:
        return await self._coordinator(payload["tablet_id"]).probe(payload)

    async def rpc_txn_status(self, payload) -> dict:
        # leader + catch-up gated: a follower (or stale new leader)
        # answering "unknown = ABORTED" for a committed txn would lose
        # committed writes on the asking participant
        return await self._coordinator(
            payload["tablet_id"]).status(payload)

    # --- vector indexes ------------------------------------------------------
    async def rpc_build_vector_index(self, payload) -> dict:
        peer = self._peer(payload["tablet_id"])

        async def run():
            # executor: the build (scan + k-means / graph construction)
            # must not stall the event loop, and the per-index build
            # lock serializes it against the background fold which also
            # runs in an executor thread
            n = await asyncio.get_running_loop().run_in_executor(
                None, lambda: peer.tablet.build_vector_index(
                    payload["column"], payload.get("lists", 100),
                    payload.get("method", "ivfflat"),
                    payload.get("options")))
            return {"indexed": n}
        return await self.scheduler.submit(Lane.MAINTENANCE, run)

    async def rpc_vector_search(self, payload) -> dict:
        peer = self._peer(payload["tablet_id"])
        hits = peer.tablet.vector_search(
            payload["column"], payload["query"], payload.get("k", 10),
            payload.get("nprobe", 8), payload.get("ef_search"))
        return {"hits": [[pk, d] for pk, d in hits]}

    # --- CDC (reference: src/yb/cdc/cdc_service.cc GetChanges) --------------
    async def rpc_get_changes(self, payload) -> dict:
        """Change stream from the tablet's Raft log: plain writes as
        committed changes; transactional intents as provisional records
        with begin/commit/abort markers — the CDC-SDK shape (reference:
        cdc/cdcsdk_producer.cc)."""
        import msgpack as _mp
        peer = self._peer(payload["tablet_id"])
        from_index = payload.get("from_index", 0)
        limit = payload.get("limit", 1000)
        if from_index < 0:
            # tail seek (resync bootstrap): report the current committed
            # position — held back below any LIVE txn's first intent so
            # its eventual commit can re-read the intents — without any
            # changes, so the consumer streams from "now" after a full
            # copy
            tail = peer.consensus.commit_index
            oldest = peer.participant.oldest_live_intent_index()
            if oldest is not None:
                tail = min(tail, oldest - 1)
            return {"changes": [],
                    "checkpoint": tail,
                    "safe_ht": peer.xcluster_safe_ht(
                        self.clock.now().value)
                    if peer.is_leader() else 0}
        if from_index + 1 < peer.log._first_index:
            # WAL GC trimmed past this consumer's checkpoint — the gap is
            # unrecoverable from the log; the consumer must resync
            raise RpcError(
                f"changes from {from_index} were garbage-collected "
                f"(log starts at {peer.log._first_index})",
                "CACHE_MISS_ERROR")
        changes = []
        last = from_index
        for e in peer.log.entries_from(from_index + 1, limit):
            if e.index > peer.consensus.commit_index:
                break
            last = e.index
            if e.etype == "write":
                d = _mp.unpackb(e.payload, raw=False)
                for item in (d["batch"] if "batch" in d else [d]):
                    for op in item["req"]["ops"]:
                        changes.append({"op": op[0], "row": op[1],
                                        "ht": item["ht"],
                                        "index": e.index})
            elif e.etype == "txn_intents":
                d = _mp.unpackb(e.payload, raw=False)
                for op in d["req"]["ops"]:
                    changes.append({"op": op[0], "row": op[1],
                                    "txn_id": d["txn_id"],
                                    "sub": d.get("sub", 0),
                                    "provisional": True, "index": e.index})
            elif e.etype == "txn_sub_rollback":
                # ROLLBACK TO SAVEPOINT: consumers discard this txn's
                # buffered provisional records from THIS tablet with
                # sub >= from_sub (log order guarantees the discarded
                # intents came first and any later ones are a fresh
                # subtransaction)
                d = _mp.unpackb(e.payload, raw=False)
                changes.append({"op": "abort_sub", "txn_id": d["txn_id"],
                                "from_sub": d["from_sub"],
                                "index": e.index})
            elif e.etype == "txn_apply":
                d = _mp.unpackb(e.payload, raw=False)
                changes.append({"op": "commit", "txn_id": d["txn_id"],
                                "ht": d["commit_ht"], "index": e.index})
            elif e.etype == "txn_rollback":
                d = _mp.unpackb(e.payload, raw=False)
                changes.append({"op": "abort", "txn_id": d["txn_id"],
                                "index": e.index})
            elif e.etype == "truncate":
                d = _mp.unpackb(e.payload, raw=False)
                changes.append({"op": "truncate",
                                "table_id": d.get("table_id", ""),
                                "ht": d.get("ht", 0), "index": e.index})
            elif e.etype == "split":
                # the write fence guarantees nothing CDC-relevant orders
                # after this entry: consumers retire the parent stream
                # here and adopt the children (reference: CDC-through-
                # split handling, cdcsdk_virtual_wal.cc GetTabletListAnd
                # CheckOnBootstrap + children checkpoint seeding)
                d = _mp.unpackb(e.payload, raw=False)
                changes.append({"op": "split", "index": e.index,
                                "children": [d["left_id"], d["right_id"]]})
        # xCluster safe time (reference: GetChanges safe_hybrid_time,
        # xcluster_safe_time_service.cc): when the consumer has drained
        # to commit_index, every future commit on this leader gets
        # HT > now, so "now" is safe; otherwise the last streamed HT is.
        if last >= peer.consensus.commit_index and peer.is_leader():
            safe_ht = peer.xcluster_safe_ht(self.clock.now().value)
        else:
            safe_ht = max((c["ht"] for c in changes if "ht" in c),
                          default=0)
        return {"changes": changes, "checkpoint": last,
                "safe_ht": safe_ht}

    async def rpc_mem_trackers(self, payload) -> dict:
        """Memory accounting rollup (reference: util/mem_tracker.h
        hierarchy surfaced at /mem-trackers)."""
        out = {}
        for tid, p in self.peers.items():
            out[tid] = {
                "memtable_bytes": p.tablet.regular._mem.approximate_bytes(),
                "sst_bytes": sum(r.file_size
                                 for r in p.tablet.regular.ssts),
                "wal_entries": len(p.log._entries),
            }
        return {"tablets": out}

    async def rpc_scheduler_stats(self, payload) -> dict:
        """Live scheduler lane stats (depths, sheds, wait/batch/fanin
        histograms) — the webserver /scheduler endpoint reads
        these."""
        return {"enabled": self.scheduler.enabled(),
                "lanes": self.scheduler.stats()}

    async def rpc_status(self, payload) -> dict:
        return {
            "uuid": self.uuid,
            "tablets": {
                tid: {"leader": p.is_leader(),
                      "size": p.tablet.approximate_size(),
                      "ssts": p.tablet.num_sst_files()}
                for tid, p in self.peers.items()
            },
        }

    # --- cross-process control endpoint (cluster/ harness) -----------------
    # The supervisor/chaos controller's seam into a running server:
    # fault arming and metric snapshots must be reachable from OUTSIDE
    # the process (ISSUE 10 satellite).  The env handshake in
    # server_main covers points that must be live before the first
    # request; these RPCs cover everything armed mid-run.

    async def rpc_arm_fault(self, payload) -> dict:
        """Arm crash/sync/stall fault state in THIS process from a spec
        dict (utils/fault_injection.arm_from_spec); `clear_all` resets
        first.  Returns the resulting fault status."""
        from ..utils import fault_injection as fi
        return {"status": fi.arm_from_spec(payload or {})}

    async def rpc_fault_status(self, payload) -> dict:
        from ..utils import fault_injection as fi
        return {"status": fi.fault_status()}

    async def rpc_metrics_snapshot(self, payload) -> dict:
        """Process-wide metric snapshot + per-tablet store stats — the
        supervisor's assertion surface (cross-process analog of reading
        utils/metrics.REGISTRY in-process)."""
        from ..utils import fault_injection as fi
        from ..utils import metrics as _metrics
        return {
            "uuid": self.uuid,
            **_metrics.snapshot(),
            "faults": fi.fault_status(),
            "scheduler": {"enabled": self.scheduler.enabled(),
                          "lanes": self.scheduler.stats()},
            "tablets": {
                tid: {"leader": p.is_leader(),
                      "size": p.tablet.approximate_size(),
                      "ssts": p.tablet.num_sst_files(),
                      "wal_index": p.consensus.last_applied,
                      "pins": p.tablet.regular.pin_stats(),
                      # async-flush visibility: frozen memtables still
                      # awaiting the background flush executor
                      "frozen_memtables":
                          p.tablet.regular.frozen_count()}
                for tid, p in self.peers.items()},
        }

    async def rpc_bypass_scan(self, payload) -> dict:
        """Serve an aggregate scan through the analytics bypass engine
        over THIS process's local replicas — the "bypass from a REAL
        separate replica process" shape (Breaking Database Lock-in):
        the session pins this node's SSTs and scans them in an executor
        thread, so a replica process can serve analytics while the
        leader process's event loop never sees the query.  Leadership
        is NOT required: a follower's applied state plus the pinner's
        MVCC safe-time wait give a consistent snapshot."""
        from ..bypass import BypassIneligible, BypassSession
        from ..docdb.wire import read_request_from_wire
        if not flags.get("bypass_reader_enabled"):
            raise RpcError("bypass_reader_enabled is off on this server",
                           "BYPASS_DISABLED")
        table_id = payload["table_id"]
        req = read_request_from_wire(payload["req"])
        if req.group_by is not None:
            raise RpcError("remote bypass serves flat aggregates only",
                           "BYPASS_INELIGIBLE")
        peers = [p for _tid, p in sorted(self.peers.items())
                 if not p.split_done and table_id in p.tablet.tables()]
        if not peers:
            raise RpcError(f"no local replica of table {table_id}",
                           "NOT_FOUND")

        from ..utils import trace as _trace
        tctx = _trace.current_context()   # executor threads see no
                                          # contextvars: bridge explicitly

        def _run():
            with _trace.use_context(tctx), \
                    _trace.TRACES.span("bypass.scan", child_only=True), \
                    wait_status("Bypass_Scan", component="bypass"):
                with BypassSession(peers, read_ht=req.read_ht,
                                   table_id=table_id) as s:
                    self._bypass_sessions.add(s)
                    try:
                        outs, counts, stats = s.scan_aggregate(
                            req.where, req.aggregates, group=req.group_by)
                        return ([float(x) for x in outs],
                                s.read_ht, stats)
                    finally:
                        self._bypass_sessions.discard(s)
        try:
            outs, read_ht, stats = await asyncio.get_running_loop() \
                .run_in_executor(None, _run)
        except BypassIneligible as e:
            raise RpcError(f"bypass ineligible: {e.reason}",
                           "BYPASS_INELIGIBLE")
        return {"agg_values": outs, "read_ht": read_ht,
                "stats": {k: v for k, v in (stats or {}).items()
                          if isinstance(v, (int, float, str, bool))}}

    async def rpc_tracez(self, payload) -> dict:
        """Sampled span dump + ASH wait-state histograms for THIS
        process, pid+timestamp stamped — the cross-process face of the
        observability layer (CLUSTER.md; cluster/collector.py stitches
        dumps from every process into span trees)."""
        from ..utils import trace as _trace
        out = _trace.TRACES.tracez()
        out["uuid"] = self.uuid
        return out

    async def rpc_set_flag(self, payload) -> dict:
        """Hot-update a runtime flag on THIS server (reference:
        yb-ts-cli set_flag / server/server_base_options flag RPC)."""
        from ..utils import flags as _flags
        name = payload["name"]
        # unknown flag -> KeyError -> RPC error surface
        old, value = _flags.coerce_and_set(name, payload["value"])
        return {"name": name, "old": old, "value": value}

    async def rpc_list_flags(self, payload) -> dict:
        from ..utils import flags as _flags
        return {"flags": {n: repr(f.value)
                          for n, f in _flags.REGISTRY.items()}}

    # --- heartbeats -------------------------------------------------------
    def _register_ash_providers(self) -> None:
        """Component wait-state providers for the ASH sampler: the
        scheduler's lanes, the flush executor, raft and compaction —
        coarse "is this component busy/backlogged" signals.  The
        sampler dedupes them against states already published by
        wait_status scopes that tick (the session-weighted signal
        wins; providers only fill the gaps).  Handles are kept so
        shutdown can UNREGISTER — the sampler is process-global, and
        a dead server's closures must not keep reporting."""
        from ..consensus.raft import REPLICATE_INFLIGHT

        def sched_provider():
            queued = sum(st.queued
                         for st in self.scheduler.lanes.values())
            return (f"sched:{self.uuid}",
                    "SchedQueue_Wait" if queued else "Idle")

        def flush_provider():
            frozen = sum(p.tablet.regular.frozen_count()
                         for p in list(self.peers.values()))
            return (f"flush:{self.uuid}",
                    "Flush_SstWrite" if frozen else "Idle")

        def raft_provider():
            return (f"raft:{self.uuid}", "Raft_Replicate"
                    if REPLICATE_INFLIGHT["n"] > 0 else "Idle")

        def compaction_provider():
            st = self.scheduler.lanes.get(Lane.MAINTENANCE)
            busy = st is not None and st.inflight > 0
            return (f"compaction:{self.uuid}",
                    "Compaction_Run" if busy else "Idle")

        self._ash_providers = [sched_provider, flush_provider,
                               raft_provider, compaction_provider]
        for p in self._ash_providers:
            ASH.register(p)

    async def _heartbeat_loop(self):
        self._register_ash_providers()
        loop = asyncio.get_running_loop()
        ticks = 0
        while self._running:
            await self._heartbeat_once()
            if ASH._thread is None:
                # no background sampler in this process (in-process
                # test clusters): the heartbeat keeps ASH minimally
                # live; server_main/ybtpud run the real thread
                ASH.sample_once()
            ticks += 1
            peers = list(self.peers.values())
            if ticks % 10 == 0:      # ~every 2s: txn coordinator sweep
                await self._maintain(
                    "coordinator_sweep",
                    [p for p in peers
                     if p.coordinator is not None and p.is_leader()],
                    lambda p: p.coordinator.sweep())
            if ticks % 25 == 0:      # ~every 5s: WAL retention pass
                await self._maintain("wal_gc", peers,
                                     lambda p: p.maybe_gc_log())
            if ticks % 50 == 0:      # ~every 10s: background compaction
                # (reference: full_compaction_manager.cc + the priority
                # compaction pool; size-tiered trigger at >= 4 SSTs)
                await self._maintain(
                    "compaction",
                    [p for p in peers
                     if p.is_leader() and p.tablet.num_sst_files() >= 4],
                    self._background_compact)
                # fold outgrown vector-index deltas back into the
                # frozen IVF chunks (vector-LSM background compaction)
                await self._maintain(
                    "vector_fold",
                    [p for p in peers if p.tablet.vector_indexes],
                    lambda p: loop.run_in_executor(
                        None, p.tablet.maybe_rebuild_vector_indexes))
            due = loop.time() + 0.2
            await asyncio.sleep(0.2)
            self._m_tick_late.increment((loop.time() - due) * 1e3)

    async def _maintain(self, what: str, peers: list, fn) -> None:
        """One periodic pass of the heartbeat loop over `peers`, each
        through `fn(peer)` (awaited where it returns an awaitable), as
        one `tserver.maintenance` root span: the pass runs on the loop
        that serves reads, so a statement that stalled behind it can
        name it.  A pass with nothing to do leaves no span."""
        if not peers:
            return
        with TRACES.span("tserver.maintenance", parent=None,
                         tags={"what": what, "tablets": len(peers)}):
            for p in peers:
                try:
                    r = fn(p)
                    if inspect.isawaitable(r):
                        await r
                except Exception:   # noqa: BLE001 — the loop goes on
                    log.exception("%s failed for %s", what,
                                  p.tablet.tablet_id)

    async def _background_compact(self, p: TabletPeer) -> None:
        async def run():
            await asyncio.get_running_loop().run_in_executor(
                None, lambda: p.tablet.compact(major=False))
        # maintenance lane: bounded + isolated from the foreground
        # lanes' dispatch slots
        await self.scheduler.submit(Lane.MAINTENANCE, run)

    async def _heartbeat_once(self):
        report = {
            "ts_uuid": self.uuid,
            "addr": list(self.messenger.addr),
            "zone": self.zone,
            **({"device_chips": self.mesh_reader.chips}
               if self.mesh_reader is not None else {}),
            "tablets": [
                {"tablet_id": tid, "is_leader": p.is_leader(),
                 "size_bytes": p.tablet.approximate_size(),
                 "num_ssts": p.tablet.num_sst_files(),
                 # applied WAL position: the master differentiates
                 # successive reports into a write rate (the auto-split
                 # traffic trigger's input)
                 "wal_index": p.consensus.last_applied}
                for tid, p in self.peers.items()
            ],
        }
        for addr in self.master_addrs:
            try:
                await self.messenger.call(tuple(addr), "master-heartbeat",
                                          "ts_heartbeat", report,
                                          timeout=2.0)
            except (RpcError, asyncio.TimeoutError, OSError):
                continue
