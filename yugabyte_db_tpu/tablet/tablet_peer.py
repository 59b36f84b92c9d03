"""TabletPeer: a tablet replica driven by Raft.

Analog of the reference's TabletPeer + OperationDriver
(reference: src/yb/tablet/tablet_peer.cc:759 Submit,
tablet/operations/operation_driver.cc): writes serialize into Raft log
entries; once committed they apply to the tablet state machine with the
leader-assigned hybrid time. Bootstrap replays WAL entries newer than
the LSM's flushed frontier (reference: tablet/tablet_bootstrap.cc:584
PlaySegments, ShouldReplayOperation :1138).
"""
from __future__ import annotations

import asyncio
import os
from typing import Optional

import msgpack

from ..consensus import Log, LogEntry, RaftConfig, RaftConsensus
from ..docdb.operations import ReadRequest, ReadResponse, WriteRequest, \
    WriteResponse
from ..docdb.wire import write_request_from_wire, write_request_to_wire
from ..rpc.messenger import Messenger, RpcError
from ..utils import trace as _trace
from ..utils.hybrid_time import HybridClock, HybridTime
from ..utils.trace import wait_status
from .tablet import Tablet

class TabletPeer:
    def __init__(self, tablet: Tablet, uuid: str, config: RaftConfig,
                 messenger: Messenger, clock: Optional[HybridClock] = None,
                 is_status_tablet: bool = False):
        from .transactions import TransactionCoordinator, TransactionParticipant
        self.tablet = tablet
        self.uuid = uuid
        self.clock = clock or tablet.clock
        wal_dir = os.path.join(tablet.dir, "wals")
        self.log = Log(wal_dir)
        self.consensus = RaftConsensus(
            tablet.tablet_id, uuid, config, self.log, messenger,
            tablet.dir, self._apply_entry, clock=self.clock)
        self.participant = TransactionParticipant(self)
        self.coordinator = (TransactionCoordinator(self, messenger)
                            if is_status_tablet else None)
        self._write_queue: list = []
        self._batcher_task = None
        # leader-memory reservations for in-flight 'insert' ops (unique
        # index gate: check + reserve happen atomically on the loop)
        self._pending_inserts: set = set()
        self.on_alter = None      # tserver persists new schema to meta
        # Raft-replicated split (reference: tablet/operations/
        # split_operation.cc): the tserver installs the apply hook; a
        # split parent stops serving and hints clients to re-route
        self.on_split = None
        self.split_done = False
        # write fence: set BEFORE the split entry replicates so no new
        # write/intent entry can order AFTER it in the log (an entry
        # behind the split would apply only to the doomed parent — a
        # lost acknowledged write)
        self.split_requested = False
        # wakes safe-time waiters when writes drain / entries apply
        self._progress_event = asyncio.Event()

    def split_fence_check(self) -> None:
        """Passed as `precheck` into consensus.replicate for every
        data entry: runs inside the append lock, so no write/intent/
        apply can take a log position after the split entry (the
        check-then-await window would otherwise let one slip in while
        waiting for the lock)."""
        if self.split_requested or self.split_done:
            raise RpcError("tablet has been split", "TABLET_SPLIT")

    async def alter(self, table_wire: dict):
        if not self.consensus.is_leader():
            raise RpcError("not leader", "LEADER_NOT_READY")
        await self.consensus.replicate(
            "alter", msgpack.packb({"table": table_wire}))

    # --- lifecycle --------------------------------------------------------
    async def start(self):
        self._bootstrap()
        # Freshly remote-bootstrapped / snapshot-installed replica: the
        # flushed store covers effects past the (empty or wiped) log.
        # Publish that floor so consensus accepts entries starting just
        # above it and never waits for entries that exist only as
        # snapshot state (reference: remote bootstrap + InstallSnapshot
        # semantics — snapshot covers committed entries only).
        fr = self.tablet.regular.flushed_frontier().get("op_id")
        if fr and int(fr[1]) > self.log.last_index:
            if self.log.all_entries():
                # the whole log sits below the store's frontier (can
                # only happen around snapshot install): keeping it
                # would leave an index gap once replication resumes
                # past the frontier — every entry in it is obsolete
                self.log.wipe()
            c = self.consensus
            c.snapshot_base_index = int(fr[1])
            c.commit_index = max(c.commit_index, c.snapshot_base_index)
            c.last_applied = max(c.last_applied, c.snapshot_base_index)
        # intents that arrived as SST files (snapshot install / remote
        # bootstrap) have no WAL entries to replay — rebuild participant
        # state from the IntentsDB (idempotent with WAL replay)
        self.participant.recover_from_store()
        self.consensus.on_peer_needs_bootstrap = self._bootstrap_lagging_peer
        self.consensus.on_applied = self._notify_progress
        await self.consensus.start()

    async def _bootstrap_lagging_peer(self, peer):
        """Leader-driven snapshot install for a follower behind our WAL
        GC horizon (reference: remote bootstrap triggered for peers the
        log can no longer catch up, tserver/remote_bootstrap_*.cc).
        Creates a local checkpoint and asks the lagging peer's tserver
        to fetch + swap it in. Returns the snapshot's frontier index so
        the leader resumes replication exactly past it. The checkpoint
        runs synchronously ON the event loop: applies cannot interleave
        between the regular and intents checkpoints (consistent cut)."""
        import shutil
        import uuid as _uuid
        snapshot_id = f"rbs-{_uuid.uuid4().hex[:12]}"
        d = os.path.join(self.tablet.dir, "snapshots", snapshot_id)
        # bulk flush off-loop first (a large memtable flush on the event
        # loop would stall heartbeats past the election timeout); the
        # create_snapshot call on the loop then re-flushes near-nothing
        # and hard-links, keeping the regular/intents cut consistent
        loop = asyncio.get_running_loop()
        await loop.run_in_executor(None, self.tablet.flush)
        await loop.run_in_executor(None, self.tablet.intents.flush)
        # deliberate on-loop consistent cut: both stores were just
        # flushed off-loop, so this flush is near-empty, and yielding
        # between the regular and intents checkpoints would let a txn
        # apply interleave the cut
        # analysis-ok(async_blocking): bounded near-empty barrier
        frontier = self.tablet.create_snapshot(d)
        try:
            await self.consensus.messenger.call(
                peer.addr, "tserver", "install_snapshot",
                {"tablet_id": self.tablet.tablet_id,
                 "snapshot_id": snapshot_id,
                 "src_addr": list(self.consensus.messenger.addr)},
                timeout=120.0)
        finally:
            # the snapshot dir is a whole checkpoint (hard links, but
            # potentially thousands of entries) — delete off-loop
            await loop.run_in_executor(
                None, lambda: shutil.rmtree(d, ignore_errors=True))
        return frontier

    def _bootstrap(self):
        """WAL replay on restart happens THROUGH Raft: consensus restarts
        with commit_index 0 and re-applies every entry as it re-commits
        (after the new leader's no-op). Re-application is idempotent —
        a write re-applies to byte-identical KVs (same HT + write_id),
        which the merge/compaction exact-duplicate elision collapses
        (reference achieves the same end with flushed-frontier replay
        filtering, tablet_bootstrap.cc:1138 ShouldReplayOperation; doing
        it via idempotence keeps divergent uncommitted tails from ever
        becoming visible). Log GC (future) must persist the committed
        op id before trimming."""
        return len(self.log.all_entries())

    async def shutdown(self):
        await self.consensus.shutdown()
        self.log.close()

    async def graceful_shutdown(self):
        """SIGTERM drain (the supervisor's clean-stop path, vs the
        SIGKILL crash path which skips straight to process death):
        flush both stores' memtables off-loop — the restarted replica
        then serves from SSTs whose flushed frontier covers the log,
        instead of replaying the whole WAL tail — and only then close
        consensus and the WAL.  Flush-before-close ordering matters:
        the flushed frontier must be durable before the log stops
        accepting the entries that produced it."""
        loop = asyncio.get_running_loop()
        try:
            await loop.run_in_executor(None, self.tablet.flush)
            # LsmStore.flush is a no-op on an empty memtable
            await loop.run_in_executor(None, self.tablet.intents.flush)
        except Exception:   # noqa: BLE001 — a failed flush must not
            # block the drain; restart falls back to full WAL replay,
            # which is exactly the crash path and always correct
            pass
        await self.shutdown()

    # --- write path -------------------------------------------------------
    def _check_inserts(self, req: WriteRequest) -> list:
        """insert-if-absent gate for 'insert' ops (unique indexes): a
        live committed row at the key, a pending queued insert of the
        same key, or a live transactional claim is a DUPLICATE.  Runs
        on the leader BEFORE enqueue; the single event loop makes
        check+reserve atomic, so two racing inserts of one key cannot
        both pass (reference: unique-index conflict through docdb
        intents, yb_access/yb_lsm.c:233-366).  Returns the reserved
        keys (caller releases after the write resolves)."""
        codec = self.tablet._codec_for(req.table_id)
        reserved = []
        try:
            for op in req.ops:
                if op.kind != "insert":
                    continue
                key = codec.doc_key_prefix(op.row)
                if key in self._pending_inserts or \
                        key in self.participant._key_holder:
                    raise RpcError(
                        "duplicate key value violates unique "
                        "constraint", "DUPLICATE_KEY")
                pk_row = {c.name: op.row[c.name]
                          for c in codec.info.schema.key_columns}
                if self.tablet.key_is_live(req.table_id, pk_row):
                    raise RpcError(
                        "duplicate key value violates unique "
                        "constraint", "DUPLICATE_KEY")
                self._pending_inserts.add(key)
                reserved.append(key)
        except Exception:
            for k in reserved:
                self._pending_inserts.discard(k)
            raise
        return reserved

    async def write(self, req: WriteRequest) -> WriteResponse:
        """Group commit: concurrent writes queue and ride ONE Raft round
        (reference: Log group commit + ReplicateBatch batching,
        consensus/log.cc TaskStream)."""
        if self.split_done or self.split_requested:
            raise RpcError("tablet has been split", "TABLET_SPLIT")
        if not self.consensus.is_leader():
            raise RpcError(
                f"not leader (hint={self.consensus.leader_hint()})",
                "LEADER_NOT_READY")
        reserved = self._check_inserts(req)
        if req.external_ht is not None:
            # HLC merge keeps local time ahead of the imported HT
            self.clock.update(HybridTime(req.external_ht))
            ht_value = req.external_ht
        else:
            ht_value = self.clock.now().value
        payload = {"req": write_request_to_wire(req), "ht": ht_value}
        fut = asyncio.get_running_loop().create_future()
        self._write_queue.append((payload, fut))
        if self._batcher_task is None or self._batcher_task.done():
            self._batcher_task = asyncio.create_task(self._drain_writes())
        try:
            await fut
        finally:
            for k in reserved:
                self._pending_inserts.discard(k)
        return WriteResponse(rows_affected=len(req.ops))

    def _pending_ht_bound(self, now_value: int, from_index: int) -> int:
        """Current HT clamped under every queued write and every log
        entry at-or-past `from_index` that already carries an assigned
        HT (the MVCC safe-time analog, reference: mvcc.cc SafeTime)."""
        bound = now_value
        for p, _ in self._write_queue:
            bound = min(bound, p["ht"] - 1)
        for e in self.log.entries_from(from_index, 1000):
            # etype check BEFORE unpack: noop (b"") and config (JSON)
            # payloads are not msgpack and carry no HT anyway
            if e.etype == "write":
                d = msgpack.unpackb(e.payload, raw=False)
                for item in (d["batch"] if "batch" in d else [d]):
                    bound = min(bound, item["ht"] - 1)
            elif e.etype == "txn_apply":
                d = msgpack.unpackb(e.payload, raw=False)
                bound = min(bound, d["commit_ht"] - 1)
        return bound

    def xcluster_safe_ht(self, now_value: int) -> int:
        """Upper bound below which no NEW commit can land. Without
        this, a write with ht=100 sitting in the queue would let
        get_changes advertise now()=105 as safe, then commit below
        it."""
        return self._pending_ht_bound(
            now_value, self.consensus.commit_index + 1)

    def safe_read_ht(self, now_value: int) -> int:
        """Upper bound at which a snapshot read sees a stable prefix:
        like xcluster_safe_ht but anchored at last_APPLIED — an entry
        that committed but hasn't hit the store yet is still invisible
        to a scan, so reads must wait it out too. Fast path: nothing
        in flight, the bound is just `now`."""
        if (not self._write_queue
                and self.consensus.last_applied >= self.log.last_index):
            return now_value
        return self._pending_ht_bound(
            now_value, self.consensus.last_applied + 1)

    def _notify_progress(self):
        """Wake safe-time waiters: the in-flight set changed."""
        self._progress_event.set()
        self._progress_event = asyncio.Event()

    async def _drain_writes(self):
        while self._write_queue:
            batch, self._write_queue = self._write_queue, []
            if self.split_requested or self.split_done:
                # the split entry is (about to be) in the log: anything
                # we append now would order after it and be lost with
                # the parent — fail so the client re-routes to children
                for _, fut in batch:
                    if not fut.done():
                        fut.set_exception(RpcError(
                            "tablet has been split", "TABLET_SPLIT"))
                self._notify_progress()
                continue
            payload = msgpack.packb({
                "batch": [p for p, _ in batch]})
            try:
                await self.consensus.replicate(
                    "write", payload, precheck=self.split_fence_check)
            except Exception as e:   # noqa: BLE001 — propagate per-waiter
                for _, fut in batch:
                    if not fut.done():
                        fut.set_exception(e)
                self._notify_progress()
                continue
            for _, fut in batch:
                if not fut.done():
                    fut.set_result(None)
            self._notify_progress()

    async def _apply_entry(self, entry: LogEntry):
        if entry.etype == "write":
            self._apply_payload(entry)
        elif entry.etype == "alter":
            from ..docdb.table_codec import TableInfo
            d = msgpack.unpackb(entry.payload, raw=False)
            # flush first: every pre-alter write must sit at-or-below
            # the flushed frontier so a restart never replays it under
            # the post-alter codec.  Off-loop: a large memtable's SST
            # write on the apply loop would stall heartbeats; apply
            # order is preserved because _apply_committed awaits each
            # entry before the next (the DDL barrier holds)
            await asyncio.get_running_loop().run_in_executor(
                None, self.tablet.flush)
            self.tablet.alter_table(TableInfo.from_wire(d["table"]))
            if self.on_alter is not None:
                self.on_alter(d["table"])
        elif entry.etype == "txn_intents":
            self.participant.apply_intent_entry(entry.payload,
                                                log_index=entry.index)
        elif entry.etype == "txn_read_locks":
            self.participant.apply_read_lock_entry(entry.payload)
        elif entry.etype == "txn_read_unlock":
            d = msgpack.unpackb(entry.payload, raw=False)
            self.participant.release_reads(d["txn_id"])
        elif entry.etype == "txn_apply":
            # frontier-covered applies replay as claim-release only; the
            # regular-store image of the txn is already in the SSTs
            fr = self.tablet.regular.flushed_frontier().get("op_id")
            covered = bool(fr) and (entry.term, entry.index) <= (fr[0],
                                                                 fr[1])
            self.participant.apply_commit_entry(
                entry.payload, op_id=(entry.term, entry.index),
                skip_regular=covered)
        elif entry.etype == "txn_rollback":
            self.participant.apply_rollback_entry(entry.payload)
        elif entry.etype == "txn_sub_rollback":
            self.participant.apply_sub_rollback_entry(entry.payload)
        elif entry.etype == "truncate":
            d = msgpack.unpackb(entry.payload, raw=False)
            if d.get("ht"):
                self.clock.update(HybridTime(d["ht"]))
            # TRUNCATE is a rare DDL barrier applied in log order —
            # the manifest rewrite is tiny, file unlinks defer
            # through the lease GC
            # analysis-ok(async_blocking): bounded DDL barrier
            self.tablet.truncate_table(d["table_id"],
                                       op_id=(entry.term, entry.index),
                                       ht=d.get("ht"))
        elif entry.etype == "txn_status" and self.coordinator is not None:
            self.coordinator.apply_entry(entry.payload)
        elif entry.etype == "split":
            # every replica applies the split at the SAME log position:
            # entries before it are applied (sequential apply), so the
            # deterministic child copy sees identical parent state on
            # every replica — online, no quiesce (reference:
            # tablet/operations/split_operation.cc)
            d = msgpack.unpackb(entry.payload, raw=False)
            if self.on_split is not None:
                await self.on_split(self, d)
            self.split_done = True

    def _apply_payload(self, entry: LogEntry):
        # entries at-or-below the flushed frontier are already durable in
        # SSTs — re-applying them is NOT merely redundant: after a schema
        # change they would re-encode under the newer codec and resurrect
        # dropped columns (reference: tablet_bootstrap.cc skips ops
        # covered by the flushed frontier)
        fr = self.tablet.regular.flushed_frontier().get("op_id")
        if fr and (entry.term, entry.index) <= (fr[0], fr[1]):
            return
        d = msgpack.unpackb(entry.payload, raw=False)
        items = d["batch"] if "batch" in d else [d]
        with _trace.TRACES.span("tablet.apply", child_only=True,
                                tags={"tablet": self.tablet.tablet_id,
                                      "entries": len(items)}):
            for item in items:
                req = write_request_from_wire(item["req"])
                self.tablet.apply_write(req, ht=HybridTime(item["ht"]),
                                        op_id=(entry.term, entry.index))

    # --- read path --------------------------------------------------------
    async def read(self, req: ReadRequest) -> ReadResponse:
        """Strong reads: leader with a valid lease picks the read time
        (reference: tserver/read_query.cc PickReadTime + leader lease
        checks), then waits until the MVCC safe time passes it — an
        in-flight write already holds an HT below now(), and a snapshot
        read that ran ahead of it would return different rows on
        re-read (reference: mvcc.cc SafeTime wait). Follower
        (consistent-prefix) reads serve from any replica at its applied
        state — the clock is ratcheted by leader heartbeats, so the
        prefix is consistent though possibly stale."""
        if req.consistency == "follower":
            if self.split_done:
                raise RpcError("tablet has been split", "TABLET_SPLIT")
            return await self.tablet.read_served(req)
        self.check_strong_read()
        if req.read_ht is None:
            req.read_ht = self.clock.now().value
            req.server_assigned_read_ht = True
        await self.wait_safe_time(req.read_ht)
        return await self.tablet.read_served(req)

    def check_strong_read(self) -> None:
        """The gates of a strong read: not split away, leader, lease."""
        if self.split_done:
            raise RpcError("tablet has been split", "TABLET_SPLIT")
        if not self.consensus.is_leader():
            raise RpcError(
                f"not leader (hint={self.consensus.leader_hint()})",
                "LEADER_NOT_READY")
        if not self.consensus.has_leader_lease():
            raise RpcError("leader lease expired", "LEADER_HAS_NO_LEASE")

    async def wait_safe_time(self, read_ht: int) -> None:
        """Wait until the MVCC safe time passes `read_ht`: every write
        below it has reached the store."""
        import time as _time
        deadline = _time.monotonic() + 10.0
        with wait_status("SafeTime_Wait", component="mvcc"):
            while self.safe_read_ht(self.clock.now().value) < read_ht:
                if _time.monotonic() > deadline:
                    raise RpcError("in-flight writes below the read time "
                                   "did not drain", "TIMED_OUT")
                # event-driven wait (drain/apply progress sets it), with
                # a timeout fallback for wakeups racing the state change
                ev = self._progress_event
                try:
                    await asyncio.wait_for(ev.wait(), 0.05)
                except asyncio.TimeoutError:
                    pass

    async def read_points(self, table_id: str, pk_rows: list) -> list:
        """Batched same-tablet strong point gets (the scheduler's
        point-read micro-batch lands here): the split/leader/lease
        gates, the server-assigned read point and the MVCC safe-time
        wait run ONCE for the whole group — each member's read point is
        at-or-above its own arrival, since the group formed before this
        call — then the engine's fused multi_get serves every key in
        one pass (same per-key result as read() with pk_eq; parity
        pinned by tests/test_scheduler.py).  Returns a row-or-None per
        pk_row."""
        self.check_strong_read()
        read_ht = self.clock.now().value
        await self.wait_safe_time(read_ht)
        # read EXACTLY at the waited-out read point (a fresh clock.now
        # inside multi_read could run ahead of a write queued during
        # the wait — a write below the read point the wait never
        # covered); allow_restart keeps the single-read contract's
        # uncertainty-window restarts
        return self.tablet.multi_read(table_id, pk_rows,
                                      read_ht=read_ht,
                                      allow_restart=True)

    def is_leader(self) -> bool:
        return self.consensus.is_leader()

    # --- transactional write path ------------------------------------------
    async def write_txn(self, req: WriteRequest, txn_id: str,
                        start_ht: int, status_tablet=None,
                        op_read_hts=None, sub_id: int = 0) -> int:
        if self.split_done or self.split_requested:
            raise RpcError("tablet has been split", "TABLET_SPLIT")
        if not self.consensus.is_leader():
            raise RpcError(
                f"not leader (hint={self.consensus.leader_hint()})",
                "LEADER_NOT_READY")
        return await self.participant.write_intents(
            req, txn_id, start_ht, status_tablet, op_read_hts, sub_id)

    async def truncate(self, table_id: str, ht: int = None):
        """Raft-replicated TRUNCATE (reference: tablet truncate
        operation, tablet/operations/truncate_operation.cc): every
        replica drops the table's data at the same log position.
        Refused while transactional intents are live on this tablet —
        truncate is non-MVCC, and yanking rows under an in-flight txn
        would break its snapshot."""
        if self.split_done or self.split_requested:
            raise RpcError("tablet has been split", "TABLET_SPLIT")
        if not self.consensus.is_leader():
            raise RpcError(
                f"not leader (hint={self.consensus.leader_hint()})",
                "LEADER_NOT_READY")
        if self.participant.has_foreign_intents():
            raise RpcError(
                "cannot TRUNCATE while transactions hold intents on "
                "this tablet", "TRY_AGAIN")
        import msgpack as _mp
        # the hybrid time is assigned ONCE for the whole statement (the
        # first tablet's leader mints it; the client fans it out) and
        # carried in every tablet's entry: replays and followers apply
        # at the SAME ht, consumers can DEDUP the per-tablet records,
        # and post-truncate writes always sort after it (each leader's
        # clock ratchets on apply)
        if ht is None:
            ht = self.clock.now().value
        else:
            self.clock.update(HybridTime(ht))
        await self.consensus.replicate(
            "truncate", _mp.packb({"table_id": table_id, "ht": ht}),
            precheck=self.split_fence_check)
        return ht

    async def rollback_sub_txn(self, txn_id: str, from_sub: int):
        """ROLLBACK TO SAVEPOINT on this participant (leader only):
        Raft-replicates the prune so it survives failover."""
        if not self.consensus.is_leader():
            raise RpcError(
                f"not leader (hint={self.consensus.leader_hint()})",
                "LEADER_NOT_READY")
        import msgpack as _mp
        await self.consensus.replicate(
            "txn_sub_rollback",
            _mp.packb({"txn_id": txn_id, "from_sub": from_sub}),
            precheck=self.split_fence_check)

    async def lock_for_update(self, keys, txn_id: str, start_ht: int,
                              status_tablet=None) -> int:
        """FOR UPDATE row locks (leader only); returns the lock ht."""
        if self.split_done or self.split_requested:
            raise RpcError("tablet has been split", "TABLET_SPLIT")
        if not self.consensus.is_leader():
            raise RpcError(
                f"not leader (hint={self.consensus.leader_hint()})",
                "LEADER_NOT_READY")
        return await self.participant.lock_for_update(
            txn_id, start_ht, keys, status_tablet)

    async def lock_reads(self, keys, txn_id: str, start_ht: int,
                         status_tablet=None) -> None:
        """SERIALIZABLE read locks on doc keys (leader only)."""
        if not self.consensus.is_leader():
            raise RpcError(
                f"not leader (hint={self.consensus.leader_hint()})",
                "LEADER_NOT_READY")
        await self.participant.read_intents(keys, txn_id, start_ht,
                                            status_tablet)

    async def apply_txn(self, txn_id: str, commit_ht: int):
        import msgpack as _mp
        await self.consensus.replicate(
            "txn_apply", _mp.packb(
                {"txn_id": txn_id, "commit_ht": commit_ht}),
            precheck=self.split_fence_check)

    async def rollback_txn(self, txn_id: str):
        import msgpack as _mp
        await self.consensus.replicate(
            "txn_rollback", _mp.packb({"txn_id": txn_id}),
            precheck=self.split_fence_check)

    def read_own_intent(self, txn_id: str, pk_row: dict,
                        table_id: str = ""):
        codec = self.tablet._codec_for(table_id)
        doc_key = codec.doc_key_prefix(pk_row)
        return self.participant.own_intent(txn_id, doc_key)

    # --- log retention ------------------------------------------------------
    def maybe_gc_log(self) -> int:
        """Drop WAL segments whose entries are both flushed to SSTs and
        committed (reference: log GC gated on the flushed op id +
        retention). New replicas beyond the retained log catch up via
        remote bootstrap (tserver snapshot fetch)."""
        frontier = self.tablet.regular.flushed_frontier()
        op = frontier.get("op_id")
        if not op:
            return 0
        from ..utils import flags as _flags
        cutoff = min(int(op[1]), self.consensus.commit_index)
        if self.consensus.is_leader():
            # don't GC entries a peer still needs — a peer behind our
            # retained log can only recover via full snapshot install.
            # Bounded: a peer lagging more than the retention cap (or
            # at match 0 — never replicated / freshly added) doesn't
            # hold GC hostage; it goes through snapshot install.
            cap = _flags.get("log_gc_max_peer_lag_entries")
            for p in self.consensus.config.others(self.consensus.uuid):
                m = self.consensus.match_index.get(p.uuid, 0)
                if m > 0 and cutoff - m < cap:
                    cutoff = min(cutoff, m)
        if cutoff <= 0:
            return 0
        return self.log.gc(cutoff)
