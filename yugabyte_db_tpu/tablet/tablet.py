"""Tablet: one shard of one table — storage + codec + read/write ops.

Analog of the reference's Tablet (reference: src/yb/tablet/tablet.h:151,
tablet.cc:2303 HandlePgsqlReadRequest, :1938 ApplyRowOperations). Holds
the RegularDB LSM (and, once distributed transactions land, the
IntentsDB — reference: tablet/tablet.h:1287-1288), the table codec, and
serves DocDB read/write operations. Raft integration drives `apply_*`
through replicated operations; single-node callers may use them
directly.
"""
from __future__ import annotations

import asyncio
import collections
import logging
import os
import threading
from concurrent.futures import ThreadPoolExecutor
from time import perf_counter as _perf_counter
from time import perf_counter_ns as _perf_counter_ns
from typing import Dict, Optional

import numpy as np

log = logging.getLogger("ybtpu.tablet")

from ..docdb.compaction import (
    DocDbCompactionFeed, RepackingCompactionFeed, tpu_compact,
)
from ..docdb.operations import (
    DocReadOperation, DocWriteOperation, ReadRequest, ReadResponse,
    ReadRestartError, WriteRequest, WriteResponse, run_steps,
)
from ..docdb.table_codec import TableCodec, TableInfo
from ..ops.device_batch import DeviceBlockCache
from ..storage.lsm import LsmStore
from ..utils import flags, metrics
from ..utils import trace as _trace
from ..utils.hybrid_time import HybridClock, HybridTime
from ..utils.trace import wait_status

# process-wide device block cache shared by all tablets (HBM is global)
_DEVICE_CACHE = DeviceBlockCache()

# bounded background flush executor shared by all tablets: the async
# flush path (async_flush_enabled) freezes the memtable on the apply
# thread and runs the SST write + fsync here (reference: the RocksDB
# high-priority flush thread pool).  Two workers: one flush streaming
# to a stalled disk must not park every other tablet's flush behind it.
_FLUSH_POOL = ThreadPoolExecutor(max_workers=2,
                                 thread_name_prefix="bg-flush")

# threads that make a served read's launches — `ScanKernel.run`:
# dispatch, the wait for the device, the read-back — while the event
# loop goes on with the other reads and with everything else
# (`serve_read`).  Most of a launch is a wait that holds no lock and no
# core; eight is two statements' four tablet reads each, more than a
# chip runs at a time.
_READ_LAUNCH_POOL = ThreadPoolExecutor(max_workers=8,
                                       thread_name_prefix="read-launch")

#: blocks a bulk load makes and serializes at a time, ahead of the file
#: write
_BULK_WORKERS = max(2, min(4, (os.cpu_count() or 2) - 1))


class ServedReads:
    """One server's reads whose launch is with `_READ_LAUNCH_POOL`: how
    many are open (`serve_read` counts on the event loop), and on the
    server's `/metrics` the most that were (`reads_in_flight_max`) and
    the time a launch stood in the pool's queue
    (`read_offload_queue_us`).  A `TabletServer` makes one and hands it
    to its tablets; a tablet with no server has its own."""

    def __init__(self, owner: str):
        ent = metrics.REGISTRY.entity("server", owner or "docdb")
        self.open = 0
        self.m_max = ent.gauge("reads_in_flight_max")
        self.m_queue = ent.histogram("read_offload_queue_us")


def _launch_in_pool(call, tctx):
    """Pool side of `serve_read`: when a thread took the launch, its
    result, and when the launch returned; `tctx` is the read's trace
    context on the loop (executor threads see no contextvars), so
    `launch.prepare`, `device.scan` and `device.wait` stay in the
    statement's span tree."""
    began = _perf_counter_ns()
    with _trace.use_context(tctx):
        got = call()
    return began, got, _perf_counter_ns()


async def serve_read(steps, served: ServedReads):
    """Drive a read written as steps (`DocReadOperation.execute_steps`,
    `MeshReader.read_steps`) on the event loop: every step runs here, on
    the loop's thread, and each launch it yields runs on a thread of
    `_READ_LAUNCH_POOL`, so the loop serves the other reads — the other
    stream's, and the other tablets of the same statement — meanwhile.
    The hop is the `tserver.read_offload` span, child of the span that
    was ambient when the read began (`tserver.read:<tablet>`): `queue_ms`
    from handing the launch over to a thread taking it, `in_flight` the
    server's reads in the pool's hands with this one.  From the launch's
    return to the read's resumption on the loop is `tserver.read_resume`
    (a child of the read's own span, `in_flight` as the hop's); the
    loop's time inside the read's steps is tag `steps_ms` of the span
    that was ambient when it began — a tag, since the steps open and
    close spans of their own across the yields."""
    parent = _trace.current_context()
    read_span = _trace.current_span()
    loop = asyncio.get_running_loop()
    steps_ns, step_began = 0, _perf_counter_ns()
    try:
        call = next(steps)
        while True:
            steps_ns += _perf_counter_ns() - step_began
            served.open += 1
            if served.open > served.m_max.value():
                served.m_max.set(served.open)
            try:
                tctx = _trace.current_context()     # the read's own span
                with _trace.TRACES.span(
                        "tserver.read_offload", parent=parent,
                        child_only=True,
                        tags={"in_flight": served.open}) as sp:
                    handed = _perf_counter_ns()
                    began, got, returned = await loop.run_in_executor(
                        _READ_LAUNCH_POOL, _launch_in_pool, call, tctx)
                    served.m_queue.increment((began - handed) / 1e3)
                    sp.set_tag("queue_ms", (began - handed) / 1e6)
                _trace.TRACES.record("tserver.read_resume", returned,
                                     _perf_counter_ns(), tctx,
                                     {"in_flight": served.open})
            except Exception as e:   # noqa: BLE001 — the read's to see
                step_began = _perf_counter_ns()
                call = steps.throw(e)
            else:
                step_began = _perf_counter_ns()
                call = steps.send(got)
            finally:
                served.open -= 1
    except StopIteration as done:
        steps_ns += _perf_counter_ns() - step_began
        return done.value
    finally:
        if read_span.sampled:
            read_span.set_tag("steps_ms", read_span.tags.get("steps_ms", 0.0)
                              + steps_ns / 1e6)
        steps.close()


class _VectorIndexState:
    """One ANN index: a frozen chunk (any registry method) plus a
    mutable delta — the vector-LSM shape (reference:
    vector_index/vector_lsm.cc)."""

    def __init__(self, col_name: str, method: str = "ivfflat",
                 options: Optional[dict] = None):
        self.col_name = col_name
        self.method = method
        self.options = dict(options or {})
        self.idx = None               # frozen AnnIndex (or None)
        self.pks: list = []           # row ids aligned with idx vectors
        self.frozen_keys: set = set()  # pk_keys present in the chunk
        self.frozen_pos: Dict[tuple, int] = {}   # pk_key -> index id
        # pk_key -> (pk_row, vector_bytes, expire_at_wall or None)
        self.delta: Dict[tuple, tuple] = {}
        self.dead: set = set()        # frozen pk_keys hidden by del/upsert
        # pk_keys any write touched while a bootstrap scan-diff is in
        # flight (None otherwise): the merge must not overwrite them —
        # in particular a DELETE of a non-frozen key leaves no
        # delta/dead trace, and the scan's pre-delete image would
        # otherwise resurrect the row
        self.touched: Optional[set] = None

    @property
    def nlists(self) -> int:
        return int(self.options.get("lists", 100))


class Tablet:
    def __init__(self, tablet_id: str, info: TableInfo, directory: str,
                 clock: Optional[HybridClock] = None,
                 partition=None, colocated: bool = False,
                 owner: str = "", served: Optional[ServedReads] = None):
        self.tablet_id = tablet_id
        # the server whose `/metrics` entity the read path counts on,
        # and its count of reads in flight
        self.owner = owner
        self.served = served or ServedReads(owner)
        self.info = info
        self.partition = partition
        self.dir = directory
        self.colocated = colocated
        os.makedirs(directory, exist_ok=True)
        self.codec = TableCodec(info)
        # colocated tablets host several tables (reference:
        # ysql-colocated-tables design; cotable-prefixed doc keys)
        self.codecs: Dict[str, TableCodec] = {info.table_id: self.codec}
        self.clock = clock or HybridClock()
        self.regular = LsmStore(
            os.path.join(directory, "regular"), name="regular",
            columnar_builder=(None if colocated
                              else self.codec.columnar_builder),
            row_decoder=(None if colocated else self.codec.row_decoder),
            key_builder=(None if colocated else self.codec.derive_keys),
            shred_cols=(None if colocated else self.codec.shred_cols))
        self.intents = LsmStore(
            os.path.join(directory, "intents"), name="intents")
        self._read_op = DocReadOperation(
            self.codec, self.regular, device_cache=_DEVICE_CACHE,
            owner=owner)
        self._read_ops: Dict[str, DocReadOperation] = {
            info.table_id: self._read_op}
        # vector ANN indexes: col_id -> _VectorIndexState
        self.vector_indexes: Dict[int, _VectorIndexState] = {}
        self._lock = threading.Lock()
        self._vector_build_lock = threading.Lock()   # serializes rebuilds
        ent = metrics.REGISTRY.entity("tablet", tablet_id,
                                      table=info.name)
        self._m_rows_written = ent.counter("rows_inserted")
        self._m_reads = ent.counter("read_ops")
        self._m_read_lat = ent.histogram("read_latency_us")
        # what the APPLY THREAD paid for flush work per trigger (an
        # inline SST write before async flush, a pointer swap since)
        self._m_flush_pause = ent.histogram("flush_pause_ms")
        self._m_stalls_avoided = ent.counter("flush_stalls_avoided")

    # --- colocation ---------------------------------------------------------
    def add_table(self, info: TableInfo) -> None:
        codec = TableCodec(info)
        self.codecs[info.table_id] = codec
        self._read_ops[info.table_id] = DocReadOperation(
            codec, self.regular, device_cache=None, owner=self.owner)

    def _codec_for(self, table_id: str) -> TableCodec:
        return self.codecs.get(table_id, self.codec)

    def schema_version_of(self, table_id: str) -> Optional[int]:
        """Current schema version for the catalog-version write fence
        (None when the table is unknown here — the write will fail with
        a clearer error downstream)."""
        codec = self._codec_for(table_id)
        return codec.info.schema.version if codec is not None else None

    def alter_table(self, new_info: TableInfo) -> None:
        """Online schema change (reference: ChangeMetadataOperation,
        tablet/operations/change_metadata_operation.cc): adopt the new
        schema version while RETAINING old packings so existing rows keep
        decoding; compaction repacks over time."""
        old = self.codecs.get(new_info.table_id, self.codec)
        merged = TableCodec(new_info)
        merged.info.packings._packings.update(
            {v: p for v, p in old.info.packings._packings.items()
             if v not in merged.info.packings._packings})
        self.codecs[new_info.table_id] = merged
        if new_info.table_id == self.info.table_id:
            self.info = new_info
            self.codec = merged
            if not self.colocated:
                self.regular.columnar_builder = merged.columnar_builder
                self.regular.row_decoder = merged.row_decoder
                # key derivation depends only on the pk/partition shape,
                # which ALTER cannot change — rebinding keeps the codec
                # object current all the same
                self.regular.key_builder = merged.derive_keys
                self.regular.shred_cols = merged.shred_cols
                for r in self.regular.ssts:
                    r.row_decoder = merged.row_decoder
                    r.key_builder = merged.derive_keys
            from ..docdb.operations import DocReadOperation
            self._read_op = DocReadOperation(
                merged, self.regular, device_cache=_DEVICE_CACHE,
                owner=self.owner)
        from ..docdb.operations import DocReadOperation as _DRO
        self._read_ops[new_info.table_id] = _DRO(
            merged, self.regular,
            device_cache=_DEVICE_CACHE
            if new_info.table_id == self.info.table_id else None,
            owner=self.owner)

    def tables(self):
        return list(self.codecs)

    # --- writes (called under Raft apply, or directly in single-node) -----
    def apply_write(self, req: WriteRequest,
                    ht: Optional[HybridTime] = None,
                    op_id=None) -> WriteResponse:
        ht = ht or self.clock.now()
        batch, n = DocWriteOperation(self._codec_for(req.table_id),
                                     req).apply(ht, op_id=op_id)
        self.regular.apply(batch)
        self._maintain_vector_indexes(req)
        self._m_rows_written.increment(n)
        if self.regular.should_flush():
            self._flush_on_apply()
        return WriteResponse(rows_affected=n)

    def _flush_on_apply(self) -> None:
        """Flush trigger on the apply path.  Async (default): freeze
        the active memtable — an in-memory pointer swap — and hand the
        SST write + fsync to the background flush executor, so the
        apply thread (the Raft apply loop) never waits on disk.
        Backpressure: past ``max_frozen_memtables`` frozen memtables
        the apply thread drains one inline instead, bounding memory and
        the WAL-replay window.  Flag off reverts to the legacy inline
        flush.  ``flush_pause_ms`` records what the apply thread paid
        either way — the stall this histogram measured (~20x p99 round
        swings in ``cluster_overload``) is what async flush removes."""
        t0 = _perf_counter()
        try:
            if not flags.get("async_flush_enabled"):
                # flag-gated legacy revert — async_flush_enabled=1
                # (the default) hands the SST write to the executor
                # analysis-ok(async_blocking): deliberate inline flush
                self.flush()
                return
            if self.regular.freeze_active():
                self._m_stalls_avoided.increment()
                _trace.TRACE("flush.handoff")
                # explicit context capture: the flush-executor thread
                # has no contextvars from this task, so the handoff
                # span would otherwise detach from the request tree
                _FLUSH_POOL.submit(self._background_flush,
                                   _trace.current_context())
            while (self.regular.frozen_count()
                   > flags.get("max_frozen_memtables")):
                # the executor fell behind; the apply thread helps
                # drain one frozen memtable, bounding frozen memory
                with wait_status("Flush_MemtableBackpressure",
                                 component="flush"):
                    # analysis-ok(async_blocking): deliberate backpressure
                    if self.regular.flush_frozen() is not None:
                        self.drop_device_state()
        finally:
            self._m_flush_pause.increment((_perf_counter() - t0) * 1e3)

    def _background_flush(self, tctx=None) -> None:
        """Flush-executor job: drain frozen memtables (oldest first,
        serialized by the store's flush IO lock) until the queue is
        empty, invalidating the device cache per install.  NON-blocking
        on the IO lock: if another flush owns it, bail — that owner's
        own drain loop covers everything queued, and a worker parked on
        one store's stalled disk would starve every other tablet's
        flushes (the pool is 2 workers wide).  A failed flush leaves
        the frozen memtable queued — the next trigger, an inline drain,
        or the shutdown flush retries it.  ``tctx`` is the apply-side
        trace context captured at the handoff (executor threads see no
        contextvars), so the SST write shows up in the request's span
        tree."""
        try:
            with _trace.use_context(tctx):
                with _trace.TRACES.span("flush.background",
                                        child_only=True) as sp:
                    with wait_status("Flush_SstWrite", component="flush"):
                        n = 0
                        while self.regular.flush_frozen(wait=False) \
                                is not None:
                            self.drop_device_state()
                            n += 1
                    sp.set_tag("flushed", n)
        except Exception:   # noqa: BLE001 — must not kill the pool
            log.exception("%s: background flush failed (frozen "
                          "memtable retained for retry)", self.tablet_id)

    def drop_device_state(self) -> None:
        """The regular store's SST set changed (flush, compaction), or
        its chips go back: its cached batches leave the device cache,
        and the read path's facts about its blocks go with them."""
        _DEVICE_CACHE.invalidate_prefix((id(self.regular),))
        self.regular.read_facts = None

    # --- reads ------------------------------------------------------------
    def read_steps(self, req: ReadRequest):
        """The read as the steps of `DocReadOperation.execute_steps`."""
        t0 = _perf_counter()
        if req.read_ht is None:
            req.read_ht = self.clock.now().value
            req.server_assigned_read_ht = True
        resp = yield from self._read_ops.get(
            req.table_id, self._read_op).execute_steps(req)
        self._m_reads.increment()
        self._m_read_lat.increment((_perf_counter() - t0) * 1e6)
        return resp

    def read(self, req: ReadRequest) -> ReadResponse:
        """For a caller that is no event loop: it makes the read's
        launches, and stands in their waits, itself."""
        return run_steps(self.read_steps(req))

    async def read_served(self, req: ReadRequest) -> ReadResponse:
        """For the event loop (`TabletPeer.read`): the steps run on it,
        the launches beside it (`serve_read`)."""
        return await serve_read(self.read_steps(req), self.served)

    def key_is_live(self, table_id: str, pk_row: dict) -> bool:
        """Whether a live row sits at the key now: what `read` of a
        `pk_eq` request tells by its rows, without making the row
        (`DocReadOperation.key_is_live`)."""
        op = self._read_ops.get(table_id, self._read_op)
        read_ht = self.clock.now().value
        for _attempt in range(3):
            try:
                live = op.key_is_live(pk_row, read_ht, allow_restart=True)
                break
            except ReadRestartError as e:
                read_ht = e.restart_ht
        else:
            live = op.key_is_live(pk_row, read_ht)
        self._m_reads.increment()
        return live

    def read_op(self, table_id: str) -> DocReadOperation:
        """The read operation over this tablet's store for `table_id`:
        what a read that spans several tablets (docdb/mesh_read.py)
        takes in place of `read`."""
        return self._read_ops.get(table_id, self._read_op)

    def note_read(self) -> None:
        """Count a read served over this tablet from outside `read`."""
        self._m_reads.increment()

    def multi_read(self, table_id: str, pk_rows, read_ht=None,
                   allow_restart=None):
        """Batched point reads: the engine seam where concurrent
        sessions' point lookups amortize per-op overhead (reference
        analog: pggate operation buffering / doc_op batching). Returns
        a row dict (or None) per pk_row, all at one read point.
        `allow_restart` defaults to "read point was server-assigned";
        a caller that pre-assigned (and safe-time-waited) its own read
        point but still wants uncertainty-window restarts — the
        scheduler's batched read path — passes True explicitly."""
        t0 = _perf_counter()
        server_assigned = read_ht is None
        if allow_restart is None:
            allow_restart = server_assigned
        if server_assigned:
            read_ht = self.clock.now().value
        op = self._read_ops.get(table_id, self._read_op)
        for _attempt in range(3):
            try:
                rows = op.multi_get(pk_rows, read_ht,
                                    allow_restart=allow_restart)
                break
            except ReadRestartError as e:
                read_ht = e.restart_ht
        else:
            rows = op.multi_get(pk_rows, read_ht, allow_restart=False)
        self._m_reads.increment(len(pk_rows))
        self._m_read_lat.increment((_perf_counter() - t0) * 1e6)
        return rows

    def safe_time(self) -> HybridTime:
        return self.clock.now()

    # --- maintenance ------------------------------------------------------
    def truncate_table(self, table_id: str, op_id=None,
                       ht=None) -> int:
        """TRUNCATE (reference: tablet truncate, tablet/tablet.cc
        Truncate — replaces the stores rather than writing tombstones).
        Dedicated tablets drop the whole regular store in one shot;
        colocated tablets tombstone only the cotable's key range.
        Vector indexes over the table reset with it.  Returns rows/SSTs
        affected (wholesale: SST count; colocated: rows tombstoned)."""
        codec = self._codec_for(table_id)
        if table_id == self.info.table_id:
            # vector indexes only ever cover the tablet's primary table
            with self._vector_build_lock:
                self.vector_indexes.clear()
                import shutil
                shutil.rmtree(os.path.join(self.dir, "vecidx"),
                              ignore_errors=True)
        if not self.colocated:
            return self.regular.truncate(op_id=op_id)
        # colocated: delete the cotable's rows (prefix tombstones at a
        # fresh HT — MVCC-correct, compaction reclaims)
        prefix = codec.scan_prefix()
        from ..dockv.key_encoding import ValueType
        from ..utils.hybrid_time import (
            ENCODED_SIZE, DocHybridTime,
        )
        from ..storage.lsm import WriteBatch
        from ..dockv.value import PrimitiveValue
        mems, ssts = self.regular.read_snapshot()
        seen = set()
        from ..utils.hybrid_time import HybridTime as _HT
        ht = _HT(ht) if ht is not None else self.clock.now()
        batch = WriteBatch(op_id=op_id)
        wid = 0
        for src in list(mems) + list(ssts):
            it = src.iterate() if hasattr(src, "iterate") else ()
            for k, _v in it:
                if not k.startswith(prefix):
                    continue
                dk = k[:-(ENCODED_SIZE + 1)]
                if dk in seen:
                    continue
                seen.add(dk)
                batch.put(dk + bytes([ValueType.kHybridTime])
                          + DocHybridTime(ht, wid).encoded_desc(),
                          PrimitiveValue.tombstone().encode())
                wid += 1
        if batch.entries:
            self.regular.apply(batch)
        return len(seen)

    def flush(self, wait: bool = True) -> Optional[str]:
        path = self.regular.flush(wait=wait)
        if path:
            self.drop_device_state()
        return path

    def history_cutoff(self) -> int:
        retention_us = flags.get("history_retention_interval_sec") * 1_000_000
        now = self.clock.now()
        return max(0, now.value - (retention_us << 12))

    def compact(self, major: bool = True) -> Optional[str]:
        """Major compaction with MVCC GC; routes to the TPU merge kernel
        when enabled (reference analog: full_compaction_manager.cc driving
        CompactionJob with the DocDB feed)."""
        self.flush()
        inputs = self.regular.ssts if major else self.regular.pick_compaction()
        if not inputs:
            return None
        cutoff = self.history_cutoff()
        multi_version = len(self.codec.info.packings.versions()) > 1
        if self.colocated:
            # colocated tablets mix schemas per cotable: one GC pass
            # with the repack packing dispatched by cotable prefix
            from ..docdb.compaction import ColocatedRepackingFeed
            path = self.regular.compact(
                inputs=inputs,
                feed=ColocatedRepackingFeed(cutoff, self.codecs.values()))
        elif not multi_version:
            # single-schema tablets: the pipelined chunked engine when
            # the offload flag is on — device merge kernel on a real
            # accelerator, native C k-way merge per chunk on CPU-only
            # backends (the XLA sort on CPU is strictly slower than the
            # native merge, measured ~2x, so the flag never routes it
            # there). Flag off keeps the pre-pipeline monolithic native
            # merge — the honest CPU baseline (reference:
            # rocksdb/db/compaction_job.cc ProcessKeyValueCompaction).
            import jax as _jax
            if flags.get("tpu_compaction_enabled"):
                backend = ("device" if _jax.default_backend() != "cpu"
                           else "native")
            else:
                backend = "baseline"
            path = tpu_compact(self.regular, self.codec, cutoff,
                               inputs=inputs, backend=backend)
        else:
            # mixed schema versions compact on the CPU feed, which also
            # repacks surviving rows to the latest schema version
            path = self.regular.compact(
                inputs=inputs, feed=RepackingCompactionFeed(cutoff,
                                                            self.codec))
        self.drop_device_state()
        return path

    def bulk_load(self, columns: Dict[str, np.ndarray],
                  ht: Optional[HybridTime] = None,
                  block_rows: int = 65536) -> int:
        """Vectorized ingest of column arrays (rows outside this tablet's
        partition are dropped, so the same arrays can be fed to every
        tablet of a table).

        After the codec's global phase (partition filter, key encode,
        sort order) every block is its own job: `_BULK_WORKERS` threads
        make (fused native gather) and serialize blocks ahead, and the
        shared stage pipeline writes them to the file in block order —
        gather, encode and IO overlap."""
        from ..storage.pipeline import StreamPipeline
        ht = ht or self.clock.now()
        makers = self.codec.bulk_block_makers(
            columns, ht, block_rows=block_rows, partition=self.partition)
        if not makers:
            return 0        # everything partition-filtered: no SST
        n = 0

        def serialized(w, pool):
            """(block, its serialized parts), in block order, a few
            blocks made and serialized ahead on the pool's threads
            (numpy's gathers, sorts and copies release the GIL)."""
            def job(make):
                blk = make()
                return blk, w.serialize_block(blk)
            ahead = collections.deque()
            for make in makers:
                ahead.append(pool.submit(job, make))
                if len(ahead) > _BULK_WORKERS:
                    yield ahead.popleft().result()
            for got in ahead:
                yield got.result()

        def build(w):
            nonlocal n
            pipe = StreamPipeline(
                [lambda got: (w.add_columnar_block(*got), got[0].n)[1]],
                depth=2, name="bulk-load")
            with ThreadPoolExecutor(
                    max_workers=_BULK_WORKERS,
                    thread_name_prefix="bulk-block") as pool:
                for bn in pipe.run(serialized(w, pool)):
                    n += bn
        self.regular.ingest_sst(build, stream=True)
        self._m_rows_written.increment(n)
        return n

    # --- vector indexes (reference: vector_index/vector_lsm.cc,
    # docdb/doc_vector_index.cc; TPU-native IVF instead of HNSW) ----------
    def _scan_vectors(self, col_name: str):
        import numpy as np
        from ..docdb.operations import ReadRequest
        pk_names = tuple(c.name for c in self.info.schema.key_columns)
        resp = self._read_op.execute(ReadRequest(
            self.info.table_id, columns=pk_names + (col_name,),
            read_ht=self.clock.now().value))
        pks, vecs = [], []
        for r in resp.rows:
            v = r.get(col_name)
            if v is None:
                continue
            pks.append({n: r[n] for n in pk_names})
            vecs.append(np.frombuffer(v, np.float32))
        return pks, (np.stack(vecs) if vecs else np.zeros((0, 1), np.float32))

    def build_vector_index(self, col_name: str, nlists: int = 100,
                           method: str = "ivfflat",
                           options: Optional[dict] = None) -> int:
        """(Re)build the frozen ANN chunk through the index registry
        (``method`` is the DDL's USING clause). Safe against writes
        racing a background fold: overlay entries recorded before the
        scan fold into the chunk and are dropped; entries that arrive
        during the build are carried over into the new state."""
        cid = self.info.schema.column_by_name(col_name).id
        options = dict(options or {})
        options.setdefault("lists", nlists)
        with self._vector_build_lock:
            return self._build_vector_index_locked(
                cid, col_name, method, options)

    @staticmethod
    def _build_ann(method: str, options: dict, vecs) -> "object":
        """Registry dispatch with per-method option mapping (the DDL's
        WITH options are method-namespaced, like pgvector's)."""
        from ..vector import get_index_cls
        cls = get_index_cls(method)
        if method in ("ivfflat", "ivf"):
            # build() itself clamps nlists to the row count
            return cls.build(
                vecs, nlists=int(options.get("lists", 100)),
                iters=int(options.get("iters", 10)))
        if method == "hnsw":
            return cls.build(
                vecs, m=int(options.get("m", 16)),
                ef_construction=int(options.get("ef_construction", 100)),
                ef_search=int(options.get("ef_search", 64)))
        return cls.build(vecs, **options)

    def _build_vector_index_locked(self, cid, col_name, method,
                                   options) -> int:
        old = self.vector_indexes.get(cid)
        with self._lock:
            pending = dict(old.delta) if old else {}
            deadsnap = set(old.dead) if old else set()
        pks, vecs = self._scan_vectors(col_name)
        pk_names = tuple(c.name for c in self.info.schema.key_columns)
        state = _VectorIndexState(col_name, method, options)
        if len(vecs):
            state.idx = self._build_ann(method, options, vecs)
            state.pks = pks
            state.frozen_pos = {tuple(p[n_] for n_ in pk_names): i
                                for i, p in enumerate(pks)}
            state.frozen_keys = set(state.frozen_pos)
        with self._lock:
            if old is not None:
                # identity check: keep only entries written AFTER the
                # snapshot (same key re-written during the build stays)
                state.delta = {kk: v for kk, v in old.delta.items()
                               if pending.get(kk) is not v}
                state.dead = (old.dead - deadsnap) & state.frozen_keys
                # rows rewritten DURING the build exist in both places;
                # the delta copy is newer — hide the frozen one
                state.dead |= set(state.delta) & state.frozen_keys
            self.vector_indexes[cid] = state
        self._persist_vector_index(cid, state)
        return len(pks)

    def _maintain_vector_indexes(self, req: WriteRequest) -> None:
        """Incremental maintenance (reference: vector_lsm.cc mutable
        chunk): writes land in a delta buffer merged at search time;
        once the delta outgrows the frozen index, rebuild folds it in."""
        if not self.vector_indexes or req.table_id != self.info.table_id:
            return
        import time as _time
        pk_names = tuple(c.name for c in self.info.schema.key_columns)
        import numpy as _np
        with self._lock:
            for state in self.vector_indexes.values():
                for op in req.ops:
                    try:
                        pk_key = tuple(op.row[n] for n in pk_names)
                    except KeyError:
                        continue
                    if state.touched is not None:
                        state.touched.add(pk_key)
                    if op.kind != "delete" and op.ttl_ms is None:
                        # WAL-replay idempotence: a re-applied write
                        # whose vector EQUALS the frozen copy (and that
                        # nothing newer shadows) must not degrade the
                        # frozen chunk into delta churn on every
                        # restart
                        i = state.frozen_pos.get(pk_key)
                        v = op.row.get(state.col_name)
                        if (i is not None and v is not None
                                and pk_key not in state.dead
                                and pk_key not in state.delta):
                            fv = state.idx.vector_of(i)
                            nv = _np.frombuffer(bytes(v), _np.float32)
                            if (nv.shape == fv.shape
                                    and _np.array_equal(nv, fv)):
                                continue
                    state.delta.pop(pk_key, None)
                    # dead only hides FROZEN copies; fresh inserts never
                    # grow it (it bounds the search over-fetch)
                    if pk_key in state.frozen_keys:
                        state.dead.add(pk_key)
                    if op.kind != "delete":
                        v = op.row.get(state.col_name)
                        if v is None:
                            continue
                        expire = (None if op.ttl_ms is None else
                                  _time.time() + op.ttl_ms / 1000.0)
                        state.delta[pk_key] = (
                            {n: op.row[n] for n in pk_names}, bytes(v),
                            expire)

    def maybe_rebuild_vector_indexes(self) -> int:
        """Fold an outgrown delta back into the frozen ANN index
        (background-compaction analog). Returns indexes rebuilt."""
        n = 0
        for cid, state in list(self.vector_indexes.items()):
            churn = len(state.delta) + len(state.dead)
            if churn and churn >= max(64, len(state.pks) // 5):
                self.build_vector_index(state.col_name, state.nlists,
                                        state.method, state.options)
                n += 1
        return n

    def vector_search(self, col_name: str, query, k: int = 10,
                      nprobe: int = 8, ef_search=None):
        """Top-k (pk row, distance) for one tablet: the frozen ANN
        index (any registry method) + exact search over the live
        delta, merged; falls back to full exact search when no index
        is built.  ``nprobe`` drives IVF probing, ``ef_search`` the
        HNSW beam; either falls back to the index's build-time option
        when None."""
        import time as _time
        import numpy as np
        from ..ops.vector import exact_search
        cid = self.info.schema.column_by_name(col_name).id
        pk_names = tuple(c.name for c in self.info.schema.key_columns)
        q = np.asarray(query, np.float32)[None, :]
        state = self.vector_indexes.get(cid)
        if state is None:
            pks, vecs = self._scan_vectors(col_name)
            if not pks:
                return []
            d, ids = exact_search(q, vecs, k=min(k, len(pks)))
            return [(pks[int(i)], float(dist))
                    for dist, i in zip(np.asarray(d)[0],
                                       np.asarray(ids)[0])]
        with self._lock:
            dead = set(state.dead)
            now = _time.time()
            expired = [kk for kk, (_, _, exp) in state.delta.items()
                       if exp is not None and exp <= now]
            for kk in expired:
                del state.delta[kk]
            delta = list(state.delta.values())
        hits = []
        if state.idx is not None and state.pks:
            idx, pks = state.idx, state.pks
            # over-fetch so post-filtering dead rows still fills k
            k_ = min(k + len(dead), len(pks))
            params = {"nprobe": nprobe,
                      "ef_search": ef_search
                      or state.options.get("ef_search")}
            d, ids = idx.search(q, k=k_, **params)
            for dist, i in zip(d[0], ids[0]):
                if int(i) < 0 or not np.isfinite(float(dist)):
                    continue          # top_k padding, not a real hit
                pk = pks[int(i)]
                if tuple(pk[n] for n in pk_names) not in dead:
                    hits.append((pk, float(dist)))
        if delta:
            dpks = [p for p, _, _ in delta]
            dvecs = np.stack([np.frombuffer(v, np.float32)
                              for _, v, _ in delta])
            d, ids = exact_search(q, dvecs, k=min(k, len(dpks)))
            hits += [(dpks[int(i)], float(dist))
                     for dist, i in zip(np.asarray(d)[0],
                                        np.asarray(ids)[0])]
        hits.sort(key=lambda h: h[1])
        return hits[:k]

    # --- vector-index persistence (reference: vector_lsm.cc chunk
    # files next to tablet data; ours: vecidx/<col_id>/ under the
    # tablet directory, loaded + scan-diffed on bootstrap) -------------
    def _vecidx_dir(self, cid: int) -> str:
        return os.path.join(self.dir, "vecidx", str(cid))

    def _persist_vector_index(self, cid: int,
                              state: _VectorIndexState) -> None:
        """Best-effort durable copy of the frozen chunk + its pk map.
        Failures degrade to rebuild-on-bootstrap, never break the
        build itself."""
        import msgpack
        try:
            if state.idx is None:
                import shutil
                shutil.rmtree(self._vecidx_dir(cid), ignore_errors=True)
                return
            path = self._vecidx_dir(cid)
            state.idx.save(path)
            tmp = os.path.join(path, ".tablet_meta.tmp")
            with open(tmp, "wb") as f:
                f.write(msgpack.packb(
                    {"col_name": state.col_name,
                     "method": state.method,
                     "options": state.options,
                     "pks": state.pks}, use_bin_type=True))
                f.flush()
                os.fsync(f.fileno())
            os.replace(tmp, os.path.join(path, "tablet_meta.msgpack"))
        except Exception:   # noqa: BLE001 — persistence is an optimization
            import logging
            logging.getLogger(__name__).exception(
                "vector index persist failed for %s/%s",
                self.tablet_id, cid)

    def bootstrap_vector_indexes(self) -> int:
        """Load persisted ANN indexes and reconcile them with the
        CURRENT store via a scan-diff (rows written after the last
        save land in the delta; frozen rows that vanished or changed
        are hidden), so an index survives restart instead of being
        rebuilt per process.  Safe off the event loop (the tserver
        runs it in an executor): the state installs BEFORE the scan,
        so concurrent applies maintain it through the normal write
        path, and the diff merge skips any key maintenance touched
        since install (their version is newer — in particular a
        concurrent delete must not be resurrected by the scan's
        pre-delete image).  A torn/unreadable payload falls back to a
        full rebuild with the recorded method/options (rebuild-on-
        bootstrap); with no readable metadata the dir is ignored and
        the next CREATE INDEX starts fresh.  Returns indexes
        restored."""
        import msgpack
        import numpy as np
        from ..vector.registry import load_index
        root = os.path.join(self.dir, "vecidx")
        if not os.path.isdir(root) or self.colocated:
            return 0
        pk_names = tuple(c.name for c in self.info.schema.key_columns)
        restored = 0
        for ent in sorted(os.listdir(root)):
            path = os.path.join(root, ent)
            try:
                with open(os.path.join(path, "tablet_meta.msgpack"),
                          "rb") as f:
                    tmeta = msgpack.unpackb(f.read(), raw=False,
                                            strict_map_key=False)
                cid = self.info.schema.column_by_name(
                    tmeta["col_name"]).id
                if str(cid) != ent:
                    continue        # schema changed under the index
            except Exception:   # noqa: BLE001 — no metadata: ignore dir
                continue
            idx = load_index(path)
            # pks are positional: pks[i] owns index id i
            pks = [dict(p) for p in tmeta.get("pks", [])]
            if idx is None or idx.size != len(pks):
                # torn payload: rebuild from the store with the
                # recorded shape (the "rebuild" half of the contract)
                self.build_vector_index(
                    tmeta["col_name"],
                    int(tmeta.get("options", {}).get("lists", 100)),
                    tmeta.get("method", "ivfflat"),
                    tmeta.get("options"))
                restored += 1
                continue
            state = _VectorIndexState(tmeta["col_name"],
                                      tmeta.get("method", "ivfflat"),
                                      tmeta.get("options"))
            state.idx = idx
            state.pks = pks
            state.frozen_pos = {tuple(p[n] for n in pk_names): i
                                for i, p in enumerate(pks)}
            state.frozen_keys = set(state.frozen_pos)
            # install FIRST: concurrent applies (WAL replay) maintain
            # the delta through the normal write path from here on,
            # and record every touched key so the merge below defers
            # to them (deletes of non-frozen keys leave no delta/dead
            # trace — `touched` is their only footprint)
            state.touched = set()
            with self._lock:
                self.vector_indexes[cid] = state
            # scan-diff against the live store
            cur_pks, cur_vecs = self._scan_vectors(state.col_name)
            frozen = idx.vectors_in_id_order()
            pos = state.frozen_pos
            cur_keys = set()
            diff = []
            for j, pk in enumerate(cur_pks):
                key = tuple(pk[n] for n in pk_names)
                cur_keys.add(key)
                i = pos.get(key)
                if i is not None and np.array_equal(cur_vecs[j],
                                                    frozen[i]):
                    continue
                diff.append((key, (pk, cur_vecs[j].tobytes(), None),
                             i is not None))
            with self._lock:
                for key, entry, was_frozen in diff:
                    if key in state.touched or key in state.delta \
                            or key in state.dead:
                        continue    # maintenance got there first
                    state.delta[key] = entry
                    if was_frozen:
                        state.dead.add(key)
                state.dead |= state.frozen_keys - cur_keys \
                    - set(state.delta) - state.touched
                state.touched = None
            restored += 1
        return restored

    # --- snapshots --------------------------------------------------------
    def create_snapshot(self, out_dir: str):
        """Consistent tablet snapshot: flush + hard-link checkpoint
        (reference: tablet/tablet_snapshots.cc:186,273). Includes the
        IntentsDB so a bootstrapped replica keeps in-flight txn
        provisional records (reference: remote_bootstrap_session.cc
        streams both rocksdb instances). MUST be called from the apply
        thread (the event loop): both checkpoints then form one
        consistent cut — no txn apply can interleave between them and
        leave e.g. release-tombstones in the intents checkpoint for
        rows the regular checkpoint missed. Returns the regular store's
        flushed op index (the snapshot's replication frontier)."""
        self.flush()
        self.regular.checkpoint(os.path.join(out_dir, "regular"))
        self.intents.flush()
        self.intents.checkpoint(os.path.join(out_dir, "intents"))
        op = self.regular.flushed_frontier().get("op_id")
        return int(op[1]) if op else None

    def trim_above_ht(self, cutoff: int) -> int:
        """Enforce a single-HT consistent cut: drop every version whose
        DocHybridTime exceeds `cutoff`. Run on a freshly-restored tablet
        so a snapshot taken at one hybrid time reads identically across
        tablets even when their clocks were skewed at checkpoint time
        (reference: tablet_snapshots.cc restore with history cutoff).
        Returns the number of dropped versions."""
        from ..dockv.key_encoding import split_key_ht
        from ..storage.lsm import CompactionFeed
        self.flush()
        inputs = self.regular.ssts
        if not inputs:
            return 0

        class _TrimFeed(CompactionFeed):
            dropped = 0

            def feed(self, key, value):
                try:
                    if split_key_ht(key)[1].ht.value > cutoff:
                        self.dropped += 1
                        return []
                except ValueError:
                    pass              # no HT suffix (shouldn't happen)
                return [(key, value)]

        feed = _TrimFeed()
        self.regular.compact(inputs, feed)
        return feed.dropped

    @classmethod
    def restore_snapshot(cls, tablet_id: str, info: TableInfo,
                         snapshot_dir: str, directory: str,
                         clock=None) -> "Tablet":
        import shutil
        os.makedirs(directory, exist_ok=True)
        dst = os.path.join(directory, "regular")
        if os.path.exists(dst):
            shutil.rmtree(dst)
        shutil.copytree(os.path.join(snapshot_dir, "regular"), dst)
        return cls(tablet_id, info, directory, clock=clock)

    def approximate_size(self) -> int:
        return self.regular.approximate_size()

    def num_sst_files(self) -> int:
        return len(self.regular.ssts)
