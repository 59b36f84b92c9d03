"""Per-tablet LSM store: memtable + SSTs + flush + compaction + checkpoint.

Analog of the reference's forked RocksDB DB instance per tablet
(reference: src/yb/rocksdb/db/db_impl.cc), with the YB-specific traits
kept: NO WAL of its own (the Raft log is the WAL — reference:
src/yb/consensus/README), consensus frontiers persisted in SST files and
the manifest (flushed op id decides bootstrap replay start), a pluggable
streaming CompactionFeed seam (reference:
src/yb/rocksdb/compaction_filter.h CompactionFeed), and hard-link
checkpoints (reference: rocksdb/utilities/checkpoint.cc).

Compaction style is size-tiered/universal (reference default for YB).
"""
from __future__ import annotations

import json
import os
import re
import threading
from dataclasses import dataclass, field
from typing import Callable, Dict, Iterator, List, Optional, Sequence, Tuple

from ..utils import flags
from ..utils.fault_injection import (MAYBE_FAULT, TEST_CRASH_POINT,
                                     TEST_DISK_STALL)
from .memtable import MemTable
from .merge import merging_iterator
from .sst import SstReader, SstWriter


@dataclass
class WriteBatch:
    """Ordered KV puts applied atomically to the memtable. Deletes are
    tombstone values written by the docdb layer; storage doesn't interpret
    values."""
    entries: List[Tuple[bytes, bytes]] = field(default_factory=list)
    # Raft op id (term, index) that produced this batch; becomes the
    # flushed frontier when the memtable holding it is flushed.
    op_id: Optional[Tuple[int, int]] = None

    def put(self, key: bytes, value: bytes) -> "WriteBatch":
        self.entries.append((key, value))
        return self

    def __len__(self):
        return len(self.entries)


class CompactionFeed:
    """Streaming compaction hook (reference: rocksdb/compaction_filter.h
    CompactionFeed + docdb/docdb_compaction_context.cc DocDBCompactionFeed).

    Subclasses see the merged, sorted entry stream and decide what
    survives into the output SST. `feed` returns entries to emit now;
    `flush` emits any held-back tail. `feed_block` lets a vectorized/TPU
    implementation process whole sorted runs at once.
    """

    def feed(self, key: bytes, value: bytes) -> List[Tuple[bytes, bytes]]:
        return [(key, value)]

    def feed_block(self, entries: Sequence[Tuple[bytes, bytes]]
                   ) -> List[Tuple[bytes, bytes]]:
        """Chunked seam: the store hands the merged stream over in
        batches so a vectorized feed can process whole sorted runs at
        once (the pipelined device engine in docdb/compaction.py is the
        canonical implementation). Default delegates to per-row feed —
        subclasses override exactly one of the two."""
        out: List[Tuple[bytes, bytes]] = []
        for k, v in entries:
            out.extend(self.feed(k, v))
        return out

    def flush(self) -> List[Tuple[bytes, bytes]]:
        return []


class SstLease:
    """Refcount lease over an LsmStore's live SST FILES (not readers):
    while held, compaction/truncate may remove the files from the store
    but their physical deletion is deferred until the last lease drops
    (reference analog: rocksdb's version refcounting keeping obsolete
    files alive for open iterators).  Out-of-band readers — the
    analytics bypass engine — open the leased paths directly, so the
    lease is what makes "scan a tablet's SST set without the tserver"
    safe against concurrent file GC.

    Release exactly once via :meth:`release` (or the context manager);
    a lease leaked by a crashed process leaves unmanifested files on
    disk, which the store's open-time sweep reclaims."""

    def __init__(self, store: "LsmStore", paths: List[str],
                 frontier: dict):
        self.store = store
        self.paths = paths
        self.frontier = frontier
        self._released = False

    def release(self) -> None:
        if not self._released:
            self._released = True
            self.store._release_pins(self.paths)

    @property
    def released(self) -> bool:
        return self._released

    def __enter__(self) -> "SstLease":
        return self

    def __exit__(self, *exc) -> None:
        self.release()


class LsmStore:
    def __init__(self, directory: str, name: str = "db",
                 columnar_builder=None, row_decoder=None,
                 key_builder=None, shred_cols=None):
        self.dir = directory
        self.name = name
        self.columnar_builder = columnar_builder
        self.row_decoder = row_decoder
        # v2 keyless-block support: rebuilds a block's key matrix from
        # its pk + MVCC lanes (docdb codec callable); writers verify
        # key drops against it, readers re-derive lazily through it
        self.key_builder = key_builder
        # JSON column ids to document-shred at flush (docstore/);
        # SstWriter resolves the doc_shred_enabled gate per file
        self.shred_cols = tuple(shred_cols or ())
        os.makedirs(directory, exist_ok=True)
        self._lock = threading.RLock()
        # serializes the file-writing half of flushes (background
        # executor vs an inline drain): frozen memtables must hit disk
        # oldest-first or the newest-first SST order and the flushed
        # frontier would both break
        self._flush_io_lock = threading.Lock()
        self._mem = MemTable()
        self._frozen: List[MemTable] = []
        # id(frozen memtable) -> the _mem_frontier captured at freeze
        self._frozen_frontiers: Dict[int, dict] = {}
        self._ssts: List[SstReader] = []       # newest first
        self._next_file = 0
        self._flushed_frontier: dict = {}
        self._write_gen = 0
        self._struct_gen = 0           # bumps on flush/compact/replace
        self._snap = None              # cached (gen-key, (mems, ssts))
        # the device read path's facts about the blocks of the current
        # contents (docdb/operations.py StoreFacts, which names the
        # contents it was made for); None = none made yet, or dropped
        self.read_facts = None
        self._mem_frontier: dict = {}
        # out-of-band reader leases: path -> refcount; paths the store
        # dropped while pinned wait in _deferred until the last lease
        # releases them (then the physical unlink happens)
        self._pins: Dict[str, int] = {}
        self._deferred: set = set()
        self._load_manifest()
        self._sweep_unmanifested()

    # --- manifest ---------------------------------------------------------
    @property
    def _manifest_path(self) -> str:
        return os.path.join(self.dir, f"{self.name}.MANIFEST")

    def _load_manifest(self) -> None:
        if not os.path.exists(self._manifest_path):
            return
        with open(self._manifest_path) as f:
            m = json.load(f)
        self._next_file = m["next_file"]
        self._flushed_frontier = m.get("flushed_frontier", {})
        for fname in m["ssts"]:
            self._ssts.append(SstReader(os.path.join(self.dir, fname),
                                        row_decoder=self.row_decoder,
                                        key_builder=self.key_builder))

    def _write_manifest(self) -> None:
        m = {
            "next_file": self._next_file,
            "flushed_frontier": self._flushed_frontier,
            "ssts": [os.path.basename(r.path) for r in self._ssts],
        }
        tmp = self._manifest_path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(m, f)
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, self._manifest_path)

    def _sweep_unmanifested(self) -> None:
        """Crash-safe sweep at open (the PR-4 tombstone discipline
        applied to SST files): the manifest is the single source of
        truth for live SSTs, so any ``<name>.NNNNNN.sst`` (or its
        ``.tmp``) in the directory that the manifest does not reference
        is garbage — a flush/ingest that crashed before its manifest
        install, or a pin-deferred delete whose process died before the
        lease released.  Both are reclaimed here, before any reader or
        new lease can observe them.  No live LsmStore writes into this
        directory while __init__ runs, so the sweep races nothing."""
        live = {os.path.basename(r.path) for r in self._ssts}
        pat = re.compile(re.escape(self.name) + r"\.\d{6,}\.sst(\.tmp)?$")
        try:
            entries = os.listdir(self.dir)
        except OSError:
            return
        for fn in entries:
            if pat.fullmatch(fn) and fn not in live:
                try:
                    os.unlink(os.path.join(self.dir, fn))
                except OSError:
                    pass

    # --- out-of-band reader leases ----------------------------------------
    def pin_ssts(self, require_empty_memtable: bool = False
                 ) -> Optional[SstLease]:
        """Lease the CURRENT live SST set against file GC.  With
        ``require_empty_memtable`` the pin only succeeds while no
        memtable (active or frozen) holds rows — checked under the same
        lock that installs flush output, so the returned file set is a
        complete image of everything applied before the pin (the
        snapshot pinner's atomicity requirement); returns None when a
        memtable is busy and the caller retries after a flush."""
        with self._lock:
            if require_empty_memtable and not (
                    self._mem.empty() and not self._frozen):
                return None
            paths = [r.path for r in self._ssts]
            for p in paths:
                self._pins[p] = self._pins.get(p, 0) + 1
            frontier = dict(self._flushed_frontier)
        return SstLease(self, paths, frontier)

    def _release_pins(self, paths: Sequence[str]) -> None:
        drop: List[str] = []
        with self._lock:
            for p in paths:
                c = self._pins.get(p, 0) - 1
                if c > 0:
                    self._pins[p] = c
                else:
                    self._pins.pop(p, None)
                    if p in self._deferred:
                        self._deferred.discard(p)
                        drop.append(p)
        for p in drop:
            try:
                os.unlink(p)
            except OSError:
                pass

    def _gc_file(self, path: str) -> None:
        """Physical SST removal for files the store no longer owns
        (compaction inputs, truncate victims).  Deletion defers while
        any lease pins the path — the last release performs the unlink;
        a crash in the deferred window leaves an unmanifested file the
        next open sweeps."""
        with self._lock:
            if self._pins.get(path, 0) > 0:
                self._deferred.add(path)
                return
        try:
            os.remove(path)
        except OSError:
            pass

    def pin_stats(self) -> dict:
        """Live lease accounting (tests + the bypass session stats)."""
        with self._lock:
            return {"pinned_files": sum(1 for c in self._pins.values()
                                        if c > 0),
                    "deferred_deletes": len(self._deferred)}

    # --- writes -----------------------------------------------------------
    def apply(self, batch: WriteBatch) -> None:
        MAYBE_FAULT()
        with self._lock:
            for k, v in batch.entries:
                self._mem.put(k, v)
            self._write_gen += 1
            if batch.op_id is not None:
                self._mem_frontier["op_id"] = list(batch.op_id)

    def write_generation(self) -> int:
        """Monotone counter bumped on every memtable write — device
        batch cache keys include it so a cached batch can never hide a
        newer committed write."""
        return self._write_gen

    def read_snapshot(self):
        """Cached ([non-empty memtables], [ssts]) for the point-read hot
        path: rebuilding these lists under the lock on every get was
        measurable at OLTP rates. The key covers both data writes
        (_write_gen) and structural changes (_struct_gen), so a stale
        snapshot can never be served after a write, flush or compaction
        it does not contain."""
        key = (self._write_gen, self._struct_gen)
        snap = self._snap
        if snap is not None and snap[0] == key:
            return snap[1]
        with self._lock:
            mems = [m for m in [self._mem] + list(self._frozen)
                    if not m.empty()]
            val = (mems, list(self._ssts))
            self._snap = ((self._write_gen, self._struct_gen), val)
        return val

    def should_flush(self) -> bool:
        return (self._mem.approximate_bytes()
                >= flags.get("memstore_flush_threshold_bytes"))

    def freeze_active(self) -> bool:
        """Freeze the active memtable into the frozen queue — a pure
        in-memory pointer swap (the fast half of a flush; the async
        flush path hands the slow half to a background executor).
        Returns True when a new frozen memtable was produced."""
        with self._lock:
            if self._mem.empty():
                return False
            mem = self._mem
            mem.freeze()
            self._frozen.append(mem)
            self._frozen_frontiers[id(mem)] = dict(self._mem_frontier)
            self._mem = MemTable()
            self._struct_gen += 1
            self._mem_frontier = {}
        return True

    def frozen_count(self) -> int:
        with self._lock:
            return len(self._frozen)

    def flush_frozen(self, wait: bool = True) -> Optional[str]:
        """Write the OLDEST frozen memtable to an SST and install it
        (the slow half of a flush — file write, fsync, manifest).
        Serialized under the flush IO lock so a background flush and an
        inline drain can never install out of order; the flushed
        frontier and newest-first SST order therefore stay monotone.
        ``wait=False`` gives up immediately when another flush owns the
        IO lock (the pinner's bounded-attempt contract: a stuck foreign
        flush must surface as a typed refusal, never a hang).
        Returns the new SST path, or None when there was nothing to do,
        the lock was busy (wait=False), or a TRUNCATE raced the write."""
        if not self._flush_io_lock.acquire(blocking=wait):
            return None
        try:
            with self._lock:
                if not self._frozen:
                    return None
                mem = self._frozen[0]
                frontier = dict(self._frozen_frontiers.get(id(mem), {}))
            path = self._new_sst_path()
            # chaos seam: an armed disk stall holds THIS thread (the
            # flush worker), exactly like a hung device under the SST
            # write
            TEST_DISK_STALL()
            w = SstWriter(path, columnar_builder=self.columnar_builder,
                          key_builder=self.key_builder,
                          shred_cols=self.shred_cols)
            for k, v in mem.iterate():
                w.add(k, v)
            w.set_frontier(**frontier)
            w.finish()
            TEST_CRASH_POINT("flush:before_manifest")
            with self._lock:
                if mem not in self._frozen:
                    # a TRUNCATE dropped the frozen memtable while this
                    # flush wrote it out — installing the SST would
                    # resurrect truncated rows
                    try:
                        os.unlink(path)
                    except OSError:
                        pass
                    return None
                self._ssts.insert(
                    0, SstReader(path, row_decoder=self.row_decoder,
                                 key_builder=self.key_builder))
                self._frozen.remove(mem)
                self._frozen_frontiers.pop(id(mem), None)
                self._struct_gen += 1
                if "op_id" in frontier:
                    cur = self._flushed_frontier.get("op_id")
                    if cur is None or frontier["op_id"] > cur:
                        self._flushed_frontier["op_id"] = frontier["op_id"]
                self._write_manifest()
            return path
        finally:
            self._flush_io_lock.release()

    def flush(self, wait: bool = True) -> Optional[str]:
        """Freeze the memtable and drain EVERY frozen memtable to SSTs
        synchronously (helping any in-flight background flush along —
        the IO lock serializes installs).  ``wait=False`` is the
        pinner's best-effort drain: it never blocks behind a foreign
        flush that owns the IO lock.  Returns the last SST path
        written (None if nothing flushed)."""
        self.freeze_active()
        last = None
        while True:
            with self._lock:
                if not self._frozen:
                    return last
            p = self.flush_frozen(wait=wait)
            if p is not None:
                last = p
            elif not wait:
                return last     # foreign flush owns the IO lock

    def truncate(self, op_id=None) -> int:
        """Drop EVERYTHING: memtables, frozen memtables, and SST files
        (reference: tablet truncate, src/yb/tablet/tablet.cc Truncate —
        replaces the RocksDB instances wholesale rather than writing
        tombstones).  The manifest persists the empty state atomically
        so a crash right after cannot resurrect deleted SSTs, and the
        flushed frontier advances to the truncate op so WAL replay
        resumes AFTER it (pre-truncate writes need not replay — their
        effect is gone either way).  Returns the number of SST files
        removed."""
        with self._lock:
            removed = list(self._ssts)
            self._mem = MemTable()
            self._frozen = []
            self._frozen_frontiers = {}
            self._ssts = []
            self._mem_frontier = {}
            self._struct_gen += 1
            self._write_gen += 1
            self._snap = None
            if op_id is not None:
                self._flushed_frontier["op_id"] = list(op_id)
            self._write_manifest()
        n = 0
        for r in removed:
            try:
                r.close() if hasattr(r, "close") else None
            except OSError:
                pass
            self._gc_file(r.path)
            n += 1
        return n

    def ingest_sst(self, build: Callable[[SstWriter], None],
                   frontier: Optional[dict] = None,
                   stream: bool = False) -> str:
        """Bulk load: caller fills a writer (rows or columnar blocks).
        ``stream=True`` opens the writer in stream-columnar mode: each
        add_columnar_block hits the file immediately (the write releases
        the GIL), so a pipelined builder overlaps gathers with IO."""
        path = self._new_sst_path()
        w = SstWriter(path, columnar_builder=self.columnar_builder,
                      stream_columnar=stream,
                      sync_every_bytes=(64 << 20) if stream else None,
                      key_builder=self.key_builder,
                      shred_cols=self.shred_cols)
        try:
            build(w)
        except BaseException:
            w.abort()
            raise
        if frontier:
            w.set_frontier(**frontier)
        w.finish()
        with self._lock:
            self._ssts.insert(0, SstReader(path, row_decoder=self.row_decoder,
                                           key_builder=self.key_builder))
            self._struct_gen += 1
            self._write_manifest()
        return path

    def _new_sst_path(self) -> str:
        with self._lock:
            n = self._next_file
            self._next_file += 1
        return os.path.join(self.dir, f"{self.name}.{n:06d}.sst")

    # --- reads ------------------------------------------------------------
    def iterate(self, lower: Optional[bytes] = None,
                upper: Optional[bytes] = None) -> Iterator[Tuple[bytes, bytes]]:
        """Merged view, ascending; newest source wins on exact-key ties."""
        with self._lock:
            sources = [self._mem.iterate(lower, upper)]
            sources += [m.iterate(lower, upper) for m in reversed(self._frozen)]
            sources += [r.iterate(lower, upper) for r in self._ssts]
        return merging_iterator(sources)

    def seek(self, key: bytes) -> Iterator[Tuple[bytes, bytes]]:
        return self.iterate(lower=key)

    def get(self, key: bytes) -> Optional[bytes]:
        """Exact-key point get."""
        for k, v in self.iterate(lower=key):
            return v if k == key else None
        return None

    @property
    def ssts(self) -> List[SstReader]:
        with self._lock:
            return list(self._ssts)

    def memtable_empty(self) -> bool:
        return self._mem.empty() and not self._frozen

    def flushed_frontier(self) -> dict:
        return dict(self._flushed_frontier)

    def approximate_size(self) -> int:
        with self._lock:
            return (sum(r.file_size for r in self._ssts)
                    + self._mem.approximate_bytes())

    # --- compaction -------------------------------------------------------
    def pick_compaction(self, max_files: int = 8) -> List[SstReader]:
        """Pick the OLDEST contiguous run (universal compaction picks
        age-adjacent runs). Contiguity in age is what lets the output be
        placed after all kept (newer) SSTs without breaking the
        newest-source-wins merge invariant."""
        with self._lock:
            if len(self._ssts) < 4:
                return []
            return list(self._ssts[-max_files:])   # newest-first list tail

    def compact(self, inputs: Optional[Sequence[SstReader]] = None,
                feed: Optional[CompactionFeed] = None,
                is_major: bool = False) -> Optional[str]:
        """Merge `inputs` (default: all SSTs = major compaction) through the
        feed into one output SST. The TPU path replaces this loop via
        docdb/compaction (ops/compaction.py) and calls replace_ssts."""
        with self._lock:
            if inputs is None:
                inputs = list(self._ssts)
                is_major = True
            inputs = list(inputs)
        if not inputs:
            return None
        feed = feed or CompactionFeed()
        path = self._new_sst_path()
        w = SstWriter(path, columnar_builder=self.columnar_builder,
                      key_builder=self.key_builder,
                      shred_cols=self.shred_cols)
        # merge newest-first sources; exact dup keys keep newest. The
        # stream goes through the feed in chunks (feed_block) so
        # vectorized feeds see whole sorted runs, not single rows.
        merged = merging_iterator([r.iterate() for r in inputs])
        batch: List[Tuple[bytes, bytes]] = []
        for kv in merged:
            batch.append(kv)
            if len(batch) >= 4096:
                for ok, ov in feed.feed_block(batch):
                    w.add(ok, ov)
                batch = []
        if batch:
            for ok, ov in feed.feed_block(batch):
                w.add(ok, ov)
        for ok, ov in feed.flush():
            w.add(ok, ov)
        frontier = {}
        for r in inputs:
            if "op_id" in r.frontier:
                op = r.frontier["op_id"]
                if "op_id" not in frontier or op > frontier["op_id"]:
                    frontier["op_id"] = op
        w.set_frontier(**frontier)
        w.finish()
        self.replace_ssts(inputs, path)
        return path

    def replace_ssts(self, old: Sequence[SstReader], new_path: str) -> None:
        with self._lock:
            old_set = {id(r) for r in old}
            live = {id(r) for r in self._ssts}
            if not old_set <= live:
                # the input set changed under the merge — a TRUNCATE
                # (or competing compaction) removed inputs while the
                # merge ran off-lock.  Installing the merged output
                # would resurrect rows the store no longer owns; the
                # merge result is simply discarded.
                try:
                    os.remove(new_path)
                except OSError:
                    pass
                return
            new_reader = SstReader(new_path, row_decoder=self.row_decoder,
                                   key_builder=self.key_builder)
            kept = [r for r in self._ssts if id(r) not in old_set]
            # output is older than anything not in the inputs → append last
            self._ssts = kept + [new_reader]
            self._struct_gen += 1
            self._write_manifest()
        for r in old:
            self._gc_file(r.path)

    # --- checkpoint -------------------------------------------------------
    def checkpoint(self, out_dir: str) -> None:
        """Hard-link all live SSTs + copy manifest (reference:
        rocksdb Checkpoint::CreateCheckpoint via
        tablet/tablet_snapshots.cc:273). Memtable contents are NOT
        included — callers flush first for a point-in-time image."""
        os.makedirs(out_dir, exist_ok=True)
        with self._lock:
            ssts = list(self._ssts)
            for r in ssts:
                dst = os.path.join(out_dir, os.path.basename(r.path))
                if not os.path.exists(dst):
                    os.link(r.path, dst)
            m = {
                "next_file": self._next_file,
                "flushed_frontier": self._flushed_frontier,
                "ssts": [os.path.basename(r.path) for r in ssts],
            }
        with open(os.path.join(out_dir, f"{self.name}.MANIFEST"), "w") as f:
            json.dump(m, f)

    @classmethod
    def open_checkpoint(cls, directory: str, name: str = "db",
                        **kw) -> "LsmStore":
        return cls(directory, name, **kw)
