"""SSTable file format: sorted KV blocks + columnar sidecars + bloom + index.

Analog of the reference's BlockBasedTable (reference:
src/yb/rocksdb/table/block_based_table_{builder,reader}.cc) redesigned
around the TPU scan path: every data block can carry a serialized
ColumnarBlock sidecar so scans read struct-of-arrays pages directly
instead of re-decoding row KVs. Blocks are cut by ROW COUNT (default
4096) so columnar pages are uniform kernel batches.

File layout:
    [data block 0][data block 1]...
    [columnar block 0][columnar block 1]...   (optional per block)
    [bloom filter]
    [index: msgpack list of per-block entries]
    [footer: msgpack meta][u32 footer_len]["YBTPUSST"]
"""
from __future__ import annotations

import os
import struct
from dataclasses import dataclass
from typing import Callable, Iterator, List, Optional, Sequence, Tuple

import msgpack
import numpy as np

from ..utils.hybrid_time import ENCODED_SIZE as _HT_ENC
from . import native_lib
from .columnar import (SUPPORTED_FORMAT_VERSION, ColumnarBlock,
                       fnv64_bytes, fnv64_keys, native_hot as _hot_mod)


def resolve_format_version() -> int:
    """THE writer-side gate for the on-disk block format: v2 only when
    ``sst_format_version`` is exactly 2; anything else (including a
    missing registry in odd test harnesses) writes the byte-identical
    v1 format. Every SstWriter resolves through here, so no writer can
    emit v2 while the flag says 1."""
    from ..utils import flags as _flags
    try:
        v = int(_flags.get("sst_format_version"))
    except Exception:   # noqa: BLE001 — default to the compatible format
        return 1
    return 2 if v == 2 else 1

_HT_MARKER = 0x05          # dockv ValueType.kHybridTime
_HT_SUFFIX = _HT_ENC + 1

def _native_finder(cb: ColumnarBlock):
    """Build (and cache on the block) the native fused point-lookup
    (native/ybtpu_hot.c BlockFinder); None when unavailable."""
    f = getattr(cb, "_finder", False)
    if f is not False:
        return f
    hot = _hot_mod()
    f = None
    if hot is not None and cb.keys is not None and cb.n:
        try:
            keys = np.ascontiguousarray(cb.keys)
            ht = np.ascontiguousarray(cb.ht.astype(np.uint64, copy=False))
            wid = np.ascontiguousarray(
                cb.write_id.astype(np.uint32, copy=False))
            tomb = np.ascontiguousarray(
                cb.tombstone.astype(np.uint8, copy=False))
            f = hot.BlockFinder(keys, ht, wid, tomb, cb.n, keys.shape[1])
        except Exception:
            f = None
    object.__setattr__(cb, "_finder", f)
    return f


def _doc_key_of(k: bytes) -> bytes:
    """Strip the hybrid-time suffix when present (doc-key bloom/point
    lookups are by key prefix)."""
    if len(k) > _HT_SUFFIX and k[-_HT_SUFFIX] == _HT_MARKER:
        return k[:-_HT_SUFFIX]
    return k

MAGIC = b"YBTPUSST"
DEFAULT_BLOCK_ROWS = 4096


class BloomFilter:
    """Double-hashing bloom over 64-bit key hashes (reference:
    src/yb/rocksdb/util/bloom.cc; fixed-key bloom over doc keys)."""

    def __init__(self, bits: np.ndarray, k: int):
        self.bits = bits          # uint8 array
        self.k = k

    @classmethod
    def build(cls, key_hashes: np.ndarray, bits_per_key: int = 10) -> "BloomFilter":
        n = max(1, len(key_hashes))
        m = max(64, n * bits_per_key)
        m = (m + 7) // 8 * 8
        k = max(1, min(30, int(round(bits_per_key * 0.69))))
        nat = native_lib.bloom_build(
            np.asarray(key_hashes, np.uint64), m, k)
        if nat is not None:
            return cls(nat, k)
        bits = np.zeros(m // 8, np.uint8)
        h1 = key_hashes.astype(np.uint64)
        h2 = (h1 >> np.uint64(33)) | np.uint64(1)
        for i in range(k):
            idx = (h1 + np.uint64(i) * h2) % np.uint64(m)
            np.bitwise_or.at(bits, (idx // 8).astype(np.int64),
                             (1 << (idx % 8)).astype(np.uint8))
        return cls(bits, k)

    def may_contain(self, key_hash: int) -> bool:
        hot = _hot_mod()
        if hot is not None:
            return hot.bloom_may_contain(self.bits, self.k,
                                         key_hash & 0xFFFFFFFFFFFFFFFF)
        m = len(self.bits) * 8
        h1 = key_hash & 0xFFFFFFFFFFFFFFFF
        h2 = ((h1 >> 33) | 1)
        for i in range(self.k):
            idx = (h1 + i * h2) % m
            if not (self.bits[idx // 8] >> (idx % 8)) & 1:
                return False
        return True

    def serialize(self) -> bytes:
        return struct.pack("<I", self.k) + self.bits.tobytes()

    @classmethod
    def deserialize(cls, data: bytes) -> "BloomFilter":
        k = struct.unpack_from("<I", data)[0]
        return cls(np.frombuffer(data[4:], np.uint8).copy(), k)


def _encode_block(entries: Sequence[Tuple[bytes, bytes]]) -> bytes:
    """Shared-prefix-compressed KV block (native fast path when built)."""
    enc = native_lib.block_encode(entries)
    if enc is not None:
        return enc
    out = bytearray(struct.pack("<I", len(entries)))
    prev = b""
    for k, v in entries:
        shared = os.path.commonprefix([prev, k]) if prev else b""
        s = len(shared)
        out += _uvarint(s) + _uvarint(len(k) - s) + _uvarint(len(v))
        out += k[s:] + v
        prev = k
    return bytes(out)


def _decode_block(data: bytes) -> List[Tuple[bytes, bytes]]:
    dec = native_lib.block_decode(data)
    if dec is not None:
        return dec
    (n,) = struct.unpack_from("<I", data)
    pos = 4
    out: List[Tuple[bytes, bytes]] = []
    prev = b""
    for _ in range(n):
        shared, pos = _read_uvarint(data, pos)
        unshared, pos = _read_uvarint(data, pos)
        vlen, pos = _read_uvarint(data, pos)
        key = prev[:shared] + data[pos:pos + unshared]
        pos += unshared
        val = data[pos:pos + vlen]
        pos += vlen
        out.append((key, val))
        prev = key
    return out


def _uvarint(v: int) -> bytes:
    out = bytearray()
    while True:
        b = v & 0x7F
        v >>= 7
        if v:
            out.append(b | 0x80)
        else:
            out.append(b)
            return bytes(out)


def _read_uvarint(data: bytes, pos: int) -> Tuple[int, int]:
    v = shift = 0
    while True:
        b = data[pos]
        pos += 1
        v |= (b & 0x7F) << shift
        if not b & 0x80:
            return v, pos
        shift += 7


# Callback: (entries in one block) -> ColumnarBlock | None. Provided by the
# docdb layer, which knows the packed-row schema; storage stays agnostic.
ColumnarBuilderFn = Callable[[Sequence[Tuple[bytes, bytes]]], Optional[ColumnarBlock]]


@dataclass
class BlockIndexEntry:
    first_key: bytes
    last_key: bytes
    offset: int
    length: int
    num_rows: int
    col_offset: int = -1
    col_length: int = 0


class SstWriter:
    def __init__(self, path: str, block_rows: int = DEFAULT_BLOCK_ROWS,
                 columnar_builder: Optional[ColumnarBuilderFn] = None,
                 stream_columnar: bool = False,
                 sync_every_bytes: Optional[int] = None,
                 format_version: Optional[int] = None,
                 key_builder=None, shred_cols=None):
        self.path = path
        self.block_rows = block_rows
        self.columnar_builder = columnar_builder
        # on-disk block format: None resolves the sst_format_version
        # flag ONCE at construction (a mid-write flag flip must not mix
        # formats inside one file); explicit 1 pins the pre-v2 bytes
        # (the baseline compaction path measures against it)
        self._fmt = (resolve_format_version() if format_version is None
                     else (2 if format_version == 2 else 1))
        # v2 only: callable(cb) -> rebuilt keys matrix | None. When the
        # rebuild byte-matches, the block serializes WITHOUT its keys
        # matrix (readers re-derive lazily through the same callable).
        self.key_builder = key_builder if self._fmt == 2 else None
        # v2 only: JSON column ids to document-shred (docstore/).
        # THE doc_shred_enabled writer gate: resolved ONCE here (a
        # mid-write flag flip must not mix shredded and unshredded
        # blocks in one file); flag off — or format 1 — pins the
        # byte-identical pre-shred output.
        self.shred_cols: tuple = ()
        if shred_cols and self._fmt == 2:
            from ..utils import flags as _flags
            try:
                enabled = bool(_flags.get("doc_shred_enabled"))
            except Exception:   # noqa: BLE001 — odd harness: stay
                enabled = False  # byte-compatible
            if enabled:
                self.shred_cols = tuple(shred_cols)
        #: per-lane encode accounting accumulated across this file's
        #: blocks (the chunked compaction copies it into its
        #: stats; {"lanes": {lane: {pre_bytes, post_bytes, encodings}}})
        self.lane_stats: dict = {}
        if stream_columnar:
            from ..utils import flags as _flags
            stream_columnar = not _flags.get("encrypt_data_at_rest")
        self._stream = stream_columnar
        # stream mode only: fsync every N written bytes FROM THE WRITER
        # THREAD, so the pipelined producers overlap the disk flush and
        # finish()'s final fsync covers only the tail instead of the
        # whole dirty file (the r05 compaction fsync tail was ~0.8s of a
        # ~1.5s wall). None keeps the single finish-time fsync.
        self._sync_every = sync_every_bytes
        self._synced_to = 0
        self._sf = None
        self._stream_index: List[BlockIndexEntry] = []
        self._entries: List[Tuple[bytes, bytes]] = []
        self._blocks: List[Sequence[Tuple[bytes, bytes]]] = []
        self._key_hashes: List[np.ndarray] = []
        self._num_entries = 0
        self._min_key: Optional[bytes] = None
        self._max_key: Optional[bytes] = None
        self._frontier: dict = {}
        self._last_key: Optional[bytes] = None
        # blocks are either row lists or pre-built ColumnarBlocks
        self._col_only: List[Optional[ColumnarBlock]] = []

    def add(self, key: bytes, value: bytes) -> None:
        if self._sf is not None:
            # streaming finish() returns early and would silently drop
            # buffered row entries — refuse the mix up front
            raise ValueError("stream mode cannot mix row entries after "
                             "streamed columnar blocks")
        if self._last_key is not None and key < self._last_key:
            raise ValueError("keys must be added in sorted order")
        self._last_key = key
        self._entries.append((key, value))
        if len(self._entries) >= self.block_rows:
            self._blocks.append(self._entries)
            self._col_only.append(None)
            self._entries = []

    def serialize_block(self, cb: ColumnarBlock):
        """`cb` as `add_columnar_block` would write it, for a caller that
        serializes blocks on threads of its own and hands each result to
        `add_columnar_block(cb, parts)` in block order.  Keeps no lane
        accounting (`lane_stats`)."""
        return cb.serialize_parts(self._fmt, self.key_builder, None,
                                  self.shred_cols)

    def add_columnar_block(self, cb: ColumnarBlock, parts=None) -> None:
        """Bulk-load fast path: a sorted, keyed ColumnarBlock becomes a
        columnar-ONLY block — no row region is materialized; readers
        reconstruct KV entries on demand via their row_decoder.

        In stream mode (SstWriter(..., stream_columnar=True)) the block
        is serialized to the output file IMMEDIATELY — the write
        releases the GIL, so compaction overlaps output IO with the
        next block's column gathers (reference analog: CompactionJob
        interleaving merge work with file writes). Only valid for
        columnar-only SSTs; falls back to buffering when encryption at
        rest is on (that path needs the whole image in memory)."""
        if cb.n == 0:
            raise ValueError("columnar-only blocks need rows")
        # boundary keys come from the helpers, not cb.keys directly: a
        # keyless v2 block (deserialized from another SST) indexes by
        # its stored boundary keys without materializing the matrix
        first = cb.first_full_key()
        last = cb.last_full_key()
        if first is None or last is None:
            raise ValueError("columnar-only blocks need a keys matrix "
                             "or derived key bounds")
        if self._entries:
            self._blocks.append(self._entries)
            self._col_only.append(None)
            self._entries = []
        if self._last_key is not None and first < self._last_key:
            raise ValueError("keys must be added in sorted order")
        self._last_key = last
        if self._stream:
            if self._blocks:
                raise ValueError("stream mode cannot mix row blocks")
            if self._sf is None:
                self._sf = open(self.path + ".tmp", "wb",
                                buffering=1 << 20)
            e = BlockIndexEntry(
                first_key=first, last_key=last, offset=0, length=0,
                num_rows=cb.n, col_offset=self._sf.tell(), col_length=0)
            head, bufs = parts or cb.serialize_parts(
                self._fmt, self.key_builder, self.lane_stats,
                self.shred_cols)
            e.col_length = len(head)
            self._sf.write(head)
            for b in bufs:
                e.col_length += (len(b) if isinstance(b, bytes)
                                 else b.nbytes)
                self._sf.write(b if isinstance(b, bytes)
                               else memoryview(b).cast("B"))
            self._stream_index.append(e)
            self._key_hashes.append(cb.key_hash)
            self._num_entries += cb.n
            if self._sync_every is not None and \
                    self._sf.tell() - self._synced_to >= self._sync_every:
                self._sf.flush()
                os.fsync(self._sf.fileno())
                self._synced_to = self._sf.tell()
            return
        self._blocks.append([])
        self._col_only.append(cb)

    def set_frontier(self, **kv) -> None:
        """Consensus frontier metadata stored in the file (reference:
        UserFrontier in rocksdb files): op_id, max_ht, history_cutoff..."""
        self._frontier.update(kv)

    def _finish_tail(self, f, index: List[BlockIndexEntry],
                     row_hashes: List[bytes]) -> None:
        """Bloom + index + footer, shared by the buffered and streaming
        paths."""
        parts = list(self._key_hashes)
        if row_hashes:
            parts.append(fnv64_keys(row_hashes))
        hashes = (np.concatenate(parts) if parts
                  else np.zeros(0, np.uint64))
        bloom = BloomFilter.build(hashes)
        bloom_off = f.tell()
        braw = bloom.serialize()
        f.write(braw)
        idx_off = f.tell()
        iraw = msgpack.packb([
            [e.first_key, e.last_key, e.offset, e.length, e.num_rows,
             e.col_offset, e.col_length] for e in index])
        f.write(iraw)
        meta = {
            "num_entries": self._num_entries,
            "min_key": self._min_key, "max_key": self._max_key,
            "bloom_offset": bloom_off, "bloom_length": len(braw),
            "index_offset": idx_off, "index_length": len(iraw),
            "frontier": self._frontier,
        }
        if self._fmt != 1:
            # v1 files stay byte-identical to the pre-v2 writer: the
            # key only appears once the format actually moved
            meta["format_version"] = self._fmt
        fraw = msgpack.packb(meta)
        f.write(fraw)
        f.write(struct.pack("<I", len(fraw)))
        f.write(MAGIC)

    def abort(self) -> None:
        """Tear down a partially-written SST (pipelined compaction aborts
        mid-stream when an input turns out ineligible): close the
        streaming handle and unlink the .tmp — the final path was never
        created, so the store state is untouched."""
        if self._sf is not None:
            try:
                self._sf.close()
            except OSError:
                pass
            self._sf = None
        try:
            os.unlink(self.path + ".tmp")
        except OSError:
            pass
        self._entries = []
        self._blocks = []

    def finish(self) -> dict:
        if self._sf is not None:
            # streaming mode: sections are already on disk; append tail
            index = self._stream_index
            if index:
                self._min_key = index[0].first_key
                self._max_key = index[-1].last_key
            with self._sf as f:
                self._finish_tail(f, index, [])
                f.flush()
                os.fsync(f.fileno())
            self._sf = None
            os.replace(self.path + ".tmp", self.path)
            return {"path": self.path, "num_entries": self._num_entries,
                    "min_key": self._min_key, "max_key": self._max_key}
        if self._entries:
            self._blocks.append(self._entries)
            self._col_only.append(None)
            self._entries = []
        index: List[BlockIndexEntry] = []
        tmp = self.path + ".tmp"
        row_hashes: List[bytes] = []
        import io
        from ..utils import flags as _flags
        # Encryption needs the whole image in memory; otherwise STREAM
        # straight to the file — compaction outputs are hundreds of MB
        # and a BytesIO staging pass doubles the write cost.
        encrypting = _flags.get("encrypt_data_at_rest")
        with (io.BytesIO() if encrypting
              else open(tmp, "wb", buffering=1 << 20)) as f:
            # data blocks (empty region for columnar-only blocks)
            for bi, blk in enumerate(self._blocks):
                cb = self._col_only[bi]
                if cb is not None:
                    index.append(BlockIndexEntry(
                        first_key=cb.first_full_key(),
                        last_key=cb.last_full_key(),
                        offset=f.tell(), length=0, num_rows=cb.n))
                    self._num_entries += cb.n
                else:
                    enc = _encode_block(blk)
                    index.append(BlockIndexEntry(
                        first_key=blk[0][0], last_key=blk[-1][0],
                        offset=f.tell(), length=len(enc), num_rows=len(blk)))
                    f.write(enc)
                    self._num_entries += len(blk)
                    row_hashes.extend(_doc_key_of(k) for k, _ in blk)
            if index:
                self._min_key = index[0].first_key
                self._max_key = index[-1].last_key
            # columnar sections
            for i, blk in enumerate(self._blocks):
                cb = self._col_only[i]
                if cb is None and self.columnar_builder is not None and blk:
                    cb = self.columnar_builder(blk)
                if cb is not None:
                    head, bufs = cb.serialize_parts(
                        self._fmt, self.key_builder, self.lane_stats,
                        self.shred_cols)
                    index[i].col_offset = f.tell()
                    index[i].col_length = len(head)
                    f.write(head)
                    for b in bufs:
                        index[i].col_length += (
                            len(b) if isinstance(b, bytes) else b.nbytes)
                        f.write(b if isinstance(b, bytes)
                                else memoryview(b).cast("B"))
                    self._key_hashes.append(cb.key_hash)
            # Bloom over doc-key hashes: columnar blocks carry doc-key
            # hashes (HT stripped); plain row blocks fall back to full-key
            # hashes, which the point-read path mirrors.
            self._finish_tail(f, index, row_hashes)
            if encrypting:
                raw = f.getvalue()
            else:
                f.flush()
                os.fsync(f.fileno())
        if encrypting:
            from ..utils.encryption import KEY_MANAGER
            raw = KEY_MANAGER.encrypt_file_bytes(raw)
            with open(tmp, "wb") as out:
                out.write(raw)
                out.flush()
                os.fsync(out.fileno())
        os.replace(tmp, self.path)
        self._blocks = []
        return {"path": self.path, "num_entries": self._num_entries,
                "min_key": self._min_key, "max_key": self._max_key}


class SstReader:
    def __init__(self, path: str, row_decoder=None, key_builder=None):
        """row_decoder: callable(ColumnarBlock) -> List[(key, value)] —
        reconstructs KV entries for columnar-only blocks (provided by the
        docdb layer, which owns the packed-row schema).
        key_builder: callable(ColumnarBlock) -> keys matrix | None —
        lazily rebuilds the full key matrix of v2 keyless blocks from
        their pk + ht/write_id lanes (the same codec callable the writer
        verified the drop against)."""
        self.path = path
        self.row_decoder = row_decoder
        self.key_builder = key_builder
        # mmap instead of an eager read: compaction outputs are hundreds
        # of MB and pages fault in lazily as blocks are touched (the
        # reference's BlockBasedTable reads blocks on demand the same
        # way). Encrypted files still need the full image to decrypt.
        import mmap as _mmap
        from ..utils.encryption import (
            KEY_MANAGER, MAGIC as ENC_MAGIC, MAGIC_V2 as ENC_MAGIC_V2,
        )
        with open(path, "rb") as f:
            head = f.read(len(ENC_MAGIC))
            if head.startswith(ENC_MAGIC) or \
                    head.startswith(ENC_MAGIC_V2):
                f.seek(0)
                self._data = KEY_MANAGER.decrypt_file_bytes(f.read())
            else:
                self._data = _mmap.mmap(f.fileno(), 0,
                                        access=_mmap.ACCESS_READ)
        d = self._data
        if d[-8:] != MAGIC:
            raise ValueError(f"{path}: bad SST magic")
        (flen,) = struct.unpack_from("<I", d, len(d) - 12)
        meta = msgpack.unpackb(d[len(d) - 12 - flen:len(d) - 12])
        self.format_version = meta.get("format_version", 1)
        if self.format_version > SUPPORTED_FORMAT_VERSION:
            raise ValueError(
                f"{path}: SST format v{self.format_version} is newer "
                f"than this reader supports "
                f"(<= v{SUPPORTED_FORMAT_VERSION}); upgrade the reader "
                "before opening this file")
        self.num_entries = meta["num_entries"]
        self.min_key: bytes = meta["min_key"] or b""
        self.max_key: bytes = meta["max_key"] or b""
        self.frontier: dict = meta.get("frontier") or {}
        self.bloom = BloomFilter.deserialize(
            d[meta["bloom_offset"]:meta["bloom_offset"] + meta["bloom_length"]])
        raw_index = msgpack.unpackb(
            d[meta["index_offset"]:meta["index_offset"] + meta["index_length"]])
        self.index = [BlockIndexEntry(*row) for row in raw_index]
        self._first_keys = [e.first_key for e in self.index]
        self._col_cache: dict = {}
        self._key_cache: dict = {}   # block idx -> keys-only projection
        self._row_cache: dict = {}   # block idx -> decoded entries
        self._point_readers: dict = {}   # codec -> native PointReader|None

    @property
    def file_size(self) -> int:
        return len(self._data)

    # --- row access -------------------------------------------------------
    @staticmethod
    def _cache_put(cache: dict, i: int, value, cap: int):
        """Bounded block cache: point reads revisit hot blocks; full
        scans touch each block once, so eviction-by-clear is fine."""
        if len(cache) > cap:
            cache.clear()
        cache[i] = value
        return value

    def _read_block(self, i: int) -> List[Tuple[bytes, bytes]]:
        cached = self._row_cache.get(i)
        if cached is not None:
            return cached
        e = self.index[i]
        if e.length == 0:   # columnar-only block
            cb = self.columnar_block(i)
            if self.row_decoder is None:
                raise ValueError(
                    f"{self.path}: block {i} is columnar-only and no "
                    "row_decoder is set")
            out = self.row_decoder(cb)
        else:
            out = _decode_block(self._data[e.offset:e.offset + e.length])
        return self._cache_put(self._row_cache, i, out, 16)

    def seek(self, key: bytes) -> Iterator[Tuple[bytes, bytes]]:
        """Yield entries with entry_key >= key, ascending."""
        import bisect
        bi = bisect.bisect_right(self._first_keys, key) - 1
        bi = max(bi, 0)
        for i in range(bi, len(self.index)):
            for k, v in self._read_block(i):
                if k >= key:
                    yield k, v

    def iterate(self, lower: Optional[bytes] = None,
                upper: Optional[bytes] = None) -> Iterator[Tuple[bytes, bytes]]:
        it = self.seek(lower) if lower else self._iter_all()
        for k, v in it:
            if upper is not None and k >= upper:
                return
            yield k, v

    def _iter_all(self) -> Iterator[Tuple[bytes, bytes]]:
        for i in range(len(self.index)):
            yield from self._read_block(i)

    def may_contain_hash(self, key_hash: int) -> bool:
        return self.bloom.may_contain(key_hash)

    def point_reader(self, codec):
        """Native whole-SST batched point reader bound to `codec`
        (native/ybtpu_hot.c PointReader): bloom probe + block bisect +
        MVCC walk + row materialization for a LIST of doc-key prefixes
        in one C call. None when the extension or any prerequisite is
        unavailable — callers fall back to per-key point_find. Cached
        per codec OBJECT (an ALTER creates a new codec; SSTs are
        immutable so no other invalidation is needed)."""
        cache = self._point_readers
        pr = cache.get(codec, False)
        if pr is not False:
            return pr
        hot = _hot_mod()
        pr = None
        # eager build deserializes and PINS every columnar block for the
        # reader's lifetime — right for point-read-hot tablets, wrong
        # for huge scan-oriented SSTs, so cap by total rows (the per-key
        # fallback path pins only the blocks it visits)
        from ..utils import flags as _flags
        total_rows = sum(e.num_rows for e in self.index)
        if total_rows > _flags.get("native_point_reader_max_rows"):
            cache[codec] = None
            return None
        if hot is not None and hasattr(hot, "PointReader") and self.index:
            try:
                firsts, lasts, finders, extractors = [], [], [], []
                for i, e in enumerate(self.index):
                    cb = self.columnar_block(i)
                    fnd = ext = None
                    if cb is not None and cb.keys is not None:
                        fnd = _native_finder(cb)
                        ext = codec._native_extractor(cb)
                    firsts.append(e.first_key)
                    lasts.append(e.last_key)
                    finders.append(fnd)
                    extractors.append(ext)
                bits = np.ascontiguousarray(self.bloom.bits) \
                    if self.bloom is not None else None
                pr = hot.PointReader(
                    tuple(firsts), tuple(lasts), tuple(finders),
                    tuple(extractors), bits,
                    self.bloom.k if self.bloom is not None else 0)
            except Exception:
                pr = None
        cache[codec] = pr
        return pr

    def _key_block(self, i: int) -> Optional[ColumnarBlock]:
        """Block `i` for a probe that reads keys and MVCC lanes only: the
        decoded block if it is at hand, else its projection to no value
        column, kept in a small cache of its own."""
        cb = self._col_cache.get(i) or self._key_cache.get(i)
        if cb is None:
            cb = self.projected_block(i, ())
            if cb is not None:
                self._cache_put(self._key_cache, i, cb, 64)
        return cb

    def point_find(self, prefix: bytes, read_ht: int,
                   restart_hi: Optional[int] = None,
                   keys_only: bool = False):
        """Newest VISIBLE version of the doc key `prefix` in this SST —
        the fused point-read hot path (reference analog:
        BlockBasedTable::Get + DocDB visibility). Returns one of:
          ("row", ht, write_id, key, value, block, pos)  — found;
            columnar hits carry value=None and (block, pos) for lazy
            single-row decode, row-path hits carry the raw value
          ("restart", ht)  — a version inside the clock-uncertainty
            window (read_ht, restart_hi] exists: caller restarts
          None — no visible version here
        Reads MVCC metadata straight from the columnar ht/write_id
        arrays instead of decoding the key's DocHybridTime suffix.
        `keys_only`: the caller reads no value column of a columnar hit
        (its `tombstone[pos]` at most), so a block is decoded without
        them (`_key_block`)."""
        import bisect
        bi = max(bisect.bisect_right(self._first_keys, prefix) - 1, 0)
        plen = len(prefix)
        for i in range(bi, len(self.index)):
            e = self.index[i]
            if e.first_key > prefix and not e.first_key.startswith(prefix):
                return None
            if e.last_key < prefix:
                continue
            cb = None
            if self.row_decoder is not None:
                cb = (self._key_block(i) if keys_only
                      else self.columnar_block(i))
            if keys_only and cb is not None and cb._keys is None and not (
                    cb.key_hash == np.uint64(fnv64_bytes(prefix))).any():
                # no row here has the key's hash (a bloom filter's false
                # positive, mostly): told without the block's key matrix
                return None
            if cb is not None and cb.keys is None:
                cb = None
            if cb is not None:
                fnd = _native_finder(cb)
                if fnd is not None:
                    r = fnd.find(prefix, read_ht,
                                 -1 if restart_hi is None else restart_hi)
                    if isinstance(r, tuple):
                        pos, ht, wid, _tomb = r
                        return ("row", ht, wid,
                                cb.keys[pos].tobytes(), None, cb, pos)
                    if r is not None:
                        return ("restart", r)
                    # nothing visible HERE; this doc key's versions
                    # continue into the next block only when they run
                    # through the block's last key
                    if e.last_key[:plen] == prefix:
                        continue
                    return None
                pos = cb.searchsorted_key(prefix)
                keys, hts, n = cb.keys, cb.ht, cb.n
                advanced = False
                while pos < n:
                    k = keys[pos].tobytes()
                    if k[:plen] != prefix:
                        break
                    advanced = True
                    ht = int(hts[pos])
                    if ht > read_ht:
                        if restart_hi is not None and ht <= restart_hi:
                            return ("restart", ht)
                        pos += 1
                        continue
                    return ("row", ht, int(cb.write_id[pos]), k, None,
                            cb, pos)
                if pos < n:
                    return None     # walked past the prefix in-block
                if not advanced and pos == 0:
                    return None
            else:
                from ..utils.hybrid_time import DocHybridTime, ENCODED_SIZE
                for k, v in self._read_block(i):
                    if k >= prefix:
                        if k[:plen] != prefix:
                            return None
                        dht = DocHybridTime.decode_desc(k[-ENCODED_SIZE:])
                        ht = dht.ht.value
                        if ht > read_ht:
                            if restart_hi is not None and ht <= restart_hi:
                                return ("restart", ht)
                            continue
                        return ("row", ht, dht.write_id, k, v, None, None)
        return None

    # --- columnar access --------------------------------------------------
    def columnar_block(self, i: int) -> Optional[ColumnarBlock]:
        e = self.index[i]
        if e.col_offset < 0:
            return None
        cached = self._col_cache.get(i)
        if cached is not None:
            return cached
        cb = ColumnarBlock.deserialize(
            self._data[e.col_offset:e.col_offset + e.col_length])
        cb.bind_key_builder(self.key_builder)
        return self._cache_put(self._col_cache, i, cb, 32)

    def projected_block(self, i: int, columns) -> Optional[ColumnarBlock]:
        """Block `i` with the MVCC, pk and key lanes and only the value
        columns in `columns` (`ColumnarBlock.deserialize`): what a key
        probe or a scan of a known column set reads, at the cost of those
        lanes alone — the other columns' pages are not touched.  Owned
        arrays, never cached here: the caller keeps what it needs."""
        e = self.index[i]
        if e.col_offset < 0:
            return None
        cb = ColumnarBlock.deserialize(
            memoryview(self._data)[e.col_offset:e.col_offset
                                   + e.col_length],
            columns=frozenset(columns))
        cb.bind_key_builder(self.key_builder)
        return cb

    def read_columnar(self, i: int) -> Optional[ColumnarBlock]:
        """Streaming (uncached) columnar-block read for the compaction
        pipeline: the decode-ahead stage touches every block exactly
        once and holds its own reference until the block is fully
        merged, so routing the read through the point-read cache would
        evict the hot working set AND pin decoded blocks past their
        lifetime. Arrays are zero-copy read-only views over the file
        mapping — pages fault in when the merge actually touches them,
        and numpy's base-reference keeps the mapping alive even after
        the input SST is unlinked post-compaction."""
        e = self.index[i]
        if e.col_offset < 0:
            return None
        cb = ColumnarBlock.deserialize(
            memoryview(self._data)[e.col_offset:e.col_offset
                                   + e.col_length], copy=False)
        cb.bind_key_builder(self.key_builder)
        return cb

    def columnar_blocks(self, lower: Optional[bytes] = None,
                        upper: Optional[bytes] = None
                        ) -> Iterator[Tuple[int, Optional[ColumnarBlock]]]:
        """(block index, ColumnarBlock|None) for blocks intersecting
        [lower, upper). None means the caller must fall back to row decode
        for that block."""
        for i, e in enumerate(self.index):
            if upper is not None and e.first_key >= upper:
                break
            if lower is not None and e.last_key < lower:
                continue
            yield i, self.columnar_block(i)

    def num_blocks(self) -> int:
        return len(self.index)
