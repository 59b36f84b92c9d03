"""Per-table codec: rows <-> doc KV entries <-> columnar blocks.

This is the layer the reference spreads across dockv's PgTableRow
materialization (src/yb/dockv/pg_row.cc), DocRowwiseIterator decode
(src/yb/docdb/doc_rowwise_iterator.cc) and packed-row build
(src/yb/dockv/packed_row.h) — concentrated here because our SSTs are
columnar-first: the codec owns (a) scalar row encode/decode, (b) the
ColumnarBlock builder plugged into SST flush, (c) the row_decoder that
reconstructs KV entries from columnar-only blocks, (d) the vectorized
bulk-load that turns user column arrays straight into sorted
columnar-only SSTs without a per-row Python loop.
"""
from __future__ import annotations

import functools
import weakref
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from ..dockv import bulk
from ..dockv.key_encoding import (
    DocKey, KeyEntryValue, SubDocKey, ValueType, decode_key_entry,
)
from ..dockv.packed_row import (
    ColumnSchema, ColumnType, RowPacker, SchemaPacking, SchemaPackingStorage,
    TableSchema, unpack_row,
)
from ..dockv.partition import PartitionSchema
from ..dockv.value import PrimitiveValue, ValueKind
from ..storage.columnar import ColumnarBlock, fnv64_bytes, fnv64_keys
from ..utils.hybrid_time import ENCODED_SIZE, DocHybridTime, HybridTime
from .hotpath import load as _hot

_HT_SUFFIX = ENCODED_SIZE + 1


@dataclass
class TableInfo:
    """Table metadata as known by tablets (reference: the schema parts of
    master/catalog_entity_info.proto + tablet metadata)."""

    table_id: str
    name: str
    schema: TableSchema
    partition_schema: PartitionSchema
    packings: SchemaPackingStorage = field(default_factory=SchemaPackingStorage)
    cotable_id: Optional[int] = None    # set for colocated tables
    # prior schema versions (ALTER history) — required so rows packed
    # under old versions keep decoding after restarts/clones/bootstraps
    schema_history: Tuple[TableSchema, ...] = ()

    def __post_init__(self):
        for old in self.schema_history:
            if old.version not in getattr(self.packings, "_packings", {}):
                self.packings.add_schema(old)
        if self.schema.version not in getattr(self.packings, "_packings", {}):
            self.packings.add_schema(self.schema)

    @property
    def packing(self) -> SchemaPacking:
        return self.packings.get(self.schema.version)

    @staticmethod
    def _schema_wire(schema: TableSchema) -> dict:
        return {
            "version": schema.version,
            "columns": [[c.id, c.name, c.type, c.nullable, c.is_hash_key,
                         c.is_range_key, c.sort_desc, c.ql_type,
                         c.default_seq, c.default_value]
                        for c in schema.columns],
        }

    @staticmethod
    def _schema_from_wire(d: dict) -> TableSchema:
        return TableSchema(
            columns=tuple(ColumnSchema(*row) for row in d["columns"]),
            version=d["version"])

    def to_wire(self) -> dict:
        return {
            "table_id": self.table_id, "name": self.name,
            "schema": self._schema_wire(self.schema),
            "schema_history": [self._schema_wire(h)
                               for h in self.schema_history],
            "partition": {"kind": self.partition_schema.kind,
                          "num_hash_columns":
                              self.partition_schema.num_hash_columns},
            "cotable_id": self.cotable_id,
        }

    @classmethod
    def from_wire(cls, d: dict) -> "TableInfo":
        schema = cls._schema_from_wire(d["schema"])
        history = tuple(cls._schema_from_wire(h)
                        for h in d.get("schema_history", []))
        return cls(d["table_id"], d["name"], schema,
                   PartitionSchema(d["partition"]["kind"],
                                   d["partition"]["num_hash_columns"]),
                   cotable_id=d.get("cotable_id"),
                   schema_history=history)


_KEV_MAKER = {
    ColumnType.INT32: KeyEntryValue.int32,
    ColumnType.INT64: KeyEntryValue.int64,
    ColumnType.FLOAT64: KeyEntryValue.double,
    ColumnType.STRING: KeyEntryValue.string,
    ColumnType.TIMESTAMP: KeyEntryValue.timestamp,
    ColumnType.BINARY: KeyEntryValue.raw_bytes,
}

_BULK_ENC = {
    ColumnType.INT32: bulk.encode_int32_column,
    ColumnType.INT64: bulk.encode_int64_column,
    ColumnType.FLOAT64: bulk.encode_double_column,
    ColumnType.TIMESTAMP: lambda v, desc=False: bulk._retype(
        bulk.encode_int64_column(v, desc),
        ValueType.kTimestampDesc if desc else ValueType.kTimestamp),
}


class TableCodec:
    def __init__(self, info: TableInfo):
        self.info = info
        self.schema = info.schema
        self.packer = RowPacker(info.packing)
        self._pk_cols = self.schema.key_columns
        # point-read decode plan, computed once per codec: property
        # recomputation and per-column type tests are measurable at
        # 30K+ point reads/s
        self._pk_ids = tuple(c.id for c in self._pk_cols)
        self._val_plan = tuple(
            (c.name, c.id,
             c.type == ColumnType.BOOL,
             c.type in (ColumnType.STRING, ColumnType.JSON,
                        ColumnType.DECIMAL))
            for c in self.schema.value_columns)
        # JSON value columns: candidates for document shredding
        # (docstore/) — threaded as `shred_cols` through LsmStore /
        # SstWriter, where the doc_shred_enabled gate resolves per file
        self.shred_cols = tuple(
            c.id for c in self.schema.value_columns
            if c.type == ColumnType.JSON)
        # native DocKey-prefix encoder spec (None = unsupported pk
        # shape, Python path used)
        self._key_spec = None
        kind_map = {ColumnType.INT64: 0, ColumnType.INT32: 1,
                    ColumnType.FLOAT64: 2, ColumnType.STRING: 3,
                    ColumnType.TIMESTAMP: 4, ColumnType.BINARY: 5}
        if all(c.type in kind_map for c in self._pk_cols):
            ps = info.partition_schema
            self._key_spec = (
                -1 if info.cotable_id is None else info.cotable_id,
                ps.num_hash_columns if ps.kind == "hash" else 0,
                bytes(kind_map[c.type] for c in self._pk_cols),
                bytes(1 if c.sort_desc else 0 for c in self._pk_cols))

    # --- scalar paths -----------------------------------------------------
    def pk_entries(self, row: Dict[str, object]) -> List[KeyEntryValue]:
        out = []
        nh = self.info.partition_schema.num_hash_columns
        for i, c in enumerate(self._pk_cols):
            v = row[c.name]
            if v is None and i >= nh:
                # NULL range components encode as kNull (PG indexes
                # rows with NULL key parts; hash components still
                # require a value — they route the tablet)
                e = KeyEntryValue.null(desc=c.sort_desc)
                out.append(e)
                continue
            maker = _KEV_MAKER[c.type]
            e = maker(v)
            if c.sort_desc:
                e = KeyEntryValue(e.kind, e.value, desc=True)
            out.append(e)
        return out

    def doc_key(self, row: Dict[str, object]) -> DocKey:
        dk = self.info.partition_schema.doc_key_for_row(self.pk_entries(row))
        if self.info.cotable_id is not None:
            dk = DocKey(dk.hash, dk.hashed, dk.range, self.info.cotable_id)
        return dk

    def encode_write(self, row: Dict[str, object], dht: DocHybridTime
                     ) -> Tuple[bytes, bytes]:
        """Full-row upsert as one packed KV (packed-row V2 path)."""
        key = SubDocKey(self.doc_key(row), (), dht).encode()
        values = {c.id: row.get(c.name) for c in self.schema.value_columns}
        return key, self.packer.pack_value(values)

    def encode_delete(self, pk_row: Dict[str, object], dht: DocHybridTime
                      ) -> Tuple[bytes, bytes]:
        key = SubDocKey(self.doc_key(pk_row), (), dht).encode()
        return key, PrimitiveValue.tombstone().encode()

    def doc_key_prefix(self, pk_row: Dict[str, object]) -> bytes:
        if self._key_spec is not None:
            hot = _hot()
            if hot is not None:
                try:
                    return hot.encode_doc_key(
                        self._key_spec,
                        tuple(pk_row[c.name] for c in self._pk_cols))
                except Exception:
                    pass   # odd value types: Python path decides
        return self.doc_key(pk_row).encode()

    def scan_prefix(self) -> bytes:
        """Key-space prefix owned by this table within its tablet —
        empty for dedicated tablets, the cotable prefix for colocated
        tables (bounds every scan)."""
        if self.info.cotable_id is None:
            return b""
        return bytes([ValueType.kCoTableId]) + \
            self.info.cotable_id.to_bytes(4, "big")

    def hash_prefix(self, row: Dict[str, object]) -> bytes:
        """Encoded prefix covering the hash components plus any
        CONTIGUOUS leading range components present in `row` — used for
        prefix scans (secondary-index lookups by indexed value; a
        composite index narrows by every provided column, not just the
        hashed first one)."""
        from ..dockv.key_encoding import KeyBytes
        ps = self.info.partition_schema
        entries = []
        for c in self._pk_cols[:ps.num_hash_columns]:
            maker = _KEV_MAKER[c.type]
            entries.append(maker(row[c.name]))
        from ..dockv.partition import hash_key_for
        kb = KeyBytes(self.scan_prefix())
        kb.append_hash(hash_key_for(entries))
        for e in entries:
            kb.append_entry(e)
        range_cols = [c for c in self._pk_cols[ps.num_hash_columns:]]
        provided = []
        for c in range_cols:
            if c.name not in row or row[c.name] is None:
                break       # prefix must stay contiguous in pk order
            provided.append(c)
        if provided:
            # the hash group closes with kGroupEnd before range
            # components (DocKey layout) — without it the prefix can
            # never match a stored key
            kb.append_group_end()
            for c in provided:
                kb.append_entry(_KEV_MAKER[c.type](row[c.name]))
        return kb.data()

    def decode_row(self, key: bytes, value: bytes) -> Optional[Dict[str, object]]:
        """KV entry -> {col name: value} (None for a tombstone)."""
        if value[0] == ValueKind.kTombstone:
            return None
        sdk = SubDocKey.decode(key)
        out: Dict[str, object] = {}
        entries = list(sdk.doc_key.hashed) + list(sdk.doc_key.range)
        for c, e in zip(self._pk_cols, entries):
            out[c.name] = e.value
        if value[0] != ValueKind.kPackedRowV2:
            raise ValueError("row values must be packed (V2) or tombstones")
        ver = self.info.packings.version_of(value, 1)
        packing = self.info.packings.get(ver)
        unpacked = unpack_row(packing, value, 1)
        for c in self.schema.value_columns:
            if c.id in unpacked:
                out[c.name] = unpacked[c.id]
            else:
                out[c.name] = None   # column added after this row's version
        return out

    _DTYPE_CHAR = {("i", 8): "q", ("i", 4): "i", ("i", 2): "h",
                   ("i", 1): "b", ("u", 8): "Q", ("u", 4): "I",
                   ("f", 8): "d", ("f", 4): "f", ("b", 1): "?"}

    def _native_extractor(self, cb: ColumnarBlock):
        """Build (and cache on the block) a native row extractor for
        this codec — the C implementation of decode_block_row's loop
        (native/ybtpu_hot.c; reference: dockv/pg_row.cc runs this in
        C++ too)."""
        cache = getattr(cb, "_extractors", None)
        if cache is None:
            cache = {}
            object.__setattr__(cb, "_extractors", cache)
        # keyed by the codec OBJECT (not id()): an ALTER creates a new
        # codec, and a recycled address must not resurrect an extractor
        # built for the old schema
        ext = cache.get(self, False)
        if ext is not False:
            return ext
        from .hotpath import load as _load_hot
        hot = _load_hot()
        ext = None
        if hot is not None and all(cid in cb.pk for cid in self._pk_ids):
            try:
                plan = []
                for c in self._pk_cols:
                    arr = np.ascontiguousarray(cb.pk[c.id])
                    ch = self._DTYPE_CHAR[(arr.dtype.kind,
                                           arr.dtype.itemsize)]
                    plan.append((c.name, 3, ch, arr, None, None))
                for name, cid, is_bool, is_str in self._val_plan:
                    f = cb.fixed.get(cid)
                    if f is not None:
                        vals = np.ascontiguousarray(f[0])
                        nulls = np.ascontiguousarray(f[1])
                        ch = self._DTYPE_CHAR[(vals.dtype.kind,
                                               vals.dtype.itemsize)]
                        plan.append((name, 0, ch, vals, nulls, None))
                        continue
                    vl = cb.varlen.get(cid)
                    if vl is not None:
                        ends = np.ascontiguousarray(
                            vl[0].astype(np.uint32, copy=False))
                        nulls = np.ascontiguousarray(vl[2])
                        plan.append((name, 1 if is_str else 2, "q",
                                     ends, nulls, vl[1]))
                    else:
                        plan.append((name, 4, "q", None, None, None))
                ext = hot.Extractor(plan, cb.n)
            except Exception:
                ext = None
        cache[self] = ext
        return ext

    def decode_block_row(self, cb: ColumnarBlock, pos: int,
                         key: bytes) -> Optional[Dict[str, object]]:
        """Single-row decode straight from a columnar block's arrays —
        produces exactly what decode_row() yields for the same row, but
        without the pack→unpack roundtrip (the point-read hot path;
        reference analog: PgTableRow materialization from a packed row,
        dockv/pg_row.cc)."""
        if cb.tombstone[pos]:
            return None
        ext = self._native_extractor(cb)
        if ext is not None:
            return ext.extract(pos)
        out: Dict[str, object] = {}
        pk = cb.pk
        if all(cid in pk for cid in self._pk_ids):
            for c in self._pk_cols:
                out[c.name] = pk[c.id][pos].item()
        else:
            sdk = SubDocKey.decode(key)
            entries = list(sdk.doc_key.hashed) + list(sdk.doc_key.range)
            for c, e in zip(self._pk_cols, entries):
                out[c.name] = e.value
        fixed, varlen = cb.fixed, cb.varlen
        for name, cid, is_bool, is_str in self._val_plan:
            f = fixed.get(cid)
            if f is not None:
                vals, nulls = f
                if nulls[pos]:
                    out[name] = None
                else:
                    v = vals[pos].item()
                    out[name] = bool(v) if is_bool else v
                continue
            vl = varlen.get(cid)
            if vl is not None:
                ends, heap, nulls = vl
                if nulls[pos]:
                    out[name] = None
                else:
                    lo = int(ends[pos - 1]) if pos else 0
                    raw = bytes(heap[lo:int(ends[pos])])
                    out[name] = raw.decode() if is_str else raw
            else:
                out[name] = None   # column added after this version
        return out

    # --- v2 keyless blocks: key matrix derivation -------------------------
    def derive_keys(self, cb: ColumnarBlock) -> Optional[np.ndarray]:
        """Rebuild a block's full encoded SubDocKey matrix from its pk
        columns + ht/write_id lanes — THE v2 keyless-block contract.

        Writers call this to VERIFY a block's keys matrix is byte-
        derivable before dropping it from the serialized form; readers
        call the same function (bound as the SST key_builder) to rebuild
        lazily, so write-time verification proves read-time exactness.

        The whole rebuild is the vectorized bulk-load encode pipeline
        (dockv/bulk.py): per-component column encode, fused 16-bit
        partition hash, one concatenate, one vectorized HT-suffix
        append — no per-row Python. None when the pk shape is
        underivable (varlen/unsupported component types, missing pk
        arrays, cotable prefixes) — such blocks keep inline keys."""
        if self.info.cotable_id is not None:
            return None
        ps = self.info.partition_schema
        pk_blocks = []
        for c in self._pk_cols:
            enc = _BULK_ENC.get(c.type)
            arr = cb.pk.get(c.id)
            if enc is None or arr is None or len(arr) != cb.n:
                return None
            try:
                pk_blocks.append(enc(np.asarray(arr), c.sort_desc))
            except (TypeError, ValueError):
                return None
        if not pk_blocks:
            return None
        n = cb.n
        hashes = None
        nh = 0
        if ps.kind == "hash":
            nh = ps.num_hash_columns
            hash_input = (pk_blocks[0] if nh == 1
                          else np.concatenate(pk_blocks[:nh], axis=1))
            hashes = bulk.fast_hash16_from_encoded(hash_input)
        # one preallocated fill instead of encode_doc_keys +
        # append_hybrid_times (each a full-matrix concat copy — this
        # runs per block on the compaction decode path, so the extra
        # 27 B/row copy was measurable); byte layout identical to the
        # bulk pipeline, asserted by the v1-vs-v2 entry-equality tests
        from ..dockv.key_encoding import ValueType as _VT
        width = (sum(b.shape[1] for b in pk_blocks) + 1
                 + (4 if hashes is not None else 0) + 13)
        out = np.empty((n, width), np.uint8)
        pos = 0
        if hashes is not None:
            out[:, 0] = _VT.kUInt16Hash
            out[:, 1:3] = hashes.astype(">u2").view(np.uint8).reshape(-1, 2)
            pos = 3
            for b in pk_blocks[:nh]:
                out[:, pos:pos + b.shape[1]] = b
                pos += b.shape[1]
            out[:, pos] = _VT.kGroupEnd
            pos += 1
        for b in pk_blocks[nh:]:
            out[:, pos:pos + b.shape[1]] = b
            pos += b.shape[1]
        out[:, pos] = _VT.kGroupEnd
        out[:, pos + 1] = _VT.kHybridTime
        out[:, pos + 2:pos + 10] = (~np.asarray(cb.ht, np.uint64)).astype(
            ">u8").view(np.uint8).reshape(-1, 8)
        out[:, pos + 10:pos + 14] = (~np.asarray(
            cb.write_id, np.uint32)).astype(">u4").view(
                np.uint8).reshape(-1, 4)
        return out

    # --- columnar builder / row decoder (plugged into LsmStore) -----------
    def columnar_builder(self, entries: Sequence[Tuple[bytes, bytes]]
                         ) -> Optional[ColumnarBlock]:
        """Build a columnar sidecar from one SST block's KV entries; None
        when the block isn't packable (mixed schema versions)."""
        try:
            n = len(entries)
            keys_noht, hts, wids = [], np.empty(n, np.uint64), np.empty(n, np.uint32)
            values = []
            ver: Optional[int] = None
            for i, (k, v) in enumerate(entries):
                if k[-_HT_SUFFIX] != ValueType.kHybridTime:
                    return None
                dht = DocHybridTime.decode_desc(k[-ENCODED_SIZE:])
                hts[i] = dht.ht.value
                wids[i] = dht.write_id
                keys_noht.append(k[:-_HT_SUFFIX])
                if v[0] == ValueKind.kMergeFlags:
                    # TTL'd rows stay on the row path (CPU TTL checks);
                    # the block simply doesn't get a columnar sidecar
                    return None
                if v[0] == ValueKind.kPackedRowV2:
                    v_ver = self.info.packings.version_of(v, 1)
                    if ver is None:
                        ver = v_ver
                    elif ver != v_ver:
                        return None
                elif v[0] != ValueKind.kTombstone:
                    return None
                values.append(v)
            if ver is None:
                ver = self.schema.version
            packing = self.info.packings.get(ver)
            blk = ColumnarBlock.from_packed_entries(
                packing, keys_noht, hts, wids, values)
            # decode fixed-width PK components for device-side key predicates
            self._attach_pk_columns(blk, keys_noht)
            # a block may contain several versions of a key
            blk.unique_keys = len(set(keys_noht)) == n
            # keep full keys for columnar-only reconstruction & merges
            lens = {len(k) for k in keys_noht}
            if len(lens) == 1:
                w = lens.pop() + _HT_SUFFIX
                km = np.frombuffer(
                    b"".join(entries[i][0] for i in range(n)),
                    np.uint8).reshape(n, w)
                blk.keys = km.copy()
            return blk
        except Exception:
            return None

    def _attach_pk_columns(self, blk: ColumnarBlock,
                           keys_noht: Sequence[bytes]) -> None:
        cols: Dict[int, list] = {c.id: [] for c in self._pk_cols
                                 if ColumnType.is_fixed(c.type)
                                 or c.type in (ColumnType.INT32,
                                               ColumnType.INT64,
                                               ColumnType.FLOAT64)}
        if not cols:
            return
        try:
            for k in keys_noht:
                dk, _ = DocKey.decode(k)
                entries = list(dk.hashed) + list(dk.range)
                for c, e in zip(self._pk_cols, entries):
                    if c.id in cols:
                        cols[c.id].append(e.value)
            for c in self._pk_cols:
                if c.id in cols:
                    dt = ColumnType.NUMPY_DTYPES.get(c.type, np.float64)
                    blk.pk[c.id] = np.asarray(cols[c.id], dt)
        except Exception:
            pass

    def row_decoder(self, blk: ColumnarBlock) -> List[Tuple[bytes, bytes]]:
        """Reconstruct KV entries from a columnar-only block (slow path,
        used by CPU merges/point-reads over bulk-loaded SSTs)."""
        if blk.keys is None:   # property: rebuilds v2 keyless blocks
            raise ValueError(
                "columnar-only block has no keys matrix and no bound "
                "key_builder — a v2 keyless block must be read through "
                "its table codec")
        packing = self.info.packings.get(blk.schema_version)
        packer = RowPacker(packing)
        # derived lanes (shredded doc paths, join build columns) are
        # scan-lifetime acceleration structures, not row data —
        # reconstruction reads schema columns only
        from ..storage.columnar import DERIVED_COL_BASE as _DERIVED_BASE
        out = []
        for i in range(blk.n):
            key = blk.keys[i].tobytes()
            if blk.tombstone[i]:
                out.append((key, PrimitiveValue.tombstone().encode()))
                continue
            values: Dict[int, object] = {}
            for cid, (vals, nulls) in blk.fixed.items():
                if cid >= _DERIVED_BASE:
                    continue
                values[cid] = None if nulls[i] else vals[i].item()
            for cid, (ends, heap, nulls) in blk.varlen.items():
                if cid >= _DERIVED_BASE:
                    continue
                if nulls[i]:
                    values[cid] = None
                else:
                    lo = int(ends[i - 1]) if i else 0
                    raw = heap[lo:int(ends[i])]
                    c = self.schema.column_by_id(cid)
                    values[cid] = (raw.decode()
                                   if c.type in (ColumnType.STRING,
                                                 ColumnType.JSON,
                                                 ColumnType.DECIMAL)
                                   else raw)
            out.append((key, packer.pack_value(values)))
        return out

    # --- vectorized bulk load ---------------------------------------------
    def bulk_blocks(self, columns: Dict[str, np.ndarray],
                    ht: HybridTime, block_rows: int = 65536,
                    partition=None) -> List[ColumnarBlock]:
        """Materialized form of :meth:`bulk_blocks_iter` (tests and small
        loads; the tablet ingest path streams the iterator instead)."""
        return list(self.bulk_blocks_iter(columns, ht,
                                          block_rows=block_rows,
                                          partition=partition))

    def _hashes_memo(self, columns: Dict[str, np.ndarray], hashes=None):
        """The partition hashes of the last arrays loaded, for a table
        loaded tablet by tablet: every tablet is handed every row
        (`Tablet.bulk_load`), and a row's hash is the same for each.
        Held by the hash columns' arrays themselves (weakly: the memo
        goes with them) and by ~1,000 of their values, evenly spaced: a
        buffer filled anew between two calls is told apart, a few values
        changed in place are not.  With `hashes`, stores them."""
        global _HASHES_MEMO
        cols = self._pk_cols[:self.info.partition_schema.num_hash_columns]
        arrs = [columns[c.name] for c in cols]
        if not all(isinstance(a, np.ndarray) and a.ndim == 1 for a in arrs):
            return None
        shape = tuple((c.type, c.sort_desc, a.dtype.str, a.shape,
                       a[::max(1, len(a) // 1024)].tobytes())
                      for c, a in zip(cols, arrs))
        if hashes is not None:
            _HASHES_MEMO = ([weakref.ref(a, _drop_hashes_memo)
                             for a in arrs], shape, hashes)
            return hashes
        refs, was, got = _HASHES_MEMO
        if was == shape and len(refs) == len(arrs) and all(
                r() is a for r, a in zip(refs, arrs)):
            return got
        return None

    def bulk_blocks_iter(self, columns: Dict[str, np.ndarray],
                         ht: HybridTime, block_rows: int = 65536,
                         partition=None):
        """The blocks of :meth:`bulk_block_makers`, made one at a time
        and in order."""
        for make in self.bulk_block_makers(columns, ht, block_rows,
                                           partition):
            yield make()

    def bulk_block_makers(self, columns: Dict[str, np.ndarray],
                          ht: HybridTime, block_rows: int = 65536,
                          partition=None) -> list:
        """Turn user column arrays into sorted columnar-only blocks: one
        callable a block, in block order, each independent of the others
        (the ingest pipeline makes and serializes several at once on its
        threads, and writes them in order).

        Requirements (bulk fast path): every PK component fixed-width
        numeric. Varlen value columns are allowed.
        partition: optional Partition — rows outside it are dropped
        (used when loading a table across several tablets).

        The global phase (key encode, partition hash, sort order, row
        hashes) is vectorized numpy/native; per block, ONE fused
        GIL-released native call (storage/native_lib.gather_multi)
        gathers the key matrix, key-hash lane, and every fixed-width
        column through the sort permutation — no per-column python
        gather loop remains on the hot path.
        """
        n = len(next(iter(columns.values())))
        ps = self.info.partition_schema

        def encoded(c, rows=None):
            arr = np.asarray(columns[c.name])
            return _BULK_ENC[c.type](arr if rows is None else arr[rows],
                                     c.sort_desc)

        # a hash-partitioned table knows a row's tablet from its hash
        # columns alone: those are encoded for every row, the rest of the
        # key only for the rows this partition keeps (loading a table
        # tablet by tablet hands every tablet every row)
        nh = ps.num_hash_columns if ps.kind == "hash" else 0
        late = nh > 0 and partition is not None
        hashes = self._hashes_memo(columns) if late else None
        pk_blocks = [] if hashes is not None else [
            encoded(c) for c in
            (self._pk_cols[:nh] if late else self._pk_cols)]
        if ps.kind == "hash":
            if hashes is None:
                hash_input = (pk_blocks[0] if nh == 1
                              else np.concatenate(pk_blocks[:nh], axis=1))
                # `bulk.fast_hash16_from_encoded` in one native pass:
                # FNV-1a 64 of each row (same basis and prime), folded
                # to 16 bits
                h64 = _fnv_rows(hash_input)
                hashes = ((h64 ^ (h64 >> np.uint64(32)))
                          & np.uint64(0xFFFF)).astype(np.uint32)
                if late:
                    self._hashes_memo(columns, hashes)
            doc_keys = (None if late else
                        bulk.encode_doc_keys(hashes, pk_blocks, nh))
            part_keys = None
        else:
            doc_keys = bulk.encode_doc_keys(None, pk_blocks, 0)
            part_keys = doc_keys
        keep = np.ones(n, bool)
        if partition is not None and part_keys is None:
            # a hash partition's bounds are 16-bit hash values, big-endian
            # and zero-padded: compared as numbers, not byte by byte
            if partition.start:
                keep &= hashes >= int.from_bytes(
                    partition.start.ljust(2, b"\x00")[:2], "big")
            if partition.end:
                keep &= hashes < int.from_bytes(
                    partition.end.ljust(2, b"\x00")[:2], "big")
        elif partition is not None:
            if partition.start:
                lo = np.frombuffer(partition.start.ljust(part_keys.shape[1],
                                                         b"\x00"), np.uint8)
                keep &= _rows_ge(part_keys, lo)
            if partition.end:
                hi = np.frombuffer(partition.end.ljust(part_keys.shape[1],
                                                       b"\x00"), np.uint8)
                keep &= ~_rows_ge(part_keys, hi)
        identity = bool(keep.all())
        if identity:
            # single-tablet load: skip the identity gather (copies the
            # whole key matrix for nothing at 6M-row bench scale)
            idx = np.arange(n, dtype=np.int64)
        else:
            idx = np.nonzero(keep)[0]
            if doc_keys is not None:
                doc_keys = doc_keys[idx]
            if ps.kind == "hash":
                hashes = hashes[idx]
        if not len(idx):
            return []
        if doc_keys is None:
            # the whole key of the rows this partition keeps (their hash
            # columns encoded again: a row in eight of a table loaded
            # tablet by tablet)
            rows = None if identity else idx
            doc_keys = bulk.encode_doc_keys(
                hashes, [encoded(c, rows) for c in self._pk_cols], nh)
        full = bulk.append_hybrid_times(
            doc_keys,
            np.full(len(idx), ht.value, np.uint64),
            np.arange(len(idx), dtype=np.uint32))
        # sort rows by encoded doc key — numeric single-pass sort when
        # the PK packs into one word (bulk.bulk_sort_order), byte-matrix
        # comparison sort otherwise
        comps = [(np.asarray(columns[c.name])[idx]
                  if not identity else np.asarray(columns[c.name]),
                  c.type, c.sort_desc) for c in self._pk_cols]
        order = np.ascontiguousarray(
            bulk.bulk_sort_order(hashes if ps.kind == "hash" else None,
                                 comps, doc_keys), np.int64)
        # row hashes over the UNSORTED doc keys (one native pass); the
        # per-block gather moves the u64 lane through the permutation.
        # All doc keys share one width here, so the matrix FNV is byte-
        # exact with fnv64_bytes — consistent with flush-built blocks
        key_hash_all = _fnv_rows(doc_keys)
        from ..storage import native_lib
        arrs = {c.id: np.asarray(columns[c.name])
                for c in self.schema.columns}
        dk_w = doc_keys.shape[1]

        def make(s: int) -> ColumnarBlock:
            ord_b = np.ascontiguousarray(order[s:s + block_rows])
            bn = len(ord_b)
            sel = ord_b if identity else np.ascontiguousarray(idx[ord_b])
            keys_b = np.empty((bn, full.shape[1]), np.uint8)
            kh_b = np.empty(bn, np.uint64)
            jobs = [(full, keys_b, ord_b, None),
                    (key_hash_all, kh_b, ord_b, None)]
            fixed, varlen, pk = {}, {}, {}
            slow_cols = []
            for c in self.schema.columns:
                arr = arrs[c.id]
                if c.is_key or ColumnType.is_fixed(c.type):
                    if arr.dtype != object and arr.flags["C_CONTIGUOUS"]:
                        out = np.empty((bn,) + arr.shape[1:], arr.dtype)
                        jobs.append((arr, out, sel, None))
                    else:
                        out = arr[sel]
                    if c.is_key:
                        pk[c.id] = out
                    else:
                        fixed[c.id] = (out, np.zeros(bn, bool))
                elif arr.dtype.kind == "S" and arr.flags["C_CONTIGUOUS"]:
                    # fixed-width byte strings ride the fused gather as
                    # rows of a byte matrix
                    mat = np.empty((bn, arr.dtype.itemsize), np.uint8)
                    jobs.append((arr.view(np.uint8).reshape(len(arr), -1),
                                 mat, sel, None))
                    slow_cols.append((c, mat, True))
                else:
                    slow_cols.append((c, arr, False))
            native_lib.gather_columns(jobs)
            for c, arr, gathered in slow_cols:
                if gathered or arr.dtype.kind == "S":
                    # fixed-width byte strings: a value is its bytes up
                    # to the trailing NULs, so the heap is one masked
                    # copy of the gathered rows' byte matrix
                    mat = arr if gathered else \
                        np.ascontiguousarray(arr[sel]).view(
                            np.uint8).reshape(bn, -1)
                    lens = np.char.str_len(mat.view(f"S{mat.shape[1]}")
                                           ).reshape(bn)
                    inside = mat != 0
                    if int(np.count_nonzero(inside)) != int(lens.sum()):
                        # a NUL inside a value
                        inside = np.arange(mat.shape[1]) < lens[:, None]
                    varlen[c.id] = (np.cumsum(lens).astype(np.uint32),
                                    mat[inside].tobytes(),
                                    np.zeros(bn, bool))
                    continue
                raws = [x.encode() if isinstance(x, str) else bytes(x)
                        for x in arr[sel]]
                ends = np.cumsum([len(r) for r in raws]).astype(np.uint32)
                varlen[c.id] = (ends, b"".join(raws), np.zeros(bn, bool))
            # unique-keys: adjacent-distinct doc keys inside the block,
            # plus the boundary row against the previous block (a
            # boundary duplicate marks this block non-unique, keeping
            # the batch-level all() exactly as conservative as the old
            # whole-load flag)
            dk_b = keys_b[:, :dk_w]
            uniq = bool((dk_b[1:] != dk_b[:-1]).any(axis=1).all()) \
                if bn > 1 else True
            if s and bool((doc_keys[order[s - 1]] == dk_b[0]).all()):
                uniq = False
            blk = ColumnarBlock.from_arrays(
                schema_version=self.schema.version,
                key_hash=kh_b,
                ht=np.full(bn, ht.value, np.uint64),
                write_id=ord_b.astype(np.uint32),
                pk=pk, fixed=fixed, varlen=varlen,
                keys=keys_b, unique_keys=uniq)
            # keys were built by the exact pipeline derive_keys replays
            # (same encoders, same fast hash, write_id == encoded
            # suffix by construction), so derivability is proven with
            # no write-time verify; cotable prefixes would break the
            # replay (derive_keys refuses them)
            blk.keys_proven = self.info.cotable_id is None
            return blk

        return [functools.partial(make, s)
                for s in range(0, len(order), block_rows)]


#: (weak references to the hash columns' arrays, what they were, their
#: partition hashes) of the last bulk load: `TableCodec._hashes_memo`
_HASHES_MEMO: tuple = ([], None, None)


def _drop_hashes_memo(ref) -> None:
    global _HASHES_MEMO
    if any(r is ref for r in _HASHES_MEMO[0]):
        _HASHES_MEMO = ([], None, None)


def _rows_ge(mat: np.ndarray, bound: np.ndarray) -> np.ndarray:
    """Row-wise lexicographic mat[i] >= bound (vectorized byte-column
    sweep; numpy void rows sort but don't support ordering ufuncs)."""
    n, w = mat.shape
    result = np.zeros(n, bool)
    decided = np.zeros(n, bool)
    for j in range(w):
        gt = ~decided & (mat[:, j] > bound[j])
        lt = ~decided & (mat[:, j] < bound[j])
        result |= gt
        decided |= gt | lt
    return result | ~decided   # fully-equal rows are >=


def _fnv_rows(mat: np.ndarray) -> np.ndarray:
    from ..storage.columnar import fnv64_rows
    return fnv64_rows(mat)
