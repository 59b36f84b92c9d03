"""Columnar block representation — the TPU-facing face of the LSM.

The reference materializes rows one at a time into PgTableRow
(reference: src/yb/dockv/pg_row.h, filled by
src/yb/docdb/doc_rowwise_iterator.cc). We instead keep each SST data
block's rows in STRUCT-OF-ARRAYS form: per-column numpy arrays + null
masks, plus per-row hybrid time / write id / tombstone / key-hash arrays
for MVCC. Decoding a block to device is then a buffer reinterpret, and
scan/filter/aggregate kernels consume it directly (ops/scan.py).

Blocks are built either from packed-row KV entries (flush/compaction
path) or straight from user arrays (bulk load path), and serialize into
the SST's columnar section.

Two on-disk formats coexist (FORMAT.md):

  v1  every lane dumped raw, keys matrix always inline — byte-identical
      to the pre-v2 writer; ``sst_format_version=1`` pins it.
  v2  the keys matrix is DROPPED when it is provably derivable from the
      pk columns + ht/write_id lanes (the writer re-encodes and
      byte-compares before committing to the drop; readers rebuild
      lazily through a bound key_builder), every lane goes through the
      lane_codec "encode only if smaller" menu, and the header carries
      per-block min/max zone maps the scan pushdown prunes on.
"""
from __future__ import annotations

import struct
from typing import Dict, List, Optional, Sequence, Tuple

import msgpack
import numpy as np

from ..dockv.key_encoding import _decode_varint_unsigned
from ..dockv.packed_row import ColumnType, SchemaPacking
from ..dockv.value import ValueKind
from . import lane_codec

#: newest block format this build can read/write; deserialize rejects
#: anything newer with a clear error instead of misparsing it
SUPPORTED_FORMAT_VERSION = 2

#: column ids at or above this are DERIVED scan-lifetime lanes, never
#: row data: join build columns live at 1<<20 (ops/join_scan) and
#: shredded doc paths at 1<<24 (docstore/pushdown).  Serializers and
#: row reconstruction skip them — a derived lane must never persist
#: as an ordinary column (its id is only meaningful in-process)
DERIVED_COL_BASE = 1 << 20

#: lazy key-matrix rebuild tally: every time a v2 keyless block's
#: ``keys`` property fires its key_builder thunk, one rebuild (and the
#: block's row count) lands here.  The analytics scan paths promise to
#: never pay this cost — tests and the bypass reader assert the counter
#: stays flat across a scan; point reads/merges legitimately increment.
KEY_REBUILD_STATS = {"rebuilds": 0, "rows": 0}

_HASH_MULT = np.uint64(0x100000001B3)
_HASH_OFF = np.uint64(0xCBF29CE484222325)


def fnv64_rows(mat: np.ndarray) -> np.ndarray:
    """Row-wise FNV-1a 64-bit over an [N, L] uint8 matrix — one native
    GIL-released pass when the library is built (the bulk-load key-hash
    lane), else vectorized numpy (loop over the short L axis)."""
    from . import native_lib
    if mat.dtype == np.uint8 and mat.ndim == 2:
        nat = native_lib.fnv64_rows_fixed(np.ascontiguousarray(mat))
        if nat is not None:
            return nat
    h = np.full(mat.shape[0], _HASH_OFF)
    for j in range(mat.shape[1]):
        h = (h ^ mat[:, j].astype(np.uint64)) * _HASH_MULT
    return h


_HOT = None


def native_hot():
    """Cached accessor for the ybtpu_hot CPython extension (or None).
    The import must stay call-time lazy — a module-level import of
    docdb.hotpath from the storage layer would cycle through
    docdb/__init__. This is the ONE shared memo; other storage modules
    import it rather than re-rolling the idiom."""
    global _HOT
    if _HOT is None:
        from ..docdb.hotpath import load as _load_hot
        _HOT = _load_hot() or False
    return _HOT or None


def fnv64_bytes(data: bytes) -> int:
    hot = native_hot()
    if hot is not None:
        return hot.fnv64(data)
    h = 0xCBF29CE484222325
    for b in data:
        h = ((h ^ b) * 0x100000001B3) & 0xFFFFFFFFFFFFFFFF
    return h


def fnv64_keys(keys: Sequence[bytes]) -> np.ndarray:
    """Vectorized fnv64_bytes over variable-length keys: column-wise masked
    updates so the result is byte-exact with the scalar hash regardless of
    block-local padding (required for cross-block/SST dedup joins)."""
    if not keys:
        return np.zeros(0, np.uint64)
    from . import native_lib
    nat = native_lib.fnv64_batch(keys)
    if nat is not None:
        return nat
    lens = np.array([len(k) for k in keys], np.int64)
    w = int(lens.max())
    mat = np.zeros((len(keys), w), np.uint8)
    if lens.min() == w:
        mat[:] = np.frombuffer(b"".join(keys), np.uint8).reshape(-1, w)
    else:
        for i, k in enumerate(keys):
            mat[i, :len(k)] = np.frombuffer(k, np.uint8)
    h = np.full(len(keys), _HASH_OFF)
    for j in range(w):
        upd = (h ^ mat[:, j].astype(np.uint64)) * _HASH_MULT
        h = np.where(j < lens, upd, h)
    return h


class ColumnarBlock:
    """Struct-of-arrays form of one sorted run of rows.

    Attributes:
      n, schema_version
      key_hash  uint64 — FNV of encoded DocKey (no HT)
      ht        uint64 — HybridTime.value
      write_id  uint32
      tombstone bool
      pk        {col id: values} — fixed-width PK component values
      fixed     {col id: (values, null_mask)}
      varlen    {col id: (end_offsets uint32 [n], heap bytes, null_mask)}
      unique_keys  True when every doc key appears exactly once in this
                   block (post-compaction / bulk-load blocks) — enables
                   the scan path that links no row versions.
      keys      optional full encoded SubDocKeys (incl. HT suffix) as an
                [N, L] uint8 matrix — present on columnar-only blocks
                (bulk loads), where the KV row region is omitted
                entirely and rows are reconstructed on demand. For v2
                keyless blocks this is a LAZY property: the matrix
                rebuilds from pk + ht/write_id through the bound
                key_builder on first access.
      zmap      {col id: (min, max)} per-block zone map over non-null
                values of pk + fixed value columns (v2 blocks only) —
                the scan pushdown prunes whole blocks on it.
    """

    __slots__ = ("n", "schema_version", "key_hash", "ht", "write_id",
                 "tombstone", "pk", "fixed", "varlen", "unique_keys",
                 "zmap", "keys_proven", "_keys",
                 "_key_thunk", "_first_key", "_last_key", "_void_keys",
                 "_vdicts", "_vdict_cache", "shred",
                 "_finder", "_extractors", "__weakref__")

    def __init__(self, n: int, schema_version: int,
                 key_hash: np.ndarray, ht: np.ndarray,
                 write_id: np.ndarray, tombstone: np.ndarray,
                 pk: Optional[Dict[int, np.ndarray]] = None,
                 fixed: Optional[Dict[int, Tuple[np.ndarray, np.ndarray]]] = None,
                 varlen: Optional[Dict[int, Tuple[np.ndarray, bytes, np.ndarray]]] = None,
                 unique_keys: bool = True,
                 keys: Optional[np.ndarray] = None):
        self.n = n
        self.schema_version = schema_version
        self.key_hash = key_hash
        self.ht = ht
        self.write_id = write_id
        self.tombstone = tombstone
        self.pk = pk if pk is not None else {}
        self.fixed = fixed if fixed is not None else {}
        self.varlen = varlen if varlen is not None else {}
        self.unique_keys = unique_keys
        self.zmap: Optional[Dict[int, Tuple[object, object]]] = None
        # True when every row's key is PROVEN byte-derivable from the
        # pk + ht/write_id lanes: set by the bulk builder (keys were
        # built by the very function derive_keys replays), by v2
        # deserialize of derived blocks (write-time verify passed), and
        # propagated row-wise through slice/concat/gather — the v2
        # serializer then drops keys without re-verifying (a full
        # re-encode per block otherwise sits on the write path)
        self.keys_proven: bool = False
        self._keys: Optional[np.ndarray] = None
        self._key_thunk = None         # callable(cb) -> ndarray | None
        self._first_key: Optional[bytes] = None
        self._last_key: Optional[bytes] = None
        # lazily-built void view of `keys` for binary search (point
        # reads revisit hot blocks; rebuilding the view per lookup is an
        # O(block) copy)
        self._void_keys: Optional[np.ndarray] = None
        # varlen dictionary state: `_vdicts[cid]` holds raw on-disk dict
        # parts (uniq_lens, uniq_heap, codes) when the block was stored
        # dict-coded; `_vdict_cache[(cid, max_card)]` memoizes
        # dict_varlen() results (False = known-uncodable under that cap)
        # so the per-block dictionary is built at most once per cap
        self._vdicts: Dict[int, tuple] = {}
        self._vdict_cache: Dict[tuple, object] = {}
        # shredded document lanes (docstore/): {json col id: {path
        # tuple: (kind, payload, present bool[n], bounds)}} — derived
        # acceleration lanes the v2 serializer emits behind
        # doc_shred_enabled; the raw JSON varlen lane stays the source
        # of truth, so slice/concat/gather deliberately do NOT carry
        # these (compaction re-shreds from the raw payload at write)
        self.shred: Dict[int, Dict[tuple, tuple]] = {}
        if keys is not None:
            self.keys = keys

    # --- lazy keys matrix --------------------------------------------
    @property
    def keys(self) -> Optional[np.ndarray]:
        """Full encoded SubDocKey matrix. For v2 keyless blocks the
        first access rebuilds it through the bound key_builder (one
        fused vectorized re-encode from pk + ht + write_id); None when
        the block has no keys and no way to derive them."""
        if self._keys is None and self._key_thunk is not None:
            thunk, self._key_thunk = self._key_thunk, None
            KEY_REBUILD_STATS["rebuilds"] += 1
            KEY_REBUILD_STATS["rows"] += self.n
            self._keys = thunk(self)
        return self._keys

    @keys.setter
    def keys(self, v: Optional[np.ndarray]) -> None:
        self._keys = v
        self._void_keys = None

    @property
    def keys_derivable(self) -> bool:
        """True when a keys matrix is available or can be rebuilt."""
        return self._keys is not None or self._key_thunk is not None

    def bind_key_builder(self, builder) -> None:
        """Attach the lazy rebuild callback of a v2 keyless block (set
        by SstReader from the docdb codec's derive_keys)."""
        if self._keys is None and builder is not None:
            self._key_thunk = builder

    def boundary_keys(self, materialize: bool = True
                      ) -> Tuple[Optional[bytes], Optional[bytes]]:
        """(first, last) full encoded keys of the block.  Consults the
        materialized matrix or the stored v2 boundary keys (k0/k1);
        with ``materialize=False`` it returns ``(None, None)`` instead
        of firing the lazy key_builder — eligibility and zone-prune
        decisions use this form so a pruning pass can never pay a
        whole-block key rebuild."""
        if self._keys is not None:
            if not self.n:
                return None, None
            return self._keys[0].tobytes(), self._keys[-1].tobytes()
        if self._first_key is not None:
            return self._first_key, self._last_key
        if not materialize:
            return None, None
        k = self.keys                  # may invoke the rebuild thunk
        if k is None or not self.n:
            return None, None
        return k[0].tobytes(), k[-1].tobytes()

    def first_full_key(self) -> Optional[bytes]:
        """First row's full encoded key WITHOUT materializing a derived
        keys matrix when the serialized boundary keys are present."""
        return self.boundary_keys()[0]

    def last_full_key(self) -> Optional[bytes]:
        return self.boundary_keys()[1]

    # --- varlen dictionaries ------------------------------------------
    def dict_varlen(self, cid: int, max_card: int = 1 << 16):
        """Block-local dictionary view of one varlen (string) column:
        ``(uniq, codes)`` with `uniq` a SORTED object array of str and
        `codes` int32 row codes into it (NULL rows code as "").  None
        when the column can't dictionary-encode (over-long rows, too
        many distinct values, non-UTF8 payloads).

        Sourced from the stored v2 dict-coded lane when present (zero
        row-string decodes), else built once with the byte-level
        void-view unique (rows are never decoded; only the few uniques
        are).  Memoized per (block, max_card) — a low-cap miss must not
        poison a later higher-cap call — and consumed by scan-global
        dictionary merges / remap tables (lane_codec.merge_dicts)."""
        got = self._vdict_cache.get((cid, max_card))
        if got is not None:
            return got if got is not False else None
        out = None
        try:
            stored = self._vdicts.get(cid)
            if stored is not None:
                ulens, uheap, codes = stored
                out = (lane_codec.decode_dict_strings(ulens, uheap),
                       np.asarray(codes, np.int32))
            elif cid in self.varlen:
                ends, heap, null = self.varlen[cid]
                # no sample guard here: this dict serves the grouped
                # kernel / predicate remap (bounded by max_card), not a
                # write-time smaller-or-skip decision
                coded = lane_codec.varlen_code_rows(
                    ends, heap, null, max_card=max_card,
                    sample_guard=False)
                if coded is not None:
                    ulens, uheap, codes = coded
                    out = (lane_codec.decode_dict_strings(ulens, uheap),
                           codes)
        except UnicodeDecodeError:
            out = None
        self._vdict_cache[(cid, max_card)] = out if out is not None \
            else False
        return out

    # ------------------------------------------------------------------
    @classmethod
    def from_packed_entries(
            cls, packing: SchemaPacking,
            keys: Sequence[bytes],              # encoded DocKey (no HT suffix)
            hts: np.ndarray, write_ids: np.ndarray,
            values: Sequence[bytes],            # KV values (kPackedRowV2 or
                                                # kTombstone)
            pk_decoder=None) -> "ColumnarBlock":
        """Build from packed-row KV entries (flush/compaction path).

        The fixed-stride prefix of the packed format means we can stack
        all rows' prefixes into one [N, stride] matrix and reinterpret —
        no per-row decode loop (see dockv/packed_row.py docstring).
        """
        n = len(keys)
        tomb = np.zeros(n, bool)
        hdr_len = _varint_len(packing.schema_version)
        plen = hdr_len + packing.prefix_size
        prefix_parts = []
        pad = b"\x00" * plen
        for i, v in enumerate(values):
            if v[0] == ValueKind.kTombstone:
                tomb[i] = True
                prefix_parts.append(pad)
            elif v[0] == ValueKind.kPackedRowV2:
                prefix_parts.append(v[1:1 + plen])
            else:
                raise ValueError("columnar block needs packed or tombstone values")
        mat = np.frombuffer(b"".join(prefix_parts), np.uint8).reshape(n, plen)
        body = mat[:, hdr_len:]
        blk = cls(
            n=n, schema_version=packing.schema_version,
            key_hash=fnv64_keys(keys),
            ht=np.asarray(hts, np.uint64),
            write_id=np.asarray(write_ids, np.uint32),
            tombstone=tomb,
        )
        # null bitmap -> per-column masks
        bitmap = body[:, :packing.bitmap_size]
        for i, c in enumerate(packing.all_columns):
            byte, bit = i // 8, i % 8
            mask = (bitmap[:, byte] >> bit) & 1
            null = mask.astype(bool) | tomb
            if ColumnType.is_fixed(c.type):
                off = packing.bitmap_size + packing.fixed_offsets[c.id]
                w = ColumnType.FIXED_WIDTHS[c.type]
                dt = ColumnType.NUMPY_DTYPES[c.type]
                vals = np.ascontiguousarray(
                    body[:, off:off + w]).view(dt).reshape(n)
                blk.fixed[c.id] = (vals.copy(), null)
        # varlen columns: per-row heaps differ in length → per-column gather
        if packing.varlen_columns:
            voff0 = packing.bitmap_size + packing.fixed_size
            ends_mat = np.ascontiguousarray(
                body[:, voff0:voff0 + 4 * len(packing.varlen_columns)]
            ).view("<u4").reshape(n, len(packing.varlen_columns))
            heaps = [v[1 + plen:] if not tomb[i] else b""
                     for i, v in enumerate(values)]
            for vi, c in enumerate(packing.varlen_columns):
                i_ = len(packing.fixed_columns) + vi
                null = ((bitmap[:, i_ // 8] >> (i_ % 8)) & 1).astype(bool) | tomb
                starts = ends_mat[:, vi - 1] if vi else np.zeros(n, np.uint32)
                ends = ends_mat[:, vi]
                heap = bytearray()
                out_ends = np.zeros(n, np.uint32)
                for i in range(n):
                    if not null[i]:
                        heap += heaps[i][starts[i]:ends[i]]
                    out_ends[i] = len(heap)
                blk.varlen[c.id] = (out_ends, bytes(heap), null)
        return blk

    @classmethod
    def from_arrays(cls, schema_version: int,
                    key_hash: np.ndarray, ht: np.ndarray,
                    write_id: Optional[np.ndarray] = None,
                    pk: Optional[Dict[int, np.ndarray]] = None,
                    fixed: Optional[Dict[int, Tuple[np.ndarray, np.ndarray]]] = None,
                    varlen: Optional[Dict[int, Tuple[np.ndarray, bytes, np.ndarray]]] = None,
                    tombstone: Optional[np.ndarray] = None,
                    unique_keys: bool = True,
                    keys: Optional[np.ndarray] = None) -> "ColumnarBlock":
        n = len(key_hash)
        return cls(
            n=n, schema_version=schema_version,
            key_hash=np.asarray(key_hash, np.uint64),
            ht=np.asarray(ht, np.uint64),
            write_id=(np.asarray(write_id, np.uint32) if write_id is not None
                      else np.zeros(n, np.uint32)),
            tombstone=(np.asarray(tombstone, bool) if tombstone is not None
                       else np.zeros(n, bool)),
            pk=dict(pk or {}), fixed=dict(fixed or {}), varlen=dict(varlen or {}),
            unique_keys=unique_keys, keys=keys)

    # ------------------------------------------------------------------
    def serialize_parts(self, version: int = 1, key_builder=None,
                        stats: Optional[dict] = None,
                        shred_cols: Tuple[int, ...] = ()
                        ) -> Tuple[bytes, List[object]]:
        """(header bytes, payload buffers). Buffers are buffer-protocol
        objects (contiguous ndarrays / bytes) so callers can stream them
        to a file without materializing one giant bytes — compaction
        writes hundreds of MB through here.

        version=1 reproduces the pre-v2 bytes EXACTLY (the
        ``sst_format_version=1`` gate). version=2 drops the keys matrix
        when ``key_builder(self)`` rebuilds it byte-identically, runs
        every lane through lane_codec, and embeds zone maps; `stats`
        (optional dict) accumulates the per-lane encode accounting.

        ``shred_cols``: JSON column ids to document-shred (docstore/) —
        v2 only, resolved by SstWriter behind ``doc_shred_enabled``;
        the default () keeps the output byte-identical to the
        pre-shred v2 writer."""
        if version == 1:
            return self._serialize_v1()
        if version != 2:
            raise ValueError(f"unknown block format version {version}")
        return self._serialize_v2(key_builder, stats, shred_cols)

    def _serialize_v1(self) -> Tuple[bytes, List[object]]:
        bufs: List[object] = []
        def ref(arr: np.ndarray) -> dict:
            a = np.ascontiguousarray(arr)
            bufs.append(a)
            return {"dtype": str(arr.dtype), "shape": list(arr.shape),
                    "len": a.nbytes}
        meta = {
            "n": self.n, "sv": self.schema_version, "uniq": self.unique_keys,
            "keys": ref(self.keys) if self.keys is not None else None,
            "key_hash": ref(self.key_hash), "ht": ref(self.ht),
            "wid": ref(self.write_id), "tomb": ref(self.tombstone),
            "pk": {str(k): ref(v) for k, v in self.pk.items()},
            "fixed": {str(k): [ref(v), ref(m)]
                      for k, (v, m) in self.fixed.items()
                      if k < DERIVED_COL_BASE},
            "varlen": {},
        }
        for k, (ends, heap, null) in self.varlen.items():
            if k >= DERIVED_COL_BASE:
                continue
            bufs.append(heap)
            meta["varlen"][str(k)] = [ref(ends), {"len": len(heap)}, ref(null)]
        head = msgpack.packb(meta)
        return struct.pack("<I", len(head)) + head, bufs

    def _serialize_v2(self, key_builder, stats: Optional[dict],
                      shred_cols: Tuple[int, ...] = ()
                      ) -> Tuple[bytes, List[object]]:
        bufs: List[object] = []

        def lane(name: str, arr: np.ndarray) -> dict:
            m, parts, enc = lane_codec.encode_lane(arr)
            bufs.extend(parts)
            lane_codec.tally(stats, name, arr.nbytes,
                             sum(p.nbytes for p in parts), enc)
            return m

        keys = self.keys
        keys_meta = None
        if keys is not None:
            drop = False
            if key_builder is not None:
                if self.keys_proven:
                    # row-wise derivability already proven upstream
                    # (bulk construction or gathered from proven
                    # blocks): skip the full re-encode+compare
                    drop = True
                else:
                    derived = None
                    try:
                        derived = key_builder(self)
                    except Exception:  # noqa: BLE001 — derivation is an
                        derived = None  # optimization, never a crasher
                    drop = (derived is not None
                            and derived.shape == keys.shape
                            and derived.dtype == keys.dtype
                            and np.array_equal(derived, keys))
            if drop:
                keys_meta = {"drv": 1}
                lane_codec.tally(stats, "keys", keys.nbytes, 0, "derived")
            else:
                keys_meta = lane("keys", keys)
        meta = {
            "v": 2,
            "n": self.n, "sv": self.schema_version, "uniq": self.unique_keys,
            "keys": keys_meta,
            "key_hash": lane("key_hash", self.key_hash),
            "ht": lane("ht", self.ht),
            "wid": lane("write_id", self.write_id),
            "tomb": lane("tombstone", self.tombstone),
            "pk": {str(k): lane("pk", v) for k, v in self.pk.items()},
            "fixed": {str(k): [lane("fixed_vals", v), lane("fixed_null", m)]
                      for k, (v, m) in self.fixed.items()
                      if k < DERIVED_COL_BASE},
            "varlen": {},
        }
        for k, (ends, heap, null) in self.varlen.items():
            if k >= DERIVED_COL_BASE:
                continue
            dict_meta = self._dict_varlen_parts(ends, heap, null, bufs,
                                                stats)
            if dict_meta is not None:
                meta["varlen"][str(k)] = [dict_meta, {"len": 0},
                                          lane("varlen_null", null)]
                continue
            # heap rides FIRST in the payload stream (the v1 order, so
            # the shared deserializer walks both formats identically)
            hb = (heap if isinstance(heap, (bytes, bytearray))
                  else np.ascontiguousarray(heap))
            bufs.append(hb)
            lane_codec.tally(stats, "varlen_heap", len(heap), len(heap),
                             "raw")
            meta["varlen"][str(k)] = [lane("varlen_ends", ends),
                                      {"len": len(heap)},
                                      lane("varlen_null", null)]
        # shredded document lanes ride LAST in the payload stream:
        # readers that predate the docstore module walk their known
        # lanes by explicit byte lengths and never reach these buffers
        if shred_cols:
            # call-time lazy import (the native_hot idiom): docstore
            # imports storage at module scope, never the reverse
            from ..docstore import shred as _doc_shred
            shred_meta = {}
            for cid in sorted(shred_cols):
                vl = self.varlen.get(cid)
                if vl is None:
                    continue
                entries = _doc_shred.serialize_shred(
                    vl[0], vl[1], vl[2], bufs, stats)
                if entries:
                    shred_meta[str(cid)] = entries
            if shred_meta:
                meta["shred"] = shred_meta
        if keys is not None and self.n:
            meta["k0"] = keys[0].tobytes()
            meta["k1"] = keys[-1].tobytes()
        zmap = self._build_zone_map()
        if zmap:
            meta["zmap"] = {str(c): [lo, hi] for c, (lo, hi) in
                            zmap.items()}
        head = msgpack.packb(meta)
        lane_codec.tally(stats, "header", len(head) + 4, len(head) + 4,
                         "raw")
        return struct.pack("<I", len(head)) + head, bufs

    def _dict_varlen_parts(self, ends, heap, null, bufs: List[object],
                           stats: Optional[dict]):
        """v2 dict coding of one varlen lane: uniques (lens + heap) +
        narrow codes replace the row heap + ends lane when STRICTLY
        smaller than their raw dump.  Only lanes whose NULL rows carry
        zero-length payloads qualify — reconstruction (codes -> per-row
        payloads) must round-trip the original (ends, heap) bytes
        exactly.  Returns the lane meta dict, or None to keep raw."""
        n = len(ends)
        if n < 2:
            return None
        ends64 = np.asarray(ends, np.int64)
        lens = np.diff(np.concatenate([[0], ends64]))
        if null is not None and np.asarray(null, bool).any() and \
                lens[np.asarray(null, bool)].any():
            return None               # lossy for non-empty NULL payloads
        coded = lane_codec.varlen_code_rows(ends, heap, null,
                                            max_card=0xFFFF)
        if coded is None:
            return None
        ulens, uheap, codes = coded
        k = len(ulens)
        cdt = np.dtype(np.uint8 if k <= 0x100 else np.uint16)
        raw_basis = len(heap) + np.asarray(ends).nbytes
        size = ulens.nbytes + uheap.nbytes + n * cdt.itemsize
        if size >= raw_basis:
            return None
        codes_n = np.ascontiguousarray(codes.astype(cdt))
        bufs.extend([np.ascontiguousarray(ulens),
                     np.ascontiguousarray(uheap), codes_n])
        lane_codec.tally(stats, "varlen_dict", raw_basis, size, "dict")
        return {"venc": "dict", "k": k, "cdt": str(cdt),
                "parts": [ulens.nbytes, uheap.nbytes, codes_n.nbytes]}

    @staticmethod
    def _dict_varlen_stored(vmeta: dict, fetch):
        """The stored parts of a dictionary-coded text lane: the uniques'
        lengths and bytes, and a code a row."""
        ulens = np.frombuffer(fetch(vmeta["parts"][0]), np.uint8).copy()
        uheap = bytes(fetch(vmeta["parts"][1]))
        codes = np.frombuffer(fetch(vmeta["parts"][2]),
                              np.dtype(vmeta["cdt"])).astype(np.int32)
        return ulens, uheap, codes

    @classmethod
    def _decode_dict_varlen(cls, vmeta: dict, fetch):
        """Inverse of _dict_varlen_parts: rebuild the exact (ends, heap)
        pair and return the raw dict parts for dict_varlen()."""
        ulens, uheap, codes = cls._dict_varlen_stored(vmeta, fetch)
        u_ends = np.cumsum(ulens.astype(np.int64))
        u_starts = u_ends - ulens
        row_lens = ulens[codes].astype(np.int64)
        ends = np.cumsum(row_lens).astype(np.uint32)
        if int(row_lens.sum()):
            # the uniques as rows of one zero-padded byte matrix; a row's
            # payload is its unique's row up to its length, so the heap
            # is one gather of whole rows and one masked copy (the values
            # are at most 255 bytes and few: a dictionary lane)
            hb = np.frombuffer(uheap, np.uint8)
            width = int(ulens.max())
            col = np.arange(width)
            inside = col < ulens[:, None]
            padded = np.zeros((len(ulens), width), np.uint8)
            padded[inside] = hb[:int(u_ends[-1])]
            heap = padded[codes][inside[codes]].tobytes()
        else:
            heap = b""
        return ends, heap, (ulens, uheap, codes)

    def _build_zone_map(self) -> Dict[int, Tuple[object, object]]:
        """Per-column (min, max) over non-null values of pk + fixed
        value columns. Exact python ints for integer lanes (no float
        rounding at int64 magnitudes — the prune comparisons must be
        safe at block boundaries); floats skip when NaN is present."""
        out: Dict[int, Tuple[object, object]] = {}
        if not self.n:
            return out

        def bounds(arr: np.ndarray, null: Optional[np.ndarray]):
            if arr.ndim != 1 or arr.dtype.kind not in "iuf":
                return None
            v = arr if null is None else arr[~null]
            if not len(v):
                return None
            lo, hi = v.min(), v.max()
            if arr.dtype.kind == "f":
                if not (np.isfinite(lo) and np.isfinite(hi)):
                    return None
                return (float(lo), float(hi))
            return (int(lo), int(hi))

        for cid, arr in self.pk.items():
            b = bounds(np.asarray(arr), None)
            if b is not None:
                out[cid] = b
        for cid, (vals, null) in self.fixed.items():
            if cid >= DERIVED_COL_BASE:
                continue    # scan-lifetime lane: never persisted
            b = bounds(np.asarray(vals), np.asarray(null))
            if b is not None:
                out[cid] = b
        return out

    def serialize(self, version: int = 1, key_builder=None) -> bytes:
        head, bufs = self.serialize_parts(version, key_builder)
        return head + b"".join(
            b if isinstance(b, bytes) else memoryview(b).cast("B")
            for b in bufs)

    @classmethod
    def deserialize(cls, data, copy: bool = True,
                    max_version: int = SUPPORTED_FORMAT_VERSION,
                    columns=None) -> "ColumnarBlock":
        """Rebuild a block from its serialized form. With copy=False and
        a buffer-backed `data` (e.g. a memoryview over the SST mmap) the
        arrays are zero-copy READ-ONLY views — the compaction pipeline
        reads each input row once, so materializing owned copies first
        would double its memory traffic for nothing. (v2 lanes that were
        lane-encoded decode into small owned arrays either way; raw
        lanes stay views.)

        Blocks newer than ``max_version`` raise a clear ValueError — the
        v2-written/v1-reader rejection path — instead of misparsing.

        ``columns``: a set of value-column ids — a PROJECTED block, for a
        reader that knows what it will touch (a key probe, a scan's
        column set).  The MVCC lanes, the pk lanes and the keys are
        always there; a value column outside the set is skipped in the
        stream and absent from `fixed`/`varlen`; a dictionary-coded text
        column inside it keeps its stored parts (`dict_varlen`) and its
        null mask, and its row heap is not rebuilt (`varlen[cid]` is
        ``(None, None, null)``); document shreds are left out.  Such a
        block is its caller's own: it never enters a shared cache."""
        hlen = struct.unpack_from("<I", data)[0]
        meta = msgpack.unpackb(data[4:4 + hlen], strict_map_key=False)
        version = meta.get("v", 1)
        if version > max_version:
            raise ValueError(
                f"columnar block format v{version} is newer than this "
                f"reader supports (<= v{max_version}); upgrade before "
                "reading this SST")
        pos = 4 + hlen

        def fetch(n):
            nonlocal pos
            raw = data[pos:pos + n]
            pos += n
            return raw

        def skip(ref) -> None:
            nonlocal pos
            pos += ref["len"] if ref.get("enc") is None \
                else sum(ref["parts"])

        if version == 1:
            def take(ref) -> np.ndarray:
                raw = fetch(ref["len"])
                arr = np.frombuffer(raw, dtype=np.dtype(ref["dtype"])
                                    ).reshape(ref["shape"])
                return arr.copy() if copy else arr
        else:
            def take(ref) -> np.ndarray:
                enc = ref.get("enc")
                arr = lane_codec.decode_lane(ref, fetch)
                if enc is None and copy:
                    return arr.copy()
                return arr

        keys_meta = meta.get("keys")
        keys = None
        derived = False
        if keys_meta is not None:
            if keys_meta.get("drv"):
                derived = True
            else:
                keys = take(keys_meta)
        blk = cls(
            n=meta["n"], schema_version=meta["sv"],
            key_hash=take(meta["key_hash"]), ht=take(meta["ht"]),
            write_id=take(meta["wid"]), tombstone=take(meta["tomb"]),
            unique_keys=meta["uniq"], keys=keys)
        for k, ref_ in meta["pk"].items():
            blk.pk[int(k)] = take(ref_)
        for k, (vref, mref) in meta["fixed"].items():
            if columns is not None and int(k) not in columns:
                skip(vref)
                skip(mref)
                continue
            v = take(vref)
            m = take(mref)
            blk.fixed[int(k)] = (v, m)
        for k, (eref, heapinfo, nref) in meta["varlen"].items():
            coded = eref.get("venc") == "dict"
            if columns is not None and int(k) not in columns:
                pos += heapinfo["len"]
                if coded:
                    pos += sum(eref["parts"])
                else:
                    skip(eref)
                skip(nref)
                continue
            heap = fetch(heapinfo["len"])
            if coded and columns is not None:
                ends = heap = None
                blk._vdicts[int(k)] = cls._dict_varlen_stored(eref, fetch)
            elif coded:
                ends, heap, parts = cls._decode_dict_varlen(eref, fetch)
                blk._vdicts[int(k)] = parts
            else:
                ends = take(eref)
            null = take(nref)
            blk.varlen[int(k)] = (ends, heap, null)
        if version >= 2:
            sh = meta.get("shred") if columns is None else None
            if sh:
                from ..docstore import shred as _doc_shred
                for cid_s, entries in sh.items():
                    blk.shred[int(cid_s)] = _doc_shred.deserialize_shred(
                        entries, fetch, cls._decode_dict_varlen)
            if derived:
                blk.keys_proven = True     # write-time verify passed
            if meta.get("k0") is not None:
                blk._first_key = meta["k0"]
                blk._last_key = meta["k1"]
            z = meta.get("zmap")
            if z:
                blk.zmap = {int(c): (b[0], b[1]) for c, b in z.items()}
        return blk

    def visible_mask(self, read_ht: int) -> np.ndarray:
        """MVCC visibility: rows written at or before read_ht."""
        return self.ht <= np.uint64(read_ht)

    def slice(self, lo: int, hi: int) -> "ColumnarBlock":
        """Cheap row-range view [lo, hi) — used by point lookups so a
        single row decodes without materializing the whole block."""
        out = ColumnarBlock(
            n=hi - lo, schema_version=self.schema_version,
            key_hash=self.key_hash[lo:hi], ht=self.ht[lo:hi],
            write_id=self.write_id[lo:hi], tombstone=self.tombstone[lo:hi],
            unique_keys=self.unique_keys,
            keys=self.keys[lo:hi] if self.keys is not None else None)
        out.keys_proven = self.keys_proven   # row-wise property
        for cid, arr in self.pk.items():
            out.pk[cid] = arr[lo:hi]
        for cid, (v, m) in self.fixed.items():
            out.fixed[cid] = (v[lo:hi], m[lo:hi])
        for cid, (ends, heap, null) in self.varlen.items():
            starts = int(ends[lo - 1]) if lo else 0
            new_ends = (ends[lo:hi].astype(np.int64) - starts).astype(
                np.uint32)
            out.varlen[cid] = (new_ends,
                               heap[starts:int(ends[hi - 1]) if hi else 0],
                               null[lo:hi])
        return out

    @classmethod
    def concat(cls, blocks: Sequence["ColumnarBlock"]) -> "ColumnarBlock":
        """Row-wise concatenation of blocks with identical column sets
        (the output-side twin of `slice`; the compaction pipeline buffers
        gathered chunk pieces and cuts exact-size output blocks from the
        concatenation). Varlen end-offsets are rebased onto the joined
        heap. `unique_keys` is NOT derived — callers that know the
        adjacency set it explicitly."""
        if len(blocks) == 1:
            return blocks[0]
        first = blocks[0]
        out = cls(
            n=sum(b.n for b in blocks),
            schema_version=first.schema_version,
            key_hash=np.concatenate([b.key_hash for b in blocks]),
            ht=np.concatenate([b.ht for b in blocks]),
            write_id=np.concatenate([b.write_id for b in blocks]),
            tombstone=np.concatenate([b.tombstone for b in blocks]),
            unique_keys=False,
            keys=(np.concatenate([b.keys for b in blocks])
                  if first.keys is not None else None))
        out.keys_proven = all(b.keys_proven for b in blocks)
        for cid in first.pk:
            out.pk[cid] = np.concatenate([b.pk[cid] for b in blocks])
        for cid in first.fixed:
            out.fixed[cid] = (
                np.concatenate([b.fixed[cid][0] for b in blocks]),
                np.concatenate([b.fixed[cid][1] for b in blocks]))
        for cid in first.varlen:
            ends_all, nulls, heaps = [], [], []
            base = 0
            for b in blocks:
                ends, heap, null = b.varlen[cid]
                ends_all.append(ends.astype(np.int64) + base)
                nulls.append(null)
                heaps.append(bytes(heap))
                base += len(heaps[-1])
            out.varlen[cid] = (
                np.concatenate(ends_all).astype(np.uint32),
                b"".join(heaps), np.concatenate(nulls))
        return out

    def searchsorted_key(self, key: bytes) -> int:
        """First row index with keys[i] >= key (requires the keys matrix).
        Pads/truncates `key` to the matrix width; doc-key prefix freedom
        makes zero padding order-correct."""
        assert self.keys is not None
        if self._void_keys is None:
            w = self.keys.shape[1]
            v = np.dtype((np.void, w))
            object.__setattr__(
                self, "_void_keys",
                np.ascontiguousarray(self.keys).view(v).reshape(-1))
        vk = self._void_keys
        w = vk.dtype.itemsize
        probe = key[:w].ljust(w, b"\x00")
        t = np.frombuffer(probe, vk.dtype)[0]
        return int(np.searchsorted(vk, t, side="left"))


def _varint_len(v: int) -> int:
    n = 1
    while v >= 0x80:
        v >>= 7
        n += 1
    return n


