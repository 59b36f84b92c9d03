"""Per-lane lightweight encodings for the v2 columnar SST block format.

Each block lane (an MVCC column, a null mask, a value column, varlen
end-offsets) is encoded independently with the cheapest scheme that
actually shrinks it — the strict "encode only if smaller" rule: every
candidate's exact encoded size is compared against the raw dump and raw
wins ties, so an incompressible lane (random f64 prices, FNV key
hashes) costs zero bytes and zero decode work over v1.

The menu targets the shapes LSM MVCC lanes actually take ("Columnar
Formats for Schemaless LSM-based Document Stores" exploits the same
structure):

  const   one value repeated (bulk-load ht lanes, all-false tombstone
          and null masks)                      -> 1 value
  dconst  arithmetic progression (write_id = arange, sequential
          row ids, fixed-width varlen offsets) -> first + step
  delta   wraparound deltas zigzag-packed into the narrowest unsigned
          dtype (slowly-varying hts, varlen end offsets of short
          strings)                             -> first + n-1 narrow
  rle     run values + run lengths (sparse tombstone/null masks,
          sorted low-cardinality lanes)        -> 2 * runs
  dict    sorted uniques + narrow codes (low-cardinality value
          columns: quantities, discounts, date columns, the ht set of
          a multi-SST compaction output)       -> uniques + n codes

All encoders operate on an unsigned-integer VIEW of the lane (floats
and bools reinterpret bit-exactly), so NaN payloads and signed zeros
round-trip byte-identically; the decoders are plain numpy — the decode
oracle the tests replay against the original arrays.

Buffer metadata rides in the block's msgpack header: a raw lane keeps
the v1 ``{"dtype", "shape", "len"}`` shape; an encoded lane adds
``"enc"`` plus per-part buffer descriptors, so v1 readers that predate
this module never see the keys (they reject on the block's version tag
first).
"""
from __future__ import annotations

from typing import Callable, Dict, List, Optional, Tuple

import numpy as np

#: unsigned view dtype per itemsize — encodings reinterpret, never
#: convert, so float/bool lanes round-trip bit-exactly
_UVIEW = {1: np.uint8, 2: np.uint16, 4: np.uint32, 8: np.uint64}
_NARROW = (np.uint8, np.uint16, np.uint32)

#: dict encoding is only attempted when a small prefix sample stays
#: under this many distinct values — np.unique over the full lane is
#: O(n log n) and must not run on high-cardinality lanes just to fail
_DICT_SAMPLE = 2048
_DICT_SAMPLE_MAX = 384


def _uview(arr: np.ndarray) -> Optional[np.ndarray]:
    """1-D same-width unsigned reinterpret of a lane (None when the
    dtype has no unsigned twin — such lanes stay raw)."""
    if arr.ndim != 1:
        return None
    u = _UVIEW.get(arr.dtype.itemsize)
    if u is None or arr.dtype.kind not in "iufb":
        return None
    return np.ascontiguousarray(arr).view(u)


def _narrowest(maxval: int) -> Optional[np.dtype]:
    for dt in _NARROW:
        if maxval <= np.iinfo(dt).max:
            return np.dtype(dt)
    return None


def encode_lane(arr: np.ndarray) -> Tuple[dict, List[np.ndarray], str]:
    """(meta, buffers, encoding_name) for one lane. The meta carries
    everything decode_lane needs; buffers are contiguous ndarrays the
    caller streams to the file in order."""
    raw = np.ascontiguousarray(arr)
    raw_meta = {"dtype": str(arr.dtype), "shape": list(arr.shape),
                "len": raw.nbytes}
    u = _uview(raw)
    n = 0 if u is None else len(u)
    if u is None or n < 2:
        return raw_meta, [raw], "raw"
    cands: List[Tuple[int, str, list, List[np.ndarray]]] = []

    diffs = u[1:] - u[:-1]            # wraparound delta in lane width
    # const / dconst: O(n) checks, no buffers beyond 1-2 values
    if not diffs.any():
        cands.append((raw.dtype.itemsize, "const", [], [u[:1]]))
    elif n > 2 and not (diffs[1:] != diffs[0]).any():
        cands.append((2 * raw.dtype.itemsize, "dconst", [], [u[:2]]))
    else:
        # delta: zigzag the signed wraparound deltas into the
        # narrowest dtype that fits
        signed = diffs.view(np.dtype(f"i{raw.dtype.itemsize}"))
        neg = np.where(signed < 0, np.iinfo(u.dtype).max,
                       0).astype(u.dtype)       # all-ones for negatives
        zz = (diffs << np.uint8(1)) ^ neg
        ndt = _narrowest(int(zz.max()))
        if ndt is not None and ndt.itemsize < raw.dtype.itemsize:
            zzn = zz.astype(ndt)
            cands.append((raw.dtype.itemsize + zzn.nbytes, "delta",
                          [str(ndt)], [u[:1], zzn]))
        # rle: boundaries already known from diffs
        bnd = np.nonzero(diffs)[0]
        runs = len(bnd) + 1
        rle_bytes = runs * (raw.dtype.itemsize + 4)
        if rle_bytes < raw.nbytes:
            starts = np.concatenate([[0], bnd + 1])
            lens = np.diff(np.concatenate([starts, [n]])).astype(np.uint32)
            cands.append((rle_bytes, "rle", [], [u[starts], lens]))
        # dict: sample-guarded full unique
        if len(np.unique(u[:_DICT_SAMPLE])) <= _DICT_SAMPLE_MAX:
            uniq, codes = np.unique(u, return_inverse=True)
            cdt = _narrowest(len(uniq) - 1)
            if cdt is not None and cdt.itemsize < raw.dtype.itemsize:
                size = uniq.nbytes + n * cdt.itemsize
                if size < raw.nbytes:
                    cands.append((size, "dict", [len(uniq), str(cdt)],
                                  [uniq, codes.astype(cdt)]))
    if not cands:
        return raw_meta, [raw], "raw"
    size, enc, extra, bufs = min(cands, key=lambda c: c[0])
    if size >= raw.nbytes:            # encode ONLY if strictly smaller
        return raw_meta, [raw], "raw"
    bufs = [np.ascontiguousarray(b) for b in bufs]
    meta = {"dtype": str(arr.dtype), "shape": list(arr.shape),
            "enc": enc, "x": extra,
            "parts": [b.nbytes for b in bufs]}
    return meta, bufs, enc


def decode_lane(meta: dict, fetch: Callable[[int], object]) -> np.ndarray:
    """Rebuild a lane from its meta + the file stream. ``fetch(nbytes)``
    returns the next raw byte region (bytes/memoryview; may be a
    zero-copy view of the SST mapping for raw lanes)."""
    dt = np.dtype(meta["dtype"])
    shape = tuple(meta["shape"])
    enc = meta.get("enc")
    if enc is None:
        raw = fetch(meta["len"])
        return np.frombuffer(raw, dtype=dt).reshape(shape)
    n = shape[0]
    udt = np.dtype(_UVIEW[dt.itemsize])
    parts = [np.frombuffer(fetch(nb), np.uint8) for nb in meta["parts"]]
    if enc == "const":
        u = np.broadcast_to(parts[0].view(udt), (n,))
    elif enc == "dconst":
        fs = parts[0].view(udt)
        step = (fs[1:] - fs[:1])[0]              # wraparound-exact
        u = fs[0] + step * np.arange(n, dtype=udt)
    elif enc == "delta":
        zz = parts[1].view(np.dtype(meta["x"][0])).astype(udt)
        signed = ((zz >> np.uint8(1))
                  ^ (-(zz & np.uint8(1)).astype(
                      np.dtype(f"i{dt.itemsize}"))).view(udt))
        u = np.cumsum(np.concatenate([parts[0].view(udt), signed]),
                      dtype=udt)
    elif enc == "rle":
        vals = parts[0].view(udt)
        lens = parts[1].view(np.uint32)
        u = np.repeat(vals, lens.astype(np.int64))
    elif enc == "dict":
        k, cdt = meta["x"]
        uniq = parts[0].view(udt)
        codes = parts[1].view(np.dtype(cdt))
        u = uniq[codes]
    else:
        raise ValueError(f"unknown lane encoding {enc!r}")
    out = np.ascontiguousarray(u).view(dt).reshape(shape)
    return out


# ---------------------------------------------------------------------------
# Varlen (string) dictionary coding + remap tables
#
# The grouped-aggregation pushdown (ops/grouped_scan.py) runs GROUP BY
# and string predicates over dictionary CODES.  Everything here stays at
# the byte level: uniques are computed with a padded-matrix void view
# (UTF-8 byte order == code-point order, and the explicit length column
# keeps "a" distinct from — and ordered before — "a\x00"), so chunk-
# local codes translate into a scan-global dictionary through a pure
# integer remap table without ever decoding row strings.
# ---------------------------------------------------------------------------

#: rows longer than this never dictionary-code (the padded unique
#: matrix is O(n * max_len); long payloads are unlikely to repeat)
_VARLEN_DICT_MAX_LEN = 255

#: prefix-sample guard mirroring _DICT_SAMPLE for fixed lanes
_VARLEN_DICT_SAMPLE = 2048
_VARLEN_DICT_SAMPLE_MAX = 384


_ROW_HASH_MULT = np.uint64(0x9E3779B97F4A7C15)


def _unique_rows(mat: np.ndarray):
    """``(uniq [k, W] uint8, codes int64 [n])`` of the rows of a byte
    matrix, uniques in byte order — what ``np.unique`` gives over a void
    view of the rows, without comparing n rows byte by byte in a sort:
    the rows are hashed a 64-bit word at a time, the hashes sorted as
    numbers, every row checked against the first of its hash (a
    collision falls back to the void sort), and only the k uniques are
    put in byte order."""
    n, W = mat.shape
    v = np.dtype((np.void, W))
    if n == 0 or W == 0:
        uniq, codes = np.unique(np.ascontiguousarray(mat).view(v)
                                .reshape(-1), return_inverse=True)
        return uniq.view(np.uint8).reshape(len(uniq), W), codes
    W8 = -(-W // 8)
    wide = mat
    if W8 * 8 != W or not mat.flags["C_CONTIGUOUS"]:
        wide = np.zeros((n, W8 * 8), np.uint8)
        wide[:, :W] = mat
    words = wide.view(np.uint64)
    h = words[:, 0].copy()
    for j in range(1, W8):
        h *= _ROW_HASH_MULT
        h ^= words[:, j]
    _, first, inv = np.unique(h, return_index=True, return_inverse=True)
    firsts = words[first]
    if W8 > 1 and not (firsts[inv] == words).all():
        uniq, codes = np.unique(np.ascontiguousarray(mat).view(v)
                                .reshape(-1), return_inverse=True)
        return uniq.view(np.uint8).reshape(len(uniq), W), codes
    urows = np.ascontiguousarray(mat[first])
    order = np.argsort(urows.view(v).reshape(-1), kind="stable")
    rank = np.empty(len(order), np.int64)
    rank[order] = np.arange(len(order))
    return urows[order], rank[inv]


def varlen_code_rows(ends: np.ndarray, heap,
                     null: Optional[np.ndarray] = None,
                     max_len: int = _VARLEN_DICT_MAX_LEN,
                     max_card: Optional[int] = None,
                     sample_guard: bool = True):
    """Dictionary-code one varlen lane without decoding strings.

    Returns ``(uniq_lens uint8[k], uniq_heap uint8[...], codes int32[n])``
    — uniques sorted in byte order (== string order for UTF-8), codes
    indexing into them — or None when the lane doesn't qualify (a row
    longer than `max_len`, or more than `max_card` distinct values).
    NULL rows code as the empty string, matching the batch builder's
    ``np.where(null, "", values)`` normalization, so dictionaries built
    here are interchangeable with decode-based ones."""
    n = len(ends)
    if n == 0:
        return (np.zeros(0, np.uint8), np.zeros(0, np.uint8),
                np.zeros(0, np.int32))
    hb = np.frombuffer(heap, np.uint8) if not isinstance(heap, np.ndarray) \
        else heap.view(np.uint8)
    ends64 = np.asarray(ends, np.int64)
    starts = np.concatenate([[0], ends64[:-1]])
    lens = ends64 - starts
    if null is not None:
        null = np.asarray(null, bool)
        lens = np.where(null, 0, lens)
    w = int(lens.max()) if n else 0
    if w > max_len:
        return None
    packed = int(lens.sum()) == int(ends64[-1])

    def padded(lo: int, hi: int) -> np.ndarray:
        """Rows [lo, hi) as a padded [m, w+1] matrix: row bytes then the
        length byte — the length column disambiguates trailing-NUL
        payloads and preserves shorter-is-smaller ordering."""
        mat = np.zeros((hi - lo, w + 1), np.uint8)
        ln = lens[lo:hi]
        if w and packed:
            # the heap is the rows' bytes end to end (no payload under a
            # NULL): one masked assignment in row order
            inb = np.arange(w)[None, :] < ln[:, None]
            mat[:, :w][inb] = hb[int(starts[lo]):int(ends64[hi - 1])]
        elif w:
            idx = starts[lo:hi, None] + np.arange(w)[None, :]
            inb = np.arange(w)[None, :] < ln[:, None]
            np.clip(idx, 0, max(len(hb) - 1, 0), out=idx)
            mat[:, :w] = np.where(inb, hb[idx] if len(hb) else 0, 0)
        mat[:, w] = ln.astype(np.uint8)
        return mat

    # the prefix sample cheaply skips near-unique lanes where a dict is
    # a write-time LOSS (before the whole lane is padded out);
    # scan-time dictionary formation (dict_varlen for
    # the grouped kernel) passes sample_guard=False — there the dict is
    # REQUIRED up to max_card, the full unique runs once per block and
    # memoizes, and a 4096-group GROUP BY must not be capped by a
    # 384-distinct write heuristic
    if sample_guard and max_card is not None and n > _VARLEN_DICT_SAMPLE:
        if len(_unique_rows(padded(0, _VARLEN_DICT_SAMPLE))[0]) > \
                _VARLEN_DICT_SAMPLE_MAX:
            return None
    umat, codes = _unique_rows(padded(0, n))
    if max_card is not None and len(umat) > max_card:
        return None
    ulens = umat[:, w]
    parts = [umat[i, :ulens[i]] for i in range(len(umat))]
    uniq_heap = (np.concatenate(parts) if parts
                 else np.zeros(0, np.uint8))
    return (ulens.astype(np.uint8), np.ascontiguousarray(uniq_heap),
            codes.astype(np.int32))


def decode_dict_strings(uniq_lens: np.ndarray,
                        uniq_heap) -> np.ndarray:
    """Object array of str — the uniques only (k strings, not n rows).
    Raises UnicodeDecodeError on non-UTF8 payloads; callers fall back
    exactly as they do for undecodable row heaps."""
    hb = bytes(uniq_heap) if not isinstance(uniq_heap, bytes) \
        else uniq_heap
    out = np.empty(len(uniq_lens), object)
    lo = 0
    for i, ln in enumerate(np.asarray(uniq_lens, np.int64)):
        out[i] = hb[lo:lo + ln].decode()
        lo += ln
    return out


def remap_table(local_uniq: np.ndarray,
                global_uniq: np.ndarray) -> np.ndarray:
    """int32 table translating codes over `local_uniq` into codes over
    `global_uniq` (both sorted ascending; every local value must be
    present globally — merge_dicts guarantees it)."""
    return np.searchsorted(global_uniq, local_uniq).astype(np.int32)


def merge_dicts(uniq_list):
    """Merge per-chunk sorted dictionaries into one scan-global sorted
    dictionary: ``(global_uniq, [remap_table per input])``.  Pure
    set-union over the (small) unique arrays — row data is never
    touched, which is what lets chunk-local codes stream through one
    shape-stable grouped kernel."""
    if not uniq_list:
        return np.zeros(0, object), []
    global_uniq = np.unique(np.concatenate(uniq_list))
    return global_uniq, [remap_table(u, global_uniq) for u in uniq_list]


def dict_identity(uniq: np.ndarray) -> tuple:
    """Stable content identity of a dictionary for device-cache keys:
    (size, fnv64 over the joined UTF-8 bytes).  Two scans whose merged
    scan-global dictionaries differ get different identities, so a
    batch of remapped codes cached under one dictionary can never serve
    a scan that merged another."""
    import hashlib
    h = hashlib.blake2b(digest_size=8)
    for s in uniq:
        h.update(s.encode() if isinstance(s, str) else bytes(s))
        h.update(b"\x00")
    return (len(uniq), int.from_bytes(h.digest(), "little"))


def tally(stats: Optional[dict], lane: str, pre: int, post: int,
          enc: str) -> None:
    """Accumulate per-lane encode accounting (the compaction stats'
    per-lane breakdown); no-op when the caller passed no stats dict."""
    if stats is None:
        return
    lanes = stats.setdefault("lanes", {})
    ent = lanes.setdefault(lane, {"pre_bytes": 0, "post_bytes": 0,
                                  "encodings": {}})
    ent["pre_bytes"] += pre
    ent["post_bytes"] += post
    ent["encodings"][enc] = ent["encodings"].get(enc, 0) + 1
