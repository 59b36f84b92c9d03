"""ctypes bindings for the native storage library (native/ybtpu_native.cpp).

Auto-builds with g++ on first import when the .so is missing; every entry
point has a pure-Python fallback in the storage layer, so environments
without a toolchain still work. `available()` reports which path is live.
"""
from __future__ import annotations

import ctypes
import os
import subprocess
from typing import List, Optional, Sequence, Tuple

import numpy as np

_LIB: Optional[ctypes.CDLL] = None
_TRIED = False

_NATIVE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    "native")
# host-fingerprinted: a .so built on another machine must never load
# (repo snapshots travel across hosts; see hostfp.py)
from ..hostfp import host_fingerprint as _host_fp  # noqa: E402

def _src_tag() -> str:
    """Short hash of the C++ source so an edited library rebuilds into a
    fresh .so instead of loading a stale build missing new symbols."""
    import hashlib
    try:
        with open(os.path.join(_NATIVE_DIR, "ybtpu_native.cpp"), "rb") as f:
            return hashlib.sha1(f.read()).hexdigest()[:8]
    except OSError:
        return "nosrc"


_SO = os.path.join(_NATIVE_DIR,
                   f"libybtpu_native.{_host_fp()}.{_src_tag()}.so")

_u8p = ctypes.POINTER(ctypes.c_uint8)
_u64p = ctypes.POINTER(ctypes.c_uint64)
_i64p = ctypes.POINTER(ctypes.c_int64)


last_build_error: Optional[str] = None


def _build() -> bool:
    global last_build_error
    src = os.path.join(_NATIVE_DIR, "ybtpu_native.cpp")
    if not os.path.exists(src):
        last_build_error = f"source missing: {src}"
        return False
    try:
        # -march=native is safe: the output path is host-fingerprinted,
        # so this .so can never load on a different CPU.  The compiler
        # writes to a name of this process's own and the rename publishes
        # it whole: a process that finds `_SO` never loads half of it
        tmp = f"{_SO}.{os.getpid()}.tmp"
        try:
            subprocess.run(
                ["g++", "-O3", "-march=native", "-std=c++17", "-shared",
                 "-fPIC", src, "-o", tmp],
                check=True, capture_output=True, timeout=120)
            os.replace(tmp, _SO)
        finally:
            if os.path.exists(tmp):
                os.unlink(tmp)
        return True
    except subprocess.CalledProcessError as e:
        last_build_error = (e.stderr or b"")[-2000:].decode(
            "utf-8", "replace")
        return False
    except Exception as e:  # noqa: BLE001 — import-time must not raise
        last_build_error = repr(e)
        return False


def _load() -> Optional[ctypes.CDLL]:
    global _LIB, _TRIED
    if _TRIED:
        return _LIB
    _TRIED = True
    if not os.path.exists(_SO) and not _build():
        return None
    try:
        lib = ctypes.CDLL(_SO)
    except OSError:
        return None
    lib.fnv64_batch.argtypes = [_u8p, _u64p, ctypes.c_int64, _u64p]
    lib.block_encode_bound.argtypes = [_u64p, _u64p, ctypes.c_int64]
    lib.block_encode_bound.restype = ctypes.c_int64
    lib.block_encode.argtypes = [_u8p, _u64p, _u8p, _u64p,
                                 ctypes.c_int64, _u8p]
    lib.block_encode.restype = ctypes.c_int64
    lib.block_decode_sizes.argtypes = [_u8p, ctypes.c_int64, _i64p, _i64p,
                                       _i64p]
    lib.block_decode.argtypes = [_u8p, ctypes.c_int64, _u8p, _u64p, _u8p,
                                 _u64p]
    lib.bloom_build.argtypes = [_u64p, ctypes.c_int64, _u8p,
                                ctypes.c_int64, ctypes.c_int32]
    lib.bloom_probe.argtypes = [_u64p, ctypes.c_int64, _u8p,
                                ctypes.c_int64, ctypes.c_int32, _u8p]
    lib.kway_merge.argtypes = [_u8p, _u64p, _i64p, ctypes.c_int32, _i64p,
                               _u8p]
    lib.kway_merge.restype = ctypes.c_int64
    lib.kway_merge_segs.argtypes = [ctypes.POINTER(ctypes.c_void_p),
                                    _i64p, ctypes.c_int32,
                                    ctypes.c_int64, _i64p, _u8p]
    lib.kway_merge_segs.restype = ctypes.c_int64
    lib.gather_rows.argtypes = [_u8p, ctypes.c_int64, _i64p,
                                ctypes.c_int64, _u8p]
    lib.gather_scatter_rows.argtypes = [_u8p, ctypes.c_int64, _i64p,
                                        _i64p, ctypes.c_int64, _u8p]
    _vpp = ctypes.POINTER(ctypes.c_void_p)
    lib.gather_multi.argtypes = [_vpp, _vpp, _i64p, _vpp, _vpp, _i64p,
                                 ctypes.c_int64]
    lib.copy_multi.argtypes = [_vpp, _vpp, _i64p, ctypes.c_int64]
    lib.gather_heap.argtypes = [_u8p, _i64p, _i64p, _i64p,
                                ctypes.c_int64, _u8p]
    lib.fnv64_rows_fixed.argtypes = [_u8p, ctypes.c_int64, ctypes.c_int64,
                                     _u64p]
    lib.prefilter_ranges.argtypes = [
        _vpp, _i64p, _vpp,
        ctypes.POINTER(ctypes.c_double), ctypes.POINTER(ctypes.c_double),
        _i64p, _i64p, ctypes.c_int64, ctypes.c_int64, _u8p]
    _LIB = lib
    return lib


def available() -> bool:
    return _load() is not None


def _ptr(arr: np.ndarray, typ):
    return arr.ctypes.data_as(typ)


def _concat_with_offsets(items: Sequence[bytes]) -> Tuple[np.ndarray, np.ndarray]:
    offsets = np.zeros(len(items) + 1, np.uint64)
    np.cumsum([len(x) for x in items], out=offsets[1:])
    buf = np.frombuffer(b"".join(items), np.uint8) if items else \
        np.zeros(0, np.uint8)
    return np.ascontiguousarray(buf), offsets


def fnv64_batch(items: Sequence[bytes]) -> Optional[np.ndarray]:
    lib = _load()
    if lib is None:
        return None
    buf, off = _concat_with_offsets(items)
    out = np.empty(len(items), np.uint64)
    lib.fnv64_batch(_ptr(buf, _u8p), _ptr(off, _u64p), len(items),
                    _ptr(out, _u64p))
    return out


def block_encode(entries: Sequence[Tuple[bytes, bytes]]) -> Optional[bytes]:
    lib = _load()
    if lib is None:
        return None
    kbuf, koff = _concat_with_offsets([k for k, _ in entries])
    vbuf, voff = _concat_with_offsets([v for _, v in entries])
    bound = lib.block_encode_bound(_ptr(koff, _u64p), _ptr(voff, _u64p),
                                   len(entries))
    out = np.empty(bound, np.uint8)
    n = lib.block_encode(_ptr(kbuf, _u8p), _ptr(koff, _u64p),
                         _ptr(vbuf, _u8p), _ptr(voff, _u64p),
                         len(entries), _ptr(out, _u8p))
    return out[:n].tobytes()


def block_decode(data: bytes) -> Optional[List[Tuple[bytes, bytes]]]:
    lib = _load()
    if lib is None:
        return None
    buf = np.frombuffer(data, np.uint8)
    n = ctypes.c_int64()
    kb = ctypes.c_int64()
    vb = ctypes.c_int64()
    lib.block_decode_sizes(_ptr(buf, _u8p), len(data),
                           ctypes.byref(n), ctypes.byref(kb),
                           ctypes.byref(vb))
    keys = np.empty(kb.value, np.uint8)
    koff = np.empty(n.value + 1, np.uint64)
    vals = np.empty(vb.value, np.uint8)
    voff = np.empty(n.value + 1, np.uint64)
    lib.block_decode(_ptr(buf, _u8p), len(data), _ptr(keys, _u8p),
                     _ptr(koff, _u64p), _ptr(vals, _u8p), _ptr(voff, _u64p))
    kraw = keys.tobytes()
    vraw = vals.tobytes()
    return [(kraw[int(koff[i]):int(koff[i + 1])],
             vraw[int(voff[i]):int(voff[i + 1])]) for i in range(n.value)]


def bloom_build(hashes: np.ndarray, nbits: int, k: int) -> Optional[np.ndarray]:
    lib = _load()
    if lib is None:
        return None
    hashes = np.ascontiguousarray(hashes, np.uint64)
    bits = np.zeros(nbits // 8, np.uint8)
    lib.bloom_build(_ptr(hashes, _u64p), len(hashes), _ptr(bits, _u8p),
                    nbits, k)
    return bits


def kway_merge_fixed(mat: np.ndarray, run_starts: np.ndarray
                     ) -> Optional[Tuple[np.ndarray, np.ndarray]]:
    """K-way merge over a fixed-width key matrix [N, W] (uint8 rows,
    lexicographically sorted within each run). run_starts: [R+1] row
    boundaries, runs newest-first. Returns (merged row order, exact-dup
    flags) without materializing per-key bytes objects."""
    lib = _load()
    if lib is None:
        return None
    n, w = mat.shape
    mat = np.ascontiguousarray(mat)
    off = np.arange(n + 1, dtype=np.uint64) * np.uint64(w)
    run_starts = np.ascontiguousarray(run_starts, np.int64)
    out_idx = np.empty(n, np.int64)
    out_dup = np.empty(n, np.uint8)
    cnt = lib.kway_merge(_ptr(mat.reshape(-1), _u8p), _ptr(off, _u64p),
                         _ptr(run_starts, _i64p), len(run_starts) - 1,
                         _ptr(out_idx, _i64p), _ptr(out_dup, _u8p))
    return out_idx[:cnt], out_dup[:cnt].astype(bool)


def kway_merge_segments(segs: Sequence[np.ndarray]
                        ) -> Optional[Tuple[np.ndarray, np.ndarray]]:
    """K-way merge over sorted fixed-width key segments WITHOUT
    concatenating them: each seg is a C-contiguous [Ni, W] uint8 matrix
    (typically a row-range view of a block's — possibly mmap-backed —
    key matrix). Returns (order, dup) where order indexes the virtual
    concatenation of the segments. The call releases the GIL (ctypes),
    so the pipelined compaction's merge stage overlaps host work."""
    lib = _load()
    if lib is None or not segs:
        return None
    w = segs[0].shape[1]
    n = 0
    ptrs = (ctypes.c_void_p * len(segs))()
    rows = np.empty(len(segs), np.int64)
    for i, s in enumerate(segs):
        if s.shape[1] != w or not s.flags["C_CONTIGUOUS"]:
            return None
        ptrs[i] = s.ctypes.data
        rows[i] = s.shape[0]
        n += s.shape[0]
    out_idx = np.empty(n, np.int64)
    out_dup = np.empty(n, np.uint8)
    cnt = lib.kway_merge_segs(ptrs, _ptr(rows, _i64p), len(segs),
                              w, _ptr(out_idx, _i64p), _ptr(out_dup, _u8p))
    return out_idx[:cnt], out_dup[:cnt].astype(bool)


def _row_bytes(arr: np.ndarray) -> int:
    """Per-row byte count treating axis-0 as rows (itemsize for 1-D,
    itemsize * row width for 2-D)."""
    rb = arr.dtype.itemsize
    for d in arr.shape[1:]:
        rb *= d
    return rb


def gather_rows(src: np.ndarray, idx: np.ndarray,
                dst: np.ndarray) -> bool:
    """dst[i] = src[idx[i]] row-wise via the native library (GIL-free
    memcpy loop). Returns False when unavailable/ineligible — caller
    falls back to numpy fancy indexing. src/dst must be C-contiguous
    with identical row widths."""
    lib = _load()
    if lib is None or not src.flags["C_CONTIGUOUS"] \
            or not dst.flags["C_CONTIGUOUS"]:
        return False
    rb = _row_bytes(src)
    if rb != _row_bytes(dst):
        return False
    idx = np.ascontiguousarray(idx, np.int64)
    lib.gather_rows(
        ctypes.cast(src.ctypes.data, _u8p),
        rb, _ptr(idx, _i64p), len(idx),
        ctypes.cast(dst.ctypes.data, _u8p))
    return True


def gather_scatter_rows(src: np.ndarray, src_idx: np.ndarray,
                        dst: np.ndarray, dst_idx: np.ndarray) -> bool:
    """dst[dst_idx[i]] = src[src_idx[i]] row-wise via the native library
    (GIL-free). Returns False when unavailable — caller falls back to
    numpy."""
    lib = _load()
    if lib is None or not src.flags["C_CONTIGUOUS"] \
            or not dst.flags["C_CONTIGUOUS"]:
        return False
    rb = _row_bytes(src)
    if rb != _row_bytes(dst):
        return False
    src_idx = np.ascontiguousarray(src_idx, np.int64)
    dst_idx = np.ascontiguousarray(dst_idx, np.int64)
    lib.gather_scatter_rows(
        ctypes.cast(src.ctypes.data, _u8p), rb,
        _ptr(src_idx, _i64p), _ptr(dst_idx, _i64p), len(src_idx),
        ctypes.cast(dst.ctypes.data, _u8p))
    return True


def gather_multi(jobs: Sequence[tuple]) -> bool:
    """THE fused multi-column gather/scatter: one GIL-released native
    call executes every (src, dst, src_idx, dst_idx) job — all value
    columns, null masks, and the ht/write_id/tombstone/key lanes of a
    chunk move together instead of one ctypes round-trip per column.

    Each job is ``(src, dst, src_idx, dst_idx)``:
      - ``src_idx is None``  -> identity source rows 0..n-1
      - ``dst_idx is None``  -> dense output rows 0..n-1
    Index arrays MUST already be int64 and C-contiguous (callers build
    them once per chunk and share them across jobs — re-coercing per job
    would reintroduce the per-column python cost this exists to remove).

    Returns False (caller falls back to numpy fancy indexing) when the
    library is unavailable or ANY job is ineligible: non-contiguous
    src/dst, mismatched row widths, or non-int64 indexes."""
    lib = _load()
    if lib is None or not jobs:
        return False
    n_jobs = len(jobs)
    src_p = (ctypes.c_void_p * n_jobs)()
    dst_p = (ctypes.c_void_p * n_jobs)()
    sidx_p = (ctypes.c_void_p * n_jobs)()
    didx_p = (ctypes.c_void_p * n_jobs)()
    rb = np.empty(n_jobs, np.int64)
    cnt = np.empty(n_jobs, np.int64)
    for j, (src, dst, src_idx, dst_idx) in enumerate(jobs):
        if not src.flags["C_CONTIGUOUS"] or not dst.flags["C_CONTIGUOUS"]:
            return False
        r = _row_bytes(src)
        if r != _row_bytes(dst):
            return False
        n = None
        for idx in (src_idx, dst_idx):
            if idx is None:
                continue
            if idx.dtype != np.int64 or not idx.flags["C_CONTIGUOUS"]:
                return False
            if n is None:
                n = len(idx)
            elif len(idx) != n:
                return False
        if n is None:       # pure copy: row counts must agree
            n = len(src)
            if len(dst) < n:
                return False
        elif dst_idx is None and len(dst) < n:
            # dense gather into an undersized dst would write past the
            # buffer — refuse (index VALUES remain the caller's
            # contract, like the raw pointer math of the C entry)
            return False
        elif src_idx is None and len(src) < n:
            return False    # scatter reading past a short source
        src_p[j] = src.ctypes.data
        dst_p[j] = dst.ctypes.data
        sidx_p[j] = src_idx.ctypes.data if src_idx is not None else None
        didx_p[j] = dst_idx.ctypes.data if dst_idx is not None else None
        rb[j] = r
        cnt[j] = n
    lib.gather_multi(src_p, dst_p, _ptr(rb, _i64p), sidx_p, didx_p,
                     _ptr(cnt, _i64p), n_jobs)
    return True


def gather_multi_fallback(jobs: Sequence[tuple]) -> None:
    """Numpy twin of gather_multi (also the parity oracle in tests)."""
    for src, dst, src_idx, dst_idx in jobs:
        if src_idx is None and dst_idx is None:
            dst[:len(src)] = src
        elif dst_idx is None:
            dst[:len(src_idx)] = src[src_idx]
        elif src_idx is None:
            dst[dst_idx] = src[:len(dst_idx)]
        else:
            dst[dst_idx] = src[src_idx]


def gather_columns(jobs: Sequence[tuple]) -> None:
    """gather_multi with automatic numpy fallback — the one entry point
    hot paths call."""
    if not gather_multi(jobs):
        gather_multi_fallback(jobs)


def copy_multi(jobs: Sequence[Tuple[np.ndarray, np.ndarray]]) -> bool:
    """One GIL-released call copying every (src, dst) pair byte-wise —
    the batch-formation concat+pad (blocks x columns) fused into a
    single native call. Pairs must be C-contiguous with equal nbytes;
    returns False for the numpy fallback."""
    lib = _load()
    if lib is None or not jobs:
        return False
    n_jobs = len(jobs)
    src_p = (ctypes.c_void_p * n_jobs)()
    dst_p = (ctypes.c_void_p * n_jobs)()
    nb = np.empty(n_jobs, np.int64)
    for j, (src, dst) in enumerate(jobs):
        if not src.flags["C_CONTIGUOUS"] or not dst.flags["C_CONTIGUOUS"] \
                or src.nbytes != dst.nbytes:
            return False
        src_p[j] = src.ctypes.data
        dst_p[j] = dst.ctypes.data
        nb[j] = src.nbytes
    lib.copy_multi(src_p, dst_p, _ptr(nb, _i64p), n_jobs)
    return True


def gather_heap(heap: np.ndarray, src_start: np.ndarray,
                dst_start: np.ndarray, lens: np.ndarray,
                out: np.ndarray) -> bool:
    """Varlen heap gather: out[dst_start[i]:+lens[i]] =
    heap[src_start[i]:+lens[i]] per row, GIL-free. False -> caller uses
    the numpy repeat-offsets fallback."""
    lib = _load()
    if lib is None:
        return False
    if heap.dtype != np.uint8 or not heap.flags["C_CONTIGUOUS"] \
            or not out.flags["C_CONTIGUOUS"]:
        return False
    n = len(lens)
    if len(src_start) != n or len(dst_start) != n:
        return False
    for a in (src_start, dst_start, lens):
        if a.dtype != np.int64 or not a.flags["C_CONTIGUOUS"]:
            return False
    lib.gather_heap(_ptr(heap, _u8p), _ptr(src_start, _i64p),
                    _ptr(dst_start, _i64p), _ptr(lens, _i64p), n,
                    _ptr(out, _u8p))
    return True


def fnv64_rows_fixed(mat: np.ndarray) -> Optional[np.ndarray]:
    """Row-wise FNV-1a over an [N, W] uint8 matrix in one native pass
    (None -> caller uses the numpy per-column loop)."""
    lib = _load()
    if lib is None or mat.dtype != np.uint8 or mat.ndim != 2 \
            or not mat.flags["C_CONTIGUOUS"]:
        return None
    out = np.empty(mat.shape[0], np.uint64)
    lib.fnv64_rows_fixed(_ptr(mat.reshape(-1), _u8p), mat.shape[0],
                         mat.shape[1], _ptr(out, _u64p))
    return out


#: dtype -> prefilter_ranges code (the C switch); anything else falls
#: back to the numpy oracle
_PREFILTER_DTYPES = {
    np.dtype(np.int32): 1, np.dtype(np.int64): 2,
    np.dtype(np.float32): 3, np.dtype(np.float64): 4,
    np.dtype(np.uint32): 5,
}

_I64_MIN, _I64_MAX = -(1 << 63), (1 << 63) - 1


def prefilter_ranges(preds: Sequence[tuple], n: int
                     ) -> Optional[np.ndarray]:
    """Near-data predicate pre-filter: one GIL-released native call
    evaluates EVERY (values, nulls, lo, hi) inclusive range predicate
    over the encoded lanes and ANDs the results into a keep mask
    (uint8[n]; NULL rows fail their predicate).  Returns None — caller
    uses :func:`prefilter_ranges_fallback` — when the library is
    unavailable or any lane is ineligible: unsupported dtype,
    non-contiguous / misaligned buffer (lanes can be raw views over the
    SST mmap, where typed access needs natural alignment), length
    mismatch, or integer bounds outside int64."""
    lib = _load()
    if lib is None or not preds:
        return None
    np_ = len(preds)
    col_p = (ctypes.c_void_p * np_)()
    null_p = (ctypes.c_void_p * np_)()
    dt = np.empty(np_, np.int64)
    lo_f = np.zeros(np_, np.float64)
    hi_f = np.zeros(np_, np.float64)
    lo_i = np.zeros(np_, np.int64)
    hi_i = np.zeros(np_, np.int64)
    for j, (vals, nulls, lo, hi) in enumerate(preds):
        code = _PREFILTER_DTYPES.get(vals.dtype)
        if code is None or vals.ndim != 1 or len(vals) != n \
                or not vals.flags["C_CONTIGUOUS"] \
                or vals.ctypes.data % vals.dtype.itemsize:
            return None
        if nulls is not None:
            if nulls.dtype != np.bool_ or len(nulls) != n \
                    or not nulls.flags["C_CONTIGUOUS"]:
                return None
            null_p[j] = nulls.ctypes.data
        if code in (1, 2, 5):
            if not (_I64_MIN <= lo <= _I64_MAX
                    and _I64_MIN <= hi <= _I64_MAX):
                return None
            lo_i[j], hi_i[j] = int(lo), int(hi)
        else:
            lo_f[j], hi_f[j] = float(lo), float(hi)
        col_p[j] = vals.ctypes.data
        dt[j] = code
    keep = np.empty(n, np.uint8)
    lib.prefilter_ranges(
        col_p, _ptr(dt, _i64p), null_p,
        lo_f.ctypes.data_as(ctypes.POINTER(ctypes.c_double)),
        hi_f.ctypes.data_as(ctypes.POINTER(ctypes.c_double)),
        _ptr(lo_i, _i64p), _ptr(hi_i, _i64p), np_, n, _ptr(keep, _u8p))
    return keep


def prefilter_ranges_fallback(preds: Sequence[tuple],
                              n: int) -> np.ndarray:
    """Numpy twin of prefilter_ranges (also the parity oracle in
    tests): identical keep-mask semantics, pure numpy."""
    keep = np.ones(n, bool)
    for vals, nulls, lo, hi in preds:
        if vals.dtype.kind == "f":
            m = (vals >= np.float64(lo)) & (vals <= np.float64(hi))
        else:
            m = (vals >= lo) & (vals <= hi)
        if nulls is not None:
            m = m & ~nulls
        keep &= m
    return keep.astype(np.uint8)


def prefilter_mask(preds: Sequence[tuple], n: int) -> np.ndarray:
    """prefilter_ranges with automatic numpy fallback — the one entry
    point the bypass reader calls (the gather_columns idiom)."""
    got = prefilter_ranges(preds, n)
    if got is None:
        got = prefilter_ranges_fallback(preds, n)
    return got


def kway_merge(runs: Sequence[Sequence[bytes]]
               ) -> Optional[Tuple[np.ndarray, np.ndarray]]:
    """runs: newest-first lists of sorted keys. Returns (global row order,
    dup flags) across the concatenation of runs."""
    lib = _load()
    if lib is None:
        return None
    flat: List[bytes] = []
    starts = [0]
    for r in runs:
        flat.extend(r)
        starts.append(len(flat))
    buf, off = _concat_with_offsets(flat)
    run_starts = np.asarray(starts, np.int64)
    out_idx = np.empty(len(flat), np.int64)
    out_dup = np.empty(len(flat), np.uint8)
    n = lib.kway_merge(_ptr(buf, _u8p), _ptr(off, _u64p),
                       _ptr(run_starts, _i64p), len(runs),
                       _ptr(out_idx, _i64p), _ptr(out_dup, _u8p))
    return out_idx[:n], out_dup[:n].astype(bool)
