"""Host→device batch formation and the device-resident block cache.

The TPU-native replacement for the reference's block cache (reference:
src/yb/rocksdb/util/cache.cc + table block cache): hot tablet blocks
live in HBM as decoded columnar arrays, so steady-state scans never
touch the host. Batches are padded to power-of-two row buckets so the
jitted scan kernels compile once per bucket instead of once per block
size (recompilation churn — SURVEY.md hard part #7).
"""
from __future__ import annotations

import threading
from collections import OrderedDict
from dataclasses import dataclass, field, replace
from typing import Any, Dict, List, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..storage.columnar import ColumnarBlock
from ..utils.trace import TRACES

_BUCKETS = [1 << b for b in range(12, 24)]  # 4096 .. 8M rows


def bucket_rows(n: int) -> int:
    for b in _BUCKETS:
        if n <= b:
            return b
    b = _BUCKETS[-1]
    while b < n:          # beyond the table: keep doubling
        b <<= 1
    return b


class Pair(NamedTuple):
    """A 64-bit lane as the two 32-bit lanes the chip computes with.
    The TPU has no 64-bit lanes: XLA splits every 64-bit parameter into
    two 32-bit arrays at a program's entry, over the whole lane, before
    any fusion or loop (a custom call that reads the lane from HBM and
    writes each half back).  A cached batch holds its 64-bit lanes split
    once, at build time, and the kernel joins a run of rows where it
    reads them (`join`).  Two forms, told apart by the words' dtype:
    uint32 words of a uint64 (`u64_pair`: high, low; exact), and the
    float32 high part and remainder of a float64 (`f64_pair`: what the
    chip's own split makes of it, so the chip computes on the same
    value).  A pytree: it rides a jitted call, a `shard_map` and the
    kernel's row tiles as its two leaves."""
    hi: Any
    lo: Any

    @property
    def dtype(self) -> np.dtype:
        """The dtype of the value the pair holds."""
        return np.dtype(np.uint64 if self.hi.dtype == np.uint32
                        else np.float64)

    def devices(self) -> set:
        return self.hi.devices()


#: a 32-bit word that is all ones: both words of `HT_NONE`
WORD_MAX = 0xFFFFFFFF


def u64_pair(a: np.ndarray) -> Pair:
    """A host uint64 lane as its (high, low) uint32 words."""
    return Pair((a >> np.uint64(32)).astype(np.uint32),
                a.astype(np.uint32))


def f64_pair(a: np.ndarray) -> Pair:
    """A host float64 lane as (hi, lo) float32: `hi` the value rounded
    to float32, `lo` the remainder rounded to float32 — the pair XLA's
    own split of a float64 on the TPU gives.  Where `hi` is not finite
    the pair is (hi, 0)."""
    with np.errstate(over="ignore", invalid="ignore"):
        hi = a.astype(np.float32)
        lo = (a - hi).astype(np.float32)
    lo[~np.isfinite(hi)] = 0
    return Pair(hi, lo)


def join(x):
    """Inside a program: the value a lane holds — a `Pair` joined into
    the 64-bit value (uint64 words exactly; a float64 pair as the sum
    of its parts, which on the TPU is the pair itself), anything else
    as it is.  The kernels join a run of rows where they read it, never
    a whole lane at a program's entry."""
    if not isinstance(x, Pair):
        return x
    if x.hi.dtype == jnp.uint32:
        return (x.hi.astype(jnp.uint64) << 32) | x.lo.astype(jnp.uint64)
    return x.hi.astype(jnp.float64) + x.lo.astype(jnp.float64)


def words(x) -> Pair:
    """Inside a program: a uint64 value (a lane or a scalar such as
    `read_ht`) as its (high, low) uint32 words; a `Pair` as it is."""
    if isinstance(x, Pair):
        return x
    return Pair((x >> 32).astype(jnp.uint32), x.astype(jnp.uint32))


def lane_sig(v) -> str:
    """A lane's dtype as a program's signature names it: a pair is not
    the 64-bit lane it holds."""
    if isinstance(v, Pair):
        return f"{v.hi.dtype}x2"
    return str(v.dtype)


def launch_leaves(tree) -> Tuple[int, int]:
    """Of a jitted call's arguments `tree`, in one pass over its leaves:
    (`host_args`, the host values the call places itself — every leaf
    that is no `jax.Array` —, `wide_lanes`, the 64-bit device arrays
    (not scalars) it holds: the lanes the chip would split over their
    whole length at a launch's entry; a host vector of runtime scalars
    is no lane)."""
    host = wide = 0
    for x in jax.tree_util.tree_leaves(tree):
        on_device = isinstance(x, jax.Array)
        host += not on_device
        wide += (on_device and x.ndim >= 1
                 and np.dtype(x.dtype).itemsize == 8)
    return host, wide


@dataclass
class DeviceBatch:
    """Padded columnar batch on device.

    cols / nulls: col_id -> [N] arrays (nulls True where SQL NULL); a
    float64 column on a backend without 64-bit lanes is a `Pair`.
    valid: [N] bool — False on padding rows and MVCC-invisible rows.
    ht / tombstone: the MVCC lanes (absent when built without them); `ht`
    is a `Pair` of uint32 words on every backend.
    next_ht: present exactly when the batch may hold several versions of
    a key — per row the `ht` of the next newer version of its key among
    the batch's rows (`link_versions`), which makes the kernel's
    newest-visible-version mask one elementwise pass; a `Pair` too.  The
    doc-key hash and the write id stay on the host: only the link reads
    them.
    """

    n_rows: int                      # true (unpadded) row count
    cols: Dict[int, Any]
    nulls: Dict[int, jnp.ndarray]
    valid: jnp.ndarray
    ht: Optional[Pair] = None
    next_ht: Optional[Pair] = None
    tombstone: Optional[jnp.ndarray] = None
    # string columns ride as int32 dictionary CODES in `cols`; the
    # sorted dictionaries stay host-side here — predicates translate to
    # code space (order-preserving) or LUT gathers before compilation
    # (SURVEY §7 hard-part 3: varlen data in fixed-shape kernels)
    dicts: Dict[int, np.ndarray] = field(default_factory=dict)
    # host-side per-column value bounds (min, max) in f64, computed once
    # at batch build — ops/expr.expr_bound turns these into STATIC
    # fixed-point SUM scales so the scan kernel needs no device
    # max-reduction or float fallback lane (absent/non-finite entries
    # route that SUM to the dynamic-scale path)
    col_bounds: Dict[int, Tuple[float, float]] = field(default_factory=dict)

    @property
    def padded_rows(self) -> int:
        return int(self.valid.shape[0])

    def narrowed(self, columns) -> "DeviceBatch":
        return narrowed(self, columns)


def narrowed(batch, columns):
    """`batch` (a `DeviceBatch` or a sharded one) as a batch of `columns`
    alone, all of them among its own: the same arrays on the chips, so a
    scan of fewer columns runs the program it would run on a batch built
    for them."""
    if set(columns) == set(batch.cols):
        return batch
    keep = lambda d: {c: d[c] for c in sorted(columns) if c in d}
    return replace(batch, cols=keep(batch.cols), nulls=keep(batch.nulls),
                   col_bounds=keep(batch.col_bounds),
                   dicts=keep(batch.dicts))


def _float64_device_dtype() -> np.dtype:
    """Device dtype for genuinely fractional f64 columns. CPU backends
    keep f64 (full double-precision per-row eval). TPU ships f32 —
    the MXU/VPU dtype — and relies on the scan kernel's exact int64
    fixed-point accumulation (ops/scan.py) so SUMs don't drift; the
    residual is the per-row f32 representation (<= 2^-24 relative).
    The `device_float_dtype` flag (auto|float32|float64) overrides, so
    tests can exercise the TPU-representative f32 path on CPU."""
    from ..utils import flags
    mode = flags.get("device_float_dtype")
    if mode == "float64":
        return np.dtype(np.float64)
    if mode == "float32":
        return np.dtype(np.float32)
    if mode != "auto":
        raise ValueError(
            f"device_float_dtype must be auto|float32|float64, got "
            f"{mode!r}")
    import jax
    return np.dtype(np.float64 if jax.default_backend() == "cpu"
                    else np.float32)


def _backend_float64_is_pair() -> bool:
    """True where the backend has no 64-bit lanes and computes a float64
    as two float32 (the TPU).  Tests steer the TPU arm by patching this,
    as `jax.default_backend()` says `cpu` in a compile for a described
    chip."""
    return jax.default_backend() == "tpu"


def f64_pairs() -> bool:
    """Whether a float64 value lane that `f64_conversion` keeps float64
    ships as a `Pair` (`f64_pair`): where the backend's float64 is itself
    a pair, the chip then computes on the same value without splitting
    the whole lane at each launch.  A CPU keeps whole float64 lanes.
    Asked by both batch builders; the lane builders of the join and
    grouped routes keep whole lanes."""
    return _backend_float64_is_pair()


def _integral_int32(arr: np.ndarray) -> bool:
    """True when every value is an exact integer within int32 range —
    such f64 columns (counts, quantities, dict-coded values) ship as
    int32 and aggregate exactly end-to-end. A cheap prefix sample
    rejects typical fractional columns without a full pass."""
    if arr.size == 0:
        return True
    head = arr[:1024]
    if not (np.all(np.isfinite(head)) and np.all(head == np.rint(head))):
        return False
    if not (np.all(np.isfinite(arr)) and np.all(arr == np.rint(arr))):
        return False
    lo, hi = arr.min(), arr.max()
    return -2**31 <= lo and hi < 2**31


def f64_conversion(parts) -> Optional[np.dtype]:
    """THE conversion policy for f64 columns, shared by the single-device
    and sharded batch builders so the same table always ships the same
    dtype: int32 when integer-valued in every given array (exact
    end-to-end aggregation), else the backend/flag float dtype. Returns
    the dtype to convert to, or None to keep f64."""
    if not parts or any(p.dtype != np.float64 for p in parts):
        return None
    if all(_integral_int32(p) for p in parts):
        return np.dtype(np.int32)
    dd = _float64_device_dtype()
    return None if dd == np.float64 else dd


#: `next_ht` of a row version that has no newer version in its batch
HT_NONE = np.uint64(0xFFFFFFFFFFFFFFFF)


def link_versions(key_hash: np.ndarray, ht: np.ndarray,
                  write_id: np.ndarray) -> Tuple[np.ndarray, int]:
    """(next_ht, superseded): per row the `ht` of the next newer version
    of its key — the row that follows it in (key_hash, ht, write_id)
    order — or HT_NONE where it is the newest; and how many rows have a
    newer version.  Host-side and independent of any read time, so it is
    computed once per batch: a version is then the newest visible one at
    `read_ht` exactly when `ht <= read_ht < next_ht`.  Of two versions
    with one `ht` the larger write id is the later; of exact duplicates
    (a replayed write) the later row.  Only the rows that share their
    hash with another row are ordered; the rest is one argsort."""
    n = len(key_hash)
    next_ht = np.full(n, HT_NONE, np.uint64)
    order = np.argsort(key_hash)
    s_kh = key_hash[order]
    same = s_kh[1:] == s_kh[:-1]
    if not same.any():
        return next_ht, 0
    shared = np.zeros(n, bool)
    shared[order[1:][same]] = True
    shared[order[:-1][same]] = True
    rows = np.flatnonzero(shared)        # block order: ties keep it
    rows = rows[np.lexsort((write_id[rows], ht[rows], key_hash[rows]))]
    has_next = key_hash[rows[1:]] == key_hash[rows[:-1]]
    next_ht[rows[:-1][has_next]] = ht[rows[1:][has_next]]
    return next_ht, int(has_next.sum())


class VersionLink:
    """`link_versions` of one store's rows, made by the first batch build
    that needs it and kept with the store's facts for every later build
    of the same contents, whatever its column set: the link reads only
    the key hash, the write time and the write id.  Builds on several
    threads wait for the one that makes it."""

    def __init__(self):
        self._lock = threading.Lock()
        self._value: Optional[Tuple[np.ndarray, int]] = None

    def of(self, blocks, ht: np.ndarray) -> Tuple[np.ndarray, int, bool]:
        """(next_ht, superseded, made): the link of `blocks`' rows, whose
        write times are `ht`, and whether this call made it."""
        with self._lock:
            made = self._value is None
            if made:
                self._value = link_versions(
                    np.concatenate([b.key_hash for b in blocks]), ht,
                    np.concatenate([b.write_id for b in blocks]))
            return (*self._value, made)


def build_batch(blocks: Sequence[ColumnarBlock],
                columns: Sequence[int],
                with_mvcc: bool = True,
                pad_to: Optional[int] = None,
                bounds_blocks: Optional[Sequence[ColumnarBlock]] = None,
                dict_plan=None,
                multi_version: bool = False,
                link: Optional[VersionLink] = None) -> DeviceBatch:
    """Concatenate columnar blocks and ship the requested columns to
    device, padded to a row bucket.

    ``multi_version``: the blocks may hold several versions of a key
    (overlapping SSTs, a memtable overlay) even where each block is
    unique-keyed by itself.  Such a batch — and any batch with a block
    that is not unique-keyed — gets the ``next_ht`` lane
    (:func:`link_versions`, span ``batch.version_link``); a batch
    proved single-version ships without it.  ``link``: where the
    blocks are a store's whole contents, the link kept with them
    (:class:`VersionLink`), made here if nothing made it yet.

    Batch formation is a single fused pass: every column (and MVCC
    lane) fills its padded host buffer directly — per-block segments of
    matching dtype accumulate into ONE GIL-released native copy
    (storage/native_lib.copy_multi) instead of a np.concatenate followed
    by a second pad copy per column.  The streaming scan pipeline runs
    this per chunk on a worker thread, overlapped with the previous
    chunk's kernel dispatch.

    ``bounds_blocks``: when given, the f64 conversion policy and the
    per-column bounds (the inputs to the kernel's static SUM scales)
    come from THESE blocks instead of `blocks`.  The bypass reader's
    near-data pre-filter compacts provably-unmatched rows out of a
    chunk but passes the unfiltered chunk here, so the device dtype and
    quantization scales — and therefore every aggregate bit — stay
    identical to the unfiltered scan.

    ``dict_plan``: an ops/grouped_scan.DictPlan covering the scan's
    string columns — their code arrays fill from the plan's per-block
    SCAN-GLOBAL remapped codes (no row-string decode, dictionaries
    shared across every chunk of a streamed scan) and ``batch.dicts``
    carries the plan's global dictionaries.  Without a plan, string
    columns fall back to the per-batch dictionary build below (itself
    served by the per-block dictionary merge when every block
    dictionary-encodes, decoding rows only as a last resort)."""
    n = sum(b.n for b in blocks)
    padded = pad_to or bucket_rows(max(n, 1))
    # `batch.build`: the host's gather and pad, up to the fused native
    # copy; `batch.h2d`: the transfers to the device
    with TRACES.span("batch.build", child_only=True):
        cols: Dict[int, jnp.ndarray] = {}
        nulls: Dict[int, jnp.ndarray] = {}
        dicts: Dict[int, np.ndarray] = {}
        col_bounds: Dict[int, Tuple[float, float]] = {}
        copy_jobs: List[Tuple[np.ndarray, np.ndarray]] = []
        host_cols: Dict[int, Tuple[np.ndarray, np.ndarray]] = {}
        pair_cols: List[int] = []      # float64 lanes shipped as pairs

        def fill(parts: List[np.ndarray],
                 out_dtype: Optional[np.dtype] = None) -> np.ndarray:
            """Padded buffer filled from per-block parts; same-dtype
            contiguous segments defer into the one fused native copy."""
            dt = out_dtype or parts[0].dtype
            out = np.zeros((padded,) + parts[0].shape[1:], dt)
            pos = 0
            for p in parts:
                m = len(p)
                if p.dtype == dt and p.flags["C_CONTIGUOUS"]:
                    copy_jobs.append((p, out[pos:pos + m]))
                else:
                    out[pos:pos + m] = p    # converting assignment
                pos += m
            return out

        for cid in columns:
            if dict_plan is not None and cid in dict_plan.dicts:
                # scan-global dictionary plan: per-block codes are already
                # remapped into the shared dictionary — a pure int32 fill,
                # no row-string decode, one dictionary for every chunk
                code_parts = [dict_plan.block_codes(cid, b) for b in blocks]
                nparts = [np.asarray(b.varlen[cid][2], bool)
                          for b in blocks]
                dicts[cid] = dict_plan.dicts[cid]
                arr = fill(code_parts) if code_parts else \
                    np.zeros(padded, np.int32)
                host_cols[cid] = (arr, fill(nparts) if nparts
                                  else np.zeros(padded, bool))
                continue
            if all(cid in b.varlen for b in blocks):
                # string column: batch-global dictionary encoding — codes
                # are order-preserving (sorted dict), so comparisons map to
                # code space and LIKE maps to a host-built LUT.  The merge
                # of per-block dictionaries (stored v2 dict lanes or the
                # one-time byte-level unique) serves this without decoding
                # rows; blocks that can't dictionary-encode fall back to
                # the decode loop below
                got = _dict_merge_column(blocks, cid)
                if got is not None:
                    uniq, code_parts = got
                    null = np.concatenate(
                        [np.asarray(b.varlen[cid][2], bool)
                         for b in blocks])
                    dicts[cid] = uniq
                    arr = fill(code_parts)
                    host_cols[cid] = (arr, _pad(null, padded))
                    continue
                vparts, nparts = [], []
                for b in blocks:
                    try:
                        vparts.append(varlen_strings(b, cid))
                    except UnicodeDecodeError:
                        # BINARY payloads (or corrupt strings) don't
                        # dictionary-encode; same contract as any other
                        # non-columnar column — the caller falls back
                        raise KeyError(
                            f"column {cid} not dictionary-encodable")
                    nparts.append(np.asarray(b.varlen[cid][2], bool))
                values = np.concatenate(vparts)
                null = np.concatenate(nparts)
                values = np.where(null, "", values)   # stable unique input
                uniq, codes = np.unique(values, return_inverse=True)
                dicts[cid] = uniq
                cols[cid] = jnp.asarray(_pad(codes.astype(np.int32), padded))
                nulls[cid] = jnp.asarray(_pad(null, padded))
                continue
            def lane_parts(src_blocks, with_nulls=True):
                ps, nps = [], []
                for b in src_blocks:
                    if cid in b.fixed:
                        v, m = b.fixed[cid]
                        ps.append(v)
                        if with_nulls:
                            nps.append(m)
                    elif cid in b.pk:
                        ps.append(b.pk[cid])
                        if with_nulls:
                            nps.append(np.zeros(b.n, bool))
                    else:
                        raise KeyError(
                            f"column {cid} not available in columnar form")
                return ps, nps

            parts, nparts = lane_parts(blocks)
            stat_parts = (parts if bounds_blocks is None
                          else lane_parts(bounds_blocks,
                                          with_nulls=False)[0])
            conv = (f64_conversion(stat_parts)
                    if stat_parts and stat_parts[0].dtype == np.float64
                    else None)
            arr = fill(parts, conv)
            if arr.dtype == np.float64 and f64_pairs():
                pair_cols.append(cid)
            stat_n = sum(len(p) for p in stat_parts)
            if stat_n and arr.dtype.kind in "fiu":
                # bounds from the parts (the padded tail is zeros and must
                # not contaminate the stats the static SUM scales use)
                col_bounds[cid] = (
                    float(min(p.min() for p in stat_parts if p.size)),
                    float(max(p.max() for p in stat_parts if p.size)))
            host_cols[cid] = (arr, fill(nparts))
        valid = np.zeros(padded, bool)
        valid[:n] = True
        ht_host = tomb_host = next_host = None
        if with_mvcc:
            ht_host = fill([b.ht for b in blocks])
            tomb_host = fill([b.tombstone for b in blocks])
        from ..storage import native_lib
        if copy_jobs and not native_lib.copy_multi(copy_jobs):
            for s, d in copy_jobs:
                d[:] = s
        if with_mvcc and (multi_version
                          or not all(b.unique_keys for b in blocks)):
            with TRACES.span("batch.version_link",
                             child_only=True) as sp:
                next_host = np.full(padded, HT_NONE, np.uint64)
                next_host[:n], superseded, made = (
                    link or VersionLink()).of(blocks, ht_host[:n])
                sp.set_tag("rows", n)
                sp.set_tag("superseded", superseded)
                sp.set_tag("kept", not made)
        # every 64-bit lane as the two 32-bit lanes the chip computes
        # with, split once here and not at each launch (`Pair`)
        for cid in pair_cols:
            host_cols[cid] = (f64_pair(host_cols[cid][0]),
                              host_cols[cid][1])
        if with_mvcc:
            ht_host = u64_pair(ht_host)
        if next_host is not None:
            next_host = u64_pair(next_host)
    with TRACES.span("batch.h2d", child_only=True) as sp:
        for cid, (arr, null) in host_cols.items():
            cols[cid] = _to_device(arr)
            nulls[cid] = jnp.asarray(null)
        batch = DeviceBatch(
            n_rows=n, cols=cols, nulls=nulls, valid=jnp.asarray(valid),
            dicts=dicts, col_bounds=col_bounds)
        if with_mvcc:
            batch.ht = _to_device(ht_host)
            batch.tombstone = jnp.asarray(tomb_host)
        if next_host is not None:
            batch.next_ht = _to_device(next_host)
        if sp.sampled:
            # transfers are asynchronous: wait, so that the span times
            # them and not their enqueue
            jax.block_until_ready(
                (cols, nulls, batch.valid, batch.ht, batch.next_ht,
                 batch.tombstone))
            sp.set_tag("bytes", batch_bytes(batch))
    return batch


def _to_device(x):
    """A host lane, or each word of a host `Pair`, on the device."""
    return jax.tree_util.tree_map(jnp.asarray, x)


def _dict_merge_column(blocks: Sequence[ColumnarBlock], cid: int):
    """(global uniq, per-block global-code arrays) through the
    per-block dictionary merge — row strings are never decoded, only
    each block's (few) uniques. None when any block can't
    dictionary-encode; the caller then decodes rows the old way."""
    per = []
    for b in blocks:
        got = b.dict_varlen(cid)
        if got is None:
            return None
        per.append(got)
    from ..storage.lane_codec import merge_dicts
    uniq, remaps = merge_dicts([u for u, _ in per])
    parts = [np.ascontiguousarray(remap[codes])
             for (_, codes), remap in zip(per, remaps)]
    return uniq, parts


def varlen_strings(b: ColumnarBlock, cid: int) -> np.ndarray:
    """Decode one varlen column of a block into an object array of str
    (raises on non-UTF8 payloads — the caller falls back to the CPU row
    path for such blocks)."""
    ends, heap, _nulls = b.varlen[cid]
    if heap is None:
        # a projected block keeps a dictionary-coded lane as its stored
        # dictionary and codes, and rebuilds no row heap
        uniq, codes = b.dict_varlen(cid)
        return uniq[codes]
    heap = bytes(heap)         # a projected block's heap is a view
    out = np.empty(b.n, object)
    lo = 0
    for i in range(b.n):
        hi = int(ends[i])
        out[i] = heap[lo:hi].decode()
        lo = hi
    return out


def _pad(arr: np.ndarray, n: int) -> np.ndarray:
    if len(arr) == n:
        return arr
    out = np.zeros((n,) + arr.shape[1:], arr.dtype)
    out[:len(arr)] = arr
    return out


class DeviceBlockCache:
    """Cache of device-resident batches keyed by (store, column set, SST
    set, ...).  An entry is a `DeviceBatch` on one chip or a
    `parallel.distributed_scan.ShardedBatch` whose lanes cover several;
    bytes are accounted per chip, by the shards of the entry's lanes,
    against a capacity that is per chip.

    A batch that does not fit evicts the least recently used entries that
    hold bytes on a chip over it — if they may go: any batch may evict
    the entries it covers (the same key but for a column set that holds
    fewer columns); a batch asked for before, those idle for more than
    `IDLE_FACTOR` times the lookups since it was last asked for; a batch
    asked for the first time, those not hit since they were admitted.
    So a scan that cycles over more batches than fit keeps the ones it
    holds, which it uses every cycle, and the rest pass through: built,
    used by the read that built them and released (`passed`); and a
    statement seen once (a warm-up, an ANALYZE) does not push out what
    another statement has already hit."""

    IDLE_FACTOR = 2
    #: keys asked for and not resident whose last lookups are remembered
    ASKED = 4096

    def __init__(self, capacity_bytes: int = 2 << 30):
        self.capacity = capacity_bytes          # per chip
        # key -> (batch, bytes over all chips); `benchmark/cluster.py`
        # reads the batches here to say where the table sits
        self._map: OrderedDict[tuple, Tuple[object, int]] = OrderedDict()
        self._placed: Dict[tuple, Dict[object, int]] = {}  # key -> chip -> B
        self._bytes = 0
        self._bytes_by_chip: Dict[object, int] = {}
        # the lookup clock: resident key -> its last use; a key missed ->
        # (its last miss, the one before)
        self._clock = 0
        self._used: Dict[tuple, int] = {}
        self._hit: set = set()      # resident keys hit since admitted
        self._asked: OrderedDict[tuple, Tuple[int, Optional[int]]] = \
            OrderedDict()
        self.hits = 0
        self.misses = 0
        self.passed = 0
        # invalidations arrive from flush/compaction executor threads
        # while the event loop serves lookups — the map needs a lock
        # (the bare-dict iterate-while-pop race the background flush
        # path would otherwise hit constantly)
        self._lock = threading.Lock()

    def bytes_by_chip(self) -> Dict[object, int]:
        with self._lock:
            return dict(self._bytes_by_chip)

    @staticmethod
    def _chip_metrics(chip):
        from ..utils import metrics
        return metrics.REGISTRY.entity(
            "device_cache", f"chip-{getattr(chip, 'id', chip)}")

    def _count(self, what: str, chips) -> None:
        """`/metrics`: hits, misses and passed batches of each chip an
        entry covers (`chips`; the default device where None)."""
        for d in jax.devices()[:1] if chips is None else chips:
            self._chip_metrics(d).counter(what).increment()

    def _publish_bytes(self) -> None:
        for d, b in self._bytes_by_chip.items():
            self._chip_metrics(d).gauge("bytes").set(b)

    def _drop(self, key: tuple) -> None:
        _, size = self._map.pop(key)
        # its last use is when it was last asked for
        self._asked[key] = (self._used.pop(key), None)
        self._hit.discard(key)
        self._bytes -= size
        for d, b in self._placed.pop(key).items():
            self._bytes_by_chip[d] -= b

    @staticmethod
    def _covers(k: tuple, key: tuple, part: Optional[int]) -> bool:
        """Entry `k` serves `key`: the same but for element `part`, whose
        members are all among `k[part]`'s."""
        return (k == key or part is not None and len(k) == len(key)
                and k[:part] == key[:part] and k[part + 1:] == key[part + 1:]
                and set(key[part]) <= set(k[part]))

    def lookup(self, key: tuple, part: Optional[int] = None, chips=None):
        """The batch cached under `key` — with `part`, the most recently
        used one whose key covers it (`_covers`): a batch of more columns
        serves fewer — or None.  `chips`: the devices the entry covers
        when it is not on the default one alone."""
        with self._lock:
            self._clock += 1
            found = key if key in self._map else None
            if found is None and part is not None:
                found = next((k for k in reversed(self._map)
                              if self._covers(k, key, part)), None)
            if found is not None:
                self.hits += 1
                self._map.move_to_end(found)
                self._used[found] = self._clock
                self._hit.add(found)
                batch = self._map[found][0]
            else:
                self.misses += 1
                last = self._asked.pop(key, (None, None))[0]
                self._asked[key] = (self._clock, last)
                while len(self._asked) > self.ASKED:
                    self._asked.popitem(last=False)
        self._count("hits" if found is not None else "misses", chips)
        return None if found is None else batch

    def admit(self, key: tuple, batch, part: Optional[int] = None,
              chips=None) -> bool:
        """Make `batch`, built after `lookup(key)` missed, the entry of
        `key` if the policy lets it in (class doc; `part` as in
        `lookup`); False where it passes: the caller holds the only
        reference, and the batch leaves the chip with it."""
        placed = batch_placement(batch)
        size = sum(placed.values())
        with self._lock:
            if key in self._map:
                # a racing builder (flush thread vs loop) landed the
                # same key while we built off-lock: keep the resident
                # entry — inserting ours would double-count _bytes
                return True
            asked, before = self._asked.get(key, (self._clock, None))
            over = {d: self._bytes_by_chip.get(d, 0) + b - self.capacity
                    for d, b in placed.items()}
            victims = []
            for k in self._map:             # least recently used first
                if max(over.values(), default=0) <= 0:
                    break
                if not any(over.get(d, 0) > 0 for d in self._placed[k]):
                    continue
                if not self._covers(key, k, part) and (
                        k in self._hit if before is None
                        else self._clock - self._used[k]
                        <= self.IDLE_FACTOR * (asked - before)):
                    continue
                victims.append(k)
                for d, b in self._placed[k].items():
                    if d in over:
                        over[d] -= b
            admitted = max(over.values(), default=0) <= 0
            if admitted:
                for k in victims:
                    self._drop(k)
                self._asked.pop(key, None)
                self._map[key] = (batch, size)
                # its last use is its own lookup, not its admission: the
                # reads of one statement look up before any build lands
                self._used[key] = asked
                self._placed[key] = placed
                self._bytes += size
                for d, b in placed.items():
                    self._bytes_by_chip[d] = \
                        self._bytes_by_chip.get(d, 0) + b
                self._publish_bytes()
            else:
                self.passed += 1
        if not admitted:
            self._count("passed", chips)
        return admitted

    def get_or_build(self, key: tuple, builder, chips=None):
        """The cached batch of `key`, or `builder()`'s, offered to the
        cache (`admit`)."""
        batch = self.lookup(key, chips=chips)
        if batch is None:
            batch = builder()
            self.admit(key, batch, chips=chips)
        return batch

    def invalidate_prefix(self, prefix: tuple) -> None:
        """Drop entries whose key starts with prefix (e.g. an SST was
        compacted away) — and, for a one-element prefix (a store), the
        multi-store entries that name it: their first key element is a
        tuple that holds every member store."""
        with self._lock:
            drop = [k for k in self._map if k[:len(prefix)] == prefix
                    or (len(prefix) == 1 and isinstance(k[0], tuple)
                        and prefix[0] in k[0])]
            for k in drop:
                self._drop(k)

    def clear(self):
        with self._lock:
            self._map.clear()
            self._placed.clear()
            self._used.clear()
            self._hit.clear()
            self._asked.clear()
            self._bytes = 0
            self._bytes_by_chip.clear()


def _lanes(b) -> list:
    """Every array of a batch, a `Pair` as its two."""
    return jax.tree_util.tree_leaves(
        [b.cols, b.nulls, b.valid, b.ht, b.next_ht, b.tombstone])


def batch_bytes(b) -> int:
    return sum(a.size * a.dtype.itemsize for a in _lanes(b))


def batch_placement(b) -> Dict[object, int]:
    """chip -> bytes of the batch's lanes that sit on it, shard by shard:
    one chip for a `DeviceBatch`, every chip of the mesh for a sharded
    one."""
    placed: Dict[object, int] = {}
    for a in _lanes(b):
        for sh in a.addressable_shards:
            placed[sh.device] = placed.get(sh.device, 0) + sh.data.nbytes
    return placed


def chip_capacity(devices, share: float = 0.5,
                  default: int = 2 << 30) -> int:
    """The cache's capacity a chip for a server that owns `devices`:
    `share` of the smallest chip's memory, by the device's own
    `bytes_limit`; `default` where a device does not say (a CPU)."""
    limits = [(d.memory_stats() or {}).get("bytes_limit") for d in devices]
    if not limits or any(l is None for l in limits):
        return default
    return int(min(limits) * share)
