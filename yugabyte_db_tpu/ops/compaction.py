"""TPU compaction: device sort as the k-way merge + vectorized MVCC GC.

Replaces the reference's heap-based MergingIterator loop and per-KV
retention decisions (reference: src/yb/rocksdb/db/compaction_job.cc:665
ProcessKeyValueCompaction, src/yb/table/merger.cc MergingIterator,
src/yb/docdb/docdb_compaction_context.cc:783 DocDBCompactionFeed) with:

1. keys → fixed-width big-endian u64 word columns; one stable
   lexicographic device sort (ops/lexsort.py: single-key passes, which
   the TPU compiler handles in bounded time where a multi-key `lax.sort`
   does not) merges ALL input runs at once (keys carry the descending-
   encoded hybrid time suffix, so versions of a doc key come out
   newest-first automatically — the same trick the LSM relies on).
2. the history-retention decision (reference:
   HistoryRetentionDirective, docdb_compaction_context.h:106) becomes a
   pure vector expression over (same-key-as-prev, ht, tombstone):
      keep = not-exact-duplicate AND
             (ht > history_cutoff  OR  (first version <= cutoff AND not
              tombstone))

Doc-key encodings are prefix-free, so zero-padding keys to a common
width preserves lexicographic order.
"""
from __future__ import annotations

from functools import partial
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..utils.hybrid_time import ENCODED_SIZE
from ..dockv.key_encoding import ValueType
from .lexsort import lex_order

_HT_SUFFIX = ENCODED_SIZE + 1  # kHybridTime marker + 12 encoded bytes


class KeySuffixError(ValueError):
    """A key matrix fed to the device compaction path does not carry the
    fixed-size hybrid-time suffix (corrupt or mixed-layout SST).

    Structured (instead of a bare ``assert``) so callers can degrade to
    the CPU compaction feed — and so the check survives ``python -O``.
    """

    def __init__(self, n_bad: int, n_total: int):
        self.n_bad = n_bad
        self.n_total = n_total
        super().__init__(
            f"{n_bad}/{n_total} keys lack the kHybridTime suffix marker "
            "(corrupt or mixed-layout input); compact via the CPU feed")


def keys_to_words(keys: np.ndarray) -> np.ndarray:
    """[N, L] uint8 -> [N, W] uint64 big-endian words (order-preserving)."""
    n, l = keys.shape
    w = (l + 7) // 8
    padded = np.zeros((n, w * 8), np.uint8)
    padded[:, :l] = keys
    return padded.reshape(n, w, 8).view(">u8").reshape(n, w).astype(np.uint64)


def split_ht_suffix(keys: np.ndarray) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """[N, L] full SubDocKeys -> (dockey part [N, L-13], ht u64, write_id
    u32) — vectorized split of the fixed-size hybrid-time suffix."""
    dk = keys[:, :-_HT_SUFFIX]
    check_ht_suffix(keys)
    ht_enc = keys[:, -ENCODED_SIZE:]
    ht = ~np.ascontiguousarray(ht_enc[:, :8]).view(">u8").reshape(-1).astype(np.uint64)
    wid = ~np.ascontiguousarray(ht_enc[:, 8:]).view(">u4").reshape(-1).astype(np.uint32)
    return dk, ht, wid


def check_ht_suffix(keys: np.ndarray) -> None:
    """Raise KeySuffixError unless every row of the [N, L] key matrix
    carries the kHybridTime marker at the fixed suffix position."""
    if keys.shape[1] <= _HT_SUFFIX:
        raise KeySuffixError(keys.shape[0], keys.shape[0])
    ok = keys[:, -_HT_SUFFIX] == ValueType.kHybridTime
    if not ok.all():
        raise KeySuffixError(int((~ok).sum()), keys.shape[0])


def compact_entry_arrays(keys: np.ndarray, tombstone: np.ndarray,
                         history_cutoff: int,
                         valid: Optional[np.ndarray] = None
                         ) -> Tuple[np.ndarray, np.ndarray]:
    """Host wrapper: full SubDocKey matrix [N, L] → (sorted_order,
    keep_mask_sorted). One retention-rule implementation: delegates to
    the split kernel (sort by dockey, ~ht, ~wid == full-key sort)."""
    return compact_runs([(keys, tombstone)], history_cutoff)


def pad_key_matrices(mats: Sequence[np.ndarray]) -> np.ndarray:
    """Stack [Ni, Li] key matrices into one [sum Ni, max Li] matrix.

    Doc-key prefix-freedom makes zero padding order-safe. All rows must
    end with an HT suffix at their true length; we right-pad, so the HT
    suffix position varies — callers needing the suffix must split
    BEFORE padding. This helper therefore also returns nothing else:
    use `concat_runs` below for full preprocessing."""
    w = max(m.shape[1] for m in mats)
    total = sum(m.shape[0] for m in mats)
    out = np.zeros((total, w), np.uint8)
    pos = 0
    for m in mats:
        out[pos:pos + m.shape[0], :m.shape[1]] = m
        pos += m.shape[0]
    return out


def concat_runs(runs: Sequence[Tuple[np.ndarray, np.ndarray]]
                ) -> Tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray]:
    """runs: [(keys [Ni, Li], tombstone [Ni])] →
    (dockey_padded, ht, wid, tombstone) with per-run HT suffixes split
    prior to padding."""
    dks, hts, wids, tombs = [], [], [], []
    for keys, tomb in runs:
        dk, ht, wid = split_ht_suffix(keys)
        dks.append(dk)
        hts.append(ht)
        wids.append(wid)
        tombs.append(tomb)
    return (pad_key_matrices(dks), np.concatenate(hts),
            np.concatenate(wids), np.concatenate(tombs))


@partial(jax.jit, static_argnames=("num_dk_words",))
def merge_gc_split_kernel(dk_words: jnp.ndarray,   # [N, Wd]
                          ht: jnp.ndarray,         # [N] u64
                          wid: jnp.ndarray,        # [N] u32
                          tombstone: jnp.ndarray, valid: jnp.ndarray,
                          history_cutoff, num_dk_words: int):
    """Same as merge_gc_kernel but with the HT split out (sort keys:
    dockey words asc, then ht desc, then write_id desc) — used when input
    runs had different key widths so suffixes were split before padding."""
    first = jnp.where(valid, dk_words[:, 0], jnp.uint64(0xFFFFFFFFFFFFFFFF))
    inv_ht = jnp.uint64(0xFFFFFFFFFFFFFFFF) - ht
    inv_wid = jnp.uint32(0xFFFFFFFF) - wid
    order = lex_order(
        (first,) + tuple(dk_words[:, i] for i in range(1, num_dk_words))
        + (inv_ht, inv_wid))
    dk_s = dk_words[order]
    ht_s = ht[order]
    wid_s = wid[order]
    tomb_s = tombstone[order]
    valid_s = valid[order]
    same_dockey = jnp.concatenate([
        jnp.array([False]), jnp.all(dk_s[1:] == dk_s[:-1], axis=1)])
    exact_dup = same_dockey & jnp.concatenate([
        jnp.array([False]), (ht_s[1:] == ht_s[:-1]) & (wid_s[1:] == wid_s[:-1])])
    leq = ht_s <= history_cutoff
    prev_leq = jnp.concatenate([jnp.array([False]), leq[:-1]])
    first_leq = leq & (~same_dockey | ~prev_leq)
    keep = valid_s & ~exact_dup & (
        (ht_s > history_cutoff) | (first_leq & ~tomb_s))
    return order, keep


def _pad_rows(n: int) -> int:
    """Row-count bucket (pow2) so the jitted merge kernel compiles once
    per bucket, not once per input size."""
    b = 1 << 12
    while b < n:
        b <<= 1
    return b


def run_merge_gc(dk_words: np.ndarray, ht: np.ndarray, wid: np.ndarray,
                 tomb: np.ndarray, history_cutoff: int
                 ) -> Tuple[np.ndarray, np.ndarray]:
    """Bucket-padded driver for merge_gc_split_kernel. Padding rows carry
    valid=False, sort last, and are never kept; the returned (order, keep)
    are already stripped back to the true row count."""
    n = dk_words.shape[0]
    padded = _pad_rows(n)
    if padded != n:
        dk_words = np.concatenate(
            [dk_words, np.zeros((padded - n, dk_words.shape[1]), np.uint64)])
        ht = np.concatenate([ht, np.zeros(padded - n, np.uint64)])
        wid = np.concatenate([wid, np.zeros(padded - n, np.uint32)])
        tomb = np.concatenate([tomb, np.zeros(padded - n, bool)])
    valid = np.zeros(padded, bool)
    valid[:n] = True
    order, keep = merge_gc_split_kernel(
        jnp.asarray(dk_words), jnp.asarray(ht), jnp.asarray(wid),
        jnp.asarray(tomb), jnp.asarray(valid), jnp.uint64(history_cutoff),
        num_dk_words=dk_words.shape[1])
    order = np.asarray(order)
    keep = np.asarray(keep)
    # all padding sorts to the tail with keep=False; stripping the tail
    # keeps indices in range
    return order[:n], keep[:n]


def compact_runs(runs: Sequence[Tuple[np.ndarray, np.ndarray]],
                 history_cutoff: int) -> Tuple[np.ndarray, np.ndarray]:
    """Merge+GC across sorted runs of differing key widths.

    Returns (order, keep) where order indexes into the concatenation of
    the runs in the given order."""
    dk_padded, ht, wid, tomb = concat_runs(runs)
    dk_words = keys_to_words(dk_padded)
    return run_merge_gc(dk_words, ht, wid, tomb, history_cutoff)


# ---------------------------------------------------------------------------
# Chunked run-aware merge: the kernel half of the pipelined compaction
# engine (docdb/compaction.py owns the host-side driver).  Instead of one
# whole-input sort over N rows, the driver feeds fixed-capacity frontiers
# (the unconsumed suffixes of the active input blocks); the kernel sorts
# only the frontier, emits the prefix strictly below the merge bound (the
# smallest key any not-yet-pulled block could contribute), and computes
# the MVCC keep mask with a carry describing the previous chunk's last
# emitted row so retention decisions stay exact across chunk boundaries.
# ---------------------------------------------------------------------------

_U64_MAX = jnp.uint64(0xFFFFFFFFFFFFFFFF)
_U32_MAX = jnp.uint32(0xFFFFFFFF)

#: process-lifetime kernel-compile accounting.  A signature is one
#: (frontier_rows, num_dk_words) pair — jax.jit compiles exactly once
#: per signature, so "compiles" counts cache misses and a repeat
#: compaction of the same shape reports zero new compiles.
_KERNEL_SIGS: set = set()
KERNEL_STATS = {"compiles": 0, "calls": 0, "cache_hits": 0}


def kernel_cache_stats() -> dict:
    return dict(KERNEL_STATS)


def reset_kernel_stats() -> None:
    KERNEL_STATS.update(compiles=0, calls=0, cache_hits=0)


def _note_kernel_call(sig: tuple) -> None:
    KERNEL_STATS["calls"] += 1
    if sig in _KERNEL_SIGS:
        KERNEL_STATS["cache_hits"] += 1
    else:
        _KERNEL_SIGS.add(sig)
        KERNEL_STATS["compiles"] += 1


def _lex_lt(cols, bounds):
    """Vectorized lexicographic (cols...) < (bounds...) over parallel
    column arrays vs scalar bound components."""
    less = None
    eq = None
    for c, b in zip(cols, bounds):
        c_lt, c_eq = c < b, c == b
        if less is None:
            less, eq = c_lt, c_eq
        else:
            less = less | (eq & c_lt)
            eq = eq & c_eq
    return less


@partial(jax.jit, static_argnames=("num_dk_words",))
def chunk_merge_kernel(dk_words: jnp.ndarray,    # [M, Wd] frontier rows
                       ht: jnp.ndarray,          # [M] u64
                       wid: jnp.ndarray,         # [M] u32
                       tombstone: jnp.ndarray,   # [M] bool
                       valid: jnp.ndarray,       # [M] bool
                       bound_dk: jnp.ndarray,    # [Wd] u64
                       bound_ht, bound_wid, has_bound,
                       carry_dk: jnp.ndarray,    # [Wd] u64
                       carry_ht, carry_wid, carry_leq, has_carry,
                       history_cutoff, num_dk_words: int):
    """One frontier merge step.  Returns (order, emit, keep), all [M] and
    aligned to the sorted frontier: `order` maps sorted position ->
    frontier position, `emit` marks the sorted prefix strictly below the
    bound (all True when has_bound is false), `keep` is the MVCC
    retention mask (meaningful only on emitted rows).

    Invalid (padding) rows sort last via a saturated first key word and
    are never emitted.  The emit comparison is strict: a frontier row
    exactly equal to the bound stays pending, because the bound is the
    first key of a block that has not been pulled yet and an exact
    duplicate of it may still arrive."""
    first = jnp.where(valid, dk_words[:, 0], _U64_MAX)
    inv_ht = _U64_MAX - ht
    inv_wid = _U32_MAX - wid
    order = lex_order(
        (first,) + tuple(dk_words[:, i] for i in range(1, num_dk_words))
        + (inv_ht, inv_wid))
    dk_s = dk_words[order]
    ht_s = ht[order]
    wid_s = wid[order]
    inv_ht_s = inv_ht[order]
    inv_wid_s = inv_wid[order]
    tomb_s = tombstone[order]
    valid_s = valid[order]

    cols = tuple(dk_s[:, i] for i in range(num_dk_words)) \
        + (inv_ht_s, inv_wid_s)
    bounds = tuple(bound_dk[i] for i in range(num_dk_words)) \
        + (_U64_MAX - bound_ht, _U32_MAX - bound_wid)
    emit = valid_s & (_lex_lt(cols, bounds) | ~has_bound)

    same_dockey = jnp.concatenate([
        (has_carry & jnp.all(dk_s[0] == carry_dk))[None],
        jnp.all(dk_s[1:] == dk_s[:-1], axis=1)])
    exact_dup = same_dockey & jnp.concatenate([
        ((ht_s[0] == carry_ht) & (wid_s[0] == carry_wid))[None],
        (ht_s[1:] == ht_s[:-1]) & (wid_s[1:] == wid_s[:-1])])
    leq = ht_s <= history_cutoff
    prev_leq = jnp.concatenate([carry_leq[None], leq[:-1]])
    first_leq = leq & (~same_dockey | ~prev_leq)
    keep = valid_s & ~exact_dup & (
        (ht_s > history_cutoff) | (first_leq & ~tomb_s))
    return order, emit, keep


def merge_frontier(dk_words: np.ndarray, ht: np.ndarray, wid: np.ndarray,
                   tomb: np.ndarray, valid: np.ndarray,
                   bound: Optional[Tuple[np.ndarray, int, int]],
                   carry: Optional[Tuple[np.ndarray, int, int, bool]],
                   history_cutoff: int):
    """Host wrapper for chunk_merge_kernel: packs the optional bound /
    carry into traced scalars (absent -> zeros + a False presence flag,
    so shapes — and therefore compiles — never depend on them) and
    records kernel-cache accounting.  Returns DEVICE arrays so the
    caller can overlap host work with the sort before materializing."""
    m, wd = dk_words.shape
    _note_kernel_call((m, wd))
    zero_dk = np.zeros(wd, np.uint64)
    b_dk, b_ht, b_wid = (bound if bound is not None
                         else (zero_dk, 0, 0))
    c_dk, c_ht, c_wid, c_leq = (carry if carry is not None
                                else (zero_dk, 0, 0, False))
    return chunk_merge_kernel(
        jnp.asarray(dk_words), jnp.asarray(ht), jnp.asarray(wid),
        jnp.asarray(tomb), jnp.asarray(valid),
        jnp.asarray(b_dk), jnp.uint64(b_ht), jnp.uint32(b_wid),
        jnp.bool_(bound is not None),
        jnp.asarray(c_dk), jnp.uint64(c_ht), jnp.uint32(c_wid),
        jnp.bool_(c_leq), jnp.bool_(carry is not None),
        jnp.uint64(history_cutoff), num_dk_words=wd)
