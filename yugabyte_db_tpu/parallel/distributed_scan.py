"""Distributed scan: per-shard kernels + ICI collectives for the combine.

Each device holds its tablet shard's columnar batch; the jitted step runs
the same scan kernel per shard under `shard_map` and combines partial
aggregates with psum/pmin/pmax over the mesh axes — the TPU translation
of pggate's per-tablet fan-out + client-side partial combine (reference:
src/yb/yql/pggate/pg_doc_op.h:117-121, aggregate combination in
src/postgres yb_scan paths).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from ..ops.device_batch import (HT_NONE, bucket_rows, _pad,
                                f64_conversion, link_versions)
from ..ops.expr import collect_constants, expr_signature
from ..ops.scan import (
    AggSpec, GroupSpec, _build_kernel, _expand_avg, _group_strategy,
    _rescale_outs, _static_scales, mvcc_lanes,
)
from ..storage.columnar import ColumnarBlock
from .mesh import BLOCKS_AXIS, TABLETS_AXIS, TabletMesh


@dataclass
class ShardedBatch:
    """[S, N] columnar arrays sharded over the mesh (S = total shards =
    tablets * blocks, N = per-shard padded rows)."""
    n_rows_per_shard: List[int]
    cols: Dict[int, jnp.ndarray]
    nulls: Dict[int, jnp.ndarray]
    # GLOBAL per-column (min, max) across all shards — static SUM scales
    # derived from these are identical on every shard, so int64 partials
    # psum exactly over ICI with no in-kernel pmax round
    col_bounds: Dict[int, Tuple[float, float]]
    valid: jnp.ndarray
    ht: jnp.ndarray
    # per shard, as DeviceBatch.next_ht: present when some block is not
    # unique-keyed.  Linking per shard is exact because one doc key
    # lives in exactly one tablet shard and one block shard.
    next_ht: Optional[jnp.ndarray]
    tombstone: jnp.ndarray
    mesh: TabletMesh

    @property
    def padded_rows(self) -> int:
        # valid is [tablet_shards, block_shards, N] after device_put
        return int(self.valid.shape[-1])

    @property
    def num_shards(self) -> int:
        return int(np.prod(self.valid.shape[:-1]))


def build_sharded_batch(tm: TabletMesh,
                        per_shard_blocks: Sequence[Sequence[ColumnarBlock]],
                        columns: Sequence[int]) -> ShardedBatch:
    """Stack per-shard block lists into mesh-sharded [S, N] arrays. The
    number of shard slots must equal the mesh size; short shards pad."""
    S = tm.num_tablet_shards * tm.num_block_shards
    if len(per_shard_blocks) != S:
        raise ValueError(f"need {S} shard block-lists, got "
                         f"{len(per_shard_blocks)}")
    ns = [sum(b.n for b in blocks) for blocks in per_shard_blocks]
    pad = bucket_rows(max(max(ns), 1))

    def stack(get, dtype=None):
        if dtype is None:
            # take the real dtype from any nonempty shard so empty shards
            # don't promote int columns to float64 via np.stack
            for blocks in per_shard_blocks:
                for b in blocks:
                    dtype = get(b).dtype
                    break
                if dtype is not None:
                    break
        rows = []
        for blocks, n in zip(per_shard_blocks, ns):
            parts = [get(b) for b in blocks]
            arr = (np.concatenate(parts) if parts
                   else np.zeros(0, dtype or np.float32))
            rows.append(_pad(arr, pad))
        return np.stack(rows)

    cols: Dict[int, jnp.ndarray] = {}
    nulls: Dict[int, jnp.ndarray] = {}
    col_bounds: Dict[int, Tuple[float, float]] = {}

    def put(tm, arr):
        T, B = tm.num_tablet_shards, tm.num_block_shards
        arr = arr.reshape(T, B, *arr.shape[1:])
        return jax.device_put(arr, tm.tablet_block_sharding(
            extra_dims=arr.ndim - 2))

    for cid in columns:
        # decide the device dtype GLOBALLY (all shards must agree) with
        # the same policy as the single-device builder: integer-valued
        # f64 columns ship as exact int32; fractional f64 follows the
        # backend policy (f64 on CPU, f32 on TPU — sums stay exact via
        # the kernel's int64 fixed-point accumulation)
        conv = f64_conversion(
            [b.fixed[cid][0] if cid in b.fixed else b.pk[cid]
             for blocks in per_shard_blocks for b in blocks])

        def getv(b, cid=cid, conv=conv):
            v = b.fixed[cid][0] if cid in b.fixed else b.pk[cid]
            return v.astype(conv) if conv is not None else v

        def getn(b, cid=cid):
            if cid in b.fixed:
                return b.fixed[cid][1]
            return np.zeros(b.n, bool)
        stacked = stack(getv)
        if stacked.size and stacked.dtype.kind in "fiu":
            # padding zeros are included — harmless: masked rows
            # contribute 0 to any SUM, the bound only sets the scale
            col_bounds[cid] = (float(stacked.min()), float(stacked.max()))
        cols[cid] = put(tm, stacked)
        nulls[cid] = put(tm, stack(getn, bool))
    valid_rows = []
    for n in ns:
        v = np.zeros(pad, bool)
        v[:n] = True
        valid_rows.append(v)
    ht = stack(lambda b: b.ht, np.uint64)
    next_ht = None
    if not all(b.unique_keys
               for blocks in per_shard_blocks for b in blocks):
        next_ht = np.full(ht.shape, HT_NONE, np.uint64)
        key_hash = stack(lambda b: b.key_hash, np.uint64)
        write_id = stack(lambda b: b.write_id, np.uint32)
        for i, n in enumerate(ns):
            next_ht[i, :n], _ = link_versions(
                key_hash[i, :n], ht[i, :n], write_id[i, :n])
    return ShardedBatch(
        n_rows_per_shard=ns, cols=cols, nulls=nulls,
        col_bounds=col_bounds,
        valid=put(tm, np.stack(valid_rows)),
        ht=put(tm, ht),
        next_ht=put(tm, next_ht) if next_ht is not None else None,
        tombstone=put(tm, stack(lambda b: b.tombstone, bool)),
        mesh=tm)


_COMBINE = {"sum": "psum", "count": "psum", "min": "pmin", "max": "pmax"}


class DistributedScanKernel:
    def __init__(self):
        self._cache: Dict[tuple, object] = {}
        self.compiles = 0

    def _get(self, sig, tm: TabletMesh, where, aggs, group, mvcc_mode,
             static_sums, strategy):
        fn = self._cache.get(sig)
        if fn is not None:
            return fn
        axes = (TABLETS_AXIS, BLOCKS_AXIS)
        S = tm.num_tablet_shards * tm.num_block_shards
        # static SUM scales derive from GLOBAL host-side column bounds,
        # so every shard quantizes identically and the int64 partials
        # psum EXACTLY over ICI with no collective before the sum; SUMs
        # without usable bounds fall back to the dynamic in-kernel scale,
        # where axis_names pmax-combines max|v| across shards first
        local = _build_kernel(where, aggs, group, mvcc_mode,
                              axis_names=axes, row_multiplier=S,
                              static_sums=static_sums, strategy=strategy)

        def shard_fn(cols, nulls, consts, valid, lanes, read_ht,
                     sum_scales):
            # local shard view: [1, 1, N] → [N]; a lane the mode does
            # not read is None
            sq = lambda a: None if a is None else a.reshape(a.shape[-1])
            lcols = {k: sq(v) for k, v in cols.items()}
            lnulls = {k: sq(v) for k, v in nulls.items()}
            outs, scales, counts, _ = local(
                lcols, lnulls, consts, sq(valid), *map(sq, lanes),
                read_ht, sum_scales)
            combined = []
            for a, o in zip(aggs, outs):
                kind = _COMBINE["count" if a.expr is None else a.op]
                for ax in axes:
                    if kind == "psum":
                        o = jax.lax.psum(o, ax)
                    elif kind == "pmin":
                        o = jax.lax.pmin(o, ax)
                    else:
                        o = jax.lax.pmax(o, ax)
                combined.append(o)
            for ax in axes:
                counts = jax.lax.psum(counts, ax)
            # scales are identical on every shard (pmax'd vmax) and pass
            # through replicated; each float-sum fallback lane is a
            # per-shard partial that psums like the int64 lane
            cscales = []
            for s in scales:
                if isinstance(s, tuple):
                    fb = s[1]
                    for ax in axes:
                        fb = jax.lax.psum(fb, ax)
                    cscales.append((s[0], fb))
                else:
                    cscales.append(s)
            return tuple(combined), tuple(cscales), counts

        spec3 = P(TABLETS_AXIS, BLOCKS_AXIS, None)
        in_specs = (
            {k: spec3 for k in sig_cols(sig)}, {k: spec3 for k in sig_cols(sig)},
            P(), spec3, spec3, P(), P())
        smapped = jax.shard_map(
            shard_fn, mesh=tm.mesh, in_specs=in_specs,
            out_specs=(tuple(P() for _ in aggs), tuple(P() for _ in aggs),
                       P()), check_vma=False)
        fn = jax.jit(smapped)
        self._cache[sig] = fn
        self.compiles += 1
        return fn

    def run(self, batch: ShardedBatch,
            where: Optional[tuple] = None,
            aggs: Sequence[AggSpec] = (),
            group: Optional[GroupSpec] = None,
            read_ht: Optional[int] = None):
        aggs = tuple(_expand_avg(aggs))
        mvcc_mode, lanes = mvcc_lanes(batch, read_ht)
        consts: List = []
        if where is not None:
            collect_constants(where, consts)
        for a in aggs:
            if a.expr is not None:
                collect_constants(a.expr, consts)
        col_sig = tuple(sorted(
            (cid, str(v.dtype)) for cid, v in batch.cols.items()))
        tm = batch.mesh
        static_sums, scale_args = _static_scales(
            aggs, batch.col_bounds,
            batch.padded_rows * batch.num_shards, batch.cols)
        strategy = _group_strategy()
        sig = (
            id(tm.mesh), expr_signature(where) if where is not None else None,
            tuple(a.signature() for a in aggs),
            group.cols if group else None, mvcc_mode,
            batch.padded_rows, col_sig, static_sums, strategy,
        )
        fn = self._get(sig, tm, where, aggs, group, mvcc_mode,
                       static_sums, strategy)
        outs, scales, counts = fn(
            batch.cols, batch.nulls,
            [jnp.asarray(c) for c in consts], batch.valid, lanes,
            jnp.uint64(read_ht if read_ht is not None
                       else 0xFFFFFFFFFFFFFFFF),
            scale_args)
        return _rescale_outs(outs, scales), counts


def sig_cols(sig) -> Tuple[int, ...]:
    return tuple(cid for cid, _ in sig[-3])


_DEFAULT = DistributedScanKernel()


def distributed_scan_aggregate(batch: ShardedBatch, where=None, aggs=(),
                               group=None, read_ht=None):
    return _DEFAULT.run(batch, where, aggs, group, read_ht)
