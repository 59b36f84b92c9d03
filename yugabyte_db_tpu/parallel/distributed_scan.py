"""Distributed scan: per-shard kernels + ICI collectives for the combine.

Each device holds its tablet shard's columnar batch; the jitted step runs
the same scan kernel per shard under `shard_map` and combines partial
aggregates with psum/pmin/pmax over the mesh axes — the TPU translation
of pggate's per-tablet fan-out + client-side partial combine (reference:
src/yb/yql/pggate/pg_doc_op.h:117-121, aggregate combination in
src/postgres yb_scan paths).
"""
from __future__ import annotations

import threading
from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from ..ops.device_batch import (HT_NONE, Pair, batch_bytes, bucket_rows,
                                f64_conversion, f64_pair, f64_pairs,
                                link_versions, narrowed, u64_pair)
from ..ops.grouped_scan import DictGroupSpec
from ..ops.scan import (AggSpec, GroupSpec, ResultLayout, _build_kernel,
                        launch, prepare_launch, unpack_scalars)
from ..storage.columnar import ColumnarBlock
from ..utils import trace as _trace
from .mesh import ROW_AXES, TabletMesh


@dataclass
class ShardedBatch:
    """[S * N] columnar row lanes cut over the mesh (S = total shards =
    tablets * blocks, N = per-shard padded rows): shard i, tablet-major,
    holds rows [i * N, (i + 1) * N) as a one-dimensional [N] lane
    (`TabletMesh.row_sharding`).  The 64-bit lanes are `Pair`s of such
    lanes, as in a `DeviceBatch`: `ht`, `next_ht`, and float64 columns
    where the backend's float64 is a pair."""
    n_rows_per_shard: List[int]
    cols: Dict[int, object]
    nulls: Dict[int, jnp.ndarray]
    # GLOBAL per-column (min, max) across all shards — static SUM scales
    # derived from these are identical on every shard, so int64 partials
    # psum exactly over ICI with no in-kernel pmax round
    col_bounds: Dict[int, Tuple[float, float]]
    valid: jnp.ndarray
    ht: Pair
    # per shard, as DeviceBatch.next_ht: present when some block is not
    # unique-keyed.  Linking per shard is exact because one doc key
    # lives in exactly one tablet shard and one block shard.
    next_ht: Optional[Pair]
    tombstone: jnp.ndarray
    mesh: TabletMesh
    # text columns ride as int32 codes of these dictionaries, which are
    # GLOBAL over the shards (one ops/grouped_scan.DictPlan over every
    # shard's blocks): equal codes on two chips mean equal strings, so a
    # dictionary-grouped partial psums slot by slot
    dicts: Dict[int, np.ndarray] = field(default_factory=dict)
    # the newest write time among the rows (host side): a read at or
    # above it has no row inside its uncertainty window
    max_ht: int = 0

    def narrowed(self, columns) -> "ShardedBatch":
        return narrowed(self, columns)

    @property
    def n_rows(self) -> int:
        return sum(self.n_rows_per_shard)

    @property
    def padded_rows(self) -> int:
        return int(self.valid.shape[0]) // self.num_shards

    @property
    def num_shards(self) -> int:
        return self.mesh.num_tablet_shards * self.mesh.num_block_shards


def _column_part(b: ColumnarBlock, cid: int) -> np.ndarray:
    if cid in b.fixed:
        return b.fixed[cid][0]
    if cid in b.pk:
        return b.pk[cid]
    raise KeyError(f"column {cid} not available in columnar form")


def build_sharded_batch(tm: TabletMesh,
                        per_shard_blocks: Sequence[Sequence[ColumnarBlock]],
                        columns: Sequence[int], dict_plan=None,
                        multi_version: bool = False) -> ShardedBatch:
    """Per-shard block lists -> mesh-sharded [S * N] lanes, one shard of
    N rows a device.  The number of shard slots must equal the mesh size;
    short shards pad.  Each shard's lanes are filled, linked and put on its own
    device by a thread of its own, so the host never holds a stacked copy
    of the table.

    ``dict_plan``: an ops/grouped_scan.DictPlan over EVERY shard's blocks
    — its columns ride as codes of the plan's global dictionaries
    (``ShardedBatch.dicts``).  ``multi_version``: the blocks of a shard
    may hold several versions of a key even where each block is
    unique-keyed by itself (several SSTs a tablet); such a batch gets
    ``next_ht``, linked per shard.  Spans as `build_batch`:
    ``batch.build`` (host fill), ``batch.version_link``, ``batch.h2d``."""
    S = tm.num_tablet_shards * tm.num_block_shards
    if len(per_shard_blocks) != S:
        raise ValueError(f"need {S} shard block-lists, got "
                         f"{len(per_shard_blocks)}")
    ns = [sum(b.n for b in blocks) for blocks in per_shard_blocks]
    pad = bucket_rows(max(max(ns), 1))
    every = [b for blocks in per_shard_blocks for b in blocks]
    devices = list(tm.mesh.devices.reshape(-1))
    dict_cols = set(dict_plan.dicts) if dict_plan is not None else set()
    pool = ThreadPoolExecutor(max_workers=S,
                              thread_name_prefix="shard-build")

    def fill(blocks, get, dtype) -> np.ndarray:
        out, pos = np.zeros(pad, dtype), 0
        for b in blocks:
            part = get(b)
            out[pos:pos + len(part)] = part
            pos += len(part)
        return out

    with pool, _trace.TRACES.span("batch.build", child_only=True) as sp:
        # the device dtype is decided GLOBALLY (all shards must agree)
        # with the single-device builder's policy: integer-valued f64
        # columns ship as exact int32, fractional f64 follows the
        # `device_float_dtype` flag; so are the bounds the static SUM
        # scales come from, so every shard quantizes identically and the
        # int64 partials psum exactly
        plain = [cid for cid in columns if cid not in dict_cols]
        pairs = f64_pairs()
        dtypes: Dict[int, np.dtype] = {}
        col_bounds: Dict[int, Tuple[float, float]] = {}
        for cid in plain:
            parts = [_column_part(b, cid) for b in every]
            conv = f64_conversion(parts)
            dtypes[cid] = np.dtype(conv if conv is not None else (
                parts[0].dtype if parts else np.float32))
            if dtypes[cid].kind in "fiu" and any(p.size for p in parts):
                col_bounds[cid] = (
                    float(min(p.min() for p in parts if p.size)),
                    float(max(p.max() for p in parts if p.size)))

        def host_lanes(shard: int) -> dict:
            blocks, n = per_shard_blocks[shard], ns[shard]
            lanes = {"cols": {}, "nulls": {}}
            for cid in plain:
                lanes["cols"][cid] = fill(
                    blocks, lambda b: _column_part(b, cid), dtypes[cid])
                if pairs and dtypes[cid] == np.float64:
                    lanes["cols"][cid] = f64_pair(lanes["cols"][cid])
                lanes["nulls"][cid] = fill(
                    blocks, lambda b: (b.fixed[cid][1] if cid in b.fixed
                                       else np.zeros(b.n, bool)), bool)
            for cid in columns:
                if cid in dict_cols:
                    lanes["cols"][cid] = fill(
                        blocks, lambda b: dict_plan.block_codes(cid, b),
                        np.int32)
                    lanes["nulls"][cid] = fill(
                        blocks, lambda b: np.asarray(b.varlen[cid][2],
                                                     bool), bool)
            valid = np.zeros(pad, bool)
            valid[:n] = True
            lanes["valid"] = valid
            lanes["ht"] = fill(blocks, lambda b: b.ht, np.uint64)
            lanes["tombstone"] = fill(blocks, lambda b: b.tombstone, bool)
            return lanes

        host = list(pool.map(host_lanes, range(S)))
        max_ht = max((int(h["ht"][:n].max())
                      for h, n in zip(host, ns) if n), default=0)
        if multi_version or not all(b.unique_keys for b in every):
            def link(shard: int) -> int:
                blocks, n = per_shard_blocks[shard], ns[shard]
                nxt = np.full(pad, HT_NONE, np.uint64)
                superseded = 0
                if n:
                    nxt[:n], superseded = link_versions(
                        np.concatenate([b.key_hash for b in blocks]),
                        host[shard]["ht"][:n],
                        np.concatenate([b.write_id for b in blocks]))
                host[shard]["next_ht"] = nxt
                return superseded
            with _trace.TRACES.span("batch.version_link",
                                    child_only=True) as lsp:
                superseded = sum(pool.map(link, range(S)))
                lsp.set_tag("rows", sum(ns))
                lsp.set_tag("superseded", superseded)
                lsp.set_tag("shards", S)

        def split(shard: int) -> None:
            """The time lanes as their 32-bit words (`Pair`), once."""
            for lane in ("ht", "next_ht"):
                if lane in host[shard]:
                    host[shard][lane] = u64_pair(host[shard][lane])
        list(pool.map(split, range(S)))
        sp.set_tag("shards", S)
        sp.set_tag("rows", sum(ns))

    sharding = tm.row_sharding()

    def put(lane: str, cid=None):
        """One lane of every shard, each on its shard's device, as one
        array sharded over the mesh — a `Pair` as a pair of them."""
        shards = [h[lane] if cid is None else h[lane][cid] for h in host]

        def one(parts):
            return jax.make_array_from_single_device_arrays(
                (S * pad,), sharding,
                [jax.device_put(p, d) for p, d in zip(parts, devices)])
        if isinstance(shards[0], Pair):
            return Pair(*map(one, zip(*shards)))
        return one(shards)

    with _trace.TRACES.span("batch.h2d", child_only=True) as sp:
        batch = ShardedBatch(
            n_rows_per_shard=ns,
            cols={cid: put("cols", cid) for cid in columns},
            nulls={cid: put("nulls", cid) for cid in columns},
            col_bounds=col_bounds, valid=put("valid"), ht=put("ht"),
            next_ht=put("next_ht") if "next_ht" in host[0] else None,
            tombstone=put("tombstone"), mesh=tm,
            dicts=({cid: dict_plan.dicts[cid] for cid in columns
                    if cid in dict_cols}), max_ht=max_ht)
        if sp.sampled:
            # transfers are asynchronous: wait, so that the span times
            # them and not their enqueue
            jax.block_until_ready(
                (batch.cols, batch.nulls, batch.valid, batch.ht,
                 batch.next_ht, batch.tombstone))
            sp.set_tag("shards", S)
            sp.set_tag("bytes", batch_bytes(batch) // S)
    return batch


_COMBINE = {"sum": "psum", "count": "psum", "min": "pmin", "max": "pmax"}


class DistributedScanKernel:
    def __init__(self):
        self._cache: Dict[tuple, object] = {}
        self.compiles = 0
        self._lock = threading.Lock()

    def _get(self, sig, tm: TabletMesh, where, aggs, group, mvcc_mode,
             static_sums, strategy, lits):
        """The jitted program `sig` names, its `ResultLayout` kept beside
        it (`layout`) — under the lock, as `ScanKernel._get`: `run` is
        called beside the event loop too."""
        with self._lock:
            got = self._cache.get(sig)
            if got is None:
                got = self._cache[sig] = self._build(
                    tm, where, aggs, group, mvcc_mode, static_sums,
                    strategy, lits)
                self.compiles += 1
            return got[0]

    def layout(self, sig) -> ResultLayout:
        return self._cache[sig][1]

    def forget(self, tm: TabletMesh) -> None:
        """Drop the programs compiled for `tm` (their signature begins
        with `id(tm.mesh)`): its owner gives its chips back."""
        with self._lock:
            for sig in [s for s in self._cache if s[0] == id(tm.mesh)]:
                del self._cache[sig]

    def _build(self, tm: TabletMesh, where, aggs, group, mvcc_mode,
               static_sums, strategy, lits):
        axes = ROW_AXES
        S = tm.num_tablet_shards * tm.num_block_shards
        # static SUM scales derive from GLOBAL host-side column bounds,
        # so every shard quantizes identically and the int64 partials
        # psum EXACTLY over ICI with no collective before the sum; SUMs
        # without usable bounds fall back to the dynamic in-kernel scale,
        # where axis_names pmax-combines max|v| across shards first
        local = _build_kernel(where, aggs, group, mvcc_mode,
                              axis_names=axes, row_multiplier=S,
                              static_sums=static_sums, strategy=strategy)
        layout = ResultLayout()

        def shard_fn(cols, nulls, arrays, valid, ht, next_ht, tombstone,
                     scalars):
            # the local shard view of a row lane is the [N] lane the
            # one-device kernel reads; a lane the mode does not read is
            # None; the runtime scalars are the replicated host vectors
            consts, read_ht, sum_scales, domains = unpack_scalars(
                arrays, scalars, lits, group, static_sums)
            got = local(cols, nulls, consts, valid, ht, next_ht, tombstone,
                        read_ht, sum_scales, domains)
            outs, scales, counts = got[:3]
            # a dictionary-grouped kernel also counts the rows whose
            # group fell past its slot budget: they add up like a count
            spilled = got[4:]
            kinds = [_COMBINE["count" if a.expr is None else a.op]
                     for a in aggs]
            # every additive partial of the launch rides ONE psum: the
            # int64 lanes, the row counts, the spill count and each
            # float fallback lane of a dynamic-scale SUM
            fallbacks = [s[1] for s in scales if isinstance(s, tuple)]
            added = jax.lax.psum(
                ([o for o, k in zip(outs, kinds) if k == "psum"],
                 counts, spilled, fallbacks), axes)
            sums, fbs = iter(added[0]), iter(added[3])
            combined = []
            for o, k in zip(outs, kinds):
                if k == "psum":
                    combined.append(next(sums))
                elif k == "pmin":
                    combined.append(jax.lax.pmin(o, axes))
                else:
                    combined.append(jax.lax.pmax(o, axes))
            # scales are identical on every shard (pmax'd vmax) and pass
            # through replicated
            cscales = [(s[0], next(fbs)) if isinstance(s, tuple) else s
                       for s in scales]
            # what the host reads, packed once combined: replicated
            return layout.pack(local, combined, cscales, added[1],
                               added[2])

        rows = P(ROW_AXES)
        in_specs = (rows, rows, P(), rows, rows, rows, rows, P())
        smapped = jax.shard_map(
            shard_fn, mesh=tm.mesh, in_specs=in_specs, out_specs=P(),
            check_vma=False)

        def mesh_scan(*args):
            """The argument list `ops.scan.prepare_launch` makes; returns
            (the packed result, no row mask)."""
            return smapped(*args), None
        # a stable program name, as `ScanKernel._get` gives the
        # single-device program: jit_mesh_scan_linked_resolveddictgroup
        mesh_scan.__name__ = mesh_scan.__qualname__ = "_".join(
            ["mesh_scan", mvcc_mode] + ([type(group).__name__.lower()]
                                        if group is not None else []))
        return jax.jit(mesh_scan), layout

    def run(self, batch: ShardedBatch,
            where: Optional[tuple] = None,
            aggs: Sequence[AggSpec] = (),
            group: Optional[GroupSpec] = None,
            read_ht: Optional[int] = None):
        """(agg results, count or group counts), every one already
        combined over the shards on the device; a DictGroupSpec adds the
        spill count (nonzero = slot overflow: the caller must fall
        back).  What it does before the dispatch is the `launch.prepare`
        span, as in `ScanKernel.run`."""
        tm = batch.mesh
        with _trace.TRACES.span("launch.prepare", child_only=True,
                                cpu=True):
            job = prepare_launch(
                batch, where, aggs, group, read_ht,
                n_total=batch.padded_rows * batch.num_shards)
            job = job._replace(sig=(id(tm.mesh),) + job.sig)
            compiled = job.sig not in self._cache
            fn = self._get(job.sig, tm, *job.key)
        outs, counts, *spilled = launch(
            fn, self.layout(job.sig), job, batch, compiled, mask=False,
            tags=(("chips", tm.mesh.devices.size),
                  ("shards", batch.num_shards)))
        if isinstance(group, DictGroupSpec):
            return outs, counts, int(spilled[0])
        return outs, counts


_DEFAULT = DistributedScanKernel()


def distributed_scan_aggregate(batch: ShardedBatch, where=None, aggs=(),
                               group=None, read_ht=None):
    return _DEFAULT.run(batch, where, aggs, group, read_ht)
