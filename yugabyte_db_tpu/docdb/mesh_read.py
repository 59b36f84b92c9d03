"""One aggregate read over several tablets of a table as ONE launch on
the chips their server owns (`tserver_device_chips` > 1).

The single-device route (`DocReadOperation._execute_tpu_aggregate`)
serves a tablet a launch; a statement over n tablets is n launches in
series on the server's event loop, their partials added by the client.
Here the tablets' columnar blocks are placed on the chips by a stated
rule (`chip_of`), cached as one `parallel.distributed_scan.ShardedBatch`
whose lanes cover the chips, and scanned by one `shard_map` program
whose partials `lax.psum` adds on the device: the host reads back one
answer, the one the client's combine would have made.

What the mesh cannot take (`MeshIneligible`, with the reason) goes back
to the caller, who serves each tablet by the one-device path: a request
that is no plain aggregate, a hash group-by, unflushed rows (a memtable
overlay), a column with no columnar form, a dictionary group past its
slot budget.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Sequence

import numpy as np

from ..ops.grouped_scan import (DictGroupSpec, dict_cols_needed,
                                make_dict_plan)
from ..ops.scan import HashGroupSpec
from ..ops.stream_scan import chunk_safe_mvcc
from ..parallel.distributed_scan import (DistributedScanKernel,
                                         build_sharded_batch)
from ..parallel.mesh import TabletMesh, tablet_mesh
from ..utils import flags, metrics
from ..utils import trace as _trace
from .operations import (_MAX_HT, DocReadOperation, ReadRequest,
                         ReadResponse, ReadRestartError, _skew_window_ht,
                         run_steps)

#: one kernel cache for every mesh of the process, as `_SHARED_KERNEL`
_MESH_KERNEL = DistributedScanKernel()


class MeshIneligible(Exception):
    """A read the mesh scan does not take; `reason` names why, and is the
    `fallback` tag of the read's span and a `/metrics` counter."""

    def __init__(self, reason: str):
        super().__init__(reason)
        self.reason = reason


def chip_of(index: int, tablets: int, chips: int) -> int:
    """THE placement rule: tablet `index` of a table's `tablets`, in
    partition order, sits on chip `index * chips // tablets` — 8 tablets
    on 4 chips are two a chip, neighbours in the key space together."""
    return index * chips // tablets


class MeshReader:
    """The chips a tablet server owns, as a mesh, and the reads over
    them.  One per server; `devices` are the first N of `jax.devices()`."""

    def __init__(self, devices: Sequence, cache, owner: str = ""):
        self.devices = list(devices)
        self.mesh: TabletMesh = tablet_mesh(len(self.devices),
                                            devices=self.devices)
        self.cache = cache
        self.kernel = _MESH_KERNEL
        ent = metrics.REGISTRY.entity("server", owner or "mesh")
        self._m_launches = ent.counter("mesh_scan_launches")
        self._m_fallbacks = ent.counter("mesh_scan_fallbacks")

    @property
    def chips(self) -> int:
        return len(self.devices)

    # -- what the mesh takes -------------------------------------------------
    def check(self, req: ReadRequest, ops: List[DocReadOperation]) -> None:
        if (not req.aggregates or req.pk_eq is not None
                or req.pk_prefix is not None or req.join is not None
                or req.window is not None or req.paging_state is not None):
            raise MeshIneligible("not_aggregate")
        if isinstance(req.group_by, HashGroupSpec):
            raise MeshIneligible("hash_group")
        if isinstance(req.group_by, DictGroupSpec) \
                and not flags.get("grouped_pushdown_enabled"):
            raise MeshIneligible("grouped_pushdown_off")
        if len(ops) < 2:
            raise MeshIneligible("one_tablet")
        if any(getattr(op.codec, "shred_cols", ()) for op in ops):
            raise MeshIneligible("doc_columns")
        # expressions and size, as the one-device route asks of a tablet
        if not all(op._tpu_eligible(req) for op in ops):
            raise MeshIneligible("not_device_eligible")
        for op in ops:
            if not op.store._mem.empty() or op.store._frozen:
                raise MeshIneligible("memtable")

    # -- the cached batch ----------------------------------------------------
    def _cache_key(self, ops, needed) -> tuple:
        """As `DocReadOperation._batch_cache_key`, over every member
        store: its SST set and write generation, the column set and the
        flags that shape a batch.  The first element names the stores,
        so a flush or compaction of one of them drops the entry
        (`DeviceBlockCache.invalidate_prefix`)."""
        return (("mesh",) + tuple(id(op.store) for op in ops),
                tuple(sorted(needed)),
                tuple((tuple(r.path for r in op.store.ssts),
                       op.store.write_generation()) for op in ops),
                flags.get("device_float_dtype"), self.chips)

    def _build(self, ops, needed):
        per_tablet = []
        for op in ops:
            # only the lanes this column set reads
            blocks = op._collect_blocks(needed)
            if blocks is None:
                raise MeshIneligible("no_columnar_form")
            per_tablet.append(blocks)
        every = [b for blocks in per_tablet for b in blocks]
        text = dict_cols_needed(every, sorted(needed))
        if text is None:
            raise MeshIneligible("no_columnar_form")
        plan = None
        if text:
            # ONE plan over every tablet's blocks: the codes are global
            # over the shards
            plan = make_dict_plan(every, text)
            if plan is None:
                raise MeshIneligible("not_dictionary_encodable")
        shards: List[list] = [[] for _ in self.devices]
        for i, blocks in enumerate(per_tablet):
            shards[chip_of(i, len(ops), self.chips)].extend(blocks)
        try:
            return build_sharded_batch(
                self.mesh, shards, sorted(needed), dict_plan=plan,
                multi_version=not all(chunk_safe_mvcc(blocks)
                                      for blocks in per_tablet if blocks))
        except KeyError:
            raise MeshIneligible("no_columnar_form") from None

    def _batch(self, ops, needed):
        with _trace.TRACES.span("docdb.batch", child_only=True) as sp:
            key = self._cache_key(ops, needed)
            # a cached batch of these stores that holds more columns
            # serves this set by its lanes (Q6's are four of Q1's): no
            # second copy of them on the chips, and no build
            batch = self.cache.lookup(key, 1, chips=self.devices)
            miss = batch is None
            if miss:
                batch = self._build(ops, needed)
                self.cache.admit(key, batch, 1, chips=self.devices)
            else:
                batch = batch.narrowed(needed)
            if sp.sampled:
                from ..ops.device_batch import batch_bytes
                sp.set_tag("cache", "miss" if miss else "hit")
                sp.set_tag("rows", batch.n_rows)
                sp.set_tag("bytes", batch_bytes(batch))
                sp.set_tag("shards", batch.num_shards)
            return batch

    # -- the read ------------------------------------------------------------
    def read(self, req: ReadRequest, ops: List[DocReadOperation],
             allow_restart: bool = True) -> ReadResponse:
        """`read_steps`, the launch made on the calling thread."""
        return run_steps(self.read_steps(req, ops, allow_restart))

    def read_steps(self, req: ReadRequest, ops: List[DocReadOperation],
                   allow_restart: bool = True):
        """`req` (its `read_ht` set by the caller, one for all tablets)
        over the tablets of `ops`, which are a table's tablets on this
        server in partition order, as the steps of
        `DocReadOperation.execute_steps`: the one launch is yielded as a
        call and its result taken back.  Raises `MeshIneligible` where the
        caller has to serve tablet by tablet, `ReadRestartError` as the
        one-device route does."""
        with _trace.TRACES.span("docdb.read", child_only=True) as sp:
            try:
                resp = yield from self._read_steps(req, ops, allow_restart)
            except MeshIneligible as e:
                self._m_fallbacks.increment()
                sp.set_tag("route", "mesh_fallback")
                sp.set_tag("fallback", e.reason)
                raise
            sp.set_tag("route", "mesh")
            sp.set_tag("tablets", len(ops))
            return resp

    def _read_steps(self, req, ops, allow_restart):
        self.check(req, ops)
        needed: set = set()
        from ..ops.expr import referenced_columns
        if req.where is not None:
            referenced_columns(req.where, needed)
        for a in req.aggregates:
            if a.expr is not None:
                referenced_columns(a.expr, needed)
        if isinstance(req.group_by, DictGroupSpec):
            needed.update(req.group_by.cols)
        elif req.group_by is not None:
            needed.update(cid for cid, _, _ in req.group_by.cols)
        read_ht = req.read_ht if req.read_ht is not None else _MAX_HT
        batch = self._batch(ops, needed)
        if allow_restart and req.server_assigned_read_ht \
                and read_ht != _MAX_HT and batch.max_ht > read_ht:
            # a row written after the read time was chosen: only then
            # can one lie inside the uncertainty window.  The batch
            # holds every row of the stores (no memtable), so its
            # newest write time decides
            if batch.max_ht <= read_ht + _skew_window_ht():
                raise ReadRestartError(batch.max_ht)
            self._restart_window(ops, read_ht)

        def run(where, aggs, group):
            self._m_launches.increment()
            got = self.kernel.run(batch, where, aggs, group, read_ht)
            # ScanKernel.run's shape: (outs, counts, mask[, spill])
            return got[:2] + (None,) + got[2:]

        resp = yield from DocReadOperation.aggregate_on_batch_steps(
            req, batch, run)
        if resp is None:
            raise MeshIneligible("shape_or_spill")
        return resp

    @staticmethod
    def _restart_window(ops, read_ht: int) -> None:
        """The one-device route's whole-block check, over every member
        tablet's blocks: the slow path of a read whose batch holds rows
        newer than its read time plus the skew window."""
        for op in ops:
            op._walk_restart_window(op._collect_blocks(()) or [], read_ht)


def tablets_in_partition_order(peers: Sequence) -> list:
    """A table's tablet peers as the placement rule counts them."""
    return sorted(peers, key=lambda p: (p.tablet.partition.start
                                        if p.tablet.partition else b""))


__all__ = ["MeshIneligible", "MeshReader", "chip_of",
           "tablets_in_partition_order"]
