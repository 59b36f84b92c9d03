#!/usr/bin/env python
"""The quickest proof that the served path still starts on the chip.

One process, one chip: SQL -> tserver -> device at TPC-H SF1 (6,000,000
lineitem rows), through the entry points a user calls — the launcher's
objects, `SqlSession.execute`, the `flush` and `compact` RPCs.  Forces no
platform, probes nothing, sets no cache directory: it imports JAX, reads
`jax.devices()`, and goes.

    python chip_smoke.py [--sf 1.0] [--seed 0]

Four chips have one way in, the served one: a tserver started with
`tserver_device_chips=4` (docdb/mesh_read.py), which the benchmark's cell
`mesh4_q1_psum` runs (`python3 benchmark/run.py --workload mesh4_q1_psum`;
`--rehearse --rows 48000` on four of the CPU's virtual devices).

Every phase prints one JSON line; the LAST line of stdout is

    {"ok": true, "device": {"platform": "tpu", "kind": ..., "count": 1}}

and the exit code is 0 only with `"ok": true`.  A phase that fails ends the
run with `"ok": false` and a non-zero exit; a run on anything but a TPU
goes through the same phases (the CPU rehearsal) and can never say true.

Left out on purpose (README "Running on the chip"): joins, windows, vector
search, YCSB/TPC-C, and the forked-process cluster (its children are
pinned to the CPU because several processes cannot share one chip).
"""
from __future__ import annotations

import argparse
import asyncio
import json
import os
import shutil
import sys
import tempfile
import time
import traceback

import jax
import numpy as np

TABLETS = 4
BULK_SLICES = 4          # SSTs per tablet from the bulk route
INSERT_ROWS = 20_000     # through SQL INSERT (client -> Raft -> apply)
INSERT_BATCH = 500
SMALL_ROWS = 60_000      # second table: device vs interpreted row path

_DDL = ("CREATE TABLE {name} (rowid bigint, l_quantity double, "
        "l_extendedprice double, l_discount double, l_tax double, "
        "l_shipdate int, l_returnflag int, l_linestatus int, "
        "PRIMARY KEY (rowid)) WITH tablets = {tablets}")
_COLS = ("rowid", "l_quantity", "l_extendedprice", "l_discount", "l_tax",
         "l_shipdate", "l_returnflag", "l_linestatus")
# TPCH_Q6 / TPCH_Q1 (models/tpch.py) as SQL text; dates are day numbers
_Q6 = ("SELECT sum(l_extendedprice * l_discount) AS revenue FROM {name} "
       "WHERE l_shipdate >= 8766 AND l_shipdate < 9131 "
       "AND l_discount BETWEEN 0.05 AND 0.07 AND l_quantity < 24")
_Q1 = ("SELECT l_returnflag, l_linestatus, sum(l_quantity) AS sum_qty, "
       "sum(l_extendedprice) AS sum_base_price, "
       "sum(l_extendedprice * (1 - l_discount)) AS sum_disc_price, "
       "sum(l_extendedprice * (1 - l_discount) * (1 + l_tax)) "
       "AS sum_charge, count(*) AS count_order FROM {name} "
       "WHERE l_shipdate <= 10471 GROUP BY l_returnflag, l_linestatus")


def require(ok, *detail) -> None:
    """A check that survives `python -O`, which removes asserts."""
    if not ok:
        raise AssertionError(" ".join(map(str, detail)) or "check failed")


class Smoke:
    def __init__(self, sf: float, seed: int):
        self.sf, self.seed = sf, seed
        self.dev = jax.devices()[0]
        self.phase = "import"
        self.compile_secs: list = []     # every backend compile, seconds
        jax.monitoring.register_event_duration_secs_listener(self._on_event)
        self.root = tempfile.mkdtemp(prefix="ybtpu-chip-smoke-")

    # -- reporting ---------------------------------------------------------
    def _on_event(self, name, secs, **kw):
        if name.endswith("backend_compile_duration"):
            self.compile_secs.append(secs)
            if secs >= 1.0:
                self.say({"compile_s": round(secs, 2), "in": self.phase})

    @staticmethod
    def say(obj) -> None:
        print(json.dumps(obj), flush=True)

    def bytes_in_use(self):
        stats = self.dev.memory_stats()
        return stats.get("bytes_in_use") if stats else None

    def done(self, t0: float, n0: int, **fields) -> None:
        mem = self.bytes_in_use()
        self.say({"phase": self.phase, "ok": True,
                  "seconds": round(time.time() - t0, 3),
                  "compiles": len(self.compile_secs) - n0,
                  "device_bytes_in_use":
                      mem if mem is not None else "not reported",
                  **fields})

    def begin(self, phase: str):
        self.phase = phase
        return time.time(), len(self.compile_secs)

    # -- phase 1: start ----------------------------------------------------
    async def start(self) -> None:
        t0, n0 = self.begin("start")
        from yugabyte_db_tpu.client import YBClient
        from yugabyte_db_tpu.docdb import hotpath
        from yugabyte_db_tpu.master import Master
        from yugabyte_db_tpu.ql.executor import SqlSession
        from yugabyte_db_tpu.storage import native_lib
        from yugabyte_db_tpu.tserver import TabletServer
        require(native_lib.available(),
                f"native storage library: {native_lib.last_build_error}")
        require(hotpath.load() is not None,
                f"native hot path: {hotpath.last_build_error}")
        # the launcher's objects (tools/ybtpud.py serve), RF1, one tserver
        self.master = Master(f"{self.root}/master")
        maddr = await self.master.start()
        self.ts = TabletServer("ts-0", f"{self.root}/ts-0",
                               master_addrs=[maddr])
        await self.ts.start()
        for _ in range(200):
            await self.ts._heartbeat_once()
            if len(self.master.live_tservers()) >= 1:
                break
            await asyncio.sleep(0.05)
        require(len(self.master.live_tservers()) == 1)
        self.client = YBClient(maddr)
        self.sql = SqlSession(self.client)
        from yugabyte_db_tpu.ops import device_batch, scan
        from yugabyte_db_tpu.utils import flags
        on_cpu = jax.default_backend() == "cpu"
        self.done(t0, n0, native_lib=True, hotpath=True, arms={
            "value_lanes": str(device_batch._float64_device_dtype()),
            "group_strategy": scan._group_strategy(),
            "sum_magnitude_cap": "f64" if on_cpu else "f32",
            "compaction_merge": "native" if on_cpu else "device",
            "pallas_scan": ("on" if flags.get("tpu_pallas_scan")
                            else "off (opt-in flag)")})

    # -- phase 2: load -----------------------------------------------------
    async def _peers(self, table: str):
        ct = await self.client._table(table, refresh=True)
        return ct, [self.ts.peers[l.tablet_id] for l in ct.locations]

    async def _insert(self, table: str, data: dict) -> None:
        n = len(data["rowid"])
        for s in range(0, n, INSERT_BATCH):
            rows = ", ".join(
                "(" + ", ".join(repr(data[c][i].item()) for c in _COLS) + ")"
                for i in range(s, min(s + INSERT_BATCH, n)))
            await self.sql.execute(
                f"INSERT INTO {table} ({', '.join(_COLS)}) VALUES {rows}")

    async def _flush(self, table: str) -> None:
        from yugabyte_db_tpu.tools.ybtpu_admin import \
            MAINTENANCE_RPC_TIMEOUT_S
        ct, _ = await self._peers(table)
        for l in ct.locations:
            await self.client._call_leader(
                ct, l.tablet_id, "flush", {"tablet_id": l.tablet_id},
                timeout=MAINTENANCE_RPC_TIMEOUT_S)

    async def _create(self, table: str, bulk: dict, inserted: dict):
        """CREATE TABLE through SQL, the bulk rows through Tablet.bulk_load
        on the serving peers in BULK_SLICES slices per tablet — slice 0
        again at a later hybrid time with identical values, so every
        tablet holds several SSTs and some keys have two versions — and
        `inserted` through SQL INSERT + the flush RPC.  Not ANALYZEd."""
        await self.sql.execute(_DDL.format(name=table, tablets=TABLETS))
        _, peers = await self._peers(table)
        n = len(bulk["rowid"])
        edges = np.linspace(0, n, BULK_SLICES + 1).astype(int)
        slices = [{k: v[a:b] for k, v in bulk.items()}
                  for a, b in zip(edges[:-1], edges[1:])]
        loaded = 0
        for sl in slices + slices[:1]:
            for p in peers:
                loaded += p.tablet.bulk_load(sl)
        require(loaded == n + len(slices[0]["rowid"]), loaded)
        await self._insert(table, inserted)
        await self._flush(table)
        ssts = [len(p.tablet.regular.ssts) for p in peers]
        require(min(ssts) >= BULK_SLICES + 2, ssts)
        return ssts

    async def load(self) -> None:
        t0, n0 = self.begin("load")
        from yugabyte_db_tpu.models.tpch import (ROWS_PER_SF,
                                                 generate_lineitem)
        bulk = generate_lineitem(self.sf, self.seed)
        n = len(bulk["rowid"])
        # one row to spare: int(rows_per_sf * sf) may round down
        extra = generate_lineitem(
            (INSERT_ROWS + SMALL_ROWS + 1) / ROWS_PER_SF, self.seed + 1)
        self.inserted = {k: v[:INSERT_ROWS] for k, v in extra.items()}
        self.inserted["rowid"] = self.inserted["rowid"] + n
        self.data = {k: np.concatenate([bulk[k], self.inserted[k]])
                     for k in _COLS}
        t_gen = time.time() - t0
        ssts = await self._create("lineitem", bulk, self.inserted)
        # ANALYZE gives Q1's GROUP BY its declared domains (GroupSpec)
        await self.sql.execute("ANALYZE lineitem")
        # the second, small table: answers are compared with the
        # interpreted row path there (minutes at 6M rows)
        small = {k: v[INSERT_ROWS:INSERT_ROWS + SMALL_ROWS]
                 for k, v in extra.items()}
        small["rowid"] = np.arange(SMALL_ROWS, dtype=np.int64)
        cut = SMALL_ROWS - 2_000
        self.small = small
        await self._create("lineitem_small",
                           {k: v[:cut] for k, v in small.items()},
                           {k: v[cut:] for k, v in small.items()})
        self.done(t0, n0, rows=n + INSERT_ROWS, bulk_rows=n,
                  inserted_rows=INSERT_ROWS, tablets=TABLETS,
                  ssts_per_tablet=ssts, generate_s=round(t_gen, 3))

    # -- phase 3: query ----------------------------------------------------
    @staticmethod
    def _reference(data: dict) -> dict:
        """numpy_reference (models/tpch.py) plus the two Q1 sums it
        leaves out, straight from the arrays."""
        from yugabyte_db_tpu.models.tpch import (TPCH_Q1, TPCH_Q6,
                                                 numpy_reference)
        q1 = numpy_reference(TPCH_Q1, data)
        m = data["l_shipdate"] <= 10471
        gid = data["l_returnflag"] + 3 * data["l_linestatus"]
        disc = data["l_extendedprice"] * (1 - data["l_discount"])
        charge = disc * (1 + data["l_tax"])
        return {"q6": numpy_reference(TPCH_Q6, data),
                "q1": {g: q1[g] + (disc[m & (gid == g)].sum(),
                                   charge[m & (gid == g)].sum())
                       for g in range(6)}}

    @staticmethod
    def _check(name: str, ref: dict, rows) -> None:
        """The contract bench.py check_q1 documents: counts and
        integer-valued sums exact, fractional sums 1e-5 relative."""
        def close(got, want):
            return abs(got - want) <= 1e-5 * max(abs(want), 1e-9)
        if name == "q6":
            require(len(rows) == 1 and close(rows[0]["revenue"], ref["q6"]),
                    rows, ref["q6"])
            return
        require(len(rows) == 6, rows)
        for r in rows:
            qty, price, cnt, disc, charge = ref["q1"][
                r["l_returnflag"] + 3 * r["l_linestatus"]]
            require(r["count_order"] == cnt, r, cnt)
            require(r["sum_qty"] == qty, r, qty)
            require(close(r["sum_base_price"], price), r, price)
            require(close(r["sum_disc_price"], disc), r, disc)
            require(close(r["sum_charge"], charge), r, charge)

    async def _timed(self, sql: str) -> dict:
        """One statement: its rows, seconds, backend compiles, scan-kernel
        compiles and client RPCs (more than one per tablet = a deadline
        retry)."""
        from yugabyte_db_tpu.docdb.operations import _SHARED_KERNEL
        sent = self.client.messenger
        t0, n0, k0, r0 = time.time(), len(self.compile_secs), \
            _SHARED_KERNEL.compiles, sent.calls_sent
        rows = (await self.sql.execute(sql)).rows
        return {"rows": rows, "s": time.time() - t0,
                "compiles": len(self.compile_secs) - n0,
                "kernel_compiles": _SHARED_KERNEL.compiles - k0,
                "rpcs": sent.calls_sent - r0}

    async def _run_queries(self, table: str, ref: dict, label: str):
        """Q6 and Q1 three times each: run 1 is cold (compiles, batch
        build), runs 2 and 3 must compile nothing."""
        out = {}
        for name, sql in (("q6", _Q6), ("q1", _Q1)):
            runs = [await self._timed(sql.format(name=table))
                    for _ in range(3)]
            for r in runs:
                self._check(name, ref, r.pop("rows"))
            require(all(r["compiles"] == 0 and r["kernel_compiles"] == 0
                        for r in runs[1:]),
                    f"{label} {name}: warm runs compiled {runs[1:]}")
            out[name] = {"cold_s": round(runs[0]["s"], 3),
                         "warm_s": [round(r["s"], 4) for r in runs[1:]],
                         "cold_compiles": runs[0]["compiles"],
                         "kernel_compiles": runs[0]["kernel_compiles"],
                         "rpcs": [r["rpcs"] for r in runs]}
        return out

    def _device_evidence(self) -> dict:
        """Where the cached device batches sit, by `.devices()` — not
        the `backend` route label of a response."""
        from yugabyte_db_tpu.tablet.tablet import _DEVICE_CACHE
        with _DEVICE_CACHE._lock:
            batches = [b for b, _ in _DEVICE_CACHE._map.values()]
        require(batches, "no device batch was cached: nothing ran on device")
        devs = set()
        for b in batches:
            devs |= set(b.valid.devices())
            for c in b.cols.values():
                devs |= set(c.devices())
        require(devs == {self.dev}, devs)
        return {"cached_batches": len(batches),
                "batch_rows": sorted({b.padded_rows for b in batches}),
                "value_dtypes": sorted({str(c.dtype) for b in batches
                                        for c in b.cols.values()}),
                "batches_on": [str(d) for d in devs]}

    async def query(self) -> None:
        t0, n0 = self.begin("query")
        from yugabyte_db_tpu.docdb.operations import _SHARED_KERNEL
        from yugabyte_db_tpu.utils import flags
        mem0, k0 = self.bytes_in_use(), _SHARED_KERNEL.compiles
        self.ref = self._reference(self.data)
        timings = await self._run_queries("lineitem", self.ref, "sf")
        require(_SHARED_KERNEL.compiles > k0, "no scan kernel was built")
        mem1 = self.bytes_in_use()
        if mem1 is not None:
            require(mem1 > mem0, mem0, mem1)
        evidence = self._device_evidence()
        # every acknowledged INSERT is read back, value for value
        ins, got = self.inserted, {}
        ids = ins["rowid"].tolist()
        for s in range(0, len(ids), INSERT_BATCH):
            res = await self.sql.execute(
                f"SELECT {', '.join(_COLS)} FROM lineitem WHERE rowid IN "
                f"({', '.join(map(str, ids[s:s + INSERT_BATCH]))})")
            got.update((r["rowid"], r) for r in res.rows)
        require(len(got) == len(ids), len(got), len(ids))
        for i, rid in enumerate(ids):
            require(all(got[rid][c] == ins[c][i] for c in _COLS), got[rid])
        # the small table: the interpreted row path (pushdown off) is the
        # plain reference — checked against numpy, then the device path
        # is held to its answers
        old = flags.get("tpu_pushdown_enabled")
        flags.set_flag("tpu_pushdown_enabled", False)
        try:
            k1 = _SHARED_KERNEL.compiles
            q6, q1 = [(await self.sql.execute(
                sql.format(name="lineitem_small"))).rows
                for sql in (_Q6, _Q1)]
            require(_SHARED_KERNEL.compiles == k1, "interpreted path compiled")
        finally:
            flags.set_flag("tpu_pushdown_enabled", old)
        small_ref = self._reference(self.small)
        self._check("q6", small_ref, q6)
        self._check("q1", small_ref, q1)
        interpreted = {"q6": q6[0]["revenue"], "q1": {
            r["l_returnflag"] + 3 * r["l_linestatus"]: (
                r["sum_qty"], r["sum_base_price"], r["count_order"],
                r["sum_disc_price"], r["sum_charge"]) for r in q1}}
        # not ANALYZEd yet: Q1's GROUP BY takes the sort + segment route
        # (HashGroupSpec), what a user without statistics gets
        self._check("q1", interpreted, (await self.sql.execute(
            _Q1.format(name="lineitem_small"))).rows)
        require(any(len(k) > 2 and k[2] and k[2][0] == "HashGroupSpec"
                    for k in _SHARED_KERNEL._cache),
                "no sort-grouped (HashGroupSpec) kernel was built")
        await self.sql.execute("ANALYZE lineitem_small")
        await self._run_queries("lineitem_small", interpreted, "small")
        self.done(t0, n0, **timings, read_back=len(ids),
                  interpreted_rows=SMALL_ROWS, **evidence,
                  device_bytes_before=mem0 if mem0 is not None
                  else "not reported")

    # -- phase 4: compact --------------------------------------------------
    async def compact(self) -> None:
        t0, n0 = self.begin("compact")
        from yugabyte_db_tpu.ops.compaction import kernel_cache_stats
        from yugabyte_db_tpu.tools.ybtpu_admin import \
            MAINTENANCE_RPC_TIMEOUT_S
        k0 = kernel_cache_stats()
        ct, peers = await self._peers("lineitem")
        per_tablet = []
        for l in ct.locations:          # what `ybtpu_admin compact_table`
            t1 = time.time()            # sends, flags at their defaults
            await self.client._call_leader(
                ct, l.tablet_id, "compact", {"tablet_id": l.tablet_id},
                timeout=MAINTENANCE_RPC_TIMEOUT_S)
            per_tablet.append(round(time.time() - t1, 3))
        k1 = kernel_cache_stats()
        device_merge = jax.default_backend() != "cpu"
        launches = k1["calls"] - k0["calls"]
        require((launches > 0) == device_merge, k0, k1)
        ssts = [len(p.tablet.regular.ssts) for p in peers]
        require(ssts == [1] * TABLETS, ssts)
        t_c = time.time() - t0
        timings = await self._run_queries("lineitem", self.ref, "compacted")
        self.done(t0, n0, compact_s=round(t_c, 3),
                  per_tablet_s=per_tablet, ssts_per_tablet=ssts,
                  merge_kernel_launches=launches,
                  merge_kernel_compiles=k1["compiles"] - k0["compiles"],
                  **timings, **self._device_evidence())

    # -- phase 5: shut down ------------------------------------------------
    async def shutdown(self) -> None:
        t0, n0 = self.begin("shutdown")
        if getattr(self, "client", None) is not None:
            await self.client.messenger.shutdown()
        if getattr(self, "ts", None) is not None:
            await self.ts.shutdown()
        if getattr(self, "master", None) is not None:
            await self.master.shutdown()
        shutil.rmtree(self.root, ignore_errors=True)
        self.done(t0, n0)
        jax.monitoring.unregister_event_duration_listener(self._on_event)

    async def run(self) -> None:
        try:
            await self.start()
            await self.load()
            await self.query()
            await self.compact()
        finally:
            await self.shutdown()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--sf", type=float, default=1.0,
                    help="TPC-H scale factor (1.0 = 6,000,000 rows)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import yugabyte_db_tpu  # noqa: F401 — x64, platform, compile cache
    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    cache_dir = jax.config.jax_compilation_cache_dir

    def cache_files():
        return (len(os.listdir(cache_dir))
                if cache_dir and os.path.isdir(cache_dir) else 0)

    Smoke.say({"phase": "devices", **device, "sf": args.sf,
               "seed": args.seed, "compile_cache_dir": cache_dir,
               "compile_cache_files": cache_files()})
    ok = True
    try:
        asyncio.run(Smoke(args.sf, args.seed).run())
    except Exception:   # noqa: BLE001 — reported, and the run FAILS
        traceback.print_exc()
        ok = False
    Smoke.say({"phase": "end", "ok": ok, "compile_cache_dir": cache_dir,
               "compile_cache_files": cache_files()})
    ok = ok and device["platform"] == "tpu"
    print(json.dumps({"ok": ok, "device": device}), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
