"""The system under test, in this process: the launcher's objects (master,
one tserver, client, SQL session) and the calls every driver and loader
shares.  What a table holds, and how it is filled, belongs to the
configuration's loader (`benchmark/loaders/<loader>.py`).

One process holds the chip, so master, tserver, client and load
generator all live here.
"""
from __future__ import annotations

import asyncio
import shutil
import tempfile


def require(ok, *detail) -> None:
    """A check that survives `python -O`, which removes asserts."""
    if not ok:
        raise AssertionError(" ".join(map(str, detail)) or "check failed")


def batch_devices(batch) -> set:
    """The devices a cached batch sits on, by `.devices()` of every lane
    it holds.  A `DeviceBatch` has one device; the lanes of a
    `parallel.distributed_scan.ShardedBatch` are arrays sharded over its
    mesh, whose `.devices()` is every device that holds a shard."""
    lanes = [getattr(batch, name, None)
             for name in ("valid", "ht", "next_ht", "tombstone")]
    for group in ("cols", "nulls"):
        lanes += list(getattr(batch, group, {}).values())
    return {d for lane in lanes if hasattr(lane, "devices")
            for d in lane.devices()}


def batches_on(batches: list, devices: list) -> bool:
    """Every batch, and every shard of a sharded one, sits on the cell's
    devices, and together they cover all of them: a four-chip cell whose
    table sits on one chip is not on its devices."""
    held, cell = [batch_devices(b) for b in batches], set(devices)
    return bool(held) and all(h and h <= cell for h in held) \
        and set().union(*held) == cell


class Cluster:
    """RF1, one tserver.  `flags` are the program's runtime flags the
    configuration states (the deployment's own settings); `devices` are
    the chips the cell was given; `data` is whatever the loader returns."""

    def __init__(self, flags: dict, devices: list):
        self.flags, self.devices = dict(flags), list(devices)
        self.data = None
        self.sessions: list = []    # the window's clients, made in warm-up
        self.master = self.ts = self.client = self.sql = None
        self._flags_before: dict = {}
        self.root = tempfile.mkdtemp(prefix="ybtpu-benchmark-")

    # -- start / stop ------------------------------------------------------
    async def start(self) -> None:
        from yugabyte_db_tpu.client import YBClient
        from yugabyte_db_tpu.docdb import hotpath
        from yugabyte_db_tpu.master import Master
        from yugabyte_db_tpu.storage import native_lib
        from yugabyte_db_tpu.tserver import TabletServer
        from yugabyte_db_tpu.utils import flags
        self._flags_before = {k: flags.get(k) for k in self.flags}
        for name, value in self.flags.items():
            flags.set_flag(name, value)
        require(native_lib.available(),
                f"native storage library: {native_lib.last_build_error}")
        require(hotpath.load() is not None,
                f"native hot path: {hotpath.last_build_error}")
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
        self.sql = self.session()

    def session(self):
        from yugabyte_db_tpu.ql.executor import SqlSession
        return SqlSession(self.client)

    async def shutdown(self) -> None:
        try:
            if self.client is not None:
                await self.client.messenger.shutdown()
            if self.ts is not None:
                await self.ts.shutdown()
            if self.master is not None:
                await self.master.shutdown()
        finally:
            from yugabyte_db_tpu.utils import flags
            for name, value in self._flags_before.items():
                flags.set_flag(name, value)
            shutil.rmtree(self.root, ignore_errors=True)

    # -- the served operations ---------------------------------------------
    async def peers(self, table: str):
        ct = await self.client._table(table, refresh=True)
        return ct, [self.ts.peers[l.tablet_id] for l in ct.locations]

    async def maintenance(self, method: str, ct, tablet_id: str):
        """The `flush` / `compact` RPC as `ybtpu_admin` sends it."""
        from yugabyte_db_tpu.tools.ybtpu_admin import \
            MAINTENANCE_RPC_TIMEOUT_S
        return await self.client._call_leader(
            ct, tablet_id, method, {"tablet_id": tablet_id},
            timeout=MAINTENANCE_RPC_TIMEOUT_S)

    @staticmethod
    def sst_files(peers) -> list:
        """Per tablet, the `file_size` of each SST a compaction would
        read now."""
        return [[r.file_size for r in p.tablet.regular.ssts] for p in peers]

    def device_evidence(self) -> dict:
        """Where the cached device batches sit, by `.devices()` — not the
        `backend` route label of a response."""
        from yugabyte_db_tpu.tablet.tablet import _DEVICE_CACHE
        with _DEVICE_CACHE._lock:
            batches = [b for b, _ in _DEVICE_CACHE._map.values()]
        held = set().union(*map(batch_devices, batches))
        return {"cached_batches": len(batches),
                "batch_rows": sorted({b.padded_rows for b in batches}),
                "value_dtypes": sorted({str(c.dtype) for b in batches
                                        for c in b.cols.values()}),
                "batches_on": sorted(str(d) for d in held),
                "on_device": batches_on(batches, self.devices)}
