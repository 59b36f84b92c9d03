"""A table larger than the device cache on one chip: a read whose batch is
not cached builds it as a step of the read, on the launch pool beside the
event loop; the cache keeps the batches it holds when a scan cycles over
more than fit, and the rest pass through (built, used by their read and
released); at most `BUILDS_IN_FLIGHT` reads of a server hold a batch they
built; the `next_ht` link of a store's contents is made once for all its
column sets; the capacity is the deployment's (`tserver_device_cache_bytes`).
Each is held to the answers of the reference and of a cache that holds the
whole table."""
import asyncio
import gc
import random
import tempfile
import threading
import types
import weakref

import jax
import pytest

from benchmark import tpch
from tests.test_store_facts import N, OLD, _rows, count_req, make_tablet
from yugabyte_db_tpu.docdb import RowOp, WriteRequest, operations
from yugabyte_db_tpu.ops import device_batch
from yugabyte_db_tpu.ops.device_batch import DeviceBlockCache, chip_capacity
from yugabyte_db_tpu.tablet import tablet as tablet_mod
from yugabyte_db_tpu.tablet.tablet import _DEVICE_CACHE
from yugabyte_db_tpu.utils import flags
from yugabyte_db_tpu.utils.trace import TRACES

TABLE = tpch.TABLE
ORDERS, ROWS, SEED, TABLETS = 4000, 16000, 23, 8
PADDED = 4096                 # 2,000 rows a tablet, in the smallest bucket
Q1_BYTES = 65 * PADDED        # seven value lanes, their nulls, the MVCC lanes


class Cold:
    """LINEITEM in 8 tablets of 2 bulk-loaded SSTs each (every batch
    linked), on one tserver whose device cache holds `capacity` bytes."""

    def __init__(self, root: str, capacity: int):
        self.root, self.capacity = root, capacity

    async def __aenter__(self):
        from yugabyte_db_tpu.ql.executor import SqlSession
        from yugabyte_db_tpu.tools.mini_cluster import MiniCluster
        flags.set_flag("device_float_dtype", "float64")
        flags.set_flag("tpu_min_rows_for_pushdown", 1)
        flags.set_flag("tserver_device_cache_bytes", self.capacity)
        _DEVICE_CACHE.clear()
        self.mc = await MiniCluster(self.root, num_tservers=1).start()
        self.ts = self.mc.tservers[0]
        self.client = self.mc.client()
        self.sql = SqlSession(self.client)
        await self.sql.execute(tpch.DDL.format(name=TABLE, tablets=TABLETS))
        self.ct = await self.client._table(TABLE, refresh=True)
        self.bulk = tpch.generate_lineitem(ORDERS, ROWS, SEED)
        for a, b in ((0, ROWS // 2), (ROWS // 2, ROWS)):
            sl = {k: v[a:b] for k, v in self.bulk.items()}
            for p in self.peers():
                p.tablet.bulk_load(sl, block_rows=512)
        await self.sql.execute(f"ANALYZE {TABLE}")
        return self

    async def __aexit__(self, *exc):
        await self.client.messenger.shutdown()
        await self.mc.shutdown()
        _DEVICE_CACHE.clear()
        for f in ("device_float_dtype", "tpu_min_rows_for_pushdown",
                  "tserver_device_cache_bytes"):
            flags.REGISTRY.reset(f)

    def peers(self) -> list:
        return [self.ts.peers[l.tablet_id] for l in self.ct.locations]

    async def query(self, q: str, traced: bool = False):
        """(rows, the statement's `docdb.batch` span tags)."""
        if not traced:
            return (await self.sql.execute(tpch.SQL[q].format(
                name=TABLE))).rows, None
        with TRACES.trace(f"cold-{q}") as root:
            rows = (await self.sql.execute(tpch.SQL[q].format(
                name=TABLE))).rows
        return rows, [s.tags for s in TRACES.finished()
                      if s.trace_id == root.trace_id
                      and s.name == "docdb.batch"]


def _run(capacity, body):
    async def go():
        with tempfile.TemporaryDirectory() as root:
            async with Cold(root, capacity) as s:
                return await body(s)
    return asyncio.run(go())


def _same(a, b) -> bool:
    """Two statements' rows, value for value and bit for bit."""
    return [sorted(r.items()) for r in a] == [sorted(r.items()) for r in b]


def test_a_cycle_over_more_batches_than_fit_keeps_a_resident_set():
    """Q6/Q1 alternating, the cache holding two of a query's eight
    batches: every answer is the reference's and, bit for bit, what a
    cache that holds the whole table answers; after the first cycle
    every statement hits, the entries held then are held to the end, the
    rest pass; each read's `docdb.batch` says which."""
    async def whole(s):
        return [(await s.query(q))[0] for q in ("q6", "q1")]
    warm = _run(1 << 30, whole)

    async def cold(s):
        ref = tpch.reference(s.bulk)
        seen, resident = [], None
        for i in range(8):
            q = ("q6", "q1")[i % 2]
            rows, tags = await s.query(q, traced=True)
            assert not any(v for v in tpch.compare(q, rows, ref).values()
                           if isinstance(v, (int, float)) and v > 1e-6), q
            assert _same(rows, warm[i % 2]), q
            assert len(tags) == TABLETS
            assert all(t["cache"] in ("hit", "miss") for t in tags)
            assert all(t["admit"] in ("resident", "passed")
                       for t in tags if t["cache"] == "miss")
            assert all("admit" not in t for t in tags if t["cache"] == "hit")
            seen.append(tags)
            if i == 1:
                resident = set(_DEVICE_CACHE._map)
            assert _DEVICE_CACHE._bytes <= s.capacity
        for tags in seen[2:]:
            hits = sum(t["cache"] == "hit" for t in tags)
            assert 1 <= hits < TABLETS
            assert all(t["admit"] == "passed" for t in tags
                       if t["cache"] == "miss")
        assert resident and set(_DEVICE_CACHE._map) == resident
        assert _DEVICE_CACHE.passed > 0
    _run(int(2.5 * Q1_BYTES), cold)


def test_passed_batches_leave_the_device_with_their_read():
    async def body(s):
        for q in ("q6", "q1", "q6", "q1"):
            await s.query(q)
        gc.collect()
        # by buffer: a lane's shard (`batch_placement`) is a second
        # array object over the same buffer
        lanes = {a.unsafe_buffer_pointer(): a.nbytes
                 for a in jax.live_arrays() if a.shape == (PADDED,)}
        cached = sum(size for _, size in _DEVICE_CACHE._map.values())
        assert _DEVICE_CACHE.passed > 0 and cached > 0
        assert sum(lanes.values()) == cached
    _run(int(2.5 * Q1_BYTES), body)


def test_at_most_builds_in_flight_batches_are_held_by_reads(monkeypatch):
    """Eight tablet reads at once, none of whose batches the cache
    admits: the server's reads hold at most `BUILDS_IN_FLIGHT` built
    batches at a time, and the rest wait for a slot."""
    alive, most = [0], [0]
    lock = threading.Lock()

    def gone():
        with lock:
            alive[0] -= 1

    real = operations.build_batch

    def counted(*a, **k):
        batch = real(*a, **k)
        with lock:
            alive[0] += 1
            most[0] = max(most[0], alive[0])
        weakref.finalize(batch, gone)
        return batch

    monkeypatch.setattr(operations, "build_batch", counted)

    async def body(s):
        rows, tags = await s.query("q1", traced=True)
        assert rows and all(t["admit"] == "passed" for t in tags)
        waits = [x.tags["queue_ms"] for x in TRACES.finished()
                 if x.name == "tserver.build_offload"][-TABLETS:]
        assert len(waits) == TABLETS
        gc.collect()
        assert alive[0] == 0
    _run(1, body)
    assert 1 < most[0] <= tablet_mod.BUILDS_IN_FLIGHT < TABLETS


def test_the_loop_serves_other_rpcs_while_a_batch_builds(monkeypatch):
    """A build held on an event: a point read through another session
    and the tserver's heartbeat complete meanwhile, so the build is not
    on the event loop."""
    entered, release = threading.Event(), threading.Event()
    real, released = operations.build_batch, []

    def held(*a, **k):
        entered.set()
        # a build on the loop would hold the loop until this times out
        released.append(release.wait(5))
        return real(*a, **k)

    async def body(s):
        order = []
        monkeypatch.setattr(operations, "build_batch", held)
        scan = asyncio.ensure_future(s.query("q6"))
        while not entered.is_set():
            await asyncio.sleep(0.005)
        key = int(s.bulk["l_orderkey"][0])
        got = await s.client.get(TABLE, {"l_orderkey": key,
                                         "l_linenumber": 1})
        await s.ts._heartbeat_once()
        order.append("others")
        release.set()
        rows, _ = await scan
        order.append("scan")
        assert order == ["others", "scan"] and all(released)
        assert got["l_quantity"] == s.bulk["l_quantity"][0]
        assert rows[0]["revenue"] == pytest.approx(
            tpch.reference(s.bulk)["q6"], rel=1e-12)
    try:
        _run(1 << 30, body)
    finally:
        release.set()


def test_the_version_link_is_made_once_for_every_column_set():
    async def body(s):
        for p in s.peers():          # the facts, and the link, made anew
            p.tablet.drop_device_state()
        _, q6 = await s.query("q6", traced=True)
        _, q1 = await s.query("q1", traced=True)
        links = [x.tags["kept"] for x in TRACES.finished()
                 if x.name == "batch.version_link"][-2 * TABLETS:]
        assert links == [False] * TABLETS + [True] * TABLETS
        assert all(t["cache"] == "miss" for t in q6 + q1)
    _run(1 << 30, body)


# --- the cache's policy, by hand ---------------------------------------------
def _entry(size):
    return types.SimpleNamespace(size=size)


@pytest.fixture
def sized(monkeypatch):
    """Entries whose placement is their `size` on one chip."""
    monkeypatch.setattr(device_batch, "batch_placement",
                        lambda b: {"chip": b.size})


def _ask(cache, key, size, part=None):
    got = cache.lookup(key, part)
    if got is None:
        got = _entry(size)
        return cache.admit(key, got, part)
    return "hit"


def test_a_cyclic_scan_keeps_what_it_holds(sized):
    cache = DeviceBlockCache(capacity_bytes=30)
    keys = [("t", i) for i in range(8)]
    first = [_ask(cache, k, 10) for k in keys]
    assert first == [True] * 8          # first asked, none hit: plain LRU
    held = set(cache._map)
    assert held == set(keys[-3:])
    for _ in range(3):
        got = [_ask(cache, k, 10) for k in keys]
        assert got == [False] * 5 + ["hit"] * 3
        assert set(cache._map) == held
    assert cache.passed == 15


def test_an_entry_idle_for_twice_the_reuse_distance_makes_room(sized):
    cache = DeviceBlockCache(capacity_bytes=20)
    assert _ask(cache, ("s",), 10) is True
    assert _ask(cache, ("x",), 10) is True
    assert _ask(cache, ("y",), 10) is True       # first asked: evicts LRU
    assert ("s",) not in cache._map
    for _ in range(4):
        assert _ask(cache, ("y",), 10) == "hit"
    # ("s",) was last asked for 7 lookups ago; ("x",) has idled 6
    assert _ask(cache, ("s",), 10) is False
    # asked again at once: ("x",) has idled more than twice that
    assert _ask(cache, ("s",), 10) is True
    assert set(cache._map) == {("s",), ("y",)}


def test_a_batch_of_more_columns_serves_and_replaces_one_of_fewer(sized):
    cache = DeviceBlockCache(capacity_bytes=20)
    wide, narrow = ("t", (1, 2), "ssts"), ("t", (1,), "ssts")
    assert _ask(cache, narrow, 10, 1) is True
    assert _ask(cache, narrow, 10, 1) == "hit"
    assert _ask(cache, ("b",), 10) is True
    assert _ask(cache, ("c",), 10) is True       # first asked: evicts LRU
    assert set(cache._map) == {narrow, ("c",)}   # ... of those not hit
    # asked for the first time, the wider batch may evict the one it
    # covers, hit or not, and nothing else
    assert _ask(cache, wide, 10, 1) is True
    assert set(cache._map) == {("c",), wide}
    assert cache.lookup(narrow, 1) is cache._map[wide][0]


def _statement(cache, keys, size, part=None, rnd=None) -> int:
    """One statement's reads as a server runs them: every read looks its
    batch up, then the misses' builds are offered as they finish (in
    turn, or in `rnd`'s order); its hits."""
    got = [cache.lookup(k, part) for k in keys]
    missed = [k for k, g in zip(keys, got) if g is None]
    if rnd is not None:
        rnd.shuffle(missed)
    for k in missed:
        cache.admit(k, _entry(size), part)
    return sum(g is not None for g in got)


@pytest.mark.parametrize("order", [None, 1, 2, 3, 4, 5])
def test_a_statement_seen_once_does_not_push_out_what_was_hit(sized, order):
    """The warm-up asks for each batch once — ANALYZE's, then Q1's, then
    Q6's, which Q1's of the same tablet serve — and leaves the set the
    Q6/Q1 cycle keeps: the first cycle after it hits as every later one
    does, and holds what it held, in whatever order the builds finish
    (a batch that left in the statement that built it was last asked for
    at its lookup, not at its admission)."""
    rnd = None if order is None else random.Random(order)
    cache = DeviceBlockCache(capacity_bytes=39)
    tablets = range(8)
    analyze = [(t, (0, 9)) for t in tablets]
    q1 = [(t, (1, 2, 3, 4, 5, 6, 7)) for t in tablets]
    q6 = [(t, (1, 2, 3, 4)) for t in tablets]
    _statement(cache, analyze, 7, 1, rnd)
    _statement(cache, q1, 10, 1, rnd)
    assert _statement(cache, q6, 7, 1, rnd) == 3  # Q1's last three serve
    cycles, held = [], []
    for _ in range(4):
        cycles.append((_statement(cache, q6, 7, 1, rnd),
                       _statement(cache, q1, 10, 1, rnd)))
        held.append(set(cache._map))
    assert cycles == [(4, 3)] * 4
    assert held == held[:1] * 4
    assert sorted(len(k[1]) for k in held[0]) == [4, 7, 7, 7]


# --- the capacity a deployment states ---------------------------------------
def test_chip_capacity_is_half_the_smallest_chip():
    chip = lambda b: types.SimpleNamespace(
        memory_stats=lambda: {"bytes_limit": b})
    assert chip_capacity([chip(16 << 30)]) == 8 << 30
    assert chip_capacity([chip(16 << 30), chip(12 << 30)]) == 6 << 30
    assert chip_capacity([types.SimpleNamespace(memory_stats=lambda: None)]
                         ) == 2 << 30


@pytest.mark.parametrize("chips", [1, 4])
@pytest.mark.parametrize("stated", [-1, 123456789])
def test_the_flag_sets_the_capacity_and_shutdown_restores_it(
        monkeypatch, chips, stated):
    if len(jax.devices()) < chips:
        pytest.skip("needs 4 devices")
    asked = []

    def capacity(devices, share=0.5, default=2 << 30):
        asked.append(list(devices))
        return 777

    monkeypatch.setattr(device_batch, "chip_capacity", capacity)
    before = _DEVICE_CACHE.capacity

    async def body():
        from yugabyte_db_tpu.tserver import TabletServer
        with tempfile.TemporaryDirectory() as root:
            ts = TabletServer("ts-cap", f"{root}/ts")
            await ts.start()
            held = _DEVICE_CACHE.capacity
            await ts.shutdown()
            return held

    flags.set_flag("tserver_device_chips", chips)
    flags.set_flag("tserver_device_cache_bytes", stated)
    try:
        held = asyncio.run(body())
    finally:
        flags.REGISTRY.reset("tserver_device_chips")
        flags.REGISTRY.reset("tserver_device_cache_bytes")
    assert held == (777 if stated == -1 else stated)
    assert asked == ([jax.devices()[:chips]] if stated == -1 else [])
    assert _DEVICE_CACHE.capacity == before


# --- a batch is named before its blocks are collected (D16) ------------------
def _upsert(t, k, qty=3.0):
    t.apply_write(WriteRequest("li", [RowOp(
        "upsert", {"k": k, "rf": "R", "ls": "O", "qty": qty})]), ht=OLD)


@pytest.mark.parametrize("where", ["loop", "build"])
def test_no_older_batch_is_served_under_a_newer_name(monkeypatch, where):
    """A write lands between a read's naming of the contents and the
    collection of the blocks its batch is built from: on the loop (a
    store with a memtable, whose blocks the read collects there) or in
    the build (a store of SSTs alone, whose build collects them).  The
    batch is cached under the name of what it holds, so the next read
    builds the newer contents."""
    t = make_tablet(f"d16-{where}")
    before = 0
    if where == "loop":
        _upsert(t, 10 * N)
        before = 1
    op = t.read_op("li")
    landed = []

    def land():
        if not landed:
            landed.append(1)
            _upsert(t, 10 * N + 1)

    if where == "loop":
        real = type(op)._collect_blocks

        def collect(self, columns=None):
            blocks = real(self, columns)
            land()
            return blocks
        monkeypatch.setattr(type(op), "_collect_blocks", collect)
    else:
        real = operations.DocReadOperation._projected_blocks

        def collect(ssts, columns, step="batch"):
            blocks = real(ssts, columns, step)
            if step == "batch":
                land()
            return blocks
        monkeypatch.setattr(operations.DocReadOperation, "_projected_blocks",
                            staticmethod(collect))
    first = t.read(count_req())
    assert landed and int(first.agg_values[0]) == 2 * N + before
    second = t.read(count_req())
    assert int(second.agg_values[0]) == 2 * N + before + 1
    assert float(second.agg_values[1]) == pytest.approx(
        sum(float(_rows(i * N, N, seed=i)["qty"].sum()) for i in range(2))
        + 3.0 * (before + 1))


def test_a_flush_between_the_facts_and_the_build_collects_what_is_there():
    """The SST set changes while a read's facts are made off the loop:
    its batch is built from the blocks there are then, with a link of
    their own, and answers for them."""
    t = make_tablet("facts-then-flush")
    op = t.read_op("li")
    steps = op.execute_steps(count_req())
    facts_step = next(steps)
    assert isinstance(facts_step, operations.BatchBuild)
    got = facts_step()
    t.bulk_load(_rows(5 * N, N, seed=5), ht=OLD)     # a third SST lands
    with TRACES.trace("flush-between") as root:
        build = steps.send(got)
        batch = build()
        try:
            launch = steps.send(batch)
            while True:
                launch = steps.send(launch())
        except StopIteration as done:
            resp = done.value
    assert int(resp.agg_values[0]) == 3 * N
    kept = [s.tags["kept"] for s in TRACES.finished()
            if s.trace_id == root.trace_id
            and s.name == "batch.version_link"]
    assert kept == [False]
    assert int(t.read(count_req()).agg_values[0]) == 3 * N
