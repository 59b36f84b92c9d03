"""One tserver that owns four chips (`tserver_device_chips` = 4, on four
of the CPU's virtual devices): TPC-H LINEITEM in 8 tablets of 3 SSTs,
Q6 and Q1 through `SqlSession.execute` served as ONE mesh launch combined
by `lax.psum`, held to the plain float64 numpy reference
(`benchmark/tpch.py reference`, which imports nothing of the program), to
the same statements with the flag at 1, and to the host combine of the
per-tablet partials; the global dictionary of the text group columns; a
write after the batch was cached; the cache's accounting per chip."""
import asyncio
import tempfile

import jax
import numpy as np
import pytest

from benchmark import tpch
from yugabyte_db_tpu.client import client as client_mod
from yugabyte_db_tpu.docdb.mesh_read import (MeshIneligible, MeshReader,
                                             chip_of)
from yugabyte_db_tpu.docdb.operations import ReadRequest
from yugabyte_db_tpu.docdb.table_codec import TableInfo
from yugabyte_db_tpu.dockv.packed_row import (ColumnSchema, ColumnType,
                                              TableSchema)
from yugabyte_db_tpu.dockv.partition import PartitionSchema
from yugabyte_db_tpu.ops import AggSpec
from yugabyte_db_tpu.ops.device_batch import (DeviceBlockCache, batch_bytes,
                                              build_batch)
from yugabyte_db_tpu.ops.expr import Expr
from yugabyte_db_tpu.ops.grouped_scan import DictGroupSpec
from yugabyte_db_tpu.ops.scan import HashGroupSpec
from yugabyte_db_tpu.tablet import Tablet
from yugabyte_db_tpu.utils import flags, metrics
from yugabyte_db_tpu.utils.trace import TRACES

pytestmark = pytest.mark.skipif(len(jax.devices()) < 4,
                                reason="needs 4 devices")

ORDERS, ROWS, SEED = 3000, 12000, 31
TABLE = tpch.TABLE
FLAGS = ("tserver_device_chips", "device_float_dtype",
         "tpu_min_rows_for_pushdown")


@pytest.fixture(autouse=True)
def _reset_flags():
    yield
    for f in FLAGS:
        flags.REGISTRY.reset(f)


# --- the served table ---------------------------------------------------------
class Served:
    """LINEITEM in 8 tablets on one tserver that owns `chips` chips: two
    bulk-loaded SSTs a tablet and a third, flushed, that holds a refresh
    set, new versions of loaded keys and one delete.  `data` is what the
    table holds, for the reference."""

    def __init__(self, root: str, chips: int):
        self.root, self.chips = root, chips

    async def __aenter__(self):
        from yugabyte_db_tpu.ql.executor import SqlSession
        from yugabyte_db_tpu.tools.mini_cluster import MiniCluster
        flags.set_flag("tserver_device_chips", self.chips)
        flags.set_flag("device_float_dtype", "float64")
        flags.set_flag("tpu_min_rows_for_pushdown", 1)
        self.mc = await MiniCluster(self.root, num_tservers=1).start()
        self.ts = self.mc.tservers[0]
        self.client = self.mc.client()
        self.sql = SqlSession(self.client)
        await self.sql.execute(tpch.DDL.format(name=TABLE, tablets=8))
        self.ct = await self.client._table(TABLE, refresh=True)
        bulk = tpch.generate_lineitem(ORDERS, ROWS, SEED)
        for part in (slice(0, ROWS // 2), slice(ROWS // 2, ROWS)):
            for p in self.peers():
                p.tablet.bulk_load({k: v[part] for k, v in bulk.items()})
        fresh = tpch.generate_lineitem(60, 240, [SEED, 1], refresh=True)
        await self.insert(fresh)
        # new versions of 40 loaded keys, spread over the tablets
        for i in range(0, ROWS, ROWS // 40):
            bulk["l_quantity"][i] = 3.0
            bulk["l_extendedprice"][i] = 1234.5
            await self.sql.execute(
                f"UPDATE {TABLE} SET l_quantity = 3.0, l_extendedprice = "
                f"1234.5 WHERE l_orderkey = {bulk['l_orderkey'][i]} AND "
                f"l_linenumber = {bulk['l_linenumber'][i]}")
        gone = ROWS // 3
        await self.sql.execute(
            f"DELETE FROM {TABLE} WHERE l_orderkey = "
            f"{bulk['l_orderkey'][gone]} AND l_linenumber = "
            f"{bulk['l_linenumber'][gone]}")
        bulk = {k: np.delete(v, gone) for k, v in bulk.items()}
        await self.flush()
        assert [len(p.tablet.regular.ssts) for p in self.peers()] == [3] * 8
        self.data = tpch.concat([bulk, fresh])
        await self.sql.execute(f"ANALYZE {TABLE}")
        return self

    async def __aexit__(self, *exc):
        await self.client.messenger.shutdown()
        await self.mc.shutdown()

    def peers(self) -> list:
        return [self.ts.peers[l.tablet_id] for l in self.ct.locations]

    async def insert(self, rows: dict) -> None:
        cols = ", ".join(tpch.COLS)
        await self.sql.execute(
            f"INSERT INTO {TABLE} ({cols}) VALUES " + ", ".join(
                "(" + ", ".join(map(tpch.literal, tpch.row(rows, i))) + ")"
                for i in range(len(rows["l_orderkey"]))))

    async def flush(self) -> None:
        for l in self.ct.locations:
            await self.client._call_leader(self.ct, l.tablet_id, "flush",
                                           {"tablet_id": l.tablet_id})

    async def traced(self, query: str):
        """(rows, the statement's spans)."""
        with TRACES.trace("forced-root") as root:
            rows = (await self.sql.execute(
                tpch.SQL[query].format(name=TABLE))).rows
        return rows, [s for s in TRACES.finished()
                      if s.trace_id == root.trace_id]


def _close(query: str, rows, ref: dict) -> None:
    """Counts and `sum_qty` exactly, money sums to 1e-9 relative."""
    gaps = tpch.compare(query, rows, ref)
    assert gaps[f"{query}_shape"] == 0, gaps
    if query == "q6":
        assert gaps["sum_usd"] <= 1e-9 * abs(ref["q6"]), gaps
        return
    assert gaps["q1_count_diff"] == 0 and gaps["q1_qty_diff"] == 0, gaps
    for r in rows:
        want = ref["q1"][r["l_returnflag"] + r["l_linestatus"]]
        for k in tpch.Q1_SUMS:
            assert abs(r[k] - want[k]) <= 1e-9 * abs(want[k]), (k, r)


def _named(spans, name: str) -> list:
    return [s for s in spans if s.name.split(":")[0] == name]


@pytest.fixture(scope="module")
def answers():
    """Q6 and Q1 over the same table from a server that owns four chips
    and from one that owns one, each with the statement's spans, and what
    the four-chip server says once the client asks tablet by tablet."""
    out = {}

    async def main():
        for chips in (4, 1):
            with tempfile.TemporaryDirectory() as root:
                async with Served(root, chips) as t:
                    got = {"ref": tpch.reference(t.data)}
                    for q in ("q6", "q1"):
                        got[q, "cold"] = await t.traced(q)
                        got[q, "warm"] = await t.traced(q)
                    if chips == 4:
                        real = client_mod._mesh_groups
                        client_mod._mesh_groups = \
                            lambda req, locations: ([], locations)
                        try:
                            for q in ("q6", "q1"):
                                got[q, "by_tablet"] = await t.traced(q)
                        finally:
                            client_mod._mesh_groups = real
                    out[chips] = got
        for f in FLAGS:
            flags.REGISTRY.reset(f)
    asyncio.run(main())
    return out


@pytest.mark.parametrize("query", ["q6", "q1"])
def test_mesh_answers_equal_the_reference(answers, query):
    got = answers[4]
    for temp in ("cold", "warm"):
        _close(query, got[query, temp][0], got["ref"])
    if query == "q1":
        assert len(got["q1", "warm"][0]) == 4


@pytest.mark.parametrize("query", ["q6", "q1"])
def test_one_chip_gives_the_same_answers(answers, query):
    _close(query, answers[1][query, "warm"][0], answers[1]["ref"])
    four = {tuple(v for v in r.values() if isinstance(v, str)): r
            for r in answers[4][query, "warm"][0]}
    for r in answers[1][query, "warm"][0]:
        other = four[tuple(v for v in r.values() if isinstance(v, str))]
        for k, v in r.items():
            if isinstance(v, (str, int)) or k == "sum_qty":
                assert other[k] == v, k
            else:
                assert abs(other[k] - v) <= 1e-9 * abs(v), k


@pytest.mark.parametrize("query", ["q6", "q1"])
def test_psum_equals_the_host_combine_of_tablet_partials(answers, query):
    """The same server asked tablet by tablet (8 RPCs, 8 launches, the
    client adds the partials): the shares add up to the whole."""
    rows, spans = answers[4][query, "by_tablet"]
    assert len(_named(spans, "device.scan")) == 8
    assert not _named(spans, "tserver.mesh_gather")
    _close(query, rows, answers[4]["ref"])
    whole = sorted(answers[4][query, "warm"][0], key=str)
    for a, b in zip(sorted(rows, key=str), whole):
        for k, v in a.items():
            if isinstance(v, (str, int)) or k == "sum_qty":
                assert b[k] == v, k
            else:
                assert abs(b[k] - v) <= 1e-9 * abs(v), k


@pytest.mark.parametrize("query", ["q6", "q1"])
def test_a_statement_is_one_rpc_one_gather_one_launch(answers, query):
    cold, warm = (answers[4][query, t][1] for t in ("cold", "warm"))
    for spans in (cold, warm):
        assert len(_named(spans, "rpc.c.tserver.read_tablets")) == 1
        assert not _named(spans, "rpc.c.tserver.read")
        (read,) = _named(spans, "docdb.read")
        assert read.tags["route"] == "mesh" and read.tags["tablets"] == 8
        (gather,) = _named(spans, "tserver.mesh_gather")
        assert (gather.tags["tablets"], gather.tags["chips"],
                gather.tags["fanin"]) == (8, 4, 8)
        (scan,) = _named(spans, "device.scan")
        assert (scan.tags["mvcc"], scan.tags["chips"],
                scan.tags["shards"]) == ("linked", 4, 4)
        # the statement's runtime scalars as one int64 vector (read_ht,
        # Q1's dictionary sizes, the integer literals) and one float64
        # vector (the static scales, the float literals) ...
        assert scan.tags["host_args"] == 2
        # a shard of two 1,500-row tablets is one tile of the kernel
        assert scan.tags["tiles"] == 1
        (wait,) = _named(spans, "device.wait")
        assert wait.tags["chips"] == 4 and wait.tags["reads"] == 1
        # ... and its result as one int64 array
        assert wait.tags["result_leaves"] == 1
        (combine,) = _named(spans, "client.combine")
        assert combine.tags["parts"] == 1
    # the miss builds, links per shard and ships; the hit does none of it
    (build,) = _named(cold, "batch.build")
    # (stored row versions: 40 new ones and the tombstone besides)
    assert build.tags["shards"] == 4
    assert build.tags["rows"] == ROWS + 240 + 41
    (link,) = _named(cold, "batch.version_link")
    assert link.tags["superseded"] == 41 and link.tags["shards"] == 4
    (h2d,) = _named(cold, "batch.h2d")
    assert h2d.tags["shards"] == 4 and h2d.tags["bytes"] > 0
    assert _named(cold, "docdb.batch")[0].tags["cache"] == "miss"
    assert _named(warm, "docdb.batch")[0].tags["cache"] == "hit"
    for name in ("batch.build", "batch.version_link", "batch.h2d",
                 "docdb.collect_blocks", "device.dict_plan"):
        assert not _named(warm, name), name


@pytest.mark.parametrize("query", ["q6", "q1"])
def test_with_one_chip_no_mesh_code_serves(answers, query):
    spans = answers[1][query, "warm"][1]
    assert len(_named(spans, "rpc.c.tserver.read")) == 8
    assert not _named(spans, "rpc.c.tserver.read_tablets")
    assert not _named(spans, "tserver.mesh_gather")
    assert {s.tags["route"] for s in _named(spans, "docdb.read")} \
        == {"tpu_aggregate"}
    assert all("chips" not in s.tags for s in _named(spans, "device.scan"))
    # the one-device launch goes through the same code: the same tags
    assert {s.tags["host_args"] for s in _named(spans, "device.scan")} == {2}
    assert {s.tags["result_leaves"]
            for s in _named(spans, "device.wait")} == {1}
    assert {s.tags["tiles"] for s in _named(spans, "device.scan")} == {1}
    assert {s.tags["reads"] for s in _named(spans, "device.wait")} == {1}


def test_an_insert_after_the_batch_was_cached_is_in_the_next_answer():
    """Acknowledged but unflushed, it is a memtable overlay: the read
    falls back to the one-device path and says so; once flushed, the
    mesh serves it again from a new batch.  Every answer holds it."""
    async def main():
        with tempfile.TemporaryDirectory() as root:
            async with Served(root, 4) as t:
                await t.traced("q1")
                more = tpch.generate_lineitem(5, 20, [SEED, 2],
                                              first_order=60, refresh=True)
                await t.insert(more)
                await t.sql.execute(f"ANALYZE {TABLE}")
                ref = tpch.reference(tpch.concat([t.data, more]))
                rows, spans = await t.traced("q1")
                _close("q1", rows, ref)
                reads = _named(spans, "docdb.read")
                assert reads[0].tags["route"] == "mesh_fallback"
                assert reads[0].tags["fallback"] == "memtable"
                assert {s.tags["route"] for s in reads[1:]} \
                    == {"tpu_aggregate"} and len(reads) == 9
                assert len(_named(spans, "rpc.c.tserver.read_tablets")) == 1
                await t.flush()
                rows, spans = await t.traced("q1")
                _close("q1", rows, ref)
                (read,) = _named(spans, "docdb.read")
                assert read.tags["route"] == "mesh"
                assert _named(spans, "docdb.batch")[0].tags["cache"] == "miss"
                server = metrics.REGISTRY.entity("server", f"ts-{t.ts.uuid}")
                assert server.counter("mesh_scan_launches").value() >= 3
                # (the ANALYZE and the Q1 over the unflushed rows)
                assert server.counter("mesh_scan_fallbacks").value() == 2
                for i in range(4):
                    chip = metrics.REGISTRY.entity("device_cache",
                                                   f"chip-{i}")
                    assert chip.counter("misses").value() >= 2
                    assert chip.gauge("bytes").value() > 0
    asyncio.run(main())


# --- below SQL: dictionaries, placement, the cache ---------------------------
C = Expr.col


def _flag_tablet(flags_here, n: int, key_base: int, seed: int):
    """A tablet of (k, rf, qty) whose `rf` holds only `flags_here`."""
    schema = TableSchema((
        ColumnSchema(0, "k", ColumnType.INT64, is_hash_key=True),
        ColumnSchema(1, "rf", ColumnType.STRING),
        ColumnSchema(2, "qty", ColumnType.FLOAT64),
    ), 1)
    info = TableInfo("li", "li", schema, PartitionSchema("hash", 1))
    t = Tablet(f"li-{key_base}", info, tempfile.mkdtemp(prefix="mesh-"))
    rng = np.random.default_rng(seed)
    data = {"k": np.arange(key_base, key_base + n, dtype=np.int64),
            "rf": np.array(flags_here, object)[
                rng.integers(0, len(flags_here), n)],
            "qty": rng.integers(1, 50, n).astype(np.float64) + 0.25}
    t.bulk_load(data, block_rows=2048)
    return t, data


def _blocks(t) -> list:
    return [r.columnar_block(i) for r in t.regular.ssts
            for i in range(r.num_blocks())]


def test_codes_of_a_text_group_column_are_global_over_the_shards():
    """Four tablets whose blocks hold different dictionaries ("A" is code
    0 in one and "R" is code 0 in another): the groups still add up by
    their text, not by their local code."""
    flags.set_flag("tpu_min_rows_for_pushdown", 1)
    flags.set_flag("device_float_dtype", "float64")
    parts = [(["A", "N"], 5000), (["N", "R"], 6000), (["R"], 4500),
             (["A", "O"], 5500)]
    tablets = [_flag_tablet(f, n, 100_000 * i, i)
               for i, (f, n) in enumerate(parts)]
    local = [sorted(map(str, _blocks(t)[0].dict_varlen(1)[0]))
             for t, _ in tablets]
    assert len({tuple(d) for d in local}) == 4
    reader = MeshReader(jax.devices()[:4], DeviceBlockCache())
    req = ReadRequest("li", aggregates=(AggSpec("sum", C(2).node),
                                        AggSpec("count")),
                      group_by=DictGroupSpec(cols=(1,)), read_ht=None)
    resp = reader.read(req, [t.read_op("li") for t, _ in tablets])
    got = {str(g): (float(s), int(n)) for g, s, n in zip(
        resp.group_values[0], resp.agg_values[0], resp.agg_values[1])}
    rf = np.concatenate([d["rf"] for _, d in tablets]).astype(str)
    qty = np.concatenate([d["qty"] for _, d in tablets])
    assert got == {g: (float(qty[rf == g].sum()), int((rf == g).sum()))
                   for g in ("A", "N", "O", "R")}
    (batch, _), = reader.cache._map.values()
    assert [str(x) for x in batch.dicts[1]] == ["A", "N", "O", "R"]
    assert batch.n_rows_per_shard == [5000, 6000, 4500, 5500]


def test_placement_rule_is_two_tablets_a_chip_in_partition_order():
    assert [chip_of(i, 8, 4) for i in range(8)] == [0, 0, 1, 1, 2, 2, 3, 3]
    assert [chip_of(i, 4, 4) for i in range(4)] == [0, 1, 2, 3]
    assert [chip_of(i, 6, 4) for i in range(6)] == [0, 0, 1, 2, 2, 3]


def test_what_the_mesh_does_not_take_says_why():
    flags.set_flag("tpu_min_rows_for_pushdown", 1)
    tablets = [_flag_tablet(["A"], 3000, 10_000 * i, i)[0] for i in range(2)]
    reader = MeshReader(jax.devices()[:4], DeviceBlockCache())
    ops = [t.read_op("li") for t in tablets]
    count = (AggSpec("count"),)
    for req, why in (
            (ReadRequest("li"), "not_aggregate"),
            (ReadRequest("li", aggregates=count, paging_state=b"x"),
             "not_aggregate"),
            (ReadRequest("li", aggregates=count,
                         group_by=HashGroupSpec(cols=(0,), max_groups=64)),
             "hash_group")):
        with pytest.raises(MeshIneligible) as e:
            reader.read(req, ops)
        assert e.value.reason == why
    with pytest.raises(MeshIneligible) as e:
        reader.read(ReadRequest("li", aggregates=count), ops[:1])
    assert e.value.reason == "one_tablet"
    assert not reader.cache._map


def test_cache_accounts_and_evicts_per_chip():
    flags.set_flag("tpu_min_rows_for_pushdown", 1)
    devices = jax.devices()[:4]
    tablets = [_flag_tablet(["A", "N"], 3000, 10_000 * i, i)[0]
               for i in range(4)]
    ops = [t.read_op("li") for t in tablets]
    cache = DeviceBlockCache(capacity_bytes=1 << 30)
    reader = MeshReader(devices, cache)
    sums = ReadRequest("li", aggregates=(AggSpec("sum", C(2).node),))
    grouped = ReadRequest("li", aggregates=(AggSpec("count"),),
                          group_by=DictGroupSpec(cols=(1,)))
    reader.read(sums, ops)
    (key_a, (a, size_a)), = cache._map.items()
    assert size_a == batch_bytes(a) and a.padded_rows == 4096
    assert cache.bytes_by_chip() == {d: size_a // 4 for d in devices}
    # a batch of one chip beside it: that chip alone holds more
    def on_chip_1():
        with jax.default_device(devices[1]):
            return build_batch(_blocks(tablets[0]), [2])
    one = cache.get_or_build(("one",), on_chip_1)
    size_one = batch_bytes(one)
    assert cache.bytes_by_chip()[devices[1]] == size_a // 4 + size_one
    assert cache.bytes_by_chip()[devices[0]] == size_a // 4
    # a chip's capacity, not the process's: both entries fit a chip that
    # may hold their sum there, though all chips together hold more
    cache.capacity = size_a // 4 + size_one
    assert cache._bytes > cache.capacity
    reader.read(sums, ops)
    assert cache.hits == 1 and len(cache._map) == 2
    # a third entry puts every chip over: the least recently used entry
    # that holds bytes on a full chip goes, the other stays while its
    # chip has room
    reader.read(grouped, ops)
    (b, size_b) = cache._map[next(reversed(cache._map))]
    assert ("one",) not in cache._map or key_a not in cache._map
    by_chip = cache.bytes_by_chip()
    assert all(v <= cache.capacity for v in by_chip.values())
    assert sum(by_chip.values()) == cache._bytes \
        == sum(size for _, size in cache._map.values())
    # a flush of one member store drops the sharded entries that hold it
    cache.invalidate_prefix((id(tablets[2].regular),))
    assert all(not isinstance(k[0], tuple) for k in cache._map)
    assert sum(cache.bytes_by_chip().values()) == cache._bytes


# --- a shard longer than the kernel's row tile --------------------------------
@pytest.mark.parametrize("lanes", ["whole", "pairs"])
@pytest.mark.parametrize("shape", ["q6", "q1_dense", "q1_dict"])
def test_a_long_shard_runs_in_row_tiles(shape, lanes, monkeypatch):
    """Four shards of 4,096 padded rows under a tile of 1,024: for a
    grouped scan each chip loops over four tiles and adds their
    partials, then ONE all-reduce adds the chips' — the bits of the
    whole program, and of the one-device kernel over the same rows
    (sixteen tiles).  Q6 has no group: whole on either kernel.  With
    `pairs` (the TPU's arm) the float64 lanes are `Pair`s, joined a tile
    at a time."""
    import dataclasses
    from yugabyte_db_tpu.docdb.table_codec import TableCodec
    from yugabyte_db_tpu.models import tpch as model
    from yugabyte_db_tpu.ops import device_batch
    from yugabyte_db_tpu.ops import scan as scan_mod
    from yugabyte_db_tpu.ops.scan import ScanKernel
    from yugabyte_db_tpu.parallel import tablet_mesh
    from yugabyte_db_tpu.parallel.distributed_scan import (
        DistributedScanKernel, build_sharded_batch)
    from yugabyte_db_tpu.utils.hybrid_time import HybridTime
    q = model.TPCH_Q6 if shape == "q6" else model.TPCH_Q1
    group = q.group
    words = {model.RETFLAG: np.array(list("ANR"), object),
             model.LINESTATUS: np.array(list("FO"), object)}
    if shape == "q1_dict":
        group = DictGroupSpec(cols=(model.RETFLAG, model.LINESTATUS))
    data = model.generate_lineitem(0.004, seed=5)
    codec = TableCodec(model.lineitem_info())
    per_shard = [codec.bulk_blocks(
        {k: v[i * 3000:(i + 1) * 3000] for k, v in data.items()},
        HybridTime.from_micros(100)) for i in range(4)]
    flags.set_flag("device_float_dtype", "float64")
    if lanes == "pairs":
        monkeypatch.setattr(device_batch, "_backend_float64_is_pair",
                            lambda: True)
    mesh = dataclasses.replace(build_sharded_batch(
        tablet_mesh(4, devices=jax.devices()[:4]), per_shard,
        sorted(q.columns), multi_version=True), dicts=words)
    one = dataclasses.replace(build_batch(
        [b for blocks in per_shard for b in blocks], sorted(q.columns),
        multi_version=True), dicts=words)
    assert (mesh.padded_rows, mesh.num_shards, one.padded_rows) \
        == (4096, 4, 16384)
    assert all(isinstance(b.cols[model.EXTPRICE], device_batch.Pair)
               == (lanes == "pairs") for b in (mesh, one))
    read_ht = HybridTime.from_micros(10_000).value
    programs = []

    class Recording(DistributedScanKernel):
        def _get(self, *key):
            fn = super()._get(*key)

            def call(*args):
                programs.append(fn.lower(*args).compile().as_text())
                return fn(*args)
            return call

    def launches():
        with TRACES.trace("tiles") as t:
            four = Recording().run(mesh, q.where, q.aggs, group, read_ht)
            alone = ScanKernel().run(one, q.where, q.aggs, group, read_ht)
        tiles = [s.tags["tiles"] for s in TRACES.recent
                 if s.trace_id == t.trace_id and s.name == "device.scan"]
        return four, alone, tiles
    whole4, whole1, tiles = launches()
    assert tiles == [1, 1]
    monkeypatch.setattr(scan_mod, "_TILE_ROWS", 1024)
    tiled4, tiled1, tiles = launches()
    assert tiles == ([1, 1] if shape == "q6" else [4, 16])
    for text, looped in zip(programs, (False, shape != "q6")):
        assert text.count("all-reduce(") + text.count("all-reduce-start(") \
            == 1
        assert (" while(" in text) == looped
    assert int(np.sum(tiled4[1])) == int(np.sum(tiled1[1])) > 0
    for got in (tiled4, whole4, tiled1[:2] + tiled1[3:]):
        got, want = (jax.tree_util.tree_leaves(x)
                     for x in (got, whole1[:2] + whole1[3:]))
        assert len(got) == len(want)
        for x, y in zip(got, want):
            assert np.asarray(x).dtype == np.asarray(y).dtype
            np.testing.assert_array_equal(x, y)
    # an aggregate launch keeps no row mask, tiled or whole (the
    # kernel's tiled mask is the whole one's: tests/test_ops_scan.py)
    assert tiled1[2] is None and whole1[2] is None
