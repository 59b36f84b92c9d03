"""What a large table's set-up leans on: a block decoded for the columns
its reader names and no others; an INSERT's uniqueness gate that reads
filters and keys, not rows; a bulk load whose blocks are independent
jobs and whose partition hashes are computed once for the tablets of a
table; text lanes dictionary-coded by hashed rows; a mesh scan of a
column set served by a cached batch of more columns.  Each is held to
the path it stands in for."""
import asyncio
import gc
import tempfile

import jax
import numpy as np
import pytest

from benchmark import tpch
from yugabyte_db_tpu.docdb import table_codec
from yugabyte_db_tpu.docdb.operations import ReadRequest
from yugabyte_db_tpu.ops.device_batch import DeviceBlockCache
from yugabyte_db_tpu.storage import lane_codec
from yugabyte_db_tpu.storage.columnar import ColumnarBlock
from yugabyte_db_tpu.utils import flags
from yugabyte_db_tpu.utils.hybrid_time import HybridTime

TABLE = tpch.TABLE
ORDERS, ROWS, SEED = 2000, 8000, 17


# --- dictionary coding of text lanes ------------------------------------------
def _void_unique(mat: np.ndarray):
    v = np.dtype((np.void, mat.shape[1]))
    uniq, codes = np.unique(np.ascontiguousarray(mat).view(v).reshape(-1),
                            return_inverse=True)
    return uniq.view(np.uint8).reshape(len(uniq), mat.shape[1]), codes


@pytest.mark.parametrize("width", [1, 2, 7, 8, 9, 16, 26, 45])
@pytest.mark.parametrize("card", [1, 5, 300])
def test_unique_rows_is_the_void_sort(width, card):
    rng = np.random.default_rng(width * 1000 + card)
    pool = rng.integers(0, 256, (card, width), dtype=np.uint8)
    pool[::2, width // 2:] = 0          # zero tails, as padded text has
    mat = pool[rng.integers(0, card, 5000)]
    got, want = lane_codec._unique_rows(mat), _void_unique(mat)
    assert np.array_equal(got[0], want[0])
    assert np.array_equal(got[1], want[1])


def test_unique_rows_survives_a_hash_collision(monkeypatch):
    # a multiplier of 0 makes the hash the last word alone: rows that
    # differ only before it collide, and the check sends them to the sort
    monkeypatch.setattr(lane_codec, "_ROW_HASH_MULT", np.uint64(0))
    rng = np.random.default_rng(3)
    mat = rng.integers(0, 3, (4000, 20), dtype=np.uint8)
    mat[:, 16:] = 7
    got, want = lane_codec._unique_rows(mat), _void_unique(mat)
    assert len(want[0]) > 1
    assert np.array_equal(got[0], want[0])
    assert np.array_equal(got[1], want[1])


@pytest.mark.parametrize("nulls", ["none", "empty", "payload"])
def test_varlen_code_rows_orders_and_codes_as_strings_sort(nulls):
    rng = np.random.default_rng(11)
    pool = [b"", b"a", b"a\x00", b"ab", b"b", b"DELIVER IN PERSON",
            b"TAKE BACK RETURN", b"NONE", b"COLLECT COD"]
    rows = [pool[i] for i in rng.integers(0, len(pool), 3000)]
    null = None
    if nulls != "none":
        null = rng.random(3000) < 0.2
        if nulls == "empty":
            rows = [b"" if z else r for r, z in zip(rows, null)]
    ends = np.cumsum([len(r) for r in rows]).astype(np.uint32)
    ulens, uheap, codes = lane_codec.varlen_code_rows(
        ends, b"".join(rows), null, max_card=0xFFFF)
    seen = [b"" if (null is not None and null[i]) else r
            for i, r in enumerate(rows)]
    # byte order with the shorter first: what (bytes, length) rows sort to
    want = sorted(set(seen), key=lambda r: (r.ljust(17, b"\x00"), len(r)))
    offs = np.concatenate([[0], np.cumsum(ulens.astype(np.int64))])
    got = [bytes(uheap[offs[i]:offs[i + 1]]) for i in range(len(ulens))]
    assert got == want
    assert [got[c] for c in codes] == seen


def test_varlen_code_rows_sample_guard_refuses_before_padding(monkeypatch):
    rng = np.random.default_rng(5)
    rows = [bytes(rng.integers(97, 123, 40, dtype=np.uint8))
            for _ in range(6000)]
    ends = np.cumsum([len(r) for r in rows]).astype(np.uint32)
    sizes = []
    real = lane_codec._unique_rows
    monkeypatch.setattr(lane_codec, "_unique_rows",
                        lambda m: sizes.append(len(m)) or real(m))
    assert lane_codec.varlen_code_rows(ends, b"".join(rows),
                                       max_card=0xFFFF) is None
    assert sizes == [lane_codec._VARLEN_DICT_SAMPLE]


# --- a served table -----------------------------------------------------------
class Served:
    """LINEITEM in `tablets` tablets on one tserver: one bulk-loaded SST
    a tablet (from `bulk`), then whatever the test writes."""

    def __init__(self, root: str, chips: int = 1, tablets: int = 4,
                 block_rows: int = 512):
        self.root, self.chips, self.tablets = root, chips, tablets
        self.block_rows = block_rows

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
        await self.sql.execute(tpch.DDL.format(name=TABLE,
                                               tablets=self.tablets))
        self.ct = await self.client._table(TABLE, refresh=True)
        self.bulk = tpch.generate_lineitem(ORDERS, ROWS, SEED)
        for p in self.peers():
            p.tablet.bulk_load(self.bulk, block_rows=self.block_rows)
        return self

    async def __aexit__(self, *exc):
        await self.client.messenger.shutdown()
        await self.mc.shutdown()
        for f in ("tserver_device_chips", "device_float_dtype",
                  "tpu_min_rows_for_pushdown"):
            flags.REGISTRY.reset(f)

    def peers(self) -> list:
        return [self.ts.peers[l.tablet_id] for l in self.ct.locations]

    def insert_sql(self, rows: dict, idx) -> str:
        cols = ", ".join(tpch.COLS)
        return f"INSERT INTO {TABLE} ({cols}) VALUES " + ", ".join(
            "(" + ", ".join(map(tpch.literal, tpch.row(rows, i))) + ")"
            for i in idx)

    async def flush(self) -> None:
        for l in self.ct.locations:
            await self.client._call_leader(self.ct, l.tablet_id, "flush",
                                           {"tablet_id": l.tablet_id})


def _run(coro_fn, **kw):
    async def go():
        with tempfile.TemporaryDirectory() as root:
            async with Served(root, **kw) as s:
                return await coro_fn(s)
    return asyncio.run(go())


# --- projected blocks ---------------------------------------------------------
def test_projected_block_holds_the_named_columns_and_no_others():
    async def body(s):
        codec = s.peers()[0].tablet.codec
        ids = {c.name: c.id for c in codec.schema.columns}
        want = {ids["l_quantity"], ids["l_returnflag"], ids["l_comment"]}
        checked, raw_lanes = 0, [0, 0]
        for p in s.peers():
            for r in p.tablet.regular.ssts:
                for i in range(r.num_blocks()):
                    full = r.columnar_block(i)
                    part = r.projected_block(i, want)
                    for lane in ("key_hash", "ht", "write_id", "tombstone"):
                        assert np.array_equal(getattr(part, lane),
                                              getattr(full, lane))
                    assert set(part.pk) == set(full.pk)
                    assert all(np.array_equal(part.pk[c], full.pk[c])
                               for c in full.pk)
                    assert np.array_equal(part.keys, full.keys)
                    assert set(part.fixed) == {ids["l_quantity"]}
                    for a, b in zip(part.fixed[ids["l_quantity"]],
                                    full.fixed[ids["l_quantity"]]):
                        assert np.array_equal(a, b)
                    assert set(part.varlen) == {ids["l_returnflag"],
                                                ids["l_comment"]}
                    # a dictionary-coded lane keeps its stored parts and
                    # its nulls, and rebuilds no row heap
                    cid = ids["l_returnflag"]
                    assert part.varlen[cid][:2] == (None, None)
                    assert np.array_equal(part.varlen[cid][2],
                                          full.varlen[cid][2])
                    pu, pc = part.dict_varlen(cid)
                    fu, fc = full.dict_varlen(cid)
                    assert list(pu) == list(fu) and np.array_equal(pc, fc)
                    # a lane stored raw comes whole
                    cid = ids["l_comment"]
                    if cid in full._vdicts:
                        assert part.varlen[cid][:2] == (None, None)
                        raw_lanes[0] += 1
                    else:
                        assert np.array_equal(part.varlen[cid][0],
                                              full.varlen[cid][0])
                        assert bytes(part.varlen[cid][1]) == \
                            bytes(full.varlen[cid][1])
                        raw_lanes[1] += 1
                    keys_only = r.projected_block(i, ())
                    assert not keys_only.fixed and not keys_only.varlen
                    assert np.array_equal(keys_only.keys, full.keys)
                    checked += 1
        assert checked >= 4 and raw_lanes[1]
    _run(body, tablets=2, block_rows=2500)


def test_projected_deserialize_of_a_flushed_block():
    """Blocks a flush writes (rows packed from the memtable) skip and
    take lanes like bulk-loaded ones."""
    async def body(s):
        fresh = tpch.generate_lineitem(30, 120, [SEED, 1], refresh=True)
        await s.sql.execute(s.insert_sql(fresh, range(120)))
        await s.flush()
        ids = {c.name: c.id for c in s.peers()[0].tablet.codec.schema.columns}
        want = {ids["l_discount"], ids["l_shipmode"]}
        seen = 0
        for p in s.peers():
            r = p.tablet.regular.ssts[-1]
            for i in range(r.num_blocks()):
                full, part = r.columnar_block(i), r.projected_block(i, want)
                if full is None:
                    assert part is None
                    continue
                assert np.array_equal(part.ht, full.ht)
                assert set(part.fixed) | set(part.varlen) == want
                assert np.array_equal(part.fixed[ids["l_discount"]][0],
                                      full.fixed[ids["l_discount"]][0])
                pu, pc = part.dict_varlen(ids["l_shipmode"])
                fu, fc = full.dict_varlen(ids["l_shipmode"])
                assert list(pu[pc]) == list(fu[fc])
                seen += 1
        assert seen
    _run(body)


# --- the uniqueness gate ------------------------------------------------------
def test_key_is_live_is_what_a_point_read_finds():
    async def body(s):
        b = s.bulk
        key = lambda i: {"l_orderkey": int(b["l_orderkey"][i]),
                         "l_linenumber": int(b["l_linenumber"][i])}
        where = lambda i: (f"l_orderkey = {b['l_orderkey'][i]} AND "
                           f"l_linenumber = {b['l_linenumber'][i]}")
        fresh = tpch.generate_lineitem(10, 40, [SEED, 2], refresh=True)
        await s.sql.execute(s.insert_sql(fresh, range(20)))     # memtable
        await s.sql.execute(f"DELETE FROM {TABLE} WHERE {where(5)}")
        await s.sql.execute(f"DELETE FROM {TABLE} WHERE {where(6)}")
        await s.flush()
        await s.sql.execute(f"DELETE FROM {TABLE} WHERE {where(7)}")
        await s.sql.execute(                                  # live again
            s.insert_sql(b, [6]))
        probes = [key(i) for i in range(0, 40)] + [
            {"l_orderkey": int(fresh["l_orderkey"][i]),
             "l_linenumber": int(fresh["l_linenumber"][i])}
            for i in range(40)] + [{"l_orderkey": 10 ** 9 + i,
                                    "l_linenumber": 1} for i in range(50)]
        live = 0
        for pk in probes:
            ct = await s.client._table(TABLE)
            tid = s.client._tablet_for_key(ct, pk).tablet_id
            tablet = s.ts.peers[tid].tablet
            want = bool(tablet.read(ReadRequest(ct.info.table_id,
                                                pk_eq=pk)).rows)
            assert tablet.key_is_live(ct.info.table_id, pk) == want, pk
            live += want
        assert live == 40 - 2 + 20      # rows 5 and 7 gone, 6 back
    _run(body)


def test_insert_gate_builds_no_point_reader_and_decodes_no_row():
    async def body(s):
        fresh = tpch.generate_lineitem(50, 200, [SEED, 3], refresh=True)
        for r in (x for p in s.peers() for x in p.tablet.regular.ssts):
            r._col_cache.clear()
            r._point_readers.clear()
        await s.sql.execute(s.insert_sql(fresh, range(200)))
        for r in (x for p in s.peers() for x in p.tablet.regular.ssts):
            assert not r._point_readers and not r._col_cache
        # and it still refuses a key that is there, old or just written
        for rows, i in ((s.bulk, 3), (fresh, 10)):
            with pytest.raises(Exception, match="duplicate key"):
                await s.sql.execute(s.insert_sql(rows, [i]))
    _run(body)


def test_a_filters_false_positive_costs_no_key_matrix():
    """`point_find(keys_only=True)` for a key a block does not hold —
    what a bloom filter's false positive sends it — is answered from the
    block's `key_hash` lane; a key that is there still builds the keys."""
    async def body(s):
        from yugabyte_db_tpu.storage.columnar import KEY_REBUILD_STATS
        tablet = s.peers()[0].tablet
        r = tablet.regular.ssts[0]
        before = KEY_REBUILD_STATS["rebuilds"]
        for i in range(300):
            absent = tablet.codec.doc_key_prefix(
                {"l_orderkey": int(s.bulk["l_orderkey"][i]),
                 "l_linenumber": 9})           # dbgen stops at 7
            assert r.point_find(absent, 2 ** 63, None, keys_only=True) is None
        assert KEY_REBUILD_STATS["rebuilds"] == before
        found = 0
        for i in range(400):
            there = tablet.codec.doc_key_prefix(
                {"l_orderkey": int(s.bulk["l_orderkey"][i]),
                 "l_linenumber": int(s.bulk["l_linenumber"][i])})
            got = r.point_find(there, 2 ** 63, None, keys_only=True)
            want = r.point_find(there, 2 ** 63, None)
            assert (got is None) == (want is None)
            assert got is None or got[:4] == want[:4]
            found += got is not None
        assert found > 10 and KEY_REBUILD_STATS["rebuilds"] > before
    _run(body)


# --- the bulk load ------------------------------------------------------------
def test_bulk_blocks_are_independent_jobs():
    async def body(s):
        t = s.peers()[1].tablet
        ht = HybridTime(77 << 12)
        args = dict(block_rows=300, partition=t.partition)
        in_order = [b.serialize(2, t.codec.derive_keys)
                    for b in t.codec.bulk_blocks_iter(s.bulk, ht, **args)]
        makers = t.codec.bulk_block_makers(s.bulk, ht, **args)
        assert len(makers) == len(in_order) > 3
        backwards = [m().serialize(2, t.codec.derive_keys)
                     for m in reversed(makers)][::-1]
        assert backwards == in_order
    _run(body)


def test_partition_hashes_are_computed_once_for_a_table_loaded_by_tablet(
        monkeypatch):
    async def body(s):
        calls = []
        real = table_codec._fnv_rows
        monkeypatch.setattr(table_codec, "_fnv_rows",
                            lambda m: calls.append(len(m)) or real(m))
        more = tpch.generate_lineitem(500, 2000, [SEED, 9], refresh=True)
        n = len(more["l_orderkey"])
        loaded = [p.tablet.bulk_load(more) for p in s.peers()]
        assert sum(loaded) == n and min(loaded) > 0
        # all rows hashed for the partition once; every tablet then
        # hashes the doc keys of the rows it keeps
        assert sorted(calls) == sorted([n] + loaded)
        # other arrays, equal or not, are hashed again
        calls.clear()
        again = {k: v.copy() for k, v in more.items()}
        again["l_linenumber"] = again["l_linenumber"] + 8
        s.peers()[0].tablet.bulk_load(again)
        assert calls[0] == n
        # a buffer filled anew is told apart from what was hashed
        calls.clear()
        again["l_orderkey"][:] = again["l_orderkey"][::-1].copy()
        assert sum(p.tablet.bulk_load(again) for p in s.peers()) == n
        assert calls[0] == n and calls.count(n) == 1
        # and the memo goes with its arrays
        del again, more
        gc.collect()
        assert table_codec._HASHES_MEMO[2] is None
    _run(body)


def test_bulk_load_with_the_memo_reads_back_whole():
    async def body(s):
        rows = (await s.sql.execute(
            f"SELECT count(*) AS n, sum(l_quantity) AS q FROM {TABLE}")).rows
        assert rows[0]["n"] == ROWS
        assert rows[0]["q"] == pytest.approx(float(s.bulk["l_quantity"].sum()))
    _run(body)


# --- a wider cached batch serves a narrower scan ------------------------------
def test_cache_get_covering():
    cache = DeviceBlockCache()
    key = lambda cols: (("mesh", 1, 2), tuple(cols), "ssts", "float64", 4)
    cache._map[key((1, 2, 3))] = ("wide", 10)
    cache._map[key((7,))] = ("other", 10)
    assert cache.lookup(key((2, 3)), 1) == "wide"
    assert cache.lookup(key((1, 2, 3)), 1) == "wide"
    assert cache.lookup(key((3, 7)), 1) is None
    assert cache.lookup(key((2,))[:4] + (2,), 1) is None
    assert cache.hits == 2 and next(reversed(cache._map)) == key((1, 2, 3))


@pytest.mark.skipif(len(jax.devices()) < 4, reason="needs 4 devices")
def test_q6_is_served_by_q1s_batch_on_the_mesh():
    async def body(s):
        from yugabyte_db_tpu.tablet.tablet import _DEVICE_CACHE
        from yugabyte_db_tpu.utils.trace import TRACES
        await s.sql.execute(f"ANALYZE {TABLE}")
        ref = tpch.reference(s.bulk)
        _DEVICE_CACHE.clear()
        q1 = (await s.sql.execute(tpch.SQL["q1"].format(name=TABLE))).rows
        held = len(_DEVICE_CACHE._map)
        with TRACES.trace("forced-root") as root:
            q6 = (await s.sql.execute(tpch.SQL["q6"].format(name=TABLE))).rows
        spans = [x for x in TRACES.finished() if x.trace_id == root.trace_id]
        assert len(_DEVICE_CACHE._map) == held          # nothing built
        assert not [x for x in spans if x.name.startswith("batch.")]
        read = [x for x in spans if x.name == "docdb.read"]
        assert [x.tags.get("route") for x in read] == ["mesh"]
        assert tpch.compare("q6", q6, ref)["sum_usd"] <= 1e-9 * abs(ref["q6"])
        gaps = tpch.compare("q1", q1, ref)
        assert gaps["q1_count_diff"] == 0 and gaps["q1_qty_diff"] == 0
        # the other way round each builds its own: Q1 needs more than Q6
        _DEVICE_CACHE.clear()
        await s.sql.execute(tpch.SQL["q6"].format(name=TABLE))
        await s.sql.execute(tpch.SQL["q1"].format(name=TABLE))
        assert len(_DEVICE_CACHE._map) == 2
    _run(body, chips=4, tablets=8)
