"""ISSUE 28: the newest visible version of a key without a per-query
sort.  `link_versions` gives every row the write time of its next newer
version once, when the batch is built; the kernels' `linked` mask is
then elementwise.  Checked against an independent per-key reference, at
the helper, through `build_batch` for the three kernels that share
`visibility_mask`, through SQL on a table of three SSTs a tablet, and
by the spans that say when the link ran."""
import asyncio

import jax
import numpy as np
import pytest

from yugabyte_db_tpu.ops import AggSpec, Expr
from yugabyte_db_tpu.ops.device_batch import (HT_NONE, build_batch,
                                              link_versions)
from yugabyte_db_tpu.ops.scan import ScanKernel, visibility_mask
from yugabyte_db_tpu.storage.columnar import ColumnarBlock
from yugabyte_db_tpu.utils import flags
from yugabyte_db_tpu.utils.trace import TRACES

C = Expr.col
MAX_HT = int(HT_NONE)


def reference_mask(key_hash, ht, write_id, tombstone, valid, read_ht):
    """Newest visible non-tombstone version per key, row by row: of a
    key's valid rows written at or before `read_ht` the one with the
    largest (ht, write_id) — the later row of exact duplicates — is
    selected unless it is a tombstone."""
    best = {}
    for i in range(len(key_hash)):
        if not valid[i] or int(ht[i]) > read_ht:
            continue
        rank = (int(ht[i]), int(write_id[i]), i)
        k = int(key_hash[i])
        if k not in best or rank > best[k]:
            best[k] = rank
    out = np.zeros(len(key_hash), bool)
    for _, _, i in best.values():
        out[i] = not tombstone[i]
    return out


def random_versions(rng, n, pad=0):
    """`n` row versions over about n/3 keys in random (block) order,
    with `write_id` ties at one `ht`, exact duplicates, tombstones and
    `pad` padding rows that repeat real keys."""
    keys = rng.integers(0, max(n // 3, 1), n).astype(np.uint64)
    # hashes at both ends of the u64 range, so an order that mistook the
    # lane for a signed one would show
    keys = np.where(keys % 2 == 0, keys, HT_NONE - np.uint64(1) - keys)
    ht = rng.integers(1, 12, n).astype(np.uint64) * np.uint64(10)
    wid = rng.integers(0, 3, n).astype(np.uint32)
    tomb = rng.random(n) < 0.15
    valid = np.ones(n + pad, bool)
    valid[n:] = False
    if pad:
        keys = np.concatenate([keys, keys[np.arange(pad) % n]])
        ht = np.concatenate([ht, np.zeros(pad, np.uint64)])
        wid = np.concatenate([wid, np.zeros(pad, np.uint32)])
        tomb = np.concatenate([tomb, np.zeros(pad, bool)])
    return keys, ht, wid, tomb, valid


#: below, between, at and above the versions' times, and "latest"
READ_POINTS = (5, 10, 55, 60, 110, 500, MAX_HT)


# --- (a) the identity, at the helper --------------------------------------

@pytest.mark.parametrize("seed", range(8))
def test_linked_mask_equals_reference(seed):
    rng = np.random.default_rng(seed)
    for _ in range(40):
        n = int(rng.integers(1, 200))
        pad = int(rng.integers(0, 20))
        kh, ht, wid, tomb, valid = random_versions(rng, n, pad)
        next_ht = np.full(n + pad, HT_NONE, np.uint64)
        next_ht[:n], superseded = link_versions(kh[:n], ht[:n], wid[:n])
        # a row has a newer version unless it is its key's last
        assert superseded == n - len(np.unique(kh[:n]))
        assert (next_ht[:n] >= ht[:n]).all()
        for read_ht in READ_POINTS:
            got = np.asarray(visibility_mask(
                "linked", valid, ht, next_ht, tomb, np.uint64(read_ht)))
            want = reference_mask(kh, ht, wid, tomb, valid, read_ht)
            assert (got == want).all(), (seed, n, read_ht)


def test_link_of_unique_keys_is_all_sentinel():
    kh = np.arange(100, dtype=np.uint64)[::-1].copy()
    next_ht, superseded = link_versions(
        kh, np.full(100, 7, np.uint64), np.zeros(100, np.uint32))
    assert superseded == 0 and (next_ht == HT_NONE).all()


def test_write_id_breaks_a_tie_and_a_replayed_write_counts_once():
    kh = np.array([3, 3, 3, 3], np.uint64)
    ht = np.array([20, 20, 20, 10], np.uint64)
    wid = np.array([1, 0, 1, 0], np.uint32)       # rows 0 and 2: a replay
    next_ht, superseded = link_versions(kh, ht, wid)
    assert superseded == 3
    assert next_ht.tolist() == [20, 20, MAX_HT, 20]
    mask = np.asarray(visibility_mask(
        "linked", np.ones(4, bool), ht, next_ht, np.zeros(4, bool),
        np.uint64(25)))
    assert mask.tolist() == [False, False, True, False]
    early = np.asarray(visibility_mask(
        "linked", np.ones(4, bool), ht, next_ht, np.zeros(4, bool),
        np.uint64(15)))
    assert early.tolist() == [False, False, False, True]


# --- (b) through build_batch, for the three kernels -----------------------

def overlapping_blocks(seed, n_keys=300, key_base=0):
    """Three SST-like blocks (each unique-keyed by itself, overlapping
    the others) and a memtable-style overlay block, as `_collect_blocks`
    hands them on: (blocks, the concatenated host lanes)."""
    rng = np.random.default_rng(seed)
    blocks, lanes = [], []
    for sst in range(4):
        overlay = sst == 3
        n = n_keys // 3 if overlay else n_keys - 40 * sst
        keys = (rng.choice(n_keys, n, replace=overlay)
                + key_base).astype(np.uint64)
        ht = np.full(n, 100 + 100 * sst, np.uint64)
        if overlay:
            ht = ht + rng.integers(0, 3, n).astype(np.uint64)
        wid = rng.integers(0, 2, n).astype(np.uint32)
        tomb = rng.random(n) < 0.2
        val = rng.integers(1, 1000, n).astype(np.float64)
        flag = rng.integers(0, 3, n).astype(np.int32)
        blocks.append(ColumnarBlock.from_arrays(
            schema_version=1, key_hash=keys, ht=ht, write_id=wid,
            fixed={1: (val, np.zeros(n, bool)),
                   4: (flag, np.zeros(n, bool))},
            tombstone=tomb, unique_keys=not overlay))
        lanes.append((keys, ht, wid, tomb, val, flag))
    cat = [np.concatenate(x) for x in zip(*lanes)]
    return blocks, cat


AGGS = (AggSpec("sum", C(1).node), AggSpec("count"))
WHERE = (C(4) < 2).node


def expected(cat, read_ht):
    kh, ht, wid, tomb, val, flag = cat
    m = reference_mask(kh, ht, wid, tomb, np.ones(len(kh), bool), read_ht)
    m &= flag < 2
    return float(val[m].sum()), int(m.sum()), m


def run_scan_kernel(blocks, read_ht):
    batch = build_batch(blocks, [1, 4], multi_version=True)
    assert batch.next_ht is not None
    kern = ScanKernel()
    (s, c), _, mask = kern.run(batch, WHERE, AGGS, None, read_ht)
    # an aggregate launch keeps no row mask: a filter launch at the same
    # read point gives it
    assert mask is None
    _, count, mask = kern.run(batch, WHERE, (), None, read_ht)
    assert int(count) == int(c)
    return float(s), int(c), np.asarray(mask)[:batch.n_rows]


def run_fused_plan_kernel(blocks, read_ht):
    from yugabyte_db_tpu.ops.plan_fusion import FusedPlanKernel
    batch = build_batch(blocks, [1, 4], multi_version=True)
    (s, c), _, mask = FusedPlanKernel().run(batch, WHERE, AGGS, None,
                                            read_ht, ())
    return float(s), int(c), np.asarray(mask)[:batch.n_rows]


@pytest.mark.parametrize("run_kernel", [run_scan_kernel,
                                        run_fused_plan_kernel])
@pytest.mark.parametrize("seed", [0, 1])
def test_kernel_serves_overlapping_blocks_and_an_overlay(run_kernel, seed):
    blocks, cat = overlapping_blocks(seed)
    for read_ht in (50, 100, 250, 401, 402, 1000, MAX_HT):
        want_sum, want_count, want_mask = expected(cat, read_ht)
        got_sum, got_count, got_mask = run_kernel(blocks, read_ht)
        assert got_count == want_count, read_ht
        assert got_sum == want_sum, read_ht
        # block order: row i of the mask is row i of the blocks
        assert (got_mask == want_mask).all(), read_ht


def test_a_block_that_is_not_unique_links_without_being_asked():
    blocks, cat = overlapping_blocks(2)
    batch = build_batch(blocks[3:], [1, 4])       # the overlay alone
    assert batch.next_ht is not None
    single = build_batch(blocks[:1], [1, 4])      # one unique block
    assert single.next_ht is None


@pytest.mark.skipif(len(jax.devices()) < 8, reason="needs 8 devices")
@pytest.mark.parametrize("seed", [0, 1])
def test_distributed_kernel_links_per_shard(seed):
    from yugabyte_db_tpu.parallel import tablet_mesh
    from yugabyte_db_tpu.parallel.distributed_scan import (
        DistributedScanKernel, build_sharded_batch)
    tm = tablet_mesh(num_tablet_shards=4, num_block_shards=2)
    per_shard, cats = [], []
    for s in range(8):       # a key lives in one shard
        blocks, cat = overlapping_blocks(10 * seed + s, n_keys=120 + 9 * s,
                                         key_base=1000 * s)
        per_shard.append(blocks)
        cats.append(cat)
    batch = build_sharded_batch(tm, per_shard, [1, 4])
    assert batch.next_ht is not None
    kernel = DistributedScanKernel()
    for read_ht in (100, 250, 402, MAX_HT):
        want = [expected(cat, read_ht) for cat in cats]
        (s, c), _ = kernel.run(batch, WHERE, AGGS, None, read_ht)
        assert int(c) == sum(w[1] for w in want), read_ht
        assert float(s) == sum(w[0] for w in want), read_ht
    assert kernel.compiles == 1


# --- (c), (d) served: three SSTs a tablet, UPDATEs and DELETEs ------------

def _spans(root):
    return [s for s in TRACES.finished() if s.trace_id == root.trace_id]


async def _three_sst_table(tmp_path):
    """t: 600 keys in 2 tablets; SST 1 holds every key, SST 2 UPDATEs
    and DELETEs, SST 3 more of both and re-INSERTs of deleted keys.
    `old` is a session whose transaction began between SST 1 and SST 2,
    `sql` reads the latest."""
    from yugabyte_db_tpu.ql.executor import SqlSession
    from yugabyte_db_tpu.tools.mini_cluster import MiniCluster
    mc = await MiniCluster(str(tmp_path), num_tservers=1).start()
    c = mc.client()
    sql, old = SqlSession(c), SqlSession(c)
    await sql.execute("CREATE TABLE t (k bigint, g bigint, v double, "
                      "PRIMARY KEY (k)) WITH tablets = 2")
    ct = await c._table("t", refresh=True)

    async def flush():
        for loc in ct.locations:
            await c._call_leader(ct, loc.tablet_id, "flush",
                                 {"tablet_id": loc.tablet_id})

    await sql.execute("INSERT INTO t (k, g, v) VALUES " + ", ".join(
        f"({i}, {i % 3}, {float(i)})" for i in range(600)))
    await flush()
    await old.execute("BEGIN")
    await sql.execute("UPDATE t SET v = 1000.0 WHERE k < 100")
    await sql.execute("DELETE FROM t WHERE k >= 550")
    await flush()
    await sql.execute("UPDATE t SET v = 2000.0 WHERE k < 30")
    await sql.execute("DELETE FROM t WHERE k >= 90 AND k < 100")
    await sql.execute("INSERT INTO t (k, g, v) VALUES " + ", ".join(
        f"({i}, 5, 7.0)" for i in range(590, 600)))
    await flush()
    ssts = [len(mc.tservers[0].peers[loc.tablet_id].tablet.regular.ssts)
            for loc in ct.locations]
    assert ssts == [3, 3], ssts
    return mc, c, sql, old


QUERIES = {
    "aggregate": "SELECT sum(v), count(*), min(v), max(v) FROM t",
    "aggregate_where": "SELECT sum(v), count(*) FROM t WHERE v >= 500",
    "group_by": "SELECT g, sum(v), count(*) FROM t GROUP BY g ORDER BY g",
    "filter": "SELECT k, v FROM t WHERE v > 540 ORDER BY k",
}
#: `aggregate_where` at the latest read time, from the statements above
LATEST_SUM = 30 * 2000.0 + 60 * 1000.0 + sum(range(500, 550))
LATEST_COUNT = 30 + 60 + 50


def test_served_scans_of_three_ssts_match_the_interpreted_path(tmp_path):
    async def main():
        flags.set_flag("tpu_min_rows_for_pushdown", 1)
        mc, c, sql, old = await _three_sst_table(tmp_path)
        try:
            for name, text in QUERIES.items():
                for session in (sql, old):
                    with TRACES.trace("forced-root") as root:
                        served = (await session.execute(text)).rows
                    spans = _spans(root)
                    scans = [s for s in spans if s.name == "device.scan"]
                    assert len(scans) == 2, (name, len(scans))
                    assert all(s.tags["mvcc"] == "linked" for s in scans)
                    flags.set_flag("tpu_pushdown_enabled", False)
                    try:
                        with TRACES.trace("forced-root") as root:
                            plain = (await session.execute(text)).rows
                    finally:
                        flags.REGISTRY.reset("tpu_pushdown_enabled")
                    assert not any(s.name == "device.scan"
                                   for s in _spans(root))
                    assert served == plain, (name, session is old)
            latest = (await sql.execute(QUERIES["aggregate_where"])).rows[0]
            assert latest["count"] == LATEST_COUNT
            assert latest["sum_v"] == LATEST_SUM
            snapshot = (await old.execute(QUERIES["aggregate"])).rows[0]
            assert snapshot["count"] == 600
            assert snapshot["sum_v"] == float(sum(range(600)))
            kept = (await sql.execute(QUERIES["filter"])).rows
            assert [r["k"] for r in kept] == (
                list(range(90)) + list(range(541, 550)))
            await old.execute("ROLLBACK")
        finally:
            flags.REGISTRY.reset("tpu_min_rows_for_pushdown")
            await c.messenger.shutdown()
            await mc.shutdown()
    asyncio.run(main())


def test_version_link_span_on_a_miss_and_none_on_a_hit(tmp_path):
    async def main():
        flags.set_flag("tpu_min_rows_for_pushdown", 1)
        mc, c, sql, old = await _three_sst_table(tmp_path)
        try:
            runs = []
            for _ in range(2):
                with TRACES.trace("forced-root") as root:
                    await sql.execute(QUERIES["aggregate"])
                runs.append(_spans(root))
            await old.execute("ROLLBACK")
        finally:
            flags.REGISTRY.reset("tpu_min_rows_for_pushdown")
            await c.messenger.shutdown()
            await mc.shutdown()
        miss, hit = runs
        by_id = {s.span_id: s for s in miss}
        links = [s for s in miss if s.name == "batch.version_link"]
        assert len(links) == 2                      # one per tablet
        for s in links:
            assert by_id[s.parent_id].name == "batch.build"
        # 600 + (100 + 50) + (30 + 10 + 10) row versions, of which the
        # UPDATEd, DELETEd and re-INSERTed keys' older ones are superseded
        assert sum(s.tags["rows"] for s in links) == 800
        assert sum(s.tags["superseded"] for s in links) == 200
        assert [s.tags["cache"] for s in miss
                if s.name == "docdb.batch"] == ["miss", "miss"]
        assert [s.tags["cache"] for s in hit
                if s.name == "docdb.batch"] == ["hit", "hit"]
        assert not any(s.name.startswith("batch.") for s in hit)
        for spans in runs:
            modes = [s.tags["mvcc"] for s in spans
                     if s.name == "device.scan"]
            assert modes == ["linked", "linked"]
    asyncio.run(main())
