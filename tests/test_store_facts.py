"""Store facts (`docdb.operations.StoreFacts`): what a device read needs
to know about a store's blocks beyond its cached batch — the newest
write time, the chunk-safety proof, the streaming route's dictionary
plans — is made once per store contents and looked up by a read of
unchanged contents.  The restart semantics, the pruning and the answers
are the ones a walk, a proof and a plan a read gave."""
import tempfile

import numpy as np
import pytest

from yugabyte_db_tpu.docdb import RowOp, WriteRequest
from yugabyte_db_tpu.docdb.operations import (DocReadOperation, ReadRequest,
                                              ReadRestartError,
                                              _skew_window_ht, run_steps)
from yugabyte_db_tpu.docdb.table_codec import TableInfo
from yugabyte_db_tpu.dockv.packed_row import (ColumnSchema, ColumnType,
                                              TableSchema)
from yugabyte_db_tpu.dockv.partition import PartitionSchema
from yugabyte_db_tpu.ops import AggSpec, stream_scan
from yugabyte_db_tpu.ops.expr import Expr
from yugabyte_db_tpu.ops.grouped_scan import DictGroupSpec
from yugabyte_db_tpu.ops.scan import zone_prune_blocks
from yugabyte_db_tpu.tablet import Tablet
from yugabyte_db_tpu.tablet.tablet import _DEVICE_CACHE
from yugabyte_db_tpu.utils import flags, metrics
from yugabyte_db_tpu.utils.hybrid_time import (HybridClock, HybridTime,
                                               MockPhysicalClock)
from yugabyte_db_tpu.utils.trace import TRACES
from tests.test_grouped_scan import _by_key

C = Expr.col
RF = np.array(["A", "N", "R"], object)
LS = np.array(["F", "O"], object)
N = 12_000                       # a load: above tpu_min_rows_for_pushdown
NOW_US = 1_000_000               # the mock clock stands here
OLD = HybridTime.from_micros(900_000)
AHEAD = HybridTime.from_micros(NOW_US + 200_000)   # inside the 500-ms skew


def _rows(lo, n, seed, step=1):
    rng = np.random.default_rng(seed)
    return {"k": lo + step * np.arange(n, dtype=np.int64),
            "rf": RF[rng.integers(0, 3, n)], "ls": LS[rng.integers(0, 2, n)],
            "qty": rng.integers(1, 50, n).astype(np.float64)}


def make_tablet(name, loads=2, ranged=False, n=N, interleaved=False):
    """`loads` bulk loads of disjoint keys, each one SST written at `OLD`:
    hash-sharded keys interleave between the SSTs (as do `interleaved`
    range keys), so two loads are not chunk-safe; one is."""
    key = (ColumnSchema(0, "k", ColumnType.INT64, is_range_key=True)
           if ranged else
           ColumnSchema(0, "k", ColumnType.INT64, is_hash_key=True))
    schema = TableSchema((key,
                          ColumnSchema(1, "rf", ColumnType.STRING),
                          ColumnSchema(2, "ls", ColumnType.STRING),
                          ColumnSchema(3, "qty", ColumnType.FLOAT64)), 1)
    part = PartitionSchema("range", 0) if ranged \
        else PartitionSchema("hash", 1)
    t = Tablet(name, TableInfo("li", "li", schema, part),
               tempfile.mkdtemp(prefix=f"facts-{name}-"),
               clock=HybridClock(MockPhysicalClock(NOW_US)),
               owner=f"ts-{name}")
    for i in range(loads):
        lo, step = (i, loads) if interleaved else (i * n, 1)
        t.bulk_load(_rows(lo, n, seed=i, step=step), ht=OLD, block_rows=2048)
    assert len(t.regular.ssts) == loads
    return t


def counters(t):
    ent = metrics.REGISTRY.entity("server", t.owner)
    return (ent.counter("store_facts_hits").value(),
            ent.counter("store_facts_misses").value())


def count_req(**kw):
    return ReadRequest("li", aggregates=(AggSpec("count"),
                                         AggSpec("sum", C(3).node)), **kw)


def traced_read(t, req):
    """(response, the statement's finished spans)."""
    with TRACES.trace("facts-test") as root:
        resp = t.read(req)
    return resp, [s for s in TRACES.finished()
                  if s.trace_id == root.trace_id]


def facts_tag(spans):
    (read,) = [s for s in spans if s.name == "docdb.read"]
    return read.tags.get("facts")


def reference_walk(blocks, read_ht):
    """The walk every read made before facts: the restart time is the
    newest record in the window of the first block that holds one."""
    hi = read_ht + _skew_window_ht()
    for b in blocks:
        amb = b.ht[(b.ht > np.uint64(read_ht)) & (b.ht <= np.uint64(hi))]
        if len(amb):
            return int(amb.max())
    return None


@pytest.fixture
def walks(monkeypatch):
    """Spy on the block walk: one entry a call, the rows it was handed."""
    seen = []
    real = DocReadOperation._walk_restart_window

    def spy(blocks, read_ht):
        seen.append(sum(b.n for b in blocks))
        return real(blocks, read_ht)
    monkeypatch.setattr(DocReadOperation, "_walk_restart_window",
                        staticmethod(spy))
    return seen


@pytest.fixture(autouse=True)
def _reset_flags():
    yield
    for f in ("grouped_pushdown_enabled", "streaming_chunk_rows",
              "streaming_scan_enabled"):
        flags.REGISTRY.reset(f)


# --- (a) a read above every write walks nothing ----------------------------
def test_read_above_every_write_walks_nothing_and_then_hits(walks):
    t = make_tablet("a")
    resp, spans = traced_read(t, count_req())
    assert resp.backend == "tpu" and int(resp.agg_values[0]) == 2 * N
    assert facts_tag(spans) == "miss" and counters(t) == (0, 1)
    resp, spans = traced_read(t, count_req())
    assert int(resp.agg_values[0]) == 2 * N
    assert facts_tag(spans) == "hit" and counters(t) == (1, 1)
    assert walks == []                   # restarts were on, nothing walked
    facts = t.regular.read_facts
    assert facts.max_ht == OLD.value and facts.chunk_safe is False
    # the gate without facts is the walk a read made before: every row
    op = t.read_op("li")
    op._check_restart_window(op._collect_blocks(), t.clock.now().value, True)
    assert walks == [2 * N]


# --- (b) a record inside the window still restarts, where the walk says ----
@pytest.mark.parametrize("shape", ["multi_sst", "memtable_overlay"])
def test_record_in_the_window_restarts_at_the_walks_time(shape, walks):
    t = make_tablet(f"b-{shape}")
    t.read(count_req())                  # facts of the quiet store
    extra = 7
    if shape == "multi_sst":
        t.bulk_load(_rows(10 * N, extra, seed=9), ht=AHEAD)
        assert len(t.regular.ssts) == 3
    else:
        t.apply_write(WriteRequest("li", [
            RowOp("upsert", {"k": 10 * N + i, "rf": "A", "ls": "F",
                             "qty": 1.0}) for i in range(extra)]), ht=AHEAD)
        assert not t.regular.memtable_empty()
    op = t.read_op("li")
    read_ht = HybridTime.from_micros(NOW_US).value
    want = reference_walk(op._collect_blocks(), read_ht)
    assert want == AHEAD.value
    req = count_req(read_ht=read_ht)
    req.server_assigned_read_ht = True
    before = len(walks)
    with pytest.raises(ReadRestartError) as e:
        run_steps(op._execute_once_steps(req))
    assert e.value.restart_ht == want
    assert len(walks) == before + 1      # the slow path is the walk
    assert t.regular.read_facts.max_ht == AHEAD.value
    # served: the read restarts and sees the rows written ahead of its clock
    resp = t.read(count_req())
    assert int(resp.agg_values[0]) == 2 * N + extra
    # an explicit read time never restarts
    resp = t.read(count_req(read_ht=read_ht))
    assert int(resp.agg_values[0]) == 2 * N


# --- (c) facts die with the contents they describe -------------------------
@pytest.mark.parametrize("event", ["insert", "flush", "compaction"])
def test_a_change_of_contents_makes_the_next_read_a_miss(event, walks):
    t = make_tablet(f"c-{event}")
    op = t.read_op("li")
    old_ht = HybridTime.from_micros(NOW_US).value
    quiet = count_req(read_ht=old_ht)
    quiet.server_assigned_read_ht = True
    run_steps(op._execute_once_steps(quiet))              # miss: facts made
    run_steps(op._execute_once_steps(quiet))              # hit
    assert counters(t) == (1, 1) and walks == []
    made = t.regular.read_facts
    assert made.max_ht == OLD.value
    t.apply_write(WriteRequest("li", [
        RowOp("upsert", {"k": 10 * N, "rf": "R", "ls": "O", "qty": 3.0})]),
        ht=AHEAD)
    if event == "flush":
        t.flush()
        assert t.regular.read_facts is None      # dropped with the batches
    elif event == "compaction":
        t.compact()
        assert t.regular.read_facts is None
        assert len(t.regular.ssts) == 1
    # no stale fact: a read at the old time meets the new record
    again = count_req(read_ht=old_ht)
    again.server_assigned_read_ht = True
    with pytest.raises(ReadRestartError) as e:
        run_steps(op._execute_once_steps(again))
    assert e.value.restart_ht == AHEAD.value
    assert counters(t) == (1, 2)
    facts = t.regular.read_facts
    assert facts is not made and facts.max_ht == AHEAD.value
    assert facts.key == (tuple(r.path for r in t.regular.ssts),
                         t.regular.write_generation())
    assert facts.chunk_safe is (event == "compaction")
    # and the next read of these contents is a hit again (twice: it
    # restarts once, at the new record's time)
    resp, spans = traced_read(t, count_req())
    assert facts_tag(spans) == "hit" and counters(t) == (3, 2)
    assert int(resp.agg_values[0]) == 2 * N + 1


# --- (d) no plan is made for a route that does not use it ------------------
def test_monolithic_dict_group_makes_no_plan(monkeypatch):
    t = make_tablet("d")
    plans = []
    real = stream_scan.make_dict_plan
    monkeypatch.setattr(stream_scan, "make_dict_plan",
                        lambda *a, **k: plans.append(a) or real(*a, **k))
    req = dict(aggregates=(AggSpec("sum", C(3).node), AggSpec("count")),
               group_by=DictGroupSpec(cols=(1, 2)), where=(C(3) > 2.0).node)
    for _ in range(2):
        resp, spans = traced_read(t, ReadRequest("li", **req))
        names = [s.name for s in spans]
        assert resp.backend == "tpu" and "device.scan" in names
        assert "device.dict_plan" not in names and plans == []
    assert not t.regular.read_facts.plans.get("li")
    flags.set_flag("grouped_pushdown_enabled", False)
    interpreted = t.read(ReadRequest("li", **req))
    assert interpreted.backend == "cpu"
    assert len(_by_key(resp)) == 6 and _by_key(resp) == _by_key(interpreted)


# --- (e) a read that streams keeps its plan --------------------------------
def test_streamed_text_predicate_builds_its_plan_once(monkeypatch):
    t = make_tablet("e", loads=1, n=2 * N)
    flags.set_flag("streaming_chunk_rows", 4096)
    plans = []
    real = stream_scan.make_dict_plan
    monkeypatch.setattr(stream_scan, "make_dict_plan",
                        lambda *a, **k: plans.append(a) or real(*a, **k))
    req = dict(where=C(1).eq("A").node)

    def streamed():
        stream_scan.LAST_STREAM_STATS.clear()
        resp, spans = traced_read(t, count_req(**req))
        assert resp.backend == "tpu"
        assert stream_scan.LAST_STREAM_STATS.get("chunks", 0) >= 3
        return ([np.asarray(v).tolist() for v in resp.agg_values],
                [s.name for s in spans].count("device.dict_plan"))

    first, made = streamed()
    assert made == 1 and len(plans) == 1
    assert t.regular.read_facts.chunk_safe is True
    second, made = streamed()
    assert second == first and made == 0 and len(plans) == 1
    # the chunks leave the device and the SST decodes its blocks anew: the
    # kept plan is handed out by block position, and builds the same batches
    _DEVICE_CACHE.invalidate_prefix((id(t.regular),))
    for r in t.regular.ssts:
        r._col_cache.clear()
    third, made = streamed()
    assert third == first and made == 0 and len(plans) == 1
    # the monolithic batch gives the same answer
    flags.set_flag("streaming_scan_enabled", False)
    stream_scan.LAST_STREAM_STATS.clear()
    mono = t.read(count_req(**req))
    assert not stream_scan.LAST_STREAM_STATS
    assert int(mono.agg_values[0]) == first[0]
    assert float(mono.agg_values[1]) == pytest.approx(first[1], rel=1e-12)
    # the plan is kept with the contents it was made for, and goes with them
    kept = t.regular.read_facts
    assert list(kept.plans["li"]) == [(1,)]
    flags.REGISTRY.reset("streaming_scan_enabled")
    t.bulk_load(_rows(10 * N, 64, seed=5), ht=OLD)
    resp = t.read(count_req(**req))      # two SSTs: the monolithic batch
    assert t.regular.read_facts is not kept
    assert not t.regular.read_facts.plans.get("li") and len(plans) == 1
    assert int(resp.agg_values[0]) > first[0]


# --- (f) zone pruning is fed by the fact -----------------------------------
@pytest.mark.parametrize("chunk_safe", [True, False])
def test_zone_prune_prunes_what_it_pruned(chunk_safe):
    t = make_tablet(f"f-{chunk_safe}", loads=1 if chunk_safe else 2,
                    ranged=True, interleaved=True)
    op = t.read_op("li")
    where = (C(0) < 3000).node
    blocks, facts = op._collect_with_facts()
    assert facts.chunk_safe is chunk_safe is stream_scan.chunk_safe_mvcc(
        blocks)
    by_zone_maps, idx = zone_prune_blocks(blocks, where)
    assert len(by_zone_maps) < len(blocks)       # the maps alone would prune
    read_ht = t.clock.now().value
    kept, key = op._zone_prune(blocks, where, read_ht, facts.chunk_safe)
    if chunk_safe:
        assert [id(b) for b in kept] == [id(b) for b in by_zone_maps]
        assert key == ("zp", idx)
    else:
        assert kept is blocks and key == ()      # a block may hide a version
    # served, pruned or not: the rows below the bound
    resp = t.read(count_req(where=where))
    assert resp.backend == "tpu" and int(resp.agg_values[0]) == 3000
