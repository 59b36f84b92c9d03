"""Tracing/ASH/webserver/encryption/CLI tests."""
import asyncio
import threading
import time
import urllib.request

import numpy as np
import pytest

from yugabyte_db_tpu.tserver.webserver import StatusWebServer
from yugabyte_db_tpu.utils import flags, metrics
from yugabyte_db_tpu.utils import trace as trace_mod
from yugabyte_db_tpu.utils.encryption import (
    CipherStream, KEY_MANAGER, UniverseKeyManager,
)
from yugabyte_db_tpu.utils.trace import (
    ASH, AshSampler, TRACE, TRACES, wait_status,
)

from yugabyte_db_tpu.utils.encryption import aes_available

requires_aes = pytest.mark.skipif(
    not aes_available(),
    reason="cryptography provider not installed in this image")


def run(coro):
    return asyncio.run(coro)


class TestTrace:
    def test_trace_records_and_rpcz(self):
        with TRACES.trace("read-query") as t:
            TRACE("picked read time")
            TRACE("scan done")
        assert len(t.events) == 2
        assert "read-query" in t.dump()

    def test_ash_sampling(self):
        state = {"s": "Idle"}
        ASH.register(lambda: ("worker", state["s"]))
        state["s"] = "WaitingOnRaft"
        ASH.sample_once()
        state["s"] = "Idle"
        ASH.sample_once()
        hist = ASH.histogram()
        assert hist.get("WaitingOnRaft", 0) >= 1


class TestSpanPropagation:
    """ISSUE 14: span context flows through task spawn, executor hops
    (explicit capture) and the RPC wire; sampled=0 propagates no-op."""

    def test_child_span_inherits_trace_and_parents(self):
        with TRACES.trace("root") as root:
            with TRACES.span("child") as child:
                assert child.trace_id == root.trace_id
                assert child.parent_id == root.span_id
                assert child.span_id != root.span_id

    def test_contextvar_survives_task_spawn(self):
        async def go():
            with TRACES.trace("root") as root:
                async def task_body():
                    return trace_mod.current_context()
                ctx = await asyncio.create_task(task_body())
                assert ctx.trace_id == root.trace_id
                assert ctx.span_id == root.span_id
        run(go())

    def test_executor_hop_needs_explicit_capture(self):
        async def go():
            loop = asyncio.get_running_loop()
            with TRACES.trace("root") as root:
                # WITHOUT capture: the thread sees no context
                naked = await loop.run_in_executor(
                    None, trace_mod.current_context)
                assert naked is None

                # WITH explicit capture + use_context: the thread-side
                # span lands in the same trace, parented correctly
                ctx = trace_mod.current_context()

                def thread_side():
                    with trace_mod.use_context(ctx):
                        with TRACES.span("thread-work",
                                         child_only=True) as sp:
                            return (sp.trace_id, sp.parent_id)
                tid, pid = await loop.run_in_executor(None, thread_side)
                assert tid == root.trace_id
                assert pid == root.span_id
        run(go())

    def test_rpc_wire_roundtrip_parents_server_span(self):
        from yugabyte_db_tpu.rpc.messenger import Messenger

        class Svc:
            async def rpc_ping(self, payload):
                ctx = trace_mod.current_context()
                return {"trace_id": ctx.trace_id if ctx else 0,
                        "sampled": bool(ctx and ctx.sampled)}

        async def go():
            m1, m2 = Messenger("c"), Messenger("s")
            m2.register_service("svc", Svc())
            addr = await m2.start()
            try:
                with TRACES.trace("client-op") as root:
                    r = await m1.call(addr, "svc", "ping", {})
                    assert r["sampled"]
                    assert r["trace_id"] == root.trace_id
                # chain: root <- rpc.c.svc.ping <- rpc.s.svc.ping
                recent = {t.name: t for t in TRACES.recent}
                cspan = recent["rpc.c.svc.ping"]
                sspan = recent["rpc.s.svc.ping"]
                assert cspan.parent_id == root.span_id
                assert sspan.parent_id == cspan.span_id
                assert sspan.trace_id == root.trace_id
            finally:
                await m1.shutdown()
                await m2.shutdown()
        run(go())

    def test_unsampled_propagates_as_noop(self):
        from yugabyte_db_tpu.rpc.messenger import Messenger

        class Svc:
            async def rpc_ping(self, payload):
                # downstream spans under an unsampled context must be
                # the shared no-op (nothing recorded)
                with TRACES.span("inner", child_only=True) as sp:
                    return {"sampled": sp.sampled}

        async def go():
            m1, m2 = Messenger("c"), Messenger("s")
            m2.register_service("svc", Svc())
            addr = await m2.start()
            flags.set_flag("trace_sampling_rate", 0.0)
            try:
                before = len(TRACES.recent)
                r = await m1.call(addr, "svc", "ping", {})
                assert r["sampled"] is False
                assert len(TRACES.recent) == before   # zero new spans
            finally:
                flags.REGISTRY.reset("trace_sampling_rate")
                await m1.shutdown()
                await m2.shutdown()
        run(go())

    def test_root_sampling_rate_zero_and_one(self):
        flags.set_flag("trace_sampling_rate", 0.0)
        try:
            with TRACES.span("maybe") as sp:
                assert not sp.sampled
            flags.set_flag("trace_sampling_rate", 1.0)
            with TRACES.span("always") as sp:
                assert sp.sampled
        finally:
            flags.REGISTRY.reset("trace_sampling_rate")

    def test_wire_inject_extract(self):
        assert trace_mod.extract(None) is None
        assert trace_mod.extract([1, 2, 0]).sampled is False
        ctx = trace_mod.extract([7, 9, 1])
        assert (ctx.trace_id, ctx.span_id, ctx.sampled) == (7, 9, True)
        assert trace_mod.extract("garbage") is None


class TestTraceRegistryRaces:
    def test_add_never_throws_after_finish(self):
        with TRACES.trace("t") as t:
            pass
        t.add("late event")          # after finish(): no raise
        t.set_tag("late", True)

    def test_rpcz_snapshot_race_with_appender(self):
        """A thread hammering Trace.add while rpcz() dumps must never
        raise (events snapshot under the registry lock)."""
        stop = threading.Event()
        errors = []

        def appender():
            try:
                with TRACES.trace("racy") as t:
                    while not stop.is_set():
                        t.add("x")
            except Exception as e:   # noqa: BLE001
                errors.append(e)

        th = threading.Thread(target=appender)
        th.start()
        try:
            for _ in range(200):
                TRACES.rpcz()
                TRACES.tracez()
        finally:
            stop.set()
            th.join(5.0)
        assert not errors

    def test_tracez_stamped_with_pid_and_ts(self):
        import os as _os
        with TRACES.trace("snap"):
            TRACE("e")
        d = TRACES.tracez()
        assert d["pid"] == _os.getpid()
        assert abs(d["ts"] - time.time()) < 5.0
        assert any(s["name"] == "snap" for s in d["spans"])
        assert "wait_states" in d["ash"]


class TestAsh:
    def test_provider_crash_swallowed(self):
        """Regression for sample_once's bare except: one crashing
        provider must not kill the sampler or starve later providers."""
        sampler = AshSampler()

        def bad():
            raise RuntimeError("provider exploded")
        hits = []

        def good():
            hits.append(1)
            return ("good", "WAL_Fsync")
        sampler.register(bad)
        sampler.register(good)
        sampler.sample_once()
        sampler.sample_once()
        assert sampler.samples_taken == 2
        assert len(hits) == 2
        assert sampler.histogram().get("WAL_Fsync", 0) >= 2
        assert sampler.summary()["cumulative"]["WAL_Fsync"] >= 2

    def test_wait_status_feeds_sampler_across_threads(self):
        """The active-wait table is process-global: a sampler running
        in THIS thread sees a wait_status scope held by another."""
        sampler = AshSampler()
        entered = threading.Event()
        release = threading.Event()

        def blocked_thread():
            with wait_status("Flush_SstWrite", component="flush"):
                entered.set()
                release.wait(5.0)

        th = threading.Thread(target=blocked_thread)
        th.start()
        try:
            assert entered.wait(5.0)
            sampler.sample_once()
        finally:
            release.set()
            th.join(5.0)
        assert sampler.histogram().get("Flush_SstWrite", 0) >= 1
        by_comp = sampler.summary()["by_component"]
        assert "flush" in by_comp

    def test_wait_status_rejects_free_text(self):
        with pytest.raises(ValueError):
            with wait_status("TotallyMadeUpState"):
                pass

    def test_sampler_thread_start_stop(self):
        sampler = AshSampler()
        sampler.start(interval_ms=5)
        time.sleep(0.1)
        sampler.stop()
        assert sampler.samples_taken >= 2

    def test_provider_deduped_against_wait_scope(self):
        """A component provider echoing a state already published by a
        wait_status scope that tick must not double-count it (the
        session-weighted scope signal wins)."""
        sampler = AshSampler()
        sampler.register(lambda: ("flush:x", "Flush_SstWrite"))
        with wait_status("Flush_SstWrite", component="flush"):
            sampler.sample_once()
        assert sampler.summary()["cumulative"]["Flush_SstWrite"] == 1
        # without the scope, the provider's coarse signal DOES count
        sampler.sample_once()
        assert sampler.summary()["cumulative"]["Flush_SstWrite"] == 2

    def test_unregister_stops_provider(self):
        sampler = AshSampler()
        calls = []

        def p():
            calls.append(1)
            return ("c", "Compaction_Run")
        sampler.register(p)
        sampler.sample_once()
        sampler.unregister(p)
        sampler.unregister(p)     # idempotent
        sampler.sample_once()
        assert len(calls) == 1


class TestHistogramSnapshot:
    def test_single_pass_matches_percentile(self):
        h = metrics.Histogram("h")
        for v in (1, 10, 100, 1000, 10000, 100000):
            for _ in range(7):
                h.increment(v)
        st = h.snapshot_stats()
        assert st["count"] == h.count()
        assert st["mean_us"] == pytest.approx(h.mean())
        for p in (50, 95, 99):
            assert st[f"p{p}_us"] == h.percentile(p)

    def test_empty_histogram(self):
        h = metrics.Histogram("e")
        st = h.snapshot_stats()
        assert st == {"count": 0, "mean_us": 0.0, "p50_us": 0.0,
                      "p95_us": 0.0, "p99_us": 0.0}

    def test_metrics_snapshot_stamped(self):
        import os as _os
        snap = metrics.snapshot()
        assert snap["pid"] == _os.getpid()
        assert abs(snap["ts"] - time.time()) < 5.0


class TestCollector:
    def _dump(self, pid, spans):
        return {"pid": pid, "ts": time.time(), "spans": spans,
                "active": [], "ash": {}}

    def _span(self, tid, sid, parent, name):
        return {"trace_id": tid, "span_id": sid, "parent_id": parent,
                "name": name, "start_unix": time.time(),
                "duration_ms": 1.0, "finished": True, "tags": {},
                "events": []}

    def test_stitch_across_processes(self):
        from yugabyte_db_tpu.cluster.collector import stitch, tree_names
        d1 = self._dump(100, [self._span(1, 10, 0, "client"),
                              self._span(1, 11, 10, "rpc.c.write")])
        d2 = self._dump(200, [self._span(1, 12, 11, "rpc.s.write"),
                              self._span(1, 13, 12, "tablet.apply")])
        trees = stitch([d1, d2])
        assert set(trees) == {1}
        t = trees[1]
        assert t["span_count"] == 4
        assert t["pids"] == [100, 200]
        assert len(t["roots"]) == 1
        names = tree_names(t["roots"][0])
        assert names == ["client", "rpc.c.write", "rpc.s.write",
                         "tablet.apply"]

    def test_orphan_span_becomes_root(self):
        from yugabyte_db_tpu.cluster.collector import stitch
        d = self._dump(1, [self._span(5, 50, 999, "orphan")])
        trees = stitch([d])
        assert trees[5]["roots"][0]["name"] == "orphan"

    def test_dominant_wait_and_attribution(self):
        from yugabyte_db_tpu.cluster.collector import (
            attribute_rounds, dominant_wait)
        # CPU buckets excluded while a blocking state exists
        assert dominant_wait({"OnCpu_Read": 100,
                              "Flush_SstWrite": 5}) == "Flush_SstWrite"
        # pure-CPU window: CPU is the honest fallback
        assert dominant_wait({"OnCpu_Read": 9}) == "OnCpu_Read"
        assert dominant_wait({}) is None
        rounds = [
            {"tag": "r0", "p99_ms": 10.0, "wait_delta": {}},
            {"tag": "r1", "p99_ms": 11.0,
             "wait_delta": {"WAL_Fsync": 2}},
            {"tag": "spike", "p99_ms": 200.0,
             "wait_delta": {"Flush_SstWrite": 40, "WAL_Fsync": 3}},
        ]
        attr = attribute_rounds(rounds, spread_gate=3.0)
        assert attr["over_spread_rounds"] == ["spike"]
        spike = [r for r in attr["rounds"] if r["tag"] == "spike"][0]
        assert spike["over_spread"]
        assert spike["dominant_wait"] == "Flush_SstWrite"
        assert spike["category"] == "flush"

    def test_every_wait_state_has_category(self):
        from yugabyte_db_tpu.cluster.collector import WAIT_CATEGORIES
        from yugabyte_db_tpu.utils.trace import WAIT_STATES
        uncovered = {s for s in WAIT_STATES if s != "Idle"} \
            - set(WAIT_CATEGORIES)
        assert not uncovered, (
            f"wait states missing an attribution category: {uncovered}")


class TestDeviceTelemetry:
    @staticmethod
    def _batch():
        from tests.test_ops_scan import make_block
        from yugabyte_db_tpu.ops.device_batch import build_batch
        blk, _ = make_block(n=512, seed=3)
        return build_batch([blk], [1, 2])

    def test_scan_launch_span_tagged(self):
        from yugabyte_db_tpu.ops import AggSpec, Expr, scan_aggregate
        batch = self._batch()
        where = (Expr.col(1) < 25.0).node
        aggs = (AggSpec("sum", Expr.col(2).node), AggSpec("count"))
        with TRACES.trace("scan-op") as t:
            scan_aggregate(batch, where, aggs)
            scan_aggregate(batch, where, aggs)
        spans = [s for s in TRACES.recent
                 if s.trace_id == t.trace_id
                 and s.name == "device.scan"]
        assert len(spans) == 2
        # first launch may or may not compile (shared kernel cache is
        # process-global), but the second MUST hit with the same sig
        assert spans[-1].tags["codepath"] == "cache_hit"
        assert spans[0].tags["signature"] == spans[1].tags["signature"]
        assert spans[0].tags["bucket"] == batch.padded_rows
        assert spans[0].tags["rows"] == batch.n_rows
        # what crosses the host-device boundary a launch: the host values
        # the jitted call placed (one int64 vector: read_ht; one float64
        # vector: the SUM's scale and the literal) ...
        assert [s.tags["host_args"] for s in spans] == [2, 2]
        # ... in how many row tiles the program ran the lane (512 rows:
        # one; `ops/scan.py tile_count`) ...
        assert [s.tags["tiles"] for s in spans] == [1, 1]
        waits = [s for s in TRACES.recent
                 if s.trace_id == t.trace_id and s.name == "device.wait"]
        # ... and the transfers that brought the result back: one, of
        # one array (the SUM, both counts: int64)
        assert [s.tags["reads"] for s in waits] == [1, 1]
        assert [s.tags["result_leaves"] for s in waits] == [1, 1]
        assert {s.tags["thread"] for s in waits} == {"executor"}

    def test_no_spans_without_sampled_trace(self):
        from yugabyte_db_tpu.ops import AggSpec, scan_aggregate
        batch = self._batch()
        before = len([s for s in TRACES.recent
                      if s.name == "device.scan"])
        scan_aggregate(batch, None, (AggSpec("count"),))
        after = len([s for s in TRACES.recent
                     if s.name == "device.scan"])
        assert after == before


class TestClusterSpanTree:
    """ISSUE 14 acceptance: ONE acked cluster write produces ONE
    stitched cross-process span tree — client (this process) ->
    leader tserver (RPC server span, raft append+fsync, tablet apply,
    flush handoff) -> follower (consensus RPC server span, WAL
    append) — assembled from rpc_tracez dumps by cluster/collector."""

    def test_write_span_tree_stitches_across_processes(self, tmp_path):
        import os as _os

        from yugabyte_db_tpu.cluster import ClusterSupervisor
        from yugabyte_db_tpu.cluster.collector import (
            collect_cluster_tracez, stitch, tree_names)
        from yugabyte_db_tpu.docdb.table_codec import TableInfo
        from yugabyte_db_tpu.dockv.packed_row import (
            ColumnSchema, ColumnType, TableSchema)
        from yugabyte_db_tpu.dockv.partition import PartitionSchema

        info = TableInfo("", "kv", TableSchema(columns=(
            ColumnSchema(0, "k", ColumnType.INT64, is_hash_key=True),
            ColumnSchema(1, "v", ColumnType.FLOAT64)), version=1),
            PartitionSchema("hash", 1))

        async def main():
            sup = await ClusterSupervisor(str(tmp_path),
                                          num_tservers=2).start()
            c = None
            try:
                c = sup.client()
                await c.create_table(info, num_tablets=1,
                                     replication_factor=2)
                # a tiny flush threshold makes THIS write cross it, so
                # the apply triggers the flush-executor handoff and the
                # tree gets its flush.background leaf
                await sup.set_flag_all("memstore_flush_threshold_bytes",
                                       2000, roles=("tserver",))
                with TRACES.trace("user-write") as root:
                    n = await c.insert("kv", [
                        {"k": i, "v": float(i)} for i in range(200)])
                assert n == 200
                # follower append + leader apply + background flush all
                # finish within the replicate round; give stragglers a
                # moment before dumping
                await asyncio.sleep(1.0)
                dumps = await collect_cluster_tracez(sup)
                local = TRACES.tracez()
                local["process"] = "test-client"
                trees = stitch(dumps + [local])
                assert root.trace_id in trees, (
                    "the root trace vanished from every dump")
                t = trees[root.trace_id]
                names = []
                for r in t["roots"]:
                    names.extend(tree_names(r))
                # client -> leader -> follower: at least 3 distinct
                # pids contribute spans (test process + 2 tservers)
                assert len(t["pids"]) >= 3, (t["pids"], names)
                assert _os.getpid() in t["pids"]

                def has(prefix):
                    return any(nm.startswith(prefix) for nm in names)
                assert has("rpc.c.tserver.write"), names   # client stamp
                assert has("rpc.s.tserver.write"), names   # leader serve
                # leader append+fsync (fused or legacy path)
                assert has("raft.append_group") or \
                    has("raft.replicate"), names
                # follower WAL append via the consensus RPC
                assert has("rpc.s.consensus-"), names
                assert has("raft.follower_append"), names
                # state-machine apply + flush-executor handoff
                assert has("tablet.apply"), names
                assert has("flush.background"), names
            finally:
                if c is not None:
                    await c.messenger.shutdown()
                await sup.shutdown()
        run(main())


def _short(name):
    return name.split(":", 1)[0]


def _trace_of(root):
    """(spans of the trace `root` belongs to, {span_id: span})."""
    spans = [s for s in TRACES.finished() if s.trace_id == root.trace_id]
    return spans, {s.span_id: s for s in spans}


class TestServedScanSpans:
    """ISSUE 27: one statement is one trace rooted at `sql.execute`,
    with a span at every layer boundary of the served scan."""

    #: table B's scan rows: span -> its parent
    PARENTS = {
        "sql.parse": "sql.execute", "sql.plan": "sql.execute",
        "client.scan": "sql.execute", "client.combine": "client.scan",
        "rpc.c.tserver.read": "client.scan",
        "rpc.s.tserver.read": "rpc.c.tserver.read",
        "sched.queue.scan": "rpc.s.tserver.read",
        "sched.dispatch.scan": "sched.queue.scan",
        "tserver.read": "sched.dispatch.scan",
        "docdb.read": "tserver.read",
        "docdb.collect_blocks": "docdb.read", "docdb.batch": "docdb.read",
        "device.scan": "docdb.read", "device.wait": "docdb.read",
        # the launch hops beside the event loop (tablet.serve_read): the
        # hop hangs under the tablet read, the launch under docdb.read
        "tserver.read_offload": "tserver.read",
    }
    QUERIES = {
        "sum_where": ("SELECT sum(v), count(*) FROM t WHERE v < 100",
                      "agg_pushdown"),
        "group_by": ("SELECT g, sum(v), count(*) FROM t GROUP BY g",
                     "grouped_pushdown"),
    }

    @staticmethod
    async def _cluster(tmp_path, tablets=2):
        from yugabyte_db_tpu.ql.executor import SqlSession
        from yugabyte_db_tpu.tools.mini_cluster import MiniCluster
        mc = await MiniCluster(str(tmp_path), num_tservers=1).start()
        c = mc.client()
        sql = SqlSession(c)
        await sql.execute(
            "CREATE TABLE t (k bigint, g varchar, v double, "
            f"PRIMARY KEY (k)) WITH tablets = {tablets}")
        await sql.execute("INSERT INTO t (k, g, v) VALUES " + ", ".join(
            f"({i}, '{'ab'[i % 2]}', {i * 0.5})" for i in range(400)))
        ct = await c._table("t", refresh=True)
        for loc in ct.locations:     # SSTs: the columnar device path
            await c._call_leader(ct, loc.tablet_id, "flush",
                                 {"tablet_id": loc.tablet_id})
        await sql.execute("ANALYZE t")
        return mc, c, sql

    @staticmethod
    async def _stop(mc, c):
        await c.messenger.shutdown()
        await mc.shutdown()

    @pytest.mark.parametrize("query", sorted(QUERIES))
    def test_statement_is_one_trace_with_every_layer(self, tmp_path, query):
        text, plan_route = self.QUERIES[query]

        async def main():
            flags.set_flag("tpu_min_rows_for_pushdown", 1)
            mc, c, sql = await self._cluster(tmp_path)
            try:
                runs = []
                for _ in range(2):
                    with TRACES.trace("forced-root") as root:
                        await sql.execute(text)
                    runs.append(_trace_of(root))
            finally:
                flags.REGISTRY.reset("tpu_min_rows_for_pushdown")
                await self._stop(mc, c)
            for spans, by_id in runs:
                names = {_short(s.name) for s in spans}
                assert set(self.PARENTS) <= names, \
                    set(self.PARENTS) - names
                for s in spans:
                    want = self.PARENTS.get(_short(s.name))
                    if want is not None:
                        assert _short(by_id[s.parent_id].name) == want, \
                            (s.name, by_id[s.parent_id].name)
                roots = [s for s in spans if s.name == "sql.execute"]
                assert len(roots) == 1
                assert roots[0].tags["stmt"] == "select"
                assert roots[0].tags["rows"] >= 1
                scan = next(s for s in spans if s.name == "client.scan")
                reads = [s for s in spans
                         if s.name == "rpc.c.tserver.read"
                         and s.parent_id == scan.span_id]
                assert scan.tags["tablets"] == len(reads) == 2
                assert scan.tags["retries"] == 0
                plan = next(s for s in spans if s.name == "sql.plan")
                assert plan.tags["route"] == plan_route
                assert plan.end_ns <= scan.start_ns
                for s in spans:
                    if s.name == "docdb.read":
                        assert s.tags["route"] == "tpu_aggregate"
                    if s.name == "sched.queue.scan":
                        assert s.tags["wait_ms"] >= 0.0
                        assert "cut_through" in s.tags
                    if s.name == "device.wait":
                        assert s.tags["thread"] == "executor"
                    if s.name == "tserver.read_offload":
                        assert s.tags["queue_ms"] >= 0.0
                        assert s.tags["in_flight"] >= 1
            caches = [[s.tags["cache"] for s in spans
                       if s.name == "docdb.batch"] for spans, _ in runs]
            assert caches == [["miss", "miss"], ["hit", "hit"]]
            first = {_short(s.name): by_id[s.parent_id].name
                     for spans, by_id in runs[:1] for s in spans
                     if s.name.startswith("batch.")}
            assert first == {"batch.build": "docdb.batch",
                             "batch.h2d": "docdb.batch"}
            assert not any(s.name.startswith("batch.")
                           for s in runs[1][0])
        run(main())

    def test_unsampled_statement_records_nothing_and_adds_no_wait(
            self, tmp_path, monkeypatch):
        import jax
        calls = []
        real = jax.block_until_ready
        monkeypatch.setattr(jax, "block_until_ready",
                            lambda x: calls.append(1) or real(x))

        async def main():
            flags.set_flag("tpu_min_rows_for_pushdown", 1)
            flags.set_flag("trace_sampling_rate", 0.0)
            mc, c, sql = await self._cluster(tmp_path)
            try:
                text = self.QUERIES["sum_where"][0]
                await sql.execute(text)          # compile, batch build
                before = (len(TRACES.finished()), TRACES.evicted,
                          len(calls))
                res = await sql.execute(text)
                assert (len(TRACES.finished()), TRACES.evicted,
                        len(calls)) == before
                assert res.rows[0]["count"] == 200
                # the same statement, sampled: the wait is made explicit
                flags.set_flag("trace_sampling_rate", 1.0)
                await sql.execute(text)
                assert len(calls) == before[2] + 2      # one per tablet
            finally:
                flags.REGISTRY.reset("tpu_min_rows_for_pushdown")
                flags.REGISTRY.reset("trace_sampling_rate")
                await self._stop(mc, c)
        run(main())

    def test_profiler_session_samples_and_mirrors_the_statement(
            self, tmp_path):
        """While the JAX profiler collects, a root is sampled at rate 0
        and the span is in the profiler's trace under `ybtpu:<name>` with
        the span's own duration."""
        import jax
        from jax.profiler import ProfileData

        async def main():
            flags.set_flag("trace_sampling_rate", 0.0)
            mc, c, sql = await self._cluster(tmp_path / "cluster")
            try:
                with TRACES.span("unprofiled") as sp:
                    assert not sp.sampled
                since = time.perf_counter_ns()
                opts = jax.profiler.ProfileOptions()
                opts.python_tracer_level = 0
                opts.host_tracer_level = 1
                jax.profiler.start_trace(str(tmp_path / "trace"),
                                         profiler_options=opts)
                try:
                    await sql.execute(self.QUERIES["sum_where"][0])
                finally:
                    jax.profiler.stop_trace()
                with TRACES.span("after-the-session") as sp:
                    assert not sp.sampled
                return TRACES.finished(since)
            finally:
                flags.REGISTRY.reset("trace_sampling_rate")
                await self._stop(mc, c)
        spans = {s.span_id: s for s in run(main())}
        roots = [s for s in spans.values() if s.name == "sql.execute"]
        assert len(roots) == 1 and roots[0].parent_id == 0
        import glob
        path, = glob.glob(str(tmp_path / "trace" / "plugins" / "profile"
                              / "*" / "*.xplane.pb"))
        mirrored = {}
        for plane in ProfileData.from_file(path).planes:
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(trace_mod.MIRROR_PREFIX):
                        assert plane.name.startswith("/host:")
                        stats = dict(e.stats)
                        mirrored[int(stats["span_id"])] = (
                            e.name, e.duration_ns, int(stats["trace_id"]))
        name, dur_ns, trace_id = mirrored[roots[0].span_id]
        assert name == "ybtpu:sql.execute"
        assert trace_id == roots[0].trace_id
        # the span and its annotation start and end microseconds apart
        assert abs(dur_ns - (roots[0].end_ns - roots[0].start_ns)) < 1e6
        # every span of the statement is there, coroutines included
        mine = {i for i, s in spans.items()
                if s.trace_id == roots[0].trace_id}
        assert mine <= set(mirrored)
        assert {"ybtpu:client.scan", "ybtpu:docdb.read"} <= \
            {mirrored[i][0] for i in mine}

    def test_device_wait_publishes_its_wait_state(self):
        from yugabyte_db_tpu.ops import scan as scan_mod
        seen = []
        real = scan_mod._rescale_outs

        def spy(outs, scales):
            seen.append(trace_mod.current_wait_state())
            return real(outs, scales)
        batch = TestDeviceTelemetry._batch()
        from yugabyte_db_tpu.ops import AggSpec, scan_aggregate
        scan_mod._rescale_outs = spy
        try:
            scan_aggregate(batch, None, (AggSpec("count"),))
        finally:
            scan_mod._rescale_outs = real
        assert seen == ["Device_BlockUntilReady"]
        from yugabyte_db_tpu.cluster.collector import classify_wait_state
        assert classify_wait_state("Device_BlockUntilReady") == "device"
        assert classify_wait_state("Device_Compile") == "compile"

    def test_retries_are_counted_on_the_ambient_span(self):
        with TRACES.trace("scan") as sp:
            with TRACES.span("inner"):
                pass
            trace_mod.current_span().count("retries")
            trace_mod.current_span().count("retries")
        assert sp.tags["retries"] == 2
        assert trace_mod.current_span().sampled is False   # none ambient
        trace_mod.current_span().count("retries")          # goes nowhere


class TestReadRouteTag:
    """Every exit of `DocReadOperation._execute_once_steps` names itself on
    the `docdb.read` span: the one way to tell which driver served a
    read.  (`tpu_aggregate` through SQL, `mesh` and `mesh_fallback`
    are pinned by TestServedScanSpans and tests/test_mesh_read.py.)"""

    @staticmethod
    def _requests(route):
        """(the reads to make in order, flag overrides): the LAST read's
        span is the one held to `route`."""
        from yugabyte_db_tpu.docdb.operations import ReadRequest
        from yugabyte_db_tpu.ops import AggSpec, Expr
        from yugabyte_db_tpu.ops.join_scan import JoinWire
        C = Expr.col
        where = (C(3) < 50).node
        aggs = (AggSpec("sum", C(2).node), AggSpec("count"))
        if route == "point":
            return [ReadRequest("probe", pk_eq={"k": 5})], {}
        if route == "prefix":
            return [ReadRequest("probe", pk_prefix={"k": 5})], {}
        if route == "join":
            wire = JoinWire(probe_col=1,
                            keys=np.arange(500, dtype=np.int64),
                            payload={})
            return [ReadRequest("probe", where=where, aggregates=aggs,
                                join=wire)], {}
        if route == "hash_enumerated":
            return [ReadRequest("probe",
                                where=C(0).between(10, 19).node)], {}
        if route == "streaming":     # 24,000 rows = six chunks
            return [ReadRequest("probe", where=where, aggregates=aggs)], \
                {"streaming_chunk_rows": 4096}
        if route == "tpu_filter":
            return [ReadRequest("probe", where=where)], {}
        if route == "cpu":
            return [ReadRequest("probe")], {}
        assert route == "tpu_aggregate"   # one chunk: the whole batch
        return [ReadRequest("probe", where=where, aggregates=aggs)
                for _ in range(2)], {}

    @pytest.mark.parametrize("route", [
        "point", "prefix", "join", "hash_enumerated", "streaming",
        "tpu_filter", "cpu", "tpu_aggregate"])
    def test_tablet_read_names_the_route_that_served(self, route):
        from tests.test_join_scan import _probe_tablet
        t, _ = _probe_tablet("route-")
        reqs, overrides = self._requests(route)
        for name, value in overrides.items():
            flags.set_flag(name, value)
        try:
            for req in reqs:
                with TRACES.trace("route") as root:
                    t.read(req)
        finally:
            for name in overrides:
                flags.REGISTRY.reset(name)
        reads = [s for s in TRACES.finished()
                 if s.trace_id == root.trace_id and s.name == "docdb.read"]
        assert [s.tags["route"] for s in reads] == [route]
        if route == "tpu_aggregate":
            # the second read of unchanged contents looks its facts up
            assert reads[0].tags["facts"] == "hit"


class TestSpanRegistry:
    """ISSUE 27: one clock, kept until read."""

    def test_spans_stamp_perf_counter_ns(self):
        t0 = time.perf_counter_ns()
        with TRACES.trace("clocked") as sp:
            TRACE("an event")
        t1 = time.perf_counter_ns()
        assert isinstance(sp.start_ns, int) and isinstance(sp.end_ns, int)
        assert t0 <= sp.start_ns <= sp.end_ns <= t1
        d = sp.to_dict()
        assert d["finished"] and d["start_unix"] > 1e9
        assert d["duration_ms"] == round((sp.end_ns - sp.start_ns) / 1e6, 3)
        assert 0 <= d["events"][0][0] <= d["duration_ms"]

    def test_finished_returns_the_interval_as_plain_tuples(self):
        with TRACES.trace("before"):
            pass
        since = time.perf_counter_ns()
        with TRACES.trace("inside", ) as a:
            with TRACES.span("child") as b:
                b.set_tag("k", 1)
        until = time.perf_counter_ns()
        with TRACES.trace("after"):
            pass
        got = TRACES.finished(since, until)
        assert [s.name for s in got] == ["child", "inside"]
        child = got[0]
        assert isinstance(child, tuple)
        assert child == ("child", a.trace_id, b.span_id, a.span_id,
                         b.start_ns, b.end_ns, {"k": 1})
        assert "after" in [s.name for s in TRACES.finished(since)]

    def test_ring_counts_what_it_evicts(self):
        reg = trace_mod.TraceRegistry()
        flags.set_flag("tracez_keep", 3)
        try:
            for i in range(5):
                with reg.span(f"s{i}", force=True):
                    pass
            assert [s.name for s in reg.finished()] == ["s2", "s3", "s4"]
            assert reg.evicted == 2
        finally:
            flags.REGISTRY.reset("tracez_keep")
        assert flags.get("tracez_keep") == 32768

    def test_slow_threshold_still_reads_durations(self):
        reg = trace_mod.TraceRegistry(slow_threshold_s=0.0)
        with reg.span("slow-one", force=True):
            pass
        assert any("slow-one" in d for d in reg.rpcz()["recent_slow"])

    def test_explicit_root_ignores_the_ambient_context(self):
        with TRACES.trace("request") as req:
            with TRACES.span("background", parent=None,
                             force=True) as bg:
                assert bg.trace_id != req.trace_id
                assert bg.parent_id == 0


class TestSchedulerQueueSpan:
    """ISSUE 27: `sched.queue.<lane>` carries `wait_ms` (admission to
    dequeue) and `cut_through`; the dispatch span is its child."""

    def test_cut_through_and_queued(self):
        from yugabyte_db_tpu.sched import Lane, RequestScheduler, ScanItem

        async def main():
            sched = RequestScheduler("ts-queue-span")
            gate = asyncio.Event()

            async def slow():
                await gate.wait()
                return {"n": 1}

            async def fast():
                return {"n": 2}
            try:
                with TRACES.trace("two-scans") as root:
                    first = asyncio.create_task(sched.submit_grouped(
                        Lane.SCAN, "sig-a", ScanItem(slow)))
                    await asyncio.sleep(0)      # cut-through, in flight
                    workers = sched.lanes[Lane.SCAN].cfg.workers
                    sched.lanes[Lane.SCAN].inflight += workers  # lane full
                    second = asyncio.create_task(sched.submit_grouped(
                        Lane.SCAN, "sig-b", ScanItem(fast)))
                    await asyncio.sleep(0.02)   # it waits in the queue
                    sched.lanes[Lane.SCAN].inflight -= workers
                    gate.set()
                    assert (await first)["n"] == 1
                    assert (await second)["n"] == 2
            finally:
                await sched.shutdown()
            spans, by_id = _trace_of(root)
            queues = sorted((s for s in spans
                             if s.name == "sched.queue.scan"),
                            key=lambda s: s.start_ns)
            assert [q.tags["cut_through"] for q in queues] == [True, False]
            assert queues[0].tags["wait_ms"] == 0.0
            assert queues[1].tags["wait_ms"] > 0.0
            for d in (s for s in spans if s.name == "sched.dispatch.scan"):
                assert by_id[d.parent_id].name == "sched.queue.scan"
            assert not any("sched.admit" in msg for s in TRACES.recent
                           if s.trace_id == root.trace_id
                           for _, msg in s.events)
        run(main())


class TestMaintenanceSpans:
    """ISSUE 27: each non-empty periodic pass of the heartbeat loop is a
    `tserver.maintenance` root; tick lateness feeds /metrics."""

    def test_pass_is_a_root_span_and_an_empty_pass_leaves_none(
            self, tmp_path):
        from yugabyte_db_tpu.tserver import TabletServer

        class Peer:
            class tablet:
                tablet_id = "t-1"

        async def main():
            ts = TabletServer("maint", str(tmp_path))
            seen = []
            flags.set_flag("trace_sampling_rate", 1.0)
            try:
                since = time.perf_counter_ns()
                with TRACES.trace("some-request") as req:
                    await ts._maintain("wal_gc", [Peer, Peer], seen.append)
                    await ts._maintain("compaction", [], seen.append)

                    async def boom(p):
                        raise RuntimeError("one tablet fails")
                    await ts._maintain("coordinator_sweep", [Peer], boom)
            finally:
                flags.REGISTRY.reset("trace_sampling_rate")
            spans = [s for s in TRACES.finished(since)
                     if s.name == "tserver.maintenance"]
            assert [s.tags for s in spans] == [
                {"what": "wal_gc", "tablets": 2},
                {"what": "coordinator_sweep", "tablets": 1}]
            assert seen == [Peer, Peer]
            assert all(s.parent_id == 0 and s.trace_id != req.trace_id
                       for s in spans)
        run(main())

    def test_tick_lateness_is_on_metrics(self, tmp_path):
        from yugabyte_db_tpu.master import Master
        from yugabyte_db_tpu.tserver import TabletServer

        async def main():
            master = Master(str(tmp_path / "m"))
            maddr = await master.start()
            ts = TabletServer("late", str(tmp_path / "ts"),
                              master_addrs=[maddr])
            await ts.start()
            try:
                await asyncio.sleep(0.5)     # two ticks of 0.2 s
            finally:
                await ts.shutdown()
                await master.shutdown()
            assert ts._m_tick_late.count() >= 1
            assert "heartbeat_tick_late_ms" in \
                metrics.REGISTRY.to_prometheus()
        run(main())


class TestEncryption:
    def test_cipher_roundtrip_random_access(self):
        cs = CipherStream(b"k" * 32, b"n" * 16)
        data = bytes(range(256)) * 10
        enc = cs.xor(data)
        assert enc != data
        assert cs.xor(enc) == data
        # random-access decrypt of a middle slice
        assert cs.xor(enc[100:200], offset=100) == data[100:200]

    def test_key_manager_envelope(self):
        km = UniverseKeyManager()
        km.generate_key("v1")
        raw = b"hello sst bytes" * 100
        enc = km.encrypt_file_bytes(raw)
        assert enc != raw and km.decrypt_file_bytes(enc) == raw
        # rotation keeps old files readable
        km.generate_key("v2")
        assert km.decrypt_file_bytes(enc) == raw

    def test_encrypted_sst_roundtrip(self, tmp_path):
        from yugabyte_db_tpu.storage import SstReader, SstWriter
        KEY_MANAGER.generate_key()
        flags.set_flag("encrypt_data_at_rest", True)
        try:
            p = str(tmp_path / "enc.sst")
            w = SstWriter(p)
            for i in range(50):
                w.add(b"k%04d" % i, b"v%d" % i)
            w.finish()
            with open(p, "rb") as f:
                raw = f.read()
            assert raw.startswith(b"YBTPUEN")  # v1 or v2 envelope
            assert b"k0001" not in raw          # actually encrypted
            r = SstReader(p)
            assert len(list(r.iterate())) == 50
        finally:
            flags.REGISTRY.reset("encrypt_data_at_rest")


class TestWebServer:
    def test_metrics_and_rpcz_endpoints(self):
        async def go():
            ent = metrics.REGISTRY.entity("server", "test-ws")
            ent.counter("test_requests").increment(3)
            ws = StatusWebServer("test")
            addr = await ws.start()
            loop = asyncio.get_running_loop()

            def fetch(path):
                with urllib.request.urlopen(
                        f"http://{addr[0]}:{addr[1]}{path}") as r:
                    return r.read().decode()

            body = await loop.run_in_executor(None, fetch, "/metrics")
            assert "test_requests" in body
            body = await loop.run_in_executor(None, fetch, "/rpcz")
            assert "active" in body
            body = await loop.run_in_executor(None, fetch, "/ash")
            assert "wait_states" in body
            await ws.shutdown()
        run(go())

    def test_master_path_handlers(self, tmp_path):
        """Master web UI (reference: master-path-handlers.cc): /tables,
        /tablet-servers, /tablets serve live catalog state as JSON."""
        async def go():
            import json as _json
            from yugabyte_db_tpu.tools.mini_cluster import MiniCluster
            from tests.test_load_balancer import kv_info
            mc = await MiniCluster(str(tmp_path), num_tservers=1).start()
            ws = StatusWebServer("m", extra_handlers=mc.master.web_handlers())
            addr = await ws.start()
            try:
                c = mc.client()
                await c.create_table(kv_info(), num_tablets=2)
                await mc.wait_for_leaders("kv")
                loop = asyncio.get_running_loop()

                def fetch(path):
                    with urllib.request.urlopen(
                            f"http://{addr[0]}:{addr[1]}{path}") as r:
                        return r.read().decode()

                tables = _json.loads(
                    await loop.run_in_executor(None, fetch, "/tables"))
                assert any(t["name"] == "kv" and t["tablets"] == 2
                           for t in tables)
                tss = _json.loads(await loop.run_in_executor(
                    None, fetch, "/tablet-servers"))
                assert len(tss) == 1 and tss[0]["alive"]
                tablets = _json.loads(await loop.run_in_executor(
                    None, fetch, "/tablets"))
                assert sum(t["leader"] is not None for t in tablets) >= 2
            finally:
                await ws.shutdown()
                await mc.shutdown()
        run(go())


class TestAdminCli:
    def test_list_tables_and_compact(self, tmp_path, capsys):
        async def go():
            from yugabyte_db_tpu.tools.mini_cluster import MiniCluster
            from yugabyte_db_tpu.tools import ybtpu_admin
            from tests.test_load_balancer import kv_info
            mc = await MiniCluster(str(tmp_path), num_tservers=1).start()
            try:
                c = mc.client()
                await c.create_table(kv_info(), num_tablets=1)
                await mc.wait_for_leaders("kv")
                await c.insert("kv", [{"k": 1, "v": 1.0}])
                maddr = mc.master.messenger.addr
                ns = type("A", (), {
                    "master": f"{maddr[0]}:{maddr[1]}",
                    "command": "list_tables", "args": []})
                assert await ybtpu_admin.run_command(ns) == 0
                ns.command, ns.args = "flush_table", ["kv"]
                assert await ybtpu_admin.run_command(ns) == 0
            finally:
                await mc.shutdown()
        run(go())
        out = capsys.readouterr().out
        assert "kv" in out


class TestSstDump:
    def test_dump_sst_and_wal(self, tmp_path, capsys):
        from yugabyte_db_tpu.storage import SstWriter
        from yugabyte_db_tpu.consensus import Log, LogEntry
        from yugabyte_db_tpu.tools import sst_dump
        p = str(tmp_path / "x.sst")
        w = SstWriter(p)
        for i in range(10):
            w.add(b"key%03d" % i, b"v")
        w.set_frontier(op_id=[1, 5])
        w.finish()
        assert sst_dump.main([p, "--blocks", "--entries", "3"]) == 0
        out = capsys.readouterr().out
        assert "entries:   10" in out and "op_id" in out
        wal = Log(str(tmp_path / "wal"), fsync=False)
        wal.append([LogEntry(1, 1, "write", b"abc")])
        wal.close()
        assert sst_dump.main(["--wal", str(tmp_path / "wal")]) == 0
        out = capsys.readouterr().out
        assert "[1:1] write" in out


class TestAesCtr:
    """AES-CTR at rest (reference: encryption/cipher_stream.h over EVP
    AES-CTR) with the BLAKE2b keystream as documented fallback and a
    format-versioned envelope keeping every combination readable."""

    @requires_aes
    def test_aes_stream_roundtrip_random_access(self):
        from yugabyte_db_tpu.utils.encryption import (AesCtrStream,
                                                      aes_available)
        assert aes_available()   # cryptography is in this image
        cs = AesCtrStream(b"k" * 32, b"n" * 16)
        data = bytes(range(256)) * 10
        enc = cs.xor(data)
        assert enc != data and cs.xor(enc) == data
        # random access at non-block-aligned offsets
        for off in (0, 1, 15, 16, 17, 100, 2000):
            assert cs.xor(enc[off:off + 77], offset=off) == \
                data[off:off + 77]

    @requires_aes
    def test_envelope_selects_aes_and_rotates(self):
        from yugabyte_db_tpu.utils.encryption import (
            CIPHER_AES_CTR, MAGIC_V2, UniverseKeyManager)
        km = UniverseKeyManager()
        km.generate_key("v1")
        raw = b"sst bytes " * 200
        enc = km.encrypt_file_bytes(raw)
        assert enc.startswith(MAGIC_V2)
        assert enc[len(MAGIC_V2)] == CIPHER_AES_CTR
        assert km.decrypt_file_bytes(enc) == raw
        # rotation: new key writes new files; old files stay readable
        km.generate_key("v2")
        enc2 = km.encrypt_file_bytes(raw)
        assert km.decrypt_file_bytes(enc2) == raw
        assert km.decrypt_file_bytes(enc) == raw

    def test_rotation_on_fallback_cipher(self):
        from yugabyte_db_tpu.utils.encryption import (
            CIPHER_BLAKE2B, UniverseKeyManager)
        km = UniverseKeyManager()
        km.force_cipher = CIPHER_BLAKE2B
        km.generate_key("b1")
        raw = b"fallback " * 100
        enc = km.encrypt_file_bytes(raw)
        km.generate_key("b2")
        assert km.decrypt_file_bytes(enc) == raw
        assert km.decrypt_file_bytes(km.encrypt_file_bytes(raw)) == raw

    def test_legacy_v1_files_stay_readable(self):
        """Files written by the round-3/4 BLAKE2b-only envelope decrypt
        under the new manager."""
        from yugabyte_db_tpu.utils.encryption import (
            CipherStream, MAGIC, UniverseKeyManager)
        import secrets as _s
        km = UniverseKeyManager()
        km.add_key("old", b"K" * 32)
        raw = b"legacy payload " * 50
        nonce = _s.token_bytes(16)
        legacy = (MAGIC + bytes([3]) + b"old" + nonce
                  + CipherStream(b"K" * 32, nonce).xor(raw))
        assert km.decrypt_file_bytes(legacy) == raw

    @requires_aes
    def test_mixed_cipher_files_coexist(self):
        from yugabyte_db_tpu.utils.encryption import (
            CIPHER_AES_CTR, CIPHER_BLAKE2B, UniverseKeyManager)
        km = UniverseKeyManager()
        km.generate_key("m1")
        raw = b"mixed " * 300
        km.force_cipher = CIPHER_BLAKE2B
        e_b = km.encrypt_file_bytes(raw)
        km.force_cipher = CIPHER_AES_CTR
        e_a = km.encrypt_file_bytes(raw)
        km.force_cipher = None
        assert km.decrypt_file_bytes(e_b) == raw
        assert km.decrypt_file_bytes(e_a) == raw
