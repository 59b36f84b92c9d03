"""A served read's launch leaves the event loop (tablet/tablet.py
`serve_read`): two statements are inside the server at once and the loop
goes on meanwhile; a read that was suspended while writes and a flush went
by answers at its own hybrid time; the scan lane keeps apart what differs
in a literal and joins what is identical.  Order is told by spans and
events, never by a clock's reading."""
import asyncio
import threading

import pytest

from yugabyte_db_tpu.ops.scan import ScanKernel
from yugabyte_db_tpu.ql.executor import SqlSession
from yugabyte_db_tpu.tools.mini_cluster import MiniCluster
from yugabyte_db_tpu.utils import fault_injection as fi
from yugabyte_db_tpu.utils import flags, metrics
from yugabyte_db_tpu.utils.trace import TRACES, current_context

SUM = "SELECT sum(v), count(*) FROM t WHERE v < {lit}"


@pytest.fixture(autouse=True)
def _device_path_at_any_size():
    flags.set_flag("tpu_min_rows_for_pushdown", 1)
    yield
    flags.REGISTRY.reset("tpu_min_rows_for_pushdown")
    fi.clear_lane_stalls()


async def _cluster(tmp_path, tablets=2, rows=400):
    mc = await MiniCluster(str(tmp_path), num_tservers=1).start()
    c = mc.client()
    sql = SqlSession(c)
    await sql.execute("CREATE TABLE t (k bigint, v double, "
                      f"PRIMARY KEY (k)) WITH tablets = {tablets}")
    await _insert(sql, 0, rows)
    await _flush(c)
    return mc, c, sql


async def _insert(sql, first, n):
    await sql.execute("INSERT INTO t (k, v) VALUES " + ", ".join(
        f"({i}, {i * 0.5})" for i in range(first, first + n)))


async def _flush(c):
    ct = await c._table("t", refresh=True)
    for loc in ct.locations:
        await c._call_leader(ct, loc.tablet_id, "flush",
                             {"tablet_id": loc.tablet_id})


def _want(n, lit=1e9):
    vs = [i * 0.5 for i in range(n) if i * 0.5 < lit]
    return {"sum_v": sum(vs), "count": len(vs)}


class _Hold:
    """`ScanKernel.run` held at its start, for the calls `when` picks:
    `entered` says a pool thread is standing in it, and `ctx` is the
    trace context that launch ran under."""

    def __init__(self, monkeypatch, when=lambda n: True):
        self.release, self.entered = threading.Event(), threading.Event()
        self.calls, self.ctx = 0, None
        real, hold = ScanKernel.run, self

        def run(kernel, *a, **kw):
            hold.calls += 1
            if when(hold.calls) and not hold.release.is_set():
                hold.ctx = current_context()
                hold.entered.set()
                assert hold.release.wait(60)
            return real(kernel, *a, **kw)

        monkeypatch.setattr(ScanKernel, "run", run)

    async def until_entered(self):
        while not self.entered.is_set():
            await asyncio.sleep(0)

    def hop(self, spans):
        """The `tserver.read_offload` hop of the held launch: the one
        whose parent is the held launch's span or one of its ancestors
        (a read's batch build hops first, so its launch's hop may begin
        after another statement's reads have)."""
        by_id = {s.span_id: s for s in spans}
        hops = {s.parent_id: s for s in spans
                if s.name == "tserver.read_offload"}
        sid = self.ctx.span_id
        while sid not in hops:
            sid = by_id[sid].parent_id
        return hops[sid]


def test_a_second_statement_is_served_while_the_first_waits(tmp_path,
                                                            monkeypatch):
    """Session A's statement stands in its first launch; session B's whole
    statement is served meanwhile, and so is a heartbeat of the server."""
    hold = _Hold(monkeypatch, when=lambda n: n == 1)

    async def main():
        mc, c, sql_a = await _cluster(tmp_path)
        try:
            sql_b = SqlSession(c)
            ts = mc.tservers[0]
            with TRACES.trace("a") as root_a:
                first = asyncio.ensure_future(
                    sql_a.execute(SUM.format(lit=1e9)))
                await hold.until_entered()
            assert not first.done()
            with TRACES.trace("b") as root_b:
                second = await sql_b.execute(SUM.format(lit=50))
            await ts._heartbeat_once()          # the loop is free
            assert not first.done() and not hold.release.is_set()
            hold.release.set()
            rows_a = (await first).rows
            ent = metrics.REGISTRY.entity("server", f"ts-{ts.uuid}")
            # A's two tablet launches were in the pool's hands when B's came
            assert ent.gauge("reads_in_flight_max").value() >= 3
            assert ent.histogram("read_offload_queue_us").count() >= 4
            return rows_a, second.rows, root_a, root_b
        finally:
            hold.release.set()
            await c.messenger.shutdown()
            await mc.shutdown()

    rows_a, rows_b, root_a, root_b = asyncio.run(main())
    assert rows_a == [_want(400)] and rows_b == [_want(400, 50)]
    spans = TRACES.finished()
    a = [s for s in spans if s.trace_id == root_a.trace_id]
    b = [s for s in spans if s.trace_id == root_b.trace_id]
    held = hold.hop(a)
    # all of B, from its first docdb.read to its answer, inside A's hop
    assert min(s.start_ns for s in b if s.name == "docdb.read") \
        > held.start_ns
    assert max(s.end_ns for s in b) < held.end_ns
    for s in a + b:
        if s.name == "device.wait":
            assert s.tags["thread"] == "executor"
    for tree in (a, b):
        reads = {s.span_id for s in tree
                 if s.name.startswith("tserver.read:")}
        hops = [s for s in tree if s.name == "tserver.read_offload"]
        assert len(hops) == 2 and {h.parent_id for h in hops} == reads


def test_a_suspended_read_answers_at_its_own_hybrid_time(tmp_path,
                                                         monkeypatch):
    """Rounds of: a read begins and stands in its launches; INSERTs are
    acknowledged (and, in the middle round, flushed: the SST set changes
    and the cached batches go) while it stands; released, it returns
    exactly the rows acknowledged before it began, and the next read
    returns every row acknowledged by then."""
    hold = _Hold(monkeypatch)

    async def main():
        mc, c, sql = await _cluster(tmp_path, rows=300)
        reader = SqlSession(c)
        seen, n = [], 300
        try:
            for round_ in range(3):
                hold.entered.clear()
                hold.release.clear()
                suspended = asyncio.ensure_future(
                    reader.execute(SUM.format(lit=1e9)))
                await hold.until_entered()
                await _insert(sql, n, 50)            # acknowledged
                if round_ == 1:
                    await _flush(c)
                assert not suspended.done()
                hold.release.set()
                seen.append(((await suspended).rows, _want(n)))
                n += 50
                seen.append(((await reader.execute(
                    SUM.format(lit=1e9))).rows, _want(n)))
            return seen
        finally:
            hold.release.set()
            await c.messenger.shutdown()
            await mc.shutdown()

    for rows, want in asyncio.run(main()):
        assert rows == [want]


def test_scans_that_differ_in_a_literal_are_not_coalesced(tmp_path,
                                                          monkeypatch):
    """Two identical scans queued together run once (`fanin` 2); the one
    that differs in its literal runs on its own and gets its own answer."""
    from yugabyte_db_tpu.docdb.operations import ReadRequest
    from yugabyte_db_tpu.docdb.wire import read_request_to_wire
    from yugabyte_db_tpu.ops import AggSpec, Expr
    from yugabyte_db_tpu.tserver.tablet_server import TabletServer

    served = []
    real = TabletServer._serve_read

    async def counted(self, peer, tablet_id, req_wire):
        served.append(req_wire)
        return await real(self, peer, tablet_id, req_wire)

    monkeypatch.setattr(TabletServer, "_serve_read", counted)

    async def main():
        mc, c, _ = await _cluster(tmp_path, tablets=1)
        try:
            ts = mc.tservers[0]
            ct = await c._table("t")
            loc = ct.locations[0]

            def req(lit):
                return {"tablet_id": loc.tablet_id,
                        "req": read_request_to_wire(ReadRequest(
                            ct.info.table_id,
                            where=(Expr.col(1) < lit).node,
                            aggregates=(AggSpec("count"),)))}
            fi.stall_lane("scan")
            loop = asyncio.get_running_loop()
            with TRACES.trace("scans") as root:
                tasks = [loop.create_task(ts.rpc_read(req(lit)))
                         for lit in (50.0, 50.0, 51.0)]
                await asyncio.sleep(0.05)      # all three are queued
                fi.release_lane("scan")
                got = await asyncio.gather(*tasks)
            return got, root
        finally:
            await c.messenger.shutdown()
            await mc.shutdown()

    got, root = asyncio.run(main())
    counts = [int(r["agg_values"][0]) for r in got]
    assert counts == [100, 100, 102]
    assert len(served) == 2                     # one execution for the pair
    fanin = sorted(s.tags["fanin"] for s in TRACES.finished()
                   if s.trace_id == root.trace_id
                   and s.name == "sched.dispatch.scan")
    assert fanin == [1, 2]


@pytest.mark.parametrize("suspended_restarts", [True, False],
                         ids=["server_assigned_held", "explicit_time_held"])
def test_a_suspended_filter_read_keeps_its_own_restart_rule(
        suspended_restarts, monkeypatch):
    """A filter read stands at its launch while a read of the SAME tablet
    with the other restart rule runs to its end; resumed, its gather finds
    no columnar rows and it falls to the row loop — which still restarts
    (server-assigned time) or still does not (explicit time) as ITS request
    says, over a record inside the skew window."""
    from tests.test_store_facts import (AHEAD, N, NOW_US, C, _rows,
                                        count_req, make_tablet)
    from yugabyte_db_tpu.docdb.operations import (DocReadOperation,
                                                  ReadRequest)
    from yugabyte_db_tpu.utils.hybrid_time import HybridTime

    t = make_tablet(f"hold-{suspended_restarts}")
    t.bulk_load(_rows(10 * N, 7, seed=9), ht=AHEAD)   # inside the window
    now = HybridTime.from_micros(NOW_US).value

    def rows_req(explicit):
        return ReadRequest("li", columns=("k",), where=(C(3) < 1e9).node,
                           read_ht=now if explicit else None)

    held = rows_req(explicit=not suspended_restarts)
    steps = t.read_steps(held)
    launch = next(steps)                  # suspended: the launch is ours
    other = t.read(count_req(read_ht=now if suspended_restarts else None))
    assert int(other.agg_values[0]) == 2 * N + (0 if suspended_restarts
                                                else 7)
    monkeypatch.setattr(DocReadOperation, "_gather_rows",
                        lambda self, *a: None)       # → the row loop
    got = launch()
    while True:
        try:
            launch = steps.send(got)      # a restart launches anew
            got = launch()
        except StopIteration as done:
            resp = done.value
            break
    assert resp.backend == "cpu"
    if suspended_restarts:
        assert held.read_ht == AHEAD.value and len(resp.rows) == 2 * N + 7
    else:
        assert held.read_ht == now and len(resp.rows) == 2 * N
