"""The reduction from the program's spans to the seven per-layer numbers
(`benchmark/span_reduce.py`, its readers under `layer_metrics/`): on
hand-made spans, and once end to end on a traced rehearsal."""
import collections
import json
import types

import pytest

from benchmark import manifest, run, span_reduce

Span = collections.namedtuple(
    "Span", "name trace_id span_id parent_id start_ns end_ns tags")
MS = 1_000_000
NEW = ["sql_front_ms", "fanout_host_ms", "sched_wait_ms", "batch_form_ms",
       "kernel_dispatch_ms", "device_wait_ms", "idle_attributed_pct"]


def statement(trace, t0, tablets=2):
    """One statement's tree, times in ms from `t0`: 1 ms of parse and plan,
    a scan of 1 + 20 * tablets + 2 ms whose tablet RPCs are served one
    after the other (each 20 ms: 1 collect, 1 batch, 2 dispatch, 15 wait),
    1 ms after the scan."""
    ids = iter(range(trace * 1000 + 1, trace * 1000 + 999))
    out = []

    def add(name, parent, a, b, **tags):
        sid = next(ids)
        out.append(Span(name, trace, sid, parent, (t0 + a) * MS,
                        (t0 + b) * MS, tags))
        return sid
    end = 1 + 1 + 20 * tablets + 2
    root = add("sql.execute", 0, 0, end + 1, stmt="select")
    add("sql.parse", root, 0, 0.5)
    add("sql.plan", root, 0.5, 1, route="agg_pushdown")
    scan = add("client.scan", root, 1, end, tablets=tablets)
    for i in range(tablets):
        a = 2 + 20 * i
        c = add("rpc.c.tserver.read", scan, 2, a + 20)     # all sent at 2
        s = add("rpc.s.tserver.read", c, a, a + 20)
        q = add("sched.queue.scan", s, a, a + 20, wait_ms=0.5 * i,
                cut_through=i == 0)
        r = add(f"tserver.read:tab-{i}", q, a, a + 20)
        d = add("docdb.read", r, a, a + 20)
        add("docdb.collect_blocks", d, a, a + 1)
        add("docdb.batch", d, a + 1, a + 2, cache="hit")
        add("device.scan", d, a + 2, a + 4)
        add("device.wait", d, a + 4, a + 19, thread="loop")
    add("client.combine", scan, end - 1, end)
    return out


def ctx_of(spans, stmts, trace=None, window=None):
    rec = types.SimpleNamespace(
        window=window or (0.0, 1.0),
        spans=[{"kind": "stmt", "ok": True, "t0": a, "t1": b}
               for a, b in stmts])
    rec.of = lambda kind, ok_only=True: [
        s for s in rec.spans if s["kind"] == kind]
    rec.spans.append({"kind": "trace_window", "ok": True,
                      "t0": rec.window[0], "t1": rec.window[1]})
    return types.SimpleNamespace(trace=trace, rec=rec), spans


@pytest.fixture
def two_statements(monkeypatch):
    spans = statement(1, 100) + statement(2, 200, tablets=4)
    ctx, spans = ctx_of(spans, [(0.0999, 0.146), (0.1999, 0.286)])
    monkeypatch.setattr(span_reduce, "window_spans", lambda c: spans)
    return ctx


def read(name, ctx):
    return manifest.load_module(manifest.layer_metric_file(name)).read(ctx)


def test_each_reader_on_a_hand_made_tree(two_statements):
    ctx = two_statements
    # per statement; the two have 2 and 4 tablets
    assert read("sql_front_ms", ctx) == pytest.approx(2.0)     # 1 + 1
    # client.scan minus the union of its rpc.c children: 1 before, 2 after
    assert read("fanout_host_ms", ctx) == pytest.approx(3.0)
    assert read("sched_wait_ms", ctx) == pytest.approx((0.5 + 3.0) / 2)
    assert read("batch_form_ms", ctx) == pytest.approx((4 + 8) / 2)
    assert read("kernel_dispatch_ms", ctx) == pytest.approx((4 + 8) / 2)
    assert read("device_wait_ms", ctx) == pytest.approx((30 + 60) / 2)


def test_self_time_takes_the_union_of_overlapping_children():
    tree = statement(1, 0, tablets=3)
    # three rpc.c spans open together from 2 ms: their sum is 120 ms, their
    # union 60 ms of a 63-ms client.scan
    assert span_reduce.total_ns(tree, "rpc.c.") == 120 * MS
    assert span_reduce.uncovered_ns(tree, "client.scan", "rpc.c.") == 3 * MS
    # the wait of RPCs 2 and 3 behind a loop that serves one at a time:
    # rpc.c minus its rpc.s child
    assert span_reduce.uncovered_ns(tree, "rpc.c.tserver.read",
                                    "rpc.s.") == (0 + 20 + 40) * MS
    # a descendant elsewhere in time does not count
    assert span_reduce.uncovered_ns(tree, "sql.parse", "rpc.c.") \
        == 0.5 * MS


def test_statement_count_guard(monkeypatch):
    spans = statement(1, 100) + statement(2, 200)
    for stmts in ([(0.0999, 0.15)],                        # a root too many
                  [(0.0999, 0.15), (0.1999, 0.25), (0.3, 0.4)],   # too few
                  [(0.0999, 0.15), (0.21, 0.25)]):         # not inside it
        ctx, _ = ctx_of(spans, stmts)
        monkeypatch.setattr(span_reduce, "window_spans", lambda c: spans)
        assert read("device_wait_ms", ctx) is None
    monkeypatch.setattr(span_reduce, "window_spans", lambda c: None)
    ctx, _ = ctx_of(spans, [(0.0999, 0.15), (0.1999, 0.25)])
    assert [read(n, ctx) for n in NEW] == [None] * 7


def test_window_spans_guards_the_ring(monkeypatch):
    from yugabyte_db_tpu.utils import trace
    reg = trace.TraceRegistry()
    monkeypatch.setattr(trace, "TRACES", reg)
    with reg.span("before", force=True):
        pass
    import time
    t0 = time.perf_counter()
    with reg.span("inside", force=True):
        pass
    ctx, _ = ctx_of([], [], window=(t0, time.perf_counter()))
    assert [s.name for s in span_reduce.window_spans(ctx)] == ["inside"]
    reg.evicted = 1          # dropped, but the oldest kept predates t0
    assert [s.name for s in span_reduce.window_spans(ctx)] == ["inside"]
    reg.recent.popleft()     # the oldest kept now finished in the window
    assert span_reduce.window_spans(ctx) is None
    # a program from before the spans could be read
    monkeypatch.setattr(trace, "TRACES", object())
    assert span_reduce.window_spans(ctx) is None


def test_clock_offset_and_its_none_case():
    off = 7_000_000_000
    rec = [{"t0": 1.0, "t1": 1.9}, {"t0": 2.0, "t1": 2.9}]
    marks = [("bench:stmt.q6", 1.0e9 + off - 20_000, 1.9e9 + off),
             ("bench:stmt.q1", 2.0e9 + off + 30_000, 2.9e9 + off),
             ("bench:warm.q1", 0.0, 1.0)]
    got = span_reduce.clock_offset_ns(marks, rec)
    assert abs(got - off) <= 30_000
    late = [marks[0], ("bench:stmt.q1", 2.0e9 + off + 2 * MS, 0.0)]
    assert span_reduce.clock_offset_ns(late, rec) is None     # > 1 ms apart
    assert span_reduce.clock_offset_ns(marks[:1], rec) is None   # unpaired
    assert span_reduce.clock_offset_ns([], []) is None


def test_idle_is_attributed_to_the_innermost_span(monkeypatch):
    """One statement from 100 to 145 ms; the device is busy during the two
    `device.wait`s but for their last 5 ms, and idle otherwise.  The trace
    is on a clock 7 s ahead; its window runs 10 ms past the statement on
    both sides."""
    off = 7_000 * MS
    spans = statement(1, 100)
    busy = [[(100 + a) * MS + off, (100 + b) * MS + off]
            for a, b in ((6, 16), (26, 36))]
    lo, hi = 90 * MS + off, 155 * MS + off
    trace = {"busy": [busy], "busy_s": 0.020, "window_s": 0.065,
             "spans": [("bench:stmt.q6", 100 * MS + off - 5_000,
                        145 * MS + off)]}
    ctx, _ = ctx_of(spans, [(0.1, 0.145)], trace=trace,
                    window=(lo / 1e9 - 7, hi / 1e9 - 7))
    monkeypatch.setattr(span_reduce, "window_spans", lambda c: spans)
    idle_s, idle, below = span_reduce.idle_by_span(ctx)
    assert idle_s == pytest.approx(0.045)
    assert idle["device.wait"] == pytest.approx(0.010)       # 2 x 5 ms
    assert idle["device.scan"] == pytest.approx(0.004)
    assert idle["sql.execute"] == pytest.approx(0.001)       # after the scan
    assert "sql.execute" not in below and "device.wait" in below
    # 45 ms idle: 20 outside any span, 1 in the root's self time, 24 below
    assert read("idle_attributed_pct", ctx) == pytest.approx(24 / 45 * 100)
    text = span_reduce.table(ctx)
    assert "device.wait" in text and "tserver.read " in text
    row = next(l for l in text.splitlines() if l.startswith("device.wait"))
    assert row.split() == ["device.wait", "2", "30.0", "30.0", "10.0"]
    # no trace, or the clocks cannot be paired: nothing to read
    trace["spans"] = []
    assert read("idle_attributed_pct", ctx) is None
    ctx.trace = None
    assert read("idle_attributed_pct", ctx) is None


def gaps_case(monkeypatch):
    """The statement of the test above on the same clocks, its benchmark
    span held 5 ms past the root (the client's side of the answer), one
    more device operation in that stretch (147..148 ms), and the
    reduction's own idle gaps by the benchmark's spans."""
    off = 7_000 * MS
    spans = statement(1, 100)
    busy = [[(100 + a) * MS + off, (100 + b) * MS + off]
            for a, b in ((6, 16), (26, 36), (47, 48))]
    lo, hi = 90 * MS + off, 155 * MS + off
    trace = {"busy": [busy], "busy_s": 0.021, "window_s": 0.065,
             "spans": [("bench:stmt.q6", 100 * MS + off, 150 * MS + off)],
             "device_ops": [["fusion.2", 0.021]],
             "idle_gaps": [["between spans", 0.025],
                           ["bench:stmt.q6", 0.019]]}
    ctx, _ = ctx_of(spans, [(0.1, 0.150)], trace=trace,
                    window=(lo / 1e9 - 7, hi / 1e9 - 7))
    monkeypatch.setattr(span_reduce, "window_spans", lambda c: spans)
    return ctx


def test_idle_gaps_name_the_program_span_and_the_bench_remainder(
        monkeypatch):
    """44 ms idle: 25 under the program's spans (as above), 19 under none
    of them — 4 in the benchmark's statement after the root closed (5 ms
    less the operation), 15 before and after the statement."""
    ctx = gaps_case(monkeypatch)
    idle_s, idle, below = span_reduce.idle_by_span(ctx)
    assert idle_s == pytest.approx(0.044)
    assert sum(idle.values()) == pytest.approx(idle_s, rel=1e-9)
    assert idle["device.wait"] == pytest.approx(0.010)
    assert idle["device.scan"] == pytest.approx(0.004)
    assert idle["sql.execute"] == pytest.approx(0.001)
    assert idle["bench:stmt.q6"] == pytest.approx(0.004)
    assert idle["between spans"] == pytest.approx(0.015)
    # the share below a statement's root reads only the program's names
    assert read("idle_attributed_pct", ctx) == pytest.approx(24 / 44 * 100)
    assert len(idle) == 13 and min(idle.values()) > 0


def test_idle_gaps_keep_ten_entries_that_add_up(monkeypatch):
    """Thirteen names: the nine longest, and the rest in a tenth
    entry."""
    ctx = gaps_case(monkeypatch)
    idle = span_reduce.idle_by_span(ctx)[1]
    gaps = span_reduce.idle_gaps(ctx)
    assert len(gaps) == 10 and gaps[-1][0] == "other names"
    assert [v for _, v in gaps[:9]] == sorted(idle.values(),
                                              reverse=True)[:9]
    assert [k for k, _ in gaps[:4]] == ["between spans", "device.wait",
                                        "device.scan", "bench:stmt.q6"]
    # client.combine 1, sql.execute 1, sql.parse 0.5, sql.plan 0.5 ms
    assert gaps[-1][1] == pytest.approx(0.003)
    assert sum(v for _, v in gaps) == pytest.approx(0.044, rel=1e-9)
    assert all(len(k) <= 160 for k, _ in gaps)
    assert run.breakdown(ctx) == {"device_ops": [["fusion.2", 0.021]],
                                  "idle_gaps": gaps}


@pytest.mark.parametrize("break_", ["clocks", "no_spans", "no_trace_busy"])
def test_idle_gaps_fall_back_to_the_bench_spans(monkeypatch, break_):
    """Where the program's spans cannot be put on the trace's clock (no
    statement mark to pair), where the program keeps none (a parent
    commit from before them), or where no device worked, the line keeps
    the reduction's gaps by the benchmark's spans."""
    ctx = gaps_case(monkeypatch)
    if break_ == "clocks":
        ctx.trace["spans"] = [("bench:other", 0.0, 1.0)]
    elif break_ == "no_spans":
        monkeypatch.setattr(span_reduce, "window_spans", lambda c: None)
    else:
        ctx.trace["busy"] = [[]]
    assert span_reduce.idle_gaps(ctx) is None
    assert run.breakdown(ctx) == {
        "device_ops": [["fusion.2", 0.021]],
        "idle_gaps": [["between spans", 0.025], ["bench:stmt.q6", 0.019]]}


def test_manifest_holds_the_seven_entries_and_validates():
    m = manifest.load()
    manifest.validate(m)
    mine = [x for x in m["per_layer"] if x["name"] in NEW]
    assert [x["name"] for x in mine] == NEW      # a later PR appends its own
    assert {x["source"] for x in mine} == {"program_span"}
    # a later cell joins a metric by its name in the list: `scan_power` stays
    assert all("scan_power" in x["workloads"]
               and x["moves"] == "scan_rows_per_s" for x in mine)
    assert set(manifest.Cell(m, "scan_power").readers) >= set(NEW)


def test_readers_on_a_traced_rehearsal(monkeypatch, capsys):
    """The real spans of a traced window (the profiler samples every
    statement): every statement is one tree, and six readers give a
    number; the CPU has no device plane, so the idle share has nothing to
    read."""
    recs = []

    class Rec(run.Recorder):
        def __init__(self, traced):
            super().__init__(traced)
            recs.append(self)
    monkeypatch.setattr(run, "Recorder", Rec)
    result = run.run_cell(["--workload", "scan_power", "--seed", "2147484002",
                           "--seconds", "1", "--rows", "24000", "--rehearse",
                           "--trace", "1"])
    capsys.readouterr()
    assert result["correct"] is True
    ctx = types.SimpleNamespace(trace=None, rec=recs[-1])
    trees = span_reduce.trees_of(ctx)
    assert trees and len(trees) == result["attempted"]
    for tree in trees:
        names = [span_reduce.short(s.name) for s in tree]
        assert names.count("sql.execute") == names.count("client.scan") == 1
        assert names.count("docdb.read") == names.count("device.wait") == 4
    values = {n: read(n, ctx) for n in NEW}
    assert values.pop("idle_attributed_pct") is None
    assert all(v is not None and v >= 0 for v in values.values()), values
    assert values["device_wait_ms"] > 0 and values["sql_front_ms"] > 0
    json.dumps(values, allow_nan=False)
    assert "docdb.batch" in span_reduce.table(ctx)
