"""The per-layer readers `scan_streams2` brought (`stmts_in_flight`,
`read_offload_ms`) on hand-made spans: statements that follow each other,
statements that overlap, waits stood on the loop and beside it, and a
program that has no hop; and the cell's traced rehearsal, which lists
them."""
import pytest

from benchmark import span_reduce
from tests.benchmark.test_span_reduce import MS, Span, ctx_of, read


def statement(trace, t0, t1, waits, offload_queue_ms=None):
    """One statement's tree from `t0` to `t1` (ms): a root, and under one
    tablet read a `device.wait` of 5 ms for each `thread` of `waits`, and
    a `tserver.read_offload` for each where the program has the hop."""
    root = Span("sql.execute", trace, trace * 100, 0, t0 * MS, t1 * MS, {})
    read_ = Span("tserver.read:t", trace, trace * 100 + 1, root.span_id,
                 t0 * MS, t1 * MS, {})
    doc = Span("docdb.read", trace, trace * 100 + 2, read_.span_id,
               t0 * MS, t1 * MS, {})
    out = [root, read_, doc]
    for i, thread in enumerate(waits):
        out.append(Span("device.wait", trace, trace * 100 + 10 + i,
                        doc.span_id, (t0 + 1) * MS, (t0 + 6) * MS,
                        {"thread": thread}))
        if offload_queue_ms is not None:
            out.append(Span("tserver.read_offload", trace,
                            trace * 100 + 20 + i, read_.span_id,
                            t0 * MS, (t0 + 7) * MS,
                            {"queue_ms": offload_queue_ms, "in_flight": 1}))
    return out


def ctx_with(monkeypatch, spans, stmts):
    ctx, spans = ctx_of(spans, stmts)
    monkeypatch.setattr(span_reduce, "window_spans", lambda c: spans)
    return ctx


@pytest.mark.parametrize("second, want", [
    ((110, 120), 1.0),          # one after the other
    ((100, 110), 2.0),          # together from start to end
    ((105, 115), 20 / 15),      # half of each overlaps
])
def test_stmts_in_flight_is_read_time_over_its_union(monkeypatch, second,
                                                     want):
    a, b = second
    spans = statement(1, 100, 110, ["loop"]) + statement(2, a, b, ["loop"])
    ctx = ctx_with(monkeypatch, spans, [(0.0999, 0.111), (a / 1e3 - 1e-4,
                                                          b / 1e3 + 1e-3)])
    assert read("stmts_in_flight", ctx) == pytest.approx(want)


def reads(trace, root, intervals):
    """A statement whose root is open over `root` and whose tablet reads
    (`docdb.read`) are open over `intervals`, all in ms."""
    out = [Span("sql.execute", trace, trace * 100, 0, root[0] * MS,
                root[1] * MS, {})]
    for i, (a, b) in enumerate(intervals):
        out.append(Span("docdb.read", trace, trace * 100 + 1 + i,
                        trace * 100, a * MS, b * MS, {}))
    return out


@pytest.mark.parametrize("second, want", [
    # served in turn: the second client's root is open while it queues,
    # its tablet reads begin when the first's have ended
    ([(120, 125), (125, 130), (130, 135), (135, 140)], 1.0),
    # served together: each of its reads beside one of the first's
    ([(100, 105), (105, 110), (110, 115), (115, 120)], 2.0),
    # its four reads overlap each other and nothing of the first's:
    # a statement counts once however many of its tablets are open
    ([(120, 130), (121, 131), (122, 132), (123, 133)], 1.0),
    # the last two of the first's four beside the first two of its own
    ([(110, 115), (115, 120), (120, 125), (125, 130)], 40 / 30),
])
def test_stmts_in_flight_counts_statements_inside_a_tablet_read(
        monkeypatch, second, want):
    first = [(100, 105), (105, 110), (110, 115), (115, 120)]
    spans = reads(1, (100, 121), first) + reads(2, (100, 141), second)
    ctx = ctx_with(monkeypatch, spans, [(0.0999, 0.1211), (0.09995, 0.1411)])
    assert read("stmts_in_flight", ctx) == pytest.approx(want)
    # roots alone would read close to 2 in every case
    roots = [s for s in spans if s.name == "sql.execute"]
    assert len(roots) == 2 and roots[0].end_ns > roots[1].start_ns


def test_stmts_in_flight_needs_a_tablet_read(monkeypatch):
    spans = reads(1, (100, 110), []) + reads(2, (105, 115), [])
    ctx = ctx_with(monkeypatch, spans, [(0.0999, 0.1101), (0.1049, 0.1151)])
    assert read("stmts_in_flight", ctx) is None


def test_waits_and_hops_beside_the_loop_are_counted(monkeypatch):
    spans = (statement(1, 100, 110, ["loop", "executor"], 0.25)
             + statement(2, 120, 130, ["executor", "executor"], 0.75))
    ctx = ctx_with(monkeypatch, spans, [(0.0999, 0.111), (0.1199, 0.131)])
    # every wait, on whichever thread it was stood
    assert read("device_wait_ms", ctx) == pytest.approx(20.0 / 2)
    # four hops, 0.25 ms twice and 0.75 ms twice, over two statements
    assert read("read_offload_ms", ctx) == pytest.approx(1.0)


def test_a_program_without_the_hop_reports_no_read_offload(monkeypatch):
    spans = statement(1, 100, 110, ["loop"]) + statement(2, 120, 130,
                                                         ["loop"])
    ctx = ctx_with(monkeypatch, spans, [(0.0999, 0.111), (0.1199, 0.131)])
    assert read("read_offload_ms", ctx) is None
    assert read("device_wait_ms", ctx) == pytest.approx(5.0)
    assert read("stmts_in_flight", ctx) == pytest.approx(1.0)


def test_without_spans_the_three_report_nothing(monkeypatch):
    ctx = ctx_with(monkeypatch, None, [(0.1, 0.2)])
    for name in ("stmts_in_flight", "read_offload_ms", "read_prep_ms"):
        assert read(name, ctx) is None


def test_two_interleaved_clients_still_pair_with_their_roots(monkeypatch):
    """`statement_trees` pairs roots and statements by start order: two
    clients whose statements overlap keep that order, because a root
    opens inside its statement before anything is awaited."""
    spans = statement(1, 100, 130, ["executor"], 0.1) + statement(
        2, 101, 112, ["executor"], 0.1) + statement(
        3, 112.5, 140, ["executor"], 0.1)
    ctx = ctx_with(monkeypatch, spans, [
        (0.0999, 0.1301), (0.1009, 0.1121), (0.1124, 0.1401)])
    trees = span_reduce.trees_of(ctx)
    assert [t[0].trace_id for t in trees] == [1, 2, 3]


def test_a_traced_rehearsal_of_the_cell_reads_the_three(monkeypatch, capsys):
    """The real spans of a traced window of `scan_streams2` on the CPU:
    the cell finds a reader for every per-layer entry that names it, the
    three span readers it lists give numbers, and no wait is stood on the
    loop: every statement's tree holds its hops."""
    import json
    import types

    from benchmark import manifest, run
    cell, recs = "scan_streams2", []

    class Rec(run.Recorder):
        def __init__(self, traced):
            super().__init__(traced)
            recs.append(self)
    monkeypatch.setattr(run, "Recorder", Rec)
    m = manifest.load()
    c = manifest.Cell(m, cell)
    result = run.run_cell([
        "--workload", cell, "--seed", "2147484032", "--seconds", "1",
        "--rows", str(c.config["rehearsal"]["rows"]), "--rehearse",
        "--trace", "1"])
    capsys.readouterr()
    assert result["correct"] is True, result["compared"]
    listed = [x["name"] for x in m["per_layer"]
              if cell in x.get("workloads", [cell])]
    assert list(c.readers) == listed
    new = ["stmts_in_flight", "read_offload_ms", "read_prep_ms"]
    assert set(new) <= set(listed)
    ctx = types.SimpleNamespace(trace=None, rec=recs[-1], cell=c,
                                peak=None, data=None)
    values = {n: read(n, ctx) for n in new}
    json.dumps(values, allow_nan=False)
    trees = span_reduce.trees_of(ctx)
    assert trees and len(trees) == result["attempted"]
    launches = []
    for t in trees:
        hops = [s for s in t if s.name == "tserver.read_offload"]
        assert len(hops) == sum(s.name == "device.wait" for s in t)
        assert all(s.tags["thread"] == "executor" for s in t
                   if s.name == "device.wait")
        launches.append(len(hops))
    # four tablets a statement; fewer only where the scan lane joined a
    # tablet's read to the other stream's identical one
    assert max(launches) == 4 and launches.count(4) > len(trees) // 2
    assert values["read_offload_ms"] > 0.0
    assert values["read_prep_ms"] >= 0.0
    assert 1.0 <= values["stmts_in_flight"] <= 2.0
