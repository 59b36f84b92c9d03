"""The cell whose table outgrows the device cache (`scan_cold_cache`) and its
five readers (`cold_reads_per_stmt`, `build_host_ms`, `h2d_ms`,
`h2d_gb_per_s`, `build_wait_ms`): on hand-made span trees, on a program
that has none of their spans, and on the real spans of a traced rehearsal
whose cache is cut to the cell's ratio, so that reads miss."""
import types

import pytest

from benchmark import manifest, run, span_reduce
from tests.benchmark.test_span_reduce import MS, Span, ctx_of, read

NEW = {"cold_reads_per_stmt": ("tserver + scheduler", "reads", "lower"),
       "build_host_ms": ("batch formation", "ms", "lower"),
       "h2d_ms": ("batch formation", "ms", "lower"),
       "h2d_gb_per_s": ("batch formation", "GB/s", "higher"),
       "build_wait_ms": ("tserver + scheduler", "ms", "lower")}
CELL = "scan_cold_cache"
#: the cell's tablets hold 8,388,608 padded rows and its cache 2 GiB; a
#: rehearsal's 6,000 rows a tablet are padded to 8,192: 1/1024 of both
REHEARSAL_CACHE = (2 << 30) // 1024


def statement(trace, t0, cold, tablets=8):
    """One statement from `t0` (ms) over `tablets` tablet reads, the first
    `cold` of them misses: each miss waits 2 ms for a build slot and a
    thread, collects 3 ms, builds 5 (a version link of 1 inside) and
    moves 1 GB in 4 ms."""
    ids = iter(range(trace * 1000 + 1, trace * 1000 + 999))
    root = next(ids)
    out = [Span("sql.execute", trace, root, 0, t0 * MS,
                (t0 + 20 * tablets) * MS, {})]

    def add(name, parent, a, b, **tags):
        sid = next(ids)
        out.append(Span(name, trace, sid, parent, int((t0 + a) * MS),
                        int((t0 + b) * MS), tags))
        return sid
    for i in range(tablets):
        a = 20 * i
        r = add("tserver.read:t", root, a, a + 19)
        d = add("docdb.read", r, a, a + 19)
        if i >= cold:
            add("docdb.batch", d, a, a + 0.1, cache="hit")
            continue
        add("tserver.build_offload", r, a, a + 14, queue_ms=2.0)
        b = add("docdb.batch", d, a, a + 14, cache="miss",
                admit="passed" if i else "resident")
        add("batch.collect", b, a + 2, a + 5, blocks=3)
        build = add("batch.build", b, a + 5, a + 10)
        add("batch.version_link", build, a + 8, a + 9, kept=i > 0)
        add("batch.h2d", b, a + 10, a + 14, bytes=1_000_000_000)
    return out


def ctx_with(monkeypatch, spans, stmts):
    ctx, spans = ctx_of(spans, stmts)
    monkeypatch.setattr(span_reduce, "window_spans", lambda c: spans)
    return ctx


TWO = [(0.0999, 0.3), (0.3999, 0.6)]


@pytest.mark.parametrize("name, want", [
    ("cold_reads_per_stmt", (6 + 4) / 2),
    ("build_host_ms", (3 + 5) * 5),
    ("h2d_ms", 4 * 5),
    ("h2d_gb_per_s", 1e9 / (4 * MS)),
    ("build_wait_ms", 2.0 * 5)])
def test_each_reader_on_a_hand_made_tree(monkeypatch, name, want):
    """Per statement: the two statements make 6 and 4 cold reads, 5 on
    average; the rate is bytes over the transfers' time."""
    ctx = ctx_with(monkeypatch,
                   statement(1, 100, 6) + statement(2, 400, 4), TWO)
    got = read(name, ctx)
    assert got == pytest.approx(want) and isinstance(got, float)


def test_a_window_of_hits_has_no_cold_read_and_nothing_to_time(monkeypatch):
    ctx = ctx_with(monkeypatch,
                   statement(1, 100, 0) + statement(2, 400, 0), TWO)
    assert read("cold_reads_per_stmt", ctx) == 0.0
    assert {n: read(n, ctx) for n in NEW if n != "cold_reads_per_stmt"} \
        == dict.fromkeys(set(NEW) - {"cold_reads_per_stmt"})


@pytest.mark.parametrize("spans, stmts", [
    (None, TWO),                                      # no spans at all
    (statement(1, 100, 6) + statement(2, 400, 4), TWO[:1]),  # not the roots
    ([s for s in statement(1, 100, 6) + statement(2, 400, 4)
      if not s.name.startswith(("docdb.batch", "batch.", "tserver.build"))],
     TWO),                                            # a program without
])
def test_where_the_spans_are_not_there_each_reads_none(monkeypatch, spans,
                                                        stmts):
    ctx = ctx_with(monkeypatch, spans, stmts)
    assert {n: read(n, ctx) for n in NEW} == dict.fromkeys(NEW)


def test_the_entries_are_appended_and_validate():
    """Found by name, moving the one end-to-end metric besides `setup_s`
    that the cell reports."""
    m = manifest.load()
    manifest.validate(m)
    names = [x["name"] for x in m["per_layer"]]
    assert names[-len(NEW):] == list(NEW)
    for name, (layer, unit, better) in NEW.items():
        entry, = [x for x in m["per_layer"] if x["name"] == name]
        assert entry == {"name": name, "unit": unit, "better": better,
                         "source": "program_span", "layer": layer,
                         "moves": "stmt_p95_ms", "workloads": [CELL]}
    cell = manifest.Cell(m, CELL)
    assert cell.chips == 1 and cell.traffic["trace_seconds"] >= 20
    assert cell.config["flags"]["tserver_device_cache_bytes"] == 2 << 30
    assert {"stmt_p95_ms", "setup_s"} == {x["name"] for x in cell.end_to_end}


def test_a_rehearsal_with_the_cells_ratio_misses_and_reads_each(
        monkeypatch, capsys):
    """The cell on the CPU at its rehearsal's rows, its cache cut by the
    rows' ratio: statements miss, the answers are the reference's, and
    the five readers and `batch_form_ms` give numbers."""
    recs = []

    class Rec(run.Recorder):
        def __init__(self, traced):
            super().__init__(traced)
            recs.append(self)

    class Cut(manifest.Cell):
        def __init__(self, *a, **k):
            super().__init__(*a, **k)
            self.config = dict(self.config, flags=dict(
                self.config["flags"],
                tserver_device_cache_bytes=REHEARSAL_CACHE))

    monkeypatch.setattr(run, "Recorder", Rec)
    monkeypatch.setattr(manifest, "Cell", Cut)
    c = Cut(manifest.load(), CELL)
    result = run.run_cell([
        "--workload", CELL, "--seed", "2147484701", "--seconds", "2",
        "--rows", str(c.config["rehearsal"]["rows"]), "--rehearse",
        "--trace", "1"])
    capsys.readouterr()
    assert result["correct"] is True and result["failed"] == 0, \
        result["compared"]
    ctx = types.SimpleNamespace(trace=None, rec=recs[-1], cell=c,
                                peak=None, data=None)
    values = {n: read(n, ctx) for n in list(NEW) + ["batch_form_ms"]}
    assert all(v is not None and v > 0.0 for v in values.values()), values
    assert 1 <= values["cold_reads_per_stmt"] < 8
