"""`read_prep_ms` (`benchmark/layer_metrics/read_prep_ms.py`): what a
statement's `docdb.read` spans do outside block collection, batch lookup,
dispatch and wait — on hand-made span trees, and on the real spans of a
traced rehearsal of each cell that lists it."""
import collections
import json
import types

import pytest

from benchmark import manifest, run, span_reduce

Span = collections.namedtuple(
    "Span", "name trace_id span_id parent_id start_ns end_ns tags")
MS = 1_000_000
NAME = "read_prep_ms"


def read(name, ctx):
    return manifest.load_module(manifest.layer_metric_file(name)).read(ctx)


def statement(trace, t0, reads):
    """One statement from `t0` ms: a root, and per entry of `reads` one
    `docdb.read` of 20 ms whose children are `(name, from, to, parent)` in
    ms from the read's start; `parent` names an earlier child to nest
    under."""
    ids = iter(range(trace * 1000 + 1, trace * 1000 + 999))
    root = next(ids)
    out = [Span("sql.execute", trace, root, 0, t0 * MS,
                (t0 + 20 * max(len(reads), 1) + 2) * MS, {})]
    for i, children in enumerate(reads):
        a = t0 + 1 + 20 * i
        rid = next(ids)
        out.append(Span("docdb.read", trace, rid, root, a * MS,
                        (a + 20) * MS, {"route": "tpu_aggregate"}))
        by_name = {}
        for name, lo, hi, parent in children:
            sid = next(ids)
            by_name[name] = sid
            out.append(Span(name, trace, sid, by_name.get(parent, rid),
                            int((a + lo) * MS), int((a + hi) * MS), {}))
    return out


def ctx_of(stmts):
    rec = types.SimpleNamespace(window=(0.0, 1.0))
    rec.of = lambda kind, ok_only=True: [
        {"kind": "stmt", "ok": True, "t0": a, "t1": b} for a, b in stmts]
    return types.SimpleNamespace(trace=None, rec=rec)


SERVED = [("docdb.collect_blocks", 0, 1, None), ("docdb.batch", 1, 2, None),
          ("device.scan", 10, 12, None), ("device.wait", 12, 19, None)]
# a cache miss: the build and its copy nest under `docdb.batch`, and are
# subtracted once — with it, not again beside it
NESTED = [("docdb.collect_blocks", 0, 1, None), ("docdb.batch", 1, 9, None),
          ("batch.build", 2, 8, "docdb.batch"),
          ("batch.h2d", 6, 8, "batch.build"),
          ("device.scan", 10, 12, None), ("device.wait", 12, 19, None)]
# a plan and a walk: neither is covered, both are what the metric holds
PLAN = SERVED + [("device.dict_plan", 2, 8, None)]
FULL = [("docdb.collect_blocks", 0, 1, None), ("docdb.batch", 1, 10, None),
        ("device.scan", 10, 12, None), ("device.wait", 12, 20, None)]


@pytest.mark.parametrize("reads, stmts, want", [
    # 20 ms a read, 11 covered: 9 left; two statements of 2 and 4 reads
    ([[SERVED] * 2, [SERVED] * 4], 2, (2 * 9 + 4 * 9) / 2),
    ([[NESTED] * 2, [SERVED] * 2], 2, (2 * 2 + 2 * 9) / 2),
    ([[PLAN], [PLAN]], 2, 9.0),
    # every millisecond of every read covered: 0.0, and not None
    ([[FULL] * 4, [FULL] * 4], 2, 0.0),
    # a statement that served from elsewhere counts as a statement
    ([[SERVED], []], 2, 4.5),
])
def test_children_are_subtracted_once(monkeypatch, reads, stmts, want):
    spans = [s for i, r in enumerate(reads)
             for s in statement(i + 1, 100 * (i + 1), r)]
    monkeypatch.setattr(span_reduce, "window_spans", lambda c: spans)
    ctx = ctx_of([(0.0999 * (i + 1), 0.0999 * (i + 1) + 0.09)
                  for i in range(stmts)])
    got = read(NAME, ctx)
    assert got == pytest.approx(want) and isinstance(got, float)
    if want == 0.0:
        assert got == 0.0 and got is not None


def test_no_docdb_read_in_the_window_is_none(monkeypatch):
    spans = statement(1, 100, []) + statement(2, 200, [])
    monkeypatch.setattr(span_reduce, "window_spans", lambda c: spans)
    ctx = ctx_of([(0.0999, 0.19), (0.1999, 0.29)])
    assert read(NAME, ctx) is None
    # a program with no spans at all, and roots that are not the statements
    monkeypatch.setattr(span_reduce, "window_spans", lambda c: None)
    assert read(NAME, ctx) is None
    spans = statement(1, 100, [SERVED])
    monkeypatch.setattr(span_reduce, "window_spans", lambda c: spans)
    assert read(NAME, ctx) is None


def test_the_entry_is_appended_and_validates():
    """Found by its name: a later PR appends its own entries after it."""
    m = manifest.load()
    manifest.validate(m)
    entry, = [x for x in m["per_layer"] if x["name"] == NAME]
    assert entry == {"name": NAME, "unit": "ms", "better": "lower",
                     "source": "program_span",
                     "layer": "tserver + scheduler",
                     "moves": "scan_rows_per_s",
                     "workloads": ["scan_power", "mesh4_q1_psum",
                                   "scan_streams2"]}


@pytest.mark.parametrize("cell", ["scan_power", "mesh4_q1_psum",
                                  "scan_streams2"])
def test_a_traced_rehearsal_reads_it_and_lists_every_metric(
        cell, monkeypatch, capsys):
    """The real spans of a traced window on the CPU: the reader gives a
    number in every cell, the cell still finds a reader for every
    `per_layer` entry of `BENCHMARK.json` that names it, and every listed
    reader that reads no device trace gives a number there: a cell lists
    only what it can report."""
    recs = []

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
    assert NAME in listed and list(c.readers) == listed
    ctx = types.SimpleNamespace(trace=None, rec=recs[-1], cell=c,
                                peak=None, data=None)
    values = {n: read(n, ctx) for n in listed}
    json.dumps(values, allow_nan=False)
    trees = span_reduce.trees_of(ctx)
    assert trees and len(trees) == result["attempted"]
    assert any(s.name == "docdb.read" for t in trees for s in t)
    assert values[NAME] is not None and values[NAME] >= 0.0
    host_side = [x["name"] for x in m["per_layer"] if x["name"] in listed
                 and x["source"] != "device_trace"
                 and x["name"] != "idle_attributed_pct"]
    assert {"read_offload_ms", "gc_pause_ms"} <= set(host_side)
    assert all(values[n] is not None and values[n] >= 0.0
               for n in host_side), values
    # never more than the reads themselves
    assert values[NAME] <= sum(
        span_reduce.total_ns(t, "docdb.read") for t in trees) \
        / len(trees) / 1e6
