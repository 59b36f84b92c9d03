"""The readers of a launch's host time split into work and waiting
(`dispatch_cpu_ms`, `dispatch_offcpu_ms`, `launch_prep_ms`,
`read_resume_ms`, `read_steps_ms`): on hand-made span trees, on a program
that has none of their spans or tags, and on the real spans of a traced
rehearsal of each cell that lists them, where `read_prep_ms` is
accounted for by its parts."""
import types

import pytest

from benchmark import manifest, run, span_reduce
from tests.benchmark.test_span_reduce import MS, Span, ctx_of, read

NEW = {"dispatch_cpu_ms": "scan kernel", "dispatch_offcpu_ms": "scan kernel",
       "launch_prep_ms": "scan kernel",
       "read_resume_ms": "tserver + scheduler",
       "read_steps_ms": "tserver + scheduler"}
CELLS = ["scan_power", "mesh4_q1_psum", "scan_streams2"]


def statement(trace, t0, launches, split=True):
    """One statement from `t0` (ms) with one tablet read a launch, 12 ms
    each: the read's first step on the loop 1.5 ms (block collection 0.5
    of it), the hop's queue 1, `launch.prepare` 0.5, `device.scan` 3 of
    which 1 on the CPU, `device.wait` 4, 0.5 from the launch's return to
    the read's resumption, the last step 0.5 (`steps_ms` 2 on the read's
    span).  `split=False` is a program from before the five: no
    `launch.prepare`, no `tserver.read_resume`, no `cpu_ms`, no
    `steps_ms`."""
    ids = iter(range(trace * 1000 + 1, trace * 1000 + 999))
    root = next(ids)
    out = [Span("sql.execute", trace, root, 0, t0 * MS,
                (t0 + 12 * launches + 1) * MS, {})]

    def add(name, parent, a, b, **tags):
        sid = next(ids)
        out.append(Span(name, trace, sid, parent, int((t0 + a) * MS),
                        int((t0 + b) * MS), tags))
        return sid
    for i in range(launches):
        a = 0.5 + 12 * i
        read_ = add("tserver.read:t", root, a, a + 11.5,
                    **({"steps_ms": 2.0} if split else {}))
        doc = add("docdb.read", read_, a, a + 11.0)
        add("docdb.collect_blocks", doc, a, a + 0.5)
        add("tserver.read_offload", read_, a + 1.5, a + 10.5,
            in_flight=1, queue_ms=1.0)
        if split:
            add("launch.prepare", doc, a + 2.5, a + 3.0)
        add("device.scan", doc, a + 3.0, a + 6.0,
            **({"cpu_ms": 1.0} if split else {}))
        add("device.wait", doc, a + 6.0, a + 10.0, thread="executor")
        if split:
            add("tserver.read_resume", doc, a + 10.0, a + 10.5, in_flight=1)
    return out


def ctx_with(monkeypatch, spans, stmts):
    ctx, spans = ctx_of(spans, stmts)
    monkeypatch.setattr(span_reduce, "window_spans", lambda c: spans)
    return ctx


TWO = [(0.0999, 0.15), (0.1999, 0.3)]        # of 2 and 4 launches


def two(split=True):
    return statement(1, 100, 2, split) + statement(2, 200, 4, split)


@pytest.mark.parametrize("name, want", [
    ("dispatch_cpu_ms", 1.0 * 3), ("dispatch_offcpu_ms", 2.0 * 3),
    ("launch_prep_ms", 0.5 * 3), ("read_resume_ms", 0.5 * 3),
    ("read_steps_ms", 2.0 * 3)])
def test_each_reader_on_a_hand_made_tree(monkeypatch, name, want):
    """Per statement: the two statements make 2 and 4 launches, 3 on
    average."""
    ctx = ctx_with(monkeypatch, two(), TWO)
    got = read(name, ctx)
    assert got == pytest.approx(want) and isinstance(got, float)


def test_the_dispatch_is_its_cpu_and_off_cpu_time(monkeypatch):
    ctx = ctx_with(monkeypatch, two(), TWO)
    assert read("dispatch_cpu_ms", ctx) + read("dispatch_offcpu_ms", ctx) \
        == pytest.approx(read("kernel_dispatch_ms", ctx))


def test_read_prep_is_accounted_for_by_its_parts(monkeypatch):
    """A launch's `docdb.read` is 11 ms, 7.5 of it under block collection,
    the dispatch and the wait: the 3.5 left are the steps less block
    collection (1.5), the queue (1), the preparation (0.5) and the wait
    for the loop (0.5)."""
    ctx = ctx_with(monkeypatch, two(), TWO)
    assert read("read_prep_ms", ctx) == pytest.approx(3.5 * 3)
    assert _parts(ctx) == pytest.approx(3.5 * 3)


@pytest.mark.parametrize("spans, stmts", [
    (two(split=False), TWO),                 # a program from before them
    (None, TWO),                             # a program with no spans
    (two(), TWO[:1]),                        # roots not the statements
])
def test_where_the_spans_are_not_there_each_reads_none(monkeypatch, spans,
                                                        stmts):
    ctx = ctx_with(monkeypatch, spans, stmts)
    assert {n: read(n, ctx) for n in NEW} == dict.fromkeys(NEW)


def test_the_entries_are_appended_and_validate():
    """Found by name: a later PR appends its own entries after them."""
    m = manifest.load()
    manifest.validate(m)
    for name, layer in NEW.items():
        entry, = [x for x in m["per_layer"] if x["name"] == name]
        assert entry == {"name": name, "unit": "ms", "better": "lower",
                         "source": "program_span", "layer": layer,
                         "moves": "scan_rows_per_s", "workloads": CELLS}


def _parts(ctx):
    return (read("read_steps_ms", ctx) - read("batch_form_ms", ctx)
            + read("read_offload_ms", ctx) + read("launch_prep_ms", ctx)
            + read("read_resume_ms", ctx))


@pytest.mark.parametrize("cell", CELLS)
def test_a_traced_rehearsal_gives_each_a_number_and_accounts_for_read_prep(
        cell, monkeypatch, capsys):
    """The real spans of a traced window on the CPU: the five give numbers
    ≥ 0; the dispatch is its CPU and off-CPU time; `read_prep_ms` is the
    read's steps less block collection and batch lookup, plus the hop's
    queue, the launch's preparation and its wait for the loop — within
    15% or 1 ms a statement."""
    recs = []

    class Rec(run.Recorder):
        def __init__(self, traced):
            super().__init__(traced)
            recs.append(self)
    monkeypatch.setattr(run, "Recorder", Rec)
    c = manifest.Cell(manifest.load(), cell)
    result = run.run_cell([
        "--workload", cell, "--seed", "2147484101", "--seconds", "1",
        "--rows", str(c.config["rehearsal"]["rows"]), "--rehearse",
        "--trace", "1"])
    capsys.readouterr()
    assert result["correct"] is True, result["compared"]
    assert set(NEW) <= set(c.readers)
    ctx = types.SimpleNamespace(trace=None, rec=recs[-1], cell=c,
                                peak=None, data=None)
    values = {n: read(n, ctx) for n in NEW}
    assert all(v is not None and v >= 0.0 for v in values.values()), values
    assert values["dispatch_cpu_ms"] + values["dispatch_offcpu_ms"] \
        == pytest.approx(read("kernel_dispatch_ms", ctx))
    prep, parts = read("read_prep_ms", ctx), _parts(ctx)
    assert abs(prep - parts) <= max(0.15 * prep, 1.0), (prep, parts, values)
