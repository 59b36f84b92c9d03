"""`gc_pause_ms` (`benchmark/layer_metrics/gc_pause_ms.py`): the seconds
`benchmark/record.py GcWatch` counts in the window, a millisecond per
statement — on a hand-made record, and on the collector itself.  The
traced rehearsal of every cell that lists it is in
`test_read_prep_metric.py`."""
import gc
import types

import pytest

from benchmark import manifest
from benchmark.record import GcWatch, Recorder

NAME = "gc_pause_ms"


def read(ctx):
    return manifest.load_module(manifest.layer_metric_file(NAME)).read(ctx)


def recorder(stmts_ok, stmts_failed, gc_seconds):
    rec = Recorder(traced=False)
    for ok in [True] * stmts_ok + [False] * stmts_failed:
        rec.spans.append({"kind": "stmt", "ok": ok, "t0": 0.0, "t1": 1.0})
    rec.spans.append({"kind": "warm", "ok": True, "t0": 0.0, "t1": 1.0})
    if gc_seconds is not None:
        rec.gc = {"collections": 3, "full": 1, "seconds": gc_seconds,
                  "longest_s": gc_seconds / 2}
    return types.SimpleNamespace(rec=rec, trace=None)


@pytest.mark.parametrize("ok, failed, seconds, want", [
    (400, 0, 0.18, 0.45),          # 180 ms over 400 statements
    (400, 2, 0.18, 0.45),          # a failed statement is no statement
    (250, 0, 0.0, 0.0),            # no collection in the window
    (0, 0, 0.18, None),            # nothing to divide by
    (400, 0, None, None),          # a window that watched nothing
])
def test_ms_per_statement_of_the_window(ok, failed, seconds, want):
    got = read(recorder(ok, failed, seconds))
    assert got == (None if want is None else pytest.approx(want))


def test_the_watch_counts_a_collection_with_all_its_digits():
    """What the reader divides: a forced full collection inside the watch
    is counted, with its time unrounded; one outside it is not."""
    junk = [[i] for i in range(200_000)]
    for item in junk:
        item.append(item)               # cycles for the collector to find
    with GcWatch() as watch:
        del junk
        gc.collect()
    gc.collect()
    summary = watch.summary()
    assert summary["collections"] >= 1 and summary["full"] >= 1
    assert 0 < summary["longest_s"] <= summary["seconds"]
    ctx = recorder(1, 0, None)
    ctx.rec.gc = summary
    assert read(ctx) == pytest.approx(summary["seconds"] * 1e3)
