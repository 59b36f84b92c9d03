"""The three per-layer readers `mesh4_q1_psum` brings (`psum_device_ms`,
`chips_busy`, `mesh_form_ms`) on hand-made input, and what a program
without a mesh scan gives them: nothing."""
import collections
import types

import pytest

from benchmark import manifest, span_reduce

Span = collections.namedtuple(
    "Span", "name trace_id span_id parent_id start_ns end_ns tags")
MS = 1_000_000


def read(name, ctx):
    return manifest.load_module(manifest.layer_metric_file(name)).read(ctx)


def trace_of(ops: dict, devices_busy: int) -> dict:
    return {"spans": [("bench:stmt.q6", 0, 10 * MS),
                      ("bench:stmt.q1", 10 * MS, 30 * MS),
                      ("bench:trace_window", 0, 30 * MS)],
            "ops_by_name": ops, "devices_busy": devices_busy}


def test_psum_device_ms_sums_the_all_reduces_a_statement_a_chip():
    ctx = types.SimpleNamespace(trace=trace_of(
        {"all-reduce.7": 0.0004, "all-reduce-start.2": 0.0002,
         "fusion.12": 0.0100, "copy.3": 0.0030}, 4))
    assert read("psum_device_ms", ctx) == pytest.approx(0.3)
    assert read("chips_busy", ctx) == 4


@pytest.mark.parametrize("name", ["psum_device_ms", "chips_busy"])
def test_a_trace_with_no_mesh_scan_gives_the_trace_readers_nothing(name):
    # (a parent commit: one chip's programs, no collective)
    ctx = types.SimpleNamespace(trace=trace_of({"fusion.12": 0.01}, 0))
    assert read(name, ctx) is None
    assert read(name, types.SimpleNamespace(trace=None)) is None


def _statement(trace: int, t0: float, gather_ms: float) -> list:
    spans = [Span("sql.execute", trace, trace * 10, 0, int(t0 * MS),
                  int((t0 + 30) * MS), {}),
             Span("tserver.read_tablets", trace, trace * 10 + 1, trace * 10,
                  int((t0 + 1) * MS), int((t0 + 29) * MS), {})]
    if gather_ms:
        spans.append(Span(
            "tserver.mesh_gather", trace, trace * 10 + 2, trace * 10 + 1,
            int((t0 + 1) * MS), int((t0 + 1 + gather_ms) * MS),
            {"tablets": 8, "chips": 4, "fanin": 8}))
    return spans


@pytest.mark.parametrize("gathers, want", [((0.5, 1.5), 1.0),
                                           ((0.5, 0), 0.25),
                                           ((0, 0), None)])
def test_mesh_form_ms_is_the_gather_spans_a_statement(monkeypatch, gathers,
                                                      want):
    spans = _statement(1, 100, gathers[0]) + _statement(2, 200, gathers[1])
    rec = types.SimpleNamespace(window=(0.0, 1.0))
    rec.of = lambda kind, ok_only=True: [
        {"kind": "stmt", "ok": True, "t0": a, "t1": b}
        for a, b in ((0.0999, 0.131), (0.1999, 0.231))]
    monkeypatch.setattr(span_reduce, "window_spans", lambda c: spans)
    got = read("mesh_form_ms", types.SimpleNamespace(trace=None, rec=rec))
    assert got == (pytest.approx(want) if want is not None else None)
