"""The reduction from trace to numbers, on a few events worked by hand and
on the recorded traces under `data/`: busy union, idle share, attribution
of device time and of idle gaps to the benchmark's spans."""
import json
import os

import pytest

from benchmark import trace_reduce as tr

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
MS = 1e6     # nanoseconds


def plane(name, **lines):
    return {"name": name, "lines": [{"name": n.replace("_", " "), "events": e}
                                    for n, e in lines.items()]}


def by_hand():
    """A 100 ms window: two statements (10..40 and 50..90 ms), device ops
    at 12..20, 18..30 (overlapping: union 12..30), 55..60, 95..98 ms, and
    an `XLA Modules` line that covers the same time again."""
    host = plane("/host:CPU", python=[
        ("bench:trace_window", 0 * MS, 100 * MS),
        ("bench:stmt.q6", 10 * MS, 30 * MS),
        ("bench:stmt.q1", 50 * MS, 40 * MS),
        ("PjitFunction(f)", 11 * MS, 1 * MS)])
    dev = plane("/device:TPU:0",
                XLA_Ops=[("sort.1", 12 * MS, 8 * MS),
                         ("fusion.2", 18 * MS, 12 * MS),
                         ("sort.1", 55 * MS, 5 * MS),
                         ("copy.3", 95 * MS, 3 * MS)],
                XLA_Modules=[("jit_scan", 12 * MS, 18 * MS)])
    return [host, dev]


def test_merge_and_intersect_by_hand():
    assert tr.merge([[5, 7], [1, 3], [2, 4], [7, 8]]) == [[1, 4], [5, 8]]
    assert tr.total(tr.merge([[0, 10], [2, 3]])) == 10
    assert tr.clip([[0, 10], [20, 30]], 5, 25) == [[5, 10], [20, 25]]
    assert tr.intersect([[0, 10], [20, 30]], [[5, 22], [29, 40]]) == \
        [[5, 10], [20, 22], [29, 30]]


def test_busy_union_and_idle_share_by_hand():
    red = tr.reduce(by_hand())
    assert red["devices"] == 1
    assert red["window_s"] == pytest.approx(0.100)
    # 12..30 + 55..60 + 95..98 = 26 ms; the modules line is not counted
    assert red["busy_s"] == pytest.approx(0.026)
    assert 1 - red["busy_s"] / red["window_s"] == pytest.approx(0.74)


def test_device_time_is_attributed_by_the_benchmarks_spans():
    red = tr.reduce(by_hand())
    assert tr.span_count(red, "bench:stmt.") == 2
    # inside the statements: 12..30 and 55..60; 95..98 is between spans
    assert tr.busy_in_spans(red, "bench:stmt.") == pytest.approx(0.023)
    assert tr.busy_in_spans(red, "bench:stmt.q1") == pytest.approx(0.005)
    assert tr.busy_in_spans(red, "bench:compact") == 0
    ops = dict(red["device_ops"])
    assert ops["sort.1"] == pytest.approx(0.013)
    assert ops["fusion.2"] == pytest.approx(0.012)
    gaps = dict(red["idle_gaps"])
    # idle: 0..12 (10 ms before the statement, 2 in q6), 30..55 (q6 10,
    # between 10, q1 5), 60..95 (q1 30, after it 5), 98..100
    assert sum(gaps.values()) == pytest.approx(0.074)
    assert gaps["bench:stmt.q6"] == pytest.approx(0.012)
    assert gaps["bench:stmt.q1"] == pytest.approx(0.035)
    assert gaps["between spans"] == pytest.approx(0.027)
    assert len(red["device_ops"]) <= 10 and len(red["idle_gaps"]) <= 10


def test_overlapping_spans_of_two_clients_count_shared_time_once():
    planes = by_hand()
    planes[0]["lines"][0]["events"].append(("bench:stmt.q1", 15 * MS, 20 * MS))
    red = tr.reduce(planes)
    assert tr.span_count(red, "bench:stmt.") == 3
    assert tr.busy_in_spans(red, "bench:stmt.") == pytest.approx(0.023)


def test_two_devices_are_averaged_and_an_idle_plane_is_left_out():
    planes = by_hand()
    planes.append(plane("/device:TPU:1", XLA_Ops=[("sort.1", 0, 50 * MS)]))
    planes.append(plane("/device:TPU:2", XLA_Ops=[]))
    red = tr.reduce(planes)
    assert red["devices"] == 2
    assert red["busy_s"] == pytest.approx((0.026 + 0.050) / 2)


def test_a_trace_with_no_device_plane_reads_nothing():
    red = tr.reduce(by_hand()[:1])
    assert red["devices"] == 0 and red["busy_s"] == 0
    assert red["device_ops"] == [] and tr.busy_in_spans(red, "bench:") == 0


def test_loader_reads_a_recorded_xplane():
    """A trace recorded on the CPU backend with the benchmark's own span
    names: the loader finds the spans on the host plane."""
    planes = tr.load_xplane(os.path.join(DATA, "cpu_spans.xplane.pb"))
    spans = tr.bench_spans(planes)
    assert [s[0] for s in spans] == ["bench:trace_window", "bench:stmt.q6",
                                     "bench:stmt.q1", "bench:stmt.q6"]
    red = tr.reduce(planes)
    assert red["devices"] == 0 and red["window_s"] > 0
    assert tr.span_count(red, "bench:stmt.") == 3
    assert "bench:trace_window" in tr.summary(planes)


@pytest.mark.parametrize("name", ["tpu_scan_power", "tpu_refresh_compact"])
def test_recorded_chip_trace_reduces_to_the_recorded_numbers(name):
    """A cut of a trace taken on the v5e in PR 26 (the events of a short
    stretch, as plain data) with the numbers worked out for it then."""
    with open(os.path.join(DATA, name + ".json")) as f:
        rec = json.load(f)
    planes = [{"name": p["name"], "lines": [
        {"name": l["name"], "events": [tuple(e) for e in l["events"]]}
        for l in p["lines"]]} for p in rec["planes"]]
    red = tr.reduce(planes)
    want = rec["expect"]
    assert red["devices"] == 1
    assert red["window_s"] == pytest.approx(want["window_s"], rel=1e-9)
    assert red["busy_s"] == pytest.approx(want["busy_s"], rel=1e-9)
    assert 0 < red["busy_s"] < red["window_s"]
    for prefix, (count, busy) in want["spans"].items():
        assert tr.span_count(red, prefix) == count
        assert tr.busy_in_spans(red, prefix) == pytest.approx(busy, rel=1e-9)
        assert tr.busy_in_spans(red, prefix) <= red["busy_s"] * (1 + 1e-9)
    assert red["device_ops"][0][0] == want["top_op"]
