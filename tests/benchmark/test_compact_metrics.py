"""The refresh cell's end-to-end metric and its merge readers on hand-made
spans and traces, and that the cell is found in `BENCHMARK.json` alone
(no second manifest)."""
import os
import types

import pytest

from benchmark import manifest, run
from benchmark.record import Recorder


def _driver():
    return manifest.load_module(manifest.driver_file("refresh_compact"))


def _reader(name):
    return manifest.load_module(manifest.layer_metric_file(name)).read


def _rec(window, compacts):
    """A recorder whose window ran `compacts`: (input bytes, output bytes,
    ok) a tablet compaction."""
    rec = Recorder(traced=False)
    rec.window = window
    for i, (inp, out, ok) in enumerate(compacts):
        rec.spans.append({"kind": "compact", "ok": ok, "t0": float(i),
                          "t1": i + 0.5, "input_bytes": inp,
                          "output_bytes": out, "ssts_after": 1})
    return rec


def test_compact_mb_per_s_is_input_bytes_over_the_window():
    rec = _rec((10.0, 62.5), [(100_000_000, 99_000_000, True),
                              (120_000_000, 119_000_000, True),
                              (110_000_000, 109_000_000, True),
                              (7_000_000, 0, False)])   # failed: no bytes
    got = _driver().end_to_end(None, {}, rec)
    assert got == {"compact_mb_per_s": pytest.approx(330.0 / 52.5,
                                                     rel=1e-12)}


def test_compact_mb_per_s_is_absent_without_a_compaction():
    assert _driver().end_to_end(None, {}, _rec((0.0, 51.0), [])) == {}
    failed = _rec((0.0, 51.0), [(100_000_000, 0, False)])
    assert _driver().end_to_end(None, {}, failed) == {}


def _trace(compacts, programs, busy=((0, 2e9),)):
    """A trace reduction by hand: `compacts` traced compact spans of one
    second each, the device busy in `busy` (ns), `programs` by name."""
    spans = [("bench:compact", i * 1e9, (i + 1) * 1e9)
             for i in range(compacts)]
    spans += [("bench:insert", 10e9, 11e9), ("bench:flush", 11e9, 12e9)]
    return {"spans": spans, "busy": [[list(b) for b in busy]],
            "busy_by_program": dict(programs), "busy_s": 2.0,
            "window_s": 20.0}


def test_merge_device_ms_sums_the_merge_programs_a_compaction():
    trace = _trace(4, {"jit_chunk_merge_kernel": 0.8,
                       "jit_merge_gc_split_kernel": 0.2,
                       "jit_scan_linked": 5.0,
                       "jit_convert_element_type": 0.01})
    got = _reader("merge_device_ms")(types.SimpleNamespace(trace=trace))
    assert got == pytest.approx((0.8 + 0.2) / 4 * 1e3, rel=1e-12)


@pytest.mark.parametrize("trace", [
    None,
    _trace(0, {"jit_chunk_merge_kernel": 0.8}),
    _trace(4, {"jit_scan_linked": 5.0}),
], ids=["no_trace", "no_compaction", "no_merge_program"])
def test_merge_device_ms_is_none_with_nothing_to_read(trace):
    assert _reader("merge_device_ms")(types.SimpleNamespace(
        trace=trace)) is None


def test_merge_device_ms_on_the_recorded_v5e_trace():
    """The recorded v5e trace of two compactions: the merge programs are
    most of the busy time inside them, and the reader divides by the two."""
    from benchmark import trace_reduce as tr
    from tests.benchmark.test_trace_reduce import recorded
    planes, want = recorded("tpu_refresh_compact")
    red = tr.reduce(planes)
    got = _reader("merge_device_ms")(types.SimpleNamespace(trace=red))
    merge = sum(s for name, s in red["busy_by_program"].items()
                if "chunk_merge_kernel" in name or "merge_gc" in name)
    assert want["spans"]["bench:compact"][0] == 2
    assert got == pytest.approx(merge / 2 * 1e3, rel=1e-12)
    # a program's span on the modules line runs a little past its last
    # operation, so the sum may pass the busy union by a hair
    assert got == pytest.approx(red["busy_s"] * 1e3 / 2, rel=0.01)


def test_no_benchmark_file_names_a_second_manifest():
    bench = os.path.join(manifest.ROOT, "benchmark")
    assert not os.path.exists(os.path.join(bench, "deferred.json"))
    assert not hasattr(manifest, "with_deferred")
    for dirpath, _, files in os.walk(bench):
        for name in files:
            if "__pycache__" in dirpath:
                continue
            with open(os.path.join(dirpath, name), "rb") as f:
                assert b"deferred.json" not in f.read(), name


def test_run_finds_the_refresh_cell_in_the_manifest(monkeypatch):
    """`run.py --workload refresh_compact --rehearse` reads the cell, its
    configuration, mix, driver and readers from `BENCHMARK.json` before it
    looks for a device (stopped there)."""
    seen = {}

    class Found(Exception):
        pass

    def device_info(chips, rehearse):
        seen.update(chips=chips, rehearse=rehearse)
        raise Found

    monkeypatch.setattr(run, "device_info", device_info)
    with pytest.raises(Found):
        run.run_cell(["--workload", "refresh_compact", "--rehearse"])
    assert seen == {"chips": 1, "rehearse": True}
    m = manifest.load()
    cell = manifest.Cell(m, "refresh_compact")
    assert cell.entry["config"] == "tpch_sf1_refresh"
    assert [x["name"] for x in cell.end_to_end] == ["setup_s",
                                                    "compact_mb_per_s"]
    assert [x["name"] for x in cell.per_layer] == [
        "insert_ack_ms", "compact_tablet_ms", "device_idle_pct.compact",
        "merge_device_ms"]
    assert all(x["moves"] == "compact_mb_per_s" for x in cell.per_layer)
