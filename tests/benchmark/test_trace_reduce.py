"""The reduction from trace to numbers, on a few events worked by hand and
on the recorded traces under `data/`: busy union, idle share, attribution
of device time and of idle gaps to the benchmark's spans, each a chip over
the chips the cell was given, and the time of every operation and program
by name.  Below those, where the harness counts a cell's chips outside the
trace: the devices a run is given, the peak of each, whether the cached
batches sit on all of them, and the least time of a roofline spread over
them.

PYTEST_DONT_REWRITE — this module is imported as it is written.  Where no
bytecode is kept (`PYTHONDONTWRITEBYTECODE`, as in the sandbox the tier-1
run is made in) pytest rewrites every test module's asserts anew in every
process, some 35,000 allocations for this one, and how much is allocated
while `tests/` is collected decides where the cyclic collector's next full
pass falls: with this module rewritten it fell inside the cached build that
`tests/test_analysis.py::test_facts_cache_hit_speedup` times, in every run
(PERF.md, Open questions).  A failing assert here shows its line and not
its operands; take the marker out to see them."""
import json
import os
import shutil
import types

import jax
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec

from benchmark import bytes_model, cluster, manifest, peaks, run
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
    assert red["devices_busy"] == red["chips"] == 1
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


def test_busy_time_is_averaged_over_the_cells_chips_idle_ones_too():
    planes = by_hand()
    planes.append(plane("/device:TPU:1", XLA_Ops=[("sort.1", 0, 50 * MS)]))
    planes.append(plane("/device:TPU:2", XLA_Ops=[]))
    red = tr.reduce(planes, 2)
    assert red["devices_busy"] == 2
    assert red["busy_s"] == pytest.approx((0.026 + 0.050) / 2)
    # the third chip has a plane that ran nothing, the fourth has none:
    # both are idle for the whole window
    red = tr.reduce(planes, 4)
    assert red["devices_busy"] == 2 and len(red["busy"]) == 4
    assert red["busy_s"] == pytest.approx((0.026 + 0.050) / 4)
    assert tr.idle_pct(red) == pytest.approx(100 - 76 / 4)
    assert sum(dict(red["idle_gaps"]).values()) == pytest.approx(
        0.100 - red["busy_s"])
    with pytest.raises(ValueError):     # a chip the cell did not ask for
        tr.reduce(planes, 1)


def test_a_trace_with_no_device_plane_reads_nothing():
    red = tr.reduce(by_hand()[:1])
    assert red["devices_busy"] == 0 and red["busy_s"] == 0
    assert red["busy"] == [[]] and tr.idle_pct(red) is None
    assert red["device_ops"] == [] and tr.busy_in_spans(red, "bench:") == 0


def test_loader_reads_a_recorded_xplane():
    """A trace recorded on the CPU backend with the benchmark's own span
    names: the loader finds the spans on the host plane."""
    planes = tr.load_xplane(os.path.join(DATA, "cpu_spans.xplane.pb"))
    spans = tr.bench_spans(planes)
    assert [s[0] for s in spans] == ["bench:trace_window", "bench:stmt.q6",
                                     "bench:stmt.q1", "bench:stmt.q6"]
    red = tr.reduce(planes)
    assert red["devices_busy"] == 0 and red["window_s"] > 0
    assert tr.span_count(red, "bench:stmt.") == 3
    assert "bench:trace_window" in tr.summary(planes)


RECORDED = ["tpu_scan_power", "tpu_refresh_compact", "tpu_scan_power_pr30"]


def recorded(name):
    """A cut of a trace taken on the v5e in PR 26, or of `scan_power` as it
    has run since PR 28 in PR 30 (the events of a short stretch, as plain
    data), with the numbers worked out for it then."""
    with open(os.path.join(DATA, name + ".json")) as f:
        rec = json.load(f)
    return [{"name": p["name"], "lines": [
        {"name": l["name"], "events": [tuple(e) for e in l["events"]]}
        for l in p["lines"]]} for p in rec["planes"]], rec["expect"]


@pytest.mark.parametrize("name", RECORDED)
def test_recorded_chip_trace_reduces_to_the_recorded_numbers(name):
    planes, want = recorded(name)
    red = tr.reduce(planes)
    assert red["devices_busy"] == 1
    assert red["window_s"] == pytest.approx(want["window_s"], rel=1e-9)
    assert red["busy_s"] == pytest.approx(want["busy_s"], rel=1e-9)
    assert 0 < red["busy_s"] < red["window_s"]
    for prefix, (count, busy) in want["spans"].items():
        assert tr.span_count(red, prefix) == count
        assert tr.busy_in_spans(red, prefix) == pytest.approx(busy, rel=1e-9)
        assert tr.busy_in_spans(red, prefix) <= red["busy_s"] * (1 + 1e-9)
    assert red["device_ops"][0][0] == want["top_op"]


@pytest.mark.parametrize("name", RECORDED + ["by_hand"])
def test_one_chip_reads_what_it_read_before_chips_were_counted(name):
    """With `chips=1` every number is what `reduce` of PR 28's tree gave,
    to the digit (`==` on floats, no tolerance): that tree's reduction of
    the recorded v5e traces is kept in `data/reduce_as_of_pr28.json`."""
    planes = by_hand() if name == "by_hand" else recorded(name)[0]
    with open(os.path.join(DATA, "reduce_as_of_pr28.json")) as f:
        was = json.load(f)["reduced"][name]
    red = tr.reduce(planes, 1)
    assert red["devices_busy"] == was["devices"] == 1
    for field in ("window_s", "busy_s", "device_ops", "idle_gaps"):
        assert red[field] == was[field], field
    assert [list(s) for s in red["spans"]] == was["spans"]
    assert [len(b) for b in red["busy"]] == was["busy_intervals"]
    assert [tr.total(b) for b in red["busy"]] == was["busy_ns"]
    assert tr.idle_pct(red) == was["idle_pct"]
    for prefix, (count, busy) in was["in_spans"].items():
        assert tr.span_count(red, prefix) == count
        assert tr.busy_in_spans(red, prefix) == busy
    assert [v for _, v in red["device_ops"]] == sorted(
        red["ops_by_name"].values(), reverse=True)[:10]


@pytest.mark.parametrize("name", RECORDED)
def test_four_chips_of_which_one_worked_read_a_quarter_busy(name):
    """The recorded one-chip trace as a four-chip cell's: three chips
    without events."""
    planes, _ = recorded(name)
    one, four = tr.reduce(planes, 1), tr.reduce(planes, 4)
    assert four["chips"] == 4 and four["devices_busy"] == 1
    assert four["window_s"] == one["window_s"]
    assert four["busy_s"] == pytest.approx(one["busy_s"] / 4, rel=1e-12)
    assert four["busy"][0] == one["busy"][0] and four["busy"][1:] == [[]] * 3
    assert 100 - tr.idle_pct(four) == pytest.approx(
        (100 - tr.idle_pct(one)) / 4, rel=1e-9)
    for prefix in ("bench:stmt.", "bench:compact"):
        assert tr.busy_in_spans(four, prefix) == pytest.approx(
            tr.busy_in_spans(one, prefix) / 4, rel=1e-12)
    for key in ("ops_by_name", "busy_by_program"):
        assert four[key].keys() == one[key].keys()
        assert sum(four[key].values()) == pytest.approx(
            sum(one[key].values()) / 4, rel=1e-9)
    # idle seconds a chip: the working chip's, and three whole windows
    assert sum(dict(four["idle_gaps"]).values()) == pytest.approx(
        four["window_s"] - four["busy_s"], rel=1e-9)


def mesh_trace(chips=4):
    """A 100 ms window with one statement (10..60 ms); every chip runs the
    scan program 12..40 ms (a fusion, then the `all-reduce` 32..40 ms that
    combines the partials), chip 0 also a scalar's conversion at 5 ms."""
    planes = [plane("/host:CPU", python=[
        ("bench:trace_window", 0 * MS, 100 * MS),
        ("bench:stmt.q1", 10 * MS, 50 * MS)])]
    for c in range(chips):
        ops = [("%fusion.7 = f64[4]{0} fusion(...)", 12 * MS, 20 * MS),
               ("%all-reduce.3 = s64[4]{0} all-reduce(...)", 32 * MS, 8 * MS)]
        mods = [("jit_scan_linked_resolveddictgroup(1234567890)", 12 * MS,
                 28 * MS)]
        if c == 0:
            ops.append(("%convert.1 = f64[] convert(...)", 5 * MS, 1 * MS))
            mods.append(("jit_convert_element_type(42)", 5 * MS, 1 * MS))
        planes.append(plane(f"/device:TPU:{c}", XLA_Ops=ops,
                            XLA_Modules=mods))
    return planes


def named(totals, part):
    """What a reader file does with `ops_by_name` or `busy_by_program`: the
    seconds a chip of every entry whose name holds `part`."""
    return sum(v for k, v in totals.items() if part in k)


def test_a_collective_and_a_program_are_read_by_name():
    red = tr.reduce(mesh_trace(), 4)
    assert red["devices_busy"] == 4
    assert red["busy_s"] == pytest.approx((4 * 0.028 + 0.001) / 4)
    # seconds a chip: every chip spent 8 ms in the all-reduce
    assert named(red["ops_by_name"], "all-reduce") == pytest.approx(0.008)
    assert named(red["ops_by_name"], "fusion") == pytest.approx(0.020)
    assert named(red["ops_by_name"], "convert") == pytest.approx(0.00025)
    assert named(red["ops_by_name"], "sort") == 0
    assert red["busy_by_program"] == {
        "jit_scan_linked_resolveddictgroup": pytest.approx(0.028),
        "jit_convert_element_type": pytest.approx(0.00025)}
    assert named(red["busy_by_program"], "jit_scan_linked") == \
        pytest.approx(0.028)
    # the result line's top ten are the same totals, names cut
    assert dict(red["device_ops"]) == pytest.approx(red["ops_by_name"])
    assert tr.busy_in_spans(red, "bench:stmt.") == pytest.approx(0.028)


def test_programs_of_a_recorded_chip_trace_by_name():
    """The v5e trace of PR 26 holds the `XLA Modules` line; its scan
    program was still called `jit_fn` (`jit_scan_linked…` since PR 27)."""
    planes, want = recorded("tpu_scan_power")
    programs = tr.reduce(planes)["busy_by_program"]
    assert {"jit_fn", "jit_convert_element_type",
            "jit_broadcast_in_dim"} <= set(programs)
    assert not any("(" in name for name in programs)    # no fingerprints
    assert max(programs, key=programs.get) == "jit_fn"
    assert sum(programs.values()) == pytest.approx(want["busy_s"], rel=0.01)
    merge = tr.reduce(recorded("tpu_refresh_compact")[0])["busy_by_program"]
    assert max(merge, key=merge.get) == "jit_chunk_merge_kernel"
    # since PR 28 (the trace of PR 30): one Q6 and one Q1, four scans each
    planes, want = recorded("tpu_scan_power_pr30")
    red = tr.reduce(planes)
    assert red["busy_by_program"] == pytest.approx(want["programs"])
    assert set(red["busy_by_program"]) == {
        "jit_scan_linked", "jit_scan_linked_resolveddictgroup",
        "jit_convert_element_type"}
    assert named(red["busy_by_program"], "jit_scan_linked") == \
        pytest.approx(0.99 * red["busy_s"], rel=0.02)
    assert named(red["ops_by_name"], "sort") == 0     # no sort since PR 28


# -- a cell's chips outside the trace -----------------------------------------
@pytest.fixture
def four():
    """Four of the CPU's virtual devices (`tests/conftest.py` gives 8),
    asked for when the test runs: nothing touches JAX at import."""
    if len(jax.devices()) < 4:
        pytest.skip("needs 4 virtual devices")
    return jax.devices()[:4]


def batch_on(*devices):
    """A stub with a cached batch's lanes: on one device, or sharded over
    several as a `ShardedBatch`'s are."""
    if len(devices) == 1:
        def put(x):
            return jax.device_put(x, devices[0])
    else:
        sharding = NamedSharding(
            jax.sharding.Mesh(np.array(devices), ("tablets",)),
            PartitionSpec("tablets"))

        def put(x):
            return jax.device_put(x, sharding)
    n = 8 * len(devices)
    return types.SimpleNamespace(
        valid=put(np.ones(n, bool)), ht=put(np.zeros(n, np.uint64)),
        next_ht=None, tombstone=put(np.zeros(n, bool)),
        cols={1: put(np.zeros(n)), 2: put(np.zeros(n, np.int32))},
        nulls={1: put(np.zeros(n, bool))}, padded_rows=n)


def test_batches_on_one_and_four_devices(four):
    d = four
    assert cluster.batches_on([batch_on(d[0]), batch_on(d[0])], d[:1])
    assert not cluster.batches_on([], d[:1])
    assert not cluster.batches_on([batch_on(d[1])], d[:1])
    # four chips: a table that sits on one of them is off its devices
    assert not cluster.batches_on([batch_on(d[0])] * 4, d)
    # a tablet a chip covers them, and so does one batch sharded over all
    assert cluster.batches_on([batch_on(x) for x in d], d)
    assert cluster.batches_on([batch_on(*d)], d)
    assert cluster.batch_devices(batch_on(*d)) == set(d)
    # a shard on a chip the cell was not given
    assert not cluster.batches_on([batch_on(*jax.devices()[:8])], d)
    # one lane left behind on another chip
    stray = batch_on(d[0])
    stray.nulls[1] = jax.device_put(np.zeros(8, bool), d[1])
    assert not cluster.batches_on([stray], d[:1])


def test_a_sharded_batch_of_the_program_is_seen_on_all_its_devices(four):
    """What `parallel/distributed_scan.py ShardedBatch` holds, so that the
    evidence sees one when a later PR caches it."""
    from yugabyte_db_tpu.parallel import tablet_mesh
    from yugabyte_db_tpu.parallel.distributed_scan import build_sharded_batch
    from yugabyte_db_tpu.storage.columnar import ColumnarBlock
    d = four
    rng = np.random.default_rng(1)
    blocks = [[ColumnarBlock.from_arrays(
        schema_version=1,
        key_hash=rng.integers(0, 2**63, 100).astype(np.uint64),
        ht=np.full(100, 10, np.uint64),
        fixed={1: (rng.uniform(0, 50, 100), np.zeros(100, bool))},
        unique_keys=True)] for _ in d]
    batch = build_sharded_batch(tablet_mesh(4, devices=d), blocks, [1])
    assert cluster.batch_devices(batch) == set(d)
    assert cluster.batches_on([batch], d)
    assert not cluster.batches_on([batch], d[:1])


def test_evidence_of_the_cluster_reads_the_cells_devices(monkeypatch):
    from yugabyte_db_tpu.tablet import tablet
    d = jax.devices()
    monkeypatch.setattr(tablet._DEVICE_CACHE, "_map",
                        {"a": (batch_on(d[0]), 0)})
    for devices, on in ((d[:1], True), (d[:4], len(d) < 4)):
        c = cluster.Cluster({}, devices)
        try:
            ev = c.device_evidence()
        finally:
            shutil.rmtree(c.root, ignore_errors=True)
        assert c.devices == list(devices)
        assert ev["cached_batches"] == 1 and ev["on_device"] is on
        assert ev["batches_on"] == [str(d[0])]


def test_device_info_hands_the_cell_its_chips(four):
    devices, info = run.device_info(4, rehearse=True)
    assert devices == four
    assert info["chips"] == 4 and info["count"] == len(jax.devices())
    assert len(run.device_info(1, rehearse=True)[0]) == 1
    with pytest.raises(run.NoChip):      # the CPU is no chip
        run.device_info(4, rehearse=False)


def test_memory_peak_is_the_fullest_chips():
    def dev(stats):
        return types.SimpleNamespace(memory_stats=lambda: stats)
    got = run.memory_peaks([dev({"peak_bytes_in_use": 5}), dev(None),
                            dev({"peak_bytes_in_use": 9}), dev({})])
    assert got == {"memory_peak_bytes": 9,
                   "memory_peak_bytes_by_device": [5, None, 9, None]}
    assert run.memory_peaks([dev(None)])["memory_peak_bytes"] is None


def test_least_seconds_over_chips():
    peak = peaks.lookup("TPU v5 lite")
    n = bytes_model.scan_bytes(6_007_215, "q1")
    assert bytes_model.least_seconds(n, peak, 1) == \
        bytes_model.least_seconds(n, peak)
    assert bytes_model.least_seconds(n, peak, 4) == \
        bytes_model.least_seconds(n, peak, 1) / 4


@pytest.mark.parametrize("metric, trace, prefix", [
    ("scan_roofline", "tpu_scan_power", "stmt")])
def test_roofline_readers_spread_the_least_bytes_over_the_cells_chips(
        metric, trace, prefix):
    """On the recorded v5e trace: a four-chip cell that moved the same
    bytes in the same busy time a chip reads a quarter of the one-chip
    share; one in which a single chip did all the work reads that chip's
    own share."""
    planes, _ = recorded(trace)
    read = manifest.load_module(manifest.layer_metric_file(metric)).read

    def ctx(trace_chips, cell_chips):
        return types.SimpleNamespace(
            trace=tr.reduce(planes, trace_chips),
            cell=types.SimpleNamespace(chips=cell_chips),
            peak=peaks.lookup("TPU v5 lite"),
            data=types.SimpleNamespace(table_rows=6_007_215))
    one = read(ctx(1, 1))
    assert 0 < one < 100
    assert read(ctx(1, 4)) == pytest.approx(one / 4, rel=1e-12)
    assert read(ctx(4, 4)) == pytest.approx(one, rel=1e-12)
    assert read(types.SimpleNamespace(trace=None)) is None
