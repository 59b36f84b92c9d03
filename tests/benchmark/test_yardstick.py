"""The yardstick's arithmetic: the seeded dbgen-shaped generator, the plain
reference and its float32 control, the byte counts behind both rooflines, the table
of peaks, and the bookkeeping that decides `correct`."""
import glob
import os
import types

import numpy as np
import pytest

from benchmark import bytes_model, manifest, peaks, tpch
from benchmark.record import Checks, Recorder

LIMITS = manifest.load_json(
    manifest.ROOT + "/benchmark/configs/tpch_sf1_scan.json")["limits"]


@pytest.mark.parametrize("seed", [0, 7, 2**31 + 12345, [9, 3]],
                         ids=["0", "7", "past_int32", "seed_and_set"])
def test_generator_is_a_function_of_the_seed(seed):
    a = tpch.generate_lineitem(1250, 5000, seed, first_order=100)
    b = tpch.generate_lineitem(1250, 5000, seed, first_order=100)
    assert all((a[c] == b[c]).all() for c in tpch.COLS)
    assert all(len(a[c]) == 5000 for c in tpch.COLS)
    other = tpch.generate_lineitem(1250, 5000, 8 if seed == 7 else 7)
    assert not (a["l_extendedprice"] == other["l_extendedprice"]).all()
    assert a["l_extendedprice"].dtype == np.float64
    assert a["l_shipdate"].dtype == np.int32
    assert a["l_orderkey"].dtype == np.int64


def test_generator_draws_lineitem_in_dbgens_shapes():
    """Clause 4.2.3, column by column, at a size a test can hold."""
    d = tpch.generate_lineitem(10_000, 40_008, 5, sf=0.01)
    key = d["l_orderkey"] * 8 + d["l_linenumber"]
    assert len(np.unique(key)) == 40_008          # the primary key
    assert (d["l_orderkey"] & 0x18 == 0).all()    # 8 keys of every 32
    lines = np.bincount(np.unique(d["l_orderkey"], return_inverse=True)[1])
    assert lines.min() == 1 and lines.max() == 7
    assert d["l_linenumber"].min() == 1 and d["l_linenumber"].max() == 7
    assert 1 <= d["l_partkey"].min() and d["l_partkey"].max() <= 2000
    assert 1 <= d["l_suppkey"].min() and d["l_suppkey"].max() <= 100
    assert set(np.unique(d["l_quantity"])) == set(range(1, 51))
    retail = (90000 + (d["l_partkey"] // 10) % 20001
              + 100 * (d["l_partkey"] % 1000)) / 100
    assert np.allclose(d["l_extendedprice"], d["l_quantity"] * retail,
                       rtol=0, atol=1e-6)
    cents = d["l_extendedprice"] * 100
    assert np.abs(cents - np.rint(cents)).max() < 1e-6   # two decimals
    assert set(np.rint(d["l_discount"] * 100)) == set(range(0, 11))
    assert set(np.rint(d["l_tax"] * 100)) == set(range(0, 9))
    ship, receipt = d["l_shipdate"], d["l_receiptdate"]
    assert tpch.STARTDATE + 1 <= ship.min() and ship.max() <= \
        tpch.ENDDATE - 151 + 121
    assert ((receipt - ship >= 1) & (receipt - ship <= 30)).all()
    assert (d["l_commitdate"] >= tpch.STARTDATE + 30).all()
    late = receipt > tpch.CURRENTDATE
    assert (d["l_returnflag"][late] == b"N").all()
    assert set(np.unique(d["l_returnflag"][~late])) == {b"R", b"A"}
    assert ((d["l_linestatus"] == b"O") == (ship > tpch.CURRENTDATE)).all()
    assert set(np.unique(tpch.groups(d))) == {b"AF", b"NF", b"NO", b"RF"}
    assert len(np.unique(d["l_shipinstruct"])) == 4
    assert len(np.unique(d["l_shipmode"])) == 7
    size = np.char.str_len(d["l_comment"])
    assert size.min() == 10 and size.max() == 43
    assert len(np.unique(d["l_comment"])) > 39_000   # nearly all distinct
    assert tpch.row(d, 0)[8:10] == (d["l_returnflag"][0].decode(),
                                    d["l_linestatus"][0].decode())


def test_refresh_keys_never_meet_loaded_keys():
    base = tpch.generate_lineitem(4000, 16_000, 1)
    sets = [tpch.generate_lineitem(4, 16, [1, k], first_order=4 * (k - 1),
                                   refresh=True) for k in (1, 2, 3)]
    keys = np.concatenate([p["l_orderkey"] for p in [base] + sets])
    assert len(np.unique(keys)) == 4000 + 12
    assert all((s["l_orderkey"] & 8 == 8).all() for s in sets)


@pytest.mark.parametrize("orders,rows", [(10, 9), (10, 71)])
def test_generator_refuses_rows_that_do_not_fit(orders, rows):
    with pytest.raises(ValueError):
        tpch.generate_lineitem(orders, rows, 0)


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_reference_agrees_with_a_plain_loop(seed):
    """The vectorised reference against sums taken row by row in
    Python, and Q6 and Q1's first two sums against the program's own
    `numpy_reference` over the same columns."""
    from yugabyte_db_tpu.models.tpch import (TPCH_Q1, TPCH_Q6,
                                             numpy_reference)
    data = tpch.generate_lineitem(10_000, 40_000, seed, sf=0.01)
    ref = tpch.reference(data)
    codes = dict(data, l_returnflag=np.searchsorted(
        [b"A", b"N", b"R"], data["l_returnflag"]).astype(np.int32),
        l_linestatus=(data["l_linestatus"] == b"O").astype(np.int32))
    assert ref["q6"] == pytest.approx(numpy_reference(TPCH_Q6, codes),
                                      rel=1e-15)
    theirs = numpy_reference(TPCH_Q1, codes)
    assert sum(1 for v in theirs.values() if v[2]) == len(ref["q1"]) == 4
    for g, (qty, price, count) in theirs.items():
        if not count:
            continue
        mine = ref["q1"]["ANR"[g % 3] + "FO"[g // 3]]
        assert mine["count_order"] == count and mine["sum_qty"] == qty
        assert mine["sum_base_price"] == pytest.approx(price, rel=1e-15)
    m = (data["l_shipdate"] <= 10471) & (tpch.groups(data) == b"NO")
    charge = sum(p * (1 - d) * (1 + t) for p, d, t in zip(
        data["l_extendedprice"][m], data["l_discount"][m],
        data["l_tax"][m]))
    assert ref["q1"]["NO"]["sum_charge"] == pytest.approx(charge, rel=1e-12)


@pytest.mark.parametrize("query", ["q6", "q1"])
def test_reference_in_the_programs_place_is_correct(query):
    data = tpch.generate_lineitem(7500, 30_000, 11)
    ref = tpch.reference(data)
    checks = Checks({k: v for k, v in LIMITS.items()
                     if k.startswith((query, "sum_usd"))})
    checks.note_all(tpch.compare(query, tpch.as_rows(query, ref), ref))
    assert checks.correct(), checks.table()


@pytest.mark.parametrize("seed", [21, 22, 23])
def test_float32_control_comes_out_not_correct(seed):
    """The control at a size a test can hold: the reference in float32,
    put in the program's place, fails the limits the configuration
    states (its readings at the cell's own size are in PERF.md)."""
    from benchmark.control import control_readings
    sizes = dict(manifest.load_json(
        manifest.ROOT + "/benchmark/configs/tpch_sf1_scan.json")["sizes"],
        rows=600_000, orders=150_000, refresh_orders=150)
    got = control_readings(sizes, 1, seed, LIMITS)
    assert got["correct"] is False
    value, limit = got["compared"]["sum_usd"]
    assert limit == 100 and value > 3 * limit
    assert got["compared"]["q1_count_diff"] == [0, 0]
    assert got["compared"]["q1_shape"] == [0, 0]


@pytest.mark.parametrize("rows,gap", [
    ([], "q6_shape"), ([{"revenue": None}], "q6_shape"),
    ([{"revenue": 1.0}, {"revenue": 1.0}], "q6_shape")])
def test_a_misshapen_answer_is_not_compared_in_part(rows, gap):
    ref = tpch.reference(tpch.generate_lineitem(1250, 5000, 1))
    assert tpch.compare("q6", rows, ref) == {gap: 1}
    q1 = tpch.as_rows("q1", ref)
    assert tpch.compare("q1", q1[:-1], ref) == {"q1_shape": 1}
    assert tpch.compare("q1", q1 + q1[:1], ref) == {"q1_shape": 1}


def test_scan_bytes_by_hand():
    # Q6 reads three doubles and a date (28 bytes), plus hybrid time 8,
    # next_ht 8, valid 1: 45 bytes a row; Q1 reads four doubles, a
    # date and two one-character flags (38): 55 bytes a row
    assert bytes_model.scan_row_bytes("q6") == 45
    assert bytes_model.scan_row_bytes("q1") == 55
    assert bytes_model.scan_bytes(6_007_215, "q6") == 270_324_675
    assert bytes_model.scan_bytes(6_007_215, "q1") == 330_396_825
    peak = peaks.lookup("TPU v5 lite")
    assert bytes_model.least_seconds(330_396_825, peak) == \
        pytest.approx(0.000403, rel=2e-3)


def test_merge_bytes_by_hand():
    assert bytes_model.merge_bytes(90_000_000, 89_000_000) == 179_000_000
    assert bytes_model.least_seconds(819e9, {"hbm_bytes_per_s": 819e9}) == 1


def test_peaks_known_and_unknown_device():
    v5e = peaks.lookup("TPU v5 lite")
    assert v5e["hbm_bytes_per_s"] == 819e9 and v5e["source"]
    assert v5e["bf16_flops_per_s"] == 197e12 and v5e["hbm_bytes"] == 16e9
    for kind in ("cpu", "TPU v4", ""):
        with pytest.raises(KeyError):
            peaks.lookup(kind)


def test_checks_hold_each_number_to_its_own_limit():
    c = Checks({"a": 0, "b": 1e-9})
    c.note("a", 0), c.note("b", 5e-10), c.note("b", 2e-10)
    assert c.correct() and c.table()["b"]["value"] == 5e-10
    c.note("b", float("nan"))
    assert not c.correct() and c.table()["b"]["value"] == 1e300
    unnamed = Checks({"a": 0})
    unnamed.note("a", 0), unnamed.note("c", 0)
    assert not unnamed.correct()          # compared, but no limit stated
    silent = Checks({"a": 0, "b": 0})
    silent.note("a", 0)
    assert not silent.correct()           # a limit nothing was held to
    assert not Checks({}).correct()



READERS = sorted(glob.glob(os.path.join(
    manifest.ROOT, "benchmark", "layer_metrics", "*.py")))


@pytest.mark.parametrize("path", READERS,
                         ids=[os.path.basename(p)[:-3] for p in READERS])
def test_a_reader_that_finds_nothing_to_read_returns_nothing(path):
    """Every reader under `layer_metrics/`, one a metric, on a run that
    left it nothing: no trace, then a trace in which no chip of the cell's
    four ran anything, a recorder with no span and no counter.  It says
    `None`, and the harness leaves the metric out of the line: never a 0
    for a time, a share of a roofline or of the window."""
    read = manifest.load_module(path).read
    nothing = {"window_s": 5.0, "chips": 4, "devices_busy": 0, "busy_s": 0.0,
               "spans": [], "busy": [[], [], [], []], "device_ops": [],
               "idle_gaps": [], "ops_by_name": {}, "busy_by_program": {}}
    for trace in (None, nothing):
        ctx = types.SimpleNamespace(
            trace=trace, rec=Recorder(traced=False),
            cell=types.SimpleNamespace(chips=4),
            peak=peaks.lookup("TPU v5 lite"),
            data=types.SimpleNamespace(table_rows=6_007_215))
        assert read(ctx) is None
