"""`BENCHMARK.json` against the contract's rules a file can break, and the
promise that a configuration, a traffic mix and a per-layer metric are each
added as new files plus one entry, with no edit to a file that is there.

What every cell and every configuration is held to is in `check_chips`,
`check_cell` and `check_config`, which take a manifest and a root: the
real manifest goes through them, and so does the copy that
`test_a_later_pr_adds_files_and_entries_only` adds a four-chip cell to.
Nothing here holds a cell to another cell's sizes, flags or chips.
Collection keeps only names: a test reads the manifest when it runs."""
import importlib.util
import json
import os
import shutil

import pytest

from benchmark import manifest


def real():
    return manifest.load()


def full():
    """With the cells kept for a later PR (`benchmark/deferred.json`)."""
    return manifest.with_deferred(manifest.load())


def names(*keys):
    return [x["name"] for key in keys for x in full()[key]]


def check_chips(m):
    """A cell has 1 chip or 4; of the cells at most half, rounded down,
    may ask for 4, and one always may."""
    chips = [w["chips"] for w in m["workloads"]]
    assert set(chips) <= {1, 4}
    assert chips.count(4) <= max(1, len(chips) // 2)


def check_cell(m, name, root=manifest.ROOT):
    """What the contract asks of any cell, read from the cell's own files."""
    c = manifest.Cell(m, name, root)
    assert c.config["chips"] == c.chips and c.chips in (1, 4)
    assert callable(c.driver.warm) and callable(c.driver.window)
    assert callable(c.driver.verify) and callable(c.driver.end_to_end)
    assert callable(c.driver.attempted_failed) and callable(c.loader.load)
    assert float(c.traffic["trace_seconds"]) > 0
    assert {x["name"] for x in c.end_to_end} >= {"setup_s"}
    assert len(c.end_to_end) >= 2 and c.per_layer
    for path in c.readers.values():
        assert callable(manifest.load_module(path).read)
    return c


def check_config(m, config, root=manifest.ROOT):
    """What the contract asks of any configuration's file: it states its
    source, deployment, cuts, assumptions and guarantees, its sizes and
    the rows of a CPU rehearsal, the program flags it sets (each one the
    program's registry knows and lets a running process set), and a limit
    for every number compared."""
    from yugabyte_db_tpu.utils import flags
    body = manifest.load_json(os.path.join(root, config["file"]))
    assert body["name"] == config["name"]
    assert body["source"] == config["source"] and len(body["source"]) <= 200
    assert sorted(body["reduced"]) == sorted(config["reduced"])
    assert body["deployment"] and body["assumed"]
    assert body["guarantees"]["writes"]
    assert body["chips"] in (1, 4)
    assert body["sizes"] and all(
        isinstance(v, int) and not isinstance(v, bool) and v > 0
        for v in body["sizes"].values())
    assert isinstance(body["rehearsal"]["rows"], int) and \
        0 < body["rehearsal"]["rows"] <= max(body["sizes"].values())
    assert isinstance(body["flags"], dict)
    known = dict(flags.REGISTRY.items())
    for name in body["flags"]:
        assert name in known and known[name].runtime, name
    # every number the driver compares has a limit, stated by the config
    assert body["limits"] and all(
        isinstance(v, (int, float)) and not isinstance(v, bool)
        for v in body["limits"].values())
    assert os.path.isfile(os.path.join(
        root, "benchmark", "loaders", body["loader"] + ".py"))
    return body


def test_manifest_is_valid():
    m = real()
    manifest.validate(m)
    manifest.validate(full())
    assert m["command"] == ["python3", "benchmark/run.py"]
    assert len(json.dumps(m)) < 64 * 1024


@pytest.mark.parametrize("m", [real, full], ids=["manifest", "with_deferred"])
def test_four_chip_cells_are_at_most_half(m):
    check_chips(m())


@pytest.mark.parametrize("chips, ok", [
    ((4,), True), ((1, 4), True), ((4, 4), False), ((1, 1, 4), True),
    ((1, 4, 4), False), ((1, 1, 4, 4), True), ((1, 2), False)])
def test_chips_rule_by_hand(chips, ok):
    m = {"workloads": [{"chips": c} for c in chips]}
    if ok:
        check_chips(m)
    else:
        with pytest.raises(AssertionError):
            check_chips(m)


@pytest.mark.parametrize("metric", names("end_to_end", "per_layer"))
def test_metric_names_units_and_cells(metric):
    ALL = full()
    metric = next(x for x in ALL["end_to_end"] + ALL["per_layer"]
                  if x["name"] == metric)
    assert manifest._NAME.match(metric["name"])
    assert manifest._UNIT.match(metric["unit"])
    cells = {w["name"] for w in ALL["workloads"]}
    assert set(manifest.cells_of(metric, ALL)) <= cells
    if "moves" in metric:        # every cell it lists reports what it moves
        moved = next(e for e in ALL["end_to_end"]
                     if e["name"] == metric["moves"])
        assert set(manifest.cells_of(metric, ALL)) <= \
            set(manifest.cells_of(moved, ALL))
        assert os.path.isfile(manifest.layer_metric_file(metric["name"]))
    if metric["name"].endswith("_roofline"):
        assert metric["unit"] == "%" and metric["source"] == "device_trace"


@pytest.mark.parametrize("cell", names("workloads"))
def test_cell_finds_its_files_by_name(cell):
    check_cell(full(), cell)


@pytest.mark.parametrize("config", names("configs"))
def test_config_file_states_its_cut(config):
    m = full()
    check_config(m, next(c for c in m["configs"] if c["name"] == config))


@pytest.mark.parametrize("config", ["tpch_sf1_scan", "tpch_sf1_refresh"])
def test_tpch_sf1_configurations_are_sf1(config):
    """What is true of the two TPC-H SF1 configurations and of no other:
    the scale factor's own counts, one chip, and the float64 the schema
    declares."""
    entry = next(c for c in full()["configs"] if c["name"] == config)
    body = manifest.load_json(os.path.join(manifest.ROOT, entry["file"]))
    assert body["sizes"]["rows"] == 6_001_215     # SF1, clause 4.2.5
    assert body["sizes"]["orders"] == 1_500_000
    assert body["flags"] == {"device_float_dtype": "float64"}
    assert body["chips"] == 1 and body["loader"] == "tpch_lineitem"


@pytest.mark.parametrize("breakage", [
    lambda m: m["end_to_end"][0].update(unit="rows per s"),
    lambda m: m["workloads"][0].update(name="scan power"),
    lambda m: m["per_layer"][0].update(moves="compact_mb_per_s"),
    lambda m: m["per_layer"][-1].update(moves="scan_rows_per_s"),
    lambda m: m["per_layer"][0].update(why="because"),
    lambda m: m["workloads"][0].update(traffic="no_such_mix"),
    lambda m: m["end_to_end"][0].update(bound=0.5),
    lambda m: m["workloads"].append(dict(m["workloads"][0], name="again")),
], ids=["unit_with_space", "name_with_space", "moves_nothing",
        "moves_not_reported",
        "extra_key", "traffic_not_found", "bound_too_wide", "pair_twice"])
def test_validation_refuses(breakage):
    m = full()
    breakage(m)
    with pytest.raises(manifest.ManifestError):
        manifest.validate(m)


DUMMY_LOADER = '''
"""A configuration with a schema of its own: a key-value table."""
TABLE = "kv"


class Kv:
    def __init__(self, rows):
        self.table_rows = rows


async def load(cluster, config, seed, rows=None):
    n = rows or config["sizes"]["rows"]
    await cluster.sql.execute(
        "CREATE TABLE kv (k bigint, v double, PRIMARY KEY (k)) "
        f"WITH tablets = {config['sizes']['tablets']}")
    for k in range(n):
        await cluster.sql.execute(
            f"INSERT INTO kv (k, v) VALUES ({k}, {(seed + k) / 4})")
    return Kv(n), {"insert_s": 0.0}
'''
DUMMY_DRIVER = '''
"""Point reads of the key-value table, on a tserver that was told it owns
the cell's chips."""
from yugabyte_db_tpu.utils import flags


async def warm(cluster, traffic, rec):
    pass


async def window(cluster, traffic, seconds, rec):
    for k in range(traffic["reads"]):
        with rec.span("read", key=k) as s:
            s["rows"] = (await cluster.sql.execute(
                f"SELECT v FROM kv WHERE k = {k}")).rows


def expected(traffic, key):
    return [{"v": (traffic["seed"] + key) / 4}]


async def verify(cluster, traffic, rec, checks):
    checks.note("read_diff", sum(
        1 for s in rec.of("read")
        if s["rows"] != expected(traffic, s["key"])))
    # the harness gave the cell its chips and set both of its flags
    checks.note("chips_missing", traffic["chips"] - len(
        {d.id for d in cluster.devices}))
    checks.note("flags_unset", sum(
        1 for name, value in cluster.flags.items()
        if flags.get(name) != value))


def attempted_failed(rec):
    n = len(rec.of("read", ok_only=False))
    return n, n - len(rec.of("read"))


def end_to_end(cluster, traffic, rec):
    per_s = len(rec.of("read")) / rec.window_s
    return {"dummy_reads_per_s": per_s, "scan_rows_per_s": per_s}
'''


def _files(top):
    return {os.path.join(dp, p): open(os.path.join(dp, p), "rb").read()
            for dp, _, fs in os.walk(top) for p in fs
            if "__pycache__" not in dp}


def test_a_later_pr_adds_files_and_entries_only(tmp_path):
    """What PR 29's cell was in the respects that broke: four chips, sizes
    that are not SF1's, two program flags, a schema and loader of its own,
    a mix with a driver of its own, an end-to-end and a per-layer metric,
    and its name appended to the `workloads` of a metric that is there.
    Added to a copy of the benchmark as new files plus entries; the copy
    then passes every check the real manifest is held to, the copy's own
    `run.py` rehearses the new cell on four of the CPU's virtual devices,
    and no file that was there has changed."""
    root = str(tmp_path)
    bench = os.path.join(root, "benchmark")
    shutil.copytree(os.path.join(manifest.ROOT, "benchmark"), bench,
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = _files(bench)

    def add(path, text):
        with open(os.path.join(bench, path), "w") as f:
            f.write(text)

    cfg = {"name": "dummy_kv_mesh4", "source": "a public source, part 1",
           "deployment": "one tserver that owns four chips",
           "chips": 4, "loader": "dummy_kv",
           "flags": {"device_float_dtype": "float64",
                     "tpu_min_rows_for_pushdown": 2},
           "sizes": {"rows": 8, "tablets": 2}, "rehearsal": {"rows": 8},
           "reduced": {"rows": "8 of the source's many"},
           "assumed": {"tablets": "2"},
           "guarantees": {"writes": "acknowledged after Raft apply"},
           "limits": {"read_diff": 0, "chips_missing": 0, "flags_unset": 0,
                      "compiles_in_window": 0}}
    add("configs/dummy_kv_mesh4.json", json.dumps(cfg))
    add("loaders/dummy_kv.py", DUMMY_LOADER)
    add("drivers/dummy_reads.py", DUMMY_DRIVER)
    add("traffic/dummy_mix.json", json.dumps(
        {"driver": "dummy_reads", "reads": 5, "seed": 3, "chips": 4,
         "trace_seconds": 1}))
    add("layer_metrics/dummy.metric.py",
        "def read(ctx):\n    return float(ctx.data.table_rows)\n")
    m = real()
    m["configs"].append({"name": cfg["name"], "source": cfg["source"],
                         "file": "benchmark/configs/dummy_kv_mesh4.json",
                         "reduced": ["rows"], "why": "a dummy"})
    m["workloads"].append({"name": "dummy_mesh4", "config": cfg["name"],
                           "traffic": "dummy_mix", "chips": 4,
                           "why": "a dummy"})
    # where the manifest already holds its four-chip cells, the PR that adds
    # one more brings one-chip cells beside it: the same deployment on one
    # chip, as often as the rule of the half asks
    mine, chips = ["dummy_mesh4"], [w["chips"] for w in m["workloads"]]
    while chips.count(4) > max(1, (len(chips) + len(mine) - 1) // 2):
        name = f"dummy_kv_one{len(mine)}"
        add(f"configs/{name}.json", json.dumps(dict(cfg, name=name, chips=1)))
        add(f"traffic/{name}.json", json.dumps(
            {"driver": "dummy_reads", "reads": 5, "seed": 3, "chips": 1,
             "trace_seconds": 1}))
        m["configs"].append({"name": name, "source": cfg["source"],
                             "file": f"benchmark/configs/{name}.json",
                             "reduced": ["rows"], "why": "a dummy"})
        m["workloads"].append({"name": name, "config": name, "traffic": name,
                               "chips": 1, "why": "a dummy"})
        mine.append(name)
    m["end_to_end"].append({"name": "dummy_reads_per_s", "unit": "reads/s",
                            "better": "higher", "bound": 0.05,
                            "source": "host_clock", "workloads": mine})
    m["per_layer"].append({"name": "dummy.metric", "unit": "x",
                           "better": "lower", "source": "program_counter",
                           "layer": "scan kernel", "moves": "dummy_reads_per_s",
                           "workloads": list(mine)})
    # the one edit to entries that are there: the cell's name joins the
    # `workloads` of a per-layer metric and of the metric that one moves
    for group, name in (("end_to_end", "scan_rows_per_s"),
                        ("per_layer", "rpcs_per_stmt")):
        next(x for x in m[group] if x["name"] == name)["workloads"].append(
            "dummy_mesh4")
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(m, f)
    # every check the real manifest is held to, on the copy
    m = manifest.load(root)
    manifest.validate(m, root)
    check_chips(m)
    cells = {w["name"]: check_cell(m, w["name"], root)
             for w in m["workloads"]}
    for c in m["configs"]:
        check_config(m, c, root)
    cell = cells["dummy_mesh4"]
    assert cell.traffic["reads"] == 5 and cell.loader.TABLE == "kv"
    assert [x["name"] for x in cell.end_to_end] == [
        "scan_rows_per_s", "setup_s", "dummy_reads_per_s"]
    assert [x["name"] for x in cell.per_layer] == ["rpcs_per_stmt",
                                                   "dummy.metric"]
    # the copy's own run.py drives the new cell on four devices: nothing
    # in the harness names a table, a size or a number of chips
    spec = importlib.util.spec_from_file_location(
        "benchmark._copy_run", os.path.join(bench, "run.py"))
    run = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(run)
    assert run._ROOT == root
    result = run.run_cell(["--workload", "dummy_mesh4", "--seed", "3",
                           "--seconds", "1", "--rehearse"])
    assert result["correct"] is True, result["compared"]
    assert result["attempted"] == 5 and result["failed"] == 0
    assert set(result["compared"]) == set(cfg["limits"])
    assert result["device"]["chips"] == 4
    assert len(result["device"]["memory_peak_bytes_by_device"]) == 4
    after = _files(bench)
    assert {p for p in before if before[p] != after.get(p)} == set()
    assert {os.path.relpath(p, bench) for p in set(after) - set(before)} == {
        "configs/dummy_kv_mesh4.json", "loaders/dummy_kv.py",
        "drivers/dummy_reads.py", "traffic/dummy_mix.json",
        "layer_metrics/dummy.metric.py"} | {
        f"{d}/{name}.json" for name in mine[1:]
        for d in ("configs", "traffic")}
