"""`BENCHMARK.json` against the contract's rules a file can break, and the
promise that a configuration, a traffic mix and a per-layer metric are each
added as new files plus one entry, with no edit to a file that is there."""
import copy
import json
import os
import shutil

import pytest

from benchmark import manifest

M = manifest.load()
ALL = manifest.with_deferred(M)      # with the cells kept for a later PR
METRICS = ALL["end_to_end"] + ALL["per_layer"]


def test_manifest_is_valid():
    manifest.validate(M)
    manifest.validate(ALL)
    assert M["command"] == ["python3", "benchmark/run.py"]
    assert [w["name"] for w in M["workloads"]][:1] == ["scan_power"]
    assert all(w["chips"] == 1 for w in M["workloads"])
    assert len(json.dumps(M)) < 64 * 1024


@pytest.mark.parametrize("metric", METRICS, ids=[m["name"] for m in METRICS])
def test_metric_names_units_and_cells(metric):
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


@pytest.mark.parametrize("cell", [w["name"] for w in ALL["workloads"]])
def test_cell_finds_its_files_by_name(cell):
    c = manifest.Cell(ALL, cell)
    assert c.config["chips"] == c.chips == 1
    assert c.config["sizes"]["rows"] == 6_001_215     # SF1, clause 4.2.5
    assert c.config["sizes"]["orders"] == 1_500_000
    assert c.config["flags"] == {"device_float_dtype": "float64"}
    assert callable(c.driver.window) and callable(c.driver.verify)
    assert callable(c.loader.load)
    assert {m["name"] for m in c.end_to_end} >= {"setup_s"}
    assert len(c.end_to_end) >= 2 and c.per_layer
    for path in c.readers.values():
        assert callable(manifest.load_module(path).read)
    # every number the driver compares has a limit, stated by the config
    assert all(isinstance(v, (int, float)) for v in
               c.config["limits"].values())


@pytest.mark.parametrize("config", ALL["configs"], ids=lambda c: c["name"])
def test_config_file_states_its_cut(config):
    body = manifest.load_json(os.path.join(manifest.ROOT, config["file"]))
    assert body["name"] == config["name"]
    assert body["source"] == config["source"] and len(body["source"]) <= 200
    assert sorted(body["reduced"]) == sorted(config["reduced"])
    assert body["assumed"] and body["guarantees"]["writes"]


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
    m = copy.deepcopy(ALL)
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
        "WITH tablets = 2")
    for k in range(n):
        await cluster.sql.execute(
            f"INSERT INTO kv (k, v) VALUES ({k}, {(seed + k) / 4})")
    return Kv(n), {"insert_s": 0.0}
'''
DUMMY_DRIVER = '''
"""Point reads of the key-value table."""
async def warm(cluster, traffic, rec):
    pass


async def window(cluster, traffic, seconds, rec):
    for k in range(traffic["reads"]):
        with rec.span("read", key=k) as s:
            s["rows"] = (await cluster.sql.execute(
                f"SELECT v FROM kv WHERE k = {k}")).rows


async def verify(cluster, traffic, rec, checks):
    checks.note("read_diff", sum(
        1 for s in rec.of("read")
        if s["rows"] != [{"v": (traffic["seed"] + s["key"]) / 4}]))


def attempted_failed(rec):
    n = len(rec.of("read", ok_only=False))
    return n, n - len(rec.of("read"))


def end_to_end(cluster, traffic, rec):
    return {"reads_per_s": len(rec.of("read")) / rec.window_s}
'''


def test_a_later_pr_adds_files_and_entries_only(tmp_path, monkeypatch):
    """A dummy configuration with another schema and a loader of its own,
    a traffic mix with a driver of its own, an end-to-end and a per-layer
    metric: added to a copy of the benchmark as new files plus one entry
    each, and the copy's `run.py` rehearses the new cell end to end."""
    root = str(tmp_path)
    bench = os.path.join(root, "benchmark")
    shutil.copytree(os.path.join(manifest.ROOT, "benchmark"), bench,
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = {os.path.join(dp, p): open(os.path.join(dp, p), "rb").read()
              for dp, _, fs in os.walk(bench) for p in fs}

    def add(path, text):
        with open(os.path.join(bench, path), "w") as f:
            f.write(text)

    cfg = {"name": "dummy_kv", "source": "a public source, part 1",
           "chips": 1, "loader": "dummy_kv", "sizes": {"rows": 8},
           "reduced": {}, "limits": {"read_diff": 0,
                                     "compiles_in_window": 0}}
    add("configs/dummy_kv.json", json.dumps(cfg))
    add("loaders/dummy_kv.py", DUMMY_LOADER)
    add("drivers/dummy_reads.py", DUMMY_DRIVER)
    add("traffic/dummy_mix.json", json.dumps(
        {"driver": "dummy_reads", "reads": 5, "seed": 3,
         "trace_seconds": 1}))
    add("layer_metrics/dummy.metric.py",
        "def read(ctx):\n    return float(ctx.data.table_rows)\n")
    m = copy.deepcopy(M)
    m["configs"].append({"name": "dummy_kv", "source": cfg["source"],
                         "file": "benchmark/configs/dummy_kv.json",
                         "reduced": [], "why": "a dummy"})
    m["workloads"].append({"name": "dummy_cell", "config": "dummy_kv",
                           "traffic": "dummy_mix", "chips": 1,
                           "why": "a dummy"})
    m["end_to_end"].append({"name": "reads_per_s", "unit": "reads/s",
                            "better": "higher", "bound": 0.05,
                            "source": "host_clock",
                            "workloads": ["dummy_cell"]})
    m["per_layer"].append({"name": "dummy.metric", "unit": "x",
                           "better": "lower", "source": "program_counter",
                           "layer": "scan kernel", "moves": "reads_per_s",
                           "workloads": ["dummy_cell"]})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(m, f)
    manifest.validate(m, root)
    cell = manifest.Cell(m, "dummy_cell", root)
    assert cell.traffic["reads"] == 5 and cell.loader.TABLE == "kv"
    assert [x["name"] for x in cell.per_layer] == ["dummy.metric"]
    # the harness as it is drives the new cell: nothing in it names a table
    from benchmark import run
    monkeypatch.setattr(manifest, "load", lambda: m)
    monkeypatch.setattr(manifest, "Cell", lambda mm, name: cell)
    result = run.run_cell(["--workload", "dummy_cell", "--seed", "3",
                           "--seconds", "1", "--rehearse"])
    assert result["correct"] is True, result["compared"]
    assert result["attempted"] == 5 and result["failed"] == 0
    after = {p: open(p, "rb").read() for p in before}
    assert {p for p in before if before[p] != after[p]} == set()
