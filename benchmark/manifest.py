"""`BENCHMARK.json` and the files it names.  Everything that belongs to one
configuration, one traffic mix, one driver or one per-layer metric is a
file of its own, found by the name in the manifest (a path, `importlib`),
never through a registry inside a file: a later PR adds files and one
entry each and edits nothing that is there."""
from __future__ import annotations

import importlib.util
import json
import os
import re

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH_DIR = os.path.join(ROOT, "benchmark")

_NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
_UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
_SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")
_KEYS = {"command", "paths", "run_seconds", "configs", "workloads",
         "end_to_end", "per_layer"}


class ManifestError(ValueError):
    pass


def load(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def _line(text, what) -> None:
    if not (isinstance(text, str) and 1 <= len(text) <= 200
            and "\n" not in text and "\t" not in text):
        raise ManifestError(f"{what}: 1 to 200 characters on one line")


def _metric(m: dict, keys: set, cells: dict) -> None:
    extra = set(m) - keys - {"workloads"}
    if extra or not keys <= set(m):
        raise ManifestError(f"metric {m.get('name')}: keys {sorted(m)}")
    if not _NAME.match(m["name"]) or not _UNIT.match(m["unit"]):
        raise ManifestError(f"metric name or unit: {m['name']} {m['unit']}")
    if m["better"] not in ("lower", "higher") or m["source"] not in _SOURCES:
        raise ManifestError(f"metric {m['name']}: better/source")
    for w in m.get("workloads", ()):
        if w not in cells:
            raise ManifestError(f"metric {m['name']}: no cell {w}")


def cells_of(metric: dict, manifest: dict) -> list:
    """The cells a metric is reported in: its `workloads`, or every cell."""
    return list(metric.get("workloads")
                or [w["name"] for w in manifest["workloads"]])


def validate(m: dict, root: str = ROOT, bench_dir: str = None) -> None:
    """The contract's rules that a file can break, so that a test catches
    them before the driver does."""
    bench_dir = bench_dir or os.path.join(root, "benchmark")
    if set(m) != _KEYS:
        raise ManifestError(f"keys {sorted(m)}")
    if not (isinstance(m["run_seconds"], int) and 1 <= m["run_seconds"] <= 51):
        raise ManifestError("run_seconds")
    for word in m["command"]:
        _line(word, "command")
    configs = {}
    for c in m["configs"]:
        if set(c) != {"name", "source", "file", "reduced", "why"}:
            raise ManifestError(f"config keys {sorted(c)}")
        if not _NAME.match(c["name"]) or c["name"] in configs:
            raise ManifestError(f"config name {c['name']}")
        _line(c["source"], "source"), _line(c["why"], "why")
        if not any(c["file"].startswith(p + "/") for p in m["paths"]):
            raise ManifestError(f"{c['file']} is outside paths")
        if not os.path.isfile(os.path.join(root, c["file"])):
            raise ManifestError(f"no file {c['file']}")
        if len(c["reduced"]) > 16 or not all(
                _NAME.match(k) for k in c["reduced"]):
            raise ManifestError(f"reduced of {c['name']}")
        configs[c["name"]] = c
    if len({c["file"] for c in m["configs"]}) != len(configs):
        raise ManifestError("two configurations share a file")
    cells, pairs = {}, set()
    for w in m["workloads"]:
        if set(w) != {"name", "config", "traffic", "chips", "why"}:
            raise ManifestError(f"workload keys {sorted(w)}")
        if not _NAME.match(w["name"]) or w["name"] in cells:
            raise ManifestError(f"workload name {w['name']}")
        if not _NAME.match(w["traffic"]) or w["config"] not in configs:
            raise ManifestError(f"workload {w['name']}: traffic/config")
        if w["chips"] not in (1, 4) or (w["config"], w["traffic"]) in pairs:
            raise ManifestError(f"workload {w['name']}: chips/pair")
        _line(w["why"], "why")
        traffic_file(w["traffic"], bench_dir)
        pairs.add((w["config"], w["traffic"]))
        cells[w["name"]] = w
    if {w["config"] for w in m["workloads"]} != set(configs):
        raise ManifestError("a configuration no cell uses")
    e2e = {}
    for x in m["end_to_end"]:
        _metric(x, {"name", "unit", "better", "bound", "source"}, cells)
        if x["source"] not in ("host_clock", "device_trace"):
            raise ManifestError(f"{x['name']}: source")
        if not 0.01 <= x["bound"] <= 0.25:
            raise ManifestError(f"{x['name']}: bound")
        e2e[x["name"]] = x
    if "setup_s" not in e2e or "workloads" in e2e["setup_s"]:
        raise ManifestError("setup_s is reported by every cell")
    layered = set()
    for x in m["per_layer"]:
        _metric(x, {"name", "unit", "better", "source", "layer", "moves"},
                cells)
        _line(x["layer"], "layer")
        if x["moves"] not in e2e:
            raise ManifestError(f"{x['name']} moves {x['moves']}")
        for w in cells_of(x, m):
            if w not in cells_of(e2e[x["moves"]], m):
                raise ManifestError(
                    f"{x['name']}: cell {w} does not report {x['moves']}")
            layered.add(w)
        layer_metric_file(x["name"], bench_dir)
    names = [x["name"] for x in m["end_to_end"] + m["per_layer"]]
    if len(names) != len(set(names)):
        raise ManifestError("two metrics share a name")
    for w in cells:
        mine = [x for x in m["end_to_end"] if w in cells_of(x, m)]
        if len(mine) < 2 or w not in layered:
            raise ManifestError(f"cell {w}: setup_s, one more end-to-end "
                                "metric and a per-layer metric")


# -- finding the files by name ----------------------------------------------
def _existing(path: str) -> str:
    if not os.path.isfile(path):
        raise ManifestError(f"no file {path}")
    return path


def traffic_file(name: str, bench_dir: str = BENCH_DIR) -> str:
    return _existing(os.path.join(bench_dir, "traffic", name + ".json"))


def layer_metric_file(name: str, bench_dir: str = BENCH_DIR) -> str:
    return _existing(os.path.join(bench_dir, "layer_metrics", name + ".py"))


def driver_file(name: str, bench_dir: str = BENCH_DIR) -> str:
    return _existing(os.path.join(bench_dir, "drivers", name + ".py"))


def loader_file(name: str, bench_dir: str = BENCH_DIR) -> str:
    return _existing(os.path.join(bench_dir, "loaders", name + ".py"))


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: str):
    """A driver or a metric reader, by its file: its name may hold dots."""
    mod = "benchmark._file_" + re.sub(r"\W", "_", os.path.relpath(path, ROOT))
    spec = importlib.util.spec_from_file_location(mod, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


class Cell:
    """One entry of `workloads`, with its configuration and the loader it
    names, its traffic mix and the driver that names, and the metrics it
    reports."""

    def __init__(self, manifest: dict, name: str, root: str = ROOT,
                 bench_dir: str = None):
        bench_dir = bench_dir or os.path.join(root, "benchmark")
        by_name = {w["name"]: w for w in manifest["workloads"]}
        if name not in by_name:
            raise ManifestError(f"no workload {name!r}; there are "
                                f"{sorted(by_name)}")
        self.name, self.entry = name, by_name[name]
        self.chips = self.entry["chips"]
        cfg = next(c for c in manifest["configs"]
                   if c["name"] == self.entry["config"])
        self.config = load_json(os.path.join(root, cfg["file"]))
        self.traffic = load_json(traffic_file(self.entry["traffic"],
                                              bench_dir))
        self.driver = load_module(driver_file(self.traffic["driver"],
                                              bench_dir))
        self.loader = load_module(loader_file(self.config["loader"],
                                              bench_dir))
        self.end_to_end = [x for x in manifest["end_to_end"]
                           if name in cells_of(x, manifest)]
        self.per_layer = [x for x in manifest["per_layer"]
                          if name in cells_of(x, manifest)]
        self.readers = {x["name"]: layer_metric_file(x["name"], bench_dir)
                        for x in self.per_layer}
