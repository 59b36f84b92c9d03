#!/usr/bin/env python3
"""One run of one cell of `BENCHMARK.json`, on the machine it is started on.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

One process holds the chip: master, tserver, client and load generator all
live here.  It sets up through the configuration's loader (timed step by
step on earlier lines), warms the
cell's own shapes, measures for `--seconds`, checks what the window
produced against the plain reference, and prints one JSON object as the
last line of standard output.  Without a TPU (or with fewer chips than the
cell asks for) it exits 3 and prints no result; `--rehearse` runs the same
phases on whatever JAX finds, at `--rows`, and reports no metric.
"""
from __future__ import annotations

import time

_T0 = time.perf_counter()      # set-up counts from the start of the process

import argparse                # noqa: E402
import asyncio                 # noqa: E402
import json                    # noqa: E402
import os                      # noqa: E402
import shutil                  # noqa: E402
import sys                     # noqa: E402
import tempfile                # noqa: E402
import traceback               # noqa: E402
import types                   # noqa: E402

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _ROOT not in sys.path:
    sys.path.insert(0, _ROOT)

from benchmark import manifest, peaks, span_reduce, trace_reduce  # noqa: E402
from benchmark.cluster import Cluster                     # noqa: E402
from benchmark.record import Checks, GcWatch, Recorder             # noqa: E402

EXIT_NO_CHIP = 3


class NoChip(RuntimeError):
    pass


def say(obj: dict) -> None:
    print(json.dumps(obj), flush=True)


def device_info(chips: int, rehearse: bool):
    """The cell's devices, the first `chips` JAX has, and what the result
    line says of them."""
    import jax
    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "chips": chips}
    if not rehearse and (info["platform"] != "tpu" or len(devs) < chips):
        raise NoChip(f"the cell needs {chips} TPU chip(s); JAX found {info}")
    return devs[:chips], info


def memory_peaks(devices: list) -> dict:
    """The peak on the fullest of the cell's chips, and each chip's."""
    by_device = [(d.memory_stats() or {}).get("peak_bytes_in_use")
                 for d in devices]
    return {"memory_peak_bytes": max((b for b in by_device if b is not None),
                                     default=None),
            "memory_peak_bytes_by_device": by_device}


class _Compiles:
    """Every backend compile of the process, by `jax.monitoring`."""

    def __init__(self):
        import jax
        self.secs: list = []
        self._jax = jax
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, name, secs, **kw):
        if name.endswith("backend_compile_duration"):
            self.secs.append(secs)

    def close(self):
        self._jax.monitoring.unregister_event_duration_listener(self._on)


def _program_counters(cluster) -> dict:
    from yugabyte_db_tpu.docdb.operations import _SHARED_KERNEL
    from yugabyte_db_tpu.ops.compaction import kernel_cache_stats
    merge = kernel_cache_stats()
    return {"messenger.calls_sent": cluster.client.messenger.calls_sent,
            "scan_kernel.compiles": _SHARED_KERNEL.compiles,
            "merge_kernel.calls": merge["calls"],
            "merge_kernel.compiles": merge["compiles"]}


def breakdown(ctx) -> dict:
    """The traced line's top device operations, and its idle gaps named by
    the program span the host was in (`span_reduce.idle_gaps`); where the
    spans cannot be read on the trace's clock, by the benchmark span alone
    (`trace_reduce`)."""
    gaps = span_reduce.idle_gaps(ctx)
    return {"device_ops": ctx.trace["device_ops"],
            "idle_gaps": ctx.trace["idle_gaps"] if gaps is None else gaps}


async def _measure(cell, cluster, rec, seconds, traced, keep_trace):
    """The window, and in a traced run the profiler around all of it.
    Returns the trace reduction or None."""
    import jax
    before = _program_counters(cluster)
    trace_dir = None
    if traced:
        trace_dir = tempfile.mkdtemp(prefix="ybtpu-benchmark-trace-")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0      # the benchmark's spans are enough
        opts.host_tracer_level = 1
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    try:
        with rec.span("trace_window"), GcWatch() as gc_watch:
            t0 = time.perf_counter()
            await cell.driver.window(cluster, cell.traffic, seconds, rec)
            rec.window = (t0, time.perf_counter())
        rec.gc = gc_watch.summary()
    finally:
        if traced:
            jax.profiler.stop_trace()
    after = _program_counters(cluster)
    rec.counters = {k: after[k] - before[k] for k in after}
    if not traced:
        return None
    try:
        path = trace_reduce.find_xplane(trace_dir)
        if keep_trace:
            os.makedirs(keep_trace, exist_ok=True)
            shutil.copy(path, keep_trace)
        return trace_reduce.reduce(trace_reduce.load_xplane(path),
                                   cell.chips)
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)


async def _run(cell, args, devices, info) -> dict:
    on_tpu = info["platform"] == "tpu"
    traced = bool(args.trace)
    seconds = (min(args.seconds, float(cell.traffic["trace_seconds"]))
               if traced else args.seconds)
    compiles = _Compiles()
    cluster = Cluster(cell.config.get("flags", {}), devices)
    rec = Recorder(traced)
    checks = Checks(cell.config["limits"])
    try:
        t = time.perf_counter()
        await cluster.start()
        say({"step": "start", "seconds": round(time.perf_counter() - t, 3)})
        cluster.data, steps = await cell.loader.load(
            cluster, cell.config, args.seed, args.rows)
        say({"step": "load", **steps})
        t, n = time.perf_counter(), len(compiles.secs)
        await cell.driver.warm(cluster, cell.traffic, rec)
        say({"step": "warm", "seconds": round(time.perf_counter() - t, 3),
             "compiles": len(compiles.secs) - n})
        setup_s = time.perf_counter() - _T0
        say({"step": "setup", "setup_s": round(setup_s, 3),
             "compiles": len(compiles.secs),
             "compile_s": round(sum(compiles.secs), 2)})
        n = len(compiles.secs)
        trace = await _measure(cell, cluster, rec, seconds, traced,
                               args.keep_trace)
        in_window = len(compiles.secs) - n
        dev = {**info, **memory_peaks(devices)}
        say({"step": "window", "seconds": round(rec.window_s, 3),
             "compiles_in_window": in_window, "counters": rec.counters,
             "ssts_per_tablet_before_after": rec.ssts_per_tablet,
             "span_ms": rec.summary(), "slowest": rec.slowest(),
             "python_gc": rec.gc, "errors": rec.errors})
        t = time.perf_counter()
        await cell.driver.verify(cluster, cell.traffic, rec, checks)
        checks.note("compiles_in_window", in_window)
        say({"step": "verify", "seconds": round(time.perf_counter() - t, 3),
             **cluster.device_evidence()})
    finally:
        await cluster.shutdown()
        compiles.close()
    attempted, failed = cell.driver.attempted_failed(rec)
    metrics, result = {}, {}
    if on_tpu and not traced:
        values = {"setup_s": setup_s,
                  **cell.driver.end_to_end(cluster, cell.traffic, rec)}
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in cell.end_to_end if m["name"] in values}
    elif on_tpu:
        ctx = types.SimpleNamespace(
            trace=trace, rec=rec, cell=cell, peak=peaks.lookup(info["kind"]),
            data=cluster.data)
        for m in cell.per_layer:
            value = manifest.load_module(cell.readers[m["name"]]).read(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        dev.update(busy_s=trace["busy_s"], window_s=trace["window_s"],
                   devices_busy=trace["devices_busy"])
        result["breakdown"] = breakdown(ctx)
    compared = checks.table()
    for name, e in compared.items():
        print(f"compared {name}: {e['value']} limit {e['limit']} "
              f"n {e['n']} {'ok' if e['ok'] else 'NOT OK'}",
              file=sys.stderr, flush=True)
    return {"correct": checks.correct() and failed == 0,
            "attempted": attempted, "failed": failed, "metrics": metrics,
            "device": dev, **result,
            "compiles_in_window": in_window,
            "compared": {k: [e["value"], e["limit"]]
                         for k, e in compared.items()}}


def run_cell(argv=None) -> dict:
    """Parse, look for the chip, run; returns the result object.  Raises
    `NoChip` where the cell's chips are not there and `--rehearse` was
    not given."""
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, default=0, choices=(0, 1))
    ap.add_argument("--rows", type=int, default=None,
                    help="rehearsal and tests: table rows in place of the "
                         "configuration's")
    ap.add_argument("--rehearse", action="store_true",
                    help="run without a TPU; no metric is reported")
    ap.add_argument("--keep-trace", default=None,
                    help="directory to copy the raw .xplane.pb into")
    args = ap.parse_args(argv)
    # this file's own checkout, so that a copy of the benchmark runs its own
    # manifest and files
    m = manifest.load(_ROOT)
    cell = manifest.Cell(m, args.workload, _ROOT)
    if args.seconds is None:
        args.seconds = float(m["run_seconds"])
    import yugabyte_db_tpu  # noqa: F401 — x64, platform, compile cache
    devices, info = device_info(cell.chips, args.rehearse)
    import jax
    say({"step": "devices", **info, "workload": cell.name,
         "seed": args.seed, "seconds": args.seconds, "trace": args.trace,
         "rows": args.rows or "the configuration's",
         "compile_cache_dir": jax.config.jax_compilation_cache_dir})
    return asyncio.run(_run(cell, args, devices, info))


def main(argv=None) -> int:
    try:
        result = run_cell(argv)
    except NoChip as e:
        print(f"benchmark: {e}", file=sys.stderr)
        return EXIT_NO_CHIP
    except Exception:   # noqa: BLE001 — no result line, non-zero exit
        traceback.print_exc()
        return 1
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
