#!/usr/bin/env python
"""Benchmark driver: TPC-H-style scan pushdown on the TPU engine.

Prints ONE JSON line:
  {"metric": ..., "value": N, "unit": ..., "vs_baseline": N, ...}

Covers the BASELINE.json configs:
  1. YCSB-C engine-level point reads
  2. TPC-H Q6 single tablet (primary metric; rows/s, vs vectorized-numpy
     CPU baseline over the identical columnar blocks — a fair stand-in
     for a good CPU engine, NOT a row-at-a-time interpreter)
  3. TPC-H Q1 distributed over 8 tablets with psum combine (falls back
     to host-side combine when fewer than 8 devices exist)
  4. Major compaction of a many-SSTable tablet, device merge vs CPU feed
  5. Vector search (IVF-flat; BENCH_FULL=1 runs the 1M x 768 config)

Q6 AND Q1 results are verified against direct-numpy references.

Env knobs: BENCH_SF (default 1.0), BENCH_REPEATS (default 5),
BENCH_COMPACT_SSTS (default 100), BENCH_COMPACT_ROWS (rows per SST,
default 20000), BENCH_YCSB_OPS, BENCH_FULL.
"""
import json
import os
import sys
import tempfile
import time

import numpy as np


def _vector_line(v):
    """Vector result block: rates plus the parameters they were bought
    with (nlists/nprobe/candidate-pool/ef and kernel-compile counts)."""
    return {"n": v["n"], "dim": v["dim"],
            "build_s": round(v["build_s"], 2),
            "nlists": v["nlists"], "nprobe": v["nprobe"],
            "candidate_pool": v["candidate_pool"], "ef": v["ef"],
            "kernel_cache": v["kernel_cache"],
            "search_qps": round(v["qps"], 1),
            "recall_at_10": round(v["recall_at_10"], 3)}


def best_of(fn, n, *args):
    ts = []
    for _ in range(n):
        t0 = time.perf_counter()
        out = fn(*args)
        ts.append(time.perf_counter() - t0)
    return min(ts), out


def _reported_errors(obj, path=""):
    """Every {"error": ...} a "report, don't fail bench" block left in
    the results tree, as (path, message) pairs: the blocks keep the run
    collecting, and main() exits non-zero at its end when any fired."""
    found = []
    if isinstance(obj, dict):
        if "error" in obj:
            found.append((path or ".", str(obj["error"])))
        for k, v in obj.items():
            found += _reported_errors(v, f"{path}.{k}" if path else str(k))
    elif isinstance(obj, (list, tuple)):
        for i, v in enumerate(obj):
            found += _reported_errors(v, f"{path}[{i}]")
    return found


def ycsb_overload_bench():
    """YCSB-C at 2x saturation through the REAL RPC path, scheduler ON
    vs OFF (the PR-3 headline): an open loop offers 2x the measured
    closed-loop saturation rate; ON must hold p99 via bounded queues +
    typed sheds (SERVICE_UNAVAILABLE + retry_after_ms) where OFF lets
    the backlog stack into seconds of latency.  Returns the comparison
    dict (or {"error": ...}); BENCH_OVERLOAD_S=0 skips."""
    import asyncio

    duration = float(os.environ.get("BENCH_OVERLOAD_S", "2.5"))
    if duration <= 0:
        return None

    async def run():
        from yugabyte_db_tpu.docdb.operations import ReadRequest
        from yugabyte_db_tpu.docdb.wire import read_request_to_wire
        from yugabyte_db_tpu.models.ycsb import usertable_info
        from yugabyte_db_tpu.rpc.messenger import Messenger, RpcError
        from yugabyte_db_tpu.tools.mini_cluster import MiniCluster
        from yugabyte_db_tpu.utils import flags as _flags

        n_rows = 20000
        mc = await MiniCluster(tempfile.mkdtemp(prefix="ybtpu-ol-"),
                               num_tservers=1).start()
        conns = []
        try:
            c = mc.client()
            await c.create_table(usertable_info(), num_tablets=1,
                                 replication_factor=1)
            await mc.wait_for_leaders("usertable")
            rows = [{"ycsb_key": i,
                     **{f"field{j}": "x" * 100 for j in range(10)}}
                    for i in range(n_rows)]
            for i in range(0, n_rows, 2000):
                await c.insert("usertable", rows[i:i + 2000])
            ct = await c._table("usertable")
            loc = ct.locations[0]
            addr = loc.leader_addr()
            # flush: scans measure the columnar/pushdown path (the
            # steady state), not a 20k-row memtable decode per query
            await c.messenger.call(addr, "tserver", "flush",
                                   {"tablet_id": loc.tablet_id},
                                   timeout=30.0)
            # 64 distinct connections (a fleet of clients, not one
            # pipelined socket): per-connection inflight caps cannot
            # compose into a global bound across a fleet — holding
            # latency here is exactly the scheduler's job
            conns = [Messenger(f"ol-{i}") for i in range(64)]
            rng = np.random.default_rng(2)

            def payload():
                return {"tablet_id": loc.tablet_id,
                        "req": read_request_to_wire(ReadRequest(
                            ct.info.table_id,
                            pk_eq={"ycsb_key":
                                   int(rng.integers(0, n_rows))}))}

            from yugabyte_db_tpu.ops.scan import AggSpec

            def scan_payload():
                # one fixed aggregate signature: under load every
                # queued copy coalesces into ONE kernel launch
                return {"tablet_id": loc.tablet_id,
                        "req": read_request_to_wire(ReadRequest(
                            ct.info.table_id,
                            aggregates=(AggSpec("count"),)))}

            async def closed_loop(dur, workers=64, pl=payload):
                stop = time.perf_counter() + dur
                count = 0

                async def w(i):
                    nonlocal count
                    m = conns[i % len(conns)]
                    while time.perf_counter() < stop:
                        await m.call(addr, "tserver", "read", pl(),
                                     timeout=30.0)
                        count += 1
                await asyncio.gather(*[w(i) for i in range(workers)])
                return count / dur

            async def open_loop(rate, dur, deadline_s=2.0, pl=payload):
                """Open loop at `rate` for `dur` seconds.  Every op
                carries a realistic client DEADLINE: a completion past
                it is wasted server work the client no longer wants —
                achieved ops/s counts in-SLA completions only (the
                goodput an overloaded server actually delivers)."""
                lat, tasks = [], []
                shed = timed_out = conn_reset = 0
                retry_after = []

                async def one(i):
                    nonlocal shed, timed_out, conn_reset
                    m = conns[i % len(conns)]
                    t0 = time.perf_counter()
                    try:
                        await m.call(addr, "tserver", "read", pl(),
                                     timeout=deadline_s)
                        lat.append(time.perf_counter() - t0)
                    except asyncio.TimeoutError:
                        timed_out += 1
                    except RpcError as e:
                        if e.code == "SERVICE_UNAVAILABLE":
                            shed += 1
                            if e.retry_after_ms and len(retry_after) < 64:
                                retry_after.append(e.retry_after_ms)
                        elif e.code == "NETWORK_ERROR":
                            # a sibling op's deadline evicted this conn
                            # mid-flight — an overload casualty too
                            conn_reset += 1
                        else:
                            raise
                total = int(rate * dur)
                interval = 1.0 / rate
                t_start = time.perf_counter()
                for i in range(total):
                    due = t_start + i * interval
                    now = time.perf_counter()
                    if now < due:
                        await asyncio.sleep(due - now)
                    tasks.append(asyncio.ensure_future(one(i)))
                await asyncio.gather(*tasks)
                wall = time.perf_counter() - t_start
                lat_ms = sorted(x * 1e3 for x in lat)

                def pct(q):
                    if not lat_ms:
                        return 0.0
                    return lat_ms[min(len(lat_ms) - 1,
                                      int(q * len(lat_ms)))]
                return {"offered_ops_per_s": round(rate, 1),
                        "achieved_ops_per_s": round(len(lat) / wall, 1),
                        "ok": len(lat), "shed": shed,
                        "timed_out": timed_out, "conn_reset": conn_reset,
                        "deadline_s": deadline_s,
                        "shed_rate": round(shed / max(1, total), 3),
                        "retry_after_ms_seen": (
                            [min(retry_after), max(retry_after)]
                            if retry_after else None),
                        "p50_ms": round(pct(0.5), 2),
                        "p99_ms": round(pct(0.99), 2)}

            async def paired_overload(pl, sat):
                # PAIRED, interleaved rounds (the Q6/compaction
                # discipline): ON and OFF run back-to-back inside each
                # round so co-tenant noise hits both sides of a round
                # equally; keep each side's best-achieved run, ratio
                # from those
                on_rounds, off_rounds = [], []
                for _ in range(2):
                    on_rounds.append(
                        await open_loop(2 * sat, duration, pl=pl))
                    _flags.set_flag("scheduler_enabled", False)
                    try:
                        off_rounds.append(
                            await open_loop(2 * sat, duration, pl=pl))
                    finally:
                        _flags.set_flag("scheduler_enabled", True)
                on = max(on_rounds,
                         key=lambda r: r["achieved_ops_per_s"])
                off = max(off_rounds,
                          key=lambda r: r["achieved_ops_per_s"])
                return {"saturation_ops_per_s": round(sat, 1),
                        "scheduler_on": on, "scheduler_off": off,
                        "p99_ratio_rounds": [
                            round(a["p99_ms"] / max(b["p99_ms"], 1e-9), 3)
                            for a, b in zip(on_rounds, off_rounds)],
                        "p99_ratio_on_vs_off": round(
                            on["p99_ms"] / max(off["p99_ms"], 1e-9), 3),
                        "achieved_ratio_on_vs_off": round(
                            on["achieved_ops_per_s"]
                            / max(off["achieved_ops_per_s"], 1e-9), 3)}

            await closed_loop(0.5)                    # warm
            sat = await closed_loop(1.5)
            points = await paired_overload(payload, sat)
            # scan lane: same-signature aggregates coalesce into ONE
            # kernel launch per batch — under overload the scheduler
            # turns N queued copies into one engine execution, a real
            # capacity multiplier (the accelerator-boundary batching
            # the subsystem exists for)
            await closed_loop(0.5, pl=scan_payload)   # warm/compile
            scan_sat = await closed_loop(1.5, pl=scan_payload)
            scans = await paired_overload(scan_payload, scan_sat)
            return {"point_reads": points, "agg_scans": scans}
        finally:
            for m in conns:
                await m.shutdown()
            await mc.shutdown()

    try:
        return asyncio.run(run())
    except Exception as e:   # noqa: BLE001 — report, don't fail bench
        return {"error": str(e)[:200]}


def cluster_overload_bench():
    """Live fire on a REAL multi-process cluster (cluster/): 1 master +
    3 tservers + 1 open-loop driver, every one its own OS process with
    its own event loop and GIL — the shape the single-loop benches
    above cannot measure.  Four legs, one cluster:

    (a) scheduler ON vs OFF at 2x the measured saturation (paired
        rounds; the PR-3 separation without the shared-loop noise),
    (b) SLA-bounded goodput THROUGH a live tablet auto-split plus a
        blacklist-drain rebalance (balancer replica moves = the
        remote-bootstrap catch-up path) while the driver keeps firing
        (`split_goodput_ratio` vs the calm scheduler-ON round),
    (c) a seeded chaos round — SIGKILL a peer + stall a disk mid-load,
        restart with backoff — followed by a quiesced byte-verify of
        EVERY acked write (`chaos_missing`/`chaos_mismatched` WARN on
        any nonzero: acked data may never vanish),
    (d) bypass aggregate scans served by a SEPARATE replica process
        (rpc_bypass_scan) under the same point-write fire:
        `cluster_bypass_p95_impact` (the WARN gate — round p99s are
        spike-dominated on 2 cores, p95 medians hold steady) is the
        write-lane tail with scans / without — compare to the
        single-loop `bypass_p99_impact` (ROADMAP: separate-process
        bypass should approach 1.0).

    BENCH_CLUSTER_S bounds each phase (0 skips); BENCH_CHAOS_SEED
    replays a chaos round bit-for-bit."""
    import asyncio

    duration = float(os.environ.get("BENCH_CLUSTER_S", "2.5"))
    if duration <= 0:
        return None
    seed = int(os.environ.get("BENCH_CHAOS_SEED", "42"))

    async def run():
        from yugabyte_db_tpu.cluster import (ChaosController,
                                             ClusterSupervisor)
        from yugabyte_db_tpu.docdb.operations import ReadRequest
        from yugabyte_db_tpu.docdb.wire import read_request_to_wire
        from yugabyte_db_tpu.ops.scan import AggSpec

        sup = await ClusterSupervisor(
            tempfile.mkdtemp(prefix="ybtpu-cluster-"),
            num_tservers=3).start()
        out = {"processes": len(sup.procs) + 2}   # + driver + this one
        try:
            await sup.spawn_driver("drv-0")
            setup = await sup.call(
                "drv-0", "driver", "setup",
                {"rows": 2000, "num_tablets": 2,
                 "replication_factor": 2}, timeout=120.0)
            table_id = setup["table_id"]
            sat = (await sup.call(
                "drv-0", "driver", "saturation",
                {"seconds": 1.5, "workers": 32}, timeout=60.0)
            )["ops_per_s"]
            # cap the offered rate: the open loop materializes one task
            # per op and this box is 2 cores
            rate = min(2.0 * sat, 4000.0)
            out["saturation_ops_per_s"] = round(sat, 1)
            out["offered_ops_per_s"] = round(rate, 1)

            async def phase(tag, seconds=None, rate_=None, wf=1.0):
                return await sup.call(
                    "drv-0", "driver", "run_phase",
                    {"rate": rate_ or rate,
                     "seconds": seconds or duration,
                     "write_fraction": wf,
                     "sla_ms": 2000, "tag": tag}, timeout=180.0)

            # (a) scheduler ON/OFF, paired interleaved rounds ----------
            # mixed 50/50 read/write at 2x saturation: point-read
            # fusion + write group commit are where the scheduler's
            # micro-batching pays, and the separation is measured from
            # REMOTE processes (the shape the single-loop ycsb_overload
            # bench could not isolate)
            on_rounds, off_rounds = [], []
            for i in range(2):
                on_rounds.append(await phase(f"on{i}", wf=0.5))
                await sup.set_flag_all("scheduler_enabled", False,
                                       roles=("tserver",))
                try:
                    off_rounds.append(await phase(f"off{i}", wf=0.5))
                finally:
                    await sup.set_flag_all("scheduler_enabled", True,
                                           roles=("tserver",))
            on = max(on_rounds, key=lambda r: r["achieved_ops_per_s"])
            off = max(off_rounds, key=lambda r: r["achieved_ops_per_s"])
            out["scheduler"] = {
                "on": on, "off": off,
                "p99_ratio_rounds": [
                    round(a["p99_ms"] / max(b["p99_ms"], 1e-9), 3)
                    for a, b in zip(on_rounds, off_rounds)],
                # own keys (and thresholds): the single-loop block's
                # p99_ratio_on_vs_off threshold (0.5) is calibrated
                # for in-process dispatch; across real processes the
                # driver-side p99 includes client backoff+retries, so
                # the bar is "ON is not worse" at matched goodput
                "cluster_p99_on_vs_off": round(
                    on["p99_ms"] / max(off["p99_ms"], 1e-9), 3),
                "cluster_achieved_on_vs_off": round(
                    on["achieved_ops_per_s"]
                    / max(off["achieved_ops_per_s"], 1e-9), 3)}

            # (a2) write-path fusion levers ON/OFF, paired ------------
            # pure-write rounds at 2x (the write path dominates):
            # async flush (no apply-thread SST stall), fused consensus
            # appends (one fsync + one round per coalesced batch) and
            # cross-tablet dispatch fusion flipped together — the
            # PR-11 claim that `cluster_achieved_on_vs_off` ~1.0 was
            # unclaimed fusion, now measured as its own paired leg
            fusion_flags = ("async_flush_enabled",
                            "fused_replicate_enabled",
                            "sched_cross_tablet_fusion")
            # cool down leg (a)'s 2x backlog first (same reason leg
            # (b) settles), then force REAL flush traffic: at the
            # default 64MB threshold a short round never flushes and
            # the async-flush lever would measure nothing — 1MB makes
            # each round pay several memtable flushes, ON as frozen
            # handoffs to the flush executor, OFF as inline
            # apply-thread stalls (the ~20x p99 source)
            await asyncio.sleep(duration)
            await phase("fuse-settle", wf=1.0)
            await sup.set_flag_all("memstore_flush_threshold_bytes",
                                   1_000_000, roles=("tserver",))
            fon_rounds, foff_rounds = [], []
            try:
                for i in range(2):
                    fon_rounds.append(await phase(f"fuse-on{i}",
                                                  wf=1.0))
                    for fl in fusion_flags:
                        await sup.set_flag_all(fl, False,
                                               roles=("tserver",))
                    try:
                        foff_rounds.append(await phase(f"fuse-off{i}",
                                                       wf=1.0))
                    finally:
                        for fl in fusion_flags:
                            await sup.set_flag_all(fl, True,
                                                   roles=("tserver",))
            finally:
                await sup.set_flag_all("memstore_flush_threshold_bytes",
                                       64 * 1024 * 1024,
                                       roles=("tserver",))
            fon = max(fon_rounds,
                      key=lambda r: r["achieved_ops_per_s"])
            foff = max(foff_rounds,
                       key=lambda r: r["achieved_ops_per_s"])
            # flush/fusion counters from the live servers: the
            # counter-assert that handoffs actually happened and
            # coalesced groups rode fused appends
            fuse_counters = {"flush_stalls_avoided": 0,
                             "fused_appends": 0,
                             "fused_append_fanin_mean": []}
            for name in sup.tserver_names():
                if not sup.procs[name].alive():
                    continue
                snap = await sup.call(name, "tserver",
                                      "metrics_snapshot", {},
                                      timeout=10.0)
                for ent in snap.get("entities", []):
                    for mname, v in ent.get("metrics", {}).items():
                        if mname == "flush_stalls_avoided":
                            fuse_counters["flush_stalls_avoided"] += v
                        elif mname == "fused_appends":
                            fuse_counters["fused_appends"] += v
                        elif mname == "fused_append_fanin" and \
                                isinstance(v, dict) and v.get("count"):
                            fuse_counters[
                                "fused_append_fanin_mean"].append(
                                    v.get("mean_us", 0.0))
            fm = fuse_counters["fused_append_fanin_mean"]
            fuse_counters["fused_append_fanin_mean"] = (
                round(sum(fm) / len(fm), 2) if fm else None)
            out["write_fusion"] = {
                "on": fon, "off": foff,
                "counters": fuse_counters,
                "cluster_fused_p99_on_vs_off": round(
                    fon["p99_ms"] / max(foff["p99_ms"], 1e-9), 3),
                "cluster_fused_achieved_on_vs_off": round(
                    fon["achieved_ops_per_s"]
                    / max(foff["achieved_ops_per_s"], 1e-9), 3)}

            # (b) goodput through live split + rebalance ---------------
            # the control-plane legs run at 1x saturation, not 2x: the
            # question is what a SUSTAINABLE load loses to a live
            # split+rebalance, not how overload shed composes with it.
            # Cool down first — leg (a)'s 2x rounds leave a server-side
            # backlog that would zero the calm reference's goodput
            await asyncio.sleep(duration)
            await phase("settle", rate_=min(sat, 3000.0))
            calm = await phase("calm", rate_=min(sat, 3000.0))
            await sup.call("master-0", "master", "set_flag",
                           {"name": "tablet_split_size_threshold_bytes",
                            "value": 120_000}, timeout=10.0)
            await sup.call("master-0", "master", "set_flag",
                           {"name": "enable_automatic_tablet_splitting",
                            "value": True}, timeout=10.0)
            await sup.spawn_tserver(3)
            await sup.wait_tservers_live()
            await sup.call("master-0", "master", "blacklist",
                           {"ts_uuid": "ts-0"}, timeout=10.0)
            cp_phases, lb_actions = [], []
            split_fired = drained = False
            deadline = time.monotonic() + max(45.0, 18 * duration)
            while time.monotonic() < deadline:
                cp_phases.append(await phase("cp",
                                             rate_=min(sat, 3000.0)))
                for _ in range(2):   # each tick = at most one move
                    r = await sup.call("master-0", "master",
                                       "balance_tick", {}, timeout=15.0)
                    if r.get("action"):
                        lb_actions.append(r["action"])
                snap = await sup.call("master-0", "master",
                                      "metrics_snapshot", {},
                                      timeout=10.0)
                if not split_fired and \
                        len(snap["tablet_reports"]) > 2:
                    split_fired = True
                    # one live split is the measurement; stop the
                    # splitter so the drain chases a FIXED replica set
                    # instead of freshly split children forever
                    await sup.call(
                        "master-0", "master", "set_flag",
                        {"name": "enable_automatic_tablet_splitting",
                         "value": False}, timeout=10.0)
                ts0 = await sup.call("ts-0", "tserver",
                                     "metrics_snapshot", {},
                                     timeout=10.0)
                drained = not ts0["tablets"]
                if split_fired and drained:
                    break
            await sup.call("master-0", "master", "set_flag",
                           {"name": "enable_automatic_tablet_splitting",
                            "value": False}, timeout=10.0)
            worst = min(cp_phases, key=lambda r: r["achieved_ops_per_s"])
            mean_ach = (sum(p["achieved_ops_per_s"] for p in cp_phases)
                        / len(cp_phases))
            out["split_rebalance"] = {
                "split_fired": split_fired,
                "ts0_drained": drained,
                "balancer_actions": lb_actions[:8],
                "phases": len(cp_phases),
                "calm_1x": calm,
                "worst_phase": worst,
                "mean_achieved_ops_per_s": round(mean_ach, 1),
                # SLA-bounded goodput through the convulsion, vs the
                # calm 1x round on the same cluster
                "split_goodput_ratio": round(
                    mean_ach
                    / max(calm["achieved_ops_per_s"], 1e-9), 3)}

            # (c) seeded chaos round + quiesced byte-verify ------------
            chaos = ChaosController(sup, seed=seed)
            plan = chaos.plan_round(kills=1, stalls=1, stall_s=1.0,
                                    round_s=duration, spare=("ts-0",))
            load = asyncio.ensure_future(
                phase("chaos", seconds=duration + 2.0))
            try:
                log = await chaos.run_round(plan)
                chaos_phase = await load
            finally:
                if not load.done():   # run_round raised: reap the
                    load.cancel()     # driver phase before teardown
                    try:
                        await load
                    except (Exception, asyncio.CancelledError):
                        pass
            await chaos.clear_all()
            verify = await sup.call("drv-0", "driver", "verify", {},
                                    timeout=600.0)
            out["chaos"] = {"seed": seed,
                            "plan": [list(e.as_tuple()) for e in plan],
                            "executed": [list(x) for x in log],
                            "phase": chaos_phase, "verify": verify}
            out["chaos_missing"] = verify["missing"]
            out["chaos_mismatched"] = verify["mismatched"]
            out["chaos_unreachable"] = verify["unreachable"]

            # (d) bypass from a SEPARATE replica process ---------------
            # the single-loop bypass_scan bench's shape, with real
            # process isolation: point writes fire at usertable while
            # aggregate scans hit a SEPARATE analytics table (written
            # once, flushed — the keyless scanner needs clean runs)
            # served via rpc_bypass_scan by a follower tserver process
            from yugabyte_db_tpu.docdb.table_codec import TableInfo
            from yugabyte_db_tpu.dockv.packed_row import (
                ColumnSchema, ColumnType, TableSchema)
            from yugabyte_db_tpu.dockv.partition import PartitionSchema
            ainfo = TableInfo("", "analytics", TableSchema(columns=(
                ColumnSchema(0, "k", ColumnType.INT64,
                             is_hash_key=True),
                ColumnSchema(1, "v", ColumnType.FLOAT64)), version=1),
                PartitionSchema("hash", 1))
            c = sup.client()
            try:
                await c.create_table(ainfo, num_tablets=1,
                                     replication_factor=2)
                n_a = 10_000
                for lo in range(0, n_a, 2000):
                    await c.insert("analytics", [
                        {"k": i, "v": float(i)}
                        for i in range(lo, lo + 2000)])
                act = await c._table("analytics", refresh=True)
                for loc in act.locations:
                    await c.messenger.call(
                        loc.leader_addr(), "tserver", "flush",
                        {"tablet_id": loc.tablet_id}, timeout=30.0)
                a_table_id = act.info.table_id
                leaders = {loc.leader for loc in act.locations}
            finally:
                await c.messenger.shutdown()
            # scan from a process that leads NONE of the analytics
            # tablets — the purest "analytics replica" (its store holds
            # follower-applied rows; the pinner's safe-time wait plus a
            # local flush give it a clean snapshot)
            victim = None
            for name in sup.tserver_names():
                if not sup.procs[name].alive():
                    continue
                snap = await sup.call(name, "tserver",
                                      "metrics_snapshot", {},
                                      timeout=10.0)
                mine = {t: d for t, d in snap["tablets"].items()
                        if t.startswith(a_table_id)}
                if mine and snap["uuid"] not in leaders:
                    victim = name
                    break
                if mine and victim is None:
                    victim = name          # fallback: any replica host
            await sup.call(victim, "tserver", "set_flag",
                           {"name": "bypass_reader_enabled",
                            "value": True}, timeout=10.0)
            agg_req = read_request_to_wire(ReadRequest(
                a_table_id, aggregates=(AggSpec("count"),
                                        AggSpec("sum", ("col", 1)))))
            byp_req = {"table_id": a_table_id, "req": agg_req}
            # the same aggregate THROUGH the hot path: an ordinary
            # `read` RPC at the analytics leader (the contrast round)
            lloc = act.locations[0]
            rpc_req = {"tablet_id": lloc.tablet_id, "req": agg_req}
            leader_name = victim
            for n in sup.tserver_names():
                if not sup.procs[n].alive():
                    continue
                u = (await sup.call(n, "tserver", "metrics_snapshot",
                                    {}, timeout=10.0))["uuid"]
                if u == lloc.leader:
                    leader_name = n
                    break
            # writes at 1x saturation: the isolation question is what
            # analytics traffic does to a HEALTHY write lane (at 2x
            # the p99 already sits at the SLA ceiling and the ratio
            # saturates); scans are PACED — an analytics session, not
            # a scan storm, so the ratio measures loop/GIL coupling
            # rather than raw 2-core oversubscription.  Re-probe
            # saturation first: the cluster behind it (split children,
            # moved replicas, restarted peers) is not the one the
            # opening probe measured
            sat2 = (await sup.call(
                "drv-0", "driver", "saturation",
                {"seconds": 1.0, "workers": 32}, timeout=60.0)
            )["ops_per_s"]
            byp_rate = min(sat2, 3000.0)
            out["post_chaos_saturation_ops_per_s"] = round(sat2, 1)
            scan_every_s = 0.25
            # PINNED compile-warm rounds before anything measured
            # (ROADMAP write-path item (d)): the first bypass scan pays
            # the local follower flush + kernel compile, the first RPC
            # read its own scan-kernel compile, and the first write
            # phase the leaders' apply-path warmup.  A single kernel
            # compile landing inside one 3s measured round swung that
            # round's p99 several-fold on this box and tripped the
            # cluster_p99_spread <= 3x WARN; with all three warmed, the
            # spread gate measures the engine, not XLA.
            await sup.call(victim, "tserver", "bypass_scan", byp_req,
                           timeout=60.0)
            await sup.call(leader_name, "tserver", "read", rpc_req,
                           timeout=60.0)
            await phase("compile_warm", rate_=byp_rate, seconds=1.0)

            async def scan_loop(stop_at, call, stats):
                while time.monotonic() < stop_at:
                    t0 = time.monotonic()
                    try:
                        r = await call()
                        stats["rounds"] += 1
                        stats["last"] = r.get("stats")
                    except Exception as e:   # noqa: BLE001 — the
                        # write-lane p99 is the metric; a scan refusal
                        # (e.g. a flush race) is counted, not fatal
                        stats["errors"] += 1
                        stats["last_error"] = str(e)[:120]
                    dt = time.monotonic() - t0
                    if dt < scan_every_s:
                        await asyncio.sleep(scan_every_s - dt)

            byp_dur = max(duration, 3.0)

            async def measured_round(tag, call, stats):
                # `stats` accumulates ACROSS rounds — the reported
                # scan counts must cover all 3, not just the last
                stop_at = time.monotonic() + byp_dur
                scans = asyncio.ensure_future(
                    scan_loop(stop_at, call, stats))
                try:
                    ph = await phase(tag, rate_=byp_rate,
                                     seconds=byp_dur)
                    await scans
                finally:
                    if not scans.done():   # phase raised: reap
                        scans.cancel()
                        try:
                            await scans
                        except (Exception, asyncio.CancelledError):
                            pass
                return ph

            def _byp_call():
                return sup.call(victim, "tserver", "bypass_scan",
                                byp_req, timeout=60.0)

            def _rpc_call():
                return sup.call(leader_name, "tserver", "read",
                                rpc_req, timeout=60.0)

            # paired interleaved rounds, MEDIAN per side: a flush
            # pause landing in one 3s window swings a single round's
            # p99 several-fold on this box, and best-of would let one
            # lucky round hide a real coupling
            def med(rounds, key):
                vals = sorted(r[key] for r in rounds)
                return vals[len(vals) // 2]

            # --- per-round ASH deltas (p99 attribution) ------------
            # every measured round brackets a tracez sweep of the live
            # tservers; the per-state CUMULATIVE tallies diff into a
            # wait-state delta for that round, so an over-spread p99
            # gets labeled with its dominant wait instead of being
            # "flush-pause luck" (cluster_p99_attribution below)
            from yugabyte_db_tpu.cluster.collector import (
                attribute_rounds, merge_ash_cumulative)

            async def ash_cum():
                dumps = []
                for nm in sup.tserver_names():
                    if not sup.procs[nm].alive():
                        continue
                    try:
                        dumps.append(await sup.call(
                            nm, "tserver", "tracez", {}, timeout=10.0))
                    except Exception:   # noqa: BLE001 — a draining
                        continue        # peer drops out of the diff
                return merge_ash_cumulative(dumps)

            attr_rounds = []

            async def attributed(tag, factory):
                pre = await ash_cum()
                r = await factory()
                post = await ash_cum()
                delta = {s: post.get(s, 0) - pre.get(s, 0)
                         for s in post
                         if post.get(s, 0) > pre.get(s, 0)}
                attr_rounds.append({"tag": tag, "p99_ms": r["p99_ms"],
                                    "wait_delta": delta})
                return r

            bases, byps, rpcs = [], [], []
            byp_stats = {"rounds": 0, "errors": 0, "last": None,
                         "last_error": None}
            rpc_stats = {"rounds": 0, "errors": 0, "last": None,
                         "last_error": None}
            for i in range(3):
                bases.append(await attributed(
                    f"bypbase{i}",
                    lambda i=i: phase(f"bypbase{i}", rate_=byp_rate,
                                      seconds=byp_dur)))
                byps.append(await attributed(
                    f"bypload{i}",
                    lambda i=i: measured_round(f"bypload{i}",
                                               _byp_call, byp_stats)))
                rpcs.append(await attributed(
                    f"rpcload{i}",
                    lambda i=i: measured_round(f"rpcload{i}",
                                               _rpc_call, rpc_stats)))
            out["bypass_from_replica"] = {
                "replica_process": victim,
                "leader_process": leader_name,
                "analytics_rows": n_a,
                "scan_every_s": scan_every_s,
                "rounds": 3,
                "bypass_scan_rounds": byp_stats["rounds"],
                "bypass_scan_errors": byp_stats["errors"],
                "scan_stats": byp_stats["last"],
                **({"scan_last_error": byp_stats["last_error"]}
                   if byp_stats["last_error"] else {}),
                "rpc_scan_rounds": rpc_stats["rounds"],
                "p99_ms_no_scan": med(bases, "p99_ms"),
                "p99_ms_with_bypass": med(byps, "p99_ms"),
                "p99_ms_with_rpc_scans": med(rpcs, "p99_ms"),
                "p99_ms_rounds": {
                    "base": [r["p99_ms"] for r in bases],
                    "bypass": [r["p99_ms"] for r in byps],
                    "rpc": [r["p99_ms"] for r in rpcs]},
                # max/median of each side's round p99s: flush-pause
                # luck swung this ~20x before async flush; the PR-11
                # acceptance bar is <= 3x (WARN-wired as
                # cluster_p99_spread — the worst side)
                "cluster_p99_spread": max(
                    round(max(vals) / max(sorted(vals)[len(vals) // 2],
                                          1e-9), 3)
                    for vals in ([r["p99_ms"] for r in bases],
                                 [r["p99_ms"] for r in byps],
                                 [r["p99_ms"] for r in rpcs])),
                "write_lane_no_scan": bases[-1],
                "write_lane_with_bypass": byps[-1],
                "write_lane_with_rpc_scans": rpcs[-1],
                # bypass from a real replica process vs the same
                # aggregate through the leader's hot path: the p99
                # impact ratios the ROADMAP bypass item (c) asks for
                # (medians across rounds; p95 twin recorded for the
                # noise floor on this 2-core box)
                "cluster_bypass_p99_impact": round(
                    med(byps, "p99_ms")
                    / max(med(bases, "p99_ms"), 1e-9), 3),
                "rpc_scan_p99_impact": round(
                    med(rpcs, "p99_ms")
                    / max(med(bases, "p99_ms"), 1e-9), 3),
                "cluster_bypass_p95_impact": round(
                    med(byps, "p95_ms")
                    / max(med(bases, "p95_ms"), 1e-9), 3),
                "rpc_scan_p95_impact": round(
                    med(rpcs, "p95_ms")
                    / max(med(bases, "p95_ms"), 1e-9), 3)}
            # every round whose p99 exceeds the 3x spread gate gets
            # its dominant wait state (flush/fsync/queue/compile/
            # lock/cpu) — the ISSUE 14 acceptance key
            out["cluster_p99_attribution"] = attribute_rounds(
                attr_rounds, spread_gate=3.0)
            return out
        finally:
            await sup.shutdown()

    try:
        return asyncio.run(run())
    except Exception as e:   # noqa: BLE001 — report, don't fail bench
        if os.environ.get("BENCH_DEBUG"):
            raise
        return {"error": str(e)[:300]}


def bypass_scan_bench():
    """Analytics bypass under live point-write fire: a 2x-saturation
    open-loop YCSB point-WRITE load rides the real RPC path while Q6
    aggregate scans run (a) through the tserver hot path and (b)
    through the SST-direct bypass engine from a plain worker thread.
    Reports both scan rates, the write-lane p99 with and without the
    bypass running (the isolation claim: bypass load must not queue on
    the event loop — `bypass_p99_impact` is WARN-wired), the keyless-
    scan counter, and the prefilter selectivity split.
    BENCH_BYPASS_S=0 skips."""
    import asyncio
    import threading

    duration = float(os.environ.get("BENCH_BYPASS_S", "2.5"))
    if duration <= 0:
        return None
    sf = float(os.environ.get("BENCH_BYPASS_SF", "0.05"))

    async def run():
        from yugabyte_db_tpu.bypass import BypassSession
        from yugabyte_db_tpu.docdb.operations import (
            ReadRequest, RowOp, WriteRequest)
        from yugabyte_db_tpu.docdb.wire import (
            read_request_to_wire, write_request_to_wire)
        from yugabyte_db_tpu.models.tpch import (
            TPCH_Q6, generate_lineitem, lineitem_range_info,
            numpy_reference)
        from yugabyte_db_tpu.models.ycsb import usertable_info
        from yugabyte_db_tpu.rpc.messenger import Messenger, RpcError
        from yugabyte_db_tpu.storage.columnar import KEY_REBUILD_STATS

        data = generate_lineitem(sf)
        n_li = len(data["rowid"])
        q6_ref = numpy_reference(TPCH_Q6, data)
        n_rows = 10000
        mc = await __import__(
            "yugabyte_db_tpu.tools.mini_cluster",
            fromlist=["MiniCluster"]).MiniCluster(
                tempfile.mkdtemp(prefix="ybtpu-byp-"),
                num_tservers=1).start()
        conns = []
        try:
            c = mc.client()
            await c.create_table(usertable_info(), num_tablets=1,
                                 replication_factor=1)
            await c.create_table(lineitem_range_info(), num_tablets=1,
                                 replication_factor=1)
            await mc.wait_for_leaders("usertable")
            await mc.wait_for_leaders("lineitem_r")
            await c.insert("usertable", [
                {"ycsb_key": i,
                 **{f"field{j}": "x" * 100 for j in range(10)}}
                for i in range(n_rows)])
            # the analytics shard: bulk-loaded straight into the peer's
            # tablet (the local-replica shape the bypass engine reads)
            ts = mc.tservers[0]
            li_peer = next(p for p in ts.peers.values()
                           if p.tablet.info.name == "lineitem_r")
            li_peer.tablet.bulk_load(data, block_rows=65536)
            uct = await c._table("usertable")
            uloc = uct.locations[0]
            lct = await c._table("lineitem_r")
            lloc = lct.locations[0]
            addr = uloc.leader_addr()
            conns = [Messenger(f"byp-{i}") for i in range(32)]
            rng = np.random.default_rng(3)

            def wr_payload():
                k = int(rng.integers(0, n_rows))
                return {"tablet_id": uloc.tablet_id,
                        "req": write_request_to_wire(WriteRequest(
                            uct.info.table_id, ops=[RowOp("upsert", {
                                "ycsb_key": k,
                                **{f"field{j}": "y" * 100
                                   for j in range(10)}})]))}

            scan_req = {"tablet_id": lloc.tablet_id,
                        "req": read_request_to_wire(ReadRequest(
                            lct.info.table_id, where=TPCH_Q6.where,
                            aggregates=TPCH_Q6.aggs))}

            async def write_closed(dur, workers=32):
                stop = time.perf_counter() + dur
                count = 0

                async def w(i):
                    nonlocal count
                    m = conns[i % len(conns)]
                    while time.perf_counter() < stop:
                        await m.call(addr, "tserver", "write",
                                     wr_payload(), timeout=30.0)
                        count += 1
                await asyncio.gather(*[w(i) for i in range(workers)])
                return count / dur

            async def write_open(rate, dur):
                lat, tasks = [], []
                dropped = 0

                async def one(i):
                    nonlocal dropped
                    m = conns[i % len(conns)]
                    t0 = time.perf_counter()
                    try:
                        await m.call(addr, "tserver", "write",
                                     wr_payload(), timeout=2.0)
                        lat.append(time.perf_counter() - t0)
                    except (asyncio.TimeoutError, RpcError, OSError):
                        dropped += 1
                total = int(rate * dur)
                interval = 1.0 / rate
                t_start = time.perf_counter()
                for i in range(total):
                    due = t_start + i * interval
                    now = time.perf_counter()
                    if now < due:
                        await asyncio.sleep(due - now)
                    tasks.append(asyncio.ensure_future(one(i)))
                await asyncio.gather(*tasks)
                lat_ms = sorted(x * 1e3 for x in lat)

                def pct(q):
                    if not lat_ms:
                        return 0.0
                    return lat_ms[min(len(lat_ms) - 1,
                                      int(q * len(lat_ms)))]
                return {"achieved_ops_per_s": round(
                            len(lat) / max(dur, 1e-9), 1),
                        "dropped": dropped,
                        "p50_ms": round(pct(0.5), 2),
                        "p99_ms": round(pct(0.99), 2)}

            async def rpc_scans_under_load(rate, dur):
                """Q6 RPCs through the tserver while the write load
                runs: the hot-path scan rate the bypass is measured
                against."""
                done = {"scans": 0}

                async def scanner():
                    m = conns[0]
                    stop = time.perf_counter() + dur
                    while time.perf_counter() < stop:
                        await m.call(addr, "tserver", "read", scan_req,
                                     timeout=30.0)
                        done["scans"] += 1
                wr_task = asyncio.ensure_future(write_open(rate, dur))
                await scanner()
                wr = await wr_task
                return done["scans"], wr

            def bypass_loop(dur, out):
                # a parity failure here must surface as THE bench
                # error, not launder into a zero-throughput number
                try:
                    t_end = time.perf_counter() + dur
                    scans = 0
                    # the peer form: pin waits on MVCC safe time,
                    # exactly what a consensus-served shard requires
                    with BypassSession([li_peer]) as s:
                        while time.perf_counter() < t_end:
                            outs, _cnt, st = s.scan_aggregate(
                                TPCH_Q6.where, TPCH_Q6.aggs, None)
                            rel = abs(float(outs[0]) - q6_ref) \
                                / max(abs(q6_ref), 1e-9)
                            assert rel < 1e-5, \
                                f"bypass q6 mismatch {rel}"
                            scans += 1
                        out.update(scans=scans, stats=st,
                                   session=s.stats())
                except BaseException as e:   # noqa: BLE001 — re-raised
                    out["error"] = repr(e)   # by the caller

            # warm both paths (compiles) before any timed round
            await conns[0].call(addr, "tserver", "read", scan_req,
                                timeout=60.0)
            warm = {}
            bypass_loop(0.1, warm)
            sat = await write_closed(1.0)
            rate = 2 * sat
            # round A: write load alone (the p99 baseline)
            alone = await write_open(rate, duration)
            # round B: write load + hot-path RPC scans
            rpc_scans, wr_rpc = await rpc_scans_under_load(rate, duration)
            # round C: write load + bypass scans on a worker thread
            bp_out = {}
            r0 = KEY_REBUILD_STATS["rebuilds"]
            th = threading.Thread(target=bypass_loop,
                                  args=(duration, bp_out))
            th.start()
            with_bp = await write_open(rate, duration)
            th.join(60)
            if "error" in bp_out:
                raise RuntimeError(
                    f"bypass scan thread failed: {bp_out['error']}")
            st = bp_out.get("stats", {})
            sess = bp_out.get("session", {})
            pf_in = st.get("prefilter_rows_in", 0)
            pf_kept = st.get("prefilter_rows_kept", 0)
            return {
                "lineitem_rows": n_li,
                "write_saturation_ops_per_s": round(sat, 1),
                "offered_write_ops_per_s": round(rate, 1),
                "write_alone": alone,
                "write_with_rpc_scans": wr_rpc,
                "write_with_bypass": with_bp,
                "hotpath_scan_rows_per_s": round(
                    rpc_scans * n_li / duration, 1),
                "bypass_scan_rows_per_s": round(
                    bp_out.get("scans", 0) * n_li / duration, 1),
                "bypass_vs_hotpath": round(
                    bp_out.get("scans", 0) / max(rpc_scans, 1e-9), 3),
                "bypass_p99_impact": round(
                    with_bp["p99_ms"] / max(alone["p99_ms"], 1e-9), 3),
                "keyless_blocks": sess.get("keyless_blocks"),
                "blocks": sess.get("blocks"),
                "key_rebuilds": KEY_REBUILD_STATS["rebuilds"] - r0,
                "prefilter_selectivity": round(
                    pf_kept / max(pf_in, 1), 4) if pf_in else None,
                "prefilter_rows_in": pf_in,
                "prefilter_rows_kept": pf_kept,
            }
        finally:
            for m in conns:
                await m.shutdown()
            await mc.shutdown()

    try:
        return asyncio.run(run())
    except Exception as e:   # noqa: BLE001 — report, don't fail bench
        return {"error": str(e)[:200]}


def matview_bench():
    """Incremental materialized views under live write fire (matview/):
    N registered GROUP BY views fold the CDC stream while a
    2x-saturation open-loop point-write load rides the RPC path.
    Reports the view-staleness p50/p99 sampled through the round, the
    write-lane p99 with and without the maintainers running
    (`matview_p99_impact` — informational, the maintainers share the
    client event loop), and the headline `matview_vs_rescan` ratio:
    serving the freshest answer from the maintained partials vs
    re-answering the same GROUP BY with a full grouped rescan per
    read — WARN-wired, incremental must WIN (> 1).
    BENCH_MATVIEW_S bounds the round (0 skips); BENCH_MATVIEW_ROWS
    sizes the base table; BENCH_MATVIEW_VIEWS sets N."""
    import asyncio

    duration = float(os.environ.get("BENCH_MATVIEW_S", "2.5"))
    if duration <= 0:
        return None
    n_rows = int(os.environ.get("BENCH_MATVIEW_ROWS", "20000"))
    n_views = int(os.environ.get("BENCH_MATVIEW_VIEWS", "3"))
    n_groups = 16

    async def run():
        from yugabyte_db_tpu.docdb.operations import (
            ReadRequest, RowOp, WriteRequest)
        from yugabyte_db_tpu.docdb.table_codec import TableInfo
        from yugabyte_db_tpu.docdb.wire import write_request_to_wire
        from yugabyte_db_tpu.dockv.packed_row import (
            ColumnSchema, ColumnType, TableSchema)
        from yugabyte_db_tpu.dockv.partition import PartitionSchema
        from yugabyte_db_tpu.matview import ViewDef
        from yugabyte_db_tpu.ops.scan import AggSpec, HashGroupSpec
        from yugabyte_db_tpu.rpc.messenger import Messenger, RpcError
        from yugabyte_db_tpu.tools.mini_cluster import MiniCluster

        schema = TableSchema(columns=(
            ColumnSchema(0, "k", ColumnType.INT64, is_hash_key=True),
            ColumnSchema(1, "g", ColumnType.INT64),
            ColumnSchema(2, "v", ColumnType.INT64),
        ), version=1)
        info = TableInfo("", "kv", schema, PartitionSchema("hash", 1))
        mc = await MiniCluster(tempfile.mkdtemp(prefix="ybtpu-mv-"),
                               num_tservers=1).start()
        conns = []
        try:
            c = mc.client()
            await c.create_table(info, num_tablets=1,
                                 replication_factor=1)
            await mc.wait_for_leaders("kv")
            rng = np.random.default_rng(11)
            for lo in range(0, n_rows, 2000):
                await c.insert("kv", [
                    {"k": i, "g": i % n_groups,
                     "v": int(rng.integers(0, 1 << 20))}
                    for i in range(lo, min(lo + 2000, n_rows))])

            # N views over the same stream: plain partials, MIN/MAX
            # (the retraction/re-scan path), and a filtered slice
            defs = [
                ViewDef("mv_sum", "kv", "", ["g"],
                        [("count", None, "cnt"),
                         ("sum", ("col", "v"), "total")]),
                ViewDef("mv_mm", "kv", "", ["g"],
                        [("min", ("col", "v"), "lo"),
                         ("max", ("col", "v"), "hi")]),
                ViewDef("mv_flt", "kv", "", ["g"],
                        [("count", None, "cnt"),
                         ("sum", ("col", "v"), "total")],
                        where=("cmp", "ge", ("col", "v"),
                               ("const", 1 << 19))),
            ][:n_views]
            mts = [await c.matviews().create(vd) for vd in defs]

            ct = await c._table("kv")
            loc = ct.locations[0]
            addr = loc.leader_addr()
            conns = [Messenger(f"mv-{i}") for i in range(32)]

            def wr_payload():
                k = int(rng.integers(0, n_rows))   # updates: retraction
                return {"tablet_id": loc.tablet_id,
                        "req": write_request_to_wire(WriteRequest(
                            ct.info.table_id, ops=[RowOp("upsert", {
                                "k": k, "g": k % n_groups,
                                "v": int(rng.integers(0, 1 << 20))})]))}

            async def write_closed(dur, workers=32):
                stop = time.perf_counter() + dur
                done = [0]

                async def w(i):
                    m = conns[i % len(conns)]
                    while time.perf_counter() < stop:
                        try:
                            await m.call(addr, "tserver", "write",
                                         wr_payload(), timeout=2.0)
                            done[0] += 1
                        except (asyncio.TimeoutError, RpcError, OSError):
                            pass
                await asyncio.gather(*[w(i) for i in range(workers)])
                return done[0] / max(dur, 1e-9)

            async def write_open(rate, dur, sample_staleness=False):
                lat, tasks, staleness = [], [], []
                dropped = 0

                async def one(i):
                    nonlocal dropped
                    m = conns[i % len(conns)]
                    t0 = time.perf_counter()
                    try:
                        await m.call(addr, "tserver", "write",
                                     wr_payload(), timeout=2.0)
                        lat.append(time.perf_counter() - t0)
                    except (asyncio.TimeoutError, RpcError, OSError):
                        dropped += 1
                total = int(rate * dur)
                interval = 1.0 / rate
                t_start = time.perf_counter()
                for i in range(total):
                    due = t_start + i * interval
                    now = time.perf_counter()
                    if now < due:
                        await asyncio.sleep(due - now)
                    if sample_staleness and i % 25 == 0:
                        staleness.extend(mt.staleness_ms()
                                         for mt in mts)
                    tasks.append(asyncio.ensure_future(one(i)))
                await asyncio.gather(*tasks)
                lat_ms = sorted(x * 1e3 for x in lat)

                def pct(vals, q):
                    if not vals:
                        return 0.0
                    vals = sorted(vals)
                    return vals[min(len(vals) - 1, int(q * len(vals)))]
                out = {"achieved_ops_per_s": round(
                           len(lat) / max(dur, 1e-9), 1),
                       "dropped": dropped,
                       "p50_ms": round(pct(lat_ms, 0.5), 2),
                       "p99_ms": round(pct(lat_ms, 0.99), 2)}
                if sample_staleness:
                    finite = [s for s in staleness
                              if s != float("inf")]
                    out["staleness_p50_ms"] = round(
                        pct(finite, 0.5), 2)
                    out["staleness_p99_ms"] = round(
                        pct(finite, 0.99), 2)
                return out

            sat = await write_closed(1.0)
            rate = 2 * sat
            # round A: maintainers quiesced — the write-p99 baseline
            for mt in mts:
                await mt.stop()
            alone = await write_open(rate, duration)
            # round B: maintainers folding live
            for mt in mts:
                mt.start()
            with_mv = await write_open(rate, duration,
                                       sample_staleness=True)

            # incremental serve vs repeated full grouped rescan: the
            # view answers at its watermark after folding ONE delta;
            # the rescan re-answers the identical GROUP BY from scratch
            vd0, mt0 = defs[0], mts[0]
            for mt in mts[1:]:
                await mt.stop()          # isolate the measured view
            gspec = HashGroupSpec(cols=(1,))
            aggs = (AggSpec("count"), AggSpec("sum", ("col", 2)))
            reads = int(os.environ.get("BENCH_MATVIEW_READS", "15"))
            # drain round B's fold backlog first: the measured reads
            # time the steady state (fold ONE delta, serve), not the
            # overload recovery
            await c.matviews().read_rows(vd0.name, max_staleness_ms=0.0)
            t0 = time.perf_counter()
            for _ in range(reads):
                await conns[0].call(addr, "tserver", "write",
                                    wr_payload(), timeout=2.0)
                await c.matviews().read_rows(
                    vd0.name, max_staleness_ms=0.0)
            t_inc = time.perf_counter() - t0
            t0 = time.perf_counter()
            for _ in range(reads):
                await conns[0].call(addr, "tserver", "write",
                                    wr_payload(), timeout=2.0)
                await c.scan("kv", ReadRequest(
                    "", aggregates=aggs, group_by=gspec))
            t_rescan = time.perf_counter() - t0

            stats = {vd.name: {k: st[k] for k in
                               ("txns_applied", "rows_added",
                                "rows_retracted", "minmax_rescans",
                                "budget_exceeded", "full_rescans")}
                     for vd, st in ((vd, c.matviews().stats(vd.name))
                                    for vd in defs)}
            return {
                "views": len(defs), "base_rows": n_rows,
                "write_saturation_ops_per_s": round(sat, 1),
                "offered_write_ops_per_s": round(rate, 1),
                "write_alone": alone,
                "write_with_matviews": with_mv,
                "matview_p99_impact": round(
                    with_mv["p99_ms"] / max(alone["p99_ms"], 1e-9), 3),
                "staleness_p50_ms": with_mv.pop("staleness_p50_ms"),
                "staleness_p99_ms": with_mv.pop("staleness_p99_ms"),
                "incremental_read_ms": round(t_inc * 1e3 / reads, 2),
                "rescan_read_ms": round(t_rescan * 1e3 / reads, 2),
                "matview_vs_rescan": round(t_rescan / max(t_inc, 1e-9),
                                           3),
                "maintainer_stats": stats,
            }
        finally:
            try:
                await c.matviews().stop()
            except Exception:
                pass
            for m in conns:
                await m.shutdown()
            await mc.shutdown()

    try:
        return asyncio.run(run())
    except Exception as e:   # noqa: BLE001 — report, don't fail bench
        return {"error": str(e)[:200]}


def tpch_bypass_bench(data, repeats):
    """TPC-H Q1/Q6 routed through ``client.scan_bypass`` (ROADMAP
    bypass item (e)): the SAME lineitem rows served from a one-tserver
    mini cluster, each query measured over the RPC hot path
    (``client.scan``) and the SST-direct bypass engine back-to-back,
    so the headline q6/q1 blocks report a bypass column by default and
    ``bypass_vs_hotpath`` regression-WARNs like any other ratio.
    BENCH_TPCH_BYPASS=0 skips (the column then reads "skipped")."""
    import asyncio

    if os.environ.get("BENCH_TPCH_BYPASS", "1") == "0":
        return None

    async def run():
        from yugabyte_db_tpu.docdb.operations import ReadRequest
        from yugabyte_db_tpu.models.tpch import (
            TPCH_Q6, lineitem_range_info, lineitem_str_data,
            lineitem_str_info, numpy_reference, tpch_q1_str)
        from yugabyte_db_tpu.utils import flags

        n_li = len(data["rowid"])
        mc = await __import__(
            "yugabyte_db_tpu.tools.mini_cluster",
            fromlist=["MiniCluster"]).MiniCluster(
                tempfile.mkdtemp(prefix="ybtpu-tpchbp-"),
                num_tservers=1).start()
        try:
            c = mc.client()
            # q6 scans the numeric range-sharded clone; q1 scans the
            # STRING-keyed clone through the dict-grouped kernel, so
            # the bypass column exercises the group-keyed partial
            # combine (ops/scan.combine_grouped_partials) on BOTH the
            # hot-path client fan-out and the bypass session
            ts = mc.tservers[0]
            peers = {}
            for info, rows in ((lineitem_range_info(), data),
                               (lineitem_str_info(),
                                lineitem_str_data(data))):
                await c.create_table(info, num_tablets=1,
                                     replication_factor=1)
                await mc.wait_for_leaders(info.name)
                peer = next(p for p in ts.peers.values()
                            if p.tablet.info.name == info.name)
                peer.tablet.bulk_load(rows, block_rows=65536)
                peers[info.name] = peer
            c.set_bypass_provider(
                lambda table: [peers[table]] if table in peers
                else None)
            flags.set_flag("bypass_reader_enabled", True)
            out = {}
            rounds = max(2, repeats // 2)
            q1s = tpch_q1_str()
            for q, tab in ((TPCH_Q6, "lineitem_r"),
                           (q1s, "lineitem_s")):
                def req():
                    return ReadRequest("", where=q.where,
                                       aggregates=q.aggs,
                                       group_by=q.group)
                hot_warm = await c.scan(tab, req())
                byp_warm = await c.scan_bypass(tab, req())
                assert c.last_bypass["used"], (
                    f"{q.name}: bypass fell back "
                    f"({c.last_bypass['reason']})")
                # parity: q6 vs direct numpy; q1 bypass-vs-hotpath BY
                # GROUP KEY (slot order vs first-seen order differ; the
                # byte-level parity proof lives in tests/ — this guards
                # the BENCH wiring, and a mismatch must fail the bench)
                if q.name == "q6":
                    ref = numpy_reference(q, data)
                    got = float(byp_warm.agg_values[0])
                    assert abs(got - ref) / max(abs(ref), 1e-9) < 1e-5, \
                        f"bypass q6 mismatch: {got} vs {ref}"
                else:
                    def keyed(resp):
                        cnt = np.asarray(resp.group_counts)
                        return {
                            tuple(str(v[g]) for v in resp.group_values):
                            (int(cnt[g]),) + tuple(
                                float(np.asarray(v)[g])
                                for v in resp.agg_values)
                            for g in np.nonzero(cnt)[0]}
                    hk, bk = keyed(hot_warm), keyed(byp_warm)
                    assert set(hk) == set(bk), (hk.keys(), bk.keys())
                    for k in hk:
                        assert hk[k][0] == bk[k][0], f"{k} count"
                        assert np.allclose(hk[k][1:], bk[k][1:],
                                           rtol=1e-5), (k, hk[k], bk[k])
                    # grouped bypass stays keyless: zero key-matrix
                    # rebuilds across warm-up AND the timed rounds
                    # (counter-asserted again below)
                # PAIRED rounds (hot, bypass back-to-back) so driver-box
                # contention cancels in the ratio, as in the main loop
                pairs = []
                for _ in range(rounds):
                    t0 = time.perf_counter()
                    await c.scan(tab, req())
                    hot_t = time.perf_counter() - t0
                    t0 = time.perf_counter()
                    await c.scan_bypass(tab, req())
                    pairs.append((hot_t, time.perf_counter() - t0))
                hot_t = min(h for h, _ in pairs)
                byp_t = min(b for _, b in pairs)
                st = c.last_bypass["stats"] or {}
                key_rebuilds = st.get("key_rebuilds", 0)
                if q.name == "q1_str":
                    # the keyless contract, counter-asserted in the
                    # bench too: grouped bypass must never rebuild a
                    # key matrix (bypass-session-scoped counter)
                    assert key_rebuilds == 0, \
                        f"grouped bypass rebuilt {key_rebuilds} key " \
                        "matrices — the keyless contract broke"
                out["q1" if q.name == "q1_str" else q.name] = {
                    "hotpath_rows_per_s": round(n_li / hot_t, 1),
                    "bypass_rows_per_s": round(n_li / byp_t, 1),
                    # best-of-N over best-of-N, consistent with the
                    # rows/s columns above (a max() of per-pair ratios
                    # would let one stalled hot round mask a real
                    # bypass regression from the WARN tail)
                    "bypass_vs_hotpath": round(hot_t / byp_t, 3),
                    "keyless_blocks": st.get("keyless_blocks"),
                    "blocks": st.get("blocks"),
                    **({"grouped_combine": "combine_grouped_partials",
                        "key_rebuilds": key_rebuilds}
                       if q.name == "q1_str" else {}),
                }
            return out
        finally:
            flags.REGISTRY.reset("bypass_reader_enabled")
            await mc.shutdown()

    try:
        return asyncio.run(run())
    except AssertionError:
        raise   # a parity mismatch IS a bench failure, not a column
    except Exception as e:   # noqa: BLE001 — report, don't fail bench
        return {"error": str(e)[:200]}


def q1_grouped_bench(data, repeats):
    """Dict-key GROUP BY on device vs the interpreted GROUP BY
    (ROADMAP operator-frontier rungs (b)+(d)): TPC-H Q1 over the
    string-keyed lineitem variant (l_returnflag/l_linestatus as real
    STRINGs), streamed end-to-end through the grouped-aggregation
    kernel, against the row-at-a-time interpreter that served every
    string GROUP BY before this PR (``grouped_pushdown_enabled=False``
    is byte-for-byte that path).  Also: the numpy CPU twin
    (ops/grouped_scan.grouped_aggregate_cpu — the parity oracle,
    recorded for the accelerator-box comparison, NOT a WARN ratio on
    this CPU-only image) and a group-cardinality sweep (4 -> 4096
    occupied slots) over synthetic dictionary-coded keys.

    The interpreter chews ~40k rows/s, so the comparison runs on a
    row-capped slice (BENCH_Q1G_ROWS, default 393216 = 6 chunks of
    65536) — both sides measure the SAME table, so the ratio is fair
    and the bench stays bounded."""
    from yugabyte_db_tpu.docdb.operations import ReadRequest
    from yugabyte_db_tpu.models.tpch import (lineitem_str_data,
                                             lineitem_str_info,
                                             numpy_reference,
                                             tpch_q1_str)
    from yugabyte_db_tpu.ops.grouped_scan import (GROUPED_STATS,
                                                  LAST_GROUPED_STATS,
                                                  DictGroupSpec,
                                                  decode_slot_groups,
                                                  grouped_aggregate_cpu,
                                                  make_dict_plan)
    from yugabyte_db_tpu.ops import Expr
    from yugabyte_db_tpu.ops.scan import AggSpec, ScanKernel
    from yugabyte_db_tpu.ops.stream_scan import streaming_scan_aggregate
    from yugabyte_db_tpu.tablet import Tablet
    from yugabyte_db_tpu.utils import flags

    n_g = min(len(data["rowid"]),
              int(os.environ.get("BENCH_Q1G_ROWS", str(6 * 65536))))
    sdata = lineitem_str_data({k: v[:n_g] for k, v in data.items()})
    t = Tablet("lineitem-s", lineitem_str_info(),
               tempfile.mkdtemp(prefix="ybtpu-q1g-"))
    t.bulk_load(sdata, block_rows=65536)
    q = tpch_q1_str()

    def req():
        return ReadRequest("lineitem_s", where=q.where,
                           aggregates=q.aggs, group_by=q.group)

    def by_key(resp):
        counts = np.asarray(resp.group_counts)
        out = {}
        for g in np.nonzero(counts)[0]:
            out[tuple(str(v[g]) for v in resp.group_values)] = \
                (int(counts[g]),) + tuple(
                    float(np.asarray(v)[g]) for v in resp.agg_values)
        return out

    flags.set_flag("streaming_chunk_rows", 65536)
    try:
        launches0 = GROUPED_STATS["launches"]
        grouped_warm = t.read(req())        # compile + warm
        assert grouped_warm.backend == "tpu", "grouped pushdown fell back"
        assert LAST_GROUPED_STATS.get("path") == "streaming", \
            f"expected the STREAMED grouped path, got {LAST_GROUPED_STATS}"
        # paired rounds: grouped and interpreted back-to-back, as in the
        # headline loop, so box contention cancels in the ratio
        rounds = max(2, repeats // 2)
        pairs = []
        for _ in range(rounds):
            t0 = time.perf_counter()
            gresp = t.read(req())
            g_t = time.perf_counter() - t0
            flags.set_flag("grouped_pushdown_enabled", False)
            try:
                t0 = time.perf_counter()
                iresp = t.read(req())
                i_t = time.perf_counter() - t0
            finally:
                flags.REGISTRY.reset("grouped_pushdown_enabled")
            assert iresp.backend == "cpu"
            pairs.append((g_t, i_t))
        gstats = dict(LAST_GROUPED_STATS)
        g_t = min(g for g, _ in pairs)
        i_t = min(i for _, i in pairs)
        # parity: device grouped vs interpreted, keyed by group values
        # (exact counts; l_quantity is integer-valued -> exact int64 SUM
        # lane; fractional price sums carry only f32 representation
        # error, same tolerance ladder as check_q1) — and vs numpy
        ga, ia = by_key(gresp), by_key(iresp)
        assert set(ga) == set(ia), (set(ga), set(ia))
        ref = numpy_reference(q, sdata)
        for k in ga:
            assert ga[k][0] == ia[k][0] == ref[k][2], f"{k} count"
            assert ga[k][1] == ia[k][1] == ref[k][0], f"{k} qty"
            assert abs(ga[k][2] - ref[k][1]) / max(ref[k][1], 1e-9) \
                < 1e-5, f"{k} price"

        # the numpy CPU twin on the same blocks (cold: its own dict plan)
        blocks = []
        for r in t.regular.ssts:
            for i in range(r.num_blocks()):
                blocks.append(r.columnar_block(i))
        cols = sorted(q.columns)

        def twin():
            return grouped_aggregate_cpu(blocks, cols, q.where, q.aggs,
                                         q.group)
        twin_t, (touts, tcounts, tspill) = best_of(twin, rounds)
        assert tspill == 0
        _, tc, tg = decode_slot_groups(
            q.group, make_dict_plan(blocks, q.group.cols).dicts,
            touts, tcounts)
        for i, k in enumerate(zip(*(map(str, g) for g in tg))):
            assert int(tc[i]) == ref[k][2], f"twin {k} count"

        out = {
            "rows": n_g,
            "grouped_rows_per_s": round(n_g / g_t, 1),
            "interp_rows_per_s": round(n_g / i_t, 1),
            "grouped_vs_interp": round(i_t / g_t, 3),
            "twin_rows_per_s": round(n_g / twin_t, 1),
            "vs_cpu_twin": round(twin_t / g_t, 3),
            "kernel_launches": GROUPED_STATS["launches"] - launches0,
            "spill_fallbacks": GROUPED_STATS["spill_fallbacks"],
            "stream_split": gstats,
        }

        # --- group-cardinality sweep: 4 -> 4096 occupied groups -------
        # synthetic dictionary-coded keys, one column per cardinality,
        # ONE table/load; each cardinality lands in its own pow2 slot
        # bucket (8 .. 8192 incl. the spill slot) = one compile each,
        # counted via the fresh kernel's own accounting
        from yugabyte_db_tpu.docdb.table_codec import TableInfo
        from yugabyte_db_tpu.dockv.packed_row import (ColumnSchema,
                                                      ColumnType,
                                                      TableSchema)
        from yugabyte_db_tpu.dockv.partition import PartitionSchema
        cards = [4, 64, 1024, 4096]
        n_sw = 262144
        rng = np.random.default_rng(7)
        sw_schema = TableSchema(
            (ColumnSchema(0, "k", ColumnType.INT64, is_hash_key=True),)
            + tuple(ColumnSchema(i + 1, f"g{c}", ColumnType.STRING)
                    for i, c in enumerate(cards))
            + (ColumnSchema(len(cards) + 1, "v", ColumnType.FLOAT64),),
            1)
        sw = Tablet("grpsweep", TableInfo(
            "grpsweep", "grpsweep", sw_schema,
            PartitionSchema("hash", 1)),
            tempfile.mkdtemp(prefix="ybtpu-q1gsw-"))
        sw.bulk_load({
            "k": np.arange(n_sw, dtype=np.int64),
            **{f"g{c}": np.array([f"g{i:04d}" for i in range(c)],
                                 object)[rng.integers(0, c, n_sw)]
               for c in cards},
            "v": rng.integers(1, 100, n_sw).astype(np.float64),
        }, block_rows=32768)
        sw_blocks = []
        for r in sw.regular.ssts:
            for i in range(r.num_blocks()):
                sw_blocks.append(r.columnar_block(i))
        skern = ScanKernel()
        sweep = {}
        for i, c in enumerate(cards):
            spec = DictGroupSpec(cols=(i + 1,), max_slots=8192)
            aggs = (AggSpec("sum", Expr.col(len(cards) + 1).node),
                    AggSpec("count"))

            def srun():
                gout = {}
                got = streaming_scan_aggregate(
                    sw_blocks, [i + 1, len(cards) + 1], None, aggs,
                    spec, None, kernel=skern, chunk_rows=32768,
                    grouped_out=gout)
                assert got is not None and gout["spill"] == 0
                return got
            srun()      # compile this slot bucket
            sw_t, _ = best_of(srun, rounds)
            sweep[str(c)] = {
                "rows_per_s": round(n_sw / sw_t, 1),
                "num_slots": LAST_GROUPED_STATS["num_slots"],
                "slots_occupied": LAST_GROUPED_STATS["slots_occupied"],
                "dict_merge_s": LAST_GROUPED_STATS["dict_merge_s"],
                "kernel_s": LAST_GROUPED_STATS["kernel_s"],
            }
        out["cardinality_sweep"] = sweep
        out["sweep_compiles"] = skern.compiles
        return out
    finally:
        flags.REGISTRY.reset("streaming_chunk_rows")


def tpch_join_bench(data, repeats):
    """Device hash join + fused plans (ROADMAP operator-ladder rung
    (c)): a TPC-H Q3/Q5-shaped join+group query — lineitem JOIN orders
    ON l_orderkey = o_orderkey, grouped by the o_orderpriority string
    payload — measured three ways on the SAME table:

      fused        ONE device program per plan signature
                   (filter -> probe -> gather -> group -> aggregate,
                   ops/plan_fusion.py, streamed pow2 chunks)
      per-operator each operator its own program + host round-trip:
                   device filter-pushdown ROW scan materializes the
                   matching probe rows, then a host hash join + numpy
                   group-aggregate (the operator-at-a-time path the
                   fused plan replaces)
      interpreted  join_pushdown_enabled=False — the row-at-a-time
                   CPU join, byte-for-byte the pre-device semantics

    Correctness asserts against direct numpy; the plan-kernel compile
    count is ASSERTED flat across repeated runs AND across a 2x data
    growth at the same plan shape (the pow2-bucket contract).  Row cap
    BENCH_JOIN_ROWS (default 4 chunks of 32768) keeps the interpreted
    leg bounded."""
    from yugabyte_db_tpu.docdb.operations import ReadRequest
    from yugabyte_db_tpu.models.tpch import (PRIO_STRINGS,
                                             generate_orders,
                                             lineitem_join_data,
                                             lineitem_join_info,
                                             numpy_reference_join,
                                             orders_build_wire,
                                             tpch_q3ish)
    from yugabyte_db_tpu.ops.join_scan import (LAST_JOIN_STATS,
                                               hash_join_cpu)
    from yugabyte_db_tpu.ops.plan_fusion import (LAST_PLAN_STATS,
                                                 default_plan_kernel)
    from yugabyte_db_tpu.tablet import Tablet
    from yugabyte_db_tpu.utils import flags

    n_j = min(len(data["rowid"]),
              int(os.environ.get("BENCH_JOIN_ROWS", str(4 * 32768))))
    n_orders = max(n_j // 4, 1)
    odata = generate_orders(n_orders)
    ldata = lineitem_join_data({k: v[:n_j] for k, v in data.items()},
                               n_orders)
    q = tpch_q3ish()
    wire = orders_build_wire(q, odata)
    t = Tablet("lineitem-j", lineitem_join_info(),
               tempfile.mkdtemp(prefix="ybtpu-join-"))
    t.bulk_load(ldata, block_rows=32768)
    flags.set_flag("streaming_chunk_rows", 32768)
    kern = default_plan_kernel()

    def req():
        return ReadRequest("lineitem_j", where=q.probe_where,
                           aggregates=q.aggs, group_by=q.group,
                           join=wire)

    def by_key(resp):
        counts = np.asarray(resp.group_counts)
        return {str(resp.group_values[0][g]):
                (int(counts[g]), float(np.asarray(resp.agg_values[0])[g]))
                for g in np.nonzero(counts)[0]}

    try:
        fused_warm = t.read(req())          # compile + warm
        assert fused_warm.backend == "tpu", "fused join fell back"
        assert LAST_PLAN_STATS.get("path") == "streaming", \
            LAST_PLAN_STATS
        compiles_warm = kern.compiles
        ref = numpy_reference_join(q, ldata, odata)
        fk = by_key(fused_warm)
        for p in PRIO_STRINGS:
            want_c, want_rev = ref[p]
            if want_c == 0:
                assert p not in fk
                continue
            assert fk[p][0] == want_c, (p, fk[p], ref[p])
            assert abs(fk[p][1] - want_rev) / max(want_rev, 1e-9) \
                < 1e-5, (p, fk[p], ref[p])

        # --- per-operator: device row filter, host join+group ---------
        probe_cols = ("l_extendedprice", "l_discount", "l_orderkey")

        def per_operator():
            rows = t.read(ReadRequest(
                "lineitem_j", columns=probe_cols,
                where=q.probe_where)).rows
            ok = np.asarray([r["l_orderkey"] for r in rows], np.int64)
            price = np.asarray([r["l_extendedprice"] for r in rows])
            disc = np.asarray([r["l_discount"] for r in rows])
            midx = hash_join_cpu(ok, np.asarray(wire.keys))
            m = midx >= 0
            prio = np.asarray(wire.payload[list(wire.payload)[0]][0],
                              object)[np.clip(midx, 0, None)]
            rev = price * (1.0 - disc)
            return {p: (int((m & (prio == p)).sum()),
                        float(rev[m & (prio == p)].sum()))
                    for p in PRIO_STRINGS}
        op_warm = per_operator()
        for p in PRIO_STRINGS:
            assert op_warm[p][0] == ref[p][0], (p, op_warm[p], ref[p])

        # paired rounds: fused / per-operator / interpreted
        # back-to-back so box contention cancels in the ratios
        rounds = max(2, repeats // 2)
        trip = []
        for _ in range(rounds):
            t0 = time.perf_counter()
            t.read(req())
            f_t = time.perf_counter() - t0
            t0 = time.perf_counter()
            per_operator()
            o_t = time.perf_counter() - t0
            flags.set_flag("join_pushdown_enabled", False)
            try:
                t0 = time.perf_counter()
                iresp = t.read(req())
                i_t = time.perf_counter() - t0
            finally:
                flags.REGISTRY.reset("join_pushdown_enabled")
            assert iresp.backend == "cpu"
            trip.append((f_t, o_t, i_t))
        f_t = min(x for x, _, _ in trip)
        o_t = min(x for _, x, _ in trip)
        i_t = min(x for _, _, x in trip)
        ik = by_key(iresp)
        assert set(ik) == set(fk)
        for p in fk:
            assert fk[p][0] == ik[p][0], (p, fk[p], ik[p])

        # compile budget: repeated runs at the same plan shape compiled
        # NOTHING new...
        assert kern.compiles == compiles_warm, \
            "plan kernel recompiled at an unchanged plan shape"
        # ...and 2x data growth (same chunk bucket, same build bucket)
        # must not either
        n2 = min(len(data["rowid"]), 2 * n_j)
        ldata2 = lineitem_join_data(
            {k: v[:n2] for k, v in data.items()}, n_orders)
        t2 = Tablet("lineitem-j2", lineitem_join_info(),
                    tempfile.mkdtemp(prefix="ybtpu-join2-"))
        t2.bulk_load(ldata2, block_rows=32768)
        growth = t2.read(req())
        assert growth.backend == "tpu"
        assert kern.compiles == compiles_warm, \
            "plan kernel recompiled on data growth inside the bucket"

        return {
            "rows": n_j,
            "build_rows": int(LAST_PLAN_STATS.get("n_build", 0)),
            "build_slots": int(LAST_PLAN_STATS.get("num_slots", 0)),
            "fused_rows_per_s": round(n_j / f_t, 1),
            "per_operator_rows_per_s": round(n_j / o_t, 1),
            "interp_rows_per_s": round(n_j / i_t, 1),
            "fused_vs_interp": round(i_t / f_t, 3),
            "fused_vs_operator": round(o_t / f_t, 3),
            "plan_compiles": kern.compiles,
            "plan_launches": kern.launches,
            "plan_cache_hits": kern.cache_hits,
            "plan_signatures": len(kern.sig_compiles),
            "compiles_flat_across_growth": True,   # asserted above
            "build_table": dict(LAST_JOIN_STATS),
            "stage_split": {k: v for k, v in LAST_PLAN_STATS.items()
                            if k.endswith("_s") or k == "chunks"},
        }
    finally:
        flags.REGISTRY.reset("streaming_chunk_rows")


def tpch_full_bench(repeats):
    """The whole-query TPC-H gauntlet: EVERY query in the 22-query
    registry (models/tpch.py tpch_queries) through the device path —
    single-table scans and 2-stage fused join chains (lineitem_j ->
    orders_c -> customer, ONE program under one shared visibility
    mask) — with per-query compile budgets ASSERTED and per-query
    fused_vs_interp ratios WARN-wired like any other ratio.

    Inexpressible queries are REPORTED with their typed registry
    reason (table_coverage / subquery_shape / semi_join / outer_join /
    group_domain / expr_shape), never silently skipped.

    Scale: BENCH_TPCH_SF picks the scale factor — default 0.1 (the
    smoke gauntlet); the literal "full" uses the tpch_sf flag (default
    10, the SF10 acceptance gauntlet); 0 skips.  The device leg runs
    the full sf; the interpreted leg replays each query on a
    row-capped clone (BENCH_TPCH_INTERP_ROWS, default 262144) so the
    row-at-a-time baseline stays bounded, with device-vs-interpreted
    PARITY asserted on that same capped clone."""
    from yugabyte_db_tpu.docdb import operations as _ops
    from yugabyte_db_tpu.docdb.operations import ReadRequest
    from yugabyte_db_tpu.models.tpch import (CUSTOMERS_PER_SF,
                                             ORDERS_PER_SF,
                                             ROWS_PER_SF,
                                             _chain_group,
                                             chain_build_wires,
                                             generate_customer,
                                             generate_lineitem,
                                             generate_orders_cust,
                                             lineitem_join_data,
                                             lineitem_join_info,
                                             lineitem_str_data,
                                             lineitem_str_info,
                                             numpy_reference,
                                             numpy_reference_chain,
                                             tpch_queries)
    from yugabyte_db_tpu.ops.plan_fusion import (LAST_PLAN_STATS,
                                                 default_plan_kernel)
    from yugabyte_db_tpu.tablet import Tablet
    from yugabyte_db_tpu.utils import flags

    raw = os.environ.get("BENCH_TPCH_SF", "0.1")
    sf = float(flags.get("tpch_sf")) if raw == "full" else float(raw)
    if sf <= 0:
        return None
    n = int(ROWS_PER_SF * sf)
    n_orders = max(int(ORDERS_PER_SF * sf), 1)
    n_cust = max(int(CUSTOMERS_PER_SF * sf), 1)
    n_cap = min(n, int(os.environ.get("BENCH_TPCH_INTERP_ROWS",
                                      str(262144))))
    data = generate_lineitem(sf)
    ldata = lineitem_join_data(data, n_orders)
    odata = generate_orders_cust(n_orders, n_cust)
    cdata = generate_customer(n_cust)
    base = tempfile.mkdtemp(prefix="ybtpu-tpch-full-")
    block_rows = 65536
    t_j = Tablet("li-full-j", lineitem_join_info(), f"{base}/j")
    t_j.bulk_load(ldata, block_rows=block_rows)
    t_s = Tablet("li-full-s", lineitem_str_info(), f"{base}/s")
    t_s.bulk_load(lineitem_str_data(data), block_rows=block_rows)
    cap_l = {k: v[:n_cap] for k, v in ldata.items()}
    cap_d = {k: v[:n_cap] for k, v in data.items()}
    t_jc = Tablet("li-cap-j", lineitem_join_info(), f"{base}/jc")
    t_jc.bulk_load(cap_l, block_rows=32768)
    t_sc = Tablet("li-cap-s", lineitem_str_info(), f"{base}/sc")
    t_sc.bulk_load(lineitem_str_data(cap_d), block_rows=32768)
    flags.set_flag("streaming_chunk_rows", min(block_rows, 1 << 20))
    # chain build sides at TPC-H scale are FACT-sized (orders is
    # 1.5M/SF; q3 ships ~45% of them) — raise the build cap to the
    # pow2 hard maximum so the gauntlet measures the device path
    # instead of refusing it.  Bucket growth across SFs is exactly
    # what the plan signature absorbs (one compile per bucket).
    flags.set_flag("join_max_build_slots", 1 << 24)
    pkern = default_plan_kernel()
    skern = _ops._SHARED_KERNEL
    rounds = max(2, repeats // 2)

    def by_key(resp):
        counts = np.asarray(resp.group_counts)
        return {tuple(str(gv[g]) for gv in resp.group_values):
                (int(counts[g]),
                 float(np.asarray(resp.agg_values[0])[g]))
                for g in np.nonzero(counts)[0]}

    def run_query(e):
        q = e.spec
        if e.kind == "chain":
            wires = chain_build_wires(q, odata, cdata)
            tab, tab_cap = t_j, t_jc
            interp_flag = "join_pushdown_enabled"

            def req():
                return ReadRequest("lineitem_j", where=q.probe_where,
                                   aggregates=q.aggs,
                                   group_by=_chain_group(q.group_col),
                                   join=wires)
            ref = numpy_reference_chain(q, cap_l, odata, cdata)
        else:
            tab, tab_cap = ((t_s, t_sc) if q.name == "q1_str"
                            else (t_j, t_jc))
            interp_flag = "tpu_pushdown_enabled"

            def req():
                return ReadRequest(tab.info.name,
                                   where=q.where, aggregates=q.aggs,
                                   group_by=q.group)
            ref = numpy_reference(q, cap_d)

        # warm (compile) then timed rounds with the compile count
        # ASSERTED flat — the per-query compile budget
        warm = tab.read(req())
        assert warm.backend == "tpu", \
            f"{e.name}: device path fell back ({warm.backend})"
        c_p, c_s = pkern.compiles, skern.compiles
        best = None
        for _ in range(rounds):
            t0 = time.perf_counter()
            tab.read(req())
            dt = time.perf_counter() - t0
            best = dt if best is None else min(best, dt)
        assert pkern.compiles == c_p and skern.compiles == c_s, \
            f"{e.name}: recompiled at an unchanged plan shape"
        split = {k: v for k, v in LAST_PLAN_STATS.items()
                 if k.endswith("_s") or k in ("chunks", "join_stages",
                                              "num_slots")} \
            if e.kind == "chain" else {}

        # parity + fused_vs_interp on the capped clone (paired rounds)
        pairs = []
        for _ in range(rounds):
            t0 = time.perf_counter()
            dresp = tab_cap.read(req())
            d_t = time.perf_counter() - t0
            flags.set_flag(interp_flag, False)
            try:
                t0 = time.perf_counter()
                iresp = tab_cap.read(req())
                i_t = time.perf_counter() - t0
            finally:
                flags.REGISTRY.reset(interp_flag)
            pairs.append((d_t, i_t))
        assert dresp.backend == "tpu" and iresp.backend == "cpu", \
            (e.name, dresp.backend, iresp.backend)
        if e.kind == "chain" or q.group is not None:
            dk, ik = by_key(dresp), by_key(iresp)
            assert set(dk) == set(ik), (e.name, set(dk) ^ set(ik))
            for g in dk:
                assert dk[g][0] == ik[g][0], (e.name, g, dk[g], ik[g])
            want = ({(str(g),): c for g, (c, _) in ref.items() if c}
                    if e.kind == "chain" else None)
            if want is not None:
                assert {g: c for g, (c, _) in dk.items()} == want, \
                    (e.name, dk, want)
        else:
            dv = float(np.asarray(dresp.agg_values[0]))
            iv = float(np.asarray(iresp.agg_values[0]))
            assert abs(dv - iv) / max(abs(iv), 1e-9) < 1e-5, \
                (e.name, dv, iv)
            assert abs(dv - ref) / max(abs(ref), 1e-9) < 1e-5, \
                (e.name, dv, ref)
        return {
            "kind": e.kind, "note": e.note, "rows": n,
            "rows_per_s": round(n / best, 1),
            "interp_rows_per_s": round(n_cap / min(i for _, i in pairs),
                                       1),
            "fused_vs_interp": round(
                max(i / d for d, i in pairs), 3),
            "new_compiles_after_warm": 0,   # asserted above
            **({"stage_split": split} if split else {}),
        }

    out = {"sf": sf, "rows": n, "orders": n_orders,
           "customers": n_cust, "interp_cap_rows": n_cap,
           "queries": {}}
    try:
        for name, e in tpch_queries().items():
            if e.kind == "inexpressible":
                out["queries"][name] = {"inexpressible": e.reason,
                                        "note": e.note}
                continue
            out["queries"][name] = run_query(e)
        out["expressible"] = sorted(
            k for k, v in out["queries"].items()
            if "inexpressible" not in v)
        out["plan_compiles_per_signature"] = \
            sorted(pkern.sig_compiles.values())
    finally:
        flags.REGISTRY.reset("streaming_chunk_rows")
        flags.REGISTRY.reset("join_max_build_slots")
    return out


def trace_overhead_bench():
    """The observability layer must not tax the hot path it observes
    (ISSUE 14 acceptance: headline rates within 2% with tracing at
    default sampling).  Paired interleaved rounds through the REAL RPC
    path (MiniCluster): YCSB-shaped point read/write ops and a
    Q6-shaped aggregate scan, measured with trace_sampling_rate=0 vs
    the flag DEFAULT (plus the ASH sampler thread running, as in a
    real server).  `trace_ycsb_on_vs_off` / `trace_q6_on_vs_off` are
    best-of-round ratios WARN-wired below 0.98.  BENCH_TRACE_S=0
    skips."""
    import asyncio

    dur = float(os.environ.get("BENCH_TRACE_S", "1.0"))
    if dur <= 0:
        return None

    async def run():
        from yugabyte_db_tpu.docdb.operations import ReadRequest, RowOp
        from yugabyte_db_tpu.docdb.table_codec import TableInfo
        from yugabyte_db_tpu.dockv.packed_row import (
            ColumnSchema, ColumnType, TableSchema)
        from yugabyte_db_tpu.dockv.partition import PartitionSchema
        from yugabyte_db_tpu.ops.scan import AggSpec
        from yugabyte_db_tpu.tools.mini_cluster import MiniCluster
        from yugabyte_db_tpu.utils import flags as _flags
        from yugabyte_db_tpu.utils.trace import ASH

        info = TableInfo("", "tracebench", TableSchema(columns=(
            ColumnSchema(0, "k", ColumnType.INT64, is_hash_key=True),
            ColumnSchema(1, "v", ColumnType.FLOAT64)), version=1),
            PartitionSchema("hash", 1))
        mc = await MiniCluster(tempfile.mkdtemp(prefix="ybtpu-trace-"),
                               num_tservers=1).start()
        default_rate = None
        try:
            c = mc.client()
            await c.create_table(info, num_tablets=1,
                                 replication_factor=1)
            await mc.wait_for_leaders("tracebench")
            n_rows = 50_000
            for lo in range(0, n_rows, 5000):
                await c.insert("tracebench", [
                    {"k": i, "v": float(i)}
                    for i in range(lo, lo + 5000)])
            agg_req = ReadRequest(
                (await c._table("tracebench")).info.table_id,
                aggregates=(AggSpec("count"), AggSpec("sum", ("col", 1))))
            # the sampler thread runs during BOTH sides (a real server
            # always has it); only root sampling is toggled
            ASH.start()

            async def ycsb_round():
                ops = 0
                stop = time.monotonic() + dur

                async def worker(base):
                    nonlocal ops
                    i = base
                    while time.monotonic() < stop:
                        if i % 4 == 0:
                            await c.write("tracebench", [RowOp(
                                "upsert", {"k": i % n_rows,
                                           "v": float(i)})])
                        else:
                            await c.get("tracebench",
                                        {"k": i % n_rows})
                        ops += 1
                        i += 7
                t0 = time.perf_counter()
                await asyncio.gather(*[worker(j * 131) for j in range(8)])
                return ops / (time.perf_counter() - t0)

            async def q6_round():
                scans = 0
                t0 = time.perf_counter()
                while time.perf_counter() - t0 < dur:
                    await c.scan("tracebench", agg_req)
                    scans += 1
                return scans * n_rows / (time.perf_counter() - t0)

            default_rate = _flags.REGISTRY._flags[
                "trace_sampling_rate"].default
            sides = {"off": 0.0, "on": default_rate}
            res = {"off": {"ycsb": [], "q6": []},
                   "on": {"ycsb": [], "q6": []}}
            # warm both paths (kernel compile + connection setup)
            await ycsb_round()
            await q6_round()
            for _ in range(2):          # paired, interleaved
                for side, rate in sides.items():
                    _flags.set_flag("trace_sampling_rate", rate)
                    res[side]["ycsb"].append(await ycsb_round())
                    res[side]["q6"].append(await q6_round())
            return {
                "seconds_per_round": dur,
                "default_sampling_rate": default_rate,
                "ycsb_ops_per_s_off": round(max(res["off"]["ycsb"]), 1),
                "ycsb_ops_per_s_on": round(max(res["on"]["ycsb"]), 1),
                "q6_rows_per_s_off": round(max(res["off"]["q6"]), 1),
                "q6_rows_per_s_on": round(max(res["on"]["q6"]), 1),
                "trace_ycsb_on_vs_off": round(
                    max(res["on"]["ycsb"]) / max(res["off"]["ycsb"]), 3),
                "trace_q6_on_vs_off": round(
                    max(res["on"]["q6"]) / max(res["off"]["q6"]), 3),
            }
        finally:
            from yugabyte_db_tpu.utils import flags as _flags2
            if default_rate is not None:
                _flags2.set_flag("trace_sampling_rate", default_rate)
            await mc.shutdown()

    try:
        return asyncio.run(run())
    except Exception as e:   # noqa: BLE001 — report, don't fail bench
        if os.environ.get("BENCH_DEBUG"):
            raise
        return {"error": str(e)[:300]}


# ratio keys whose value < 1.0 means "slower than the baseline it was
# measured against" — surfaced as a WARN in the bench tail instead of
# sitting silently inside the JSON (satellite of PR 3; Q6's r05
# vs_baseline of 0.923 went unnoticed for a round)
_RATIO_KEYS = ("vs_baseline", "speedup", "vs_cpu", "vs_xla",
               "shred_vs_interp",
               "p99_ratio_on_vs_off", "achieved_ratio_on_vs_off",
               "stream_vs_mono", "v2_vs_v1_bytes", "prune_speedup",
               "bypass_vs_hotpath", "bypass_p99_impact",
               "grouped_vs_interp", "fused_vs_interp",
               "fused_vs_operator", "split_goodput_ratio",
               "cluster_bypass_p95_impact", "cluster_p99_on_vs_off",
               "cluster_achieved_on_vs_off", "cluster_p99_spread",
               "cluster_fused_p99_on_vs_off",
               "cluster_fused_achieved_on_vs_off",
               "trace_ycsb_on_vs_off", "trace_q6_on_vs_off",
               "matview_vs_rescan")

#: keys where ANY nonzero value is a regression (acked data vanished
#: or corrupted across a chaos round — never acceptable)
_NONZERO_BAD_KEYS = ("chaos_missing", "chaos_mismatched",
                     "chaos_unreachable")


def warn_regressed_ratios(node, path="", out=None):
    """Collect (path, value) for every ratio key below 1.0."""
    if out is None:
        out = []
    if isinstance(node, dict):
        for k, v in node.items():
            p = f"{path}.{k}" if path else k
            if k in _RATIO_KEYS and isinstance(v, (int, float)):
                # p99_ratio: LOWER is better (scheduler holds latency);
                # bypass_p99_impact: the bypass thread must not inflate
                # the hot path's write p99 past CPU-contention noise
                # (2x on this 2-core box — queueing coupling would show
                # as 10x+); everything else: below 1.0 is a regression
                if k == "p99_ratio_on_vs_off":
                    bad = v > 0.5
                elif k == "bypass_p99_impact":
                    bad = v > 2.0
                elif k == "cluster_bypass_p95_impact":
                    # the gate rides the p95 ratio, not p99: on 2
                    # cores a round's p99 is its ~50th-highest sample
                    # and flush-pause spikes swing it ~20x run to run
                    # (p99_ms_rounds records the spread), while the
                    # p95 medians hold steady; a REAL event-loop
                    # coupling reads 10x+ either way
                    bad = v > 2.0
                elif k == "cluster_p99_on_vs_off":
                    # cross-process: driver p99 includes client
                    # backoff/retry; the bar is "scheduler ON is not
                    # WORSE", with headroom for 2-core noise
                    bad = v > 1.5
                elif k == "cluster_achieved_on_vs_off":
                    # tightened from 0.9 in PR 11: the fusion levers
                    # (async flush, fused appends, cross-tablet
                    # dispatch) are claimed — scheduler ON must now
                    # WIN at matched goodput, not merely tie
                    bad = v < 1.0
                elif k == "cluster_fused_achieved_on_vs_off":
                    bad = v < 1.0
                elif k == "cluster_fused_p99_on_vs_off":
                    # fusion ON must not worsen the write p99 (2-core
                    # noise headroom mirrors cluster_p99_on_vs_off)
                    bad = v > 1.5
                elif k == "cluster_p99_spread":
                    # per-round p99 max/median: flush-pause luck made
                    # this ~20x pre-async-flush; the PR-11 bar is 3x
                    bad = v > 3.0
                elif k == "split_goodput_ratio":
                    # goodput through a live split+rebalance may dip,
                    # but collapsing past 4x is a control-plane stall
                    bad = v < 0.25
                elif k in ("trace_ycsb_on_vs_off",
                           "trace_q6_on_vs_off"):
                    # tracing at DEFAULT sampling may cost at most 2%
                    # of the hot path it observes (ISSUE 14 overhead
                    # gate; 0.98 = the 2% bar)
                    bad = v < 0.98
                else:
                    bad = v < 1.0
                if bad:
                    out.append((p, v))
            elif k in _NONZERO_BAD_KEYS and isinstance(v, (int, float)):
                if v > 0:
                    out.append((p, v))
            else:
                warn_regressed_ratios(v, p, out)
    elif isinstance(node, list):
        for i, v in enumerate(node):
            warn_regressed_ratios(v, f"{path}[{i}]", out)
    return out


def warn_suppression_growth(base_dir=None):
    """Collect WARN lines when the static-analysis suppression count
    grew past tools/analyze/baseline.json — annotations accreting
    instead of hazards being fixed is its own regression — or when the
    sweep's own wall clock grew past 1.5x the recorded
    ``analyze_wall_ms`` (the engine rides in tier-1 and the pre-commit
    hook; its cost is tracked like any hot path)."""
    here = base_dir or os.path.dirname(os.path.abspath(__file__))
    out = []
    try:
        sys.path.insert(0, os.path.join(here, "tools"))
        try:
            from analyze import ALL_PASSES, ProjectIndex, run_analysis
        finally:
            sys.path.pop(0)
        with open(os.path.join(here, "tools", "analyze",
                               "baseline.json")) as f:
            base = json.load(f)
        baseline = base["suppressions"]
        report = run_analysis(ProjectIndex(
            here, cache_dir=os.path.join(here, ".analyze_cache")),
            ALL_PASSES)
        for pass_id, n in sorted(report["suppressions"].items()):
            if n > baseline.get(pass_id, 0):
                out.append(
                    f"analysis suppressions for {pass_id} grew to {n} "
                    f"(baseline {baseline.get(pass_id, 0)}) — fix the "
                    f"hazard or commit a new baseline deliberately")
        base_ms = base.get("analyze_wall_ms")
        if base_ms and report["wall_ms"] > 1.5 * base_ms:
            out.append(
                f"analyze_wall_ms grew to {report['wall_ms']:.0f} "
                f"(baseline {base_ms}, limit 1.5x) — the analysis "
                f"engine's own cost regressed; profile the passes or "
                f"re-record the baseline deliberately")
    except Exception as e:   # noqa: BLE001 — account, don't fail bench
        out.append(f"analysis suppression check failed: {e!r:.120}")
    return out


def _logical_row_bytes(info) -> int:
    """User-data bytes per row straight from the schema (fixed-width
    columns only — the lineitem shape): the write-amp denominator,
    so 'bytes written / logical bytes' is comparable across formats."""
    from yugabyte_db_tpu.dockv.packed_row import ColumnType
    return sum(ColumnType.FIXED_WIDTHS.get(c.type, 8)
               for c in info.schema.columns)


def _make_compaction_tablet(data, n_ssts, rows_per_sst, tag):
    """A tablet with `n_ssts` SSTables: sequential loads with 25%
    overlapping (re-written) keys so the merge has real MVCC work
    (BASELINE config 4; reference: 100-SST major compaction,
    rocksdb/db/compaction_job.cc:665)."""
    from yugabyte_db_tpu.models.tpch import LineitemTable
    from yugabyte_db_tpu.utils.hybrid_time import HybridTime
    t = LineitemTable(tempfile.mkdtemp(prefix=f"ybtpu-comp-{tag}-"),
                      num_tablets=1).tablets[0]
    n = len(data["rowid"])
    base_us = int(time.time() * 1e6)
    for i in range(n_ssts):
        # 75% fresh rows, 25% re-writes of the previous batch's keys
        fresh = (i * rows_per_sst) % max(n - rows_per_sst, 1)
        sel = np.arange(fresh, fresh + rows_per_sst) % n
        if i > 0:
            prev = (sel - rows_per_sst // 4) % n
            sel[: rows_per_sst // 4] = prev[: rows_per_sst // 4]
        batch = {k: v[sel] for k, v in data.items()}
        t.bulk_load(batch, ht=HybridTime.from_micros(base_us + i * 1000))
    assert len(t.regular.ssts) >= n_ssts
    return t


def doc_scan_bench(repeats):
    """Document shredding (docstore/): a selective path predicate +
    aggregates over ~1M JSON documents, shredded v2 lanes on the
    device path vs the interpreted row-at-a-time JSON extractor
    (``doc_shred_enabled=False`` at read time is byte-for-byte that
    path over the SAME SSTs).  The request exercises the int-path
    compare, the exact int64 SUM over a shredded lane, and the
    dict-code MAX decode satellite in one shot; shred_coverage (the
    fraction of scanned rows served from shredded lanes) is asserted
    nonzero and shred_vs_interp WARN-wires like stream_vs_mono.
    Interpreted rounds cost ~10s/M rows, so the interpreted side runs
    once (the >=10x margin dwarfs round noise)."""
    from yugabyte_db_tpu.docdb.operations import ReadRequest
    from yugabyte_db_tpu.docstore import (DOC_STATS, DOC_WRITE_STATS,
                                          LAST_DOC_STATS)
    from yugabyte_db_tpu.models.docbench import (doc_qty_query,
                                                 docs_info,
                                                 generate_docs)
    from yugabyte_db_tpu.tablet import Tablet
    from yugabyte_db_tpu.utils import flags

    n = int(os.environ.get("BENCH_DOC_ROWS", str(1_000_000)))
    data = generate_docs(n)
    t = Tablet("docs-bench", docs_info(),
               tempfile.mkdtemp(prefix="ybtpu-doc-"))
    t0 = time.perf_counter()
    t.bulk_load(data, block_rows=65536)
    load_s = time.perf_counter() - t0
    where, aggs = doc_qty_query()

    def req():
        return ReadRequest("docs", where=where, aggregates=aggs)

    warm = t.read(req())                   # compile + warm
    assert warm.backend == "tpu", \
        f"doc pushdown fell back: {DOC_STATS}"
    coverage = LAST_DOC_STATS.get("coverage", 0.0)
    assert coverage > 0, f"shred_coverage {coverage}"
    shred_ts = []
    for _ in range(max(2, repeats)):
        t0 = time.perf_counter()
        sresp = t.read(req())
        shred_ts.append(time.perf_counter() - t0)
    flags.set_flag("doc_shred_enabled", False)
    try:
        t0 = time.perf_counter()
        iresp = t.read(req())
        interp_t = time.perf_counter() - t0
    finally:
        flags.REGISTRY.reset("doc_shred_enabled")
    assert iresp.backend == "cpu"
    a = [np.asarray(v).tolist() for v in sresp.agg_values]
    b = [np.asarray(v).tolist() for v in iresp.agg_values]
    assert a == b, f"doc shredded/interpreted parity: {a} != {b}"
    shred_t = min(shred_ts)
    return {
        "rows": n, "load_s": round(load_s, 2),
        "agg_values": a,
        "shred_rows_per_s": round(n / shred_t, 1),
        "interp_rows_per_s": round(n / interp_t, 1),
        "shred_s": round(shred_t, 4),
        "interp_s": round(interp_t, 4),
        "shred_vs_interp": round(interp_t / shred_t, 2),
        "shred_coverage": coverage,
        "paths_referenced": LAST_DOC_STATS.get("paths"),
        "write_stats": dict(DOC_WRITE_STATS),
        "fallback_reasons": dict(DOC_STATS.get("reasons", {})),
    }


def main():
    sf = float(os.environ.get("BENCH_SF", "1.0"))
    repeats = int(os.environ.get("BENCH_REPEATS", "5"))

    import jax
    from yugabyte_db_tpu.models.tpch import (
        LineitemTable, TPCH_Q1, TPCH_Q6, generate_lineitem, numpy_reference,
    )
    from yugabyte_db_tpu.ops.cpu_scan import cpu_scan_aggregate
    from yugabyte_db_tpu.ops.device_batch import build_batch
    from yugabyte_db_tpu.ops.scan import ScanKernel
    from yugabyte_db_tpu.utils import flags

    # no probe and no fallback: a benchmark that finds no chip fails,
    # unless the caller asked for the CPU itself (YBTPU_PLATFORM=cpu,
    # the rehearsal) — and then no metric is named after the TPU
    dev = jax.devices()[0]
    if dev.platform != "tpu" and \
            os.environ.get("YBTPU_PLATFORM", "").lower() != "cpu":
        sys.exit(f"bench.py: no TPU found (jax.devices()[0] is {dev}); "
                 "set YBTPU_PLATFORM=cpu to rehearse on the CPU")
    data = generate_lineitem(sf)
    n = len(data["rowid"])

    tmp = tempfile.mkdtemp(prefix="ybtpu-bench-")
    table = LineitemTable(tmp, num_tablets=1)
    t0 = time.perf_counter()
    loaded = table.load(data)
    load_s = time.perf_counter() - t0
    tablet = table.tablets[0]

    # --- bulk-load output-byte accounting (v2 format satellite) ---------
    # logical bytes = raw user column data; write-amp is what the
    # on-disk format adds on top (keys/MVCC/index/bloom). The small v1
    # comparison load yields v2_vs_v1_bytes (>= 1.0 means v2 is
    # smaller), surfacing byte regressions like speed regressions.
    lrb = _logical_row_bytes(table.info)
    out_bytes = sum(r.file_size for r in tablet.regular.ssts)
    flags.set_flag("sst_format_version", 1)
    try:
        v1_table = LineitemTable(tempfile.mkdtemp(prefix="ybtpu-v1-"),
                                 num_tablets=1)
        v1_table.load(data)
        v1_bytes = sum(r.file_size
                       for r in v1_table.tablets[0].regular.ssts)
    finally:
        flags.REGISTRY.reset("sst_format_version")
    bulk_load_block = {
        "rows": loaded, "load_rows_per_s": round(loaded / load_s, 1),
        "output_bytes": out_bytes,
        "output_bytes_per_row": round(out_bytes / max(loaded, 1), 2),
        "write_amp": round(out_bytes / max(loaded * lrb, 1), 3),
        "v1_output_bytes_per_row": round(v1_bytes / max(loaded, 1), 2),
        "v2_vs_v1_bytes": round(v1_bytes / max(out_bytes, 1), 3),
        "format_version": flags.get("sst_format_version"),
    }

    blocks = []
    for r in tablet.regular.ssts:
        for i in range(r.num_blocks()):
            blocks.append(r.columnar_block(i))

    def check_q1(sums, counts, ref):
        """sums: list of per-group arrays (5 aggs), counts: [6].

        Tolerances derive from the engine's documented accumulation
        contract (ops/scan.py): SUM accumulates EXACTLY in int64 fixed
        point on every backend, so integer-valued columns (l_quantity)
        are exact and counts are exact. Fractional sums carry only the
        per-row f32 device representation error (<= 2^-24 relative per
        row — all-positive terms, so <= ~1.2e-7 on the sum) plus
        <= 1e-12 quantization; 1e-5 keeps two orders of margin without
        re-admitting accumulation drift."""
        for g in range(6):
            want_qty, want_price, want_cnt = ref[g]
            assert int(counts[g]) == want_cnt, f"q1 g{g} count"
            assert abs(float(sums[0][g]) - want_qty) \
                <= 1e-9 * max(abs(want_qty), 1), \
                f"q1 g{g} qty: {float(sums[0][g])} vs {want_qty}"
            rel = abs(float(sums[1][g]) - want_price) / max(want_price, 1e-9)
            assert rel < 1e-5, f"q1 g{g} price: {float(sums[1][g])} vs " \
                f"{want_price}"

    results = {}
    results["bulk_load"] = bulk_load_block
    kernel = ScanKernel()
    for q in (TPCH_Q6, TPCH_Q1):
        batch = build_batch(blocks, sorted(q.columns))

        def cpu_run():
            return cpu_scan_aggregate(blocks, q.columns, q.where,
                                      q.aggs, q.group)

        def tpu_run():
            outs, counts, _ = kernel.run(batch, q.where, q.aggs, q.group)
            jax.block_until_ready(outs)
            return outs, counts
        tpu_run()   # compile + warm
        cpu_run()   # page-cache warm for the baseline too
        # PAIRED measurement (VERDICT r5 item 2): kernel and baseline
        # run BACK-TO-BACK inside each round, so driver-box contention
        # hits both sides of a round equally and cancels in the ratio.
        # vs_baseline is the best-of-N of the per-round RATIO (raw
        # best-of-N times ride along for absolute rates); three rounds
        # of vs_baseline < 1.0 were contention noise, not the engine.
        pairs = []
        for _ in range(repeats):
            t0 = time.perf_counter()
            tpu_out, tpu_counts = tpu_run()
            tpu_r = time.perf_counter() - t0
            t0 = time.perf_counter()
            cpu_run()
            cpu_r = time.perf_counter() - t0
            pairs.append((tpu_r, cpu_r))
        tpu_t = min(t for t, _ in pairs)
        cpu_t = min(c for _, c in pairs)
        ratios = [c / t for t, c in pairs]
        # correctness vs direct numpy — BOTH queries
        ref = numpy_reference(q, data)
        if q.name == "q6":
            # sum of f32 products of two f32 values: per-row rel error
            # <= 3*2^-24 ~ 1.8e-7, all-positive terms, exact int64
            # accumulation => 1e-5 has ~50x margin
            rel = abs(float(tpu_out[0]) - ref) / max(abs(ref), 1e-9)
            assert rel < 1e-5, f"q6 mismatch: {float(tpu_out[0])} vs {ref}"
        else:
            check_q1([np.asarray(o) for o in tpu_out],
                     np.asarray(tpu_counts), ref)
        results[q.name] = {
            "cpu_s": cpu_t, "tpu_s": tpu_t,
            "cpu_rows_per_s": n / cpu_t, "tpu_rows_per_s": n / tpu_t,
            "speedup": max(ratios),
            "ratio_rounds": [round(r, 3) for r in ratios],
        }

    # --- the bypass column: Q1/Q6 through client.scan_bypass ------------
    bp = tpch_bypass_bench(data, repeats)
    for qn in ("q6", "q1"):
        if bp is None:
            results[qn]["bypass"] = "skipped (BENCH_TPCH_BYPASS=0)"
        elif "error" in bp:
            results[qn]["bypass"] = {"error": bp["error"]}
        else:
            results[qn]["bypass"] = bp[qn]

    # --- cold-scan split: streaming chunk pipeline vs monolithic batch --
    # The headline q6/q1 numbers above are WARM-scan rates (batch already
    # on device; kernel time only).  A COLD scan also pays batch
    # formation — decode + concat + pad + device_put — which the r05
    # monolithic path ran serially before the first kernel byte.  This
    # block measures both cold paths (monolithic = r05 behavior =
    # streaming_scan_enabled=False; streaming = pow2-chunk pipeline with
    # batch formation overlapped against kernel dispatch) and reports
    # the batch-build vs kernel time split, so batch-formation wins are
    # visible separately from kernel wins.
    from yugabyte_db_tpu.ops.stream_scan import (LAST_STREAM_STATS,
                                                 streaming_scan_aggregate)
    cold_results = {}
    for q in (TPCH_Q6, TPCH_Q1):
        cols = sorted(q.columns)
        mono_build_s = [0.0]

        def mono_cold():
            t0 = time.perf_counter()
            b = build_batch(blocks, cols)
            mono_build_s[0] = time.perf_counter() - t0
            outs, counts, _ = kernel.run(b, q.where, q.aggs, q.group)
            jax.block_until_ready(outs)
            return outs

        def stream_cold():
            return streaming_scan_aggregate(blocks, cols, q.where,
                                            q.aggs, q.group,
                                            kernel=kernel)
        if stream_cold() is None:   # compile; None = too few chunks to
            # stream (tiny BENCH_SF) — the cold comparison is mono-only
            cold_results[q.name] = {"stream": "declined (too few chunks)"}
            continue
        rounds = max(2, repeats // 2)
        mono_rounds = []
        for _ in range(rounds):
            t0 = time.perf_counter()
            mono_cold()
            mono_rounds.append((time.perf_counter() - t0,
                                mono_build_s[0]))
        mono_t, mono_build = min(mono_rounds)   # split from the SAME round
        stream_t, (souts, scounts) = best_of(stream_cold, rounds)
        if q.name == "q6":
            ref = numpy_reference(q, data)
            rel = abs(float(souts[0]) - ref) / max(abs(ref), 1e-9)
            assert rel < 1e-5, f"q6 stream mismatch: {float(souts[0])}"
        else:
            check_q1([np.asarray(o) for o in souts],
                     np.asarray(scounts), numpy_reference(q, data))
        cold_results[q.name] = {
            "mono_rows_per_s": round(n / mono_t, 1),
            "stream_rows_per_s": round(n / stream_t, 1),
            "stream_vs_mono": round(mono_t / stream_t, 3),
            "mono_split": {"batch_build_s": round(mono_build, 4),
                           "kernel_s": round(mono_t - mono_build, 4)},
            "stream_split": dict(LAST_STREAM_STATS),
        }
    # --- zone-map pruning on a selective Q6-style scan ------------------
    # Hash sharding scrambles rowid across blocks, so the prune scenario
    # uses the range-sharded clone (rowid-clustered blocks): Q6's
    # predicates plus a selective rowid range. Paired ON/OFF rounds;
    # the skipped-block counter comes from the streaming stats.
    try:
        from yugabyte_db_tpu.docdb.operations import (
            LAST_SCAN_PRUNE_STATS, ReadRequest)
        from yugabyte_db_tpu.models.tpch import lineitem_range_info
        from yugabyte_db_tpu.ops import Expr
        from yugabyte_db_tpu.ops.stream_scan import LAST_STREAM_STATS
        from yugabyte_db_tpu.tablet import Tablet
        from yugabyte_db_tpu.utils.hybrid_time import HybridTime
        from yugabyte_db_tpu.models.tpch import ROWID, TPCH_Q6

        rt = Tablet("lineitem-range", lineitem_range_info(),
                    tempfile.mkdtemp(prefix="ybtpu-zp-"))
        rt.bulk_load(data, ht=HybridTime.from_micros(
            int(time.time() * 1e6)))
        hi = n // 8
        zwhere = ("and", TPCH_Q6.where,
                  (Expr.col(ROWID) < hi).node)
        zreq = ReadRequest("lineitem_r", where=zwhere,
                           aggregates=TPCH_Q6.aggs)

        def zp_round():
            return rt.read(zreq)

        zp_round()   # compile + warm
        on_t, on_r = best_of(zp_round, max(2, repeats // 2))
        skipped = (LAST_STREAM_STATS.get("zone_blocks_pruned")
                   or LAST_SCAN_PRUNE_STATS.get("blocks_pruned", 0))
        total_blk = (LAST_STREAM_STATS.get("zone_blocks_total")
                     or LAST_SCAN_PRUNE_STATS.get("blocks_total", 0))
        flags.set_flag("zone_map_pruning", False)
        try:
            zp_round()   # warm the unpruned batches too
            off_t, off_r = best_of(zp_round, max(2, repeats // 2))
        finally:
            flags.REGISTRY.reset("zone_map_pruning")
        m = ((data["l_shipdate"] >= 8766) & (data["l_shipdate"] < 9131)
             & (data["l_discount"] >= 0.05) & (data["l_discount"] <= 0.07)
             & (data["l_quantity"] < 24.0) & (data["rowid"] < hi))
        ref = (data["l_extendedprice"][m] * data["l_discount"][m]).sum()
        for r in (on_r, off_r):
            rel = abs(float(np.asarray(r.agg_values[0])) - ref) \
                / max(abs(ref), 1e-9)
            assert rel < 1e-5, f"zone-prune q6 mismatch: {rel}"
        cold_results["zone_prune_q6"] = {
            "selectivity": round(hi / n, 3),
            "blocks_skipped": int(skipped),
            "blocks_total": int(total_blk),
            "on_s": round(on_t, 4), "off_s": round(off_t, 4),
            "prune_speedup": round(off_t / on_t, 3),
        }
    except Exception as e:   # noqa: BLE001 — report, don't fail bench
        cold_results["zone_prune_q6"] = {"error": str(e)[:200]}
    results["cold_scan"] = cold_results

    # --- q1_grouped: dict-key GROUP BY kernel vs interpreted ------------
    # (operator-frontier rungs (b)+(d): string group keys aggregate on
    # device over scan-global dictionary codes; grouped_vs_interp
    # WARN-wires like stream_vs_mono)
    results["q1_grouped"] = q1_grouped_bench(data, repeats)

    # --- document shredding: path predicates over JSON as columnar
    # lanes vs the interpreted extractor (docstore/) -------------------
    try:
        results["doc_scan"] = doc_scan_bench(repeats)
    except AssertionError:
        raise   # a parity/coverage break IS a bench failure
    except Exception as e:   # noqa: BLE001 — report, don't fail bench
        if os.environ.get("BENCH_DEBUG"):
            raise
        results["doc_scan"] = {"error": str(e)[:300]}

    # --- device hash join + fused plans (Q3/Q5-shaped join+group) -------
    try:
        results["tpch_join"] = tpch_join_bench(data, repeats)
    except AssertionError:
        raise   # a parity/compile-budget break IS a bench failure
    except Exception as e:   # noqa: BLE001 — report, don't fail bench
        if os.environ.get("BENCH_DEBUG"):
            raise
        results["tpch_join"] = {"error": str(e)[:300]}

    # --- whole-query gauntlet: the 22-query TPC-H registry --------------
    # (BENCH_TPCH_SF sets the scale — 0.1 smoke default, "full" = the
    # tpch_sf flag's SF10, 0 skips; inexpressible queries report typed
    # reasons, fused_vs_interp WARN-wires per query)
    try:
        tf = tpch_full_bench(repeats)
        results["tpch_full"] = (tf if tf is not None
                                else "skipped (BENCH_TPCH_SF=0)")
    except AssertionError:
        raise   # a parity/compile-budget break IS a bench failure
    except Exception as e:   # noqa: BLE001 — report, don't fail bench
        if os.environ.get("BENCH_DEBUG"):
            raise
        results["tpch_full"] = {"error": str(e)[:300]}

    # --- optional: hand-fused pallas scan vs the XLA kernel -------------
    # (BENCH_PALLAS=1; the flag stays off otherwise so the driver's run
    # never depends on the pallas TPU compile)
    if os.environ.get("BENCH_PALLAS") == "1":
        flags.set_flag("tpu_pallas_scan", True)
        try:
            pk = ScanKernel()
            q = TPCH_Q6
            batch = build_batch(blocks, sorted(q.columns))

            def pallas_run():
                outs, counts, m = pk.run(batch, q.where, q.aggs, q.group)
                jax.block_until_ready(outs)
                return outs, counts, m
            _, _, m0 = pallas_run()
            pl_t, (pl_out, _, _) = best_of(pallas_run, repeats)
            ref = numpy_reference(q, data)
            rel = abs(float(pl_out[0]) - ref) / max(abs(ref), 1e-9)
            results["q6_pallas"] = {
                "routed": m0 is None, "rows_per_s": n / pl_t,
                "vs_xla": results["q6"]["tpu_s"] / pl_t,
                "rel_err": rel,
            }
        except Exception as e:   # noqa: BLE001 — report, don't fail bench
            results["q6_pallas"] = {"error": str(e)[:200]}
        finally:
            flags.set_flag("tpu_pallas_scan", False)

    # --- distributed Q1 (BASELINE config 3): 8 tablets ------------------
    dtable = LineitemTable(tempfile.mkdtemp(prefix="ybtpu-dist-"),
                           num_tablets=8)
    dtable.load(data)
    q1ref = numpy_reference(TPCH_Q1, data)
    if len(jax.devices()) >= 8:
        from yugabyte_db_tpu.parallel.distributed_scan import (
            build_sharded_batch, distributed_scan_aggregate,
        )
        from yugabyte_db_tpu.parallel.mesh import tablet_mesh
        tm = tablet_mesh(num_tablet_shards=8)
        shard_blocks = []
        for t in dtable.tablets:
            bl = []
            for r in t.regular.ssts:
                for i in range(r.num_blocks()):
                    bl.append(r.columnar_block(i))
            shard_blocks.append(bl)
        sbatch = build_sharded_batch(tm, shard_blocks,
                                     sorted(TPCH_Q1.columns))

        def dist_run():
            sums, counts = distributed_scan_aggregate(
                sbatch, TPCH_Q1.where, TPCH_Q1.aggs, TPCH_Q1.group)
            jax.block_until_ready(sums)
            return sums, counts
        dist_run()
        dist_t, (dsums, dcounts) = best_of(dist_run, repeats)
        check_q1([np.asarray(s) for s in dsums], np.asarray(dcounts), q1ref)
        combine = "psum"
    else:
        # single visible device: per-tablet kernels + host combine (the
        # single-chip execution of the same fan-out)
        def dist_run():
            return dtable.run(TPCH_Q1)
        dist_run()
        dist_t, (dsums, dcounts) = best_of(dist_run, max(2, repeats // 2))
        check_q1([np.asarray(s) for s in dsums], np.asarray(dcounts), q1ref)
        combine = "host"
    results["q1_dist"] = {"tablets": 8, "combine": combine,
                          "rows_per_s": n / dist_t, "seconds": dist_t}

    # --- compaction at spec (BASELINE config 4): N-SST major merge ------
    n_ssts = int(os.environ.get("BENCH_COMPACT_SSTS", "100"))
    rows_per = int(os.environ.get("BENCH_COMPACT_ROWS", "20000"))

    def timed_compaction_once(flag, tag):
        # the CPU side is the full PRE-PR configuration: monolithic
        # baseline engine AND sst_format_version=1 for both the input
        # tablet and the output, so vs_cpu measures the complete
        # engine+format upgrade and the cpu output doubles as the v1
        # byte yardstick for v2_vs_v1_bytes
        if not flag:
            flags.set_flag("sst_format_version", 1)
        try:
            ct = _make_compaction_tablet(data, n_ssts, rows_per, tag)
            nbytes = ct.approximate_size()
            flags.set_flag("tpu_compaction_enabled", flag)
            t0 = time.perf_counter()
            ct.compact()
            dt = time.perf_counter() - t0
        finally:
            if not flag:
                flags.REGISTRY.reset("sst_format_version")
        out = ct.regular.ssts[0]
        return dt, nbytes, out.file_size, out.num_entries

    # best-of-2 rounds, modes INTERLEAVED inside each round: the two
    # paths then see the same machine conditions (page cache, competing
    # load), so the ratio measures the engines rather than system drift;
    # round 0 additionally absorbs cold imports for both
    from yugabyte_db_tpu.docdb.compaction import LAST_COMPACTION_STATS
    dev_s = cpu_comp_s = None
    dev_in = cpu_in = dev_out = dev_rows = cpu_out = 0
    dev_pipeline = {}
    for i in range(2):
        d, dev_in, dev_out, dev_rows = \
            timed_compaction_once(True, f"dev{i}")
        if dev_s is None or d < dev_s:
            dev_pipeline = {k: (round(v, 4) if isinstance(v, float)
                                else v)
                            for k, v in LAST_COMPACTION_STATS.items()
                            if k != "lanes"}
        c, cpu_in, cpu_out, _ = timed_compaction_once(False, f"cpu{i}")
        dev_s = d if dev_s is None else min(dev_s, d)
        cpu_comp_s = c if cpu_comp_s is None else min(cpu_comp_s, c)
    flags.set_flag("tpu_compaction_enabled", True)
    lrb = _logical_row_bytes(table.info)
    results["compaction"] = {
        # input byte counts differ per world (the v2 inputs are ~3x
        # smaller on disk): each rate is computed over its own bytes
        "ssts": n_ssts, "input_mb": dev_in / 1e6,
        "cpu_input_mb": cpu_in / 1e6,
        "mb_per_s": dev_in / 1e6 / dev_s,
        "cpu_mb_per_s": cpu_in / 1e6 / cpu_comp_s,
        "vs_cpu": cpu_comp_s / dev_s,
        "seconds": dev_s,
        # output-byte surgery accounting: the baseline run writes the
        # pre-v2 format, so v2_vs_v1_bytes = v1 bytes / v2 bytes on
        # the SAME logical output (>= 1.0 means v2 is smaller)
        "output_rows": dev_rows,
        "output_bytes_per_row": round(dev_out / max(dev_rows, 1), 2),
        "v1_output_bytes_per_row": round(cpu_out / max(dev_rows, 1), 2),
        "v2_vs_v1_bytes": round(cpu_out / max(dev_out, 1), 3),
        "write_amp": round(dev_out / max(dev_rows * lrb, 1), 3),
        "write_wait_s": dev_pipeline.get("write_wait_s"),
        "pipeline": dev_pipeline,
    }

    # YCSB workload C (BASELINE config 1): engine-level point reads.
    # A short untimed run first: the first few thousand ops pay block-
    # cache warmup and would dominate a small timed run.
    from yugabyte_db_tpu.models.ycsb import YcsbTabletWorkload, usertable_info
    from yugabyte_db_tpu.tablet import Tablet
    yt = Tablet("ycsb", usertable_info(), tempfile.mkdtemp(prefix="ycsb-"))
    w = YcsbTabletWorkload(yt, n_rows=100_000)
    w.load()
    w.run("c", ops=2000)   # warm
    ycsb_ops = int(os.environ.get("BENCH_YCSB_OPS", "20000"))
    rc = w.run("c", ops=ycsb_ops)
    # 16 concurrent sessions batching at the server seam (the engine
    # analog of the reference's multi-threaded YCSB drivers; reference
    # number: 77K ops/s across 3 nodes, ycsb-ysql.md:188)
    rb = w.run("c", ops=ycsb_ops, clients=16)
    results["ycsb_c"] = {"ops_per_s": rc.ops_per_sec,
                         "batched16_ops_per_s": rb.ops_per_sec}
    # workloads A (50/50 read-update) and E (short scans) round out the
    # reference's YCSB table (ycsb-ysql.md:186,190)
    ra = w.run("a", ops=max(2000, ycsb_ops // 4))
    rb_ = w.run("b", ops=max(2000, ycsb_ops // 4))
    re_ = w.run("e", ops=max(1000, ycsb_ops // 10))
    results["ycsb_a"] = {"ops_per_s": ra.ops_per_sec}
    results["ycsb_b"] = {"ops_per_s": rb_.ops_per_sec}
    results["ycsb_e"] = {"ops_per_s": re_.ops_per_sec}

    # YCSB-C at 2x saturation through the RPC path: scheduler ON vs
    # OFF (admission control + micro-batching headline; BENCH_OVERLOAD_S
    # bounds each side, 0 skips)
    bp = bypass_scan_bench()
    if bp is not None:
        results["bypass_scan"] = bp

    # incremental matviews fed by the CDC stream under 2x write load:
    # staleness p99, write-lane p99 impact, and the incremental-vs-
    # full-rescan serve ratio (matview_vs_rescan WARNs below 1;
    # BENCH_MATVIEW_S=0 skips)
    mv = matview_bench()
    if mv is not None:
        results["matview"] = mv

    ol = ycsb_overload_bench()
    if ol is not None:
        results["ycsb_overload"] = ol

    # live fire on a REAL multi-process cluster: scheduler separation,
    # goodput through split+rebalance, seeded chaos with byte-verify,
    # bypass from a separate replica process (BENCH_CLUSTER_S bounds
    # each phase, 0 skips)
    co = cluster_overload_bench()
    if co is not None:
        results["cluster_overload"] = co

    # observability overhead gate: headline YCSB/Q6 rates through the
    # RPC path with tracing at default sampling vs off (BENCH_TRACE_S
    # bounds each round, 0 skips; ratios WARN below 0.98)
    tr = trace_overhead_bench()
    if tr is not None:
        results["trace_overhead"] = tr

    # TPC-C-style NEW-ORDER/PAYMENT through REAL distributed txns on an
    # in-process cluster (reference headline bench; tpmC here is the
    # UNCONSTRAINED NewOrder rate — no spec think times). BENCH_TPCC_S
    # bounds the run; 0 skips.
    tpcc_s = float(os.environ.get("BENCH_TPCC_S", "10"))
    if tpcc_s > 0:
        import asyncio as _aio
        from yugabyte_db_tpu.models.tpcc import TpccWorkload
        from yugabyte_db_tpu.tools.mini_cluster import MiniCluster

        tpcc_wh = int(os.environ.get("BENCH_TPCC_WAREHOUSES", "1"))
        tpcc_terms = int(os.environ.get("BENCH_TPCC_TERMINALS", "8"))

        async def run_tpcc():
            mc = await MiniCluster(
                tempfile.mkdtemp(prefix="ybtpu-tpcc-"),
                num_tservers=1).start()
            try:
                c = mc.client()
                wload = TpccWorkload(c, warehouses=tpcc_wh)
                await wload.create_tables(num_tablets=1)
                for t_ in ("warehouse", "district", "customer", "stock",
                           "orders", "order_line", "history"):
                    await mc.wait_for_leaders(t_)
                await wload.load()
                await wload.run(seconds=2.0, concurrency=4)   # warm
                return await wload.run(seconds=tpcc_s,
                                       concurrency=tpcc_terms)
            finally:
                await mc.shutdown()
        try:
            tr = _aio.run(run_tpcc())
            import dataclasses as _dc
            # record the run CONFIGURATION next to the rates (VERDICT
            # item 9): tpmC without warehouse/terminal count is not a
            # comparable number
            results["tpcc"] = {**_dc.asdict(tr),
                               "warehouses": tpcc_wh,
                               "terminals": tpcc_terms,
                               "tpmc_unconstrained": tr.tpmc,
                               "abort_rate": tr.abort_rate}
        except Exception as e:   # noqa: BLE001 — report, don't fail bench
            results["tpcc"] = {"error": str(e)[:200]}

    # Vector search (BASELINE config 5): the reduced config plus the
    # full 1M x 768 spec config, through the vector/ subsystem's
    # two-stage IVF (multi-probe + GEMM re-rank).  Fine clustering is
    # the recall lever on isotropic data (the IVF worst case): r5's
    # flat IVF at nlists=200/nprobe=50 stalled at recall 0.744; the
    # two-stage engine at nlists=1024/nprobe=256 measures >=0.99 while
    # the blocked shared re-rank GEMM keeps qps above the old engine.
    # (BENCH_VECTOR_FULL=0 skips the big one)
    from yugabyte_db_tpu.vector import TwoStageIvfIndex

    def vector_bench(vn, vd, nlists, iters, repeats_v, nprobe=None):
        from yugabyte_db_tpu.ops.vector import exact_search
        rngv = np.random.default_rng(0)
        vbase = rngv.normal(size=(vn, vd)).astype(np.float32)
        t0 = time.perf_counter()
        idx = TwoStageIvfIndex.build(vbase, nlists=nlists, iters=iters,
                                     sample=50_000)
        build_s = time.perf_counter() - t0
        vq = vbase[:64] + 0.001
        np_ = nprobe or max(8, nlists // 4)
        idx.search(vq, k=10, nprobe=np_)   # warm/compile
        t0 = time.perf_counter()
        for _ in range(repeats_v):
            idx.search(vq, k=10, nprobe=np_)
        search_s = (time.perf_counter() - t0) / repeats_v
        # honesty: IVF search is approximate — report recall@10 vs an
        # exact scan on a query subsample so qps can't silently trade
        # away accuracy.  Same routing as the QPS loop: search the
        # FULL 64-query batch, compare a subsample.
        nq_r = 16
        _, ids = idx.search(vq, k=10, nprobe=np_)
        ids = ids[:nq_r]
        import jax.numpy as _jnp
        _, ref_ids = exact_search(_jnp.asarray(vq[:nq_r]),
                                  _jnp.asarray(vbase), 10)
        ref_ids = np.asarray(ref_ids)
        recall = float(np.mean([
            len(set(ids[i]) & set(ref_ids[i])) / 10.0
            for i in range(nq_r)]))
        from yugabyte_db_tpu.vector.ivf import kernel_cache_stats
        return {"n": vn, "dim": vd, "build_s": build_s,
                "nlists": int(idx.nlists), "nprobe": np_,
                "candidate_pool": int(idx.last_pool_rows),
                "ef": None,    # the HNSW twin's knob; IVF has none
                "kernel_cache": kernel_cache_stats(),
                "qps": 64 / search_s, "recall_at_10": recall}

    results["vector"] = vector_bench(200_000, 128, 256, 5, 5)
    if os.environ.get("BENCH_VECTOR_FULL", "1") != "0":
        results["vector_full"] = vector_bench(1_000_000, 768, 1024, 2, 2)

    # --- driver-conformance accounting (VERDICT r4 item 8) --------------
    # The external-driver suites (psycopg / cassandra-driver / redis-py)
    # need real drivers that cannot be installed in this image; a
    # pytest skip must never read as coverage, so the bench records
    # exactly which suites RAN (and their outcome) vs were SKIPPED and
    # why.  If a driver ever appears in the image, the suite runs here
    # automatically and its result replaces the skip entry.
    # one process per chip: this parent holds it by now, so a child that
    # needed JAX's accelerator would fail or hang — these pytest
    # children force the CPU themselves (tests/conftest.py)
    import subprocess as _sp
    driver_conf = {"ran": {}, "skipped": {}}
    _here = os.path.dirname(os.path.abspath(__file__))
    for mod, suite in (("psycopg", "tests/test_driver_conformance.py"),
                       ("cassandra", "tests/test_driver_conformance_cql.py"),
                       ("redis", "tests/test_driver_conformance_redis.py")):
        try:
            __import__(mod)
        except ImportError:
            # redis has a vendored fallback client (third_party/redispy,
            # an API-compatible RESP2 subset) which the suite imports
            # itself — that tier RUNS even without a system driver
            if not (mod == "redis" and os.path.isdir(os.path.join(
                    _here, "third_party", "redispy", "redis"))):
                driver_conf["skipped"][suite] = \
                    f"driver {mod!r} not installed"
                continue
        try:
            r = _sp.run([sys.executable, "-m", "pytest", suite, "-q",
                         "--no-header"],
                        capture_output=True, timeout=600,
                        cwd=os.path.dirname(os.path.abspath(__file__)))
            tail = (r.stdout or b"").decode("utf-8", "replace")
            tail = tail.strip().splitlines()[-1] if tail.strip() else ""
            driver_conf["ran"][suite] = {
                "passed": r.returncode == 0, "summary": tail[:120]}
        except Exception as e:   # noqa: BLE001 — account, don't fail bench
            driver_conf["ran"][suite] = {"passed": False,
                                         "summary": str(e)[:120]}

    q6 = results["q6"]
    line = {
        "metric": "tpch_q6_sf%g_%s_rows_per_sec" % (sf, dev.platform),
        "value": round(q6["tpu_rows_per_s"], 1),
        "unit": "rows/s",
        # best-of-N of the PER-ROUND ratio (kernel and baseline
        # interleaved back-to-back each round, so host contention
        # cancels); q6_paired carries the per-round ratios + raw times
        "vs_baseline": round(q6["speedup"], 3),
        "q6_paired": {"ratio_rounds": q6["ratio_rounds"],
                      "ratio_median": round(sorted(
                          q6["ratio_rounds"])[
                              len(q6["ratio_rounds"]) // 2], 3),
                      "tpu_s": round(q6["tpu_s"], 4),
                      "cpu_s": round(q6["cpu_s"], 4)},
        # RPC hot path vs SST-direct bypass on the same rows (ROADMAP
        # bypass item (e)); bypass_vs_hotpath WARN-wires like any ratio
        "q6_bypass": q6["bypass"],
        "device": str(dev),
        "rows": n,
        "load_rows_per_s": round(loaded / load_s, 1),
        "bulk_load": results["bulk_load"],
        # warm rates above; cold-scan split below (batch formation vs
        # kernel, streaming pipeline vs the r05 monolithic build)
        "cold_scan": results["cold_scan"],
        "q1": {"tpu_rows_per_s": round(results["q1"]["tpu_rows_per_s"], 1),
               "speedup": round(results["q1"]["speedup"], 3),
               "bypass": results["q1"]["bypass"]},
        # string-keyed Q1 through the streamed grouped kernel vs the
        # interpreted GROUP BY (+ cardinality sweep, CPU-twin oracle)
        "q1_grouped": results["q1_grouped"],
        # whole-query TPC-H gauntlet: the 22-query registry (runnable
        # adapted specs or typed inexpressible reasons); every
        # per-query fused_vs_interp in the subtree WARN-wires
        "tpch_full": results["tpch_full"],
        "doc_scan": results["doc_scan"],
        "q1_dist8": {
            "rows_per_s": round(results["q1_dist"]["rows_per_s"], 1),
            "combine": results["q1_dist"]["combine"]},
        "compaction": {
            "ssts": results["compaction"]["ssts"],
            "input_mb": round(results["compaction"]["input_mb"], 1),
            "mb_per_s": round(results["compaction"]["mb_per_s"], 2),
            "cpu_mb_per_s": round(results["compaction"]["cpu_mb_per_s"], 2),
            "vs_cpu": round(results["compaction"]["vs_cpu"], 3),
            "output_bytes_per_row":
                results["compaction"]["output_bytes_per_row"],
            "v1_output_bytes_per_row":
                results["compaction"]["v1_output_bytes_per_row"],
            "v2_vs_v1_bytes": results["compaction"]["v2_vs_v1_bytes"],
            "write_amp": results["compaction"]["write_amp"],
            "write_wait_s": results["compaction"]["write_wait_s"],
            "pipeline": results["compaction"]["pipeline"]},
        **({"q6_pallas": {
            k: (round(v, 4) if isinstance(v, float) else v)
            for k, v in results["q6_pallas"].items()}}
           if "q6_pallas" in results else {}),
        "ycsb_c_ops_per_s": round(results["ycsb_c"]["ops_per_s"], 1),
        "ycsb_c16_ops_per_s": round(
            results["ycsb_c"]["batched16_ops_per_s"], 1),
        "ycsb_a_ops_per_s": round(results["ycsb_a"]["ops_per_s"], 1),
        "ycsb_b_ops_per_s": round(results["ycsb_b"]["ops_per_s"], 1),
        **({"tpcc": {k: (round(v, 1) if isinstance(v, float) else v)
                     for k, v in results["tpcc"].items()}}
           if "tpcc" in results else {}),
        "ycsb_e_ops_per_s": round(results["ycsb_e"]["ops_per_s"], 1),
        **({"ycsb_overload": results["ycsb_overload"]}
           if "ycsb_overload" in results else {}),
        **({"cluster_overload": results["cluster_overload"]}
           if "cluster_overload" in results else {}),
        **({"trace_overhead": results["trace_overhead"]}
           if "trace_overhead" in results else {}),
        **({"bypass_scan": results["bypass_scan"]}
           if "bypass_scan" in results else {}),
        **({"matview": results["matview"]}
           if "matview" in results else {}),
        "driver_conformance": driver_conf,
        "vector": _vector_line(results["vector"]),
        **({"vector_full": _vector_line(results["vector_full"])}
           if "vector_full" in results else {}),
    }
    print(json.dumps(line))
    # regression visibility: any kernel-vs-baseline ratio below 1.0 (or
    # an overload p99 ratio the scheduler failed to hold) lands as a
    # WARN in the bench tail (stderr keeps the one-JSON-line stdout
    # contract) instead of hiding inside the blob
    for path, v in warn_regressed_ratios(line):
        print(f"WARN: ratio {path}={v} regressed past its threshold",
              file=sys.stderr)
    for msg in warn_suppression_growth():
        print(f"WARN: {msg}", file=sys.stderr)
    errors = _reported_errors(results)
    for path, msg in errors:
        print(f"ERROR: {path}: {msg}", file=sys.stderr)
    if errors:
        sys.exit(1)


if __name__ == "__main__":
    main()
