"""One closed-loop client repeating TPC-H's refresh function RF1 and the
full compaction `ybtpu_admin compact_table` sends: the INSERT statements
of one refresh set, the `flush` RPC on every tablet, the `compact` RPC on
each tablet in turn.  The window closes when the iteration in flight at
`--seconds` has sent its last compaction: every run does whole iterations,
so the rate is all their bytes over all their time, and where in an
iteration the clock happens to run out moves nothing.

Traffic parameters: `readback_orders`, how many inserted orders are read
back with all their lineitems (half of them the orders inserted last, half
a sample drawn from the seed);
`trace_seconds`.
"""
from __future__ import annotations

import time

import numpy as np

from benchmark import tpch
from benchmark.record import Recorder

TABLE = tpch.TABLE


async def _iteration(cluster, rec) -> None:
    """One refresh set, flush, compact.  Rows join the acknowledged set
    statement by statement; each compaction notes how many SSTs it left."""
    data = cluster.data
    new = data.next_refresh_set()
    batch, n = data.insert_batch, len(new["l_orderkey"])
    for k, stmt in enumerate(data.insert_statements(new)):
        with rec.span("insert", rows=min(batch, n - k * batch)):
            await cluster.sql.execute(stmt)
        data.acknowledged({c: v[k * batch:(k + 1) * batch]
                           for c, v in new.items()})
    for l in data.ct.locations:
        with rec.span("flush"):
            await cluster.maintenance("flush", data.ct, l.tablet_id)
    for l, p in zip(data.ct.locations, data.peers):
        size = sum(cluster.sst_files([p])[0])
        with rec.span("compact", input_bytes=size) as s:
            await cluster.maintenance("compact", data.ct, l.tablet_id)
            after = cluster.sst_files([p])[0]
            s["output_bytes"], s["ssts_after"] = sum(after), len(after)


async def _queries(cluster, rec, kind: str) -> dict:
    """ANALYZE (the INSERTs voided the session's statistics), Q6, Q1."""
    out = {}
    with rec.span(kind, label=f"{kind}.analyze"):
        await cluster.data.analyze(cluster.sql)
    for q in ("q6", "q1"):
        with rec.span(kind, label=f"{kind}.{q}", query=q):
            out[q] = (await cluster.sql.execute(
                tpch.SQL[q].format(name=TABLE))).rows
    return out


async def warm(cluster, traffic: dict, rec) -> None:
    """The first compaction of every tablet (the bulk SSTs), one whole
    iteration as the window runs it, and the two statements the check
    sends afterwards."""
    for l in cluster.data.ct.locations:
        with rec.span("warm", label="warm.compact"):
            await cluster.maintenance("compact", cluster.data.ct, l.tablet_id)
    await _iteration(cluster, Recorder(traced=False))
    await _queries(cluster, rec, "warm")


async def window(cluster, traffic: dict, seconds: float, rec) -> None:
    ssts_before = cluster.data.sst_counts()
    deadline = time.perf_counter() + seconds
    while time.perf_counter() < deadline:
        try:
            await _iteration(cluster, rec)
        except Exception as e:   # noqa: BLE001 — counted as failed
            rec.error(e)
    rec.ssts_per_tablet = (ssts_before, cluster.data.sst_counts())


async def verify(cluster, traffic: dict, rec, checks) -> None:
    """The window's own compactions each left one SST, and the tablets
    still hold just that SST: the window ends on a whole iteration, so
    its last compaction wrote what Q6 and Q1 read, with no flush or
    compaction between them.  Q6 and Q1 are held to the reference over
    every acknowledged row, and a sample of the inserted orders is read
    back."""
    data = cluster.data
    checks.note("ssts_after_compact", max(
        [s["ssts_after"] - 1 for s in rec.of("compact")], default=1))
    checks.note("ssts_after_compact", max(data.sst_counts()) - 1)
    ref = tpch.reference(data.all_rows())
    for q, rows in (await _queries(cluster, rec, "check")).items():
        checks.note_all(tpch.compare(q, rows, ref))
    # read-back: rows inserted through SQL, value for value, by order: the
    # orders inserted last and as many drawn from the seed.  Few, because
    # the program serves a lookup by order key as a scan of the tablet
    # (PERF.md, Open questions)
    inserted = tpch.concat(data.parts[1:])
    half = max(1, int(traffic["readback_orders"]) // 2)
    orders = inserted["l_orderkey"][np.sort(np.unique(
        inserted["l_orderkey"], return_index=True)[1])]   # insert order
    last, rest = orders[-half:], orders[:-half]
    rng = np.random.default_rng([data.seed, 0])
    pick = np.concatenate([last, rng.choice(
        rest, min(half, len(rest)), replace=False)])
    keep = np.isin(inserted["l_orderkey"], pick)
    expect = {(r[0], r[3]): r for r in (
        tpch.row(inserted, i) for i in np.nonzero(keep)[0])}
    got = {}
    for s in range(0, len(pick), 250):
        res = await cluster.sql.execute(
            f"SELECT {', '.join(tpch.COLS)} FROM {TABLE} WHERE l_orderkey "
            f"IN ({', '.join(map(str, pick[s:s + 250].tolist()))})")
        got.update(((r["l_orderkey"], r["l_linenumber"]),
                    tuple(r[c] for c in tpch.COLS)) for r in res.rows)
    checks.note("readback_missing", len(set(expect) - set(got))
                + len(set(got) - set(expect)))
    checks.note("readback_value_diff", sum(
        1 for k, r in expect.items() if k in got and got[k] != r))
    checks.note("op_failed", attempted_failed(rec)[1])
    # the device did the work: the check's scans ran over device batches,
    # and off the CPU backend (whose merge is native) the window launched
    # the merge kernel
    checks.note("batches_off_device",
                0 if cluster.device_evidence()["on_device"] else 1)
    merged = (cluster.devices[0].platform == "cpu"
              or rec.counters["merge_kernel.calls"] > 0)
    checks.note("merge_off_device", 0 if merged else 1)


def attempted_failed(rec) -> tuple:
    kinds = ("insert", "flush", "compact")
    n = sum(len(rec.of(k, ok_only=False)) for k in kinds)
    return n, n - sum(len(rec.of(k)) for k in kinds)


def end_to_end(cluster, traffic: dict, rec) -> dict:
    done = rec.of("compact")
    if not done:
        return {}
    return {"compact_mb_per_s": sum(s["input_bytes"] for s in done)
            / 1e6 / rec.window_s}
