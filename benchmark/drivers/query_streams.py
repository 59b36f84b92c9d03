"""Closed-loop query streams over a static table: each client is one
`SqlSession` on the shared `YBClient`, an asyncio task that sends its next
statement when the last one has answered (TPC-H's query streams).

Traffic parameters: `clients`, a list with one statement cycle per client
(`[["q6", "q1"]]` is the power test's single stream); `trace_seconds`.
"""
from __future__ import annotations

import asyncio
import time

import numpy as np

from benchmark import tpch

TABLE = tpch.TABLE


async def warm(cluster, traffic: dict, rec) -> None:
    """Each client's session: ANALYZE, then each of its statements once
    (batch build, host->device, compile)."""
    for cycle in traffic["clients"]:
        session = cluster.session()
        cluster.sessions.append(session)
        with rec.span("warm", label="warm.analyze"):
            await cluster.data.analyze(session)
        for q in sorted(set(cycle)):
            with rec.span("warm", label=f"warm.{q}", query=q):
                await session.execute(tpch.SQL[q].format(name=TABLE))


async def window(cluster, traffic: dict, seconds: float, rec) -> None:
    sessions = cluster.sessions
    # a count that moves under a static table is background maintenance
    ssts_before = cluster.data.sst_counts()
    deadline = time.perf_counter() + seconds

    async def client(i: int, cycle: list) -> None:
        k = 0
        while time.perf_counter() < deadline:
            q = cycle[k % len(cycle)]
            k += 1
            try:
                with rec.span("stmt", label=f"stmt.{q}", query=q,
                              client=i) as s:
                    s["rows"] = (await sessions[i].execute(
                        tpch.SQL[q].format(name=TABLE))).rows
            except Exception as e:   # noqa: BLE001 — counted as failed
                rec.error(e)

    await asyncio.gather(*(client(i, c)
                           for i, c in enumerate(traffic["clients"])))
    rec.ssts_per_tablet = (ssts_before, cluster.data.sst_counts())


async def verify(cluster, traffic: dict, rec, checks) -> None:
    """Every statement of the window against the reference over the rows
    the table holds (it is static, so one reference serves all)."""
    ref = tpch.reference(cluster.data.all_rows())
    for s in rec.of("stmt"):
        checks.note_all(tpch.compare(s["query"], s["rows"], ref))
    checks.note("batches_off_device",
                0 if cluster.device_evidence()["on_device"] else 1)
    checks.note("stmt_failed", len(rec.of("stmt", ok_only=False))
                - len(rec.of("stmt")))


def attempted_failed(rec) -> tuple:
    n = len(rec.of("stmt", ok_only=False))
    return n, n - len(rec.of("stmt"))


def end_to_end(cluster, traffic: dict, rec) -> dict:
    secs = rec.seconds("stmt")
    if not secs:
        return {}
    return {"scan_rows_per_s": (cluster.data.table_rows * len(secs)
                                / rec.window_s),
            "stmt_p95_ms": float(np.percentile(secs, 95)) * 1e3}
