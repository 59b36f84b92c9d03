"""Closed-loop query streams whose every statement draws its own
substitution parameters (TPC-H's throughput test, clause 5.3.4: S query
streams at once, each with the parameters qgen gives it, clause 2.4): each
client is one `SqlSession` on the shared `YBClient`, an asyncio task that
sends its next statement when the last one has answered, over a static
table.  `query_streams` with the draw: a statement's parameters come from
numpy's generator seeded with (`--seed`, stream number), are recorded on
its `stmt` span, and `verify` holds its answer to the plain reference at
those parameters (`benchmark/tpch_qgen.py`).

Traffic parameters: `clients`, a list with one statement cycle per client
(`[["q6", "q1"], ["q1", "q6"]]`: two streams, out of step);
`trace_seconds`.
"""
from __future__ import annotations

import asyncio
import time

import numpy as np

from benchmark import manifest, tpch, tpch_qgen

TABLE = tpch.TABLE


async def warm(cluster, traffic: dict, rec) -> None:
    """Each client's session: ANALYZE, then each of its statements once at
    the validation parameters (batch build, host->device, compile: the
    literals are runtime values of the one program a statement shape
    has, so other parameters compile nothing — the window counts)."""
    for cycle in traffic["clients"]:
        session = cluster.session()
        cluster.sessions.append(session)
        with rec.span("warm", label="warm.analyze"):
            await cluster.data.analyze(session)
        for q in sorted(set(cycle)):
            with rec.span("warm", label=f"warm.{q}", query=q):
                await session.execute(
                    tpch_qgen.sql(q, tpch_qgen.VALIDATION[q], TABLE))


async def window(cluster, traffic: dict, seconds: float, rec) -> None:
    sessions = cluster.sessions
    # a count that moves under a static table is background maintenance
    ssts_before = cluster.data.sst_counts()
    deadline = time.perf_counter() + seconds

    async def client(i: int, cycle: list) -> None:
        rng = np.random.default_rng([cluster.data.seed, i])
        k = 0
        while time.perf_counter() < deadline:
            q = cycle[k % len(cycle)]
            k += 1
            params = tpch_qgen.draw(rng, q)
            text = tpch_qgen.sql(q, params, TABLE)
            try:
                with rec.span("stmt", label=f"stmt.{q}", query=q,
                              client=i, params=params) as s:
                    s["rows"] = (await sessions[i].execute(text)).rows
            except Exception as e:   # noqa: BLE001 — counted as failed
                rec.error(e)

    await asyncio.gather(*(client(i, c)
                           for i, c in enumerate(traffic["clients"])))
    rec.ssts_per_tablet = (ssts_before, cluster.data.sst_counts())


async def verify(cluster, traffic: dict, rec, checks) -> None:
    """Every statement of the window against the reference at that
    statement's own parameters, over the rows the table holds (it is
    static, so each distinct parameter set is computed once)."""
    ref = tpch_qgen.Reference(cluster.data.all_rows())
    for s in rec.of("stmt"):
        checks.note_all(tpch.compare(
            s["query"], s["rows"], ref.answer(s["query"], s["params"])))
    checks.note("batches_off_device",
                0 if cluster.device_evidence()["on_device"] else 1)
    checks.note("stmt_failed", len(rec.of("stmt", ok_only=False))
                - len(rec.of("stmt")))


# counted as `query_streams` counts: every client's statements
_streams = manifest.load_module(manifest.driver_file("query_streams"))
attempted_failed = _streams.attempted_failed
end_to_end = _streams.end_to_end
