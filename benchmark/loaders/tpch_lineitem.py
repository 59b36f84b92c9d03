"""Loader of the TPC-H configurations: LINEITEM at its full 16-column
layout, filled from `--seed`, and the table's state afterwards — every
acknowledged row, from which the reference is computed.

`load(cluster, config, seed, rows)` is what `run.py` calls: CREATE TABLE
through SQL; the initial population through `Tablet.bulk_load` on the
serving peers, `bulk_slices` SSTs per tablet, no slice twice; one RF1
refresh set through SQL INSERT, every statement acknowledged, then the
`flush` RPC.  Returns the state and the seconds of each step.  `rows`
(tests and rehearsals) scales the configuration's sizes down.
"""
from __future__ import annotations

import time

import numpy as np

from benchmark import tpch
from benchmark.cluster import require

TABLE = tpch.TABLE


class Lineitem:
    """What the table holds: `parts`, every acknowledged row in load
    order, and the next refresh set's order keys."""

    def __init__(self, cluster, sizes: dict, seed: int, rows: int = None):
        self.cluster, self.seed = cluster, seed
        share = (rows or sizes["rows"]) / sizes["rows"]
        self.rows = int(rows or sizes["rows"])
        self.orders = max(1, round(sizes["orders"] * share))
        self.refresh_orders = max(1, round(sizes["refresh_orders"] * share))
        self.refresh_rows = self.refresh_orders * int(
            sizes["refresh_rows_per_order"])
        self.insert_batch = int(sizes["insert_batch"])
        self.sf = self.orders / 1_500_000
        self.parts: list = []
        self.refresh_sets = 0
        self.ct = self.peers = None     # the client's table, its tablets

    def sst_counts(self) -> list:
        return [len(x) for x in self.cluster.sst_files(self.peers)]

    def insert_statements(self, data: dict) -> list:
        n, cols = len(data["l_orderkey"]), ", ".join(tpch.COLS)
        return [f"INSERT INTO {TABLE} ({cols}) VALUES " + ", ".join(
            "(" + ", ".join(map(tpch.literal, tpch.row(data, i))) + ")"
            for i in range(s, min(s + self.insert_batch, n)))
            for s in range(0, n, self.insert_batch)]

    def next_refresh_set(self) -> dict:
        """One RF1 set: the lineitems of `refresh_orders` new orders, keys
        out of the gaps dbgen leaves for them, drawn from (seed, set
        number).  It joins `parts` when the caller has seen every INSERT
        of it acknowledged (`acknowledged`)."""
        self.refresh_sets += 1
        return tpch.generate_lineitem(
            self.refresh_orders, self.refresh_rows,
            [self.seed, self.refresh_sets],
            first_order=(self.refresh_sets - 1) * self.refresh_orders,
            refresh=True, sf=self.sf)

    def acknowledged(self, data: dict) -> None:
        self.parts.append(data)

    @property
    def table_rows(self) -> int:
        return sum(len(p["l_orderkey"]) for p in self.parts)

    def all_rows(self) -> dict:
        return tpch.concat(self.parts)

    async def analyze(self, session) -> None:
        """ANALYZE gives the planner its statistics.  The program keeps
        them in the session, and an INSERT through the session voids
        them: every session that sends Q1 runs it, again after it has
        inserted."""
        await session.execute(f"ANALYZE {TABLE}")


async def load(cluster, config: dict, seed: int, rows: int = None):
    sizes = config["sizes"]
    data = Lineitem(cluster, sizes, seed, rows)
    steps, t = {}, time.perf_counter()

    def lap(name):
        nonlocal t
        now = time.perf_counter()
        steps[name], t = round(now - t, 3), now

    bulk = tpch.generate_lineitem(data.orders, data.rows, seed, sf=data.sf)
    lap("generate_s")
    tablets, slices = int(sizes["tablets"]), int(sizes["bulk_slices"])
    await cluster.sql.execute(tpch.DDL.format(name=TABLE, tablets=tablets))
    ct, peers = data.ct, data.peers = await cluster.peers(TABLE)
    require(len(peers) == tablets, len(peers))
    edges = np.linspace(0, data.rows, slices + 1).astype(int)
    loaded = 0
    for a, b in zip(edges[:-1], edges[1:]):
        sl = {k: v[a:b] for k, v in bulk.items()}
        for p in peers:
            loaded += p.tablet.bulk_load(sl)
    require(loaded == data.rows, loaded, data.rows)
    data.acknowledged(bulk)
    lap("bulk_load_s")
    first = data.next_refresh_set()
    for stmt in data.insert_statements(first):
        await cluster.sql.execute(stmt)
    data.acknowledged(first)
    for l in ct.locations:
        await cluster.maintenance("flush", ct, l.tablet_id)
    ssts = data.sst_counts()
    # (a tablet that got no row of a tiny rehearsal set flushes nothing)
    require(slices <= min(ssts) and max(ssts) == slices + 1, ssts)
    lap("insert_flush_s")
    return data, steps
