"""Q1 and Q6 at drawn substitution parameters (`benchmark/tpch_qgen.py`,
the driver `query_streams_qgen`): the statement text, the plain reference
at any parameter set against the accepted one at the validation set, the
system against both copies of the reference over seeded rows, and the
program cache over fifty drawn sets.  One cluster serves the module."""
import asyncio

import numpy as np
import pytest

from benchmark import control_qgen, manifest, tpch, tpch_qgen
from benchmark.cluster import Cluster
from benchmark.record import Checks

ROWS = 24000
# the ends of every range, both QUANTITY values, and sets in between
Q6_SETS = [(1993, 2, 24), (1997, 9, 25), (1994, 6, 24), (1994, 6, 25),
           (1995, 9, 24), (1996, 5, 25), (1993, 2, 25), (1997, 3, 24)]
Q1_DELTAS = [60, 120, 90, 77, 61, 119, 100, 83]


def _cell():
    return manifest.Cell(manifest.load(), "scan_streams2")


@pytest.fixture(scope="module")
def served():
    """(loop, two sessions, the rows, the reference over them): the
    cell's table at 24,000 rows."""
    import jax
    cell = _cell()
    loop = asyncio.new_event_loop()
    cluster = Cluster(cell.config.get("flags", {}), jax.devices()[:1])

    async def start():
        await cluster.start()
        cluster.data, _ = await cell.loader.load(cluster, cell.config, 38,
                                                 ROWS)
        sessions = [cluster.session(), cluster.session()]
        for s in sessions:
            await cluster.data.analyze(s)
        return sessions

    try:
        sessions = loop.run_until_complete(start())
        data = cluster.data.all_rows()
        yield loop, sessions, data, tpch_qgen.Reference(data)
    finally:
        loop.run_until_complete(cluster.shutdown())
        loop.close()


def _params(query, p):
    return ({"delta": p} if query == "q1" else
            dict(zip(("year", "discount", "quantity"), p)))


def test_text_at_the_validation_parameters_is_the_accepted_text():
    for q in ("q1", "q6"):
        assert tpch_qgen.sql(q, tpch_qgen.VALIDATION[q], "t") == \
            tpch.SQL[q].format(name="t")
    assert tpch_qgen.sql("q6", _params("q6", (1997, 2, 25))).endswith(
        "WHERE l_shipdate >= 9862 AND l_shipdate < 10227 AND l_discount "
        "BETWEEN 0.01 AND 0.03 AND l_quantity < 25")
    assert "l_shipdate <= 10441 GROUP" in tpch_qgen.sql("q1", {"delta": 120})


def test_draws_stay_inside_the_sources_ranges_and_follow_the_seed():
    for q in ("q1", "q6"):
        assert tpch_qgen.draw(np.random.default_rng([5, 0]), q) == \
            tpch_qgen.draw(np.random.default_rng([5, 0]), q)
    rng = np.random.default_rng([2147484001, 1])
    q1 = [tpch_qgen.draw(rng, "q1")["delta"] for _ in range(2000)]
    q6 = [tpch_qgen.draw(rng, "q6") for _ in range(2000)]
    assert set(q1) == set(range(60, 121))
    assert {d["year"] for d in q6} == set(range(1993, 1998))
    assert {d["discount"] for d in q6} == set(range(2, 10))
    assert {d["quantity"] for d in q6} == {24, 25}


def test_reference_at_the_validation_parameters_is_the_accepted_one():
    data = tpch.generate_lineitem(30000, 120000, 11)
    old, new = tpch.reference(data), tpch_qgen.Reference(data)
    for q in ("q1", "q6"):
        gaps = tpch.compare(q, tpch.as_rows(
            q, new.answer(q, tpch_qgen.VALIDATION[q])), old)
        assert all(v <= 1e-5 for v in gaps.values()), gaps


@pytest.mark.parametrize("query, p, session", [
    (q, p, i % 2) for q, sets in (("q6", Q6_SETS), ("q1", Q1_DELTAS))
    for i, p in enumerate(sets)])
def test_the_system_answers_each_parameter_set_as_both_references(
        served, query, p, session):
    """Through `SqlSession.execute`, against the benchmark's reference
    under the configuration's limits and against the models' own copy."""
    from yugabyte_db_tpu.models import tpch as models_tpch
    loop, sessions, data, reference = served
    params = _params(query, p)
    rows = loop.run_until_complete(sessions[session].execute(
        tpch_qgen.sql(query, params))).rows
    checks = Checks(_cell().config["limits"])
    gaps = tpch.compare(query, rows, reference.answer(query, params))
    checks.note_all(gaps)
    assert all(e["ok"] for e in checks.table().values()
               if e["value"] is not None), gaps
    want = models_tpch.numpy_reference_at(query, data, **params)
    assert models_tpch.sql_at(query, tpch.TABLE, **params) == \
        tpch_qgen.sql(query, params)
    if query == "q6":
        assert rows[0]["revenue"] == pytest.approx(want, rel=1e-9)
        assert want > 0
    else:
        got = {r["l_returnflag"] + r["l_linestatus"]: r for r in rows}
        assert set(got) == set(want) and len(want) >= 3
        for g, w in want.items():
            assert int(got[g]["count_order"]) == w["count_order"]
            for k in ("sum_qty", *tpch.Q1_SUMS):
                assert got[g][k] == pytest.approx(w[k], rel=1e-9)


def test_fifty_drawn_sets_compile_nothing(served):
    from yugabyte_db_tpu.docdb.operations import _SHARED_KERNEL
    loop, sessions, _, _ = served

    async def send(n):
        rng = np.random.default_rng([38, 7])
        for k in range(n):
            q = ("q6", "q1")[k % 2]
            await sessions[k % 2].execute(
                tpch_qgen.sql(q, tpch_qgen.draw(rng, q)))

    loop.run_until_complete(send(2))          # each shape once
    before = (_SHARED_KERNEL.compiles, len(_SHARED_KERNEL._cache))
    loop.run_until_complete(send(50))
    assert (_SHARED_KERNEL.compiles, len(_SHARED_KERNEL._cache)) == before


def test_control_reads_the_four_ends_of_the_ranges():
    cell = _cell()
    sizes = {**cell.config["sizes"], "rows": 60000, "orders": 15000,
             "refresh_orders": 15}
    lines = control_qgen.control_readings(sizes, 3, cell.config["limits"])
    assert [(l["query"], l["params"]) for l in lines] == \
        list(control_qgen.ENDS)
    for line in lines:
        assert line["compared"]["sum_usd"][0] > 0     # float32 is not exact
        assert line["compared"]["sum_usd"][1] == 100
