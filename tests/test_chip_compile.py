"""Ask the chip's compiler about the served path's programs — without a
chip.  Each test lowers one program of the SQL -> tserver -> device path
at the SERVED shapes for a described (not attached) v5e and compiles it
with the TPU compiler installed here: what the compiler would refuse or
stall on at first contact with the chip fails here instead, at no chip
time.  Nothing runs, so these tests say nothing about results or speed.

`jax.default_backend()` still says `cpu` during such a compile, so the
TPU arm of each backend branch is steered from here (f32 value lanes,
`unroll` group strategy), never through an option of the program.

The topology is described inside a module-scoped fixture — not at
import, not in conftest.py — so that under several xdist workers only
the worker that is given this file loads the TPU library.
"""
import os
import re
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, SingleDeviceSharding

from yugabyte_db_tpu.utils import flags

#: what the server uses: `streaming_chunk_rows` and `compaction_chunk_rows`
SCAN_ROWS = 1 << 20
MERGE_ROWS = 524_288

#: a program whose compile outlasts this does not, for a machine that
#: starts with an empty cache, compile (ISSUE 22 §2).  The bound is
#: doubled against the 60 s target because the driver runs this file
#: next to five other workers on eight cores.
COMPILE_BUDGET_S = 120.0


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:   # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def tpu_arms():
    """The arms `jax.default_backend() != "cpu"` would pick."""
    old = {k: flags.get(k)
           for k in ("device_float_dtype", "scan_group_strategy")}
    flags.set_flag("device_float_dtype", "float32")
    flags.set_flag("scan_group_strategy", "unroll")
    yield
    for k, v in old.items():
        flags.set_flag(k, v)


def _compile(lower):
    """Compile what `lower()` lowers, inside the budget."""
    t0 = time.time()
    compiled = lower().compile()
    secs = time.time() - t0
    print(f"compiled in {secs:.1f}s")
    assert secs < COMPILE_BUDGET_S, f"compile took {secs:.0f}s"
    return compiled


#: an `X64SplitHigh` / `X64SplitLow` custom call whose result is an array
#: of a lane's length (10,000 rows or more): the chip splitting a whole
#: 64-bit lane into 32-bit halves at the program's entry (one on a
#: launch's few runtime scalars, the packed host vectors, costs nothing)
_LANE_SPLIT = re.compile(
    r"= \w+\[\d{5,}[\d,]*\]\S* custom-call\(.*custom_call_target=\"X64Split")


def _lane_splits(text: str) -> int:
    return sum(bool(_LANE_SPLIT.search(line)) for line in text.splitlines())


def _lanes_as(lanes: str, shape):
    """`shape` (a leaf -> ShapeDtypeStruct function) over an argument
    tree whose `Pair`s are kept as their two words (`pairs`) or made the
    one 64-bit lane each holds (`whole`: what a launch was handed before
    the batches held pairs)."""
    from yugabyte_db_tpu.ops.device_batch import Pair

    def one(x):
        if not isinstance(x, Pair):
            return shape(x)
        if lanes == "pairs":
            return Pair(shape(x.hi), shape(x.lo))
        hi = shape(x.hi)
        return jax.ShapeDtypeStruct(hi.shape, x.dtype, sharding=hi.sharding)
    return lambda tree: jax.tree_util.tree_map(
        one, tree, is_leaf=lambda x: isinstance(x, Pair))


def _scan_args(query, n_total, mvcc_mode="visible"):
    """(the launch, example rows): a served launch — `ops.scan.
    prepare_launch`, which `ScanKernel.run`, `DistributedScanKernel.run`
    and `__graft_entry__.entry()` call — on a small batch with the TPU
    arm's dtypes; the MVCC lanes (`args[4:7]`) are those `mvcc_lanes`
    hands out for `mvcc_mode`.  A described device can hold no array,
    so callers turn its argument list into shapes."""
    from __graft_entry__ import _example_batch
    from yugabyte_db_tpu.ops.device_batch import build_batch
    from yugabyte_db_tpu.ops.scan import prepare_launch
    batch = build_batch(_example_batch(), sorted(query.columns),
                        multi_version=mvcc_mode == "linked")
    assert batch.cols[2].dtype == jnp.float32       # l_extendedprice
    assert batch.ht.dtype == jnp.uint64             # as its two words
    job = prepare_launch(batch, query.where, query.aggs, query.group,
                         1 << 63, n_total=n_total)
    _, _, _, mode, _, strategy, _ = job.key
    assert strategy == "unroll"
    assert mode == mvcc_mode
    assert (job.args[5] is not None) == (mode == "linked")
    return job, batch.padded_rows


def _shapes(tree, small: int, rows_shape, row_sharding, scalar_sharding):
    """`tree` as ShapeDtypeStructs: every `small`-row vector becomes
    `rows_shape` on `row_sharding`, anything else keeps its shape."""
    def one(x):
        x = jnp.asarray(x)      # a Python literal stays weakly typed
        if x.shape == (small,):
            return jax.ShapeDtypeStruct(rows_shape, x.dtype,
                                        sharding=row_sharding)
        return jax.ShapeDtypeStruct(x.shape, x.dtype,
                                    sharding=scalar_sharding,
                                    weak_type=x.weak_type)
    return jax.tree_util.tree_map(one, tree)


@pytest.mark.parametrize("mvcc_mode", ["visible", "linked"])
@pytest.mark.parametrize("query_name", ["q6", "q1"])
def test_scan_kernel_compiles(one_chip, tpu_arms, query_name, mvcc_mode):
    """Flat (Q6) and grouped (Q1) scan, single-version (`visible`) and
    multi-version (`linked`: the `next_ht` lane) MVCC, f32 value lanes
    and u64 time lanes, at the streaming bucket.  No served scan
    program sorts: the mask is elementwise in either mode."""
    from yugabyte_db_tpu.models import tpch
    from yugabyte_db_tpu.ops.scan import scan_program
    query = {"q6": tpch.TPCH_Q6, "q1": tpch.TPCH_Q1}[query_name]
    job, small = _scan_args(query, SCAN_ROWS, mvcc_mode)
    fn, _ = scan_program(*job.key)
    shapes = _shapes(job.args, small, (SCAN_ROWS,), one_chip, one_chip)
    compiled = _compile(lambda: jax.jit(fn).lower(*shapes))
    text = compiled.as_text()
    assert "sort" not in text and "while" not in text


#: `scan_power` / `scan_streams2`: a tablet of 1.5M rows, padded
SERVED_ROWS = 1 << 21
#: `X64Split` calls on whole lanes of a `linked` launch before the lanes
#: were pairs: `ht`, `next_ht` and the float64 values, each split high
#: and low (Q6 price and discount, Q1 also tax)
WHOLE_LANE_SPLITS = {"q6": 8, "q1": 10}


@pytest.mark.parametrize("lanes", ["pairs", "whole"])
@pytest.mark.parametrize("query_name", ["q6", "q1"])
def test_served_scan_splits_no_whole_lane(one_chip, monkeypatch,
                                          query_name, lanes):
    """The one-device programs of the one-chip cells — `linked`, the
    configuration's float64 on the TPU's arm, 2,097,152 rows — take
    every 64-bit lane of the batch as two 32-bit lanes, so the chip
    splits none at the program's entry; the same program handed whole
    lanes splits each (the count before the pairs)."""
    from __graft_entry__ import _example_batch
    from yugabyte_db_tpu.models import tpch
    from yugabyte_db_tpu.ops import device_batch
    from yugabyte_db_tpu.ops.scan import prepare_launch, scan_program
    query = {"q6": tpch.TPCH_Q6, "q1": tpch.TPCH_Q1}[query_name]
    monkeypatch.setattr(device_batch, "_backend_float64_is_pair",
                        lambda: True)
    flags.set_flag("device_float_dtype", "float64")
    flags.set_flag("scan_group_strategy", "unroll")
    try:
        batch = device_batch.build_batch(
            _example_batch(), sorted(query.columns), multi_version=True)
        job = prepare_launch(batch, query.where, query.aggs, query.group,
                             1 << 63, n_total=SERVED_ROWS)
    finally:
        for f in ("device_float_dtype", "scan_group_strategy"):
            flags.REGISTRY.reset(f)
    _, _, _, mode, _, strategy, _ = job.key
    assert (mode, strategy) == ("linked", "unroll")
    assert isinstance(batch.cols[tpch.EXTPRICE], device_batch.Pair)
    fn, _ = scan_program(*job.key)
    small = batch.padded_rows
    shapes = _lanes_as(lanes, lambda x: _shapes(
        x, small, (SERVED_ROWS,), one_chip, one_chip))(job.args)
    text = _compile(lambda: jax.jit(fn).lower(*shapes)).as_text()
    assert _lane_splits(text) == (
        0 if lanes == "pairs" else WHOLE_LANE_SPLITS[query_name])
    # what is left: the runtime scalars' splits (the packed int64 and
    # float64 host vectors)
    assert "X64Split" in text


def test_sort_grouped_kernel_compiles(one_chip, tpu_arms):
    """Q1 grouped by sort + segments (HashGroupSpec): the GROUP BY route
    of a table that was never ANALYZEd."""
    from yugabyte_db_tpu.models import tpch
    from yugabyte_db_tpu.ops.scan import HashGroupSpec, scan_program
    q = tpch.TPCH_Q1
    job, small = _scan_args(q, SCAN_ROWS)
    where, aggs, _, mode, static_sums, strategy, lits = job.key
    fn, _ = scan_program(
        where, aggs, HashGroupSpec((tpch.RETFLAG, tpch.LINESTATUS)),
        mode, static_sums, strategy, lits)
    shapes = _shapes(job.args, small, (SCAN_ROWS,), one_chip, one_chip)
    assert "sort" in _compile(lambda: jax.jit(fn).lower(*shapes)).as_text()


@pytest.mark.parametrize("key_words", [2, 3])
def test_chunk_merge_kernel_compiles(one_chip, key_words):
    """The compaction frontier merge at `compaction_chunk_rows`."""
    from yugabyte_db_tpu.ops.compaction import chunk_merge_kernel

    def s(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    m, u64, u32, b = MERGE_ROWS, jnp.uint64, jnp.uint32, jnp.bool_
    compiled = _compile(lambda: chunk_merge_kernel.lower(
        s((m, key_words), u64), s((m,), u64), s((m,), u32), s((m,), b),
        s((m,), b),
        s((key_words,), u64), s((), u64), s((), u32), s((), b),
        s((key_words,), u64), s((), u64), s((), u32), s((), b), s((), b),
        s((), u64), num_dk_words=key_words))
    assert "sort" in compiled.as_text()


def test_distributed_scan_compiles_for_four_chips(topo, tpu_arms):
    """The four-shard `distributed_scan_aggregate` program (Q1) on a
    mesh of the described devices: the combine is an all-reduce."""
    from yugabyte_db_tpu.models.tpch import TPCH_Q1 as q
    from yugabyte_db_tpu.parallel.distributed_scan import \
        DistributedScanKernel
    from yugabyte_db_tpu.parallel.mesh import (BLOCKS_AXIS, TABLETS_AXIS,
                                               TabletMesh)
    tm = TabletMesh(Mesh(np.array(topo.devices).reshape(4, 1),
                         (TABLETS_AXIS, BLOCKS_AXIS)))
    job, small = _scan_args(q, SCAN_ROWS * 4)
    fn = DistributedScanKernel()._get((id(tm.mesh),) + job.sig, tm,
                                      *job.key)
    shapes = _shapes(job.args, small, (4 * SCAN_ROWS,), tm.row_sharding(),
                     tm.replicated())
    compiled = _compile(lambda: fn.lower(*shapes))
    assert "all-reduce" in compiled.as_text()


#: `mesh4_q1_psum`: two tablets of 7.5M rows a chip, padded to one bucket
MESH_SHARD_ROWS = 1 << 24


@pytest.mark.parametrize("lanes", ["pairs", "whole"])
def test_served_mesh_scan_compiles_for_four_chips(topo, tmp_path,
                                                  monkeypatch, lanes):
    """The programs one tserver that owns four chips launches for Q6 and
    Q1 through SQL (`docdb/mesh_read.py`): `linked` mask with `next_ht`
    per shard, float64 lanes, Q1 grouped by two text columns as codes of
    a global dictionary (its body a loop over row tiles), every additive
    partial in one all-reduce — at the shard size of `mesh4_q1_psum`, on
    a mesh of the described chips.
    The statements run here first, on four of the CPU's devices, to take
    each program's own arguments; what is compiled is the TPU's arm
    (`unroll`, float64 lanes as pairs).  With `pairs` the chip splits no
    whole lane at a program's entry; handed `whole` lanes, as before the
    batches held pairs, it splits each, high and low."""
    import asyncio
    from test_mesh_scan import TABLE, Served
    from benchmark import tpch
    from yugabyte_db_tpu.ops import device_batch
    from yugabyte_db_tpu.parallel.distributed_scan import \
        DistributedScanKernel
    from yugabyte_db_tpu.parallel.mesh import (BLOCKS_AXIS, TABLETS_AXIS,
                                               TabletMesh)
    seen = []

    class Recording(DistributedScanKernel):
        def _get(self, *key):
            fn = super()._get(*key)

            def call(*args):
                seen.append((key, args))
                return fn(*args)
            return call

    async def statements():
        async with Served(str(tmp_path), 4) as t:
            t.ts.mesh_reader.kernel = Recording()
            for q in ("q6", "q1"):
                await t.sql.execute(tpch.SQL[q].format(name=TABLE))

    flags.set_flag("scan_group_strategy", "unroll")
    monkeypatch.setattr(device_batch, "_backend_float64_is_pair",
                        lambda: True)
    try:
        asyncio.run(statements())
    finally:
        for f in ("scan_group_strategy", "tserver_device_chips",
                  "device_float_dtype", "tpu_min_rows_for_pushdown"):
            flags.REGISTRY.reset(f)
    assert [key[5] for key, _ in seen] == ["linked", "linked"]
    tm = TabletMesh(Mesh(np.array(topo.devices).reshape(4, 1),
                         (TABLETS_AXIS, BLOCKS_AXIS)))
    rows, everywhere = tm.row_sharding(), tm.replicated()
    small = seen[0][1][3].shape[0]      # `valid`: the four shards' rows

    def shape(x):
        x = jnp.asarray(x)
        if x.shape == (small,):
            return jax.ShapeDtypeStruct((4 * MESH_SHARD_ROWS,), x.dtype,
                                        sharding=rows)
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=everywhere,
                                    weak_type=x.weak_type)

    for (sig, _, *key), args in seen:
        _, _, group, _, _, strategy, _ = key
        assert strategy == "unroll"
        assert {str(v.dtype) for v in args[0].values()} \
            == {"float64", "int32"}
        fn = DistributedScanKernel()._get(
            (id(tm.mesh),) + sig[1:], tm, *key)
        compiled = _compile(lambda: fn.lower(*_lanes_as(lanes, shape)(args)))
        text = compiled.as_text()
        assert _lane_splits(text) == (0 if lanes == "pairs" else
                                      WHOLE_LANE_SPLITS[
                                          "q6" if group is None else "q1"])
        assert text.count("all-reduce(") + text.count("all-reduce-start(") \
            == 1, "every additive partial rides one all-reduce"
        # Q6 runs whole, Q1's grouped body in one loop over four row
        # tiles (`ops.scan.tile_count`)
        assert "sort" not in text
        assert text.count(" while(") == (group is not None)
        # a shard's bool lane is the one-device kernel's packed [N]
        # lane, one byte a row, not a [1, 1, N] array padded to four
        assert f"pred[{MESH_SHARD_ROWS}]" in text
        assert f"pred[1,1,{MESH_SHARD_ROWS}]" not in text
        mem = compiled.memory_analysis()
        assert mem.argument_size_in_bytes + mem.temp_size_in_bytes \
            < 8 << 30
        print(group, mem.argument_size_in_bytes, mem.temp_size_in_bytes)
