"""A cached batch's 64-bit lanes as the two 32-bit lanes the chip
computes with (`ops/device_batch.py Pair`): the write times as their
uint32 words on every backend, a float64 value lane as its float32 high
part and remainder where the backend's float64 is itself such a pair
(the TPU's arm, steered here by patching `_backend_float64_is_pair`:
`jax.default_backend()` says `cpu`).  The words are exact; the mask over
them is the mask over uint64; the kernel gives the partials of whole
lanes that hold the same values (on the mesh and in row tiles:
`tests/test_mesh_scan.py`); the device holds the same bytes; and no
launch is handed a 64-bit array (`device.scan` / `device.fused_plan` tag `wide_lanes`).

A CPU computes a float64 exactly, so a pair there is not the float64 it
came from (it keeps 48 bits of the 53): the whole lanes it is held to
hold the pair's value, which is what the TPU computes on in either
form."""
import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import tpch as bench
from yugabyte_db_tpu.docdb.table_codec import TableCodec
from yugabyte_db_tpu.models import tpch as model
from yugabyte_db_tpu.ops import device_batch as db
from yugabyte_db_tpu.ops.device_batch import (
    HT_NONE, WORD_MAX, DeviceBlockCache, Pair, batch_bytes, batch_placement,
    build_batch, f64_pair, join, u64_pair, words)
from yugabyte_db_tpu.ops.scan import ScanKernel, visibility_mask
from yugabyte_db_tpu.utils import flags
from yugabyte_db_tpu.utils.hybrid_time import HybridTime
from yugabyte_db_tpu.utils.trace import TRACES

MAX = int(HT_NONE)
QUERIES = {"q6": model.TPCH_Q6, "q1": model.TPCH_Q1}
#: the 64-bit lanes a `linked` launch of each query was handed whole
#: before the lanes were pairs: `ht`, `next_ht` and the float64 values
#: (Q6 price and discount, Q1 also tax; quantity is int32)
WHOLE_LANES = {"q6": 4, "q1": 5}


@pytest.fixture(autouse=True)
def float64_lanes():
    flags.set_flag("device_float_dtype", "float64")
    yield
    flags.REGISTRY.reset("device_float_dtype")


@pytest.fixture
def tpu_pairs(monkeypatch):
    """The TPU's arm: a float64 value lane ships as a pair."""
    monkeypatch.setattr(db, "_backend_float64_is_pair", lambda: True)


def wholly(batch):
    """`batch` as whole lanes: every `Pair` joined into the one 64-bit
    lane it holds, on the devices its words sit on — what a launch was
    handed before the lanes were pairs."""
    one = jax.jit(join)
    j = lambda x: one(x) if isinstance(x, Pair) else x
    return dataclasses.replace(
        batch, cols={c: j(v) for c, v in batch.cols.items()},
        ht=j(batch.ht), next_ht=j(batch.next_ht))


def dbgen_blocks(rows: int, seed: int, parts: int = 1) -> list:
    """`rows` rows of `benchmark/tpch.py`'s generator (dbgen's shapes:
    prices in cents, discounts and taxes in hundredths) in the model's
    eight-column LINEITEM, cut into `parts` lists of blocks."""
    d = bench.generate_lineitem(rows // 4, rows, seed)
    data = {"rowid": np.arange(rows, dtype=np.int64),
            **{c: d[c] for c in ("l_quantity", "l_extendedprice",
                                 "l_discount", "l_tax", "l_shipdate")},
            "l_returnflag": np.searchsorted(
                [b"A", b"N", b"R"], d["l_returnflag"]).astype(np.int32),
            "l_linestatus": (d["l_linestatus"] == b"O").astype(np.int32)}
    codec = TableCodec(model.lineitem_info())
    step = rows // parts
    return [codec.bulk_blocks({k: v[i * step:(i + 1) * step]
                               for k, v in data.items()},
                              HybridTime.from_micros(100 + i))
            for i in range(parts)]


# --- the words -----------------------------------------------------------

HIGH = 0x1234_5678
TIMES = {
    "ht_none": [MAX],
    "edges": [0, 1, WORD_MAX, WORD_MAX + 1, MAX - 1, MAX],
    "one_high_word": [(HIGH << 32) | lo for lo in
                      (0, 1, 0x8000_0000, 0xFFFF_FFFE, 0xFFFF_FFFF)],
    "one_low_word": [(hi << 32) | 0x9ABC_DEF0 for hi in
                     (0, 1, 0x8000_0000, 0xFFFF_FFFF)],
    "random": np.random.default_rng(7).integers(
        0, MAX, 1000, dtype=np.uint64, endpoint=True).tolist(),
}


@pytest.mark.parametrize("name", sorted(TIMES))
def test_write_times_round_trip_through_their_words(name):
    a = np.array(TIMES[name], np.uint64)
    p = u64_pair(a)
    assert (p.hi.dtype, p.lo.dtype, p.dtype) == (
        np.uint32, np.uint32, np.uint64)
    np.testing.assert_array_equal(
        (p.hi.astype(np.uint64) << np.uint64(32)) | p.lo, a)
    # joined inside a program, and split there as `words` splits a
    # whole lane or `read_ht`
    np.testing.assert_array_equal(
        np.asarray(jax.jit(join)(db._to_device(p))), a)
    w = jax.jit(words)(jnp.asarray(a))
    np.testing.assert_array_equal(np.asarray(w.hi), p.hi)
    np.testing.assert_array_equal(np.asarray(w.lo), p.lo)
    if name == "ht_none":
        assert (int(p.hi[0]), int(p.lo[0])) == (WORD_MAX, WORD_MAX)


def mask_u64(mode, valid, ht, nxt, tomb, read):
    """The mask over uint64 lanes, in numpy."""
    m = valid & (ht <= read) & ~tomb
    if mode == "visible":
        return m
    return m & ((nxt == HT_NONE) | (nxt > read))


@pytest.mark.parametrize("mode", ["visible", "linked"])
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_mask_over_words_is_the_mask_over_uint64(mode, seed):
    """Times on four high words, so that rows and read points often
    share the high word and differ in the low one; the read points
    include 0, each side of a row's time and MAX ("latest")."""
    rng = np.random.default_rng(seed)
    n = 4096
    high = rng.integers(0, 4, n, dtype=np.uint64) + np.uint64(0x5F000)
    ht = (high << np.uint64(32)) | rng.integers(
        0, 1 << 32, n, dtype=np.uint64)
    ht[:4] = [0, 1, MAX - 1, WORD_MAX]
    step = rng.integers(1, 1 << 34, n, dtype=np.uint64)
    nxt = np.where((rng.random(n) < 0.4) | (ht > HT_NONE - step), HT_NONE,
                   ht + step)
    tomb, valid = rng.random(n) < 0.1, rng.random(n) < 0.95
    probe = ht[rng.integers(4, n, 6)]
    reads = [0, MAX, MAX - 1, WORD_MAX, *probe, *(probe - np.uint64(1)),
             *(probe + np.uint64(1)), *((probe >> np.uint64(32))
                                        << np.uint64(32))]
    fn = jax.jit(functools.partial(visibility_mask, mode))
    pairs = db._to_device((u64_pair(ht), u64_pair(nxt)))
    for read in map(np.uint64, reads):
        want = mask_u64(mode, valid, ht, nxt, tomb, read)
        got = np.asarray(fn(valid, pairs[0], pairs[1], tomb, read))
        np.testing.assert_array_equal(got, want, err_msg=str(read))
        # a whole uint64 lane is split by the same helper: one mask
        whole = np.asarray(fn(valid, ht, nxt, tomb, read))
        np.testing.assert_array_equal(whole, want, err_msg=str(read))


# --- the float64 pairs ---------------------------------------------------

def test_a_float64_pair_is_its_float32_part_and_remainder():
    rng = np.random.default_rng(3)
    a = np.concatenate([
        bench.generate_lineitem(500, 2000, 5)["l_extendedprice"],
        rng.integers(0, 11, 100) / 100.0, rng.normal(0, 1e6, 1000),
        [0.0, -0.0, 1.0, 0.05, 0.07, 1e-30, 3e38]])
    p = f64_pair(a)
    assert (p.hi.dtype, p.lo.dtype, p.dtype) == (
        np.float32, np.float32, np.float64)
    np.testing.assert_array_equal(p.hi, a.astype(np.float32))
    joined = p.hi.astype(np.float64) + p.lo.astype(np.float64)
    # 48 bits of the 53: the remainder rounded to float32
    assert (np.abs(joined - a) <= np.abs(a) * 2.0 ** -47).all()
    # the remainder is below half a unit of the high part's last place
    assert (np.abs(p.lo) <= np.spacing(np.abs(p.hi)) / 2).all()
    # what float32 cannot hold is not summed into a NaN
    odd = f64_pair(np.array([np.inf, -np.inf, np.nan, 1e300]))
    assert odd.lo.tolist() == [0.0] * 4
    assert np.isinf(odd.hi[[0, 1, 3]]).all() and np.isnan(odd.hi[2])


@pytest.mark.parametrize("mode", ["visible", "linked"])
@pytest.mark.parametrize("query", ["q6", "q1"])
def test_pair_partials_equal_whole_lanes(tpu_pairs, query, mode):
    """The one-device kernel on pairs and on whole lanes of the same
    values: the same int64 fixed-point partials, bit for bit."""
    q = QUERIES[query]
    batch = build_batch(dbgen_blocks(20_000, 11)[0], sorted(q.columns),
                        multi_version=mode == "linked")
    assert isinstance(batch.ht, Pair)
    assert {c for c, v in batch.cols.items() if isinstance(v, Pair)} \
        == {c for c in (model.EXTPRICE, model.DISCOUNT, model.TAX)
            if c in q.columns}
    read_ht = HybridTime.from_micros(10_000).value
    kernel = ScanKernel()
    got = kernel.run(batch, q.where, q.aggs, q.group, read_ht)
    want = kernel.run(wholly(batch), q.where, q.aggs, q.group, read_ht)
    assert kernel.compiles == 2       # a pair is not its lane's program
    np.testing.assert_array_equal(np.asarray(got[2]), np.asarray(want[2]))
    for x, y in zip(jax.tree_util.tree_leaves(got[:2]),
                    jax.tree_util.tree_leaves(want[:2])):
        assert np.asarray(x).dtype == np.asarray(y).dtype
        np.testing.assert_array_equal(x, y)
    assert int(np.sum(got[1])) > 0


def test_the_cpu_keeps_whole_float64_lanes():
    batch = build_batch(dbgen_blocks(5000, 2)[0],
                        sorted(model.TPCH_Q1.columns), multi_version=True)
    assert isinstance(batch.ht, Pair) and isinstance(batch.next_ht, Pair)
    assert not any(isinstance(v, Pair) for v in batch.cols.values())
    assert batch.cols[model.EXTPRICE].dtype == np.float64
    assert batch.cols[model.QTY].dtype == np.int32


# --- the bytes on the device ---------------------------------------------

def _mesh_batch(columns):
    from yugabyte_db_tpu.parallel import tablet_mesh
    from yugabyte_db_tpu.parallel.distributed_scan import build_sharded_batch
    return build_sharded_batch(tablet_mesh(4, devices=jax.devices()[:4]),
                               dbgen_blocks(16_000, 4, parts=4),
                               columns, multi_version=True)


def _one_batch(columns):
    return build_batch(dbgen_blocks(16_000, 4)[0], columns,
                       multi_version=True)


@pytest.mark.skipif(len(jax.devices()) < 4, reason="needs 4 devices")
@pytest.mark.parametrize("build", [_mesh_batch, _one_batch])
def test_pairs_hold_the_bytes_whole_lanes_held(tpu_pairs, build):
    """8 B a row either way: `batch_bytes` and the per-chip placement the
    cache accounts, of the batch, of Q6's narrowing of Q1's batch, and of
    the entry `get_covering` hands Q6."""
    q1, q6 = sorted(model.TPCH_Q1.columns), sorted(model.TPCH_Q6.columns)
    batch = build(q1)
    assert isinstance(batch.cols[model.EXTPRICE], Pair)
    whole = wholly(batch)
    assert not isinstance(whole.cols[model.EXTPRICE], Pair)
    assert batch_bytes(batch) == batch_bytes(whole) > 0
    assert batch_placement(batch) == batch_placement(whole)
    if hasattr(batch, "narrowed"):
        six = batch.narrowed(q6)
        assert six.cols[model.EXTPRICE] is batch.cols[model.EXTPRICE]
        assert six.ht is batch.ht
        assert batch_bytes(six) == batch_bytes(whole.narrowed(q6))
    cache = DeviceBlockCache()
    cache.get_or_build(("t", tuple(q1), "ssts"), lambda: batch)
    assert cache.get_covering(("t", tuple(q6), "ssts"), 1) is batch
    assert cache.bytes_by_chip() == batch_placement(whole)


# --- what a launch is handed ---------------------------------------------

def _tags(kind: str, run) -> list:
    with TRACES.trace("wide") as t:
        run()
    return [s.tags["wide_lanes"] for s in TRACES.recent
            if s.trace_id == t.trace_id and s.name == f"device.{kind}"]


def _scan(batch, q):
    return ScanKernel().run(batch, q.where, q.aggs, q.group,
                            HybridTime.from_micros(10_000).value)


def _mesh(batch, q):
    from yugabyte_db_tpu.parallel.distributed_scan import \
        DistributedScanKernel
    return DistributedScanKernel().run(batch, q.where, q.aggs, q.group,
                                       HybridTime.from_micros(10_000).value)


def _fused(batch, q):
    from yugabyte_db_tpu.ops.plan_fusion import FusedPlanKernel
    return FusedPlanKernel().run(batch, q.where, q.aggs, q.group,
                                 HybridTime.from_micros(10_000).value, ())


@pytest.mark.skipif(len(jax.devices()) < 4, reason="needs 4 devices")
@pytest.mark.parametrize("query", ["q6", "q1"])
@pytest.mark.parametrize("kind, build, run", [
    ("scan", _one_batch, _scan), ("scan", _mesh_batch, _mesh),
    ("fused_plan", _one_batch, _fused)], ids=["scan", "mesh", "fused_plan"])
def test_no_launch_is_handed_a_64_bit_lane(tpu_pairs, query, kind, build,
                                           run):
    """0 on the TPU's arm; a batch of whole lanes, built so on purpose,
    hands the launch each 64-bit lane (the count before the pairs)."""
    q = QUERIES[query]
    batch = build(sorted(q.columns))
    assert _tags(kind, lambda: run(batch, q)) == [0]
    assert _tags(kind, lambda: run(wholly(batch), q)) \
        == [WHOLE_LANES[query]]
