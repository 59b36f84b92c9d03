"""Distributed scan / sharded vector search over the virtual 8-device CPU
mesh (the MiniCluster analog for the TPU data plane — reference tests run
real multi-node stacks in-process, src/yb/integration-tests/mini_cluster.h)."""
import jax
import numpy as np
import pytest

from yugabyte_db_tpu.ops import AggSpec, Expr
from yugabyte_db_tpu.ops.scan import GroupSpec
from yugabyte_db_tpu.parallel import tablet_mesh, sharded_exact_search
from yugabyte_db_tpu.parallel.distributed_scan import (
    build_sharded_batch, distributed_scan_aggregate, DistributedScanKernel,
)
from yugabyte_db_tpu.storage.columnar import ColumnarBlock

C = Expr.col

pytestmark = pytest.mark.skipif(
    len(jax.devices()) < 8, reason="needs 8 virtual devices")


def shard_block(n, seed, uniq=True):
    rng = np.random.default_rng(seed)
    qty = rng.uniform(0, 50, n)
    flag = rng.integers(0, 4, n).astype(np.int32)
    return ColumnarBlock.from_arrays(
        schema_version=1,
        key_hash=rng.integers(0, 2**63, n).astype(np.uint64),
        ht=np.full(n, 10, np.uint64),
        fixed={1: (qty, np.zeros(n, bool)),
               4: (flag, np.zeros(n, bool))},
        unique_keys=uniq), qty, flag


class TestDistributedScan:
    def test_psum_sum_count_8_tablets(self):
        tm = tablet_mesh(num_tablet_shards=8)
        blocks, all_qty = [], []
        for s in range(8):
            blk, qty, _ = shard_block(500 + 13 * s, seed=s)
            blocks.append([blk])
            all_qty.append(qty)
        batch = build_sharded_batch(tm, blocks, [1])
        (s_, c_), cnt = distributed_scan_aggregate(
            batch, (C(1) < 25.0).node,
            (AggSpec("sum", C(1).node), AggSpec("count")))
        cat = np.concatenate(all_qty)
        m = cat < 25.0
        np.testing.assert_allclose(float(s_), cat[m].sum(), rtol=1e-4)
        assert int(c_) == m.sum() == int(cnt)

    def test_min_max_combine(self):
        tm = tablet_mesh(num_tablet_shards=8)
        blocks, all_qty = [], []
        for s in range(8):
            blk, qty, _ = shard_block(100, seed=100 + s)
            blocks.append([blk])
            all_qty.append(qty)
        batch = build_sharded_batch(tm, blocks, [1])
        (mn, mx), _ = distributed_scan_aggregate(
            batch, None, (AggSpec("min", C(1).node), AggSpec("max", C(1).node)))
        cat = np.concatenate(all_qty)
        np.testing.assert_allclose(float(mn), cat.min(), rtol=1e-6)
        np.testing.assert_allclose(float(mx), cat.max(), rtol=1e-6)

    def test_grouped_2d_mesh(self):
        """4 tablet shards x 2 block shards (dp x sp) — Q1-style grouped
        aggregate combined across both axes."""
        tm = tablet_mesh(num_tablet_shards=4, num_block_shards=2)
        blocks, qs, fs = [], [], []
        for s in range(8):
            blk, qty, flag = shard_block(300, seed=200 + s)
            blocks.append([blk])
            qs.append(qty)
            fs.append(flag)
        batch = build_sharded_batch(tm, blocks, [1, 4])
        (sums, counts), _ = distributed_scan_aggregate(
            batch, None,
            (AggSpec("sum", C(1).node), AggSpec("count")),
            group=GroupSpec(cols=((4, 4, 0),)))
        qcat, fcat = np.concatenate(qs), np.concatenate(fs)
        for g in range(4):
            m = fcat == g
            np.testing.assert_allclose(np.asarray(sums)[g], qcat[m].sum(),
                                       rtol=1e-4)
            assert int(np.asarray(counts)[g]) == m.sum()

    def test_kernel_cached_across_runs(self):
        tm = tablet_mesh(num_tablet_shards=8)
        kern = DistributedScanKernel()
        for trial in range(3):
            blocks = [[shard_block(64, seed=300 + trial * 8 + s)[0]]
                      for s in range(8)]
            batch = build_sharded_batch(tm, blocks, [1])
            kern.run(batch, (C(1) < float(trial)).node, (AggSpec("count"),))
        assert kern.compiles == 1


class TestMeshLaunchProtocol:
    """The mesh kernel launches through `ops.scan.prepare_launch` /
    `launch`, as the one-device kernel does: same argument list, same
    weakly typed literals, one read-back — so the two give the same
    bits."""

    @staticmethod
    def _lineitem(rows_a_shard, shards):
        from yugabyte_db_tpu.docdb.table_codec import TableCodec
        from yugabyte_db_tpu.models.tpch import (generate_lineitem,
                                                 lineitem_info)
        from yugabyte_db_tpu.utils.hybrid_time import HybridTime
        data = generate_lineitem(0.004, seed=5)
        assert len(data["rowid"]) >= rows_a_shard * shards
        codec = TableCodec(lineitem_info())
        return [codec.bulk_blocks(
            {k: v[i * rows_a_shard:(i + 1) * rows_a_shard]
             for k, v in data.items()}, HybridTime.from_micros(100))
            for i in range(shards)]

    @pytest.mark.parametrize("float_dtype", ["float32", "float64"])
    @pytest.mark.parametrize("query_name", ["q6", "q1"])
    def test_mesh_result_is_the_one_device_result(self, query_name,
                                                  float_dtype):
        from yugabyte_db_tpu.models import tpch
        from yugabyte_db_tpu.ops.device_batch import build_batch
        from yugabyte_db_tpu.ops.scan import ScanKernel, prepare_launch
        from yugabyte_db_tpu.utils import flags
        from yugabyte_db_tpu.utils.hybrid_time import HybridTime
        q = {"q6": tpch.TPCH_Q6, "q1": tpch.TPCH_Q1}[query_name]
        read_ht = HybridTime.from_micros(10_000).value
        flags.set_flag("device_float_dtype", float_dtype)
        try:
            # 4 shards of one 4,096-row bucket = one batch of 16,384: both
            # kernels quantize their SUMs over the same row count
            per_shard = self._lineitem(3000, 4)
            tm = tablet_mesh(num_tablet_shards=4)
            mesh = build_sharded_batch(tm, per_shard, sorted(q.columns))
            one = build_batch([b for blocks in per_shard for b in blocks],
                              sorted(q.columns))
        finally:
            flags.REGISTRY.reset("device_float_dtype")
        assert mesh.padded_rows * mesh.num_shards == one.padded_rows
        assert str(one.cols[tpch.EXTPRICE].dtype) == float_dtype
        a = prepare_launch(one, q.where, q.aggs, q.group, read_ht)
        b = prepare_launch(mesh, q.where, q.aggs, q.group, read_ht,
                           n_total=one.padded_rows)
        # the same program (over other row counts) and runtime scalars
        assert a.key == b.key
        assert a.args[2] == b.args[2] == []
        for x, y in zip(a.args[-1], b.args[-1], strict=True):
            np.testing.assert_array_equal(x, y)
        np.testing.assert_array_equal(a.scales, b.scales)
        outs1, counts1, _ = ScanKernel().run(one, q.where, q.aggs, q.group,
                                             read_ht)
        outs4, counts4 = DistributedScanKernel().run(
            mesh, q.where, q.aggs, q.group, read_ht)
        assert int(np.sum(counts1)) > 0
        np.testing.assert_array_equal(counts1, counts4)
        assert len(outs1) == len(outs4) > 0
        for x, y in zip(outs1, outs4):
            assert x.dtype == y.dtype
            np.testing.assert_array_equal(x, y)

    def test_host_values_in_one_read_back_out(self, monkeypatch):
        from yugabyte_db_tpu.utils.trace import TRACES
        seen = []

        class Recording(DistributedScanKernel):
            def _get(self, *key):
                fn = super()._get(*key)

                def call(*args):
                    seen.append(args)
                    return fn(*args)
                return call
        tm = tablet_mesh(num_tablet_shards=8)
        batch = build_sharded_batch(
            tm, [[shard_block(200, seed=400 + s)[0]] for s in range(8)],
            [1, 4])
        kern = Recording()
        where = ((C(1) < 25.0) & (C(4) >= 1)).node
        aggs = (AggSpec("sum", C(1).node), AggSpec("count"))
        kern.run(batch, where, aggs, GroupSpec(cols=((4, 4, 0),)), 20)
        reads = []
        real = jax.device_get
        monkeypatch.setattr(jax, "device_get",
                            lambda x: reads.append(x) or real(x))
        with TRACES.trace("mesh-launch") as t:
            outs, counts = kern.run(batch, where, aggs,
                                    GroupSpec(cols=((4, 4, 0),)), 1 << 40)
        monkeypatch.undo()
        assert kern.compiles == 1 and len(reads) == 1
        # one int64 array back: the SUM, the count, the group counts
        assert len(reads[0]) == 1 and reads[0][0].dtype == np.int64
        assert all(isinstance(x, np.ndarray) for x in (*outs, counts))
        cols, nulls, alone, valid, ht, next_ht, tomb, (ints, floats) = \
            seen[-1]
        # read_ht's two words and the integer literal; the SUM's static
        # scale and the float literal — each vector a host value
        assert ints.tolist() == [1 << 8, 0, 1]
        assert floats.shape == (2,) and floats[0] > 0 and floats[1] == 25.0
        assert alone == [] and next_ht is None and ht is not None
        assert not any(isinstance(x, jax.Array) for x in (ints, floats))
        spans = {s.name: s for s in TRACES.recent
                 if s.trace_id == t.trace_id}
        assert spans["device.scan"].tags["host_args"] == 2
        assert spans["device.wait"].tags["result_leaves"] == 1
        assert (spans["device.scan"].tags["chips"],
                spans["device.scan"].tags["shards"]) == (8, 8)
        assert spans["device.wait"].tags["reads"] == 1
        assert spans["device.wait"].tags["chips"] == 8

    @pytest.mark.parametrize("query_name", ["q6", "q1", "q1_dict"])
    def test_mesh_literals_compare_in_the_lane_type(self, query_name):
        """The mesh program takes its literals from the replicated packed
        vectors weakly typed, as the one-device program does: a shard
        compares `l_shipdate` and the int32 code lanes in 32 bits, and
        only a dictionary group's slot test in 64."""
        import dataclasses
        from tests.test_ops_scan import TestPackedLaunch
        from yugabyte_db_tpu.models import tpch
        from yugabyte_db_tpu.ops.grouped_scan import DictGroupSpec
        from yugabyte_db_tpu.ops.scan import prepare_launch
        q = {"q6": tpch.TPCH_Q6, "q1": tpch.TPCH_Q1,
             "q1_dict": tpch.TPCH_Q1}[query_name]
        group = DictGroupSpec((tpch.RETFLAG, tpch.LINESTATUS)) \
            if query_name == "q1_dict" else q.group
        tm = tablet_mesh(num_tablet_shards=4)
        mesh = build_sharded_batch(
            tm, self._lineitem(3000, 4),
            sorted(set(q.columns) | {tpch.RETFLAG, tpch.LINESTATUS}))
        mesh = dataclasses.replace(mesh, dicts={
            tpch.RETFLAG: np.array(list("ANR"), object),
            tpch.LINESTATUS: np.array(list("FO"), object)})
        where = (Expr(q.where) & C(tpch.RETFLAG).ne(2)).node
        job = prepare_launch(mesh, where, q.aggs, group, 1 << 40,
                             n_total=mesh.padded_rows * mesh.num_shards)
        kern = DistributedScanKernel()
        fn = kern._get((id(tm.mesh),) + job.sig, tm, *job.key)
        text = fn.lower(*job.args).as_text()
        rows = mesh.padded_rows
        compares = TestPackedLaunch._compares
        assert compares(text, rows, "i32") == (
            3 if query_name == "q6" else 2)
        assert compares(text, rows, "i64") == (query_name == "q1_dict")

    @staticmethod
    def _unpacked_mesh_launch(batch, job, read_ht):
        """What the mesh launch answered before its scalars and result
        were packed: `_build_kernel`'s program a shard under
        `shard_map`, its additive partials psummed and its extremes
        pmin/pmax-ed over the shards, called with `unpacked_args`; every
        output read back, the sums rescaled by the scales the program
        returned."""
        from jax.sharding import PartitionSpec as P
        from tests.test_ops_scan import unpacked_args
        from yugabyte_db_tpu.ops.scan import _build_kernel, _rescale_outs
        from yugabyte_db_tpu.parallel.mesh import ROW_AXES
        where, aggs, group, mode, static_sums, strategy, _ = job.key
        local = _build_kernel(where, aggs, group, mode, axis_names=ROW_AXES,
                              row_multiplier=batch.num_shards,
                              static_sums=static_sums, strategy=strategy)

        def shard_fn(*args):
            outs, scales, counts, _, *spilled = local(*args)
            outs = [jax.lax.pmin(o, ROW_AXES) if a.op == "min"
                    and a.expr is not None else jax.lax.pmax(o, ROW_AXES)
                    if a.op == "max" and a.expr is not None
                    else jax.lax.psum(o, ROW_AXES)
                    for a, o in zip(aggs, outs)]
            scales = [(s[0], jax.lax.psum(s[1], ROW_AXES))
                      if isinstance(s, tuple) else s for s in scales]
            return (outs, scales, jax.lax.psum(counts, ROW_AXES),
                    [jax.lax.psum(x, ROW_AXES) for x in spilled])
        rows = P(ROW_AXES)
        program = jax.jit(jax.shard_map(
            shard_fn, mesh=batch.mesh.mesh,
            in_specs=(rows, rows, P(), rows, rows, rows, rows, P(), P(), P()),
            out_specs=P(), check_vma=False))
        outs, scales, counts, spilled = jax.device_get(program(
            *unpacked_args(job, read_ht)))
        return (_rescale_outs(outs, scales), counts, *spilled)

    @pytest.mark.parametrize("rows", ["some", "none"])
    @pytest.mark.parametrize("scales", ["static", "dynamic", "nan"])
    @pytest.mark.parametrize("kind", ["none", "dense", "dict", "dict_spill"])
    def test_packed_mesh_result_is_the_unpacked_program(self, kind, scales,
                                                        rows):
        """The mesh's packed launch (its scalars one replicated vector an
        element kind, its result packed after the one all-reduce) answers
        what its program answered with its scalars one host value each
        and its result read back leaf by leaf — bit for bit: the static
        and dynamic scales, a NaN scale's float fallback, the extremes'
        sentinels, int64 sums near 2^62 and the spill count."""
        import dataclasses
        from tests.test_ops_scan import (PACKED_COLUMNS, PACKED_WORDS,
                                         TestPackedLaunch, packed_block)
        from yugabyte_db_tpu.ops.scan import prepare_launch
        blocks = [packed_block(3000, seed=60 + s, first_key=3000 * s)
                  for s in range(4)]
        mesh = dataclasses.replace(build_sharded_batch(
            tablet_mesh(num_tablet_shards=4), [[b] for b in blocks],
            PACKED_COLUMNS), dicts=PACKED_WORDS)
        if scales != "static":
            mesh = dataclasses.replace(mesh, col_bounds={})
        where, aggs, group = TestPackedLaunch._shape(kind, scales, rows)
        read_ht = TestPackedLaunch.READ_HT
        got = DistributedScanKernel().run(mesh, where, aggs, group, read_ht)
        job = prepare_launch(mesh, where, aggs, group, read_ht,
                             n_total=mesh.padded_rows * mesh.num_shards)
        assert any(job.key[4]) == (scales == "static")
        want = self._unpacked_mesh_launch(mesh, job, read_ht)
        assert len(got) == len(want) == 2 + kind.startswith("dict")
        if kind.startswith("dict"):
            assert got[2] == int(want[2])
            assert (got[2] > 0) == (kind == "dict_spill" and rows == "some")
        got, want = (jax.tree_util.tree_leaves(x[:2]) for x in (got, want))
        assert len(got) == len(want) > 0
        for x, y in zip(got, want):
            x, y = np.asarray(x), np.asarray(y)
            assert (x.dtype, x.shape) == (y.dtype, y.shape)
            assert x.tobytes() == y.tobytes()


class TestShardedVector:
    def test_global_topk_matches_local(self):
        tm = tablet_mesh(num_tablet_shards=4, num_block_shards=2)
        rng = np.random.default_rng(5)
        base = rng.normal(size=(8 * 64, 16)).astype(np.float32)
        q = base[[3, 200, 500]] + 0.001
        d, idx = sharded_exact_search(
            tm, q, np.asarray(base).reshape(8, 64, 16), k=4)
        assert idx[0, 0] == 3 and idx[1, 0] == 200 and idx[2, 0] == 500
        ref = ((q[:, None, :] - base[None, :, :]) ** 2).sum(-1)
        np.testing.assert_allclose(np.sort(d, axis=1)[:, 0],
                                   ref.min(axis=1), atol=1e-1)
