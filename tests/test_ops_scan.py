"""Scan kernel tests: expression eval, aggregates, group-by, MVCC masks —
verified against numpy reference implementations (the CPU path double-
checks the TPU path, mirroring how the reference cross-checks DocDB with
an in-memory model, src/yb/docdb/in_mem_docdb.cc)."""
import numpy as np
import pytest

from yugabyte_db_tpu.ops import (
    AggSpec, DeviceBatch, Expr, ScanKernel, scan_aggregate, scan_filter,
)
from yugabyte_db_tpu.ops.device_batch import Pair, build_batch, bucket_rows
from yugabyte_db_tpu.ops.scan import GroupSpec
from yugabyte_db_tpu.storage.columnar import ColumnarBlock


def make_block(n=1000, seed=0, versions=False):
    rng = np.random.default_rng(seed)
    qty = rng.uniform(0, 50, n)
    price = rng.uniform(1, 100, n)
    disc = rng.uniform(0, 0.1, n)
    flag = rng.integers(0, 3, n)
    if versions:
        key_hash = rng.integers(0, n // 2, n).astype(np.uint64)
        ht = rng.integers(1, 1000, n).astype(np.uint64)
    else:
        key_hash = np.arange(n, dtype=np.uint64)
        ht = np.full(n, 10, np.uint64)
    tomb = np.zeros(n, bool)
    blk = ColumnarBlock.from_arrays(
        schema_version=1, key_hash=key_hash, ht=ht,
        fixed={
            1: (qty, np.zeros(n, bool)),
            2: (price, np.zeros(n, bool)),
            3: (disc, np.zeros(n, bool)),
            4: (flag.astype(np.int32), np.zeros(n, bool)),
        },
        tombstone=tomb, unique_keys=not versions)
    return blk, dict(qty=qty, price=price, disc=disc, flag=flag,
                     key_hash=key_hash, ht=ht)


C = Expr.col


class TestScanAggregate:
    def test_simple_sum_count(self):
        blk, d = make_block()
        batch = build_batch([blk], [1, 2, 3])
        where = ((C(1) < 24.0) & C(3).between(0.05, 0.07)).node
        aggs = (AggSpec("sum", (C(2) * C(3)).node), AggSpec("count"))
        (s, cnt2), cnt, mask = scan_aggregate(batch, where, aggs)
        m = (d["qty"] < 24.0) & (d["disc"] >= 0.05) & (d["disc"] <= 0.07)
        np.testing.assert_allclose(float(s), (d["price"] * d["disc"])[m].sum(),
                                   rtol=1e-5)
        assert int(cnt2) == m.sum() == int(cnt)

    def test_min_max(self):
        blk, d = make_block()
        batch = build_batch([blk], [1, 2])
        aggs = (AggSpec("min", col_expr(2)), AggSpec("max", col_expr(2)))
        (mn, mx), _, _ = scan_aggregate(batch, None, aggs)
        np.testing.assert_allclose(float(mn), d["price"].min(), rtol=1e-6)
        np.testing.assert_allclose(float(mx), d["price"].max(), rtol=1e-6)

    def test_avg_expansion(self):
        blk, d = make_block()
        batch = build_batch([blk], [1])
        (s, c), _, _ = scan_aggregate(batch, None, (AggSpec("avg", col_expr(1)),))
        np.testing.assert_allclose(float(s) / int(c), d["qty"].mean(),
                                   rtol=1e-5)

    def test_padding_excluded(self):
        blk, d = make_block(n=100)
        batch = build_batch([blk], [1])
        assert batch.padded_rows == bucket_rows(100) > 100
        (_, cnt), _, _ = scan_aggregate(
            batch, None, (AggSpec("sum", col_expr(1)), AggSpec("count")))
        assert int(cnt) == 100

    def test_group_by_matmul(self):
        blk, d = make_block()
        batch = build_batch([blk], [1, 4])
        group = GroupSpec(cols=((4, 3, 0),))
        aggs = (AggSpec("sum", col_expr(1)), AggSpec("count"),
                AggSpec("min", col_expr(1)))
        (sums, cnts, mins), gcounts, _ = scan_aggregate(
            batch, None, aggs, group=group)
        for g in range(3):
            m = d["flag"] == g
            np.testing.assert_allclose(np.asarray(sums)[g], d["qty"][m].sum(),
                                       rtol=1e-4)
            assert int(np.asarray(cnts)[g]) == m.sum()
            np.testing.assert_allclose(np.asarray(mins)[g], d["qty"][m].min(),
                                       rtol=1e-6)

    def test_null_semantics(self):
        n = 8
        vals = np.arange(n, dtype=np.float64)
        nulls = np.zeros(n, bool)
        nulls[2] = nulls[5] = True
        blk = ColumnarBlock.from_arrays(
            schema_version=1, key_hash=np.arange(n, dtype=np.uint64),
            ht=np.ones(n, np.uint64), fixed={1: (vals, nulls)})
        batch = build_batch([blk], [1])
        # COUNT(col) skips nulls; COUNT(*) doesn't; SUM skips nulls
        (c_col, c_star, s), _, _ = scan_aggregate(
            batch, None,
            (AggSpec("count", col_expr(1)), AggSpec("count"),
             AggSpec("sum", col_expr(1))))
        assert int(c_col) == 6
        assert int(c_star) == 8
        assert float(s) == vals[~nulls].sum()
        # WHERE col < 100 excludes null rows (three-valued logic)
        (c2,), _, _ = scan_aggregate(
            batch, (C(1) < 100.0).node, (AggSpec("count"),))
        assert int(c2) == 6

    def test_in_and_or(self):
        blk, d = make_block()
        batch = build_batch([blk], [4])
        where = C(4).isin([0, 2]).node
        (cnt,), _, _ = scan_aggregate(batch, where, (AggSpec("count"),))
        assert int(cnt) == ((d["flag"] == 0) | (d["flag"] == 2)).sum()


class TestMvcc:
    def test_visible_mode(self):
        blk, d = make_block()
        batch = build_batch([blk], [1])
        # read_ht below write time: nothing visible
        (c0,), _, _ = scan_aggregate(batch, None, (AggSpec("count"),),
                                     read_ht=5)
        assert int(c0) == 0
        (c1,), _, _ = scan_aggregate(batch, None, (AggSpec("count"),),
                                     read_ht=10)
        assert int(c1) == blk.n

    def test_dedup_newest_visible_wins(self):
        # 3 versions of one key + 1 of another
        key_hash = np.array([7, 7, 7, 9], np.uint64)
        ht = np.array([10, 20, 30, 15], np.uint64)
        vals = np.array([1.0, 2.0, 3.0, 50.0])
        blk = ColumnarBlock.from_arrays(
            schema_version=1, key_hash=key_hash, ht=ht,
            fixed={1: (vals, np.zeros(4, bool))}, unique_keys=False)
        batch = build_batch([blk], [1])
        # read at 25: key7 -> version ht=20 (val 2.0), key9 -> 50.0
        (s, c), _, _ = scan_aggregate(
            batch, None, (AggSpec("sum", col_expr(1)), AggSpec("count")),
            read_ht=25)
        assert int(c) == 2
        assert float(s) == 52.0
        # read at 35: newest (3.0) + 50
        (s2, _), _, _ = scan_aggregate(
            batch, None, (AggSpec("sum", col_expr(1)), AggSpec("count")),
            read_ht=35)
        assert float(s2) == 53.0

    def test_dedup_tombstone_hides_row(self):
        key_hash = np.array([7, 7], np.uint64)
        ht = np.array([10, 20], np.uint64)
        vals = np.array([1.0, 0.0])
        tomb = np.array([False, True])
        blk = ColumnarBlock.from_arrays(
            schema_version=1, key_hash=key_hash, ht=ht,
            fixed={1: (vals, np.zeros(2, bool))}, tombstone=tomb,
            unique_keys=False)
        batch = build_batch([blk], [1])
        (c_after,), _, _ = scan_aggregate(batch, None, (AggSpec("count"),),
                                          read_ht=25)
        assert int(c_after) == 0   # deleted
        (c_before,), _, _ = scan_aggregate(batch, None, (AggSpec("count"),),
                                           read_ht=15)
        assert int(c_before) == 1  # visible before the delete


class TestKernelCache:
    def test_no_recompile_on_literal_change(self):
        kern = ScanKernel()
        blk, d = make_block()
        batch = build_batch([blk], [1])
        for threshold in (10.0, 20.0, 30.0):
            where = (C(1) < threshold).node
            (cnt,), _, _ = kern.run(batch, where, (AggSpec("count"),))
            assert int(cnt) == (d["qty"] < threshold).sum()
        assert kern.compiles == 1

    def test_filter_mask(self):
        blk, d = make_block()
        batch = build_batch([blk], [2])
        mask, count = scan_filter(batch, (C(2) > 50.0).node)
        np_mask = np.asarray(mask)[:blk.n]
        np.testing.assert_array_equal(np_mask, d["price"] > 50.0)
        assert int(count) == np_mask.sum()


class TestLaunchProtocol:
    """One launch = one program and one read-back (`prepare_launch`,
    `launch`): the runtime scalars go into the jitted call as one host
    vector an element kind, the result comes back as one array an
    element kind in one transfer, and only a filter launch returns the
    row mask (on the device)."""

    AGGS = (AggSpec("sum", (C(2) * (Expr.const(1) - C(3))).node),
            AggSpec("avg", C(1).node), AggSpec("count"))

    @staticmethod
    def _batch(seed=0, n=1000):
        import dataclasses
        blk, d = make_block(n=n, seed=seed)
        batch = build_batch([blk], [1, 2, 3, 4])
        # column 4 doubles as codes of a three-word dictionary
        return dataclasses.replace(
            batch, dicts={4: np.array(["a", "b", "c"], object)}), d

    @staticmethod
    def _group(kind):
        from yugabyte_db_tpu.ops.grouped_scan import DictGroupSpec
        from yugabyte_db_tpu.ops.scan import HashGroupSpec
        return {"none": None, "dense": GroupSpec(cols=((4, 3, 0),)),
                "dict": DictGroupSpec(cols=(4,)),
                "hash": HashGroupSpec((4,), max_groups=8)}[kind]

    @staticmethod
    def _recording(seen):
        """A ScanKernel whose `_get` hands out programs that note the
        key they were built from and the argument list they were called
        with."""
        class Recording(ScanKernel):
            def _get(self, *key):
                fn = super()._get(*key)

                def call(*args):
                    seen.append((key, args))
                    return fn(*args)
                return call
        return Recording()

    @pytest.mark.parametrize("kind", ["none", "dense", "dict", "hash"])
    def test_runtime_scalars_are_host_values(self, kind):
        import jax
        from yugabyte_db_tpu.ops.scan import unpack_scalars
        seen = []
        kern = self._recording(seen)
        batch, _ = self._batch()
        where = ((C(1) < 24.0) & (C(4) >= 1)).node
        for _ in range(2):          # the second launch is warm
            kern.run(batch, where, self.AGGS, self._group(kind), 20)
        assert kern.compiles == 1
        key, (cols, nulls, alone, valid, ht, next_ht, tomb, scalars) = \
            seen[-1]
        # what the batch keeps on the device goes in as it is, the
        # write times as their two 32-bit words ...
        assert isinstance(ht, Pair)
        assert all(isinstance(x, jax.Array) for x in
                   jax.tree_util.tree_leaves((cols, valid, ht, tomb)))
        # ... and nothing else is put there ahead of the call: the
        # runtime scalars are one int64 and one float64 host vector
        assert alone == []
        ints, floats = scalars
        assert not any(isinstance(x, jax.Array) for x in scalars)
        assert (ints.dtype, floats.dtype) == (np.int64, np.float64)
        # read_ht's two words, the dictionary sizes, the integer literals
        assert ints.tolist() == [0, 20] + [3] * (kind == "dict") + [1, 1]
        # the static scales (AVG expanded: sum, sum, count, count), then
        # the float literal
        assert floats.shape == (3,) and (floats[:2] > 0).all() \
            and floats[2] == 24.0
        # where each literal rides is the program's: Python scalars, so
        # the program makes them weakly typed again
        lits = key[-1]
        assert lits == ("f", "i", "i")
        sig, where_, aggs, group, mode, static_sums, strategy, _ = key
        consts, read_ht, sum_scales, domains = unpack_scalars(
            alone, scalars, lits, group, static_sums)
        assert [(c.dtype, c.weak_type) for c in consts] == [
            (np.float64, True), (np.int64, True), (np.int64, True)]
        assert (int(read_ht.hi), int(read_ht.lo)) == (0, 20)
        assert [s is not None for s in sum_scales] == [True, True,
                                                       False, False]
        if kind == "dict":
            assert (domains.dtype, domains.tolist()) == (np.int32, [3])
        else:
            assert domains == ()

    def test_latest_read_point_fits(self):
        seen = []
        kern = self._recording(seen)
        batch, d = self._batch()
        (cnt,), _, _ = kern.run(batch, None, (AggSpec("count"),))
        # all ones, "latest", as its two words; no float in the launch
        (ints,) = seen[-1][1][-1]
        assert ints.tolist() == [0xFFFFFFFF, 0xFFFFFFFF]
        assert int(cnt) == len(d["qty"])

    @pytest.mark.parametrize("kind", ["none", "dense", "dict", "hash"])
    def test_one_read_back_host_result_device_mask(self, kind, monkeypatch):
        import jax
        from yugabyte_db_tpu.utils.trace import TRACES
        batch, d = self._batch()
        kern = ScanKernel()
        kern.run(batch, None, self.AGGS, self._group(kind), 20)   # warm
        reads = []
        real = jax.device_get
        monkeypatch.setattr(jax, "device_get",
                            lambda x: reads.append(x) or real(x))
        with TRACES.trace("launch") as t:
            got = kern.run(batch, None, self.AGGS, self._group(kind), 20)
        monkeypatch.undo()
        assert len(reads) == 1
        # the transfer carried one int64 array: every result of the
        # launch is an integer (no float SUM needs its scale back)
        (packed,) = reads[0]
        assert packed.dtype == np.int64 and packed.ndim == 1
        outs, counts, mask, *rest = got
        # an aggregate launch keeps no row mask
        assert mask is None
        host = jax.tree_util.tree_leaves((outs, counts, rest))
        assert host and all(isinstance(x, (np.ndarray, np.generic))
                            for x in host)
        assert len(rest) == {"none": 0, "dense": 0, "dict": 1,
                             "hash": 2}[kind]
        assert int(np.sum(counts)) == len(d["qty"]) == int(np.sum(outs[-1]))
        spans = {s.name: s for s in TRACES.recent
                 if s.trace_id == t.trace_id}
        assert spans["device.wait"].tags["reads"] == 1
        assert spans["device.wait"].tags["result_leaves"] == 1
        # the int64 vector (read_ht, sizes, the literal) and the float64
        # one (the scales)
        assert spans["device.scan"].tags["host_args"] == 2
        # a filter launch returns its mask, on the device
        _, count, mask = kern.run(batch, None, (), None, 20)
        assert isinstance(mask, jax.Array) and mask.shape == batch.valid.shape
        assert int(count) == int(np.asarray(mask).sum()) == len(d["qty"])

    @pytest.mark.parametrize("kind", ["none", "dict"])
    def test_other_literals_read_ht_and_bounds_do_not_compile(self, kind):
        import dataclasses
        kern = ScanKernel()
        batch, d = self._batch(seed=0)
        words = np.array(list("abcdefg"), object)
        batch = dataclasses.replace(batch, dicts={4: words[:5]})
        group = self._group(kind)

        def run(batch, d, threshold, read_ht):
            outs, counts, *_ = kern.run(
                batch, (C(1) < threshold).node,
                (AggSpec("sum", C(2).node), AggSpec("count")), group,
                read_ht)
            m = (d["qty"] < threshold) & (d["ht"] <= read_ht)
            assert int(np.sum(counts)) == m.sum()
            np.testing.assert_allclose(np.sum(outs[0]), d["price"][m].sum(),
                                       rtol=1e-9)
        run(batch, d, 10.0, 20)
        assert kern.compiles == 1
        run(batch, d, 33.5, 5)                    # literals, read point
        # other rows: other column bounds, so other SUM scales
        blk, d2 = make_block(n=900, seed=9)
        blk.fixed[2] = (blk.fixed[2][0] * 1000.0, blk.fixed[2][1])
        d2["price"] = d2["price"] * 1000.0
        other = dataclasses.replace(
            build_batch([blk], [1, 2, 3, 4]),
            dicts={4: words})
        assert other.col_bounds[2] != batch.col_bounds[2]
        # ... and a dictionary that grew inside its slot bucket
        run(other, d2, 12.0, 1 << 40)
        assert kern.compiles == 1


def packed_block(n=1000, seed=3, first_key=0):
    """A block for the packed launch's tests: float value lanes (1, 2), an
    int32 code lane (4, six words), an int64 lane of values near 2^52
    (5: its SUM over a batch comes near 2^62) and a float lane that holds
    an inf (6: its SUM has no scale, dynamic or static)."""
    rng = np.random.default_rng(seed)
    ht = rng.integers(1, 30, n).astype(np.uint64)
    qty = rng.uniform(0, 50, n)
    flag = rng.integers(0, 6, n).astype(np.int32)
    inf = rng.uniform(0, 5, n)
    # the inf is in a row every read and WHERE of the tests keep
    inf[7], ht[7], qty[7], flag[7] = np.inf, 1, 1.0, 0
    zeros = np.zeros(n, bool)
    return ColumnarBlock.from_arrays(
        schema_version=1,
        key_hash=np.arange(first_key, first_key + n, dtype=np.uint64),
        ht=ht,
        fixed={1: (qty, zeros),
               2: (rng.uniform(1, 100, n), zeros),
               4: (flag, zeros),
               5: (rng.integers(2 ** 51, 2 ** 52, n).astype(np.int64),
                   zeros),
               6: (inf, zeros)},
        tombstone=zeros, unique_keys=True)


PACKED_COLUMNS = [1, 2, 4, 5, 6]
PACKED_WORDS = {4: np.array(list("abcdef"), object)}


def packed_batch():
    import dataclasses
    batch = build_batch([packed_block()], PACKED_COLUMNS)
    return dataclasses.replace(batch, dicts=PACKED_WORDS)


def unpacked_args(job, read_ht):
    """The argument list `_build_kernel`'s program took for the launch
    `job` (`prepare_launch`) before its scalars were packed: the literals
    as the Python scalars they are (`collect_constants`), `read_ht` as
    one uint64, the scales as a float32 vector and the dictionary sizes
    as an int32 one."""
    from yugabyte_db_tpu.ops.expr import collect_constants
    where, aggs, group = job.key[:3]
    cols, nulls, _, valid, ht, next_ht, tomb, (ints, *_) = job.args
    consts = []
    for node in [where] + [a.expr for a in aggs]:
        if node is not None:
            collect_constants(node, consts)
    n_domains = len(group.cols) if hasattr(group, "num_slots") else 0
    domains = np.asarray(ints[2:2 + n_domains], np.int32) \
        if n_domains else ()
    return (cols, nulls, consts, valid, ht, next_ht, tomb,
            np.uint64(read_ht), job.scales, domains)


def unpacked_launch(job, read_ht):
    """What a launch answered before its scalars and result were packed:
    `_build_kernel`'s program called with `unpacked_args`, every output
    read back, the fixed-point sums rescaled by the scales the program
    returned.  (outs, counts, *rest), host values."""
    import jax
    from yugabyte_db_tpu.ops.scan import _build_kernel, _rescale_outs
    where, aggs, group, mode, static_sums, strategy, _ = job.key
    raw = jax.jit(_build_kernel(where, aggs, group, mode,
                                static_sums=static_sums, strategy=strategy))
    outs, scales, counts, _, *rest = jax.device_get(
        raw(*unpacked_args(job, read_ht)))
    return (_rescale_outs(outs, scales), counts, *rest)


class TestPackedLaunch:
    """The packed launch (`prepare_launch`, `unpack_scalars`,
    `ResultLayout`) answers what the program answered with its scalars
    one host value each and its result read back leaf by leaf — bit for
    bit, dtypes and shapes included."""

    READ_HT = 20
    MONEY = (C(2) * (Expr.const(1) - C(1) * 0.01)).node

    @classmethod
    def _shape(cls, kind, scales, rows):
        from yugabyte_db_tpu.ops.grouped_scan import DictGroupSpec
        from yugabyte_db_tpu.ops.scan import HashGroupSpec
        aggs = (AggSpec("sum", cls.MONEY), AggSpec("sum", C(5).node),
                AggSpec("avg", C(2).node), AggSpec("count"),
                AggSpec("count", C(2).node),
                AggSpec("min", C(2).node), AggSpec("max", C(2).node),
                AggSpec("min", C(4).node), AggSpec("max", C(4).node),
                AggSpec("min", C(5).node), AggSpec("max", C(5).node))
        if scales == "nan":
            aggs += (AggSpec("sum", C(6).node),)
        # (six words in four slots: codes 3 to 5 spill)
        group = {"none": None, "dense": GroupSpec(cols=((4, 6, 0),)),
                 "dict": DictGroupSpec(cols=(4,)),
                 "dict_spill": DictGroupSpec(cols=(4,), max_slots=4),
                 "hash": HashGroupSpec((4,), max_groups=8)}[kind]
        # "some": group 1 answers no row, so its extremes are sentinels;
        # "none": no row at all
        where = {"some": ((C(1) < 40.0) & C(4).ne(1)).node,
                 "none": (C(1) < -1.0).node}[rows]
        return where, aggs, group

    @pytest.mark.parametrize("rows", ["some", "none"])
    @pytest.mark.parametrize("scales", ["static", "dynamic", "nan"])
    @pytest.mark.parametrize("kind", ["none", "dense", "dict", "dict_spill",
                                      "hash"])
    def test_packed_is_the_unpacked_program_bit_for_bit(self, kind, scales,
                                                        rows):
        import dataclasses
        import jax
        from yugabyte_db_tpu.ops.scan import prepare_launch
        batch = packed_batch()
        if scales != "static":
            batch = dataclasses.replace(batch, col_bounds={})
        where, aggs, group = self._shape(kind, scales, rows)
        job = prepare_launch(batch, where, aggs, group, self.READ_HT)
        assert any(job.key[4]) == (scales == "static")
        got = ScanKernel().run(batch, where, aggs, group, self.READ_HT)
        outs, counts, mask, *rest = got
        assert mask is None
        want = unpacked_launch(job, self.READ_HT)
        got, want = (jax.tree_util.tree_leaves(x)
                     for x in ((outs, counts, *rest), want))
        assert len(got) == len(want) > 0
        for x, y in zip(got, want):
            x, y = np.asarray(x), np.asarray(y)
            assert (x.dtype, x.shape) == (y.dtype, y.shape)
            assert x.tobytes() == y.tobytes()
        sums = np.asarray(outs[1])
        if rows == "some":
            # the int64 SUM comes near 2^62 and stays exact ...
            assert sums.dtype == np.int64 \
                and sum(map(int, sums.ravel())) > 2 ** 60
        else:
            # ... and every extreme of no row is its type's sentinel
            assert np.isposinf(np.asarray(outs[6])).all()
            assert (np.asarray(outs[8]) == np.iinfo(np.int32).max).all()
            assert (np.asarray(outs[11]) == np.iinfo(np.int64).min).all()
        if scales == "nan":
            # no scale fits an inf: the float fallback answers
            assert np.isinf(np.asarray(outs[-1])).any() or rows == "none"
        if kind == "dict_spill" and rows == "some":
            assert int(rest[0]) > 0

    def test_fifty_literal_sets_and_read_points_one_program(self):
        from yugabyte_db_tpu.utils.trace import TRACES
        kern = ScanKernel()
        rng = np.random.default_rng(17)
        batch = packed_batch()
        blk_qty = np.asarray(batch.cols[1])[:1000]
        price = np.asarray(batch.cols[2])[:1000]
        flag = np.asarray(batch.cols[4])[:1000]
        ht = (np.asarray(batch.ht.hi, np.uint64)[:1000] << np.uint64(32)) \
            | np.asarray(batch.ht.lo, np.uint64)[:1000]
        for i in range(50):
            lo = float(rng.uniform(0, 25))
            hi = lo + float(rng.uniform(1, 25))
            code = int(rng.integers(0, 3))
            read_ht = int(rng.integers(1, 32))
            where = (C(1).between(lo, hi) & C(4).ne(code)).node
            with TRACES.trace("literals") as t:
                (s, c), _, mask = kern.run(
                    batch, where, (AggSpec("sum", C(2).node),
                                   AggSpec("count")), None, read_ht)
            m = (blk_qty >= lo) & (blk_qty <= hi) & (flag != code) \
                & (ht <= read_ht)
            assert int(c) == m.sum() and mask is None
            np.testing.assert_allclose(float(s), price[m].sum(), rtol=1e-9)
            (scan,) = [x for x in TRACES.recent if x.trace_id == t.trace_id
                       and x.name == "device.scan"]
            assert scan.tags["host_args"] == 2
        assert kern.compiles == 1

    @staticmethod
    def _compares(text, rows, dtype):
        """Lines of a lowered program that compare `rows`-long lanes of
        `dtype` (StableHLO)."""
        return sum("stablehlo.compare" in line
                   and f"tensor<{rows}x{dtype}>" in line
                   for line in text.splitlines())

    @pytest.mark.parametrize("query_name", ["q6", "q1", "q1_dict"])
    def test_literals_compare_in_the_lane_type(self, query_name):
        """`l_shipdate` and the int32 code lanes compare with the
        literals in 32 bits, as with the Python scalars the launch took
        before the packing; literals of a strong int64 would make them
        compare in 64."""
        import dataclasses
        import jax
        from __graft_entry__ import _example_batch
        from yugabyte_db_tpu.models import tpch
        from yugabyte_db_tpu.ops.grouped_scan import DictGroupSpec
        from yugabyte_db_tpu.ops.scan import (_build_kernel, prepare_launch,
                                              scan_program)
        q = {"q6": tpch.TPCH_Q6, "q1": tpch.TPCH_Q1,
             "q1_dict": tpch.TPCH_Q1}[query_name]
        group = DictGroupSpec((tpch.RETFLAG, tpch.LINESTATUS)) \
            if query_name == "q1_dict" else q.group
        batch = build_batch(_example_batch(), sorted(
            set(q.columns) | {tpch.RETFLAG, tpch.LINESTATUS}),
            multi_version=True)
        batch = dataclasses.replace(batch, dicts={
            tpch.RETFLAG: np.array(list("ANR"), object),
            tpch.LINESTATUS: np.array(list("FO"), object)})
        # a code literal besides: RETFLAG <> 'R' as its code
        where = (Expr(q.where) & C(tpch.RETFLAG).ne(2)).node
        assert batch.cols[tpch.SHIPDATE].dtype == np.int32
        assert batch.cols[tpch.RETFLAG].dtype == np.int32
        job = prepare_launch(batch, where, q.aggs, group, 1 << 40)
        rows = batch.padded_rows
        program, _ = scan_program(*job.key)
        packed = jax.jit(program).lower(*job.args).as_text()
        where_, aggs, group_, mode, static_sums, strategy, _ = job.key
        raw = _build_kernel(where_, aggs, group_, mode,
                            static_sums=static_sums, strategy=strategy)
        args = unpacked_args(job, 1 << 40)
        before = jax.jit(raw).lower(*args).as_text()
        # l_shipdate against one or two dates, the code against its
        # literal
        assert self._compares(packed, rows, "i32") \
            == self._compares(before, rows, "i32") \
            == (3 if query_name == "q6" else 2)
        assert self._compares(packed, rows, "i64") \
            == self._compares(before, rows, "i64")
        # (only the dictionary group's slot test is an int64 compare)
        assert self._compares(packed, rows, "i64") == (
            query_name == "q1_dict")
        strong = [np.int64(c) if type(c) is int else c for c in args[2]]
        control = jax.jit(raw).lower(*args[:2], strong, *args[3:]).as_text()
        assert self._compares(control, rows, "i64") \
            > self._compares(packed, rows, "i64")


class TestRowTiles:
    """A grouped scan of a lane longer than `_TILE_ROWS` runs as one
    device loop over row tiles whose partials add (int64 sums and
    counts, order-free extremes): the answer is the whole program's bit
    for bit, the row mask element for element.  8,192 padded rows by
    tiles of 1,024: 5,000 rows, so tiles 5..7 hold padding only, and
    tile 2 holds only deleted rows."""

    N, TILE, READ_HT = 8192, 1024, 600

    @classmethod
    def _batch(cls, bounds=True):
        import dataclasses
        rng = np.random.default_rng(11)
        n = 5000
        blk = ColumnarBlock.from_arrays(
            schema_version=1,
            key_hash=rng.integers(0, n // 2, n).astype(np.uint64),
            ht=rng.integers(1, 1000, n).astype(np.uint64),
            fixed={1: (rng.uniform(0, 50, n), np.zeros(n, bool)),
                   2: (rng.uniform(1, 100, n), rng.random(n) < 0.01),
                   3: (rng.uniform(0, 0.1, n), np.zeros(n, bool)),
                   4: (rng.integers(0, 5, n).astype(np.int32),
                       np.zeros(n, bool))},
            tombstone=np.zeros(n, bool), unique_keys=False)
        batch = build_batch([blk], [1, 2, 3, 4])
        assert batch.padded_rows == cls.N and batch.next_ht is not None
        return dataclasses.replace(
            batch, tombstone=batch.tombstone.at[2048:3072].set(True),
            dicts={4: np.array(list("abcde"), object)},
            col_bounds=batch.col_bounds if bounds else {})

    @staticmethod
    def _shape(kind):
        """(where, aggs, group) of a scan shape."""
        from yugabyte_db_tpu.ops.grouped_scan import DictGroupSpec
        from yugabyte_db_tpu.ops.scan import HashGroupSpec
        money = (C(2) * (Expr.const(1) - C(3))).node
        every = (AggSpec("sum", money), AggSpec("sum", C(4).node),
                 AggSpec("count"), AggSpec("count", C(2).node),
                 AggSpec("min", C(2).node), AggSpec("max", C(2).node),
                 AggSpec("min", C(4).node), AggSpec("max", C(4).node))
        where = (C(1) < 40.0).node
        return {
            "sum_static": (where, (AggSpec("sum", money),), None),
            "sum_integer": (where, (AggSpec("sum", C(4).node),), None),
            "count_min_max": (None, every[2:], None),
            "dense": (where, every, GroupSpec(cols=((4, 5, 0),))),
            "dense_count": (None, (AggSpec("count"),),
                            GroupSpec(cols=((4, 5, 0),))),
            # five words in four slots: codes 3 and 4 spill, in every tile
            "dict_spilling": (where, every, DictGroupSpec((4,), max_slots=4)),
            "hash": (where, every[:4], HashGroupSpec((4,), max_groups=8)),
        }[kind]

    @classmethod
    def _programs(cls, kind, strategy="segment", bounds=True):
        """(tiled, whole, args, key): the kernel of a launch
        (`prepare_launch`) built with tiles of 1,024 rows and with one
        of the lane's length."""
        import jax
        from yugabyte_db_tpu.ops.scan import _build_kernel, prepare_launch
        where, aggs, group = cls._shape(kind)
        job = prepare_launch(cls._batch(bounds), where, aggs, group,
                             cls.READ_HT)
        key = job.key[:6]
        where, aggs, group, mode, static_sums, _ = key
        assert mode == "linked"
        tiled, whole = (jax.jit(_build_kernel(
            where, aggs, group, mode, static_sums=static_sums,
            strategy=strategy, tile_rows=t)) for t in (cls.TILE, cls.N))
        return tiled, whole, kernel_args(job), key

    @staticmethod
    def _same_bits(a, b):
        import jax
        a, b = (jax.tree_util.tree_leaves(x) for x in (a, b))
        assert len(a) == len(b) > 0
        for x, y in zip(a, b):
            assert (x.dtype, x.shape) == (y.dtype, y.shape)
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y))

    @pytest.mark.parametrize("strategy", ["segment", "unroll"])
    @pytest.mark.parametrize("kind", ["dense", "dense_count",
                                      "dict_spilling"])
    def test_tiled_is_the_whole_program_bit_for_bit(self, kind, strategy):
        from yugabyte_db_tpu.ops.scan import tile_count
        tiled, whole, args, (_, aggs, group, _, static_sums, _) = \
            self._programs(kind, strategy)
        assert tile_count(self.N, group, aggs, static_sums, self.TILE) == 8
        assert "while" in tiled.lower(*args).as_text()
        assert "while" not in whole.lower(*args).as_text()
        got, want = tiled(*args), whole(*args)
        self._same_bits(got, want)
        # (outs, scales, counts, mask[, spilled]): the mask covers the
        # lane, rows answered, and the deleted and the padded tiles none
        mask = np.asarray(got[3])
        assert mask.shape == (self.N,) and mask[:2048].any()
        assert not mask[2048:3072].any() and not mask[5000:].any()
        assert int(np.sum(got[2])) == int(mask.sum()) > 0
        if kind == "dict_spilling":
            assert int(got[4]) > 0
            spilled = np.asarray(args[0][4])[mask] >= 3
            assert int(got[4]) == spilled.sum()
            # ... from rows of several tiles
            assert len(set(np.nonzero(mask)[0][spilled] // self.TILE)) > 1

    @pytest.mark.parametrize("kind,bounds", [
        ("sum_static", True), ("sum_integer", True), ("count_min_max", True),
        ("hash", True), ("dense", False), ("dict_spilling", False)])
    def test_a_shape_that_tiles_do_not_serve_runs_whole(self, kind, bounds):
        """An ungrouped body reads each lane once and has nothing to keep
        near; HashGroupSpec sorts the whole lane; a SUM with no
        host-derived scale takes a max over all rows first: one tile,
        the program that it was, and the answer with it."""
        from yugabyte_db_tpu.ops.scan import tile_count
        tiled, whole, args, (_, aggs, group, _, static_sums, _) = \
            self._programs(kind, bounds=bounds)
        assert bounds or not any(static_sums)
        assert tile_count(self.N, group, aggs, static_sums, self.TILE) == 1
        assert tiled.lower(*args).as_text() == whole.lower(*args).as_text()
        got = tiled(*args)
        self._same_bits(got, whole(*args))
        assert int(np.sum(got[2])) == int(np.asarray(got[3]).sum()) > 0

    @pytest.mark.parametrize("kind", ["dense", "dense_count",
                                      "dict_spilling"])
    def test_a_lane_of_one_tile_is_the_program_it_was(self, kind):
        """`n` <= the tile: no loop, and nothing else of the tiled form
        in the program either."""
        import jax
        from yugabyte_db_tpu.ops.scan import _build_kernel
        _, whole, args, (where, aggs, group, mode, static_sums, strategy) = \
            self._programs(kind)
        texts = [jax.jit(_build_kernel(
            where, aggs, group, mode, static_sums=static_sums,
            strategy=strategy, **kw)).lower(*args).as_text()
            for kw in ({}, {"tile_rows": self.N}, {"tile_rows": 4 * self.N})]
        assert texts[0] == texts[1] == texts[2] == \
            whole.lower(*args).as_text()
        assert "while" not in texts[0]

    @pytest.mark.parametrize("kind", ["sum_static", "dense",
                                      "dict_spilling", "hash"])
    def test_launch_tags_the_tiles_it_ran(self, kind, monkeypatch):
        """`ScanKernel.run` with the module's tile at 1,024: the same
        answer and mask as with the served tile, and `device.scan` says
        how many tiles the program ran the lane in."""
        from yugabyte_db_tpu.ops import scan as scan_mod
        from yugabyte_db_tpu.utils.trace import TRACES
        batch = self._batch()
        where, aggs, group = self._shape(kind)

        def run():
            with TRACES.trace("tiles") as t:
                got = ScanKernel().run(batch, where, aggs, group,
                                       self.READ_HT)
            (span,) = [s for s in TRACES.recent if s.trace_id == t.trace_id
                       and s.name == "device.scan"]
            return got, span.tags["tiles"]
        want, tiles = run()
        assert tiles == 1                   # 8,192 rows: under the tile
        monkeypatch.setattr(scan_mod, "_TILE_ROWS", self.TILE)
        got, tiles = run()
        assert tiles == (8 if kind in ("dense", "dict_spilling") else 1)
        self._same_bits(got, want)


def col_expr(cid):
    return C(cid).node


def kernel_args(job):
    """The argument list of `_build_kernel`'s program for the launch
    `job` (`prepare_launch`): its packed runtime scalars taken apart as
    the launched program takes them (`unpack_scalars`)."""
    from yugabyte_db_tpu.ops.scan import unpack_scalars
    cols, nulls, alone, valid, ht, next_ht, tomb, scalars = job.args
    _, _, group, _, static_sums, _, lits = job.key
    consts, read_ht, sum_scales, domains = unpack_scalars(
        alone, scalars, lits, group, static_sums)
    return (cols, nulls, consts, valid, ht, next_ht, tomb, read_ht,
            sum_scales, domains)
