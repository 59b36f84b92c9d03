"""Pallas fused-scan kernel vs numpy reference (interpret mode on the CPU
mesh; the same kernel compiles for TPU)."""
import numpy as np
import pytest

from yugabyte_db_tpu.ops.pallas_scan import BLOCK_ROWS, q6_scan


class TestPallasScan:
    def test_matches_numpy(self):
        rng = np.random.default_rng(1)
        n = 3 * BLOCK_ROWS + 777    # force padding
        qty = rng.uniform(1, 50, n)
        price = rng.uniform(900, 105000, n)
        disc = rng.integers(0, 11, n) / 100.0
        ship = rng.integers(8036, 10592, n).astype(float)
        s, c = q6_scan(qty, price, disc, ship, 8766, 9131, 0.05, 0.07,
                       24.0, interpret=True)
        m = ((ship >= 8766) & (ship < 9131) & (disc >= 0.05)
             & (disc <= 0.07) & (qty < 24))
        assert c == int(m.sum())
        expect = float((price * disc)[m].sum())
        assert abs(s - expect) <= max(1e-6, 2e-4 * abs(expect))

    def test_empty_match(self):
        n = BLOCK_ROWS
        z = np.zeros(n)
        s, c = q6_scan(z, z, z, z, 10.0, 20.0, 0.5, 0.6, -1.0,
                       interpret=True)
        assert (s, c) == (0.0, 0)


class TestPallasGrouped:
    def test_grouped_sums_match_numpy(self):
        rng = np.random.default_rng(2)
        n = 2 * BLOCK_ROWS + 123
        gids = rng.integers(0, 6, n).astype(np.float64)
        vals = rng.uniform(0, 10, n)
        mask = rng.random(n) < 0.7
        from yugabyte_db_tpu.ops.pallas_scan import grouped_sum
        out = grouped_sum(gids, vals, mask, num_groups=6, interpret=True)
        for g in range(6):
            m = (gids == g) & mask
            np.testing.assert_allclose(out[g], vals[m].sum(), rtol=2e-4)


class TestPallasRoutedPath:
    """The generic pallas path routed from ScanKernel.run behind the
    tpu_pallas_scan flag: results must match the XLA kernel on the
    same batch (Q6-style ungrouped and Q1-style grouped shapes)."""

    def _batch(self, n=3 * BLOCK_ROWS):
        from yugabyte_db_tpu.ops.device_batch import DeviceBatch
        import jax.numpy as jnp
        rng = np.random.default_rng(7)
        padded = ((n + BLOCK_ROWS - 1) // BLOCK_ROWS) * BLOCK_ROWS

        def pad(a, fill=0):
            out = np.full(padded, fill, a.dtype)
            out[:n] = a
            return jnp.asarray(out)
        cols = {
            0: pad(rng.uniform(1, 50, n).astype(np.float32)),
            1: pad(rng.uniform(900, 105000, n).astype(np.float64)),
            2: pad((rng.integers(0, 11, n) / 100.0)),
            3: pad(rng.integers(8036, 10592, n).astype(np.int32)),
            4: pad(rng.integers(0, 3, n).astype(np.int32)),
        }
        valid = np.zeros(padded, bool)
        valid[:n] = True
        nulls = {cid: jnp.zeros(padded, bool) for cid in cols}
        return DeviceBatch(cols=cols, nulls=nulls, valid=jnp.asarray(valid),
                           n_rows=n)

    def _q6(self, kernel, batch):
        from yugabyte_db_tpu.ops import Expr
        from yugabyte_db_tpu.ops.scan import AggSpec
        C = Expr.col
        where = ((C(3) >= 8766) & (C(3) < 9131) & (C(2) >= 0.05)
                 & (C(2) <= 0.07) & (C(0) < 24.0)).node
        aggs = (AggSpec("sum", (C(1) * C(2)).node), AggSpec("count"),
                AggSpec("min", C(1).node), AggSpec("max", C(1).node))
        return kernel.run(batch, where, aggs)

    def test_routed_matches_xla_ungrouped(self):
        from yugabyte_db_tpu.ops.scan import ScanKernel
        from yugabyte_db_tpu.utils import flags
        batch = self._batch()
        xla_out, xla_cnt, _ = self._q6(ScanKernel(), batch)
        flags.set_flag("tpu_pallas_scan", True)
        try:
            k = ScanKernel()
            pl_out, pl_cnt, mask = self._q6(k, batch)
            assert mask is None, "pallas path was not taken"
        finally:
            flags.set_flag("tpu_pallas_scan", False)
        assert int(pl_cnt) == int(xla_cnt)
        for a, b in zip(pl_out, xla_out):
            av, bv = float(np.asarray(a)), float(np.asarray(b))
            assert abs(av - bv) <= max(1e-6, 2e-4 * abs(bv)), (av, bv)

    def test_routed_matches_xla_grouped(self):
        from yugabyte_db_tpu.ops import Expr
        from yugabyte_db_tpu.ops.scan import AggSpec, GroupSpec, ScanKernel
        from yugabyte_db_tpu.utils import flags
        C = Expr.col
        batch = self._batch()
        group = GroupSpec(cols=((4, 3, 0),))
        where = (C(3) <= 10000).node
        aggs = (AggSpec("sum", C(1).node), AggSpec("count"))
        xla_out, xla_cnt, _ = ScanKernel().run(batch, where, aggs, group)
        flags.set_flag("tpu_pallas_scan", True)
        try:
            pl_out, pl_cnt, mask = ScanKernel().run(batch, where, aggs,
                                                    group)
            assert mask is None, "pallas path was not taken"
        finally:
            flags.set_flag("tpu_pallas_scan", False)
        assert np.asarray(pl_cnt).tolist() == np.asarray(xla_cnt).tolist()
        for a, b in zip(pl_out, xla_out):
            av, bv = np.asarray(a, np.float64), np.asarray(b, np.float64)
            assert np.allclose(av, bv, rtol=2e-4), (av, bv)

    def test_refusals_are_typed_and_tallied(self):
        # regression for the dead-PallasIneligible laundering: the
        # eligibility gate used to silently `return None`, so a
        # refused shape was indistinguishable from a bug.  Now every
        # decline raises PallasIneligible(reason) and the dispatcher
        # tallies it per reason.
        from yugabyte_db_tpu.ops import Expr
        from yugabyte_db_tpu.ops.pallas_scan import PallasIneligible
        from yugabyte_db_tpu.ops.scan import AggSpec, ScanKernel
        from yugabyte_db_tpu.utils import flags
        C = Expr.col
        batch = self._batch()
        k = ScanKernel()
        with pytest.raises(PallasIneligible, match="mvcc_or_no_aggs"):
            k._pallas_eligible(batch, None, (), None, "snapshot", ())
        with pytest.raises(PallasIneligible, match="agg_op"):
            k._pallas_eligible(batch, None, (AggSpec("avg", C(0).node),),
                               None, "none", ())
        import jax.numpy as jnp
        batch.cols[5] = jnp.asarray(
            np.arange(batch.padded_rows, dtype=np.int64))
        batch.nulls[5] = jnp.zeros(batch.padded_rows, bool)
        flags.set_flag("tpu_pallas_scan", True)
        try:
            out, cnt, mask = k.run(batch, (C(5) >= 10).node,
                                   (AggSpec("count"),))
            assert mask is not None          # served by XLA fallback
            assert k.pallas_refusals == {"column_dtype": 1}
            k.run(batch, (C(5) >= 10).node, (AggSpec("count"),))
            assert k.pallas_refusals == {"column_dtype": 2}
        finally:
            flags.set_flag("tpu_pallas_scan", False)

    def test_int64_columns_fall_back_to_xla(self):
        import jax.numpy as jnp
        from yugabyte_db_tpu.ops import Expr
        from yugabyte_db_tpu.ops.scan import AggSpec, ScanKernel
        from yugabyte_db_tpu.utils import flags
        batch = self._batch()
        batch.cols[5] = jnp.asarray(
            np.arange(batch.padded_rows, dtype=np.int64))
        batch.nulls[5] = jnp.zeros(batch.padded_rows, bool)
        C = Expr.col
        flags.set_flag("tpu_pallas_scan", True)
        try:
            out, cnt, mask = ScanKernel().run(
                batch, (C(5) >= 10).node,
                (AggSpec("count"),))
            assert mask is not None, "int64 predicate must stay on XLA"
        finally:
            flags.set_flag("tpu_pallas_scan", False)
        assert int(out[0]) == batch.padded_rows - 10 - int(
            (~np.asarray(batch.valid)).sum())
