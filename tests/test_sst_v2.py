"""v2 columnar SST block format: lane codec, keyless derivation, zone
maps, format gates.

Four contracts under test:

1. LANE CODEC — every encoding round-trips bit-exactly through its
   numpy decode oracle, and the strict "encode only if smaller" rule
   keeps incompressible lanes raw.
2. V1 BYTE IDENTITY — ``sst_format_version=1`` serializes blocks
   byte-identically to the pre-v2 writer (pinned by an inline oracle
   reimplementation of the old serializer).
3. KEYLESS V2 — the keys matrix is dropped only when the codec rebuild
   byte-matches, readers re-derive lazily, and the whole read surface
   (entries, point reads, aggregates) is equal across formats —
   including mixed v1+v2 SSTs in one tablet.
4. ZONE MAPS — pruning never changes results (boundary-straddling
   predicates included) and provably skips blocks on selective scans
   over key-clustered data.
"""
import struct

import msgpack
import numpy as np
import pytest

from yugabyte_db_tpu.docdb import ReadRequest, RowOp, WriteRequest
from yugabyte_db_tpu.ops.scan import (AggSpec, zone_maybe_match,
                                      zone_prune_blocks)
from yugabyte_db_tpu.storage import lane_codec
from yugabyte_db_tpu.storage.columnar import (SUPPORTED_FORMAT_VERSION,
                                              ColumnarBlock)
from yugabyte_db_tpu.storage.sst import SstReader, resolve_format_version
from yugabyte_db_tpu.tablet import Tablet
from yugabyte_db_tpu.utils import flags
from yugabyte_db_tpu.utils.hybrid_time import (HybridClock, HybridTime,
                                               MockPhysicalClock)
from tests.test_tablet import make_info


@pytest.fixture
def v2_flag():
    flags.set_flag("sst_format_version", 2)
    yield
    flags.REGISTRY.reset("sst_format_version")


@pytest.fixture
def v1_flag():
    flags.set_flag("sst_format_version", 1)
    yield
    flags.REGISTRY.reset("sst_format_version")


def _roundtrip(arr):
    meta, bufs, enc = lane_codec.encode_lane(arr)
    stream = b"".join(memoryview(np.ascontiguousarray(b)).cast("B")
                      for b in bufs)
    pos = [0]

    def fetch(nb):
        raw = stream[pos[0]:pos[0] + nb]
        pos[0] += nb
        return raw

    out = lane_codec.decode_lane(meta, fetch)
    assert pos[0] == len(stream)
    assert out.dtype == arr.dtype and out.shape == arr.shape
    assert np.array_equal(out.view(np.uint8),
                          np.ascontiguousarray(arr).view(np.uint8))
    return enc, sum(np.ascontiguousarray(b).nbytes for b in bufs)


class TestLaneCodec:
    def test_const_lane(self):
        enc, size = _roundtrip(np.full(4096, 0x1234, np.uint64))
        assert enc == "const" and size == 8

    def test_dconst_arange(self):
        enc, size = _roundtrip(np.arange(4096, dtype=np.uint32))
        assert enc == "dconst" and size == 8

    def test_dconst_descending_wraparound(self):
        enc, _ = _roundtrip(np.arange(4096, 0, -1, dtype=np.uint64))
        assert enc == "dconst"

    def test_delta_slowly_varying(self):
        rng = np.random.default_rng(0)
        arr = np.cumsum(rng.integers(0, 100, 4096)).astype(np.uint64)
        enc, size = _roundtrip(arr)
        assert enc == "delta" and size < arr.nbytes / 4

    def test_rle_sparse_bool(self):
        rng = np.random.default_rng(1)
        enc, size = _roundtrip(rng.random(4096) < 0.005)
        assert enc == "rle" and size < 4096

    def test_dict_low_cardinality_floats(self):
        rng = np.random.default_rng(2)
        arr = rng.integers(0, 11, 8192).astype(np.float64) / 100.0
        enc, size = _roundtrip(arr)
        assert enc == "dict"
        assert size < arr.nbytes / 4

    def test_nan_payloads_bit_exact(self):
        # two distinct NaN bit patterns must survive (dict/const work on
        # the unsigned VIEW, never on float comparisons)
        a = np.array([np.nan] * 8, np.float64)
        b = a.view(np.uint64).copy()
        b[::2] |= np.uint64(1)
        _roundtrip(b.view(np.float64))

    def test_encode_only_if_smaller_incompressible(self):
        rng = np.random.default_rng(3)
        for arr in (rng.random(4096),
                    rng.integers(0, 2**63, 4096).astype(np.uint64)):
            enc, size = _roundtrip(arr)
            assert enc == "raw" and size == arr.nbytes

    def test_tiny_and_empty_lanes(self):
        _roundtrip(np.array([], np.float64))
        _roundtrip(np.array([7], np.uint64))
        _roundtrip(np.array([1, 2], np.int32))

    def test_fuzz_all_dtypes(self):
        rng = np.random.default_rng(4)
        for dt in (np.uint8, np.int16, np.uint32, np.int64, np.float64,
                   np.float32, bool):
            for shape in (1, 2, 3, 100, 4097):
                if dt is bool:
                    arr = rng.random(shape) < rng.random()
                else:
                    arr = rng.integers(-50, 50, shape).astype(dt)
                _roundtrip(arr)


def _oracle_v1_serialize(cb: ColumnarBlock) -> bytes:
    """The PRE-v2 serializer, verbatim — pins v1 byte identity."""
    bufs = []

    def ref(arr):
        a = np.ascontiguousarray(arr)
        bufs.append(a)
        return {"dtype": str(arr.dtype), "shape": list(arr.shape),
                "len": a.nbytes}

    meta = {
        "n": cb.n, "sv": cb.schema_version, "uniq": cb.unique_keys,
        "keys": ref(cb.keys) if cb.keys is not None else None,
        "key_hash": ref(cb.key_hash), "ht": ref(cb.ht),
        "wid": ref(cb.write_id), "tomb": ref(cb.tombstone),
        "pk": {str(k): ref(v) for k, v in cb.pk.items()},
        "fixed": {str(k): [ref(v), ref(m)]
                  for k, (v, m) in cb.fixed.items()},
        "varlen": {},
    }
    for k, (ends, heap, null) in cb.varlen.items():
        bufs.append(heap)
        meta["varlen"][str(k)] = [ref(ends), {"len": len(heap)},
                                  ref(null)]
    head = msgpack.packb(meta)
    return struct.pack("<I", len(head)) + head + b"".join(
        b if isinstance(b, bytes) else memoryview(b).cast("B")
        for b in bufs)


def _make_tablet(tmp_path, tag, rows=600, versions=2):
    clock = HybridClock(MockPhysicalClock(1_000_000))
    t = Tablet(f"v2-{tag}", make_info(), str(tmp_path / tag), clock=clock)
    for ver in range(versions):
        t.apply_write(WriteRequest("t1", [
            RowOp("upsert", {"k": i, "v": float(ver * 1000 + i),
                             "s": f"s{i % 7}"})
            for i in range(rows)]))
        t.flush()
    return t


class TestV1ByteIdentity:
    def test_flush_block_serializes_identically(self, tmp_path, v1_flag):
        t = _make_tablet(tmp_path, "oracle")
        for r in t.regular.ssts:
            for i in range(r.num_blocks()):
                cb = r.columnar_block(i)
                assert cb.serialize(version=1) == _oracle_v1_serialize(cb)

    def test_v1_sst_has_no_version_markers(self, tmp_path, v1_flag):
        t = _make_tablet(tmp_path, "gate")
        for r in t.regular.ssts:
            assert r.format_version == 1
            for i in range(r.num_blocks()):
                raw = r._data[r.index[i].col_offset:]
                hlen = struct.unpack_from("<I", raw)[0]
                meta = msgpack.unpackb(bytes(raw[4:4 + hlen]),
                                       strict_map_key=False)
                assert "v" not in meta

    def test_resolver_clamps(self):
        flags.set_flag("sst_format_version", 1)
        assert resolve_format_version() == 1
        flags.set_flag("sst_format_version", 3)   # unknown -> compatible
        assert resolve_format_version() == 1
        flags.set_flag("sst_format_version", 2)
        assert resolve_format_version() == 2
        flags.REGISTRY.reset("sst_format_version")


class TestKeylessV2:
    def test_bulk_load_drops_keys_and_rereads_identically(
            self, tmp_path, v2_flag):
        rng = np.random.default_rng(0)
        n = 5000
        data = {"k": rng.permutation(n).astype(np.int64),
                "v": rng.random(n),
                "s": np.array([f"x{i % 13}" for i in range(n)],
                              dtype=object)}
        t2 = Tablet("kb2", make_info(), str(tmp_path / "b2"))
        t2.bulk_load(data, ht=HybridTime.from_micros(1 << 40),
                     block_rows=1024)
        flags.set_flag("sst_format_version", 1)
        t1 = Tablet("kb1", make_info(), str(tmp_path / "b1"))
        t1.bulk_load(data, ht=HybridTime.from_micros(1 << 40),
                     block_rows=1024)
        flags.set_flag("sst_format_version", 2)
        r2 = t2.regular.ssts[0]
        assert r2.format_version == 2
        assert r2.file_size < t1.regular.ssts[0].file_size * 0.8
        # keys genuinely absent on disk, derived lazily on access
        cb = r2.columnar_block(0)
        assert cb._keys is None and cb.keys_derivable
        assert list(t1.regular.iterate()) == list(t2.regular.iterate())

    def test_point_reads_over_keyless_blocks(self, tmp_path, v2_flag):
        rng = np.random.default_rng(1)
        n = 3000
        data = {"k": np.arange(n, dtype=np.int64), "v": rng.random(n),
                "s": np.array(["p"] * n, dtype=object)}
        t = Tablet("kp", make_info(), str(tmp_path))
        t.bulk_load(data, ht=HybridTime.from_micros(1 << 40),
                    block_rows=512)
        for k in (0, 17, 1234, n - 1):
            rows = t.read(ReadRequest("t1", pk_eq={"k": k})).rows
            assert len(rows) == 1 and rows[0]["k"] == k
            assert rows[0]["v"] == data["v"][k]

    def test_underivable_pk_keeps_inline_keys(self, tmp_path, v2_flag):
        """String hash PK can't rebuild from cb.pk (varlen component)
        — the writer must keep the keys matrix inline and everything
        still reads."""
        from yugabyte_db_tpu.dockv.packed_row import (ColumnSchema,
                                                      ColumnType,
                                                      TableSchema)
        from yugabyte_db_tpu.dockv.partition import PartitionSchema
        from yugabyte_db_tpu.docdb.table_codec import TableInfo
        info = TableInfo("ts", "ts", TableSchema(columns=(
            ColumnSchema(0, "k", ColumnType.STRING, is_hash_key=True),
            ColumnSchema(1, "v", ColumnType.FLOAT64),
        ), version=1), PartitionSchema("hash", 1))
        t = Tablet("str", info, str(tmp_path))
        t.apply_write(WriteRequest("ts", [
            RowOp("upsert", {"k": f"key-{i:04d}", "v": float(i)})
            for i in range(300)]))
        t.flush()
        r = t.regular.ssts[0]
        cb = r.columnar_block(0)
        assert cb is not None and cb._keys is not None   # inline keys
        rows = t.read(ReadRequest("ts", pk_eq={"k": "key-0042"})).rows
        assert rows and rows[0]["v"] == 42.0

    def test_mixed_v1_v2_ssts_in_one_tablet(self, tmp_path):
        rng = np.random.default_rng(2)
        n = 2000

        def halves(t):
            flags.set_flag("sst_format_version", 1)
            t.bulk_load({"k": np.arange(n, dtype=np.int64) * 2,
                         "v": rng.random(n),
                         "s": np.array(["a"] * n, dtype=object)},
                        ht=HybridTime.from_micros(1 << 40),
                        block_rows=512)
            flags.set_flag("sst_format_version", 2)
            t.bulk_load({"k": np.arange(n, dtype=np.int64) * 2 + 1,
                         "v": rng.random(n),
                         "s": np.array(["b"] * n, dtype=object)},
                        ht=HybridTime.from_micros((1 << 40) + 100),
                        block_rows=512)

        try:
            t = Tablet("mix", make_info(), str(tmp_path / "m"))
            halves(t)
            got = {1, 2} <= {r.format_version for r in t.regular.ssts}
            assert got
            total = t.read(ReadRequest(
                "t1", aggregates=(AggSpec("count"),)))
            assert int(np.asarray(total.agg_values[0])) == 2 * n
            for k in (0, 1, 777, 2 * n - 1):
                rows = t.read(ReadRequest("t1", pk_eq={"k": k})).rows
                assert len(rows) == 1
        finally:
            flags.REGISTRY.reset("sst_format_version")


class TestVersionRejection:
    def test_block_newer_version_rejected(self):
        cb = ColumnarBlock.from_arrays(
            schema_version=1,
            key_hash=np.arange(4, dtype=np.uint64),
            ht=np.full(4, 9, np.uint64),
            keys=np.zeros((4, 20), np.uint8))
        raw = cb.serialize(version=2)
        with pytest.raises(ValueError, match="v2 is newer"):
            ColumnarBlock.deserialize(raw, max_version=1)
        # and the supported version round-trips
        back = ColumnarBlock.deserialize(raw)
        assert back.n == 4

    def test_v2_file_rejected_by_v1_reader(self, tmp_path, v2_flag,
                                           monkeypatch):
        t = _make_tablet(tmp_path, "rej", rows=100, versions=1)
        path = t.regular.ssts[0].path
        import yugabyte_db_tpu.storage.sst as sst_mod
        monkeypatch.setattr(sst_mod, "SUPPORTED_FORMAT_VERSION", 1)
        with pytest.raises(ValueError, match="format v2 is newer"):
            SstReader(path)
        assert SUPPORTED_FORMAT_VERSION == 2   # module constant intact


class TestZoneMaps:
    def _range_tablet(self, tmp_path, n=20000, block_rows=1024):
        from yugabyte_db_tpu.models.tpch import lineitem_range_info
        rng = np.random.default_rng(5)
        data = {
            "rowid": np.arange(n, dtype=np.int64),
            "l_quantity": rng.integers(1, 51, n).astype(np.float64),
            "l_extendedprice": rng.uniform(900, 105000, n),
            "l_discount": rng.integers(0, 11, n).astype(np.float64) / 100,
            "l_tax": rng.integers(0, 9, n).astype(np.float64) / 100,
            "l_shipdate": rng.integers(8036, 10592, n).astype(np.int32),
            "l_returnflag": rng.integers(0, 3, n).astype(np.int32),
            "l_linestatus": rng.integers(0, 2, n).astype(np.int32),
        }
        t = Tablet("zr", lineitem_range_info(), str(tmp_path))
        t.bulk_load(data, ht=HybridTime.from_micros(1 << 40),
                    block_rows=block_rows)
        return t, data

    def test_zone_maps_stored_and_exact(self, tmp_path, v2_flag):
        t, data = self._range_tablet(tmp_path, n=4000)
        r = t.regular.ssts[0]
        lo = 0
        for i in range(r.num_blocks()):
            cb = r.columnar_block(i)
            assert cb.zmap is not None
            zlo, zhi = cb.zmap[0]            # rowid: range-clustered
            assert zlo == lo and zhi == lo + cb.n - 1
            lo += cb.n
            qlo, qhi = cb.zmap[1]            # l_quantity
            sl = data["l_quantity"][zlo:zhi + 1]
            assert qlo == sl.min() and qhi == sl.max()

    def test_boundary_straddling_predicates(self, tmp_path, v2_flag):
        """Predicate edges exactly ON block boundary min/max values:
        pruning must keep every boundary row (le/ge/lt/gt asymmetry is
        where an off-by-one would hide)."""
        t, data = self._range_tablet(tmp_path, n=8000, block_rows=1000)
        n = len(data["rowid"])
        from yugabyte_db_tpu.ops import Expr
        C = Expr.col
        cases = [
            (C(0) < 1000).node,             # exactly one block
            (C(0) <= 1000).node,            # first row of block 2
            (C(0) >= 6999).node,            # last row of block 7
            (C(0) > 6999).node,
            ((C(0) >= 999) & (C(0) <= 1000)).node,   # straddles a cut
            ((C(0) >= 2000) & (C(0) < 3000)).node,   # aligned window
            (C(0) < 0).node,                         # empty
        ]
        for where in cases:
            req = ReadRequest("lineitem_r", where=where,
                              aggregates=(AggSpec("count"),
                                          AggSpec("sum", C(0).node)))
            on = t.read(req)
            flags.set_flag("zone_map_pruning", False)
            off = t.read(req)
            flags.REGISTRY.reset("zone_map_pruning")
            for a, b in zip(on.agg_values, off.agg_values):
                assert float(np.asarray(a)) == float(np.asarray(b)), \
                    where
            got = int(np.asarray(on.agg_values[0]))
            # CPU oracle over raw data
            from yugabyte_db_tpu.docdb.operations import eval_expr_py
            want = sum(
                1 for i in range(n)
                if eval_expr_py(where, {0: int(data["rowid"][i])})
                is True)
            assert got == want, where

    @pytest.mark.parametrize("streamed", [True, False])
    def test_selective_scan_skips_blocks(self, tmp_path, v2_flag,
                                         streamed):
        """Either driver drops the ~18 of 20 blocks provably out of
        range: the streamed one tallies its prune, the monolithic one
        tags the `zone_prune` step of `docdb.collect_blocks`."""
        t, data = self._range_tablet(tmp_path, n=20000, block_rows=1000)
        from yugabyte_db_tpu.ops import Expr
        from yugabyte_db_tpu.ops.stream_scan import LAST_STREAM_STATS
        from yugabyte_db_tpu.utils.trace import TRACES
        C = Expr.col
        req = ReadRequest("lineitem_r",
                          where=(C(0) < 2000).node,
                          aggregates=(AggSpec("count"),))
        LAST_STREAM_STATS.clear()
        flags.set_flag("streaming_scan_enabled", streamed)
        try:
            with TRACES.trace("zone-prune") as root:
                resp = t.read(req)
        finally:
            flags.REGISTRY.reset("streaming_scan_enabled")
        assert int(np.asarray(resp.agg_values[0])) == 2000
        if streamed:
            assert LAST_STREAM_STATS["zone_blocks_total"] == 20
            assert LAST_STREAM_STATS["zone_blocks_pruned"] >= 15
            return
        assert not LAST_STREAM_STATS
        prune, = [s.tags for s in TRACES.finished()
                  if s.trace_id == root.trace_id
                  and s.name == "docdb.collect_blocks"
                  and s.tags.get("step") == "zone_prune"]
        assert prune["blocks"] == 20 and prune["pruned"] >= 15

    def test_f32_boundary_rounding_never_prunes_matches(
            self, tmp_path, v2_flag):
        """Zone maps are exact f64 but the kernel may evaluate in the
        device float dtype (f32): a value just below a predicate
        boundary can f32-round ONTO it and match. The prune intervals
        widen through the f32 envelope, so pruning must agree with the
        unpruned scan bit-for-bit."""
        from yugabyte_db_tpu.models.tpch import lineitem_range_info
        from yugabyte_db_tpu.ops import Expr
        n = 8192
        data = {
            "rowid": np.arange(n, dtype=np.int64),
            "l_quantity": np.full(n, 1.0),
            "l_extendedprice": np.full(n, 1.0),
            "l_discount": np.full(n, 0.0499999999),  # f32-rounds to .05
            "l_tax": np.zeros(n),
            "l_shipdate": np.full(n, 9000, np.int32),
            "l_returnflag": np.zeros(n, np.int32),
            "l_linestatus": np.zeros(n, np.int32),
        }
        flags.set_flag("device_float_dtype", "float32")
        try:
            t = Tablet("f32z", lineitem_range_info(), str(tmp_path))
            t.bulk_load(data, ht=HybridTime.from_micros(1 << 40),
                        block_rows=512)
            req = ReadRequest("lineitem_r",
                              where=(Expr.col(3) >= 0.05).node,
                              aggregates=(AggSpec("count"),))
            on = t.read(req)
            flags.set_flag("zone_map_pruning", False)
            off = t.read(req)
            assert int(np.asarray(on.agg_values[0])) == \
                int(np.asarray(off.agg_values[0]))
        finally:
            flags.REGISTRY.reset("zone_map_pruning")
            flags.REGISTRY.reset("device_float_dtype")

    def test_prune_helper_conservative_shapes(self):
        zmap = {0: (10, 20), 1: (0.5, 0.7)}
        # provable misses
        assert not zone_maybe_match(("cmp", "lt", ("col", 0),
                                     ("const", 10)), zmap)
        assert not zone_maybe_match(("cmp", "eq", ("col", 0),
                                     ("const", 21)), zmap)
        assert not zone_maybe_match(("in", ("col", 0), [1, 2, 30]), zmap)
        # boundary hits stay
        assert zone_maybe_match(("cmp", "le", ("col", 0),
                                 ("const", 10)), zmap)
        assert zone_maybe_match(("cmp", "ge", ("col", 0),
                                 ("const", 20)), zmap)
        # unknown shapes / columns never prune
        assert zone_maybe_match(("cmp", "lt", ("col", 9),
                                 ("const", 0)), zmap)
        assert zone_maybe_match(("not", ("cmp", "lt", ("col", 0),
                                         ("const", 10))), zmap)
        assert zone_maybe_match(("like", ("col", 2), "x%"), zmap)
        # OR needs every branch to miss
        assert not zone_maybe_match(
            ("or", ("cmp", "lt", ("col", 0), ("const", 5)),
             ("cmp", "gt", ("col", 0), ("const", 25))), zmap)
        assert zone_maybe_match(
            ("or", ("cmp", "lt", ("col", 0), ("const", 5)),
             ("cmp", "gt", ("col", 0), ("const", 15))), zmap)

    def test_prune_never_empties_block_list(self):
        blocks = []
        for i in range(3):
            cb = ColumnarBlock.from_arrays(
                schema_version=1,
                key_hash=np.arange(4, dtype=np.uint64),
                ht=np.full(4, 9, np.uint64))
            cb.zmap = {0: (i * 10, i * 10 + 9)}
            blocks.append(cb)
        kept, idx = zone_prune_blocks(
            blocks, ("cmp", "gt", ("col", 0), ("const", 100)))
        assert len(kept) == 1 and len(idx) == 1


class TestLaneStatsPlumbing:
    def test_incompressible_lane_reports_raw(self, tmp_path, v2_flag):
        rng = np.random.default_rng(6)
        n = 4000
        t = Tablet("st", make_info(), str(tmp_path))
        t.bulk_load({"k": np.arange(n, dtype=np.int64),
                     "v": rng.random(n),
                     "s": np.array(["q"] * n, dtype=object)},
                    ht=HybridTime.from_micros(1 << 40), block_rows=1024)
        from yugabyte_db_tpu.docdb.compaction import (
            LAST_COMPACTION_STATS, tpu_compact)
        t.bulk_load({"k": np.arange(n, dtype=np.int64) + n,
                     "v": rng.random(n),
                     "s": np.array(["q"] * n, dtype=object)},
                    ht=HybridTime.from_micros((1 << 40) + 5),
                    block_rows=1024)
        tpu_compact(t.regular, t.codec, t.history_cutoff(),
                    backend="native")
        lanes = LAST_COMPACTION_STATS["lanes"]
        # random f64 value column: encode-only-if-smaller keeps it raw
        fv = lanes["fixed_vals"]
        assert fv["encodings"].get("raw", 0) >= 1
        # keys derived away entirely
        assert lanes["keys"]["post_bytes"] == 0
        assert lanes["keys"]["encodings"] == {
            "derived": lanes["keys"]["encodings"]["derived"]}
        assert LAST_COMPACTION_STATS["format_version"] == 2
        assert LAST_COMPACTION_STATS["output_bytes"] > 0
