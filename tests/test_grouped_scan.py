"""Device grouped aggregation (dict-key GROUP BY): device-vs-CPU-twin
parity across dictionary remaps, NaN payloads, empty groups, slot
overflow -> interpreter fallback, chunk-straddling groups, flag revert,
mixed v1+v2 SST inputs — plus the dict-identity device-cache key
regression and the shared group-keyed partial combine.  Launches are
counted as the `device.scan` spans of a forced trace."""
import tempfile

import numpy as np
import pytest

from yugabyte_db_tpu.docdb.operations import ReadRequest
from yugabyte_db_tpu.docdb.table_codec import TableInfo
from yugabyte_db_tpu.docdb.wire import (read_request_from_wire,
                                        read_request_to_wire)
from yugabyte_db_tpu.dockv.packed_row import (ColumnSchema, ColumnType,
                                              TableSchema)
from yugabyte_db_tpu.dockv.partition import PartitionSchema
from yugabyte_db_tpu.ops import AggSpec, stream_scan
from yugabyte_db_tpu.ops.device_batch import DeviceBlockCache, build_batch
from yugabyte_db_tpu.ops.expr import Expr
from yugabyte_db_tpu.ops.grouped_scan import (GROUPED_STATS, DictGroupSpec,
                                              decode_slot_groups,
                                              grouped_aggregate_cpu,
                                              make_dict_plan)
from yugabyte_db_tpu.ops.scan import ScanKernel, combine_grouped_partials
from yugabyte_db_tpu.storage import lane_codec
from yugabyte_db_tpu.tablet import Tablet
from yugabyte_db_tpu.utils import flags
from yugabyte_db_tpu.utils.trace import TRACES

C = Expr.col
RF = np.array(["A", "N", "R"], object)
LS = np.array(["F", "O"], object)
N = 24_000


def _make_tablet(prefix, n=N, seed=3, block_rows=4096, nan_every=0,
                 frac=False):
    schema = TableSchema((
        ColumnSchema(0, "k", ColumnType.INT64, is_hash_key=True),
        ColumnSchema(1, "rf", ColumnType.STRING),
        ColumnSchema(2, "ls", ColumnType.STRING),
        ColumnSchema(3, "qty", ColumnType.FLOAT64),
    ), 1)
    info = TableInfo("li", "li", schema, PartitionSchema("hash", 1))
    t = Tablet("li", info, tempfile.mkdtemp(prefix=prefix))
    rng = np.random.default_rng(seed)
    rf = rng.integers(0, 3, n)
    ls = rng.integers(0, 2, n)
    # integer-valued qty by default: the device's exact int64 SUM lane
    # makes grouped results BYTE-identical to the interpreted path;
    # frac=True exercises the fixed-point float lane (bitwise only vs
    # the CPU twin, which replays the quantization contract)
    qty = (rng.uniform(1.0, 50.0, n) if frac
           else rng.integers(1, 50, n).astype(np.float64))
    if nan_every:
        qty[::nan_every] = np.nan
    data = {
        "k": np.arange(n, dtype=np.int64),
        "rf": RF[rf], "ls": LS[ls], "qty": qty,
    }
    t.bulk_load(data, block_rows=block_rows)
    return t, data


def _blocks(t):
    out = []
    for r in t.regular.ssts:
        for i in range(r.num_blocks()):
            out.append(r.columnar_block(i))
    return out


def _grouped_read(t, where=None, spec=None):
    spec = spec or DictGroupSpec(cols=(1, 2))
    return t.read(ReadRequest(
        "li", where=where,
        aggregates=(AggSpec("sum", C(3).node), AggSpec("count")),
        group_by=spec))


def _by_key(resp):
    """{group key tuple: (count, *agg values)} — order-free comparison
    between device (slot-ordered) and interpreted (first-seen) paths."""
    counts = np.asarray(resp.group_counts)
    out = {}
    for g in np.nonzero(counts)[0]:
        key = tuple(str(v[g]) for v in resp.group_values)
        out[key] = (int(counts[g]),) + tuple(
            np.asarray(v)[g] for v in resp.agg_values)
    return out


@pytest.fixture(scope="module")
def strtab():
    t, data = _make_tablet("grp-")
    return t, data


@pytest.fixture(autouse=True)
def _reset_flags():
    yield
    for f in ("grouped_pushdown_enabled", "grouped_max_slots",
              "streaming_chunk_rows", "streaming_scan_enabled",
              "sst_format_version", "tpu_min_rows_for_pushdown",
              "grouped_spill_merge_enabled"):
        flags.REGISTRY.reset(f)


# --- dictionary plan / remap units ----------------------------------------

class TestDictPlan:
    def test_merge_disjoint_and_overlapping(self):
        a = np.array(["A", "N"], object)
        b = np.array(["N", "R", "Z"], object)
        g, remaps = lane_codec.merge_dicts([a, b])
        assert list(g) == ["A", "N", "R", "Z"]
        assert list(remaps[0]) == [0, 1]
        assert list(remaps[1]) == [1, 2, 3]

    def test_dict_identity_distinguishes_contents(self):
        a = lane_codec.dict_identity(np.array(["A", "N"], object))
        b = lane_codec.dict_identity(np.array(["A", "R"], object))
        c = lane_codec.dict_identity(np.array(["A", "N"], object))
        assert a != b and a == c

    def test_varlen_code_rows_trailing_nul_distinct(self):
        # "a" and "a\x00" must code as DIFFERENT dictionary entries
        payload = b"a" + b"a\x00" + b"a"
        ends = np.array([1, 3, 4], np.uint32)
        got = lane_codec.varlen_code_rows(ends, payload)
        assert got is not None
        ulens, uheap, codes = got
        assert len(ulens) == 2
        assert codes[0] == codes[2] != codes[1]

    def test_plan_remaps_block_local_codes(self, strtab):
        t, data = strtab
        blocks = _blocks(t)
        plan = make_dict_plan(blocks, [1])
        assert plan is not None
        assert list(plan.dicts[1]) == ["A", "N", "R"]
        dec = np.concatenate(
            [plan.dicts[1][plan.block_codes(1, b)] for b in blocks])
        # block order == load order for a single bulk-loaded SST run;
        # compare as multisets per value to stay order-robust
        assert len(dec) == len(data["rf"])
        for v in ("A", "N", "R"):
            assert (dec == v).sum() == (data["rf"] == v).sum()


# --- device kernel vs CPU twin, bitwise -----------------------------------

class TestGroupedParity:
    def test_device_matches_cpu_twin_bitwise(self):
        # FRACTIONAL payloads: the fixed-point SUM lane quantizes, and
        # the twin replays that exact contract — bitwise on x64
        t, _data = _make_tablet("twin-", frac=True)
        blocks = _blocks(t)
        spec = DictGroupSpec(cols=(1, 2))
        aggs = (AggSpec("sum", C(3).node), AggSpec("count"),
                AggSpec("min", C(3).node), AggSpec("max", C(3).node))
        plan = make_dict_plan(blocks, [1, 2, 3])
        kernel = ScanKernel()
        batch = build_batch(blocks, [1, 2, 3], dict_plan=plan,
                            multi_version=len(blocks) > 1)
        douts, dcounts, _, spill = kernel.run(batch, None, aggs, spec,
                                              None)
        assert int(spill) == 0
        couts, ccounts, cspill = grouped_aggregate_cpu(
            blocks, [1, 2, 3], None, aggs, spec, plan=plan)
        assert cspill == 0
        nslots = len(np.asarray(ccounts))
        assert np.array_equal(np.asarray(dcounts)[:nslots],
                              np.asarray(ccounts))
        for dv, cv in zip(douts, couts):
            da = np.asarray(dv)[:nslots]
            ca = np.asarray(cv)
            # min/max carry sentinel values in empty slots; compare on
            # occupied slots bitwise (x64 backend)
            occ = np.asarray(ccounts) > 0
            assert np.array_equal(da[occ].astype(np.float64),
                                  ca[occ].astype(np.float64)), (da, ca)

    def test_parity_across_dict_remaps(self):
        # two SSTs with DIFFERENT string universes: per-block dicts
        # disagree, so the scan-global remap is non-trivial
        t, _ = _make_tablet("remap-", n=6000, seed=5)
        n2 = 6000
        rng = np.random.default_rng(11)
        t.bulk_load({
            "k": np.arange(N, N + n2, dtype=np.int64),
            "rf": np.array(["R", "X", "Z"], object)[
                rng.integers(0, 3, n2)],
            "ls": LS[rng.integers(0, 2, n2)],
            "qty": rng.integers(1, 50, n2).astype(np.float64),
        }, block_rows=4096)
        on = _grouped_read(t)
        assert on.backend == "tpu"
        flags.set_flag("grouped_pushdown_enabled", False)
        off = _grouped_read(t)
        assert off.backend == "cpu"
        assert _by_key(on) == _by_key(off)
        # 5 distinct rf values survived the merge
        assert len({k[0] for k in _by_key(on)}) == 5

    def test_nan_payloads(self):
        t, _ = _make_tablet("nan-", n=8000, nan_every=7)
        on = _grouped_read(t)
        flags.set_flag("grouped_pushdown_enabled", False)
        off = _grouped_read(t)
        ka, kb = _by_key(on), _by_key(off)
        assert set(ka) == set(kb)
        for k in ka:
            assert ka[k][0] == kb[k][0]                      # counts
            np.testing.assert_array_equal(
                np.isnan(float(ka[k][1])), np.isnan(float(kb[k][1])))

    def test_empty_groups_compact_away(self, strtab):
        t, data = strtab
        # WHERE excludes every 'R' row: the 'R' dictionary entries stay
        # in the scan-global dictionary but their slots count zero and
        # must NOT appear in the response
        on = _grouped_read(t, where=C(1).ne("R").node)
        assert on.backend == "tpu"
        keys = {k[0] for k in _by_key(on)}
        assert keys == {"A", "N"}
        flags.set_flag("grouped_pushdown_enabled", False)
        off = _grouped_read(t, where=C(1).ne("R").node)
        assert _by_key(on) == _by_key(off)

    def test_no_rows_match(self, strtab):
        t, _ = strtab
        resp = _grouped_read(t, where=C(1).eq("ZZZ").node)
        counts = np.asarray(resp.group_counts)
        assert counts.sum() == 0 or len(counts) == 0

    def test_chunk_straddling_groups_stream(self, strtab):
        t, _ = strtab
        flags.set_flag("streaming_chunk_rows", 4096)
        stream_scan.LAST_STREAM_STATS.clear()
        on = _grouped_read(t)
        assert on.backend == "tpu"
        from yugabyte_db_tpu.ops.grouped_scan import LAST_GROUPED_STATS
        assert LAST_GROUPED_STATS.get("path") == "streaming"
        # every group is present in every chunk: per-chunk partials had
        # to combine across chunk boundaries
        flags.set_flag("grouped_pushdown_enabled", False)
        off = _grouped_read(t)
        assert _by_key(on) == _by_key(off)


# --- fallbacks ------------------------------------------------------------

def _launches(root):
    """The kernel launches of the trace `root` began."""
    return sum(s.name == "device.scan" for s in TRACES.finished()
               if s.trace_id == root.trace_id)


class TestFallbacks:
    def test_slot_overflow_merges_on_monolithic_route(self, strtab):
        # DEFAULT behavior since the monolithic partial-spill merge:
        # an over-cardinality scan on the MONOLITHIC dict-group route
        # keeps its exact in-range device partials and re-aggregates
        # only the spilled rows interpreted — backend stays tpu, no
        # full re-scan fallback (the streamed route got this first;
        # this is its monolithic twin)
        t, _ = strtab
        m0 = GROUPED_STATS["spill_merges"]
        fb0 = GROUPED_STATS["spill_fallbacks"]
        resp = _grouped_read(t, spec=DictGroupSpec(cols=(1, 2),
                                                   max_slots=4))
        assert resp.backend == "tpu"
        assert GROUPED_STATS["spill_merges"] == m0 + 1
        assert GROUPED_STATS["spill_fallbacks"] == fb0
        flags.set_flag("grouped_pushdown_enabled", False)
        off = _grouped_read(t)
        assert _by_key(resp) == _by_key(off)

    def test_monolithic_spill_mask_comes_from_a_filter_launch(self, strtab):
        # an aggregate launch returns no row mask: the monolithic merge
        # takes the spilled rows' mask from a filter launch at the same
        # read point, which folds the WHERE — one launch more, on this
        # path alone, and the interpreted GROUP BY's answer
        t, _ = strtab
        where = (C(3) < 40.0).node
        with TRACES.trace("spill") as root:
            resp = _grouped_read(t, where=where,
                                 spec=DictGroupSpec(cols=(1, 2), max_slots=4))
        assert resp.backend == "tpu"
        assert _launches(root) == 2
        flags.set_flag("grouped_pushdown_enabled", False)
        off = _grouped_read(t, where=where)
        assert _by_key(resp) == _by_key(off)

    def test_slot_overflow_reverts_when_merge_disabled(self, strtab):
        t, _ = strtab
        flags.set_flag("grouped_spill_merge_enabled", False)
        fb0 = GROUPED_STATS["spill_fallbacks"]
        resp = _grouped_read(t, spec=DictGroupSpec(cols=(1, 2),
                                                   max_slots=4))
        assert resp.backend == "cpu"       # interpreted GROUP BY served
        # EXACTLY one spill fallback per query: the monolithic path must
        # not re-run (and re-spill) a scan the streamed path already
        # proved over-cardinality
        assert GROUPED_STATS["spill_fallbacks"] == fb0 + 1
        flags.set_flag("grouped_pushdown_enabled", False)
        off = _grouped_read(t)
        assert _by_key(resp) == _by_key(off)

    def test_streamed_spill_skips_monolithic_pass(self, strtab):
        # with streaming active and the partial-spill MERGE disabled,
        # an over-cardinality scan must pay ONE device pass (the
        # streamed one that detected the spill), then go straight to
        # the interpreter: one spill fallback, and no extra grouped
        # kernel launches beyond the streamed chunks
        t, _ = strtab
        flags.set_flag("streaming_chunk_rows", 4096)
        flags.set_flag("grouped_spill_merge_enabled", False)
        _grouped_read(t)                     # warm the chunk plan/cache
        fb0 = GROUPED_STATS["spill_fallbacks"]
        with TRACES.trace("spill") as root:
            resp = _grouped_read(t, spec=DictGroupSpec(cols=(1, 2),
                                                       max_slots=4))
        chunks = stream_scan.LAST_STREAM_STATS.get("chunks", 0)
        assert resp.backend == "cpu"
        assert GROUPED_STATS["spill_fallbacks"] == fb0 + 1
        assert chunks >= 3
        assert _launches(root) == chunks

    def test_streamed_spill_merges_partials(self, strtab):
        # DEFAULT spill behavior since the partial-spill merge: device
        # slots below the spill slot keep their exact partials, the
        # spilled rows re-aggregate on the interpreted tail, and the
        # combined answer equals the full interpreted GROUP BY — no
        # full re-scan, backend stays tpu
        t, _ = strtab
        flags.set_flag("streaming_chunk_rows", 4096)
        m0 = GROUPED_STATS["spill_merges"]
        fb0 = GROUPED_STATS["spill_fallbacks"]
        resp = _grouped_read(t, spec=DictGroupSpec(cols=(1, 2),
                                                   max_slots=4))
        assert resp.backend == "tpu"
        assert GROUPED_STATS["spill_merges"] == m0 + 1
        assert GROUPED_STATS["spill_fallbacks"] == fb0
        flags.set_flag("grouped_pushdown_enabled", False)
        off = _grouped_read(t)
        assert _by_key(resp) == _by_key(off)

    def test_flag_off_reverts(self, strtab):
        t, _ = strtab
        flags.set_flag("grouped_pushdown_enabled", False)
        with TRACES.trace("off") as root:
            resp = _grouped_read(t)
        assert resp.backend == "cpu"
        assert _launches(root) == 0
        assert sum(c for c, *_ in _by_key(resp).values()) == N

    def test_overlong_strings_stay_correct(self):
        # rows longer than the dict-lane coder's max_len can't ride the
        # scan-global plan (streaming declines) but the monolithic
        # batch's legacy decode dictionary still serves them — whatever
        # path wins, results must match the interpreter
        t, _ = _make_tablet("long-", n=6000)
        long_tail = np.array(["x" * 300, "y" * 300], object)
        rng = np.random.default_rng(2)
        t.bulk_load({
            "k": np.arange(N, N + 6000, dtype=np.int64),
            "rf": long_tail[rng.integers(0, 2, 6000)],
            "ls": LS[rng.integers(0, 2, 6000)],
            "qty": rng.integers(1, 50, 6000).astype(np.float64),
        }, block_rows=4096)
        flags.set_flag("streaming_chunk_rows", 4096)
        from yugabyte_db_tpu.ops.grouped_scan import LAST_GROUPED_STATS
        LAST_GROUPED_STATS.clear()
        on = _grouped_read(t)
        assert LAST_GROUPED_STATS.get("path") != "streaming"
        flags.set_flag("grouped_pushdown_enabled", False)
        off = _grouped_read(t)
        assert _by_key(on) == _by_key(off)


# --- mixed v1 + v2 SST inputs ---------------------------------------------

class TestMixedFormats:
    def test_mixed_v1_v2_ssts(self):
        flags.set_flag("sst_format_version", 1)
        t, _ = _make_tablet("mixed-", n=8000)
        flags.set_flag("sst_format_version", 2)
        rng = np.random.default_rng(9)
        t.bulk_load({
            "k": np.arange(N, N + 8000, dtype=np.int64),
            "rf": RF[rng.integers(0, 3, 8000)],
            "ls": LS[rng.integers(0, 2, 8000)],
            "qty": rng.integers(1, 50, 8000).astype(np.float64),
        }, block_rows=4096)
        vs = {r.format_version for r in t.regular.ssts}
        assert vs == {1, 2}
        on = _grouped_read(t)
        assert on.backend == "tpu"
        flags.set_flag("grouped_pushdown_enabled", False)
        off = _grouped_read(t)
        assert _by_key(on) == _by_key(off)

    def test_v2_dict_lane_round_trips(self):
        # a v2-written block with dict-coded varlen lanes must decode
        # to the exact original (ends, heap) pair AND serve dict_varlen
        # straight from the stored parts
        t, data = _make_tablet("v2rt-", n=6000)
        blocks = _blocks(t)
        got = [b for b in blocks if b._vdicts]
        assert got, "v2 writer never dict-coded the string lanes"
        b = got[0]
        uniq, codes = b.dict_varlen(1)
        assert sorted(set(uniq)) == list(uniq)
        dec = uniq[codes]
        ends, heap, null = b.varlen[1]
        raw = [bytes(heap[(0 if i == 0 else ends[i - 1]):ends[i]]).decode()
               for i in range(b.n)]
        assert list(dec) == raw


# --- the device-cache key regression (satellite) --------------------------

class TestDeviceCacheKey:
    def test_dict_identity_keys_cached_chunks(self):
        """Two streamed scans under the SAME cache key but different
        merged dictionaries must never share a cached batch of remapped
        codes — the dict identity rides in the chunk key."""
        t1, _ = _make_tablet("ck1-", n=16000, seed=21)
        t2, _ = _make_tablet("ck2-", n=16000, seed=22)
        # different universe on t2: same shapes, different dictionary
        rng = np.random.default_rng(23)
        n = 16000
        t2b = Tablet("li", t2.info, tempfile.mkdtemp(prefix="ck3-"))
        t2b.bulk_load({
            "k": np.arange(n, dtype=np.int64),
            "rf": np.array(["X", "Y", "Z"], object)[
                rng.integers(0, 3, n)],
            "ls": LS[rng.integers(0, 2, n)],
            "qty": rng.integers(1, 50, n).astype(np.float64),
        }, block_rows=4096)
        blocks1, blocks2 = _blocks(t1), _blocks(t2b)
        spec = DictGroupSpec(cols=(1, 2))
        aggs = (AggSpec("count"),)
        cache = DeviceBlockCache()
        kernel = ScanKernel()
        key = ("same", "store", "key")
        out = []
        for blocks in (blocks1, blocks2):
            gout: dict = {}
            got = stream_scan.streaming_scan_aggregate(
                blocks, [1, 2], None, aggs, spec, None, kernel=kernel,
                chunk_rows=4096, cache=cache, cache_key=key,
                grouped_out=gout)
            assert got is not None
            outs, counts = got
            out.append(decode_slot_groups(spec, gout["dicts"], outs,
                                          counts))
        # the second scan's decoded keys must be ITS universe — a
        # shared cached batch would leak t1's codes under t2's dicts
        keys2 = {v for v in out[1][2][0]}
        assert keys2 <= {"X", "Y", "Z"}
        assert int(np.asarray(out[0][1]).sum()) == 16000
        assert int(np.asarray(out[1][1]).sum()) == 16000
        # and both scans' batches are distinct cache entries
        assert cache.misses >= 8

    def test_same_dicts_reuse_cache(self):
        t, _ = _make_tablet("ckr-", n=16000, seed=31)
        blocks = _blocks(t)
        spec = DictGroupSpec(cols=(1, 2))
        aggs = (AggSpec("count"),)
        cache = DeviceBlockCache()
        kernel = ScanKernel()
        key = ("k",)
        for _ in range(2):
            got = stream_scan.streaming_scan_aggregate(
                blocks, [1, 2], None, aggs, spec, None, kernel=kernel,
                chunk_rows=4096, cache=cache, cache_key=key,
                grouped_out={})
            assert got is not None
        assert cache.hits >= 4      # warm re-scan reused every chunk


# --- wire + shared combine -------------------------------------------------

class TestWireAndCombine:
    def test_wire_roundtrip_dict_group(self):
        req = ReadRequest("li", aggregates=(AggSpec("count"),),
                          group_by=DictGroupSpec(cols=(1, 2),
                                                 max_slots=64))
        got = read_request_from_wire(read_request_to_wire(req))
        assert isinstance(got.group_by, DictGroupSpec)
        assert got.group_by.cols == (1, 2)
        assert got.group_by.max_slots == 64

    def test_combine_grouped_partials_string_keys(self):
        aggs = (AggSpec("sum", C(3).node), AggSpec("count"),
                AggSpec("min", C(3).node))
        p1 = ((np.array([10.0, 5.0]), np.array([2, 1], np.int64),
               np.array([3.0, 7.0])),
              np.array([2, 1], np.int64),
              (np.array(["A", "N"], object),))
        p2 = ((np.array([4.0, 6.0]), np.array([1, 2], np.int64),
               np.array([1.0, 9.0])),
              np.array([1, 2], np.int64),
              (np.array(["N", "R"], object),))
        outs, counts, gvals = combine_grouped_partials(aggs, [p1, p2])
        m = {g: (float(outs[0][i]), int(outs[1][i]), float(outs[2][i]),
                 int(counts[i]))
             for i, g in enumerate(gvals[0])}
        assert m["A"] == (10.0, 2, 3.0, 2)
        assert m["N"] == (9.0, 2, 1.0, 2)      # 5+4, 1+1, min(7,1)
        assert m["R"] == (6.0, 2, 9.0, 2)

    def test_bypass_grouped_keyless(self):
        from yugabyte_db_tpu.bypass import BypassSession
        from yugabyte_db_tpu.storage.columnar import KEY_REBUILD_STATS
        t, _ = _make_tablet("byp-", n=16000, seed=41)
        rb0 = KEY_REBUILD_STATS["rebuilds"]
        with BypassSession([t]) as s:
            gout: dict = {}
            outs, counts, stats = s.scan_aggregate(
                None, (AggSpec("sum", C(3).node), AggSpec("count")),
                DictGroupSpec(cols=(1, 2)), grouped_out=gout)
        assert KEY_REBUILD_STATS["rebuilds"] == rb0
        assert int(np.asarray(counts).sum()) == 16000
        assert len(gout["group_values"]) == 2
        flags.set_flag("grouped_pushdown_enabled", False)
        off = _grouped_read(t)
        ref = _by_key(off)
        for i in range(len(np.asarray(counts))):
            key = tuple(str(v[i]) for v in gout["group_values"])
            assert ref[key][0] == int(np.asarray(counts)[i])

    def test_bypass_slot_overflow_typed(self):
        from yugabyte_db_tpu.bypass import (REASON_SLOT_OVERFLOW,
                                            BypassIneligible,
                                            BypassSession)
        t, _ = _make_tablet("bypof-", n=16000, seed=43)
        with BypassSession([t]) as s:
            with pytest.raises(BypassIneligible) as ei:
                s.scan_aggregate(
                    None, (AggSpec("count"),),
                    DictGroupSpec(cols=(1, 2), max_slots=4))
        assert ei.value.reason == REASON_SLOT_OVERFLOW

    def test_bypass_undecodable_binary_typed(self):
        # a BINARY varlen column with non-UTF8 payloads can't
        # dictionary-encode: the typed-fallback contract must hold (a
        # BypassIneligible the client routing catches, never a raw
        # KeyError escaping build_batch's decode fallback)
        from yugabyte_db_tpu.bypass import (REASON_COLUMN_NOT_FIXED,
                                            BypassIneligible,
                                            BypassSession)
        from yugabyte_db_tpu.docdb.table_codec import TableInfo
        from yugabyte_db_tpu.dockv.partition import PartitionSchema
        schema = TableSchema((
            ColumnSchema(0, "k", ColumnType.INT64, is_hash_key=True),
            ColumnSchema(1, "blob", ColumnType.BINARY),
            ColumnSchema(2, "qty", ColumnType.FLOAT64),
        ), 1)
        t = Tablet("bin", TableInfo("bin", "bin", schema,
                                    PartitionSchema("hash", 1)),
                   tempfile.mkdtemp(prefix="bypbin-"))
        n = 6000
        rng = np.random.default_rng(4)
        t.bulk_load({
            "k": np.arange(n, dtype=np.int64),
            "blob": np.array([b"\xff\xfe\x01", b"\x80\x81"],
                             object)[rng.integers(0, 2, n)],
            "qty": rng.integers(1, 50, n).astype(np.float64),
        }, block_rows=4096)
        with BypassSession([t]) as s:
            with pytest.raises(BypassIneligible) as ei:
                s.scan_aggregate(
                    None, (AggSpec("sum", C(2).node), AggSpec("count")),
                    DictGroupSpec(cols=(1,)))
        assert ei.value.reason == REASON_COLUMN_NOT_FIXED


# --- streamed filter-pushdown ROW path ------------------------------------

class TestStreamedRowPath:
    def test_rows_match_monolithic(self):
        t, data = _make_tablet("rows-", n=16000, seed=51)
        flags.set_flag("streaming_chunk_rows", 4096)
        stream_scan.LAST_STREAM_STATS.clear()
        on = t.read(ReadRequest("li", where=C(1).eq("A").node,
                                columns=["k", "rf", "qty"]))
        assert on.backend == "tpu"
        assert stream_scan.LAST_STREAM_STATS.get("chunks_run", 0) >= 2
        flags.set_flag("streaming_scan_enabled", False)
        off = t.read(ReadRequest("li", where=C(1).eq("A").node,
                                 columns=["k", "rf", "qty"]))
        assert on.rows == off.rows
        assert len(on.rows) == int((data["rf"] == "A").sum())

    def test_limit_early_exit(self):
        t, _ = _make_tablet("rowlim-", n=16000, seed=52)
        flags.set_flag("streaming_chunk_rows", 4096)
        stream_scan.LAST_STREAM_STATS.clear()
        resp = t.read(ReadRequest("li", where=C(1).eq("A").node,
                                  columns=["k"], limit=5))
        assert len(resp.rows) == 5
        st = stream_scan.LAST_STREAM_STATS
        assert st.get("chunks_run", 99) < st.get("chunks", 0)
