"""`bulk_blocks_iter` encodes a text column that arrives as a fixed-width
byte array (`S<n>`) in one masked copy; the blocks, and the SST written
from them, are byte for byte what the row-by-row path (`object` arrays)
gives."""
import tempfile

import numpy as np
import pytest

from yugabyte_db_tpu.docdb.table_codec import TableCodec, TableInfo
from yugabyte_db_tpu.dockv.packed_row import (ColumnSchema, ColumnType,
                                              TableSchema)
from yugabyte_db_tpu.dockv.partition import PartitionSchema
from yugabyte_db_tpu.tablet import Tablet
from yugabyte_db_tpu.utils.hybrid_time import HybridTime


def _info() -> TableInfo:
    schema = TableSchema((
        ColumnSchema(0, "k", ColumnType.INT64, is_hash_key=True),
        ColumnSchema(1, "flag", ColumnType.STRING),
        ColumnSchema(2, "note", ColumnType.STRING),
        ColumnSchema(3, "v", ColumnType.FLOAT64),
    ), 1)
    return TableInfo("t", "t", schema, PartitionSchema("hash", 1))


def _columns(n: int, seed: int) -> dict:
    rng = np.random.default_rng(seed)
    width = rng.integers(0, 20, n)              # empty strings too
    note = rng.integers(97, 123, (n, 19)).astype(np.uint8)
    note[np.arange(19) >= width[:, None]] = 0
    note[::7, 3] = 0                            # a NUL inside a value
    return {"k": rng.permutation(n).astype(np.int64),
            "flag": np.array([b"A", b"NO", b"R"])[rng.integers(0, 3, n)],
            "note": note.view("S19").ravel(),
            "v": rng.uniform(0, 1, n)}


@pytest.mark.parametrize("n, block_rows", [(5000, 1024), (700, 65536)])
def test_fixed_width_bytes_give_the_blocks_of_the_row_loop(n, block_rows):
    codec, cols = TableCodec(_info()), _columns(n, n)
    by_row = dict(cols, flag=cols["flag"].astype(object),
                  note=cols["note"].astype(object))
    assert cols["note"].dtype.kind == "S" and by_row["note"].dtype == object
    ht = HybridTime(12345)
    fast = list(codec.bulk_blocks_iter(cols, ht, block_rows=block_rows))
    slow = list(codec.bulk_blocks_iter(by_row, ht, block_rows=block_rows))
    assert len(fast) == len(slow) == -(-n // block_rows)
    for a, b in zip(fast, slow):
        assert a.n == b.n and set(a.varlen) == set(b.varlen) == {1, 2}
        for cid in a.varlen:
            ends_a, heap_a, nulls_a = a.varlen[cid]
            ends_b, heap_b, nulls_b = b.varlen[cid]
            assert ends_a.dtype == ends_b.dtype
            assert ends_a.tobytes() == ends_b.tobytes()
            assert bytes(heap_a) == bytes(heap_b)
            assert nulls_a.tobytes() == nulls_b.tobytes()
        assert a.keys.tobytes() == b.keys.tobytes()


def test_the_sst_is_byte_identical():
    cols = _columns(3000, 5)
    by_row = dict(cols, flag=cols["flag"].astype(object),
                  note=cols["note"].astype(object))
    files = []
    for data in (cols, by_row):
        t = Tablet("t", _info(), tempfile.mkdtemp(prefix="bulk-text-"))
        assert t.bulk_load(data, ht=HybridTime(777), block_rows=512) == 3000
        (sst,) = t.regular.ssts
        with open(sst.path, "rb") as f:
            files.append(f.read())
    assert files[0] == files[1]


def test_a_table_loaded_tablet_by_tablet_keeps_each_row_once():
    """Every tablet is handed every row and keeps its partition's: the
    hash columns are hashed in one native pass (the value
    `bulk.fast_hash16_from_encoded` gives), the bounds compared as 16-bit
    numbers, the rest of the key encoded for the kept rows only — the
    blocks are those of the whole-key encoding."""
    from yugabyte_db_tpu.dockv import bulk
    from yugabyte_db_tpu.dockv.partition import Partition
    codec, cols = TableCodec(_info()), _columns(6000, 9)
    want = bulk.fast_hash16_from_encoded(
        bulk.encode_int64_column(cols["k"]))
    edges = [b"", (0x3000).to_bytes(2, "big"), (0x9000).to_bytes(2, "big"),
             (0xD000).to_bytes(2, "big"), b""]
    seen = []
    for lo, hi in zip(edges[:-1], edges[1:]):
        blocks = list(codec.bulk_blocks_iter(
            cols, HybridTime(5), block_rows=1024,
            partition=Partition(lo, hi)))
        keys = np.concatenate([b.pk[0] for b in blocks])
        h = bulk.fast_hash16_from_encoded(bulk.encode_int64_column(keys))
        lo16 = int.from_bytes(lo, "big") if lo else 0
        hi16 = int.from_bytes(hi, "big") if hi else 1 << 16
        assert ((h >= lo16) & (h < hi16)).all()
        assert len(keys) == ((want >= lo16) & (want < hi16)).sum()
        # sorted by doc key: hash first
        assert (np.diff(h.astype(np.int64)) >= 0).all()
        for b in blocks:
            assert b.keys[:, 1:3].tobytes() == bulk.fast_hash16_from_encoded(
                bulk.encode_int64_column(b.pk[0])).astype(">u2").tobytes()
        seen.append(keys)
    assert sorted(np.concatenate(seen)) == sorted(cols["k"])
