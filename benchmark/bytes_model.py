"""The least bytes a scan and a compaction have to move, from rows, query
and file sizes alone — the same whatever kernel does the work and however
it holds a column, so a later PR's kernel is held to the same count.  Both
rooflines are memory-bound: the work per byte is a compare and an add."""
from __future__ import annotations

from . import tpch

# a column at the width its declared type needs: double 8, int 4, a
# one-character flag 1
COLUMN_BYTES = {"l_quantity": 8, "l_extendedprice": 8, "l_discount": 8,
                "l_tax": 8, "l_shipdate": 4, "l_returnflag": 1,
                "l_linestatus": 1}
KEY_HASH_BYTES = 8        # u64 doc-key hash: MVCC needs it to find versions
HYBRID_TIME_BYTES = 8     # u64 write time: visibility at the read time
VALID_BYTES = 1           # bool: padding and tombstones


def scan_row_bytes(query: str) -> int:
    return (sum(COLUMN_BYTES[c] for c in tpch.QUERY_COLUMNS[query])
            + KEY_HASH_BYTES + HYBRID_TIME_BYTES + VALID_BYTES)


def scan_bytes(rows: int, query: str) -> int:
    """One pass over the columns `query` reads, for `rows` stored row
    versions.  The answer (a few sums) is nothing beside it."""
    return rows * scan_row_bytes(query)


def merge_bytes(input_file_bytes: int, output_file_bytes: int) -> int:
    """A compaction reads every input SST byte once and writes every
    output byte once."""
    return input_file_bytes + output_file_bytes


def least_seconds(nbytes: float, peak: dict) -> float:
    return nbytes / peak["hbm_bytes_per_s"]
