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
HYBRID_TIME_BYTES = 8     # u64 write time: visibility at the read time
# u64 write time of the key's next newer version (`next_ht`): with it the
# newest visible version is one elementwise mask.  Until PR 28 the kernel
# read the doc-key hash here, also 8 B, and sorted by it
NEXT_HT_BYTES = 8
VALID_BYTES = 1           # bool: padding and tombstones


def scan_row_bytes(query: str) -> int:
    return (sum(COLUMN_BYTES[c] for c in tpch.QUERY_COLUMNS[query])
            + HYBRID_TIME_BYTES + NEXT_HT_BYTES + VALID_BYTES)


def scan_bytes(rows: int, query: str) -> int:
    """One pass over the columns `query` reads, for `rows` stored row
    versions.  The answer (a few sums) is nothing beside it."""
    return rows * scan_row_bytes(query)


def merge_bytes(input_file_bytes: int, output_file_bytes: int) -> int:
    """A compaction reads every input SST byte once and writes every
    output byte once."""
    return input_file_bytes + output_file_bytes


def least_seconds(nbytes: float, peak: dict, chips: int = 1) -> float:
    """`nbytes` spread evenly over `chips`, each moving its share at the
    peak HBM rate."""
    return nbytes / (chips * peak["hbm_bytes_per_s"])
