"""tserver + scheduler: per statement, the time of its tablets'
`docdb.collect_blocks` (block collection, zone-map pruning) and
`docdb.batch` spans (device-cache lookup; on a miss `batch.build` and
`batch.h2d` inside it)."""
from benchmark import span_reduce


def read(ctx):
    return span_reduce.per_statement_ms(
        ctx, lambda t: span_reduce.total_ns(t, "docdb.collect_blocks")
        + span_reduce.total_ns(t, "docdb.batch"))
