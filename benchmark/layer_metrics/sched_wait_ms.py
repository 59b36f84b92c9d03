"""tserver + scheduler: per statement, the sum of `wait_ms` of its
`sched.queue.scan` spans — admission to dequeue, 0 on cut-through (what
the program's `sched_wait_us` histogram counts)."""
from benchmark import span_reduce


def read(ctx):
    return span_reduce.per_statement_ms(
        ctx, lambda t: 1e6 * span_reduce.tag_sum(t, "sched.queue.scan",
                                                 "wait_ms"))
