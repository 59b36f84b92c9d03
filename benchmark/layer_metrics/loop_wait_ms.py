"""tserver + scheduler: per statement, the sum of its `device.wait` spans
tagged `thread=loop` — the time the server's event loop itself stood
waiting for the device, in which it served nobody.  0.0 where every wait
is stood beside the loop (`thread=executor`); what `device_wait_ms` reads
where none is."""
from benchmark import span_reduce


def read(ctx):
    return span_reduce.per_statement_ms(ctx, lambda tree: sum(
        s.end_ns - s.start_ns for s in tree
        if s.name == "device.wait" and s.tags.get("thread") == "loop"))
