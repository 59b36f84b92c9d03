"""tserver + scheduler: how many statements the server has inside its read
path at once — the time-weighted mean number of statements (traces) with
a `docdb.read` open, while at least one has: the sum over statements of
the time their `docdb.read` spans cover, over the length of the union of
all of them.  `docdb.read` is open from a tablet read's first look at the
store to its response, its launch and the wait for the device included;
a server that makes its launches on the event loop runs one such span at
a time, whatever its clients send, and reads 1.0.  2.0 is two streams
that are both inside a tablet read whenever either is.  (The
`sql.execute` roots do not tell: a closed-loop client's root stays open
while its statement queues behind the other's.)  It needs no pairing of
roots with the recorder's statements.  None where the program keeps no
spans, or no `docdb.read` began in the window."""
from benchmark import span_reduce, trace_reduce


def read(ctx):
    by_stmt: dict = {}
    for s in span_reduce.window_spans(ctx) or ():
        if s.name == "docdb.read":
            by_stmt.setdefault(s.trace_id, []).append([s.start_ns, s.end_ns])
    if not by_stmt:
        return None
    held = [trace_reduce.merge(v) for v in by_stmt.values()]
    return sum(trace_reduce.total(h) for h in held) / trace_reduce.total(
        trace_reduce.merge([iv for h in held for iv in h]))
