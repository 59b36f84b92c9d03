"""Client fan-out: per statement, `client.scan` minus the union of its
`rpc.c.*` children: table lookup, request encoding, response decoding,
the combine of the tablets' partials, and any retry's back-off."""
from benchmark import span_reduce


def read(ctx):
    return span_reduce.per_statement_ms(
        ctx, lambda t: span_reduce.uncovered_ns(t, "client.scan", "rpc.c."))
