"""SQL front end: per statement, the self time of `sql.execute` — its
duration minus the part `client.scan` covers: parse, bind, the choice of
plan shape, lowering to a `ReadRequest`, and shaping the result rows."""
from benchmark import span_reduce


def read(ctx):
    return span_reduce.per_statement_ms(
        ctx, lambda t: span_reduce.uncovered_ns(t, span_reduce.ROOT,
                                                "client.scan"))
