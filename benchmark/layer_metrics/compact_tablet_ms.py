"""Compaction: median time of the `compact` RPC for one tablet, by the
benchmark's own span."""


def read(ctx):
    return ctx.rec.median_ms("compact")
