"""Client + consensus: median client-side time of one INSERT statement of
the window, from send to acknowledgement (after Raft apply)."""


def read(ctx):
    return ctx.rec.median_ms("insert")
