"""Client fan-out: RPCs the client's messenger sent per statement of the
window (`messenger.calls_sent`).  One per tablet is the floor; more is a
deadline retry."""


def read(ctx):
    n = len(ctx.rec.of("stmt"))
    sent = ctx.rec.counters.get("messenger.calls_sent")
    return sent / n if n and sent is not None else None
