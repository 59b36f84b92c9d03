"""Device: how many of the cell's chips ran an operation inside the traced
window (`devices_busy`).  Fewer than the cell's chips: the placement left
a chip without its share of the table."""


def read(ctx):
    if ctx.trace is None:
        return None
    return ctx.trace.get("devices_busy") or None
