"""Host runtime: per statement of the window, the milliseconds in which
Python's cyclic collector held the process (`benchmark/record.py
GcWatch`, over `gc.callbacks`: the `python_gc` seconds of the window's
step line).  Every thread stands still while it runs, the event loop and
the launch threads alike.  None where the window recorded no statement."""


def read(ctx):
    n = len(ctx.rec.of("stmt"))
    seconds = ctx.rec.gc.get("seconds")
    return seconds * 1e3 / n if n and seconds is not None else None
