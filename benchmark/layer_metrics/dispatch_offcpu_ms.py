"""Scan kernel: per statement, the time its `device.scan` spans stood off
the CPU — each span's wall time less its `cpu_ms` tag: the dispatching
thread waiting for the interpreter's lock (the event loop and the other
launch threads hold it), or blocked inside the runtime.  With
`dispatch_cpu_ms` it adds up to `kernel_dispatch_ms`.  None where no
`device.scan` of the window carries the tag (a program from before it)."""
from benchmark import span_reduce

SPAN, TAG = "device.scan", "cpu_ms"


def read(ctx):
    trees = span_reduce.trees_of(ctx)
    if not trees or not any(s.name == SPAN and TAG in s.tags
                            for t in trees for s in t):
        return None
    return sum((s.end_ns - s.start_ns) / 1e6 - float(s.tags.get(TAG) or 0.0)
               for t in trees for s in t if s.name == SPAN) / len(trees)
