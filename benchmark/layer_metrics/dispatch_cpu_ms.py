"""Scan kernel: per statement, the thread CPU time of its `device.scan`
spans (tag `cpu_ms`: `time.thread_time_ns` of the dispatching thread from
the span's open to its close) — the dispatch's own work: the jit cache
lookup, flattening the arguments, placing the host values, the enqueue.
With `dispatch_offcpu_ms` it adds up to `kernel_dispatch_ms`.  None where
no `device.scan` of the window carries the tag (a program from before
it)."""
from benchmark import span_reduce

SPAN, TAG = "device.scan", "cpu_ms"


def read(ctx):
    trees = span_reduce.trees_of(ctx)
    if not trees or not any(s.name == SPAN and TAG in s.tags
                            for t in trees for s in t):
        return None
    return sum(span_reduce.tag_sum(t, SPAN, TAG) for t in trees) / len(trees)
