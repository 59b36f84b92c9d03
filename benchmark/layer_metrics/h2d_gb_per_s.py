"""Batch formation: the rate of the window's transfers to the device — the
`bytes` of every `batch.h2d` span over their summed time, in GB/s.  No
share of a peak: `peaks.json` holds no sourced host-to-device peak.  None
where no statement of the window built a batch."""
from benchmark import span_reduce

SPAN = "batch.h2d"


def read(ctx):
    trees = span_reduce.trees_of(ctx)
    spans = [s for t in trees or () for s in t if s.name == SPAN]
    ns = sum(s.end_ns - s.start_ns for s in spans)
    if not ns:
        return None
    return sum(float(s.tags.get("bytes") or 0) for s in spans) / ns
