"""tserver + scheduler: per statement, the time of its `docdb.read` spans
that none of their `docdb.collect_blocks`, `docdb.batch`, `device.scan` and
`device.wait` descendants covers — what a tablet's read does around its
cached batch and its launch: the facts about the store's blocks (newest
write time, chunk-safety proof; on a program that keeps none, the
restart-window walk and the proofs every read), `device.dict_plan`, the
request's rewrite into the batch's code space and the decode of the
result.  None where no statement of the window has a `docdb.read`; 0.0
where the spans are there and nothing is left."""
from benchmark import span_reduce

SPAN = "docdb.read"
COVERED = ("docdb.collect_blocks", "docdb.batch", "device.scan",
           "device.wait")


def read(ctx):
    trees = span_reduce.trees_of(ctx)
    if not trees or not any(s.name == SPAN for t in trees for s in t):
        return None
    # (`uncovered_ns` matches names by `str.startswith`: a tuple is any of)
    return sum(span_reduce.uncovered_ns(t, SPAN, COVERED)
               for t in trees) / len(trees) / 1e6
