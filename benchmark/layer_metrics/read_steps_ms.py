"""tserver + scheduler: per statement, the event loop's time inside its
tablet reads' own steps (tag `steps_ms` of the server's read spans,
`tserver.read:<tablet>` or `tserver.read_tablets`: the wall time of each
advance of the read's generator) — block collection, the batch lookup,
the facts, the rewrite, the decode; everything of a read that is not its
launch.  None where no span of the window carries the tag (a program from
before it)."""
from benchmark import span_reduce

TAG = "steps_ms"


def read(ctx):
    trees = span_reduce.trees_of(ctx)
    if not trees or not any(TAG in s.tags for t in trees for s in t):
        return None
    return sum(float(s.tags[TAG]) for t in trees for s in t
               if TAG in s.tags) / len(trees)
