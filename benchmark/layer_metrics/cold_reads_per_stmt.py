"""tserver + scheduler: per statement, its tablet reads whose batch was not
in the device cache — `docdb.batch` spans with `cache` = `miss`, each one
built for the read (`batch.build`).  None where no statement of the window
has a `docdb.batch` span."""
from benchmark import span_reduce

SPAN = "docdb.batch"


def read(ctx):
    trees = span_reduce.trees_of(ctx)
    if not trees or not any(s.name == SPAN for t in trees for s in t):
        return None
    return sum(s.tags.get("cache") == "miss" for t in trees for s in t
               if s.name == SPAN) / len(trees)
