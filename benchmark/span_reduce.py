"""From the program's own spans to per-layer numbers.

The program keeps its finished spans in memory (`utils/trace.py`,
`TRACES.finished(since_ns, until_ns)`: name, trace, span and parent ids,
start and end on `time.perf_counter_ns`, tags).  A traced run samples every
statement, so after the window each statement of the recorder is one
trace rooted at `sql.execute`; the readers under `layer_metrics/` reduce
those trees here.  Everything below `window_spans` works on plain tuples,
so a test can hand it a few hand-made spans.

A program that has no such spans (a parent commit from before them), a
ring that dropped spans of the window, or roots that are not exactly the
recorder's statements give `None`, and the result line leaves the metric
out.

    span_reduce.table(ctx)      # by hand: per span name, count, total,
                                # self time, device-idle seconds inside
"""
from __future__ import annotations

import bisect

from benchmark import trace_reduce

ROOT = "sql.execute"
STMT_SPAN = "bench:stmt."          # the recorder's statement annotations
MAX_CLOCK_DISAGREEMENT_NS = 1e6    # 1 ms
TOP_GAPS = 10                      # entries of `breakdown.idle_gaps`


# -- the window's spans -----------------------------------------------------
def window_spans(ctx):
    """The program's finished spans that began inside the recorder's
    window, or None where the program keeps none or its ring dropped one
    that finished inside the window."""
    try:
        from yugabyte_db_tpu.utils.trace import TRACES
    except ImportError:
        return None
    finished = getattr(TRACES, "finished", None)
    if finished is None:
        return None
    since, until = (int(t * 1e9) for t in ctx.rec.window)
    kept = finished()
    # the ring drops the span that finished first: none of the window's
    # is gone while the oldest one kept finished before the window began
    if TRACES.evicted and (not kept or kept[0].end_ns > since):
        return None
    return [s for s in kept if since <= s.start_ns <= until]


def statement_trees(spans, stmts):
    """One list of spans per recorded statement (`stmts`: dicts with
    `t0`, `t1` in `perf_counter` seconds), each the whole trace of the one
    `sql.execute` root that began inside it — or None when the roots are
    not exactly the statements."""
    if spans is None or not stmts:
        return None
    roots = sorted((s for s in spans if s.name == ROOT and not s.parent_id),
                   key=lambda s: s.start_ns)
    stmts = sorted(stmts, key=lambda s: s["t0"])
    if len(roots) != len(stmts):
        return None
    by_trace: dict = {}
    for s in spans:
        by_trace.setdefault(s.trace_id, []).append(s)
    trees = []
    for root, stmt in zip(roots, stmts):
        if not stmt["t0"] * 1e9 <= root.start_ns <= stmt["t1"] * 1e9:
            return None
        trees.append(by_trace[root.trace_id])
    return trees


def trees_of(ctx):
    return statement_trees(window_spans(ctx),
                           ctx.rec.of("stmt", ok_only=False))


# -- reductions of one statement's tree, in nanoseconds ---------------------
def short(name: str) -> str:
    """`tserver.read:<tablet>` -> `tserver.read`."""
    return name.split(":", 1)[0]


def total_ns(tree, prefix: str) -> int:
    return sum(s.end_ns - s.start_ns for s in tree
               if s.name.startswith(prefix))


def by_parent(spans) -> dict:
    kids: dict = {}
    for s in spans:
        kids.setdefault(s.parent_id, []).append(s)
    return kids


def descendants(kids: dict, span) -> list:
    out, todo = [], [span.span_id]
    while todo:
        for c in kids.get(todo.pop(), ()):
            out.append(c)
            todo.append(c.span_id)
    return out


def left_ns(span, inner) -> float:
    """`span`'s duration minus the part the union of `inner` covers."""
    cover = trace_reduce.clip(trace_reduce.merge(
        [[c.start_ns, c.end_ns] for c in inner]), span.start_ns, span.end_ns)
    return (span.end_ns - span.start_ns) - trace_reduce.total(cover)


def uncovered_ns(tree, outer: str, inner_prefix: str) -> float:
    """Over every span named `outer`: its duration minus the part the
    union of its descendants named `inner_prefix`* covers."""
    kids = by_parent(tree)
    return sum(left_ns(o, [d for d in descendants(kids, o)
                           if d.name.startswith(inner_prefix)])
               for o in tree if o.name == outer)


def tag_sum(tree, name: str, tag: str) -> float:
    return sum(float(s.tags.get(tag) or 0.0) for s in tree
               if s.name == name)


def per_statement_ms(ctx, reduce_ns):
    """Mean over the window's statements of `reduce_ns(tree)`, in
    milliseconds; None where the trees cannot be had."""
    trees = trees_of(ctx)
    if not trees:
        return None
    return sum(reduce_ns(t) for t in trees) / len(trees) / 1e6


# -- the device's idle time, by the program's spans --------------------------
def clock_offset_ns(trace_spans, rec_stmts):
    """Profiler clock minus `perf_counter_ns`, from the statements the
    recorder holds on both (`(name, start_ns, end_ns)` annotations of the
    trace, `t0` of the recorder, in order).  None when they cannot be
    paired or the pairs disagree by more than 1 ms."""
    marks = sorted(a for name, a, _ in trace_spans
                   if name.startswith(STMT_SPAN))
    t0s = sorted(s["t0"] * 1e9 for s in rec_stmts)
    if not marks or len(marks) != len(t0s):
        return None
    offsets = sorted(a - t for a, t in zip(marks, t0s))
    if offsets[-1] - offsets[0] > MAX_CLOCK_DISAGREEMENT_NS:
        return None
    return offsets[len(offsets) // 2]


def innermost_cover(spans) -> dict:
    """span name -> merged intervals in which a span of that name is the
    innermost one open: of the spans that hold an instant, the one that
    began last (one thread runs them, so that is the one at work)."""
    cuts = sorted({t for s in spans for t in (s.start_ns, s.end_ns)})
    by_start = sorted(spans, key=lambda s: s.start_ns)
    out: dict = {}
    open_, i = [], 0
    for lo, hi in zip(cuts, cuts[1:]):
        while i < len(by_start) and by_start[i].start_ns <= lo:
            open_.append(by_start[i])
            i += 1
        open_ = [s for s in open_ if s.end_ns > lo]
        if open_:
            out.setdefault(short(open_[-1].name), []).append([lo, hi])
    return {k: trace_reduce.merge(v) for k, v in out.items()}


def bench_cover(bench, free) -> dict:
    """label -> merged intervals of `free` (merged) cut where a benchmark
    span begins or ends and named as `trace_reduce.reduce` names an idle
    gap: the shortest `bench:` span that holds it, or `between spans`."""
    cuts = sorted({t for _, a, b in bench for t in (a, b)})
    out: dict = {}
    for a, b in free:
        edges = [a] + cuts[bisect.bisect_right(cuts, a):
                            bisect.bisect_left(cuts, b)] + [b]
        for x, y in zip(edges, edges[1:]):
            out.setdefault(trace_reduce.label(bench, (x + y) / 2),
                           []).append([x, y])
    return {k: trace_reduce.merge(v) for k, v in out.items()}


def idle_by_span(ctx):
    """(idle seconds of the traced window, {name: idle seconds a chip},
    the span names below a statement's root) — or None without a trace,
    spans, or a common clock.  A program span's name holds the idle time
    in which it was the innermost span open; idle time under no program
    span goes to the benchmark span it fell in (`bench:stmt.q1`, ...,
    `between spans`), so the values add up to the window's idle seconds."""
    if ctx.trace is None or not any(ctx.trace["busy"]):
        return None
    spans = window_spans(ctx)
    trees = statement_trees(spans, ctx.rec.of("stmt", ok_only=False))
    off = clock_offset_ns(ctx.trace["spans"],
                          ctx.rec.of("stmt", ok_only=False))
    window = ctx.rec.of("trace_window", ok_only=False)
    if not trees or off is None or not window:
        return None
    lo, hi = (window[0][k] * 1e9 + off for k in ("t0", "t1"))
    n = len(ctx.trace["busy"])

    def idle_in(held):
        busy = sum(trace_reduce.total(trace_reduce.intersect(b, held))
                   for b in ctx.trace["busy"]) / n
        return (trace_reduce.total(held) - busy) / 1e9
    idle, program = {}, []
    for name, held in innermost_cover(spans).items():
        held = trace_reduce.clip([[a + off, b + off] for a, b in held],
                                 lo, hi)
        program += held
        idle[name] = idle_in(held)
    free, edge = [], lo
    for a, b in trace_reduce.merge(program) + [[hi, hi]]:
        if a > edge:
            free.append([edge, a])
        edge = max(edge, b)
    for name, held in bench_cover(ctx.trace["spans"], free).items():
        idle[name] = idle.get(name, 0.0) + idle_in(held)
    below = {short(s.name) for t in trees for s in t if s.name != ROOT}
    return ctx.trace["window_s"] - ctx.trace["busy_s"], idle, below


def idle_gaps(ctx):
    """The result line's `breakdown.idle_gaps`: [name, idle seconds a
    chip] by `idle_by_span`, longest first, names cut to 160 characters;
    where there are more than `TOP_GAPS` names the last entry holds the
    rest (`other names`), so the entries add up to the window's idle time.
    None where `idle_by_span` has nothing to read."""
    got = idle_by_span(ctx)
    if got is None:
        return None
    ranked = sorted(([k[:160], v] for k, v in got[1].items() if v > 0),
                    key=lambda kv: -kv[1])
    if len(ranked) > TOP_GAPS:
        ranked[TOP_GAPS - 1:] = [["other names",
                                  sum(v for _, v in ranked[TOP_GAPS - 1:])]]
    return ranked


def idle_attributed_pct(ctx):
    got = idle_by_span(ctx)
    if got is None or got[0] <= 0:
        return None
    idle_s, idle, below = got
    return sum(v for k, v in idle.items() if k in below) / idle_s * 100


# -- by hand -----------------------------------------------------------------
def table(ctx) -> str:
    """Per span name of the window: count, total ms, self ms (duration
    minus what its children cover) and, in a traced run, the device-idle
    ms that fell in it as the innermost span."""
    spans = window_spans(ctx)
    if not spans:
        return "no spans"
    kids = by_parent(spans)
    rows: dict = {}
    for s in spans:
        r = rows.setdefault(short(s.name), [0, 0.0, 0.0])
        r[0] += 1
        r[1] += (s.end_ns - s.start_ns) / 1e6
        r[2] += left_ns(s, kids.get(s.span_id, ())) / 1e6
    got = idle_by_span(ctx)
    idle = got[1] if got else {}
    lines = [f"{'span':28s} {'n':>5s} {'total ms':>11s} {'self ms':>11s} "
             f"{'idle ms':>9s}"]
    for name, (n, tot, self_) in sorted(rows.items(),
                                        key=lambda kv: -kv[1][1]):
        idle_ms = f"{idle[name] * 1e3:9.1f}" if name in idle else f"{'-':>9s}"
        lines.append(f"{name:28s} {n:5d} {tot:11.1f} {self_:11.1f} "
                     f"{idle_ms}")
    if got:
        inside = sum(v for k, v in idle.items() if k in rows)
        lines.append(f"device idle {got[0] * 1e3:.1f} ms of the window, "
                     f"{inside * 1e3:.1f} ms inside a span")
    return "\n".join(lines)
