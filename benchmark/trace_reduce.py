"""From the profiler's trace to numbers: device busy time as the union of
the intervals in which an operation ran, the idle share, the busy time
inside the benchmark's own spans, the time of every operation and of every
program by name, and the idle gaps by the span they fell in — each a chip,
over the chips the cell was given, whether or not all of them worked.

Works on plain data — planes as `{"name", "lines": [{"name", "events":
[(name, start_ns, duration_ns), ...]}]}` — so a test can hand it a few
events; `load_xplane` makes that from an `.xplane.pb` with nothing but
JAX.  The metrics of `BENCHMARK.json` attribute device time by the
benchmark's span; `ops_by_name` and `busy_by_program` are there for a
reader that asks for one operation (`all-reduce`) or one program
(`jit_scan_linked`).

    python -m benchmark.trace_reduce <file.xplane.pb>     # look at a trace
"""
from __future__ import annotations

import bisect
import glob
import os
import re
import sys

from .record import SPAN_PREFIX

WINDOW_SPAN = SPAN_PREFIX + "trace_window"
_DEVICE_PREFIXES = ("/device:TPU:", "/device:GPU:")
# the line of a device plane that holds one event per executed operation;
# the others ("XLA Modules", "Steps", ...) cover the same time again
OPS_LINE = "XLA Ops"
# one event per launched program, named `jit_<function>(<fingerprint>)`
MODULES_LINE = "XLA Modules"
_FINGERPRINT = re.compile(r"\(\d+\)$")


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def load_xplane(path: str) -> list:
    from jax.profiler import ProfileData
    planes = []
    for plane in ProfileData.from_file(path).planes:
        lines = [{"name": line.name,
                  "events": [(e.name, float(e.start_ns), float(e.duration_ns))
                             for e in line.events]}
                 for line in plane.lines]
        planes.append({"name": plane.name, "lines": lines})
    return planes


def is_device_plane(plane: dict) -> bool:
    return plane["name"].startswith(_DEVICE_PREFIXES)


def op_events(plane: dict) -> list:
    """A device plane's operation events: its `XLA Ops` line, or every
    line where the plane has no line of that name."""
    named = [l for l in plane["lines"] if l["name"] == OPS_LINE]
    lines = named or plane["lines"]
    return [e for l in lines for e in l["events"] if e[2] > 0]


def merge(intervals: list) -> list:
    """The union of [start, end) intervals as a sorted, disjoint list."""
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def total(intervals: list) -> float:
    return sum(b - a for a, b in intervals)


def clip(merged: list, lo: float, hi: float) -> list:
    return [[max(a, lo), min(b, hi)] for a, b in merged
            if min(b, hi) > max(a, lo)]


def intersect(merged_a: list, merged_b: list) -> list:
    out, i, j = [], 0, 0
    while i < len(merged_a) and j < len(merged_b):
        lo = max(merged_a[i][0], merged_b[j][0])
        hi = min(merged_a[i][1], merged_b[j][1])
        if hi > lo:
            out.append([lo, hi])
        if merged_a[i][1] < merged_b[j][1]:
            i += 1
        else:
            j += 1
    return out


def bench_spans(planes: list) -> list:
    """(name, start_ns, end_ns) of the benchmark's `TraceAnnotation`s, from
    the host planes."""
    return sorted(
        ((name, s, s + d) for p in planes if not is_device_plane(p)
         for l in p["lines"] for name, s, d in l["events"]
         if name.startswith(SPAN_PREFIX)), key=lambda x: x[1])


def label(spans: list, t: float) -> str:
    """The shortest benchmark span that holds time `t`."""
    best = None
    for name, a, b in spans:
        if a <= t < b and (best is None or b - a < best[1]):
            best = (name, b - a)
    return best[0] if best else "between spans"


def program_events(plane: dict) -> list:
    """A device plane's launches, one event a program run, the name
    without its fingerprint: its `XLA Modules` line."""
    return [(_FINGERPRINT.sub("", name), s, d) for l in plane["lines"]
            if l["name"] == MODULES_LINE for name, s, d in l["events"]
            if d > 0]


def reduce(planes: list, chips: int = 1, top: int = 10) -> dict:
    """The reduction every per-layer reader takes its numbers from.
    Seconds in the numbers, nanoseconds in the intervals.  `chips` is how
    many the cell was given: `busy_s`, the operations, the programs and
    the idle gaps are seconds a chip over all of them, and a chip whose
    plane ran nothing, or that has no plane, is idle for the whole window;
    `devices_busy` says how many ran anything.  More busy planes than
    chips is an error: the cell used a chip it did not ask for.  The
    window is the `bench:trace_window` span, or where the trace has none,
    from the first to the last event seen.  `spans` are the benchmark's
    spans inside the window, `busy` each chip's merged busy intervals;
    `busy_in_spans` and `span_count` read them.  `device_ops` are the
    `top` longest of `ops_by_name`, names cut for the result line."""
    spans = bench_spans(planes)
    working = [(p, ev) for p in planes if is_device_plane(p)
               for ev in [op_events(p)] if ev]
    if len(working) > chips:
        raise ValueError(f"{len(working)} device planes ran operations, "
                         f"the cell has {chips} chip(s)")
    devices = [ev for _, ev in working]
    window = next(((a, b) for name, a, b in spans if name == WINDOW_SPAN),
                  None)
    if window is None:
        starts = [s for ev in devices for _, s, _ in ev] + \
                 [a for _, a, _ in spans]
        ends = [s + d for ev in devices for _, s, d in ev] + \
               [b for _, _, b in spans]
        window = (min(starts), max(ends)) if starts else (0.0, 0.0)
    lo, hi = window
    inner = [(name, max(a, lo), min(b, hi)) for name, a, b in spans
             if name != WINDOW_SPAN and b > lo and a < hi]
    out = {"window_s": (hi - lo) / 1e9, "chips": chips,
           "devices_busy": len(devices), "busy_s": 0.0, "spans": inner,
           "busy": [], "device_ops": [], "idle_gaps": []}
    ops: dict = {}
    programs: dict = {}
    gaps: dict = {}

    def add(totals, events):
        for name, s, d in events:
            if s + d > lo and s < hi:
                totals[name] = totals.get(name, 0.0) + \
                    (min(s + d, hi) - max(s, lo)) / 1e9 / chips

    cuts = sorted({t for _, a, b in inner for t in (a, b)})
    for events in devices + [[]] * (chips - len(devices)):
        busy = clip(merge([[s, s + d] for _, s, d in events]), lo, hi)
        out["busy"].append(busy)
        out["busy_s"] += total(busy) / 1e9 / chips
        add(ops, events)
        edge = lo
        for a, b in busy + [[hi, hi]]:
            # an idle gap, cut where a span begins or ends inside it
            i = bisect.bisect_right(cuts, edge)
            while a > edge:
                end = cuts[i] if i < len(cuts) and cuts[i] < a else a
                name = label(inner, (edge + end) / 2)
                gaps[name] = gaps.get(name, 0.0) + \
                    (end - edge) / 1e9 / chips
                edge, i = end, i + 1
            edge = max(edge, b)
    for p, _ in working:
        add(programs, program_events(p))

    def rank(d):       # an operation's name is its whole HLO line: cut it
        return [[k[:160], v] for k, v in
                sorted(d.items(), key=lambda kv: -kv[1])[:top]]
    out["device_ops"], out["idle_gaps"] = rank(ops), rank(gaps)
    out["ops_by_name"], out["busy_by_program"] = ops, programs
    return out


def idle_pct(red):
    """The share of the traced window in which no operation ran, or
    nothing where the trace shows no device work."""
    if red is None or red["busy_s"] <= 0 or red["window_s"] <= 0:
        return None
    return (1 - red["busy_s"] / red["window_s"]) * 100


def span_count(red: dict, prefix: str) -> int:
    return sum(1 for name, _, _ in red["spans"] if name.startswith(prefix))


def busy_in_spans(red: dict, prefix: str) -> float:
    """Device busy seconds inside the union of the spans whose name starts
    with `prefix`, averaged over the cell's chips; overlapping spans (two
    clients) count their shared time once."""
    inside = merge([[a, b] for name, a, b in red["spans"]
                    if name.startswith(prefix)])
    if not red["busy"]:
        return 0.0
    return sum(total(intersect(busy, inside)) for busy in red["busy"]) \
        / 1e9 / len(red["busy"])


def summary(planes: list, top: int = 25) -> str:
    """What a trace holds, for reading by hand before code is written
    against it: planes, lines, event counts and the commonest names."""
    text = []
    for p in planes:
        text.append(f"PLANE {p['name']!r}")
        for l in p["lines"]:
            ev = l["events"]
            if not ev:
                continue
            names: dict = {}
            for name, _, d in ev:
                c = names.setdefault(name, [0, 0.0])
                c[0] += 1
                c[1] += d
            span = (min(s for _, s, _ in ev), max(s + d for _, s, d in ev))
            text.append(f"  LINE {l['name']!r}: {len(ev)} events, "
                        f"{span[0] / 1e9:.6f}..{span[1] / 1e9:.6f} s, "
                        f"union {total(merge([[s, s + d] for _, s, d in ev])) / 1e9:.6f} s")
            for name, (n, d) in sorted(names.items(),
                                       key=lambda kv: -kv[1][1])[:top]:
                text.append(f"    {d / 1e9:12.6f} s {n:8d} x {name[:100]}")
    return "\n".join(text)


if __name__ == "__main__":
    _path = sys.argv[1]
    if os.path.isdir(_path):
        _path = find_xplane(_path)
    _planes = load_xplane(_path)
    print(summary(_planes))
    # by hand nobody says how many chips the cell had: the planes there are
    _red = reduce(_planes, max(1, sum(map(is_device_plane, _planes))))
    for _key in ("busy_by_program", "ops_by_name"):
        print(f"{_key} (seconds a chip):")
        for _name, _s in sorted(_red[_key].items(), key=lambda kv: -kv[1]):
            print(f"  {_s:12.6f} s  {_name[:160]}")
    print({k: v for k, v in _red.items()
           if k not in ("spans", "busy", "ops_by_name", "busy_by_program")})
