"""What a run records from the benchmark's own files: spans on the host's
clock around each call into the system (and, in a traced run, the same
span as a `TraceAnnotation` in the profiler's trace), the program's
counters read before and after the window, and the numbers compared for
`correct`, each held to the limit the configuration states."""
from __future__ import annotations

import contextlib
import statistics
import time

SPAN_PREFIX = "bench:"


class Recorder:
    def __init__(self, traced: bool):
        self.traced = traced
        self.spans: list = []        # {"kind", "t0", "t1", "ok", **attrs}
        self.counters: dict = {}     # name -> delta over the window
        self.window = (0.0, 0.0)     # perf_counter at start and end
        self.errors: list = []       # the first few, as text
        self.gc: dict = {}           # Python collections inside the window
        self.ssts_per_tablet = ([], [])   # at the window's start and end

    @contextlib.contextmanager
    def span(self, kind: str, label: str = None, **attrs):
        """Time one call into the system; `label` (default `kind`) names
        it in the trace.  A call that raises is recorded with `ok` false
        and the exception goes on to the driver."""
        rec = {"kind": kind, "ok": False, **attrs}
        with contextlib.ExitStack() as stack:
            if self.traced:
                import jax
                stack.enter_context(jax.profiler.TraceAnnotation(
                    SPAN_PREFIX + (label or kind)))
            rec["t0"] = time.perf_counter()
            try:
                yield rec
                rec["ok"] = True
            finally:
                rec["t1"] = time.perf_counter()
                self.spans.append(rec)

    def error(self, exc: BaseException) -> None:
        if len(self.errors) < 5:
            self.errors.append(f"{type(exc).__name__}: {exc}"[:500])

    def of(self, kind: str, ok_only: bool = True) -> list:
        return [s for s in self.spans
                if s["kind"] == kind and (s["ok"] or not ok_only)]

    def seconds(self, kind: str) -> list:
        return [s["t1"] - s["t0"] for s in self.of(kind)]

    def median_ms(self, kind: str):
        secs = self.seconds(kind)
        return statistics.median(secs) * 1e3 if secs else None

    def summary(self) -> dict:
        """kind -> [count, median ms, largest ms] of the spans that
        succeeded, for the window's earlier line."""
        out = {}
        for kind in sorted({s["kind"] for s in self.spans}):
            ms = sorted(1e3 * x for x in self.seconds(kind))
            if ms:
                out[kind] = [len(ms), round(statistics.median(ms), 1),
                             round(ms[-1], 1)]
        return out

    def slowest(self, n: int = 3) -> list:
        """The `n` longest spans inside the window: [label, ms, seconds
        into the window at which it began]."""
        inside = [s for s in self.spans if s["kind"] != "trace_window"
                  and self.window[0] <= s["t0"] <= self.window[1]]
        inside.sort(key=lambda s: s["t0"] - s["t1"])
        return [[s.get("query") or s["kind"],
                 round((s["t1"] - s["t0"]) * 1e3, 1),
                 round(s["t0"] - self.window[0], 3)] for s in inside[:n]]

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]


class GcWatch:
    """Python's own stop-the-world: collections, their seconds and the
    longest pause between `start` and `stop`, by `gc.callbacks`.  A
    full collection over a large heap is a stall every client sees."""

    def __init__(self):
        self.collections = self.full = 0
        self.seconds = self.longest_s = 0.0
        self._t = None

    def _on(self, phase, info):
        if phase == "start":
            self._t = time.perf_counter()
        elif self._t is not None:
            dt = time.perf_counter() - self._t
            self.collections += 1
            self.full += info["generation"] == 2
            self.seconds += dt
            self.longest_s = max(self.longest_s, dt)

    def __enter__(self):
        import gc
        gc.callbacks.append(self._on)
        return self

    def __exit__(self, *exc):
        import gc
        gc.callbacks.remove(self._on)

    def summary(self) -> dict:
        return {"collections": self.collections, "full": self.full,
                "seconds": self.seconds, "longest_s": self.longest_s}


class Checks:
    """The numbers compared for `correct`.  `note` keeps the worst reading
    of each; a number the configuration gives no limit for fails the run,
    as does a limit that nothing was compared against."""

    def __init__(self, limits: dict):
        self.limits = dict(limits)
        self.worst: dict = {}
        self.counts: dict = {}

    def note(self, name: str, value) -> None:
        v = float(value)
        if not v < 1e300:                # NaN or infinite: fails any limit
            v = 1e300
        self.worst[name] = max(self.worst.get(name, 0.0), v)
        self.counts[name] = self.counts.get(name, 0) + 1

    def note_all(self, gaps: dict) -> None:
        for k, v in gaps.items():
            self.note(k, v)

    def table(self) -> dict:
        """name -> {"value", "limit", "n", "ok"}, limits never compared
        included (value null)."""
        out = {}
        for name in sorted(set(self.worst) | set(self.limits)):
            value, limit = self.worst.get(name), self.limits.get(name)
            out[name] = {"value": value, "limit": limit,
                         "n": self.counts.get(name, 0),
                         "ok": (value is not None and limit is not None
                                and value <= limit)}
        return out

    def correct(self) -> bool:
        t = self.table()
        return bool(t) and all(e["ok"] for e in t.values())
