"""Distributed request tracing + ASH (Active Session History).

Reference: per-request Trace objects appended via TRACE() macros and
dumped on slow requests or /rpcz (src/yb/util/trace.h:88-113); ASH
cross-component wait-state annotation via SET_WAIT_STATUS /
SCOPED_WAIT_STATUS (src/yb/ash/wait_state.h:35-66) with a background
sampler feeding a history buffer.

This module is the system-wide observability layer (ISSUE 14):

- SPANS: every ``Trace`` is a span in a distributed trace — it carries
  ``(trace_id, span_id, parent_id, sampled)``.  ``TRACES.span(name)``
  opens a child of the ambient context (one ``contextvars`` read);
  roots are sampled at ``trace_sampling_rate`` so the layer stays
  cheap by default.  The RPC layer injects/extracts the 3-tuple wire
  form ``[trace_id, span_id, sampled]`` on every frame
  (rpc/messenger.py), which is how one user write becomes one
  cross-process span tree (client -> leader append/fsync -> follower
  append -> apply -> flush handoff).
- ONE CLOCK: a span stamps ``time.perf_counter_ns()`` at start and
  end (integers; ``start_unix`` stays for cross-process dumps).  While
  the JAX profiler is collecting, every root is sampled whatever the
  rate says and every sampled span is also entered as a
  ``jax.profiler.TraceAnnotation("ybtpu:" + name, ...)``, so a
  captured profile shows the program's spans on the device's own
  timeline.  ``TRACES.finished(since_ns, until_ns)`` hands the
  finished spans of an interval to in-process readers (the benchmark's
  per-layer metrics); ``TRACES.evicted`` counts what the ring dropped.
- EXECUTOR HOPS: a ``contextvars`` context does NOT survive
  ``run_in_executor`` / ``ThreadPoolExecutor.submit``.  Callers bridge
  explicitly: capture ``current_context()`` before the hop and wrap
  the thread-side body in ``use_context(ctx)`` (the flush executor,
  bypass sessions and compaction jobs all do).
- ASH: ``wait_status(state)`` scopes publish into a process-global
  active-wait table that a background sampler thread
  (``ASH.start()``; ``ash_sample_interval_ms``) snapshots — so a
  sampler can see a WAL fsync or a frozen-memtable backpressure stall
  in SOME OTHER thread, which the old contextvar-only read never
  could.  States come from the canonical ``WAIT_STATES`` table; free
  text raises here and is rejected statically by the
  ``trace_discipline`` analysis pass.
- tracez(): the pid+timestamp-stamped cross-process dump served by the
  ``rpc_tracez`` RPCs and stitched by cluster/collector.py.
"""
from __future__ import annotations

import contextvars
import itertools
import os
import random
import sys
import threading
import time
from collections import deque
from contextlib import contextmanager, nullcontext
from dataclasses import dataclass, field
from typing import Deque, Dict, List, NamedTuple, Optional

_current_trace: contextvars.ContextVar = contextvars.ContextVar(
    "ybtpu_trace", default=None)


class SpanContext(NamedTuple):
    """The propagated identity of a span: what crosses RPC frames and
    executor hops.  ``sampled=False`` propagates as a no-op — children
    allocate nothing and record nothing."""

    trace_id: int
    span_id: int
    sampled: bool


#: name prefix of a span's mirror in the profiler's trace
MIRROR_PREFIX = "ybtpu:"

#: the ambient context after an UNSAMPLED root decision: children see
#: "a trace exists and it is off" instead of re-rolling the sampler.
_UNSAMPLED_CTX = SpanContext(0, 0, False)

_rng = random.Random(os.urandom(8))


def _new_id() -> int:
    return _rng.getrandbits(63) or 1


def _profiler_annotation():
    """``jax.profiler.TraceAnnotation`` while a JAX profiler session is
    collecting, else None (``is_enabled`` is a static call that is False
    outside a session).  This module never loads jax: while nothing in
    the process has imported it there is no session either."""
    jax = sys.modules.get("jax")
    if jax is None:
        return None
    ann = jax.profiler.TraceAnnotation
    return ann if ann.is_enabled() else None


def _flag(name: str, default):
    from . import flags
    try:
        return flags.get(name)
    except KeyError:        # flag module not initialized (unit tests)
        return default


@dataclass
class Trace:
    """One span.  Kept under its historical name: the pre-span
    ``Trace`` API (``add``/``finish``/``dump``) is a strict subset."""

    name: str
    trace_id: int = 0
    span_id: int = 0
    parent_id: int = 0
    sampled: bool = True
    start_ns: int = field(default_factory=time.perf_counter_ns)
    start_unix: float = field(default_factory=time.time)
    events: List[tuple] = field(default_factory=list)
    tags: Dict[str, object] = field(default_factory=dict)
    end_ns: Optional[int] = None
    dropped_events: int = 0

    #: per-span event cap: a chatty span (a tight loop calling TRACE)
    #: must stay O(1) memory and O(cap) to dump — past the cap events
    #: are counted, not stored (the count lands in the dump tail)
    MAX_EVENTS = 512

    def add(self, message: str) -> None:
        # stamp fast path: seconds relative to `start_ns`, and
        # never throws — a late event (a thread racing finish(), or a
        # registry dump mid-append) degrades to a dropped event, not an
        # exception on the hot path it instruments
        try:
            if len(self.events) < self.MAX_EVENTS:
                self.events.append(
                    ((time.perf_counter_ns() - self.start_ns) / 1e9,
                     message))
            else:
                self.dropped_events += 1
        except Exception:   # noqa: BLE001 — observability must not throw
            pass

    def set_tag(self, key: str, value) -> None:
        try:
            self.tags[key] = value
        except Exception:   # noqa: BLE001 — observability must not throw
            pass

    @property
    def context(self) -> SpanContext:
        return SpanContext(self.trace_id, self.span_id, self.sampled)

    def count(self, key: str) -> None:
        """Add one to a counting tag (retries, restarts)."""
        self.set_tag(key, self.tags.get(key, 0) + 1)

    def finish(self) -> float:
        self.end_ns = time.perf_counter_ns()
        return (self.end_ns - self.start_ns) / 1e9

    def duration_s(self) -> float:
        return ((self.end_ns or time.perf_counter_ns())
                - self.start_ns) / 1e9

    def dump(self, events: Optional[list] = None) -> str:
        evs = list(self.events) if events is None else events
        out = [f"trace {self.name} ({self.duration_s():.6f}s)"]
        for dt, msg in evs:
            out.append(f"  {dt*1000:8.3f}ms  {msg}")
        if self.dropped_events:
            out.append(f"  ... {self.dropped_events} events dropped "
                       f"(cap {self.MAX_EVENTS})")
        return "\n".join(out)

    def to_dict(self) -> dict:
        """Wire/JSON form for tracez dumps (events capped so one chatty
        span cannot bloat a cross-process dump)."""
        evs = list(self.events)[:256]
        return {
            "trace_id": self.trace_id, "span_id": self.span_id,
            "parent_id": self.parent_id, "name": self.name,
            "start_unix": self.start_unix,
            "duration_ms": round(self.duration_s() * 1e3, 3),
            "finished": self.end_ns is not None,
            "tags": dict(self.tags),
            "events": [[round(dt * 1e3, 3), str(m)] for dt, m in evs],
        }


class _NoopSpan:
    """Shared span stand-in when the trace is unsampled: every method
    is a no-op, so callers never branch."""

    __slots__ = ()
    sampled = False
    trace_id = span_id = parent_id = 0
    name = ""

    def add(self, message: str) -> None:
        pass

    def set_tag(self, key: str, value) -> None:
        pass

    def count(self, key: str) -> None:
        pass

    def finish(self) -> float:
        return 0.0

    @property
    def context(self) -> SpanContext:
        return _UNSAMPLED_CTX


_NOOP = _NoopSpan()
_NO_WAIT = nullcontext()


class SpanRecord(NamedTuple):
    """One finished span as ``TRACES.finished`` hands it out: plain
    data on the ``perf_counter_ns`` clock."""

    name: str
    trace_id: int
    span_id: int
    parent_id: int
    start_ns: int
    end_ns: int
    tags: dict


class TraceRegistry:
    """Keeps recent finished spans for /rpcz, rpc_tracez and the
    in-process readers of ``finished``."""

    def __init__(self, keep: int = 32768, slow_threshold_s: float = 0.5):
        self.recent: Deque[Trace] = deque(maxlen=keep)
        self.active: Dict[int, Trace] = {}
        self.slow_threshold_s = slow_threshold_s
        #: finished spans the ring dropped to make room: a reader that
        #: needs every span of an interval checks this did not move
        self.evicted = 0
        self._lock = threading.Lock()
        self._next = 0

    def _ensure_keep(self) -> None:
        keep = int(_flag("tracez_keep", self.recent.maxlen or 32768))
        if keep > 0 and keep != self.recent.maxlen:
            with self._lock:
                self.evicted += max(0, len(self.recent) - keep)
                self.recent = deque(self.recent, maxlen=keep)

    def _append(self, t: Trace) -> None:
        """Put a finished span in the ring (the caller holds the lock)."""
        if len(self.recent) == self.recent.maxlen:
            self.evicted += 1
        self.recent.append(t)

    @contextmanager
    def span(self, name: str, parent="inherit", tags: Optional[dict] = None,
             child_only: bool = False, force: bool = False,
             cpu: bool = False):
        """Open a span.

        - parent="inherit" (default): child of the ambient context;
          parent=None: a root whatever is ambient (background loops
          whose task inherited some request's context).
        - No parent context: a ROOT, sampled at
          ``trace_sampling_rate`` — and always while the JAX profiler
          is collecting (``force=True`` records regardless —
          the legacy ``trace()`` API and test harnesses use it;
          ``child_only=True`` refuses to root at all — for seams like
          raft broadcasts that are only meaningful inside a request).
        - Unsampled context: yields a shared no-op span.
        - ``cpu=True``: a sampled span also reads the opening thread's
          CPU clock (``time.thread_time_ns``) at open and close and
          sets tag ``cpu_ms``; its wall time minus ``cpu_ms`` is the
          time that thread stood off the CPU — waiting for the
          interpreter's lock, or blocked in native code.  Meaningful
          only for a span that neither yields to an event loop nor is
          closed on another thread: the clock is one thread's.  Where
          that clock advances by scheduler ticks, one span reads 0 or a
          tick, and only a sum over many spans estimates the CPU time.

        A sampled span is mirrored into the profiler's trace while a
        session collects (``ybtpu:<name>``, same start and end).
        """
        cur = _current_trace.get() if parent == "inherit" else parent
        pctx = cur.context if isinstance(cur, Trace) else cur
        if pctx is not None and not pctx.sampled and not force:
            yield _NOOP
            return
        if pctx is None and not force:
            if child_only:
                yield _NOOP
                return
            rate = float(_flag("trace_sampling_rate", 0.0))
            if (rate <= 0.0 or _rng.random() >= rate) \
                    and _profiler_annotation() is None:
                token = _current_trace.set(_UNSAMPLED_CTX)
                try:
                    yield _NOOP
                finally:
                    _current_trace.reset(token)
                return
        inherit = pctx is not None and pctx.sampled
        t = Trace(name,
                  trace_id=pctx.trace_id if inherit else _new_id(),
                  parent_id=pctx.span_id if inherit else 0,
                  span_id=_new_id())
        cpu0 = time.thread_time_ns() if cpu else 0
        if tags:
            t.tags.update(tags)
        with self._lock:
            tid = self._next
            self._next += 1
            self.active[tid] = t
        token = _current_trace.set(t)
        mirror = _profiler_annotation()
        if mirror is not None:
            mirror = mirror(MIRROR_PREFIX + name, trace_id=t.trace_id,
                            span_id=t.span_id, parent_id=t.parent_id)
            mirror.__enter__()
        try:
            yield t
        finally:
            if mirror is not None:
                mirror.__exit__(None, None, None)
            _current_trace.reset(token)
            self._ensure_keep()
            with self._lock:
                # stamped last, under the lock: a span holds its own
                # bookkeeping, which on a thread that contends for the
                # interpreter's lock can hold a wait for it
                if cpu:
                    t.tags["cpu_ms"] = (time.thread_time_ns() - cpu0) / 1e6
                t.finish()
                self.active.pop(tid, None)
                self._append(t)

    def record(self, name: str, start_ns: int, end_ns: int, parent,
               tags: Optional[dict] = None) -> None:
        """Keep a finished span with the given ``perf_counter_ns``
        stamps, a child of ``parent`` (a span or a ``SpanContext``): for
        a stretch whose start is only known once it is over, such as a
        finished launch waiting for its event loop.  Nothing where
        ``parent`` is None or unsampled.  Not mirrored into the
        profiler's trace, which takes only spans opened as they run."""
        pctx = parent.context if isinstance(parent, Trace) else parent
        if pctx is None or not pctx.sampled:
            return
        t = Trace(name, trace_id=pctx.trace_id, parent_id=pctx.span_id,
                  span_id=_new_id(), start_ns=start_ns,
                  start_unix=time.time() - (time.perf_counter_ns()
                                            - start_ns) / 1e9,
                  end_ns=end_ns)
        if tags:
            t.tags.update(tags)
        self._ensure_keep()
        with self._lock:
            self._append(t)

    def finished(self, since_ns: int = 0,
                 until_ns: Optional[int] = None) -> List[SpanRecord]:
        """The finished spans still in the ring that BEGAN in
        ``[since_ns, until_ns]`` (``perf_counter_ns``), oldest first."""
        with self._lock:
            spans = list(self.recent)
        return [SpanRecord(t.name, t.trace_id, t.span_id, t.parent_id,
                           t.start_ns, t.end_ns, dict(t.tags))
                for t in spans
                if t.start_ns >= since_ns
                and (until_ns is None or t.start_ns <= until_ns)]

    @contextmanager
    def trace(self, name: str):
        """Legacy always-recorded trace (now: a force-sampled span —
        a child when a sampled context is ambient, a root otherwise)."""
        with self.span(name, force=True) as t:
            yield t

    def rpcz(self) -> dict:
        # event lists snapshot UNDER the registry lock: handler threads
        # append to active traces while we dump (the PR-14 race fix —
        # the old path iterated live lists outside any lock)
        with self._lock:
            act = [(t, list(t.events)) for t in self.active.values()]
            rec = [(t, list(t.events)) for t in self.recent
                   if t.end_ns and t.duration_s() > self.slow_threshold_s]
        return {
            "active": [t.dump(evs) for t, evs in act],
            "recent_slow": [t.dump(evs) for t, evs in rec],
        }

    def tracez(self) -> dict:
        """Cross-process span dump: pid+timestamp stamped so a
        harness-side collector can order dumps from many processes
        (cluster/collector.py stitches them into span trees)."""
        self._ensure_keep()
        with self._lock:
            spans = [t.to_dict() for t in self.recent]
            active = [t.to_dict() for t in self.active.values()]
        return {"pid": os.getpid(), "ts": time.time(),
                "spans": spans, "active": active,
                "ash": ASH.summary()}


TRACES = TraceRegistry()


def TRACE(message: str) -> None:
    t = _current_trace.get()
    if isinstance(t, Trace):
        t.add(message)


def current_span():
    """The ambient span when this process opened it and it records;
    the shared no-op span otherwise (tags set on it go nowhere)."""
    t = _current_trace.get()
    return t if isinstance(t, Trace) else _NOOP


def current_context() -> Optional[SpanContext]:
    """The ambient span context (for explicit capture across executor
    hops, scheduler queues and fused-append groups)."""
    cur = _current_trace.get()
    if cur is None:
        return None
    return cur.context if isinstance(cur, Trace) else cur


def inject() -> Optional[list]:
    """Wire form of the ambient context: ``[trace_id, span_id,
    sampled]`` (what every RPC frame carries), or None when no trace
    has been started at all."""
    ctx = current_context()
    if ctx is None:
        return None
    return [ctx.trace_id, ctx.span_id, 1 if ctx.sampled else 0]


def extract(wire) -> Optional[SpanContext]:
    """Parse the wire 3-tuple back into a SpanContext (None/garbage ->
    no context: the frame predates tracing or carries nothing)."""
    try:
        if not wire:
            return None
        tid, sid, sampled = wire[0], wire[1], wire[2]
        if not sampled:
            return _UNSAMPLED_CTX
        return SpanContext(int(tid), int(sid), True)
    except Exception:   # noqa: BLE001 — a bad frame must not kill RPC
        return None


@contextmanager
def use_context(ctx: Optional[SpanContext]):
    """Re-establish a captured context on the far side of an executor
    or thread hop (contextvars do NOT survive ``run_in_executor``)."""
    if ctx is None:
        yield
        return
    token = _current_trace.set(ctx)
    try:
        yield
    finally:
        _current_trace.reset(token)


def sampled() -> bool:
    """Whether a span opened here would record: work done only for a
    span's tags is skipped where it would not."""
    ctx = current_context()
    return ctx is not None and ctx.sampled


@contextmanager
def device_span(kind: str, signature=None, compiled: bool = False,
                bucket=None, rows=None, mvcc=None, tags=()):
    """Per-kernel-launch telemetry: a span tagged {signature,
    compile|cache_hit, bucket, rows}, on a scan the MVCC mode it was
    served with (`mvcc`), and the (key, value) pairs of `tags` — which
    the caller works out before the span opens, so that counting them
    is not timed as the launch — so a compile landing inside a
    measured round is VISIBLE in the trace instead of inferred from
    compile counters.  It times the DISPATCH — jit cache lookup,
    argument flattening, host-value placement, enqueue — and the
    compile when ``codepath=compile`` (published to ASH as
    ``Device_Compile``); JAX returns before the device finishes, so the
    device's own time is the ``device.wait`` span around the first host
    read of the result.  ``cpu_ms`` is the dispatching thread's CPU
    time inside it (`TraceRegistry.span`).  One contextvar read when no
    sampled trace is ambient — safe on the hot path."""
    with (wait_status("Device_Compile", component="device")
          if compiled else _NO_WAIT):
        # a sampled trace is ambient as the span itself, or — on the
        # far side of an executor hop (`use_context`: a served read's
        # launch runs beside the event loop) — as its context
        if not sampled():
            yield None
            return
        sig = (f"{hash(signature) & 0xFFFFFFFFFFFFFFFF:016x}"
               if signature is not None else None)
        span_tags = {"signature": sig,
                     "codepath": "compile" if compiled else "cache_hit",
                     "bucket": bucket, "rows": rows}
        if mvcc is not None:
            span_tags["mvcc"] = mvcc
        span_tags.update(tags)
        with TRACES.span(f"device.{kind}", child_only=True,
                         tags=span_tags, cpu=True) as sp:
            yield sp


# --- ASH ------------------------------------------------------------------

#: Canonical wait-state table — the ONLY strings ``wait_status()``
#: accepts.  The ``trace_discipline`` analysis pass statically rejects
#: any call-site literal outside this set (no free-text drift), and the
#: runtime check below makes a missed site fail loudly in tests.
WAIT_STATES = frozenset({
    "Idle",
    # on-CPU request classes (the not-blocked buckets)
    "OnCpu_Read",
    "OnCpu_WriteApply",
    # durability boundaries
    "WAL_Fsync",
    "Catalog_Fsync",
    # consensus
    "Raft_Replicate",
    "Raft_ApplyWait",
    # MVCC / leadership waits
    "SafeTime_Wait",
    "LeaderLease_Wait",
    # storage / flush executor
    "Flush_MemtableBackpressure",
    "Flush_SstWrite",
    "Compaction_Run",
    # device kernels
    "Device_BlockUntilReady",
    "Device_Compile",
    # scheduler
    "SchedQueue_Wait",
    # analytics bypass
    "Bypass_Scan",
    # generic lock contention
    "Lock_Wait",
})

_wait_state: contextvars.ContextVar = contextvars.ContextVar(
    "ybtpu_wait_state", default="Idle")

#: process-global active-wait table: key -> (component, state).
#: Writers are lock-free (GIL-atomic dict set/pop); the sampler thread
#: snapshots it, retrying the rare resize race.
_ACTIVE_WAITS: Dict[int, tuple] = {}
_wait_seq = itertools.count()


@contextmanager
def wait_status(state: str, component: str = ""):
    """SCOPED_WAIT_STATUS analog.  Publishes into the process-global
    active-wait table so the ASH sampler THREAD can attribute blocked
    time in any thread/task, not just its own context."""
    if state not in WAIT_STATES:
        raise ValueError(
            f"wait state {state!r} is not in the canonical "
            f"trace.WAIT_STATES table (trace_discipline)")
    token = _wait_state.set(state)
    key = next(_wait_seq)
    _ACTIVE_WAITS[key] = (component, state)
    try:
        yield
    finally:
        _ACTIVE_WAITS.pop(key, None)
        _wait_state.reset(token)


def current_wait_state() -> str:
    return _wait_state.get()


class AshSampler:
    """Periodic sampler of wait states into a bounded history ring.

    ``sample_once`` snapshots the process-global active-wait table plus
    every registered provider; ``start()`` runs it on a background
    daemon thread every ``ash_sample_interval_ms``.  A crashing
    provider is swallowed (it must never kill the sampler — regression
    pinned in tests/test_observability.py)."""

    def __init__(self, keep: int = 10_000):
        self.samples: Deque[tuple] = deque(maxlen=keep)
        self._registered: List = []   # callables returning (name, state)
        self._lock = threading.Lock()
        self._thread: Optional[threading.Thread] = None
        self._stop = threading.Event()
        self.samples_taken = 0
        # monotonic per-state tallies: unlike the sliding-window
        # histogram these DIFF cleanly across round boundaries, which
        # is what the bench's p99 attribution needs
        self._cum: Dict[str, int] = {}

    def register(self, provider) -> None:
        with self._lock:
            self._registered.append(provider)

    def unregister(self, provider) -> None:
        """Remove a provider (server shutdown must not leave closures
        over dead servers reporting forever on the process-global
        sampler)."""
        with self._lock:
            try:
                self._registered.remove(provider)
            except ValueError:
                pass

    def sample_once(self) -> None:
        now = time.time()
        waits: List[tuple] = []
        for _ in range(4):
            try:
                waits = list(_ACTIVE_WAITS.values())
                break
            except RuntimeError:   # resized mid-iteration: retry
                continue
        seen_states = set()
        for comp, state in waits:
            if state != "Idle":
                self._record(now, comp or "wait", state)
                seen_states.add(state)
        with self._lock:
            providers = list(self._registered)
        for p in providers:
            try:
                name, state = p()
            except Exception:   # noqa: BLE001 — a crashing provider
                continue        # must never kill the sampler
            # providers are COARSE fallbacks: a state already sampled
            # from a wait_status scope this tick (session-weighted,
            # the better signal) is not double-counted by a component
            # saying the same thing
            if state != "Idle" and state not in seen_states:
                self._record(now, name, state)
        self.samples_taken += 1

    def _record(self, now: float, name: str, state: str) -> None:
        self.samples.append((now, name, state))
        self._cum[state] = self._cum.get(state, 0) + 1

    def start(self, interval_ms: Optional[float] = None) -> None:
        """Run the sampler on a daemon thread (idempotent).  Without
        an explicit ``interval_ms`` the ``ash_sample_interval_ms``
        flag is re-read every tick — it is a RUNTIME flag, so a hot
        update through rpc_set_flag takes effect immediately."""
        if self._thread is not None and self._thread.is_alive():
            return
        self._stop = threading.Event()
        stop = self._stop

        def loop():
            while True:
                iv = (interval_ms if interval_ms is not None
                      else _flag("ash_sample_interval_ms", 50))
                if stop.wait(max(1.0, float(iv)) / 1000.0):
                    return
                self.sample_once()

        self._thread = threading.Thread(target=loop, name="ash-sampler",
                                        daemon=True)
        self._thread.start()

    def stop(self) -> None:
        if self._thread is not None:
            self._stop.set()
            self._thread.join(1.0)
            self._thread = None

    def histogram(self, last_s: float = 60.0) -> Dict[str, int]:
        cutoff = time.time() - last_s
        out: Dict[str, int] = {}
        for ts, _name, state in list(self.samples):
            if ts >= cutoff:
                out[state] = out.get(state, 0) + 1
        return out

    def summary(self, last_s: float = 60.0) -> dict:
        """JSON-able image for rpc_tracez: windowed histogram,
        monotonic per-state tallies, per-component split."""
        cutoff = time.time() - last_s
        by_state: Dict[str, int] = {}
        by_comp: Dict[str, Dict[str, int]] = {}
        for ts, name, state in list(self.samples):
            if ts < cutoff:
                continue
            by_state[state] = by_state.get(state, 0) + 1
            d = by_comp.setdefault(name, {})
            d[state] = d.get(state, 0) + 1
        return {"wait_states": by_state,
                "by_component": by_comp,
                "cumulative": dict(self._cum),
                "samples_taken": self.samples_taken}


ASH = AshSampler()
