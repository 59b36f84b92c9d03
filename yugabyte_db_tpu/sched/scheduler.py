"""RequestScheduler: bounded lanes + dynamic micro-batching.

Sits between RPC dispatch (tserver/tablet_server.py) and tablet
execution.  Responsibilities:

1. ADMISSION: every lane has a depth bound and a memory-based soft
   limit.  Past either, the request is shed IMMEDIATELY with a typed
   SERVICE_UNAVAILABLE carrying retry_after_ms (estimated from the
   lane's backlog x EWMA service time) — overload turns into fast,
   client-visible pushback instead of unbounded queue growth and
   latency collapse (reference analog: rpc/service_pool.cc queue
   limits + "server is overloaded" responses).

2. MICRO-BATCHING: queued work coalesces into groups —
   - same-tablet plain writes merge into ONE WriteRequest: one Raft
     item (one WAL append) and one tablet apply for the whole group
     (group commit; reference: Log group commit, consensus/log.cc
     TaskStream — ours merges one level higher so the per-request
     docdb encode/apply overhead amortizes too);
   - same-signature scans execute ONCE and fan the response out to
     every waiter; the signature is exactly what keys the ops/scan.py
     jitted-kernel cache, so a coalesced group is one cached kernel
     launch instead of N.
   Groups accrete while queued (zero added latency when idle) plus an
   ADAPTIVE window when the worker dequeues them: if the lane's recent
   arrival rate suggests the batch would grow, the worker waits
   expected-fill-time, bounded by max_wait_us and max_batch.

3. FAIRNESS: lanes have independent worker pools, so maintenance work
   can never occupy the dispatch slots foreground point reads need.

Fault injection (utils/fault_injection.py): armed lane stalls hold a
lane's workers before dispatch; forced sheds make admission reject —
both let tests drive overload behavior deterministically.
"""
from __future__ import annotations

import asyncio
import time
from typing import Callable, Dict, List, Optional

from ..rpc.messenger import RECEIVED_AT, RpcError
from ..utils import fault_injection as fi
from ..utils import flags, metrics
from ..utils.tasks import drain_all
from ..utils.trace import TRACES, wait_status
from .batching import (PointReadItem, ScanItem, WriteItem,
                       dispatch_point_read_group, dispatch_scan_group,
                       dispatch_write_group)
from .lanes import (DEFAULT_CONFIGS, Lane, LaneConfig,
                    classify_read as classify_read_wire)


#: tags of a `sched.queue.<lane>` span that never queued (shared: the
#: span copies them)
_CUT_THROUGH = {"wait_ms": 0.0, "cut_through": True}


class OverloadError(RpcError):
    """Typed overload shed: SERVICE_UNAVAILABLE + retry_after_ms.
    Crosses the wire intact (rpc/messenger.py carries retry_after_ms in
    the error payload); client/client.py turns it into jittered
    exponential backoff."""

    def __init__(self, message: str, retry_after_ms: int):
        super().__init__(message, "SERVICE_UNAVAILABLE")
        self.retry_after_ms = max(1, int(retry_after_ms))


def canon(node):
    """Hashable canonical form of a wire payload (dicts key-sorted
    recursively) — the scan-coalescing signature.  Includes read_ht:
    requests with an explicit read point only coalesce with the SAME
    read point (identical snapshot)."""
    if isinstance(node, dict):
        return tuple((k, canon(v)) for k, v in sorted(node.items()))
    if isinstance(node, (list, tuple)):
        return tuple(canon(v) for v in node)
    return node


class _Ewma:
    __slots__ = ("value", "alpha")

    def __init__(self, alpha: float = 0.2, initial: float = 0.0):
        self.value = initial
        self.alpha = alpha

    def update(self, x: float) -> float:
        self.value = (x if self.value == 0.0
                      else self.value + self.alpha * (x - self.value))
        return self.value


class _Group:
    """One schedulable unit: 1..max_batch requests sharing a dispatch.
    `items` are (payload, future, cost_bytes, enqueue_t) tuples.  The
    lane queue carries the GROUP OBJECT (not its key): a key may be
    re-queued for a fresh group once this one fills, and the two must
    dispatch independently."""

    __slots__ = ("key", "items", "started", "taken")

    def __init__(self, key):
        self.key = key
        self.items: List[tuple] = []
        self.started = False
        self.taken: Optional[float] = None   # monotonic, at dequeue


class _LaneState:
    def __init__(self, owner: str, lane: Lane, cfg: LaneConfig):
        self.lane = lane
        self.cfg = cfg
        self.queue: asyncio.Queue = asyncio.Queue()
        self.groups: Dict[object, _Group] = {}
        self.inflight = 0
        self.queued = 0
        self.queued_bytes = 0
        self.service_ms = _Ewma(initial=1.0)
        self.arrival_interval_s = _Ewma()
        self.last_arrival: Optional[float] = None
        ent = metrics.REGISTRY.entity("sched", f"{owner}:{lane.value}",
                                      server=owner, lane=lane.value)
        self.m_admitted = ent.counter("sched_admitted")
        self.m_shed = ent.counter("sched_shed")
        self.m_depth = ent.gauge("sched_queue_depth")
        self.m_wait = ent.histogram("sched_wait_us")
        self.m_batch = ent.histogram("sched_batch_size")
        self.m_occupancy = ent.histogram("sched_window_occupancy_pct")
        self.m_fanin = ent.histogram("sched_group_commit_fanin")
        # groups dispatched per fused worker wakeup (>1 = cross-tablet
        # fusion actually collapsed loop sweeps)
        self.m_fused_wakeup = ent.histogram("sched_fused_groups_per_wakeup")

    @property
    def depth(self) -> int:
        return self.queued + self.inflight

    def note_arrival(self) -> None:
        # frame-arrival stamp (rpc.messenger.RECEIVED_AT) when this is
        # an RPC task: a burst of frames read in one sweep must measure
        # as near-zero inter-arrival even though their handler tasks
        # run serially behind synchronous work
        t = RECEIVED_AT.get() or time.monotonic()
        if self.last_arrival is not None:
            self.arrival_interval_s.update(max(0.0, t - self.last_arrival))
        self.last_arrival = t

    def retry_after_ms(self) -> int:
        """Backlog drained at the lane's EWMA service rate: how long
        until a retry has a fair shot at admission."""
        per_slot = self.service_ms.value or 1.0
        slots = max(1, self.cfg.workers or 8)
        return int(min(2000.0, max(1.0, self.depth * per_slot / slots)))

    def adaptive_window_s(self, have: int) -> float:
        """Expected time for the group to FILL (recent arrival rate x
        remaining slots), clamped by max_wait — batches grow only when
        traffic is actually arriving; an idle lane never waits.  A
        singleton group earns no window either: one fast SEQUENTIAL
        caller produces the same small inter-arrival EWMA as a
        concurrent fleet, but its next request cannot arrive while it
        is blocked on this one — sleeping would be pure added latency.
        A second member already in the group is the proof of actual
        concurrency."""
        if have < 2 or have >= self.cfg.max_batch \
                or self.cfg.max_wait_us <= 0:
            return 0.0
        iv = self.arrival_interval_s.value
        max_wait = self.cfg.max_wait_us / 1e6
        if iv <= 0.0 or iv > max_wait:
            return 0.0
        return min(iv * (self.cfg.max_batch - have), max_wait)

    def busy(self) -> bool:
        """Arrival-rate gate for the cut-through fast path.  The
        execution engine is largely SYNCHRONOUS on the event loop, so
        an inline dispatch gives concurrent arrivals no await-window in
        which to coalesce — under a fast arrival stream everything
        would degrade to singleton batches.  When requests arrive
        faster than the lane completes them (inter-arrival below the
        EWMA service time — utilization > 1, queueing is inevitable)
        or faster than the floor threshold, they take the queue+worker
        path instead: all arrivals buffered in the same loop sweep then
        join one group before a worker task runs (this deferral IS the
        dynamic part of the micro-batch window)."""
        iv = self.arrival_interval_s.value
        threshold = max(
            flags.get("sched_cut_through_min_interval_us") / 1e6,
            self.service_ms.value / 1e3)
        return 0.0 < iv < threshold


class RequestScheduler:
    """One per tserver. `submit*` either dispatches (through a lane's
    worker pool, possibly batched), sheds with OverloadError, or — when
    the `scheduler_enabled` flag is off — falls straight through to the
    handler (today's direct-dispatch path)."""

    def __init__(self, owner: str,
                 configs: Optional[Dict[Lane, LaneConfig]] = None):
        self.owner = owner
        cfgs = {lane: LaneConfig(**vars(cfg))
                for lane, cfg in DEFAULT_CONFIGS.items()}
        # runtime-flag overrides (tests/ops tune without code changes)
        for lane in Lane:
            cfgs[lane].max_depth = int(flags.get(f"sched_{lane.value}_depth"))
        cfgs[Lane.POINT_READ].max_batch = \
            int(flags.get("sched_read_max_batch"))
        cfgs[Lane.POINT_READ].max_wait_us = \
            int(flags.get("sched_read_max_wait_us"))
        cfgs[Lane.POINT_WRITE].max_batch = \
            int(flags.get("sched_write_max_batch"))
        cfgs[Lane.POINT_WRITE].max_wait_us = \
            int(flags.get("sched_write_max_wait_us"))
        cfgs[Lane.SCAN].max_batch = int(flags.get("sched_scan_max_batch"))
        cfgs[Lane.SCAN].max_wait_us = \
            int(flags.get("sched_scan_max_wait_us"))
        if configs:
            cfgs.update(configs)
        self.lanes: Dict[Lane, _LaneState] = {
            lane: _LaneState(owner, lane, cfg)
            for lane, cfg in cfgs.items()}
        self._workers: List[asyncio.Task] = []
        self._started = False
        self._closed = False

    # --- lifecycle --------------------------------------------------------
    def _ensure_workers(self) -> None:
        if self._started or self._closed:
            return
        self._started = True
        for st in self.lanes.values():
            for i in range(st.cfg.workers or 0):
                self._workers.append(asyncio.create_task(
                    self._worker(st), name=f"sched-{st.lane.value}-{i}"))

    async def shutdown(self) -> None:
        self._closed = True
        # drain_all re-cancels until each worker is really done: a
        # dispatch completing in the cancel's tick can swallow the
        # CancelledError (bpo-37658) and a bare `await t` then hangs
        await drain_all(self._workers)
        self._workers.clear()
        # fail anything still queued so callers don't hang on shutdown
        for st in self.lanes.values():
            pending = list(st.groups.values())
            st.groups.clear()
            while not st.queue.empty():
                pending.append(st.queue.get_nowait())
            for g in pending:
                for _, fut, _, _ in g.items:
                    if not fut.done():
                        fut.set_exception(RpcError("scheduler shut down",
                                                   "SHUTDOWN_IN_PROGRESS"))

    # --- admission --------------------------------------------------------
    def _admit(self, st: _LaneState, cost_bytes: int) -> None:
        if fi.lane_shed_forced(st.lane.value):
            st.m_shed.increment()
            raise OverloadError(
                f"{st.lane.value} lane shedding (fault injection)",
                st.retry_after_ms())
        if st.depth >= st.cfg.max_depth:
            st.m_shed.increment()
            raise OverloadError(
                f"{st.lane.value} lane over depth "
                f"({st.depth}/{st.cfg.max_depth})", st.retry_after_ms())
        if st.queued_bytes + cost_bytes > st.cfg.soft_bytes:
            st.m_shed.increment()
            raise OverloadError(
                f"{st.lane.value} lane over memory soft limit "
                f"({st.queued_bytes >> 20}MB)", st.retry_after_ms())
        st.m_admitted.increment()
        st.note_arrival()

    @staticmethod
    def enabled() -> bool:
        return bool(flags.get("scheduler_enabled"))

    # --- generic (admission-only / unbatched) submission ------------------
    async def submit(self, lane: Lane, run: Callable, *,
                     cost_bytes: int = 1024):
        """Run `run()` under the lane's admission + (for pooled lanes)
        its worker queue.  `run` is an async callable of no args."""
        if not self.enabled():
            return await run()
        if self._closed:
            raise RpcError("scheduler shut down", "SHUTDOWN_IN_PROGRESS")
        st = self.lanes[lane]
        self._admit(st, cost_bytes)
        if st.cfg.workers is None or (
                st.queued == 0 and st.inflight < st.cfg.workers
                and not st.busy() and not fi.lane_armed(st.lane.value)):
            # admission-only lane (TXN class — queueing txn control
            # behind txn control can deadlock), or cut-through on an
            # idle pooled lane: dispatch immediately
            st.inflight += 1
            t0 = time.monotonic()
            try:
                with TRACES.span(f"sched.queue.{st.lane.value}",
                                 child_only=True, tags=_CUT_THROUGH):
                    return await run()
            finally:
                st.inflight -= 1
                st.service_ms.update((time.monotonic() - t0) * 1e3)
        self._ensure_workers()
        fut = asyncio.get_running_loop().create_future()
        g = _Group(key=object())      # unique key: no batching
        now = time.monotonic()
        g.items.append((run, fut, cost_bytes, now))
        st.queued += 1
        st.queued_bytes += cost_bytes
        st.m_depth.set(st.depth)
        st.queue.put_nowait(g)
        return await self._await_queued(
            st, g, fut, now, {"depth": st.depth, "cut_through": False})

    async def _await_queued(self, st: _LaneState, g: _Group, fut, t_in,
                            tags: dict, payload=None):
        """Park one queued request on its future under its
        `sched.queue.<lane>` span.  The span runs from admission to the
        result, service included; `wait_ms` is the part before a worker
        dequeued the group (what `sched_wait_us` counts).  The
        worker-side `sched.dispatch.*` span (the shared execution) is
        this span's child for the group's first member."""
        with TRACES.span(f"sched.queue.{st.lane.value}", child_only=True,
                         tags=tags) as sp:
            if sp.sampled and payload is not None:
                payload.tctx = sp.context
            try:
                with wait_status("SchedQueue_Wait", component="sched"):
                    return await fut
            finally:
                if g.taken is not None:
                    sp.set_tag("wait_ms", (g.taken - t_in) * 1e3)

    # --- batched submission ----------------------------------------------
    async def submit_grouped(self, lane: Lane, key, payload, *,
                             cost_bytes: int = 1024):
        """Queue `payload` under `key`; payloads sharing a key while
        queued dispatch as ONE group (the lane's executor receives the
        whole group).  Returns this payload's share of the result.

        CUT-THROUGH fast path: when the lane is idle (nothing queued,
        spare worker-equivalent slots) the request dispatches INLINE as
        a singleton group — no queue hop, no future park, zero added
        latency.  Batches form exactly when there is contention to
        amortize (arrivals while work is in flight land in the queue
        and coalesce)."""
        if self._closed:
            raise RpcError("scheduler shut down", "SHUTDOWN_IN_PROGRESS")
        st = self.lanes[lane]
        self._admit(st, cost_bytes)
        now = time.monotonic()
        if st.queued == 0 and st.inflight < (st.cfg.workers or 1) \
                and not st.busy() and not fi.lane_armed(st.lane.value):
            st.inflight += 1
            st.m_batch.increment(1)
            st.m_occupancy.increment(100.0 / max(1, st.cfg.max_batch))
            fut = asyncio.get_running_loop().create_future()
            try:
                with TRACES.span(f"sched.queue.{st.lane.value}",
                                 child_only=True, tags=_CUT_THROUGH) as sp:
                    if sp.sampled:
                        payload.tctx = sp.context
                    await self._dispatch_group(
                        st, [(payload, fut, cost_bytes, now)])
                st.service_ms.update((time.monotonic() - now) * 1e3)
                return fut.result()
            finally:
                st.inflight -= 1
        self._ensure_workers()
        fut = asyncio.get_running_loop().create_future()
        g = st.groups.get(key)
        if g is None or g.started or len(g.items) >= st.cfg.max_batch:
            g = _Group(key)
            st.groups[key] = g
            st.queue.put_nowait(g)
        g.items.append((payload, fut, cost_bytes, now))
        st.queued += 1
        st.queued_bytes += cost_bytes
        return await self._await_queued(
            st, g, fut, now,
            {"depth": st.depth, "group_members": len(g.items),
             "cut_through": False}, payload)

    # --- worker loop ------------------------------------------------------
    async def _worker(self, st: _LaneState):
        while True:
            g = await st.queue.get()
            # adaptive micro-batch window: wait only when arrivals are
            # coming fast enough to grow the group, never past max_wait
            # — and never when the lane already has backlog beyond this
            # group (work is waiting NOW; a sleep would cost a whole
            # event-loop sweep and starve it, batches grow via the
            # queue anyway under load)
            try:
                w = (0.0 if st.queued > len(g.items)
                     else st.adaptive_window_s(len(g.items)))
                if w > 0.0:
                    await asyncio.sleep(w)
            except asyncio.CancelledError:
                # cancelled mid-window: the group is off the queue (and
                # may have been replaced under its key once full), so
                # shutdown()'s pending sweep cannot see it — fail its
                # members here or their RPC handlers hang to timeout
                g.started = True
                if st.groups.get(g.key) is g:
                    del st.groups[g.key]
                for _, fut, _, _ in g.items:
                    if not fut.done():
                        fut.set_exception(RpcError(
                            "scheduler shut down", "SHUTDOWN_IN_PROGRESS"))
                raise
            batch = [self._take_group(st, g)]
            # cross-tablet batch fusion: every group already READY in
            # the queue rides THIS wakeup (bounded) and dispatches
            # concurrently below — N same-table groups on different
            # tablets cost one loop sweep + one accounting pass
            # instead of N worker wakeups, and a coalesced device
            # scan's kernel execution overlaps the next group's batch
            # formation (the StreamPipeline stages release the GIL)
            # batched lanes only (max_batch > 1): an admission-
            # serialized lane like MAINTENANCE runs workers=1 exactly
            # so compactions/index builds never overlap — fusing its
            # queue would gather N of them concurrently and break the
            # isolation the lane exists for
            if flags.get("sched_cross_tablet_fusion") \
                    and st.cfg.max_batch > 1:
                cap = int(flags.get("sched_fusion_max_groups"))
                while len(batch) <= cap:
                    try:
                        g2 = st.queue.get_nowait()
                    except asyncio.QueueEmpty:
                        break
                    batch.append(self._take_group(st, g2))
            st.m_fused_wakeup.increment(len(batch))
            if len(batch) == 1:
                await self._run_group(st, batch[0])
            else:
                await asyncio.gather(
                    *[self._run_group(st, items) for items in batch])

    def _take_group(self, st: _LaneState, g: _Group) -> List[tuple]:
        """Synchronous dequeue bookkeeping for one group (no awaits —
        admission must never observe a group as both queued and
        inflight, or neither)."""
        g.started = True
        if st.groups.get(g.key) is g:
            del st.groups[g.key]
        items = g.items
        n = len(items)
        st.queued -= n
        st.queued_bytes -= sum(it[2] for it in items)
        st.inflight += n
        now = g.taken = time.monotonic()
        for _, _, _, t_in in items:
            st.m_wait.increment((now - t_in) * 1e6)
        st.m_batch.increment(n)
        st.m_occupancy.increment(100.0 * n / max(1, st.cfg.max_batch))
        return items

    async def _run_group(self, st: _LaneState, items: List[tuple]):
        # armed lane stall (fault injection): hold the dispatch —
        # admission keeps running, so tests can fill the queue and
        # observe typed sheds + foreground/background isolation
        try:
            await fi.lane_stall_wait(st.lane.value)
            t0 = time.monotonic()
            await self._dispatch_group(st, items)
            st.service_ms.update((time.monotonic() - t0) * 1e3)
        except asyncio.CancelledError:
            for _, fut, _, _ in items:
                if not fut.done():
                    fut.set_exception(RpcError(
                        "scheduler shut down", "SHUTDOWN_IN_PROGRESS"))
            raise
        except Exception as e:  # noqa: BLE001 — fan the error out
            for _, fut, _, _ in items:
                if not fut.done():
                    fut.set_exception(e)
        finally:
            st.inflight -= len(items)
            st.m_depth.set(st.depth)

    async def _dispatch_group(self, st: _LaneState, items: List[tuple]):
        first = items[0][0]
        if isinstance(first, WriteItem):
            await dispatch_write_group(items, st.m_fanin)
            return
        if isinstance(first, PointReadItem):
            st.m_fanin.increment(len(items))
            await dispatch_point_read_group(items)
            return
        if isinstance(first, ScanItem):
            await dispatch_scan_group(items)
            return
        # generic callable payloads (always singleton groups)
        for payload, fut, _, _ in items:
            res = await payload()
            if not fut.done():
                fut.set_result(res)

    # --- edge admission (messenger overload_probe) ------------------------
    def overload_probe(self, service: str, method: str, payload):
        """Pre-dispatch gate the tserver installs on its messenger: a
        request headed for a lane that is ALREADY past its depth bound
        is shed at the frame edge — no task spawn, no handler — so
        pushback costs a fraction of a served call.  Conservative by
        design: anything it cannot cheaply classify falls through to
        the full admission check in the handler."""
        if service != "tserver" or not self.enabled():
            return None
        try:
            if method == "read":
                lane = classify_read_wire(payload["req"])
            elif method == "write":
                lane = Lane.POINT_WRITE
            elif method == "txn_write":
                lane = Lane.TXN
            else:
                return None
        except (KeyError, TypeError):
            return None
        st = self.lanes[lane]
        if st.depth >= st.cfg.max_depth \
                or fi.lane_shed_forced(st.lane.value):
            st.m_shed.increment()
            return st.retry_after_ms()
        return None

    # --- introspection ----------------------------------------------------
    def stats(self) -> dict:
        """Per-lane live stats for /scheduler and the dashboard."""
        out = {}
        for lane, st in self.lanes.items():
            out[lane.value] = {
                "depth": st.depth,
                "queued": st.queued,
                "inflight": st.inflight,
                "queued_bytes": st.queued_bytes,
                "admitted": st.m_admitted.value(),
                "shed": st.m_shed.value(),
                "service_ms_ewma": round(st.service_ms.value, 3),
                "retry_after_ms": st.retry_after_ms(),
                "wait_us": {
                    "count": st.m_wait.count(),
                    "p50": st.m_wait.percentile(50),
                    "p99": st.m_wait.percentile(99)},
                "batch_size": {
                    "count": st.m_batch.count(),
                    "mean": round(st.m_batch.mean(), 2),
                    "p50": st.m_batch.percentile(50),
                    "max": st.m_batch._max},
                "window_occupancy_pct": {
                    "mean": round(st.m_occupancy.mean(), 1)},
                "group_commit_fanin": {
                    "count": st.m_fanin.count(),
                    "mean": round(st.m_fanin.mean(), 2),
                    "max": st.m_fanin._max},
                "fused_groups_per_wakeup": {
                    "count": st.m_fused_wakeup.count(),
                    "mean": round(st.m_fused_wakeup.mean(), 2),
                    "max": st.m_fused_wakeup._max},
            }
        return out
