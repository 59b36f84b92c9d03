"""Micro-batch group semantics: what a coalesced group MEANS.

scheduler.py owns queueing/admission/windows; this module owns the
three batch shapes and their correctness arguments:

- write group commit: same-(tablet, table, schema fence) plain writes
  merge into ONE WriteRequest — one Raft item (one WAL append), one
  tablet apply.  write_id preserves intra-batch order, so the merge is
  observationally the serial execution at one hybrid time; requests
  with external HTs or insert-if-absent ops never enter a group.
- point-read batch: same-(tablet, table) strong point gets share ONE
  leader/lease gate, ONE server-assigned read point (taken after every
  member arrived — each member reads at-or-above its own submit time)
  and ONE engine multi_get (the batched point-read seam YCSB-C
  saturates); per-member projection applied after.
- scan coalesce: same-signature scans execute ONCE — one batched
  kernel launch through the signature-keyed ops/scan.py cache — and
  every waiter receives the response.
"""
from __future__ import annotations

from typing import List

from ..utils import trace as _trace


class WriteItem:
    """A plain write queued for group commit.  ``tctx`` captures the
    submitter's trace context at construction (the RPC handler's
    context) — the worker task that dispatches the group runs in its
    own context, so the dispatch span bridges back explicitly."""

    __slots__ = ("peer", "req", "tctx")

    def __init__(self, peer, req):
        self.peer = peer
        self.req = req
        self.tctx = _trace.current_context()


class PointReadItem:
    """A strong point get queued for a batched multi_get.  `req_wire`
    is the wire dict (pk_eq set, no pushdown, no explicit read point —
    the tserver checks eligibility before routing here)."""

    __slots__ = ("peer", "req_wire", "tctx")

    def __init__(self, peer, req_wire):
        self.peer = peer
        self.req_wire = req_wire
        self.tctx = _trace.current_context()


class ScanItem:
    """A scan/aggregate read queued for signature coalescing; `run`
    executes it (once per GROUP)."""

    __slots__ = ("run", "tctx")

    def __init__(self, run):
        self.run = run
        self.tctx = _trace.current_context()


async def dispatch_write_group(items: List[tuple], fanin_hist) -> None:
    """GROUP COMMIT: merge the group's ops into one WriteRequest → one
    Raft item + one tablet apply.  Ops keep arrival order, so write_id
    order within the merged batch IS the members' serial order.  The
    merged request rides the peer's write queue, where same-sweep
    requests pack into ONE LogEntry batch, and — with
    ``fused_replicate_enabled`` — concurrent entries (other tables,
    txn ops) further fuse into one WAL append + one replicate round
    (the ReplicateBatch shape)."""
    from ..docdb.operations import WriteRequest
    first = items[0][0]
    ops = []
    for wb, _, _, _ in items:
        ops.extend(wb.req.ops)
    merged = WriteRequest(first.req.table_id, ops,
                          schema_version=first.req.schema_version)
    fanin_hist.increment(len(items))
    # dispatch span parents under the FIRST member's request (the
    # worker task has no ambient context of its own); fanin tags how
    # many requests shared this one WAL append + apply
    with _trace.use_context(first.tctx):
        with _trace.TRACES.span("sched.dispatch.write", child_only=True,
                                tags={"fanin": len(items)}):
            await first.peer.write(merged)
    for wb, fut, _, _ in items:
        if not fut.done():
            fut.set_result({"rows_affected": len(wb.req.ops)})


async def dispatch_point_read_group(items: List[tuple]) -> None:
    """Batched point gets: one gate + read point + safe-time wait +
    multi_get for the whole group; per-member wire responses built
    through the SAME response codec as the unbatched path (byte
    parity is pinned by tests/test_scheduler.py)."""
    from ..docdb.operations import ReadResponse
    from ..docdb.wire import read_response_to_wire
    first = items[0][0]
    table_id = first.req_wire["table_id"]
    pk_rows = [it[0].req_wire["pk_eq"] for it in items]
    with _trace.use_context(first.tctx):
        with _trace.TRACES.span("sched.dispatch.point_read",
                                child_only=True,
                                tags={"fanin": len(items)}):
            rows = await first.peer.read_points(table_id, pk_rows)
    for (pr, fut, _, _), row in zip(items, rows):
        cols = tuple(pr.req_wire.get("columns") or ())
        if row is not None and cols:
            row = {c: row.get(c) for c in cols}   # _project twin
        resp = ReadResponse(rows=[row] if row is not None else [],
                            backend="cpu")
        if not fut.done():
            fut.set_result(read_response_to_wire(resp))


async def dispatch_scan_group(items: List[tuple]) -> None:
    """Same-signature scans: ONE execution, response fanned out.  The
    read point resolves at dispatch — AFTER every member arrived — so
    coalescing never serves a member data older than its own arrival;
    explicit read points are part of the signature (identical
    snapshot only)."""
    sb = items[0][0]
    with _trace.use_context(sb.tctx):
        with _trace.TRACES.span("sched.dispatch.scan", child_only=True,
                                tags={"fanin": len(items)}):
            resp = await sb.run()
    for _, fut, _, _ in items:
        if not fut.done():
            # top-level copy per waiter: local short-circuit callers
            # must not see each other's mutations of the envelope
            fut.set_result(dict(resp))
