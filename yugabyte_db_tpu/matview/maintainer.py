"""The per-view maintainer: seed once, fold the change stream forever.

Lifecycle (the xCluster resync alignment, applied to aggregates):

1. **Seed** — create a CDC slot with ``start_from="now"`` (records the
   per-tablet log tails), drive the VirtualWal until it establishes a
   watermark R, then run ONE grouped scan at ``read_ht=R``. Everything
   committed at or below R is in the seed; the stream delivers
   everything above it — the filter ``commit_ht <= seed_ht`` is what
   makes the handoff exact (cdc/consumer.py resync precedent).
2. **Fold** — each round drains the VirtualWal's ready transactions in
   commit order. Inserts combine through the shared
   ``ops.scan.combine_grouped_partials``; deletes/updates retract
   through ``ops.scan.retract_grouped_partials`` after recovering the
   before-image with an MVCC point read at ``commit_ht - 1`` (CDC
   delete records carry only the PK — time travel IS the before-image
   store, bounded by the cluster's history retention like any stale
   read). Adds apply before retracts so an in-place update that raises
   an extremum never triggers a spurious re-scan. A round is atomic:
   draining pops txns from the VirtualWal, so a mid-round failure
   rolls the staged fold back and re-attaches the slot at its durable
   restart positions — the batch replays whole, never half-applies.
3. **Repair** — retraction marks MIN/MAX slots dirty when the removed
   value challenged the survivor; those groups re-aggregate with one
   bounded per-group scan at the round's watermark (every folded txn
   is ≤ it, so the re-scan is consistent by construction). More dirty
   groups than ``matview_rescan_budget`` is a typed event: count it,
   tag the reason, answer with one full re-seed.
4. **Persist** — fold state (partials + applied LSN + watermark)
   writes to the master catalog BEFORE ``confirm_flush``: a crash
   between the two replays txns the applied-LSN filter drops —
   exactly-once without a second log.
"""
import asyncio
import time
from typing import Dict, List, Optional

from ..cdc.virtual_wal import SlotInvalidError, VirtualWal, _lsn_le
from ..docdb.operations import ReadRequest
from ..docdb.wire import read_request_to_wire, read_response_from_wire
from ..dockv.packed_row import ColumnType
from ..ops.grouped_scan import DictGroupSpec
from ..ops.scan import (AggSpec, HashGroupSpec, _keyed_partials,
                        _mm2, _scalar_of, combine_grouped_partials,
                        retract_grouped_partials)
from ..utils import flags
from ..utils.tasks import cancel_and_drain
from .definition import (ViewDef, bind_expr, group_eq_where,
                         key_normalizers)
from .errors import (REASON_RESCAN_BUDGET, REASON_SLOT_INVALID,
                     MatviewError, RescanBudgetExceeded)
from .expr import eval_expr, passes

kLogicalBits = 12


def _now_micros() -> int:
    return int(time.time() * 1_000_000)


def _fresh_counters() -> dict:
    return {"seeds": 0, "seed_route": None, "txns_applied": 0,
            "rows_added": 0, "rows_retracted": 0,
            "before_image_reads": 0, "minmax_rescans": 0,
            "budget_exceeded": 0, "full_rescans": 0, "truncates": 0,
            "loop_errors": 0, "loop_refusals": 0,
            "last_fallback_reason": None}


class ViewMaintainer:
    """One registered view's fold state + stream consumer."""

    def __init__(self, client, viewdef: ViewDef, schema):
        self.client = client
        self.viewdef = viewdef
        self.schema = schema
        self.pk_names = [c.name for c in schema.key_columns]
        self.keyfns = key_normalizers(viewdef, schema)
        self.group_cids = [schema.column_by_name(n).id
                           for n in viewdef.group_by]
        self.bound_where = bind_expr(viewdef.where, schema)
        self.bound_aggs = tuple(
            AggSpec(op, bind_expr(e, schema) if e is not None else None)
            for op, e, _ in viewdef.aggs)
        # group key tuple -> [agg scalar list, row count]
        self.state: Dict[tuple, list] = {}
        # set when a round failed after draining the VirtualWal: its
        # in-memory buffers are past txns we never applied, so the next
        # round must re-attach from the slot's durable positions first
        self._stream_dirty = False
        self.seed_ht = 0
        self.watermark_ht = 0
        self.applied_lsn: Optional[list] = None
        self.counters = _fresh_counters()
        self.vw: Optional[VirtualWal] = None
        self._task: Optional[asyncio.Task] = None
        self._round_lock = asyncio.Lock()

    # --- seed / attach ----------------------------------------------------
    async def seed(self) -> None:
        """Create the slot, pin the read point, run the one seed scan,
        persist the registered state."""
        self.vw = await VirtualWal.create(
            self.client, [self.viewdef.table], start_from="now")
        await self._seed_current_slot(first=True)

    async def _seed_current_slot(self, first: bool) -> None:
        pre_lsn = None
        wm = 0
        for _ in range(600):
            for r in await self.vw.get_consistent_changes():
                if r["op"] == "COMMIT":
                    pre_lsn = r["lsn"]
            wm = self.vw._watermark()
            if wm > 0:
                break
            await asyncio.sleep(0.02)
        if wm <= 0:
            raise MatviewError(
                f"matview {self.viewdef.name}: no CDC watermark "
                f"(are the table's leaders up?)")
        self.seed_ht = wm
        self.watermark_ht = wm
        self.applied_lsn = pre_lsn
        await self._seed_scan(wm)
        self.counters["seeds"] += 1
        if not first:
            self.counters["full_rescans"] += 1
        await self._persist(create=first)
        if pre_lsn is not None:
            await self.vw.confirm_flush(pre_lsn)

    async def _seed_scan(self, read_ht: int) -> None:
        gspec = self._group_spec()
        if gspec is not None:
            resp = await self.client.scan_bypass(
                self.viewdef.table,
                ReadRequest("", where=self.bound_where,
                            aggregates=self.bound_aggs,
                            group_by=gspec, read_ht=read_ht))
            self.state = self._norm_keys(_keyed_partials(
                (resp.agg_values, resp.group_counts,
                 resp.group_values)))
            used = getattr(self.client, "last_bypass", {}).get("used")
            self.counters["seed_route"] = \
                "bypass" if used else "grouped_scan"
        else:
            # mixed int/string group keys: no single device group
            # spec — one paged row scan folds host-side through the
            # same accumulation the stream uses (typed, counted route)
            resp = await self.client.scan(
                self.viewdef.table,
                ReadRequest("", where=self.bound_where,
                            read_ht=read_ht))
            self.state = _keyed_partials(
                self._rows_to_triple(resp.rows))
            self.counters["seed_route"] = "row_scan"

    def _group_spec(self):
        types = [self.schema.column_by_name(n).type
                 for n in self.viewdef.group_by]
        if all(t == ColumnType.STRING for t in types):
            return DictGroupSpec(
                cols=tuple(self.group_cids),
                max_slots=int(flags.get("grouped_max_slots")))
        if all(t in (ColumnType.INT32, ColumnType.INT64,
                     ColumnType.TIMESTAMP, ColumnType.BOOL)
               for t in types):
            return HashGroupSpec(cols=tuple(self.group_cids))
        return None

    async def attach(self, ent: dict) -> None:
        """Resume from a persisted catalog entry: partials + applied
        LSN + watermark restore verbatim; the slot re-attaches at its
        held-back restart positions — no re-seed."""
        st = ent.get("state") or {}
        self.state = {
            tuple(k): [list(vals), int(cnt)]
            for k, vals, cnt in st.get("partials", ())}
        self.seed_ht = st.get("seed_ht", 0)
        self.watermark_ht = st.get("watermark_ht", 0)
        self.applied_lsn = st.get("applied_lsn")
        self.counters = {**_fresh_counters(), **st.get("counters", {})}
        self.vw = await VirtualWal.attach(self.client, ent["slot_id"])

    # --- the fold loop ----------------------------------------------------
    def start(self) -> None:
        if self._task is None:
            self._task = asyncio.create_task(self._loop())

    async def stop(self) -> None:
        t, self._task = self._task, None
        # re-cancel until the task actually ends: an in-flight RPC
        # completing in the same tick as the cancel can swallow the
        # CancelledError inside wait_for (bpo-37658), leaving the loop
        # alive — cancel_and_drain is the shared spelling of the guard
        await cancel_and_drain(t)

    async def _loop(self) -> None:
        while True:
            try:
                n = await self.round()
            except asyncio.CancelledError:
                raise
            except MatviewError as e:
                # typed refusal out of the reseed path (no CDC
                # watermark while leaders move / catch-up stall):
                # retry next round, but counted APART from bugs so a
                # wedged stream is visible as refusals, not errors
                self.counters["loop_refusals"] += 1
                self.counters["last_fallback_reason"] = str(e)
                n = 0
            except Exception:
                # transient (leader moves, master failover): the round
                # rolled its staged fold back and flagged the stream
                # dirty, so the next round re-attaches the slot at its
                # durable positions and replays the same batch
                self.counters["loop_errors"] += 1
                n = 0
            await asyncio.sleep(
                0 if n else float(flags.get("matview_poll_ms")) / 1000.0)

    async def round(self) -> int:
        """One fold round; returns the number of stream records
        consumed. Serialized — the background loop and read-path
        catch-ups share the lock."""
        async with self._round_lock:
            try:
                return await self._round_inner()
            except SlotInvalidError:
                # WAL GC outran the restart position (maintainer lag
                # past retention): typed full-re-seed fallback
                self.counters["last_fallback_reason"] = \
                    REASON_SLOT_INVALID
                await self._reseed()
                return 1
            except RescanBudgetExceeded:
                self.counters["budget_exceeded"] += 1
                self.counters["last_fallback_reason"] = \
                    REASON_RESCAN_BUDGET
                await self._reseed()
                return 1

    async def _reseed(self) -> None:
        old = self.vw
        snap = (self.state, self.seed_ht, self.watermark_ht,
                self.applied_lsn, dict(self.counters))
        new = await VirtualWal.create(
            self.client, [self.viewdef.table], start_from="now")
        self.vw = new
        try:
            await self._seed_current_slot(first=False)
        except BaseException:
            try:
                ent = await self.client.get_matview(self.viewdef.name)
            except Exception:
                ent = None
            if ent is not None and ent.get("slot_id") == new.slot_id:
                # the catalog rebound before the failure (the persist
                # landed, confirm_flush did not): the seed is durable —
                # keep it; the unconfirmed tail replays LSN-filtered
                self._stream_dirty = False
                if old is not None:
                    try:
                        await old.drop()
                    except Exception:
                        pass
            else:
                # the seed never reached the catalog: roll the fold
                # state back whole and reclaim the slot nothing
                # references (it would hold back WAL GC forever)
                (self.state, self.seed_ht, self.watermark_ht,
                 self.applied_lsn, self.counters) = snap
                self.vw = old
                try:
                    await new.drop()
                except Exception:
                    pass
            raise
        self._stream_dirty = False
        if old is not None:
            try:
                await old.drop()
            except Exception:
                pass                   # the catalog entry rebound already

    async def _drop_unreferenced(self, vw: VirtualWal) -> None:
        """Best-effort drop of a slot UNLESS the catalog references it
        (then it is not a leak — the entry owns it)."""
        try:
            ent = await self.client.get_matview(self.viewdef.name)
            if ent is None or ent.get("slot_id") != vw.slot_id:
                await vw.drop()
        except Exception:
            pass

    async def _recover_stream(self) -> None:
        """Re-attach the VirtualWal at the slot's DURABLE restart
        positions. confirm_flush holds those below every record of
        every unconfirmed txn, so a batch a failed round drained (and
        never confirmed) replays in full; the applied-LSN filter keeps
        the replay exactly-once."""
        self.vw = await VirtualWal.attach(self.client, self.vw.slot_id)
        self._stream_dirty = False

    async def _round_inner(self) -> int:
        if self._stream_dirty:
            await self._recover_stream()
        recs = await self.vw.get_consistent_changes()
        wm = self.vw._watermark()
        if not recs:
            if wm > 0:
                self.watermark_ht = max(self.watermark_ht, wm)
            return 0
        txns: List[dict] = []
        cur: Optional[dict] = None
        for r in recs:
            if r["op"] == "BEGIN":
                cur = {"ht": r["commit_ht"], "ops": [], "lsn": None}
            elif r["op"] == "COMMIT":
                cur["lsn"] = r["lsn"]
                txns.append(cur)
                cur = None
            else:
                cur["ops"].append(r)
        # Stage the fold: get_consistent_changes POPPED these txns from
        # the VirtualWal's buffers, so an in-process retry after a
        # mid-round failure (leader move during a before-image read, a
        # rescan RPC dying) would silently lose them. The batch applies
        # whole — state, counters, watermark and applied LSN move
        # together — or not at all: on failure the snapshot restores
        # and the stream is flagged for re-attach from the slot's
        # durable restart positions, which re-deliver the entire batch.
        snap_state = {k: [list(vals), cnt]
                      for k, (vals, cnt) in self.state.items()}
        snap_counters = dict(self.counters)
        last_lsn = None
        try:
            dirty_keys: set = set()
            for t in txns:
                last_lsn = t["lsn"]
                if t["ht"] <= self.seed_ht:
                    continue           # already inside the seed scan
                if self.applied_lsn is not None \
                        and _lsn_le(t["lsn"], self.applied_lsn):
                    continue           # replay of an applied txn
                dirty_keys |= await self._apply_txn(t)
                self.counters["txns_applied"] += 1
            if dirty_keys:
                await self._rescan_groups(dirty_keys,
                                          max(wm, self.seed_ht))
        except BaseException:
            self.state = snap_state
            self.counters = snap_counters
            self._stream_dirty = True
            # the typed fallbacks in round() re-seed on top of this;
            # the rollback matters there too — a re-seed that itself
            # fails mid-flight must leave a consistent view behind
            raise
        if wm > 0:
            self.watermark_ht = max(self.watermark_ht, wm)
        if last_lsn is not None:
            self.applied_lsn = last_lsn
            await self._persist()
            await self.vw.confirm_flush(last_lsn)
        return len(recs)

    async def _apply_txn(self, txn: dict) -> set:
        adds: List[dict] = []
        retracts: List[dict] = []
        per_pk: Dict[tuple, List[dict]] = {}
        for o in txn["ops"]:
            if o.get("table") != self.viewdef.table:
                continue
            if o["op"] == "TRUNCATE":
                self.state = {}
                self.counters["truncates"] += 1
                per_pk.clear()
                adds.clear()
                retracts.clear()
                continue
            row = o["row"]
            pk = tuple(row[n] for n in self.pk_names)
            per_pk.setdefault(pk, []).append(o)
        for pk, ops in per_pk.items():
            pk_row = dict(zip(self.pk_names, pk))
            old = await self._get_at(pk_row, txn["ht"] - 1)
            self.counters["before_image_reads"] += 1
            img = dict(old) if old is not None else None
            for o in ops:
                if o["op"] == "delete":
                    img = None
                else:
                    img = {**(img or {}), **o["row"]}
            if old is not None and passes(self.viewdef.where, old):
                retracts.append(old)
            if img is not None and passes(self.viewdef.where, img):
                adds.append(img)
        dirty: set = set()
        # adds first: an update that RAISES a group's extremum then
        # retracts the old value below it needs no re-scan at all
        if adds:
            self.state = _keyed_partials(combine_grouped_partials(
                self.bound_aggs,
                [self._to_triple(), self._rows_to_triple(adds)]))
            self.counters["rows_added"] += len(adds)
        if retracts:
            triple, dirty_slots = retract_grouped_partials(
                self.bound_aggs, self._to_triple(),
                self._rows_to_triple(retracts))
            self.state = _keyed_partials(triple)
            self.counters["rows_retracted"] += len(retracts)
            dirty = {key for key, _ in dirty_slots}
        return dirty

    async def _rescan_groups(self, keys: set, read_ht: int) -> None:
        todo = [k for k in keys if k in self.state]
        budget = int(flags.get("matview_rescan_budget"))
        if len(todo) > budget:
            raise RescanBudgetExceeded(len(todo), budget)
        aggs = self.bound_aggs + (AggSpec("count"),)
        for key in todo:
            resp = await self.client.scan(
                self.viewdef.table,
                ReadRequest("",
                            where=group_eq_where(
                                self.bound_where, self.group_cids, key),
                            aggregates=aggs, read_ht=read_ht))
            self.counters["minmax_rescans"] += 1
            cnt = int(_scalar_of(resp.agg_values[-1]))
            if cnt <= 0:
                self.state.pop(key, None)
            else:
                self.state[key] = [
                    [_scalar_of(v) for v in resp.agg_values[:-1]], cnt]

    # --- host accumulation (the numpy-twin contract over rows) ------------
    def _rows_to_triple(self, rows: List[dict]):
        import numpy as np
        acc: Dict[tuple, list] = {}
        for row in rows:
            key = tuple(fn(row.get(n)) for fn, n in
                        zip(self.keyfns, self.viewdef.group_by))
            st = acc.get(key)
            if st is None:
                st = acc[key] = [
                    [0 if op in ("sum", "count") else None
                     for op, _, _ in self.viewdef.aggs], 0]
            st[1] += 1
            for i, (op, e, _) in enumerate(self.viewdef.aggs):
                v = None if e is None else eval_expr(e, row)
                if op == "count":
                    st[0][i] += 1 if (e is None or v is not None) else 0
                elif op == "sum":
                    if v is not None:
                        st[0][i] += int(v)
                else:
                    st[0][i] = _mm2(st[0][i],
                                    None if v is None else int(v), op)
        keys = list(acc)
        outs = tuple(np.asarray([acc[k][0][i] for k in keys])
                     for i in range(len(self.viewdef.aggs)))
        counts = np.asarray([acc[k][1] for k in keys], np.int64)
        gvals = tuple(np.asarray([k[j] for k in keys])
                      for j in range(len(self.viewdef.group_by)))
        return outs, counts, gvals

    def _to_triple(self):
        import numpy as np
        keys = list(self.state)
        outs = tuple(np.asarray([self.state[k][0][i] for k in keys])
                     for i in range(len(self.viewdef.aggs)))
        counts = np.asarray([self.state[k][1] for k in keys], np.int64)
        gvals = tuple(np.asarray([k[j] for k in keys])
                      for j in range(len(self.viewdef.group_by)))
        return outs, counts, gvals

    def _norm_keys(self, keyed: Dict[tuple, list]) -> Dict[tuple, list]:
        return {tuple(fn(v) for fn, v in zip(self.keyfns, k)): st
                for k, st in keyed.items()}

    # --- MVCC before-image point read --------------------------------------
    async def _get_at(self, pk_row: dict, read_ht: int):
        c = self.client

        async def go(ct):
            loc = c._tablet_for_key(ct, pk_row)
            req = ReadRequest(ct.info.table_id, pk_eq=pk_row,
                              read_ht=read_ht)
            payload = {"tablet_id": loc.tablet_id,
                       "req": read_request_to_wire(req)}
            resp = read_response_from_wire(await c._call_leader(
                ct, loc.tablet_id, "read", payload))
            return resp.rows[0] if resp.rows else None
        return await c._retry_on_split(self.viewdef.table, go)

    # --- reads -------------------------------------------------------------
    def rows(self) -> List[dict]:
        out = []
        for key, (vals, _cnt) in self.state.items():
            row: dict = {}
            for gname, v in zip(self.viewdef.group_by, key):
                row[gname] = v
                for alias in self.viewdef.group_out.get(gname, ()):
                    row[alias] = v
            for (op, _e, out_name), v in zip(self.viewdef.aggs, vals):
                v = _scalar_of(v)
                row[out_name] = int(v) if v is not None else None
            out.append(row)
        return out

    def staleness_ms(self) -> float:
        """Wall-clock lag of the applied watermark, CLIENT-clock
        relative: this host's clock minus the physical component of
        the tserver-assigned watermark, so client/tserver skew shifts
        the number one-for-one (see matview_max_staleness_ms)."""
        if self.watermark_ht <= 0:
            return float("inf")
        return max(0.0, (_now_micros()
                         - (self.watermark_ht >> kLogicalBits)) / 1000.0)

    async def catch_up(self) -> None:
        """Drive fold rounds until the applied watermark passes the
        wall clock at call time — the bounded-staleness read path."""
        target = _now_micros()
        for _ in range(400):
            await self.round()
            if (self.watermark_ht >> kLogicalBits) >= target:
                return
            await asyncio.sleep(0.01)
        raise MatviewError(
            f"matview {self.viewdef.name}: catch-up stalled")

    # --- persistence --------------------------------------------------------
    @staticmethod
    def _plain(v):
        sv = _scalar_of(v)
        return None if sv is None else int(sv)

    def _state_wire(self) -> dict:
        return {
            "partials": [[list(k), [self._plain(v) for v in vals],
                          int(cnt)]
                         for k, (vals, cnt) in self.state.items()],
            "applied_lsn": self.applied_lsn,
            "seed_ht": self.seed_ht,
            "watermark_ht": self.watermark_ht,
            "counters": dict(self.counters)}

    async def _persist(self, create: bool = False) -> None:
        if create:
            await self.client.create_matview(
                self.viewdef.name, self.viewdef.to_wire(),
                slot_id=self.vw.slot_id, state=self._state_wire())
        else:
            await self.client.update_matview(
                self.viewdef.name, state=self._state_wire(),
                slot_id=self.vw.slot_id)
