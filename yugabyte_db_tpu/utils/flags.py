"""Runtime flag registry.

Mirrors the reference's gflags + YB wrappers: DEFINE_RUNTIME_* flags are
hot-updatable at runtime (reference: src/yb/util/flags.h), flags carry tags
(reference: src/yb/util/flags/flag_tags.h), and AutoFlags gate wire/disk
format changes on universe-wide upgrade (reference:
src/yb/util/flags/auto_flags.h, architecture/design/auto_flags.md).

The TPU pushdown switch `tpu_pushdown_enabled` follows the reference's
planned `yb_enable_tpu_pushdown` GUC pattern: a runtime flag consulted at
the scan/compaction seams with zero SQL changes.
"""
from __future__ import annotations

import threading
from dataclasses import dataclass, field
from typing import Any, Callable


@dataclass
class Flag:
    name: str
    default: Any
    help: str
    tags: tuple = ()
    runtime: bool = False
    value: Any = None
    callbacks: list = field(default_factory=list)

    def get(self):
        return self.value


class FlagRegistry:
    def __init__(self):
        self._flags: dict[str, Flag] = {}
        self._lock = threading.Lock()

    def define(self, name: str, default: Any, help: str = "",
               tags: tuple = (), runtime: bool = False) -> Flag:
        with self._lock:
            if name in self._flags:
                return self._flags[name]
            f = Flag(name, default, help, tags, runtime, default)
            self._flags[name] = f
            return f

    def get(self, name: str) -> Any:
        return self._flags[name].value

    def set(self, name: str, value: Any) -> None:
        f = self._flags[name]
        if not f.runtime:
            raise ValueError(f"flag {name} is not runtime-settable")
        f.value = value
        for cb in f.callbacks:
            cb(value)

    def on_change(self, name: str, cb: Callable[[Any], None]) -> None:
        self._flags[name].callbacks.append(cb)

    def all(self) -> dict[str, Any]:
        return {n: f.value for n, f in self._flags.items()}

    def items(self) -> list[tuple[str, Flag]]:
        """Sorted (name, Flag) pairs — introspection surfaces
        (pg_settings, /flags web endpoint)."""
        with self._lock:
            return sorted(self._flags.items())

    def reset(self, name: str) -> None:
        f = self._flags[name]
        f.value = f.default
        # observers (e.g. cached derived values) must see resets too
        for cb in f.callbacks:
            cb(f.value)


REGISTRY = FlagRegistry()

define_flag = REGISTRY.define


def DEFINE_RUNTIME(name: str, default: Any, help: str = "", tags: tuple = ()):
    return REGISTRY.define(name, default, help, tags, runtime=True)


def DEFINE(name: str, default: Any, help: str = "", tags: tuple = ()):
    return REGISTRY.define(name, default, help, tags, runtime=False)


def get(name: str) -> Any:
    return REGISTRY.get(name)


def set_flag(name: str, value: Any) -> None:
    REGISTRY.set(name, value)


def coerce_and_set(name: str, value: Any) -> tuple:
    """Set a flag from an UNTYPED wire/env value, coercing it to the
    current value's type (the set_flag RPCs and the YBTPU_FLAGS env
    handshake all parse the same way — one parser, no drift).  Unknown
    flags raise KeyError loudly.  Returns (old, coerced)."""
    old = get(name)
    if isinstance(old, bool):
        value = str(value).lower() in ("1", "true", "on", "yes")
    elif isinstance(old, int):
        value = int(value)
    elif isinstance(old, float):
        value = float(value)
    set_flag(name, value)
    return old, value


# --- AutoFlags ------------------------------------------------------------
# A flag whose value auto-promotes from `initial` to `target` only once the
# whole universe is upgraded (reference: util/flags/auto_flags.h). We track
# promotion state in the registry; the master's auto-flags manager flips it.

@dataclass
class AutoFlag:
    name: str
    initial: Any
    target: Any
    flag_class: str  # kLocalVolatile/kLocalPersisted/kExternal
    promoted: bool = False

    @property
    def value(self):
        return self.target if self.promoted else self.initial


_AUTO_FLAGS: dict[str, AutoFlag] = {}


def DEFINE_AUTO(name: str, initial: Any, target: Any,
                flag_class: str = "kLocalVolatile") -> AutoFlag:
    f = AutoFlag(name, initial, target, flag_class)
    _AUTO_FLAGS[name] = f
    return f


def promote_auto_flags() -> None:
    for f in _AUTO_FLAGS.values():
        f.promoted = True


def auto_flags() -> dict[str, AutoFlag]:
    return dict(_AUTO_FLAGS)


# --- Core engine flags ----------------------------------------------------
DEFINE_RUNTIME("tpu_pushdown_enabled", True,
               "Route scan/filter/aggregate pushdown to the TPU execution "
               "backend (the yb_enable_tpu_pushdown analog).")
DEFINE_RUNTIME("tpu_compaction_enabled", True,
               "Offload LSM compaction merge + MVCC GC to TPU kernels.")
DEFINE_RUNTIME("compaction_chunk_rows", 524288,
               "Frontier capacity (rows) of the pipelined chunked "
               "compaction engine; rounded up to a power of two so the "
               "merge kernel compiles once per shape bucket.")
DEFINE_RUNTIME("streaming_scan_enabled", True,
               "Stream cold aggregate scans as pow2-bucket chunks "
               "through the overlapped batch-formation pipeline "
               "(ops/stream_scan.py) instead of materializing one "
               "monolithic padded batch first. Off = the monolithic "
               "r05 batch path, the honest comparison baseline.")
DEFINE_RUNTIME("streaming_chunk_rows", 1 << 20,
               "Target rows per streamed scan chunk; the chunk bucket "
               "is the pow2 ceiling, so every chunk of a scan shares "
               "one kernel-cache signature.")
DEFINE_RUNTIME("device_float_dtype", "auto",
               "Device representation of fractional f64 columns: 'auto' "
               "keeps f64 on CPU backends and ships f32 on TPU (SUMs stay "
               "exact via the scan kernel's int64 fixed-point "
               "accumulation); 'float32'/'float64' force one (tests use "
               "float32 to exercise the TPU-representative path on CPU).")
DEFINE_RUNTIME("tserver_device_chips", 1,
               "Chips a tablet server owns: it takes the first N of "
               "jax.devices() when it starts.  1 serves every tablet read "
               "on the default device, one launch a tablet.  With N > 1 "
               "the tablets of a table are placed on the chips in "
               "partition order (tablet i of n on chip i*N//n), their "
               "lanes are cached as one batch sharded over the chips, and "
               "an aggregate read of all of them is one shard_map launch "
               "combined by lax.psum (docdb/mesh_read.py).")
DEFINE_RUNTIME("scan_group_strategy", "auto",
               "Grouped-aggregate reduction strategy: 'segment' "
               "(scatter-add segment_sum — fastest on CPU backends), "
               "'unroll' (per-group masked tree reductions — pure VPU "
               "code, no scatter, for TPU), or 'auto' (segment on cpu, "
               "unroll elsewhere).")
DEFINE_RUNTIME("grouped_pushdown_enabled", True,
               "Serve GROUP BY over dictionary-encoded (string) key "
               "columns on the device grouped-aggregation kernel "
               "(ops/grouped_scan.py): chunk-local dictionary codes "
               "remap into one scan-global dictionary, group ids "
               "scatter into pow2 slot buckets, and string equality/IN "
               "predicates ride along as integer compares. Off — or "
               "any over-cardinality group set that overflows the slot "
               "budget — reverts to the interpreted row-at-a-time "
               "GROUP BY path.")
DEFINE_RUNTIME("grouped_max_slots", 4096,
               "Group-slot budget of the device grouped-aggregation "
               "kernel (rounded up to a power of two, one slot "
               "reserved for overflow spill). Scans whose scan-global "
               "dictionary domain product exceeds the budget launch "
               "optimistically: rows landing in the spill slot are "
               "counted and a nonzero spill reverts the whole scan to "
               "the interpreted GROUP BY.")
DEFINE_RUNTIME("join_pushdown_enabled", True,
               "Serve FK-equijoin aggregate requests (ReadRequest.join) "
               "on the device hash-join kernel (ops/join_scan.py): the "
               "shipped build side becomes a pow2-bucket open-addressed "
               "table, the probe runs inside the scan program, and "
               "build-side payload columns gather by match index. Off "
               "— or any shape the kernel cannot serve exactly "
               "(duplicate build keys, oversized build side, "
               "incompatible expressions) — reverts to the interpreted "
               "row-at-a-time join path, byte-for-byte the pre-device "
               "semantics.")
DEFINE_RUNTIME("plan_fusion_enabled", True,
               "Compile whole filter->join->group->aggregate plan "
               "shapes into ONE jitted device program per canonical "
               "plan signature (ops/plan_fusion.py). Off keeps every "
               "operator its own program + host round-trip (the "
               "operator-at-a-time path): the SQL tier stops pushing "
               "joins down and executes them client-side.")
DEFINE_RUNTIME("window_pushdown_enabled", True,
               "Evaluate eligible window functions (row_number/rank/"
               "dense_rank/lag/lead and exact-integer SUM frames) "
               "through the vectorized segment-scan window kernels "
               "(ops/window_scan.py) instead of the row-at-a-time "
               "Python loop. Ineligible shapes (float arithmetic "
               "frames, NULL partition/order keys, unsupported "
               "functions) always fall back; off forces the Python "
               "path.")
DEFINE_RUNTIME("join_max_build_slots", 65536,
               "Pow2 cap on the device hash-join build table (slots = "
               "smallest pow2 >= 2x build rows, so load factor stays "
               "<= 0.5). Build sides needing more slots fall back to "
               "the interpreted join with a typed reason.")
DEFINE_RUNTIME("multi_join_max_stages", 4,
               "Max probe stages a multi-join fused plan may carry "
               "(ordered JoinWire list on one ReadRequest: chains like "
               "lineitem JOIN orders JOIN customer, or stars with "
               "several fact-table FKs). Each stage is one host-built "
               "pow2 hash table probed sequentially inside ONE device "
               "program under one shared visibility mask. Requests "
               "with more stages fall back whole to the interpreted "
               "join with a typed join_stage_count reason.")
DEFINE_RUNTIME("window_server_pushdown_enabled", True,
               "Serve window functions SERVER-side over a sorted-scan "
               "request shape (ReadRequest.window routed through "
               "ops/window_scan.py behind the docdb pushdown "
               "boundary): the tablet sorts its visible rows by "
               "(partition, order) and runs the segment-scan window "
               "kernels over its OWN rows instead of the executor's "
               "materialized ones. Ineligible shapes serve plain "
               "sorted rows with a typed reason and the client tier "
               "recomputes bit-identically; off disables the request "
               "shape entirely.")
DEFINE_RUNTIME("grouped_spill_merge_enabled", True,
               "Partial-spill merge for over-cardinality device GROUP "
               "BYs: slots below the spill slot keep their (exact) "
               "device partials, rows that landed in the spill slot "
               "re-aggregate on the interpreted tail, and the two "
               "partials combine through combine_grouped_partials — "
               "so slot overflow no longer pays a full interpreted "
               "re-scan. Off reverts to the full re-scan fallback.")
DEFINE_RUNTIME("hash_scan_enumerate_max", 1024,
               "Max enumerable key-target count for rewriting a "
               "short range/IN scan over a single-integer-hash-PK "
               "table into batched point gets (hash sharding cannot "
               "seek key ranges; a small target set IS a MultiGet).")
DEFINE_RUNTIME("bnl_batch_size", 1024,
               "Join-key batch size for batched-nested-loop joins: the "
               "inner side fetches WHERE inner_col IN (batch) pushed to "
               "storage per batch of outer keys (reference: "
               "yb_bnl_batch_size GUC / nodeYbBatchedNestloop.c).")
DEFINE_RUNTIME("bnl_max_keys", 65536,
               "Above this many distinct outer join keys the planner "
               "falls back to a full inner fetch + hash join instead "
               "of batched IN pushdown.")
DEFINE_RUNTIME("native_point_reader_max_rows", 4_000_000,
               "SSTs above this row count skip the eager native "
               "PointReader (it deserializes and pins every columnar "
               "block); their point reads use the per-block path, which "
               "pins only visited blocks.")
DEFINE_RUNTIME("tpu_min_rows_for_pushdown", 4096,
               "Scans smaller than this stay on the CPU path: point reads "
               "must never pay a device round-trip.")
DEFINE_RUNTIME("raft_heartbeat_interval_ms", 50, "Raft leader heartbeat period.")
DEFINE_RUNTIME("leader_lease_duration_ms", 2000, "Raft leader lease length.")
DEFINE_RUNTIME("master_orphan_gc_grace_s", 60.0,
               "A replica reported by a tserver but absent from the "
               "catalog's replica set must stay orphaned this long "
               "(across heartbeats) before the master deletes it — "
               "longer than any in-flight create/split/move window "
               "(splits and moves are also structurally protected).")
DEFINE_RUNTIME("log_segment_size_bytes", 16 * 1024 * 1024, "WAL segment size.")
DEFINE_RUNTIME("log_gc_max_peer_lag_entries", 100_000,
               "Leader WAL retention bound for lagging peers: entries are "
               "kept for a behind peer only while its lag stays under this; "
               "beyond it GC proceeds and the peer recovers via snapshot "
               "install (reference: log retention caps + remote bootstrap).")
DEFINE_RUNTIME("memstore_flush_threshold_bytes", 64 * 1024 * 1024,
               "Memtable size that triggers a flush.")
DEFINE_RUNTIME("async_flush_enabled", True,
               "Memtable flushes run on a background flush executor: "
               "the apply thread freezes the active memtable (an "
               "in-memory pointer swap) and returns immediately, so a "
               "Raft apply never stalls behind an SST write + fsync. "
               "Off reverts to the inline flush on the apply path "
               "(byte-identical on-disk state either way).")
DEFINE_RUNTIME("max_frozen_memtables", 2,
               "Backpressure bound for async flush: once this many "
               "frozen memtables await the background flush executor, "
               "the apply thread drains one inline instead of freezing "
               "another (reference: max_write_buffer_number — bounded "
               "memory, bounded WAL-replay window).")
DEFINE_RUNTIME("fused_replicate_enabled", True,
               "Group-fused consensus appends (the ReplicateBatch "
               "shape, raft_consensus.cc:1224): replicate() calls that "
               "arrive while an append round is in flight coalesce "
               "into ONE WAL append (one fsync) and ONE broadcast "
               "round. Off reverts to one append + one round per "
               "call; log CONTENT is identical either way — fusion "
               "changes batching at the durability boundary only.")
DEFINE_RUNTIME("max_clock_skew_ms", 500,
               "Clock uncertainty window: strong reads restart when they "
               "encounter records within (read_ht, read_ht + skew].")
DEFINE_RUNTIME("history_retention_interval_sec", 900,
               "MVCC history retention before compaction GC "
               "(timestamp_history_retention_interval_sec analog).")

DEFINE_RUNTIME("encrypt_data_at_rest", False,
               "Encrypt SST files with the active universe key.")

DEFINE_RUNTIME("sst_format_version", 2,
               "On-disk columnar SST block format version (default 2). "
               "2 = v2 blocks: keys matrix dropped when derivable from "
               "pk+ht/write_id, per-lane delta/dict/RLE encodings "
               "(encode only if smaller), per-block min/max zone maps. "
               "1 = the pre-v2 format, byte-identical to the old "
               "writer. Readers handle both versions side by side; "
               "storage/sst.py resolve_format_version is the ONLY "
               "writer gate, so no writer can emit v2 while this is 1.")
DEFINE_RUNTIME("doc_shred_enabled", True,
               "Shred scalar JSON document paths ($.a.b) into derived "
               "per-path columnar v2 lanes at flush/compaction time "
               "(yugabyte_db_tpu/docstore/): int/float values become "
               "fixed lanes with presence bitmaps and per-block zone "
               "maps, string/bool values dictionary-code, and doc "
               "predicates/aggregates push down to device integer "
               "compares exactly like scalar columns. The raw JSON "
               "payload always stays on disk, so paths that resist "
               "shredding (heterogeneous types, arrays, low coverage) "
               "fall back to the interpreted row path byte-identically. "
               "Off = the v2 writer emits byte-identical pre-shred "
               "output and every doc predicate runs interpreted.")
DEFINE_RUNTIME("doc_shred_max_paths", 16,
               "Per-column cap on shredded document paths per block; "
               "when a block's inferred path schema is wider, the "
               "highest-coverage paths win and the rest stay in the "
               "raw JSON payload (interpreted fallback).")
DEFINE_RUNTIME("bypass_reader_enabled", False,
               "Route eligible aggregate scans through the analytics "
               "bypass engine (yugabyte_db_tpu/bypass/): snapshot-"
               "pinned SST-direct scans that never touch the tserver "
               "hot path. Off (the default) keeps the RPC scan path "
               "byte-identical to a build without the subsystem; "
               "ineligible shapes always fall back to RPC with a "
               "typed reason.")
DEFINE_RUNTIME("bypass_prefilter_enabled", True,
               "Near-data predicate pre-filter inside the bypass "
               "reader: fixed-width comparison conjuncts evaluate "
               "against encoded lanes in one GIL-released native pass "
               "and provably-unmatched rows are dropped before batch "
               "formation. Result bits are unchanged (the batch keeps "
               "the unfiltered dtype policy, bucket and static-scale "
               "bounds); off = every row reaches batch formation.")
DEFINE_RUNTIME("zone_map_pruning", True,
               "Consult v2 per-block min/max zone maps in the scan "
               "pushdown paths to skip whole blocks whose value ranges "
               "cannot satisfy the WHERE predicate (gated on MVCC "
               "chunk-safety so a pruned block can never hide a newer "
               "row version). Off = every block reaches batch "
               "formation, the pre-zone-map behavior.")

# --- request scheduler (sched/) -------------------------------------------
DEFINE_RUNTIME("scheduler_enabled", True,
               "Route tserver data-path RPCs through the admission-"
               "controlled request scheduler (priority lanes, typed "
               "overload sheds, dynamic micro-batching). Off = the "
               "direct per-RPC dispatch path.")
DEFINE_RUNTIME("sched_point_read_depth", 512,
               "Point-read lane admission bound (queued + inflight): "
               "bounds worst-case queueing of admitted point reads to "
               "depth/drain-rate; past it the lane sheds with "
               "retry_after_ms and the client backs off.")
DEFINE_RUNTIME("sched_point_write_depth", 2048,
               "Point-write lane admission bound.")
DEFINE_RUNTIME("sched_scan_depth", 512,
               "Scan/aggregate lane admission bound.")
DEFINE_RUNTIME("sched_txn_depth", 4096,
               "Txn lane admission bound (admission-only: txn control "
               "never queues behind txn control, which could deadlock).")
DEFINE_RUNTIME("sched_maintenance_depth", 64,
               "Maintenance lane admission bound.")
DEFINE_RUNTIME("sched_read_max_batch", 64,
               "Point-read batching cap: same-tablet strong point gets "
               "coalesced into one engine multi_get (one leader/lease "
               "gate + one read point + one fused lookup).")
DEFINE_RUNTIME("sched_read_max_wait_us", 1000,
               "Upper bound of the adaptive point-read micro-batch "
               "window.")
DEFINE_RUNTIME("sched_write_max_batch", 64,
               "Group-commit cap: same-tablet plain writes coalesced "
               "into one WAL append + one tablet apply.")
DEFINE_RUNTIME("sched_write_max_wait_us", 1000,
               "Upper bound of the adaptive write micro-batch window; "
               "the actual wait adapts to the arrival rate and is zero "
               "on an idle lane.")
DEFINE_RUNTIME("sched_scan_max_batch", 32,
               "Scan-coalescing cap: same-signature scans share one "
               "batched kernel launch.")
DEFINE_RUNTIME("sched_scan_max_wait_us", 2000,
               "Upper bound of the adaptive scan micro-batch window.")
DEFINE_RUNTIME("sched_cut_through_min_interval_us", 500,
               "Below this recent inter-arrival time a lane stops "
               "inline cut-through dispatch and defers to the "
               "queue+worker path so same-sweep arrivals coalesce "
               "into one batch (the engine is synchronous: inline "
               "execution leaves no await-window to batch in).")
DEFINE_RUNTIME("rpc_max_inflight_per_connection", 1024,
               "Per-connection dispatch-slot cap: frames past this many "
               "in-flight calls on one connection are rejected with the "
               "typed overload status, so one misbehaving client cannot "
               "occupy every dispatch slot.")

# --- control plane under load (master auto-split; cluster/ harness) -------
DEFINE_RUNTIME("enable_automatic_tablet_splitting", False,
               "Master-driven tablet auto-splitting: each maintenance "
               "tick the leader master splits at most one tablet whose "
               "leader-reported size or write rate crossed its "
               "threshold (reference: the tablet-splitting manager "
               "behind enable_automatic_tablet_splitting).")
DEFINE_RUNTIME("tablet_split_size_threshold_bytes", 64 * 1024 * 1024,
               "Auto-split a tablet once its leader reports at least "
               "this many bytes (tablet_split_low_phase_size_"
               "threshold_bytes analog).")
DEFINE_RUNTIME("tablet_split_traffic_threshold_ops_s", 0.0,
               "Auto-split a tablet whose write rate (WAL entries/s, "
               "EWMA over master heartbeats) sustains above this; "
               "0 disables the traffic trigger and leaves only the "
               "size threshold.")
DEFINE_RUNTIME("tablet_split_max_tablets_per_table", 16,
               "Auto-splitting stops growing a table past this many "
               "tablets (outstanding_tablet_split_limit analog — "
               "bounds split storms under hot-key load).")
DEFINE_RUNTIME("outstanding_tablet_split_limit", 1,
               "At most this many auto-splits in flight at once, and "
               "NONE while a blacklist drain is rebalancing replicas "
               "(the load balancer would otherwise chase freshly "
               "split children forever — measured in the PR-10 "
               "cluster harness). 0 removes the bound.")
DEFINE_RUNTIME("sched_cross_tablet_fusion", True,
               "One scheduler-worker wakeup dispatches up to "
               "sched_fusion_max_groups ready groups from its lane's "
               "queue (concurrently), not just the group that woke "
               "it: same-signature work on DIFFERENT tablets shares "
               "one loop sweep and one admission pass, and coalesced "
               "device scans overlap one group's batch formation with "
               "another's kernel execution. Off dispatches one group "
               "per wakeup.")
DEFINE_RUNTIME("sched_fusion_max_groups", 8,
               "Cap on extra groups one fused worker wakeup may drain "
               "from its lane queue.  NB: a fused wakeup dispatches "
               "its groups concurrently, so a lane's worst-case "
               "in-flight dispatch count is workers x (this cap + 1), "
               "not workers.")

# --- observability (utils/trace.py; ISSUE 14) -----------------------------
DEFINE_RUNTIME("trace_sampling_rate", 0.01,
               "Fraction of trace ROOTS (requests with no propagated "
               "context) that record spans; propagated decisions "
               "(sampled bit on the RPC frame) always win, so a "
               "harness forcing a sampled root gets the full "
               "cross-process tree regardless of this rate. 0 "
               "disables root sampling entirely; the default keeps "
               "the layer's hot-path cost under the bench-asserted "
               "2% overhead gate (trace_overhead blocks).")
DEFINE_RUNTIME("ash_sample_interval_ms", 50,
               "Period of the background ASH wait-state sampler "
               "thread (utils/trace.AshSampler.start; started by "
               "tools/server_main in every server process). Cheap by "
               "construction: one pass over the active-wait table + "
               "registered providers per tick.")
DEFINE_RUNTIME("tracez_keep", 32768,
               "Finished spans retained per process for rpc_tracez / "
               "rpcz dumps and TRACES.finished (bounded ring; oldest "
               "evicted and counted in TRACES.evicted). A statement "
               "of four tablet scans is ~53 spans, and a fully "
               "sampled window now holds some ten of them a second.")

# --- incremental materialized views (matview/; ISSUE 17) ------------------
DEFINE_RUNTIME("matview_enabled", True,
               "Incremental materialized aggregate views (yugabyte_db_"
               "tpu/matview/): CREATE MATERIALIZED VIEW registers a "
               "grouped-partial set seeded by one pinned-read-point "
               "scan and maintained from the CDC change stream. The "
               "flag gates only the new surface — with it off, "
               "registration and matview reads raise a typed error "
               "and every existing path keeps its shape.")
DEFINE_RUNTIME("matview_rescan_budget", 8,
               "Per-fold-round cap on MIN/MAX per-group re-scans (a "
               "retraction that challenges the current extremum needs "
               "one bounded group re-aggregate). Exceeding the budget "
               "is a typed event: the maintainer falls back to one "
               "full re-seed for the round and counts it.")
DEFINE_RUNTIME("matview_max_staleness_ms", 500.0,
               "Bounded-staleness read gate for matview reads: a read "
               "observing view staleness (now - applied watermark) "
               "beyond this bound first drives a synchronous catch-up "
               "fold round, then serves. Every read surfaces its "
               "staleness_ms either way. Staleness compares the "
               "CLIENT's wall clock against the physical component of "
               "the tserver-assigned watermark, so client/tserver "
               "clock skew shifts it one-for-one: skew past the bound "
               "forces a catch-up on every read, negative skew masks "
               "real staleness. Size the bound well above the "
               "deployment's expected clock skew.")
DEFINE_RUNTIME("matview_poll_ms", 50,
               "Idle poll period of a matview maintainer's fold loop "
               "(the steady-state staleness knob: each round drains "
               "the VirtualWal and advances the view watermark even "
               "without new writes).")

# TEST_ flags (reference: DEFINE_test_flag, util/flags/flag_tags.h:311)
DEFINE_RUNTIME("TEST_fault_crash_fraction", 0.0,
               "Probabilistic fault injection fraction (MAYBE_FAULT analog).")
