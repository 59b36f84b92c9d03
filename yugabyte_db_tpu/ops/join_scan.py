"""Device hash join — the FK-equijoin probe inside the scan program.

TPC-H's multi-operator queries (Q3/Q5-shaped) are an FK equijoin from
the big fact table (lineitem) into a small, already-filtered dimension
side (orders, customer x nation), followed by GROUP BY + aggregates.
Before this module every such query fell off the pushdown boundary to
the client-tier row-at-a-time join.  The device shape (ROADMAP
operator-ladder rung (c); Tailwind / "In-RDBMS Hardware Acceleration
of Advanced Analytics", PAPERS.md):

- The BUILD side ships with the read request (:class:`JoinWire`):
  unique join keys + the payload columns the aggregate/group step
  needs.  :func:`make_join_runtime` turns it into an open-addressed
  pow2 hash table (linear probing, load factor <= 0.5) on the HOST —
  the build side is small by contract, the expensive side is the
  probe — and pads keys/payload to pow2 buckets so build-side GROWTH
  inside a bucket never changes a kernel signature.
- The PROBE runs on device inside the fused plan program
  (ops/plan_fusion.py): a vectorized ``lax.while_loop`` follows each
  probe row's collision chain until hit-or-empty.  The table size is
  static per pow2 bucket; the table CONTENTS (and so the true
  occupancy) are runtime arguments, so the kernel-cache contract
  matches ops/compaction.py / ops/grouped_scan.py exactly.
- String join keys ride as dictionary codes (per PR 9): build keys map
  through the probe column's scan-global dictionary host-side; a build
  key absent from the dictionary can never match and keeps a distinct
  negative sentinel so table construction stays collision-correct.
- Build-side payload columns gather by match index after the probe;
  string payloads dictionary-encode host-side so group keys stay
  integer strides on device.

Ineligible shapes raise :class:`JoinIneligible` with a typed reason
and the caller reverts to the interpreted row-at-a-time join —
byte-for-byte the pre-device semantics.  :func:`hash_join_cpu` is the
numpy twin of the probe, used by the plan twin for bitwise parity.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, Optional, Tuple

import numpy as np

#: build-side payload columns live at ids >= this in plan expressions,
#: group specs and aggregate ASTs, so they can never collide with a
#: probe table's real column ids
BUILD_COL_BASE = 1 << 20

#: process-wide join accounting (probes tallied by the plan kernel;
#: builds/fallbacks tallied here)
JOIN_STATS = {"builds": 0, "fallbacks": 0}

_MIN_TABLE_SLOTS = 8
_MAX_TABLE_SLOTS_HARD = 1 << 24
_HASH_MULT = np.uint64(0x9E3779B97F4A7C15)

REASON_JOIN_OFF = "join_pushdown_off"
REASON_DUPLICATE_KEY = "duplicate_build_key"
REASON_BUILD_OVERFLOW = "build_overflow"
REASON_KEY_TYPE = "join_key_type"
REASON_PROBE_SHAPE = "probe_shape"
REASON_STAGE_COUNT = "join_stage_count"


class JoinIneligible(Exception):
    """Typed refusal: the device join cannot serve this shape exactly;
    the caller falls back to the interpreted join.  ``stage`` is the
    0-based probe stage that refused (None when the refusal is not
    stage-specific) — a multi-join chain falls back WHOLE, but the
    reason names the stage that killed it."""

    def __init__(self, reason: str, detail: str = "",
                 stage: Optional[int] = None):
        if stage is not None:
            detail = (f"stage {stage}: {detail}" if detail
                      else f"stage {stage}")
        super().__init__(f"{reason}: {detail}" if detail else reason)
        self.reason = reason
        self.detail = detail
        self.stage = stage


@dataclass
class JoinWire:
    """The build side as it crosses the wire inside a ReadRequest.

    ``probe_col``: probe-table column id holding the FK.
    ``keys``: UNIQUE build-side join keys — int64 array, or an object
    array of strings when the probe column is dictionary-encoded.
    ``payload``: build-column id (>= BUILD_COL_BASE) ->
    (values, nulls) arrays aligned with ``keys``; values are numeric
    or object (string) arrays."""
    probe_col: int
    keys: np.ndarray
    payload: Dict[int, Tuple[np.ndarray, np.ndarray]] = \
        field(default_factory=dict)

    def signature(self) -> tuple:
        """The SHAPE identity of this build side (not its contents):
        probe col, payload ids and payload kinds — what the fused plan
        signature embeds.  Contents (keys, values, sizes inside one
        bucket) are runtime."""
        kinds = tuple(
            (bid, "str" if self.payload[bid][0].dtype == object
             else "num")
            for bid in sorted(self.payload))
        return (self.probe_col, kinds)


def table_bucket(n_build: int, max_slots: int) -> int:
    """Smallest pow2 slot count >= 2 * n_build (load factor <= 0.5,
    which bounds probe chains and guarantees the device while_loop
    always finds an empty slot), floored at _MIN_TABLE_SLOTS.  Raises
    JoinIneligible(REASON_BUILD_OVERFLOW) past the pow2 cap of
    `max_slots`."""
    cap = _MIN_TABLE_SLOTS
    limit = min(max(int(max_slots), _MIN_TABLE_SLOTS),
                _MAX_TABLE_SLOTS_HARD)
    while cap < limit:
        cap <<= 1
    s = _MIN_TABLE_SLOTS
    while s < 2 * n_build:
        if s >= cap:
            raise JoinIneligible(
                REASON_BUILD_OVERFLOW,
                f"{n_build} build rows need > {cap} slots")
        s <<= 1
    return s


def _home_slots(keys: np.ndarray, num_slots: int) -> np.ndarray:
    """Multiplicative-hash home slot per key (high bits — the low bits
    of a Fibonacci hash are the weak ones)."""
    bits = num_slots.bit_length() - 1
    h = keys.astype(np.uint64) * _HASH_MULT
    return (h >> np.uint64(64 - bits)).astype(np.int64)


def build_hash_table(keys: np.ndarray, num_slots: int
                     ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Open-addressed linear-probe table over UNIQUE int64 keys:
    (used bool[S], table_key int64[S], table_val int32[S] = build-row
    index).  Vectorized batch insertion: each round every unplaced key
    bids for its current slot, first-in-input-order wins, losers (and
    keys whose slot was already taken) advance one slot.  A key only
    ever advances past an occupied slot and slots never free, so the
    linear-probe invariant (no empty slot between a key's home and its
    resting place) holds and the device probe's hit-or-empty walk is
    exact."""
    n = len(keys)
    if n and len(np.unique(keys)) != n:
        raise JoinIneligible(REASON_DUPLICATE_KEY,
                             "build keys are not unique")
    used = np.zeros(num_slots, bool)
    tkey = np.zeros(num_slots, np.int64)
    tval = np.zeros(num_slots, np.int32)
    if not n:
        return used, tkey, tval
    mask = num_slots - 1
    slots = _home_slots(keys, num_slots)
    pending = np.arange(n)
    while len(pending):
        s = slots[pending]
        order = np.argsort(s, kind="stable")
        s_sorted = s[order]
        first = np.ones(len(order), bool)
        first[1:] = s_sorted[1:] != s_sorted[:-1]
        winners = pending[order[first]]
        ws = slots[winners]
        free = ~used[ws]
        claim = winners[free]
        cs = slots[claim]
        used[cs] = True
        tkey[cs] = keys[claim]
        tval[cs] = claim
        placed = np.zeros(n, bool)
        placed[claim] = True
        pending = pending[~placed[pending]]
        slots[pending] = (slots[pending] + 1) & mask
    return used, tkey, tval


@dataclass
class JoinRuntime:
    """Host-resolved build side, ready for the fused plan kernel.

    Static (kernel-signature) parts: ``probe_col``, ``num_slots``,
    ``build_cols`` (sorted payload ids) and each payload lane's device
    dtype.  Runtime parts: the table arrays, the true build-row count
    and the padded payload lanes — growth inside one pow2 bucket never
    recompiles."""
    probe_col: int
    num_slots: int                    # pow2 table bucket (static)
    build_rows_pad: int               # pow2 payload bucket (static)
    n_build: int                      # true build rows (runtime)
    used: np.ndarray
    table_key: np.ndarray
    table_val: np.ndarray
    #: build keys AFTER dictionary mapping, aligned with the wire's
    #: build rows — the CPU twin probes these (hash_join_cpu) so twin
    #: match indices are identical to the device table's
    keys_mapped: np.ndarray = field(
        default_factory=lambda: np.zeros(0, np.int64))
    build_cols: Tuple[int, ...] = ()
    payload_vals: Dict[int, np.ndarray] = field(default_factory=dict)
    payload_nulls: Dict[int, np.ndarray] = field(default_factory=dict)
    payload_dicts: Dict[int, np.ndarray] = field(default_factory=dict)
    payload_bounds: Dict[int, Tuple[float, float]] = \
        field(default_factory=dict)

    def shape_signature(self) -> tuple:
        return (self.probe_col, self.num_slots, self.build_rows_pad,
                tuple((bid, str(self.payload_vals[bid].dtype))
                      for bid in self.build_cols))


def _pad_to(arr: np.ndarray, n: int) -> np.ndarray:
    if len(arr) == n:
        return arr
    out = np.zeros((n,) + arr.shape[1:], arr.dtype)
    out[:len(arr)] = arr
    return out


def make_join_runtime(wire: JoinWire,
                      probe_dicts: Dict[int, np.ndarray],
                      max_slots: Optional[int] = None) -> JoinRuntime:
    """Resolve a JoinWire against the probe scan's dictionaries,
    emitting a ``device.join_build`` telemetry span (build rows +
    slot bucket) when a sampled trace is ambient."""
    from ..utils import trace as _trace
    with _trace.device_span("join_build",
                            signature=(wire.probe_col,
                                       len(wire.keys)),
                            rows=len(wire.keys)) as sp:
        rt = _make_join_runtime(wire, probe_dicts, max_slots)
        if sp is not None:
            sp.set_tag("slots", rt.num_slots)
        return rt


def _make_join_runtime(wire: JoinWire,
                       probe_dicts: Dict[int, np.ndarray],
                       max_slots: Optional[int] = None) -> JoinRuntime:
    """Resolve a JoinWire against the probe scan's dictionaries.

    String build keys map into the probe column's sorted dictionary
    (codes); keys absent from the dictionary can never match a probe
    row, so they keep a DISTINCT negative sentinel (-2 - row) — the
    table stays collision-correct and the payload gather indexes stay
    aligned with the wire's build rows.  Raises JoinIneligible with a
    typed reason for every shape the device join cannot serve."""
    if max_slots is None:
        from ..utils import flags
        max_slots = flags.get("join_max_build_slots")
    keys = np.asarray(wire.keys)
    n = len(keys)
    if keys.dtype == object or keys.dtype.kind in ("U", "S"):
        d = probe_dicts.get(wire.probe_col)
        if d is None:
            raise JoinIneligible(
                REASON_KEY_TYPE,
                "string build keys need a dictionary-coded probe "
                "column")
        svals = np.asarray(keys, object)
        if n and len(set(map(str, svals))) != n:
            raise JoinIneligible(REASON_DUPLICATE_KEY,
                                 "build keys are not unique")
        pos = np.searchsorted(d, svals) if len(d) else \
            np.zeros(n, np.int64)
        pos = np.clip(pos, 0, max(len(d) - 1, 0))
        hit = (np.asarray(d, object)[pos] == svals) if len(d) else \
            np.zeros(n, bool)
        codes = np.where(hit, pos, -2 - np.arange(n)).astype(np.int64)
        keys = codes
    elif keys.dtype.kind in "iu":
        keys = keys.astype(np.int64)
    elif keys.dtype.kind == "f" and (not n or np.all(
            keys == np.rint(keys))):
        keys = keys.astype(np.int64)
    else:
        raise JoinIneligible(REASON_KEY_TYPE,
                             f"unsupported key dtype {keys.dtype}")
    num_slots = table_bucket(n, max_slots)
    used, tkey, tval = build_hash_table(keys, num_slots)
    rows_pad = max(num_slots // 2, 1)
    rt = JoinRuntime(
        probe_col=wire.probe_col, num_slots=num_slots,
        build_rows_pad=rows_pad, n_build=n,
        used=used, table_key=tkey, table_val=tval,
        keys_mapped=keys, build_cols=tuple(sorted(wire.payload)))
    from .device_batch import f64_conversion
    for bid in rt.build_cols:
        vals, nulls = wire.payload[bid]
        vals = np.asarray(vals)
        nulls = (np.asarray(nulls, bool) if nulls is not None
                 else np.zeros(n, bool))
        if vals.dtype == object or vals.dtype.kind in ("U", "S"):
            sv = np.asarray(vals, object)
            filled = np.where(nulls, "", sv)
            uniq, codes = np.unique(filled.astype(str),
                                    return_inverse=True)
            rt.payload_dicts[bid] = uniq.astype(object)
            vals = codes.astype(np.int32)
        else:
            conv = (f64_conversion([vals])
                    if vals.dtype == np.float64 else None)
            if conv is not None:
                vals = vals.astype(conv)
            if n and vals.dtype.kind in "fiu":
                nz = vals[~nulls] if nulls.any() else vals
                if len(nz):
                    rt.payload_bounds[bid] = (float(nz.min()),
                                              float(nz.max()))
        rt.payload_vals[bid] = _pad_to(vals, rows_pad)
        rt.payload_nulls[bid] = _pad_to(nulls, rows_pad)
    JOIN_STATS["builds"] += 1
    return rt


def normalize_join(join) -> Tuple[JoinWire, ...]:
    """Canonical multi-stage form of a ReadRequest's join field: None,
    one JoinWire, or an ordered sequence of JoinWires all normalize to
    a tuple of stages (empty for None).  The order IS the probe order:
    stage k may probe a payload column shipped by an earlier stage (a
    chain: lineitem -> orders -> customer) or another real probe-table
    column (a star: lineitem -> orders, lineitem -> part)."""
    if join is None:
        return ()
    if isinstance(join, JoinWire):
        return (join,)
    return tuple(join)


def make_join_runtimes(wires, probe_dicts: Dict[int, np.ndarray],
                       max_slots: Optional[int] = None,
                       max_stages: Optional[int] = None
                       ) -> Tuple[JoinRuntime, ...]:
    """Resolve an ordered multi-stage build list into JoinRuntimes.

    Later stages may probe an earlier stage's dict-coded payload column
    (string FKs ride as codes): the dictionary namespace ACCUMULATES
    stage by stage, so stage k's string build keys map through the
    payload dictionary stage j < k shipped for that column.  Payload
    ids must be unique across stages (one shared BUILD_COL_BASE
    counter); a collision or an over-budget stage count raises a typed
    JoinIneligible carrying the offending stage."""
    wires = normalize_join(wires)
    if max_stages is None:
        from ..utils import flags
        max_stages = int(flags.get("multi_join_max_stages"))
    if len(wires) > max_stages:
        raise JoinIneligible(
            REASON_STAGE_COUNT,
            f"{len(wires)} probe stages > multi_join_max_stages="
            f"{max_stages}", stage=max_stages)
    dicts = dict(probe_dicts)
    seen_bids: set = set()
    rts = []
    for si, wire in enumerate(wires):
        overlap = seen_bids & set(wire.payload)
        if overlap:
            raise JoinIneligible(
                REASON_PROBE_SHAPE,
                f"payload id {sorted(overlap)[0]} shipped by two "
                "stages", stage=si)
        try:
            rt = make_join_runtime(wire, dicts, max_slots)
        except JoinIneligible as e:
            if e.stage is None:
                raise JoinIneligible(e.reason, e.detail,
                                     stage=si) from e
            raise
        rts.append(rt)
        seen_bids |= set(wire.payload)
        dicts.update(rt.payload_dicts)
    return tuple(rts)


# ---------------------------------------------------------------------------
# The traceable probe (called from the fused plan kernel)
# ---------------------------------------------------------------------------

def probe_table(pk, table_used, table_key, table_val, num_slots: int):
    """Vectorized linear-probe walk: for each probe key, follow its
    collision chain until key-hit or empty slot.  ``num_slots`` is
    STATIC (pow2, part of the kernel signature); the table arrays are
    runtime.  Termination is guaranteed by the builder's <= 0.5 load
    factor (at least half the slots are empty).  Returns match_idx
    int32 [N] (-1 = no match) — the build-row gather index."""
    import jax
    import jax.numpy as jnp

    bits = num_slots.bit_length() - 1
    mask = num_slots - 1
    k64 = pk.astype(jnp.int64)
    h = k64.astype(jnp.uint64) * jnp.uint64(int(_HASH_MULT))
    slot = (h >> jnp.uint64(64 - bits)).astype(jnp.int32)
    n = pk.shape[0]
    midx0 = jnp.full(n, -1, jnp.int32)
    done0 = jnp.zeros(n, bool)

    def cond(state):
        _, _, done = state
        return jnp.logical_not(jnp.all(done))

    def body(state):
        slot, midx, done = state
        tk = table_key[slot]
        tu = table_used[slot]
        hit = tu & (tk == k64) & jnp.logical_not(done)
        stop = jnp.logical_not(tu) & jnp.logical_not(done)
        midx = jnp.where(hit, table_val[slot], midx)
        done = done | hit | stop
        slot = jnp.where(done, slot, (slot + 1) & mask)
        return slot, midx, done

    _, midx, _ = jax.lax.while_loop(cond, body, (slot, midx0, done0))
    return midx


# ---------------------------------------------------------------------------
# Numpy twin of the probe — the plan twin's join step
# ---------------------------------------------------------------------------

def hash_join_cpu(probe_keys: np.ndarray, build_keys: np.ndarray
                  ) -> np.ndarray:
    """match_idx int32 per probe key (-1 dangling), identical to the
    device probe's answer for unique build keys (HOW the match is
    found cannot change WHICH unique key matches)."""
    n_b = len(build_keys)
    if n_b == 0:
        return np.full(len(probe_keys), -1, np.int32)
    order = np.argsort(build_keys, kind="stable")
    skeys = build_keys[order]
    pos = np.searchsorted(skeys, probe_keys)
    pos_c = np.clip(pos, 0, n_b - 1)
    hit = skeys[pos_c] == probe_keys
    return np.where(hit, order[pos_c], -1).astype(np.int32)
