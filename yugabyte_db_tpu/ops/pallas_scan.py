"""Pallas TPU kernel: fused predicate + masked-aggregate scan.

The XLA path (ops/scan.py) already fuses well; this hand-written kernel
is the Pallas counterpart for the hottest fixed shape — a Q6-style
conjunctive range predicate with masked SUM/COUNT — streaming each row
block HBM -> VMEM exactly once and emitting per-block partials (grid
dim 0), which the host-side wrapper reduces. Serves as the template for
further pallas offloads (compaction mask, grouped one-hot) and runs
under interpret mode on CPU for tests.

Layout notes (pallas_guide): blocks are (8, 128)-aligned f32 tiles; we
use (BLOCK_ROWS,) = 8*128 multiples so each block is a whole tile row
set; scalars ride in SMEM. Mosaic rejects sub-tile output blocks, and
rank-1 outputs can't verify (XLA picks a size-dependent 1D tile T(512),
T(1024), ... while Mosaic picks T(block)), so per-block partials are
emitted as one full rank-2 (SUBLANES, lanes) f32 tile per grid step —
a scalar partial broadcast across a (8, 128) tile, a grouped [G]
partial broadcast across (8, G_pad) — and the host wrapper slices one
representative element/row back out ([::SUBLANES, 0] / [::SUBLANES, :G]).
"""
from __future__ import annotations

from functools import partial
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

BLOCK_ROWS = 8 * 128 * 4          # 4096 rows per grid step
LANES = 128                       # TPU lane count (last-dim tile)
SUBLANES = 8                      # f32 sublane count


def _pad_lanes(g: int) -> int:
    return ((g + LANES - 1) // LANES) * LANES


# The package enables jax_enable_x64, which makes BlockSpec index maps
# trace to i64 — Mosaic then fails to legalize the index-map func.return.
# Every index map below casts to int32 explicitly.
def _im1(i):
    return (jnp.int32(i),)


def _im1_0(i):
    return (jnp.int32(0),)


def _im2(i):
    return (jnp.int32(i), jnp.int32(0))


def _q6_kernel(scalars_ref, qty_ref, price_ref, disc_ref, ship_ref,
               valid_ref, sum_ref, cnt_ref):
    ship_lo = scalars_ref[0]
    ship_hi = scalars_ref[1]
    disc_lo = scalars_ref[2]
    disc_hi = scalars_ref[3]
    qty_max = scalars_ref[4]
    qty = qty_ref[:]
    price = price_ref[:]
    disc = disc_ref[:]
    ship = ship_ref[:]
    valid = valid_ref[:]
    mask = ((ship >= ship_lo) & (ship < ship_hi)
            & (disc >= disc_lo) & (disc <= disc_hi)
            & (qty < qty_max) & (valid > 0))
    maskf = mask.astype(jnp.float32)
    sum_ref[...] = jnp.broadcast_to(jnp.sum(price * disc * maskf),
                                    (SUBLANES, LANES))
    cnt_ref[...] = jnp.broadcast_to(jnp.sum(maskf), (SUBLANES, LANES))


@partial(jax.jit, static_argnames=("interpret",))
def q6_scan_pallas(qty, price, disc, shipdate, valid, scalars,
                   interpret: bool = False):
    """scalars: [ship_lo, ship_hi, disc_lo, disc_hi, qty_max] f32.
    Inputs must be f32 arrays padded to a BLOCK_ROWS multiple (valid=0 on
    padding). Returns (revenue_sum, match_count)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    n = qty.shape[0]
    grid = n // BLOCK_ROWS
    blk = pl.BlockSpec((BLOCK_ROWS,), _im1)
    # explicit shape + int32 index map: the default (map-less) SMEM spec
    # traces an i64 index map under x64, which Mosaic can't legalize
    scalar_spec = pl.BlockSpec((5,), _im1_0, memory_space=pltpu.SMEM)
    sums, cnts = pl.pallas_call(
        _q6_kernel,
        grid=(grid,),
        in_specs=[scalar_spec, blk, blk, blk, blk, blk],
        out_specs=(pl.BlockSpec((SUBLANES, LANES), _im2),
                   pl.BlockSpec((SUBLANES, LANES), _im2)),
        out_shape=(jax.ShapeDtypeStruct((grid * SUBLANES, LANES),
                                        jnp.float32),
                   jax.ShapeDtypeStruct((grid * SUBLANES, LANES),
                                        jnp.float32)),
        interpret=interpret,
    )(scalars, qty, price, disc, shipdate, valid)
    return (jnp.sum(sums[::SUBLANES, 0]), jnp.sum(cnts[::SUBLANES, 0]))


def q6_scan(qty: np.ndarray, price: np.ndarray, disc: np.ndarray,
            shipdate: np.ndarray, ship_lo: float, ship_hi: float,
            disc_lo: float, disc_hi: float, qty_max: float,
            interpret: bool = False) -> Tuple[float, int]:
    """Host wrapper: pads to the block grid and runs the kernel."""
    n = len(qty)
    padded = ((n + BLOCK_ROWS - 1) // BLOCK_ROWS) * BLOCK_ROWS

    def pad(a):
        out = np.zeros(padded, np.float32)
        out[:n] = a
        return jnp.asarray(out)

    valid = np.zeros(padded, np.float32)
    valid[:n] = 1.0
    scalars = jnp.asarray(
        np.array([ship_lo, ship_hi, disc_lo, disc_hi, qty_max], np.float32))
    s, c = q6_scan_pallas(pad(qty), pad(price), pad(disc), pad(shipdate),
                          jnp.asarray(valid), scalars,
                          interpret=interpret)
    return float(s), int(c)


# --------------------------------------------------------------------------
# Grouped masked sums: the Q1-style one-hot matmul, hand-fused in pallas.
# Each grid step streams one row block and emits [G] partial sums computed
# as  one_hot(gid)ᵀ · (value · mask)  — an MXU matmul per block.
# --------------------------------------------------------------------------
def _grouped_kernel(gid_ref, val_ref, mask_ref, out_ref, *, num_groups):
    gid = gid_ref[:]
    val = val_ref[:] * mask_ref[:]
    g_pad = _pad_lanes(num_groups)
    # one_hot via broadcasted iota compare: [B, G_pad]. tpu.iota is
    # integer-only, so build an i32 iota and compare against i32 gids.
    groups = jax.lax.broadcasted_iota(jnp.int32, (gid.shape[0],
                                                  g_pad), 1)
    onehot = (gid.astype(jnp.int32)[:, None] == groups).astype(jnp.float32)
    # 2D lhs: Mosaic's dot lowering rejects rank-1 operands
    part = val[None, :] @ onehot            # [1, B] @ [B, G_pad]
    out_ref[...] = jnp.broadcast_to(part, (SUBLANES, g_pad))


@partial(jax.jit, static_argnames=("num_groups", "interpret"))
def grouped_sum_pallas(gids, values, mask, num_groups: int,
                       interpret: bool = False):
    """gids/values/mask: f32 arrays padded to BLOCK_ROWS multiples
    (mask 0 on padding). Returns [num_groups] sums."""
    from jax.experimental import pallas as pl
    n = gids.shape[0]
    grid = n // BLOCK_ROWS
    blk = pl.BlockSpec((BLOCK_ROWS,), _im1)
    g_pad = _pad_lanes(num_groups)
    partials = pl.pallas_call(
        partial(_grouped_kernel, num_groups=num_groups),
        grid=(grid,),
        in_specs=[blk, blk, blk],
        out_specs=pl.BlockSpec((SUBLANES, g_pad), _im2),
        out_shape=jax.ShapeDtypeStruct((grid * SUBLANES, g_pad),
                                       jnp.float32),
        interpret=interpret,
    )(gids, values, mask)
    return jnp.sum(partials[::SUBLANES, :num_groups], axis=0)


def grouped_sum(gids: np.ndarray, values: np.ndarray, mask: np.ndarray,
                num_groups: int, interpret: bool = False) -> np.ndarray:
    n = len(gids)
    padded = ((n + BLOCK_ROWS - 1) // BLOCK_ROWS) * BLOCK_ROWS

    def pad(a):
        out = np.zeros(padded, np.float32)
        out[:n] = a
        return jnp.asarray(out)

    return np.asarray(grouped_sum_pallas(
        pad(gids), pad(values), pad(mask.astype(np.float32)), num_groups,
        interpret=interpret))


# --------------------------------------------------------------------------
# The GENERIC pallas scan path: the engine's compiled WHERE/aggregate
# expressions (ops/expr.py emits plain jnp elementwise code, which
# traces inside a pallas kernel unchanged) fused into one hand-blocked
# kernel streaming each 4096-row block HBM -> VMEM once. Routed from
# ScanKernel.run behind the `tpu_pallas_scan` flag for aggregate
# queries whose columns are f32-exact (f32/f64/int32/bool) — the
# pallas compute is f32, so int64 keys/timestamps stay on the XLA
# path. Grouped queries use the one-hot MXU matmul per block.
# --------------------------------------------------------------------------
class PallasIneligible(Exception):
    pass


def build_generic_scan(where, agg_fns, group_cols, num_groups,
                       col_order, null_order, n_consts,
                       interpret: bool = False):
    """Returns jitted fn(consts_f32, cols..., nulls..., valid) ->
    (per-agg partials [grid] or [grid, G], count partials).

    agg_fns: [(op, compiled_expr_or_None)]; group_cols: GroupSpec cols
    tuple or None; col_order/null_order: cid tuples fixing ref order."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    from .expr import compile_expr
    where_fn = compile_expr(where) if where is not None else None
    n_cols, n_nulls = len(col_order), len(null_order)
    n_aggs = len(agg_fns)
    G = num_groups

    def kernel(consts_ref, *refs):
        col_refs = refs[:n_cols]
        null_refs = refs[n_cols:n_cols + n_nulls]
        valid_ref = refs[n_cols + n_nulls]
        out_refs = refs[n_cols + n_nulls + 1:]
        cols = {cid: col_refs[i][:] for i, cid in enumerate(col_order)}
        nulls = {cid: null_refs[i][:] > 0
                 for i, cid in enumerate(null_order)}
        consts = [consts_ref[i] for i in range(n_consts)]
        mask = valid_ref[:] > 0
        if where_fn is not None:
            wv, wn = where_fn(cols, nulls, consts)
            mask = mask & wv
            if wn is not None:
                mask = mask & jnp.logical_not(wn)
        maskf = mask.astype(jnp.float32)

        def put(ref, scalar):
            ref[...] = jnp.broadcast_to(scalar, (SUBLANES, LANES))

        if G is None:
            for oi, (op, f) in enumerate(agg_fns):
                if f is None:
                    put(out_refs[oi], jnp.sum(maskf))
                    continue
                v, vn = f(cols, nulls, consts)
                v = v.astype(jnp.float32)
                m = maskf if vn is None else \
                    maskf * jnp.logical_not(vn).astype(jnp.float32)
                if op == "count":
                    put(out_refs[oi], jnp.sum(m))
                elif op == "sum":
                    # where, not multiply: garbage on masked rows may
                    # be NaN and 0*NaN would poison the block partial
                    put(out_refs[oi], jnp.sum(
                        jnp.where(m > 0, v, jnp.float32(0))))
                elif op == "min":
                    put(out_refs[oi], jnp.min(
                        jnp.where(m > 0, v, jnp.float32(np.inf))))
                elif op == "max":
                    put(out_refs[oi], jnp.max(
                        jnp.where(m > 0, v, jnp.float32(-np.inf))))
            put(out_refs[n_aggs], jnp.sum(maskf))
            return
        # grouped: one-hot [B, G] matmul per block (MXU)
        gid = None
        stride = 1
        for cid, domain, offset in group_cols:
            gn = nulls.get(cid)
            if gn is not None:
                mask = mask & jnp.logical_not(gn)
            c = cols[cid].astype(jnp.float32) - offset
            # clip exactly like the XLA kernel: out-of-domain values
            # (stale ANALYZE stats) land in the edge bucket instead of
            # aliasing into another group's id
            c = jnp.clip(c, 0.0, float(domain - 1))
            gid = c * stride if gid is None else gid + c * stride
            stride *= domain
        maskf = mask.astype(jnp.float32)
        g_pad = _pad_lanes(G)
        # integer iota + i32 compare: tpu.iota is integer-only
        groups = jax.lax.broadcasted_iota(
            jnp.int32, (gid.shape[0], g_pad), 1)
        onehot = (gid.astype(jnp.int32)[:, None] == groups) \
            .astype(jnp.float32) * maskf[:, None]

        def put_g(ref, part):
            ref[...] = jnp.broadcast_to(part[None, :], (SUBLANES, g_pad))

        for oi, (op, f) in enumerate(agg_fns):
            if f is None:
                # mosaic has no int64 lanes; one block is <= 4096 rows
                # so the f32 one-hot count partial is exact, and the
                # host combines per-block partials in int64
                # analysis-ok(numeric_exactness): block-exact f32 count
                put_g(out_refs[oi], jnp.sum(onehot, axis=0))
                continue
            v, vn = f(cols, nulls, consts)
            v = v.astype(jnp.float32)
            oh = onehot if vn is None else \
                onehot * jnp.logical_not(vn).astype(jnp.float32)[:, None]
            if op == "count":
                # analysis-ok(numeric_exactness): block-exact f32 count
                put_g(out_refs[oi], jnp.sum(oh, axis=0))
            elif op == "sum":
                row_m = oh.max(axis=1)
                vm = jnp.where(row_m > 0, v, jnp.float32(0))
                # 2D lhs: Mosaic's dot lowering rejects rank-1 operands
                put_g(out_refs[oi], (vm[None, :] @ oh)[0])
            elif op == "min":
                put_g(out_refs[oi], jnp.min(jnp.where(
                    oh > 0, v[:, None], jnp.float32(np.inf)), axis=0))
            elif op == "max":
                put_g(out_refs[oi], jnp.max(jnp.where(
                    oh > 0, v[:, None], jnp.float32(-np.inf)), axis=0))
        # analysis-ok(numeric_exactness): block-exact f32 count
        put_g(out_refs[n_aggs], jnp.sum(onehot, axis=0))

    @partial(jax.jit, static_argnames=())
    def run(consts, col_arrs, null_arrs, valid):
        n = valid.shape[0]
        grid = n // BLOCK_ROWS
        blk = pl.BlockSpec((BLOCK_ROWS,), _im1)
        scalar_spec = pl.BlockSpec((max(n_consts, 1),), _im1_0,
                                   memory_space=pltpu.SMEM)
        if G is None:
            out_specs = tuple(
                pl.BlockSpec((SUBLANES, LANES), _im2)
                for _ in range(n_aggs + 1))
            out_shape = tuple(
                jax.ShapeDtypeStruct((grid * SUBLANES, LANES),
                                     jnp.float32)
                for _ in range(n_aggs + 1))
        else:
            g_pad = _pad_lanes(G)
            out_specs = tuple(
                pl.BlockSpec((SUBLANES, g_pad), _im2)
                for _ in range(n_aggs + 1))
            out_shape = tuple(
                jax.ShapeDtypeStruct((grid * SUBLANES, g_pad),
                                     jnp.float32)
                for _ in range(n_aggs + 1))
        outs = pl.pallas_call(
            kernel,
            grid=(grid,),
            in_specs=[scalar_spec] + [blk] * (n_cols + n_nulls + 1),
            out_specs=out_specs,
            out_shape=out_shape,
            interpret=interpret,
        )(consts, *col_arrs, *null_arrs, valid)
        # slice the tile-broadcast partials back to [grid] / [grid, G]
        # so the host reduce in ScanKernel._try_pallas is layout-blind
        if G is None:
            return tuple(o[::SUBLANES, 0] for o in outs)
        return tuple(o[::SUBLANES, :G] for o in outs)
    return run
