"""Stable lexicographic row order from single-key sort passes.

Callers: the sort-grouped scan (`HashGroupSpec`, ops/scan.py) and the
compaction merge (ops/compaction.py).  MVCC no longer sorts: a
multi-version batch carries its versions' links from the host
(ops/device_batch.py link_versions) and the scan's mask is elementwise.

The TPU compiler's time over `lax.sort` grows steeply with the number
of sort keys (the comparator is inlined into every stage of the sort
network): a 4-key sort and the (key words + 2)-key compaction merge
each took it minutes per row bucket.  A single u32 key plus the
carried permutation compiles in seconds, so the multi-key order is
built the radix way — one stable single-key pass per 32-bit word,
least significant word first — inside a `fori_loop`, which keeps ONE
sort instance in the program however many words the key has.

The result is exactly the permutation a stable multi-key `lax.sort`
returns for an iota payload: ties keep their original relative order.
"""
from __future__ import annotations

from typing import Sequence

import jax
import jax.numpy as jnp


def _u32_words(k):
    """Order-preserving split of one key column into unsigned 32-bit
    words, most significant first, under `lax.sort`'s own order: signed
    integers flip the sign bit; floats compare in IEEE total order after
    -0 -> +0 and NaN -> the canonical NaN (which sorts last)."""
    if k.dtype == jnp.bool_:
        return [k.astype(jnp.uint32)]
    bits = k.dtype.itemsize * 8
    top = jnp.dtype(f"uint{bits}").type(1 << (bits - 1))
    if jnp.issubdtype(k.dtype, jnp.floating):
        k = jnp.where(k == 0, jnp.zeros_like(k), k)
        k = jnp.where(jnp.isnan(k), jnp.full_like(k, jnp.nan), k)
        u = k.view(top.dtype)
        u = jnp.where(u >= top, ~u, u | top)
    elif jnp.issubdtype(k.dtype, jnp.signedinteger):
        u = k.view(top.dtype) ^ top
    elif jnp.issubdtype(k.dtype, jnp.unsignedinteger):
        u = k
    else:
        raise TypeError(f"lex_order: unsupported key dtype {k.dtype}")
    if bits == 64:
        return [(u >> jnp.uint64(32)).astype(jnp.uint32),
                u.astype(jnp.uint32)]
    return [u.astype(jnp.uint32)]


def lex_order(keys: Sequence[jnp.ndarray]) -> jnp.ndarray:
    """int32 permutation that sorts rows ascending by `keys` (integer,
    float or bool columns, most significant first), stable — identical to
    ``lax.sort(keys + (iota,), num_keys=len(keys))[-1]``."""
    words = [w for k in keys for w in _u32_words(k)]
    stack = jnp.stack(words[::-1])          # least significant first
    n = stack.shape[1]

    def one_pass(i, perm):
        return jax.lax.sort((stack[i][perm], perm), num_keys=1)[1]

    return jax.lax.fori_loop(0, len(words), one_pass,
                             jnp.arange(n, dtype=jnp.int32))
