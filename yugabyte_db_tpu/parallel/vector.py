"""Sharded vector search: base vectors split across the mesh, per-shard
top-k, then a gather+re-rank — model-parallel ANN over ICI."""
from __future__ import annotations

from functools import partial
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from .mesh import BLOCKS_AXIS, TABLETS_AXIS, TabletMesh


def sharded_exact_search(tm: TabletMesh, queries: np.ndarray,
                         base_sharded: jnp.ndarray, k: int
                         ) -> Tuple[np.ndarray, np.ndarray]:
    """base_sharded: [S, N_shard, D] sharded over (tablets, blocks) as
    [T, B, N, D]. Returns global (distances [Q, k], indices [Q, k]) where
    indices are global row ids (shard_offset + local)."""
    T, B = tm.num_tablet_shards, tm.num_block_shards
    n_shard = base_sharded.shape[1]      # [S, N_shard, D] input

    def shard_fn(q, base):
        b = base.reshape(base.shape[-2], base.shape[-1])
        d = (jnp.sum(q ** 2, axis=1, keepdims=True)
             + jnp.sum(b.astype(jnp.float32) ** 2, axis=1)[None, :]
             - 2.0 * jax.lax.dot_general(
                 q.astype(jnp.bfloat16), b.astype(jnp.bfloat16),
                 (((1,), (1,)), ((), ())),
                 preferred_element_type=jnp.float32))
        d = jnp.maximum(d, 0.0)   # bf16 rounding can push |q-b|^2 below 0
        neg, idx = jax.lax.top_k(-d, k)
        ti = jax.lax.axis_index(TABLETS_AXIS)
        bi = jax.lax.axis_index(BLOCKS_AXIS)
        shard_id = ti * B + bi
        gidx = idx + shard_id * n_shard
        # gather all shards' candidates
        alld = jax.lax.all_gather(-neg, TABLETS_AXIS)
        alld = jax.lax.all_gather(alld, BLOCKS_AXIS)     # [B, T, Q, k]
        alli = jax.lax.all_gather(gidx, TABLETS_AXIS)
        alli = jax.lax.all_gather(alli, BLOCKS_AXIS)
        Q = q.shape[0]
        alld = jnp.moveaxis(alld.reshape(T * B, Q, k), 0, 1).reshape(Q, -1)
        alli = jnp.moveaxis(alli.reshape(T * B, Q, k), 0, 1).reshape(Q, -1)
        neg2, pos = jax.lax.top_k(-alld, k)
        return -neg2, jnp.take_along_axis(alli, pos, axis=1)

    fn = jax.jit(jax.shard_map(
        shard_fn, mesh=tm.mesh,
        in_specs=(P(), P(TABLETS_AXIS, BLOCKS_AXIS, None, None)),
        out_specs=(P(), P()), check_vma=False))
    d, i = fn(jnp.asarray(queries, jnp.float32),
              base_sharded.reshape(T, B, n_shard, -1))
    return np.asarray(d), np.asarray(i)


def sharded_ann_search(queries: np.ndarray, indexes, k: int,
                       **params) -> Tuple[np.ndarray, np.ndarray]:
    """Sharded search across per-shard ANN indexes (any registry
    method — the index-aware twin of sharded_exact_search's all_gather
    merge): per-shard top-k through each AnnIndex, then one host-side
    gather + re-rank with ids offset into the global row space
    (shard s owns ids [sum(sizes[:s]), sum(sizes[:s+1]))).  Shards may
    mix methods (an IVF shard next to an HNSW shard) — the merge only
    sees (distance, global id) pairs."""
    q = np.asarray(queries, np.float32)
    if q.ndim == 1:
        q = q[None, :]
    all_d = []
    all_i = []
    offset = 0
    for idx in indexes:
        d, i = idx.search(q, k=min(k, max(idx.size, 1)), **params)
        gi = np.where(i >= 0, i + offset, -1)
        all_d.append(np.asarray(d, np.float32))
        all_i.append(gi.astype(np.int64))
        offset += idx.size
    if not all_d:
        return (np.full((len(q), k), np.inf, np.float32),
                np.full((len(q), k), -1, np.int64))
    from ..vector.registry import merge_topk
    return merge_topk(np.concatenate(all_d, axis=1),
                      np.concatenate(all_i, axis=1), k)
