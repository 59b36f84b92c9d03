"""yugabyte_db_tpu — a TPU-native distributed SQL database.

A from-scratch implementation of YugabyteDB's capability surface
(reference: /root/reference, see /root/repo/SURVEY.md), re-architected
TPU-first:

- Control plane (Raft consensus, WAL, tablet lifecycle, master/catalog,
  RPC) is host-side code with the same seams as the reference
  (`src/yb/consensus/`, `src/yb/master/`, `src/yb/rpc/`).
- Data-plane hot loops — scan/filter/aggregate execution (reference:
  `src/yb/docdb/pgsql_operation.cc:2790` ExecuteScalar) and LSM
  compaction merge + MVCC GC (reference:
  `src/yb/rocksdb/db/compaction_job.cc:665`,
  `src/yb/docdb/docdb_compaction_context.cc:783`) — run as JAX/XLA
  kernels on TPU, behind a runtime flag (`tpu_pushdown_enabled`).
- Storage blocks are columnar from day one so device decode is a
  reinterpret + reshape, not a row loop.

Package layout:
  utils/      Status/Result, hybrid time (HLC), flags, metrics, trace
  dockv/      doc key / value encoding, packed rows, partitions
  storage/    LSM: memtable, SSTables (columnar blocks), merge, compaction
  docdb/      MVCC document store: read/write paths, intents, conflicts
  ops/        JAX kernels: scan/filter/aggregate, compaction merge, vector
  parallel/   device mesh, shard_map distributed scan, psum combine
  consensus/  per-tablet Raft + replicated log (the WAL)
  tablet/     tablet core, peers, operations, bootstrap, snapshots, txns
  tserver/    data node: tablet service, read path driver, heartbeater
  master/     control plane: sys catalog, catalog manager, load balancer
  client/     cluster client: meta cache, batcher, transactions
  rpc/        async RPC framework (asyncio reactors, binary framing)
  ql/         query layers: YSQL-subset SQL, YCQL, Redis
  models/     end-to-end engine pipelines (benchmark workloads, flagship
              scan models used by __graft_entry__)
  tools/      admin CLI, local cluster launcher
"""

__version__ = "0.1.0"

# Hybrid times and key hashes are 64-bit; JAX must carry u64 end-to-end.
# (TPU emulates 64-bit integer ops; the scan kernels only use them for
# visibility compares, which are negligible next to the f32 aggregate work.)
import os as _os  # noqa: E402

import jax as _jax  # noqa: E402

_jax.config.update("jax_enable_x64", True)

# Operator platform override: the environment may preset a platform via
# JAX_PLATFORMS before process start; YBTPU_PLATFORM lets servers, tools
# and the tests force e.g. cpu regardless.
if _os.environ.get("YBTPU_PLATFORM"):
    _jax.config.update("jax_platforms", _os.environ["YBTPU_PLATFORM"])

# Persistent XLA compilation cache: the TPU compiler takes tens of
# seconds over each sort-bearing kernel, so compiled programs are kept
# across processes.  Where JAX_COMPILATION_CACHE_DIR is set, JAX reads
# it itself and no directory is set here; otherwise the cache lives at
# the fixed path <checkout>/.jax_cache (the path is part of the cache
# key, so it must not move).  The cache stays off only where the
# platform was forced to cpu: CPU compiles are fast, and XLA:CPU AOT
# entries embed tuning pseudo-features that fail the loader's machine
# check even on the host that wrote them.
_forced = (_os.environ.get("YBTPU_PLATFORM")
           or _os.environ.get("JAX_PLATFORMS", "")).lower()
if _forced != "cpu":
    if not _os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        _jax.config.update(
            "jax_compilation_cache_dir",
            _os.path.join(_os.path.dirname(_os.path.dirname(
                _os.path.abspath(__file__))), ".jax_cache"))
    _jax.config.update("jax_persistent_cache_min_compile_time_secs", 1.0)
