"""Test configuration: force JAX onto a virtual 8-device CPU mesh so
multi-chip sharding paths are exercised without TPU hardware (the driver
separately dry-runs them; see __graft_entry__.dryrun_multichip).

The platform is forced through jax.config, before any backend
initialization, so it holds whatever JAX_PLATFORMS the environment set.
"""
import os

_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_enable_x64", True)

# the package keeps the persistent XLA compile cache off where the
# platform was forced to cpu (XLA:CPU AOT entries can fail the loader's
# machine check); make the choice visible to yugabyte_db_tpu/__init__.py
# before its import.
os.environ.setdefault("YBTPU_PLATFORM", "cpu")

# state-invariant sanitizer (utils/sanitizer.py — the TSAN/DCHECK-build
# analog): every MiniCluster shutdown sweeps claims-vs-intents,
# read-lock symmetry, memtable probe guards, and manifest consistency,
# so every test drive doubles as an invariant check
os.environ.setdefault("YBTPU_SANITIZE", "1")
