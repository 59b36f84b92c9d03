"""Host fingerprint for the auto-built native libraries' file names.

The native .so files are built with -march=native, so each is only
valid on hosts with the same CPU feature set. Benchmark/CI environments
copy the repo directory (ignored build products included) across
machines, and loading code compiled for another host ranges from silent
slowdowns to SIGILL. Keying the file name by a hash of the CPU identity
makes a foreign artifact invisible rather than load-then-crash: the new
host just rebuilds into its own name. (The XLA compile cache is NOT
keyed this way: TPU executables do not depend on the host's CPU.)

Stdlib-only and import-cycle-free.
"""
from __future__ import annotations

import hashlib
import platform

_FP: str | None = None


def host_fingerprint() -> str:
    """Short stable hash of (arch, CPU model, CPU feature flags)."""
    global _FP
    if _FP is None:
        parts = [platform.machine(), platform.system()]
        # one line per key covers the feature set compilers specialize
        # for: x86 exposes "model name"/"flags"; ARM exposes
        # "CPU implementer"/"CPU part"/"Features" instead
        want = ("model name", "flags", "Features", "CPU part",
                "CPU implementer")
        try:
            with open("/proc/cpuinfo") as f:
                seen = set()
                for line in f:
                    key = line.split(":", 1)[0].strip()
                    if key in want and key not in seen:
                        seen.add(key)
                        parts.append(line.strip())
        except OSError:
            pass            # non-Linux: arch alone still partitions
        _FP = hashlib.blake2b(
            "\n".join(parts).encode(), digest_size=6).hexdigest()
    return _FP
