"""tools/analyze — multi-pass static analysis for event-loop, JAX-kernel
and concurrency hazards.

The hazard classes this repo keeps re-growing are mechanical and
AST-checkable: a blocking call or lock-held ``await`` on the one event
loop freezes admission and Raft heartbeats for the whole server; a
host-sync or shape-dependent branch inside a jitted kernel silently
destroys the compile-once property the served path depends on; a flag
that drifts between definition and use lies to operators; an attribute
mutated from both an executor thread and the event loop is a data race.

Layout:

- ``core``       shared walker (one parse per file), findings model,
                 the ``analysis-ok(<pass>): <reason>`` suppression
                 grammar (``blocking-ok`` kept as an alias), runner
                 with per-pass wall time.
- ``passes/``    one module per pass; ``passes.ALL_PASSES`` is the
                 registry.
- ``run``        CLI: human output or ``--json`` (schema consumed by
                 tests/test_analysis.py).

See ANALYSIS.md at the repo root for the pass catalog, the suppression
grammar, and how to add a pass.
"""
from .core import (AnalysisPass, Finding, ModuleInfo, ProjectIndex,
                   is_suppressed, run_analysis)
from .passes import ALL_PASSES, get_pass

__all__ = ["AnalysisPass", "Finding", "ModuleInfo", "ProjectIndex",
           "is_suppressed", "run_analysis", "ALL_PASSES", "get_pass"]
