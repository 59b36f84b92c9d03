#!/usr/bin/env python
"""CLI for the static-analysis framework.

    python tools/analyze/run.py                    # all passes, human
    python tools/analyze/run.py --json             # machine schema
    python tools/analyze/run.py --sarif out.sarif  # SARIF 2.1.0 file
    python tools/analyze/run.py --pass jit_hazards --pass flag_drift
    python tools/analyze/run.py yugabyte_db_tpu/sched   # narrower roots
    python tools/analyze/run.py --changed origin/main..HEAD   # CI mode

Exit status: 1 when any unsuppressed finding exists, else 0 (2 on a
bad --changed range).

Incremental modes (``--staged`` for the pre-commit hook, ``--changed
<git-range>`` for CI / pre-push) still analyze the WHOLE tree — the
interprocedural passes need every caller — but report only findings
in the staged/changed files.  Repeat runs stay cheap because the call
graph's per-file facts persist under ``.analyze_cache/`` keyed on
(path, mtime, size); ``--no-cache`` opts out.

The ``--json`` schema (consumed by tests/test_analysis.py):

    {"passes": [{"id", "title", "findings": N, "suppressed": N,
                 "wall_ms": F}],
     "findings": [{"path", "line", "pass", "message", "detail",
                   "hint"}],
     "suppressions": {pass_id: N},
     "total_findings": N, "total_suppressed": N, "wall_ms": F,
     "parse_errors": [{"path", "error"}]}

``--sarif <path>`` additionally writes the unsuppressed findings as a
single-run SARIF 2.1.0 log (rules = the executed passes, ruleId = the
pass id, the pass hint as the rule help text) so CI code-scanning
uploads can annotate the diff; it composes with every other mode and
does not change the exit status.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

_HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(_HERE))       # tools/ -> `analyze`

from analyze import ALL_PASSES, ProjectIndex, get_pass, run_analysis  # noqa: E402
from analyze.core import DEFAULT_ROOTS  # noqa: E402


def _staged_files(base: str) -> list:
    """Repo-relative paths staged for commit (added/copied/modified/
    renamed — deletions have nothing to analyze)."""
    import subprocess
    try:
        r = subprocess.run(
            ["git", "diff", "--cached", "--name-only",
             "--diff-filter=ACMR"],
            cwd=base, capture_output=True, text=True, timeout=30,
            check=True)
    except (OSError, subprocess.SubprocessError):
        return []
    return [ln.strip() for ln in r.stdout.splitlines() if ln.strip()]


def _changed_files(base: str, git_range: str):
    """Repo-relative paths changed across ``git_range`` (committed
    AND working-tree edits — `run.py --changed origin/main` right
    before committing sees what the commit will contain).  Returns
    None when git cannot resolve the range."""
    import subprocess
    try:
        r = subprocess.run(
            ["git", "diff", "--name-only", "--diff-filter=ACMR",
             git_range, "--"],
            cwd=base, capture_output=True, text=True, timeout=30,
            check=True)
    except (OSError, subprocess.SubprocessError):
        return None
    return [ln.strip() for ln in r.stdout.splitlines() if ln.strip()]


def _index_content(base: str, rel: str):
    """The staged (index) content of `rel`, or None when unreadable."""
    import subprocess
    try:
        r = subprocess.run(["git", "show", f":{rel}"], cwd=base,
                           capture_output=True, timeout=30, check=True)
    except (OSError, subprocess.SubprocessError):
        return None
    return r.stdout.decode("utf-8", "replace")


def _sarif_log(report: dict, passes) -> dict:
    """The report as a one-run SARIF 2.1.0 log.  Pass ids become rule
    ids (hint text as the rule help); parse errors ship as tool
    notifications so an upload still shows WHY coverage shrank."""
    by_id = {p.id: p for p in passes}
    rules = [{
        "id": pid,
        "name": pid,
        "shortDescription": {"text": by_id[pid].title},
        "help": {"text": by_id[pid].hint},
        "defaultConfiguration": {"level": "error"},
    } for pid in sorted(by_id)]
    rule_index = {r["id"]: i for i, r in enumerate(rules)}
    results = [{
        "ruleId": f["pass"],
        "ruleIndex": rule_index[f["pass"]],
        "level": "error",
        "message": {"text": f["message"]},
        "locations": [{
            "physicalLocation": {
                "artifactLocation": {"uri": f["path"],
                                     "uriBaseId": "SRCROOT"},
                "region": {"startLine": max(1, f["line"])},
            },
        }],
    } for f in report["findings"]]
    notifications = [{
        "level": "error",
        "message": {"text": f"parse error: {e['error']}"},
        "locations": [{
            "physicalLocation": {
                "artifactLocation": {"uri": e["path"],
                                     "uriBaseId": "SRCROOT"},
            },
        }],
    } for e in report["parse_errors"]]
    return {
        "$schema": ("https://raw.githubusercontent.com/oasis-tcs/"
                    "sarif-spec/master/Schemata/sarif-schema-2.1.0.json"),
        "version": "2.1.0",
        "runs": [{
            "tool": {"driver": {
                "name": "yugabyte-tpu-analyze",
                "informationUri": "tools/analyze/run.py",
                "rules": rules,
            }},
            "invocations": [{
                "executionSuccessful": True,
                "toolExecutionNotifications": notifications,
            }],
            "originalUriBaseIds": {"SRCROOT": {"uri": "file:///"}},
            "results": results,
        }],
    }


def _write_sarif(path: str, log: dict) -> None:
    if path == "-":
        print(json.dumps(log))
        return
    with open(path, "w", encoding="utf-8") as fh:
        json.dump(log, fh, indent=2)
        fh.write("\n")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="multi-pass static analysis for event-loop, "
                    "JAX-kernel and concurrency hazards")
    ap.add_argument("roots", nargs="*", default=list(DEFAULT_ROOTS),
                    help="analysis roots relative to the repo "
                         "(default: %s)" % (DEFAULT_ROOTS,))
    ap.add_argument("--json", action="store_true", dest="as_json",
                    help="emit the machine schema on stdout")
    ap.add_argument("--sarif", metavar="PATH",
                    help="also write unsuppressed findings as a SARIF "
                         "2.1.0 log to PATH (ruleId = pass id; '-' "
                         "for stdout)")
    ap.add_argument("--pass", action="append", dest="passes", default=[],
                    metavar="ID", help="run only this pass (repeatable)")
    ap.add_argument("--base", default=os.path.dirname(os.path.dirname(_HERE)),
                    help="repo root (default: two levels up)")
    ap.add_argument("--staged", action="store_true",
                    help="analyze only git-staged .py files inside the "
                         "default analysis roots (the pre-commit hook "
                         "mode; exits 0 when nothing relevant is "
                         "staged)")
    ap.add_argument("--changed", metavar="GIT-RANGE",
                    help="report only findings in files changed across "
                         "this git range (e.g. origin/main..HEAD); the "
                         "index still covers the whole tree so "
                         "interprocedural findings stay sound")
    ap.add_argument("--no-cache", action="store_true",
                    help="skip the persisted .analyze_cache/ facts "
                         "cache (forces a full re-parse)")
    args = ap.parse_args(argv)

    passes = ([get_pass(p) for p in args.passes] if args.passes
              else list(ALL_PASSES))
    roots = args.roots
    focus = None        # report-only file set (staged/changed modes)
    focus_label = None
    if args.staged:
        focus = {f for f in _staged_files(args.base)
                 if f.endswith(".py")
                 and any(f == r or f.startswith(r.rstrip("/") + "/")
                         for r in DEFAULT_ROOTS)}
        focus_label = "staged"
    elif args.changed:
        changed = _changed_files(args.base, args.changed)
        if changed is None:
            print(f"analyze --changed: git could not resolve range "
                  f"{args.changed!r}", file=sys.stderr)
            return 2
        focus = {f for f in changed
                 if f.endswith(".py")
                 and any(f == r or f.startswith(r.rstrip("/") + "/")
                         for r in DEFAULT_ROOTS)}
        focus_label = f"changed in {args.changed}"
    if focus is not None:
        if not focus:
            if args.sarif:
                _write_sarif(args.sarif, _sarif_log(
                    {"findings": [], "parse_errors": []}, passes))
            if args.as_json:
                print(json.dumps({"passes": [], "findings": [],
                                  "suppressions": {}, "total_findings": 0,
                                  "total_suppressed": 0, "wall_ms": 0.0,
                                  "parse_errors": []}))
            else:
                print(f"analyze: no {focus_label} files under "
                      f"{DEFAULT_ROOTS}; nothing to check")
            return 0
        # whole-program passes (flag_drift's defs-vs-reads join, the
        # call graph) are only meaningful over the full roots: analyze
        # EVERYTHING, then gate on findings in the focus files alone
        roots = list(DEFAULT_ROOTS)
    # staged files are analyzed at their INDEX content, not the working
    # tree — a partially staged file is checked against the bytes that
    # will actually land in the commit.  --changed deliberately reads
    # the CHECKOUT: in CI the checkout IS the range head; a local
    # pre-push from a dirty tree is told about the hazards as they
    # stand now (the next push re-checks whatever actually lands)
    overlay = {rel: src for rel in (focus if args.staged else ())
               if (src := _index_content(args.base, rel)) is not None}
    cache_dir = None if args.no_cache else os.path.join(
        args.base, ".analyze_cache")
    index = ProjectIndex(args.base, roots=roots, overlay=overlay,
                         cache_dir=cache_dir)
    report = run_analysis(index, passes)
    if focus is not None:
        report["findings"] = [f for f in report["findings"]
                              if f["path"] in focus]
        report["parse_errors"] = [e for e in report["parse_errors"]
                                  if e["path"] in focus]
        report["total_findings"] = len(report["findings"])

    if args.sarif:
        _write_sarif(args.sarif, _sarif_log(report, passes))
    if args.as_json:
        print(json.dumps(report))
    else:
        for f in report["findings"]:
            h = f"  [fix: {f['hint']}]" if f["hint"] else ""
            print(f"{f['path']}:{f['line']}: [{f['pass']}] "
                  f"{f['message']}{h}")
        for e in report["parse_errors"]:
            print(f"{e['path']}: PARSE ERROR {e['error']}")
        tally = ", ".join(
            f"{p['id']}: {p['findings']} finding(s), {p['suppressed']} "
            f"suppressed, {p['wall_ms']:.0f}ms"
            for p in report["passes"])
        print(f"-- {tally}")
        print(f"-- total: {report['total_findings']} finding(s), "
              f"{report['total_suppressed']} suppressed, "
              f"{report['wall_ms']:.0f}ms")
    return 1 if (report["total_findings"]
                 or report["parse_errors"]) else 0


if __name__ == "__main__":
    sys.exit(main())
