"""tools/analyze/ wired into tier-1.

Four layers:

1. PASS FIXTURES — for each pass: a true positive the pass must
   catch, the same hazard suppressed with a reasoned annotation, and a
   clean negative that must NOT fire (the negatives encode the idioms
   the real tree depends on — `.shape` math inside jit bodies,
   executor-target sync defs, async-with on asyncio locks).  The
   interprocedural passes add a TRANSITIVE triple each (hazard behind
   a helper), plus the pre-fix product shapes the engine was built to
   catch (master._persist's fsync under an async commit).
2. CALL GRAPH — the shared interprocedural layer's own contract:
   alias chains, method resolution across (multi-module) inheritance,
   recursion termination, and the persisted facts-cache speedup.
3. WHOLE-TREE — the real `yugabyte_db_tpu/` must produce ZERO
   unannotated findings, so any new hazard is a failing build from the
   day the pass shipped.
4. CONTRACTS — the run.py --json schema (pass ids, counts, findings,
   suppression tally, per-pass wall time), the --changed incremental
   mode, the suppression tally held to baseline.json, and the
   wall-time budget that keeps the sweep from bloating tier-1.
"""
import json
import os
import subprocess
import sys
import textwrap

import pytest

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(HERE, "tools"))
from analyze import (ALL_PASSES, ProjectIndex, get_pass,  # noqa: E402
                     run_analysis)

#: generous ceiling for the whole five-pass sweep over the full tree —
#: the sweep measures ~2-6s here; the budget exists so a pass that goes
#: accidentally quadratic fails tier-1 instead of eating the 870s cap.
WALL_BUDGET_MS = 60_000


def _run(tmp_path, files, pass_id):
    for rel, src in files.items():
        p = tmp_path / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(textwrap.dedent(src))
    index = ProjectIndex(str(tmp_path), roots=("pkg",))
    return run_analysis(index, [get_pass(pass_id)])


def _findings(report):
    return [(f["path"], f["line"], f["detail"]) for f in report["findings"]]


# --- 1. per-pass fixtures --------------------------------------------------

class TestAsyncBlocking:
    def test_true_positive(self, tmp_path):
        r = _run(tmp_path, {"pkg/a.py": """\
            import time, os, subprocess
            async def handler():
                time.sleep(1)
                os.fsync(3)
                subprocess.run(["ls"])
            """}, "async_blocking")
        assert sorted(d for _, _, d in _findings(r)) == [
            "os.fsync", "subprocess.run", "time.sleep"]

    def test_suppressed_with_reason(self, tmp_path):
        r = _run(tmp_path, {"pkg/a.py": """\
            import time
            async def handler():
                time.sleep(1)   # analysis-ok(async_blocking): test stall
                time.sleep(2)   # blocking-ok: legacy alias honored
            """}, "async_blocking")
        assert r["findings"] == []
        assert r["suppressions"]["async_blocking"] == 2

    def test_bare_marker_suppresses_nothing(self, tmp_path):
        r = _run(tmp_path, {"pkg/a.py": """\
            import time
            async def handler():
                time.sleep(1)   # analysis-ok(async_blocking):
            """}, "async_blocking")
        assert len(r["findings"]) == 1   # reason is mandatory

    def test_clean_negative(self, tmp_path):
        r = _run(tmp_path, {"pkg/a.py": """\
            import asyncio, time
            def sync_helper():
                time.sleep(1)            # sync context: fine
            async def handler():
                await asyncio.sleep(1)   # the correct spelling
                def executor_target():
                    time.sleep(1)        # nested sync def: fine
                return executor_target
            """}, "async_blocking")
        assert r["findings"] == []


class TestLockHeldAwait:
    def test_true_positive(self, tmp_path):
        r = _run(tmp_path, {"pkg/a.py": """\
            import threading
            class C:
                def __init__(self):
                    self._lock = threading.Lock()
                async def work(self):
                    with self._lock:
                        await self.other()
            """}, "lock_held_await")
        assert [(l, d) for _, l, d in _findings(r)] == [(7, "self._lock")]

    def test_suppressed_with_reason(self, tmp_path):
        r = _run(tmp_path, {"pkg/a.py": """\
            class C:
                async def work(self):
                    with self._lock:
                        # analysis-ok(lock_held_await): lock-free await
                        await self.other()
            """}, "lock_held_await")
        assert r["findings"] == []
        assert r["suppressions"]["lock_held_await"] == 1

    def test_clean_negative(self, tmp_path):
        r = _run(tmp_path, {"pkg/a.py": """\
            class C:
                async def work(self):
                    async with self._alock:     # asyncio lock: fine
                        await self.other()
                    with self._lock:
                        self.x = 1              # no await held: fine
                    with self._lock:
                        def helper():           # nested def: its own
                            pass                # awaits, its own locks
                    await self.other()
            """}, "lock_held_await")
        assert r["findings"] == []


class TestJitHazards:
    def test_true_positives(self, tmp_path):
        r = _run(tmp_path, {"pkg/a.py": """\
            import jax, numpy as np
            import jax.numpy as jnp
            from functools import partial
            @partial(jax.jit, static_argnames=("k",))
            def kern(x, y, k):
                if y > 0:                 # python branch on traced
                    x = x + 1
                v = float(x)              # host cast
                w = np.asarray(y)         # host numpy mid-trace
                s = x.sum().item()        # host sync
                return x + k
            def driver(a):
                return kern(jnp.zeros(50000), a, k=4)   # literal shape
            """}, "jit_hazards")
        details = sorted(d for _, _, d in _findings(r))
        assert details == ["kern:float", "kern:if", "kern:item",
                           "kern:jnp.zeros", "kern:np.asarray"]

    def test_suppressed_with_reason(self, tmp_path):
        r = _run(tmp_path, {"pkg/a.py": """\
            import jax
            @jax.jit
            def kern(x):
                if x > 0:  # analysis-ok(jit_hazards): proven static
                    return x
                return -x
            """}, "jit_hazards")
        assert r["findings"] == []
        assert r["suppressions"]["jit_hazards"] == 1

    def test_clean_negative_shape_math_untaints(self, tmp_path):
        # the exact idiom ops/compaction.py + vector/ivf.py live on:
        # .shape unpacking yields static python ints, branches and
        # range() over them are fine, as is jax.jit-by-assignment
        r = _run(tmp_path, {"pkg/a.py": """\
            import jax
            import jax.numpy as jnp
            from functools import partial
            @partial(jax.jit, static_argnames=("num_words",))
            def kern(words, ht, num_words):
                n = words.shape[0]
                ops = tuple(words[:, i] for i in range(1, num_words))
                if num_words > 2:            # static arg: fine
                    ht = ht + 1
                first = jnp.where(ht > 0, words[:, 0], jnp.uint64(0))
                c = min(num_words, n)        # static math: fine
                return first, ops, c
            def _raw(x):
                m = x.shape[1]
                return x.reshape(x.shape[0] * m)
            fn = jax.jit(_raw)
            def debug_path():
                # direct raw call runs EAGERLY — no compile, no trap
                return _raw(jnp.zeros((4, 500)))
            class Unrelated:
                def kern(self):          # leaf-name collision: fine
                    return jnp.ones(128)
            """}, "jit_hazards")
        assert r["findings"] == []

    def test_grouped_scatter_idiom_clean(self, tmp_path):
        # the grouped-aggregation kernel's segment-sum/scatter-add shape
        # (ops/grouped_scan.grouped_reduce): group-slot count S is a
        # STATIC pow2 (part of the signature — branching on it is
        # fine), dictionary domain sizes arrive as TRACED scalars and
        # only ever feed jnp arithmetic, the spill count leaves the
        # kernel as an output instead of steering trace-time control
        # flow
        r = _run(tmp_path, {"pkg/a.py": """\
            import jax
            import jax.numpy as jnp
            from functools import partial
            @partial(jax.jit, static_argnames=("S",))
            def grouped(codes, vals, mask, domains, S):
                gid = None
                stride = jnp.int64(1)
                for i in range(len(domains)):   # static arity: fine
                    c = codes.astype(jnp.int64)
                    gid = c * stride if gid is None else gid + c * stride
                    stride = stride * domains[i].astype(jnp.int64)
                spill_slot = S - 1           # static math on S: fine
                in_range = gid < spill_slot
                spilled = jnp.sum(mask & jnp.logical_not(in_range))
                gid_c = jnp.where(mask & in_range, gid,
                                  spill_slot).astype(jnp.int32)
                out = jnp.zeros(S, jnp.int64).at[gid_c].add(
                    jnp.where(mask, vals, 0))
                if S > 4:                    # static branch: fine
                    out = out + 0
                return out, spilled
            """}, "jit_hazards")
        assert r["findings"] == []

    def test_grouped_scatter_idiom_true_positives(self, tmp_path):
        # the shapes the grouped kernel must NEVER take: the traced
        # spill count / domain product steering Python control flow, or
        # a host round-trip mid-trace to size the slot array
        r = _run(tmp_path, {"pkg/a.py": """\
            import jax
            import jax.numpy as jnp
            @jax.jit
            def bad_grouped(codes, vals, mask, dom):
                prod = dom * 2
                if prod > 4096:            # python branch on traced
                    return jnp.zeros(1, jnp.int64), jnp.int64(0)
                spilled = jnp.sum(mask)
                n = int(spilled)           # host cast of traced count
                gid = codes.astype(jnp.int32)
                out = jnp.zeros(4096, jnp.int64).at[gid].add(
                    jnp.where(mask, vals, 0))
                while spilled > 0:         # python loop on traced
                    spilled = spilled - 1
                return out, spilled
            """}, "jit_hazards")
        details = sorted(d for _, _, d in _findings(r))
        assert details == ["bad_grouped:if", "bad_grouped:int",
                           "bad_grouped:while"]


class TestJitHazardsJoinWindow:
    """The join build/probe and window segment-scan idioms
    (ops/join_scan.probe_table, ops/window_scan kernels): table size
    static per pow2 bucket, the true build count a traced runtime
    scalar, chain-walking via lax.while_loop — and the shapes those
    kernels must NEVER take."""

    def test_join_probe_idiom_clean(self, tmp_path):
        r = _run(tmp_path, {"pkg/a.py": """\
            import jax
            import jax.numpy as jnp
            from functools import partial
            @partial(jax.jit, static_argnames=("num_slots",))
            def probe(pk, table_used, table_key, table_val, n_build,
                      num_slots):
                bits = num_slots.bit_length() - 1    # static math: fine
                mask = num_slots - 1
                k64 = pk.astype(jnp.int64)
                h = k64.astype(jnp.uint64) * jnp.uint64(0x9E3779B97F4A7C15)
                slot = (h >> jnp.uint64(64 - bits)).astype(jnp.int32)
                n = pk.shape[0]                      # static shape: fine
                midx0 = jnp.full(n, -1, jnp.int32)
                done0 = jnp.zeros(n, bool)
                def cond(state):
                    _, _, done = state
                    return jnp.logical_not(jnp.all(done))
                def body(state):
                    slot, midx, done = state
                    tk = table_key[slot]
                    hit = table_used[slot] & (tk == k64) & ~done
                    stop = ~table_used[slot] & ~done
                    midx = jnp.where(hit, table_val[slot], midx)
                    done = done | hit | stop
                    slot = jnp.where(done, slot, (slot + 1) & mask)
                    return slot, midx, done
                _, midx, _ = jax.lax.while_loop(cond, body,
                                                (slot, midx0, done0))
                # the runtime build count guards matches as ARITHMETIC,
                # never as Python control flow
                return jnp.where(midx < n_build, midx, -1)
            """}, "jit_hazards")
        assert r["findings"] == []

    def test_join_probe_idiom_true_positives(self, tmp_path):
        # the shapes the probe must never take: a Python while over the
        # traced done-mask, a host cast of the traced build count, and
        # a literal-shaped table at the jitted call site
        r = _run(tmp_path, {"pkg/a.py": """\
            import jax
            import jax.numpy as jnp
            @jax.jit
            def bad_probe(pk, table_key, n_build):
                done = table_key[pk] == pk
                while not done.all():      # python loop on traced
                    done = done | (table_key[pk] == pk)
                nb = int(n_build)          # host cast of traced count
                return done, nb
            def driver(pk, n_build):
                return bad_probe(pk, jnp.zeros(65536), n_build)
            """}, "jit_hazards")
        details = sorted(d for _, _, d in _findings(r))
        assert details == ["bad_probe:int", "bad_probe:jnp.zeros",
                           "bad_probe:while"]

    def test_window_segment_idiom_clean(self, tmp_path):
        r = _run(tmp_path, {"pkg/a.py": """\
            import jax
            import jax.numpy as jnp
            def _raw(seg_start, peer_start, valid, vals):
                n = seg_start.shape[0]
                idx = jnp.arange(n, dtype=jnp.int32)
                start = jax.lax.cummax(jnp.where(seg_start, idx, -1))
                rn = idx - start + 1
                q = jnp.where(valid, vals, 0).astype(jnp.int64)
                c = jnp.cumsum(q)
                base = jnp.where(start > 0,
                                 c[jnp.clip(start - 1, 0, None)], 0)
                return rn, c - base
            fn = jax.jit(_raw)
            """}, "jit_hazards")
        assert r["findings"] == []

    def test_multi_stage_probe_idiom_clean(self, tmp_path):
        # the N-stage probe idiom (ops/plan_fusion FusedPlanKernel:
        # multi-join chains): the stage list is a STATIC tuple baked
        # into the plan signature — a Python for over it unrolls at
        # trace time; each stage ANDs its match into ONE shared
        # visibility mask, gathers its payload lanes into the column
        # namespace (clipped indices — masked rows gather garbage that
        # the mask keeps out of every aggregate), and a later stage may
        # probe an earlier stage's payload lane
        r = _run(tmp_path, {"pkg/a.py": """\
            import jax
            import jax.numpy as jnp
            from functools import partial
            @partial(jax.jit, static_argnames=("join_shape",))
            def fused(cols, mask, joins, join_shape):
                for si in range(len(join_shape)):   # static arity: fine
                    probe_col, num_slots, rows_pad, payload = \\
                        join_shape[si]
                    tu, tk, tv, pvals = joins[si]
                    bits = num_slots.bit_length() - 1  # static: fine
                    pk = cols[probe_col].astype(jnp.int64)
                    h = pk.astype(jnp.uint64) \\
                        * jnp.uint64(0x9E3779B97F4A7C15)
                    slot = (h >> jnp.uint64(64 - bits)).astype(
                        jnp.int32)
                    hit = tu[slot] & (tk[slot] == pk)
                    midx = jnp.where(hit, tv[slot], -1)
                    mask = mask & (midx >= 0)   # ONE shared mask
                    gidx = jnp.clip(midx, 0, rows_pad - 1)
                    cols = dict(cols)
                    for bi in range(len(payload)):  # static: fine
                        cols[payload[bi]] = pvals[bi][gidx]
                return mask, cols
            """}, "jit_hazards")
        assert r["findings"] == []

    def test_multi_stage_probe_idiom_true_positives(self, tmp_path):
        # the shapes the N-stage chain must NEVER take: early-exit
        # Python branching on a stage's traced match count (the whole
        # point of the shared mask is that dead rows ride along), a
        # host sync of the surviving-row count between stages, and a
        # Python while chasing convergence of the traced mask
        r = _run(tmp_path, {"pkg/a.py": """\
            import jax
            import jax.numpy as jnp
            @jax.jit
            def bad_chain(pk, used, key, val):
                hit = used[pk] & (key[pk] == pk)
                midx = jnp.where(hit, val[pk], -1)
                mask = midx >= 0
                if mask.sum() == 0:        # python branch on traced
                    return midx
                alive = mask.sum().item()  # host sync between stages
                while mask.sum() > 0:      # python loop on traced
                    mask = mask & ~mask
                return midx, alive
            """}, "jit_hazards")
        details = sorted(d for _, _, d in _findings(r))
        assert details == ["bad_chain:if", "bad_chain:item",
                           "bad_chain:while"]

    def test_window_segment_idiom_true_positive(self, tmp_path):
        r = _run(tmp_path, {"pkg/a.py": """\
            import jax
            import jax.numpy as jnp
            @jax.jit
            def bad_window(seg_start, vals):
                starts = jnp.flatnonzero(seg_start).tolist()  # host sync
                out = vals
                for s in starts:           # python for over traced
                    out = out.at[s].set(0)
                return out
            """}, "jit_hazards")
        details = sorted(d for _, _, d in _findings(r))
        assert "bad_window:for" in details
        assert "bad_window:tolist" in details


class TestFlagDrift:
    FILES = {
        "pkg/flags.py": """\
            def DEFINE_RUNTIME(name, default, help=""):
                pass
            DEFINE_RUNTIME("used_flag", 7, "wired below")
            DEFINE_RUNTIME("dead_flag", 1, "nobody reads this")
            DEFINE_RUNTIME("sched_point_read_depth", 512, "dynamic read")
            DEFINE_RUNTIME("doc_flag", 4, "defaults to 9")
            DEFINE_RUNTIME("doc_flag2", 4, "window size (default: 3)")
            DEFINE_RUNTIME("doc_flag3", 4, "uses the default backend")
            """,
        "pkg/user.py": """\
            from . import flags
            def f(lane):
                a = flags.get("used_flag")
                b = flags.get(f"sched_{lane}_depth")
                c = flags.get("missing_flag")
                return a, b, c
            """,
    }

    def test_true_positives(self, tmp_path):
        r = _run(tmp_path, dict(self.FILES), "flag_drift")
        got = {(p, d) for p, _, d in _findings(r)}
        assert ("pkg/flags.py", "dead_flag") in got       # never read
        assert ("pkg/user.py", "missing_flag") in got     # never defined
        assert ("pkg/flags.py", "doc_flag") in got        # help disagrees
        assert ("pkg/flags.py", "doc_flag2") in got       # "(default: 3)"
        # prose "the default backend" is not a value claim
        assert not any(d == "doc_flag3" and "documents default" in
                       f["message"] for f, (_, _, d) in
                       zip(r["findings"], _findings(r)))
        # dynamic f-string read covers the sched_*_depth flag
        assert not any(d == "sched_point_read_depth" for _, _, d in
                       _findings(r))

    def test_duplicate_default_drift(self, tmp_path):
        files = dict(self.FILES)
        files["pkg/extra.py"] = """\
            from .flags import DEFINE_RUNTIME
            DEFINE_RUNTIME("used_flag", 8, "second default loses")
            """
        r = _run(tmp_path, files, "flag_drift")
        assert any(d == "used_flag" and "re-defined" in m for (_, _, d), m
                   in zip(_findings(r),
                          [f["message"] for f in r["findings"]]))

    def test_matview_flag_surface(self, tmp_path):
        """The matview flag family (ISSUE 17) as a drift fixture: every
        flag read in the subsystem is wired clean; an aspirational flag
        nobody folded in yet (the classic way a knob rots) fires."""
        r = _run(tmp_path, {
            "pkg/flags.py": """\
                def DEFINE_RUNTIME(name, default, help=""):
                    pass
                DEFINE_RUNTIME("matview_enabled", True, "gate")
                DEFINE_RUNTIME("matview_rescan_budget", 8, "cap")
                DEFINE_RUNTIME("matview_max_staleness_ms", 500.0, "bound")
                DEFINE_RUNTIME("matview_poll_ms", 50, "cadence")
                DEFINE_RUNTIME("matview_parallel_seed", 4, "unwired")
                """,
            "pkg/maintainer.py": """\
                from . import flags
                def f():
                    return (flags.get("matview_enabled"),
                            flags.get("matview_rescan_budget"),
                            flags.get("matview_max_staleness_ms"),
                            flags.get("matview_poll_ms"))
                """}, "flag_drift")
        got = {d for _, _, d in _findings(r)}
        assert got == {"matview_parallel_seed"}

    def test_suppressed_with_reason(self, tmp_path):
        files = dict(self.FILES)
        files["pkg/flags.py"] = files["pkg/flags.py"].replace(
            'DEFINE_RUNTIME("dead_flag", 1, "nobody reads this")',
            'DEFINE_RUNTIME("dead_flag", 1, "r")  '
            '# analysis-ok(flag_drift): reserved for r07')
        r = _run(tmp_path, files, "flag_drift")
        assert not any(d == "dead_flag" for _, _, d in _findings(r))
        assert r["suppressions"]["flag_drift"] == 1

    def test_clean_negative(self, tmp_path):
        r = _run(tmp_path, {
            "pkg/flags.py": """\
                def DEFINE_RUNTIME(name, default, help=""):
                    pass
                DEFINE_RUNTIME("wired", True, "read next door")
                """,
            "pkg/user.py": """\
                from . import flags
                def f():
                    data = {}
                    data.get("not_a_flag")    # dict get: out of scope
                    return flags.get("wired")
                """}, "flag_drift")
        assert r["findings"] == []

    def test_join_window_plan_flags_covered(self, tmp_path):
        # the PR-13 flag set under the pass's four drift shapes: wired
        # reads stay clean, an unwired clone and a typo'd read fire
        r = _run(tmp_path, {
            "pkg/flags.py": """\
                def DEFINE_RUNTIME(name, default, help=""):
                    pass
                DEFINE_RUNTIME("join_pushdown_enabled", True, "wired")
                DEFINE_RUNTIME("window_pushdown_enabled", True, "w")
                DEFINE_RUNTIME("plan_fusion_enabled", True, "p")
                DEFINE_RUNTIME("join_max_build_slots", 65536,
                               "slots (default 65536)")
                DEFINE_RUNTIME("join_pushdown_enabled_v2", True,
                               "nobody reads this clone")
                """,
            "pkg/user.py": """\
                from . import flags
                def f():
                    a = flags.get("join_pushdown_enabled")
                    b = flags.get("window_pushdown_enabled")
                    c = flags.get("plan_fusion_enabled")
                    d = flags.get("join_max_build_slots")
                    e = flags.get("plan_fuson_enabled")   # typo
                    return a, b, c, d, e
                """}, "flag_drift")
        got = {(p, d) for p, _, d in _findings(r)}
        assert ("pkg/flags.py", "join_pushdown_enabled_v2") in got
        assert ("pkg/user.py", "plan_fuson_enabled") in got
        wired = {"join_pushdown_enabled", "window_pushdown_enabled",
                 "plan_fusion_enabled", "join_max_build_slots"}
        assert not {d for _, d in got} & wired

    def test_real_flag_defaults_match_docs(self):
        # the REAL tree: the four new flags are defined, read by
        # product code, and their documented defaults agree (the
        # whole-tree zero-findings gate covers this too; this pins the
        # specific names so a rename can't silently drop coverage)
        index = ProjectIndex(HERE)
        r = run_analysis(index, [get_pass("flag_drift")])
        assert r["findings"] == []
        from yugabyte_db_tpu.utils import flags as _f
        for name in ("join_pushdown_enabled", "window_pushdown_enabled",
                     "plan_fusion_enabled", "join_max_build_slots",
                     "grouped_spill_merge_enabled"):
            assert name in _f.REGISTRY.all()


class TestSharedStateRaces:
    def test_true_positive(self, tmp_path):
        r = _run(tmp_path, {"pkg/a.py": """\
            import asyncio
            class Srv:
                def flush(self):
                    self.stats["flushes"] = 1      # thread side
                async def handler(self):
                    self.stats["reads"] = 2        # loop side
                async def kick(self):
                    loop = asyncio.get_running_loop()
                    await loop.run_in_executor(None, self.flush)
            """}, "shared_state_races")
        assert [d for _, _, d in _findings(r)] == ["Srv.stats"]

    def test_executor_lambda_counts_as_thread_side(self, tmp_path):
        r = _run(tmp_path, {"pkg/a.py": """\
            import asyncio
            class Srv:
                async def handler(self):
                    self.stats["reads"] = 2          # loop side
                async def kick(self):
                    loop = asyncio.get_running_loop()
                    await loop.run_in_executor(
                        None, lambda: self.stats.update(x=1))
            """}, "shared_state_races")
        assert [d for _, _, d in _findings(r)] == ["Srv.stats"]

    def test_suppressed_with_reason(self, tmp_path):
        r = _run(tmp_path, {"pkg/a.py": """\
            import asyncio
            class Srv:
                def flush(self):
                    # analysis-ok(shared_state_races): torn-read-safe
                    self.n = 1
                async def handler(self):
                    self.n = 2
                async def kick(self):
                    loop = asyncio.get_running_loop()
                    await loop.run_in_executor(None, self.flush)
            """}, "shared_state_races")
        assert r["findings"] == []
        assert r["suppressions"]["shared_state_races"] == 1

    def test_clean_negative_locked_both_sides(self, tmp_path):
        r = _run(tmp_path, {"pkg/a.py": """\
            import asyncio, threading
            class Srv:
                def flush(self):
                    with self._lock:
                        self.stats["flushes"] = 1
                async def handler(self):
                    with self._lock:
                        self.stats["reads"] = 2
                async def kick(self):
                    loop = asyncio.get_running_loop()
                    await loop.run_in_executor(None, self.flush)
            class NotATarget:
                def helper(self):
                    self.x = 1      # never shipped to an executor
                async def h(self):
                    self.x = 2
            """}, "shared_state_races")
        assert r["findings"] == []


class TestUnawaitedCoroutine:
    def test_true_positives(self, tmp_path):
        r = _run(tmp_path, {"pkg/a.py": """\
            import asyncio
            async def refresh():
                pass
            class Srv:
                async def _flush(self):
                    pass
                async def handler(self):
                    self._flush()                    # coroutine dropped
                    refresh()                        # module-level coro
                    asyncio.gather(self._flush())    # builtin awaitable
                    asyncio.create_task(self._flush())   # F&F task
                    asyncio.ensure_future(refresh())     # F&F task
            """}, "unawaited_coroutine")
        details = sorted(d for _, _, d in _findings(r))
        assert details == ["asyncio.create_task", "asyncio.ensure_future",
                           "asyncio.gather", "refresh", "self._flush"]

    def test_suppressed_with_reason(self, tmp_path):
        r = _run(tmp_path, {"pkg/a.py": """\
            import asyncio
            class Srv:
                async def _bg(self):
                    pass
                async def go(self):
                    # analysis-ok(unawaited_coroutine): supervised set
                    asyncio.create_task(self._bg())
            """}, "unawaited_coroutine")
        assert r["findings"] == []
        assert r["suppressions"]["unawaited_coroutine"] == 1

    def test_clean_negatives(self, tmp_path):
        r = _run(tmp_path, {"pkg/a.py": """\
            import asyncio
            def close():                   # sync twin un-flags the name
                pass
            class Srv:
                async def _bg(self):
                    pass
                async def close(self):
                    pass
                async def run(self):
                    await self._bg()                    # awaited: fine
                    t = asyncio.create_task(self._bg())     # handle kept
                    self.tasks.append(
                        asyncio.create_task(self._bg()))    # stored
                    asyncio.create_task(
                        self._bg()).add_done_callback(print)  # chained
                    await t
                    close()            # sync/async collision: not ours
                    self.writer.write(b"x")   # non-self receiver: the
                                              # stdlib sync write shape
            """}, "unawaited_coroutine")
        assert [d for _, _, d in _findings(r)
                if d not in ("asyncio.create_task",)] == []
        # the kept/stored/chained create_task forms must NOT fire either
        assert r["findings"] == []

    def test_taskgroup_spawn_not_flagged(self, tmp_path):
        # TaskGroup holds strong refs + propagates exceptions: its
        # discarded create_task handle is the documented safe pattern
        r = _run(tmp_path, {"pkg/a.py": """\
            import asyncio
            class Srv:
                async def _bg(self):
                    pass
                async def run(self):
                    async with asyncio.TaskGroup() as tg:
                        tg.create_task(self._bg())       # fine
                    loop = asyncio.get_running_loop()
                    loop.create_task(self._bg())         # weak set: bug
                    asyncio.get_running_loop().create_task(
                        self._bg())                      # weak set: bug
            """}, "unawaited_coroutine")
        assert sorted(d for _, _, d in _findings(r)) == \
            ["create_task", "loop.create_task"]

    def test_nested_class_rescopes(self, tmp_path):
        # a class nested inside a method must NOT inherit the outer
        # class's async-method set (its sync self.flush() is fine) —
        # and a dropped coroutine inside an except block IS caught
        r = _run(tmp_path, {"pkg/a.py": """\
            class Outer:
                async def flush(self):
                    pass
                def make(self):
                    class Inner:
                        def flush(self):
                            pass
                        def go(self):
                            self.flush()        # sync: fine
                    return Inner
                async def run(self):
                    try:
                        await self.flush()
                    except Exception:
                        self.flush()            # dropped coroutine
            """}, "unawaited_coroutine")
        assert [(l, d) for _, l, d in _findings(r)] == \
            [(15, "self.flush")]


class TestFormatGate:
    FILES = {
        "pkg/writer.py": """\
            from .sstlib import SstWriter
            def dump(path, cb):
                w = SstWriter(path, format_version=2)
                head, bufs = cb.serialize_parts(version=2)
                return w, head, bufs
            """,
        "pkg/sstlib.py": """\
            class SstWriter:
                def __init__(self, path, format_version=None):
                    self.path = path
            """,
    }

    def test_true_positives(self, tmp_path):
        r = _run(tmp_path, dict(self.FILES), "format_gate")
        got = {(p, d) for p, _, d in _findings(r)}
        assert ("pkg/writer.py", "format_version=2") in got
        assert ("pkg/writer.py", "version=2") in got

    def test_generic_version_kwarg_not_flagged(self, tmp_path):
        """`version=2` on non-serializer callees (schema versions etc.)
        is unrelated to the on-disk format and must not fire."""
        files = dict(self.FILES)
        files["pkg/writer.py"] = """\
            def make():
                return TableSchema(columns=(), version=2)
            """
        r = _run(tmp_path, files, "format_gate")
        assert _findings(r) == []

    def test_pinning_v1_allowed(self, tmp_path):
        files = dict(self.FILES)
        files["pkg/writer.py"] = """\
            from .sstlib import SstWriter
            def dump(path, cb, fmt):
                w = SstWriter(path, format_version=1)   # baseline pin
                return cb.serialize_parts(version=fmt)  # flag-resolved
            """
        r = _run(tmp_path, files, "format_gate")
        assert _findings(r) == []

    def test_suppressed_with_reason(self, tmp_path):
        files = dict(self.FILES)
        files["pkg/writer.py"] = """\
            from .sstlib import SstWriter
            def dump(path, cb):
                # analysis-ok(format_gate): migration tool writes v2 on purpose
                w = SstWriter(path, format_version=2)
                return w
            """
        r = _run(tmp_path, files, "format_gate")
        assert _findings(r) == []
        assert r["suppressions"]["format_gate"] == 1

    def test_shred_cols_literal_on_serializer_flagged(self, tmp_path):
        """A serializer call feeding a non-empty literal shred_cols
        would emit shredded doc lanes even with doc_shred_enabled off
        — the writer gate lives in SstWriter, nowhere else."""
        files = dict(self.FILES)
        files["pkg/writer.py"] = """\
            def dump(cb, fmt, kb):
                return cb.serialize_parts(fmt, kb, None,
                                          shred_cols=(1, 2))
            """
        r = _run(tmp_path, files, "format_gate")
        assert [d for _, _, d in _findings(r)] == ["shred_cols literal"]

    def test_shred_cols_through_writer_allowed(self, tmp_path):
        """SstWriter(shred_cols=...) resolves the doc_shred_enabled
        flag itself — threading codec.shred_cols through it (or an
        empty/None literal on a serializer) is the sanctioned path."""
        files = dict(self.FILES)
        files["pkg/writer.py"] = """\
            from .sstlib import SstWriter
            def dump(path, cb, codec, fmt, kb):
                w = SstWriter(path, shred_cols=codec.shred_cols)
                head, bufs = cb.serialize_parts(fmt, kb, None,
                                                shred_cols=())
                return w, head, bufs
            """
        r = _run(tmp_path, files, "format_gate")
        assert _findings(r) == []


class TestLayering:
    """bypass/ must not import tserver/sched/rpc — the subsystem's
    isolation guarantee as a tier-1 fact."""

    def _run_scoped(self, tmp_path, files):
        import textwrap as _tw
        for rel, src in files.items():
            p = tmp_path / rel
            p.parent.mkdir(parents=True, exist_ok=True)
            p.write_text(_tw.dedent(src))
        index = ProjectIndex(str(tmp_path),
                             roots=("yugabyte_db_tpu",))
        return run_analysis(index, [get_pass("layering")])

    def test_true_positives(self, tmp_path):
        r = self._run_scoped(tmp_path, {
            "yugabyte_db_tpu/bypass/bad.py": """\
                import yugabyte_db_tpu.tserver.tablet_server
                from yugabyte_db_tpu.rpc import messenger
                from ..sched.lanes import Lane
                from .. import rpc
                def f():
                    from ..tserver import tablet_server
                    return tablet_server
                """})
        layers = sorted(d.split(":")[0] for _, _, d in _findings(r))
        assert layers == ["rpc", "rpc", "sched", "tserver", "tserver"]

    def test_suppressed_with_reason(self, tmp_path):
        r = self._run_scoped(tmp_path, {
            "yugabyte_db_tpu/bypass/bad.py": """\
                from ..rpc import messenger  # analysis-ok(layering): fixture
                """})
        assert r["findings"] == []
        assert r["suppressions"]["layering"] == 1

    def test_clean_negatives(self, tmp_path):
        """Allowed seams (storage/ops/parallel/docdb), sibling-package
        imports of the same names, and other layers importing tserver
        must not fire."""
        r = self._run_scoped(tmp_path, {
            "yugabyte_db_tpu/bypass/ok.py": """\
                from ..storage.lsm import LsmStore
                from ..ops import stream_scan
                from ..parallel.distributed_scan import ShardedBatch
                from ..docdb.operations import ReadResponse
                from .errors import BypassIneligible
                import numpy.rpc_like as rpcx    # not our layer
                """,
            "yugabyte_db_tpu/client/uses_rpc.py": """\
                from ..rpc.messenger import Messenger
                from ..tserver import tablet_server
                """})
        assert _findings(r) == []

    def test_cluster_rule(self, tmp_path):
        """cluster/ may import client/rpc/utils/models but never
        server-side internals (tserver/tablet/master/storage/...) —
        the harness talks to servers ONLY over RPC."""
        r = self._run_scoped(tmp_path, {
            "yugabyte_db_tpu/cluster/ok.py": """\
                from ..client import YBClient
                from ..rpc.messenger import Messenger
                from ..utils.metrics import REGISTRY
                from ..models.ycsb import usertable_info
                """,
            "yugabyte_db_tpu/cluster/bad.py": """\
                from ..tserver import TabletServer
                from ..tablet.tablet_peer import TabletPeer
                import yugabyte_db_tpu.storage.lsm
                from ..master import Master
                def f():
                    from ..bypass import BypassSession
                    return BypassSession
                """})
        layers = sorted(d.split(":")[0] for _, _, d in _findings(r))
        assert layers == ["bypass", "master", "storage", "tablet",
                         "tserver"]
        assert all(f == "yugabyte_db_tpu/cluster/bad.py"
                   for f, _, _ in _findings(r))

    def test_docstore_rule(self, tmp_path):
        """docstore/ is a pure library: storage/dockv/ops/utils (and
        docdb for the shared rewrite) are fine; tserver/tablet/rpc
        never — shredding must not reach into server layers."""
        r = self._run_scoped(tmp_path, {
            "yugabyte_db_tpu/docstore/ok.py": """\
                from ..storage import lane_codec
                from ..dockv.packed_row import ColumnType
                from ..ops.scan import AggSpec
                from ..utils import flags
                """,
            "yugabyte_db_tpu/docstore/bad.py": """\
                from ..tserver import TabletServer
                from ..tablet.tablet import Tablet
                import yugabyte_db_tpu.rpc.messenger
                """})
        layers = sorted(d.split(":")[0] for _, _, d in _findings(r))
        assert layers == ["rpc", "tablet", "tserver"]
        assert all(f == "yugabyte_db_tpu/docstore/bad.py"
                   for f, _, _ in _findings(r))

    def test_matview_rule(self, tmp_path):
        """matview/ folds exclusively through client RPCs, the CDC slot
        API and the ops combine seam (cdc/client/ops/utils/models are
        fine); importing tserver/tablet/storage/consensus would let a
        maintainer read a memtable directly, bypassing the pinned read
        point the whole design hangs on."""
        r = self._run_scoped(tmp_path, {
            "yugabyte_db_tpu/matview/ok.py": """\
                from ..cdc.virtual_wal import VirtualWal
                from ..client.client import YBClient
                from ..ops.scan import combine_grouped_partials
                from ..utils import flags
                from ..models.ycsb import usertable_info
                from .errors import MatviewIneligible
                """,
            "yugabyte_db_tpu/matview/bad.py": """\
                from ..tserver import TabletServer
                from ..tablet.tablet_peer import TabletPeer
                import yugabyte_db_tpu.storage.lsm
                def f():
                    from ..consensus import RaftConsensus
                    return RaftConsensus
                """})
        layers = sorted(d.split(":")[0] for _, _, d in _findings(r))
        assert layers == ["consensus", "storage", "tablet", "tserver"]
        assert all(f == "yugabyte_db_tpu/matview/bad.py"
                   for f, _, _ in _findings(r))


# --- interprocedural: the call graph itself --------------------------------

class TestTraceDiscipline:
    """wait_status() states are a closed vocabulary: every call-site
    literal must come from the canonical trace.WAIT_STATES table (a
    typo'd state silently vanishes from every ASH histogram)."""

    TABLE = """\
        WAIT_STATES = frozenset({
            "Idle",
            "WAL_Fsync",
            "Flush_SstWrite",
        })
        def wait_status(state, component=""):
            pass
        """

    def _run_with_table(self, tmp_path, files):
        import textwrap as _tw
        files = dict(files)
        files["yugabyte_db_tpu/utils/trace.py"] = self.TABLE
        for rel, src in files.items():
            p = tmp_path / rel
            p.parent.mkdir(parents=True, exist_ok=True)
            p.write_text(_tw.dedent(src))
        index = ProjectIndex(str(tmp_path), roots=("yugabyte_db_tpu",))
        return run_analysis(index, [get_pass("trace_discipline")])

    def test_true_positive_free_text(self, tmp_path):
        r = self._run_with_table(tmp_path, {
            "yugabyte_db_tpu/a.py": """\
                from .utils.trace import wait_status
                def f():
                    with wait_status("WalFsyncTypo"):
                        pass
                """})
        assert [d for _, _, d in _findings(r)] == ["WalFsyncTypo"]

    def test_true_positive_non_literal(self, tmp_path):
        r = self._run_with_table(tmp_path, {
            "yugabyte_db_tpu/a.py": """\
                from .utils import trace
                def f(state):
                    with trace.wait_status(state):
                        pass
                """})
        assert [d for _, _, d in _findings(r)] == ["non-literal"]

    def test_suppressed_with_reason(self, tmp_path):
        r = self._run_with_table(tmp_path, {
            "yugabyte_db_tpu/a.py": """\
                from .utils.trace import wait_status
                def f():
                    with wait_status("Legacy"):   # analysis-ok(trace_discipline): fixture
                        pass
                """})
        assert r["findings"] == []
        assert r["suppressions"]["trace_discipline"] == 1

    def test_clean_negative(self, tmp_path):
        """Canonical literals (bare and attribute-qualified calls) and
        unrelated call names must not fire."""
        r = self._run_with_table(tmp_path, {
            "yugabyte_db_tpu/a.py": """\
                from .utils import trace
                from .utils.trace import wait_status
                def f():
                    with wait_status("WAL_Fsync"):
                        pass
                    with trace.wait_status("Flush_SstWrite",
                                           component="flush"):
                        pass
                    return trace.current_wait_state()
                """})
        assert _findings(r) == []

    def test_no_table_no_findings(self, tmp_path):
        """A tree without a WAIT_STATES table (bare fixture) produces
        nothing rather than flagging every call."""
        import textwrap as _tw
        p = tmp_path / "yugabyte_db_tpu" / "a.py"
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(_tw.dedent("""\
            def wait_status(s):
                pass
            def f():
                with wait_status("Whatever"):
                    pass
            """))
        index = ProjectIndex(str(tmp_path), roots=("yugabyte_db_tpu",))
        r = run_analysis(index, [get_pass("trace_discipline")])
        assert _findings(r) == []

    def test_real_tree_table_discovered(self):
        """The pass finds the REAL canonical table in utils/trace.py
        (so it tracks table growth with zero pass edits)."""
        sys.path.insert(0, os.path.join(HERE, "tools"))
        from analyze.passes.trace_discipline import find_state_table
        from yugabyte_db_tpu.utils.trace import WAIT_STATES
        index = ProjectIndex(HERE, roots=("yugabyte_db_tpu",))
        mod, states = find_state_table(index)
        assert mod is not None
        assert mod.rel.replace("\\", "/").endswith("utils/trace.py")
        assert states == set(WAIT_STATES)


class TestCallGraph:
    def _graph(self, tmp_path, files):
        for rel, src in files.items():
            p = tmp_path / rel
            p.parent.mkdir(parents=True, exist_ok=True)
            p.write_text(textwrap.dedent(src))
        index = ProjectIndex(str(tmp_path), roots=("pkg",))
        return index.call_graph()

    def test_alias_chain_resolution(self, tmp_path):
        g = self._graph(tmp_path, {
            "pkg/__init__.py": "",
            "pkg/util.py": """\
                def helper():
                    pass
                """,
            "pkg/a.py": """\
                from pkg import util
                fn = util.helper
                fn2 = fn
                def caller():
                    fn2()
                """})
        assert g.resolve("pkg/a.py", "caller", "fn2") \
            == "pkg/util.py::helper"

    def test_method_resolution_across_inheritance(self, tmp_path):
        g = self._graph(tmp_path, {
            "pkg/__init__.py": "",
            "pkg/base.py": """\
                class Base:
                    def close(self):
                        pass
                """,
            "pkg/sub.py": """\
                from pkg.base import Base
                class Mid(Base):
                    pass
                class Sub(Mid):
                    def open(self):
                        self.close()     # binds Base.close via the MRO
                """})
        assert g.resolve("pkg/sub.py", "Sub.open", "self.close") \
            == "pkg/base.py::Base.close"
        # an override wins over the base definition
        g2 = self._graph(tmp_path / "o", {
            "pkg/__init__.py": "",
            "pkg/m.py": """\
                class A:
                    def f(self):
                        pass
                class B(A):
                    def f(self):
                        pass
                    def g(self):
                        self.f()
                """})
        assert g2.resolve("pkg/m.py", "B.g", "self.f") == "pkg/m.py::B.f"

    def test_classname_and_module_qualified_calls(self, tmp_path):
        g = self._graph(tmp_path, {
            "pkg/__init__.py": "",
            "pkg/m.py": """\
                import pkg.util
                from pkg.util import helper as h
                class C:
                    def m(self):
                        pass
                def f():
                    C.m(None)
                    pkg.util.helper()
                    h()
                """,
            "pkg/util.py": """\
                def helper():
                    pass
                """})
        assert g.resolve("pkg/m.py", "f", "C.m") == "pkg/m.py::C.m"
        assert g.resolve("pkg/m.py", "f", "pkg.util.helper") \
            == "pkg/util.py::helper"
        assert g.resolve("pkg/m.py", "f", "h") == "pkg/util.py::helper"

    def test_recursion_terminates(self, tmp_path):
        g = self._graph(tmp_path, {
            "pkg/__init__.py": "",
            "pkg/r.py": """\
                import time
                def a():
                    b()
                def b():
                    a()
                    time.sleep(1)
                def solo():
                    solo()
                    time.sleep(2)
                """})

        def direct(key):
            d = g.def_fact(key)
            return {t: ln for ln, t in d["calls"]
                    if t == "time.sleep"} if d else {}

        # mutual recursion: summaries converge and still see the hazard
        s = g.summarize(g.key("pkg/r.py", "a"), "t", direct,
                        lambda k: True)
        assert "time.sleep" in s
        s2 = g.summarize(g.key("pkg/r.py", "solo"), "t", direct,
                         lambda k: True)
        assert "time.sleep" in s2

    def test_facts_cache_hit_speedup(self, tmp_path):
        files = {"pkg/__init__.py": ""}
        for i in range(30):
            files[f"pkg/m{i}.py"] = "def f():\n    pass\n" * 40
        for rel, src in files.items():
            p = tmp_path / rel
            p.parent.mkdir(parents=True, exist_ok=True)
            p.write_text(src)
        cache = str(tmp_path / ".analyze_cache")
        # the two timed builds run with the cyclic collector off: a full
        # pass (50-90 ms) inside the cached build of ~15 ms flips the
        # comparison, and where it falls depends on how much the rest of
        # the test run allocated (PERF.md section 7)
        import gc
        gc.disable()
        try:
            i1 = ProjectIndex(str(tmp_path), roots=("pkg",),
                              cache_dir=cache)
            g1 = i1.call_graph()
            i2 = ProjectIndex(str(tmp_path), roots=("pkg",),
                              cache_dir=cache)
            g2 = i2.call_graph()
        finally:
            gc.enable()
        assert g1.stats["cache_misses"] == len(files)
        assert g2.stats["cache_hits"] == len(files)
        assert g2.stats["cache_misses"] == 0
        # the cached run must actually be cheaper, not just "hit"
        assert g2.stats["build_ms"] < g1.stats["build_ms"]
        # identical facts either way
        assert g2.facts == g1.facts
        # an edited file is re-extracted, the rest stay cached
        p = tmp_path / "pkg/m0.py"
        p.write_text("def f():\n    pass\ndef g():\n    pass\n")
        os.utime(p, (1, 1))
        i3 = ProjectIndex(str(tmp_path), roots=("pkg",), cache_dir=cache)
        g3 = i3.call_graph()
        assert g3.stats["cache_misses"] == 1
        assert "g" in g3.facts["pkg/m0.py"]["defs"]


# --- interprocedural: transitive pass upgrades ------------------------------

class TestAsyncBlockingTransitive:
    def test_true_positive_reports_chain(self, tmp_path):
        r = _run(tmp_path, {"pkg/__init__.py": "",
                            "pkg/a.py": """\
            import shutil
            def nuke(path):
                shutil.rmtree(path)
            def indirection(path):
                nuke(path)
            async def handler(path):
                indirection(path)
            """}, "async_blocking")
        assert [(l, d) for _, l, d in _findings(r)] == [
            (7, "shutil.rmtree")]
        msg = r["findings"][0]["message"]
        # the full helper chain is the finding's evidence
        assert "indirection" in msg and "nuke" in msg \
            and "shutil.rmtree" in msg

    def test_cross_module_chain(self, tmp_path):
        r = _run(tmp_path, {"pkg/__init__.py": "",
                            "pkg/helpers.py": """\
            import subprocess
            def run_tool():
                subprocess.run(["x"])
            """,
                            "pkg/srv.py": """\
            from pkg.helpers import run_tool
            async def handler():
                run_tool()
            """}, "async_blocking")
        assert [(p, l, d) for p, l, d in _findings(r)] == [
            ("pkg/srv.py", 3, "subprocess.run")]

    def test_suppression_at_direct_site_does_not_taint(self, tmp_path):
        r = _run(tmp_path, {"pkg/__init__.py": "",
                            "pkg/a.py": """\
            import time
            def bounded_wait():
                time.sleep(0.001)  # analysis-ok(async_blocking): bounded
            async def handler():
                bounded_wait()
            """}, "async_blocking")
        assert r["findings"] == []

    def test_suppression_at_call_site(self, tmp_path):
        r = _run(tmp_path, {"pkg/__init__.py": "",
                            "pkg/a.py": """\
            import time
            def helper():
                time.sleep(1)
            async def handler():
                helper()   # analysis-ok(async_blocking): startup only
            """}, "async_blocking")
        assert r["findings"] == []
        assert r["suppressions"]["async_blocking"] == 1

    def test_clean_negatives(self, tmp_path):
        r = _run(tmp_path, {"pkg/__init__.py": "",
                            "pkg/a.py": """\
            import asyncio, time
            def tiny_config():
                open("/tmp/x")        # lexical-only offender: does NOT
                #                       taint callers (accepted idiom)
            def stall():
                time.sleep(1)
            async def co_helper():
                await asyncio.sleep(0)
            async def handler():
                tiny_config()
                await co_helper()     # async callee: scanned on its own
                await asyncio.get_running_loop().run_in_executor(
                    None, stall)      # executor dispatch, not a call
            """}, "async_blocking")
        assert r["findings"] == []


class TestLockHeldAwaitTransitive:
    def test_true_positive_blocking_under_lock(self, tmp_path):
        r = _run(tmp_path, {"pkg/__init__.py": "",
                            "pkg/a.py": """\
            import time
            class C:
                def _drain(self):
                    time.sleep(1)
                async def work(self):
                    with self._lock:
                        self._drain()
            """}, "lock_held_await")
        assert [(l, d) for _, l, d in _findings(r)] == [
            (7, "self._lock->time.sleep")]
        assert "_drain" in r["findings"][0]["message"]

    def test_suppressed_with_reason(self, tmp_path):
        r = _run(tmp_path, {"pkg/__init__.py": "",
                            "pkg/a.py": """\
            import time
            class C:
                def _drain(self):
                    time.sleep(1)
                async def work(self):
                    with self._lock:
                        # analysis-ok(lock_held_await): bounded drain
                        self._drain()
            """}, "lock_held_await")
        assert r["findings"] == []
        assert r["suppressions"]["lock_held_await"] == 1

    def test_clean_negatives(self, tmp_path):
        r = _run(tmp_path, {"pkg/__init__.py": "",
                            "pkg/a.py": """\
            import time
            class C:
                def _fast(self):
                    return self.x + 1
                def _stall(self):
                    time.sleep(1)
                async def work(self):
                    with self._lock:
                        self._fast()       # no blocking in the summary
                    self._stall()          # blocking, but no lock held
            """}, "lock_held_await")
        assert r["findings"] == []


class TestSharedStateRacesResolved:
    def test_name_collision_no_longer_overapproximates(self, tmp_path):
        # Shipper hands ITS OWN self.flush to an executor; Bystander
        # merely shares the method NAME.  The class-resolved pass must
        # flag Shipper only (terminal-name matching flagged both).
        src = {"pkg/__init__.py": "",
               "pkg/a.py": """\
            class Shipper:
                def flush(self):
                    self.buf = []
                async def go(self):
                    self.buf = [1]
                    await self._loop.run_in_executor(None, self.flush)
            class Bystander:
                def flush(self):
                    self.buf = []
                async def go(self):
                    self.buf = [1]
            """}
        r = _run(tmp_path, src, "shared_state_races")
        paths = {(p, l) for p, l, _ in _findings(r)}
        assert ("pkg/a.py", 3) in paths or ("pkg/a.py", 5) in paths
        assert all(l < 7 for _, l in paths), (
            "Bystander got flagged through a shared method name:\n"
            + str(r["findings"]))

    def test_subclass_override_stays_thread_side(self, tmp_path):
        # Base ships self.flush to an executor; Sub OVERRIDES flush —
        # for Sub instances the override is what runs on the thread,
        # so its unlocked writes must still race Sub's async methods
        r = _run(tmp_path, {"pkg/__init__.py": "",
                            "pkg/base.py": """\
            class Base:
                def flush(self):
                    pass
                async def go(self):
                    await self._loop.run_in_executor(None, self.flush)
            """,
                            "pkg/sub.py": """\
            from pkg.base import Base
            class Sub(Base):
                def flush(self):
                    self.dirty = []
                async def serve(self):
                    self.dirty = [1]
            """}, "shared_state_races")
        assert any(p == "pkg/sub.py" for p, _, _ in _findings(r)), (
            "the override lost its thread-side marking:\n"
            + str(r["findings"]))

    def test_unresolvable_target_still_falls_back(self, tmp_path):
        # `peer.tablet.flush` has an unknowable receiver: the terminal-
        # name fallback must keep flagging a same-named sync mutator
        r = _run(tmp_path, {"pkg/__init__.py": "",
                            "pkg/a.py": """\
            def ship(peer, loop):
                loop.run_in_executor(None, peer.tablet.flush)
            class T:
                def flush(self):
                    self.rows = []
                async def ingest(self):
                    self.rows = [1]
            """}, "shared_state_races")
        assert len(r["findings"]) >= 1


# --- new graph-powered passes ----------------------------------------------

class TestLockOrder:
    def test_true_positive_ab_ba_cycle(self, tmp_path):
        r = _run(tmp_path, {"pkg/__init__.py": "",
                            "pkg/a.py": """\
            class S:
                async def handler(self):
                    with self._meta_lock:
                        with self._flush_lock:
                            self.x = 1
                def compact(self):
                    with self._flush_lock:
                        with self._meta_lock:
                            self.y = 1
            """}, "lock_order")
        assert len(r["findings"]) == 1
        msg = r["findings"][0]["message"]
        assert "_meta_lock" in msg and "_flush_lock" in msg
        assert "deadlock" in msg

    def test_transitive_cycle_through_helper(self, tmp_path):
        # handler holds A and CALLS a helper that takes B; compact
        # takes B then A directly — the cycle spans a call edge
        r = _run(tmp_path, {"pkg/__init__.py": "",
                            "pkg/a.py": """\
            class S:
                def _drain(self):
                    with self._flush_lock:
                        self.q = []
                async def handler(self):
                    with self._meta_lock:
                        self._drain()
                def compact(self):
                    with self._flush_lock:
                        with self._meta_lock:
                            self.y = 1
            """}, "lock_order")
        assert len(r["findings"]) == 1
        assert "via" in r["findings"][0]["message"]

    def test_suppressed_with_reason(self, tmp_path):
        r = _run(tmp_path, {"pkg/__init__.py": "",
                            "pkg/a.py": """\
            class S:
                async def handler(self):
                    with self._a_lock:
                        # analysis-ok(lock_order): B-holders never take A
                        with self._b_lock:
                            self.x = 1
                def compact(self):
                    with self._b_lock:
                        with self._a_lock:
                            self.y = 1
            """}, "lock_order")
        assert r["findings"] == []
        assert r["suppressions"]["lock_order"] == 1

    def test_clean_negatives(self, tmp_path):
        r = _run(tmp_path, {"pkg/__init__.py": "",
                            "pkg/a.py": """\
            class S:
                async def handler(self):
                    with self._a_lock:
                        with self._b_lock:
                            self.x = 1
                def compact(self):
                    with self._a_lock:     # same global order: fine
                        with self._b_lock:
                            self.y = 1
            class T:
                def one(self):
                    with self._b_lock:     # same NAMES, different class
                        with self._a_lock: # = different locks: no cycle
                            self.z = 1
            """}, "lock_order")
        assert r["findings"] == []

    def test_base_class_lock_is_one_lock(self, tmp_path):
        # the lock lives on the base; two subclasses ordering it
        # against their own lock INCONSISTENTLY is a real cycle
        r = _run(tmp_path, {"pkg/__init__.py": "",
                            "pkg/a.py": """\
            import threading
            class Base:
                def __init__(self):
                    self._install_lock = threading.Lock()
            class U(Base):
                def f(self):
                    with self._install_lock:
                        with self._side_lock:
                            self.x = 1
            class V(Base):
                def g(self):
                    with self._side_lock:
                        with self._install_lock:
                            self.y = 1
            """}, "lock_order")
        # U._side and V._side are DIFFERENT locks (each class assigns
        # its own), so no cycle exists here; only the shared base lock
        # could close one.  The negative pins the identity rule.
        assert r["findings"] == []
        r2 = _run(tmp_path / "pos", {"pkg/__init__.py": "",
                                     "pkg/a.py": """\
            import threading
            class Base:
                def __init__(self):
                    self._install_lock = threading.Lock()
                    self._gc_lock = threading.Lock()
            class U(Base):
                def f(self):
                    with self._install_lock:
                        with self._gc_lock:
                            self.x = 1
            class V(Base):
                def g(self):
                    with self._gc_lock:
                        with self._install_lock:
                            self.y = 1
            """}, "lock_order")
        assert len(r2["findings"]) == 1

class TestResourceBalance:
    def test_discarded_lease_always_leaks(self, tmp_path):
        r = _run(tmp_path, {"pkg/__init__.py": "",
                            "pkg/a.py": """\
            def pin(store):
                store.pin_ssts(require_empty_memtable=True)
            """}, "resource_balance")
        assert [(l, d) for _, l, d in _findings(r)] == [
            (2, "pin_ssts:discarded")]

    def test_early_return_skips_release(self, tmp_path):
        r = _run(tmp_path, {"pkg/__init__.py": "",
                            "pkg/a.py": """\
            def scan(store, cond):
                lease = store.pin_ssts()
                if cond:
                    return None
                lease.release()
                return 1
            """}, "resource_balance")
        assert [(l, d) for _, l, d in _findings(r)] == [
            (4, "pin_ssts:lease")]

    def test_fall_through_never_released(self, tmp_path):
        r = _run(tmp_path, {"pkg/__init__.py": "",
                            "pkg/a.py": """\
            def peek(path):
                f = open(path)
                f.read(4)
            """}, "resource_balance")
        assert [(l, d) for _, l, d in _findings(r)] == [(2, "open:f")]

    def test_gauge_early_return_skips_decrement(self, tmp_path):
        r = _run(tmp_path, {"pkg/__init__.py": "",
                            "pkg/a.py": """\
            class L:
                def admit(self, shed):
                    self._inflight += 1
                    if shed:
                        return False
                    self.dispatch()
                    self._inflight -= 1
                    return True
            """}, "resource_balance")
        assert [(l, d) for _, l, d in _findings(r)] == [
            (5, "gauge:self._inflight")]

    def test_suppressed_with_reason(self, tmp_path):
        r = _run(tmp_path, {"pkg/__init__.py": "",
                            "pkg/a.py": """\
            def scan(store, cond):
                lease = store.pin_ssts()
                if cond:
                    # analysis-ok(resource_balance): owner releases
                    return None
                lease.release()
            """}, "resource_balance")
        assert r["findings"] == []
        assert r["suppressions"]["resource_balance"] == 1

    def test_clean_negatives(self, tmp_path):
        r = _run(tmp_path, {"pkg/__init__.py": "",
                            "pkg/a.py": """\
            import contextlib

            def ctx_owned(path):
                with open(path) as f:      # context manager owns it
                    return f.read()

            def try_finally(store):
                lease = store.pin_ssts()
                try:
                    return work(lease)
                finally:
                    lease.release()

            def transfer(store):
                lease = store.pin_ssts()
                return Snapshot(lease=lease)   # ownership moved out

            def stored(self, store):
                lease = store.pin_ssts()
                self._lease = lease            # escapes to the owner

            def with_stmt_release(path):
                f = open(path)
                with contextlib.closing(f):
                    return f.read()

            def release_then_raise(store, cond):
                lease = store.pin_ssts()
                if not lease.paths:
                    lease.release()
                    raise ValueError("empty")  # raising exits exempt
                lease.release()
                return 1

            class Cache:
                def put(self, k, v, size):
                    self._bytes += size
                    while self._bytes > self.cap:
                        self._bytes -= self.evict()
                    return v                   # dec behind the return:
                    #                            eviction accounting,
                    #                            not an in-flight pair

            def parser(s):
                depth = 0
                for ch in s:
                    depth += 1
                    if ch == ")":
                        depth -= 1
                    if depth > 40:
                        return None            # bare local: no gauge
                return depth

            def monotonic(self):
                self._stats += 1               # inc-only: a counter
                return self._stats
            """}, "resource_balance")
        assert r["findings"] == []

    def test_pinner_shape_is_clean(self, tmp_path):
        # the REAL bypass/pinner.py shape: acquire in a retry loop,
        # release+raise on the empty branch, transfer via the returned
        # snapshot — zero findings, pinned as a regression fixture
        r = _run(tmp_path, {"pkg/__init__.py": "",
                            "pkg/a.py": """\
            def pin_snapshot(store, attempts):
                lease = None
                for attempt in range(attempts):
                    lease = store.pin_ssts(require_empty_memtable=True)
                    if lease is not None:
                        break
                if lease is None:
                    raise RuntimeError("memtable active")
                if not lease.paths:
                    lease.release()
                    raise RuntimeError("no ssts")
                return Snapshot(lease=lease, paths=list(lease.paths))
            """}, "resource_balance")
        assert r["findings"] == []


# --- the pre-fix product shapes the engine was built to catch ---------------

class TestPreFixProductShapes:
    """Minimal reproductions of hazards that lived in yugabyte_db_tpu/
    BEFORE this PR's fixes — invisible to the lexical passes, caught by
    the interprocedural engine.  These pin the engine's reason to
    exist: if a refactor re-introduces the shape, tier-1 names it."""

    def test_master_persist_fsync_under_async_commit(self, tmp_path):
        # pre-fix master.py: async _commit_catalog -> sync _persist()
        # -> open/fsync/replace inline on the event loop
        r = _run(tmp_path, {"pkg/__init__.py": "",
                            "pkg/master.py": """\
            import json, os
            class Master:
                def _persist(self):
                    with open(self._path + ".tmp", "w") as f:
                        json.dump(self.tables, f)
                        f.flush()
                        os.fsync(f.fileno())
                    os.replace(self._path + ".tmp", self._path)
                async def _commit_catalog(self, ops):
                    self.apply(ops)
                    self._persist()
            """}, "async_blocking")
        details = sorted(d for _, _, d in _findings(r))
        assert "os.fsync" in details, r["findings"]
        assert all(l == 11 for _, l, _ in _findings(r)), (
            "the finding must land on the async-side call line")

    def test_tserver_meta_write_under_async_split(self, tmp_path):
        # pre-fix tablet_server.py: async _apply_split calling the
        # sync _atomic_json helper (fsync + cross-FS-safe replace)
        r = _run(tmp_path, {"pkg/__init__.py": "",
                            "pkg/ts.py": """\
            import json, os
            def _atomic_json(path, obj):
                with open(path + ".tmp", "w") as f:
                    json.dump(obj, f)
                    f.flush()
                    os.fsync(f.fileno())
                os.replace(path + ".tmp", path)
            class TabletServer:
                async def _apply_split(self, meta):
                    _atomic_json(self._marker(meta["id"]), meta)
            """}, "async_blocking")
        assert any(d == "os.fsync" for _, _, d in _findings(r))

    def test_fixed_master_shape_is_clean(self, tmp_path):
        # the POST-fix shape: serialize on the loop, fsync in the
        # executor — the engine must see it as clean (else the fix
        # would have needed an annotation, which the tentpole forbids)
        r = _run(tmp_path, {"pkg/__init__.py": "",
                            "pkg/master.py": """\
            import asyncio, json, os
            class Master:
                def _write(self, data):
                    with open(self._path + ".tmp", "w") as f:
                        f.write(data)
                        f.flush()
                        os.fsync(f.fileno())
                    os.replace(self._path + ".tmp", self._path)
                async def _commit_catalog(self, ops):
                    self.apply(ops)
                    data = json.dumps(self.tables)
                    await asyncio.get_running_loop().run_in_executor(
                        None, self._write, data)
            """}, "async_blocking")
        assert r["findings"] == []


#: the pre-fix PR-11 write-path stall, minimally: Raft apply (async)
#: -> sync _apply_payload -> self.tablet.apply_write (attr typed by
#: the annotated __init__ param) -> Tablet.flush -> self.regular.flush
#: (attr typed by its constructor) -> SST write + fsync ON THE APPLY
#: THREAD.  Both attr hops need the call graph's attribute typing —
#: the lexical layers and the PR-8 engine were blind to this chain.
_APPLY_FLUSH_SHAPE = {
    "pkg/__init__.py": "",
    "pkg/store.py": """\
        import os
        class LsmStore:
            def flush(self):
                with open(self._path + ".tmp", "w") as f:
                    f.write(self._mem)
                    f.flush()
                    os.fsync(f.fileno())
                os.replace(self._path + ".tmp", self._path)
    """,
    "pkg/tablet.py": """\
        from .store import LsmStore
        class Tablet:
            def __init__(self, directory):
                self.regular = LsmStore(directory)
            def apply_write(self, req):
                self.regular.apply(req)
                if self.regular.should_flush():
                    self.flush()
            def flush(self):
                return self.regular.flush()
    """,
    "pkg/peer.py": """\
        from .tablet import Tablet
        class TabletPeer:
            def __init__(self, tablet: Tablet):
                self.tablet = tablet
            def _apply_payload(self, entry):
                self.tablet.apply_write(entry.req)
            async def _apply_entry(self, entry):
                self._apply_payload(entry)
    """,
}


class TestWritePathHotPath:
    """PR-11 rule: a synchronous LsmStore.flush reachable from the
    Raft apply path is an apply-thread stall — the ~20x p99 round
    swing the cluster harness measured.  Pinned pre-fix; the post-fix
    tree (frozen-memtable handoff to the flush executor) gates clean
    via test_whole_tree_zero_unannotated_findings."""

    def test_prefix_apply_write_flush_shape_flagged(self, tmp_path):
        r = _run(tmp_path, _APPLY_FLUSH_SHAPE, "async_blocking")
        details = {d for _, _, d in _findings(r)}
        assert "os.fsync" in details, r["findings"]
        # the finding lands on the async-side call in _apply_entry
        assert any(p.endswith("peer.py") and l == 8
                   for p, l, _ in _findings(r)), r["findings"]

    def test_edge_annotation_stops_taint_without_silencing_helper(
            self, tmp_path):
        # annotating the INTERMEDIATE flush call (the flag-gated
        # legacy revert shape) stops the taint at that edge only: an
        # unannotated second path through the same helper still flags
        files = dict(_APPLY_FLUSH_SHAPE)
        files["pkg/tablet.py"] = """\
            from .store import LsmStore
            class Tablet:
                def __init__(self, directory):
                    self.regular = LsmStore(directory)
                def apply_write(self, req):
                    self.regular.apply(req)
                    if self.regular.should_flush():
                        # analysis-ok(async_blocking): bounded revert
                        self.flush()
                def flush(self):
                    return self.regular.flush()
        """
        files["pkg/other.py"] = """\
            from .tablet import Tablet
            class Maintenance:
                def __init__(self, tablet: Tablet):
                    self.tablet = tablet
                async def tick(self):
                    self.tablet.flush()
        """
        r = _run(tmp_path, files, "async_blocking")
        paths = {p for p, _, _ in _findings(r)}
        assert not any(p.endswith("peer.py") for p in paths), (
            "annotated edge must stop the apply-path taint",
            r["findings"])
        assert any(p.endswith("other.py") for p in paths), (
            "the unannotated path through Tablet.flush must still "
            "flag", r["findings"])

    def test_attr_type_conflict_poisons_resolution(self, tmp_path):
        # an attr assigned two different classes resolves to neither
        # (under-approximate, never guess)
        files = dict(_APPLY_FLUSH_SHAPE)
        files["pkg/peer.py"] = """\
            from .tablet import Tablet
            class Other:
                def noop(self):
                    return 1
            class TabletPeer:
                def __init__(self, tablet: Tablet):
                    self.tablet = tablet
                    if tablet is None:
                        self.tablet = Other()
                def _apply_payload(self, entry):
                    self.tablet.apply_write(entry.req)
                async def _apply_entry(self, entry):
                    self._apply_payload(entry)
        """
        r = _run(tmp_path, files, "async_blocking")
        assert not any(p.endswith("peer.py")
                       for p, _, _ in _findings(r)), r["findings"]


def _run_pass(tmp_path, files, pass_obj):
    """Like _run but with a pass INSTANCE — the registry-driven passes
    (cache_key_completeness, wire_drift) take synthetic registries."""
    for rel, src in files.items():
        p = tmp_path / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(textwrap.dedent(src))
    index = ProjectIndex(str(tmp_path), roots=("pkg",))
    return run_analysis(index, [pass_obj])


class TestRefusalFlow:
    ERRORS = """\
        class ScanIneligible(Exception):
            pass
        """

    def test_true_positive_transitive(self, tmp_path):
        # the raise and the broad except are two calls apart — the
        # laundering shape no lexical check can see
        r = _run(tmp_path, {
            "pkg/errors.py": self.ERRORS,
            "pkg/fast.py": """\
                from pkg.errors import ScanIneligible
                def fast_path(x):
                    if x < 0:
                        raise ScanIneligible("neg")
                    return x
                def mid(x):
                    return fast_path(x)
                def caller(x):
                    try:
                        return mid(x)
                    except Exception:
                        return None
                """}, "refusal_flow")
        assert _findings(r) == [("pkg/fast.py", 11, "ScanIneligible")]
        # witness: the call that lets the refusal into this def
        assert "mid()" in r["findings"][0]["message"]

    def test_typed_catch_before_broad_is_clean(self, tmp_path):
        r = _run(tmp_path, {
            "pkg/errors.py": self.ERRORS,
            "pkg/fast.py": """\
                from pkg.errors import ScanIneligible
                def fast_path(x):
                    raise ScanIneligible("no")
                def caller(x):
                    try:
                        return fast_path(x)
                    except ScanIneligible:
                        return None          # routed to fallback
                    except Exception:
                        return -1            # real bugs only
                """}, "refusal_flow")
        assert r["findings"] == []

    def test_reraise_and_isinstance_route_are_clean(self, tmp_path):
        r = _run(tmp_path, {
            "pkg/errors.py": self.ERRORS,
            "pkg/fast.py": """\
                from pkg.errors import ScanIneligible
                def fast_path(x):
                    raise ScanIneligible("no")
                def translating(x):
                    try:
                        return fast_path(x)
                    except Exception:
                        raise RuntimeError("ctx")   # not swallowed
                def routing(x):
                    try:
                        return fast_path(x)
                    except Exception as e:
                        if isinstance(e, ScanIneligible):
                            return None
                        return -1
                """}, "refusal_flow")
        assert r["findings"] == []

    def test_marker_class_caught_via_ancestor(self, tmp_path):
        # marker-declared refusal outside an errors module; catching
        # its stdlib ancestor (ValueError) is a typed catch
        r = _run(tmp_path, {"pkg/keys.py": """\
            # analysis: refusal-class
            class KeyRefusal(ValueError):
                pass
            def parse(k):
                raise KeyRefusal(k)
            def ok(k):
                try:
                    return parse(k)
                except ValueError:
                    return None
            def bad(k):
                try:
                    return parse(k)
                except Exception:
                    return None
            """}, "refusal_flow")
        assert _findings(r) == [("pkg/keys.py", 14, "KeyRefusal")]

    def test_suppressed_with_reason(self, tmp_path):
        r = _run(tmp_path, {
            "pkg/errors.py": self.ERRORS,
            "pkg/fast.py": """\
                from pkg.errors import ScanIneligible
                def fast_path(x):
                    raise ScanIneligible("no")
                def boundary(x):
                    try:
                        return fast_path(x)
                    # analysis-ok(refusal_flow): protocol boundary
                    except Exception:
                        return None
                """}, "refusal_flow")
        assert r["findings"] == []
        assert r["suppressions"]["refusal_flow"] == 1

    def test_task_cancel_true_positive(self, tmp_path):
        r = _run(tmp_path, {"pkg/a.py": """\
            import asyncio
            async def shutdown(job):
                task = asyncio.create_task(job())
                task.cancel()
            """}, "refusal_flow")
        assert _findings(r) == [("pkg/a.py", 4, "task.cancel")]
        assert "bpo-37658" in r["findings"][0]["message"]

    def test_task_cancel_drain_loop_and_non_task_clean(self, tmp_path):
        r = _run(tmp_path, {"pkg/a.py": """\
            import asyncio
            async def shutdown(job, timer):
                t = asyncio.create_task(job())
                while not t.done():
                    t.cancel()            # the bpo-37658 drain shape
                    try:
                        await t
                    except asyncio.CancelledError:
                        pass
                timer.cancel()            # not a task: fine
            def sync_stop(task):
                task.cancel()             # sync def: out of scope
            """}, "refusal_flow")
        assert r["findings"] == []


class TestCacheKeyCompleteness:
    CACHE_MOD = """\
        flags = {}
        def _compute(x):
            mode = flags.get("exact_mode")
            return (x, mode)
        class Engine:
            def __init__(self):
                self._cache = {}
            def run(self, x):
                key = ("k", x)
                if key not in self._cache:
                    self._cache[key] = _compute(x)
                return self._cache[key]
        """

    @staticmethod
    def _entry(**over):
        ent = {"key_builder": ("pkg/cachemod.py", "Engine.run"),
               "roots": [("pkg/cachemod.py", "Engine.run")],
               "key_helpers": [], "allow": {}, "must_mention": []}
        ent.update(over)
        return ent

    def _pass(self, **over):
        from analyze.passes.cache_key_completeness import (
            CacheKeyCompletenessPass)
        return CacheKeyCompletenessPass([self._entry(**over)])

    def test_flag_read_missing_from_key(self, tmp_path):
        # the PR-9 shape: the keyed computation reads a flag the key
        # never includes, one call below the key constructor
        r = _run_pass(tmp_path, {"pkg/cachemod.py": self.CACHE_MOD},
                      self._pass())
        assert _findings(r) == [("pkg/cachemod.py", 8,
                                 "Engine.run:exact_mode")]
        assert "_compute" in r["findings"][0]["message"]  # witness chain

    def test_flag_in_key_literal_is_clean(self, tmp_path):
        fixed = self.CACHE_MOD.replace(
            'key = ("k", x)',
            'key = ("k", x, flags.get("exact_mode"))')
        r = _run_pass(tmp_path, {"pkg/cachemod.py": fixed}, self._pass())
        assert r["findings"] == []

    def test_allow_reason_is_clean(self, tmp_path):
        r = _run_pass(
            tmp_path, {"pkg/cachemod.py": self.CACHE_MOD},
            self._pass(allow={"exact_mode": "rebuilt outside the "
                                            "cached lambda"}))
        assert r["findings"] == []

    def test_must_mention_lost_component(self, tmp_path):
        r = _run_pass(
            tmp_path, {"pkg/cachemod.py": self.CACHE_MOD},
            self._pass(allow={"exact_mode": "n/a"},
                       must_mention=[("prune_sig", "pruned identity")]))
        assert _findings(r) == [("pkg/cachemod.py", 8,
                                 "Engine.run:prune_sig")]

    def test_stale_registry_entry(self, tmp_path):
        r = _run_pass(
            tmp_path, {"pkg/cachemod.py": self.CACHE_MOD},
            self._pass(key_builder=("pkg/cachemod.py", "Engine.gone")))
        assert [d for _, _, d in _findings(r)] == [
            "pkg/cachemod.py::Engine.gone"]

    def test_real_registry_pins_known_constructors(self):
        # the registry is the contract: the known keyed caches stay
        # registered, and the PR-9 regression input stays pinned
        from analyze.passes.cache_key_completeness import REGISTRY
        quals = {e["key_builder"][1] for e in REGISTRY}
        assert {"DocReadOperation._batch_cache_key", "prepare_launch",
                "FusedPlanKernel.run"} <= quals
        batch = next(e for e in REGISTRY if e["key_builder"][1]
                     == "DocReadOperation._batch_cache_key")
        assert "device_float_dtype" in dict(batch["must_mention"])


class TestWireDrift:
    @staticmethod
    def _entry(**over):
        ent = {"dataclass": ("pkg/msg.py", "Ping"),
               "encode": ("pkg/msg.py", "ping_to_wire"),
               "decode": ("pkg/msg.py", "ping_from_wire"),
               "ignore": {}, "combined": {}}
        ent.update(over)
        return ent

    def _pass(self, **over):
        from analyze.passes.wire_drift import WireDriftPass
        return WireDriftPass([self._entry(**over)])

    def test_field_dropped_by_both_codecs(self, tmp_path):
        r = _run_pass(tmp_path, {"pkg/msg.py": """\
            from dataclasses import dataclass
            @dataclass
            class Ping:
                a: int
                b: int
                c: int = 0
            def ping_to_wire(p):
                return {"a": p.a, "b": p.b}
            def ping_from_wire(d):
                return Ping(a=d["a"], b=d["b"])
            """}, self._pass())
        assert sorted(d for _, _, d in _findings(r)) == [
            "Ping.c:decode", "Ping.c:encode"]

    def test_round_trip_and_positional_cover_clean(self, tmp_path):
        r = _run_pass(tmp_path, {"pkg/msg.py": """\
            from dataclasses import dataclass
            @dataclass
            class Ping:
                a: int
                b: int
                c: int = 0
            def ping_to_wire(p):
                return (p.a, p.b, p.c)
            def ping_from_wire(w):
                first, second, third = w
                return Ping(first, second, third)
            """}, self._pass())
        assert r["findings"] == []

    def test_ignore_reason_is_clean(self, tmp_path):
        r = _run_pass(tmp_path, {"pkg/msg.py": """\
            from dataclasses import dataclass
            @dataclass
            class Ping:
                a: int
                c: int = 0
            def ping_to_wire(p):
                return {"a": p.a}
            def ping_from_wire(d):
                return Ping(a=d["a"])
            """}, self._pass(ignore={"c": "server-local"}))
        assert r["findings"] == []

    def test_combiner_drops_partial_field(self, tmp_path):
        files = {"pkg/msg.py": """\
            from dataclasses import dataclass
            @dataclass
            class Ping:
                a: int
            def ping_to_wire(p):
                return {"a": p.a}
            def ping_from_wire(d):
                return Ping(a=d["a"])
            def combine(parts):
                return sum(p.b for p in parts)
            """}
        combined = {"a": [("pkg/msg.py", "combine")]}
        r = _run_pass(tmp_path, files, self._pass(combined=combined))
        assert _findings(r) == [("pkg/msg.py", 9, "Ping.a:combine")]

    def test_stale_registry_entry(self, tmp_path):
        r = _run_pass(tmp_path, {"pkg/msg.py": "x = 1\n"}, self._pass())
        assert [d for _, _, d in _findings(r)] == ["pkg/msg.py::Ping"]

    def test_real_registry_pins_known_wire_types(self):
        from analyze.passes.wire_drift import REGISTRY
        names = {e["dataclass"][1] for e in REGISTRY}
        assert {"ReadRequest", "ReadResponse", "WriteRequest", "RowOp",
                "ViewDef"} <= names
        req = next(e for e in REGISTRY
                   if e["dataclass"][1] == "ReadRequest")
        # server-assigned read point must never cross the wire
        assert "server_assigned_read_ht" in req["ignore"]
        resp = next(e for e in REGISTRY
                    if e["dataclass"][1] == "ReadResponse")
        assert set(resp["combined"]) >= {"agg_values", "group_counts",
                                         "group_values"}


class TestNumericExactness:
    def test_narrow_sum_true_positive(self, tmp_path):
        r = _run(tmp_path, {"pkg/k.py": """\
            import jax.numpy as jnp
            def f(col):
                x = col.astype(jnp.int32)
                return jnp.sum(x)
            """}, "numeric_exactness")
        assert _findings(r) == [("pkg/k.py", 4, "sum-dtype")]

    def test_float_accumulator_true_positive(self, tmp_path):
        r = _run(tmp_path, {"pkg/k.py": """\
            import jax.numpy as jnp
            def g(mask):
                m = mask.astype(jnp.int32)
                fm = m.astype(jnp.float32)
                return jnp.sum(fm)
            """}, "numeric_exactness")
        assert _findings(r) == [("pkg/k.py", 5, "float-accumulator")]

    def test_exact_accumulators_clean(self, tmp_path):
        r = _run(tmp_path, {"pkg/k.py": """\
            import jax.numpy as jnp
            def f(col):
                x = col.astype(jnp.int32)
                a = jnp.sum(x, dtype=jnp.int64)   # explicit widen
                y = col.astype(jnp.int64)
                b = jnp.sum(y)                    # already wide
                return a + b
            """}, "numeric_exactness")
        assert r["findings"] == []

    def test_zone_envelope_rule(self, tmp_path):
        r = _run(tmp_path, {
            "pkg/consumer.py": """\
                def prune(block, lo):
                    return block.zmap[0] >= lo
                """,
            "pkg/ops/scan.py": """\
                def _f32_widen(block):
                    return block.zmap          # envelope impl: allowed
                """}, "numeric_exactness")
        assert _findings(r) == [("pkg/consumer.py", 2, "zone-envelope")]

    def test_consts_offset_regression(self, tmp_path):
        # the PR-12 shape: second compile_expr in the same def without
        # offset= re-reads the first expression's constant table
        r = _run(tmp_path, {"pkg/p.py": """\
            from pkg.expr import compile_expr
            def plan(e1, e2):
                a = compile_expr(e1)
                b = compile_expr(e2)
                return a, b
            def fixed(e1, e2):
                a, n = compile_expr(e1)
                b, _ = compile_expr(e2, offset=n)
                return a, b
            """, "pkg/expr.py": "def compile_expr(e, offset=0):\n"
                                "    return e, offset\n"},
            "numeric_exactness")
        assert _findings(r) == [("pkg/p.py", 4, "consts-offset")]

    def test_suppressed_with_reason(self, tmp_path):
        r = _run(tmp_path, {"pkg/k.py": """\
            import jax.numpy as jnp
            def f(col):
                x = col.astype(jnp.int32)
                # analysis-ok(numeric_exactness): block-local partial
                return jnp.sum(x)
            """}, "numeric_exactness")
        assert r["findings"] == []
        assert r["suppressions"]["numeric_exactness"] == 1


# --- 2 + 3. whole tree, schema, budget, baseline ---------------------------

@pytest.fixture(scope="module")
def tree_report():
    index = ProjectIndex(HERE)
    return run_analysis(index, ALL_PASSES)


def test_whole_tree_zero_unannotated_findings(tree_report):
    assert tree_report["parse_errors"] == [], tree_report["parse_errors"]
    assert tree_report["findings"] == [], (
        "unannotated static-analysis findings — fix them or annotate "
        "with `# analysis-ok(<pass>): <reason>`:\n" + "\n".join(
            f"{f['path']}:{f['line']}: [{f['pass']}] {f['message']}"
            for f in tree_report["findings"]))


def test_all_passes_ran(tree_report):
    assert [p["id"] for p in tree_report["passes"]] == [
        "async_blocking", "lock_held_await", "jit_hazards",
        "flag_drift", "shared_state_races", "unawaited_coroutine",
        "format_gate", "layering", "lock_order", "resource_balance",
        "trace_discipline", "refusal_flow", "cache_key_completeness",
        "wire_drift", "numeric_exactness"]


def test_wall_time_budget(tree_report):
    # r05 carry-over hygiene: the sweep must not bloat tier-1
    assert tree_report["wall_ms"] < WALL_BUDGET_MS, tree_report["passes"]
    for p in tree_report["passes"]:
        assert p["wall_ms"] >= 0.0


def test_suppressions_do_not_exceed_baseline(tree_report):
    with open(os.path.join(HERE, "tools", "analyze",
                           "baseline.json")) as f:
        baseline = json.load(f)["suppressions"]
    for pass_id, n in tree_report["suppressions"].items():
        assert n <= baseline.get(pass_id, 0), (
            f"suppression count for {pass_id} grew to {n} vs committed "
            f"baseline {baseline.get(pass_id, 0)} — fix the hazard or "
            f"bump tools/analyze/baseline.json deliberately")


def test_run_py_json_schema():
    r = subprocess.run(
        [sys.executable, os.path.join(HERE, "tools", "analyze", "run.py"),
         "--json"], capture_output=True, text=True)
    assert r.returncode == 0, r.stdout + r.stderr
    report = json.loads(r.stdout)
    for key in ("passes", "findings", "suppressions", "total_findings",
                "total_suppressed", "wall_ms", "parse_errors"):
        assert key in report, key
    assert report["total_findings"] == 0
    assert set(report["suppressions"]) == {p.id for p in ALL_PASSES}
    for p in report["passes"]:
        assert {"id", "title", "findings", "suppressed",
                "wall_ms"} <= set(p)


def test_run_py_changed_mode(tmp_path):
    """--changed <range>: whole-tree index, findings gated to the
    changed files — the CI / pre-push incremental contract."""
    pkg = tmp_path / "yugabyte_db_tpu"
    pkg.mkdir()
    (pkg / "clean.py").write_text("def ok():\n    return 1\n")
    env = {**os.environ, "GIT_AUTHOR_NAME": "t", "GIT_AUTHOR_EMAIL": "t@t",
           "GIT_COMMITTER_NAME": "t", "GIT_COMMITTER_EMAIL": "t@t"}

    def git(*a):
        subprocess.run(["git", *a], cwd=tmp_path, check=True, env=env,
                       capture_output=True)

    git("init", "-q", ".")
    git("add", "-A")
    git("commit", "-qm", "seed")
    # a NEW hazard lands in the tree (git add so the range diff sees
    # the untracked file); clean.py is untouched
    (pkg / "bad.py").write_text(
        "import time\nasync def h():\n    time.sleep(1)\n")
    git("add", "-A")
    run_py = os.path.join(HERE, "tools", "analyze", "run.py")
    r = subprocess.run(
        [sys.executable, run_py, "--base", str(tmp_path),
         "--changed", "HEAD", "--json", "--no-cache"],
        capture_output=True, text=True)
    assert r.returncode == 1, r.stdout + r.stderr
    report = json.loads(r.stdout)
    assert {f["path"] for f in report["findings"]} == \
        {"yugabyte_db_tpu/bad.py"}
    # an unresolvable range is a hard error, not a silent full run
    r2 = subprocess.run(
        [sys.executable, run_py, "--base", str(tmp_path),
         "--changed", "no-such-ref..HEAD", "--no-cache"],
        capture_output=True, text=True)
    assert r2.returncode == 2, r2.stdout + r2.stderr
    # nothing changed in range => trivially clean exit
    git("add", "-A")
    git("commit", "-qm", "hazard (committed so the range is empty)")
    r3 = subprocess.run(
        [sys.executable, run_py, "--base", str(tmp_path),
         "--changed", "HEAD", "--no-cache"],
        capture_output=True, text=True)
    assert r3.returncode == 0, r3.stdout + r3.stderr


def test_run_py_exits_nonzero_on_findings(tmp_path):
    pkg = tmp_path / "pkg"
    pkg.mkdir()
    (pkg / "bad.py").write_text(
        "import time\nasync def h():\n    time.sleep(1)\n")
    r = subprocess.run(
        [sys.executable, os.path.join(HERE, "tools", "analyze", "run.py"),
         "--base", str(tmp_path), "--pass", "async_blocking", "pkg"],
        capture_output=True, text=True)
    assert r.returncode == 1, r.stdout
    assert "time.sleep" in r.stdout


def test_run_py_sarif_contract(tmp_path):
    """--sarif writes a one-run SARIF 2.1.0 log: pass ids as rule ids,
    findings as level=error results anchored at path:line."""
    pkg = tmp_path / "pkg"
    pkg.mkdir()
    (pkg / "bad.py").write_text(
        "import time\nasync def h():\n    time.sleep(1)\n")
    out = tmp_path / "r.sarif"
    r = subprocess.run(
        [sys.executable, os.path.join(HERE, "tools", "analyze", "run.py"),
         "--base", str(tmp_path), "--pass", "async_blocking",
         "--sarif", str(out), "pkg"],
        capture_output=True, text=True)
    assert r.returncode == 1, r.stdout + r.stderr   # exit unchanged
    log = json.loads(out.read_text())
    assert log["version"] == "2.1.0"
    run0 = log["runs"][0]
    rules = run0["tool"]["driver"]["rules"]
    assert [rl["id"] for rl in rules] == ["async_blocking"]
    assert rules[0]["help"]["text"]          # the pass hint
    results = run0["results"]
    assert len(results) == 1
    res = results[0]
    assert res["ruleId"] == "async_blocking"
    assert rules[res["ruleIndex"]]["id"] == res["ruleId"]
    loc = res["locations"][0]["physicalLocation"]
    assert loc["artifactLocation"]["uri"] == "pkg/bad.py"
    assert loc["region"]["startLine"] == 3
    assert "time.sleep" in res["message"]["text"]
