"""The rest of a run with the timed path broken underneath: the look for a
chip skipped (`--rehearse`), one fault planted in the program, and
`correct` has to come out false — once for each fault a cell can have:
an answer altered where it is produced, part of the batch (a tablet's
partial) left out, a step that returns its state unchanged (a compaction
that compacts nothing), an acknowledged write that was never applied."""
import numpy as np
import pytest

from benchmark import run

ARGS = ["--seed", "77", "--seconds", "1.5", "--rows", "24000", "--rehearse"]


def _bad(result) -> set:
    """The numbers that failed their limit, or were never compared."""
    return {k for k, (v, limit) in result["compared"].items()
            if v is None or v > limit}


def _kernel():
    """The scan kernel's class: patched there, not on the shared instance,
    where the undo would leave an instance attribute that shadows a later
    test's patch of the class."""
    from yugabyte_db_tpu.ops.scan import ScanKernel
    return ScanKernel


@pytest.mark.parametrize("cell", ["scan_power", "refresh_compact"])
def test_an_altered_answer_is_not_correct(cell, monkeypatch):
    real = _kernel().run

    def altered(self, batch, where=None, aggs=(), group=None, read_ht=None):
        outs, counts, *rest = real(self, batch, where, aggs, group, read_ht)
        outs = tuple(None if o is None else np.asarray(o) * 1.001
                     for o in outs)
        return (outs, np.asarray(counts) + 1, *rest)

    monkeypatch.setattr(_kernel(), "run", altered)
    result = run.run_cell(["--workload", cell, *ARGS])
    assert result["correct"] is False
    bad = _bad(result)
    # (a count of one in every slot also makes groups that do not exist)
    assert "sum_usd" in bad and bad & {"q1_count_diff", "q1_shape"}


@pytest.mark.parametrize("cell", ["scan_power"])
def test_a_tablet_left_out_is_not_correct(cell, monkeypatch):
    real, calls = _kernel().run, [0]

    def partial(self, batch, where=None, aggs=(), group=None, read_ht=None):
        outs, counts, *rest = real(self, batch, where, aggs, group, read_ht)
        calls[0] += 1
        if aggs and calls[0] % 4 == 0:      # one tablet in four says nothing
            outs = tuple(None if o is None else np.asarray(o) * 0
                         for o in outs)
            counts = np.asarray(counts) * 0
        return (outs, counts, *rest)

    monkeypatch.setattr(_kernel(), "run", partial)
    result = run.run_cell(["--workload", cell, *ARGS])
    assert result["correct"] is False
    value, limit = result["compared"]["q1_count_diff"]
    assert value > limit


def _around_the_window(monkeypatch, around) -> None:
    """The refresh driver's `window` as the run loads it (by its file,
    anew each run), called through `around(real_window, cluster, traffic,
    seconds, rec)`."""
    from benchmark import manifest
    real_load = manifest.load_module

    def load_module(path):
        mod = real_load(path)
        if path.endswith("drivers/refresh_compact.py"):
            real_window = mod.window
            mod.window = lambda *a: around(real_window, *a)
        return mod

    monkeypatch.setattr(manifest, "load_module", load_module)


@pytest.mark.parametrize("which", ["every", "window_only"])
def test_a_compaction_that_changes_nothing_is_not_correct(which, monkeypatch):
    """`window_only`: set-up's compactions and the one the check sends
    after the window are real; only the timed ones return having done
    nothing, so it is their own SST counts that have to catch it."""
    from yugabyte_db_tpu.tablet.tablet import Tablet
    real, in_window = Tablet.compact, [which == "every"]
    monkeypatch.setattr(
        Tablet, "compact", lambda self, major=True:
        None if in_window[0] else real(self, major))

    async def window(real_window, *a):
        in_window[0] = True
        try:
            return await real_window(*a)
        finally:
            in_window[0] = which == "every"

    _around_the_window(monkeypatch, window)
    result = run.run_cell(["--workload", "refresh_compact", *ARGS])
    assert result["correct"] is False
    value, limit = result["compared"]["ssts_after_compact"]
    assert value > limit


def test_an_acknowledged_insert_that_was_dropped_is_not_correct(monkeypatch):
    """One acknowledged INSERT, the window's first, is never applied; the
    loader's, the warm-up's and the window's two later ones are sound.
    The window is three whole iterations whatever the host's speed (each
    the driver's own window with a deadline one iteration outlasts): the
    read-back draws half of its 16 orders from the seed among all that
    were inserted, so what it draws depends on how many iterations there
    were, and `q1_count_diff` alone sees a lost write it did not draw."""
    from yugabyte_db_tpu.ql.executor import SqlResult, SqlSession
    real, seen = SqlSession._insert, [0]

    async def lossy(self, stmt):
        seen[0] += 1
        if seen[0] == 3:     # the loader's and the warm-up's come first
            return SqlResult([], "INSERT 0 0")
        return await real(self, stmt)

    async def three_iterations(real_window, cluster, traffic, seconds, rec):
        for _ in range(3):
            await real_window(cluster, traffic, 1e-3, rec)

    monkeypatch.setattr(SqlSession, "_insert", lossy)
    _around_the_window(monkeypatch, three_iterations)
    result = run.run_cell(["--workload", "refresh_compact", *ARGS])
    assert seen[0] >= 5                  # 2 before the window, 3 inside
    assert result["correct"] is False
    assert {"readback_missing", "q1_count_diff"} <= _bad(result)
    assert result["compared"]["readback_value_diff"] == [0.0, 0]
