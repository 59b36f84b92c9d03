"""Each cell end to end on the CPU at a tiny size: the phases of a run,
`correct`, no compile inside the window, no metric reported off a TPU, the
shape of the last line — and the look for a chip, which a run without
`--rehearse` fails here."""
import json

import pytest

from benchmark import manifest, run



def full():
    """The manifest with the cells kept for a later PR."""
    return manifest.with_deferred(manifest.load())


CELLS = [w["name"] for w in full()["workloads"]]


def rehearse(cell, *more):
    """At the rows the cell's own configuration states for a rehearsal."""
    rows = manifest.Cell(full(), cell).config["rehearsal"]["rows"]
    return run.run_cell(["--workload", cell, "--seed", "2147484001",
                         "--seconds", "2", "--rows", str(rows), "--rehearse",
                         *more])


@pytest.mark.parametrize("cell", CELLS)
def test_rehearsal_is_correct_and_reports_no_metric(cell, capsys):
    result = rehearse(cell)
    steps = [json.loads(l) for l in capsys.readouterr().out.splitlines()
             if l.startswith('{"step"')]
    assert [s["step"] for s in steps] == [
        "devices", "start", "load", "warm", "setup", "window", "verify"]
    assert result["correct"] is True, result["compared"]
    assert result["compiles_in_window"] == 0
    assert steps[5]["compiles_in_window"] == 0 and steps[5]["errors"] == []
    ssts_before, ssts_after = steps[5]["ssts_per_tablet_before_after"]
    # under the background compaction trigger (a driver that records no
    # SST counts has no tablets to compact)
    assert max(ssts_before, default=0) < 4
    assert result["attempted"] > 0 and result["failed"] == 0
    assert result["metrics"] == {}            # a CPU run names no metric
    assert result["device"]["platform"] == "cpu"
    assert list(result)[-1] == "compared"     # the numbers compared come last
    assert all(v is not None and v <= limit
               for v, limit in result["compared"].values())
    json.dumps(result, allow_nan=False)
    limits = manifest.Cell(full(), cell).config["limits"]
    assert set(result["compared"]) == set(limits)


def test_traced_rehearsal_keeps_the_window_to_trace_seconds(capsys):
    result = rehearse("scan_power", "--trace", "1", "--seconds", "30")
    window = next(json.loads(l) for l in capsys.readouterr().out.splitlines()
                  if l.startswith('{"step": "window"'))
    assert result["correct"] is True and result["metrics"] == {}
    assert window["seconds"] < 30


def test_without_a_tpu_the_command_exits_nonzero_and_prints_no_result(capsys):
    rc = run.main(["--workload", "scan_power", "--seed", "1",
                   "--seconds", "1", "--trace", "0"])
    out = capsys.readouterr()
    assert rc == run.EXIT_NO_CHIP and rc != 0
    assert '"correct"' not in out.out and "TPU" in out.err


def test_unknown_workload_exits_nonzero(capsys):
    assert run.main(["--workload", "no_such_cell", "--rehearse"]) != 0
    assert '"correct"' not in capsys.readouterr().out
