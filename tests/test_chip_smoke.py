"""chip_smoke.py's phases, in this process, at a tiny size on the CPU:
the first rehearsal of /opt/skills/guides/on-chip-measurement §2 kept as
a test.  Every phase must pass — and the run must still refuse to claim
the chip: a CPU run never prints `"ok": true`."""
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import chip_smoke  # noqa: E402


def test_cpu_rehearsal_runs_every_phase_and_never_claims_the_chip(capsys):
    rc = chip_smoke.main(["--sf", "0.002"])
    lines = [json.loads(l) for l in capsys.readouterr().out.splitlines()
             if l.startswith("{")]
    phases = {l["phase"]: l for l in lines if "phase" in l}
    assert list(phases) == ["devices", "start", "load", "query", "compact",
                            "shutdown", "end"]
    assert all(phases[p]["ok"] is True for p in phases if p != "devices")
    assert phases["devices"]["platform"] == "cpu"
    assert phases["load"]["rows"] == 12_000 + chip_smoke.INSERT_ROWS
    assert phases["query"]["read_back"] == chip_smoke.INSERT_ROWS
    assert phases["compact"]["ssts_per_tablet"] == [1] * chip_smoke.TABLETS
    # the CPU arms ran, and said so
    assert phases["start"]["arms"] == {
        "value_lanes": "float64", "group_strategy": "segment",
        "sum_magnitude_cap": "f64", "compaction_merge": "native",
        "pallas_scan": "off (opt-in flag)"}
    assert lines[-1]["ok"] is False and rc != 0
    assert lines[-1]["device"]["platform"] == "cpu"
