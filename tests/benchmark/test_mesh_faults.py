"""`mesh4_q1_psum` rehearsed on four of the CPU's virtual devices with one
fault planted in the program, in the manner of `test_faults.py`: one
chip's partial left out of the combine, a combined partial added twice
by the client, the table cached on one chip of the four.  `correct` has to
come out false each time, by the number that names the fault."""
import jax
import pytest

from benchmark import manifest, run

CELL = "mesh4_q1_psum"
pytestmark = pytest.mark.skipif(len(jax.devices()) < 4,
                                reason="needs 4 devices")


def _rehearse() -> dict:
    rows = manifest.Cell(manifest.load(), CELL).config["rehearsal"]["rows"]
    return run.run_cell(["--workload", CELL, "--seed", "77", "--seconds",
                         "1.5", "--rows", str(rows), "--rehearse"])


def _bad(result) -> set:
    return {k for k, (v, limit) in result["compared"].items()
            if v is None or v > limit}


def test_the_sound_rehearsal_is_one_launch_a_statement_on_four_chips():
    from yugabyte_db_tpu.docdb import mesh_read
    before = mesh_read._MESH_KERNEL.compiles
    result = _rehearse()
    assert result["correct"] is True, result["compared"]
    assert result["device"]["chips"] == 4
    assert result["compared"]["batches_off_device"] == [0.0, 0]
    # ANALYZE, Q6 and Q1: the mesh programs of the warm-up, none after
    assert 1 <= mesh_read._MESH_KERNEL.compiles - before <= 3
    assert result["compiles_in_window"] == 0


def test_a_chips_partial_left_out_is_not_correct(monkeypatch):
    """The fourth chip's shard is built empty: its two tablets' rows are
    in no answer, while every lane still covers the four chips."""
    from yugabyte_db_tpu.docdb import mesh_read
    real = mesh_read.build_sharded_batch

    def without_a_chip(tm, per_shard_blocks, columns, **kw):
        return real(tm, list(per_shard_blocks[:-1]) + [[]], columns, **kw)

    monkeypatch.setattr(mesh_read, "build_sharded_batch", without_a_chip)
    result = _rehearse()
    assert result["correct"] is False
    bad = _bad(result)
    assert {"q1_count_diff", "q1_qty_diff", "sum_usd"} <= bad
    assert "batches_off_device" not in bad


def test_a_combined_partial_added_twice_is_not_correct(monkeypatch):
    """The client counts the server's one answer, already combined over
    its 8 tablets, and then once more."""
    from yugabyte_db_tpu.client.client import YBClient
    real = YBClient._combine
    monkeypatch.setattr(
        YBClient, "_combine", lambda self, req, parts: real(
            self, req, parts + parts[:1] if req.group_by is not None
            or len(parts) == 1 else parts))
    result = _rehearse()
    assert result["correct"] is False
    assert {"q1_count_diff", "sum_usd"} <= _bad(result)


def test_a_table_cached_on_one_chip_of_four_is_not_correct(monkeypatch):
    """The client asks tablet by tablet, so the server serves each on its
    default chip: every answer is right and three chips hold nothing."""
    from yugabyte_db_tpu.client import client
    monkeypatch.setattr(client, "_mesh_groups",
                        lambda req, locations: ([], locations))
    result = _rehearse()
    assert result["correct"] is False
    assert _bad(result) == {"batches_off_device"}
