"""`scan_streams2` with a fault planted: a statement sent with another
statement's substitution parameters — with two streams out of step, the
other stream's — while its span records the parameters it drew.  Every
answer is then a right answer to the wrong question, and `correct` has to
come out false.  The sound rehearsal is `test_rehearsal.py`'s."""
from benchmark import run, tpch_qgen

ARGS = ["--seed", "78", "--seconds", "1.5", "--rows", "24000", "--rehearse"]


def test_the_other_streams_parameters_are_not_correct(monkeypatch):
    real, last, swapped = tpch_qgen.sql, {}, [0]

    def others(query, params, name=tpch_qgen.tpch.TABLE):
        text = real(query, params, name)
        sent, last[query] = last.get(query, text), text
        swapped[0] += sent != text
        return sent

    monkeypatch.setattr(tpch_qgen, "sql", others)
    result = run.run_cell(["--workload", "scan_streams2", *ARGS])
    assert swapped[0] > 10
    assert result["failed"] == 0          # every statement was answered
    assert result["correct"] is False
    bad = {k for k, (v, limit) in result["compared"].items()
           if v is None or v > limit}
    assert {"sum_usd", "q1_count_diff"} <= bad
    assert not bad & {"q1_shape", "q6_shape", "stmt_failed",
                      "compiles_in_window", "batches_off_device"}
