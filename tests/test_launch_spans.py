"""Where a launch's host time goes (`utils/trace.py`, `tablet/tablet.py
serve_read`): a span's thread CPU time beside its wall time, a span kept
after the fact, and a finished launch waiting for the event loop to resume
its read."""
import asyncio
import threading
import time

from yugabyte_db_tpu.tablet.tablet import ServedReads, serve_read
from yugabyte_db_tpu.utils.trace import (_UNSAMPLED_CTX, TRACES,
                                         TraceRegistry, current_context,
                                         use_context)


def _wall_ms(span):
    return (span.end_ns - span.start_ns) / 1e6


def test_cpu_ms_of_a_sleep_is_near_zero():
    with TRACES.trace("root"):
        with TRACES.span("sleeps", cpu=True) as sp:
            time.sleep(0.05)
    assert _wall_ms(sp) >= 50.0
    assert 0.0 <= sp.tags["cpu_ms"] < 5.0


def test_cpu_ms_of_a_busy_loop_is_its_cpu_time():
    """A loop that spins until its thread has run 50 ms: `cpu_ms` holds
    those 50 ms and no more than the span's wall time, which is longer
    only by what the machine's other work took from the thread."""
    with TRACES.trace("root"):
        with TRACES.span("spins", cpu=True) as sp:
            end = time.thread_time() + 0.05
            while time.thread_time() < end:
                pass
        with TRACES.span("plain") as plain:
            pass
    assert 50.0 <= sp.tags["cpu_ms"] <= _wall_ms(sp)
    assert "cpu_ms" not in plain.tags


def test_record_keeps_a_child_only_of_a_sampled_context():
    reg = TraceRegistry()
    reg.record("none", 1, 2, None)
    reg.record("unsampled", 1, 2, _UNSAMPLED_CTX)
    with use_context(_UNSAMPLED_CTX):
        reg.record("ambient_unsampled", 1, 2, current_context())
    assert reg.finished() == []
    with reg.trace("root") as root:
        reg.record("of_span", 10, 20, root, {"k": 1})
        reg.record("of_context", 30, 45, root.context)
    got = {s.name: s for s in reg.finished()}
    assert set(got) == {"root", "of_span", "of_context"}
    for name, (a, b) in {"of_span": (10, 20), "of_context": (30, 45)}.items():
        s = got[name]
        assert (s.trace_id, s.parent_id) == (root.trace_id, root.span_id)
        assert (s.start_ns, s.end_ns) == (a, b)
    assert got["of_span"].tags == {"k": 1} and got["of_context"].tags == {}


def _one_launch_read(returned):
    """A read as steps: one launch, which says when it has returned."""
    with TRACES.span("docdb.read", child_only=True):
        got = yield lambda: returned.set() or 7
        return got + 1


def test_a_finished_launch_waiting_for_the_loop_is_read_resume():
    """The launch returns at once; the loop is held 80 ms by something
    else: the read's `tserver.read_resume` holds that wait (at least 50
    ms of it, whatever the launch thread's own way back took), under the
    read's own span, and the loop's time in the read's steps is a tag of
    the span the read began under."""
    returned = threading.Event()

    async def main():
        with TRACES.trace("tserver.read:t") as read:
            task = asyncio.ensure_future(serve_read(
                _one_launch_read(returned), ServedReads("resume-test")))
            await asyncio.sleep(0)         # the read hands its launch over
            assert returned.wait(10)       # the launch has returned ...
            time.sleep(0.08)               # ... and the loop is held
            return await task, read

    resp, read = asyncio.run(main())
    assert resp == 8
    spans = [s for s in TRACES.finished() if s.trace_id == read.trace_id]
    resume, = [s for s in spans if s.name == "tserver.read_resume"]
    doc, = [s for s in spans if s.name == "docdb.read"]
    hop, = [s for s in spans if s.name == "tserver.read_offload"]
    assert resume.parent_id == doc.span_id and hop.parent_id == read.span_id
    assert _wall_ms(resume) >= 50.0
    assert doc.start_ns < resume.start_ns < resume.end_ns <= doc.end_ns
    assert resume.tags == {"in_flight": 1}
    assert 0.0 < read.tags["steps_ms"] < _wall_ms(read) - _wall_ms(resume)


def test_an_unsampled_read_records_no_resume_and_no_steps():
    returned = threading.Event()

    def ours():
        return [s for s in TRACES.finished() if s.name in (
            "docdb.read", "tserver.read_offload", "tserver.read_resume")]
    before = ours()

    async def main():
        with use_context(_UNSAMPLED_CTX):
            return await serve_read(_one_launch_read(returned),
                                    ServedReads("resume-test"))

    assert asyncio.run(main()) == 8 and returned.is_set()
    assert ours() == before
