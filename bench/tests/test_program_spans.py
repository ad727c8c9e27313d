"""The daemon's spans against the chips' idle time (`program_spans.py`),
on interval arithmetic and the recorded trace, and the program's counters
in a traced run of a tiny cell."""
from pathlib import Path

import pytest

from bench import harness, program_spans, trace

FIXTURE = Path(__file__).parent / "data" / "tpu_small.xplane.pb"
# three one-chunk jobs through the daemon (`record_fos_trace.py`)
DAEMON = Path(__file__).parent / "data" / "tpu_daemon.xplane.pb"
SEED = 2 ** 33 + 7


def test_idle_goes_to_the_innermost_span_and_sums_to_the_gap():
    spans = [("fos.chunk", 0, 100, "worker"), ("fos.wait", 10, 60, "worker"),
             ("fos.complete", 60, 70, "worker"),
             # the loop's pass, opened last
             ("fos.schedule", 65, 80, "loop")]
    parts = program_spans.split_idle((50, 120), spans)
    assert parts == {"fos.wait": 10, "fos.complete": 5, "fos.schedule": 15,
                     "fos.chunk": 20, program_spans.NO_FOS_SPAN: 20}
    assert sum(parts.values()) == 70
    assert program_spans.split_idle((200, 210), spans) == {
        program_spans.NO_FOS_SPAN: 10}


def test_a_child_opened_with_its_parent_is_inner():
    parts = program_spans.split_idle(
        (0, 10), [("fos.chunk", 0, 10, 1), ("fos.slot_wait", 0, 4, 1)])
    assert parts == {"fos.slot_wait": 4, "fos.chunk": 6}


def test_a_thread_waiting_for_the_slot_yields_to_the_one_holding_it():
    # a preemptor waits for the slot while its victim's run stalls
    spans = [("fos.chunk", 0, 100, "victim"), ("fos.wait", 5, 90, "victim"),
             ("fos.chunk", 20, 200, "preemptor"),
             ("fos.slot_wait", 20, 95, "preemptor")]
    assert program_spans.split_idle((30, 80), spans) == {"fos.wait": 50}
    assert program_spans.split_idle((92, 98), spans) == {
        "fos.chunk": 6}


def test_gap_label_appends_the_span_covering_most():
    assert program_spans.gap_label(
        "bench.wait", {"fos.dispatch": 3, "fos.put": 1,
                       program_spans.NO_FOS_SPAN: 9}) \
        == "bench.wait > fos.dispatch"
    assert program_spans.gap_label(
        "bench.wait", {program_spans.NO_FOS_SPAN: 9}) == "bench.wait"


def test_clock_offset_places_the_most_executions_in_their_flights():
    execs = [(0, 10), (100, 110), (200, 210)]
    flights = [(2, 15), (103, 115), (150, 160)]
    # the first two fit offsets [2, 5] and [3, 5]; the third fits none
    assert program_spans.clock_offset(execs, flights) == (3, 5, 2)
    assert program_spans.clock_offset(execs, []) is None


@pytest.mark.skipif(not DAEMON.exists(), reason="no recorded trace")
def test_recorded_daemon_trace():
    s = trace.reduce(DAEMON)
    p = program_spans.reduce(str(DAEMON))
    assert p.n_chips == 1 and len(s.executions) == 3
    # the device clock ran 1.0-1.7 ms behind the host's on this trace
    least, used, most = p.offsets_ms[0]
    assert 0.9 < least < used < most < 2.0
    # every idle second is given to a span or to none
    assert p.idle_s == pytest.approx(s.window_s - s.busy_s, rel=0.01)
    for step in ("fos.wait", "fos.adapt", "fos.put", "fos.dispatch",
                 "fos.schedule", program_spans.NO_FOS_SPAN):
        assert p.idle_under[step] > 0, step
    # the three 20 ms sleeps between jobs, covered in part by a chunk's
    # spans, are the longest gaps
    gaps = p.breakdown()["idle_gaps"]
    assert all(label.startswith("chip0: bench.wait > fos.")
               for label, _ in gaps[:3])
    assert all(sec > 0.019 for _, sec in gaps[:3])


@pytest.mark.skipif(not FIXTURE.exists(), reason="no recorded trace")
def test_a_trace_without_program_spans_gives_none():
    assert program_spans.reduce(str(FIXTURE)) is None


def test_traced_run_reports_the_program_counters(tiny_root):
    out = harness.run("tiny.lm", SEED, 1.5, True, root=tiny_root,
                      require_tpu=False,
                      trace_dir=tiny_root / "bench" / "out" / "trace")
    assert out["correct"], out["checks"]
    m = out["metrics"]
    assert m["queue_ms"]["value"] > 0
    assert m["slot_wait_ms"]["value"] > 0
    assert 0 <= m["discarded_share"]["value"] < 100
    assert m["adapt_ms"]["value"] > 0
    assert m["adapt_ms.shared"] == m["adapt_ms"]
    # no chip: no device plane, so nothing to split
    assert not [k for k in m if k.startswith("wait_idle")]
