"""The trace reduction, on a TPU trace recorded by `record_trace.py` and
on interval arithmetic."""
from pathlib import Path

import pytest

from bench import trace

FIXTURE = Path(__file__).parent / "data" / "tpu_small.xplane.pb"


def test_union_merges_overlaps():
    assert trace._union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]


def test_gap_is_named_by_the_span_covering_most_of_it():
    spans = [("bench.submit", 0, 4), ("bench.wait", 4, 20)]
    assert trace._label((3, 10), spans) == "bench.wait"
    assert trace._label((30, 40), spans) == trace.NO_SPAN


@pytest.mark.skipif(not FIXTURE.exists(), reason="no recorded trace")
def test_recorded_tpu_trace():
    s = trace.reduce(FIXTURE)
    # one chip; five executions in the window, of two programs; the one
    # before the window is left out
    assert s.n_chips == 1
    assert len(s.executions) == 5
    names = sorted({n for n, _, _ in s.executions})
    assert len(names) == 2
    counts = sorted(sum(1 for n, _, _ in s.executions if n == m)
                    for m in names)
    assert counts == [2, 3]
    # five 20 ms host waits: the window is longer than 0.1 s and the chip
    # idles through most of it
    assert s.window_s > 0.1
    assert 0 < s.busy_s < s.window_s
    assert s.idle_share() > 0.5
    b = s.breakdown()
    assert 0 < len(b["device_ops"]) <= 10
    assert b["idle_gaps"][0][0] == "chip0: bench.wait"
    assert b["idle_gaps"][0][1] >= 0.019
    assert sum(t for _, t in b["device_ops"]) == pytest.approx(s.busy_s,
                                                              rel=0.05)


def test_self_times_leave_out_nested_operations():
    got = trace._self_times([(0, 10, "while"), (1, 4, "a"), (5, 9, "b"),
                             (12, 13, "c")])
    assert dict(got) == pytest.approx({"while": 3e-9, "a": 3e-9,
                                       "b": 4e-9, "c": 1e-9})


def test_op_name_is_short():
    assert trace.op_name(
        "%fusion.128 = bf16[8,512]{1,0:T(8,128)} fusion(bf16[8] %x), "
        "kind=kOutput") == "%fusion.128 bf16[8,512] fusion"
    assert trace.op_name(
        "%copy-start = (bf16[2]{0:S(1)}, u32[]{:S(2)}) copy-start(%p)"
    ) == "%copy-start (bf16[2], u32[]) copy-start"
