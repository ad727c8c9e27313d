"""The `attn_ms` reader on a hand-made trace summary: the kernel's device
time per execution where it ran, None where it did not."""
import types

import pytest

import conftest
from bench import harness, trace

READER = harness.load_file_module(conftest.ROOT / "bench/metrics/attn_ms.py")


def _run(ops, executions=4):
    s = trace.Summary(window_s=1.0, busy_by_chip={0: 0.8},
                      executions=[("jit_lm_forward", 0, 0.16)] * executions,
                      ops=ops, gaps=[])
    return types.SimpleNamespace(trace=s)


def test_kernel_time_per_execution():
    run = _run({"%flash_attention.6 bf16[8,512,5120] custom-call": 0.010,
                "%flash_attention.7 bf16[8,512,5120] custom-call": 0.002,
                "%fusion.142 bf16[8,512,17408] fusion": 0.5})
    assert READER.read(run) == pytest.approx(3.0)


@pytest.mark.parametrize("ops,executions", [
    ({"%fusion.139 f32[8,40,512,512] fusion": 0.1}, 4),   # the XLA path
    ({"%flash_attention.6 bf16[8,512,5120] custom-call": 0.1}, 0),
])
def test_none_where_the_kernel_did_not_run(ops, executions):
    assert READER.read(_run(ops, executions)) is None


def test_none_without_a_trace():
    assert READER.read(types.SimpleNamespace(trace=None)) is None


def test_the_name_is_the_kernels():
    from repro.kernels.flash_attention import flash_attention as knl
    assert READER.KERNEL == knl.KERNEL_NAME
