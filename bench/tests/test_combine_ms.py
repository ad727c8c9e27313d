"""The `combine_ms` reader on a hand-made trace summary: the combine
kernel's device time per execution where it ran, None where it did not
(the parent's program gathers the rows in XLA fusions)."""
import types

import pytest

import conftest
from bench import harness, trace

READER = harness.load_file_module(conftest.ROOT / "bench/metrics/combine_ms.py")
OPS = {"%moe_gmm.24 bf16[32768,1408] custom-call": 0.090,
       "%moe_combine.3 bf16[4096,2048] custom-call": 0.004,
       "%moe_combine.4 bf16[4096,2048] custom-call": 0.002,
       "%fusion.281 bf16[24576,2048] fusion": 0.5}


def _run(ops=OPS, executions=4):
    s = trace.Summary(window_s=1.0, busy_by_chip={0: 0.8},
                      executions=[("jit_moe_forward", 0, 0.06)] * executions,
                      ops=ops, gaps=[])
    return types.SimpleNamespace(trace=s)


def test_kernel_time_per_execution():
    assert READER.read(_run()) == pytest.approx(1.5)     # 6 ms / 4


@pytest.mark.parametrize("run", [
    _run(ops={"%moe_gmm.24 bf16[32768,1408] custom-call": 0.09,
              "%fusion.281 bf16[24576,2048] fusion": 0.5}),
    _run(executions=0),
    types.SimpleNamespace(trace=None),
], ids=["xla-combine", "no-execution", "no-trace"])
def test_none_where_the_kernel_did_not_run(run):
    assert READER.read(run) is None


def test_the_name_is_the_kernels_and_not_the_matmuls():
    from repro.kernels.moe_gmm import moe_gmm as knl
    experts = harness.load_file_module(conftest.ROOT /
                                       "bench/metrics/experts_ms.py")
    assert READER.KERNEL == knl.COMBINE_NAME
    assert not knl.COMBINE_NAME.startswith(experts.KERNEL)
