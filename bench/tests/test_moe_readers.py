"""The `moonlight.batch` readers on a hand-made trace summary: `moe_mfu`,
`experts_ms` and `gmm_roofline` where the program and its kernel ran,
None where they did not."""
import types

import pytest

import conftest
from bench import harness, trace

METRICS = conftest.ROOT / "bench" / "metrics"
MFU = harness.load_file_module(METRICS / "moe_mfu.py")
EXPERTS = harness.load_file_module(METRICS / "experts_ms.py")
ROOF = harness.load_file_module(METRICS / "gmm_roofline.py")
PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
OPS = {"%moe_gmm.24 bf16[32768,1408] custom-call": 0.090,
       "%moe_gmm.25 bf16[32768,2048] custom-call": 0.030,
       "%fusion.281 bf16[24576,2048] fusion": 0.5}


def _run(ops=OPS, executions=4, flops=True):
    s = trace.Summary(window_s=1.0, busy_by_chip={0: 0.8},
                      executions=[("jit_moe_forward", 0, 0.08)] * executions,
                      ops=ops, gaps=[])
    return types.SimpleNamespace(
        trace=s, peak=PEAK, cell=harness.find_cell("moonlight.batch"),
        flops_per_chunk={"moe-forward": 6.32e12} if flops else {})


def test_readings_where_the_program_and_kernel_ran():
    run = _run()
    assert MFU.read(run) == pytest.approx(100 * 6.32e12 / (0.08 * 197e12))
    assert EXPERTS.read(run) == pytest.approx(30.0)     # 0.12 s / 4
    mod = harness.module_file(run.cell, "moe-forward")
    work = mod.gmm_work(run.cell.config, run.cell.config["modules"][0])
    least = max(work["flops"] / 197e12, work["bytes"] / 819e9)
    assert least == pytest.approx(3.4016e12 / 197e12, rel=1e-4)
    assert ROOF.read(run) == pytest.approx(100 * least / 0.030)


@pytest.mark.parametrize("run", [
    _run(ops={"%flash_attention.6 bf16[8,512,5120] custom-call": 0.1}),
    _run(executions=0),
    types.SimpleNamespace(trace=None, flops_per_chunk={}),
], ids=["no-kernel", "no-execution", "no-trace"])
def test_none_where_the_kernel_did_not_run(run):
    assert EXPERTS.read(run) is None and ROOF.read(run) is None


def test_moe_mfu_none_without_the_module_or_a_trace():
    assert MFU.read(_run(flops=False)) is None
    assert MFU.read(types.SimpleNamespace(trace=None,
                                          flops_per_chunk={})) is None


def test_the_name_is_the_kernels():
    from repro.kernels.moe_gmm import moe_gmm as knl
    assert EXPERTS.KERNEL == knl.KERNEL_NAME
