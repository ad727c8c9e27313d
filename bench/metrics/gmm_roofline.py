"""gmm_roofline: the routed experts' grouped matmul against its roofline,
in %: per execution, the least time the chip could take for the kernel's
work, max(operations / bf16 peak, HBM bytes / HBM peak), with the work
from `gmm_work` of the module file (computed from the configuration and
the chunk, not from how the kernel is called), over the kernel's measured
device time (`experts_ms`).  None where the kernel did not run."""
from pathlib import Path

from bench import harness

MODULE = "moe-forward"
_EXPERTS = harness.load_file_module(Path(__file__).with_name(
    "experts_ms.py"))


def read(run):
    secs = _EXPERTS.kernel_seconds(run)
    if secs is None:
        return None
    entry = next(m for m in run.cell.config["modules"]
                 if m["name"] == MODULE)
    work = harness.module_file(run.cell, MODULE).gmm_work(run.cell.config,
                                                          entry)
    least = max(work["flops"] / run.peak["bf16_flops_per_s"],
                work["bytes"] / run.peak["hbm_bytes_per_s"])
    return 100.0 * least * len(run.trace.executions) / secs
