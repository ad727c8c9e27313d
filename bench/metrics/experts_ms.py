"""experts_ms: device milliseconds of the routed experts' grouped matmul
per program execution, from the profiler trace: the operations named
after the kernel (`moe_gmm`, its `pallas_call` name), summed, over the
executions in the window.  None where no such operation ran."""

KERNEL = "moe_gmm"


def kernel_seconds(run) -> float | None:
    """The kernel's device seconds in the window; None without a trace,
    an execution or the kernel."""
    if run.trace is None or not run.trace.executions:
        return None
    secs = [s for name, s in run.trace.ops.items()
            if name.split(" ", 1)[0].lstrip("%").startswith(KERNEL)]
    return sum(secs) if secs else None


def read(run):
    secs = kernel_seconds(run)
    return None if secs is None else 1e3 * secs / len(run.trace.executions)
