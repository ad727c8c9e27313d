"""attn_ms: device milliseconds of the Pallas flash-attention kernel per
program execution, from the profiler trace: the operations named after
the kernel (`flash_attention`, its `pallas_call` name), summed, over the
executions in the window.  None where no such operation ran, as on the
XLA attention path."""

KERNEL = "flash_attention"


def read(run):
    if run.trace is None or not run.trace.executions:
        return None
    secs = [s for name, s in run.trace.ops.items()
            if name.split(" ", 1)[0].lstrip("%").startswith(KERNEL)]
    if not secs:
        return None
    return 1e3 * sum(secs) / len(run.trace.executions)
