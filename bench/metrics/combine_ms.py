"""combine_ms: device milliseconds of the routed experts' combine per
program execution, from the profiler trace: the operations named after
the Pallas kernel that sums each token's expert rows (`moe_combine`, its
`pallas_call` name), summed, over the executions in the window.  None
where no such operation ran, as on the XLA path, which gathers the rows
in fusions of no fixed name."""

KERNEL = "moe_combine"


def read(run):
    if run.trace is None or not run.trace.executions:
        return None
    secs = [s for name, s in run.trace.ops.items()
            if name.split(" ", 1)[0].lstrip("%").startswith(KERNEL)]
    if not secs:
        return None
    return 1e3 * sum(secs) / len(run.trace.executions)
