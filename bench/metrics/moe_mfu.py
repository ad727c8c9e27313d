"""moe_mfu: the `moe-forward` executions' share of the chip's bf16 peak:
their operations (`flops_per_chunk` of the module file, from the
configuration's shapes) over their device time in the trace times the
peak.  Every module's program is named alike, so this takes the one XLA
program that ran in the window, and fails if more than one did."""

MODULE = "moe-forward"


def read(run):
    if run.trace is None or MODULE not in run.flops_per_chunk:
        return None
    names = {name for name, _, _ in run.trace.executions}
    if not names:
        return None
    if len(names) > 1:
        raise ValueError(f"moe_mfu: executions of {len(names)} programs in "
                         f"the window, {sorted(names)}; cannot tell "
                         f"{MODULE}'s apart")
    times = [s for _, _, s in run.trace.executions]
    flops = len(times) * run.flops_per_chunk[MODULE]
    return 100.0 * flops / (sum(times) * run.peak["bf16_flops_per_s"])
