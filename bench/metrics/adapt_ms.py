"""adapt_ms: mean milliseconds of `bus.adapt_inputs` per chunk run on a
slot in the window (`Daemon.stats` adapt_ns over runs): casting, padding
and copying a chunk's inputs to the default device.  None from a daemon
without these counters."""


def read(run):
    if "adapt_ns" not in run.stats1:
        return None
    runs = run.delta("runs")
    if runs <= 0:
        return None
    return run.delta("adapt_ns") / runs / 1e6
