"""Share of the traced window in which no operation ran on a chip, the
mean over the cell's chips, from the profiler trace."""


def read(run):
    if run.trace is None:
        return None
    idle = run.trace.idle_share()
    return None if idle is None else 100.0 * idle
