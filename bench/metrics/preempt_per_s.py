"""preempt_per_s: chunks the daemon preempted in the window, per second
(`Daemon.stats` preemptions)."""


def read(run):
    return run.delta("preemptions") / run.window_s
