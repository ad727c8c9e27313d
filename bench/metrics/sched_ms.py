"""sched_ms: mean milliseconds of one of the daemon's scheduling passes in
the window (`Daemon.stats` sched_ns over sched_calls)."""


def read(run):
    calls = run.delta("sched_calls")
    if calls <= 0:
        return None
    return run.delta("sched_ns") / calls / 1e6
