"""queue_ms: mean milliseconds a job waited in the daemon's queue, from
`Daemon.submit` to the scheduling pass that issued its first chunk, over
the jobs first issued in the window (`Daemon.stats` queue_ns over
queue_jobs).  None from a daemon without these counters."""


def read(run):
    if "queue_jobs" not in run.stats1:
        return None
    jobs = run.delta("queue_jobs")
    if jobs <= 0:
        return None
    return run.delta("queue_ns") / jobs / 1e6
