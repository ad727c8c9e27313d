"""discarded_share: share of the slots' time in the window spent running
chunks whose results the daemon threw away because they were preempted
mid-run (`Daemon.stats` discarded_ns, host clock of `run_placement`), over
the window's slot-seconds (window x slots).  None from a daemon without
this counter."""


def read(run):
    if "discarded_ns" not in run.stats1:
        return None
    return 100.0 * run.delta("discarded_ns") * 1e-9 / (run.window_s
                                                       * run.n_slots)
