"""slot_wait_ms: mean milliseconds from the scheduling pass that issued a
chunk to its worker holding the slot's locks, over the chunks run on a
slot in the window (`Daemon.stats` slot_wait_ns over runs): the hand-off
to the worker pool, and a preemptor's wait for its victim to end.  None
from a daemon without these counters."""


def read(run):
    if "slot_wait_ns" not in run.stats1:
        return None
    runs = run.delta("runs")
    if runs <= 0:
        return None
    return run.delta("slot_wait_ns") / runs / 1e6
