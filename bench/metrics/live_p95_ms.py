"""live_p95_ms: 95th percentile of the interactive tenants' job latency,
from when each job was due to when its result was ready, over every job
due in the window; a job that failed or never came counts as infinitely
late.  Like `p50_ms`, a per-layer metric: at four fifths of the knee
both swing too widely from run to run to hold to a bound."""
from bench.harness import percentile


def read(run):
    return percentile([j.latency_ms for j in run.due_in_window("interactive")],
                      95)
