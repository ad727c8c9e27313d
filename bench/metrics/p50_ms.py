"""p50_ms: median of the interactive tenants' job latency, from when each
job was due to when its result was ready, over every job due in the
window; a job that failed or never came counts as infinitely late."""
from bench.harness import percentile


def read(run):
    return percentile([j.latency_ms for j in run.due_in_window("interactive")],
                      50)
