"""gen_late_ms: 95th percentile of how late the load generator sent the
open-loop jobs of the window (sent - due), host clock."""
from bench.harness import percentile


def read(run):
    late = [(j.sent - j.due) * 1e3 for j in run.jobs
            if run.t0 <= j.due < run.t1 and j.role == "interactive"]
    return percentile(late, 95)
