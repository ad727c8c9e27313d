"""wait_idle: share of the traced window in which the chips sat idle while
the daemon waited for a chunk it had dispatched (under its `fos.wait`
span, `run_placement`'s `block_until_ready`), the mean over the cell's
chips, from the profiler trace with each chip's events moved onto the
host's clock (`bench/program_spans.py`).  Logs how all of the idle time
splits by the daemon's spans.  None without a chip or without the
daemon's spans."""
import json

from bench.harness import log
from bench.program_spans import for_run


def read(run):
    spans = for_run(run)
    if spans is None:
        return None
    log(f"idle by program span {json.dumps(spans.breakdown())}")
    return 100.0 * spans.idle_under.get("fos.wait", 0.0) / spans.window_s
