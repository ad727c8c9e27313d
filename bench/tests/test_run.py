"""Whole runs of tiny cells on the CPU (the harness's look for a chip
skipped): correct outputs read correct, and each fault planted under the
timed path reads not correct."""
import json
import math

import numpy as np
import pytest

from bench import harness

SEED = 2 ** 33 + 5      # more than 32 bits, as the driver's seeds are


@pytest.mark.parametrize("trace", [False, True])
def test_run_is_correct(tiny_root, trace):
    out = harness.run("tiny.lm", SEED, 1.5, trace, root=tiny_root,
                      require_tpu=False,
                      trace_dir=tiny_root / "bench" / "out" / "trace")
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["attempted"] > 0
    assert list(out)[-1] == "checks"
    json.dumps(out)
    metrics = out["metrics"]
    if not trace:
        assert metrics["setup_s"]["value"] > 0
        assert metrics["tokens_per_s"]["value"] > 0
        # one quantity split by cells: `tokens_per_s.shared` has no file
        # of its own and is read by `tokens_per_s`'s reader
        assert metrics["tokens_per_s.shared"] == metrics["tokens_per_s"]
    else:
        # no chip: the trace has no device plane, so no device metric
        assert "device_idle.lm" not in metrics and "lm_mfu" not in metrics
        assert metrics["sched_ms"]["value"] > 0
        assert metrics["p50_ms"]["value"] > 0
        assert metrics["live_p95_ms"]["value"] >= metrics["p50_ms"]["value"]
        assert "breakdown" in out


def _alter(out):
    """An answer altered where it is produced: reversed along its last
    axis."""
    return out[..., ::-1]


def _half(out):
    """Half of the batch (the leading axis, the sequences) left out."""
    n = out.shape[0] // 2
    return out.at[n:].set(0)


@pytest.mark.parametrize("fault", [_alter, _half])
def test_fault_reads_not_correct(tiny_root, monkeypatch, fault):
    import repro.core.daemon as daemon_mod
    real = daemon_mod.run_placement

    def broken(placement, *args):
        return fault(real(placement, *args))
    monkeypatch.setattr(daemon_mod, "run_placement", broken)
    out = harness.run("tiny.lm", SEED, 1.0, False, root=tiny_root,
                      require_tpu=False)
    assert not out["correct"]
    assert any(c["value"] > c["limit"] for c in out["checks"].values())


def test_arrivals_are_the_same_set_for_every_seed():
    a = harness.arrival_offsets(7.0, 20.0, harness.seed_rng(1, 1))
    b = harness.arrival_offsets(7.0, 20.0, harness.seed_rng(2 ** 40, 1))
    assert len(a) == len(b) == 140
    assert not np.allclose(a, b)
    np.testing.assert_allclose(np.sort(np.diff(a, prepend=0.0)),
                               np.sort(np.diff(b, prepend=0.0)))
    assert 0 < a[0] and a[-1] < 20.0


def test_percentile_is_nearest_rank_and_failures_are_late():
    assert harness.percentile(range(1, 101), 95) == 95
    assert harness.percentile([3.0, math.inf], 50) == 3.0
    assert harness.percentile([3.0, math.inf], 95) == math.inf
    assert harness.percentile([], 50) is None


def test_stall_watch_logs_where_the_threads_stand(capsys):
    import threading
    import time

    stop = threading.Event()
    stuck = threading.Thread(target=stop.wait, name="stuck-daemon")
    stuck.start()
    t = time.perf_counter() - 5.0
    with harness.StallWatch(lambda: t, after_s=1.0, poll_s=0.01):
        time.sleep(0.1)
    stop.set()
    stuck.join()
    err = capsys.readouterr().err
    assert err.count("bench: stall: no job completed for") == 1
    assert "thread stuck-daemon" in err
