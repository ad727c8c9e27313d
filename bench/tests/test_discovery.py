"""A configuration, a traffic mix and a per-layer metric added as new
files are found by name, with no file of the benchmark edited; a name
with no file is refused."""
import json

import pytest

from bench import harness


def _add(root, cfg_name="tiny-lm.other", traffic="lm.other",
         metric="chunks_seen.lm"):
    cfg = json.loads((root / "bench/configs/tiny-lm.1slot.json")
                     .read_text())
    cfg["name"] = cfg_name
    cfg["policy"] = {"preemptive": False}
    (root / f"bench/configs/{cfg_name}.json").write_text(json.dumps(cfg))
    (root / f"bench/traffic/{traffic}.json").write_text(json.dumps({
        "tenants": [{"name": "solo", "role": "batch",
                     "module": "lm-forward", "loop": "closed",
                     "outstanding": 1}],
        "check_sample": {"solo": 1}}))
    (root / f"bench/metrics/{metric}.py").write_text(
        "def read(run):\n"
        "    return run.delta('chunks') / run.window_s\n")
    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": cfg_name, "source": "test",
                            "file": f"bench/configs/{cfg_name}.json",
                            "reduced": [], "why": "test"})
    spec["workloads"].append({"name": "tiny.other", "config": cfg_name,
                              "traffic": traffic, "chips": 1, "why": "t"})
    spec["per_layer"].append({"name": metric, "unit": "1/s",
                              "better": "higher",
                              "source": "program_counter", "layer": "t",
                              "moves": "tokens_per_s",
                              "workloads": ["tiny.other"]})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))


def test_new_files_are_found_by_name(tiny_root):
    before = {p: p.read_bytes() for p in (tiny_root / "bench").rglob("*")
              if p.is_file()}
    _add(tiny_root)
    after = {p: p.read_bytes() for p in (tiny_root / "bench").rglob("*")
             if p.is_file()}
    assert all(after[p] == b for p, b in before.items())   # none edited
    out = harness.run("tiny.other", 9, 1.0, True, root=tiny_root,
                      require_tpu=False,
                      trace_dir=tiny_root / "bench" / "out" / "trace")
    assert out["correct"]
    assert out["metrics"]["chunks_seen.lm"]["value"] > 0
    # the other cells do not report the new metric
    cell = harness.find_cell("tiny.lm", tiny_root)
    assert "chunks_seen.lm" not in {m["name"] for m in cell.per_layer}


@pytest.mark.parametrize("what", ["workload", "config", "traffic",
                                  "metric", "metric.variant", "module"])
def test_unknown_names_are_refused(tiny_root, what):
    spec = json.loads((tiny_root / "BENCHMARK.json").read_text())
    name = "tiny.lm"
    if what == "workload":
        name = "no.such.cell"
    elif what == "config":
        spec["workloads"][0]["config"] = "no-such-config"
    elif what == "traffic":
        spec["workloads"][0]["traffic"] = "no.such.traffic"
    elif what.startswith("metric"):
        # a `<base>.<variant>` name falls back to `<base>`'s reader only
        # where that exists
        spec["end_to_end"].append({"name": {"metric": "no_such_metric",
                                            "metric.variant": "no_such.lm"}[
                                                what], "unit": "s",
                                   "better": "lower", "bound": 0.1,
                                   "source": "host_clock"})
    elif what == "module":
        cfg = json.loads((tiny_root / "bench/configs/tiny-lm.1slot.json")
                         .read_text())
        cfg["modules"][0]["name"] = "no-such-module"
        (tiny_root / "bench/configs/tiny-lm.1slot.json").write_text(
            json.dumps(cfg))
    (tiny_root / "BENCHMARK.json").write_text(json.dumps(spec))
    with pytest.raises(harness.SpecError):
        harness.run(name, 1, 0.5, False, root=tiny_root, require_tpu=False)
