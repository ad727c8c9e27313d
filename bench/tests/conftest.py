"""Fixtures for the benchmark's CPU tests: a copy of the benchmark's tree
with tiny configurations, whose cells run here in seconds.

    python -m pytest bench/tests
"""
from __future__ import annotations

import json
import shutil
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

# Qwen3's equations (RMS-normed queries and keys, untied output
# projection) at a width at which float8 rounding shows against the limit
TINY_LM = {
    "name": "tiny-lm.1slot", "source": "test", "model_type": "qwen3",
    "hidden_size": 256, "intermediate_size": 512, "num_attention_heads": 4,
    "num_key_value_heads": 1, "head_dim": 64, "num_hidden_layers": 4,
    "vocab_size": 1000, "rope_theta": 1000000.0, "rms_norm_eps": 1e-06,
    "hidden_act": "silu", "tie_word_embeddings": False,
    "torch_dtype": "bfloat16", "reduced": [], "chips": 1,
    "fabric": {"shells": [{"name": "s", "grid": [1, 1], "slots": 1,
                           "devices": [0]}]},
    "policy": {"preemptive": True},
    "modules": [{"name": "lm-forward",
                 "entrypoint": "bench.builders:build_lm_forward",
                 "model_from_config": True,
                 "impls": [{"name": "x1", "footprint": 1,
                            "est_chunk_ms": 5.0}],
                 "builder_args": {"batch": 2, "seq": 64}}],
    "checks": {"lm_logit_err": 0.07},
}
TRAFFIC = {
    "lm.tiny": {"tenants": [
        {"name": "batch0", "role": "batch", "module": "lm-forward",
         "loop": "closed", "outstanding": 2},
        {"name": "live", "role": "interactive", "module": "lm-forward",
         "loop": "open", "rate_per_s": 10.0, "priority": 3}],
        "check_sample": {"batch0": 2, "live": 2}},
}


def make_tree(dst: Path) -> Path:
    """A checkout-like tree at `dst`: the benchmark's files, the tiny
    configurations and traffic, and a BENCHMARK.json whose cells run
    them with every metric of the real one."""
    shutil.copytree(ROOT / "bench", dst / "bench",
                    ignore=shutil.ignore_patterns("tests", "out",
                                                  "__pycache__"))
    for cfg in (TINY_LM,):
        (dst / "bench" / "configs" / f"{cfg['name']}.json").write_text(
            json.dumps(cfg))
    for name, t in TRAFFIC.items():
        (dst / "bench" / "traffic" / f"{name}.json").write_text(
            json.dumps(t))
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    spec["configs"] = [
        {"name": c["name"], "source": "test",
         "file": f"bench/configs/{c['name']}.json", "reduced": [],
         "why": "test"} for c in (TINY_LM,)]
    spec["workloads"] = [
        {"name": "tiny.lm", "config": "tiny-lm.1slot", "traffic": "lm.tiny",
         "chips": 1, "why": "test"}]
    for m in spec["end_to_end"] + spec["per_layer"]:
        m.pop("workloads", None)
    (dst / "BENCHMARK.json").write_text(json.dumps(spec))
    return dst


@pytest.fixture
def tiny_root(tmp_path) -> Path:
    return make_tree(tmp_path)
