"""BENCHMARK.json keeps to the benchmark's contract, and every name in it
has the file the harness looks for."""
import json
import re

import pytest

import conftest
from bench import harness

ROOT = conftest.ROOT
SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
KEYS = {
    "configs": {"name", "source", "file", "reduced", "why"},
    "workloads": {"name", "config", "traffic", "chips", "why"},
    "end_to_end": {"name", "unit", "better", "bound", "source"},
    "per_layer": {"name", "unit", "better", "source", "layer", "moves"},
}


def _line(s):
    return 1 <= len(s) <= 200 and "\n" not in s and "\t" not in s


def test_top_level():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert SPEC["paths"] == ["bench"]
    assert SPEC["command"] == ["python3", "bench/run.py"]
    assert 1 <= SPEC["run_seconds"] <= 51
    # a full check of 24 cells fits its 43200 s
    runs = 2 + 14 * 24
    assert runs * (SPEC["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200
    assert len(json.dumps(SPEC)) <= 64 * 1024


@pytest.mark.parametrize("section", sorted(KEYS))
def test_entries(section):
    names = set()
    for e in SPEC[section]:
        extra = {"workloads"} if section in ("end_to_end",
                                             "per_layer") else set()
        assert KEYS[section] <= set(e) <= KEYS[section] | extra, e
        assert NAME.match(e["name"]) and e["name"] not in names
        names.add(e["name"])
        for k in ("why", "layer", "source"):
            if k in e and section != "end_to_end":
                assert _line(e[k]), (e["name"], k)
        if "unit" in e:
            assert UNIT.match(e["unit"]) and e["better"] in ("lower",
                                                             "higher")


def test_cells_and_metrics():
    cells = {w["name"]: w for w in SPEC["workloads"]}
    configs = {c["name"] for c in SPEC["configs"]}
    assert configs == {w["config"] for w in cells.values()}
    assert sum(w["chips"] == 4 for w in cells.values()) <= max(
        1, len(cells) // 2)
    e2e = {m["name"]: m for m in SPEC["end_to_end"]}
    assert e2e["setup_s"]["bound"] == 0.25 and "workloads" not in e2e[
        "setup_s"]
    for m in e2e.values():
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    layers = {}
    for m in SPEC["per_layer"]:
        assert m["moves"] in e2e
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        for w in m["workloads"]:
            # the cell reports the metric this one moves
            assert w in e2e[m["moves"]].get("workloads", cells)
    for name in cells:
        cell = harness.find_cell(name, ROOT)
        assert any(m["name"] == "setup_s" for m in cell.end_to_end)
        assert len(cell.end_to_end) >= 2 and cell.per_layer
        assert cell.chips == cell.config["chips"] == w_chips(cells, name)
        for m in cell.end_to_end + cell.per_layer:
            harness.metric_reader(cell, m["name"])
        for mod in cell.config["modules"]:
            mf = harness.module_file(cell, mod["name"])
            assert hasattr(mf, "CONTROL")
        for t in cell.traffic["tenants"]:
            assert t["role"] in ("batch", "interactive")
            assert t["loop"] in ("open", "closed")
        assert set(cell.traffic["check_sample"]) <= {
            t["name"] for t in cell.traffic["tenants"]}


def w_chips(cells, name):
    return cells[name]["chips"]


def test_configs_name_their_reductions():
    for c in SPEC["configs"]:
        cfg = json.loads((ROOT / c["file"]).read_text())
        assert cfg["name"] == c["name"]
        assert cfg["reduced"] == c["reduced"]
        assert len(c["reduced"]) <= 16
        for k in c["reduced"]:
            assert NAME.match(k)
            assert not re.search(r"(_dim|_rank|_size|heads|experts)", k), k
