"""BENCHMARK.json names only what the harness can find by name, and meets
the shape the benchmark's contract gives it."""
import json
import re
from pathlib import Path

import pytest

from perfbench import registry

ROOT = Path(__file__).resolve().parents[2]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
CELLS = [w["name"] for w in BENCH["workloads"]]


def test_top_level_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= BENCH["run_seconds"] <= 51
    for p in BENCH["paths"]:
        assert (ROOT / p).is_dir() and not p.startswith("/") and ".." not in p
    assert (ROOT / BENCH["command"][1]).is_file()


@pytest.mark.parametrize("cell", CELLS)
def test_cell_files_found_by_name(cell):
    wl = registry.workload(cell)
    entry = next(w for w in BENCH["workloads"] if w["name"] == cell)
    assert (wl["config"], wl["traffic"], wl["why"]) == (
        entry["config"], entry["traffic"], entry["why"])
    cfg = registry.config(wl["config"])
    tr = registry.traffic(wl["traffic"])
    assert tr["seq_len"] % tr["per_worker_batch"] == 0
    fam = registry.reference_family(cfg["reference"])
    assert callable(fam.loss) and callable(fam.flops_per_token)
    assert set(wl["limits"]) <= {"loss_gap", "moment_gap", "update_gap", "moment_diff",
                                 "filter_gap"}
    assert wl["limits"]["filter_gap"] == 0
    assert entry["chips"] == 1 and len(entry["why"]) <= 200


def test_configs_point_at_their_files():
    for c in BENCH["configs"]:
        cfg = json.loads((ROOT / c["file"]).read_text())
        assert cfg["name"] == c["name"] == cfg["model"]["name"]
        assert cfg["source"] == c["source"]
        assert sorted(cfg["reduced"]) == sorted(c["reduced"])
        assert any(w["config"] == c["name"] for w in BENCH["workloads"])


@pytest.mark.parametrize("entry", BENCH["end_to_end"] + BENCH["per_layer"],
                         ids=lambda e: e["name"])
def test_metric_names_units_and_readers(entry):
    assert NAME.match(entry["name"]) and UNIT.match(entry["unit"])
    assert entry["better"] in ("lower", "higher")
    if "layer" in entry:
        assert entry["moves"] in {e["name"] for e in BENCH["end_to_end"]}
        assert callable(registry.metric(entry["name"]).read)
    else:
        assert 0.01 <= entry["bound"] <= 0.25
    assert set(entry.get("workloads", CELLS)) <= set(CELLS)


def test_unknown_names_raise():
    with pytest.raises(KeyError):
        registry.workload("no-such-cell")
    with pytest.raises(KeyError):
        registry.metric("no_such_metric")
    with pytest.raises(KeyError):
        registry.reference_family("no_such_family")


def test_metrics_of_respects_workloads_key():
    bench = {"per_layer": [{"name": "a"}, {"name": "b", "workloads": ["x"]}]}
    assert [e["name"] for e in registry.metrics_of(bench, "x", "per_layer")] == ["a", "b"]
    assert [e["name"] for e in registry.metrics_of(bench, "y", "per_layer")] == ["a"]
