"""``BENCHMARK.json`` against the benchmark's contract: every cell's files
exist, every metric has its reader, each per-layer metric's cells report
the end-to-end metric it moves, names and units use the allowed letters,
and at most half of the cells take four chips."""
import os
import re

import pytest

from bench import run
from benchtools import BENCH, ROOT, load

B = load(os.path.join(ROOT, "BENCHMARK.json"))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
METRICS = B["end_to_end"] + B["per_layer"]


def test_top_level_keys():
    assert set(B) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert 1 <= B["run_seconds"] <= 51
    for p in B["paths"]:
        assert os.path.isdir(os.path.join(ROOT, p)), p
    assert not any(w.startswith("/") or ".." in w for w in B["command"])


@pytest.mark.parametrize("cell", B["workloads"], ids=lambda w: w["name"])
def test_cell_files_exist(cell):
    cfg = {c["name"]: c for c in B["configs"]}[cell["config"]]
    assert os.path.isfile(os.path.join(ROOT, cfg["file"]))
    for d in ("traffic",):
        assert os.path.isfile(os.path.join(BENCH, d, cell["traffic"] + ".json"))
    assert os.path.isfile(os.path.join(BENCH, "limits", cell["name"] + ".json"))
    assert cell["chips"] in (1, 4)
    assert 1 <= len(cell["why"]) <= 200


@pytest.mark.parametrize("metric", METRICS, ids=lambda m: m["name"])
def test_metric_reader_and_names(metric):
    assert NAME.match(metric["name"]) and UNIT.match(metric["unit"])
    assert metric["better"] in ("lower", "higher")
    assert os.path.isfile(run.reader_path(metric["name"], BENCH))
    if metric in B["end_to_end"]:
        assert metric["source"] in ("host_clock", "device_trace")
        assert 0.01 <= metric["bound"] <= 0.25
    cells = {w["name"] for w in B["workloads"]}
    assert set(metric.get("workloads", [])) <= cells


@pytest.mark.parametrize("metric", B["per_layer"], ids=lambda m: m["name"])
def test_per_layer_cells_report_what_it_moves(metric):
    moved = {m["name"]: m for m in B["end_to_end"]}[metric["moves"]]
    for cell in metric["workloads"]:
        assert cell in moved.get("workloads", [cell])
    assert "\n" not in metric["layer"] and len(metric["layer"]) <= 200


def test_every_cell_reports_setup_and_more():
    e2e = B["end_to_end"]
    assert any(m["name"] == "setup_s" and "workloads" not in m for m in e2e)
    for cell in B["workloads"]:
        n = cell["name"]
        assert sum(n in m.get("workloads", [n]) for m in e2e) >= 2
        assert any(n in m.get("workloads", [n]) for m in B["per_layer"])


def test_at_most_half_the_cells_take_four_chips():
    four = sum(w["chips"] == 4 for w in B["workloads"])
    assert four <= max(1, len(B["workloads"]) // 2)


def test_names_are_unique():
    for group in (B["configs"], B["workloads"], METRICS):
        names = [g["name"] for g in group]
        assert len(names) == len(set(names))
    pairs = [(w["config"], w["traffic"]) for w in B["workloads"]]
    assert len(pairs) == len(set(pairs))
