"""BENCHMARK.json against the rules it is read by: names and units,
files found by name, and which metrics each cell reports."""
import json
import re
from pathlib import Path

import pytest

from bench import harness

ROOT = Path(__file__).resolve().parents[2]
MAN = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
E2E = {m["name"]: m for m in MAN["end_to_end"]}
CELLS = {w["name"]: w for w in MAN["workloads"]}


def reports(metric: dict, cell: str) -> bool:
    return cell in metric.get("workloads", [cell])


def test_top_level_keys_and_run_budget():
    assert set(MAN) == {"command", "paths", "run_seconds", "configs",
                        "workloads", "end_to_end", "per_layer"}
    rs = MAN["run_seconds"]
    assert isinstance(rs, int) and 1 <= rs <= 51
    # a full check of 24 cells: 2 + 14 * 24 runs, 2 x 90 s of compile
    # per cell, 1200 s spare, inside 43200 s
    assert (2 + 14 * 24) * (rs + 60) + 24 * 2 * 90 + 1200 <= 43200
    for word in MAN["command"]:
        assert not word.startswith("/") and ".." not in word


def test_names_and_units():
    groups = (MAN["configs"], MAN["workloads"], MAN["end_to_end"],
              MAN["per_layer"])
    for group in groups:
        names = [x["name"] for x in group]
        assert len(names) == len(set(names))
        for n in names:
            assert NAME.match(n), n
    for m in MAN["end_to_end"] + MAN["per_layer"]:
        assert UNIT.match(m["unit"]), m["unit"]
        assert m["better"] in ("lower", "higher")
    for m in MAN["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_each_cell_reports_what_it_must(cell):
    e2e = [m["name"] for m in MAN["end_to_end"] if reports(m, cell)]
    assert "setup_s" in e2e and len(e2e) >= 2
    layer = [m for m in MAN["per_layer"] if reports(m, cell)]
    assert layer
    for m in layer:
        assert reports(E2E[m["moves"]], cell), (m["name"], cell)


def test_per_layer_metrics_name_their_layer_and_cells():
    for m in MAN["per_layer"]:
        assert m["moves"] in E2E
        assert m["workloads"] and set(m["workloads"]) <= set(CELLS)
        assert m["layer"] and "\n" not in m["layer"]
        assert callable(harness.load_metric(m["name"]))


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_cell_files_are_found_by_name(cell):
    w = CELLS[cell]
    confs = {c["name"]: c for c in MAN["configs"]}
    conf = confs[w["config"]]
    assert conf["file"].startswith(MAN["paths"][0] + "/")
    data = json.loads((ROOT / conf["file"]).read_text())
    assert data["name"] == conf["name"]
    assert sorted(data["reduced"]) == sorted(conf["reduced"])
    assert harness.load_json("configs", w["config"]) == data
    traffic = harness.load_json("traffic", w["traffic"])
    assert traffic["name"] == w["traffic"]
    assert w["chips"] in (1, 4)
    assert len(w["why"]) <= 200


def test_no_width_is_reduced():
    for c in MAN["configs"]:
        for key in c["reduced"]:
            assert not re.search(r"(_size$|_dim$|_rank$|heads$|"
                                 r"per_tok)", key), key
