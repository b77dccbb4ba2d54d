"""``BENCHMARK.json`` keeps to its contract, and every name in it resolves
to the files the harness loads."""
import re

import pytest

from bench import common

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
BENCH = common.load_json(common.ROOT / "BENCHMARK.json")


def test_top_level_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["paths"] == ["bench"]
    assert BENCH["command"] == ["python3", "bench/run.py"]
    assert 1 <= BENCH["run_seconds"] <= 51
    assert isinstance(BENCH["run_seconds"], int)


def test_cells_configs_and_chips():
    cells = BENCH["workloads"]
    names = [c["name"] for c in cells]
    assert len(set(names)) == len(names)
    assert len({(c["config"], c["traffic"]) for c in cells}) == len(cells)
    assert {c["config"] for c in cells} == {c["name"]
                                           for c in BENCH["configs"]}
    assert len({c["file"] for c in BENCH["configs"]}) == len(BENCH["configs"])
    four = sum(c["chips"] == 4 for c in cells)
    assert four <= max(1, len(cells) // 2)
    for m in BENCH["per_layer"]:
        assert set(m.get("workloads", [])) <= set(names)


def test_names_units_and_bounds():
    metrics = BENCH["end_to_end"] + BENCH["per_layer"]
    names = [m["name"] for m in metrics]
    assert len(set(names)) == len(names)
    for m in metrics:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
    for m in BENCH["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    assert any(m["name"] == "setup_s" for m in BENCH["end_to_end"])
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")


@pytest.mark.parametrize("cell", BENCH["workloads"],
                         ids=lambda c: c["name"])
def test_every_cell_resolves(cell):
    assert NAME.match(cell["name"]) and cell["chips"] in (1, 4)
    assert len(cell["why"]) <= 200
    cfg = next(c for c in BENCH["configs"] if c["name"] == cell["config"])
    data = common.load_json(common.ROOT / cfg["file"])
    assert data["chips"] == cell["chips"]
    assert set(cfg["reduced"]) <= set(data) and "source" in data
    assert "assumed" in data
    traffic = common.load_json(common.BENCH / "traffic"
                               / f"{cell['traffic']}.json")
    assert (common.BENCH / "drivers" / f"{traffic['driver']}.py").exists()
    assert (common.BENCH / "limits" / f"{cell['name']}.json").exists()
    listed = [m for m in BENCH["per_layer"]
              if cell["name"] in m.get("workloads", [cell["name"]])]
    assert listed
    for m in listed:
        assert (common.BENCH / "metrics" / f"{m['name']}.py").exists()
