"""``BENCHMARK.json`` and the files it names: everything is found by name,
and the file keeps to the benchmark's contract."""

import json
import os
import re
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)

import spec  # noqa: E402

B = spec.load_benchmark(ROOT)
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
CELLS = [w["name"] for w in B["workloads"]]
METRICS = B["end_to_end"] + B["per_layer"]
WIDTH = re.compile(r"(_dim|_rank|bytes|size|width)$")


def test_top_level_keys():
    assert set(B) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert B["paths"] == ["bench"]
    assert B["command"][1] == "bench/run.py"
    assert 1 <= B["run_seconds"] <= 51
    assert len(json.dumps(B)) < 64 * 1024


def test_names_units_and_entries():
    names = [c["name"] for c in B["configs"]] + CELLS + \
        [m["name"] for m in METRICS]
    for n in names:
        assert NAME.match(n), n
    assert len(set(CELLS)) == len(CELLS)
    assert len({m["name"] for m in METRICS}) == len(METRICS)
    for m in METRICS:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for m in B["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in B["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
    assert "setup_s" in {m["name"] for m in B["end_to_end"]}


def test_every_cell_reports_what_the_contract_asks():
    e2e = {m["name"]: m for m in B["end_to_end"]}
    pairs = set()
    for w in B["workloads"]:
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
        pairs.add((w["config"], w["traffic"]))
        cell = spec.find_cell(w["name"], ROOT)
        got = {m.name for m in cell.end_to_end}
        assert "setup_s" in got and len(got) >= 2
        assert cell.per_layer
        for m in cell.per_layer:
            # a per-layer metric moves an end-to-end metric of its cells
            assert m.moves in got, (w["name"], m.name)
    assert len(pairs) == len(B["workloads"])
    for m in METRICS:
        for c in m.get("workloads", []):
            assert c in CELLS
    for m in B["per_layer"]:
        assert m["moves"] in e2e


def test_configs_are_used_and_only_cut_in_scale():
    used = {w["config"] for w in B["workloads"]}
    files = set()
    for c in B["configs"]:
        assert c["name"] in used
        assert c["file"].startswith("bench/") and c["file"] not in files
        files.add(c["file"])
        with open(os.path.join(ROOT, c["file"])) as f:
            cfg = json.load(f)
        assert cfg["name"] == c["name"]
        for key in c["reduced"]:
            assert not WIDTH.search(key), key
            assert key in cfg["reduced"]
        for key in ("source", "durability", "assumed", "records",
                    "value_size", "key_bytes", "sst_bytes", "l0_trigger"):
            assert key in cfg


@pytest.mark.parametrize("cell", CELLS)
def test_cells_are_found_by_name(cell):
    c = spec.find_cell(cell, ROOT)
    assert c.name == cell and c.config["name"] == c.config_name
    assert c.traffic["mix"]
    assert os.path.isfile(c.config_path)


@pytest.mark.parametrize("metric", [m["name"] for m in B["per_layer"]])
def test_metric_readers_are_found_by_name(metric):
    assert callable(spec.load_reader(metric, BENCH))


def test_unknown_cell_is_an_error():
    with pytest.raises(KeyError):
        spec.find_cell("no-such-cell", ROOT)


def test_command_names_nothing_outside_paths():
    for word in B["command"]:
        assert not word.startswith("/") and ".." not in word
    assert os.path.isfile(os.path.join(ROOT, B["command"][1]))
