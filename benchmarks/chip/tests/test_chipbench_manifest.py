"""BENCHMARK.json against the rules the harness and the driver read it by."""

import json
import os
import re
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(os.path.dirname(BENCH))
sys.path.insert(0, BENCH)

from chipbench import harness  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


@pytest.fixture(scope="module")
def man():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_names_and_units(man):
    metrics = man["end_to_end"] + man["per_layer"]
    for entry in man["configs"] + man["workloads"] + metrics:
        assert NAME.match(entry["name"]), entry["name"]
    for w in man["workloads"]:
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])
    for c in man["configs"]:
        assert all(NAME.match(k) for k in c["reduced"])
    for m in metrics:
        assert UNIT.match(m["unit"]), m["unit"]
        assert m["better"] in ("lower", "higher")
    names = [e["name"] for e in metrics]
    assert len(names) == len(set(names))
    assert len({w["name"] for w in man["workloads"]}) == len(man["workloads"])


def test_files_found_by_name(man):
    for w in man["workloads"]:
        conf = harness.config_file(man, w["config"])
        assert conf["name"] == w["config"]
        traffic = harness.traffic_file(w["traffic"])
        assert os.path.exists(os.path.join(
            BENCH, "drivers", traffic["driver"] + ".py"))
        assert w["config"] in traffic["limits"]
    for m in man["per_layer"]:
        assert callable(harness.reader(m["name"]).read)


def test_reduced_lists_every_change(man):
    for c in man["configs"]:
        conf = harness.load_json(os.path.join(ROOT, c["file"]))
        assert set(c["reduced"]) == set(conf["reduced"])


CONFIGS = sorted(f[:-5] for f in os.listdir(os.path.join(BENCH, "configs")))
METRICS = sorted(f[:-3] for f in os.listdir(os.path.join(BENCH, "metrics")))


@pytest.mark.parametrize("name", CONFIGS)
def test_config_file_states_its_cuts(name):
    conf = harness.load_json(os.path.join(BENCH, "configs", name + ".json"))
    assert conf["name"] == name
    changed = {k for k, v in conf["published"].items() if conf[k] != v}
    assert changed == set(conf["reduced"])
    harness.arch_config(conf)  # every key the program runs


@pytest.mark.parametrize("name", METRICS)
def test_reader_finds_nothing_in_an_empty_run(name):
    layer = {"trace": None, "commits": 0, "requests": [], "admits": [],
             "conf": {}, "traffic": {}, "peak": {}, "chips": 1}
    assert harness.reader(name).read(layer) is None


def test_moves_is_reported_by_each_cell(man):
    e2e = {m["name"]: m for m in man["end_to_end"]}
    cells = {w["name"] for w in man["workloads"]}
    for m in man["per_layer"]:
        assert m["moves"] in e2e
        for w in m["workloads"]:
            assert w in cells
            moved = e2e[m["moves"]]
            assert w in moved.get("workloads", cells)


def test_every_cell_reports_enough(man):
    for w in man["workloads"]:
        e2e = {m["name"] for m in harness.cell_metrics(
            man, w["name"], "end_to_end")}
        assert "setup_s" in e2e and len(e2e) >= 2
        assert harness.cell_metrics(man, w["name"], "per_layer")


def test_at_most_half_the_cells_take_four_chips(man):
    four = sum(w["chips"] == 4 for w in man["workloads"])
    assert all(w["chips"] in (1, 4) for w in man["workloads"])
    assert four <= len(man["workloads"]) // 2


def test_peaks_keyed_by_device_kind():
    assert harness.peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        harness.peaks("cpu")
