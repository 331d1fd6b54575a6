"""`BENCHMARK.json` against the limits of its contract that can be
checked without a chip, and against the files it names."""

import json
import os
import re

from perfbench import run
from perfbench.tests.test_harness import REPO, ROOT

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
MANIFEST = run.read_json(os.path.join(REPO, "BENCHMARK.json"))


def one_line(text):
    return 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


def cells_of(metric):
    return set(metric.get("workloads",
                          [w["name"] for w in MANIFEST["workloads"]]))


def test_keys_names_and_lengths():
    assert set(MANIFEST) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert MANIFEST["paths"] == ["perfbench"]
    assert 1 <= MANIFEST["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(REPO, "BENCHMARK.json")) < 65536
    assert all(one_line(word) for word in MANIFEST["command"])
    for c in MANIFEST["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and one_line(c["source"])
        assert one_line(c["why"]) and len(c["reduced"]) <= 16
        assert all(NAME.match(k) for k in c["reduced"])
        assert c["file"].startswith("perfbench/")
    for w in MANIFEST["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and one_line(w["why"])
    for m in MANIFEST["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better",
                                          "bound", "source"}
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
    for m in MANIFEST["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better",
                                          "source", "layer", "moves"}
        assert one_line(m["layer"]) and m["source"] in SOURCES
    metrics = MANIFEST["end_to_end"] + MANIFEST["per_layer"]
    for m in metrics:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
    for group in (metrics, MANIFEST["workloads"], MANIFEST["configs"]):
        names = [x["name"] for x in group]
        assert len(names) == len(set(names))


def test_cells_configs_and_metrics_fit_together():
    cells = MANIFEST["workloads"]
    configs = {c["name"]: c for c in MANIFEST["configs"]}
    assert {w["config"] for w in cells} == set(configs)
    pairs = [(w["config"], w["traffic"]) for w in cells]
    assert len(pairs) == len(set(pairs))
    four = sum(w["chips"] == 4 for w in cells)
    assert four <= max(1, len(cells) // 4)
    end = {m["name"]: m for m in MANIFEST["end_to_end"]}
    assert cells_of(end["setup_s"]) == {w["name"] for w in cells}
    for w in cells:
        mine = [m for m in end.values() if w["name"] in cells_of(m)]
        assert len(mine) >= 2
        assert any(w["name"] in cells_of(m) for m in MANIFEST["per_layer"])
    for m in MANIFEST["per_layer"]:
        assert cells_of(m) <= cells_of(end[m["moves"]]), m["name"]


def test_every_named_file_is_there_and_says_the_same():
    for c in MANIFEST["configs"]:
        config = run.read_json(os.path.join(REPO, c["file"]))
        assert config["source"] == c["source"]
        assert sorted(config["reduced"]) == sorted(c["reduced"])
    for w in MANIFEST["workloads"]:
        cell = run.Cell(w["name"], ROOT, MANIFEST)
        assert cell.spec["chips"] == w["chips"]
        assert cell.spec["config"] == w["config"]
        assert cell.spec["rate_metric"] in cell.end_to_end
        for kind, key in (("drivers", "driver"), ("models", "model"),
                          ("reference", "model")):
            run.load_module(ROOT, kind, cell.spec[key])
    for m in MANIFEST["per_layer"]:
        reader = run.load_module(ROOT, "layer_metrics", m["name"])
        assert (reader.NAME, reader.UNIT, reader.LAYER, reader.MOVES) == (
            m["name"], m["unit"], m["layer"], m["moves"])
    readers = {f[:-3] for f in os.listdir(
        os.path.join(ROOT, "layer_metrics")) if f.endswith(".py")}
    assert readers == {m["name"] for m in MANIFEST["per_layer"]}


def test_the_harness_knows_no_cell_and_not_the_old_benchmarks():
    with open(os.path.join(ROOT, "run.py")) as f:
        source = f.read()
    for entry in MANIFEST["workloads"] + MANIFEST["configs"]:
        assert entry["name"] not in source
    for family in ("transformer", "resnet", "mistral"):
        assert family not in source.lower()
    for folder, _, files in os.walk(ROOT):
        for name in files:
            if name.endswith(".py") and "tests" not in folder:
                with open(os.path.join(folder, name)) as f:
                    text = f.read()
                assert not re.search(
                    r"^\s*(import|from)\s+(bench|chip_smoke)\b", text,
                    re.M), name
