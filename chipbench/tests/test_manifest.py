"""BENCHMARK.json against the files it names, and the proof that a cell, a
configuration, a per-layer metric and an entry are each added as new files
plus list entries, with no edit to a file that is there."""

import json
import os
import re

import pytest

from chipbench import harness
from chipbench.tests.conftest import HERE, ROOT

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def _read(path):
    with open(path, "rb") as f:
        return f.read()


@pytest.fixture(scope="module")
def manifest():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_keys_and_names(manifest):
    assert set(manifest) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert manifest["paths"] == ["chipbench"]
    assert 1 <= manifest["run_seconds"] <= 51
    for section in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [e["name"] for e in manifest[section]]
        assert len(names) == len(set(names))
        assert all(NAME.match(n) for n in names), names
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for e in manifest["configs"] + manifest["workloads"]:
        assert 1 <= len(e["why"]) <= 200 and "\n" not in e["why"]
    for c in manifest["configs"]:
        assert 1 <= len(c["source"]) <= 200


def test_every_cell_finds_its_files(manifest):
    configs = {c["name"]: c for c in manifest["configs"]}
    used = set()
    for w in manifest["workloads"]:
        assert w["chips"] in (1, 4)
        c = configs[w["config"]]
        used.add(c["name"])
        assert c["file"] == f"chipbench/configs/{c['name']}.json"
        cfg = harness.load_json("configs", c["name"] + ".json")
        assert cfg["reduced"] == c["reduced"]
        mix = harness.load_json("traffic", w["traffic"] + ".json")
        harness.load_json("workloads", w["name"] + ".json")
        for kind, name in (("entries", cfg["entry"]),
                           ("reference", cfg["reference"]),
                           ("counts", cfg["counts"]),
                           ("checks", cfg["check"]),
                           ("traffic", mix["generator"]),
                           ("drivers", mix["driver"])):
            assert os.path.isfile(os.path.join(HERE, kind, name + ".py"))
    assert used == set(configs), "a configuration no cell uses"


def test_metrics_and_their_arrows(manifest):
    cells = [w["name"] for w in manifest["workloads"]]
    e2e = {m["name"]: m for m in manifest["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.1
    for m in manifest["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        how = harness.load_json("metrics", m["name"] + ".json")
        assert os.path.isfile(
            os.path.join(HERE, "readers", how["reader"] + ".py"))
        assert set(m.get("workloads", cells)) <= set(cells)
    for m in manifest["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        moved = e2e[m["moves"]]
        # each of the metric's cells reports the metric it moves
        assert set(m.get("workloads", cells)) <= set(
            moved.get("workloads", cells))
    for cell in cells:
        assert len(harness.cell_metrics(manifest, cell, "end_to_end")) >= 2
        assert harness.cell_metrics(manifest, cell, "per_layer")


def test_additions_are_files_and_list_entries(tiny_tree):
    """A dummy configuration, traffic mix, cell, per-layer metric with its
    reader, and entry: new files and new list entries only."""
    bench = os.path.join(tiny_tree, "chipbench")
    before = {}
    for d, _, files in os.walk(bench):
        for f in files:
            before[os.path.join(d, f)] = _read(os.path.join(d, f))

    def add(rel, text):
        path = os.path.join(bench, rel)
        assert not os.path.exists(path)
        with open(path, "w") as f:
            f.write(text)

    cfg = harness.load_json("configs", "mlp36-frozen.json")
    cfg.update(name="dummy-config", entry="dummy_entry")
    add("configs/dummy-config.json", json.dumps(cfg))
    mix = harness.load_json("traffic", "backlog1024.json")
    mix.update(routers=3, pool=5)
    add("traffic/dummy-mix.json", json.dumps(mix))
    cell = harness.load_json("workloads", "mlp36-frozen.backlog1024.json")
    add("workloads/dummy-config.dummy-mix.json", json.dumps(cell))
    add("entries/dummy_entry.py",
        "from chipbench.entries.inprocess_scorer import *  # noqa\n"
        "EXPECT_SCORE_PATH = {}\n")
    add("metrics/dummy.calls.json", json.dumps({"reader": "dummy_reader"}))
    add("readers/dummy_reader.py",
        "def read(run, how):\n    return float(len(run['window']['calls']))\n")
    with open(os.path.join(tiny_tree, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    manifest["configs"].append(
        {"name": "dummy-config", "source": "test", "reduced": [],
         "file": "chipbench/configs/dummy-config.json", "why": "test"})
    manifest["workloads"].append(
        {"name": "dummy-config.dummy-mix", "config": "dummy-config",
         "traffic": "dummy-mix", "chips": 1, "why": "test"})
    manifest["per_layer"].append(
        {"name": "dummy.calls", "unit": "calls", "better": "higher",
         "source": "program_counter", "layer": "dummy", "moves": "rows_per_s",
         "workloads": ["dummy-config.dummy-mix"]})
    with open(os.path.join(tiny_tree, "BENCHMARK.json"), "w") as f:
        json.dump(manifest, f)

    result = harness.run_cell("dummy-config.dummy-mix", 5, 0.5, False,
                              on_chip=False)
    assert result["correct"], result["compared"]
    assert result["info"]["entry"] == "dummy_entry"
    # 3 routers' clients: the dummy mix really ran
    assert result["info"]["outstanding"] == 6
    assert list(result["info"]["state"]["score_batches"]) == ["1024"]
    assert {m["name"] for m in harness.cell_metrics(
        manifest, "dummy-config.dummy-mix", "per_layer")} >= {"dummy.calls"}
    for p, content in before.items():
        assert _read(p) == content, f"{p} was edited"
