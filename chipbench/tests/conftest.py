"""Copies of the benchmark's own directory for the CPU tests."""

import json
import os
import shutil

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import pytest

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(HERE)


@pytest.fixture
def tiny_tree(tmp_path, monkeypatch):
    """A copy of BENCHMARK.json and chipbench/, with the harness pointed at
    it. The benchmark's cell hands over 2 M rows a call, more than a test
    run can hold, so the copy's manifest gets two cells of a test's size
    beside it: the same entry, driver, check, reference, control and
    faults on the telemeter's own micro-batch (``traffic/backlog1024.json``:
    maxBatch 1,024 rows a call), with and without fits, whose limits were
    read on the chip (``workloads/*.backlog1024.json``). Only the score
    path differs on the CPU (plain XLA, not the fused kernel)."""
    from chipbench import harness
    tree = str(tmp_path / "tree")
    shutil.copytree(HERE, os.path.join(tree, "chipbench"),
                    ignore=shutil.ignore_patterns(
                        "tests", "__pycache__", ".jax_cache"))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    online = manifest["configs"][0]
    assert online["name"] == "mlp36-online"
    manifest["configs"].append(dict(
        online, name="mlp36-frozen",
        file="chipbench/configs/mlp36-frozen.json"))
    tiny = [f"{c}.backlog1024" for c in ("mlp36-online", "mlp36-frozen")]
    for name in tiny:
        manifest["workloads"].append(
            {"name": name, "config": name.split(".")[0],
             "traffic": "backlog1024", "chips": 1, "why": "a test's size"})
    for m in manifest["per_layer"]:
        m["workloads"] += [t for t in tiny
                           if "online" in t or not m["name"].startswith(
                               ("train_step", "fit."))]
    with open(os.path.join(tree, "BENCHMARK.json"), "w") as f:
        json.dump(manifest, f)
    # a window of a second or two makes a few dozen fits: anchor more of them
    wdir = os.path.join(tree, "chipbench", "workloads")
    for name in os.listdir(wdir):
        with open(os.path.join(wdir, name)) as f:
            cell = json.load(f)
        if cell["check"]["anchor_every"]:
            cell["check"]["anchor_every"] = 6
        with open(os.path.join(wdir, name), "w") as f:
            json.dump(cell, f)
    monkeypatch.setattr(harness, "HERE", os.path.join(tree, "chipbench"))
    monkeypatch.setattr(harness, "ROOT", tree)
    return tree
