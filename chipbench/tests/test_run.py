"""A whole run of a second or two on the CPU: the look for a chip refuses
what is not a TPU; past it, a sound run comes out correct, the control
(the reference in float8 in the program's place) and each fault planted
under the timed path (``tests/faults/``, put in the entry's place) come
out not correct."""

import json
import os
import shutil
import subprocess

import pytest

from chipbench import harness
from chipbench.tests.conftest import HERE, ROOT

FROZEN, ONLINE = "mlp36-frozen.backlog1024", "mlp36-online.backlog1024"


def test_refuses_without_a_tpu():
    """The command itself, in a process of its own, where JAX has only
    the CPU: another exit code than 0 and no result."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    command, cell = manifest["command"], manifest["workloads"][0]["name"]
    p = subprocess.run(
        command + ["--workload", cell, "--seed", "1", "--seconds", "1",
                   "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no accelerator" in p.stderr


@pytest.mark.parametrize("cell", [FROZEN, ONLINE])
def test_sound_run_is_correct(tiny_tree, cell):
    r = harness.run_cell(cell, 2147483699, 1.5, False, on_chip=False)
    assert r["correct"], r["compared"]
    assert r["failed"] == 0 and r["attempted"] > 8
    assert set(r["metrics"]) == {"rows_per_s", "score_p95_ms", "setup_s"}
    assert list(r)[-1] == "compared"
    assert r["info"]["calls_compared"] > 8
    if cell == ONLINE:
        assert r["info"]["fits_followed_from_seed"] == 3
        assert r["info"]["fits_anchored"] >= 1
        assert "late_change_gap" in r["compared"]
        assert r["info"]["fits"] == (
            sum(r["info"]["state"]["fit_batches"].values()) - 1)


@pytest.mark.parametrize("cell", [FROZEN, ONLINE])
def test_control_in_float8_is_not_correct(tiny_tree, cell):
    r = harness.run_cell(cell, 2147483701, 1.0, False, on_chip=False,
                         entry_name="control_fp8")
    assert not r["correct"]
    bad = {k for k, c in r["compared"].items() if c["value"] > c["limit"]}
    assert "score_rms_ratio" in bad, r["compared"]


@pytest.mark.parametrize("cell,fault", [
    (FROZEN, "answer_altered"), (ONLINE, "answer_altered"),
    (ONLINE, "state_unchanged"), (ONLINE, "half_batch")])
def test_fault_under_the_timed_path_is_not_correct(tiny_tree, cell, fault):
    shutil.copy(os.path.join(HERE, "tests", "faults", f"fault_{fault}.py"),
                os.path.join(tiny_tree, "chipbench", "entries"))
    r = harness.run_cell(cell, 2147483703, 1.0, False, on_chip=False,
                         entry_name=f"fault_{fault}")
    assert not r["correct"], r["compared"]


DRAIN = "mlp36-online.drain32"


@pytest.fixture
def small_drain(tiny_tree):
    """The benchmark's own cell with its call cut from 2 M rows to 4,096
    (and its set-up fit from 65,536 to 512): every step of its run, the
    warm fit on the pool's first batch with it, at a size a test can hold.
    Its limits were read at the cell's own size, where a fit averages the
    compute type's noise over 2 M rows: here they are the test cells'."""
    path = os.path.join(tiny_tree, "chipbench", "traffic", "drain32.json")
    with open(path) as f:
        mix = json.load(f)
    mix.update(rows_per_call=4096, rows_per_block=256, setup_fit_rows=512,
               setup_fit_rows_per_call=512)
    with open(path, "w") as f:
        json.dump(mix, f)
    wdir = os.path.join(tiny_tree, "chipbench", "workloads")
    with open(os.path.join(wdir, ONLINE + ".json")) as f:
        limits = json.load(f)["limits"]
    with open(os.path.join(wdir, DRAIN + ".json")) as f:
        cell = json.load(f)
    # (the widest single gap, compared at 2 M rows a call, is not at 1,024)
    assert set(cell["limits"]) - set(limits) == {"score_gap"}
    cell["limits"] = limits
    with open(os.path.join(wdir, DRAIN + ".json"), "w") as f:
        json.dump(cell, f)
    return tiny_tree


def test_the_cell_itself_at_a_small_size(small_drain):
    r = harness.run_cell(DRAIN, 2147483711, 2.0, False, on_chip=False)
    assert r["correct"], r["compared"]
    state = r["info"]["state"]
    assert list(state["score_batches"]) == ["4096"]
    # the set-up fit, then the warm fit and the window's fits at the call's rows
    assert state["fit_batches"]["512"] == 1
    assert state["fit_batches"]["4096"] == 1 + r["info"]["fits"]
    assert r["info"]["outstanding"] == 2
    assert r["info"]["fits_followed_from_seed"] == 3
    assert r["info"]["fits_anchored"] >= 1
    assert r["metrics"]["rows_per_s"]["value"] > 0


def test_the_cell_itself_under_the_control(small_drain):
    r = harness.run_cell(DRAIN, 2147483713, 1.0, False, on_chip=False,
                         entry_name="control_fp8")
    assert not r["correct"]
