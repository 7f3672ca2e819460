"""The flow cell on the CPU at a tiny preset: its files are found, the
generator is a function of the seed, a sound run comes out correct, and the
control and each planted fault come out not correct."""

import json
import os
import shutil

import numpy as np
import pytest

from chipbench import harness
from chipbench.tests.conftest import HERE

CELL = "kimi-k2-6-ep32.flows64x64"
FAULTS = ("expert_left_out", "shared_twice", "bias_left_out",
          "positions_off_by_one", "restart_keeps_cache")

TINY_MODEL = {
    "hidden_size": 64, "num_attention_heads": 4, "q_lora_rank": 32,
    "kv_lora_rank": 16, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
    "v_head_dim": 16, "intermediate_size": 128, "moe_intermediate_size": 32,
    "num_experts_per_tok": 2, "num_hidden_layers": 3, "vocab_size": 128,
    "n_routed_experts": 4}
TINY_GROUP = {"router_experts": 16, "experts_held": [4, 8], "layer_share": 4,
              "slots": 16, "positions": 128, "expert_tile": 8,
              "counted_context": 40}
TINY_MIX = {"rows_per_call": 32, "setup_fit_rows_per_call": 32, "flows": 16,
            "flows_per_call": 4, "chunk": 8, "visits": 8,
            "lifetime_median_events": 24, "lifetime_cap_events": 96,
            "ids_per_flow": 64, "vocab": 128}


def _edit(path, change):
    with open(path) as f:
        d = json.load(f)
    change(d)
    with open(path, "w") as f:
        json.dump(d, f)


@pytest.fixture
def tiny_flow_tree(tiny_tree):
    """The copy's flow configuration, mix and cell cut to a test's size:
    hidden 64, 4 heads, 16 experts top 2 of which 4 are held, a vocabulary
    of 128; 16 flows of 4 x 8 events a call."""
    bench = os.path.join(tiny_tree, "chipbench")

    def config(c):
        c.update(TINY_MODEL)
        c["model"].update(TINY_GROUP)

    _edit(os.path.join(bench, "configs", "kimi-k2-6-ep32.json"), config)
    _edit(os.path.join(bench, "traffic", "flows64x64.json"),
          lambda m: m.update(TINY_MIX))

    def cell(c):
        c["check"].update(calls_compared=6, flows_compared=2)
        # a test's size, read on the CPU over 3 seeds (the program's
        # largest / the control's and faults' smallest): rms ratio 1.09 /
        # 1.5 (a fault's; the control 42.6), median gap 0.00014 / 0.00017,
        # p90 0.00039 / 0.00078, p99 0.0026 / 0.0066, cache_rel_rms 0.0027
        # / 0.0167 (every fault over it), cache_off_share 0 / 0.006
        c["limits"].update(score_rms_ratio=4.0, score_median_gap=0.0004,
                           score_p90_gap=0.001, score_p99_gap=0.008,
                           cache_rel_rms=0.007, cache_off_share=0.003,
                           near_tie_share=0.5)

    _edit(os.path.join(bench, "workloads", CELL + ".json"), cell)
    return tiny_tree


def test_the_cell_finds_its_files():
    manifest = harness.load_manifest()
    spec = {w["name"]: w for w in manifest["workloads"]}[CELL]
    assert spec["chips"] == 1
    cfg = harness.load_json("configs", spec["config"] + ".json")
    mix = harness.load_json("traffic", spec["traffic"] + ".json")
    cell = harness.load_json("workloads", CELL + ".json")
    assert cfg["model"]["in_dim"] == 3
    assert cfg["telemeter"]["trainEveryBatches"] == 0
    assert mix["vocab"] == cfg["vocab_size"]
    assert mix["rows_per_call"] == mix["flows_per_call"] * mix["chunk"]
    assert mix["flows"] == cfg["model"]["slots"]
    assert set(cell["limits"]) >= {"evictions", "wraps", "failed_calls",
                                   "window_compiles", "unexpected_shapes"}
    for kind, name in (("entries", cfg["entry"]), ("entries",
                                                   "flow_control_fp8"),
                       ("reference", cfg["reference"]),
                       ("counts", cfg["counts"]), ("checks", cfg["check"]),
                       ("traffic", mix["generator"])):
        harness.load_code(kind, name)
    for fault in FAULTS:
        assert os.path.isfile(os.path.join(HERE, "tests", "faults",
                                           f"fault_flow_{fault}.py"))


def test_the_generator_is_a_function_of_the_seed():
    mix = harness.load_json("traffic", "flows64x64.json")
    gen = harness.load_code("traffic", mix["generator"])
    a = gen.generate(mix, 4096, 3, 2147483699)["pool"]
    b = gen.generate(mix, 4096, 3, 2147483699)["pool"]
    c = gen.generate(mix, 4096, 3, 2147483700)["pool"]
    assert len(a) == mix["visits"] * mix["flows"] // mix["flows_per_call"]
    assert all(np.array_equal(x[0], y[0]) for x, y in zip(a, b))
    assert not all(np.array_equal(x[0], y[0]) for x, y in zip(a, c))
    rows = np.stack([x[0] for x in a])
    assert rows.dtype == np.int32 and rows.shape[1:] == (4096, 3)
    assert rows[..., 0].min() > 0 and rows[..., 0].max() < 2 ** 24
    assert rows[..., 2].min() >= 1 and rows[..., 2].max() < mix["vocab"]
    # 8 consecutive calls touch every flow once; a call's flows are distinct
    keys = [set(r[:, 0].tolist()) for r in rows[:8]]
    assert all(len(k) == mix["flows_per_call"] for k in keys)
    assert len(set().union(*keys)) == mix["flows"]
    # no flow outlives the cap, the period cycled: the longest run of
    # visits without a restart, around the circle
    flags = rows[:, :mix["flows_per_call"], 1].reshape(
        mix["visits"], -1, mix["flows_per_call"])
    assert flags.any(0).all(), "a key that never restarts"
    twice = np.concatenate([flags, flags])
    longest = max(
        np.diff(np.flatnonzero(twice[:, g, f])).max()
        for g in range(twice.shape[1]) for f in range(twice.shape[2]))
    assert longest * mix["chunk"] <= mix["lifetime_cap_events"]


def test_sound_run_is_correct(tiny_flow_tree):
    r = harness.run_cell(CELL, 2147483699, 1.5, False, on_chip=False)
    assert r["correct"], r["compared"]
    assert r["failed"] == 0 and r["attempted"] > 8
    assert set(r["metrics"]) == {"rows_per_s", "score_p95_ms", "setup_s"}
    state = r["info"]["state"]
    assert list(state["score_batches"]) == ["32"]
    assert list(state["flow"]["layouts"]) == ["4x8"]
    assert state["flow"]["evictions"] == state["flow"]["wraps"] == 0
    assert state["flow"]["resident"] == 16
    assert r["info"]["flows_compared"] == 12
    json.dumps(r)


def test_control_in_float8_is_not_correct(tiny_flow_tree):
    r = harness.run_cell(CELL, 2147483701, 0.5, False, on_chip=False,
                         entry_name="flow_control_fp8")
    assert not r["correct"]
    bad = {k for k, c in r["compared"].items() if c["value"] > c["limit"]}
    assert "score_rms_ratio" in bad, r["compared"]


@pytest.mark.parametrize("fault", FAULTS)
def test_fault_under_the_timed_path_is_not_correct(tiny_flow_tree, fault):
    shutil.copy(os.path.join(HERE, "tests", "faults",
                             f"fault_flow_{fault}.py"),
                os.path.join(tiny_flow_tree, "chipbench", "entries"))
    r = harness.run_cell(CELL, 2147483703, 1.5, False, on_chip=False,
                         entry_name=f"fault_flow_{fault}")
    assert not r["correct"], r["compared"]


def test_counts_at_the_published_widths():
    cfg = harness.load_json("configs", "kimi-k2-6-ep32.json")
    counts = harness.load_code("counts", cfg["counts"])
    model = cfg["model"]
    assert counts.attention_weights(cfg) == 101_122_048
    assert counts.expert_weights(cfg) == 44_040_192
    # 5 x attention, the dense FFN, 4 x (shared + router + 12 experts),
    # embedding and head over the slice: 6.99 GB in bfloat16
    assert counts.weights_held(model) == 3_496_673_280
    assert counts.weight_bytes_per_step(model) == 2 * (
        3_496_673_280 - 7168 * 20480)
    per_event = counts.score_flops_per_row(model)
    assert per_event == pytest.approx(2.62e9, rel=0.01)
    # a call of the cell: 10.7 TFLOP, 54 ms at the bf16 peak
    assert per_event * 4096 / 197e12 == pytest.approx(0.0545, rel=0.02)


def test_the_counted_context_is_the_traffics_mean():
    """``model.counted_context``: the positions an event attends over, on
    average, once the schedule has cycled once: the flow's cached length
    before the call, half the chunk, the event itself."""
    cfg = harness.load_json("configs", "kimi-k2-6-ep32.json")
    mix = harness.load_json("traffic", "flows64x64.json")
    gen = harness.load_code("traffic", mix["generator"])
    s = gen.schedule(mix, 2147483699)
    length = np.zeros(mix["flows"], np.int64)
    seen = []
    for period in range(2):
        for v in range(mix["visits"]):
            length = np.where(s["restart"][v] | (length == 0), 1, length)
            if period:
                seen.append(length + (mix["chunk"] + 1) / 2)
            length = length + mix["chunk"]
            assert length.max() < cfg["model"]["positions"] - mix["chunk"]
    assert np.mean(seen) == pytest.approx(cfg["model"]["counted_context"],
                                          rel=0.05)
