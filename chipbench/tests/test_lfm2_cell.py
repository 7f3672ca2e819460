"""The cell of ``lfm2-24b-a2b`` on the CPU at a tiny preset: its files are
found, a sound run comes out correct, and the control and each planted
fault come out not correct; its counts at the published widths; its
traffic's mean context."""

import json
import os
import shutil

import numpy as np
import pytest

from chipbench import harness
from chipbench.tests.conftest import HERE
from chipbench.tests.test_flow_cell import TINY_MIX, _edit

CELL = "lfm2-24b-a2b.flows64x64-fullvocab"
FAULTS = ("tail_not_carried", "restart_keeps_tail", "wrong_kv_head",
          "bias_in_weights", "weights_unnormalised", "expert_left_out")

TINY_MODEL = {
    "hidden_size": 64, "intermediate_size": 128, "moe_intermediate_size": 32,
    "num_attention_heads": 4, "num_key_value_heads": 2, "num_experts": 16,
    "num_hidden_layers": 5, "vocab_size": 128,
    "layer_types": ["conv", "full_attention", "conv", "conv", "conv"]}
TINY_GROUP = {"experts_held": [0, 16], "slots": 16, "positions": 128,
              "expert_tile": 8, "router_bias_std": 0.3, "counted_context": 40,
              "counted_attended_positions": 128, "counted_chunk": 8}


@pytest.fixture
def tiny_lfm2_tree(tiny_tree):
    """The copy's configuration, mix and cell cut to a test's size: hidden
    64, 4 heads over 2 key/value heads, 16 experts top 4, 1 dense + 1
    period, a vocabulary of 128; 16 flows of 4 x 8 events a call."""
    bench = os.path.join(tiny_tree, "chipbench")

    def config(c):
        c.update(TINY_MODEL)
        c["model"].update(TINY_GROUP)

    _edit(os.path.join(bench, "configs", "lfm2-24b-a2b.json"), config)
    _edit(os.path.join(bench, "traffic", "flows64x64-fullvocab.json"),
          lambda m: m.update(TINY_MIX))

    def cell(c):
        c["check"].update(calls_compared=6, flows_compared=2)
        c["limits"].update(TINY_LIMITS)

    _edit(os.path.join(bench, "workloads", CELL + ".json"), cell)
    return tiny_tree


# a test's size, read on the CPU over 3 seeds and several windows (the
# program's largest / the control's and the faults' smallest): rms ratio
# 3.1 / 5.1 (bias_in_weights; the control 21.2), median gap 0.00067 /
# 0.0037 (expert_left_out: every fault is over it), p90 0.0057 / 0.021,
# p99 0.028 / 0.037. 64 wide, a token routed otherwise in one layer moves
# every later layer's ``u`` of it: the kept tails read 0.009-0.20 / 0.06-1.0
# from window to window, so at this size the scores decide, and the state's
# limits only bound it (at the published widths they are tight: the
# workload file's readings)
TINY_LIMITS = dict(score_rms_ratio=6.0, score_median_gap=0.0015,
                   score_p90_gap=0.008, score_p99_gap=0.04,
                   cache_rel_rms=0.02, cache_off_share=0.05,
                   conv_rel_rms=0.35, conv_off_share=0.5,
                   near_tie_share=0.5)


def test_the_cell_finds_its_files():
    manifest = harness.load_manifest()
    spec = {w["name"]: w for w in manifest["workloads"]}[CELL]
    assert spec["chips"] == 1
    cfg = harness.load_json("configs", spec["config"] + ".json")
    mix = harness.load_json("traffic", spec["traffic"] + ".json")
    cell = harness.load_json("workloads", CELL + ".json")
    assert cfg["model"]["in_dim"] == 3
    assert cfg["telemeter"]["trainEveryBatches"] == 0
    assert mix["vocab"] == cfg["vocab_size"] == 65536
    # the traffic of the other flow cell, but for the vocabulary
    other = harness.load_json("traffic", "flows64x64.json")
    assert {k: v for k, v in mix.items()
            if k not in ("vocab", "what", "source")} == {
        k: v for k, v in other.items()
        if k not in ("vocab", "what", "source")}
    assert mix["flows"] == cfg["model"]["slots"]
    assert set(cell["limits"]) >= {"evictions", "wraps", "failed_calls",
                                   "window_compiles", "unexpected_shapes",
                                   "cache_rel_rms", "conv_rel_rms"}
    for kind, name in (("entries", cfg["entry"]),
                       ("entries", "lfm2_control_fp8"),
                       ("reference", cfg["reference"]),
                       ("counts", cfg["counts"]), ("checks", cfg["check"]),
                       ("traffic", mix["generator"])):
        harness.load_code(kind, name)
    for fault in FAULTS:
        assert os.path.isfile(os.path.join(HERE, "tests", "faults",
                                           f"fault_lfm2_{fault}.py"))


def test_the_configuration_is_the_catalogs_cut_in_depth_alone():
    cfg = harness.load_json("configs", "lfm2-24b-a2b.json")
    assert cfg["reduced"] == ["num_hidden_layers", "num_dense_layers",
                              "layer_types"]
    assert (cfg["hidden_size"], cfg["intermediate_size"],
            cfg["moe_intermediate_size"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"], cfg["conv_L_cache"],
            cfg["num_experts"], cfg["num_experts_per_tok"],
            cfg["vocab_size"]) == (2048, 11776, 1536, 32, 8, 3, 64, 4, 65536)
    pub = cfg["published"]
    assert (pub["num_hidden_layers"], pub["num_dense_layers"],
            len(pub["layer_types"])) == (40, 2, 40)
    # one leading dense layer, then whole periods of the published pattern
    # after its dense layers
    assert cfg["layer_types"] == (pub["layer_types"][:1]
                                  + pub["layer_types"][2:10])
    assert pub["layer_types"][2:6] * 2 == pub["layer_types"][2:10]
    assert len(cfg["layer_types"]) == cfg["num_hidden_layers"] == 9
    assert cfg["model"]["experts_held"] == [0, cfg["num_experts"]]


def test_sound_run_is_correct(tiny_lfm2_tree):
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


def test_control_in_float8_is_not_correct(tiny_lfm2_tree):
    r = harness.run_cell(CELL, 2147483701, 0.5, False, on_chip=False,
                         entry_name="lfm2_control_fp8")
    assert not r["correct"]
    bad = {k for k, c in r["compared"].items() if c["value"] > c["limit"]}
    assert "score_rms_ratio" in bad, r["compared"]


@pytest.mark.parametrize("fault", FAULTS)
def test_fault_under_the_timed_path_is_not_correct(tiny_lfm2_tree, fault):
    shutil.copy(os.path.join(HERE, "tests", "faults",
                             f"fault_lfm2_{fault}.py"),
                os.path.join(tiny_lfm2_tree, "chipbench", "entries"))
    r = harness.run_cell(CELL, 2147483703, 1.5, False, on_chip=False,
                         entry_name=f"fault_lfm2_{fault}")
    assert not r["correct"], r["compared"]


def test_counts_at_the_published_widths():
    cfg = harness.load_json("configs", "lfm2-24b-a2b.json")
    counts = harness.load_code("counts", cfg["counts"])
    model = cfg["model"]
    assert counts.conv_weights(cfg) == 16_783_360
    assert counts.attention_weights(cfg) == 10_485_760
    assert counts.expert_weights(cfg) == 9_437_184
    # 7 conv + 2 attention operators, the dense FFN, 8 x (router + 64
    # experts), the tied embedding: 10.36 GB in bfloat16
    assert counts.weights_held(model) == 5_177_911_296
    assert counts.weight_bytes_per_step(model) == 2 * 5_177_911_296
    per_event = counts.score_flops_per_row(model)
    attended = 2 * 2 * 2 * 2048 * model["counted_context"]
    # ISSUE 32's 1.296 GFLOP an event, and attention over the context
    assert per_event - attended == pytest.approx(1.2962e9, rel=2e-4)
    assert attended / per_event == pytest.approx(0.004, abs=0.001)
    # a call of the cell: 5.33 TFLOP, 27 ms at the bf16 peak
    assert per_event * 4096 / 197e12 == pytest.approx(0.02705, rel=0.01)
    # the kernel's two calls of a step: 0.14 ms of FLOPs, 0.22 of bytes
    assert counts.grouped_attention_flops_per_row(model) * 4096 / 197e12 \
        == pytest.approx(1.417e-4, rel=0.02)
    assert counts.grouped_attention_bytes_per_row(model) * 4096 / 819e9 \
        == pytest.approx(2.151e-4, rel=0.02)


def test_the_counted_context_is_the_traffics_mean():
    """``model.counted_context``: the positions an event attends over, on
    average, once the schedule has cycled once; and
    ``counted_attended_positions``: what the kernel's loops run over for
    it, the chunk's last position rounded up to a block of 128."""
    cfg = harness.load_json("configs", "lfm2-24b-a2b.json")
    mix = harness.load_json("traffic", "flows64x64-fullvocab.json")
    gen = harness.load_code("traffic", mix["generator"])
    s = gen.schedule(mix, 2147483699)
    length = np.zeros(mix["flows"], np.int64)
    seen, blocks = [], []
    for period in range(2):
        for v in range(mix["visits"]):
            length = np.where(s["restart"][v] | (length == 0), 1, length)
            if period:
                seen.append(length + (mix["chunk"] + 1) / 2)
                blocks.append(-(-(length + mix["chunk"]) // 128) * 128)
            length = length + mix["chunk"]
            assert length.max() < cfg["model"]["positions"] - mix["chunk"]
    assert np.mean(seen) == pytest.approx(cfg["model"]["counted_context"],
                                          rel=0.05)
    assert np.mean(blocks) == pytest.approx(
        cfg["model"]["counted_attended_positions"], rel=0.05)
    assert cfg["model"]["counted_chunk"] == mix["chunk"]
