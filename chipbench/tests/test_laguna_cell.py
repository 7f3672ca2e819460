"""The cell of ``laguna-xs.2`` on the CPU at a tiny preset: its files are
found, a sound run comes out correct, and the control and each planted
fault come out not correct; its configuration against the catalog's cut;
its counts at the published widths; its traffic's mean contexts."""

import json
import os
import shutil

import numpy as np
import pytest

from chipbench import harness
from chipbench.tests.conftest import HERE
from chipbench.tests.test_flow_cell import _edit

CELL = "laguna-xs.2.flows64x64-long"
FAULTS = ("window_off_by_one", "ring_not_wrapped", "restart_keeps_ring",
          "wrong_head_group", "gate_left_out", "shared_twice")

FULL, SLIDING = "full_attention", "sliding_attention"
TINY_MODEL = {
    "hidden_size": 64, "head_dim": 16, "num_key_value_heads": 2,
    "num_attention_heads_per_layer": [6, 8, 8, 8, 6],
    "intermediate_size": 128, "moe_intermediate_size": 32,
    "shared_expert_intermediate_size": 32, "num_experts": 16,
    "num_experts_per_tok": 2, "sliding_window": 16, "vocab_size": 128}
TINY_GROUP = {"experts_held": [0, 16], "slots": 16, "positions": 128,
              "expert_tile": 8, "chunk_max": 8, "ring_block": 8,
              "counted_context_full": 40, "counted_context_window": 14,
              "counted_attended_full": 128, "counted_attended_window": 24}
# 16 flows of 4 x 8 events a call, lifetimes of 24 events at the median
# and 96 at most: most flows pass a ring of 24 (a window of 16 behind
# chunks of 8), none a cache of 128
TINY_MIX = {"rows_per_call": 32, "setup_fit_rows_per_call": 32, "flows": 16,
            "flows_per_call": 4, "chunk": 8, "visits": 16,
            "lifetime_median_events": 48, "lifetime_cap_events": 96,
            "ids_per_flow": 64, "vocab": 128}
TINY_LIMITS = dict(score_rms_ratio=6.0, score_median_gap=0.0015,
                   score_p90_gap=0.008, score_p99_gap=0.04,
                   cache_rel_rms=0.05, cache_off_share=0.1,
                   ring_rel_rms=0.05, ring_off_share=0.1,
                   near_tie_share=0.5, unwrapped_share=0.6)


@pytest.fixture
def tiny_laguna_tree(tiny_tree):
    """The copy's configuration, mix and cell cut to a test's size: hidden
    64, heads of 16 (6 or 8 over 2), a window of 16 and so a ring of 24,
    16 experts top 2 beside a shared one, all five layers, a vocabulary
    of 128."""
    bench = os.path.join(tiny_tree, "chipbench")

    def config(c):
        c.update(TINY_MODEL)
        c["model"].update(TINY_GROUP)
        c["rope_parameters"][FULL]["original_max_position_embeddings"] = 32

    _edit(os.path.join(bench, "configs", "laguna-xs.2.json"), config)
    _edit(os.path.join(bench, "traffic", "flows64x64-long.json"),
          lambda m: m.update(TINY_MIX))

    def cell(c):
        c["check"].update(calls_compared=6, flows_compared=2,
                          sequence_bucket=128)
        c["limits"].update(TINY_LIMITS)

    _edit(os.path.join(bench, "workloads", CELL + ".json"), cell)
    return tiny_tree


def test_the_cell_finds_its_files():
    manifest = harness.load_manifest()
    spec = {w["name"]: w for w in manifest["workloads"]}[CELL]
    assert spec["chips"] == 1 and len(spec["why"]) <= 200
    cfg = harness.load_json("configs", spec["config"] + ".json")
    mix = harness.load_json("traffic", spec["traffic"] + ".json")
    cell = harness.load_json("workloads", CELL + ".json")
    assert cfg["model"]["in_dim"] == 3
    assert cfg["telemeter"] == {"model": "laguna_moe", "trainEveryBatches": 0,
                                "scoreConcurrency": 2}
    assert mix["vocab"] == cfg["vocab_size"] == 100352
    # ISSUE 34's traffic, letter for letter
    assert {k: mix[k] for k in (
        "flows", "flows_per_call", "chunk", "visits",
        "lifetime_median_events", "lifetime_sigma", "lifetime_cap_events",
        "zipf_a", "ids_per_flow", "uniform_share", "vocab", "warm_rounds",
        "generator", "driver")} == {
        "flows": 128, "flows_per_call": 64, "chunk": 64, "visits": 128,
        "lifetime_median_events": 1024, "lifetime_sigma": 1.4,
        "lifetime_cap_events": 4032, "zipf_a": 1.1, "ids_per_flow": 4096,
        "uniform_share": 0.02, "vocab": 100352, "warm_rounds": 2,
        "generator": "flow_events", "driver": "closed_loop"}
    assert mix["flows"] == cfg["model"]["slots"]
    assert mix["lifetime_cap_events"] + 1 <= cfg["model"]["positions"]
    assert mix["chunk"] <= cfg["model"]["chunk_max"]
    assert set(cell["limits"]) >= {"evictions", "wraps", "failed_calls",
                                   "window_compiles", "unexpected_shapes",
                                   "cache_rel_rms", "ring_rel_rms",
                                   "unwrapped_share"}
    for kind, name in (("entries", cfg["entry"]),
                       ("entries", "laguna_control_fp8"),
                       ("reference", cfg["reference"]),
                       ("counts", cfg["counts"]), ("checks", cfg["check"]),
                       ("traffic", mix["generator"])):
        harness.load_code(kind, name)
    for fault in FAULTS:
        assert os.path.isfile(os.path.join(HERE, "tests", "faults",
                                           f"fault_laguna_{fault}.py"))
    mine = [m for m in manifest["per_layer"] if CELL in m["workloads"]]
    assert len(mine) == 16 and all(m["workloads"] == [CELL] for m in mine)
    for m in mine:
        how = harness.load_json("metrics", m["name"] + ".json")
        harness.load_code("readers", how["reader"])
    assert {m["name"] for m in mine if m["moves"] != "rows_per_s"} == {
        "laguna.map_ms"}


def test_the_configuration_is_the_catalogs_cut_in_depth_alone():
    cfg = harness.load_json("configs", "laguna-xs.2.json")
    depth = ["num_hidden_layers", "layer_types", "mlp_layer_types",
             "num_attention_heads_per_layer"]
    assert cfg["reduced"] == depth == list(cfg["reduced_how"])
    assert (cfg["hidden_size"], cfg["head_dim"], cfg["num_key_value_heads"],
            cfg["num_attention_heads"], cfg["intermediate_size"],
            cfg["moe_intermediate_size"],
            cfg["shared_expert_intermediate_size"], cfg["num_experts"],
            cfg["num_experts_per_tok"], cfg["sliding_window"],
            cfg["vocab_size"], cfg["tie_word_embeddings"]) == (
        2048, 128, 8, 48, 8192, 512, 512, 256, 8, 512, 100352, False)
    pub = cfg["published"]
    assert pub["num_hidden_layers"] == 40
    for key in depth[1:]:
        assert len(pub[key]) == 40 and cfg[key] == pub[key][:5]
    assert cfg["layer_types"] == [FULL, SLIDING, SLIDING, SLIDING, FULL]
    assert cfg["mlp_layer_types"] == ["dense"] + ["sparse"] * 4
    assert cfg["num_attention_heads_per_layer"] == [48, 64, 64, 64, 48]
    # a whole period of the pattern after the leading dense layer
    assert pub["layer_types"][1:5] * 9 == pub["layer_types"][1:37]
    assert cfg["model"]["experts_held"] == [0, cfg["num_experts"]]
    assert set(cfg["assumed"]) >= {"gate", "router rule", "no norms, no bias"}
    assert any("exactly the last 512" in g for g in cfg["guarantees"])
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.isfile(catalog):
        with open(catalog) as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "Laguna-XS.2")
        assert cfg["source"].startswith(row["source_url"])
        for key, value in row["config"].items():
            if key not in depth:
                assert cfg[key] == value, key


def test_sound_run_is_correct(tiny_laguna_tree):
    r = harness.run_cell(CELL, 2147483699, 1.5, False, on_chip=False)
    assert r["correct"], r["compared"]
    assert r["failed"] == 0 and r["attempted"] > 8
    assert set(r["metrics"]) == {"rows_per_s", "score_p95_ms", "setup_s"}
    state = r["info"]["state"]
    assert list(state["score_batches"]) == ["32"]
    assert list(state["flow"]["layouts"]) == ["4x8"]
    assert state["flow"]["evictions"] == state["flow"]["wraps"] == 0
    assert state["flow"]["resident"] == 16
    assert state["flow"]["state"]["window_attention"]["positions"] == 24
    assert r["info"]["flows_compared"] == 12
    # what was compared has been round a ring
    assert r["compared"]["unwrapped_share"]["value"] <= 0.6
    assert r["info"]["longest_sequence"] > 24
    json.dumps(r)


def test_control_in_float8_is_not_correct(tiny_laguna_tree):
    r = harness.run_cell(CELL, 2147483701, 0.5, False, on_chip=False,
                         entry_name="laguna_control_fp8")
    assert not r["correct"]
    bad = {k for k, c in r["compared"].items() if c["value"] > c["limit"]}
    assert "score_rms_ratio" in bad, r["compared"]


@pytest.mark.parametrize("fault", FAULTS)
def test_fault_under_the_timed_path_is_not_correct(tiny_laguna_tree, fault):
    shutil.copy(os.path.join(HERE, "tests", "faults",
                             f"fault_laguna_{fault}.py"),
                os.path.join(tiny_laguna_tree, "chipbench", "entries"))
    r = harness.run_cell(CELL, 2147483703, 1.5, False, on_chip=False,
                         entry_name=f"fault_laguna_{fault}")
    assert not r["correct"], r["compared"]


def test_counts_at_the_published_widths():
    cfg = harness.load_json("configs", "laguna-xs.2.json")
    counts = harness.load_code("counts", cfg["counts"])
    model = cfg["model"]
    assert counts.attention_weights(cfg, 0) == 29_458_432
    assert counts.attention_weights(cfg, 1) == 37_879_808
    assert counts.expert_weights(cfg) == 3_145_728
    # 2 full + 3 sliding attentions, the dense FFN, 4 x (router + shared +
    # 256 experts), embedding and head: 7.74 GB in bfloat16
    assert counts.weights_held(model) == 3_869_835_264
    assert counts.weight_bytes_per_step(model) == 2 * (
        3_869_835_264 - 2048 * 100352)
    per_event = counts.score_flops_per_row(model)
    # ISSUE 34's 1.19 GFLOP an event; attention over the contexts 104 M
    assert per_event == pytest.approx(1.19e9, rel=5e-3)
    attended = 2 * 2 * 128 * (2 * 48 * model["counted_context_full"]
                              + 3 * 64 * model["counted_context_window"])
    assert attended == pytest.approx(104e6, rel=0.02)
    # a call of the cell: 4.88 TFLOP, 24.8 ms at the bf16 peak
    assert per_event * 4096 / 197e12 == pytest.approx(0.0248, rel=0.01)
    # the two kernels' calls of a step, at the attended blocks
    assert counts.full_attention_flops_per_row(model) == (
        2 * 2 * 2 * 48 * 128 * model["counted_attended_full"])
    assert counts.window_attention_flops_per_row(model) == (
        2 * 3 * 2 * 64 * 128 * model["counted_attended_window"])
    assert counts.full_attention_bytes_per_row(model) == 2 * 2 * (
        2 * 48 * 128 + 2048 * model["counted_attended_full"] / 64)
    assert counts.window_attention_bytes_per_row(model) == 2 * 3 * (
        2 * 64 * 128 + 2048 * model["counted_attended_window"] / 64)
    # both are bound by the MXU, not by their bytes
    for kind in ("full", "window"):
        flops = getattr(counts, f"{kind}_attention_flops_per_row")(model)
        moved = getattr(counts, f"{kind}_attention_bytes_per_row")(model)
        assert flops / 197e12 > moved / 819e9


def test_the_counted_contexts_are_the_traffics_means():
    """``model.counted_context_full`` / ``_window``: the positions an
    event attends over on a full and on a sliding layer, on average, once
    the schedule has cycled once; ``counted_attended_*``: what the
    kernels' loops run over for them, in blocks of 128 by tiles of 32
    events (a full layer's group of 6 heads) or 64 (a sliding one's);
    and two thirds of the chunks reach past the window."""
    cfg = harness.load_json("configs", "laguna-xs.2.json")
    mix = harness.load_json("traffic", "flows64x64-long.json")
    gen = harness.load_code("traffic", mix["generator"])
    s = gen.schedule(mix, 1)
    W, T = cfg["sliding_window"], mix["chunk"]
    length = np.zeros(mix["flows"], np.int64)
    full, window, blocks, ring_blocks, past, longest = [], [], [], [], [], 0
    for period in range(2):
        for v in range(mix["visits"]):
            length = np.where(s["restart"][v] | (length == 0), 1, length)
            if period:
                seen = length[:, None] + np.arange(T)[None] + 1
                full.append(seen)
                window.append(np.minimum(seen, W))
                blocks.append(sum(-(-(length + a + 32) // 128)
                                  for a in (0, 32)) * 128 / 2)
                ring_blocks.append((-(-(length + T) // 128) - np.maximum(
                    length - W + 1, 0) // 128) * 128)
                past.append(length + T - 1 >= W)
            length = length + T
            longest = max(longest, length.max())
    assert longest <= cfg["model"]["positions"]
    model = cfg["model"]
    for got, key in ((full, "counted_context_full"),
                     (window, "counted_context_window"),
                     (blocks, "counted_attended_full"),
                     (ring_blocks, "counted_attended_window")):
        assert np.mean(got) == pytest.approx(model[key], rel=0.01), key
    assert model["counted_chunk"] == T
    assert 0.6 < np.mean(past) < 0.8
