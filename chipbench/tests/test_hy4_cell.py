"""The cell of ``hy4-preview-ep16`` on the CPU at a tiny preset: its files
are found, a sound run comes out correct, and the control and each planted
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

CELL = "hy4-preview-ep16.flows64x64-6k"
FAULTS = ("selection_off", "shared_reselect", "sink_left_out",
          "gate_left_out", "one_stream", "sinkhorn_skipped")
DEPTH = ["num_hidden_layers", "indexer_types", "layer_types",
         "mlp_layer_types"]
TINY_MODEL = {
    "hidden_size": 64, "num_attention_heads": 4, "q_lora_rank": 32,
    "kv_lora_rank": 16, "qk_nope_head_dim": 8, "qk_rope_head_dim": 8,
    "qk_head_dim": 16, "v_head_dim": 8, "index_n_heads": 4,
    "index_head_dim": 16, "index_topk": 16, "intermediate_size": 128,
    "moe_intermediate_size": 32, "n_routed_experts": 16,
    "swiglu_limit": 1.0, "vocab_size": 128}
TINY_GROUP = {"router_experts": 16, "experts_held": [0, 16], "slots": 16,
              "positions": 128, "expert_tile": 8, "counted_context": 40,
              "counted_selected": 14, "counted_attended": 128}
# 16 flows of 4 x 8 events a call, lifetimes of 48 events at the median
# and 96 at most: most flows pass the top 16, none a cache of 128
TINY_MIX = {"rows_per_call": 32, "setup_fit_rows_per_call": 32, "flows": 16,
            "flows_per_call": 4, "chunk": 8, "visits": 16,
            "lifetime_median_events": 48, "lifetime_cap_events": 96,
            "ids_per_flow": 64, "vocab": 128}
TINY_LIMITS = dict(score_rms_ratio=6.0, score_median_gap=0.0015,
                   score_p90_gap=0.008, score_p99_gap=0.04,
                   cache_rel_rms=0.05, cache_off_share=0.1,
                   index_rel_rms=0.05, index_off_share=0.1,
                   near_tie_share=0.5, unselected_share=0.6)


@pytest.fixture
def tiny_hy4_tree(tiny_tree):
    """The copy's configuration, mix and cell cut to a test's size: hidden
    64, 4 heads of latent attention, an indexer of 4 heads that selects 16
    positions, four streams, 16 experts top 8 beside a shared one, all
    five layers, a vocabulary of 128."""
    bench = os.path.join(tiny_tree, "chipbench")

    def config(c):
        c.update(TINY_MODEL)
        c["model"].update(TINY_GROUP)

    _edit(os.path.join(bench, "configs", "hy4-preview-ep16.json"), config)
    _edit(os.path.join(bench, "traffic", "flows64x64-6k.json"),
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
    assert cfg["telemeter"] == {"model": "hy4_moe", "trainEveryBatches": 0,
                                "scoreConcurrency": 2}
    assert mix["vocab"] == cfg["vocab_size"] == 15104
    # the cell's traffic, letter for letter
    assert {k: mix[k] for k in (
        "flows", "flows_per_call", "chunk", "visits",
        "lifetime_median_events", "lifetime_sigma", "lifetime_cap_events",
        "zipf_a", "ids_per_flow", "uniform_share", "warm_rounds",
        "generator", "driver")} == {
        "flows": 128, "flows_per_call": 64, "chunk": 64, "visits": 128,
        "lifetime_median_events": 4096, "lifetime_sigma": 1.0,
        "lifetime_cap_events": 5952, "zipf_a": 1.1, "ids_per_flow": 4096,
        "uniform_share": 0.02, "warm_rounds": 2,
        "generator": "flow_events", "driver": "closed_loop"}
    assert mix["flows"] == cfg["model"]["slots"]
    # a chunk and the warm rounds' replay of room (PERF.md section 7(4))
    assert mix["lifetime_cap_events"] + 3 * mix["chunk"] \
        == cfg["model"]["positions"]
    assert set(cell["limits"]) >= {"evictions", "wraps", "failed_calls",
                                   "window_compiles", "unexpected_shapes",
                                   "cache_rel_rms", "index_rel_rms",
                                   "unselected_share"}
    for kind, name in (("entries", cfg["entry"]),
                       ("entries", "hy4_control_fp8"),
                       ("reference", cfg["reference"]),
                       ("counts", cfg["counts"]), ("checks", cfg["check"]),
                       ("traffic", mix["generator"])):
        harness.load_code(kind, name)
    for fault in FAULTS:
        assert os.path.isfile(os.path.join(HERE, "tests", "faults",
                                           f"fault_hy4_{fault}.py"))
    mine = [m for m in manifest["per_layer"] if CELL in m["workloads"]]
    assert len(mine) == 10 and all(m["workloads"] == [CELL] for m in mine)
    for m in mine:
        how = harness.load_json("metrics", m["name"] + ".json")
        harness.load_code("readers", how["reader"])


def test_the_configuration_is_the_catalogs_cut():
    cfg = harness.load_json("configs", "hy4-preview-ep16.json")
    share = ["n_routed_experts", "vocab_size", "num_nextn_predict_layers"]
    assert cfg["reduced"] == DEPTH + share == list(cfg["reduced_how"])
    pub = cfg["published"]
    assert pub["num_hidden_layers"] == 78
    for key in DEPTH[1:]:
        assert len(pub[key]) == 78 and cfg[key] == pub[key][:5]
    assert cfg["indexer_types"] == ["full", "full"] + ["shared"] * 3
    assert cfg["mlp_layer_types"] == ["dense"] + ["sparse"] * 4
    # a whole period of the indexer's pattern after the leading pair
    assert pub["indexer_types"][1:5] * 19 == pub["indexer_types"][1:77]
    assert (cfg["n_routed_experts"], pub["n_routed_experts"]) == (16, 256)
    assert cfg["model"]["experts_held"] == [0, 16]
    assert cfg["model"]["router_experts"] == 256
    assert cfg["model"]["layer_share"] == 16
    assert cfg["vocab_size"] * 8 == pub["vocab_size"] == 120832
    assert (cfg["num_nextn_predict_layers"],
            pub["num_nextn_predict_layers"]) == (0, 1)
    assert set(cfg["assumed"]) >= {
        "hyper-connections", "Sinkhorn iterations", "streams at the end",
        "indexer", "shared layers", "sink", "gate", "router rule",
        "SwiGLU clamp", "head"}
    # a catalog of published configurations, one JSON object a line
    catalog = os.environ.get("MODEL_CATALOG", "")
    if os.path.isfile(catalog):
        with open(catalog) as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "Hy4-preview")
        assert cfg["source"].startswith(row["source_url"])
        for key, value in row["config"].items():
            if key not in cfg["reduced"]:
                assert cfg[key] == value, key


def test_sound_run_is_correct(tiny_hy4_tree):
    r = harness.run_cell(CELL, 2147483699, 1.5, False, on_chip=False)
    assert r["correct"], r["compared"]
    assert r["failed"] == 0 and r["attempted"] > 8
    assert set(r["metrics"]) == {"rows_per_s", "score_p95_ms", "setup_s"}
    state = r["info"]["state"]
    assert list(state["score_batches"]) == ["32"]
    assert list(state["flow"]["layouts"]) == ["4x8"]
    assert state["flow"]["evictions"] == state["flow"]["wraps"] == 0
    assert state["flow"]["state"]["attention"]["call"] == \
        "attend_selected_xla"
    assert r["info"]["flows_compared"] == 12
    # what was compared went past the selection
    assert r["compared"]["unselected_share"]["value"] <= 0.6
    assert r["info"]["longest_sequence"] > 16
    json.dumps(r)


def test_control_in_float8_is_not_correct(tiny_hy4_tree):
    r = harness.run_cell(CELL, 2147483701, 0.5, False, on_chip=False,
                         entry_name="hy4_control_fp8")
    assert not r["correct"]
    bad = {k for k, c in r["compared"].items() if c["value"] > c["limit"]}
    assert "score_rms_ratio" in bad, r["compared"]


@pytest.mark.parametrize("fault", FAULTS)
def test_fault_under_the_timed_path_is_not_correct(tiny_hy4_tree, fault):
    shutil.copy(os.path.join(HERE, "tests", "faults",
                             f"fault_hy4_{fault}.py"),
                os.path.join(tiny_hy4_tree, "chipbench", "entries"))
    r = harness.run_cell(CELL, 2147483703, 1.5, False, on_chip=False,
                         entry_name=f"fault_hy4_{fault}")
    assert not r["correct"], r["compared"]


def test_counts_at_the_published_widths():
    cfg = harness.load_json("configs", "hy4-preview-ep16.json")
    counts = harness.load_code("counts", cfg["counts"])
    model = cfg["model"]
    # at the published widths: MLA 165.0 M + gate 100.7 M a layer, the indexer
    # 9.4 M, the hyper-connections 1.2 M; 4.452 G held, 8.90 GB
    assert counts.attention_weights(cfg) == 265_682_944
    assert counts.indexer_weights(cfg) == 9_371_648
    assert counts.hyper_weights(cfg) == 1_179_648
    assert counts.expert_weights(cfg) == 37_748_736
    assert counts.weights_held(model) == pytest.approx(4.452e9, rel=2e-4)
    per_event = counts.score_flops_per_row(model)
    assert per_event == pytest.approx(4.54e9, rel=5e-3)
    # a call of the cell: 18.6 TFLOP, 94 ms at the bf16 peak
    assert per_event * 4096 / 197e12 == pytest.approx(0.0943, rel=0.01)
    # the kernel's calls of a step, at the selected positions
    assert counts.sparse_attention_flops_per_row(model) == (
        2 * 5 * 64 * (2 * 512 + 64) * model["counted_selected"])
    flops = counts.sparse_attention_flops_per_row(model)
    moved = counts.sparse_attention_bytes_per_row(model)
    assert flops / 197e12 > moved / 819e9     # bound by the MXU


def test_the_counted_contexts_are_the_traffics_means():
    """``model.counted_context``: the positions in an event's causal
    context, on average, once the schedule has cycled once;
    ``counted_selected``: those its selection holds (``min(2048, pos +
    1)``); ``counted_attended``: what the kernel's loops run over for it,
    in blocks of 128 by tiles of 16 events; and two fifths of the events
    attend over a selection."""
    cfg = harness.load_json("configs", "hy4-preview-ep16.json")
    mix = harness.load_json("traffic", "flows64x64-6k.json")
    gen = harness.load_code("traffic", mix["generator"])
    ctx, sel, att, longest = [], [], [], 0
    for seed in (1, 2):
        s = gen.schedule(mix, seed)
        T = mix["chunk"]
        length = np.zeros(mix["flows"], np.int64)
        for period in range(2):
            for v in range(mix["visits"]):
                length = np.where(s["restart"][v] | (length == 0), 1,
                                  length)
                if period:
                    seen = length[:, None] + np.arange(T)[None] + 1
                    ctx.append(seen)
                    sel.append(np.minimum(seen, cfg["index_topk"]))
                    att.append(sum(-(-(length + a + 16) // 128)
                                   for a in range(0, T, 16)) * 128 / 4)
                length = length + T
                longest = max(longest, length.max())
    assert longest + 2 * T <= cfg["model"]["positions"]
    model = cfg["model"]
    for got, key in ((ctx, "counted_context"), (sel, "counted_selected"),
                     (att, "counted_attended")):
        assert np.mean(got) == pytest.approx(model[key], rel=0.02), key
    assert model["counted_chunk"] == T
    assert 0.35 < np.mean(np.concatenate(ctx) > 2048) < 0.5
