"""The flow models (``models/latent_moe.py``, ``models/lfm2_moe.py``) behind
``InProcessScorer``, each against the benchmark's plain reference
(``chipbench/reference/``) on seeded weights, at a tiny preset on the CPU:
hidden 64, 4 heads, a vocabulary of 128; 16 experts, top 2 of which 4 are
held (``latent_moe``) or top 4 with all held, 2 key/value heads and 3
convolution layers beside 1 of attention (``lfm2_moe``). The cases that
are the same for both run over both (the ``model`` fixture); what only
the second model has is in ``tests/test_lfm2_moe.py``."""

import asyncio
import dataclasses
import functools
import time
from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.reference import latent_moe as ref
from chipbench.reference import lfm2_moe as ref_lfm2
from linkerd_tpu.models import latent_moe as lm
from linkerd_tpu.models import lfm2_moe as lf
from linkerd_tpu.models.features import FEATURE_DIM, event_ids
from linkerd_tpu.models.spec import SPECS, latent_moe, lfm2_moe, mlp36
from linkerd_tpu.telemetry import phases
from linkerd_tpu.telemetry.anomaly import (
    InProcessScorer, JaxAnomalyConfig, JaxAnomalyTelemeter,
)
from linkerd_tpu.telemetry.flowstate import FlowTable
from linkerd_tpu.telemetry.linerate import (
    NATIVE_COL_KIND, NATIVE_COL_SEQ, NATIVE_COL_STREAM, NATIVE_ROW_WIDTH,
)
from linkerd_tpu.telemetry.metrics import MetricsTree

SEED = 7
TINY = {
    "hidden_size": 64, "num_attention_heads": 4, "q_lora_rank": 32,
    "kv_lora_rank": 16, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
    "v_head_dim": 16, "intermediate_size": 128, "moe_intermediate_size": 32,
    "n_routed_experts": 4, "n_shared_experts": 1, "num_experts_per_tok": 2,
    "routed_scaling_factor": 2.827, "first_k_dense_replace": 1,
    "rms_norm_eps": 1e-5, "rope_theta": 50000,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 64,
                     "mscale": 1, "mscale_all_dim": 1,
                     "original_max_position_embeddings": 4096,
                     "type": "yarn"},
    "scoring_func": "sigmoid", "n_group": 1, "num_hidden_layers": 3,
    "vocab_size": 128,
    "model": {"in_dim": 3, "router_experts": 16, "experts_held": [4, 8],
              "layer_share": 4, "slots": 8, "positions": 64,
              "expert_tile": 8, "compute_dtype": "bfloat16"}}
CFG = lm.LatentMoEConfig.from_config(TINY)
TINY_LFM2 = {
    "hidden_size": 64, "intermediate_size": 128, "moe_intermediate_size": 32,
    "num_attention_heads": 4, "num_key_value_heads": 2, "conv_L_cache": 3,
    "conv_bias": False, "num_experts": 16, "num_experts_per_tok": 4,
    "routed_scaling_factor": 1, "norm_eps": 1e-5, "norm_topk_prob": True,
    "use_expert_bias": True,
    "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"},
    "layer_types": ["conv", "full_attention", "conv", "conv"],
    "num_hidden_layers": 4, "num_dense_layers": 1, "vocab_size": 128,
    "model": {"in_dim": 3, "experts_held": [0, 16], "layer_share": 1,
              "slots": 8, "positions": 64, "expert_tile": 8,
              "compute_dtype": "bfloat16", "router_bias_std": 0.05}}
CFG_LFM2 = lf.Lfm2MoEConfig.from_config(TINY_LFM2)


# a score is off by the compute type's rounding, a few 1e-4 at this width;
# a token whose second and third router scores lie within rounding takes
# another expert in bfloat16 than in float32 and is off by up to 1e-2
TYPICAL, WORST = 6e-4, 2e-2
# the second model selects 4 of 16, whose 4th and 5th scores lie closer:
# more tokens take another expert, and one that does is further off
TYPICAL_LFM2, WORST_LFM2 = 1e-3, 6e-2


class Model(NamedTuple):
    """One flow model at its tiny preset: the configuration file's dict,
    the program's configuration of it, its spec and its reference."""
    name: str
    tiny: dict
    cfg: Any
    spec: Callable
    ref: Any
    typical: float = TYPICAL    # a score's median gap, and its largest
    worst: float = WORST

    def kept_gap(self, state, full, b: int, n: int) -> list:
        """Per layer, what the state keeps of the flow in slot ``b`` (``n``
        positions long) less what the reference computes of them: the
        cache's entries; of a convolution layer ``u`` of the last two
        positions."""
        gaps = []
        for l in range(self.cfg.layers):
            got = np.asarray(state[0][l][b], np.float32)
            if self.name == "latent_moe":
                gaps.append(got[:n] - full["entries"][l, b, :n])
            elif self.tiny["layer_types"][l] == "conv":
                gaps.append(got - full["kept"][l][b, n - 2:n])
            else:       # the cache lies [entry, positions]
                gaps.append(got.T[:n] - full["kept"][l][b, :n])
        return gaps


MODELS = {"latent_moe": Model("latent_moe", TINY, CFG, latent_moe, ref),
          "lfm2_moe": Model("lfm2_moe", TINY_LFM2, CFG_LFM2, lfm2_moe,
                            ref_lfm2, TYPICAL_LFM2, WORST_LFM2)}


def run(coro):
    return asyncio.run(asyncio.wait_for(coro, 300))


def scorer(model=MODELS["latent_moe"], seed=SEED):
    return InProcessScorer(seed=seed, spec=model.spec(model.cfg),
                           devices=jax.devices()[:1])


@pytest.fixture(params=sorted(MODELS))
def model(request):
    return MODELS[request.param]


def rows_of(flows: dict, restart=()) -> np.ndarray:
    """``{key: ids}`` -> rows, the flows' events interleaved round robin."""
    out, t = [], 0
    while any(t < len(v) for v in flows.values()):
        out += [(k, int(k in restart and t == 0), v[t])
                for k, v in flows.items() if t < len(v)]
        t += 1
    return np.array(out, np.int32).reshape(-1, 3)


def reference_scores(seqs: dict, model=MODELS["latent_moe"]) -> dict:
    """``{key: ids}`` -> ``{key: the reference's score of every event}``,
    each flow forward once from its start token."""
    L = model.tiny["model"]["positions"]
    tokens = np.zeros((len(seqs), L), np.int32)
    for b, ids in enumerate(seqs.values()):
        tokens[b, 1:1 + len(ids)] = ids
    got = model.ref.forward(SEED, model.tiny, tokens, block=2)
    return ({k: got["score"][b, 1:1 + len(v)]
             for b, (k, v) in enumerate(seqs.items())}, got)


def close_to(got, want, model=None):
    model = model or MODELS["latent_moe"]
    gap = np.abs(np.asarray(got) - np.asarray(want))
    assert np.median(gap) < model.typical and gap.max() < model.worst, (
        np.median(gap), gap.max())


@pytest.fixture(params=["xla", "fused"])
def attention(request, monkeypatch):
    """The step built on each attention and each grouped product of the
    routed experts: XLA's, which this platform gets, and the TPU's
    kernels (``ops/flow_attention.py``, ``ops/expert_product.py``),
    interpreted; the TPU's append too (``ops/cache_append.py``), which
    hands these slots of 64 positions, no whole tile, to XLA's."""
    if request.param == "fused":
        from linkerd_tpu.ops import cache_append as ca
        from linkerd_tpu.ops import expert_product as ep
        from linkerd_tpu.ops import flow_attention as fa
        monkeypatch.setattr(
            ca, "best_append", lambda platform: functools.partial(
                ca.cache_append_fused, interpret=True))
        monkeypatch.setattr(
            fa, "best_attention",
            lambda platform, grouped=False: functools.partial(
                fa.grouped_attention_fused if grouped
                else fa.latent_attention_fused, interpret=True))
        monkeypatch.setattr(ep, "best_expert_product",
                            lambda platform: product_of("fused"))
    return request.param


def product_of(kind: str) -> lm.ExpertOps:
    """``routed_experts``' ``experts`` of a kind: XLA's loop and
    scatter-add, or the two kernels interpreted."""
    if kind == "xla":
        return lm.ExpertOps()
    from linkerd_tpu.ops import expert_product as ep
    return lm.ExpertOps(
        functools.partial(ep.swiglu_tiles_fused, interpret=True),
        functools.partial(ep.add_rows_fused, interpret=True))


@pytest.fixture(scope="module")
def seqs():
    rng = np.random.default_rng(0)
    return {11: rng.integers(1, 128, 40), 22: rng.integers(1, 128, 25),
            33: rng.integers(1, 128, 33)}


class TestAgainstTheReference:
    def test_the_reference_is_plain(self, model):
        with open(model.ref.__file__) as f:
            src = f.read()
        assert "linkerd_tpu" not in src.replace(
            "It imports\nnothing of the program", "")
        assert 'default_matmul_precision("highest")' in src

    def test_one_full_forward(self, seqs, attention, model):
        async def go():
            s = scorer(model)
            try:
                rows = rows_of(seqs)
                return rows, await s.score(rows), s._state
            finally:
                s.close()
        rows, got, state = run(go())
        want, full = reference_scores(seqs, model)
        assert got.shape == (len(rows),) and got.dtype == np.float32
        assert ((got >= 0) & (got <= 1)).all()
        for key in seqs:
            close_to(got[rows[:, 0] == key], want[key], model)
        # every layer's state holds what the reference computes of each
        # position (a product of two gates, ``u``, is the wider)
        for b, (key, ids) in enumerate(seqs.items()):
            for gap in model.kept_gap(state, full, b, 1 + len(ids)):
                gap = np.abs(gap)
                assert np.median(gap) < 8e-3 and gap.max() < 0.2
        assert np.asarray(state[1])[:3].tolist() == [41, 26, 34]

    def test_chunked_appends_through_the_cache(self, seqs, attention,
                                               model):
        """Chunks of unequal length, a restart in the middle: every call's
        scores are the reference's for one full forward of each flow since
        its restart."""
        rng = np.random.default_rng(1)
        again = rng.integers(1, 128, 17)     # flow 22's second life

        async def go():
            s = scorer(model)
            at, got = {k: 0 for k in seqs}, {k: [] for k in seqs}
            got[220] = []
            try:
                step = 0
                while any(at[k] < len(v) for k, v in seqs.items()):
                    chunk = {k: v[at[k]:at[k] + int(rng.integers(0, 9))]
                             for k, v in seqs.items()}
                    rows = rows_of({k: c for k, c in chunk.items()
                                    if len(c)})
                    for k, c in chunk.items():
                        at[k] += len(c)
                    if len(rows):
                        out = await s.score(rows)
                        for k in chunk:
                            got[k].extend(out[rows[:, 0] == k])
                    step += 1
                # flow 22 restarts under its key and runs on in two calls
                out = await s.score(rows_of({22: again[:9]}, restart={22}))
                got[220].extend(out)
                got[220].extend(await s.score(rows_of({22: again[9:]})))
                return got, s.device_state()
            finally:
                s.close()
        got, state = run(go())
        want, _ = reference_scores({**seqs, 220: again}, model)
        for key in got:
            close_to(got[key], want[key], model)
        assert len(state["flow"]["layouts"]) > 1     # more than one shape
        assert state["flow"]["resident"] == 3

    @pytest.mark.parametrize("product", ["xla", "fused"])
    def test_the_shares_add_up_to_the_uncut_layer(self, model, product):
        """Guide section 4: the routed parts that all 4 shares give, with
        what every share computes alike (the shared expert, where the model
        has one: ``lfm2_moe`` has none) counted once, add up to what the
        uncut reference gives for the whole layer; program and reference
        alike. ``lfm2_moe``: 64 experts in shares of 16."""
        layer, r = 1, model.ref
        E = 16 if model.name == "latent_moe" else 64
        tiny = {**model.tiny, "num_experts": E}
        x = jax.random.normal(jax.random.key(3), (24, model.cfg.hidden_size))
        whole = r.layer_weights(SEED, tiny, layer, held=(0, E))
        idx, wts, _ = r.route(whole, tiny, r._q(x, "bf16"))
        alike = (r.swiglu(x, whole["shared_gate"], whole["shared_up"],
                          whole["shared_down"], None)
                 if "shared_gate" in whole else jnp.zeros_like(x))
        uncut = alike + r.routed_part(whole, tiny, x, idx, wts, 0)
        ref_sum = got_sum = alike
        tokens = 0
        for lo in range(0, E, E // 4):
            held = (lo, lo + E // 4)
            part = r.layer_weights(SEED, tiny, layer, held=held)
            ref_sum = ref_sum + r.routed_part(part, tiny, x, idx, wts, lo)
            cfg = dataclasses.replace(model.cfg, n_routed_experts=E,
                                      experts_held=held)
            lp = lm.init(jax.random.key(SEED), cfg)["layers"][layer]
            out, cnt, _ = lm.routed_experts(lp, cfg, x, jnp.ones(24, bool),
                                            product_of(product))
            got_sum = got_sum + out
            tokens += int(cnt.sum())
        # every pair computed on one share
        assert tokens == 24 * model.cfg.num_experts_per_tok
        np.testing.assert_allclose(ref_sum, uncut, atol=1e-5)
        gap = np.abs(np.asarray(got_sum) - np.asarray(uncut))
        scale = np.abs(np.asarray(uncut)).mean()
        assert np.median(gap) < 0.01 * scale and gap.max() < 0.2 * scale

    @pytest.mark.parametrize("product", ["xla", "fused"])
    def test_routing_is_dropless_when_every_token_picks_one_expert(
            self, model, product):
        cfg = model.cfg
        (lo, hi), k = cfg.experts_held, cfg.num_experts_per_tok
        params = lm.init(jax.random.key(SEED), cfg)
        lp = dict(params["layers"][1])
        # the bias puts experts 5, 6, .. (all held) first for every token
        first = list(range(5, 5 + k))
        lp["router_bias"] = jnp.zeros(16, jnp.bfloat16).at[
            jnp.array(first)].set(10.0)
        x = jax.random.normal(jax.random.key(4), (50, cfg.hidden_size))
        valid = jnp.arange(50) < 47          # three rows of padding
        out, cnt, _ = lm.routed_experts(lp, cfg, x, valid,
                                        product_of(product))
        assert cnt.tolist() == [47 * (lo + g in first)
                                for g in range(hi - lo)]
        idx, w = (np.asarray(a) for a in lm.route(lp, cfg, x))
        assert (np.sort(idx, 1) == first).all()
        want = sum(
            np.where(idx == e, w, 0).sum(1, keepdims=True)
            * np.asarray(lm._swiglu(x, lp["exp_gate"][e - lo],
                                    lp["exp_up"][e - lo],
                                    lp["exp_down"][e - lo]))
            for e in first)
        np.testing.assert_allclose(np.asarray(out)[:47], want[:47],
                                   rtol=2e-2, atol=2e-3)
        assert not np.asarray(out)[47:].any()


def test_a_closed_scorer_frees_its_device_arrays_without_the_collector(
        model):
    """A caller that closes a scorer and drops it has the device's memory
    back at once, by reference counts alone: the benchmark's check draws
    a float32 reference beside where 12.6 GB of weights and state just
    lay, and cannot wait for the cycle collector (the dispatcher held
    the scorer's bound methods, and the scorer the dispatcher)."""
    import gc
    import weakref

    async def go(s):
        return await s.score(rows_of({11: [3, 4, 5], 22: [7, 8]}))

    gc.collect()
    gc.disable()
    try:
        s = scorer(model)
        run(go(s))
        kept = weakref.ref(s._state[0][0])      # a layer's state
        s.close()
        gone = weakref.ref(s)
        del s
        assert gone() is None and kept() is None
    finally:
        gc.enable()


class TestState:
    def test_padding_rows_leave_the_state_untouched(self, seqs, attention,
                                                    model):
        """The same rows in a bucket of their own size and in a larger one
        whose padding holds stale rows: the same scores, the same state,
        of every kind."""
        spec = model.spec(model.cfg)
        params = spec.init(jax.random.key(SEED))
        step = spec.make_step("cpu")
        rows = rows_of({k: v[:5] for k, v in seqs.items()})    # 15 rows
        plan = spec.make_table().map(rows)
        outs = []
        for bucket, stale in ((15, None), (32, 77)):
            staged = np.full((bucket, 3), stale or 0, np.int32)
            staged[:15] = plan.rows
            outs.append(step(params, spec.init_state(), jnp.asarray(staged),
                             15, plan.layout))
        (a, sa, ca), (b, sb, cb) = outs
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b)[:15])
        assert not np.asarray(b)[15:].any()
        for x, y in zip(jax.tree_util.tree_leaves(sa),
                        jax.tree_util.tree_leaves(sb)):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
        assert int(ca["cache.positions"]) == int(cb["cache.positions"]) == 18

    def test_a_call_writes_its_chunks_rows_and_counts_them(self, seqs,
                                                            attention):
        """Three calls bring two flows their next chunks (the second call
        one flow alone). Each call leaves every cache row outside its
        chunks (and position 0 of a flow that begins) bit for bit as it
        was, the slots are taken in place whichever attention runs, and
        the call's record counts the rows written: a window of ``T + 1``
        positions a live flow a layer, of the slot's 64."""
        flows = {11: seqs[11][:24], 33: seqs[33][:16]}
        calls = [{11: (0, 8), 33: (0, 8)}, {11: (8, 16)},
                 {11: (16, 24), 33: (8, 16)}]

        async def go():
            s = scorer()
            try:
                t0, caches = time.monotonic(), []
                for call in calls:
                    await s.score(rows_of(
                        {k: flows[k][a:b] for k, (a, b) in call.items()}))
                    caches.append(np.stack(
                        [np.asarray(c, np.float32) for c in s._state[0]]))
                return t0, caches, dict(s._table.slot_of)
            finally:
                s.close()
        t0, caches, slot_of = run(go())
        records = [c for c in phases.records()
                   if c.kind == phases.SCORE and c.t0 >= t0]
        assert len(records) == len(calls)
        before = np.zeros_like(caches[0])
        for call, after, rec in zip(calls, caches, records):
            mine = np.zeros(after.shape[1:3], bool)
            for k, (a, b) in call.items():
                mine[slot_of[k], 1 + a:1 + b] = True
                mine[slot_of[k], 0] |= a == 0
            np.testing.assert_array_equal(after[:, ~mine], before[:, ~mine])
            changed = int((after != before).any(-1).sum())
            assert 0 < changed <= CFG.layers * mine.sum()
            # a layout of 8 events: windows of 9 positions
            wrote = CFG.layers * len(call) * 9
            assert changed <= wrote == rec.counts["cache.rows_written"]
            assert rec.counts["append.flows"] == CFG.layers * len(call)
            assert rec.counts["append.flows_in_kernel"] == 0
            assert rec.counts["cache.rows_whole"] == (
                CFG.layers * len(call) * CFG.positions)
            before = after

    def test_two_calls_in_flight_apply_in_call_order(self, seqs, model):
        calls = [rows_of({k: v[a:a + 6] for k, v in seqs.items()})
                 for a in range(0, 24, 6)]

        async def go(together):
            s = scorer(model)
            try:
                if together:
                    return await asyncio.gather(*(s.score(c) for c in calls))
                return [await s.score(c) for c in calls]
            finally:
                s.close()
        for a, b in zip(run(go(True)), run(go(False))):
            np.testing.assert_array_equal(a, b)

    def test_a_failed_call_leaves_the_state_as_it_was(self, seqs, model):
        first = rows_of({k: v[:6] for k, v in seqs.items()})
        second = rows_of({k: v[6:12] for k, v in seqs.items()})
        bad = second.copy()
        bad[3, 2] = 128                     # an id outside the slice

        async def go(fail):
            s = scorer(model)
            try:
                await s.score(first)
                if fail:
                    before = s._table.checkpoint()
                    state = jax.tree_util.tree_map(np.asarray, s._state)
                    with pytest.raises(ValueError):
                        await s.score(bad)
                    # a new key in a call that fails at its launch
                    real = s._scorer

                    def boom(*a, **kw):
                        raise RuntimeError("launch failed")
                    s._scorer = boom
                    with pytest.raises(RuntimeError, match="launch failed"):
                        await s.score(np.array([[99, 0, 5]], np.int32))
                    s._scorer = real
                    after = s._table.checkpoint()
                    assert before[0] == after[0] and before[4] == after[4]
                    np.testing.assert_array_equal(before[2], after[2])
                    # and the device's state, of every kind, is untouched
                    for a, b in zip(jax.tree_util.tree_leaves(state),
                                    jax.tree_util.tree_leaves(s._state)):
                        np.testing.assert_array_equal(a, np.asarray(b))
                return await s.score(second)
            finally:
                s.close()
        np.testing.assert_array_equal(run(go(True)), run(go(False)))

    def test_the_frozen_spec_has_no_fit_and_one_device(self, seqs, model):
        async def go():
            s = scorer(model)
            try:
                rows = rows_of(seqs)
                with pytest.raises(RuntimeError, match="frozen"):
                    await s.fit(rows, np.zeros(len(rows)),
                                np.zeros(len(rows)))
                with pytest.raises(RuntimeError, match="frozen"):
                    s.snapshot()
                d = s.device_state()
                assert d["model"] == model.name
                assert d["flow"]["experts_held"] == list(
                    model.cfg.experts_held)
                assert d["flow"]["slots"] == 8
                assert d["flow"]["positions"] == 64
                assert d["flow"]["attention"] == "xla"   # not a TPU
            finally:
                s.close()
        run(go())
        with pytest.raises(ValueError, match="single-device"):
            InProcessScorer(spec=model.spec(model.cfg),
                            devices=jax.devices()[:2])

    def test_the_default_spec_is_todays_model(self):
        s = InProcessScorer(devices=jax.devices()[:1])
        try:
            assert s.spec.name == "mlp36" and s.spec.trains
            assert s.spec.row_width == FEATURE_DIM
            assert s.device_state()["model"] == "mlp36"
            assert "flow" not in s.device_state()
        finally:
            s.close()
        assert sorted(SPECS) == ["hy4_moe", "laguna_moe", "latent_moe",
                                 "lfm2_moe", "mlp36"]
        assert mlp36(0.5).cfg.recon_weight == 0.5


class TestFlowTable:
    def table(self, slots=4, positions=16):
        return FlowTable(slots, positions, 128)

    def rows(self, *events):
        return np.array(events, np.int32).reshape(-1, 3)

    def test_layout_and_addresses(self):
        t = self.table()
        p = t.map(self.rows((7, 0, 3), (9, 0, 4), (7, 0, 5), (7, 0, 6)))
        assert p.layout == (2, 4) and t.layouts == {(2, 4): 1}
        # flow 7 is the call's first flow, slot 0; its events at 1, 2, 3
        assert p.rows.tolist() == [[0, 1, 3], [4, 17, 4], [1, 2, 5],
                                   [2, 3, 6]]
        assert p.counts["flow.restarts"] == 2
        assert p.counts["flow.resident"] == 2
        q = t.map(self.rows((9, 0, 8), (7, 0, 2)))
        assert q.rows.tolist() == [[0, 18, 8], [1, 4, 2]]
        assert q.counts["flow.restarts"] == 0

    def test_restart_wrap_and_eviction_are_counted(self):
        t = self.table(slots=2, positions=8)
        t.map(self.rows(*[(1, 0, 9)] * 5))
        p = t.map(self.rows((1, 0, 9), (1, 1, 9)))   # a flag on any row
        assert p.rows[:, 1].tolist() == [1, 2]
        assert p.counts["flow.restarts"] == 1 and not p.counts["flow.wraps"]
        t.map(self.rows(*[(1, 0, 9)] * 4))           # holds 7 of 8
        p = t.map(self.rows((1, 0, 9), (1, 0, 9)))   # would pass 8: wraps
        assert p.rows[:, 1].tolist() == [1, 2]
        assert p.counts["flow.wraps"] == 1 and p.counts["flow.restarts"] == 1
        t.map(self.rows((2, 0, 9)))
        p = t.map(self.rows((3, 0, 9)))              # evicts 1, the older
        assert p.counts["flow.evictions"] == 1
        assert sorted(t.slot_of) == [2, 3]
        with pytest.raises(ValueError, match="more than 2 flows"):
            t.map(self.rows((4, 0, 9), (5, 0, 9), (6, 0, 9)))

    def test_rows_are_validated_and_a_rollback_undoes_a_call(self):
        t = self.table()
        t.map(self.rows((7, 0, 3)))
        saved = t.checkpoint()
        for bad in ((0, 0, 3), (7, 0, 0), (7, 0, 128)):
            with pytest.raises(ValueError):
                t.map(self.rows(bad))
        with pytest.raises(ValueError, match="does not fit"):
            t.map(self.rows(*[(8, 0, 3)] * 16))
        t.map(self.rows((8, 0, 3), (7, 0, 4)))
        t.rollback(saved)
        assert t.slot_of == {7: 0} and t.length[0] == 2 and t.free[-1] == 1


def test_event_ids_fold_a_row_onto_the_slice():
    x = np.zeros((4, FEATURE_DIM), np.float32)
    x[:, 0] = np.log1p([0.0, 3.0, 3.0, 1000.0])
    x[0, 2] = x[1, 2] = x[2, 5] = 1.0           # 2xx, 2xx, 5xx, none
    x[:, 14] = 1.0
    x[3, 14], x[3, 20], x[3, 13] = 0.0, -1.0, 1.0
    ids = event_ids(x)
    assert ids.dtype == np.int32 and ((ids >= 1) & (ids < 20480)).all()
    assert len(set(ids.tolist())) == 4
    assert (event_ids(x) == ids).all()
    small = event_ids(x, vocab=128)
    assert ((small >= 1) & (small < 128)).all()


async def drain_streams(tele, drain: int) -> None:
    """One drain of 12 engine rows: rows 0..7 are samples of two h2
    streams (their frames 1..4, then 5..8), rows 8..11 requests."""
    block = np.zeros((12, NATIVE_ROW_WIDTH), np.float32)
    block[:, 0] = np.arange(12) % 3            # route ids
    block[:, 1] = 5.0 + np.arange(12)          # latency ms
    block[:, 2] = 200.0
    block[:8, NATIVE_COL_KIND] = 1.0
    block[:8, NATIVE_COL_STREAM] = [501, 502] * 4
    block[:8, NATIVE_COL_SEQ] = np.arange(8) // 2 + 1 + 4 * drain
    views = tele.native_ring.produce_views(12)
    views[0][:] = block
    tele.native_ring.commit(12)
    assert await tele.drain_once() == 12


def test_the_telemeter_sends_keyed_rows_to_the_flow_scorer():
    """``model: latent_moe`` end to end: engine rows with a stream key are
    scored by the flow scorer as their streams' next events and their
    scores published per route; rows without a key keep the MLP path."""
    async def go():
        flow = scorer()
        # the weights arrive: drawn from the seed alone the tier would
        # run in shadow (the test below)
        flow.load(flow.params)
        tele = JaxAnomalyTelemeter(
            JaxAnomalyConfig(model="latent_moe", nativeTier="off",
                             trainEveryBatches=0),
            MetricsTree(), flow_scorer=flow)
        tele.set_native_route_resolver(lambda rid: f"/svc/r{rid}")
        t0 = time.monotonic()   # the log is a ring: by time, not by place
        try:
            for drain in range(2):
                await drain_streams(tele, drain)
            state = flow.device_state()
            assert state["weights"] == "loaded"
            assert state["flow"]["resident"] == 2
            assert state["score_batches"] == {"8": 2}
            assert tele._flow_scored.value == 16
            # (put.bytes: a call the ring made, not a record another
            # file's test wrote by hand)
            calls = [c for c in phases.records()
                     if c.t0 >= t0 and "flow.events" in c.counts
                     and "put.bytes" in c.counts]
            assert [c.counts["flow.events"] for c in calls] == [8, 8]
            # the streams' first samples (frame 1) began their flows; the
            # second drain went on from the cache
            assert [c.counts["flow.restarts"] for c in calls] == [2, 0]
            assert flow._table.length[:2].tolist() == [9, 9]
            # the 4 request rows of each drain went the MLP's way
            assert sum(tele._scorer.device_state()[
                "score_batches"].values()) == 2
            assert tele._scored.value == 24
            for r in range(3):
                assert 0.0 < tele.board.score_of(f"/svc/r{r}") < 1.0
        finally:
            tele.close()
    run(go())


def test_the_telemeter_builds_the_flow_tier_on_one_device_in_shadow(
        monkeypatch, model):
    """Through ``_ensure_scorer`` on a host of 8 devices: the flow scorer
    is pinned to the first, and on weights drawn from the seed it runs in
    shadow: keyed rows are scored by both tiers, the flow tier's scores are
    counted and not published."""
    assert len(jax.devices()) > 1
    monkeypatch.setitem(SPECS, model.name, lambda: model.spec(model.cfg))

    async def go():
        tele = JaxAnomalyTelemeter(
            JaxAnomalyConfig(model=model.name, nativeTier="off",
                             trainEveryBatches=0), MetricsTree())
        tele.set_native_route_resolver(lambda rid: f"/svc/r{rid}")
        published = []
        real = tele._publish_route_means
        monkeypatch.setattr(
            tele, "_publish_route_means",
            lambda dsts, inv, scores: (published.append(len(scores)),
                                       real(dsts, inv, scores)))
        try:
            await drain_streams(tele, 0)
            flow = tele._flow_scorer
            state = flow.device_state()
            assert state["model"] == model.name
            assert state["weights"] == "seed"
            assert flow._devices == jax.devices()[:1]
            assert state["score_batches"] == {"8": 1}
            assert tele._flow_shadow.value == 8
            assert tele._flow_scored.value == 0
            # all 12 rows went the row scorer's way, and only they
            # were published
            assert tele._scored.value == 12 and published == [12]
            assert sum(tele._scorer.device_state()[
                "score_batches"].values()) == 1
        finally:
            tele.close()
    run(go())


def test_the_default_model_builds_no_flow_tier():
    tele = JaxAnomalyTelemeter(JaxAnomalyConfig(), MetricsTree())
    try:
        assert tele._flow_spec is None and tele._flow_scorer is None
    finally:
        tele.close()
    with pytest.raises(ValueError, match="model must be one of"):
        JaxAnomalyTelemeter(JaxAnomalyConfig(model="gru"), MetricsTree())


class TestTheSeam:
    def test_a_frozen_model_without_a_table_scores_and_does_not_fit(self):
        """``trains`` alone says whether a fit exists; a table, whether
        calls are mapped and ordered: mlp36 frozen is neither."""
        spec = dataclasses.replace(mlp36(), name="mlp36-frozen",
                                   trains=False)
        assert not spec.keyed and not latent_moe(CFG).trains

        async def go():
            s = InProcessScorer(spec=spec, devices=jax.devices()[:1])
            try:
                assert s._table is None and s._dispatcher._turn is None
                await s.warmup()
                x = np.ones((5, FEATURE_DIM), np.float32)
                out = await s.score(x)
                assert out.shape == (5,) and np.isfinite(out).all()
                with pytest.raises(RuntimeError, match="frozen"):
                    await s.fit(x, np.zeros(5), np.zeros(5))
                s.load(s.params)
                assert s.device_state()["weights"] == "loaded"
                np.testing.assert_array_equal(await s.score(x), out)
            finally:
                s.close()
        run(go())

    def test_both_models_steps_have_one_signature(self, seqs):
        rows = rows_of({k: v[:5] for k, v in seqs.items()})
        plan = FlowTable(CFG.slots, CFG.positions, CFG.vocab_slice).map(rows)
        for spec, staged, layout in (
                (mlp36(), np.ones((16, FEATURE_DIM), np.float32), None),
                (latent_moe(CFG), plan.rows, plan.layout),
                (lfm2_moe(CFG_LFM2), plan.rows, plan.layout)):
            params = spec.init(jax.random.key(SEED))
            state = jax.device_put(spec.init_state())
            scores, new, counts = spec.make_step("cpu")(
                params, state, jnp.asarray(staged), len(staged), layout)
            assert scores.shape == (len(staged),)
            # a step that only reads its state hands back what it got
            assert (new is state) == (not spec.keyed)
            assert isinstance(counts, dict)

    def test_a_trained_model_takes_its_weights_by_restore(self):
        s = InProcessScorer(devices=jax.devices()[:1])
        try:
            assert s.device_state()["weights"] == "seed"
            with pytest.raises(RuntimeError, match="restore"):
                s.load(s.params)
            s.restore(s.snapshot())
            assert s.device_state()["weights"] == "loaded"
        finally:
            s.close()

    def test_the_start_tokens_constants_come_from_the_steps_own_program(
            self, seqs, model):
        """The first call makes them (``with_start``) with the program it
        is about to run, and they ride in the state from then on: of every
        layer what the start token leaves of its kind of state."""
        cfg = model.cfg
        spec = model.spec(cfg)
        params = spec.init(jax.random.key(SEED))
        assert "start_h" not in params
        rows = rows_of({k: v[:5] for k, v in seqs.items()})
        plan = spec.make_table().map(rows)
        step = spec.make_step("cpu")
        state = spec.init_state()
        assert state[-1] is None
        _, state, _ = step(params, state, jnp.asarray(plan.rows), len(rows),
                           plan.layout)
        starts, h = state[-1]
        assert [s.shape for s in starts] == [
            s.shape for s in lm.start_shapes(cfg)[0]]
        assert h.shape == (cfg.hidden_size,)
        # what the reference computes of position 0 of any flow: a cache
        # entry; of a convolution layer the tail [0, u(start token)]
        _, full = reference_scores(seqs, model)
        for l, got in enumerate(np.asarray(s, np.float32) for s in starts):
            if model.name == "latent_moe":
                want = full["entries"][l, 0, 0]
            elif model.tiny["layer_types"][l] == "conv":
                assert got.shape == (2, cfg.hidden_size) and not got[0].any()
                got, want = got[1], full["kept"][l][0, 0]
            else:
                want = full["kept"][l][0, 0]
            gap = np.abs(got - want)
            assert np.median(gap) < 8e-3 and gap.max() < 0.2
        # and the lengths count the call's flows alone, not the making
        assert int(np.asarray(state[1]).sum()) == 3 * 6
