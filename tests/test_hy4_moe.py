"""The fifth flow model (``models/hy4_moe.py``) behind ``InProcessScorer``,
against ``chipbench/reference/hy4_moe.py`` on seeded weights at a tiny
preset on the CPU: hidden 64, 4 heads of latent attention (nope 8, rope 8,
values 8, a latent of 16), an indexer of 4 heads of 16 that selects the
top 16 positions, on layers 0 and 1 (``full``) and reused by layers 2-4
(``shared``), four streams, 16 experts top 2 beside a shared one, a SwiGLU
clamp of 1 (so that it bites at these weights; the published 10 does not),
a vocabulary of 128. Flows run to 70 positions, so most events attend over
a selection, through several calls of the cache."""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.reference import hy4_moe as ref
from linkerd_tpu.models import hy4_moe as hy
from linkerd_tpu.models import latent_moe as lm
from linkerd_tpu.models.spec import SPECS, hy4_moe
from linkerd_tpu.ops import expert_product as ep
from linkerd_tpu.ops import flow_attention as fa
from linkerd_tpu.telemetry.anomaly import InProcessScorer
from tests.test_latent_moe import SEED, rows_of, run

FULL, SHARED = "full", "shared"
TINY = {
    "hidden_size": 64, "num_attention_heads": 4, "q_lora_rank": 32,
    "kv_lora_rank": 16, "qk_nope_head_dim": 8, "qk_rope_head_dim": 8,
    "qk_head_dim": 16, "v_head_dim": 8, "index_n_heads": 4,
    "index_head_dim": 16, "index_topk": 16,
    "indexer_types": [FULL, FULL, SHARED, SHARED, SHARED],
    "layer_types": ["deepseek_sparse_attention"] * 5,
    "mlp_layer_types": ["dense", "sparse", "sparse", "sparse", "sparse"],
    "num_hidden_layers": 5, "intermediate_size": 128,
    "moe_intermediate_size": 32, "n_routed_experts": 16,
    "n_shared_experts": 1, "num_experts_per_tok": 2,
    "routed_scaling_factor": 2.827, "rms_norm_eps": 1e-5,
    "rope_parameters": {"rope_theta": 10000000, "rope_type": "default"},
    "use_mla": True, "use_dsa": True, "gated_mla": True,
    "gating_type": "elementwise", "learnable_sink": True, "n_group": 1,
    "norm_topk_prob": True, "num_nextn_predict_layers": 0,
    "enable_ihc": True, "hc_mult": 4, "hc_magnitude": 2, "hc_eps": 1e-6,
    "swiglu_limit": 1.0, "enable_lm_head_fp32": True, "vocab_size": 128,
    "model": {"in_dim": 3, "router_experts": 16, "experts_held": [0, 16],
              "layer_share": 1, "slots": 8, "positions": 128,
              "expert_tile": 8, "compute_dtype": "bfloat16"}}
CFG = hy.Hy4MoEConfig.from_config(TINY)
# a score is off by the compute type's rounding (median 4-7e-5 here); a
# token whose second and third router scores, or whose 16th and 17th
# index scores, lie within rounding is further off
TYPICAL, WORST = 2.5e-4, 4e-2


def scorer(cfg=CFG):
    return InProcessScorer(seed=SEED, spec=hy4_moe(cfg),
                           devices=jax.devices()[:1])


@pytest.fixture(scope="module")
def seqs():
    """Flows of 70, 45 and 12 events: the first two pass the top 16."""
    rng = np.random.default_rng(0)
    return {11: rng.integers(1, 128, 70), 22: rng.integers(1, 128, 45),
            33: rng.integers(1, 128, 12)}


@pytest.fixture(scope="module")
def whole(seqs):
    """The reference's one full forward of every flow."""
    tokens = np.zeros((len(seqs), 128), np.int32)
    for b, ids in enumerate(seqs.values()):
        tokens[b, 1:1 + len(ids)] = ids
    return ref.forward(SEED, TINY, tokens)


def in_chunks(s, seqs: dict, chunk: int) -> dict:
    async def go():
        got, at = {k: [] for k in seqs}, 0
        while at < max(len(v) for v in seqs.values()):
            rows = rows_of({k: v[at:at + chunk] for k, v in seqs.items()
                            if at < len(v)})
            out = await s.score(rows)
            for k in seqs:
                got[k].extend(out[rows[:, 0] == k])
            at += chunk
        return got
    return run(go())


def gaps_of(got: dict, seqs: dict, whole) -> np.ndarray:
    return np.concatenate([
        np.abs(np.asarray(got[k]) - whole["score"][b, 1:1 + len(ids)])
        for b, (k, ids) in enumerate(seqs.items())])


def test_calls_through_the_cache_agree_with_one_full_forward(seqs, whole):
    """The same three flows in calls of 16 events a flow, five through the
    cache: every call's scores are the reference's for the flow forward
    once (no cache, up-projected, the selection made afresh over the whole
    sequence), and at the end every layer's latent cache, and every
    ``full`` layer's index keys, hold each position's entry."""
    s = scorer()
    try:
        got, state, table = in_chunks(s, seqs, 16), s._state, s._table
    finally:
        s.close()
    gap = gaps_of(got, seqs, whole)
    assert np.median(gap) < TYPICAL and gap.max() < WORST, (
        np.median(gap), gap.max())
    for l, kind in enumerate(TINY["indexer_types"]):
        kept = state[0][l]
        latent = kept[0] if kind == FULL else kept
        assert isinstance(kept, tuple) == (kind == FULL)
        for b, (key, ids) in enumerate(seqs.items()):
            slot, n = table.slot_of[key], 1 + len(ids)
            pairs = [(np.asarray(latent[slot, :n], np.float32),
                      whole["kept"][l][b, :n])]
            if kind == FULL:
                pairs.append((np.asarray(kept[1][slot, :, :n], np.float32).T,
                              whole["keys"][l][b, :n]))
            for got_rows, want_rows in pairs:
                rel = (np.linalg.norm(got_rows - want_rows)
                       / np.linalg.norm(want_rows))
                # a position whose token took another expert in bfloat16
                # is off as a whole in the layers after
                assert rel < 0.05, (l, key, rel)
    assert np.asarray(state[1])[[table.slot_of[k] for k in seqs]].tolist() \
        == [71, 46, 13]


def scores_of(s, seqs: dict) -> dict:
    try:
        return in_chunks(s, seqs, 64)
    finally:
        s.close()


def without(part: str):
    """A scorer of the tiny model with ``part`` of the mathematics left
    out."""
    if part == "sink" or part == "gate":
        s = scorer()
        for lp in s.params["layers"]:
            del lp["sink" if part == "sink" else "wg"]
        return s
    return scorer(dataclasses.replace(CFG, **{
        "sinkhorn": {"hc_sinkhorn_iterations": 0},
        "clamp": {"swiglu_limit": None},
        "selection": {"index_topk": 10 ** 6}}[part]))


@pytest.mark.parametrize("part", ["sink", "gate", "sinkhorn", "clamp",
                                  "selection"])
def test_leaving_out_any_part_fails_the_tolerance(seqs, whole, part):
    """The sink, the gate, Sinkhorn's normalisation, the SwiGLU's clamp or
    the selection (every position attended) left out of the program: its
    scores fall outside the tolerance the whole program meets, by the
    median gap."""
    gap = gaps_of(scores_of(without(part), seqs), seqs, whole)
    assert np.median(gap) > 2 * TYPICAL, np.median(gap)


def test_the_selection_is_the_top_k_of_the_scores_ties_to_the_earlier():
    """``select_top``, by construction: over scores with many ties (small
    integers), signed zeros and ``-inf`` past each event's position, the
    positions selected are, for each event, the ``k`` best by score, the
    earlier of two equal ones first: what a stable sort gives."""
    rng = np.random.default_rng(5)
    F, T, P = 3, 7, 50
    scores = rng.integers(-3, 4, (F, T, P)).astype(np.float32)
    scores[0, 0] = rng.normal(size=P)
    scores[1, 2, ::3] = -0.0
    pos = rng.integers(0, P, (F, T))
    scores[np.arange(P)[None, None] > pos[..., None]] = -np.inf
    k = np.minimum(rng.integers(1, 20, (F, T)), pos + 1)
    got = np.asarray(hy.selected(jax.jit(hy.select_top)(
        jnp.asarray(scores), jnp.asarray(k, jnp.int32))))
    for f in range(F):
        for t in range(T):
            order = np.argsort(-scores[f, t], kind="stable")
            want = np.zeros(P, bool)
            want[order[:k[f, t]]] = True
            assert (got[f, t] == want).all(), (f, t)


def test_the_program_attends_over_its_own_indexers_selection(seqs):
    """On a call of the program's own weights, ``full`` layer by ``full``
    layer, the selection handed to the attention holds exactly ``min(16,
    t + 1)`` positions of each event, the best by the program's own index
    scores; layers 2-4 (``shared``) are handed layer 1's selection, the
    same arrays, and layer 1's is not layer 0's."""
    params = lm.init(jax.random.key(SEED), CFG)
    seen = []

    def spy(q_abs, q_rope, cache, slot, p0, scale, selection, sink=None):
        seen.append(selection)
        return hy.attend_selected_xla(q_abs, q_rope, cache, slot, p0, scale,
                                      selection, sink)

    rows = rows_of({11: seqs[11][:48], 22: seqs[22][:40]}, restart={11, 22})
    plan = SPECS["hy4_moe"](CFG).make_table().map(rows)
    assert plan.layout == (2, 64)
    step = functools.partial(lm.flow_step, params, cfg=CFG, F=2, T=64,
                             attend=spy)

    @jax.jit
    def traced(state, rows, n):
        seen.clear()
        out = step(state, rows, n)
        # which selections are the same arrays, told while they are traced
        same = [s is seen[1] for s in seen]
        return out, (tuple(seen), same)

    state = lm.with_start(lambda st, r, n: traced(st, r, n)[0], CFG,
                          lm.init_state(CFG), jnp.asarray(plan.rows))
    _, (seen, same) = traced(state, jnp.asarray(plan.rows),
                             plan.rows.shape[0])
    assert len(seen) == 5
    assert [bool(b) for b in same] == [False, True, True, True, True]
    assert not (np.asarray(seen[0].threshold)
                == np.asarray(seen[1].threshold)).all()
    pos = 1 + np.arange(64)     # the start token is at position 0
    for sel in seen[:2]:
        chosen = np.asarray(hy.selected(sel))
        scores = np.asarray(sel.scores)
        for f, n in ((0, 48), (1, 40)):
            assert (chosen[f, :n].sum(-1) == np.minimum(16, pos[:n] + 1)).all()
            for t in range(n):
                order = np.argsort(-scores[f, t], kind="stable")
                assert set(np.flatnonzero(chosen[f, t])) == set(
                    order[:min(16, pos[t] + 1)])


def test_sinkhorn_gives_doubly_stochastic_mixing():
    """A hyper-connection's coefficients on the seeded weights: ``H_res``'s
    columns sum to 1 to ``hc_eps`` (the last division is by the columns'
    sums plus it) and its rows to what 20 iterations converge to from
    ``exp`` of these scales (1e-4), ``H_pre`` lies in (0, 1) and
    ``H_post`` in (0, ``hc_magnitude``)."""
    params = lm.init(jax.random.key(SEED), CFG)
    X = tuple(jax.random.normal(jax.random.key(2), (4, 3, 5, 64)) * 3)
    for lp in params["layers"][:2]:
        for name in ("hc_attn", "hc_ffn"):
            pre, post, res = (np.asarray(a) for a in lm.hyper_coefficients(
                lp, name, CFG, X))
            assert res.shape == (3, 5, 4, 4) and (res > 0).all()
            np.testing.assert_allclose(res.sum(-2), 1.0,
                                       atol=2 * CFG.hc_eps)
            np.testing.assert_allclose(res.sum(-1), 1.0, atol=2e-4)
            assert ((pre > 0) & (pre < 1)).all()
            assert ((post > 0) & (post < 2)).all()
    raw = np.asarray(lm.sinkhorn(jnp.exp(X[0][..., :4, None]
                                         + X[1][..., None, :4]),
                                 0, 1e-6))
    assert np.abs(raw.sum(-1) - 1).max() > 0.5     # none: not stochastic


def test_the_shares_add_up_to_the_uncut_layer():
    """Guide section 4: the routed parts that all 16 shares give (one
    expert each, as 16 chips each hold 16 of 256), with the shared expert
    counted once, add up to what the uncut reference gives for the whole
    layer; program and reference alike, each SwiGLU clamped."""
    layer, E = 1, 16
    x = jax.random.normal(jax.random.key(3), (24, 64))
    with jax.default_matmul_precision("highest"):
        whole = ref.layer_weights(SEED, TINY, layer, held=(0, E))
        idx, wts, _ = ref.route(whole, TINY, ref._q(x, "bf16"))
        alike = ref.swiglu(x, whole["shared_gate"], whole["shared_up"],
                           whole["shared_down"], 1.0, None)
        uncut = alike + ref.routed_part(whole, TINY, x, idx, wts, 0)
        ref_sum = got_sum = alike
        tokens = 0
        for lo in range(E):
            part = ref.layer_weights(SEED, TINY, layer, held=(lo, lo + 1))
            ref_sum = ref_sum + ref.routed_part(part, TINY, x, idx, wts, lo)
            cfg = dataclasses.replace(CFG, experts_held=(lo, lo + 1),
                                      layer_share=16)
            lp = lm.init(jax.random.key(SEED), cfg)["layers"][layer]
            out, cnt, _ = lm.routed_experts(lp, cfg, x, jnp.ones(24, bool))
            got_sum = got_sum + out
            tokens += int(cnt.sum())
    assert tokens == 24 * CFG.num_experts_per_tok
    np.testing.assert_allclose(ref_sum, uncut, atol=1e-5)
    gap = np.abs(np.asarray(got_sum) - np.asarray(uncut))
    scale = np.abs(np.asarray(uncut)).mean()
    assert np.median(gap) < 0.01 * scale and gap.max() < 0.2 * scale


@pytest.mark.parametrize("sink", [True, False])
def test_the_selection_kernel_agrees_with_xla(sink):
    """``sparse_latent_attention_fused``, interpreted, against
    ``attend_selected_xla``: 3 flows of 32 events in tiles of 16, slots of
    384 positions (3 blocks), one flow out of range, selections of 1 to
    40 positions with ties, with and without a sink; and the blocks
    counted are those up to each tile's last event."""
    F, T, H, rank, rope, S, P = 3, 32, 4, 128, 64, 5, 384
    k = jax.random.split(jax.random.key(9), 6)
    q_abs = jax.random.normal(k[0], (F, T, H, rank), jnp.bfloat16)
    q_rope = jax.random.normal(k[1], (F, T, H, rope), jnp.bfloat16)
    cache = jax.random.normal(k[2], (S, P, rank + rope), jnp.bfloat16)
    slot = jnp.array([3, 0, S], jnp.int32)
    p0 = jnp.array([300, 1, 1], jnp.int32)
    pos = p0[:, None] + jnp.arange(T)[None]
    scores = jnp.round(jax.random.normal(k[3], (F, T, P)) * 2)
    scores = jnp.where(jnp.arange(P)[None, None] <= pos[..., None], scores,
                       -jnp.inf)
    top = jnp.minimum(jax.random.randint(k[4], (F, T), 1, 40), pos + 1)
    sel = hy.select_top(scores, top)
    logit = (jax.random.normal(k[5], (H,)) * 2 + 3) if sink else None
    want, ones, one = hy.attend_selected_xla(q_abs, q_rope, cache, slot, p0,
                                             0.1, sel, logit)
    got, blocks, whole = fa.sparse_latent_attention_fused(
        q_abs, q_rope, cache, slot, p0, 0.1, sel, logit, interpret=True)
    assert got.shape == (F, T, H, rank) and got.dtype == jnp.bfloat16
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    np.testing.assert_allclose(got[:2], want[:2], rtol=2e-2, atol=2e-2)
    assert np.abs(got[:2] - want[:2]).mean() < 2e-3
    # tiles of 16 events: flow 0 from 300 sees 3 blocks in both tiles
    assert np.asarray(blocks).tolist() == [6, 2, 2] and whole == 2 * 3


def test_the_expert_kernel_clamps_as_xla_does():
    """``swiglu_tiles_fused(limit=)``, interpreted, against
    ``swiglu_tiles_xla(limit=)`` on rows large enough that the clamp
    bites: equal to bfloat16 rounding, and not equal without it."""
    D, I, G, M = 128, 256, 3, 8
    k = jax.random.split(jax.random.key(4), 4)
    xs = (jax.random.normal(k[0], (4 * M, D)) * 4).astype(jnp.bfloat16)
    gate, up = (jax.random.normal(kk, (G, D, I), jnp.bfloat16) * 0.3
                for kk in k[1:3])
    down = jax.random.normal(k[3], (G, I, D), jnp.bfloat16) * 0.1
    wt = jnp.ones((4 * M,), jnp.float32)
    te = jnp.array([0, 0, 2, 1], jnp.int32)
    want, _ = lm.swiglu_tiles_xla(xs, wt, te, 4, gate, up, down, limit=1.0)
    got, _ = ep.swiglu_tiles_fused(xs, wt, te, jnp.int32(4), gate, up, down,
                                   interpret=True, limit=1.0)
    free, _ = lm.swiglu_tiles_xla(xs, wt, te, 4, gate, up, down)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-2,
                               atol=2e-2)
    assert np.abs(np.asarray(free) - np.asarray(want)).mean() > 0.1


def test_the_configuration_is_the_published_one_at_full_size():
    """At the published sizes (nothing is drawn): the defaults are what
    the benchmark's file gives, the latent entry is 576 wide and a ``full``
    layer keeps a second array of 128 a position, and the parameters with
    the gate count 769.96 G for the published 78 layers of 256 experts."""
    import json
    import os
    here = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "chipbench", "configs",
        "hy4-preview-ep16.json")
    with open(here) as f:
        file = json.load(f)
    cfg = hy.Hy4MoEConfig.from_config(file)
    assert cfg == hy.Hy4MoEConfig()
    shapes = [jax.tree_util.tree_map(lambda a: a.shape, s) for s in
              jax.eval_shape(lambda: tuple(cfg.operator(l).init(cfg)
                                           for l in range(5)))]
    assert shapes[:2] == [((128, 6144, 576), (128, 128, 6144))] * 2
    assert shapes[2:] == [(128, 6144, 576)] * 3
    t = cfg.tensors()
    assert t["layers.0.wg"][0] == (6144, 64 * 256)
    assert t["layers.1.wiq"][0] == (2048, 32 * 128)
    assert "layers.2.wiq" not in t and "layers.2.wik" not in t
    assert t["layers.3.hc_ffn_phi"][0] == (4 * 6144, 24)
    per_layer = {name.split(".", 2)[2]: int(np.prod(shape))
                 for name, (shape, *_) in t.items()
                 if name.startswith("layers.1.")}
    expert = 3 * 6144 * 2048
    moe = (sum(per_layer.values()) - per_layer["exp_gate"]
           - per_layer["exp_up"] - per_layer["exp_down"]
           - sum(v for k, v in per_layer.items() if k.startswith("wi")
                 or k.startswith("ik_")) + 256 * expert)
    index = sum(v for k, v in per_layer.items()
                if k.startswith("wi") or k.startswith("ik_"))
    dense = sum(int(np.prod(shape)) for name, (shape, *_) in t.items()
                if name.startswith("layers.0.")) - index
    top = 2 * 6144 * 120832 + 6144
    full_layers = file["published"]["indexer_types"].count(FULL)
    total = dense + 77 * moe + full_layers * index + top
    assert abs(total - 769.96e9) < 0.01e9, total
