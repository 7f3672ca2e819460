"""The third flow model (``models/laguna_moe.py``) behind
``InProcessScorer``, against ``chipbench/reference/laguna_moe.py`` on
seeded weights at a tiny preset on the CPU: hidden 64, a head of 16, 6
query heads on the full layers and 8 on the sliding ones over 2 key/value
heads, a window of 8 whose ring (chunks of 16 at most, blocks of 8) is 24
positions beside caches of 256, 8 experts top 2 beside a shared one, a
vocabulary of 128. What both kinds of layer share with ``lfm2_moe``'s,
the one grouped-query operator (``models/grouped_attention.py``), and
what only this model has: a ring that wraps, two head counts, two RoPEs,
a gate a head, a router with no bias."""

import dataclasses
import functools
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.counts import laguna_moe as counts
from chipbench.reference import laguna_moe as ref
from linkerd_tpu.models import grouped_attention as ga
from linkerd_tpu.models import laguna_moe as lg
from linkerd_tpu.models import latent_moe as lm
from linkerd_tpu.models import lfm2_moe as lf
from linkerd_tpu.models.spec import SPECS, laguna_moe
from linkerd_tpu.ops import cache_append as ca
from linkerd_tpu.ops import flow_attention as fa
from linkerd_tpu.telemetry import phases
from linkerd_tpu.telemetry.anomaly import (
    InProcessScorer, JaxAnomalyConfig, JaxAnomalyTelemeter,
)
from linkerd_tpu.telemetry.metrics import MetricsTree
from tests.test_flow_attention import (
    operator_and_parent, queries_of, unturned,
)
from tests.test_latent_moe import SEED, product_of, rows_of, run

FULL, SLIDING = "full_attention", "sliding_attention"
TINY = {
    "hidden_size": 64, "head_dim": 16, "num_key_value_heads": 2,
    "num_attention_heads_per_layer": [6, 8, 8, 6],
    "layer_types": [FULL, SLIDING, SLIDING, FULL],
    "mlp_layer_types": ["dense", "sparse", "sparse", "sparse"],
    "num_hidden_layers": 4, "intermediate_size": 128,
    "moe_intermediate_size": 32, "shared_expert_intermediate_size": 32,
    "num_experts": 8, "num_experts_per_tok": 2,
    "moe_routed_scaling_factor": 2.5,
    "moe_apply_router_weight_on_input": False, "rms_norm_eps": 1e-6,
    "sliding_window": 8, "vocab_size": 128,
    "rope_parameters": {
        FULL: {"rope_theta": 500000, "rope_type": "yarn", "factor": 64,
               "original_max_position_embeddings": 64, "beta_slow": 1,
               "beta_fast": 64, "attention_factor": 1.4158883083359672,
               "partial_rotary_factor": 0.5},
        SLIDING: {"rope_type": "default", "rope_theta": 10000,
                  "partial_rotary_factor": 1}},
    "model": {"in_dim": 3, "experts_held": [0, 8], "layer_share": 1,
              "slots": 8, "positions": 256, "expert_tile": 8,
              "compute_dtype": "bfloat16", "chunk_max": 16,
              "ring_block": 8}}
CFG = lg.LagunaMoEConfig.from_config(TINY)
RING, WINDOW = 24, 8
RINGS = [l for l, kind in enumerate(TINY["layer_types"]) if kind == SLIDING]
CACHES = [l for l, kind in enumerate(TINY["layer_types"]) if kind == FULL]
# a score is off by the compute type's rounding; a token whose second and
# third router scores lie within rounding takes another expert in bfloat16
# than in float32 and is further off
TYPICAL, WORST = 6e-4, 4e-2


def scorer(cfg=CFG):
    return InProcessScorer(seed=SEED, spec=laguna_moe(cfg),
                           devices=jax.devices()[:1])


def reference_of(seqs: dict, L: int = 256) -> tuple:
    tokens = np.zeros((len(seqs), L), np.int32)
    for b, ids in enumerate(seqs.values()):
        tokens[b, 1:1 + len(ids)] = ids
    got = ref.forward(SEED, TINY, tokens)
    return ({k: got["score"][b, 1:1 + len(v)]
             for b, (k, v) in enumerate(seqs.items())}, got)


def close_to(got, want):
    gap = np.abs(np.asarray(got) - np.asarray(want))
    assert np.median(gap) < TYPICAL and gap.max() < WORST, (
        np.median(gap), gap.max())


def kept_gaps(state, full, slot: int, b: int, n: int) -> list:
    """Per layer, what slot ``slot`` keeps of a flow of ``n`` positions
    less the reference's keys and values of sequence ``b``: a cache's rows
    ``0 .. n - 1``; of a ring the last ``WINDOW`` positions, each read
    back from ``position mod RING``."""
    gaps = []
    for l, kind in enumerate(TINY["layer_types"]):
        got = np.asarray(state[0][l][slot], np.float32).T    # [positions, e]
        want = full["kept"][l][b]
        if kind == FULL:
            gaps.append(got[:n] - want[:n])
        else:
            last = np.arange(max(0, n - WINDOW), n)
            gaps.append(got[last % RING] - want[last])
    return gaps


@pytest.fixture(scope="module")
def seqs():
    """Two flows over four times the ring long, one shorter than it."""
    rng = np.random.default_rng(0)
    return {11: rng.integers(1, 128, 100), 22: rng.integers(1, 128, 97),
            33: rng.integers(1, 128, 19)}


@pytest.fixture(scope="module")
def whole(seqs):
    """The reference's one full forward of every flow."""
    return reference_of(seqs)


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


@pytest.mark.parametrize("chunk", [1, 3, 5, 16])
def test_calls_of_any_length_agree_with_one_full_forward(seqs, whole, chunk):
    """The same three sequences in calls of ``chunk`` events a flow: the
    ring of 24 is passed four times, by chunks that end at its end (1, 3)
    and that straddle it (5, 16). Every call's scores are the reference's
    for the flow forward once, under a mask over the whole sequence, and
    at the end a cache holds every position's keys and values and a ring
    those of the last 8 positions, where ``position mod 24`` puts them."""
    s = scorer()
    try:
        got, state = in_chunks(s, seqs, chunk), s._state
    finally:
        s.close()
    want, full = whole
    for b, (key, ids) in enumerate(seqs.items()):
        close_to(got[key], want[key])
        # (a position whose token took another expert in bfloat16 than in
        # float32 in the layer before is off as a whole: a few are)
        for gap in kept_gaps(state, full, b, b, 1 + len(ids)):
            gap = np.abs(gap)
            assert np.median(gap) < 8e-3 and np.mean(
                gap.max(-1) > 0.25) < 0.05
    assert np.asarray(state[1])[:3].tolist() == [101, 98, 20]
    shapes = sorted({tuple(a.shape) for a in state[0]})
    assert shapes == [(8, 64, 24), (8, 64, 256)]


def test_a_restart_and_a_reused_slot_leave_nothing_of_the_old_flow(seqs):
    """A flow of 48 events (the ring passed twice) restarts under its key
    with 3 events, and a new key takes the slot of a flow that was evicted:
    neither ring is cleared, and both flows' scores and kept state are the
    reference's for the new sequences alone."""
    again = np.array([5, 9, 77], np.int32)
    other = np.random.default_rng(3).integers(1, 128, 12)
    small = dataclasses.replace(CFG, slots=1)

    async def go():
        s, t = scorer(), scorer(small)
        try:
            for at in (0, 16, 32):
                await s.score(rows_of({22: seqs[22][at:at + 16]}))
            out = await s.score(rows_of({22: again}, restart={22}))
            # one slot: key 44 evicts key 11 and begins where it lay
            await t.score(rows_of({11: seqs[11][:16]}))
            await t.score(rows_of({11: seqs[11][16:32]}))
            new = await t.score(rows_of({44: other}))
            return out, s._state, new, t._state
        finally:
            s.close()
            t.close()
    out, state, new, reused = run(go())
    want, full = reference_of({22: again, 44: other})
    close_to(out, want[22])
    close_to(new, want[44])
    assert int(np.asarray(state[1])[0]) == 4
    assert int(np.asarray(reused[1])[0]) == 13
    for st, b, n in ((state, 0, 4), (reused, 1, 13)):
        for gap in kept_gaps(st, full, 0, b, n):
            assert np.abs(gap).max() < 0.1


# (ring, T): p0 and count per flow; the second flow of each begins
WRAPS = {"ends-at-the-end": (24, 8, [17, 1], [7, 8]),
         "straddles": (24, 8, [20, 1], [8, 3]),
         "twice-round": (24, 16, [24 * 2 + 15, 1], [16, 16]),
         "one-event": (24, 1, [47, 1], [1, 1]),
         "from-index-0": (16, 4, [32, 1], [4, 2]),
         # rings of whole tiles of 128, where the kernel appends: the
         # published ring's last tile to its first, a window inside a tile
         # and one across two several times round, one event at the end
         "wraps-last-tile-to-first": (640, 64, [639, 1], [64, 64]),
         "inside-a-tile": (640, 64, [640 * 3 + 130, 1], [64, 0]),
         "across-two-tiles": (256, 64, [256 * 2 + 100, 1], [64, 30]),
         "one-event-wraps": (256, 1, [256 * 3, 1], [1, 1])}


@pytest.mark.parametrize("append", ["xla", "kernel"])
@pytest.mark.parametrize("case", sorted(WRAPS))
def test_a_chunk_that_passes_the_rings_end_goes_on_at_its_start(case,
                                                                 append):
    """``append_chunk(ring=True)`` and the TPU's kernel
    (``cache_append_fused(ring=True)``, interpreted) against the ring
    written position by position by hand: ``entry[f, t]`` at ``(p0 + t)
    mod ring`` for ``t < count``, the start token's at index 0 where the
    flow begins, a flow that brings nothing (slot out of range) writes
    nothing, and every other value of the layer is bit for bit what it
    was. XLA's takes a second window where a chunk wraps; the kernel
    takes a ring's tiles modulo the ring, the wrap in the same pass, and
    counts whole tiles (on a ring of whole tiles: elsewhere it hands the
    call to XLA's)."""
    P, T, p0, count = WRAPS[case]
    S, E, F = 5, 16, 3
    k = jax.random.split(jax.random.key(P + T), 3)
    cache = jax.random.normal(k[0], (S, E, P), jnp.bfloat16)
    entry = jax.random.normal(k[1], (F, T, E), jnp.bfloat16)
    start = jax.random.normal(k[2], (E,), jnp.bfloat16)
    slot = np.array([3, 1, S], np.int32)
    p0 = np.array(p0 + [1], np.int32)
    count = np.array(count + [0], np.int32)
    begins = p0 == 1
    fn = (functools.partial(ca.cache_append_fused, interpret=True)
          if append == "kernel" else lm.append_chunk)
    got, written, in_kernel = jax.jit(functools.partial(
        fn, positions_last=True, ring=True))(
            cache, entry, start, slot, p0, count, begins)
    want = np.asarray(cache, np.float32)
    for f in range(2):
        if begins[f]:
            want[slot[f], :, 0] = np.asarray(start, np.float32)
        for t in range(count[f]):
            want[slot[f], :, (p0[f] + t) % P] = np.asarray(
                entry[f, t], np.float32)
    np.testing.assert_array_equal(np.asarray(got).view(np.uint16),
                                  want.astype(jnp.bfloat16).view(np.uint16))
    W = T + 1
    if append == "kernel" and P % 128 == 0:
        assert ca.serves(cache.shape, T, True, True)
        tiles = sum(-(-((int(p) - 1) % P % 128 + W) // 128) for p in p0[:2])
        assert (int(written), int(in_kernel)) == (tiles * 128, 2)
        return
    wraps = sum((int(p) - 1) % P + W > P for p in p0[:2])
    assert (int(written), int(in_kernel)) == ((2 + wraps) * W, 0)


def test_full_and_sliding_layers_take_their_own_heads_rotary_part_and_rope(
        monkeypatch):
    """At the published sizes (nothing is drawn): a full layer has 48
    query heads, turns 64 of a head's 128 values by YaRN's frequencies
    with cos and sin times 1.4159, sees every position and keeps 4,224 (33
    blocks: the traffic's longest flow, 4,033 positions, and a chunk); a
    sliding layer has 64, turns all 128 by the default kind at theta
    10,000, sees 512 and keeps a ring of 640. Both are the one operator;
    the reference computes the same frequencies from the file's keys."""
    cfg = lg.LagunaMoEConfig()
    published = counts._cfg({"config": "laguna-xs.2"})
    assert lg.LagunaMoEConfig.from_config(published) == cfg
    assert cfg.layers == 5 and cfg.ring == 640
    ops = [cfg.operator(l) for l in range(5)]
    assert [op.scope for op in ops] == [
        "full_attention", "window_attention", "window_attention",
        "window_attention", "full_attention"]
    assert [op.ring for op in ops] == [0, 640, 640, 640, 0]
    shapes = [s.shape for s in jax.eval_shape(
        lambda: tuple(op.init(cfg) for op in ops))]
    assert shapes == [(128, 2048, 4224)] + [(128, 2048, 640)] * 3 + [
        (128, 2048, 4224)]
    t = cfg.tensors()
    assert t["layers.0.wq"][0] == (2048, 6144) == t["layers.4.wq"][0]
    assert t["layers.1.wq"][0] == (2048, 8192) and t["layers.1.wo"][0] == (
        8192, 2048)
    assert t["layers.0.wg"][0] == (2048, 48) and t["layers.2.wg"][0] == (
        2048, 64)
    assert t["layers.3.wk"][0] == (2048, 1024) == t["layers.0.wv"][0]
    assert "layers.1.router_bias" not in t and "layers.1.q_norm" not in t
    assert t["head"][0] == (2048, 100352) and t["embed"][0] == (100352, 2048)
    rope = published["rope_parameters"]
    full = ref.inv_freq(rope[FULL], 64)
    slide = ref.inv_freq(rope[SLIDING], 128)
    assert full.shape == (32,) and slide.shape == (64,)
    np.testing.assert_allclose(slide, 10000.0 ** (-np.arange(64) / 64),
                               rtol=1e-6)
    # YaRN: the fastest pairs as they are, the slowest divided by 64
    np.testing.assert_allclose(full[0], 1.0, rtol=1e-6)
    np.testing.assert_allclose(full[-1], 500000.0 ** (-31 / 32) / 64,
                               rtol=1e-5)
    seen = {}

    def spy(layer):
        seen[layer.kind] = layer
        return ga.grouped_attention(layer)

    monkeypatch.setattr(lg, "grouped_attention", spy)
    cfg.operator(0), cfg.operator(1)
    assert (seen["full"].heads, seen["window"].heads) == (48, 64)
    assert seen["full"].window is None and seen["window"].window == 512
    assert seen["full"].rope_scale == pytest.approx(1.4158883083359672)
    assert seen["window"].rope_scale == 1.0
    np.testing.assert_array_equal(seen["full"].inv_freq, full)
    np.testing.assert_array_equal(seen["window"].inv_freq, slide)
    # the second model's layers are instances of the same operator
    lfm2 = lf.Lfm2MoEConfig().operator(1)
    assert lfm2.apply.__code__ is ops[0].apply.__code__


@pytest.mark.parametrize("l", [0, 1], ids=["full", "sliding"])
def test_on_xla_the_operator_is_the_parents_bit_for_bit(l, monkeypatch):
    """On XLA's attention (every platform but the TPU) the operator that
    hands ``attend`` the queries as projected computes **what it computed
    when it turned, cast and gated them itself** (``parents_apply``: turn
    -> cast -> attend -> gate in float32), on the seeded weights: a full
    layer (6 heads, half a head turned, cos and sin scaled) and a sliding
    one (8 heads, a ring passed five times): the output and the appended
    state are equal, not close."""
    seen = {}

    def spy(layer):
        seen[layer.kind] = layer
        return ga.grouped_attention(layer)

    monkeypatch.setattr(lg, "grouped_attention", spy)
    CFG.operator(l)
    s = scorer()
    try:
        (y, state, counts), (y_then, state_then) = operator_and_parent(
            CFG, s.params, l, seen["full" if l in CACHES else "window"])
    finally:
        s.close()
    assert "wg" in s.params["layers"][l] and y.dtype == jnp.float32
    assert np.isfinite(np.asarray(y)).all() and np.asarray(y).std() > 0.01
    assert (np.asarray(y) == np.asarray(y_then)).all()
    assert (np.asarray(state, np.float32)
            == np.asarray(state_then, np.float32)).all()
    # one call's query rows by hand: 4 flows x 8 events x the layer's
    # heads, none of them taken on a kernel's tile here
    assert int(counts["attn.q_rows"]) == 4 * 8 * (6 if l in CACHES else 8)
    assert int(counts["attn.q_rows_in_tile"]) == 0


def test_the_rotary_part_turns_and_the_rest_passes():
    x = jax.random.normal(jax.random.key(1), (2, 3, 4, 16))
    pos = jnp.array([[0, 1, 2], [7, 8, 9]])
    cos, sin = lm.angles(pos, np.array([1.0, 0.1, 0.01, 0.001], np.float32))
    out = np.asarray(ga.rotate(x, cos[:, :, None], sin[:, :, None], 8))
    np.testing.assert_array_equal(out[..., 8:], np.asarray(x)[..., 8:])
    np.testing.assert_allclose(out[0, 0, :, :8], np.asarray(x)[0, 0, :, :8],
                               rtol=1e-6)      # position 0 turns nothing
    a, b = np.asarray(x)[1, 2, :, :4], np.asarray(x)[1, 2, :, 4:8]
    c, s = np.asarray(cos)[1, 2], np.asarray(sin)[1, 2]
    np.testing.assert_allclose(out[1, 2, :, :8], np.concatenate(
        [a * c - b * s, b * c + a * s], -1), rtol=1e-5, atol=1e-6)


def test_the_gate_and_the_shared_expert_are_each_counted_once():
    """The parameters at the published sizes, by the benchmark's counts
    and by the program's tensors: attention 29.46 M on a full layer and
    37.88 M on a sliding one **with its gate** (hidden x heads, once), an
    expert layer 809.0 M **with its one shared expert**, 3.870 G held;
    and an event's FLOPs count the gate and the shared expert once."""
    model = counts._cfg({"config": "laguna-xs.2"})["model"]
    c = counts._cfg(model)
    assert counts.attention_weights(c, 0) == 2048 * (
        6144 * 2 + 2 * 1024 + 48) == 29_458_432
    assert counts.attention_weights(c, 1) == 2048 * (
        8192 * 2 + 2 * 1024 + 64) == 37_879_808
    assert counts.expert_weights(c) == 3 * 2048 * 512
    matmul = sum(int(np.prod(shape)) * (256 if per_expert else 1)
                 for name, (shape, _, _, per_expert)
                 in lg.LagunaMoEConfig().tensors().items()
                 if len(shape) == 2)
    assert counts.weights_held(model) == matmul
    assert 3.869e9 < matmul < 3.871e9
    one_layer = (2048 * 256 + 257 * counts.expert_weights(c))
    assert abs(one_layer - 809.0e6) < 0.1e6
    # an event: the held weights less the 248 experts it does not select
    # and the embedding's rows, plus attention over the counted contexts
    weights = matmul - 4 * 248 * counts.expert_weights(c) - 2048 * 100352
    attended = sum(2 * h * 128 * model[key] for h, key in (
        (48, "counted_context_full"), (64, "counted_context_window"),
        (64, "counted_context_window"), (64, "counted_context_window"),
        (48, "counted_context_full")))
    assert counts.score_flops_per_row(model) == 2.0 * (weights + attended)


def test_the_router_selects_by_the_scores_themselves():
    """No selection bias among the tensors: the top 2 of the sigmoid
    scores, weighed by those scores over their sum times 2.5; the
    reference routes alike."""
    lp = lm.init(jax.random.key(SEED), CFG)["layers"][1]
    assert "router_bias" not in lp and "shared_gate" in lp
    x = jax.random.normal(jax.random.key(9), (40, CFG.hidden_size))
    xr = x.astype(jnp.bfloat16).astype(jnp.float32)
    s = np.asarray(jax.nn.sigmoid(xr @ lp["router"].astype(jnp.float32)),
                   np.float64)
    idx, w = (np.asarray(a) for a in lm.route(lp, CFG, x))
    assert (np.sort(idx, 1) == np.sort(np.argsort(-s, 1)[:, :2], 1)).all()
    sel = np.take_along_axis(s, idx, 1)
    np.testing.assert_allclose(w, 2.5 * sel / sel.sum(1, keepdims=True),
                               rtol=1e-5)
    ridx, rw, _ = ref.route({"router": lp["router"].astype(jnp.float32)},
                            TINY, xr)
    assert (np.sort(np.asarray(ridx), 1) == np.sort(idx, 1)).all()
    np.testing.assert_allclose(np.sort(np.asarray(rw), 1), np.sort(w, 1),
                               rtol=1e-5)


@pytest.mark.parametrize("key,value,match", [
    ("moe_apply_router_weight_on_input", True, "outputs"),
    ("layer_types", [FULL, SLIDING], "every layer"),
    ("mlp_layer_types", ["dense"] * 3, "every layer"),
    ("num_attention_heads_per_layer", [6] * 5, "every layer"),
    ("mlp_layer_types", ["dense", "sparse", "moe", "sparse"], "dense"),
    ("layer_types", [FULL, "chunked_attention", SLIDING, FULL], "computed"),
])
def test_a_configuration_that_is_not_computed_is_refused(key, value, match):
    with pytest.raises(ValueError, match=match):
        lg.LagunaMoEConfig.from_config({**TINY, key: value})


def test_another_kind_of_rope_is_refused():
    rope = {**TINY["rope_parameters"],
            SLIDING: {**TINY["rope_parameters"][SLIDING],
                      "rope_type": "linear"}}
    with pytest.raises(ValueError, match="yarn"):
        lg.LagunaMoEConfig.from_config({**TINY, "rope_parameters": rope})


def test_a_chunk_longer_than_the_rings_slack_fails_and_moves_nothing(seqs):
    """17 events a flow (a layout of 32) behind a window of 8 do not fit
    a ring of 24: the call fails and the table is rolled back, the next
    call goes on from where the flow was."""
    async def go():
        s = scorer()
        try:
            a = await s.score(rows_of({11: seqs[11][:16]}))
            with pytest.raises(ValueError, match="ring"):
                await s.score(rows_of({11: seqs[11][16:33]}))
            b = await s.score(rows_of({11: seqs[11][16:32]}))
            return np.concatenate([a, b])
        finally:
            s.close()
    want, _ = reference_of({11: seqs[11][:32]})
    close_to(run(go()), want[11])


def test_a_call_counts_what_each_kind_of_layer_attended_and_holds(seqs):
    """Two flows' next 8 events on this platform's attention (a slot or a
    ring whole is one block): the record counts each kind's blocks apart
    and together, the windows written (9 positions a flow a layer; a ring
    is 24 of them), and **what the rings hold against the same layers as
    caches**: 8 x 24 rows a sliding layer, 8 x 256 as a cache."""
    async def go():
        s = scorer()
        try:
            t0 = time.monotonic()
            await s.score(rows_of({11: seqs[11][:8], 33: seqs[33][:8]}))
            return t0, time.monotonic(), s.device_state()["flow"]
        finally:
            s.close()
    t0, t1, flow = run(go())
    rec, = [c for c in phases.records()
            if c.kind == phases.SCORE and t0 <= c.t0 <= t1
            and c.counts.get("flow.events") == 16]
    n = rec.counts
    assert n["attn.full_blocks"] == 2 * len(CACHES) == n[
        "attn.full_blocks_whole"]
    assert n["attn.window_blocks"] == 2 * len(RINGS) == n[
        "attn.window_blocks_unwindowed"]
    assert n["attn.kv_blocks"] == 2 * 4 == n["attn.kv_blocks_whole"]
    # query rows: a layout of 2 flows x 8 events, 6 + 8 + 8 + 6 heads; XLA's
    # attention takes none of them as projected on a kernel's tile
    assert n["attn.q_rows"] == 2 * 8 * 28 and n["attn.q_rows_in_tile"] == 0
    assert n["state.window_rows"] == 8 * RING * len(RINGS)
    assert n["state.window_rows_as_cache"] == 8 * 256 * len(RINGS)
    assert n["cache.rows_written"] == 2 * 9 * 4
    assert n["cache.rows_whole"] == 2 * (256 * len(CACHES)
                                         + RING * len(RINGS))
    assert not [name for name in n if name.startswith("conv.")]
    pairs = 16 * 2 * 3
    assert n["moe.local_pairs"] == pairs
    assert flow["state"] == {
        "full_attention": {"positions": 256, "call": "attend_grouped_xla"},
        "window_attention": {"positions": RING,
                             "call": "attend_grouped_xla"}}
    assert fa.attention_call("tpu", True, True) == "window_attention_fused"
    assert fa.attention_call("tpu", True, False) == "grouped_attention_fused"
    assert fa.attention_call("tpu", False, False) == "latent_attention_fused"


def test_the_spec_is_a_flow_model_over_the_whole_vocabulary():
    """The published sizes' spec: ids up to 100,351 are events, the table
    addresses by the flow's position in 4,224 whatever a ring holds, and
    the telemeter takes the model by name."""
    spec = SPECS["laguna_moe"]()
    assert spec.cfg == lg.LagunaMoEConfig() and spec.name == "laguna_moe"
    assert spec.keyed and not spec.trains and spec.single_device
    table = spec.make_table()
    assert (table.slots, table.positions, table.vocab) == (128, 4224, 100352)
    plan = table.map(np.array([[5, 0, 100351], [5, 0, 1]], np.int32))
    assert plan.rows[:, 1].tolist() == [1, 2]
    with pytest.raises(ValueError, match="event id"):
        table.map(np.array([[5, 0, 100352]], np.int32))
    t = JaxAnomalyTelemeter(JaxAnomalyConfig(
        model="laguna_moe", trainEveryBatches=0, scoreConcurrency=2),
        MetricsTree())
    assert t._flow_spec.name == "laguna_moe"
    with pytest.raises(ValueError, match="laguna_moe"):
        JaxAnomalyTelemeter(JaxAnomalyConfig(model="laguna"), MetricsTree())


# -- the kernel over a ring, and at a head of 128 -----------------------------

def ring_of(rng, F: int, T: int, H: int, G: int, hd: int, P: int, p0):
    """A layer's ``Queries`` (projected, still to be turned and gated),
    its rings, the flows' slots and positions."""
    seed = int(rng.integers(1 << 30))
    S = 2 * F + 1
    slot = (1 + 2 * rng.permutation(F)).astype(np.int32)
    slot[-1] = S        # a flow that brings nothing: read clipped
    p0 = np.asarray(p0, np.int32)
    return (queries_of(seed, F, T, H, hd, p0, hd // 2),
            jax.random.normal(jax.random.key(seed), (S, 2 * G * hd, P),
                              jnp.bfloat16), slot, p0)


def blocks_by_hand(p0, T: int, R: int, P: int, window=None) -> tuple:
    """``(attended, whole, unwindowed)`` a flow, tile by tile, as the
    kernel's loops run."""
    bk = fa.kv_block(P)
    events = fa._events_a_tile(T, R, P)
    seen, plain = [], []
    for p in p0:
        a = u = 0
        for first in range(int(p), int(p) + T, events):
            hi = -(-(first + events) // bk)
            if window is None:
                a += -(-min(first + events, P) // bk)
            else:
                a += hi - max(first - window + 1, 0) // bk
                u += hi
        seen.append(a)
        plain.append(u)
    return seen, (T // events) * (P // bk), plain


# (heads, kv heads, head, ring, window): a ring that is one block, a ring
# of several blocks, and the published sizes
WINDOWS = {"tiny": (8, 2, 16, 88, 24), "blocks": (4, 2, 16, 384, 200),
           "published": (16, 2, 128, 640, 512)}


@pytest.mark.parametrize("width", sorted(WINDOWS))
@pytest.mark.parametrize("layout", [(4, 16), (2, 64), (8, 1)],
                         ids=lambda l: f"{l[0]}x{l[1]}")
def test_the_window_kernel_is_xlas_attention_over_a_ring(layout, width):
    """``grouped_attention_fused(window=)``, the call a trace names
    ``window_attention_fused``, interpreted, on the inputs of
    ``attend_grouped_xla(window=)``: flows that begin, that are shorter
    than the window, that have just passed it, that have been round the
    ring several times, and one that brings nothing."""
    (F, T), (H, G, hd, P, W) = layout, WINDOWS[width]
    rng = np.random.default_rng(F * T + P)
    p0 = [1, W // 2, W - T // 2, P - T // 2, 3 * P + 5, 7 * P - T,
          2 * P - 1, 1][:F]
    q, cache, slot, p0 = ring_of(rng, F, T, H, G, hd, P, p0)
    want, one, whole, plain, none = jax.jit(functools.partial(
        ga.attend_grouped_xla, scale=0.25, window=W))(q, cache, slot, p0)
    assert np.asarray(one).tolist() == [1] * F == np.asarray(plain).tolist()
    got, seen, whole, plain, in_tile = jax.jit(functools.partial(
        fa.grouped_attention_fused, scale=0.25, window=W, interpret=True))(
            q, cache, slot, p0)
    assert (int(none), int(in_tile)) == (
        0, F * T * H if fa.on_the_tile(T, H // G, P) else 0)
    assert got.shape == want.shape == (F, T, H * hd)
    gap = np.abs(np.asarray(got, np.float32) - np.asarray(want, np.float32))
    assert gap.max() < 0.06 and np.median(gap) < 4e-3, (gap.max(),
                                                        np.median(gap))
    by_hand = blocks_by_hand(p0, T, H // G, P, W)
    assert (np.asarray(seen).tolist(), whole, np.asarray(plain).tolist()
            ) == by_hand
    # no flow attends over more than a block more than its ring
    assert max(by_hand[0]) <= by_hand[1] + T // fa._events_a_tile(
        T, H // G, P)


def test_a_window_sees_exactly_its_last_positions():
    """One query against a ring whose values name their index: with keys
    all alike the output is the mean of the values seen, so it says which
    indices were: the last 8 positions' and no other, wherever the ring
    has been written since."""
    P, W, hd = 24, 8, 16
    cache = np.zeros((1, 2 * hd, P), np.float32)
    cache[0, hd] = np.arange(P)             # value row 0: the index
    q = unturned(jnp.ones((1, 1, 1, hd)), 1)
    for p in (0, 3, 7, 8, 23, 24, 30, 100):
        for attend in (
                functools.partial(ga.attend_grouped_xla, window=W),
                functools.partial(fa.grouped_attention_fused, window=W,
                                  interpret=True)):
            o, *_ = attend(q, jnp.asarray(cache, jnp.bfloat16),
                           np.zeros(1, np.int32), np.array([p], np.int32),
                           scale=1.0)
            seen = [i % P for i in range(max(0, p - W + 1), p + 1)]
            assert float(o[0, 0, 0]) == pytest.approx(np.mean(seen),
                                                      rel=1e-2), p


# (heads, kv heads, head, positions): a slot of 4,096 at a head of 128,
# where a group's rows come in tiles
HEAD128 = {"six-to-a-group": (12, 2, 128, 512),
           "long-slot": (6, 1, 128, 4096)}


@pytest.mark.parametrize("width", sorted(HEAD128))
@pytest.mark.parametrize("layout", [(2, 64), (4, 8)],
                         ids=lambda l: f"{l[0]}x{l[1]}")
def test_the_grouped_kernel_at_a_head_of_128_is_xlas_attention(layout, width):
    (F, T), (H, G, hd, P) = layout, HEAD128[width]
    rng = np.random.default_rng(P + T)
    p0 = np.linspace(1, P - T, F).astype(np.int32)
    q, cache, slot, p0 = ring_of(rng, F, T, H, G, hd, P, p0)
    want, *_ = jax.jit(functools.partial(
        ga.attend_grouped_xla, scale=0.09))(q, cache, slot, p0)
    got, seen, whole, in_tile = jax.jit(functools.partial(
        fa.grouped_attention_fused, scale=0.09, interpret=True))(
            q, cache, slot, p0)
    assert int(in_tile) == (F * T * H if fa.on_the_tile(T, H // G, P) else 0)
    gap = np.abs(np.asarray(got, np.float32) - np.asarray(want, np.float32))
    assert gap.max() < 0.06 and np.median(gap) < 4e-3
    by_hand = blocks_by_hand(p0, T, H // G, P)
    assert (np.asarray(seen).tolist(), whole) == by_hand[:2]
    if width == "long-slot" and T == 64:
        # 384 rows of 4,096 scores pass SCORE_BYTES: tiles of 32 events
        assert fa._events_a_tile(T, H // G, P) == 32


def test_the_whole_step_on_the_kernels_is_the_reference(seqs, whole,
                                                        monkeypatch):
    """The step built on the TPU's kernels, interpreted (both attention
    calls, the grouped product, the combine, the append: over the caches
    of 256 positions, two tiles; the rings of 24 are no whole tiles and
    take XLA's append), over calls of 16 events that straddle the ring's
    end."""
    from linkerd_tpu.ops import expert_product as ep
    monkeypatch.setattr(
        fa, "best_attention",
        lambda platform, grouped=False: functools.partial(
            fa.grouped_attention_fused, interpret=True))
    monkeypatch.setattr(ca, "best_append", lambda platform: functools.partial(
        ca.cache_append_fused, interpret=True))
    monkeypatch.setattr(ep, "best_expert_product",
                        lambda platform: product_of("fused"))
    short = {k: v[:48] for k, v in seqs.items()}
    s = scorer()
    try:
        t0 = time.monotonic()
        got = in_chunks(s, short, 16)
        t1 = time.monotonic()
    finally:
        s.close()
    for key, ids in short.items():
        close_to(got[key], whole[0][key][:len(ids)])
    # every query row of every layer went to the kernel as projected: 3
    # calls (the third of 2 flows in a layout of 2) of 16 events x 28 heads
    recs = [c.counts for c in phases.records()
            if c.kind == phases.SCORE and t0 <= c.t0 <= t1]
    rows = sum(n["attn.q_rows"] for n in recs)
    assert rows == sum(n["attn.q_rows_in_tile"] for n in recs)
    assert rows == (4 + 4 + 2) * 16 * 28
    # the live flows (3, 3, 2) of every layer appended to; by the kernel on
    # the two full layers
    flows = [n["append.flows"] for n in recs]
    assert flows == [3 * 4, 3 * 4, 2 * 4]
    assert [n["append.flows_in_kernel"] for n in recs] == [
        f // 2 for f in flows]
