"""What only the second flow model has (``models/lfm2_moe.py``): two kinds
of per-flow state, a convolution's tail beside a cache of keys and values,
and a routed layer with no shared expert. At the tiny preset of
``tests/test_latent_moe.py`` (which runs the cases both models share),
against ``chipbench/reference/lfm2_moe.py``."""

import functools
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.reference import lfm2_moe as ref
from linkerd_tpu.models import grouped_attention as ga
from linkerd_tpu.models import latent_moe as lm
from linkerd_tpu.models import lfm2_moe as lf
from linkerd_tpu.models.spec import lfm2_moe
from linkerd_tpu.ops import flow_attention as fa
from linkerd_tpu.telemetry import phases
from tests.test_flow_attention import operator_and_parent, queries_of
from tests.test_latent_moe import (
    CFG_LFM2 as CFG, MODELS, SEED, TINY_LFM2 as TINY, close_to,
    reference_scores, rows_of, run, scorer,
)

MODEL = MODELS["lfm2_moe"]
CONVS = [l for l, kind in enumerate(TINY["layer_types"]) if kind == "conv"]
ATTNS = [l for l, kind in enumerate(TINY["layer_types"]) if kind != "conv"]


@pytest.fixture(scope="module")
def seqs():
    rng = np.random.default_rng(0)
    return {11: rng.integers(1, 128, 40), 22: rng.integers(1, 128, 25),
            33: rng.integers(1, 128, 33)}


@pytest.fixture(scope="module")
def whole(seqs):
    """The reference's one full forward of every flow."""
    return reference_scores(seqs, MODEL)


@pytest.mark.parametrize("chunk", [1, 2, 3, 64])
def test_calls_of_any_length_agree_with_one_full_forward(seqs, whole, chunk):
    """The same three sequences brought in calls of ``chunk`` events a
    flow: 1 and 2 are chunks no longer than a convolution's tail, which
    merge with the tail they meet; 3 is one longer; 64 is every flow
    whole. Every call's scores are the reference's for the flow forward
    once, and when the flows have ended both kinds of state hold what the
    reference computes: the cache every position's keys and values, a
    convolution layer ``u`` of the flow's last two positions."""
    async def go():
        s = scorer(MODEL)
        try:
            got, at = {k: [] for k in seqs}, 0
            while at < max(len(v) for v in seqs.values()):
                rows = rows_of({k: v[at:at + chunk] for k, v in seqs.items()
                                if at < len(v)})
                out = await s.score(rows)
                for k in seqs:
                    got[k].extend(out[rows[:, 0] == k])
                at += chunk
            return got, s._state
        finally:
            s.close()
    got, state = run(go())
    want, full = whole
    for b, (key, ids) in enumerate(seqs.items()):
        close_to(got[key], want[key], MODEL)
        for gap in MODEL.kept_gap(state, full, b, 1 + len(ids)):
            gap = np.abs(gap)
            assert np.median(gap) < 8e-3 and gap.max() < 0.2
    assert np.asarray(state[1])[:3].tolist() == [41, 26, 34]


def test_a_restart_clears_both_kinds_of_state(seqs):
    """A flow of 12 events restarts under its key with a chunk of one
    event, shorter than a convolution's tail: the tail it leaves is the
    start token's ``u`` and the event's, nothing of the flow before; the
    cache counts 2 positions; and the score is the reference's for the
    sequence of that one event."""
    again = np.array([5], np.int32)     # no near-tie in its routing

    async def go():
        s = scorer(MODEL)
        try:
            await s.score(rows_of({22: seqs[22][:12]}))
            before = [np.asarray(k[0], np.float32) for k in s._state[0]]
            out = await s.score(rows_of({22: again}, restart={22}))
            return out, before, s._state
        finally:
            s.close()
    out, before, state = run(go())
    want, full = reference_scores({22: again}, MODEL)
    close_to(out, want[22], MODEL)
    assert int(np.asarray(state[1])[0]) == 2
    for l, gap in enumerate(MODEL.kept_gap(state, full, 0, 2)):
        assert np.abs(gap).max() < 0.1, l
    for l in CONVS:     # and it is another tail than the old flow's
        assert np.abs(np.asarray(state[0][l][0], np.float32)
                      - before[l]).max() > 0.1


def test_a_call_counts_the_state_it_wrote_of_each_kind(seqs):
    """Two flows' next 8 events: the call's record counts the cache rows
    written (a window of 9 positions a flow an attention layer, of the
    slot's 64) and nothing of a convolution's tail (2 rows a flow of the
    call a layer, whatever the call), the query rows, the tiles the
    grouped product ran, which hold every (token, expert) pair, and the
    experts' weights it read for them. The record is picked from the
    process's log by what this test sent and when: 16 events between two
    readings of the clock (``tests/test_phase_spans.py`` logs hand-made
    records on a clock far ahead of this one, and under ``--dist
    loadfile`` a worker may have run it first)."""
    async def go():
        s = scorer(MODEL)
        try:
            t0 = time.monotonic()
            await s.score(rows_of({11: seqs[11][:8], 33: seqs[33][:8]}))
            return t0, time.monotonic()
        finally:
            s.close()
    t0, t1 = run(go())
    rec, = [c for c in phases.records()
            if c.kind == phases.SCORE and t0 <= c.t0 <= t1
            and c.counts.get("flow.events") == 16]
    assert CONVS and not [n for n in rec.counts if n.startswith("conv.")]
    assert rec.counts["cache.rows_written"] == 2 * 9 * len(ATTNS)
    assert rec.counts["cache.rows_whole"] == 2 * CFG.positions * len(ATTNS)
    # query rows: a layout of 2 flows x 8 events x the heads, an attention
    # layer; XLA's attention takes none as projected on a kernel's tile
    assert rec.counts["attn.q_rows"] == 2 * 8 * CFG.num_attention_heads * len(
        ATTNS)
    assert rec.counts["attn.q_rows_in_tile"] == 0
    pairs = 16 * CFG.num_experts_per_tok * (CFG.layers - CFG.num_dense_layers)
    assert rec.counts["moe.local_pairs"] == pairs
    tiles = rec.counts["moe.tiles"]
    assert pairs <= tiles * CFG.expert_tile < pairs + (
        CFG.layers - CFG.num_dense_layers) * 16 * CFG.expert_tile
    # this platform's product is XLA's loop: an expert's weights a tile
    assert rec.counts["moe.weight_loads"] == tiles


def test_the_bias_is_in_the_selection_and_not_in_the_weights():
    """Top 4 of score + bias; the weights are the selected experts' own
    scores over their sum + 1e-6, times ``routed_scaling_factor`` 1: an
    expert that only its bias selects weighs what its score weighs."""
    lp = dict(lm.init(jax.random.key(SEED), CFG)["layers"][1])
    x = jax.random.normal(jax.random.key(9), (40, CFG.hidden_size))
    s = np.asarray(jax.nn.sigmoid(
        x.astype(jnp.bfloat16).astype(jnp.float32)
        @ lp["router"].astype(jnp.float32)), np.float64)
    lowest = int(s.sum(0).argmin())
    lp["router_bias"] = lp["router_bias"].at[lowest].set(2.0)
    idx, w = (np.asarray(a) for a in lm.route(lp, CFG, x))
    assert idx.shape == (40, 4) and (idx == lowest).any(1).all()
    biased = s + np.asarray(lp["router_bias"], np.float64)
    assert (np.sort(idx, 1) == np.sort(np.argsort(-biased, 1)[:, :4], 1)).all()
    sel = np.take_along_axis(s, idx, 1)
    np.testing.assert_allclose(w, sel / (sel.sum(1, keepdims=True) + 1e-6),
                               rtol=1e-5)
    # the reference routes alike
    ridx, rw, _ = ref.route(
        {"router": lp["router"].astype(jnp.float32),
         "router_bias": lp["router_bias"].astype(jnp.float32)}, TINY,
        x.astype(jnp.bfloat16).astype(jnp.float32))
    assert (np.sort(np.asarray(ridx), 1) == np.sort(idx, 1)).all()
    np.testing.assert_allclose(np.sort(np.asarray(rw), 1), np.sort(w, 1),
                               rtol=1e-5)


def test_the_table_is_built_over_the_whole_vocabulary():
    """The published sizes' spec (nothing is drawn to look at it): ids up
    to 65,535 are events, 65,536 is refused; 512 slots of 1,024."""
    spec = lfm2_moe()
    assert spec.cfg == lf.Lfm2MoEConfig() and spec.name == "lfm2_moe"
    assert spec.keyed and not spec.trains and spec.single_device
    table = spec.make_table()
    assert (table.slots, table.positions, table.vocab) == (512, 1024, 65536)
    plan = table.map(np.array([[5, 0, 65535], [5, 0, 1]], np.int32))
    assert plan.rows[:, 2].tolist() == [65535, 1]
    with pytest.raises(ValueError, match="event id"):
        table.map(np.array([[5, 0, 65536]], np.int32))
    kinds = [spec.cfg.operator(l).scope for l in range(spec.cfg.layers)]
    assert kinds.count("conv") == 7 and kinds.count("attention") == 2
    # state: keys and values 1 GiB a layer, a tail 2 MiB a layer
    shapes = jax.eval_shape(spec.init_state)[0]
    assert sorted({s.shape for s in shapes}) == [(512, 2, 2048),
                                                 (512, 1024, 1024)]


# the grouped kernel against XLA's: (F, T), (heads, kv heads, head, P)
GROUPED = {"tiny": (4, 2, 16, 64), "four-blocks": (4, 2, 16, 512),
           "published-head": (8, 2, 64, 256)}


@pytest.mark.parametrize("width", sorted(GROUPED))
@pytest.mark.parametrize("layout", [(8, 8), (2, 32), (16, 1)],
                         ids=lambda l: f"{l[0]}x{l[1]}")
def test_the_grouped_kernel_is_xlas_attention(layout, width):
    """``grouped_attention_fused``, interpreted, on the inputs of
    ``attend_grouped_xla``: each flow's slot taken from the cache by its
    number, flows that begin, that end at the slot's last position, a
    padding flow (slot out of range, read clipped)."""
    (F, T), (H, G, hd, P) = layout, GROUPED[width]
    S = 2 * F + 1
    slot = (1 + 2 * np.random.default_rng(F).permutation(F)).astype(np.int32)
    slot[-1] = S
    cache = jax.random.normal(jax.random.key(F * T + P), (S, 2 * G * hd, P),
                              jnp.bfloat16)
    p0 = np.linspace(1, P - T, F).astype(np.int32)
    p0[:2] = 1, 0
    # as this model's layers hand them over: the whole head turned, no gate
    q = queries_of(F * T + P, F, T, H, hd, p0, hd // 2, gated=False)
    want, one, whole, none = jax.jit(functools.partial(
        lf.attend_grouped_xla, scale=0.25))(q, cache, slot, p0)
    assert np.asarray(one).tolist() == [1] * F and whole == 1 and none == 0
    got, seen, whole, in_tile = jax.jit(functools.partial(
        fa.grouped_attention_fused, scale=0.25, interpret=True))(
            q, cache, slot, p0)
    assert got.shape == want.shape == (F, T, H * hd)
    assert got.dtype == want.dtype == jnp.bfloat16
    assert in_tile == (F * T * H if fa.on_the_tile(T, H // G, P) else 0)
    gap = np.abs(np.asarray(got, np.float32) - np.asarray(want, np.float32))
    assert gap.max() < 0.05 and np.median(gap) < 4e-3, (gap.max(),
                                                        np.median(gap))
    bk = 128 if P % 128 == 0 else P
    assert whole == P // bk
    assert np.asarray(seen).tolist() == [
        -(-min(int(p) + T, P) // bk) for p in p0]
    assert fa.best_attention("tpu", True) is fa.grouped_attention_fused
    assert fa.best_attention("cpu", True) is lf.attend_grouped_xla


def test_on_xla_the_operator_is_the_parents_bit_for_bit(monkeypatch):
    """This model's attention layer (``q_norm`` and ``k_norm``, the whole
    head turned, no gate) on XLA's attention: the operator that hands
    ``attend`` the normed queries as projected computes what it computed
    when it turned and cast them itself (``parents_apply``), output and
    appended state equal, on the seeded weights."""
    seen = []

    def spy(layer):
        seen.append(layer)
        return ga.grouped_attention(layer)

    monkeypatch.setattr(lf, "grouped_attention", spy)
    l = ATTNS[0]
    CFG.operator(l)
    s = scorer(MODEL)
    try:
        lp = s.params["layers"][l]
        (y, state, counts), (y_then, state_then) = operator_and_parent(
            CFG, s.params, l, seen[-1])
    finally:
        s.close()
    assert "q_norm" in lp and "wg" not in lp
    assert np.isfinite(np.asarray(y)).all() and np.asarray(y).std() > 0.01
    assert (np.asarray(y) == np.asarray(y_then)).all()
    assert (np.asarray(state, np.float32)
            == np.asarray(state_then, np.float32)).all()
    assert int(counts["attn.q_rows"]) == 4 * 8 * CFG.num_attention_heads
    assert int(counts["attn.q_rows_in_tile"]) == 0
