"""The fused latent attention (``ops/flow_attention.py``), interpreted on
the CPU, against XLA's ``attend_xla`` on the same inputs: alone, over
layouts, widths and flows of every length, each flow's slot taken from
the layer's cache by its number; inside ``flow_step``, where padding rows
and empty flows meet it; and the append that goes before it
(``models.latent_moe.append_chunk``, and the TPU's kernel of
``ops/cache_append.py`` interpreted), against the formulation it replaced
(gather the slots, ``where`` the chunk in, scatter them back whole), which
is kept here as the oracle."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from linkerd_tpu.models import latent_moe as lm
from linkerd_tpu.ops import cache_append as ca
from linkerd_tpu.ops import flow_attention as fa

# heads, rank, rope, positions: the tiny preset's (one block of 64
# positions), the same over four blocks of 128, and the published entry
# (512 + 64: what Mosaic's lanes hold) at few heads
WIDTHS = {"tiny": (4, 16, 8, 64), "tiny-4-blocks": (4, 16, 8, 512),
          "entry-512+64": (2, 512, 64, 512)}
LAYOUTS = [(8, 8), (2, 32), (16, 1)]
SCALE = 0.135


def flows_at(F: int, T: int, P: int) -> np.ndarray:
    """Where each of ``F`` flows' chunks begins: a flow that begins (1),
    the start token's own call (0), one that ends at the slot's last
    position, one across a block's edge, the rest spread over the slot."""
    p0 = np.linspace(1, P - T, F).astype(np.int32)
    p0[:2] = 1, 0
    p0[-1] = P - T
    if F > 3 and P > 128 + T:
        p0[2] = 128 - T // 2 - 1
    return p0


def slots_for(F: int) -> tuple:
    """``(slots of the cache, each flow's slot)``: a cache of twice the
    flows and one, the flows in no order and never two adjacent ones
    running (odd slots, permuted), the last flow of a call of several a
    padding flow (its slot out of range: read clipped)."""
    S = 2 * F + 1
    slot = (1 + 2 * np.random.default_rng(F).permutation(F)).astype(np.int32)
    if F > 1:
        slot[-1] = S
    return S, slot


def by_hand(p0, T: int, H: int, P: int) -> tuple:
    """``(blocks attended over, blocks of the slots whole)``, counted tile
    by tile: a tile of ``events`` events at ``first`` sees positions
    ``0 .. first + events - 1``."""
    bk = 128 if P % 128 == 0 else P
    events = T      # halved until 1,024 rows and 4 MiB of scores hold them
    while events * H > min(1024, 2 ** 20 // P) and events % 2 == 0:
        events //= 2
    seen = sum(-(-min(int(p) + i * events + events, P) // bk)
               for p in p0 for i in range(T // events))
    return seen, len(p0) * (T // events) * (P // bk)


@pytest.mark.parametrize("width", sorted(WIDTHS))
@pytest.mark.parametrize("layout", LAYOUTS, ids=lambda l: f"{l[0]}x{l[1]}")
def test_the_kernel_is_xlas_attention(layout, width):
    (F, T), (H, rank, rope, P) = layout, WIDTHS[width]
    k = jax.random.split(jax.random.key(F * T + P), 3)
    qa = jax.random.normal(k[0], (F, T, H, rank), jnp.bfloat16)
    qr = jax.random.normal(k[1], (F, T, H, rope), jnp.bfloat16)
    S, slot = slots_for(F)
    cache = jax.random.normal(k[2], (S, P, rank + rope), jnp.bfloat16)
    p0 = flows_at(F, T, P)
    want, one, whole = jax.jit(functools.partial(
        lm.attend_xla, scale=SCALE))(qa, qr, cache, slot, p0)
    assert np.asarray(one).tolist() == [1] * F and whole == 1
    got, seen, whole = jax.jit(functools.partial(
        fa.latent_attention_fused, scale=SCALE, interpret=True))(
            qa, qr, cache, slot, p0)
    assert got.shape == (F, T, H, rank) and got.dtype == jnp.bfloat16
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    assert np.isfinite(got).all()
    # a weighted mean of normal values, both sides rounded to bfloat16
    # (8 bits: 2**-8 of a value of size 1-4) and the weights rounded at
    # another place (before the division here, after it there)
    gap = np.abs(got - want)
    assert gap.max() < 0.04 and gap.mean() < 2e-3, (gap.max(), gap.mean())
    assert (int(np.asarray(seen).sum()), F * int(whole)) == by_hand(
        p0, T, H, P)


def test_a_tile_ends_where_its_own_events_end():
    """64 events x 64 heads are 4 tiles of 16 events: a chunk at position
    65 fills positions 65..128, so only its last tile sees the second
    block (position 128) and the others stop after one."""
    p0 = np.array([65], np.int32)
    assert by_hand(p0, 64, 64, 1024) == (5, 32)
    seen = sum(fa.blocks_seen(p0 + i * 16, 16, 1024) for i in range(4))
    assert np.asarray(seen).tolist() == [5]
    assert fa.kv_block(1024) == 128 and fa.kv_block(64) == 64
    assert fa._events_a_tile(64, 64, 1024) == 16
    assert fa._events_a_tile(1, 64, 1024) == 1
    assert fa._events_a_tile(64, 64, 4096) == 4     # 4 MiB of scores


def test_selection_is_by_platform_alone():
    assert fa.attention_kind("tpu") == "fused_pallas"
    assert fa.best_attention("tpu") is fa.latent_attention_fused
    for platform in ("cpu", "gpu"):
        assert fa.attention_kind(platform) == "xla"
        assert fa.best_attention(platform) is lm.attend_xla


def test_the_append_is_selected_by_platform_and_served_by_shape():
    """``best_append``: the kernel on a TPU, XLA's form elsewhere; the
    kernel serves a state whose positions are whole tiles of 128 lanes
    and chunks that divide a tile (the three flow cells' caches and rings,
    either layout), and hands any other shape to XLA's form itself."""
    assert ca.append_kind("tpu") == "fused_pallas"
    assert ca.best_append("tpu") is ca.cache_append_fused
    for platform in ("cpu", "gpu"):
        assert ca.append_kind(platform) == "xla"
        assert ca.best_append(platform) is lm.append_chunk
    for shape, last, ring in (((128, 2048, 4224), True, False),
                              ((128, 2048, 640), True, True),
                              ((512, 1024, 1024), True, False),
                              ((512, 1024, 576), False, False)):
        assert ca.serves(shape, 64, last, ring)
        assert ca.serves(shape, 1, last, ring)
        assert not ca.serves(shape, 256, last, ring)    # a chunk of 2 tiles
        assert not ca.serves(shape, 96, last, ring)     # straddles a tile
    assert not ca.serves((8, 64, 24), 8, True, True)    # no whole tiles
    assert not ca.serves((8, 64, 88), 8, True, False)
    # a ring of one tile: a window from its last position would take it
    # twice
    assert not ca.serves((8, 64, 128), 8, True, True)
    assert ca.serves((8, 64, 128), 8, True, False)
    # (the first tile, the tiles) of a window of 65 positions
    p0 = jnp.array([1, 64, 65, 100, 4224 - 63, 0])
    first, n = ca.window_tiles(p0, 4224, 65, False)
    assert first.tolist() == [0, 0, 0, 0, 32, 0]
    assert n.tolist() == [1, 1, 2, 2, 1, 1]
    first, n = ca.window_tiles(jnp.array([0, 640, 641, 2020]), 640, 65, True)
    assert first.tolist() == [4, 4, 0, 0] and n.tolist() == [2, 2, 1, 2]


TINY = lm.LatentMoEConfig(
    hidden_size=64, num_attention_heads=4, q_lora_rank=32, kv_lora_rank=16,
    qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
    intermediate_size=128, moe_intermediate_size=32, n_routed_experts=16,
    num_experts_per_tok=2, layers=3, experts_held=(4, 8), layer_share=4,
    vocab_slice=128, slots=16, positions=256, expert_tile=8)


def device_rows(chunks: dict, T: int) -> np.ndarray:
    """``{f: (slot, p0, ids)}`` -> the rows ``(cell, address, id)`` a
    ``FlowTable`` would stage for them in a layout of ``T`` events."""
    return np.array([(f * T + t, slot * TINY.positions + p0 + t, i)
                     for f, (slot, p0, ids) in chunks.items()
                     for t, i in enumerate(ids)], np.int32).reshape(-1, 3)


@pytest.mark.parametrize("layout", LAYOUTS, ids=lambda l: f"{l[0]}x{l[1]}")
def test_in_the_step_padding_and_empty_flows_come_out_finite(layout):
    """One call on each attention over the same state: a flow that begins,
    flows with prefixes on both sides of a block's edge, one that ends at
    the slot's last position, flows of ``count < T`` and, past them, flows
    of the layout that bring nothing (``count == 0``, their slot out of
    range). Live rows' scores agree, every number of the state is finite
    and the counts are the tiles' by hand."""
    F, T = layout
    P = TINY.positions
    rng = np.random.default_rng(F * T)
    held = [0, 5, 70, 127, 128, 200][:max(F - 2, 1)]    # flow f's prefix
    held[-1] = P - 1 - T if len(held) > 1 else 0
    steps = {name: jax.jit(functools.partial(lm.flow_step, cfg=TINY,
                                             attend=attend),
                           static_argnames=("F", "T"))
             for name, attend in (
                 ("xla", lm.attend_xla),
                 ("fused", functools.partial(fa.latent_attention_fused,
                                             interpret=True)))}
    params = lm.init(jax.random.key(3), TINY)

    def run(name, state, rows, F, T):
        staged = np.zeros((F * T, 3), np.int32)
        staged[:len(rows)] = rows
        return steps[name](params, state, jnp.asarray(staged),
                           np.int32(len(rows)), F=F, T=T)

    # the state both meet, made by XLA's: the start token's constants,
    # then the prefixes in chunks of 32
    state = lm.with_start(
        lambda s, r, n: steps["xla"](params, s, jnp.asarray(r), np.int32(n),
                                     F=8, T=32),
        TINY, lm.init_state(TINY), jnp.zeros((8 * 32, 3), jnp.int32))
    for at in range(0, max(held), 32):
        chunks = {f: (f, 1 + at, rng.integers(1, 128, min(32, n - at)))
                  for f, n in enumerate(held) if n > at}
        state = run("xla", state, device_rows(chunks, 32), 8, 32)[1]
    assert np.asarray(state[1])[:len(held)].tolist() == [
        n + 1 if n else 0 for n in held]

    chunks = {f: (f, n + 1, rng.integers(1, 128, max(T - f % 3, 1)))
              for f, n in enumerate(held)}
    chunks[len(held) - 1] = (len(held) - 1, held[-1] + 1,
                             rng.integers(1, 128, T))
    rows = device_rows(chunks, T)
    (a, sa, ca), (b, sb, cb) = (run(name, state, rows, F, T)
                                for name in ("xla", "fused"))
    # bfloat16 rounding, a few 1e-4; an event whose second and third
    # router scores lie within that rounding takes another expert on one
    # side and is off by up to a few 1e-2
    gap = np.abs(np.asarray(a) - np.asarray(b))[:len(rows)]
    assert (np.median(gap) < 6e-4 and np.quantile(gap, 0.9) < 3e-3
            and gap.max() < 5e-2), (np.median(gap), gap.max())
    for leaf in jax.tree_util.tree_leaves(sb):
        assert np.isfinite(np.asarray(leaf, np.float32)).all()
    np.testing.assert_array_equal(np.asarray(sa[1]), np.asarray(sb[1]))
    # the flows' newest hidden states (all a call of T = 1 leaves of its
    # attention: its scores come from the states before it)
    ha, hb = (np.asarray(s[2], np.float32)[:len(held)] for s in (sa, sb))
    assert np.abs(ha).mean() > 0.5 and np.abs(ha - hb).max() < 0.1
    assert np.median(np.abs(ha - hb)) < 4e-3
    p0 = [n + 1 for n in held] + [1] * (F - len(held))  # an empty flow: 1
    seen, whole = by_hand(p0, T, TINY.num_attention_heads, P)
    assert int(cb["attn.kv_blocks"]) == TINY.layers * seen
    assert int(cb["attn.kv_blocks_whole"]) == TINY.layers * whole
    assert (int(ca["attn.kv_blocks"]) == int(ca["attn.kv_blocks_whole"])
            == TINY.layers * F)


# -- the append that goes before the attention ---------------------------------

def append_by_gather(cache, entry, start_entry, slot, p0, count, begins):
    """The formulation ``append_chunk`` replaced, as PRs 28-30 had it in
    ``_attention``: every flow's slot gathered whole, the chunk set into
    it by a ``where`` over all its positions, the slots scattered back
    whole. Returns the cache, the rows it wrote (whole slots) and the flows
    a kernel appended (none): ``Call.append``'s result."""
    S, P, _ = cache.shape
    T = entry.shape[1]
    kv = cache[jnp.minimum(slot, S - 1)]
    t = jnp.arange(P)[None] - p0[:, None]
    mine = (t >= 0) & (t < count[:, None])
    kv = jnp.where(mine[..., None], jnp.take_along_axis(
        entry, jnp.clip(t, 0, T - 1)[..., None], 1), kv)
    kv = kv.at[:, 0].set(jnp.where(begins[:, None], start_entry[None],
                                   kv[:, 0]))
    return (cache.at[slot].set(kv, mode="drop"), (slot < S).sum() * P,
            jnp.int32(0))


# (positions, T, [(slot, p0, count) a flow]); 8 slots, so slot 8 is a flow
# of the layout that brings nothing
APPENDS = {
    "counts-0-1-T": (64, 8, [(8, 1, 0), (2, 9, 1), (5, 30, 8)]),
    "a-flow-begins": (64, 8, [(4, 1, 5), (1, 17, 8)]),
    "a-padding-flow-with-rows": (64, 8, [(8, 12, 3), (3, 12, 3)]),
    "ends-at-the-last-position": (64, 8, [(6, 56, 8), (0, 61, 3),
                                          (2, 63, 1)]),
    "non-adjacent-slots": (64, 8, [(1, 20, 8), (6, 33, 7)]),
    "the-start-tokens-own-call": (64, 1, [(0, 0, 1)]),
    "a-chunk-as-long-as-the-slot": (16, 16, [(3, 1, 15), (7, 4, 12)]),
    "four-blocks-of-positions": (512, 32, [(0, 1, 32), (7, 120, 17),
                                           (3, 480, 32), (8, 1, 0)]),
    # slots of whole tiles of 128 positions, where the kernel appends: a
    # window inside one tile and one across two; a window pushed back at
    # the slot's end (inside a tile, and across two at a chunk of a whole
    # tile); flows that begin, of count 0, out of range
    "one-tile": (256, 8, [(2, 9, 8), (5, 100, 8), (8, 40, 8), (1, 1, 0)]),
    "across-two-tiles": (384, 64, [(1, 100, 64), (3, 127, 5), (6, 1, 64),
                                   (4, 250, 20), (8, 1, 0)]),
    "pushed-back-at-the-end": (256, 64, [(6, 250, 6), (0, 200, 56),
                                         (2, 192, 64)]),
    "a-tile-a-chunk": (256, 128, [(4, 200, 56), (2, 1, 127), (7, 128, 128),
                                  (0, 0, 1)]),
    "one-event-a-flow": (128, 1, [(3, 127, 1), (5, 1, 1), (0, 0, 1),
                                  (8, 1, 0)]),
}


def tiles_by_hand(P: int, T: int, flows) -> int:
    """Lane tiles of 128 positions a cache's windows of ``T + 1`` touch,
    over the flows with a slot in range (8 slots)."""
    W = min(T + 1, P)
    w0 = [min(max(p - 1, 0), P - W) for s, p, _ in flows if s < 8]
    return sum(-(-(w % 128 + W) // 128) for w in w0)


@pytest.mark.parametrize("append", ["xla", "kernel"])
@pytest.mark.parametrize("positions_last", [False, True],
                         ids=["positions-minor", "positions-last"])
@pytest.mark.parametrize("case", sorted(APPENDS))
def test_the_append_touches_the_chunks_rows_and_no_other(
        case, positions_last, append):
    """XLA's append (``append_chunk``) and the TPU's kernel
    (``cache_append_fused``, interpreted) on a layer's cache laid either
    way: the latent cache ``[slots, positions, entry]`` (the kernel takes
    it as the bitcast view ``[slots, entry, positions]``) and the grouped
    operator's positions last. Both are **bit for bit the gathered
    slots'**, touch no row but the chunks' and the start token's, and
    count what they write: windows of ``T + 1`` (XLA's), or whole tiles of
    128 (the kernel's, where its shapes are served: whole tiles of
    positions; elsewhere it hands the call to XLA's and counts no flow)."""
    P, T, flows = APPENDS[case]
    S, E, F = 8, 32, len(flows)
    slot, p0, count = (np.array(c, np.int32) for c in zip(*flows))
    begins = (count > 0) & (p0 == 1)
    k = jax.random.split(jax.random.key(P + T + F), 3)
    before = jax.random.normal(k[0], (S, P, E), jnp.bfloat16)
    entry = jax.random.normal(k[1], (F, T, E), jnp.bfloat16)
    start = jax.random.normal(k[2], (E,), jnp.bfloat16)
    args = (before, entry, start, slot, p0, count, begins)
    laid = ((before.transpose(0, 2, 1),) + args[1:] if positions_last
            else args)
    fn = (functools.partial(ca.cache_append_fused, interpret=True)
          if append == "kernel" else lm.append_chunk)
    got, written, in_kernel = jax.jit(functools.partial(
        fn, positions_last=positions_last))(*laid)
    if positions_last:
        got = got.transpose(0, 2, 1)
    want, whole, _ = jax.jit(append_by_gather)(*args)
    got, want, was = (np.asarray(a) for a in (got, want, before))
    np.testing.assert_array_equal(got.view(np.uint16), want.view(np.uint16))
    got, want, was = (a.astype(np.float32) for a in (got, want, was))
    # by hand: the rows of the chunk and the start token's, nothing else
    mine = np.zeros((S, P), bool)
    for f, (sl, at, n) in enumerate(flows):
        if sl < S:
            mine[sl, at:at + n] = True
            mine[sl, 0] |= bool(begins[f])
            np.testing.assert_array_equal(
                got[sl, at:at + n], np.asarray(entry, np.float32)[f, :n])
            if begins[f]:
                np.testing.assert_array_equal(
                    got[sl, 0], np.asarray(start, np.float32))
    np.testing.assert_array_equal(got[~mine], was[~mine])
    changed = int((got != was).any(-1).sum())
    live = int((slot < S).sum())
    served = append == "kernel" and P % 128 == 0
    assert served == (append == "kernel" and ca.serves(
        laid[0].shape, T, positions_last, False))
    assert int(in_kernel) == (live if served else 0)
    assert changed <= mine.sum() <= int(written) == (
        128 * tiles_by_hand(P, T, flows) if served else live * min(T + 1, P))
    assert int(whole) == live * P


def test_three_calls_append_to_one_slot_as_the_gathered_slots_did():
    """Three calls in a row bring the same flows their next chunks (one of
    them begins in the first call; one call has a flow of fewer events and
    a flow that brings nothing). On XLA's attention the step with the
    append in place gives the scores and the cache of the step with the
    gathered slots **bit for bit**; the TPU's step (both kernels,
    interpreted: the append's over slots of two tiles, one flow's chunks
    across a tile's edge in the second call) agrees to rounding; after
    each call every row outside the chunks is what it was; and the step
    counts the rows it wrote: windows of ``T + 1``, or the kernel's whole
    tiles, and the flows the kernel appended."""
    F, T, P, S = 4, 8, TINY.positions, TINY.slots
    params = lm.init(jax.random.key(5), TINY)
    rng = np.random.default_rng(11)

    def build(attend, append):
        return jax.jit(functools.partial(lm.flow_step, cfg=TINY, F=F, T=T,
                                         attend=attend, append=append))

    def run(step, state, chunks):
        rows = device_rows(chunks, T)
        staged = np.zeros((F * T, 3), np.int32)
        staged[:len(rows)] = rows
        return step(params, state, jnp.asarray(staged), np.int32(len(rows)))

    place = build(lm.attend_xla, lm.append_chunk)
    fused = build(functools.partial(fa.latent_attention_fused,
                                    interpret=True),
                  functools.partial(ca.cache_append_fused, interpret=True))
    gathered = build(lm.attend_xla, append_by_gather)
    state = lm.with_start(
        lambda s, r, n: gathered(params, s, jnp.asarray(r), np.int32(n)),
        TINY, lm.init_state(TINY), jnp.zeros((F * T, 3), jnp.int32))
    states = {"place": state, "gathered": state, "fused": state}
    at = {0: 1, 1: 120, 2: P - 3 * T}       # flow f's next position
    slot_of = {0: 9, 1: 2, 2: 14}
    for call in range(3):
        n = {0: T, 1: T if call != 1 else 3, 2: T}
        chunks = {f: (slot_of[f], at[f], rng.integers(1, 128, n[f]))
                  for f in at}
        (sg, stg, cg), (sp, stp, cp), (sf, stf, cf) = (
            run(step, states[name], chunks) for name, step in (
                ("gathered", gathered), ("place", place), ("fused", fused)))
        np.testing.assert_array_equal(np.asarray(sp), np.asarray(sg))
        for a, b in zip(jax.tree_util.tree_leaves(stp),
                        jax.tree_util.tree_leaves(stg)):
            np.testing.assert_array_equal(np.asarray(a, np.float32),
                                          np.asarray(b, np.float32))
        live = sum(n.values())
        gap = np.abs(np.asarray(sf) - np.asarray(sp))[:live]
        assert np.median(gap) < 6e-4 and gap.max() < 5e-2, gap.max()
        # rows outside the chunks: bit for bit what they were
        mine = np.zeros((S, P), bool)
        for f in at:
            mine[slot_of[f], at[f]:at[f] + n[f]] = True
            mine[slot_of[f], 0] |= at[f] == 1
        for name, st in (("place", stp), ("fused", stf)):
            for new, old in zip(st[0], states[name][0]):
                new, old = (np.asarray(a, np.float32) for a in (new, old))
                np.testing.assert_array_equal(new[~mine], old[~mine])
                assert (new[mine] != old[mine]).any()
        # the counts: 3 live flows (the layout's fourth brings nothing)
        changed = sum(int((np.asarray(new, np.float32) != np.asarray(
            old, np.float32)).any(-1).sum())
            for new, old in zip(stp[0], states["place"][0]))
        wrote = TINY.layers * 3 * (T + 1)
        assert changed <= TINY.layers * int(mine.sum()) <= wrote
        assert int(cp["cache.rows_written"]) == wrote
        # a window of 9 from position 127 on touches both tiles of a slot
        tiles = 3 + sum(at[f] - 1 in range(120, 128) for f in at)
        assert tiles == (4 if call == 1 else 3)
        assert int(cf["cache.rows_written"]) == TINY.layers * tiles * 128
        for c, kernel in ((cp, 0), (cf, 3)):
            assert int(c["cache.rows_whole"]) == TINY.layers * 3 * P
            assert int(c["append.flows"]) == TINY.layers * 3
            assert int(c["append.flows_in_kernel"]) == TINY.layers * kernel
        assert (int(cg["cache.rows_written"]) == int(cg["cache.rows_whole"])
                == TINY.layers * 3 * P)
        states = {"place": stp, "gathered": stg, "fused": stf}
        at = {f: at[f] + n[f] for f in at}
    # the third flow's last chunk ended at the slot's last position
    assert at[2] == P == int(stp[1][14])


# -- grouped attention: the queries as projected, turned and gated on the tile

def queries_of(seed: int, F: int, T: int, H: int, hd: int, p0, half: int,
               gated: bool = True, rope_scale: float = 1.0):
    """A layer's ``Queries`` as ``models.grouped_attention._apply`` hands
    them to ``attend``: ``q [F, T, H x hd]`` float32 as a projection
    leaves it, the cosines and sines of the events' positions (``p0[f] +
    t``) at ``half`` frequencies times ``rope_scale``, a gate a head."""
    from linkerd_tpu.models.grouped_attention import Queries
    k = jax.random.split(jax.random.key(seed), 2)
    cos, sin = lm.angles(
        jnp.asarray(p0)[:, None] + jnp.arange(T)[None],
        (10000.0 ** (-np.arange(half) / half)).astype(np.float32))
    return Queries(
        jax.random.normal(k[0], (F, T, H * hd), jnp.float32),
        (cos * rope_scale)[:, :, None], (sin * rope_scale)[:, :, None],
        jax.nn.sigmoid(jax.random.normal(k[1], (F, T, H), jnp.float32))
        if gated else None, H)


def unturned(q, H: int):
    """``Queries`` of queries that are not to be turned (``cos`` 1, ``sin``
    0: ``a x 1 - b x 0`` is ``a``) nor gated, ``q [F, T, H, hd]``."""
    from linkerd_tpu.models.grouped_attention import Queries
    F, T, _, hd = q.shape
    one = jnp.ones((F, T, 1, hd // 2), jnp.float32)
    return Queries(q.reshape(F, T, H * hd).astype(jnp.float32), one,
                   0 * one, None, H)


def as_the_step_did(q, cache, slot, p0, scale, window=None):
    """The parent's order around the same kernel body, each pass an array
    of its own: turn in float32, **round to bfloat16**, lay the rows
    ``[F, G, T x R, head]``, attend (``_attend`` with no ``turn``), lay
    them back, widen, times the gate in float32, round as ``wo``'s product
    does."""
    from linkerd_tpu.models.grouped_attention import rotate
    F, T, width = q.q.shape
    H, hd = q.heads, width // q.heads
    G = cache.shape[1] // (2 * hd)
    R = H // G
    qb = rotate(q.q.reshape(F, T, H, hd), q.cos, q.sin,
                2 * q.cos.shape[-1]).astype(jnp.bfloat16)
    o, *blocks = fa._attend(
        [qb.reshape(F, T, G, R, hd).transpose(0, 2, 1, 3, 4).reshape(
            F, G, T * R, hd)], cache, slot, p0, T=T, values_at=G, vd=hd,
        scale=scale, interpret=True, window=window, name="as_the_step_did")
    o = o.reshape(F, G, T, R, hd).transpose(0, 2, 1, 3, 4).reshape(F, T, H, hd)
    if q.gate is not None:
        o = o.astype(jnp.float32) * q.gate[..., None]
    return o.astype(jnp.bfloat16).reshape(F, T, width), *blocks


def ulps_apart(a, b) -> np.ndarray:
    """bfloat16 values' distance in units of the last place of the
    larger, or of 1 where both are smaller (a weighted mean of values of
    size 1 that cancels to nearly nothing is as exact as its terms)."""
    a, b = (np.asarray(x, np.float32) for x in (a, b))
    big = np.maximum(np.maximum(np.abs(a), np.abs(b)), 1.0)
    return np.abs(a - b) / 2.0 ** (np.floor(np.log2(big)) - 7)


# (F, T), heads, key/value heads, head, rotated half, RoPE's scale, gate,
# positions a slot, window: each kind of layer at its published heads
IN_TILE = {
    # Laguna's full layer: 48 heads over 8, 64 of 128 values turned, cos and
    # sin scaled, a gate; a tile is a flow's 16 events x 6 heads
    "full": ((3, 16), 48, 8, 128, 32, 1.4158883, True, 512, None),
    # the same over a slot of 4,096: 64 events come in two tiles of 32
    "full-two-tiles": ((2, 64), 12, 2, 128, 32, 1.4158883, True, 4096, None),
    # its sliding layer: 64 heads, all 128 turned, a ring that wraps
    "sliding": ((4, 16), 64, 8, 128, 64, 1.0, True, 640, 512),
    # LFM2's: a head of 64 (two to a lane tile), no gate (and a q_norm,
    # which is XLA's, before the kernel)
    "lfm2": ((3, 16), 32, 8, 64, 32, 1.0, False, 256, None),
    # chunks of one event: too few to fill a tile's sublanes, so XLA turns
    # and gates around the kernel (``on_the_tile``)
    "one-event": ((8, 1), 16, 2, 128, 64, 1.0, True, 640, 512),
    # the tests' tiny heads
    "tiny": ((4, 16), 8, 2, 16, 4, 1.3, True, 88, 24),
}


@pytest.mark.parametrize("kind", sorted(IN_TILE))
def test_the_tile_turns_rounds_and_gates_as_the_step_did(kind):
    """``grouped_attention_fused`` handed the queries as projected
    (float32, unturned, ``[F, T, H x head]``), interpreted: **rounding for
    rounding what the step did around the kernel** (``as_the_step_did``:
    turn in float32, one rounding to bfloat16, the same kernel body, the
    output rounded, widened, gated in float32, rounded): not one value
    differs. Against ``attend_grouped_xla`` on the same ``Queries`` (a
    softmax normalised before its weights are rounded, where the kernel
    divides after) no value is further than two units of bfloat16's last
    place (``ulps_apart``), a handful further than one, and more than
    half are equal. (At chunks of one event the wrapper itself keeps that
    order around the kernel: the same holds, and no row is counted as
    taken on the tile.) The last flow is padding (its slot out of
    range, read clipped); a tile's rows span all of a flow's events, or,
    at 4,096 positions, half of them."""
    from linkerd_tpu.models import grouped_attention as ga
    (F, T), H, G, hd, half, rope_scale, gated, P, W = IN_TILE[kind]
    rng = np.random.default_rng(P + T)
    S, slot = slots_for(F)
    p0 = (rng.integers(0, 4 * P, F) if W else flows_at(F, T, P)).astype(
        np.int32)
    q = queries_of(F + P, F, T, H, hd, p0, half, gated, rope_scale)
    cache = jax.random.normal(jax.random.key(P), (S, 2 * G * hd, P),
                              jnp.bfloat16)
    scale = hd ** -0.5
    got, *counted, in_tile = jax.jit(functools.partial(
        fa.grouped_attention_fused, scale=scale, window=W, interpret=True))(
            q, cache, slot, p0)
    assert got.shape == (F, T, H * hd) and got.dtype == jnp.bfloat16
    assert fa.on_the_tile(T, H // G, P) == (kind != "one-event")
    assert int(in_tile) == (F * T * H if kind != "one-event" else 0)
    was, *counted_then = jax.jit(functools.partial(
        as_the_step_did, scale=scale, window=W))(q, cache, slot, p0)
    assert (np.asarray(got, np.float32) == np.asarray(was, np.float32)).all()
    for a, b in zip(counted, counted_then):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    want, *_, none = jax.jit(functools.partial(
        ga.attend_grouped_xla, scale=scale, window=W))(q, cache, slot, p0)
    assert want.shape == got.shape and int(none) == 0
    assert want.dtype == (jnp.float32 if gated else jnp.bfloat16)
    apart = ulps_apart(got, want.astype(jnp.bfloat16))
    # (6 of the full layer's 294,912 values are two apart; none elsewhere)
    assert apart.max() <= 2.0 and np.sum(apart > 1.0) <= 8, (
        apart.max(), np.sum(apart > 1.0))
    assert np.mean(apart > 0) < 0.5
    if kind == "full-two-tiles":
        assert fa._events_a_tile(T, H // G, P) == 32


def test_a_head_is_turned_with_its_own_lanes():
    """``turn_lanes``: a head of 128 is turned by itself; two heads of 64
    share the 128 lanes, each rolled against its own half; the tests'
    heads of 16 go as many as the group has."""
    assert fa.turn_lanes(128, 6) == 128 == fa.turn_lanes(64, 4)
    assert fa.turn_lanes(64, 1) == 64 and fa.turn_lanes(256, 2) == 256
    assert fa.turn_lanes(16, 4) == 64 and fa.turn_lanes(16, 16) == 128
    # three heads of 64 a group: no two of them divide it
    assert fa.turn_lanes(64, 3) == 64 and fa.turn_lanes(32, 6) == 96
    # on the tile from 16 events a tile (whole sublane tiles of bfloat16)
    assert [fa.on_the_tile(T, 8, 640) for T in (1, 8, 16, 64, 128)] == [
        False, False, True, True, True]
    assert fa.on_the_tile(64, 6, 4224) and fa._events_a_tile(64, 6, 4224) == 32


def parents_apply(layer, lp, cfg, cache, start_entry, h, call):
    """The attention operator in the order the step ran before the queries
    went to ``attend`` as projected, every pass an array of its own: turn
    ``q`` and ``k`` in float32, **cast** ``q``, append, attend over
    already-turned bfloat16 queries (``attend_grouped_xla`` handed a turn
    by nothing and no gate), **the gate in float32** on the widened
    output, ``wo``. Returns the output and the appended state."""
    from linkerd_tpu.models import grouped_attention as ga
    F, T, _ = h.shape
    H, G, hd = layer.heads, layer.kv_heads, layer.head_dim
    eps = cfg.rms_norm_eps
    x = lm._rms(h, lp["operator_norm"], eps)
    cos, sin = lm.angles(call.pos, layer.inv_freq)
    if layer.rope_scale != 1.0:
        cos, sin = cos * layer.rope_scale, sin * layer.rope_scale
    cos, sin = cos[:, :, None], sin[:, :, None]
    rotary = 2 * len(layer.inv_freq)
    q = lm._mm(x, lp["wq"]).reshape(F, T, H, hd)
    k = lm._mm(x, lp["wk"]).reshape(F, T, G, hd)
    if "q_norm" in lp:
        q, k = (lm._rms(q, lp["q_norm"], eps), lm._rms(k, lp["k_norm"], eps))
    q, k = ga.rotate(q, cos, sin, rotary), ga.rotate(k, cos, sin, rotary)
    entry = jnp.concatenate([k.reshape(F, T, G * hd), lm._mm(x, lp["wv"])],
                            -1).astype(jnp.bfloat16)
    cache, *_ = lm.append_chunk(cache, entry, start_entry, call.slot,
                                call.p0, call.count, call.begins,
                                positions_last=True,
                                ring=layer.window is not None)
    o, *_ = ga.attend_grouped_xla(
        unturned(q.astype(jnp.bfloat16), H), cache, call.slot, call.p0,
        hd ** -0.5, window=layer.window)
    assert o.dtype == jnp.bfloat16
    o = o.reshape(F, T, H, hd)
    if "wg" in lp:
        o = o.astype(jnp.float32) * jax.nn.sigmoid(
            lm._mm(x, lp["wg"]))[..., None]
    return lm._mm(o.reshape(F, T, H * hd), lp["wo"]), cache


def operator_and_parent(cfg, params, l: int, layer, seed: int = 0):
    """Layer ``l``'s operator (``cfg.operator(l).apply``) and
    ``parents_apply`` over the same call on XLA's attention: four flows'
    next 8 events (one begins, one is deep in its slot, or several times
    round its ring, one brings 3 events, one is padding) on a state of
    random entries. Returns ``((y, state, counts), (y, state))``."""
    from linkerd_tpu.models import grouped_attention as ga
    op, lp = cfg.operator(l), params["layers"][l]
    F, T = 4, 8
    rng = np.random.default_rng(seed)
    state = jax.random.normal(jax.random.key(seed), op.init(cfg).shape,
                              jnp.bfloat16)
    p0 = np.array([1, 200 if not op.ring else 5 * op.ring - 3, 17, 0],
                  np.int32)
    call = lm.Call(
        slot=np.array([1, 3, 5, cfg.slots], np.int32), p0=p0,
        count=np.array([T, T, 3, 0], np.int32),
        begins=np.array([True, False, False, False]),
        pos=jnp.asarray(p0[:, None] + np.arange(T)[None]),
        attend=ga.attend_grouped_xla, experts=None, append=lm.append_chunk)
    h = jnp.asarray(rng.normal(size=(F, T, cfg.hidden_size)), jnp.float32)
    start = state[0, :, 0]
    mine = jax.jit(lambda lp, state, h: op.apply(
        lp, cfg, state, start, h, call))(lp, state, h)
    then = jax.jit(lambda lp, state, h: parents_apply(
        layer, lp, cfg, state, start, h, call))(lp, state, h)
    return mine, then
