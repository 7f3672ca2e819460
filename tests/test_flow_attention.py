"""The fused latent attention (``ops/flow_attention.py``), interpreted on
the CPU, against XLA's ``attend_xla`` on the same inputs: alone, over
layouts, widths and flows of every length; and inside ``flow_step``, where
padding rows and empty flows meet it."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from linkerd_tpu.models import latent_moe as lm
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
    kv = jax.random.normal(k[2], (F, P, rank + rope), jnp.bfloat16)
    p0 = flows_at(F, T, P)
    want, one, whole = jax.jit(functools.partial(
        lm.attend_xla, scale=SCALE))(qa, qr, kv, p0)
    assert np.asarray(one).tolist() == [1] * F and whole == 1
    got, seen, whole = jax.jit(functools.partial(
        fa.latent_attention_fused, scale=SCALE, interpret=True))(
            qa, qr, kv, p0)
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
