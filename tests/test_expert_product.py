"""The routed experts' grouped product and combine
(``ops/expert_product.py``): the two Pallas kernels, interpreted on the CPU
at small widths, against the XLA forms (``models.latent_moe.
swiglu_tiles_xla``, ``add_rows_xla``) on the same sorted rows; the
schedule's count of weights brought to the chip; and ``routed_experts``
whole through both forms."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from linkerd_tpu.models import latent_moe as lm
from linkerd_tpu.ops import expert_product as ep

FUSED = functools.partial(ep.swiglu_tiles_fused, interpret=True)
KERNELS = lm.ExpertOps(FUSED,
                       functools.partial(ep.add_rows_fused, interpret=True))
# both models' (hidden, moe_intermediate), a sixteenth and a twenty-eighth:
# LFM2's 2,048 x 1,536 and Kimi's 7,168 x 2,048
WIDTHS = {"lfm2": (128, 96), "kimi": (256, 128)}
G = 6
# pairs an expert holds -> which kinds of group the tiles are of
GROUPS = {
    "some_experts_empty": [0, 5, 0, 11, 3, 0],
    "exactly_one_tile": [8, 8, 8, 8, 8, 8],
    "several_tiles": [20, 17, 3, 30, 9, 16],
    "last_tile_partly_filled": [8, 16, 24, 8, 16, 5],
    "no_pair_at_all": [0, 0, 0, 0, 0, 0],
    "every_pair_on_one_expert": [0, 0, 37, 0, 0, 0],
}


def sorted_rows(counts, M, D, slack=2, seed=0):
    """The rows ``routed_experts`` would hand the product for experts
    holding ``counts`` pairs: each expert's pairs from a tile's edge, the
    padding rows zero with weight 0, ``slack`` tiles that hold no pair at
    the end."""
    tiles = [-(-c // M) for c in counts]
    live = sum(tiles)
    rows = (live + slack) * M
    rng = np.random.default_rng(seed)
    xs = np.zeros((rows, D), np.float32)
    wt = np.zeros((rows,), np.float32)
    te, at = [], 0
    for e, (c, t) in enumerate(zip(counts, tiles)):
        xs[at:at + c] = rng.standard_normal((c, D))
        wt[at:at + c] = rng.uniform(0.1, 1.0, c)
        te += [e] * t
        at += t * M
    te += [len(counts) - 1] * slack     # as the sort's map gives them
    return (jnp.asarray(xs, jnp.bfloat16), jnp.asarray(wt),
            jnp.asarray(te, jnp.int32), jnp.int32(live))


def weights(D, I, seed=1):
    k = jax.random.split(jax.random.key(seed), 3)

    def mat(k, i, o):
        return (jax.random.normal(k, (G, i, o)) / np.sqrt(i)
                ).astype(jnp.bfloat16)

    return mat(k[0], D, I), mat(k[1], D, I), mat(k[2], I, D)


@pytest.mark.parametrize("widths", sorted(WIDTHS))
@pytest.mark.parametrize("M", [8, 128])
@pytest.mark.parametrize("groups", sorted(GROUPS))
def test_kernel_agrees_with_the_xla_form_on_the_same_sorted_rows(
        groups, M, widths):
    """Every row of a tile that holds a pair: float32 accumulation over
    the same bfloat16 products, so the two differ by the order of the
    sums alone. Rows of the tiles past ``live`` are no one's."""
    D, I = WIDTHS[widths]
    counts = [c * M // 8 for c in GROUPS[groups]]
    xs, wt, te, live = sorted_rows(counts, M, D)
    gate, up, down = weights(D, I)
    want, loops = lm.swiglu_tiles_xla(xs, wt, te, live, gate, up, down)
    got, loads = FUSED(xs, wt, te, live, gate, up, down)
    n = int(live) * M
    assert got.shape == want.shape == xs.shape and got.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(got)[:n], np.asarray(want)[:n],
                               atol=2e-5 * np.sqrt(I), rtol=1e-5)
    if n:
        assert np.abs(np.asarray(want)[:n]).max() > 0.1
    # padding rows inside a live tile weigh nothing
    assert not np.asarray(got)[:n][np.asarray(wt)[:n] == 0].any()
    # the loop reads an expert a tile, the kernel an expert it meets
    assert int(loops) == int(live)
    assert int(loads) == sum(c > 0 for c in counts)


@pytest.mark.parametrize("widths", sorted(WIDTHS))
def test_an_expert_in_several_blocks_gives_the_same_rows(widths):
    """Where two blocks of an expert whole pass the VMEM budget (Kimi's
    7,168 x 2,048) the intermediate columns come in blocks and a tile's
    output is summed over them in VMEM: the same rows, and every tile
    fetches its expert."""
    D, I = WIDTHS[widths]
    I *= 4                              # 384 = 3 x 128, 512 = 4 x 128
    xs, wt, te, live = sorted_rows(GROUPS["several_tiles"], 8, D)
    gate, up, down = weights(D, I)
    budget = 2 * 3 * D * 128 * 2        # one lane tile of columns a block
    assert ep.column_block(D, I, budget) == 128
    want, _ = lm.swiglu_tiles_xla(xs, wt, te, live, gate, up, down)
    got, loads = ep.swiglu_tiles_fused(xs, wt, te, live, gate, up, down,
                                       interpret=True, budget=budget)
    n = int(live) * 8
    np.testing.assert_allclose(np.asarray(got)[:n], np.asarray(want)[:n],
                               atol=2e-5 * np.sqrt(I), rtol=1e-5)
    assert int(loads) == int(live)


@pytest.mark.parametrize("D,I,want", [
    (2048, 1536, 1536),     # LFM2: an expert whole, 37.7 MB twice over
    (7168, 2048, 512),      # Kimi: four blocks of 22 MB
    (64, 32, 32),           # no multiple of 128 lanes: whole
    (7168, 2000, 2000),
])
def test_column_block_follows_the_widths(D, I, want):
    assert ep.column_block(D, I) == want
    assert I % want == 0


@pytest.mark.parametrize("blocks,tile_expert,live,want", [
    (1, [0, 0, 1, 3, 3, 3, 5, 5], 7, 4),    # one an expert met
    (1, [0, 0, 1, 3, 3, 3, 5, 5], 2, 1),    # only the tiles that ran
    (1, [2, 2, 2, 2], 0, 0),
    (1, [4, 4, 4, 4], 4, 1),
    (4, [0, 0, 1, 3, 3, 3, 5, 5], 7, 7),    # several blocks: one a tile
    (4, [2, 2, 2, 2], 3, 3),
])
def test_the_schedule_fetches_a_block_once_for_the_steps_that_share_it(
        blocks, tile_expert, live, want):
    """``fetches``: a step is fresh where the block it needs is not the
    one the step before it held; the fresh steps over the blocks an
    expert comes in are the whole experts read; fetches alternate
    between the two slots, and each fresh step names the next fetch."""
    te = jnp.asarray(tile_expert, jnp.int32)
    fresh, slot, ahead = (np.asarray(a) for a in ep.fetches(
        te, jnp.int32(live), blocks))
    assert fresh.sum() // blocks == want and fresh.sum() % blocks == 0
    at = np.flatnonzero(fresh)
    assert (slot[at] == np.arange(len(at)) % 2).all()
    # steps that share a block read the slot it was fetched into
    run = np.cumsum(fresh) - 1
    assert (slot[:live * blocks] == run[:live * blocks] % 2).all()
    for i, s in enumerate(at):
        if i + 1 < len(at):
            nxt = at[i + 1]
            assert ahead[s].tolist() == [tile_expert[nxt // blocks],
                                         nxt % blocks]
        else:
            assert ahead[s].tolist() == [-1, -1]


@pytest.mark.parametrize("D,budget", [(128, ep.OUT_BLOCK_BYTES),
                                      (512, 4 * 40 * 128 * 4)])
@pytest.mark.parametrize("M,live", [(8, 5), (8, 1), (8, 7), (128, 2)])
def test_combine_agrees_with_xlas_scatter_add(M, live, D, budget):
    """A token's rows from several tiles, rows that are no one's (``N``),
    tiles past ``live`` left alone, added to what ``out`` held; the
    output in one block of columns and in four (the second budget: 128
    columns a block)."""
    N, tiles = 40, 7
    y = jax.random.normal(jax.random.key(0), (tiles * M, D))
    tok = jax.random.randint(jax.random.key(1), (tiles, M), 0, N + N // 4)
    tok = jnp.minimum(tok, N)
    out = jax.random.normal(jax.random.key(2), (N, D))
    assert ep.row_block(N, D, budget) == min(D, 128 if D == 512 else D)
    want = lm.add_rows_xla(y, tok, jnp.int32(live), out)
    got = ep.add_rows_fused(y, tok, jnp.int32(live), out,
                            interpret=True, budget=budget)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)
    assert np.abs(np.asarray(want) - np.asarray(out)).max() > 1


def test_selection_is_by_platform_alone():
    assert ep.expert_product_kind("tpu") == "fused_pallas"
    assert ep.expert_product_kind("cpu") == "xla"
    assert ep.best_expert_product("tpu") == lm.ExpertOps(
        ep.swiglu_tiles_fused, ep.add_rows_fused)
    assert ep.best_expert_product("cpu") == lm.ExpertOps(
        lm.swiglu_tiles_xla, lm.add_rows_xla)


CFG = lm.LatentMoEConfig(
    hidden_size=64, num_attention_heads=4, q_lora_rank=32, kv_lora_rank=16,
    qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
    intermediate_size=128, moe_intermediate_size=32, n_routed_experts=16,
    num_experts_per_tok=4, layers=2, experts_held=(4, 12), vocab_slice=128,
    slots=8, positions=64, expert_tile=8)


@pytest.mark.parametrize("product", ["xla", "fused"])
@pytest.mark.parametrize("chunk_bytes", [lm.CHUNK_BYTES, 3 * 8 * 64 * 4])
def test_routed_experts_in_runs_of_tiles(product, chunk_bytes, monkeypatch):
    """The layer whole against the masked sum over every held expert,
    through both forms, in one run of all the tiles and in runs of 3 (the
    last partly live): the same output, the pairs counted, and the count
    of weights read by the form's own schedule."""
    monkeypatch.setattr(lm, "CHUNK_BYTES", chunk_bytes)
    lp = lm.init(jax.random.key(5), CFG)["layers"][1]
    x = jax.random.normal(jax.random.key(6), (50, CFG.hidden_size))
    valid = jnp.arange(50) < 47
    out, cnt, loads = lm.routed_experts(
        lp, CFG, x, valid, KERNELS if product == "fused"
        else lm.ExpertOps())
    lo, hi = CFG.experts_held
    idx, w = (np.asarray(a) for a in lm.route(lp, CFG, x))
    want = sum(
        np.where((idx == e) & np.asarray(valid)[:, None], w, 0
                 ).sum(1, keepdims=True)
        * np.asarray(lm._swiglu(x, lp["exp_gate"][e - lo],
                                lp["exp_up"][e - lo], lp["exp_down"][e - lo]))
        for e in range(lo, hi))
    np.testing.assert_allclose(np.asarray(out), want, atol=2e-5)
    assert not np.asarray(out)[47:].any()
    held = (idx >= lo) & (idx < hi) & np.asarray(valid)[:, None]
    assert int(cnt.sum()) == held.sum()
    tiles = -(-np.asarray(cnt) // CFG.expert_tile)
    run = max(1, chunk_bytes // (8 * 64 * 4))
    starts = -(-int(tiles.sum()) // run) - 1    # runs after the first
    if product == "xla":
        assert int(loads) == tiles.sum()
    else:
        # one an expert with a pair; an expert whose tiles lie in two runs
        # is read in both
        hit = int((tiles > 0).sum())
        assert hit <= int(loads) <= hit + starts
