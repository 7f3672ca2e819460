"""The routed experts as one grouped product: two Pallas kernels over the
sorted, tile-padded pairs of ``models/latent_moe.routed_experts``.

``routed_experts`` sorts a call's (token, expert) pairs by expert into
tiles of ``expert_tile`` rows, each tile of one expert, and hands a run of
consecutive tiles here: ``xs [tiles x M, hidden]`` the tiles' rows
(bfloat16), ``wt`` each row's routing weight, ``tile_expert [tiles]`` which
held expert a tile is of, ``live`` how many of the tiles hold a pair (the
first ``live``), and a layer's three expert tensors whole. The product
(``swiglu_tiles_fused``) gives back ``wt * (silu(x W_gate) * (x W_up))
W_down`` a row in float32 (bfloat16 inputs, float32 accumulation, the
gated product rounded to bfloat16 once before ``W_down``, as
``models.latent_moe._swiglu`` and ``swiglu_tiles_xla``, the XLA form this
is tested against, compute it); the combine (``add_rows_fused``) adds those
rows to their tokens' rows of the layer's output.

Until PR 33 both were one ``fori_loop`` over the tiles in XLA, whose body
sliced the tile's expert out of the three tensors every trip (18.9 MB a
tile at LFM2's widths although consecutive tiles are mostly of one expert,
fetched before the trip's matmuls could start, since a ``while`` body
overlaps nothing with the trip before it), gathered the tile's 128 rows
and scatter-added 128 float32 rows: 7.7 ms a layer of the benchmark's LFM2
cell where the FLOPs are 1.6 and the layer's weights read once 1.5
(ledger, PR 32). Here:

- **The product's grid is (tile, block of the intermediate columns)**, its
  first extent ``live``, a value of the call (Mosaic takes a dynamic grid
  bound): a tile that holds no pair is no grid step, costs nothing and
  moves nothing. ``R``, the rows the sort may need at worst, only sizes
  the small arrays of the placement.
- **An expert's weights are fetched once for all its tiles, and the next
  expert's under this one's matmuls.** The three tensors stay in HBM; a
  block of ``bi`` columns of ``W_gate`` and ``W_up`` and the same ``bi``
  rows of ``W_down`` comes by the kernel's own copies into one of two
  VMEM slots, by a schedule made from ``tile_expert`` beforehand
  (``fetches``, scalar prefetch): a step that needs a block the step
  before it did not hold waits for it and starts the fetch *after* it
  into the other slot. ``bi`` is the widest that lets two blocks of the
  three matrices lie in VMEM together (``column_block``). Where that is
  the whole intermediate width (LFM2: 2,048 x 1,536, 18.9 MB an expert)
  the fresh steps are the first tiles of the runs of one expert, so the
  look-ahead is a whole expert's tiles and not one grid step (Pallas's
  own pipeline looks one step ahead and supports no more in this
  version: the same kernel on block specs took 3.14 ms for 160 tiles of
  64 experts against 2.46; my chip runs, PR 33); where an expert is wider
  (Kimi: 7,168 x 2,048, four blocks of 22 MB) every step is fresh and the
  schedule is the pipeline's. ``weight_loads`` counts the fresh steps.
  A step forms the block's gated product ``[M, bi]`` and adds its part
  of the output, which stays in VMEM across the tile's blocks.
- **The combine keeps a block of the output's columns in VMEM** while
  the rows of every tile are added to it, each at the sublane its token
  gives, and writes it once: XLA's scatter-add of rows this wide takes a
  microsecond a row.

``best_expert_product`` selects by platform as
``ops/flow_attention.best_attention`` does: these kernels on ``tpu``, the
XLA forms elsewhere; no probe and no fallback. CPU tests run the kernels
with ``interpret=True``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

WEIGHT_BLOCK_BYTES = 48 * 2 ** 20   # two blocks of the three matrices
OUT_BLOCK_BYTES = 72 * 2 ** 20      # a block of the output, in and out
VMEM_LIMIT = 100 * 2 ** 20          # of a v5e's 128 MiB
ROWS_A_TRIP = 8     # rows of a tile the combine adds a trip of its loop


def column_block(D: int, I: int, budget: int = WEIGHT_BLOCK_BYTES) -> int:
    """Columns of the intermediate width ``I`` a block of an expert's
    matrices holds: the most that divides ``I`` in whole lane tiles and
    lets two blocks of the three bfloat16 matrices ``[D, bi]``, ``[D,
    bi]``, ``[bi, D]`` fit ``budget``; ``I`` whole where it fits (or is no
    multiple of 128 lanes)."""
    fits = [bi for bi in range(128, I + 1, 128)
            if I % bi == 0 and 2 * 3 * D * bi * 2 <= budget]
    return max(fits) if I % 128 == 0 and fits else I


def fetches(tile_expert, live, blocks: int):
    """The kernel's schedule of weight fetches, a step of the grid ``(tile,
    block)`` each (``s = tile x blocks + block``): ``fresh [S]`` whether
    step ``s`` needs a block of weights that the step before it did not
    hold (always, where an expert comes in several blocks; where it is one
    block, at the first tile of a run of tiles of one expert), ``slot [S]``
    which of the two VMEM slots holds the step's block (a fetch's number,
    alternating), and ``ahead [S, 2]`` the ``(expert, block)`` of the fetch
    after the step's own, ``-1`` where there is none: what a fresh step
    starts fetching into the other slot while it computes. Steps of tiles
    past ``live`` are never fresh."""
    steps = jnp.arange(tile_expert.shape[0] * blocks)
    expert, block = tile_expert[steps // blocks], steps % blocks
    fresh = (steps < live * blocks) & (
        (steps == 0) | (expert != jnp.roll(expert, 1))
        | (block != jnp.roll(block, 1)))
    slot = (jnp.cumsum(fresh) - 1) % 2
    # the next fresh step after each step, found from the back
    at = jnp.where(fresh, steps, steps.size)
    nxt = jnp.concatenate([jax.lax.cummin(at[::-1])[::-1][1:],
                           jnp.array([steps.size])])
    there = nxt < steps.size
    to = jnp.minimum(nxt, steps.size - 1)
    ahead = jnp.where(there[:, None],
                      jnp.stack([expert[to], block[to]], -1), -1)
    return (fresh.astype(jnp.int32), slot.astype(jnp.int32),
            ahead.astype(jnp.int32))


def _kernel(fresh_ref, slot_ref, ahead_ref, first_ref, x_ref, wt_ref,
            gate_hbm, up_hbm, down_hbm, o_ref, gate_v, up_v, down_v, sem,
            *acc, limit=None):
    """One tile of ``M`` rows of one expert, one block of ``bi``
    intermediate columns. The weights stay in HBM and come by the kernel's
    own copies into two VMEM slots: a step that needs a fresh block waits
    for it (it was started by the fresh step before it, or just now by
    the first step) and starts the next one. ``limit``: the SwiGLU's clamp
    (``models.latent_moe._swiglu``), or None."""
    blocks, bi = pl.num_programs(1), gate_v.shape[-1]
    j = pl.program_id(1)
    s = pl.program_id(0) * blocks + j
    slot = slot_ref[s]

    def fetch(expert, block, slot):
        at = pl.multiple_of(block * bi, bi)
        return [pltpu.make_async_copy(
                    gate_hbm.at[expert, :, pl.ds(at, bi)], gate_v.at[slot],
                    sem.at[slot, 0]),
                pltpu.make_async_copy(
                    up_hbm.at[expert, :, pl.ds(at, bi)], up_v.at[slot],
                    sem.at[slot, 1]),
                pltpu.make_async_copy(
                    down_hbm.at[expert, pl.ds(at, bi), :], down_v.at[slot],
                    sem.at[slot, 2])]

    @pl.when(s == 0)
    def _():
        for copy in fetch(first_ref[0], 0, slot):
            copy.start()

    @pl.when(fresh_ref[s] == 1)
    def _():
        # a wait takes its size from the copy's shape: any block's will do
        for copy in fetch(0, 0, slot):
            copy.wait()

        @pl.when(ahead_ref[s, 0] >= 0)
        def _():
            for copy in fetch(ahead_ref[s, 0], ahead_ref[s, 1], 1 - slot):
                copy.start()

    x = x_ref[...]
    g = jnp.dot(x, gate_v[slot], preferred_element_type=jnp.float32)
    u = jnp.dot(x, up_v[slot], preferred_element_type=jnp.float32)
    if limit is not None:
        g, u = jnp.minimum(g, limit), jnp.clip(u, -limit, limit)
    y = jnp.dot((jax.nn.silu(g) * u).astype(x.dtype), down_v[slot],
                preferred_element_type=jnp.float32)
    if not acc:             # the expert is one block
        o_ref[...] = y * wt_ref[...]
        return
    acc_ref, = acc

    @pl.when(j == 0)
    def _():
        acc_ref[...] = y

    @pl.when(j > 0)
    def _():
        acc_ref[...] += y

    @pl.when(j == blocks - 1)
    def _():
        o_ref[...] = acc_ref[...] * wt_ref[...]


@functools.partial(jax.jit, static_argnames=("interpret", "budget", "limit"))
def swiglu_tiles_fused(xs, wt, tile_expert, live, gate, up, down,
                       interpret: bool = False,
                       budget: int = WEIGHT_BLOCK_BYTES, limit=None):
    """``xs [tiles x M, D]`` bfloat16, ``wt [tiles x M]`` float32,
    ``tile_expert [tiles]`` int32 (in ``0 .. G - 1``), ``live`` int32: the
    first ``live`` tiles hold a pair; ``gate``, ``up [G, D, I]``, ``down
    [G, I, D]`` bfloat16. Returns ``(y [tiles x M, D]`` float32, the
    rows of the first ``live`` tiles computed and weighed and the others
    **unspecified** (no grid step writes them), the whole-expert
    equivalents of weights fetched``: the schedule's fresh steps over the
    blocks an expert comes in)``. ``limit``: the SwiGLU's clamp
    (``models.latent_moe._swiglu``), or None. Jitted, so that a step of several expert
    layers traces and lowers the kernel once."""
    rows, D = xs.shape
    tiles = tile_expert.shape[0]
    M, I = rows // tiles, gate.shape[-1]
    bi = column_block(D, I, budget)
    blocks = I // bi
    tile_expert = tile_expert.astype(jnp.int32)
    fresh, slot, ahead = fetches(tile_expert, live, blocks)
    y = pl.pallas_call(
        _kernel if limit is None else functools.partial(_kernel, limit=limit),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(live, blocks),
            in_specs=[pl.BlockSpec((M, D), lambda t, j, *_: (t, 0)),
                      pl.BlockSpec((M, 1), lambda t, j, *_: (t, 0)),
                      pl.BlockSpec(memory_space=pl.ANY),
                      pl.BlockSpec(memory_space=pl.ANY),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((M, D), lambda t, j, *_: (t, 0)),
            scratch_shapes=[pltpu.VMEM((2, D, bi), gate.dtype),
                            pltpu.VMEM((2, D, bi), up.dtype),
                            pltpu.VMEM((2, bi, D), down.dtype),
                            pltpu.SemaphoreType.DMA((2, 3))]
            + ([pltpu.VMEM((M, D), jnp.float32)] if blocks > 1 else [])),
        out_shape=jax.ShapeDtypeStruct((rows, D), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
        name="swiglu_tiles_fused",
    )(fresh, slot, ahead, tile_expert[:1], xs, wt[:, None], gate, up, down)
    return y, fresh.sum() // blocks


def row_block(N: int, D: int, budget: int = OUT_BLOCK_BYTES) -> int:
    """Columns of ``out [N, D]`` float32 that ``add_rows_fused`` keeps in
    VMEM at a time: the most that divides ``D`` in whole lane tiles and
    fits ``budget`` four times over (the pipeline's two buffers of the
    block coming in and of the block going out); ``D`` whole where it is
    no multiple of 128 lanes."""
    fits = [cb for cb in range(128, D + 1, 128)
            if D % cb == 0 and 4 * N * cb * 4 <= budget]
    return max(fits) if D % 128 == 0 and fits else D


def _add_rows_kernel(tok_ref, y_ref, prev_ref, o_ref):
    """One block of ``cb`` columns of ``out``, which stays in VMEM over
    the inner axis of the grid: the tiles. A tile's rows are added to
    their tokens' rows of it one by one, each a load, an add and a store
    of ``cb / 128`` vector registers at the sublane the token gives."""
    t = pl.program_id(1)
    M = y_ref.shape[0]
    N = o_ref.shape[0]

    @pl.when(t == 0)
    def _():
        o_ref[...] = prev_ref[...]

    def add(trip, _):
        # eight rows (a sublane tile of ``y``) a trip, under one branch:
        # all 128 rows of a tile unrolled, or a branch a row, cost half a
        # second of every process's set-up to trace and lower
        at = trip * ROWS_A_TRIP
        toks = [tok_ref[t * M + at + r] for r in range(ROWS_A_TRIP)]

        @pl.when(functools.reduce(jnp.minimum, toks) < N)
        def _():
            for r, tok in enumerate(toks):
                # a row that is no one's adds nought to the last row
                row = jnp.where(tok < N, y_ref[pl.ds(at + r, 1), :], 0.0)
                o_ref[pl.ds(jnp.minimum(tok, N - 1), 1), :] += row

    jax.lax.fori_loop(0, M // ROWS_A_TRIP, add, None)


@functools.partial(jax.jit, static_argnames=("interpret", "budget"))
def add_rows_fused(y, tok, live, out, interpret: bool = False,
                   budget: int = OUT_BLOCK_BYTES):
    """``out [N, D]`` float32 with the rows of the first ``live`` tiles of
    ``y [tiles x M, D]`` added to their tokens' rows: row ``i`` of tile
    ``t`` to row ``tok[t, i]`` (``tok [tiles, M]`` int32; ``N`` or more:
    the row is no one's), as ``models.latent_moe.add_rows_xla`` gives it,
    in ``out``'s own buffer. The grid is (block of ``row_block`` columns,
    tile) with ``live`` its second extent: a block of ``out`` is read
    once, lies in VMEM while every tile's rows of those columns are added
    to it, and is written once. (XLA's scatter-add of rows this wide
    takes a microsecond a row: 4.0 ms for 2,304 rows of 7,168, 0.93 for
    8,192 of 2,048; my chip runs, PR 33.)"""
    D = y.shape[1]
    N = out.shape[0]
    tiles, M = tok.shape
    if M % ROWS_A_TRIP:
        raise ValueError(f"a tile of {M} rows: no multiple of {ROWS_A_TRIP}")
    cb = row_block(N, D, budget)
    return pl.pallas_call(
        _add_rows_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(D // cb, live),
            in_specs=[pl.BlockSpec((M, cb), lambda c, t, tok: (t, c)),
                      pl.BlockSpec((N, cb), lambda c, t, tok: (0, c))],
            out_specs=pl.BlockSpec((N, cb), lambda c, t, tok: (0, c))),
        out_shape=jax.ShapeDtypeStruct((N, D), jnp.float32),
        input_output_aliases={2: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
        name="add_rows_fused",
    )(tok.reshape(-1).astype(jnp.int32), y, out)


def expert_product_kind(platform: str) -> str:
    """Which grouped product ``best_expert_product`` hands the flow step
    on ``platform``: ``"fused_pallas"`` on a TPU, ``"xla"`` elsewhere."""
    return "fused_pallas" if platform == "tpu" else "xla"


def best_expert_product(platform: str):
    """The flow step's ``experts`` (``models.latent_moe.ExpertOps``) for
    parameters living on ``platform``: the two kernels on ``tpu``, the XLA
    loop and XLA's scatter-add elsewhere (an interpreted kernel is far too
    slow to serve)."""
    from linkerd_tpu.models.latent_moe import ExpertOps
    if expert_product_kind(platform) == "fused_pallas":
        return ExpertOps(swiglu_tiles_fused, add_rows_fused)
    return ExpertOps()
