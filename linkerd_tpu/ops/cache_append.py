"""The attention operators' append as one Pallas pass of whole lane tiles:
a call's chunk of entries set into each flow's slot of a layer's state,
**written where the state lies** (the donated cache or ring is the kernel's
output, aliased), a flow's one or two tiles of 128 positions read, merged
and written back whole.

``models.latent_moe.append_chunk`` does the same in XLA: it reads a window
of ``W = T + 1`` positions of every flow's slot, sets the chunk into it and
scatters the window back, and on a ring does all of that a second time for
the chunks that pass the ring's end. XLA makes each window a loop of ``F``
reads and a loop of ``F`` writes of ``[entry, W]`` at an unaligned lane
offset: 1.1-1.2 ms a layer of the Laguna cell's caches, 2.1-2.25 a ring
(two windows), where the bytes take 0.04 (one TPU v5e).

Here a grid step is one flow (``slot``, ``p0``, ``count`` and ``begins`` by
scalar prefetch) and the state stays in HBM (``memory_space=pl.ANY``):

- **What moves**: the whole tiles ``[entry, 128]`` of the flow's slot that
  its window ``[w0, w0 + W)`` touches (``window_tiles``: the window begins
  at ``p0 - 1``; a cache pushes it back where it would pass the slot's
  end, as XLA's does), one or two of them at the cells' ``W = 65``. On a
  ring a tile's index is taken modulo the ring's tiles, so **the chunks
  that pass the ring's end are written in the same pass** as the rest.
- **By the kernel's own copies, two flows deep**: flow ``f + 1``'s tiles
  are fetched into the other of two VMEM buffers while flow ``f`` is
  merged and written back (a call's flows have slots of their own: no
  flow reads what another writes). A flow whose slot is out of range (a
  flow of the layout that brings nothing) reads and writes nothing.
- **The merge on the tile**: the chunk's entries, ``[entry, T]`` flows
  side by side along the lanes (XLA lays them so before the call, inside
  the ``append`` scope), are rolled to the lanes of their positions and
  selected where ``t = position - p0`` (modulo a ring) lies in ``[0,
  count)``; the start token's entry goes at position 0 where the flow
  ``begins``; every other value of a tile is written back as it was read.

The state lies ``[slots, entry, positions]`` (the grouped operator keeps it
so) or ``[slots, positions, entry]`` (the latent cache, which the compiler
stores positions-minor: the kernel takes ``cache.transpose(0, 2, 1)``, a
bitcast of that layout, as ``ops/flow_attention.latent_attention_fused``
does). The kernel serves a state whose positions are whole tiles, a chunk
of at most one tile and buffers that fit ``APPEND_VMEM`` (``serves``: the
shapes decide, nothing else); any other shape takes ``append_chunk``.
``best_append`` selects by platform as ``best_attention`` does: this kernel
on ``tpu``, ``append_chunk`` elsewhere; no probe and no fallback. CPU tests
run the kernel with ``interpret=True``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128                 # positions a tile of the state
APPEND_VMEM = 8 * 2 ** 20   # two flows' tiles
VMEM_LIMIT = 16 * 2 ** 20   # and the blocks of entries: 4 MiB at the cells


def tiles_at_most(W: int, P: int) -> int:
    """Tiles a window of ``W`` positions of a slot of ``P`` touches at
    most: ``W`` from the last position of a tile on."""
    return min((W + 2 * LANES - 2) // LANES, P // LANES)


def serves(shape, T: int, positions_last: bool, ring: bool,
           itemsize: int = 2) -> bool:
    """Whether the kernel takes a state of ``shape`` and chunks of ``T``:
    positions in whole tiles, chunks that divide a tile (a flow's entries
    then lie in one tile of the call's), on a ring a window that touches
    no tile twice, rows that pack into 32-bit words (``_roll_lanes``), and two
    flows' tiles within ``APPEND_VMEM``."""
    _, E, P = shape if positions_last else (shape[0], shape[2], shape[1])
    W = min(T + 1, P)
    K = tiles_at_most(W, P)
    return (P % LANES == 0 and LANES % T == 0 and E * itemsize % 4 == 0
            and not (ring and (W + 2 * LANES - 2) // LANES > P // LANES)
            and 2 * K * E * LANES * itemsize <= APPEND_VMEM)


def window_tiles(p0, P: int, W: int, ring: bool):
    """``(first tile, tiles)`` of the window a flow's chunk is set into: from
    ``p0 - 1`` (the start token's position, or the one before the chunk),
    on a cache pushed back to end at the slot's end at the latest, on a
    ring from ``(p0 - 1) mod P`` on and round it."""
    if ring:
        w0 = jax.lax.rem(p0 - 1 + P, P)
    else:
        w0 = jnp.minimum(jnp.maximum(p0 - 1, 0), P - W)
    return w0 // LANES, (jax.lax.rem(w0, LANES) + W + LANES - 1) // LANES


def _roll_lanes(x, shift):
    """``x [rows, 128]`` rolled ``shift`` lanes up (``jnp.roll``'s sense).
    Mosaic rotates 32-bit words alone: narrower values are rolled as the
    words that pack rows in pairs (a lane's pair moves as one)."""
    bits = jnp.dtype(x.dtype).itemsize * 8
    if bits == 32:
        return pltpu.roll(x, shift, 1)
    return pltpu.bitcast(pltpu.roll(pltpu.bitcast(x, jnp.uint32), shift, 1),
                         x.dtype)


def _kernel(slot_ref, p0_ref, count_ref, begins_ref, entry_ref, start_ref,
            cache_ref, out_ref, buf, reads, writes, *, T: int, W: int,
            ring: bool):
    """One flow: wait for its tiles (started by the step before), start
    the next flow's into the other buffer once that buffer's write-back
    is done, merge, start the write-back."""
    del cache_ref       # aliased: ``out_ref`` is the same array
    f, flows = pl.program_id(0), pl.num_programs(0)
    S, _, P = out_ref.shape
    K = buf.shape[1]
    b = jax.lax.rem(f, 2)

    def tile_of(first, k):
        return jax.lax.rem(first + k, P // LANES) if ring else first + k

    def each_tile(g, do):
        """``do(k, tile index, HBM view)`` for each tile flow ``g`` moves."""
        first, n = window_tiles(p0_ref[g], P, W, ring)
        live = slot_ref[g] < S
        slot = jnp.minimum(slot_ref[g], S - 1)
        for k in range(K):
            @pl.when(live & (k < n))
            def _(k=k):
                tile = tile_of(first, k)
                at = pl.multiple_of(tile * LANES, LANES)
                do(k, tile, out_ref.at[slot, :, pl.ds(at, LANES)])

    def fetch(into):        # a tile of the slot into buffer ``into``
        return lambda k, _, hbm: pltpu.make_async_copy(
            hbm, buf.at[into, k], reads.at[into, k])

    def store(frm):         # and back from buffer ``frm``
        return lambda k, _, hbm: pltpu.make_async_copy(
            buf.at[frm, k], hbm, writes.at[frm, k])

    @pl.when(f == 0)
    def _():
        each_tile(0, lambda *a: fetch(0)(*a).start())

    @pl.when(f > 0)
    def _():
        each_tile(f - 1, lambda *a: store(1 - b)(*a).wait())

    @pl.when(f + 1 < flows)
    def _():
        each_tile(f + 1, lambda *a: fetch(1 - b)(*a).start())

    each_tile(f, lambda *a: fetch(b)(*a).wait())

    p0, count = p0_ref[f], count_ref[f]
    begins = begins_ref[f] != 0
    # the flow's entries lie at lane ``(f x T) mod 128`` of their tile of
    # the call's: rolled so that the entry of position ``p`` is at lane ``p
    # mod 128``
    shift = jax.lax.rem(jax.lax.rem(p0, LANES) - jax.lax.rem(f * T, LANES)
                        + LANES, LANES)
    placed = _roll_lanes(entry_ref[...], shift)
    start = start_ref[...]
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, LANES), 1)
    held = jax.lax.rem(p0, P)

    def merge(k, tile, _):
        pos = tile * LANES + lane
        if ring:
            t = pos - held
            t = jnp.where(t < 0, t + P, t)
        else:
            t = pos - p0
        x = jnp.where((t >= 0) & (t < count), placed, buf[b, k])
        buf[b, k] = jnp.where(begins & (pos == 0), start, x)

    each_tile(f, merge)
    each_tile(f, lambda *a: store(b)(*a).start())

    @pl.when(f == flows - 1)
    def _():
        each_tile(f, lambda *a: store(b)(*a).wait())


@functools.partial(jax.jit,
                   static_argnames=("positions_last", "ring", "interpret"))
def cache_append_fused(cache, entry, start_entry, slot, p0, count, begins,
                       positions_last: bool = False, ring: bool = False,
                       interpret: bool = False):
    """``append_chunk``'s signature and result, by the kernel where it
    ``serves`` the shapes (else by ``append_chunk``): the call's entries
    ``entry [F, T, entry]`` into the layer's ``cache`` in place, flow
    ``f``'s at ``(slot[f], p0[f] + t)`` for ``t < count[f]`` (on a ring at
    ``p0[f] + t mod positions``), the start token's ``start_entry`` at
    position 0 where it ``begins``; every other value as it was. Returns
    the cache, the rows written (``128`` a tile moved: whole tiles) and the
    flows the kernel appended (those with a slot in range; none on XLA's
    path). Jitted, so that a step of several layers traces and lowers the
    kernel once a kind of state."""
    from linkerd_tpu.models.latent_moe import append_chunk
    F, T, E = entry.shape
    if not serves(cache.shape, T, positions_last, ring,
                  jnp.dtype(cache.dtype).itemsize):
        return append_chunk(cache, entry, start_entry, slot, p0, count,
                            begins, positions_last=positions_last, ring=ring)
    kt = cache if positions_last else cache.transpose(0, 2, 1)
    S, _, P = kt.shape
    W = min(T + 1, P)
    K = tiles_at_most(W, P)
    # the flows' entries side by side along the lanes, a whole tile at least
    lanes = entry.reshape(F * T, E).T.astype(kt.dtype)
    if F * T < LANES:
        lanes = jnp.pad(lanes, ((0, 0), (0, LANES - F * T)))
    slot, p0, count = (a.astype(jnp.int32) for a in (slot, p0, count))
    out = pl.pallas_call(
        functools.partial(_kernel, T=T, W=W, ring=ring),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(F,),
            in_specs=[pl.BlockSpec((E, LANES),
                                   lambda f, *_: (0, f * T // LANES)),
                      pl.BlockSpec((E, 1), lambda f, *_: (0, 0)),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec(memory_space=pl.ANY),
            scratch_shapes=[pltpu.VMEM((2, K, E, LANES), kt.dtype),
                            pltpu.SemaphoreType.DMA((2, K)),
                            pltpu.SemaphoreType.DMA((2, K))]),
        out_shape=jax.ShapeDtypeStruct(kt.shape, kt.dtype),
        input_output_aliases={6: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
        name="cache_append_fused",
    )(slot, p0, count, begins.astype(jnp.int32), lanes,
      start_entry.astype(kt.dtype)[:, None], kt)
    live = slot < S
    _, n = window_tiles(p0, P, W, ring)
    return (out if positions_last else out.transpose(0, 2, 1),
            jnp.where(live, n, 0).sum() * LANES, live.sum())


def append_kind(platform: str) -> str:
    """Which append ``best_append`` hands the flow step on ``platform``:
    ``"fused_pallas"`` on a TPU, ``"xla"`` elsewhere."""
    return "fused_pallas" if platform == "tpu" else "xla"


def best_append(platform: str):
    """The flow step's ``append`` for state living on ``platform``: the
    kernel on ``tpu`` (which hands a shape it does not serve to XLA's
    form itself), ``models.latent_moe.append_chunk`` elsewhere (an
    interpreted kernel is far too slow to serve)."""
    if append_kind(platform) == "fused_pallas":
        return cache_append_fused
    from linkerd_tpu.models.latent_moe import append_chunk
    return append_chunk
