"""Fused attention over a flow's slot of the cache: one Pallas kernel, for
the latent attention of ``models/latent_moe.py`` (told first, as it was
built) and for the grouped-query attention of
``models/grouped_attention.py`` (at the end).

``models/latent_moe._attention`` absorbs ``wukv`` into the query, so every
head of a flow attends over the one latent ``[positions, rank]`` and the
one rope key ``[positions, rope]`` of the flow's slot: a flow's queries are
one ``[events * heads, rank + rope]`` matrix against ``[positions, rank +
rope]``, and the weighted values are the latent again. The XLA path
(``models.latent_moe.attend_xla``) gathers the flows' slots, forms the
whole float32 score tensor, masks it, takes a softmax over it and
multiplies it into the latent: that tensor crosses HBM four or so times
and every event attends over all the slot's positions whatever the flow
holds.

Here a grid cell is one flow and one tile of its query rows (a few whole
events, all heads). **The flow's slot is read where it lies**: the kernel
takes the layer's cache whole, and the block spec of the keys and values
indexes it by ``slot[f]``, handed in with ``p0`` by
``PrefetchScalarGridSpec``, so a slot comes from HBM once a flow and no
gathered copy of the slots exists (PRs 29-30 handed the kernel
``cache[slot]``, for which XLA sliced and copied the whole layer: 604 MB
read and written to reach 75; PR 31). The slot's keys and values lie in
VMEM whole, and so do the tile's scores, in a scratch ``[rows,
positions]`` that never leaves the chip. Two loops run over blocks of
``KV_BLOCK`` positions: the first forms a block's scores and keeps their
maximum lane by lane; then one reduction across lanes gives each row's
maximum; the second takes ``exp(score - maximum)``, sums it lane by lane
and adds the block's weights, cast to bfloat16, times the block's latent
into a float32 accumulator; one division a row at the end. **Both loops
end at the last block that holds a position any of the tile's events may
see** (``blocks_seen``, from the flow's position ``p0``), and only the
blocks that reach past the tile's first position are masked.
(Online softmax, a running maximum and a rescaled accumulator a block,
computes the same in one loop and was the first attempt: its two
reductions across lanes a block are what a v5e does slowest: 2.59 ms a
layer of the benchmark's cell against 2.12; my chip runs, PR 29.)

**The slot lies transposed, and is used so.** The TPU's compiler stores a
layer ``[slots, positions, 576]`` positions-minor (576 is no multiple of
128 lanes, 1,024 is), so the kernel is handed ``cache.transpose(0, 2, 1)``,
which in that layout is a bitcast, and a slot arrives as ``[entry,
positions]``: the keys as the first product wants them, positions along
the lanes (``s = q_abs . c^T + q_rope . k_rope^T``, the entry split at
``rank``: sublanes ``0..rank`` and ``rank..``), with no transposed copy
(XLA's took 0.5 ms a layer). The values are the same rows, and the
second product contracts the weights with them over the positions, the
last axis of both (``POSITION_AXES``), which the MXU does at no cost
that shows: 1.93 ms a layer, against 1.94 with a flow's latent
transposed once, at its first tile, into a VMEM scratch, and 2.04 for
PR 29's kernel with its copy (my chip runs, PR 31; the variant with the
scratch was deleted). Where a compiler stores the cache entry-minor the
transpose is a copy of the layer: ``tests/test_chip_bringup.py`` compiles
the cell's step for a described v5e and fails on any such copy.

**Keys and values in groups of heads** (PR 32) are the same kernel body
(``_kernel``) under another grid and other block specs (``_attend``): the
grid gains an axis of ``G`` groups, a group's queries are scored against
key rows of their own, in as many parts as the queries come in, and the
values are either among the key rows or rows of their own. The latent
attention is one group of all the heads, queries in two parts (``rank``,
``rope``) against the ``rank + rope`` rows of the slot, values the first
``rank`` of them. Grouped-query attention (``grouped_attention_fused``)
is ``G`` key/value heads, each a grid cell with the ``H / G`` query heads
that attend over it: the cache ``[slots, 2 x G x head, positions]`` is
kept positions-last by the model (1,024 entry values a position are a
multiple of 128 lanes, so the compiler would not store it so of itself),
and the block specs take the head's ``head`` key rows (block ``g``) and
``head`` value rows (block ``G + g``) of the flow's slot: 128 KB each at
the published sizes, fetched once a cell. At a head of 64 the lanes of the
queries and the output are half used and the first product contracts
over 64: the kernel's pace there is its grid cells' fetches, not the MXU.

**Longer slots, wider heads, a window** (PR 34). At a head of 128 the
lanes are full and the first product contracts over 128. A slot of 4,096
positions is a group's keys and values of 1 MiB each, still one block of
the cache, and the tile's scores ``[rows, positions]`` are what bounds
the tile: the events a tile holds halve until they fit ``SCORE_BYTES``
(a group of 6 heads: 32 events, 192 rows, 3 MiB). **A window** is the same
body with a second bound: a tile's loops *begin* at the block of the
first position its first event sees (``block_range``), and the blocks
that reach behind the last event's window are masked there too. The
slot is then a ring (``models/grouped_attention.py``): it is as many
blocks as the ring has, block ``b`` of the flow's positions lies at ``b
mod`` that, and **the mask is the plain one in the flow's positions**:
what a ring block holds in place of a position that has been written
over, or not yet written, is outside every row's window by the ring's
size. A tile's span of ``window - 1 + events`` positions can touch one
block more than the ring has (the first and the last then the same ring
block, under two labels), so the scores' scratch has a block to spare.
The call is named ``window_attention_fused``, so a trace tells the two
apart.

**The queries as the projection left them** (PR 37). The grouped
operator used to turn ``q`` (RoPE's split and concatenation), cast it,
lay it ``[F, G, T x R, head]`` for the kernel, lay the output back, widen
it, gate it and cast it for ``wo``: ten or so passes of XLA's over
``[events, heads x head]``, 134 MB each in float32 at 8,192 values an
event, four times the cost of the projections (the compiler's estimate:
32 ms of an 81 ms step). Now a grid cell's block of ``q`` is the tile's
events and its group's ``R x head`` columns of ``wq``'s own float32
output, ``[F, T, H x head]``; the tile is turned in float32 (``_turn``: a
value's partner is a roll of the lanes away), **rounded to bfloat16 once,
after the turn, as the step rounded it**, and stored head-major (an
event's row of a head below the row of the event before: the mask reads
``row mod events``); the output is rounded to bfloat16 as it always was,
widened, multiplied by its (event, head)'s gate in float32, rounded as
``wo``'s product rounded it, and written to the same block of ``[F, T, H
x head]``, which ``wo`` reads as it is. A head of 128 is its own lane
tile; two heads of 64 share one and each is rolled against its own half
(``turn_lanes``). **A tile of fewer than 16 events** (chunks of 1 to 8)
would be stored row by row, three times the kernel's cost at chunks of 1
(my chip run, PR 37): ``on_the_tile`` reads the layout, and such a call's
few hundred rows are turned, rounded and gated by XLA around the kernel,
in the same order. The latent attention takes its queries ready, as
before (``turn=None``): its program is unchanged.

``best_attention`` selects by platform as ``ops/scoring.best_scorer``
does: this kernel on ``tpu``, the XLA path elsewhere. There is no probe
and no fallback: a kernel that Mosaic refuses on the chip is an error the
caller sees. CPU tests run the kernel with ``interpret=True``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

KV_BLOCK = 128      # positions a block: one lane tile of scores
QUERY_ROWS = 1024   # query rows (events x heads) a tile, at most
SCORE_BYTES = 4 * 2 ** 20   # a tile's scores [rows, positions] float32
MASKED = -1e30      # finite: a block a row sees nothing of makes no NaN
VMEM_LIMIT = 32 * 2 ** 20   # 1,024 rows x 1,024 positions need 20 MiB


def kv_block(P: int) -> int:
    """Positions a block of the loops holds, for a slot of ``P``."""
    return KV_BLOCK if P % KV_BLOCK == 0 else P


def blocks_seen(first, events: int, P: int):
    """Blocks of a slot that hold a position which ``events`` events
    appended at position ``first`` may see (positions ``0 .. first +
    events - 1``): where a tile's loops end, and what ``attn.kv_blocks``
    counts. At least 1: position 0 is always seen."""
    bk = kv_block(P)
    return (jnp.minimum(first + events, P) + bk - 1) // bk


def block_range(first, events: int, P: int, window: int):
    """``(lo, hi)``: the blocks ``[lo, hi)`` **of the flow's positions**
    that hold one which ``events`` events appended at position ``first``
    may see through a window of ``window`` (positions ``first - window +
    1 .. first + events - 1``, from 0); block ``b`` lies in the ring of
    ``P`` at ``b mod (P / block)``. At most one more than the ring has."""
    bk = kv_block(P)
    return (jnp.maximum(first - window + 1, 0) // bk,
            (first + events + bk - 1) // bk)


def _events_a_tile(T: int, H: int, P: int) -> int:
    """Whole events a tile of query rows holds: ``T`` halved until the
    tile's rows and its scores fit."""
    te = T
    while te % 2 == 0 and te * H > min(QUERY_ROWS, SCORE_BYTES // (4 * P)):
        te //= 2
    return te


POSITION_AXES = (((1,), (1,)), ((), ()))    # p [rows, pos] . c [rank, pos]


def _turn(q_ref, cos_ref, sin_ref, qt_ref, events: int, head: int, half: int):
    """The tile's queries as the projection left them, ``q_ref [1, events,
    heads x head]`` float32, turned (rotate-half over the first ``2 x
    half`` values of every head) in float32, **rounded to bfloat16 once**
    and laid head-major into ``qt_ref [heads x events, head]``. ``cos_ref``
    and ``sin_ref [1, events, lanes]``: an event's cosines, and its sines
    signed as the rotation adds them (``-sin`` over a head's first
    ``half`` values, ``+sin`` over the next, 0 and a cosine of 1 over
    those that pass), over ``lanes`` values, whole heads: a value's
    partner is ``half`` lanes up or down, a roll of the lanes away, and
    ``x cos + partner sin`` is, value for value, the ``a cos - b sin``
    and ``b cos + a sin`` of ``models.latent_moe._rope``."""
    lanes = cos_ref.shape[-1]
    cos, sin = cos_ref[0], sin_ref[0]
    both_ways = 2 * half != lanes   # else half a turn up is half a turn down
    if both_ways:
        up = jax.lax.rem(jax.lax.broadcasted_iota(jnp.int32, cos.shape, 1),
                         head) < half       # where the partner lies above
    for c in range(q_ref.shape[-1] // lanes):
        x = q_ref[0, :, c * lanes:(c + 1) * lanes]
        partner = pltpu.roll(x, half, 1)            # x[lane - half]
        if both_ways:
            partner = jnp.where(up, pltpu.roll(x, lanes - half, 1), partner)
        x = (x * cos + partner * sin).astype(qt_ref.dtype)
        for j in range(lanes // head):
            r = c * (lanes // head) + j
            qt_ref[r * events:(r + 1) * events, :] = x[
                :, j * head:(j + 1) * head]


def _kernel(slot_ref, p0_ref, *refs, scale: float, heads: int, widths: tuple,
            values_apart: bool, window, turn=None):
    """One flow, one group of heads, one tile of its query rows. ``refs``:
    the queries in ``len(widths)`` parts, part ``j`` ``widths[j]`` wide and
    scored against the key rows that follow those of the parts before it;
    the keys ``[key rows, positions]``; the values ``[value rows,
    positions]`` where they are ``values_apart`` (else they are the first
    of the key rows); the output; the scratch. ``heads``: query rows an
    event. ``window``: None, or the positions a row sees, itself
    included, of a slot that is a ring. ``turn``: None, and the queries
    come ready, bfloat16, a row an (event, head), event-major; or ``(half,
    gated)``: **the one part is the projection's own output** ``[1,
    events, heads x head]`` float32, followed by the events' cosines and
    signed sines and, where ``gated``, their gates ``[1, 1, events,
    heads]``: the tile is turned and rounded here (``_turn``), its rows
    lie head-major, and the output, rounded to bfloat16 as it always was,
    is widened, multiplied by its (event, head)'s gate in float32, rounded
    to bfloat16 again and written ``[1, events, heads x head]``: where the
    output projection reads it."""
    del slot_ref    # the block specs' alone: which slot the keys are of
    refs = list(refs)
    q_refs = [refs.pop(0) for _ in widths]
    if turn is not None:
        half, gated = turn
        cos_ref, sin_ref = refs.pop(0), refs.pop(0)
        gate_ref = refs.pop(0) if gated else None
        qt_ref = refs.pop()
    k_ref = refs.pop(0)
    v_ref = refs.pop(0) if values_apart else k_ref
    o_ref, s_ref, m_ref, l_ref, acc_ref = refs
    f, i = pl.program_id(0), pl.program_id(2)
    rows, P = s_ref.shape[0], k_ref.shape[-1]
    events, bk, vd = rows // heads, m_ref.shape[1], acc_ref.shape[1]
    first = p0_ref[f] + i * events      # position of the tile's first event
    if window is None:
        lo, last = 0, blocks_seen(first, events, P)
    else:
        lo, last = block_range(first, events, P, window)
    if turn is None:
        qs = [q_ref[0, 0] for q_ref in q_refs]
    else:
        _turn(q_refs[0], cos_ref, sin_ref, qt_ref, events, widths[0], half)
        qs = [qt_ref[...]]
    at_row = [sum(widths[:j]) for j in range(len(widths))]

    def places(j):
        """Where block ``j``'s scores lie in the scratch and its keys and
        values in the slot: the same place, but in a ring."""
        if window is None:
            at = pl.multiple_of(j * bk, bk)
            return at, at
        return (pl.multiple_of((j - lo) * bk, bk),
                pl.multiple_of(jax.lax.rem(j, P // bk) * bk, bk))

    def score(j, masked):
        at, held = places(j)
        s = functools.reduce(jnp.add, (
            jnp.dot(q, k_ref[0, a:a + w, pl.ds(held, bk)],
                    preferred_element_type=jnp.float32)
            for q, a, w in zip(qs, at_row, widths))) * scale
        if masked:
            row = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
            pos = first + (row // heads if turn is None
                           else jax.lax.rem(row, events))
            col = (at if window is None else j * bk
                   ) + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            seen = col <= pos
            if window is not None:
                seen &= col > pos - window
            s = jnp.where(seen, s, MASKED)
        s_ref[:, pl.ds(at, bk)] = s
        m_ref[...] = jnp.maximum(m_ref[...], s)     # lane by lane

    m_ref[...] = jnp.full(m_ref.shape, MASKED, jnp.float32)
    # blocks that end at or before the tile's first position hide nothing
    # from any of its rows
    clear = jnp.minimum((first + 1) // bk, last)
    if window is None:
        begin = 0
    else:
        # nor do those that begin inside the last row's window
        begin = jnp.clip(
            (jnp.maximum(first + events - window, 0) + bk - 1) // bk, lo,
            clear)
        jax.lax.fori_loop(lo, begin, lambda j, _: score(j, True), None)
    jax.lax.fori_loop(begin, clear, lambda j, _: score(j, False), None)
    jax.lax.fori_loop(clear, last, lambda j, _: score(j, True), None)
    # each row's maximum, in every lane (every row sees a position: 0, or
    # with a window its own; so it is a score and not MASKED)
    m_ref[...] = jnp.broadcast_to(m_ref[...].max(-1, keepdims=True),
                                  m_ref.shape)
    l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
    acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

    def weigh(j, _):
        at, held = places(j)
        p = jnp.exp(s_ref[:, pl.ds(at, bk)] - m_ref[...])
        l_ref[...] += p         # lane by lane: summed across once, below
        ct = v_ref[0, :vd, pl.ds(held, bk)]
        acc_ref[...] += jax.lax.dot_general(
            p.astype(ct.dtype), ct, POSITION_AXES,
            preferred_element_type=jnp.float32)

    jax.lax.fori_loop(lo, last, weigh, None)
    o = (acc_ref[...] * (1.0 / l_ref[...].sum(-1, keepdims=True))
         ).astype(o_ref.dtype)
    if turn is None:
        o_ref[0, 0] = o
        return
    for r in range(heads):
        head = o[r * events:(r + 1) * events]
        if gated:
            head = (head.astype(jnp.float32) * gate_ref[0, 0, :, r:r + 1]
                    ).astype(o_ref.dtype)
        o_ref[0, :, r * vd:(r + 1) * vd] = head


def _attend(parts, kt, slot, p0, *, T: int, values_at, vd: int, scale: float,
            interpret: bool, name: str, window=None, turn=None):
    """The kernel over ``parts``: the queries ``[F, G, T * heads, width]``
    a part, ``G`` groups of heads each with keys and values of its own;
    ``kt [slots, rows, P]``, the layer's cache, positions last: group
    ``g``'s keys are its rows ``[g * kd, (g + 1) * kd)``, ``kd`` the
    parts' widths together, and its values the ``vd`` rows of block
    ``values_at + g`` (in blocks of ``vd`` rows), or, where ``values_at``
    is None, the first ``vd`` of its key rows. ``window``: None, or the
    positions an event sees of a slot that is a ring. Returns ``(o [F, G,
    T * heads, vd]``, the blocks of positions attended over ``[F]``,
    those of a slot whole)``, and with a window those with no window
    ``[F]``, a fourth.

    ``turn``: None, or ``(half, cos, sin, gate)`` and **the one part is
    the projection's output** ``[F, T, G x heads x vd]`` float32
    (``values_at`` groups of heads ``vd`` wide): the block of grid cell
    ``(f, g, i)`` is the tile's events and group ``g``'s columns of it,
    ``half``, ``cos`` and ``sin [F, T, lanes]`` what ``_turn`` takes,
    ``gate [F, G, T, heads]`` float32 or None, and ``o`` comes back ``[F,
    T, G x heads x vd]`` bfloat16, an event's heads side by side
    (``_kernel``)."""
    S, _, P = kt.shape
    if turn is None:
        F, G, rows_all, _ = parts[0].shape
        heads = rows_all // T
        widths = tuple(q.shape[-1] for q in parts)
    else:
        F, G, widths = parts[0].shape[0], values_at, (vd,)
        heads = parts[0].shape[-1] // (G * vd)
    bk, events = kv_block(P), _events_a_tile(T, heads, P)
    rows, tiles = events * heads, T // events
    if window is not None and window - 1 + events > P:
        raise ValueError(f"{events} events behind a window of {window} do "
                         f"not fit a ring of {P}")
    # a window's span may touch one block more than the ring has
    scratch = [pltpu.VMEM((rows, P if window is None else P + bk),
                          jnp.float32),
               pltpu.VMEM((rows, bk), jnp.float32),
               pltpu.VMEM((rows, bk), jnp.float32),
               pltpu.VMEM((rows, vd), jnp.float32)]
    on_tile, more = None, []    # what the kernel is told, and handed, of it
    if turn is None:
        q_specs = [pl.BlockSpec((1, 1, rows, w),
                                lambda f, g, i, slot, p0: (f, g, i, 0))
                   for w in widths]
        out_spec = pl.BlockSpec((1, 1, rows, vd),
                                lambda f, g, i, slot, p0: (f, g, i, 0))
        out_shape = jax.ShapeDtypeStruct((F, G, T * heads, vd),
                                         parts[0].dtype)
    else:
        half, cos, sin, gate = turn
        # an event's heads of the group where the projection wrote them
        by_event = pl.BlockSpec((1, events, heads * vd),
                                lambda f, g, i, slot, p0: (f, i, g))
        angle = pl.BlockSpec((1, events, cos.shape[-1]),
                             lambda f, g, i, slot, p0: (f, i, 0))
        q_specs, out_spec, more = [by_event, angle, angle], by_event, [cos, sin]
        if gate is not None:
            q_specs.append(pl.BlockSpec(
                (1, 1, events, heads), lambda f, g, i, slot, p0: (f, g, i, 0)))
            more.append(gate)
        out_shape = jax.ShapeDtypeStruct((F, T, G * heads * vd), kt.dtype)
        scratch.append(pltpu.VMEM((rows, vd), kt.dtype))
        on_tile = (half, gate is not None)
    kernel = functools.partial(_kernel, scale=scale, heads=heads,
                               widths=widths,
                               values_apart=values_at is not None,
                               window=window, turn=on_tile)
    # the flow's slot where it lies, positions along the lanes: the same
    # block for all of a flow's (and a group's) tiles, so it is fetched
    # once
    kv_specs = [pl.BlockSpec((1, sum(widths), P),
                             lambda f, g, i, slot, p0: (slot[f], g, 0))]
    if values_at is not None:
        kv_specs.append(pl.BlockSpec(
            (1, vd, P), lambda f, g, i, slot, p0: (slot[f], values_at + g, 0)))
    o = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(F, G, tiles),
            in_specs=q_specs + kv_specs,
            out_specs=out_spec,
            scratch_shapes=scratch),
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel"),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
        name=name,
    )(jnp.minimum(slot, S - 1).astype(jnp.int32), p0.astype(jnp.int32),
      *parts, *more, *[kt] * len(kv_specs))
    firsts = [p0 + i * events for i in range(tiles)]
    if window is None:
        return (o, sum(blocks_seen(first, events, P) for first in firsts),
                tiles * (P // bk))
    ranges = [block_range(first, events, P, window) for first in firsts]
    return (o, sum(hi - lo for lo, hi in ranges), tiles * (P // bk),
            sum(hi for _, hi in ranges))


@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def latent_attention_fused(q_abs, q_rope, cache, slot, p0, scale: float,
                           interpret: bool = False):
    """``q_abs [F, T, H, rank]``, ``q_rope [F, T, H, rope]`` bfloat16;
    ``cache [slots, P, rank + rope]`` bfloat16, the layer's, whole, with
    the call's entries already appended; ``slot [F]``, ``p0 [F]`` int32:
    flow ``f`` attends over ``kv = cache[slot[f]]`` (a slot out of range:
    clipped), read in place by the block spec, and its event ``t`` sees
    positions ``0 .. p0[f] + t``. Returns ``(o [F, T, H, rank]``
    bfloat16 ``= softmax(mask(q . kv^T * scale)) . kv[..., :rank]``, the
    blocks of positions attended over, summed over a flow's tiles of
    query rows ``[F]``, and what the slot whole would have been)``: one
    group of all the heads, the queries in two parts, the values among
    the keys. Jitted, so that a step of several layers traces and lowers
    the kernel once (0.1 s a layer of every set-up; my chip runs, PR
    29)."""
    F, T, H, rank = q_abs.shape
    o, attended, whole = _attend(
        [q_abs.reshape(F, 1, T * H, rank), q_rope.reshape(F, 1, T * H, -1)],
        cache.transpose(0, 2, 1), slot, p0, T=T, values_at=None, vd=rank,
        scale=scale, interpret=interpret, name="latent_attention_fused")
    return o.reshape(F, T, H, rank), attended, whole


TILE_EVENTS = 16    # events a tile, from which it is turned in the kernel


def on_the_tile(T: int, heads: int, P: int) -> bool:
    """Whether a call of ``T`` events a flow is turned, rounded and gated
    on the kernel's tile: where a tile's events fill whole sublane tiles
    (16 rows of bfloat16). A tile of fewer (chunks of 1 to 8 events, whose
    ``q`` is a few hundred rows in all) is stored row by row, which on the
    chip costs three times the whole kernel at chunks of 1 (7.0 ms against
    2.4 for a full layer of 64 flows; my chip run, PR 37): there XLA turns
    and gates, as it did."""
    return _events_a_tile(T, heads, P) % TILE_EVENTS == 0


def turn_lanes(head: int, heads: int) -> int:
    """Lanes the kernel turns at a time: whole heads, so a value's partner
    lane is among them: a head of 128 and more is its own; narrower heads
    go as many to the 128 lanes as divide the group's ``heads``."""
    per = max(1, min(128 // head, heads))
    while heads % per:
        per -= 1
    return head * per


@functools.partial(jax.jit, static_argnames=("scale", "interpret", "window"))
def grouped_attention_fused(q, cache, slot, p0, scale: float,
                            interpret: bool = False, window=None):
    """Grouped-query attention by the same kernel. ``q``: what the layer
    projected and what is still to be done to it
    (``models.grouped_attention.Queries``: ``q [F, T, H x head]`` float32
    as the projection (and a norm) left it, ``heads`` = ``H``, ``cos`` and
    ``sin [F, T, 1, rotary / 2]`` float32, the output's ``gate [F, T, H]``
    float32 or None): **the kernel reads ``q`` where the projection wrote
    it**, turns, rounds and scores it on the tile, gates the output there
    and writes it where the output projection reads it, so neither
    crosses HBM but once, and no transposed copy of either exists. ``cache [slots, 2 x G x head, P]``
    bfloat16, the layer's, whole and **positions last**
    (``models/grouped_attention.py`` keeps it so), a position's keys of
    ``G`` key/value heads and then its values. A grid cell is one flow,
    one key/value head and a tile of events: the ``H / G`` query heads
    that attend over it are ``H / G x head`` columns of ``q``, side by
    side; the block specs take that head's ``head`` key rows and ``head``
    value rows from the flow's slot. With a ``window`` the slot is a ring
    and the call is named ``window_attention_fused``. Returns what
    ``models.grouped_attention.attend_grouped_xla`` returns: ``(o [F, T,
    H x head]``, the blocks attended over ``[F]``, those of a slot
    whole)``, with a window those with no window ``[F]``, and last the
    query rows taken as projected: all ``F x T x H``, or, where a tile
    holds too few events (``on_the_tile``), none: XLA then turns, rounds
    and gates around the kernel, in the same order."""
    q, cos, sin, gate, H = q.q, q.cos, q.sin, q.gate, q.heads
    F, T, width = q.shape
    hd = width // H
    G = cache.shape[1] // (2 * hd)
    R = H // G
    half = cos.shape[-1]
    attend = functools.partial(
        _attend, kt=cache, slot=slot, p0=p0, T=T, values_at=G, vd=hd,
        scale=scale, interpret=interpret, window=window,
        name=("grouped" if window is None else "window") + "_attention_fused")
    if not on_the_tile(T, R, cache.shape[-1]):
        from linkerd_tpu.models.grouped_attention import rotate
        q = rotate(q.reshape(F, T, H, hd), cos, sin, 2 * half).astype(
            jnp.bfloat16)
        o, *blocks = attend([q.reshape(F, T, G, R, hd).transpose(
            0, 2, 1, 3, 4).reshape(F, G, T * R, hd)])
        o = o.reshape(F, G, T, R, hd).transpose(0, 2, 1, 3, 4).reshape(
            F, T, H, hd)
        if gate is not None:
            o = (o.astype(jnp.float32) * gate[..., None]).astype(jnp.bfloat16)
        return (o.reshape(F, T, width), *blocks, 0)
    cos, sin = cos.reshape(F, T, half), sin.reshape(F, T, half)
    passes = jnp.zeros((F, T, hd - 2 * half), jnp.float32)
    times = turn_lanes(hd, R) // hd
    cos = jnp.tile(jnp.concatenate([cos, cos, passes + 1], -1), times)
    sin = jnp.tile(jnp.concatenate([-sin, sin, passes], -1), times)
    if gate is not None:
        gate = gate.reshape(F, T, G, R).transpose(0, 2, 1, 3)
    return (*attend([q], turn=(half, cos, sin, gate)), F * T * H)


SELECT_EVENTS = 16      # events a tile of the selection's kernel, at most
SELECT_VMEM_LIMIT = 100 * 2 ** 20   # of a v5e's 128 MiB: 45 MiB at 6,144


def _selected_kernel(slot_ref, p0_ref, qa_ref, qr_ref, sink_ref, idx_ref,
                     thr_ref, tie_ref, k_ref, o_ref, s_ref, m_ref, l_ref,
                     acc_ref, *, scale: float, heads: int):
    """One flow, one tile of its events, all heads, over **the positions
    each event's selection holds**: ``_kernel``'s two loops with no mask
    but the selection's and a sink in the softmax's sum. ``qa_ref``,
    ``qr_ref [1, rows, rank | rope]`` the tile's queries, a row an (event,
    head), event-major; ``sink_ref [rows, 1]`` each row's head's sink
    logit (``MASKED``: none); ``idx_ref [1, events, P]`` the events'
    index scores, ``thr_ref`` and ``tie_ref [1, events, 1]`` their
    thresholds: position ``s`` is selected iff its score is over the
    threshold, or equal to it and ``s <= tie``; ``k_ref [1, rank + rope,
    P]`` the flow's slot, positions along the lanes."""
    del slot_ref    # the block spec's alone: which slot the keys are of
    f, i = pl.program_id(0), pl.program_id(1)
    rows, P = s_ref.shape[0], k_ref.shape[-1]
    events, bk, rank = rows // heads, m_ref.shape[1], acc_ref.shape[1]
    first = p0_ref[f] + i * events      # position of the tile's first event
    last = blocks_seen(first, events, P)
    qa, qr = qa_ref[0], qr_ref[0]
    thr, tie = thr_ref[0], tie_ref[0]

    def score(j, _):
        at = pl.multiple_of(j * bk, bk)
        s = (jnp.dot(qa, k_ref[0, :rank, pl.ds(at, bk)],
                     preferred_element_type=jnp.float32)
             + jnp.dot(qr, k_ref[0, rank:, pl.ds(at, bk)],
                       preferred_element_type=jnp.float32)) * scale
        got = idx_ref[0, :, pl.ds(at, bk)]                  # [events, bk]
        col = at + jax.lax.broadcasted_iota(jnp.int32, got.shape, 1)
        chosen = (got > thr) | ((got == thr) & (col <= tie))
        # an event's row of the mask for each of its heads' rows
        off = jnp.broadcast_to(
            jnp.where(chosen, 0.0, MASKED)[:, None, :],
            (events, heads, bk)).reshape(rows, bk)
        s = s + off
        s_ref[:, pl.ds(at, bk)] = s
        m_ref[...] = jnp.maximum(m_ref[...], s)     # lane by lane

    # the sink takes part in the maximum as in the sum
    m_ref[...] = jnp.broadcast_to(sink_ref[...], m_ref.shape)
    jax.lax.fori_loop(0, last, score, None)
    m_ref[...] = jnp.broadcast_to(m_ref[...].max(-1, keepdims=True),
                                  m_ref.shape)
    l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
    acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

    def weigh(j, _):
        at = pl.multiple_of(j * bk, bk)
        p = jnp.exp(s_ref[:, pl.ds(at, bk)] - m_ref[...])
        l_ref[...] += p
        ct = k_ref[0, :rank, pl.ds(at, bk)]
        acc_ref[...] += jax.lax.dot_general(
            p.astype(ct.dtype), ct, POSITION_AXES,
            preferred_element_type=jnp.float32)

    jax.lax.fori_loop(0, last, weigh, None)
    total = (l_ref[...].sum(-1, keepdims=True)
             + jnp.exp(sink_ref[...] - m_ref[:, :1]))
    o_ref[0] = (acc_ref[...] * (1.0 / total)).astype(o_ref.dtype)


def selection_tile(T: int) -> int:
    """Events a tile of ``sparse_latent_attention_fused`` holds: ``T``
    halved until it is ``SELECT_EVENTS`` or fewer (16 at chunks of 64)."""
    events = T
    while events > SELECT_EVENTS and events % 2 == 0:
        events //= 2
    return events


@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def sparse_latent_attention_fused(q_abs, q_rope, cache, slot, p0,
                                  scale: float, selection, sink=None,
                                  interpret: bool = False):
    """The latent attention over **a selection of each event's positions**
    (``models/hy4_moe.py``: an indexer's top ``k``), with a sink: what
    ``models.hy4_moe.attend_selected_xla`` returns. ``q_abs``, ``q_rope``
    and ``cache`` as ``latent_attention_fused`` takes them;
    ``selection``: ``(scores [F, T, P]`` float32, ``threshold [F, T]``
    float32, ``tie [F, T]`` int32``)``: event ``(f, t)`` attends over the
    positions whose score is over its threshold, or equal to it at or
    before ``tie`` (a score past the event is ``-inf``, never selected);
    ``sink [H]``, a logit a head added to the softmax's sum (None: none).

    A grid cell is a flow and a tile of ``selection_tile(T)`` events (16
    at chunks of 64: 1,024 rows at 64 heads, whatever the slot's length):
    the tile's scores ``[rows, P]`` stay in VMEM whole (24 MiB at 6,144
    positions), as ``_kernel``'s do, so that the softmax takes two loops
    over blocks and one reduction across lanes; both loops end at the last
    block an event of the tile may see (``blocks_seen``), and every block
    is masked by the selection, which the kernel reads from the scores'
    rows of the tile's events. Returns ``(o [F, T, H, rank]`` bfloat16,
    the blocks attended over ``[F]``, those of a slot whole)``."""
    F, T, H, rank = q_abs.shape
    scores, threshold, tie = selection
    kt = cache.transpose(0, 2, 1)
    S, E, P = kt.shape
    bk, events = kv_block(P), selection_tile(T)
    rows, tiles = events * H, T // events
    sink = (jnp.full((H,), MASKED, jnp.float32) if sink is None
            else sink.astype(jnp.float32))
    o = pl.pallas_call(
        functools.partial(_selected_kernel, scale=scale, heads=H),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(F, tiles),
            in_specs=[
                pl.BlockSpec((1, rows, rank),
                             lambda f, i, slot, p0: (f, i, 0)),
                pl.BlockSpec((1, rows, E - rank),
                             lambda f, i, slot, p0: (f, i, 0)),
                pl.BlockSpec((rows, 1), lambda f, i, slot, p0: (0, 0)),
                pl.BlockSpec((1, events, P),
                             lambda f, i, slot, p0: (f, i, 0)),
                pl.BlockSpec((1, events, 1),
                             lambda f, i, slot, p0: (f, i, 0)),
                pl.BlockSpec((1, events, 1),
                             lambda f, i, slot, p0: (f, i, 0)),
                pl.BlockSpec((1, E, P),
                             lambda f, i, slot, p0: (slot[f], 0, 0))],
            out_specs=pl.BlockSpec((1, rows, rank),
                                   lambda f, i, slot, p0: (f, i, 0)),
            scratch_shapes=[pltpu.VMEM((rows, P), jnp.float32),
                            pltpu.VMEM((rows, bk), jnp.float32),
                            pltpu.VMEM((rows, bk), jnp.float32),
                            pltpu.VMEM((rows, rank), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((F, T * H, rank), q_abs.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=SELECT_VMEM_LIMIT),
        interpret=interpret,
        name="sparse_latent_attention_fused",
    )(jnp.minimum(slot, S - 1).astype(jnp.int32), p0.astype(jnp.int32),
      q_abs.reshape(F, T * H, rank), q_rope.reshape(F, T * H, -1),
      jnp.tile(sink, events)[:, None], scores,
      threshold.astype(jnp.float32)[..., None],
      tie.astype(jnp.int32)[..., None], kt)
    attended = sum(blocks_seen(p0 + i * events, events, P)
                   for i in range(tiles))
    return o.reshape(F, T, H, rank), attended, tiles * (P // bk)


def attention_kind(platform: str) -> str:
    """Which attention ``best_attention`` hands the flow step on
    ``platform``: ``"fused_pallas"`` on a TPU, ``"xla"`` elsewhere."""
    return "fused_pallas" if platform == "tpu" else "xla"


def attention_call(platform: str, grouped: bool, windowed: bool,
                   sparse: bool = False) -> str:
    """The name of the call a layer's attention makes on ``platform``."""
    if attention_kind(platform) != "fused_pallas":
        if sparse:
            return "attend_selected_xla"
        return "attend_grouped_xla" if grouped else "attend_xla"
    if sparse:
        return "sparse_latent_attention_fused"
    if not grouped:
        return "latent_attention_fused"
    return ("window" if windowed else "grouped") + "_attention_fused"


def best_attention(platform: str, grouped: bool = False,
                   sparse: bool = False):
    """The flow step's ``attend`` for parameters living on ``platform``:
    the fused kernel on ``tpu``, the XLA path elsewhere (an interpreted
    kernel is far too slow to serve); ``grouped``: for grouped-query
    attention over keys and values (``models/grouped_attention.py``: a
    layer with a window hands it ``window=``), ``sparse``: for the latent
    attention over a selection of positions (``models/hy4_moe.py``), else
    for the latent attention (``models/latent_moe.py``)."""
    if sparse:
        if attention_kind(platform) == "fused_pallas":
            return sparse_latent_attention_fused
        from linkerd_tpu.models.hy4_moe import attend_selected_xla
        return attend_selected_xla
    if attention_kind(platform) == "fused_pallas":
        return grouped_attention_fused if grouped else latent_attention_fused
    if grouped:
        from linkerd_tpu.models.grouped_attention import attend_grouped_xla
        return attend_grouped_xla
    from linkerd_tpu.models.latent_moe import attend_xla
    return attend_xla
