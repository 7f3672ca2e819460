"""Grouped-query attention over a per-flow cache of keys and values: **one
operator, told by the layer what it is** (``AttentionLayer``), for every
flow model whose layers attend so: ``models/lfm2_moe.py``'s attention
layers (one head count, RoPE over the whole head, q/k norms, no window)
and both kinds of ``models/laguna_moe.py``'s (head counts of their own, a
rotary part, two kinds of RoPE, an output gate a head, and on the sliding
layers a window over a **ring**).

``q`` in ``heads`` heads of ``head_dim``, ``k`` and ``v`` in ``kv_heads``;
where the layer has ``q_norm`` / ``k_norm`` among its tensors, ``q`` and
``k`` are RMS-normed per head; RoPE (rotate-half) turns the first
``2 x len(inv_freq)`` values of every head, cos and sin times
``rope_scale``, and leaves the rest as they are; scores ``q . k /
sqrt(head_dim)``, query head ``i`` against key/value head ``i // (heads /
kv_heads)``; where the layer has ``wg``, head ``i``'s output is multiplied
by ``sigmoid(x wg)[i]`` before ``wo``.

**The state lies ``[slots, 2 x kv_heads x head_dim, positions]``**: a
position's rotated keys and then its values, positions along the lanes, as
the kernel of ``ops/flow_attention.py`` reads a slot (the compiler would
store a multiple of 128 lanes entry-minor and transpose it, a copy of the
layer, every call). The step's ``append`` writes the call's entries in
place (on a TPU a flow's whole lane tiles, a ring's wrap in the same
pass: ``ops/cache_append.py``), then the chunk attends over its flow's
slot by the step's ``attend``,
which is handed ``q`` **as the projection left it** (``Queries``: float32,
unturned, with the angles and the gate) and hands back what ``wo``
multiplies: on a TPU the kernel turns, rounds and gates on its tile, and
``q`` and the output cross HBM once each (PR 37); ``k``, an eighth of
``q`` and what the state holds, is turned and rounded here as it was.

- **No window: a cache.** A slot holds ``cfg.positions``, position ``p``
  at ``p``; event ``t`` sees ``0 .. p0 + t``.
- **A window ``W``: a ring.** An event sees the last ``W`` positions,
  itself included, so a slot keeps ``ring`` positions, ``ring_positions(W,
  chunk_max)``: the ``W - 1`` behind a chunk's first event and the chunk,
  up to whole blocks of the kernel. Position ``p`` lies at ``p mod ring``
  (a chunk that passes the ring's end goes on at its start) and **nothing
  is ever cleared**: what an index holds is known from the flow's
  position alone. Writing positions ``p0 .. p0 + T - 1`` overwrites ``p -
  ring <= p0 - W``, which no event of the chunk sees; index ``j`` then
  holds the newest position congruent to ``j`` that the flow has reached,
  and it is seen iff it is ``>= 0``, not after the event and inside its
  window. A flow that restarts in a slot writes its start token at index
  0 and goes on from there: what the flow before left is outside every
  window by the same arithmetic.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from linkerd_tpu.models.latent_moe import (
    ATTENTION_BLOCK, Operator, _mm, _rms, _rope, angles,
)

RING_BLOCK = 128    # a ring is whole blocks of the kernel's positions


def ring_positions(window: int, chunk_max: int,
                   block: int = RING_BLOCK) -> int:
    """Positions a slot of a sliding layer keeps: the ``window - 1``
    behind a chunk's first event and the longest chunk, rounded up to
    ``block`` (640 for 512 and 64)."""
    return -(-(window - 1 + chunk_max) // block) * block


class AttentionLayer(NamedTuple):
    """What a layer tells the operator. ``inv_freq``: the rotary
    frequencies, half as many as values of a head are rotated;
    ``window``: positions an event sees, itself included (None: all
    before it), and ``ring`` the positions a slot then keeps (0 with no
    window: ``cfg.positions``); ``kind``:
    where a model has attention layers of several kinds, this one's name:
    its device scope is ``<kind>_attention`` and its blocks are counted
    under ``attn.<kind>_blocks`` too."""
    heads: int
    kv_heads: int
    head_dim: int
    inv_freq: np.ndarray
    rope_scale: float = 1.0
    window: Optional[int] = None
    ring: int = 0
    kind: str = ""


@functools.partial(jax.tree_util.register_dataclass,
                   data_fields=["q", "cos", "sin", "gate"],
                   meta_fields=["heads"])
@dataclasses.dataclass(frozen=True)
class Queries:
    """What the operator hands the step's ``attend`` in the queries'
    place: ``q [F, T, heads x head_dim]`` **float32 as the projection (and
    ``q_norm``) left it**, ``heads`` of them an event (static), and what
    is still to be done around the attention: the events' ``cos`` and
    ``sin [F, T, 1, rotary / 2]`` float32 (times the layer's
    ``rope_scale``) to turn it by, and the output's ``gate [F, T, heads]``
    float32 (None: no gate). A wrapper of ``attend`` passes it through
    unopened; ``attend`` turns ``q`` in float32, rounds it to bfloat16
    **once, after the turn**, attends, rounds the output to bfloat16,
    multiplies it by the gate in float32 and hands back ``[F, T, heads x
    head_dim]`` for ``wo``. On a TPU all of that happens on the kernel's
    tile (``ops/flow_attention.grouped_attention_fused``): ``q`` and the
    output cross HBM once each, where ``wq`` wrote and ``wo`` reads (kept
    ``[F, T, heads x head_dim]`` throughout: a view by heads is another
    tiling on the chip, and a copy)."""
    q: Any
    cos: Any
    sin: Any
    gate: Any
    heads: int


def rotate(x, cos, sin, rotary: int):
    """RoPE over the first ``rotary`` values of every head of ``x [F, T,
    heads, head_dim]``; the others pass."""
    if rotary == x.shape[-1]:
        return _rope(x, cos, sin)
    return jnp.concatenate([_rope(x[..., :rotary], cos, sin),
                            x[..., rotary:].astype(jnp.float32)], -1)


def _apply(layer: AttentionLayer, lp, cfg, cache, start_entry, h, call):
    """``h [F, T, hidden]`` the residual stream; ``cache [slots, entry,
    positions]`` this layer's, donated. The chunk's entries are appended
    in place, then the chunk attends over its flow's slot by
    ``call.attend`` (``attend_grouped_xla``'s signature), which is handed
    the queries as projected (``Queries``) and hands back what ``wo``
    multiplies. Returns the output, the cache and the layer's counts
    (``Operator``)."""
    F, T, _ = h.shape
    H, G, hd = layer.heads, layer.kv_heads, layer.head_dim
    S, P = cfg.slots, cache.shape[-1]
    eps = cfg.rms_norm_eps
    if layer.window is not None and layer.window - 1 + T > P:
        raise ValueError(f"a chunk of {T} events behind a window of "
                         f"{layer.window} does not fit a ring of {P}")
    with jax.named_scope("project"):
        x = _rms(h, lp["operator_norm"], eps)
        cos, sin = angles(call.pos, layer.inv_freq)
        if layer.rope_scale != 1.0:
            cos, sin = cos * layer.rope_scale, sin * layer.rope_scale
        cos, sin = cos[:, :, None], sin[:, :, None]
        q = _mm(x, lp["wq"])
        k = _mm(x, lp["wk"]).reshape(F, T, G, hd)
        if "q_norm" in lp:
            q = _rms(q.reshape(F, T, H, hd), lp["q_norm"], eps).reshape(
                F, T, -1)
            k = _rms(k, lp["k_norm"], eps)
        k = rotate(k, cos, sin, 2 * len(layer.inv_freq))
        entry = jnp.concatenate([k.reshape(F, T, G * hd), _mm(x, lp["wv"])],
                                -1).astype(jnp.bfloat16)
    with jax.named_scope("append"):
        cache, written, in_kernel = call.append(
            cache, entry, start_entry, call.slot, call.p0, call.count,
            call.begins, positions_last=True, ring=layer.window is not None)
    with jax.named_scope("project"):    # an output gate a head, where the
        q = Queries(q, cos, sin,        # layer has one
                    jax.nn.sigmoid(_mm(x, lp["wg"])) if "wg" in lp else None,
                    H)
    with jax.named_scope("attend"):
        if layer.window is None:
            o, blocks, whole, in_tile = call.attend(
                q, cache, call.slot, call.p0, hd ** -0.5)
            own = {"attn.{}_blocks_whole": F * whole}
        else:
            # a ring's rows are held whatever the flows' lengths: what is
            # counted beside them is the same layer as a cache
            o, blocks, whole, unwindowed, in_tile = call.attend(
                q, cache, call.slot, call.p0, hd ** -0.5, window=layer.window)
            own = {"attn.{}_blocks_unwindowed": unwindowed.sum(),
                   "state.{}_rows": jnp.int32(S * P),
                   "state.{}_rows_as_cache": jnp.int32(S * cfg.positions)}
        live = (call.slot < S).sum()
        counts = {"cache.rows_written": written,
                  "cache.rows_whole": live * P,
                  "append.flows": live,
                  "append.flows_in_kernel": in_kernel,
                  "attn.kv_blocks": blocks.sum(),
                  "attn.kv_blocks_whole": F * whole,
                  "attn.q_rows": jnp.int32(F * T * H),
                  "attn.q_rows_in_tile": jnp.int32(in_tile)}
        if layer.kind:      # and under the kind's own names
            own["attn.{}_blocks"] = blocks.sum()
            counts.update({name.format(layer.kind): v
                           for name, v in own.items()})
    with jax.named_scope("out"):
        return _mm(o, lp["wo"]), cache, counts


def grouped_attention(layer: AttentionLayer) -> Operator:
    """The layer's instance of the operator."""
    def apply(lp, cfg, cache, start_entry, h, call):
        return _apply(layer, lp, cfg, cache, start_entry, h, call)

    def init(cfg):
        return jnp.zeros(
            (cfg.slots, 2 * layer.kv_heads * layer.head_dim,
             layer.ring or cfg.positions), jnp.bfloat16)

    return Operator(apply=apply, init=init,
                    start_of=lambda cache: cache[0, :, 0],
                    scope=f"{layer.kind}_attention".lstrip("_"), caches=True,
                    ring=layer.ring, parts=True)


def attend_grouped_xla(q, cache, slot, p0, scale: float,
                       window: Optional[int] = None):
    """Grouped-query attention as XLA does it, ``ATTENTION_BLOCK`` flows'
    whole score tensor at a time: the path of every platform but the TPU,
    and what ``ops/flow_attention.grouped_attention_fused`` is tested
    against. ``q``: the layer's ``Queries``: turned in float32, rounded to
    bfloat16, and the output times the gate in float32, each as an array
    of its own; ``cache [slots, 2 x G x head,
    positions]`` the layer's, whole: flow ``f`` attends over slot
    ``slot[f]`` (clipped into range, gathered here), query head ``i``
    against the keys ``[i // (H / G)]`` and the values ``[G + i // (H /
    G)]`` of its ``head`` rows; event ``t`` sees positions ``0 .. p0[f] +
    t``, or with a ``window`` the last ``window`` of them, the cache then
    a ring: index ``j`` holds the newest position congruent to ``j`` that
    is no later than the chunk's last. Returns ``(o [F, T, H x head]``
    (bfloat16; float32 where gated: ``wo``'s product rounds it), the
    blocks of positions attended over ``[F]``, the blocks of
    a whole slot)``: every slot is attended whole, as one block; with a
    window, a fourth: the blocks the flows would attend over with no
    window, one each as well; and last the query rows taken as projected
    on a kernel's tile: none."""
    gate, H = q.gate, q.heads
    F, T, width = q.q.shape
    hd = width // H
    q = rotate(q.q.reshape(F, T, H, hd), q.cos, q.sin,
               2 * q.cos.shape[-1]).astype(jnp.bfloat16)
    S, E, P = cache.shape
    G = E // (2 * hd)
    R = H // G
    # one batch axis (flow, key/value head), positions before the head's
    # width: the products XLA:CPU runs in bfloat16
    kv = cache[jnp.minimum(slot, S - 1)].reshape(F, 2, G, hd, P).transpose(
        0, 1, 2, 4, 3)
    q = q.reshape(F, T, G, R, hd).transpose(0, 2, 1, 3, 4)  # [F, G, T, R, hd]

    def attend(block):
        q, kv, pos = block
        nb = q.shape[0]
        s = jnp.einsum("bqd,bpd->bqp", q.reshape(nb * G, T * R, hd),
                       kv[:, 0].reshape(nb * G, P, hd),
                       preferred_element_type=jnp.float32) * scale
        if window is None:
            seen = jnp.arange(P)[None, None] <= pos[:, :, None]  # [nb, T, P]
        else:
            last = pos[:, -1:, None]
            held = last - (last - jnp.arange(P)[None, None]) % P
            seen = ((held >= 0) & (held <= pos[:, :, None])
                    & (held > pos[:, :, None] - window))
        s = jnp.where(seen[:, None, :, None], s.reshape(nb, G, T, R, P),
                      -jnp.inf)
        p = jax.nn.softmax(s, -1).reshape(nb * G, T * R, P)
        return jnp.einsum("bqp,bpd->bqd", p.astype(jnp.bfloat16),
                          kv[:, 1].reshape(nb * G, P, hd),
                          preferred_element_type=jnp.float32
                          ).astype(jnp.bfloat16).reshape(nb, G, T, R, hd)

    nb = min(ATTENTION_BLOCK, F)
    o = jax.lax.map(attend, jax.tree_util.tree_map(
        lambda a: a.reshape(F // nb, nb, *a.shape[1:]),
        (q, kv, p0[:, None] + jnp.arange(T)[None])))
    o = o.reshape(F, G, T, R, hd).transpose(0, 2, 1, 3, 4).reshape(
        F, T, H, hd)
    if gate is not None:
        o = o.astype(jnp.float32) * gate[..., None]
    o, one = o.reshape(F, T, H * hd), jnp.ones((F,), jnp.int32)
    return (o, one, 1, 0) if window is None else (o, one, 1, one, 0)
