"""Plain reference of the flow model of ``configs/laguna-xs.2.json``:
Laguna's block as Laguna-XS.2's ``config.json`` sizes it, cut in depth
alone (every expert of a layer and the whole vocabulary are here).

``h0 = E[id]``; per layer ``h += Attn_l(RMSNorm(h))`` then ``h +=
FFN_l(RMSNorm(h))``; a final RMSNorm; ``logits = h Wout`` (embedding and
head are two tensors).

- Attention of layer ``l``, grouped-query, no cache and no ring: ``q = x
  Wq`` in ``num_attention_heads_per_layer[l]`` heads of ``head_dim``, ``k
  = x Wk``, ``v = x Wv`` in ``num_key_value_heads``; no q/k norm; RoPE by
  the layer's type (``rope_parameters[layer_types[l]]``, rotate-half
  pairing) over the first ``partial_rotary_factor x head_dim`` values of
  every head of ``q`` and ``k`` at the token's position, the others left
  as they are: ``yarn`` blends the plain and the interpolated frequencies
  by the ramp between the two correction dimensions and multiplies cos
  and sin by ``attention_factor``; ``default`` is the plain kind. Query
  head ``i`` meets key/value head ``i // (heads / kv heads)``; scores ``q
  . k / sqrt(head_dim)``; the token at position ``t`` sees ``0 .. t`` in a
  ``full_attention`` layer and ``max(0, t - sliding_window + 1) .. t`` in
  a ``sliding_attention`` one, **by a mask over the whole sequence**;
  softmax; each head's output times its gate ``sigmoid(x Wg)[head]``;
  heads concatenated through ``Wo``. What a flow's state would hold of a
  position, the rotated keys and then the values, is returned (``kept``).
  Scores are formed a key/value head's query heads at a time, so that
  4,096 positions fit.
- FFN: a dense SwiGLU where ``mlp_layer_types[l]`` is ``dense``; else
  ``shared(x) + routed(x)``: ``s = sigmoid(x Wr)`` over ``num_experts``,
  the top ``num_experts_per_tok`` of ``s`` itself, weights the selected
  ``s`` over their sum, times ``moe_routed_scaling_factor``; the sum over
  the selected experts, every held expert computed for every token and
  masked by the selection: no sorting, no capacity.
- The score of the token at position ``t >= 1``: ``1 - exp(-nll / ln V)``
  with ``nll = -log_softmax(logits[t - 1])[id_t]``.

Straight ``jax.numpy`` in float32 under ``jax.default_matmul_precision
("highest")``, one whole sequence forward at a time, layer by layer so
that one layer's float32 weights are on the device at a time (an expert
layer is 3.2 GB). It imports nothing of the program; the rounding, the
products and the draw of a tensor from the seed are
``reference/latent_moe.py``'s. Weights are its own draw by the
configuration file's rule (``weights.rule``).

``quant``: ``None`` is the reference; ``"bf16"`` rounds both operands
ahead of every matrix product to bfloat16 (the configuration's stated
compute type); ``"fp8"`` to float8 e4m3 (the control).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

# noqa: F401 below: PRECISION and _q are the check's and the tests'
from chipbench.reference.latent_moe import (  # noqa: F401
    OUT_GAIN, PRECISION, _CFGS, _draw, _ein, _gain, _mat, _mm,
    _q, _register, held_range, rms_norm, swiglu,
)
from chipbench.reference.lfm2_moe import routed_part


# -- weights ------------------------------------------------------------------

def top_weights(seed: int, cfg: dict) -> dict:
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    return {"embed": _draw(seed, "embed", (v, d), 1.0),
            "head": _mat(seed, "head", d, v),
            "final_norm": _gain(seed, "final_norm", d)}


def layer_weights(seed: int, cfg: dict, l: int, held=None) -> dict:
    """Layer ``l``'s tensors; ``held``: the range of experts to draw
    (default: the configuration's)."""
    d, hd = cfg["hidden_size"], cfg["head_dim"]
    heads = cfg["num_attention_heads_per_layer"][l]
    kv = cfg["num_key_value_heads"] * hd
    p = f"layers.{l}."
    w = {"operator_norm": _gain(seed, p + "operator_norm", d),
         "ffn_norm": _gain(seed, p + "ffn_norm", d),
         "wq": _mat(seed, p + "wq", d, heads * hd),
         "wk": _mat(seed, p + "wk", d, kv),
         "wv": _mat(seed, p + "wv", d, kv),
         "wg": _mat(seed, p + "wg", d, heads),
         "wo": _mat(seed, p + "wo", heads * hd, d, OUT_GAIN)}
    if cfg["mlp_layer_types"][l] == "dense":
        i = cfg["intermediate_size"]
        w.update(w_gate=_mat(seed, p + "w_gate", d, i),
                 w_up=_mat(seed, p + "w_up", d, i),
                 w_down=_mat(seed, p + "w_down", i, d, OUT_GAIN))
    else:
        i, s = (cfg["moe_intermediate_size"],
                cfg["shared_expert_intermediate_size"])
        ex = range(*(held if held is not None else held_range(cfg)))
        w.update(router=_mat(seed, p + "router", d, cfg["num_experts"]),
                 shared_gate=_mat(seed, p + "shared_gate", d, s),
                 shared_up=_mat(seed, p + "shared_up", d, s),
                 shared_down=_mat(seed, p + "shared_down", s, d, OUT_GAIN),
                 exp_gate=_mat(seed, p + "exp_gate", d, i, experts=ex),
                 exp_up=_mat(seed, p + "exp_up", d, i, experts=ex),
                 exp_down=_mat(seed, p + "exp_down", i, d, OUT_GAIN,
                               experts=ex))
    return w


# -- the block ----------------------------------------------------------------

def inv_freq(rope: dict, dim: int) -> np.ndarray:
    """The frequencies of ``dim`` rotated values, by the layer type's
    ``rope_parameters``."""
    base = rope["rope_theta"]
    plain = 1.0 / base ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    if rope["rope_type"] == "default":
        return plain.astype(np.float32)

    def correction_dim(rotations):
        return (dim * math.log(rope["original_max_position_embeddings"]
                               / (rotations * 2 * math.pi))
                / (2 * math.log(base)))

    low = max(math.floor(correction_dim(rope["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(rope["beta_slow"])), dim - 1)
    ramp = np.clip((np.arange(dim // 2) - low) / max(high - low, 1e-3), 0, 1)
    return (plain / rope["factor"] * ramp + plain * (1 - ramp)).astype(
        np.float32)


def rope(x, rope: dict):
    """``x [B, L, heads, head_dim]`` at positions ``0 .. L - 1``: the
    first ``partial_rotary_factor`` of every head turned, pairs ``(x[i],
    x[i + dim/2])`` of that part; the rest as it is."""
    dim = int(x.shape[-1] * rope["partial_rotary_factor"])
    angle = (jnp.arange(x.shape[1])[:, None].astype(jnp.float32)
             * jnp.asarray(inv_freq(rope, dim)))[:, None]
    scale = rope.get("attention_factor", 1.0)
    cos, sin = jnp.cos(angle) * scale, jnp.sin(angle) * scale
    a, b = jnp.split(x[..., :dim], 2, -1)
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin,
                            x[..., dim:]], -1)


def attention(w, cfg, x, kind: str, quant=None):
    """``x [B, L, hidden]`` normed -> ``(out, kept [B, L, 2 x kv heads x
    head])``: the keys as a flow's state would hold them, then the
    values."""
    B, L, _ = x.shape
    G, hd = cfg["num_key_value_heads"], cfg["head_dim"]
    H = w["wq"].shape[1] // hd
    R = H // G
    how = cfg["rope_parameters"][kind]
    q = rope(_mm(x, w["wq"], quant).reshape(B, L, H, hd), how)
    k = rope(_mm(x, w["wk"], quant).reshape(B, L, G, hd), how)
    v = _mm(x, w["wv"], quant).reshape(B, L, G, hd)
    pos = jnp.arange(L)
    seen = pos[None, :] <= pos[:, None]
    if kind == "sliding_attention":
        seen &= pos[None, :] > pos[:, None] - cfg["sliding_window"]

    def group(qkv):         # a key/value head's query heads at a time
        qg, kg, vg = qkv
        s = _ein("bthd,bsd->bhts", qg, kg, quant) * hd ** -0.5
        p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), -1)
        return _ein("bhts,bsd->bthd", p, vg, quant)

    out = jax.lax.map(group, (
        q.reshape(B, L, G, R, hd).transpose(2, 0, 1, 3, 4),
        k.transpose(2, 0, 1, 3), v.transpose(2, 0, 1, 3)))
    gate = jax.nn.sigmoid(_mm(x, w["wg"], quant))           # [B, L, H]
    o = out.transpose(1, 2, 0, 3, 4).reshape(B, L, H, hd) * gate[..., None]
    return (_mm(o.reshape(B, L, H * hd), w["wo"], quant),
            jnp.concatenate([k.reshape(B, L, G * hd),
                             v.reshape(B, L, G * hd)], -1))


def route(w, cfg, x):
    """``(selected experts [.., k], their weights, the margin between the
    last selected and the first left out)``: float32, never quantised."""
    s = jax.nn.sigmoid(jnp.matmul(x, w["router"], precision="highest"))
    k = cfg["num_experts_per_tok"]
    top, idx = jax.lax.top_k(s, k + 1)
    return (idx[..., :k], top[..., :k] / top[..., :k].sum(-1, keepdims=True)
            * cfg["moe_routed_scaling_factor"], top[..., k - 1] - top[..., k])


@functools.partial(jax.jit, static_argnames=("cfg_key", "quant", "kind"))
def _attend(w, h, *, cfg_key, quant, kind):
    """``h [B, L, hidden]`` -> ``(h + Attn(RMSNorm(h)), kept)``."""
    cfg = _CFGS[cfg_key]
    a, kept = attention(w, cfg, rms_norm(h, w["operator_norm"],
                                         cfg["rms_norm_eps"]), kind, quant)
    return h + a, kept


@functools.partial(jax.jit, static_argnames=("cfg_key", "quant", "lo"))
def _feed(w, h, *, cfg_key, quant, lo):
    """``h [rows, hidden]``, positions of any sequences (the feed-forward
    is a position's own) -> ``(h + FFN(RMSNorm(h)), margin [rows])``."""
    cfg = _CFGS[cfg_key]
    x = rms_norm(h, w["ffn_norm"], cfg["rms_norm_eps"])
    if "router" in w:
        # the router sees what the experts see: the stated compute type's
        # values of x, in float32 arithmetic
        idx, wts, margin = route(w, cfg, _q(x, PRECISION[
            cfg["model"]["compute_dtype"]]))
        y = (swiglu(x, w["shared_gate"], w["shared_up"], w["shared_down"],
                    quant)
             + routed_part(w, cfg, x, idx, wts, lo, quant))
    else:
        y = swiglu(x, w["w_gate"], w["w_up"], w["w_down"], quant)
        margin = jnp.ones(h.shape[:-1], jnp.float32)
    return h + y, margin


@functools.partial(jax.jit, static_argnames=("cfg_key", "quant"))
def _head(top, h, ids, *, cfg_key, quant):
    """``h [rows, hidden]`` and the id that follows each row's position
    -> that id's score under the row's logits ``[rows]``."""
    cfg = _CFGS[cfg_key]
    logits = _mm(rms_norm(h, top["final_norm"], cfg["rms_norm_eps"]),
                 top["head"], quant)
    nll = (jax.nn.logsumexp(logits, -1)
           - jnp.take_along_axis(logits, ids[:, None], -1)[:, 0])
    return 1.0 - jnp.exp(-nll / math.log(cfg["vocab_size"]))


def _rows(fn, h, rows: int, *more):
    """``fn`` over ``h [B, L, hidden]`` (and ``more [B, L]``) as rows, in
    calls of exactly ``rows`` rows (the last filled up with zeros), so
    that one compiled program serves sequences of every length."""
    B, L, d = h.shape
    flat = [h.reshape(B * L, d)] + [m.reshape(B * L) for m in more]
    outs = []
    for a in range(0, B * L, rows):
        part = [jnp.pad(f[a:a + rows],
                        ((0, max(0, a + rows - B * L)),) + ((0, 0),) * (
                            f.ndim - 1)) for f in flat]
        outs.append(fn(*part))
    many = isinstance(outs[0], tuple)
    outs = [jnp.concatenate([o[i] if many else o for o in outs])[:B * L]
            for i in range(len(outs[0]) if many else 1)]
    outs = [o.reshape(B, L, *o.shape[1:]) for o in outs]
    return tuple(outs) if many else outs[0]


def forward_groups(seed: int, cfg: dict, groups: list, held=None) -> list:
    """Several forwards under one draw of the weights, a layer's tensors
    drawn once for all of them: ``groups`` is ``[(tokens [B, L], quant,
    keep)]`` and each gets what ``forward`` returns, ``"kept"`` only
    where ``keep`` (a layer's ``[B, L, 2,048]`` float32 crosses to the
    host for it). **The compiled programs are few**: attention, which
    sees a sequence whole, takes ``positions // L`` sequences a call
    (the last call filled up with sequences of zeros), so one program a
    length; the feed-forward and the head are a position's own and take
    ``positions`` rows a call, whatever sequences they are of."""
    key = _register(cfg)
    lo = (held if held is not None else held_range(cfg))[0]
    rows = cfg["model"]["positions"]
    with jax.default_matmul_precision("highest"):
        top = top_weights(seed, cfg)
        tokens = [jnp.asarray(t, jnp.int32) for t, _, _ in groups]
        hs = [top["embed"][t] for t in tokens]
        kept = [[] for _ in groups]
        margins = [[] for _ in groups]
        for l in range(cfg["num_hidden_layers"]):
            w = layer_weights(seed, cfg, l, held)
            for g, (_, quant, keep) in enumerate(groups):
                B, L, _ = hs[g].shape
                at_once = max(1, rows // L)
                outs = []
                for a in range(0, B, at_once):
                    part = hs[g][a:a + at_once]
                    part = jnp.pad(part, ((0, at_once - len(part)),
                                          (0, 0), (0, 0)))
                    outs.append(_attend(w, part, cfg_key=key, quant=quant,
                                        kind=cfg["layer_types"][l]))
                if keep:
                    kept[g].append(np.concatenate(
                        [np.asarray(o[1]) for o in outs])[:B])
                h = jnp.concatenate([o[0] for o in outs])[:B]
                hs[g], margin = _rows(
                    lambda x: _feed(w, x, cfg_key=key, quant=quant, lo=lo),
                    h, rows)
                margins[g].append(np.asarray(margin))
            del w, outs
        out = []
        for g, (_, quant, keep) in enumerate(groups):
            score = _rows(
                lambda x, ids: _head(top, x, ids, cfg_key=key, quant=quant),
                hs[g], rows, jnp.roll(tokens[g], -1, 1))
            out.append({"score": np.pad(np.asarray(score)[:, :-1],
                                        ((0, 0), (1, 0))),
                        "margin": np.stack(margins[g]),
                        **({"kept": kept[g]} if keep else {})})
        return out


def forward(seed: int, cfg: dict, tokens, quant=None, held=None) -> dict:
    """``tokens [B, L]`` int32, position 0 the start token (id 0), padded
    at the end with any id (causality keeps padding out of what comes
    before it). Returns ``{"score" [B, L], "kept": a layer's [B, L, 2 x kv
    heads x head], "margin" [layers, B, L]}`` as NumPy; ``score[:, 0]`` is
    0 and ``margin`` is 1 in a dense layer."""
    return forward_groups(seed, cfg, [(tokens, quant, True)], held)[0]
