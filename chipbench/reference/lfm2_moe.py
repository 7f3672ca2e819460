"""Plain reference of the flow model of ``configs/lfm2-24b-a2b.json``:
LFM2-MoE's block as LFM2-24B-A2B's ``config.json`` sizes it, cut in depth
alone (every expert of a layer and the whole vocabulary are here).

``h0 = E[id]``; per layer ``h += Op(RMSNorm(h))`` then
``h += FFN(RMSNorm(h))``; a final RMSNorm (the published
``embedding_norm``); ``logits = h E^T``, embedding and head tied.

- Op of a ``conv`` layer, the gated short convolution: ``[B, C, X] =
  h W_in`` in thirds, ``u = B * X``, ``v[t] = sum_j w[j] u[t - (K - 1) +
  j]`` over ``K = conv_L_cache`` taps, depthwise, causal, by explicit
  shifts of the whole sequence with zeros before position 0; ``y = (C *
  v) W_out``. What a flow's state must hold of a position is ``u``.
- Op of a ``full_attention`` layer, grouped-query attention, no cache:
  ``q = h Wq`` in ``num_attention_heads`` heads, ``k = h Wk``, ``v = h
  Wv`` in ``num_key_value_heads``; ``q`` and ``k`` RMS-normed per head;
  RoPE (``rope_theta``, the default kind, rotate-half pairing) on both at
  the token's position; the key/value heads repeated ``heads / kv heads``
  times, so query head ``i`` meets key/value head ``i // (heads / kv
  heads)``; scores ``q . k / sqrt(head)``, causal, softmax; heads
  concatenated through ``Wo``. What a cache would hold of a position, the
  normed, rotated keys and then the values, is returned (``kept``).
- FFN: dense SwiGLU in the first ``num_dense_layers`` layers; in the
  others ``s = sigmoid(x Wr)`` over ``num_experts``, the top
  ``num_experts_per_tok`` of ``s + b``, weights the selected ``s`` over
  their sum + 1e-6, times ``routed_scaling_factor``; the sum over the
  selected experts, every held expert computed for every token and masked
  by the selection: no sorting, no capacity, no shared expert.
- The score of the token at position ``t >= 1``: ``1 - exp(-nll / ln V)``
  with ``nll = -log_softmax(logits[t - 1])[id_t]``.

Straight ``jax.numpy`` in float32 under ``jax.default_matmul_precision
("highest")``, one whole sequence forward at a time, layer by layer so
that one layer's float32 weights are on the device at a time. It imports
nothing of the program; the rounding, the products and the draw of a
tensor from the seed are ``reference/latent_moe.py``'s.
Weights are its own draw by the configuration file's rule
(``weights.rule``).

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

ROUTE_EPS = 1e-6


# -- weights ------------------------------------------------------------------

def top_weights(seed: int, cfg: dict) -> dict:
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    return {"embed": _draw(seed, "embed", (v, d), 1 / math.sqrt(d)),
            "final_norm": _gain(seed, "final_norm", d)}


def layer_weights(seed: int, cfg: dict, l: int, held=None) -> dict:
    """Layer ``l``'s tensors; ``held``: the range of experts to draw
    (default: the configuration's)."""
    d = cfg["hidden_size"]
    hd = d // cfg["num_attention_heads"]
    kv = cfg["num_key_value_heads"] * hd
    p = f"layers.{l}."
    w = {"operator_norm": _gain(seed, p + "operator_norm", d),
         "ffn_norm": _gain(seed, p + "ffn_norm", d)}
    if cfg["layer_types"][l] == "conv":
        K = cfg["conv_L_cache"]
        w.update(in_proj=_mat(seed, p + "in_proj", d, 3 * d),
                 conv=_draw(seed, p + "conv", (K, d), 1 / math.sqrt(K)),
                 out_proj=_mat(seed, p + "out_proj", d, d, OUT_GAIN))
    else:
        w.update(wq=_mat(seed, p + "wq", d, d),
                 wk=_mat(seed, p + "wk", d, kv),
                 wv=_mat(seed, p + "wv", d, kv),
                 q_norm=_gain(seed, p + "q_norm", hd),
                 k_norm=_gain(seed, p + "k_norm", hd),
                 wo=_mat(seed, p + "wo", d, d, OUT_GAIN))
    if l < cfg["num_dense_layers"]:
        i = cfg["intermediate_size"]
        w.update(w_gate=_mat(seed, p + "w_gate", d, i),
                 w_up=_mat(seed, p + "w_up", d, i),
                 w_down=_mat(seed, p + "w_down", i, d, OUT_GAIN))
    else:
        i, e = cfg["moe_intermediate_size"], cfg["num_experts"]
        ex = range(*(held if held is not None else held_range(cfg)))
        w.update(router=_mat(seed, p + "router", d, e),
                 router_bias=_draw(seed, p + "router_bias", (e,),
                                   cfg["model"]["router_bias_std"]),
                 exp_gate=_mat(seed, p + "exp_gate", d, i, experts=ex),
                 exp_up=_mat(seed, p + "exp_up", d, i, experts=ex),
                 exp_down=_mat(seed, p + "exp_down", i, d, OUT_GAIN,
                               experts=ex))
    return w


# -- the block ----------------------------------------------------------------

def short_conv(w, cfg, x, quant=None):
    """``x [B, L, hidden]`` normed -> ``(out, u [B, L, hidden])``."""
    L, K = x.shape[1], cfg["conv_L_cache"]
    gate_in, gate_out, xs = jnp.split(_mm(x, w["in_proj"], quant), 3, -1)
    u = gate_in * xs
    # tap j meets u of K - 1 - j positions back: u shifted, zeros before 0
    v = sum(w["conv"][j] * jnp.pad(u, ((0, 0), (K - 1 - j, 0), (0, 0)))[:, :L]
            for j in range(K))
    return _mm(gate_out * v, w["out_proj"], quant), u


def rope(x, cfg):
    """``x [B, L, heads, dim]`` at positions ``0 .. L - 1``; pairs
    ``(x[i], x[i + dim/2])``."""
    dim = x.shape[-1]
    inv_freq = (1.0 / cfg["rope_parameters"]["rope_theta"] ** (
        np.arange(0, dim, 2, dtype=np.float64) / dim)).astype(np.float32)
    angle = (jnp.arange(x.shape[1])[:, None].astype(jnp.float32)
             * jnp.asarray(inv_freq))[:, None]
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    a, b = jnp.split(x, 2, -1)
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def attention(w, cfg, x, quant=None):
    """``x [B, L, hidden]`` normed -> ``(out, kept [B, L, 2 x kv heads x
    head])``: the keys as a cache would hold them, then the values."""
    B, L, D = x.shape
    H, G, eps = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["norm_eps"])
    hd = D // H
    q = rope(rms_norm(_mm(x, w["wq"], quant).reshape(B, L, H, hd),
                      w["q_norm"], eps), cfg)
    k = rope(rms_norm(_mm(x, w["wk"], quant).reshape(B, L, G, hd),
                      w["k_norm"], eps), cfg)
    v = _mm(x, w["wv"], quant).reshape(B, L, G, hd)
    s = _ein("bthd,bshd->bhts", q, jnp.repeat(k, H // G, 2),
             quant) * hd ** -0.5
    pos = jnp.arange(L)
    p = jax.nn.softmax(jnp.where(pos[None, :] <= pos[:, None], s, -jnp.inf),
                       -1)
    o = _ein("bhts,bshd->bthd", p, jnp.repeat(v, H // G, 2), quant)
    return (_mm(o.reshape(B, L, D), w["wo"], quant),
            jnp.concatenate([k.reshape(B, L, G * hd),
                             v.reshape(B, L, G * hd)], -1))


def route(w, cfg, x):
    """``(selected experts [.., k], their weights, the margin between the
    last selected and the first left out)``: float32, never quantised."""
    s = jax.nn.sigmoid(jnp.matmul(x, w["router"], precision="highest"))
    k = cfg["num_experts_per_tok"]
    top, idx = jax.lax.top_k(s + w["router_bias"], k + 1)
    idx = idx[..., :k]
    sel = jnp.take_along_axis(s, idx, -1)
    return (idx, sel / (sel.sum(-1, keepdims=True) + ROUTE_EPS)
            * cfg["routed_scaling_factor"], top[..., k - 1] - top[..., k])


def routed_part(w, cfg, x, idx, wts, lo, quant=None):
    """The sum over the selected experts that ``w`` holds (``exp_*``
    stacked from expert ``lo``): every one of them computed for every
    token, one after the other, and weighed by the selection (nought for
    a token that did not select it)."""
    def add(out, expert):
        j, gate, up, down = expert
        weight = jnp.where(idx == lo + j, wts, 0.0).sum(-1)
        return out + weight[..., None] * swiglu(x, gate, up, down, quant), None

    held = w["exp_gate"].shape[0]
    return jax.lax.scan(add, jnp.zeros_like(x), (
        jnp.arange(held), w["exp_gate"], w["exp_up"], w["exp_down"]))[0]


@functools.partial(jax.jit,
                   static_argnames=("cfg_key", "quant", "lo", "kind"))
def _layer(w, h, *, cfg_key, quant, lo, kind):
    cfg = _CFGS[cfg_key]
    eps = cfg["norm_eps"]
    op = short_conv if kind == "conv" else attention
    a, kept = op(w, cfg, rms_norm(h, w["operator_norm"], eps), quant)
    h = h + a
    x = rms_norm(h, w["ffn_norm"], eps)
    if "router" in w:
        # the router sees what the experts see: the stated compute type's
        # values of x, in float32 arithmetic
        idx, wts, margin = route(w, cfg, _q(x, PRECISION[
            cfg["model"]["compute_dtype"]]))
        y = routed_part(w, cfg, x, idx, wts, lo, quant)
    else:
        y = swiglu(x, w["w_gate"], w["w_up"], w["w_down"], quant)
        margin = jnp.ones(h.shape[:-1], jnp.float32)
    return h + y, kept, margin


@functools.partial(jax.jit, static_argnames=("cfg_key", "quant"))
def _head(top, h, tokens, *, cfg_key, quant):
    cfg = _CFGS[cfg_key]
    logits = _ein("bld,vd->blv",
                  rms_norm(h, top["final_norm"], cfg["norm_eps"]),
                  top["embed"], quant)
    logp = jax.nn.log_softmax(logits[:, :-1], -1)
    nll = -jnp.take_along_axis(logp, tokens[:, 1:, None], -1)[..., 0]
    score = 1.0 - jnp.exp(-nll / math.log(cfg["vocab_size"]))
    return jnp.pad(score, ((0, 0), (1, 0)))


def forward(seed: int, cfg: dict, tokens, quant=None, held=None,
            block: int = 4) -> dict:
    """``tokens [B, L]`` int32, position 0 the start token (id 0), padded
    at the end with any id (causality keeps padding out of what comes
    before it). Returns ``{"score" [B, L], "kept": a layer's [B, L, width]
    (``u`` of a conv layer, keys and values of an attention layer),
    "margin" [layers, B, L]}`` as NumPy; ``score[:, 0]`` is 0 and
    ``margin`` is 1 in a dense layer."""
    key = _register(cfg)
    lo = (held if held is not None else held_range(cfg))[0]
    tokens = jnp.asarray(tokens, jnp.int32)
    B = tokens.shape[0]
    with jax.default_matmul_precision("highest"):
        top = top_weights(seed, cfg)
        h = top["embed"][tokens]
        kept, margins = [], []
        for l in range(cfg["num_hidden_layers"]):
            w = layer_weights(seed, cfg, l, held)
            outs = [_layer(w, h[a:a + block], cfg_key=key, quant=quant,
                           lo=lo, kind=cfg["layer_types"][l])
                    for a in range(0, B, block)]
            h = jnp.concatenate([o[0] for o in outs])
            kept.append(np.concatenate([np.asarray(o[1]) for o in outs]))
            margins.append(np.concatenate([np.asarray(o[2]) for o in outs]))
            del w, outs
        score = np.concatenate([
            np.asarray(_head(top, h[a:a + block], tokens[a:a + block],
                             cfg_key=key, quant=quant))
            for a in range(0, B, block)])
    return {"score": score, "kept": kept, "margin": np.stack(margins)}
