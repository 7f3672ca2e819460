"""Plain reference of the flow model of ``configs/kimi-k2-6-ep32.json``:
the DeepSeek-V3 family's block as Kimi-K2.6's ``config.json`` sizes it,
cut to the configuration's share (the held experts, the vocabulary's
slice, the layers kept).

``h0 = E[id]``; per layer ``h += Attn(RMSNorm(h))`` then
``h += FFN(RMSNorm(h))``; a final RMSNorm; ``logits = h Wout``.

- Attention (MLA), up-projected, no cache: ``cq = RMSNorm(h Wdq)``,
  ``q = cq Wuq`` into heads of ``nope + rope``; ``[ckv, kr] = h Wdkv``,
  ``ckv = RMSNorm(ckv)``, ``[k_nope, v] = ckv Wukv`` per head, ``kr`` one
  rope key for all heads; RoPE (YaRN's blended frequencies, rotate-half
  pairing) on ``q_rope`` and ``kr`` at the token's position; scores
  ``(q_nope k_nope + q_rope kr) * (nope + rope)^-0.5 * (0.1 ln factor +
  1)^2``, causal, softmax; heads concatenated through ``Wo``. What a cache
  would hold of a position, ``[ckv, kr]`` after the norm and the rotation,
  is returned beside the output (``entries``).
- FFN: dense SwiGLU in the first ``first_k_dense_replace`` layers; in the
  others ``shared(x) + routed(x)``: ``s = sigmoid(x Wr)`` over the whole
  layer's experts, the top ``num_experts_per_tok`` of ``s + b``, weights
  the selected ``s`` over their sum times ``routed_scaling_factor``;
  ``routed`` sums over the selected experts *that are held* (the
  configuration's range, or the one asked for): every held expert is
  computed for every token and masked, no sorting, no capacity.
- The score of the token at position ``t >= 1``: ``1 - exp(-nll / ln V)``
  with ``nll = -log_softmax(logits[t - 1])[id_t]`` over the slice.

Straight ``jax.numpy`` in float32 under ``jax.default_matmul_precision
("highest")``, one whole sequence forward at a time, layer by layer so
that one layer's float32 weights are on the device at a time. It imports
nothing of the program. Weights are its own draw from the seed by the
configuration file's rule (``weights.rule``): tensor ``name`` is
``(mean + std * normal(fold_in(fold_in(key(seed), crc32(name))[, expert]),
shape, float32))`` rounded to bfloat16 (the parameters' stated type), an
expert's tensors folding the expert's index in.

``quant``: ``None`` is the reference; ``"bf16"`` rounds both operands
ahead of every matrix product to bfloat16 (the configuration's stated
compute type: the reference's own rounding error, against which the
program's is measured); ``"fp8"`` to float8 e4m3 (the control).
"""

from __future__ import annotations

import functools
import math
import zlib

import jax
import jax.numpy as jnp
import numpy as np

QUANT = {"fp8": (4, 3, 240.0), "bf16": (8, 7, 3.3e38)}
PRECISION = {"bfloat16": "bf16"}
OUT_GAIN, GAIN_SPREAD, BIAS_SPREAD = 0.3, 0.1, 0.01


def _q(a, quant):
    if quant is None:
        return a
    e, m, largest = QUANT[quant]
    return jax.lax.reduce_precision(jnp.clip(a, -largest, largest), e, m)


def _mm(a, b, quant):
    return jnp.matmul(_q(a, quant), _q(b, quant), precision="highest")


def _ein(spec, a, b, quant):
    return jnp.einsum(spec, _q(a, quant), _q(b, quant), precision="highest")


def held_range(cfg: dict):
    return tuple(cfg["model"]["experts_held"])


# -- weights ------------------------------------------------------------------

def _draw(seed, name, shape, std, mean=0.0, experts=None):
    k = jax.random.fold_in(jax.random.key(seed),
                           np.uint32(zlib.crc32(name.encode())))

    def one(k):
        w = np.float32(mean) + np.float32(std) * jax.random.normal(
            k, shape, jnp.float32)
        return w.astype(jnp.bfloat16).astype(jnp.float32)

    if experts is None:
        return one(k)
    return jnp.stack([one(jax.random.fold_in(k, np.uint32(e)))
                      for e in experts])


def _mat(seed, name, i, o, gain=1.0, experts=None):
    return _draw(seed, name, (i, o), gain / math.sqrt(i), experts=experts)


def _gain(seed, name, n):
    return _draw(seed, name, (n,), GAIN_SPREAD, 1.0)


def top_weights(seed: int, cfg: dict) -> dict:
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    return {"embed": _draw(seed, "embed", (v, d), 1.0),
            "head": _mat(seed, "head", d, v),
            "final_norm": _gain(seed, "final_norm", d)}


def layer_weights(seed: int, cfg: dict, l: int, held=None) -> dict:
    """Layer ``l``'s tensors; ``held``: the range of experts to draw
    (default: the configuration's)."""
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    nope, rope, vd = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                      cfg["v_head_dim"])
    qr, kvr = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    p = f"layers.{l}."
    w = {"attn_norm": _gain(seed, p + "attn_norm", d),
         "wdq": _mat(seed, p + "wdq", d, qr),
         "q_norm": _gain(seed, p + "q_norm", qr),
         "wuq": _mat(seed, p + "wuq", qr, h * (nope + rope)),
         "wdkv": _mat(seed, p + "wdkv", d, kvr + rope),
         "kv_norm": _gain(seed, p + "kv_norm", kvr),
         "wukv": _mat(seed, p + "wukv", kvr, h * (nope + vd)),
         "wo": _mat(seed, p + "wo", h * vd, d, OUT_GAIN),
         "ffn_norm": _gain(seed, p + "ffn_norm", d)}
    if l < cfg["first_k_dense_replace"]:
        i = cfg["intermediate_size"]
        w.update(w_gate=_mat(seed, p + "w_gate", d, i),
                 w_up=_mat(seed, p + "w_up", d, i),
                 w_down=_mat(seed, p + "w_down", i, d, OUT_GAIN))
    else:
        i = cfg["moe_intermediate_size"]
        s = i * cfg["n_shared_experts"]
        e = cfg["model"]["router_experts"]
        ex = range(*(held if held is not None else held_range(cfg)))
        w.update(router=_mat(seed, p + "router", d, e),
                 router_bias=_draw(seed, p + "router_bias", (e,),
                                   BIAS_SPREAD),
                 shared_gate=_mat(seed, p + "shared_gate", d, s),
                 shared_up=_mat(seed, p + "shared_up", d, s),
                 shared_down=_mat(seed, p + "shared_down", s, d, OUT_GAIN),
                 exp_gate=_mat(seed, p + "exp_gate", d, i, experts=ex),
                 exp_up=_mat(seed, p + "exp_up", d, i, experts=ex),
                 exp_down=_mat(seed, p + "exp_down", i, d, OUT_GAIN,
                               experts=ex))
    return w


# -- the block ----------------------------------------------------------------

def rms_norm(x, gain, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * gain


def swiglu(x, gate, up, down, quant):
    return _mm(jax.nn.silu(_mm(x, gate, quant)) * _mm(x, up, quant), down,
               quant)


def inv_freq(cfg: dict) -> np.ndarray:
    y = cfg["rope_scaling"]
    dim, base = cfg["qk_rope_head_dim"], cfg["rope_theta"]
    plain = 1.0 / base ** (np.arange(0, dim, 2, dtype=np.float64) / dim)

    def correction_dim(rotations):
        return (dim * math.log(y["original_max_position_embeddings"]
                               / (rotations * 2 * math.pi))
                / (2 * math.log(base)))

    low = max(math.floor(correction_dim(y["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(y["beta_slow"])), dim - 1)
    ramp = np.clip((np.arange(dim // 2) - low) / max(high - low, 1e-3), 0, 1)
    return (plain / y["factor"] * ramp + plain * (1 - ramp)).astype(
        np.float32)


def rope(x, pos, cfg):
    """``x [..., L, (heads,) dim]`` at positions ``pos [L]``; pairs
    ``(x[i], x[i + dim/2])``."""
    angle = pos[:, None].astype(jnp.float32) * jnp.asarray(inv_freq(cfg))
    if x.ndim == 4:
        angle = angle[:, None]
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    a, b = jnp.split(x, 2, -1)
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def attention(w, cfg, x, quant=None):
    """``x [B, L, hidden]`` normed -> ``(out, entries [B, L, kv + rope])``."""
    B, L, _ = x.shape
    H, nope, rp, vd = (cfg["num_attention_heads"], cfg["qk_nope_head_dim"],
                       cfg["qk_rope_head_dim"], cfg["v_head_dim"])
    kvr, eps = cfg["kv_lora_rank"], cfg["rms_norm_eps"]
    pos = jnp.arange(L)
    cq = rms_norm(_mm(x, w["wdq"], quant), w["q_norm"], eps)
    q = _mm(cq, w["wuq"], quant).reshape(B, L, H, nope + rp)
    q_nope, q_rope = q[..., :nope], rope(q[..., nope:], pos, cfg)
    ckr = _mm(x, w["wdkv"], quant)
    ckv = rms_norm(ckr[..., :kvr], w["kv_norm"], eps)
    kr = rope(ckr[..., kvr:], pos, cfg)
    kv = _mm(ckv, w["wukv"], quant).reshape(B, L, H, nope + vd)
    k_nope, v = kv[..., :nope], kv[..., nope:]
    m = 0.1 * cfg["rope_scaling"]["mscale_all_dim"] * math.log(
        cfg["rope_scaling"]["factor"]) + 1.0
    s = (_ein("bthd,bshd->bhts", q_nope, k_nope, quant)
         + _ein("bthd,bsd->bhts", q_rope, kr, quant)) * (
             (nope + rp) ** -0.5 * m * m)
    causal = pos[None, :] <= pos[:, None]
    p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), -1)
    o = _ein("bhts,bshd->bthd", p, v, quant).reshape(B, L, H * vd)
    return _mm(o, w["wo"], quant), jnp.concatenate([ckv, kr], -1)


def route(w, cfg, x):
    """``(selected experts [.., k], their weights, the margin between the
    last selected and the first left out)``: float32, never quantised."""
    s = jax.nn.sigmoid(jnp.matmul(x, w["router"], precision="highest"))
    k = cfg["num_experts_per_tok"]
    top, idx = jax.lax.top_k(s + w["router_bias"], k + 1)
    idx = idx[..., :k]
    sel = jnp.take_along_axis(s, idx, -1)
    return (idx, sel / sel.sum(-1, keepdims=True)
            * cfg["routed_scaling_factor"], top[..., k - 1] - top[..., k])


def routed_part(w, cfg, x, idx, wts, lo, quant=None):
    """The sum over the selected experts that ``w`` holds (``exp_*``
    stacked from expert ``lo``)."""
    out = jnp.zeros_like(x)
    for j in range(w["exp_gate"].shape[0]):
        weight = jnp.where(idx == lo + j, wts, 0.0).sum(-1)
        out += weight[..., None] * swiglu(
            x, w["exp_gate"][j], w["exp_up"][j], w["exp_down"][j], quant)
    return out


def shared_part(w, x, quant=None):
    return swiglu(x, w["shared_gate"], w["shared_up"], w["shared_down"],
                  quant)


@functools.partial(jax.jit, static_argnames=("cfg_key", "quant", "lo"))
def _layer(w, h, *, cfg_key, quant, lo):
    cfg = _CFGS[cfg_key]
    eps = cfg["rms_norm_eps"]
    a, entries = attention(w, cfg, rms_norm(h, w["attn_norm"], eps), quant)
    h = h + a
    x = rms_norm(h, w["ffn_norm"], eps)
    if "router" in w:
        # the router sees what the experts see: the stated compute type's
        # values of x, in float32 arithmetic
        idx, wts, margin = route(w, cfg, _q(x, PRECISION[
            cfg["model"]["compute_dtype"]]))
        y = shared_part(w, x, quant) + routed_part(w, cfg, x, idx, wts, lo,
                                                   quant)
    else:
        y = swiglu(x, w["w_gate"], w["w_up"], w["w_down"], quant)
        margin = jnp.ones(h.shape[:-1], jnp.float32)
    return h + y, entries, margin


@functools.partial(jax.jit, static_argnames=("cfg_key", "quant"))
def _head(top, h, tokens, *, cfg_key, quant):
    cfg = _CFGS[cfg_key]
    logits = _mm(rms_norm(h, top["final_norm"], cfg["rms_norm_eps"]),
                 top["head"], quant)
    logp = jax.nn.log_softmax(logits[:, :-1], -1)
    nll = -jnp.take_along_axis(logp, tokens[:, 1:, None], -1)[..., 0]
    score = 1.0 - jnp.exp(-nll / math.log(cfg["vocab_size"]))
    return jnp.pad(score, ((0, 0), (1, 0)))


# a configuration (a dict) cannot be a static argument: the jitted
# functions get a key into this table
_CFGS: dict = {}


def _register(cfg: dict) -> str:
    key = repr(sorted((k, repr(v)) for k, v in cfg.items()))
    _CFGS.setdefault(key, cfg)
    return key


def forward(seed: int, cfg: dict, tokens, quant=None, held=None,
            block: int = 4) -> dict:
    """``tokens [B, L]`` int32, position 0 the start token (id 0), padded
    at the end with any id (causality keeps padding out of what comes
    before it). Returns ``{"score" [B, L], "entries" [layers, B, L, kv +
    rope], "margin" [layers, B, L]}`` as NumPy; ``score[:, 0]`` is 0 and
    ``margin`` is 1 in a dense layer."""
    key = _register(cfg)
    lo = (held if held is not None else held_range(cfg))[0]
    tokens = jnp.asarray(tokens, jnp.int32)
    B = tokens.shape[0]
    with jax.default_matmul_precision("highest"):
        top = top_weights(seed, cfg)
        h = top["embed"][tokens]
        entries, margins = [], []
        for l in range(cfg["num_hidden_layers"]):
            w = layer_weights(seed, cfg, l, held)
            outs = [_layer(w, h[a:a + block], cfg_key=key, quant=quant,
                           lo=lo) for a in range(0, B, block)]
            h = jnp.concatenate([o[0] for o in outs])
            entries.append(np.concatenate([np.asarray(o[1]) for o in outs]))
            margins.append(np.concatenate([np.asarray(o[2]) for o in outs]))
            del w, outs
        score = np.concatenate([
            np.asarray(_head(top, h[a:a + block], tokens[a:a + block],
                             cfg_key=key, quant=quant))
            for a in range(0, B, block)])
    return {"score": score, "entries": np.stack(entries),
            "margin": np.stack(margins)}
