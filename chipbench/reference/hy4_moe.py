"""Plain reference of the flow model of ``configs/hy4-preview-ep16.json``:
Hy4-preview's block as its ``config.json`` sizes it, cut to the
configuration's share (the held experts, the vocabulary's slice, the
layers kept).

``X0 = E[id]`` in each of ``hc_mult`` streams; per layer ``l`` two
sublayers, the attention and the feed-forward, each wrapped by a
hyper-connection: ``x~ = RMSNorm(vec X)`` (no gain), ``a = alpha . (x~
phi) + b`` (``alpha`` one scalar for each of the three groups of ``a``),
``H_pre = sigmoid(a[:n])``, ``H_post = hc_magnitude sigmoid(a[n:2n])``,
``H_res = Sinkhorn(exp(a[2n:]))`` (rows then columns divided by their sums
plus ``hc_eps``, ``model.hc_sinkhorn_iterations`` times), ``u = sum_i
H_pre[i] X_i``, ``X'_i = sum_j H_res[i, j] X_j + H_post[i] F(u)``; the
final hidden ``RMSNorm(sum_i X_i)``; ``logits = h Wout``. With
``enable_ihc`` false, one stream and ``X' = X + F(X)``.

- Attention (gated MLA over an indexer's selection, with a sink),
  **up-projected, no cache**: ``cq = RMSNorm(x Wdq)``, ``q = cq Wuq`` in
  heads of ``nope + rope``; ``[ckv, kr] = x Wdkv``, ``ckv =
  RMSNorm(ckv)``, ``[k_nope, v] = ckv Wukv`` a head, ``kr`` one rope key
  for all heads; RoPE (plain frequencies at ``rope_theta``, rotate-half
  pairing) on ``q_rope`` and ``kr`` at the token's position; scores
  ``(q_nope k_nope + q_rope kr) (nope + rope)^-1/2``. On a ``full``
  layer of ``indexer_types`` the indexer: ``qI = cq WiQ`` in
  ``index_n_heads`` of ``index_head_dim``, ``kI = LayerNorm(x WiK)``
  (gain, bias, eps 1e-6), RoPE on the first ``qk_rope_head_dim`` values of
  both, ``wI = x WiW / sqrt(heads x dim)``, ``I[t, s] = sum_j wI_j
  relu(qI_j . kI_s)`` for ``s <= t``; the selection of the token at ``t``
  is the top ``min(index_topk, t + 1)`` positions by ``I``
  (``lax.top_k``: ties to the earlier position). A ``shared`` layer takes
  the selection of the ``full`` layer before it. Softmax over the
  selected positions with the head's sink in the sum (``e^{l_s} / (sum
  e^{l_s'} + e^{sink})``); each head's output times ``sigmoid(x Wg)``,
  element by element; heads concatenated through ``Wo``. What a flow's
  state would hold of a position (``[ckv, kr]``, and ``kI`` on a full
  layer) is returned.
- FFN: a dense SwiGLU where ``mlp_layer_types`` says ``dense``; else
  ``shared(x) + routed(x)``: ``s = sigmoid(x Wr)`` over the whole layer's
  experts, the top ``num_experts_per_tok`` of ``s + b``, weights the
  selected ``s`` over their sum times ``routed_scaling_factor``, summed
  over the selected experts **that are held** (every held expert
  computed for every token and masked). Every SwiGLU is ``silu(min(g,
  limit)) * clip(u, -limit, limit)``, ``limit = swiglu_limit``.
- The score of the token at ``t >= 1``: ``1 - exp(-nll / ln V)`` with
  ``nll = -log_softmax(logits[t - 1])[id_t]``.

Straight ``jax.numpy`` in float32 under ``jax.default_matmul_precision
("highest")``, every layer's weights held in the bfloat16 they are drawn
in and widened to float32 by each product, a few sequences at a time
through all layers (``forward_groups``). Attention takes a sequence whole, its
queries in blocks of ``QUERY_BLOCK`` rows (the scores of a block, ``[64,
256, L]``, and the index's ``[256, 32, L]``); the feed-forward and the
head are a position's own and take ``positions`` rows a call. It imports
nothing of the program; the rounding, the products and the draw of a
tensor from the seed are ``reference/latent_moe.py``'s. Weights are its
own draw by the configuration file's rule (``weights.rule``).

``quant``: ``None`` is the reference; ``"bf16"`` rounds both operands
ahead of every matrix product to bfloat16 (the configuration's stated
compute type; the head, stated float32, is not rounded); ``"fp8"`` to
float8 e4m3 (the control), the head too.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

# noqa: F401 below: PRECISION and _q are the check's and the tests'
from chipbench.reference.latent_moe import (  # noqa: F401
    BIAS_SPREAD, OUT_GAIN, PRECISION, _CFGS, _draw, _ein, _gain, _mat, _mm,
    _q, _register, held_range, rms_norm, top_weights,
)

QUERY_BLOCK = 256   # query rows of a sequence attended at a time, at most
SINK = (4.0, 2.0)   # a head's sink logit: mean, std (the file's rule)


# -- weights ------------------------------------------------------------------

def _streams(cfg: dict) -> int:
    return cfg["hc_mult"] if cfg["enable_ihc"] else 1


def layer_weights(seed: int, cfg: dict, l: int, held=None) -> dict:
    """Layer ``l``'s tensors, float32; ``held``: the range of experts to
    draw (default: the configuration's)."""
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    nope, rope, vd = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                      cfg["v_head_dim"])
    qr, kvr = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    n = _streams(cfg)
    p = f"layers.{l}."
    w = {"attn_norm": _gain(seed, p + "attn_norm", d),
         "wdq": _mat(seed, p + "wdq", d, qr),
         "q_norm": _gain(seed, p + "q_norm", qr),
         "wuq": _mat(seed, p + "wuq", qr, h * (nope + rope)),
         "wdkv": _mat(seed, p + "wdkv", d, kvr + rope),
         "kv_norm": _gain(seed, p + "kv_norm", kvr),
         "wukv": _mat(seed, p + "wukv", kvr, h * (nope + vd)),
         "wg": _mat(seed, p + "wg", d, h * vd),
         "sink": _draw(seed, p + "sink", (h,), SINK[1], SINK[0]),
         "wo": _mat(seed, p + "wo", h * vd, d, OUT_GAIN),
         "ffn_norm": _gain(seed, p + "ffn_norm", d)}
    if cfg["indexer_types"][l] == "full":
        nh, dh = cfg["index_n_heads"], cfg["index_head_dim"]
        w.update(wiq=_mat(seed, p + "wiq", qr, nh * dh),
                 wik=_mat(seed, p + "wik", d, dh),
                 wiw=_mat(seed, p + "wiw", d, nh),
                 ik_norm=_gain(seed, p + "ik_norm", dh),
                 ik_bias=_draw(seed, p + "ik_bias", (dh,), BIAS_SPREAD))
    if n > 1:
        for sub in ("hc_attn", "hc_ffn"):
            w[sub + "_phi"] = _mat(seed, p + sub + "_phi", n * d, n * (n + 2))
            w[sub + "_alpha"] = _gain(seed, p + sub + "_alpha", 3)
            w[sub + "_bias"] = _draw(seed, p + sub + "_bias", (n * (n + 2),),
                                     1.0)
    if cfg["mlp_layer_types"][l] == "dense":
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

def swiglu(x, gate, up, down, limit, quant):
    g, u = _mm(x, gate, quant), _mm(x, up, quant)
    return _mm(jax.nn.silu(jnp.minimum(g, limit))
               * jnp.clip(u, -limit, limit), down, quant)


def sinkhorn(m, iterations: int, eps: float):
    for _ in range(iterations):
        m = m / (m.sum(-1, keepdims=True) + eps)
        m = m / (m.sum(-2, keepdims=True) + eps)
    return m


def hyper_pre(w, sub: str, cfg: dict, X, quant):
    """``X [..., n, C]`` -> ``(u [..., C], post [..., n], res [..., n,
    n])``."""
    n = X.shape[-2]
    flat = X.reshape(*X.shape[:-2], -1)
    x = flat * jax.lax.rsqrt(jnp.mean(flat * flat, -1, keepdims=True)
                             + cfg["rms_norm_eps"])
    alpha = jnp.concatenate([jnp.full((k,), w[sub + "_alpha"][g])
                             for g, k in enumerate((n, n, n * n))])
    a = _mm(x, w[sub + "_phi"], quant) * alpha + w[sub + "_bias"]
    pre = jax.nn.sigmoid(a[..., :n])
    post = cfg["hc_magnitude"] * jax.nn.sigmoid(a[..., n:2 * n])
    res = sinkhorn(jnp.exp(a[..., 2 * n:].reshape(*a.shape[:-1], n, n)),
                   cfg["model"].get("hc_sinkhorn_iterations", 20),
                   cfg["hc_eps"])
    return jnp.einsum("...n,...nc->...c", pre, X), post, res


def hyper_post(X, y, post, res):
    return (jnp.einsum("...ij,...jc->...ic", res, X)
            + post[..., None] * y[..., None, :])


def inv_freq(cfg: dict) -> np.ndarray:
    dim = cfg["qk_rope_head_dim"]
    return (1.0 / float(cfg["rope_parameters"]["rope_theta"]) ** (
        np.arange(0, dim, 2, dtype=np.float64) / dim)).astype(np.float32)


def rope(x, cfg: dict, dim: int):
    """``x [B, L, (heads,) width]`` at positions ``0 .. L - 1``: the first
    ``dim`` values turned, pairs ``(x[i], x[i + dim/2])``; the rest as it
    is."""
    angle = (jnp.arange(x.shape[1])[:, None].astype(jnp.float32)
             * jnp.asarray(inv_freq(cfg)))
    if x.ndim == 4:
        angle = angle[:, None]
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    a, b = jnp.split(x[..., :dim], 2, -1)
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin,
                            x[..., dim:]], -1)


def layer_norm(x, gain, bias, eps=1e-6):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * gain + bias


def attention(w, cfg: dict, x, chosen, quant=None):
    """``x [B, L, hidden]`` normed, ``chosen [B, L, L]`` the selection of
    the ``full`` layer before (None on a ``full`` layer, which makes its
    own) -> ``(out, entries [B, L, kv + rope], index keys [B, L, dim] or
    None, the selection [B, L, L])``."""
    B, L, _ = x.shape
    H, nope, rp, vd = (cfg["num_attention_heads"], cfg["qk_nope_head_dim"],
                       cfg["qk_rope_head_dim"], cfg["v_head_dim"])
    kvr, eps = cfg["kv_lora_rank"], cfg["rms_norm_eps"]
    cq = rms_norm(_mm(x, w["wdq"], quant), w["q_norm"], eps)
    q = _mm(cq, w["wuq"], quant).reshape(B, L, H, nope + rp)
    q_nope, q_rope = q[..., :nope], rope(q[..., nope:], cfg, rp)
    ckr = _mm(x, w["wdkv"], quant)
    ckv = rms_norm(ckr[..., :kvr], w["kv_norm"], eps)
    kr = rope(ckr[..., kvr:], cfg, rp)
    kv = _mm(ckv, w["wukv"], quant).reshape(B, L, H, nope + vd)
    k_nope, v = kv[..., :nope], kv[..., nope:]
    gate = jax.nn.sigmoid(_mm(x, w["wg"], quant))
    pos = jnp.arange(L)
    full = chosen is None
    ki = None
    if full:
        nh, dh = cfg["index_n_heads"], cfg["index_head_dim"]
        qi = rope(_mm(cq, w["wiq"], quant).reshape(B, L, nh, dh), cfg, rp)
        ki = rope(layer_norm(_mm(x, w["wik"], quant), w["ik_norm"],
                             w["ik_bias"]), cfg, rp)
        wi = _mm(x, w["wiw"], quant) * (nh * dh) ** -0.5
        k = min(cfg["index_topk"], L)
    qb = QUERY_BLOCK if L % QUERY_BLOCK == 0 else L

    def block(at):
        rows = at + jnp.arange(qb)
        causal = pos[None, :] <= rows[:, None]                  # [qb, L]
        if full:
            part = jax.lax.dynamic_slice_in_dim
            got = _ein("bthd,bsd->bths", part(qi, at, qb, 1), ki, quant)
            I = (jax.nn.relu(got) * part(wi, at, qb, 1)[..., None]).sum(2)
            I = jnp.where(causal, I, -jnp.inf)
            _, top = jax.lax.top_k(I, k)
            mine = jnp.zeros((B, qb, L), bool).at[
                jnp.arange(B)[:, None, None], jnp.arange(qb)[None, :, None],
                top].set(True) & causal
        else:
            mine = jax.lax.dynamic_slice_in_dim(chosen, at, qb, 1)
        part = functools.partial(jax.lax.dynamic_slice_in_dim, start_index=at,
                                 slice_size=qb, axis=1)
        s = (_ein("bthd,bshd->bhts", part(q_nope), k_nope, quant)
             + _ein("bthd,bsd->bhts", part(q_rope), kr, quant)) * (
                 (nope + rp) ** -0.5)
        s = jnp.where(mine[:, None], s, -jnp.inf)
        sink = w["sink"][None, :, None, None]
        m = jnp.maximum(s.max(-1, keepdims=True), sink)
        p = jnp.exp(s - m)
        p = p / (p.sum(-1, keepdims=True) + jnp.exp(sink - m))
        return _ein("bhts,bshd->bthd", p, v, quant), mine

    o, sel = jax.lax.map(block, jnp.arange(0, L, qb))   # [L / qb, B, qb, ..]
    o = o.transpose(1, 0, 2, 3, 4).reshape(B, L, H * vd) * gate
    sel = sel.transpose(1, 0, 2, 3).reshape(B, L, L)
    return (_mm(o, w["wo"], quant), jnp.concatenate([ckv, kr], -1), ki, sel)


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


def routed_part(w, cfg: dict, x, idx, wts, lo, quant=None):
    """The sum over the selected experts that ``w`` holds (``exp_*``
    stacked from expert ``lo``), each SwiGLU clamped."""
    limit = float(cfg["swiglu_limit"])
    out = jnp.zeros_like(x)
    for j in range(w["exp_gate"].shape[0]):
        weight = jnp.where(idx == lo + j, wts, 0.0).sum(-1)
        out = out + weight[..., None] * swiglu(
            x, w["exp_gate"][j], w["exp_up"][j], w["exp_down"][j], limit,
            quant)
    return out


@functools.partial(jax.jit, static_argnames=("cfg_key", "quant", "full"))
def _attend(w, X, chosen, *, cfg_key, quant, full):
    """``X [B, L, n, hidden]`` -> ``(X after the attention sublayer,
    entries, index keys, selection)``."""
    cfg = _CFGS[cfg_key]
    eps = cfg["rms_norm_eps"]
    if X.shape[-2] > 1:
        u, post, res = hyper_pre(w, "hc_attn", cfg, X, quant)
    else:
        u = X[..., 0, :]
    a, entries, ki, sel = attention(w, cfg, rms_norm(u, w["attn_norm"], eps),
                                    None if full else chosen, quant)
    X = (hyper_post(X, a, post, res) if X.shape[-2] > 1
         else X + a[..., None, :])
    return X, entries, ki, sel


@functools.partial(jax.jit, static_argnames=("cfg_key", "quant", "lo"))
def _feed(w, X, *, cfg_key, quant, lo):
    """``X [rows, n, hidden]``, positions of any sequences -> ``(X after
    the feed-forward sublayer, margin [rows])``."""
    cfg = _CFGS[cfg_key]
    limit = float(cfg["swiglu_limit"])
    if X.shape[-2] > 1:
        u, post, res = hyper_pre(w, "hc_ffn", cfg, X, quant)
    else:
        u = X[..., 0, :]
    x = rms_norm(u, w["ffn_norm"], cfg["rms_norm_eps"])
    if "router" in w:
        # the router sees what the experts see: the stated compute type's
        # values of x, in float32 arithmetic
        idx, wts, margin = route(w, cfg, _q(x, PRECISION[
            cfg["model"]["compute_dtype"]]))
        y = (swiglu(x, w["shared_gate"], w["shared_up"], w["shared_down"],
                    limit, quant)
             + routed_part(w, cfg, x, idx, wts, lo, quant))
    else:
        y = swiglu(x, w["w_gate"], w["w_up"], w["w_down"], limit, quant)
        margin = jnp.ones(X.shape[:1], jnp.float32)
    X = (hyper_post(X, y, post, res) if X.shape[-2] > 1
         else X + y[..., None, :])
    return X, margin


@functools.partial(jax.jit, static_argnames=("cfg_key", "quant"))
def _head(top, X, ids, *, cfg_key, quant):
    """``X [rows, n, hidden]`` and the id that follows each row's position
    -> that id's score under the row's logits ``[rows]``."""
    cfg = _CFGS[cfg_key]
    logits = _mm(rms_norm(X.sum(-2), top["final_norm"], cfg["rms_norm_eps"]),
                 top["head"], quant)
    nll = (jax.nn.logsumexp(logits, -1)
           - jnp.take_along_axis(logits, ids[:, None], -1)[:, 0])
    return 1.0 - jnp.exp(-nll / math.log(cfg["vocab_size"]))


def _rows(fn, X, rows: int, *more):
    """``fn`` over ``X [B, L, ...]`` (and ``more [B, L]``) as rows, in calls
    of exactly ``rows`` rows (the last filled up with zeros), so that one
    compiled program serves sequences of every length."""
    B, L = X.shape[:2]
    flat = [X.reshape(B * L, *X.shape[2:])] + [m.reshape(B * L)
                                               for m in more]
    outs = []
    for a in range(0, B * L, rows):
        part = [jnp.pad(f[a:a + rows], ((0, max(0, a + rows - B * L)),)
                        + ((0, 0),) * (f.ndim - 1)) for f in flat]
        outs.append(fn(*part))
    many = isinstance(outs[0], tuple)
    outs = [jnp.concatenate([o[i] if many else o for o in outs])[:B * L]
            for i in range(len(outs[0]) if many else 1)]
    outs = [o.reshape(B, L, *o.shape[1:]) for o in outs]
    return tuple(outs) if many else outs[0]


def _forward_part(ws, top, key, cfg, tokens, quant, keep, lo):
    """``tokens [b, L]`` through every layer, the streams on the device
    throughout: ``(score [b, L], margin [layers, b, L], kept, keys)``."""
    rows = cfg["model"]["positions"]
    b, L = tokens.shape
    X = jnp.broadcast_to(top["embed"][tokens][:, :, None],
                         (b, L, _streams(cfg), cfg["hidden_size"]))
    at_once = max(1, rows // L)
    chosen, margins, kept, keys = None, [], [], []
    for l, w in enumerate(ws):
        full = cfg["indexer_types"][l] == "full"
        outs = []
        for a in range(0, b, at_once):
            part = X[a:a + at_once]
            pad = at_once - len(part)
            sel = None if full else jnp.pad(chosen[a:a + at_once],
                                            ((0, pad), (0, 0), (0, 0)))
            outs.append(_attend(w, jnp.pad(part, ((0, pad),) + ((0, 0),) * 3),
                                sel, cfg_key=key, quant=quant, full=full))
        X = jnp.concatenate([o[0] for o in outs])[:b]
        if full:
            chosen = jnp.concatenate([o[3] for o in outs])[:b]
        if keep:
            kept.append(np.asarray(jnp.concatenate([o[1] for o in outs]))[:b])
            if full:
                keys.append(np.asarray(jnp.concatenate(
                    [o[2] for o in outs]))[:b])
        del outs
        X, margin = _rows(lambda x: _feed(w, x, cfg_key=key, quant=quant,
                                          lo=lo), X, rows)
        margins.append(np.asarray(margin))
    head_quant = {"bf16": None if cfg["enable_lm_head_fp32"] else "bf16"}
    score = _rows(lambda x, ids: _head(top, x, ids, cfg_key=key,
                                       quant=head_quant.get(quant, quant)),
                  X, rows, jnp.roll(jnp.asarray(tokens), -1, 1))
    return (np.pad(np.asarray(score)[:, :-1], ((0, 0), (1, 0))),
            np.stack(margins), kept, keys)


def forward_groups(seed: int, cfg: dict, groups: list, held=None) -> list:
    """Several forwards under one draw of the weights: ``groups`` is
    ``[(tokens [B, L], quant, keep)]`` and each gets ``{"score" [B, L],
    "margin" [layers, B, L]}`` (1 in a dense layer) and, where ``keep``,
    ``"kept"``: a layer's ``[B, L, kv + rope]`` and ``"keys"``: a ``full``
    layer's ``[B, L, index dim]`` (float32, on the host). **Every layer's
    weights are drawn once and held in the bfloat16 they are drawn in**
    (8.90 GB at the published widths: exactly the drawn values; every
    product widens them to float32), and a group goes through all layers
    ``positions // L`` sequences at a time, its streams on the device:
    four streams of 6,144 float32 are 98 KB a position, and the sequences
    of a check do not fit the host beside their copies. Attention takes
    ``positions // L`` sequences a call, so one program a length and a
    kind of layer; the feed-forward and the head take ``positions`` rows
    a call."""
    key = _register(cfg)
    lo = (held if held is not None else held_range(cfg))[0]
    with jax.default_matmul_precision("highest"):
        top = top_weights(seed, cfg)
        ws = [jax.tree_util.tree_map(lambda a: a.astype(jnp.bfloat16),
                                     layer_weights(seed, cfg, l, held))
              for l in range(cfg["num_hidden_layers"])]
        out = []
        for tokens, quant, keep in groups:
            tokens = np.asarray(tokens, np.int32)
            per = max(1, cfg["model"]["positions"] // tokens.shape[1])
            parts = [_forward_part(ws, top, key, cfg, tokens[a:a + per],
                                   quant, keep, lo)
                     for a in range(0, len(tokens), per)]
            got = {"score": np.concatenate([p[0] for p in parts]),
                   "margin": np.concatenate([p[1] for p in parts], 1)}
            if keep:
                got["kept"] = [np.concatenate([p[2][l] for p in parts])
                               for l in range(len(parts[0][2]))]
                got["keys"] = [np.concatenate([p[3][l] for p in parts])
                               for l in range(len(parts[0][3]))]
            out.append(got)
        return out


def forward(seed: int, cfg: dict, tokens, quant=None, held=None) -> dict:
    """``tokens [B, L]`` int32, position 0 the start token (id 0), padded
    at the end with any id (causality keeps padding out of what comes
    before it). Returns ``forward_groups``'s entry for them, kept."""
    return forward_groups(seed, cfg, [(tokens, quant, True)], held)[0]
