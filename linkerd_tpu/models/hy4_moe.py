"""A fifth flow model: latent attention over **a selection of each event's
positions**, chosen by a learned indexer whose choice three layers in four
reuse, with a sink and an element-wise output gate; sigmoid-routed experts
beside a shared one; and **a residual stream four wide**, mixed around
every sublayer by hyper-connections (Hy4-preview's block, as its
``config.json`` sizes it).

The step, the ``[F, T]`` layout, the routed experts, the shared expert's
branch, the head and the score mapping are ``models/latent_moe.py``'s
(``flow_step``), which takes the four-wide residual path, the SwiGLU's
clamp and the float32 head from this configuration (``hc_mult``,
``swiglu_limit``, ``head_fp32``). This module gives the configuration,
the tensors and the attention operator, in two instances
(``indexer_types[l]``): a ``full`` layer has an indexer and keeps, beside
its latent cache, an array of the indexer's keys; a ``shared`` layer keeps
the latent cache alone and takes the selection of the ``full`` layer
before it (``Operator.carries``: the selection is what one layer hands the
next).

Notation: ``X [4, C]`` a token's four streams (``hc_mult`` 4, ``C`` =
``hidden_size``), float32.

- **Hyper-connections** (mHC's form, "Manifold-Constrained
  Hyper-Connections", DeepSeek 2025) wrap each sublayer ``F``, the
  attention and the feed-forward: ``x~ = RMSNorm(vec X)`` (no gain);
  ``[a_pre (4), a_post (4), a_res (16)] = alpha . (x~ phi) + b`` with ``phi
  [4C, 24]``, ``alpha`` three scalars, one a group, and ``b [24]``;
  ``H_pre = sigmoid(a_pre)``, ``H_post = hc_magnitude sigmoid(a_post)``,
  ``H_res = Sinkhorn(exp(a_res))``: 20 iterations of rows then columns
  divided by their sums plus ``hc_eps``; ``u = sum_i H_pre[i] X_i``,
  ``X'_i = sum_j H_res[i, j] X_j + H_post[i] F(u)``; ``F`` norms its own
  input. The embedding is copied into all four streams; the final hidden
  is ``RMSNorm(sum_i X_i)``. (``models/latent_moe.hyper_in`` /
  ``hyper_out``, scope ``hyper``.)
- **The indexer** (DeepSeek-V3.2's lightning indexer), on a ``full``
  layer: ``q^I = c_q W_iq`` in ``index_n_heads`` (32) heads of
  ``index_head_dim`` (128), ``c_q`` the normed query latent (2,048);
  ``k^I = LayerNorm(x W_ik)`` (128); RoPE on the first ``rope`` (64) values
  of both; ``w = (x W_iw) 32^-1/2 128^-1/2``; ``I[t, s] = sum_j w_j
  ReLU(q^I_j . k^I_s)`` over ``s <= t``; **the selection is the top
  ``min(index_topk, t + 1)`` positions by ``I``, ties to the earlier
  position**. A ``shared`` layer uses the selection of the nearest
  ``full`` layer before it and holds no indexer (IndexCache's cross-layer
  reuse). Scope ``index``: the indexer's projections, its scores
  (``index_scores``: XLA, a few flows at a time, so that ``[F, T, 32,
  P]`` is never formed) and the selection (``select_top``: each event's
  threshold and the position of its last tie, found by bisection on the
  scores' bits, so that the attention reads the selection from the scores
  themselves).
- **Gated latent attention with a sink**: Kimi's operator
  (``models/latent_moe.py``: absorbed, the cache entry ``[c_kv (512),
  k_rope (64)]``), over the event's selection; RoPE plain (theta 10^7);
  the softmax's scale ``qk_head_dim^-1/2`` (256); a sink a head in its
  sum: ``p_s = e^{l_s} / (sum_{s' in sel} e^{l_s'} + e^{sink_h})``; each
  head's output ``[256]``, after ``W_uv``, times ``sigmoid(x W_g)``
  element by element in float32 (``W_g [hidden, heads x 256]``), before
  ``W_o``. **How the product runs is the step's ``attend``**
  (``ops/flow_attention.best_attention(sparse=True)``): on a TPU
  ``sparse_latent_attention_fused``, elsewhere ``attend_selected_xla``.
- **FFN**: layer 0 dense (18,432 wide); the others a shared expert and the
  top 8 of 256 by sigmoid scores with a selection bias, renormalised,
  times 2.827, this chip's held experts' part (``experts_held``). Every
  SwiGLU is ``silu(min(g, 10)) . clip(u, -10, 10)``.
- **Head**: float32 from float32 operands.

Per-flow state: a ``full`` layer ``(latent [slots, positions, 576],
index keys [slots, 128, positions])`` (the keys positions-last, as the
indexer's product reads them), a ``shared`` layer the latent alone; both
appended in place by the step's ``append``. The start token's constants
are a layer's entries at position 0, one array (``[576 + 128]`` on a
``full`` layer).
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Any, Dict, NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from linkerd_tpu.models.grouped_attention import rotate
from linkerd_tpu.models.latent_moe import (
    ATTENTION_BLOCK, BIAS_SPREAD, GAIN_SPREAD, OUT_GAIN, Operator, _mm, _rms,
    _rope, angles, check_held,
)
from linkerd_tpu.ops.flow_attention import MASKED

FULL, SHARED = "full", "shared"
INDEX_FLOWS = 2     # flows whose index scores are formed at a time
INDEX_NORM_EPS = 1e-6   # the indexer key's LayerNorm (DeepSeek-V3.2's)


@dataclass(frozen=True)
class Hy4MoEConfig:
    hidden_size: int = 6144
    num_attention_heads: int = 64
    q_lora_rank: int = 2048
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 192
    qk_rope_head_dim: int = 64
    v_head_dim: int = 256
    index_n_heads: int = 32
    index_head_dim: int = 128
    index_topk: int = 2048
    indexer_types: Tuple[str, ...] = (FULL, FULL, SHARED, SHARED, SHARED)
    mlp_layer_types: Tuple[str, ...] = ("dense", "sparse", "sparse",
                                        "sparse", "sparse")
    intermediate_size: int = 18432
    moe_intermediate_size: int = 2048
    n_routed_experts: int = 256         # the router's width: the whole layer's
    n_shared_experts: int = 1
    num_experts_per_tok: int = 8
    routed_scaling_factor: float = 2.827
    rms_norm_eps: float = 1e-5
    rope_theta: float = 1e7
    hc_mult: int = 4
    hc_magnitude: float = 2.0
    hc_eps: float = 1e-6
    hc_sinkhorn_iterations: int = 20
    swiglu_limit: float = 10.0
    head_fp32: bool = True
    experts_held: Tuple[int, int] = (0, 16)     # [lo, hi) of every layer
    layer_share: int = 16               # devices that share each layer
    vocab_slice: int = 15104
    slots: int = 128
    positions: int = 6144
    expert_tile: int = 128
    route_eps: float = 0.0

    def __post_init__(self):
        check_held(self)
        if set(self.indexer_types) - {FULL, SHARED}:
            raise ValueError(f"indexer_types: only {FULL!r} and {SHARED!r} "
                             "are computed")
        if self.indexer_types[0] != FULL:
            raise ValueError("a shared layer needs a full layer before it")
        if len(self.indexer_types) != len(self.mlp_layer_types):
            raise ValueError("indexer_types and mlp_layer_types do not name "
                             "the same layers")

    @property
    def layers(self) -> int:
        return len(self.indexer_types)

    @property
    def entry_width(self) -> int:
        """Values the latent cache holds a position a layer."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    def operator(self, l: int) -> Operator:
        return SPARSE_ATTENTION[self.indexer_types[l]]

    def tensors(self) -> Dict[str, tuple]:
        return tensor_table(self)

    @classmethod
    def from_config(cls, cfg: dict) -> "Hy4MoEConfig":
        """From a configuration file of the benchmark (the published keys
        at the top level; under ``model`` what is this repo's: the router's
        width where the file's ``n_routed_experts`` is the count held here,
        the held range, the share, the state's size)."""
        m = cfg["model"]
        if cfg["rope_parameters"]["rope_type"] != "default":
            raise ValueError("only the default RoPE is computed")
        if not (cfg["use_mla"] and cfg["use_dsa"] and cfg["gated_mla"]
                and cfg["gating_type"] == "elementwise"
                and cfg["learnable_sink"]):
            raise ValueError("only gated (element-wise) latent attention "
                             "over an indexer's selection, with a sink, is "
                             "computed")
        if cfg["n_group"] != 1 or not cfg["norm_topk_prob"]:
            raise ValueError("only renormalised scores in one group are "
                             "computed")
        n = cfg["num_hidden_layers"]
        for key in ("indexer_types", "mlp_layer_types", "layer_types"):
            if len(cfg[key]) != n:
                raise ValueError(f"{key} does not name every layer")
        if cfg["num_nextn_predict_layers"]:
            raise ValueError("a score is the main head's: no multi-token "
                             "prediction module is computed")
        return cls(
            hidden_size=cfg["hidden_size"],
            num_attention_heads=cfg["num_attention_heads"],
            q_lora_rank=cfg["q_lora_rank"], kv_lora_rank=cfg["kv_lora_rank"],
            qk_nope_head_dim=cfg["qk_nope_head_dim"],
            qk_rope_head_dim=cfg["qk_rope_head_dim"],
            v_head_dim=cfg["v_head_dim"],
            index_n_heads=cfg["index_n_heads"],
            index_head_dim=cfg["index_head_dim"],
            index_topk=cfg["index_topk"],
            indexer_types=tuple(cfg["indexer_types"]),
            mlp_layer_types=tuple(cfg["mlp_layer_types"]),
            intermediate_size=cfg["intermediate_size"],
            moe_intermediate_size=cfg["moe_intermediate_size"],
            n_routed_experts=m["router_experts"],
            n_shared_experts=cfg["n_shared_experts"],
            num_experts_per_tok=cfg["num_experts_per_tok"],
            routed_scaling_factor=cfg["routed_scaling_factor"],
            rms_norm_eps=cfg["rms_norm_eps"],
            rope_theta=float(cfg["rope_parameters"]["rope_theta"]),
            hc_mult=cfg["hc_mult"] if cfg["enable_ihc"] else 1,
            hc_magnitude=float(cfg["hc_magnitude"]), hc_eps=cfg["hc_eps"],
            hc_sinkhorn_iterations=m.get("hc_sinkhorn_iterations", 20),
            swiglu_limit=float(cfg["swiglu_limit"]),
            head_fp32=bool(cfg["enable_lm_head_fp32"]),
            experts_held=tuple(m["experts_held"]),
            layer_share=m["layer_share"], vocab_slice=cfg["vocab_size"],
            slots=m["slots"], positions=m["positions"],
            expert_tile=m.get("expert_tile", 128))


SINK_MEAN, SINK_SPREAD = 4.0, 2.0   # a head's sink logit: 4 + 2 normal
HC_BIAS_SPREAD = 1.0                # a hyper-connection's bias: normal


def tensor_table(cfg: Hy4MoEConfig) -> Dict[str, tuple]:
    """``{name: (shape, std, mean, per_expert)}`` of every tensor, by
    ``models/latent_moe.tensor_table``'s rule (a matrix ``[fan_in,
    fan_out]`` of std ``1/sqrt(fan_in)``, output projections 0.3 of that,
    a norm's gain 1 + 0.1 normal, the router's bias 0.01 normal), and for
    what that model lacks: the gate ``wg`` at ``1/sqrt(hidden)`` (gates
    over 0.1-0.9); a head's sink ``4 + 2 normal`` (``e^4``, 55, against
    sums of 1 to 2,048 weights near 1: a sink takes from a few per cent to
    most of a head's attention); the indexer's matrices by the rule, its
    key's LayerNorm a gain and a bias (0.01 normal); a hyper-connection's
    ``phi`` by the rule (``x~ phi`` of std 1), its three ``alpha`` gains
    ``1 + 0.1 normal`` and its bias ``normal``."""
    d, h = cfg.hidden_size, cfg.num_attention_heads
    qd = cfg.qk_nope_head_dim + cfg.qk_rope_head_dim
    kvd = cfg.qk_nope_head_dim + cfg.v_head_dim
    inter = cfg.moe_intermediate_size
    shared = inter * cfg.n_shared_experts
    n = cfg.hc_mult

    def mat(i, o, gain=1.0):
        return ((i, o), gain / math.sqrt(i), 0.0, False)

    def gain(k):
        return ((k,), GAIN_SPREAD, 1.0, False)

    t = {"embed": ((cfg.vocab_slice, d), 1.0, 0.0, False),
         "head": mat(d, cfg.vocab_slice), "final_norm": gain(d)}
    for l in range(cfg.layers):
        p = f"layers.{l}."
        t.update({
            p + "attn_norm": gain(d), p + "wdq": mat(d, cfg.q_lora_rank),
            p + "q_norm": gain(cfg.q_lora_rank),
            p + "wuq": mat(cfg.q_lora_rank, h * qd),
            p + "wdkv": mat(d, cfg.entry_width),
            p + "kv_norm": gain(cfg.kv_lora_rank),
            p + "wukv": mat(cfg.kv_lora_rank, h * kvd),
            p + "wg": mat(d, h * cfg.v_head_dim),
            p + "sink": ((h,), SINK_SPREAD, SINK_MEAN, False),
            p + "wo": mat(h * cfg.v_head_dim, d, OUT_GAIN),
            p + "ffn_norm": gain(d)})
        if cfg.indexer_types[l] == FULL:
            di = cfg.index_head_dim
            t.update({
                p + "wiq": mat(cfg.q_lora_rank, cfg.index_n_heads * di),
                p + "wik": mat(d, di), p + "wiw": mat(d, cfg.index_n_heads),
                p + "ik_norm": gain(di),
                p + "ik_bias": ((di,), BIAS_SPREAD, 0.0, False)})
        if n > 1:
            for sub in ("hc_attn", "hc_ffn"):
                t.update({
                    p + sub + "_phi": mat(n * d, n * (n + 2)),
                    p + sub + "_alpha": gain(3),
                    p + sub + "_bias": ((n * (n + 2),), HC_BIAS_SPREAD, 0.0,
                                        False)})
        if cfg.mlp_layer_types[l] == "dense":
            t.update({p + "w_gate": mat(d, cfg.intermediate_size),
                      p + "w_up": mat(d, cfg.intermediate_size),
                      p + "w_down": mat(cfg.intermediate_size, d, OUT_GAIN)})
        else:
            t.update({
                p + "router": mat(d, cfg.n_routed_experts),
                p + "router_bias": ((cfg.n_routed_experts,), BIAS_SPREAD,
                                    0.0, False),
                p + "shared_gate": mat(d, shared),
                p + "shared_up": mat(d, shared),
                p + "shared_down": mat(shared, d, OUT_GAIN),
                p + "exp_gate": ((d, inter), 1 / math.sqrt(d), 0.0, True),
                p + "exp_up": ((d, inter), 1 / math.sqrt(d), 0.0, True),
                p + "exp_down": ((inter, d), OUT_GAIN / math.sqrt(inter),
                                 0.0, True)})
    return t


def rope_inv_freq(cfg: Hy4MoEConfig) -> np.ndarray:
    """The plain frequencies of the ``qk_rope_head_dim`` turned values."""
    dim = cfg.qk_rope_head_dim
    return (1.0 / cfg.rope_theta ** (
        np.arange(0, dim, 2, dtype=np.float64) / dim)).astype(np.float32)


# -- the selection ------------------------------------------------------------

class Selection(NamedTuple):
    """Which positions each event of a call attends over: ``scores [F, T,
    P]`` float32, the indexer's (``-inf`` past the event), ``threshold
    [F, T]`` float32 and ``tie [F, T]`` int32: position ``s`` is selected
    iff ``scores[s] > threshold``, or ``== threshold`` and ``s <= tie``.
    What a ``full`` layer hands the layers after it."""
    scores: Any
    threshold: Any
    tie: Any


def layer_norm(x, gain, bias, eps: float):
    x = x.astype(jnp.float32)
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return ((x - mu) * jax.lax.rsqrt(var + eps) * gain.astype(jnp.float32)
            + bias.astype(jnp.float32))


def index_scores(qi, w, keys, slot, p0):
    """``I [F, T, P]`` float32: ``qi [F, T, heads, dim]`` bfloat16 the
    indexer's queries, ``w [F, T, heads]`` float32 their weights, ``keys
    [slots, dim, P]`` the layer's index keys, whole, of which flow ``f``'s
    are slot ``slot[f]`` (clipped: sliced where it lies); ``I[t, s] = sum_j w_j
    relu(qi_j . k_s)`` for ``s <= p0 + t``, ``-inf`` after. XLA,
    ``INDEX_FLOWS`` flows at a time: the products ``[T x heads, P]`` of a
    few flows exist at once, never ``[F, T, heads, P]``; the weighted sum
    is float32 element by element."""
    F, T, nh, dh = qi.shape
    S, _, P = keys.shape
    nb = INDEX_FLOWS if F % INDEX_FLOWS == 0 else 1

    def flows(args):
        q, w, slot, p0 = args
        # each flow's slot by a slice of its own: a gather of the slots
        # made XLA:TPU copy the whole layer every trip (16 ms a layer); one
        # batch axis and the contraction minor: XLA:CPU's bfloat16 product
        k = jnp.stack([jax.lax.dynamic_index_in_dim(
            keys, jnp.minimum(slot[b], S - 1), 0, keepdims=False)
            for b in range(nb)]).transpose(0, 2, 1)          # [nb, P, dh]
        s = jnp.einsum("bqd,bpd->bqp", q.reshape(nb, T * nh, dh), k,
                       preferred_element_type=jnp.float32)
        got = (jax.nn.relu(s).reshape(nb, T, nh, P) * w[..., None]).sum(2)
        seen = (jnp.arange(P)[None, None]
                <= (p0[:, None] + jnp.arange(T)[None])[..., None])
        return jnp.where(seen, got, -jnp.inf)

    return jax.lax.map(flows, jax.tree_util.tree_map(
        lambda a: a.reshape(F // nb, nb, *a.shape[1:]),
        (qi, w, slot, p0))).reshape(F, T, P)


def _order(x):
    """float32 -> uint32 in the same order (``-0`` just under ``+0``)."""
    bits = jax.lax.bitcast_convert_type(x, jnp.int32)
    bits = jnp.where(bits < 0, bits ^ 0x7FFFFFFF, bits)
    return jax.lax.bitcast_convert_type(bits, jnp.uint32) ^ np.uint32(
        0x80000000)


def _unorder(u):
    bits = jax.lax.bitcast_convert_type(u ^ np.uint32(0x80000000), jnp.int32)
    bits = jnp.where(bits < 0, bits ^ 0x7FFFFFFF, bits)
    return jax.lax.bitcast_convert_type(bits, jnp.float32)


def select_top(scores, k) -> Selection:
    """The top ``k [F, T]`` positions of ``scores [F, T, P]`` by score,
    ties to the earlier position, as a ``Selection``: the threshold is the
    ``k``-th largest score (bisection on its 32 bits: the largest value
    with ``k`` scores at or over it), and ``tie`` the position of the
    ``k - (scores over it)``-th score equal to it (bisection on the
    position). ``k`` at least 1 and no more than the finite scores."""
    u = _order(scores)
    P = scores.shape[-1]

    def bit(b, at):
        trial = at | jnp.left_shift(jnp.uint32(1),
                                    (31 - b).astype(jnp.uint32))
        enough = (u >= trial[..., None]).sum(-1) >= k
        return jnp.where(enough, trial, at)

    threshold = _unorder(jax.lax.fori_loop(
        0, 32, bit, jnp.zeros(k.shape, jnp.uint32)))
    need = k - (scores > threshold[..., None]).sum(-1)
    equal = scores == threshold[..., None]
    col = jnp.arange(P)

    bits = max(1, (P - 1).bit_length())

    def place(b, at):
        # the least position by which ``need`` ties have come
        trial = at - jnp.left_shift(1, bits - 1 - b)
        enough = (equal & (col <= trial[..., None])).sum(-1) >= need
        return jnp.where((trial >= 0) & enough, trial, at)

    tie = jax.lax.fori_loop(0, bits, place,
                            jnp.full(k.shape, (1 << bits) - 1, jnp.int32))
    return Selection(scores, threshold, jnp.minimum(tie, P - 1))


def selected(sel: Selection):
    """The selection as a mask ``[F, T, P]``."""
    col = jnp.arange(sel.scores.shape[-1])
    th = sel.threshold[..., None]
    return (sel.scores > th) | ((sel.scores == th)
                                & (col <= sel.tie[..., None]))


def attend_selected_xla(q_abs, q_rope, cache, slot, p0, scale: float,
                        selection: Selection, sink=None):
    """The latent attention over a selection as XLA does it
    (``models.latent_moe.attend_xla``'s form: the flows' slots gathered,
    the whole score tensor of ``ATTENTION_BLOCK`` flows at a time, every
    slot attended whole, as one block): the path of every platform but
    the TPU, and what ``ops/flow_attention.sparse_latent_attention_fused``
    is tested against. Event ``(f, t)`` attends over the positions its
    ``selection`` holds; ``sink [H]`` float32 is a logit a head in the
    softmax's sum (None: none). The weights are rounded to bfloat16 before
    they multiply the latent and the sum divides after, as on the
    kernel."""
    F, T, H, rank = q_abs.shape
    kv = cache[jnp.minimum(slot, cache.shape[0] - 1)]       # [F, P, entry]
    sink = (jnp.full((H,), MASKED, jnp.float32) if sink is None
            else sink.astype(jnp.float32))[None, :, None, None]
    chosen = selected(selection)

    def attend(block):
        qk, kv, chosen = block
        s = jnp.einsum("fthk,fpk->fhtp", qk, kv,
                       preferred_element_type=jnp.float32) * scale
        s = jnp.where(chosen[:, None], s, MASKED)
        m = jnp.maximum(s.max(-1, keepdims=True), sink)
        p = jnp.exp(s - m)
        total = p.sum(-1, keepdims=True) + jnp.exp(sink - m)
        return (jnp.einsum("fhtp,fpc->fhtc", p.astype(jnp.bfloat16),
                           kv[..., :rank], preferred_element_type=jnp.float32)
                * (1.0 / total)).astype(jnp.bfloat16)

    nb = min(ATTENTION_BLOCK, F)
    o = jax.lax.map(attend, jax.tree_util.tree_map(
        lambda a: a.reshape(F // nb, nb, *a.shape[1:]),
        (jnp.concatenate([q_abs, q_rope], -1), kv, chosen)))
    return (o.reshape(F, H, T, rank).transpose(0, 2, 1, 3),
            jnp.ones((F,), jnp.int32), 1)


# -- the operator -------------------------------------------------------------

def _apply(full: bool, lp, cfg, kept, start_entry, h, call, carry):
    """The gated latent attention over the selection, as an
    ``Operator.apply`` that carries: ``kept`` the layer's state, donated
    (``(latent, index keys)`` on a ``full`` layer, the latent on a
    ``shared`` one), ``carry`` the selection of the ``full`` layer before
    (None at layer 0). A ``full`` layer appends the call's index keys,
    scores the flow's positions and selects (scope ``index``); both kinds
    append the latent entries and attend over the selection by
    ``call.attend``. Returns the output, the state, the counts (the
    ``Operator``'s and ``attn.selected`` / ``attn.context``: positions
    attended after the selection and in causal context, over the events;
    ``index.scored``: positions an indexer scored; ``index.reused``:
    layers that took another layer's selection) and the selection."""
    F, T, _ = h.shape
    H, nope, rope = (cfg.num_attention_heads, cfg.qk_nope_head_dim,
                     cfg.qk_rope_head_dim)
    rank, vd, eps = cfg.kv_lora_rank, cfg.v_head_dim, cfg.rms_norm_eps
    E = cfg.entry_width
    cache, keys = kept if full else (kept, None)
    with jax.named_scope("project"):
        x = _rms(h, lp["attn_norm"], eps)
        cos, sin = angles(call.pos, rope_inv_freq(cfg))
        cq = _rms(_mm(x, lp["wdq"]), lp["q_norm"], eps)
        q = _mm(cq, lp["wuq"]).reshape(F, T, H, nope + rope)
        q_rope = _rope(q[..., nope:], cos[:, :, None], sin[:, :, None])
        ckr = _mm(x, lp["wdkv"])
        entry = jnp.concatenate(
            [_rms(ckr[..., :rank], lp["kv_norm"], eps),
             _rope(ckr[..., rank:], cos, sin)], -1).astype(jnp.bfloat16)
        gate = (jax.nn.sigmoid(_mm(x, lp["wg"])) if "wg" in lp else None)
    if full:
        with jax.named_scope("index"):
            nh, dh = cfg.index_n_heads, cfg.index_head_dim
            qi = rotate(_mm(cq, lp["wiq"]).reshape(F, T, nh, dh),
                        cos[:, :, None], sin[:, :, None], rope)
            ki = rotate(layer_norm(_mm(x, lp["wik"]), lp["ik_norm"],
                                   lp["ik_bias"], INDEX_NORM_EPS
                                   )[:, :, None], cos[:, :, None],
                        sin[:, :, None], rope)[:, :, 0]
            wi = _mm(x, lp["wiw"]) * (nh ** -0.5 * dh ** -0.5)
    with jax.named_scope("append"):
        cache, written, in_kernel = call.append(
            cache, entry, start_entry[:E], call.slot, call.p0, call.count,
            call.begins)
        if full:
            keys, more, more_in_kernel = call.append(
                keys, ki.astype(jnp.bfloat16), start_entry[E:], call.slot,
                call.p0, call.count, call.begins, positions_last=True)
            written, in_kernel = written + more, in_kernel + more_in_kernel
    if full:
        with jax.named_scope("index"):
            carry = select_top(
                index_scores(qi.astype(jnp.bfloat16), wi, keys, call.slot,
                             call.p0),
                jnp.minimum(cfg.index_topk, call.pos + 1))
    with jax.named_scope("project"):      # the absorption of ``wukv`` into q
        wukv = lp["wukv"].reshape(rank, H, nope + vd)
        q_abs = jnp.einsum("fthd,chd->fthc",
                           q[..., :nope].astype(jnp.bfloat16),
                           wukv[..., :nope],
                           preferred_element_type=jnp.float32)
        q_abs, q_rope = q_abs.astype(jnp.bfloat16), q_rope.astype(jnp.bfloat16)
    with jax.named_scope("attend"):
        o, blocks, whole = call.attend(
            q_abs, q_rope, cache, call.slot, call.p0,
            (nope + rope) ** -0.5, carry, lp.get("sink"))
    with jax.named_scope("out"):
        o = jnp.einsum("fthc,chd->fthd", o, wukv[..., nope:],
                       preferred_element_type=jnp.float32).reshape(F, T, -1)
        if gate is not None:
            o = o * gate
        y = _mm(o, lp["wo"])
    with jax.named_scope("attend"):
        live = (call.slot < cfg.slots).sum()
        valid = jnp.arange(T)[None] < call.count[:, None]
        context = jnp.where(valid, call.pos + 1, 0)
        counts = {"attn.kv_blocks": blocks.sum(),
                  "attn.kv_blocks_whole": F * whole,
                  "cache.rows_written": written,
                  "cache.rows_whole": live * cfg.positions * (2 if full
                                                              else 1),
                  "append.flows": live * (2 if full else 1),
                  "append.flows_in_kernel": in_kernel,
                  "attn.selected": jnp.minimum(context,
                                               cfg.index_topk).sum(),
                  "attn.context": context.sum()}
        if full:
            counts["index.scored"] = context.sum()
        else:
            counts["index.reused"] = jnp.int32(1)
    return y, ((cache, keys) if full else cache), counts, carry


def _operator(full: bool) -> Operator:
    def apply(lp, cfg, kept, start_entry, h, call, carry):
        return _apply(full, lp, cfg, kept, start_entry, h, call, carry)

    def init(cfg):
        latent = jnp.zeros((cfg.slots, cfg.positions, cfg.entry_width),
                           jnp.bfloat16)
        if not full:
            return latent
        return latent, jnp.zeros(
            (cfg.slots, cfg.index_head_dim, cfg.positions), jnp.bfloat16)

    def start_of(kept):
        if not full:
            return kept[0, 0]
        latent, keys = kept
        return jnp.concatenate([latent[0, 0], keys[0, :, 0]])

    return Operator(apply=apply, init=init, start_of=start_of,
                    scope="attention", caches=True, parts=True,
                    carries=True)


SPARSE_ATTENTION = {FULL: _operator(True), SHARED: _operator(False)}
