"""A second flow model: gated short convolutions among grouped-query
attention layers, and sigmoid-routed experts with no shared one beside
them (LFM2-MoE's block, as LFM2-24B-A2B's ``config.json`` sizes it).

The step, the ``[F, T]`` layout, the routed experts, the head and the
score mapping are ``models/latent_moe.py``'s (``flow_step``): this module
gives that step the configuration, the tensors and the two operators of
this model's layers, which keep **two kinds of per-flow state side by
side**. Per layer ``h += Op(RMSNorm(h))``, then ``h += FFN(RMSNorm(h))``;
``layer_types[l]`` says which operator:

- **``conv``, a gated short convolution.** ``B, C, X = split(x in_proj,
  3)``; ``u = B * X``; ``v[t] = sum_j w[j] * u[t - (K - 1) + j]`` over the
  ``K = conv_L_cache`` taps, depthwise and causal, zeros before the
  flow's position 0; ``y = (C * v) out_proj``. **A flow keeps ``u`` of
  its last ``K - 1`` positions and nothing else**: ``[slots, K - 1,
  hidden]`` a layer, whatever the flow's length. A chunk is convolved
  behind the tail it meets (the start token's where the flow begins) and
  leaves the last ``K - 1`` rows of tail and chunk together, so a chunk
  shorter than the tail merges with it. ``u`` is rounded to bfloat16
  where it is made, as the tail holds it, so a sequence gives the same
  ``v`` however it is cut into calls; the taps' sum is float32.
- **``full_attention``, grouped-query attention.** ``q`` in
  ``num_attention_heads`` heads, ``k`` and ``v`` in
  ``num_key_value_heads``; ``q`` and ``k`` RMS-normed per head, then
  RoPE (the default kind, rotate-half); scores ``q . k / sqrt(head)``,
  causal, query head ``i`` against key/value head ``i // (heads / kv
  heads)``. **The cache holds the normed, rotated keys and the values**
  of a position, ``2 x kv heads x head`` wide, and lies ``[slots, entry,
  positions]``: positions along the lanes, as the kernel of
  ``ops/flow_attention.py`` reads a slot (the latent cache reaches that
  layout by the compiler's own choice, its 576 being no multiple of 128
  lanes; 1,024 would be stored entry-minor and transposed, a copy of the
  layer, every call). ``append_chunk`` writes the call's entries into it
  in place, then the chunk attends over its flow's slot by the step's
  ``attend``: ``grouped_attention_fused`` on a TPU, ``attend_grouped_xla``
  elsewhere.

The first ``num_dense_layers`` feed-forwards are a dense SwiGLU; the
others route: ``sigmoid`` scores over ``n_routed_experts``, the top
``num_experts_per_tok`` of score + bias, weights the selected scores over
their sum + 1e-6, times ``routed_scaling_factor``; no shared expert.
Embedding and head are tied: the logits are ``h . embed^T``.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from linkerd_tpu.models.latent_moe import (
    ATTENTION_BLOCK, GAIN_SPREAD, OUT_GAIN, Operator, _mm, _rms,
    _rope, angles, append_chunk, check_held,
)

CONV, ATTENTION = "conv", "full_attention"


@dataclass(frozen=True)
class Lfm2MoEConfig:
    hidden_size: int = 2048
    intermediate_size: int = 11776
    moe_intermediate_size: int = 1536
    num_attention_heads: int = 32
    num_key_value_heads: int = 8
    conv_L_cache: int = 3
    n_routed_experts: int = 64
    num_experts_per_tok: int = 4
    routed_scaling_factor: float = 1.0
    route_eps: float = 1e-6
    rms_norm_eps: float = 1e-5
    rope_theta: float = 1e6
    layer_types: Tuple[str, ...] = (
        CONV, ATTENTION, CONV, CONV, CONV, ATTENTION, CONV, CONV, CONV)
    num_dense_layers: int = 1
    experts_held: Tuple[int, int] = (0, 64)     # [lo, hi) of every layer
    layer_share: int = 1                # devices that share each layer
    vocab_slice: int = 65536            # the whole vocabulary
    slots: int = 512
    positions: int = 1024
    expert_tile: int = 128
    # the spread of the drawn selection bias: wide enough that the bias
    # decides a share of the selections, as a trained balancing bias does
    router_bias_std: float = 0.05

    def __post_init__(self):
        check_held(self)
        if set(self.layer_types) - {CONV, ATTENTION}:
            raise ValueError(f"layer_types {sorted(set(self.layer_types))}: "
                             f"only {CONV!r} and {ATTENTION!r} are computed")

    @property
    def layers(self) -> int:
        return len(self.layer_types)

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    @property
    def entry_width(self) -> int:
        """Values an attention layer's cache holds a position: the keys
        and the values of every key/value head."""
        return 2 * self.num_key_value_heads * self.head_dim

    def operator(self, l: int) -> Operator:
        return SHORT_CONV if self.layer_types[l] == CONV else GROUPED_ATTENTION

    def tensors(self) -> Dict[str, tuple]:
        return tensor_table(self)

    @classmethod
    def from_config(cls, cfg: dict) -> "Lfm2MoEConfig":
        """From a configuration file of the benchmark (the published keys
        at the top level; under ``model`` what is this repo's: the held
        range, the share, the state's size)."""
        m, rope = cfg["model"], cfg["rope_parameters"]
        if rope["rope_type"] != "default":
            raise ValueError("only the default RoPE is computed")
        if not (cfg["use_expert_bias"] and cfg["norm_topk_prob"]):
            raise ValueError("only biased selection with renormalised "
                             "weights is computed")
        if cfg["conv_bias"] or len(cfg["layer_types"]) != cfg[
                "num_hidden_layers"]:
            raise ValueError("a bias on the convolution, or layer_types "
                             "that do not name every layer")
        return cls(
            hidden_size=cfg["hidden_size"],
            intermediate_size=cfg["intermediate_size"],
            moe_intermediate_size=cfg["moe_intermediate_size"],
            num_attention_heads=cfg["num_attention_heads"],
            num_key_value_heads=cfg["num_key_value_heads"],
            conv_L_cache=cfg["conv_L_cache"],
            n_routed_experts=cfg["num_experts"],
            num_experts_per_tok=cfg["num_experts_per_tok"],
            routed_scaling_factor=float(cfg["routed_scaling_factor"]),
            rms_norm_eps=cfg["norm_eps"],
            rope_theta=float(rope["rope_theta"]),
            layer_types=tuple(cfg["layer_types"]),
            num_dense_layers=cfg["num_dense_layers"],
            experts_held=tuple(m["experts_held"]),
            layer_share=m["layer_share"], vocab_slice=cfg["vocab_size"],
            slots=m["slots"], positions=m["positions"],
            expert_tile=m.get("expert_tile", 128),
            router_bias_std=m["router_bias_std"])


def tensor_table(cfg: Lfm2MoEConfig) -> Dict[str, tuple]:
    """``{name: (shape, std, mean, per_expert)}`` of every tensor, by the
    configuration file's rule (``models/latent_moe.tensor_table``'s, with
    the embedding at ``1/sqrt(hidden)``, since it is the head as well, and
    a convolution's taps at ``1/sqrt(taps)``)."""
    d, hd = cfg.hidden_size, cfg.head_dim
    kv = cfg.num_key_value_heads * hd
    inter = cfg.moe_intermediate_size

    def mat(i, o, gain=1.0):
        return ((i, o), gain / math.sqrt(i), 0.0, False)

    def gain(n):
        return ((n,), GAIN_SPREAD, 1.0, False)

    t = {"embed": ((cfg.vocab_slice, d), 1 / math.sqrt(d), 0.0, False),
         "final_norm": gain(d)}
    for l, kind in enumerate(cfg.layer_types):
        p = f"layers.{l}."
        t.update({p + "operator_norm": gain(d), p + "ffn_norm": gain(d)})
        if kind == CONV:
            K = cfg.conv_L_cache
            t.update({p + "in_proj": mat(d, 3 * d),
                      p + "conv": ((K, d), 1 / math.sqrt(K), 0.0, False),
                      p + "out_proj": mat(d, d, OUT_GAIN)})
        else:
            t.update({p + "wq": mat(d, d), p + "wk": mat(d, kv),
                      p + "wv": mat(d, kv), p + "q_norm": gain(hd),
                      p + "k_norm": gain(hd), p + "wo": mat(d, d, OUT_GAIN)})
        if l < cfg.num_dense_layers:
            t.update({p + "w_gate": mat(d, cfg.intermediate_size),
                      p + "w_up": mat(d, cfg.intermediate_size),
                      p + "w_down": mat(cfg.intermediate_size, d, OUT_GAIN)})
        else:
            t.update({
                p + "router": mat(d, cfg.n_routed_experts),
                p + "router_bias": ((cfg.n_routed_experts,),
                                    cfg.router_bias_std, 0.0, False),
                p + "exp_gate": ((d, inter), 1 / math.sqrt(d), 0.0, True),
                p + "exp_up": ((d, inter), 1 / math.sqrt(d), 0.0, True),
                p + "exp_down": ((inter, d), OUT_GAIN / math.sqrt(inter),
                                 0.0, True)})
    return t


# -- the operators ------------------------------------------------------------

def _short_conv(lp, cfg, tail, start_tail, h, call):
    """``h [F, T, hidden]`` the residual stream; ``tail [slots, K - 1,
    hidden]`` this layer's, donated: ``u`` of each flow's last ``K - 1``
    positions, oldest first. The chunk is convolved behind the tail it
    meets: the slot's, the start token's (``start_tail``) where the flow
    ``begins``, zeros at position 0 (the start token's own call); then
    the slot is left the last ``K - 1`` rows of tail and chunk together
    (``count`` events of it: padding rows are not the flow's). A
    ``slot`` out of range reads clipped and writes nothing. Returns the
    output, the tails, and ``[0, 0, 0, rows of tail written]``."""
    F, T, D = h.shape
    S, K = cfg.slots, cfg.conv_L_cache
    x = _rms(h, lp["operator_norm"], cfg.rms_norm_eps)
    gate_in, gate_out, xs = jnp.split(_mm(x, lp["in_proj"]), 3, -1)
    u = (gate_in * xs).astype(jnp.bfloat16)
    met = jnp.where(
        call.begins[:, None, None], start_tail[None],
        jnp.where((call.p0 == 0)[:, None, None], 0,
                  tail[jnp.minimum(call.slot, S - 1)]))
    seq = jnp.concatenate([met, u], 1).astype(jnp.float32)  # [F, K-1+T, D]
    w = lp["conv"].astype(jnp.float32)
    v = sum(w[j] * seq[:, j:j + T] for j in range(K))
    left = jnp.take_along_axis(
        seq, (call.count[:, None] + jnp.arange(K - 1)[None])[..., None], 1)
    tail = tail.at[call.slot].set(left.astype(jnp.bfloat16), mode="drop")
    return (_mm(gate_out * v, lp["out_proj"]), tail,
            jnp.stack([0, 0, 0, (call.slot < S).sum() * (K - 1)]))


SHORT_CONV = Operator(
    apply=_short_conv,
    init=lambda cfg: jnp.zeros(
        (cfg.slots, cfg.conv_L_cache - 1, cfg.hidden_size), jnp.bfloat16),
    start_of=lambda tail: tail[0], scope="conv", caches=False)


def rope_inv_freq(cfg: Lfm2MoEConfig) -> np.ndarray:
    dim = cfg.head_dim
    return (1.0 / cfg.rope_theta ** (
        np.arange(0, dim, 2, dtype=np.float64) / dim)).astype(np.float32)


def _grouped_attention(lp, cfg, cache, start_entry, h, call):
    """``h [F, T, hidden]`` the residual stream; ``cache [slots, entry,
    positions]`` this layer's, donated: a position's normed, rotated keys
    (``kv heads x head``) and then its values. As the latent operator:
    the chunk's entries are appended in place, then the chunk attends
    over its flow's slot by ``call.attend`` (``attend_grouped_xla``'s
    signature). Returns the output, the cache, and ``[blocks of
    positions attended over, those of the slots whole, rows of the cache
    written, 0]``."""
    F, T, D = h.shape
    H, G, hd = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
    eps = cfg.rms_norm_eps
    x = _rms(h, lp["operator_norm"], eps)
    cos, sin = angles(call.pos, rope_inv_freq(cfg))
    cos, sin = cos[:, :, None], sin[:, :, None]
    q = _rope(_rms(_mm(x, lp["wq"]).reshape(F, T, H, hd), lp["q_norm"], eps),
              cos, sin)
    k = _rope(_rms(_mm(x, lp["wk"]).reshape(F, T, G, hd), lp["k_norm"], eps),
              cos, sin)
    entry = jnp.concatenate([k.reshape(F, T, G * hd), _mm(x, lp["wv"])],
                            -1).astype(jnp.bfloat16)
    cache, written = append_chunk(cache, entry, start_entry, call.slot,
                                  call.p0, call.count, call.begins,
                                  positions_last=True)
    o, blocks, whole = call.attend(q.astype(jnp.bfloat16), cache, call.slot,
                                   call.p0, hd ** -0.5)
    return (_mm(o.reshape(F, T, D), lp["wo"]), cache,
            jnp.stack([blocks.sum(), F * whole, written, 0]))


GROUPED_ATTENTION = Operator(
    apply=_grouped_attention,
    init=lambda cfg: jnp.zeros((cfg.slots, cfg.entry_width, cfg.positions),
                               jnp.bfloat16),
    start_of=lambda cache: cache[0, :, 0], scope="attention", caches=True)


def attend_grouped_xla(q, cache, slot, p0, scale: float):
    """Grouped-query attention as XLA does it, ``ATTENTION_BLOCK`` flows'
    whole score tensor at a time: the path of every platform but the TPU,
    and what ``ops/flow_attention.grouped_attention_fused`` is tested
    against. ``q [F, T, H, head]`` bfloat16; ``cache [slots, 2 x G x head,
    positions]`` the layer's, whole: flow ``f`` attends over slot
    ``slot[f]`` (clipped into range, gathered here), query head ``i``
    against the keys ``[i // (H / G)]`` and the values ``[G + i // (H /
    G)]`` of its ``head`` rows; event ``t`` sees positions ``0 .. p0[f] +
    t``. Returns ``(o [F, T, H, head]`` bfloat16, the blocks of positions
    attended over ``[F]``, the blocks of a whole slot)``: every slot is
    attended whole, as one block."""
    F, T, H, hd = q.shape
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
        seen = jnp.arange(P)[None, None] <= pos[:, :, None]     # [nb, T, P]
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
    return (o.reshape(F, G, T, R, hd).transpose(0, 2, 1, 3, 4).reshape(
        F, T, H, hd), jnp.ones((F,), jnp.int32), 1)
