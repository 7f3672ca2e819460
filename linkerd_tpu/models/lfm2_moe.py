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
  layer, every call). The step's ``append`` writes the call's entries into
  it in place, then the chunk attends over its flow's slot by the step's
  ``attend``: ``grouped_attention_fused`` on a TPU, ``attend_grouped_xla``
  elsewhere. The operator is ``models/grouped_attention.py``'s, which
  another model's layers take with other head counts, a rotary part and
  a window: this model's instance of it is ``Lfm2MoEConfig.operator``'s.

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

import jax.numpy as jnp
import numpy as np

# noqa: F401 below: the XLA attention is this model's too, by this name
from linkerd_tpu.models.grouped_attention import (  # noqa: F401
    AttentionLayer, attend_grouped_xla, grouped_attention,
)
from linkerd_tpu.models.latent_moe import (
    GAIN_SPREAD, OUT_GAIN, Operator, _mm, _rms, check_held,
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
        """A convolution, or this model's instance of the grouped-query
        attention (``models/grouped_attention.py``): one head count, RoPE
        of the default kind over the whole head, no window."""
        if self.layer_types[l] == CONV:
            return SHORT_CONV
        return grouped_attention(AttentionLayer(
            self.num_attention_heads, self.num_key_value_heads,
            self.head_dim, rope_inv_freq(self)))

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
    output, the tails, and no counts."""
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
    return _mm(gate_out * v, lp["out_proj"]), tail, {}


SHORT_CONV = Operator(
    apply=_short_conv,
    init=lambda cfg: jnp.zeros(
        (cfg.slots, cfg.conv_L_cache - 1, cfg.hidden_size), jnp.bfloat16),
    start_of=lambda tail: tail[0], scope="conv", caches=False)


def rope_inv_freq(cfg: Lfm2MoEConfig) -> np.ndarray:
    dim = cfg.head_dim
    return (1.0 / cfg.rope_theta ** (
        np.arange(0, dim, 2, dtype=np.float64) / dim)).astype(np.float32)
