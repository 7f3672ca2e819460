"""A third flow model: window and full attention layers mixed, each kind
with a head count, a RoPE and per-flow state of its own, and sigmoid-routed
experts beside a shared one (Laguna's block, as Laguna-XS.2's
``config.json`` sizes it).

The step, the ``[F, T]`` layout, the routed experts, the shared expert's
branch, the head and the score mapping are ``models/latent_moe.py``'s
(``flow_step``), and both kinds of layer are instances of the one
grouped-query attention of ``models/grouped_attention.py``
(``LagunaMoEConfig.operator``): this module gives them the configuration
and the tensors. Per layer ``h += Attn(RMSNorm(h))``, then ``h +=
FFN(RMSNorm(h))``; ``layer_types[l]`` says which attention:

- **``full_attention``**: ``num_attention_heads_per_layer[l]`` query heads
  (48) over ``num_key_value_heads`` (8) of ``head_dim`` (128); RoPE of
  YaRN's kind over the first ``partial_rotary_factor`` of every head (64
  of 128 values), cos and sin times its ``attention_factor``; an event
  sees every position before it. **The state is a cache of
  ``positions``** a slot.
- **``sliding_attention``**: a head count of its own (64), RoPE of the
  default kind over the whole head, and an event sees the last
  ``sliding_window`` positions (512, itself included). **The state is a
  ring** of ``ring_positions(window, chunk_max)`` (640) a slot, whatever
  the flow's length: written at ``position mod ring``, never cleared.

Both have an output gate a head (``wg``), no q/k norm and no bias. The
feed-forward of a ``dense`` layer is a SwiGLU; of a ``sparse`` one
``shared(x) + routed(x)``: sigmoid scores over ``num_experts``, the top
``num_experts_per_tok`` **of the scores themselves** (no selection bias),
weights the selected scores over their sum, times
``moe_routed_scaling_factor``. Embedding and head are two tensors.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Dict, NamedTuple, Tuple

import numpy as np

from linkerd_tpu.models.grouped_attention import (
    RING_BLOCK, AttentionLayer, grouped_attention, ring_positions,
)
from linkerd_tpu.models.latent_moe import (
    GAIN_SPREAD, OUT_GAIN, Operator, check_held, yarn_frequencies,
)

FULL, SLIDING = "full_attention", "sliding_attention"
DENSE, SPARSE = "dense", "sparse"


class Rope(NamedTuple):
    """A layer type's ``rope_parameters``: ``rotary`` the part of a head
    that turns; with a ``factor``, YaRN's kind, cos and sin times
    ``scale``; without, the default kind."""
    theta: float
    rotary: float = 1.0
    factor: float = 0.0
    original: int = 0
    beta_fast: float = 0.0
    beta_slow: float = 0.0
    scale: float = 1.0

    def inv_freq(self, head_dim: int) -> np.ndarray:
        dim = int(head_dim * self.rotary)
        if self.factor:
            return yarn_frequencies(dim, self.theta, self.factor,
                                    self.original, self.beta_fast,
                                    self.beta_slow)
        return (1.0 / self.theta ** (
            np.arange(0, dim, 2, dtype=np.float64) / dim)).astype(np.float32)


@dataclass(frozen=True)
class LagunaMoEConfig:
    hidden_size: int = 2048
    head_dim: int = 128
    num_key_value_heads: int = 8
    heads_per_layer: Tuple[int, ...] = (48, 64, 64, 64, 48)
    layer_types: Tuple[str, ...] = (FULL, SLIDING, SLIDING, SLIDING, FULL)
    mlp_layer_types: Tuple[str, ...] = (DENSE, SPARSE, SPARSE, SPARSE, SPARSE)
    intermediate_size: int = 8192
    moe_intermediate_size: int = 512
    shared_expert_intermediate_size: int = 512
    n_routed_experts: int = 256
    num_experts_per_tok: int = 8
    routed_scaling_factor: float = 2.5
    route_eps: float = 0.0
    rms_norm_eps: float = 1e-6
    sliding_window: int = 512
    full_rope: Rope = Rope(500000.0, 0.5, 64.0, 4096, 64.0, 1.0,
                           1.4158883083359672)
    sliding_rope: Rope = Rope(10000.0)
    experts_held: Tuple[int, int] = (0, 256)    # [lo, hi) of every layer
    layer_share: int = 1                # devices that share each layer
    vocab_slice: int = 100352           # the whole vocabulary
    slots: int = 128
    positions: int = 4224   # 33 blocks: a flow of 4,033 and a chunk more
    expert_tile: int = 128
    chunk_max: int = 64         # the longest chunk a ring has room behind
    ring_block: int = RING_BLOCK

    def __post_init__(self):
        check_held(self)
        if set(self.layer_types) - {FULL, SLIDING}:
            raise ValueError(f"layer_types {sorted(set(self.layer_types))}: "
                             f"only {FULL!r} and {SLIDING!r} are computed")
        if not (len(self.layer_types) == len(self.mlp_layer_types)
                == len(self.heads_per_layer)):
            raise ValueError("layer_types, mlp_layer_types and the head "
                             "counts do not name the same layers")

    @property
    def layers(self) -> int:
        return len(self.layer_types)

    @property
    def entry_width(self) -> int:
        """Values a layer's state holds a position: the keys and the
        values of every key/value head."""
        return 2 * self.num_key_value_heads * self.head_dim

    @property
    def ring(self) -> int:
        """Positions a sliding layer's slot keeps."""
        return ring_positions(self.sliding_window, self.chunk_max,
                              self.ring_block)

    def operator(self, l: int) -> Operator:
        """Layer ``l``'s instance of the grouped-query attention."""
        H, G, hd = (self.heads_per_layer[l], self.num_key_value_heads,
                    self.head_dim)
        if self.layer_types[l] == FULL:
            return grouped_attention(AttentionLayer(
                H, G, hd, self.full_rope.inv_freq(hd),
                rope_scale=self.full_rope.scale, kind="full"))
        return grouped_attention(AttentionLayer(
            H, G, hd, self.sliding_rope.inv_freq(hd),
            window=self.sliding_window, ring=self.ring, kind="window"))

    def tensors(self) -> Dict[str, tuple]:
        return tensor_table(self)

    @classmethod
    def from_config(cls, cfg: dict) -> "LagunaMoEConfig":
        """From a configuration file of the benchmark (the published keys
        at the top level; under ``model`` what is this repo's: the held
        range, the share, the state's size)."""
        m, rope = cfg["model"], cfg["rope_parameters"]
        full, sliding = rope[FULL], rope[SLIDING]
        if full["rope_type"] != "yarn" or sliding["rope_type"] != "default":
            raise ValueError("only yarn on the full layers and the default "
                             "RoPE on the sliding ones are computed")
        if cfg["moe_apply_router_weight_on_input"]:
            raise ValueError("only router weights on the experts' outputs "
                             "are computed")
        n = cfg["num_hidden_layers"]
        for key in ("layer_types", "mlp_layer_types",
                    "num_attention_heads_per_layer"):
            if len(cfg[key]) != n:
                raise ValueError(f"{key} does not name every layer")
        if set(cfg["mlp_layer_types"]) - {DENSE, SPARSE}:
            raise ValueError(f"mlp_layer_types: only {DENSE!r} and "
                             f"{SPARSE!r} are computed")
        return cls(
            hidden_size=cfg["hidden_size"], head_dim=cfg["head_dim"],
            num_key_value_heads=cfg["num_key_value_heads"],
            heads_per_layer=tuple(cfg["num_attention_heads_per_layer"]),
            layer_types=tuple(cfg["layer_types"]),
            mlp_layer_types=tuple(cfg["mlp_layer_types"]),
            intermediate_size=cfg["intermediate_size"],
            moe_intermediate_size=cfg["moe_intermediate_size"],
            shared_expert_intermediate_size=cfg[
                "shared_expert_intermediate_size"],
            n_routed_experts=cfg["num_experts"],
            num_experts_per_tok=cfg["num_experts_per_tok"],
            routed_scaling_factor=float(cfg["moe_routed_scaling_factor"]),
            rms_norm_eps=cfg["rms_norm_eps"],
            sliding_window=cfg["sliding_window"],
            full_rope=Rope(
                float(full["rope_theta"]),
                float(full["partial_rotary_factor"]), float(full["factor"]),
                full["original_max_position_embeddings"],
                float(full["beta_fast"]), float(full["beta_slow"]),
                float(full["attention_factor"])),
            sliding_rope=Rope(float(sliding["rope_theta"]),
                              float(sliding["partial_rotary_factor"])),
            experts_held=tuple(m["experts_held"]),
            layer_share=m["layer_share"], vocab_slice=cfg["vocab_size"],
            slots=m["slots"], positions=m["positions"],
            expert_tile=m.get("expert_tile", 128),
            chunk_max=m["chunk_max"],
            ring_block=m.get("ring_block", RING_BLOCK))


def tensor_table(cfg: LagunaMoEConfig) -> Dict[str, tuple]:
    """``{name: (shape, std, mean, per_expert)}`` of every tensor, by the
    configuration file's rule (``models/lfm2_moe.tensor_table``'s, with an
    embedding of std 1 and a head of its own, as
    ``models/latent_moe.tensor_table`` has them; the gate ``wg`` at
    ``1/sqrt(hidden)``, so the gates spread over 0.1-0.9)."""
    d, hd = cfg.hidden_size, cfg.head_dim
    kv = cfg.num_key_value_heads * hd
    inter = cfg.moe_intermediate_size
    shared = cfg.shared_expert_intermediate_size

    def mat(i, o, gain=1.0):
        return ((i, o), gain / math.sqrt(i), 0.0, False)

    def gain(n):
        return ((n,), GAIN_SPREAD, 1.0, False)

    t = {"embed": ((cfg.vocab_slice, d), 1.0, 0.0, False),
         "head": mat(d, cfg.vocab_slice), "final_norm": gain(d)}
    for l, (heads, mlp) in enumerate(zip(cfg.heads_per_layer,
                                         cfg.mlp_layer_types)):
        p = f"layers.{l}."
        t.update({p + "operator_norm": gain(d), p + "ffn_norm": gain(d),
                  p + "wq": mat(d, heads * hd), p + "wk": mat(d, kv),
                  p + "wv": mat(d, kv), p + "wg": mat(d, heads),
                  p + "wo": mat(heads * hd, d, OUT_GAIN)})
        if mlp == DENSE:
            t.update({p + "w_gate": mat(d, cfg.intermediate_size),
                      p + "w_up": mat(d, cfg.intermediate_size),
                      p + "w_down": mat(cfg.intermediate_size, d, OUT_GAIN)})
        else:
            t.update({
                p + "router": mat(d, cfg.n_routed_experts),
                p + "shared_gate": mat(d, shared),
                p + "shared_up": mat(d, shared),
                p + "shared_down": mat(shared, d, OUT_GAIN),
                p + "exp_gate": ((d, inter), 1 / math.sqrt(d), 0.0, True),
                p + "exp_up": ((d, inter), 1 / math.sqrt(d), 0.0, True),
                p + "exp_down": ((inter, d), OUT_GAIN / math.sqrt(inter),
                                 0.0, True)})
    return t
