"""Operations and bytes of the flow model, from the configuration's widths
alone: the algorithm's counts, the same whatever implements it. A matmul
weight is one multiply and one add an event; norms, activations, RoPE, the
router's sigmoid and the softmaxes are not counted. Attention is counted
in its up-projected form (an event's query against the keys, and the
weights against the values, of the positions it attends over: all heads,
``nope + rope`` and ``v`` wide) at the traffic's mean context
(``model.counted_context``), a cached position's keys and values being
computed once, when the position was an event. The routed experts are
counted at their expectation: ``num_experts_per_tok`` of the layer's
experts a token, of which this chip holds ``held / router_experts``.

The functions take the configuration's ``model`` group (what the readers
hand over); the widths are the published keys at the file's top level,
read from ``configs/<model.config>.json`` beside this directory."""

from __future__ import annotations

import json
import os


def _cfg(model: dict) -> dict:
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "configs", model["config"] + ".json")
    with open(path) as f:
        return json.load(f)


def attention_weights(c: dict) -> int:
    """One layer's projections (101.1 M at the published widths)."""
    d, h = c["hidden_size"], c["num_attention_heads"]
    qd = c["qk_nope_head_dim"] + c["qk_rope_head_dim"]
    return (d * c["q_lora_rank"] + c["q_lora_rank"] * h * qd
            + d * (c["kv_lora_rank"] + c["qk_rope_head_dim"])
            + c["kv_lora_rank"] * h * (c["qk_nope_head_dim"]
                                       + c["v_head_dim"])
            + h * c["v_head_dim"] * d)


def expert_weights(c: dict) -> int:
    """One routed expert (44.04 M)."""
    return 3 * c["hidden_size"] * c["moe_intermediate_size"]


def weights_held(model: dict) -> int:
    """Matmul weights on this chip (3.49 G: 6.99 GB in bfloat16)."""
    c = _cfg(model)
    lo, hi = model["experts_held"]
    dense = c["first_k_dense_replace"]
    moe = c["num_hidden_layers"] - dense
    d = c["hidden_size"]
    return (c["num_hidden_layers"] * attention_weights(c)
            + dense * 3 * d * c["intermediate_size"]
            + moe * (c["n_shared_experts"] * expert_weights(c)
                     + d * model["router_experts"]
                     + (hi - lo) * expert_weights(c))
            + 2 * d * c["vocab_size"])


def score_flops_per_row(model: dict) -> float:
    """One event through the block, here (2.63 G at the published widths
    and a context of 293)."""
    c = _cfg(model)
    lo, hi = model["experts_held"]
    dense = c["first_k_dense_replace"]
    moe = c["num_hidden_layers"] - dense
    d, h = c["hidden_size"], c["num_attention_heads"]
    attended = h * (c["qk_nope_head_dim"] + c["qk_rope_head_dim"]
                    + c["v_head_dim"]) * model["counted_context"]
    routed = (c["num_experts_per_tok"] * (hi - lo) / model["router_experts"]
              * expert_weights(c))
    weights = (c["num_hidden_layers"] * (attention_weights(c) + attended)
               + dense * 3 * d * c["intermediate_size"]
               + moe * (c["n_shared_experts"] * expert_weights(c)
                        + d * model["router_experts"] + routed)
               + d * c["vocab_size"])
    return 2.0 * weights


def weight_bytes_per_step(model: dict) -> int:
    """What a step must read of the weights at least: every matrix once,
    in the parameters' bfloat16, the embedding's rows aside."""
    c = _cfg(model)
    return 2 * (weights_held(model) - c["hidden_size"] * c["vocab_size"])
