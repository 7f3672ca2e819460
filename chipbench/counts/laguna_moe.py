"""Operations and bytes of the flow model of ``configs/laguna-xs.2.json``,
from the configuration's widths alone: the algorithm's counts, the same
whatever implements it (``counts/latent_moe.py``'s rules). A matmul weight
is one multiply and one add an event, the gate's ``hidden x heads`` among
them, once; norms, activations, RoPE, the sigmoids and the softmaxes are
not counted. Attention is counted as an event's query against the keys,
and the weights against the values, of the positions it attends over (the
layer's query heads, ``head_dim`` wide each) at the traffic's mean
context: ``model.counted_context_full`` on a full layer,
``model.counted_context_window`` (the window's cut of it) on a sliding
one. The routed experts are counted at their expectation,
``num_experts_per_tok`` of the layer's experts a token, all held here,
and the shared expert once a token. Embedding and head are two tensors:
both are held, the head is a product an event.

The kernels' own counts are **at the attended blocks**: a tile's loops
run over whole blocks of 128 positions, from the first a row's window
reaches to the last an event sees, so their least work is over
``model.counted_attended_full`` / ``_window`` (the traffic's mean over the
tiles the kernel makes), all layers of a kind in a step together, as the
trace sums a kernel's calls of one step.

The functions take the configuration's ``model`` group (what the readers
hand over); the widths are the published keys at the file's top level."""

from __future__ import annotations

from chipbench.counts.latent_moe import _cfg


def attention_weights(c: dict, l: int) -> int:
    """Layer ``l``'s projections and its gate (29.46 M on a full layer,
    37.88 M on a sliding one)."""
    d, hd = c["hidden_size"], c["head_dim"]
    heads = c["num_attention_heads_per_layer"][l]
    return d * (2 * heads * hd + 2 * c["num_key_value_heads"] * hd + heads)


def expert_weights(c: dict) -> int:
    """One routed expert (3.146 M)."""
    return 3 * c["hidden_size"] * c["moe_intermediate_size"]


def _ffn_weights(c: dict, l: int, experts: float) -> float:
    """Layer ``l``'s feed-forward with ``experts`` routed experts."""
    d = c["hidden_size"]
    if c["mlp_layer_types"][l] == "dense":
        return 3 * d * c["intermediate_size"]
    return (d * c["num_experts"] + 3 * d * c[
        "shared_expert_intermediate_size"] + experts * expert_weights(c))


def _layers_of(c: dict, kind: str) -> list:
    return [l for l, k in enumerate(c["layer_types"]) if k == kind]


def weights_held(model: dict) -> int:
    """Matmul weights on this chip (3.870 G: 7.74 GB in bfloat16)."""
    c = _cfg(model)
    lo, hi = model["experts_held"]
    return (sum(attention_weights(c, l) + _ffn_weights(c, l, hi - lo)
                for l in range(c["num_hidden_layers"]))
            + 2 * c["hidden_size"] * c["vocab_size"])


def score_flops_per_row(model: dict) -> float:
    """One event through the block, here (1.19 G at the published widths
    and the counted contexts)."""
    c = _cfg(model)
    lo, hi = model["experts_held"]
    routed = c["num_experts_per_tok"] * (hi - lo) / c["num_experts"]
    hd = c["head_dim"]
    context = {"full_attention": model["counted_context_full"],
               "sliding_attention": model["counted_context_window"]}
    weights = sum(
        attention_weights(c, l) + _ffn_weights(c, l, routed)
        + 2 * c["num_attention_heads_per_layer"][l] * hd * context[kind]
        for l, kind in enumerate(c["layer_types"]))
    return 2.0 * (weights + c["hidden_size"] * c["vocab_size"])


def weight_bytes_per_step(model: dict) -> int:
    """What a step must read of the weights at least: every matrix once,
    in the parameters' bfloat16, the embedding's rows aside."""
    c = _cfg(model)
    return 2 * (weights_held(model) - c["hidden_size"] * c["vocab_size"])


def _attention_flops(model: dict, kind: str, attended: str) -> float:
    c = _cfg(model)
    return 2.0 * sum(
        2 * c["num_attention_heads_per_layer"][l] * c["head_dim"]
        * model[attended] for l in _layers_of(c, kind))


def _attention_bytes(model: dict, kind: str, attended: str) -> float:
    """A kernel's least traffic an event: its queries in and its output
    back (the layer's heads x head_dim each), and its share of the flow's
    keys and values over the attended blocks, read once a flow (a chunk
    of ``counted_chunk`` events shares them), in bfloat16."""
    c = _cfg(model)
    hd = c["head_dim"]
    kv = 2 * c["num_key_value_heads"] * hd
    return 2.0 * sum(
        2 * c["num_attention_heads_per_layer"][l] * hd
        + kv * model[attended] / model["counted_chunk"]
        for l in _layers_of(c, kind))


def full_attention_flops_per_row(model: dict) -> float:
    """``grouped_attention_fused``, the full layers of a step, an event:
    every query head's scores against, and weights times, the attended
    blocks' positions."""
    return _attention_flops(model, "full_attention", "counted_attended_full")


def full_attention_bytes_per_row(model: dict) -> float:
    return _attention_bytes(model, "full_attention", "counted_attended_full")


def window_attention_flops_per_row(model: dict) -> float:
    """``window_attention_fused``, the sliding layers of a step."""
    return _attention_flops(model, "sliding_attention",
                            "counted_attended_window")


def window_attention_bytes_per_row(model: dict) -> float:
    return _attention_bytes(model, "sliding_attention",
                            "counted_attended_window")
