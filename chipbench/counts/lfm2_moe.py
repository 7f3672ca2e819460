"""Operations and bytes of the flow model of ``configs/lfm2-24b-a2b.json``,
from the configuration's widths alone: the algorithm's counts, the same
whatever implements it (``counts/latent_moe.py``'s rules). A matmul weight
is one multiply and one add an event; the convolution's taps count as
weights (3 a channel); norms, activations, RoPE, the router's sigmoid and
the softmaxes are not counted. Attention is counted as an event's query
against the keys, and the weights against the values, of the positions it
attends over (all query heads, ``head`` wide each) at the traffic's mean
context (``model.counted_context``). The routed experts are counted at
their expectation: ``num_experts_per_tok`` of the layer's experts a token,
of which this chip holds ``held / num_experts`` (all of them here). The
head is the tied embedding, counted once as weights held and once an event
as a product.

The kernel's own counts (``grouped_attention_*``) are **at the attended
blocks**: the kernel stops at the last block of 128 positions an event may
see, so its least work is over ``model.counted_attended_positions`` (the
traffic's mean of the context rounded up to blocks), all attention layers
of a step together, as the trace sums the kernel's calls of one step.

The functions take the configuration's ``model`` group (what the readers
hand over); the widths are the published keys at the file's top level."""

from __future__ import annotations

from chipbench.counts.latent_moe import _cfg


def conv_weights(c: dict) -> int:
    """One conv layer's operator: in_proj (3 x), out_proj and the taps
    (16.78 M at the published widths)."""
    d = c["hidden_size"]
    return 4 * d * d + c["conv_L_cache"] * d


def attention_weights(c: dict) -> int:
    """One attention layer's projections (10.49 M)."""
    d = c["hidden_size"]
    kv = c["num_key_value_heads"] * (d // c["num_attention_heads"])
    return 2 * d * d + 2 * d * kv


def expert_weights(c: dict) -> int:
    """One routed expert (9.44 M)."""
    return 3 * c["hidden_size"] * c["moe_intermediate_size"]


def _layers(c: dict) -> tuple:
    """``(conv layers, attention layers, dense layers, expert layers)``."""
    conv = sum(k == "conv" for k in c["layer_types"])
    return (conv, len(c["layer_types"]) - conv, c["num_dense_layers"],
            c["num_hidden_layers"] - c["num_dense_layers"])


def weights_held(model: dict) -> int:
    """Matmul weights on this chip (5.18 G: 10.36 GB in bfloat16), the
    tied embedding once."""
    c = _cfg(model)
    lo, hi = model["experts_held"]
    conv, attn, dense, moe = _layers(c)
    d = c["hidden_size"]
    return (conv * conv_weights(c) + attn * attention_weights(c)
            + dense * 3 * d * c["intermediate_size"]
            + moe * (d * c["num_experts"] + (hi - lo) * expert_weights(c))
            + d * c["vocab_size"])


def score_flops_per_row(model: dict) -> float:
    """One event through the block, here: 1.296 G at the published widths
    before attention over the context, 1.301 G with it at a context of
    293."""
    c = _cfg(model)
    lo, hi = model["experts_held"]
    conv, attn, dense, moe = _layers(c)
    d = c["hidden_size"]
    attended = 2 * d * model["counted_context"]     # all heads, k and v
    routed = (c["num_experts_per_tok"] * (hi - lo) / c["num_experts"]
              * expert_weights(c))
    weights = (conv * conv_weights(c)
               + attn * (attention_weights(c) + attended)
               + dense * 3 * d * c["intermediate_size"]
               + moe * (d * c["num_experts"] + routed)
               + d * c["vocab_size"])
    return 2.0 * weights


def weight_bytes_per_step(model: dict) -> int:
    """What a step must read of the weights at least: every matrix once,
    in the parameters' bfloat16 (the embedding as the head's matrix)."""
    return 2 * weights_held(model)


def grouped_attention_flops_per_row(model: dict) -> float:
    """``grouped_attention_fused``, all attention layers of a step, an
    event: every query head's scores against, and weights times, the
    attended blocks' positions."""
    c = _cfg(model)
    attn = _layers(c)[1]
    return 2.0 * attn * 2 * c["hidden_size"] * model[
        "counted_attended_positions"]


def grouped_attention_bytes_per_row(model: dict) -> float:
    """The same kernel's least traffic an event: its queries in and its
    output back (``hidden`` wide each), and its share of the flow's keys
    and values over the attended blocks, read once a flow (a chunk of
    ``rows_per_call / flows_per_call`` events shares them), in
    bfloat16."""
    c = _cfg(model)
    attn = _layers(c)[1]
    d = c["hidden_size"]
    kv = 2 * c["num_key_value_heads"] * (d // c["num_attention_heads"])
    return 2.0 * attn * (2 * d + kv * model["counted_attended_positions"]
                         / model["counted_chunk"])
