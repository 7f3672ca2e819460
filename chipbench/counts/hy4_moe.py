"""Operations and bytes of the flow model of ``configs/hy4-preview-ep16.json``,
from the configuration's widths alone: the algorithm's counts, the same
whatever implements it (``counts/latent_moe.py``'s rules). A matmul weight
is one multiply and one add an event, the gate's ``hidden x heads x 256``
and the hyper-connections' ``4 x hidden x 24`` (two a layer) among them,
once; norms, activations, RoPE, Sinkhorn, the sigmoids, the softmaxes and
the selection are not counted. Attention is counted in its up-projected
form (an event's query against the keys, and the weights against the
values, **of the positions its selection holds**: all heads, ``nope +
rope`` and ``v`` wide) at the traffic's mean selection,
``model.counted_selected``; the indexer on a ``full`` layer as its
weights and an event's ``heads x dim`` query against the index keys of
every position in context, ``model.counted_context``. The routed experts
are counted at their expectation: ``num_experts_per_tok`` of the layer's
experts a token, of which this chip holds ``held / router_experts``; the
shared expert once. The head is a product an event.

The selection kernel's own counts are **at the selected positions**, in
the absorbed form it computes (a head's query against the ``rank + rope``
values of a selected position's entry, its weight times the ``rank`` of
its latent), all five layers of a step together, as the trace sums a
kernel's calls of one step: the work any kernel of this attention must do,
whatever blocks it walks.

The functions take the configuration's ``model`` group (what the readers
hand over; the widths are the published keys at the file's top level) and,
from the readers that see them (``readers/program_mfu_seen.py``,
``kernel_roofline_seen.py``), what the traced calls attended over, an
event: ``seen["attn.selected"]`` positions over the five layers,
``seen["index.scored"]`` over the ``full`` layers (the program's counters
over the calls of the traced slice). **The window begins with every flow
empty and does not reach the schedule's steady state** (140 calls of a
period of 256): the counted means above are the steady state's, and a
trace of seconds 4-10 of the window sees contexts of a few hundred
positions, so the readers hand over what was seen."""

from __future__ import annotations

from chipbench.counts.latent_moe import _cfg
from chipbench.counts.latent_moe import attention_weights as mla_weights


def attention_weights(c: dict) -> int:
    """One layer's projections and its gate (265.7 M)."""
    return mla_weights(c) + c["hidden_size"] * c["num_attention_heads"] * c[
        "v_head_dim"]


def indexer_weights(c: dict) -> int:
    """A ``full`` layer's indexer (9.4 M)."""
    d, n, dh = c["hidden_size"], c["index_n_heads"], c["index_head_dim"]
    return c["q_lora_rank"] * n * dh + d * dh + d * n


def hyper_weights(c: dict) -> int:
    """A layer's two hyper-connections (1.18 M)."""
    n = c["hc_mult"] if c["enable_ihc"] else 0
    return 2 * n * c["hidden_size"] * n * (n + 2)


def expert_weights(c: dict) -> int:
    """One routed expert (37.7 M)."""
    return 3 * c["hidden_size"] * c["moe_intermediate_size"]


def _ffn_weights(c: dict, model: dict, l: int, experts: float) -> float:
    d = c["hidden_size"]
    if c["mlp_layer_types"][l] == "dense":
        return 3 * d * c["intermediate_size"]
    return (d * model["router_experts"]
            + c["n_shared_experts"] * expert_weights(c)
            + experts * expert_weights(c))


def _fulls(c: dict) -> int:
    return c["indexer_types"].count("full")


def weights_held(model: dict) -> int:
    """Matmul weights on this chip (4.45 G: 8.90 GB in bfloat16)."""
    c = _cfg(model)
    lo, hi = model["experts_held"]
    n = c["num_hidden_layers"]
    return int(n * (attention_weights(c) + hyper_weights(c))
               + _fulls(c) * indexer_weights(c)
               + sum(_ffn_weights(c, model, l, hi - lo) for l in range(n))
               + 2 * c["hidden_size"] * c["vocab_size"])


def _selected(c: dict, model: dict, seen) -> float:
    """Positions attended an event, over the layers."""
    if seen is not None:
        return seen["attn.selected"]
    return c["num_hidden_layers"] * model["counted_selected"]


def score_flops_per_row(model: dict, seen=None) -> float:
    """One event through the block, here (4.54 G at the published widths
    and the counted contexts)."""
    c = _cfg(model)
    lo, hi = model["experts_held"]
    n = c["num_hidden_layers"]
    h = c["num_attention_heads"]
    attended = h * (c["qk_nope_head_dim"] + c["qk_rope_head_dim"]
                    + c["v_head_dim"]) * _selected(c, model, seen)
    scored = c["index_n_heads"] * c["index_head_dim"] * (
        seen["index.scored"] if seen is not None
        else _fulls(c) * model["counted_context"])
    routed = c["num_experts_per_tok"] * (hi - lo) / model["router_experts"]
    weights = (n * (attention_weights(c) + hyper_weights(c))
               + attended + _fulls(c) * indexer_weights(c) + scored
               + sum(_ffn_weights(c, model, l, routed) for l in range(n))
               + c["hidden_size"] * c["vocab_size"])
    return 2.0 * weights


def weight_bytes_per_step(model: dict) -> int:
    """What a step must read of the weights at least: every matrix once,
    in the parameters' bfloat16, the embedding's rows aside."""
    c = _cfg(model)
    return 2 * (weights_held(model) - c["hidden_size"] * c["vocab_size"])


def sparse_attention_flops_per_row(model: dict, seen=None) -> float:
    """``sparse_latent_attention_fused``, the five layers of a step, an
    event: every head's scores against, and weights times, the latent
    entries of the selected positions."""
    c = _cfg(model)
    return 2.0 * c["num_attention_heads"] * (
        2 * c["kv_lora_rank"] + c["qk_rope_head_dim"]) * _selected(
            c, model, seen)


def sparse_attention_bytes_per_row(model: dict, seen=None) -> float:
    """The kernel's least traffic an event: its queries in and its output
    back (every head's ``rank + rope`` and ``rank``, bfloat16, a layer),
    the index scores of its selected positions (float32), and its share of
    the flow's entries at the selected positions, read once a flow (a
    chunk of ``counted_chunk`` events shares them), in bfloat16."""
    c = _cfg(model)
    rank, rope, h = (c["kv_lora_rank"], c["qk_rope_head_dim"],
                     c["num_attention_heads"])
    sel = _selected(c, model, seen)
    return (c["num_hidden_layers"] * 2.0 * h * (2 * rank + rope) + 4.0 * sel
            + 2.0 * (rank + rope) * sel / model["counted_chunk"])
