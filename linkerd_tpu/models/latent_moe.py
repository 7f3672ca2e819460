"""A flow model: latent attention over a per-flow cache, and a sigmoid-routed
mixture of experts beside a shared one (the DeepSeek-V3 family's block);
and **the step every flow model runs** (``flow_step``: the ``[F, T]``
layout, the layers' loop, the routed experts, the head and the score
mapping), which takes a model's layers from its configuration: each
layer's *operator* (``cfg.operator(l)``, an ``Operator``: how it is
applied, and what state of a flow it keeps) and its tensors
(``cfg.tensors()``). This model's one operator is the latent attention
below; ``models/lfm2_moe.py`` brings a second model's two.

An *event* is one token: an id in ``[1, vocab_slice)``; id 0 is the start
token the program writes at position 0 of every flow. A *flow* is the
events under one stream key, held in one of ``slots`` rows of the cache.
The score of an event is how surprised the model is by it given the flow
so far: ``1 - exp(-nll / ln vocab_slice)`` with ``nll`` the event's
negative log-likelihood under the logits of the position before it.

Per layer ``h += Attn(RMSNorm(h))``, then ``h += FFN(RMSNorm(h))``:

- **Latent attention.** Queries through a low-rank pair (``wdq``, norm,
  ``wuq``) into ``heads x (nope + rope)``; keys and values through ``wdkv``
  into a ``kv_lora_rank`` latent (normed) and one rope key shared by all
  heads. *The cache holds that latent and the rotated rope key, and
  nothing else*: ``kv_lora_rank + rope`` values a position a layer.
  Attention runs with ``wukv`` absorbed into the query and the output
  (``q_nope . Wuk`` against the latent itself; the weighted latents through
  ``Wuv``), so a cached position is never up-projected. RoPE is YaRN's,
  rotate-half pairing. All heads of a flow share the one latent and the
  one rope key, so a flow's queries are one ``[events x heads, rank +
  rope]`` matrix against its slot ``[positions, rank + rope]``. **The
  cache is read and written where it lies** (PR 31). A layer first
  appends the call's entries in place by the step's ``append`` (on a TPU
  ``ops/cache_append.py``'s kernel: a flow's one or two tiles of 128
  positions read, merged and written back whole; elsewhere
  ``append_chunk``: a window of ``T + 1`` positions a flow is read, the
  chunk's rows and, where the flow begins, the start token's are set into
  it, and the window is written back; no slot is gathered, merged and
  written back whole), then the
  chunk attends over its flow's slot of the appended cache. *How that
  product runs is the step's ``attend``*, which takes the layer's cache
  whole and each flow's slot number, chosen by platform where the step is
  built (``models/spec.py``): on a TPU the Pallas kernel of
  ``ops/flow_attention.py``, whose block specs take a flow's slot from
  the cache by its number, which tiles the query rows and the positions,
  keeps a tile's scores in VMEM from the product to the softmax's weights
  and stops at the last block of positions the tile's events may see,
  **so neither a copy of the slots nor the score tensor reaches HBM and
  positions past a flow's length cost nothing**; elsewhere ``attend_xla``,
  which gathers the flows' slots itself and forms the scores of
  ``ATTENTION_BLOCK`` flows over their whole slots at a time. Both mask
  causally by ``p0`` and agree to bfloat16 rounding.
- **FFN.** The first ``first_k_dense_replace`` layers: dense SwiGLU. The
  others: ``shared(x) + routed(x)``. The router scores all
  ``n_routed_experts`` in float32 (``sigmoid``), selects the top
  ``num_experts_per_tok`` of score + bias, weighs them by their scores
  (without the bias) over their sum, times ``routed_scaling_factor``.
  **This device holds the experts ``experts_held``** (a range) of every
  layer: ``routed(x)`` sums over the token's selected experts that are
  held here, the others add nothing, and nothing stands in for them. The
  held pairs are sorted by expert into tiles of ``expert_tile`` rows, each
  tile of one expert, and the tiles *that hold a pair* go through **one
  grouped product** (``routed_experts``): their rows are gathered, the
  step's ``experts.product`` computes every tile's SwiGLU under its
  expert's weights, and ``experts.combine`` adds the weighted rows to
  their tokens'. *How those two run is chosen by platform where the step
  is built*, as ``attend`` is: on a TPU the Pallas kernels of
  ``ops/expert_product.py``, whose grids are the tiles that hold a pair:
  the product fetches an expert's weights once for all its tiles, the
  next expert's under this one's matmuls, and the combine keeps a block
  of the output's columns in VMEM while the rows are added to it;
  elsewhere ``swiglu_tiles_xla``, a loop that slices the tile's expert
  out of the weights every trip, and XLA's scatter-add. No capacity, no
  token dropped, work in proportion to the pairs.

The device step (``flow_step``) takes the call's rows as the host's
``FlowTable`` laid them out (``telemetry/flowstate.py``): row ``i`` is
``(cell, address, id)`` with ``cell = f * T + t`` its place in the call's
``[F flows, T events]`` layout and ``address = slot * positions + pos``
where it lies in the cache. Rows at and past ``n`` are padding and are
masked out of the state by ``n``. A chunk that begins at position 1 begins
a flow: the start token's cache entries (constants of the parameters,
kept with the state: ``with_start``) are written at position 0 first.

Parameters are bfloat16, drawn on the device from the seed (``init``):
one draw a tensor, ``normal(fold_in(key(seed), crc32(name)))`` in float32
times the tensor's scale, cast to bfloat16; an expert tensor folds the
expert's index in once more, so an expert's weights are the same whichever
device holds it. Compute is bfloat16 with float32 accumulation; the
residual stream, the norms, the router and the softmaxes are float32.
"""

from __future__ import annotations

import contextlib
import math
import zlib
from dataclasses import dataclass
from typing import Any, Callable, Dict, NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np

Params = Dict[str, Any]


@dataclass(frozen=True)
class LatentMoEConfig:
    hidden_size: int = 7168
    num_attention_heads: int = 64
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    intermediate_size: int = 18432
    moe_intermediate_size: int = 2048
    n_routed_experts: int = 384         # the router's width: the whole layer's
    n_shared_experts: int = 1
    num_experts_per_tok: int = 8
    routed_scaling_factor: float = 2.827
    first_k_dense_replace: int = 1
    rms_norm_eps: float = 1e-5
    rope_theta: float = 50000.0
    rope_factor: float = 64.0
    rope_original_positions: int = 4096
    rope_beta_fast: float = 32.0
    rope_beta_slow: float = 1.0
    rope_mscale_all_dim: float = 1.0
    layers: int = 5
    experts_held: Tuple[int, int] = (0, 12)    # [lo, hi) of every layer
    layer_share: int = 32               # devices that share each layer
    vocab_slice: int = 20480
    slots: int = 512
    positions: int = 1024
    expert_tile: int = 128
    route_eps: float = 0.0      # added to the selected scores' sum

    def __post_init__(self):
        check_held(self)

    @property
    def entry_width(self) -> int:
        """Values the cache holds a position a layer."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    def operator(self, l: int) -> "Operator":
        return LATENT_ATTENTION

    def tensors(self) -> Dict[str, tuple]:
        return tensor_table(self)

    @classmethod
    def from_config(cls, cfg: dict) -> "LatentMoEConfig":
        """From a configuration file of the benchmark (the published keys at
        the top level; under ``model`` what is this repo's: the router's
        width where the file's ``n_routed_experts`` is the count held
        here, the held range, the share, the cache's size)."""
        m, rope = cfg["model"], cfg["rope_scaling"]
        if rope["type"] != "yarn" or rope["mscale"] != rope["mscale_all_dim"]:
            # equal mscales leave cos and sin unscaled, as computed here
            raise ValueError("only yarn with mscale == mscale_all_dim")
        if cfg["scoring_func"] != "sigmoid" or cfg["n_group"] != 1:
            raise ValueError("only sigmoid scores in one group are computed")
        return cls(
            hidden_size=cfg["hidden_size"],
            num_attention_heads=cfg["num_attention_heads"],
            q_lora_rank=cfg["q_lora_rank"], kv_lora_rank=cfg["kv_lora_rank"],
            qk_nope_head_dim=cfg["qk_nope_head_dim"],
            qk_rope_head_dim=cfg["qk_rope_head_dim"],
            v_head_dim=cfg["v_head_dim"],
            intermediate_size=cfg["intermediate_size"],
            moe_intermediate_size=cfg["moe_intermediate_size"],
            n_routed_experts=m["router_experts"],
            n_shared_experts=cfg["n_shared_experts"],
            num_experts_per_tok=cfg["num_experts_per_tok"],
            routed_scaling_factor=cfg["routed_scaling_factor"],
            first_k_dense_replace=cfg["first_k_dense_replace"],
            rms_norm_eps=cfg["rms_norm_eps"], rope_theta=cfg["rope_theta"],
            rope_factor=rope["factor"],
            rope_original_positions=rope["original_max_position_embeddings"],
            rope_beta_fast=rope["beta_fast"], rope_beta_slow=rope["beta_slow"],
            rope_mscale_all_dim=rope["mscale_all_dim"],
            layers=cfg["num_hidden_layers"],
            experts_held=tuple(m["experts_held"]),
            layer_share=m["layer_share"], vocab_slice=cfg["vocab_size"],
            slots=m["slots"], positions=m["positions"],
            expert_tile=m.get("expert_tile", 128))


def check_held(cfg) -> None:
    lo, hi = cfg.experts_held
    if not 0 <= lo < hi <= cfg.n_routed_experts:
        raise ValueError(f"experts_held {cfg.experts_held} outside "
                         f"0..{cfg.n_routed_experts}")


class Call(NamedTuple):
    """What a call brings every layer's operator: per flow of the layout
    its ``slot`` (``slots`` where the flow brings nothing), the position
    ``p0`` its chunk is appended at, the chunk's ``count`` events and
    whether the flow ``begins`` here; ``pos [F, T]`` each event's
    position; ``attend``: the attention over a slot, ``experts``: the
    routed experts' grouped product and combine (``ExpertOps``), and
    ``append``: how a chunk's entries reach a layer's state
    (``append_chunk``'s signature and result), that the step was built
    with."""
    slot: Any
    p0: Any
    count: Any
    begins: Any
    pos: Any
    attend: Callable
    experts: Any
    append: Callable


class Operator(NamedTuple):
    """One kind of a layer's operator (what stands before the
    feed-forward), and the per-flow state it keeps. ``apply(lp, cfg, kept,
    start, h, call) -> (y, kept, counts)``: ``h [F, T, hidden]`` the
    residual stream (the operator norms it itself), ``kept`` this layer's
    state, donated, ``start`` what the start token leaves of it (set
    where a flow begins), ``counts`` a dict of scalars, what the layer
    attended over and wrote, under the names ``flow_step`` reports
    (``OPERATOR_COUNTS``, and any of the operator's own), summed over the
    flows. ``init(cfg)``: the state, empty;
    ``start_of(kept)``: what slot 0 holds of the start token once the
    call that makes the constants (``with_start``) has run; ``scope``: the
    operator's name in a device scope; ``caches``: whether the state
    holds positions of the flow (a row a position: ``cfg.positions`` of
    them, or where ``ring`` is set the newest ``ring``, at ``position mod
    ring``); ``parts``: whether ``apply`` opens the step's parts of an
    attention operator inside its scope (``project``: norms, projections,
    RoPE and the entry; ``append``; ``attend``; ``out``: the output's
    projection, and the residual add that XLA fuses with it); where it
    does not, the operator's scope is one part. ``carries``: whether the
    layer hands something to the layers after it (``models/hy4_moe.py``:
    a selection of positions): ``apply`` then takes the carry the layer
    before it handed on (None at the first) as a last argument and hands
    back the one to pass on as a fourth result."""
    apply: Callable
    init: Callable
    start_of: Callable
    scope: str
    caches: bool
    ring: int = 0
    parts: bool = False
    carries: bool = False


# what every flow model's step reports of its operators, nought where no
# layer counts it: the blocks of positions attended over, those of the
# slots whole, the cache rows written and those of the touched slots whole,
# the flows appended to (a slot in range, a layer) and of those the flows
# a kernel appended
OPERATOR_COUNTS = ("attn.kv_blocks", "attn.kv_blocks_whole",
                   "cache.rows_written", "cache.rows_whole",
                   "append.flows", "append.flows_in_kernel")


# -- weights from the seed ----------------------------------------------------

OUT_GAIN = 0.3      # output projections: residual norms stay near 1
GAIN_SPREAD = 0.1   # a norm's learned gain: 1 + 0.1 * normal
BIAS_SPREAD = 0.01  # the router's per-expert selection bias


def tensor_table(cfg: LatentMoEConfig) -> Dict[str, tuple]:
    """``{name: (shape, std, mean, per_expert)}`` of every tensor, in the
    words of the configuration file's rule: a matrix ``[fan_in, fan_out]``
    has std ``1/sqrt(fan_in)`` (output projections 0.3 of that), the
    embedding std 1, a norm's gain 1 + 0.1 normal, the router's bias 0.01
    normal. ``per_expert`` tensors are drawn expert by expert."""
    d, h = cfg.hidden_size, cfg.num_attention_heads
    qd = cfg.qk_nope_head_dim + cfg.qk_rope_head_dim
    kvd = cfg.qk_nope_head_dim + cfg.v_head_dim
    inter = cfg.moe_intermediate_size
    shared = inter * cfg.n_shared_experts

    def mat(i, o, gain=1.0):
        return ((i, o), gain / math.sqrt(i), 0.0, False)

    def gain(n):
        return ((n,), GAIN_SPREAD, 1.0, False)

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
            p + "wo": mat(h * cfg.v_head_dim, d, OUT_GAIN),
            p + "ffn_norm": gain(d)})
        if l < cfg.first_k_dense_replace:
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


def draw(key, tag, std, mean, experts, *, shape):
    """One tensor by the rule. ``tag``: the crc32 of the tensor's name;
    ``experts``: the indices of the experts to draw, stacked on a leading
    axis, or None."""
    k = jax.random.fold_in(key, tag)

    def one(k):
        return (mean + std * jax.random.normal(k, shape, jnp.float32)
                ).astype(jnp.bfloat16)

    if experts is None:
        return one(k)
    return jax.vmap(lambda e: one(jax.random.fold_in(k, e)))(experts)


_draw = jax.jit(draw, static_argnames=("shape",))


def name_tag(name: str) -> np.uint32:
    return np.uint32(zlib.crc32(name.encode()))


def init(key, cfg) -> Params:
    """The parameters on the default device, tensor by tensor (each draw's
    float32 scratch is freed before the next)."""
    held = jnp.arange(*cfg.experts_held, dtype=jnp.uint32)
    params: Params = {"layers": [{} for _ in range(cfg.layers)]}
    for name, (shape, std, mean, per_expert) in cfg.tensors().items():
        w = _draw(key, name_tag(name), np.float32(std), np.float32(mean),
                  held if per_expert else None, shape=shape)
        parts = name.split(".")
        if parts[0] == "layers":
            params["layers"][int(parts[1])][parts[2]] = w
        else:
            params[name] = w
    return params


def init_state(cfg):
    """``(kept, length [slots], last_h [slots, hidden], start)``: what
    each layer's operator keeps of a flow (``Operator.init``: the latent
    cache, one array ``[slots, positions, entry]`` a layer, in this model;
    keys and values, or a short convolution's tail, in
    ``models/lfm2_moe.py``), the positions each slot holds, each slot's
    newest final hidden state (which predicts the flow's next event), and
    what the start token leaves behind: None until the first call has
    computed it (``with_start``)."""
    return (tuple(cfg.operator(l).init(cfg) for l in range(cfg.layers)),
            jnp.zeros((cfg.slots,), jnp.int32),
            jnp.zeros((cfg.slots, cfg.hidden_size), jnp.bfloat16),
            None)


def start_shapes(cfg) -> tuple:
    """``ShapeDtypeStruct``s of the start token's constants: what each
    layer keeps of it (``Operator.start_of``), and its final hidden
    state."""
    return (jax.eval_shape(lambda: tuple(
        cfg.operator(l).start_of(cfg.operator(l).init(cfg))
        for l in range(cfg.layers))),
        jax.ShapeDtypeStruct((cfg.hidden_size,), jnp.bfloat16))


def with_start(run, cfg, state, rows):
    """The state with the start token's constants, which every flow begins
    from: what it leaves in every layer's state (a cache entry
    ``[entry]``; a convolution's tail) and its final hidden state
    ``[hidden]`` (which predicts a flow's first event). They
    are constants of the parameters, as the cache is a function of them,
    and are made by the step's own program (``run(state, rows, n)``): one
    call whose single event is the start token itself, which ``flow_step``
    takes as the call that makes them. Its arguments have the shapes of
    the call about to be made (``rows``: that call's, staged) and are
    placed where that call's are, so the program is lowered and read from
    the compile cache once: for arrays of the host it was lowered a second
    time (1.1 s of every set-up; my chip runs, PR 29)."""
    kept, length, last_h, _ = state
    blank, first = jax.device_put(
        (jax.tree_util.tree_map(lambda a: np.zeros(a.shape, a.dtype),
                                start_shapes(cfg)),
         np.zeros(rows.shape, np.int32)), rows.sharding)
    return run((kept, length, last_h, blank), first, 1)[1]


# -- the block ----------------------------------------------------------------

def _mm(x, w):
    return jnp.dot(x.astype(jnp.bfloat16), w,
                   preferred_element_type=jnp.float32)


def _rms(x, gain, eps):
    x = x.astype(jnp.float32)
    return (x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)
            * gain.astype(jnp.float32))


def _swiglu(x, gate, up, down, limit=None):
    """``silu(x gate) * (x up)`` through ``down``; with a ``limit`` (a
    configuration's ``swiglu_limit``) ``silu(min(x gate, limit)) *
    clip(x up, -limit, limit)``."""
    if limit is None:
        return _mm(jax.nn.silu(_mm(x, gate)) * _mm(x, up), down)
    return _mm(jax.nn.silu(jnp.minimum(_mm(x, gate), limit))
               * jnp.clip(_mm(x, up), -limit, limit), down)


def yarn_frequencies(dim: int, base: float, factor: float, original: int,
                     beta_fast: float, beta_slow: float) -> np.ndarray:
    """YaRN's blend of the plain and the interpolated frequencies of
    ``dim`` rotated values by the linear ramp between the two correction
    dimensions."""
    extra = 1.0 / base ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    inter = extra / factor

    def correction(rotations):
        return (dim * math.log(original / (rotations * 2 * math.pi))
                / (2 * math.log(base)))

    low = max(math.floor(correction(beta_fast)), 0)
    high = min(math.ceil(correction(beta_slow)), dim - 1)
    ramp = np.clip((np.arange(dim // 2) - low) / max(high - low, 1e-3), 0, 1)
    return (inter * ramp + extra * (1 - ramp)).astype(np.float32)


def yarn_inv_freq(cfg: LatentMoEConfig) -> np.ndarray:
    return yarn_frequencies(cfg.qk_rope_head_dim, cfg.rope_theta,
                            cfg.rope_factor, cfg.rope_original_positions,
                            cfg.rope_beta_fast, cfg.rope_beta_slow)


def softmax_scale(cfg: LatentMoEConfig) -> float:
    m = 0.1 * cfg.rope_mscale_all_dim * math.log(cfg.rope_factor) + 1.0
    return (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim) ** -0.5 * m * m


def _rope(x, cos, sin):
    """Rotate-half pairing: (x[i], x[i + d/2]) turn together."""
    a, b = jnp.split(x.astype(jnp.float32), 2, -1)
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


ATTENTION_BLOCK = 8     # flows attended at a time: bounds the score tensor


def attend_xla(q_abs, q_rope, cache, slot, p0, scale: float):
    """Attention as XLA does it, the whole score tensor formed in blocks
    of ``ATTENTION_BLOCK`` flows: the path of every platform but the TPU,
    and what ``ops/flow_attention.latent_attention_fused`` is tested
    against. ``q_abs [F, T, H, rank]``, ``q_rope [F, T, H, rope]``
    bfloat16; ``cache [slots, positions, rank + rope]`` the layer's,
    whole, of which flow ``f`` attends over slot ``slot[f]`` (clipped into
    range): **this path gathers the flows' slots itself**, a copy of ``F``
    slots (the kernel reads them where they lie); event ``t`` of flow
    ``f`` sees positions ``0 .. p0[f] + t``. Returns ``(o [F, T, H,
    rank]`` bfloat16, the blocks of positions attended over ``[F]``, the
    blocks of a whole slot)``: here every slot is attended whole, as one
    block."""
    F, T, H, rank = q_abs.shape
    kv = cache[jnp.minimum(slot, cache.shape[0] - 1)]       # [F, P, entry]
    P = kv.shape[1]

    def attend(block):
        qk, kv, pos = block
        s = jnp.einsum("fthk,fpk->fhtp", qk, kv,
                       preferred_element_type=jnp.float32) * scale
        seen = jnp.arange(P)[None, None] <= pos[:, :, None]
        p = jax.nn.softmax(jnp.where(seen[:, None], s, -jnp.inf), -1)
        return jnp.einsum("fhtp,fpc->fhtc", p.astype(jnp.bfloat16),
                          kv[..., :rank], preferred_element_type=jnp.float32
                          ).astype(jnp.bfloat16)

    nb = min(ATTENTION_BLOCK, F)
    o = jax.lax.map(attend, jax.tree_util.tree_map(
        lambda a: a.reshape(F // nb, nb, *a.shape[1:]),
        (jnp.concatenate([q_abs, q_rope], -1), kv,
         p0[:, None] + jnp.arange(T)[None])))
    return (o.reshape(F, H, T, rank).transpose(0, 2, 1, 3),
            jnp.ones((F,), jnp.int32), 1)


def append_chunk(cache, entry, start_entry, slot, p0, count, begins,
                 positions_last: bool = False, ring: bool = False):
    """The call's entries into the layer's ``cache [slots, positions,
    entry]`` where they belong, and no other row touched: flow ``f``'s
    ``entry[f, t]`` for ``t < count[f]`` at ``(slot[f], p0[f] + t)``, and
    ``start_entry`` at ``(slot[f], 0)`` where the flow ``begins``. A
    window of ``W = T + 1`` positions that holds them all (from ``p0 - 1``
    on, pushed back where it would pass the slot's end) is read from the
    slot, the entries are set into it and the window is written back over
    itself, so what the window holds besides is bit for bit what it was;
    with a donated cache that is ``F`` small updates in place (XLA makes
    a loop of ``F`` reads and one of ``F`` writes of it, each an
    unaligned window: 0.63 ms a layer of the Kimi cell, 1.1-2.25 of the
    Laguna cell's; a scatter of single rows makes XLA relayout the whole
    layer). **This is the append of every platform but the TPU**, and
    what ``ops/cache_append.cache_append_fused`` (the TPU's: whole lane
    tiles by a Pallas kernel, both of a ring's windows in one pass) is
    tested against and hands the shapes it does not serve. A ``slot`` out
    of range (a flow of the layout that brings nothing) reads clipped and
    writes nothing. ``positions_last``: the
    cache lies ``[slots, entry, positions]`` (as a kernel reads it where
    the compiler would not store it so of itself), and a window is
    ``[entry, W]`` of it. ``ring``: a slot is a ring of its positions,
    position ``p`` at ``p mod positions``: a chunk that passes the
    ring's end goes on at its start, by a second window there (read,
    set and written back likewise, for the flows whose chunk wraps).
    Returns the cache, the rows written (``W`` a window of a slot in
    range) and the flows a kernel appended: none (``Call.append``'s
    result)."""
    S, P, E = cache.shape
    if positions_last:
        P, E = E, P
    F, T, _ = entry.shape
    W = min(T + 1, P)

    def read(s, w):
        if positions_last:
            return jax.lax.dynamic_slice(cache, (s, 0, w), (1, E, W))[0].T
        return jax.lax.dynamic_slice(cache, (s, w, 0), (1, W, E))[0]

    def put(cache, slot, w0):
        pos = w0[:, None] + jnp.arange(W)[None]             # [F, W]
        t = (pos - p0[:, None]) % P if ring else pos - p0[:, None]
        window = jax.vmap(read)(jnp.minimum(slot, S - 1), w0)   # [F, W, E]
        mine = (t >= 0) & (t < count[:, None])
        window = jnp.where(mine[..., None], jnp.take_along_axis(
            entry, jnp.clip(t, 0, T - 1)[..., None], 1), window)
        window = jnp.where((begins[:, None] & (pos == 0))[..., None],
                           start_entry[None, None], window)
        return jax.lax.scatter(
            cache, jnp.stack([slot, w0], -1),
            window.transpose(0, 2, 1) if positions_last else window,
            jax.lax.ScatterDimensionNumbers(
                update_window_dims=(1, 2), inserted_window_dims=(0,),
                scatter_dims_to_operand_dims=(0, 2 if positions_last else 1)),
            mode=jax.lax.GatherScatterMode.FILL_OR_DROP)

    if not ring:
        return (put(cache, slot, jnp.clip(p0 - 1, 0, P - W)),
                (slot < S).sum() * W, jnp.int32(0))
    at = (p0 - 1) % P                       # the start token's, or the
    cache = put(cache, slot, jnp.minimum(at, P - W))    # position before
    wraps = jnp.where(at + W > P, slot, S)
    return (put(cache, wraps, jnp.zeros_like(at)),
            ((slot < S).sum() + (wraps < S).sum()) * W, jnp.int32(0))


def angles(pos, inv_freq):
    """``(cos, sin) [F, T, dim / 2]`` of the events' positions."""
    angle = pos[..., None].astype(jnp.float32) * jnp.asarray(inv_freq)
    return jnp.cos(angle), jnp.sin(angle)


def _attention(lp, cfg, cache, start_entry, h, call):
    """The latent attention as an ``Operator.apply``: ``h [F, T, hidden]``
    the residual stream, ``cache [slots, positions, entry]``
    this layer's, donated. The chunk's ``count`` entries are appended to
    the cache in place *before* the layer attends (``call.append``: at
    ``(slot, p0 + t)``, and the start token's at position 0 where the
    flow ``begins``; a ``slot`` out of range writes nothing), then the
    chunk attends causally over its flow's slot by ``call.attend``
    (``attend_xla``'s signature), which takes the appended cache whole
    and the slots' numbers: **no slot is gathered, merged and written
    back here** (PRs 28-30 did: 604 MB of a layer sliced and copied to
    read 75 MB and write 4.7). Returns the output, the cache, and the
    blocks of positions attended over, those of the slots whole, the
    rows of the cache written and those of the touched slots whole
    (``Operator``'s counts), summed over the flows."""
    F, T, _ = h.shape
    H, nope, rope = (cfg.num_attention_heads, cfg.qk_nope_head_dim,
                     cfg.qk_rope_head_dim)
    rank, eps = cfg.kv_lora_rank, cfg.rms_norm_eps
    with jax.named_scope("project"):
        x = _rms(h, lp["attn_norm"], eps)
        cos, sin = angles(call.pos, yarn_inv_freq(cfg))
        q = _mm(_rms(_mm(x, lp["wdq"]), lp["q_norm"], eps), lp["wuq"]
                ).reshape(F, T, H, nope + rope)
        q_rope = _rope(q[..., nope:], cos[:, :, None], sin[:, :, None])
        ckr = _mm(x, lp["wdkv"])
        entry = jnp.concatenate(
            [_rms(ckr[..., :rank], lp["kv_norm"], eps),
             _rope(ckr[..., rank:], cos, sin)], -1).astype(jnp.bfloat16)
    with jax.named_scope("append"):
        cache, written, in_kernel = call.append(
            cache, entry, start_entry, call.slot, call.p0, call.count,
            call.begins)
    with jax.named_scope("project"):      # the absorption of ``wukv`` into q
        wukv = lp["wukv"].reshape(rank, H, nope + cfg.v_head_dim)
        q_abs = jnp.einsum("fthd,chd->fthc",
                           q[..., :nope].astype(jnp.bfloat16),
                           wukv[..., :nope],
                           preferred_element_type=jnp.float32)
        q_abs, q_rope = q_abs.astype(jnp.bfloat16), q_rope.astype(jnp.bfloat16)
    with jax.named_scope("attend"):
        o, blocks, whole = call.attend(q_abs, q_rope, cache, call.slot,
                                       call.p0, softmax_scale(cfg))
    with jax.named_scope("out"):
        o = jnp.einsum("fthc,chd->fthd", o, wukv[..., nope:],
                       preferred_element_type=jnp.float32)
        y = _mm(o.reshape(F, T, H * cfg.v_head_dim), lp["wo"])
    with jax.named_scope("attend"):
        live = (call.slot < cfg.slots).sum()
        return (y, cache,
                {"attn.kv_blocks": blocks.sum(),
                 "attn.kv_blocks_whole": F * whole,
                 "cache.rows_written": written,
                 "cache.rows_whole": live * cfg.positions,
                 "append.flows": live,
                 "append.flows_in_kernel": in_kernel})


LATENT_ATTENTION = Operator(
    apply=_attention,
    init=lambda cfg: jnp.zeros((cfg.slots, cfg.positions, cfg.entry_width),
                               jnp.bfloat16),
    start_of=lambda cache: cache[0, 0], scope="attention", caches=True,
    parts=True)


def route(lp, cfg, x):
    """``x [N, hidden]`` float32 (already normed) -> the selected experts
    ``[N, k]`` and their weights: float32 throughout, the matmul's inputs
    being the bfloat16 values the experts see."""
    xr = x.astype(jnp.bfloat16).astype(jnp.float32)
    s = jax.nn.sigmoid(jnp.dot(xr, lp["router"].astype(jnp.float32),
                               precision=jax.lax.Precision.HIGHEST))
    # the selection's bias where the layer has one; the weights never
    biased = (s + lp["router_bias"].astype(jnp.float32)
              if "router_bias" in lp else s)
    _, idx = jax.lax.top_k(biased, cfg.num_experts_per_tok)
    sel = jnp.take_along_axis(s, idx, -1)
    return idx, (sel / (sel.sum(-1, keepdims=True) + cfg.route_eps)
                 * cfg.routed_scaling_factor)


CHUNK_BYTES = 192 * 2 ** 20  # the float32 rows a run of tiles gives back


def swiglu_tiles_xla(xs, wt, tile_expert, live, gate, up, down, limit=None):
    """The grouped product as XLA does it, a loop over the tiles that
    slices the tile's expert out of ``gate``, ``up [G, D, I]`` and ``down
    [G, I, D]`` every trip: the path of every platform but the TPU, and
    what ``ops/expert_product.swiglu_tiles_fused`` is tested against.
    ``xs [tiles x M, D]`` bfloat16 the tiles' rows, ``wt [tiles x M]``
    their routing weights, ``tile_expert [tiles]`` each tile's held
    expert, ``live`` how many tiles, the first, hold a pair; ``limit``:
    ``_swiglu``'s. Returns ``(y
    [tiles x M, D]`` float32 ``= wt * swiglu(xs)`` for the rows of the
    first ``live`` tiles (the others zero here, unspecified on the
    kernel), the whole-expert equivalents of weights read``: one a tile
    here)``."""
    M = xs.shape[0] // tile_expert.shape[0]

    def tile(t, y):
        e = tile_expert[t]
        w = jax.lax.dynamic_slice_in_dim(wt, t * M, M)
        return jax.lax.dynamic_update_slice_in_dim(
            y, _swiglu(jax.lax.dynamic_slice_in_dim(xs, t * M, M),
                       gate[e], up[e], down[e], limit) * w[:, None], t * M, 0)

    return (jax.lax.fori_loop(0, live, tile,
                              jnp.zeros(xs.shape, jnp.float32)),
            jnp.asarray(live, jnp.int32))


def add_rows_xla(y, tok, live, out):
    """``out [N, D]`` with the rows of ``y``'s first ``live`` tiles added
    to their tokens' rows, as XLA does it: one scatter-add (``tok [tiles,
    M]``; ``N``: no one's, dropped). What
    ``ops/expert_product.add_rows_fused`` is tested against."""
    mine = (jnp.arange(tok.shape[0]) < live)[:, None]
    return out.at[jnp.where(mine, tok, out.shape[0]).reshape(-1)].add(
        y, mode="drop")


class ExpertOps(NamedTuple):
    """How the routed experts' sorted rows are multiplied and brought
    back to their tokens: ``product`` (``swiglu_tiles_xla``'s signature)
    and ``combine`` (``add_rows_xla``'s). The XLA forms unless
    ``ops/expert_product.best_expert_product`` chose for a platform."""
    product: Callable = swiglu_tiles_xla
    combine: Callable = add_rows_xla


def routed_experts(lp, cfg, x, valid, experts=ExpertOps(), base=None):
    """The held experts' part of the routed sum for ``x [N, hidden]``,
    added to ``base [N, hidden]`` float32 (nought where None: the layer
    hands in what the sum is added to, the residual stream and the shared
    expert's output, so that no array of zeros is made and no pass adds
    two arrays afterwards): ``(out [N, hidden] float32, tokens per held
    expert [G], whole-expert equivalents of weights the product brought
    to the chip)``. Tokens not ``valid`` (padding) are routed nowhere.
    The held pairs are sorted by
    expert and placed in tiles of ``expert_tile`` rows of one expert (a
    pair's row: ``dest``); ``R`` rows hold them at worst, and the tiles
    that hold a pair are the first ``tile_end[-1]``. Those go through
    ``experts`` (``ExpertOps``) in runs of ``C``
    consecutive tiles, as many runs as hold a pair, one as a rule: a
    run's rows are gathered from ``x``, multiplied (``experts.product``)
    and added to their tokens' rows of ``out`` (``experts.combine``),
    **so what moves follows the tiles that hold a pair and not ``R`` or
    ``N x k``** (where 12 of 384 experts are held ``R`` is 34,304 rows
    for some 1,000 pairs). ``C`` is as many tiles as give back
    ``CHUNK_BYTES`` of float32 rows. Where the configuration has a
    ``swiglu_limit``, ``experts.product`` is handed it as ``limit``."""
    N, D = x.shape
    limit = getattr(cfg, "swiglu_limit", None)
    clamp = {} if limit is None else {"limit": limit}
    lo, hi = cfg.experts_held
    G, k, M = hi - lo, cfg.num_experts_per_tok, cfg.expert_tile
    with jax.named_scope("route"):     # and the sort into tiles
        idx, w = route(lp, cfg, x)
        local = (idx >= lo) & (idx < hi) & valid[:, None]
        g = jnp.where(local, idx - lo, G).reshape(-1)           # [N * k]
        order = jnp.argsort(g, stable=True)
        g_sorted = g[order]
        cnt = (g[:, None] == jnp.arange(G)[None]).sum(0).astype(jnp.int32)
        tiles = (cnt + M - 1) // M
        tile_end = jnp.cumsum(tiles)
        # a group's rows start at a tile's edge; a pair's place is its group's
        # start plus its rank among the group's pairs
        ge = jnp.minimum(g_sorted, G - 1)
        rank = jnp.arange(N * k) - (jnp.cumsum(cnt) - cnt)[ge]
        most = N * min(k, G) // M + G                       # tiles at most
        C = min(most, max(1, CHUNK_BYTES // (M * D * 4)))
        R = -(-most // C) * C * M                           # rows: whole runs
        dest = jnp.where(g_sorted < G, (tile_end - tiles)[ge] * M + rank, R)
        dest_tok = jnp.full((R,), N, jnp.int32).at[dest].set(
            (order // k).astype(jnp.int32), mode="drop")
        dest_w = jnp.zeros((R,), jnp.float32).at[dest].set(
            w.reshape(-1)[order], mode="drop")
        tile_expert = jnp.minimum(
            (jnp.arange(R // M)[:, None] >= tile_end[None]).sum(1), G - 1
        ).astype(jnp.int32)
        x_pad = jnp.concatenate(
            [x.astype(jnp.bfloat16), jnp.zeros((1, D), jnp.bfloat16)])

    def run(c, carry):
        out, loads = carry
        rows = jax.lax.dynamic_slice_in_dim(dest_tok, c * C * M, C * M)
        live = jnp.minimum(tile_end[-1] - c * C, C)
        y, n = experts.product(
            x_pad[rows],
            jax.lax.dynamic_slice_in_dim(dest_w, c * C * M, C * M),
            jax.lax.dynamic_slice_in_dim(tile_expert, c * C, C), live,
            lp["exp_gate"], lp["exp_up"], lp["exp_down"], **clamp)
        return experts.combine(y, rows.reshape(C, M), live, out), loads + n

    with jax.named_scope("expert_tiles"):
        out, loads = jax.lax.fori_loop(
            0, (tile_end[-1] + C - 1) // C, run,
            (jnp.zeros((N, D), jnp.float32) if base is None else base,
             jnp.int32(0)))
    return out, cnt, loads


def _forward(params, cfg, operators, kept, starts, tok, call):
    """``tok [F, T]``; ``call``: the flows' slots, positions and counts;
    ``kept``: each layer's state (``init_state``); ``starts``: the start
    token's, a layer, set where a flow begins. A layer is its operator
    (``operators[l]``: a kind of attention over a cache, or one with
    state of a fixed size) and its feed-forward (dense where the layer
    has no router; else the routed experts, beside a shared one where
    the layer has one), both residual.
    Returns the final normed hidden ``[F, T, hidden]`` float32, the
    layers' state with the chunks applied, tokens per held expert
    ``[expert layers, G]``, the operators' counts (``Operator``), summed
    over flows and layers, and the expert layers' ``weight_loads``
    (``routed_experts``), summed.

    Operators that carry (``Operator.carries``) are handed what the one
    before them handed on. **Where the configuration has ``hc_mult``
    streams** (more than one; ``models/hy4_moe.py``) the residual is ``X``,
    ``streams`` arrays ``[F, T, hidden]`` float32 (the embedding in every
    stream; arrays of their own, so that no pass relays a stream out of a
    wider array), and
    each of a layer's two sublayers is wrapped by a hyper-connection:
    its input is the streams mixed by ``hyper_in``, and ``hyper_out``
    mixes the streams and adds its output to each (scope ``hyper``); the
    final hidden is the norm of the streams' sum. Every SwiGLU takes the
    configuration's ``swiglu_limit`` where it has one. Both are read at
    trace time: a configuration with neither traces as it did."""
    F, T = tok.shape
    streams = getattr(cfg, "hc_mult", 1)
    limit = getattr(cfg, "swiglu_limit", None)
    h = params["embed"][tok].astype(jnp.float32)
    if streams > 1:
        h = (h,) * streams
    valid = jnp.arange(T)[None] < call.count[:, None]
    counts, kept, tally = [], list(kept), {}
    loads = jnp.int32(0)
    carry = None
    for l, (lp, op) in enumerate(zip(params["layers"], operators)):
        with jax.named_scope(f"layer{l}.{op.scope}"):
            u, mix = (hyper_in(lp, "hc_attn", cfg, h) if streams > 1
                      else (h, None))
            if op.carries:
                a, kept[l], layer_tally, carry = op.apply(
                    lp, cfg, kept[l], starts[l], u, call, carry)
            else:
                a, kept[l], layer_tally = op.apply(lp, cfg, kept[l],
                                                   starts[l], u, call)
            if streams > 1:
                h = hyper_out(h, a, mix)
            else:
                with (jax.named_scope("out") if op.parts
                      else contextlib.nullcontext()):
                    h = h + a
        for name, v in layer_tally.items():
            tally[name] = tally.get(name, 0) + v
        with jax.named_scope(f"layer{l}.ffn"):
            u, mix = (hyper_in(lp, "hc_ffn", cfg, h) if streams > 1
                      else (h, None))
            routed = "router" in lp
            with jax.named_scope("route" if routed else "dense"):
                x = _rms(u, lp["ffn_norm"], cfg.rms_norm_eps)
            if routed:
                with jax.named_scope("dense"):
                    flat = x.reshape(F * T, -1)
                    # the routed rows are added where the sum lies: the
                    # residual stream, or with several streams the shared
                    # expert's output alone (nought where there is none)
                    base = None if streams > 1 else h.reshape(F * T, -1)
                    if "shared_gate" in lp:
                        shared = _swiglu(flat, lp["shared_gate"],
                                         lp["shared_up"], lp["shared_down"],
                                         limit)
                        base = shared if base is None else base + shared
                with jax.named_scope("route"):
                    mine = valid.reshape(-1)
                y, cnt, loaded = routed_experts(
                    lp, cfg, flat, mine, call.experts, base)
                counts.append(cnt)
                with jax.named_scope("expert_tiles"):
                    loads = loads + loaded
                    y = y.reshape(F, T, -1)
                h = y if streams == 1 else hyper_out(h, y, mix)
            else:
                with jax.named_scope("dense"):
                    y = _swiglu(x, lp["w_gate"], lp["w_up"], lp["w_down"],
                                limit)
                    if streams == 1:
                        h = h + y
                if streams > 1:
                    h = hyper_out(h, y, mix)
    G = cfg.experts_held[1] - cfg.experts_held[0]
    counts = (jnp.stack(counts) if counts else jnp.zeros((0, G), jnp.int32))
    if streams > 1:
        h = sum(h)
    return (_rms(h, params["final_norm"], cfg.rms_norm_eps), tuple(kept),
            counts, tally, loads)


def sinkhorn(m, iterations: int, eps: float):
    """``m [..., n, n]`` positive, its rows and then its columns divided by
    their sums (plus ``eps``), ``iterations`` times: doubly stochastic."""
    for _ in range(iterations):
        m = m / (m.sum(-1, keepdims=True) + eps)
        m = m / (m.sum(-2, keepdims=True) + eps)
    return m


def hyper_coefficients(lp, name: str, cfg, X):
    """A hyper-connection's coefficients for the streams ``X`` (``n``
    arrays ``[F, T, hidden]`` float32; mHC's form): ``x = RMSNorm(vec X)``
    (no gain), ``a = alpha * (x phi) + bias`` with ``phi [n x hidden, n
    (n + 2)]`` (``<name>_phi``: stream ``i``'s rows ``i x hidden ..``) and
    ``alpha`` three scalars, one for each group of ``a``
    (``<name>_alpha``); ``pre = sigmoid(a[:n])``, ``post = hc_magnitude x
    sigmoid(a[n:2n])``, ``res = sinkhorn(exp(a[2n:]) as [n, n])``.
    Returns ``(pre [F, T, n], post [F, T, n], res [F, T, n, n])``."""
    n, C = len(X), X[0].shape[-1]
    scale = jax.lax.rsqrt(sum(jnp.mean(x * x, -1, keepdims=True) for x in X)
                          / n + cfg.rms_norm_eps)
    phi = lp[name + "_phi"]
    alpha = jnp.repeat(lp[name + "_alpha"].astype(jnp.float32),
                       np.array([n, n, n * n]),
                       total_repeat_length=n * (n + 2))
    a = sum(_mm(x * scale, phi[i * C:(i + 1) * C]) for i, x in enumerate(X)
            ) * alpha + lp[name + "_bias"].astype(jnp.float32)
    return (jax.nn.sigmoid(a[..., :n]),
            cfg.hc_magnitude * jax.nn.sigmoid(a[..., n:2 * n]),
            sinkhorn(jnp.exp(a[..., 2 * n:].reshape(*a.shape[:-1], n, n)),
                     cfg.hc_sinkhorn_iterations, cfg.hc_eps))


def hyper_in(lp, name: str, cfg, X):
    """A sublayer's input ``u = sum_i pre[i] X_i [F, T, hidden]``, and what
    ``hyper_out`` needs: ``(post, res)``. In float32, element by element
    (a product on the MXU would round the streams to bfloat16)."""
    with jax.named_scope("hyper"):
        pre, post, res = hyper_coefficients(lp, name, cfg, X)
        return (sum(pre[..., i, None] * x for i, x in enumerate(X)),
                (post, res))


def hyper_out(X, y, mix):
    """The streams after a sublayer whose output is ``y [F, T, hidden]``:
    ``X'_i = sum_j res[i, j] X_j + post[i] y``."""
    post, res = mix
    with jax.named_scope("hyper"):
        return tuple(sum(res[..., i, j, None] * x for j, x in enumerate(X))
                     + post[..., i, None] * y for i in range(len(X)))


HEAD_LOGITS_BYTES = 384 * 2 ** 20   # a block of float32 logits, at most


def event_scores(params, cfg, pred, tok):
    """``pred [F, T, hidden]`` float32, the hidden state that predicts
    each event, ``tok [F, T]`` the events -> their scores ``[F, T]``: ``1 -
    exp(-nll / ln vocab)`` under the logits of ``pred``, through the head,
    or the embedding where the two are tied (no ``head`` among the
    parameters). Where the call's logits pass ``HEAD_LOGITS_BYTES`` (4,096
    events over a vocabulary of 65,536 are 1 GiB of float32) they are
    formed in blocks of rows, one after the other. Where the
    configuration's ``head_fp32`` is set the product is float32 from
    float32 operands (``Precision.HIGHEST``), not bfloat16's."""
    N, vocab = tok.size, cfg.vocab_slice
    rows = N
    while rows * vocab * 4 > HEAD_LOGITS_BYTES and rows % 2 == 0:
        rows //= 2
    wide = getattr(cfg, "head_fp32", False)
    if wide:
        head = (params["head"] if "head" in params
                else params["embed"].T).astype(jnp.float32)

    def block(args):
        x, ids = args
        if wide:
            logits = jnp.dot(x.astype(jnp.float32), head,
                             precision=jax.lax.Precision.HIGHEST)
        else:
            logits = _mm(x, params["head"] if "head" in params
                         else params["embed"].T)
        nll = (jax.nn.logsumexp(logits, -1)
               - jnp.take_along_axis(logits, ids[..., None], -1)[..., 0])
        return 1.0 - jnp.exp(-nll / math.log(vocab))

    if rows == N:
        return block((pred, tok))
    return jax.lax.map(block, (pred.reshape(N // rows, rows, -1),
                               tok.reshape(N // rows, rows))
                       ).reshape(tok.shape)


def flow_step(params, state, rows, n, *, cfg, F: int, T: int,
              attend=attend_xla, experts=ExpertOps(), append=append_chunk):
    """One call, of this model or of any whose configuration gives its
    layers' operators (``cfg.operator``, ``cfg.tensors``: here and
    ``models/lfm2_moe.py``). ``rows [B, 3]`` int32 ``(cell, address,
    id)``; rows at and past ``n`` are padding; ``attend``: the attention
    over a slot that the model's attention layers call (``attend_xla``,
    or the kernel ``ops/flow_attention.best_attention`` gives for a TPU);
    ``experts``: the routed experts' grouped product and combine (XLA's,
    or the kernels ``ops/expert_product.best_expert_product`` gives);
    ``append``: how the attention layers write a chunk into their state
    (``append_chunk``, or the kernel ``ops/cache_append.best_append``
    gives).
    Returns ``(scores [B] float32 in row order, state, counts)``. Its
    device scopes name the parts its time is read by (PERF.md section 3):
    ``layer<l>.<operator's scope>`` (an attention operator's ``project``,
    ``append``, ``attend`` and ``out`` inside), ``layer<l>.ffn``
    (``route``, ``dense``, ``expert_tiles``) and ``head``; the rest (the
    embedding, the state's bookkeeping) is in none."""
    kept, length, last_h, (starts, start_h) = state
    S, P = cfg.slots, cfg.positions
    B = rows.shape[0]
    live = jnp.arange(B) < n
    cell = jnp.where(live, rows[:, 0], F * T)
    tok = jnp.zeros((F * T,), jnp.int32).at[cell].set(
        rows[:, 2], mode="drop").reshape(F, T)
    addr = jnp.full((F * T,), -1, jnp.int32).at[cell].set(
        rows[:, 1], mode="drop").reshape(F, T)
    count = (addr >= 0).sum(1)              # a chunk fills from t = 0
    flow = count > 0
    slot = jnp.where(flow, addr[:, 0] // P, S)
    p0 = jnp.where(flow, addr[:, 0] % P, 1)
    begins = flow & (p0 == 1)
    prev_h = jnp.where(begins[:, None], start_h[None],
                       last_h[jnp.minimum(slot, S - 1)])
    call = Call(slot, p0, count, begins, p0[:, None] + jnp.arange(T)[None],
                attend, experts, append)
    operators = [cfg.operator(l) for l in range(cfg.layers)]
    h, kept, expert_tokens, tally, loads = _forward(
        params, cfg, operators, kept, starts, tok, call)
    with jax.named_scope("head"):
        pred = jnp.concatenate(
            [prev_h[:, None].astype(jnp.float32), h[:, :-1]], 1)
        score = event_scores(params, cfg, pred, tok)
    newest = jnp.take_along_axis(
        h, jnp.maximum(count - 1, 0)[:, None, None], 1)[:, 0]
    last_h = last_h.at[slot].set(newest.astype(jnp.bfloat16), mode="drop")
    length = length.at[slot].set((p0 + count).astype(jnp.int32), mode="drop")
    # the call that makes the start token's constants (``with_start``): its
    # one event is id 0, which no flow's event is, at position 0 of slot 0,
    # where it has attended over itself alone; what it left there is kept
    # and the slot is counted empty again
    making = (n == 1) & (rows[0, 2] == 0)
    starts = tuple(jnp.where(making, op.start_of(k), s)
                   for op, k, s in zip(operators, kept, starts))
    start_h = jnp.where(making, last_h[0], start_h)
    length = jnp.where(making, 0, length)
    scores = jnp.where(live, score.reshape(-1)[jnp.minimum(cell, F * T - 1)],
                       0.0)
    M = cfg.expert_tile
    counts = {"moe.local_pairs": expert_tokens.sum(),
              "moe.max_expert_tokens": expert_tokens.max(initial=0),
              "moe.tiles": ((expert_tokens + M - 1) // M).sum(),
              "moe.weight_loads": loads,
              "cache.positions": length.sum(),
              **{name: tally.get(name, jnp.int32(0))
                 for name in OPERATOR_COUNTS},
              **tally,
              "expert_tokens": expert_tokens}
    return scores, (kept, length, last_h, (starts, start_h)), counts
