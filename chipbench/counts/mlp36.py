"""Operations and bytes of the 36-column scorer, from the configuration's
widths alone. The algorithm's counts, whatever implements it: a matmul
weight is one multiply and one add a row; biases, activations and the
z-score are not counted; a recomputed forward pass is not counted."""

from __future__ import annotations

from chipbench.reference.mlp36 import layer_dims


def weights(model: dict) -> int:
    """Matmul weights of the whole model (96,384 at the published widths)."""
    return sum(i * o for pairs in layer_dims(model).values() for i, o in pairs)


def score_flops_per_row(model: dict) -> int:
    """One forward pass: 2 FLOP a weight a row (192,768)."""
    return 2 * weights(model)


def train_flops_per_row(model: dict) -> int:
    """Forward and backward: three times the forward pass."""
    return 3 * score_flops_per_row(model)


def score_bytes_per_row(model: dict) -> int:
    """What the algorithm must move a row: the float32 feature row in and
    one float32 score out. Weights stay resident and are not counted."""
    return 4 * model["in_dim"] + 4
