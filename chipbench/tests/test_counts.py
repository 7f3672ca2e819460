"""The count functions, from the configuration's widths."""

import pytest

from chipbench import harness
from chipbench.counts import mlp36


@pytest.mark.parametrize("config", ["mlp36-frozen", "mlp36-online"])
def test_counts_at_published_widths(config):
    model = harness.load_json("configs", config + ".json")["model"]
    assert mlp36.weights(model) == 96_384 == model["weights"]
    assert mlp36.score_flops_per_row(model) == 192_768
    assert mlp36.train_flops_per_row(model) == 3 * 192_768
    assert mlp36.score_bytes_per_row(model) == 36 * 4 + 4


def test_counts_follow_the_widths():
    model = {"in_dim": 4, "enc_dims": [8], "bottleneck": 2, "cls_hidden": 3}
    # enc 4*8 + 8*2, dec mirrored, cls 2*3 + 3*1
    assert mlp36.weights(model) == 2 * (32 + 16) + 6 + 3
