"""A fault for the tests and the chip readings, never for a run of the
benchmark: query heads paired with the wrong key/value head: a layer's
query heads in reversed order (and the gate's columns and the output
projection's rows with them, so each head's own weights stay together),
so the head that belongs to key/value head ``i // (H / 8)`` attends over
key/value head ``(H - 1 - i) // (H / 8)``."""

from chipbench.entries import laguna_scorer as base
from chipbench.entries.laguna_scorer import *  # noqa: F401,F403


def build(config, seed):
    s = base.build(config, seed)
    for lp, heads in zip(s.params["layers"],
                         config["num_attention_heads_per_layer"]):
        d = lp["wq"].shape[0]
        lp["wq"] = lp["wq"].reshape(d, heads, -1)[:, ::-1].reshape(d, -1)
        lp["wg"] = lp["wg"][:, ::-1]
        lp["wo"] = lp["wo"].reshape(heads, -1, d)[::-1].reshape(-1, d)
    return s
