"""A fault for the tests and the chip readings, never for a run of the
benchmark: query heads paired with the wrong key/value head: the query
heads in reversed order (and the output projection's rows with them, so
each head's own weights stay together), so the head that belongs to
key/value head ``i // 4`` attends over key/value head ``(31 - i) // 4``."""

from chipbench.entries import lfm2_scorer as base
from chipbench.entries.lfm2_scorer import *  # noqa: F401,F403


def build(config, seed):
    s = base.build(config, seed)
    heads = config["num_attention_heads"]
    for lp in s.params["layers"]:
        if "wq" in lp:
            d = lp["wq"].shape[0]
            lp["wq"] = lp["wq"].reshape(d, heads, -1)[:, ::-1].reshape(d, d)
            lp["wo"] = lp["wo"].reshape(heads, -1, d)[::-1].reshape(d, d)
    return s
