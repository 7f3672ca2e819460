"""The one general generator of feature rows: reads a traffic mix
(``traffic/<name>.json``) and makes, from the seed alone and on the host,
the pool of batches a cell cycles through, their labels and label masks,
and the rows of the set-up fit.

A batch is ``rows_per_call`` rows, made in blocks of ``rows_per_block``
rows (a route's, a router's: what the mix says); every block shifts the
heavy-tailed columns by an offset of its own, so a batch mixes traffic as
a ring does. Columns are described in the mix by ``kind``; a column no
entry names stays zero.
"""

from __future__ import annotations

import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np


def _log1p_lognormal(rng, n, spec, shift):
    z = rng.standard_normal(n, dtype=np.float32) * np.float32(spec["sigma"])
    z += np.float32(spec["mu"])
    if spec.get("per_block", False):
        z += shift
    return np.log1p(np.exp(z, out=z), out=z)


def _fill(rng, x, spec, shift):
    n = len(x)
    cols = spec["cols"]
    kind = spec["kind"]
    if kind == "log1p_lognormal":
        for c in cols:
            x[:, c] = _log1p_lognormal(rng, n, spec, shift)
    elif kind == "normal":
        for c in cols:
            x[:, c] = rng.standard_normal(n, dtype=np.float32) * np.float32(
                spec["sigma"])
    elif kind == "bernoulli":
        for c in cols:
            x[:, c] = rng.random(n, dtype=np.float32) < spec["p"]
    elif kind == "const":
        x[:, cols] = np.float32(spec["value"])
    elif kind in ("onehot", "signed_onehot"):
        p = np.asarray(spec.get("p") or [1.0 / len(cols)] * len(cols))
        if len(p) != len(cols):
            raise ValueError(f"{kind}: {len(p)} shares for {len(cols)} columns")
        pick = np.searchsorted(np.cumsum(p) / p.sum(),
                               rng.random(n, dtype=np.float32))
        pick = np.minimum(pick, len(cols) - 1)
        value = np.ones(n, np.float32)
        if kind == "signed_onehot":
            value[rng.random(n, dtype=np.float32) < 0.5] = -1.0
        x[np.arange(n), np.asarray(cols)[pick]] = value
    else:
        raise ValueError(f"unknown column kind {kind!r}")


def _block(job) -> None:
    """Fill one block's rows, labels and label mask, from a generator of
    the block's own: the result does not depend on how many threads ran."""
    seq, mix, x, labels, mask = job
    rng = np.random.default_rng(seq)
    shift = np.float32(rng.standard_normal() * mix["block_spread"])
    for spec in mix["columns"]:
        _fill(rng, x, spec, shift)
    n = len(x)
    mask[:] = rng.random(n, dtype=np.float32) < mix["labelled_share"]
    labels[:] = (rng.random(n, dtype=np.float32)
                 < mix["anomalous_share"]) * mask


def _batch(pool, seq, mix: dict, n: int, width: int):
    x = np.zeros((n, width), np.float32)
    labels = np.zeros(n, np.float32)
    mask = np.zeros(n, np.float32)
    per = mix["rows_per_block"]
    starts = range(0, n, per)
    jobs = [(child, mix, x[a:a + per], labels[a:a + per], mask[a:a + per])
            for a, child in zip(starts, seq.spawn(len(starts)))]
    list(pool.map(_block, jobs))
    return x, labels, mask


def generate(mix: dict, rows_per_call: int, width: int, seed: int) -> dict:
    """``{"pool": [(x, labels, mask), ...], "setup": (x, labels, mask)}``.
    One block at a time (it stays in the cache), a few threads
    wide (NumPy's generators and ufuncs release the interpreter lock)."""
    seqs = np.random.SeedSequence(seed).spawn(mix["pool"] + 1)
    with ThreadPoolExecutor(max_workers=min(8, os.cpu_count() or 1)) as tp:
        setup = _batch(tp, seqs[0], mix, mix["setup_fit_rows"], width)
        pool = [_batch(tp, s, mix, rows_per_call, width)
                for s in seqs[1:]]
    return {"pool": pool, "setup": setup}
