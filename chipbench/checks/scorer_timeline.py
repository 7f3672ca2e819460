"""The comparison that decides ``correct`` for a scorer cell.

The reference starts from the seed and follows, in its own float32
arithmetic, the set-up fits and the window's first ``follow_fits`` fits.
Further on its parameters and the program's part ways by rounding alone
(a fit of 1,024 rows averages little of bfloat16's noise out of a
gradient, and Adam carries it on), so the later part of the window is
held fit by fit: one in so many of the later fits, from an offset drawn
from the seed, lies between two snapshots of the program (``drivers/closed_loop.py``), and
the reference makes that one fit from the snapshot before it. Those
anchored fits are the one place where the reference starts from
something the program made; every number that stands on them says so
below.

The kept calls are scored under the state each met: ``score`` reads the
parameters and the statistics the instant it is called, so a call that
began between fits met a settled state, and one that began while a fit
was in flight met the parameters after any of that fit's steps (the
program repoints them step by step) and is held to the nearest of those.

Numbers (each compared against the cell's limit of the same name):

- ``init_gap``: widest gap between the program's fresh weights and the
  reference's own draw from the seed (exact).
- ``score_rms_ratio``: the root mean square, over all rows of the kept
  calls, of a returned score less the reference's, over the same of the
  reference's own scores with its matmuls' inputs rounded to the compute
  type the configuration states, less the reference's: how many times the
  stated precision's own error the program's is, whatever the seed's
  weights make of an error. Calls inside the followed horizon are held to
  the reference's own state, those beside an anchored fit to the
  snapshot's.
- ``score_median_gap``: the median, over the same rows, of the gap's size:
  what a typical row's score is off by.
- ``score_rms_gap``, ``score_call_gap``, ``score_gap``: the ratio's
  numerator by itself, the largest root mean square of one call's rows,
  and the widest single gap.
- ``fit_loss_gap``: widest relative gap between a fit's returned loss
  (its last step's) and the reference's, over the set-up fits, the
  followed fits and the anchored ones.
- ``param_change_gap`` / ``adam_m_gap``: after the set-up fits, by the
  worst leaf, the gap between the program's and the reference's norm of
  the parameters' change / of Adam's first moment (the gradients as the
  optimizer got them), against the reference's norm of that leaf or of
  the median leaf, whichever is larger. Leaves whose first gradient in
  the reference is under a thousandth of the median leaf's are left out.
- ``late_change_gap``: the same measure of the parameters' change over
  one anchored fit, the median of the anchored fits'
  (``late_change_worst``: the largest).
- ``window_compiles``, ``unexpected_shapes``, ``wrong_score_path``,
  ``failed_calls``: counts, each held to 0.
"""

from __future__ import annotations

import numpy as np


def _leaves(tree):
    return [np.asarray(tree[g][i][k], np.float64)
            for g in sorted(tree) for i in range(len(tree[g]))
            for k in sorted(tree[g][i])]


def _norm_gap(got, want, keep):
    """Worst kept leaf: | |got| - |want| | over max(|want|, median |want|)."""
    g = np.array([np.linalg.norm(a) for a in got])
    w = np.array([np.linalg.norm(a) for a in want])
    floor = np.median(w)
    gaps = np.abs(g - w) / np.maximum(np.maximum(w, floor), 1e-30)
    return float(np.max(gaps[keep]))


def _change(after, before):
    return [a - b for a, b in zip(after, before)]


def compare(run: dict) -> dict:
    import jax
    import jax.numpy as jnp
    ref = run["reference"]
    cfg, cell = run["config"], run["cell"]
    model, tel = cfg["model"], cfg["telemeter"]
    lr, steps = jnp.float32(tel["learningRate"]), int(tel["fitSteps"])
    rw, mom = tel["reconWeight"], tel["normMomentum"]
    stated = ref.PRECISION[model["compute_dtype"]]
    numbers: dict = {}

    params = ref.init(run["seed"], model)
    init_leaves = _leaves(params)
    numbers["init_gap"] = float(max(
        np.max(np.abs(a - b)) for a, b in
        zip(_leaves(run["snap_init"]["params"]), init_leaves)))

    def one_fit(params, opt, norm, rows):
        x, labels, mask = rows
        norm = ref.norm_update(norm, np.asarray(x), np.asarray(labels),
                               np.asarray(mask), mom)
        return (norm, *ref.fit_steps(params, opt, norm[0], norm[1], x, labels,
                                     mask, lr, steps))

    # -- the set-up fits, through the program's own fit() -----------------
    opt, norm = ref.adam_init(params), None
    got_loss, want_loss, first_grad = [], [], None
    for f in run["setup_fits"]:
        norm, params, opt, _, losses, g1 = one_fit(params, opt, norm,
                                                   f["rows"])
        first_grad = g1 if first_grad is None else first_grad
        got_loss.append(f["loss"])
        want_loss.append(losses[-1])
    keep = None
    if run["setup_fits"]:
        g = np.array([np.linalg.norm(a) for a in _leaves(first_grad)])
        keep = g >= 1e-3 * np.median(g)
        snap = run["snap_setup"]
        setup_leaves = _leaves(params)
        numbers["param_change_gap"] = _norm_gap(
            _change(_leaves(snap["params"]), init_leaves),
            _change(setup_leaves, init_leaves), keep)
        numbers["adam_m_gap"] = _norm_gap(
            _leaves(snap["m"]), _leaves(opt["m"]), keep)

    # -- the window: the first fits followed from the seed, the anchored
    # ones from the program's snapshot before them; the kept calls scored
    # under the state they met --------------------------------------------
    window = run["window"]
    pool = [tuple(jnp.asarray(a) for a in rows) for rows in run["pool"]]
    fits = window["fits"]
    follow = min(int(cell["check"]["follow_fits"]), len(fits))
    settled: dict = {}      # fits done -> kept calls that met that state
    flight: dict = {}       # fit in flight -> kept calls that began meanwhile
    for c in window["calls"]:
        if "out" in c and c["ok"]:
            (settled if c["fits_started"] == c["fits_done"]
             else flight).setdefault(c["fits_done"], []).append(c)
    gaps, row_gaps, late_change = [], [], []

    def score_gaps(calls, states):
        """For each call, under the nearest of ``states`` (the least root
        mean square of its output less the reference's scores): the sums of
        squares of that gap and of the gap that the reference's own scores
        show when its matmuls' inputs are rounded to the configuration's
        compute type, and the widest gap of a single score."""
        for c in calls:
            x, out = pool[c["k"]][0], jnp.asarray(c["out"])
            want = jnp.stack([ref.scores_on_device(p, n, x, rw)
                              for p, n in states])
            diffs = out - want
            sq = jnp.sum(jnp.square(diffs), axis=1)
            best = jnp.argmin(sq)
            rounded = jnp.stack([ref.scores_on_device(p, n, x, rw, stated)
                                 for p, n in states])[best]
            gaps.append(jnp.stack([
                sq[best], jnp.sum(jnp.square(rounded - want[best])),
                jnp.max(jnp.abs(diffs[best]))]))
            row_gaps.append(jnp.abs(diffs[best]))

    def fit_and_flight(j, params, opt, norm):
        """Fit ``j`` from the state given; the calls that began while the
        program made it are held to the states it passes through."""
        f = fits[j]
        norm_after, after, opt, stacked, losses, _ = one_fit(
            params, opt, norm, pool[f["k"]])
        if "loss" in f:
            got_loss.append(f["loss"])
            want_loss.append(losses[-1])
        if j in flight:
            steps_ = [jax.tree_util.tree_map(lambda a, i=i: a[i], stacked)
                      for i in range(steps)]
            score_gaps(flight[j], [(p, norm_after) for p in [params] + steps_])
        return after, opt, norm_after

    for j in range(follow):
        score_gaps(settled.get(j, ()), [(params, norm)])
        params, opt, norm = fit_and_flight(j, params, opt, norm)
    score_gaps(settled.get(follow, ()), [(params, norm)])
    anchored = [j for j in range(follow, len(fits)) if "after" in fits[j]]
    for j in anchored:
        before, after = fits[j]["before"], fits[j]["after"]
        p0 = jax.tree_util.tree_map(jnp.asarray, before["params"])
        o0 = {"m": jax.tree_util.tree_map(jnp.asarray, before["m"]),
              "v": jax.tree_util.tree_map(jnp.asarray, before["v"]),
              "t": jnp.int32(before["t"])}
        p1, _, _ = fit_and_flight(j, p0, o0, before["norm"])
        late_change.append(_norm_gap(
            _change(_leaves(after["params"]), _leaves(before["params"])),
            _change(_leaves(p1), _leaves(before["params"])), keep))
        score_gaps(settled.get(j + 1, ()),
                   [(jax.tree_util.tree_map(jnp.asarray, after["params"]),
                     after["norm"])])
    per_call = (np.asarray(jnp.stack(gaps), np.float64) if gaps
                else np.full((1, 3), np.inf))
    rows = run["rows_per_call"]
    numbers["score_rms_ratio"] = float(np.sqrt(
        np.sum(per_call[:, 0]) / max(np.sum(per_call[:, 1]), 1e-30)))
    numbers["score_rms_gap"] = float(np.sqrt(np.mean(per_call[:, 0]) / rows))
    numbers["score_call_gap"] = float(np.sqrt(np.max(per_call[:, 0]) / rows))
    numbers["score_gap"] = float(np.max(per_call[:, 2]))
    # on the host: the kept calls differ in number from run to run, and a
    # device program would compile for each
    numbers["score_median_gap"] = (
        float(np.median(np.concatenate([np.asarray(g) for g in row_gaps])))
        if row_gaps else float("inf"))
    loss_gaps = np.zeros(0)
    if want_loss:
        want = np.asarray(jnp.stack(want_loss), np.float64)
        loss_gaps = (np.abs(np.asarray(got_loss) - want)
                     / np.maximum(np.abs(want), 1e-30))
        numbers["fit_loss_gap"] = float(np.max(loss_gaps))
    if late_change:
        # the median fit's: late in a window single fits read ten times
        # their fellows (a leaf whose gradient has shrunk moves by Adam's
        # normalised rounding noise), a fault shows in every fit
        numbers["late_change_gap"] = float(np.median(late_change))
        numbers["late_change_worst"] = float(max(late_change))

    # -- what the program says it ran ------------------------------------
    state = run["entry_state"]
    expect = run["expected_shapes"]
    numbers["unexpected_shapes"] = float(
        len(set(state["score_batches"]) - set(expect["score"]))
        + len(set(state["fit_batches"]) - set(expect["fit"])))
    want_path = run["expected_score_path"]
    numbers["wrong_score_path"] = float(
        want_path is not None and state["score_path"] != want_path)
    numbers["window_compiles"] = float(run["window_compiles"])
    numbers["failed_calls"] = float(
        sum(not c["ok"] for c in window["calls"])
        + sum("error" in f for f in window["fits"]))
    info = {"calls_compared": len(gaps), "calls_in_window": len(window["calls"]),
            "fits_followed_from_seed": follow, "fits_anchored": len(anchored),
            "fits_in_window": len(fits),
            # in order: the set-up fits, the followed ones, the anchored
            "fit_loss_gaps": [float(g) for g in loss_gaps],
            "late_change_gaps": late_change}
    return {"numbers": numbers, "info": info}
