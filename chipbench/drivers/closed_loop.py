"""Closed-loop drain: ``outstanding`` clients, each taking the next batch
of the pool the instant its slot frees, in the cadence of the telemeter's
``_line_rate_loop`` / ``_score_and_publish`` (the benchmark's own copy):
a task scores its batch; every ``fit_every``-th scored batch is then
fitted on the same rows under one lock before the task's slot frees.

A call is due when its slot is free; its latency runs from there to its
scores readable on the host. When the window's seconds are up nothing
more is sent; the calls then outstanding (and a fit one of them is due)
are waited for, timed and counted, and the clock is read after that wait
(``t_end``): the rate is all of the rows over all of that time.

The event-loop thread is the program's own bottleneck (it copies every
batch into the dispatch ring's staging buffer and launches it), so the
harness does little on it: it takes the time, looks at every output's
shape and sum (finite exactly where every score is), and keeps the whole
output of the calls drawn for the comparison.

What the comparison can hold a call to is the state that call met. The
reference follows the window's first ``follow_fits`` fits from the seed;
past them rounding has carried its parameters away from the program's, so
one in so many of the later fits, from an offset drawn from the seed
(``anchor``), is taken between two snapshots of the program (through the entry, on a
thread, under the fit's lock: as the lifecycle manager checkpoints). Calls
that began inside the followed horizon, during an anchored fit, or after
one and before the next fit, met a state the comparison knows: only their
outputs are kept."""

from __future__ import annotations

import asyncio
import time

import numpy as np


async def run(entry, scorer, pool, *, seconds: float, outstanding: int,
              fit_every: int, keep, follow_fits: int = 0, anchor=(),
              on_start=None) -> dict:
    """Drive the window. ``keep[i]`` says whether call ``i``'s whole output
    is kept for the comparison, where it met a known state (calls past the
    end of ``keep`` are not kept); ``anchor[j]`` whether fit ``j`` is taken
    between two snapshots. Returns ``{"calls", "fits", "t0", "t1",
    "t_end"}``, all times by ``time.monotonic()``."""
    calls, fits = [], []
    lock = asyncio.Lock()
    issued = scored = fits_started = fits_done = 0
    known_until = follow_fits   # the last count of fits done that is known
    anchoring = False           # an anchored fit is in flight
    n_pool, n_keep, n_anchor = len(pool), len(keep), len(anchor)
    t0 = time.monotonic()
    t_close = t0 + seconds
    if on_start is not None:
        on_start(t0)

    async def client() -> None:
        nonlocal issued, scored, fits_started, fits_done
        nonlocal known_until, anchoring
        due = t0
        while due < t_close:
            i, issued = issued, issued + 1
            x, labels, mask = pool[i % n_pool]
            call = {"i": i, "k": i % n_pool, "due": due, "ok": False,
                    "fits_started": fits_started, "fits_done": fits_done}
            calls.append(call)
            known = (fits_started < follow_fits or fits_done == known_until
                     if fits_started == fits_done else
                     fits_started <= follow_fits or anchoring)
            try:
                out = await entry.score(scorer, x)
            except Exception as e:  # noqa: BLE001 - a raised call is a failed call, and is counted
                call["done"] = time.monotonic()
                call["error"] = repr(e)
            else:
                call["done"] = time.monotonic()
                call["ok"] = bool(out.shape == (len(x),)
                                  and np.isfinite(out.sum()))
                if known and i < n_keep and keep[i]:
                    call["out"] = out
            scored += 1
            if (fit_every and scored % fit_every == 0
                    and "error" not in call and call["done"] < t_close):
                async with lock:
                    j = fits_started
                    f = {"j": j, "k": call["k"]}
                    fits.append(f)
                    anchored = j < n_anchor and bool(anchor[j])
                    if anchored:
                        f["before"] = await asyncio.to_thread(
                            entry.snapshot, scorer)
                    f["start"] = time.monotonic()
                    fits_started += 1
                    anchoring = anchored
                    try:
                        f["loss"] = await entry.fit(scorer, x, labels, mask)
                    except Exception as e:  # noqa: BLE001 - a raised fit is reported, not hidden
                        f["error"] = repr(e)
                    f["end"] = time.monotonic()
                    fits_done += 1
                    anchoring = False
                    if anchored:
                        # known from here on: calls that begin while the
                        # snapshot is taken meet the state it takes
                        known_until = fits_done
                        f["after"] = await asyncio.to_thread(
                            entry.snapshot, scorer)
            due = time.monotonic()

    await asyncio.gather(*(client() for _ in range(outstanding)))
    # the window closes here: nothing was sent past ``t_close``, all that
    # was sent has come back (its fits made), and the clock is read after
    return {"calls": calls, "fits": fits, "t0": t0, "t1": t_close,
            "t_end": time.monotonic()}
