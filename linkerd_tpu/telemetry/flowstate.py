"""The host's half of per-flow device state: which slot of the device's
cache holds which flow, how many positions each holds, and the layout of
one call's rows for the device step (``models/latent_moe.flow_step``).

A row handed to ``score`` is int32 ``(stream key, restart flag, event
id)``: the key is the 24-bit stream-lifetime key the engines put on a row
(``native/stream_track.h``; never 0), the id an event of the vocabulary's
slice (``[1, vocab)``: 0 is the start token, the program's own). The rows
of one key, in the order handed over, are that flow's next events: its
*chunk* of the call.

``map`` gives every key of the call its slot (a new key takes a free one,
else evicts the flow least recently touched, counted) and its chunk the
position it is appended at: 1 where the flow begins (a new key; a restart
flag on any of the chunk's rows, which takes effect at the chunk's first
row: a call is the unit of order between flows; or a flow that would pass
the slot's ``positions``, counted as a wrap), else the slot's length.
Position 0 of every flow is the start token. It returns the rows as the
step takes them, ``(cell, address, id)``: ``cell = f * T + t`` for the
``t``-th event of the call's ``f``-th flow, in a layout ``[F, T]`` of
powers of two, and ``address = slot * positions + position``.

``map`` moves the table forward at once, so the next call's ``map`` sees
this call applied: the dispatcher launches calls in the order it mapped
them, and the device applies them in that order. ``checkpoint`` /
``rollback`` put the table back where a call failed before its launch.
Single-threaded: the event loop's.
"""

from __future__ import annotations

from typing import Dict, NamedTuple, Tuple

import numpy as np


class Plan(NamedTuple):
    rows: np.ndarray        # [n, 3] int32 (cell, address, id)
    layout: Tuple[int, int]  # (F flows, T events a flow), powers of two
    counts: Dict[str, int]  # flow.* counts of this call


def _pow2(n: int) -> int:
    return 1 << max(0, int(n) - 1).bit_length()


class FlowTable:
    def __init__(self, slots: int, positions: int, vocab: int):
        self.slots, self.positions, self.vocab = slots, positions, vocab
        self.slot_of: Dict[int, int] = {}
        self.key_of = np.full(slots, -1, np.int64)
        self.length = np.zeros(slots, np.int64)     # start token included
        self.touched = np.zeros(slots, np.int64)    # the call that last did
        self.free = list(range(slots - 1, -1, -1))
        self.calls = 0
        self.layouts: Dict[Tuple[int, int], int] = {}   # calls a layout

    def checkpoint(self):
        return (dict(self.slot_of), self.key_of.copy(), self.length.copy(),
                self.touched.copy(), list(self.free), self.calls,
                dict(self.layouts))

    def rollback(self, saved) -> None:
        (self.slot_of, self.key_of, self.length, self.touched, self.free,
         self.calls, self.layouts) = saved

    def _take_slot(self, key: int, counts: Dict[str, int]) -> int:
        if self.free:
            slot = self.free.pop()
        else:
            # the least recently touched of the flows not in this call
            idle = np.flatnonzero(self.touched < self.calls)
            if not len(idle):
                raise ValueError(f"a call of more than {self.slots} flows")
            slot = int(idle[np.argmin(self.touched[idle])])
            del self.slot_of[int(self.key_of[slot])]
            counts["flow.evictions"] += 1
        self.slot_of[key] = slot
        self.key_of[slot] = key
        self.length[slot] = 0
        return slot

    def map(self, rows: np.ndarray) -> Plan:
        rows = np.asarray(rows)
        if rows.ndim != 2 or rows.shape[1] != 3:
            raise ValueError(f"flow rows are [n, 3], not {rows.shape}")
        n = len(rows)
        keys, ids = rows[:, 0].astype(np.int64), rows[:, 2].astype(np.int64)
        if n and (keys.min() <= 0 or ids.min() < 1 or ids.max() >= self.vocab):
            raise ValueError("a stream key must be positive and an event id "
                             f"in [1, {self.vocab})")
        self.calls += 1
        counts = {"flow.events": n, "flow.restarts": 0, "flow.evictions": 0,
                  "flow.wraps": 0}
        uniq, first, inv, per = np.unique(
            keys, return_index=True, return_inverse=True, return_counts=True)
        # flows in the order their first rows came; an event's place in its
        # flow's chunk by a stable sort on the flow
        f_of = np.empty(len(uniq), np.int64)
        f_of[np.argsort(first, kind="stable")] = np.arange(len(uniq))
        by_flow = np.argsort(inv, kind="stable")
        t = np.empty(n, np.int64)
        t[by_flow] = np.arange(n) - np.repeat(np.cumsum(per) - per, per)
        restart = np.bincount(inv, weights=rows[:, 1] != 0,
                              minlength=len(uniq)) > 0
        if len(uniq) and per.max() + 1 > self.positions:
            raise ValueError(f"a chunk of {per.max()} events does not fit "
                             f"{self.positions} positions")
        base = np.empty(len(uniq), np.int64)
        for u, key in enumerate(uniq.tolist()):
            slot = self.slot_of.get(key)
            if slot is None:
                slot = self._take_slot(key, counts)
            at = int(self.length[slot])
            if at and at + per[u] > self.positions:
                counts["flow.wraps"] += 1
                at = 0
            if at == 0 or restart[u]:
                counts["flow.restarts"] += 1
                at = 1
            self.length[slot] = at + per[u]
            self.touched[slot] = self.calls
            base[u] = slot * self.positions + at
        F, T = _pow2(len(uniq)), _pow2(per.max() if n else 1)
        out = np.empty((n, 3), np.int32)
        out[:, 0] = f_of[inv] * T + t
        out[:, 1] = base[inv] + t
        out[:, 2] = ids
        counts["flow.resident"] = len(self.slot_of)
        self.layouts[F, T] = self.layouts.get((F, T), 0) + 1
        return Plan(out, (F, T), counts)
