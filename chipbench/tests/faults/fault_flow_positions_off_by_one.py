"""A fault for the tests and the chip readings, never for a run of the
benchmark: a chunk appended to a flow that already holds events lands one
position late."""

from chipbench.entries import flow_scorer as base
from chipbench.entries.flow_scorer import *  # noqa: F401,F403


def build(config, seed):
    s = base.build(config, seed)
    table, positions = s._table, s.cfg.positions
    plan = table.map

    def late(rows):
        p = plan(rows)
        cell, chunk = p.rows[:, 0], p.layout[1]
        first = {int(c) // chunk: int(a) % positions
                 for c, a in zip(cell, p.rows[:, 1]) if c % chunk == 0}
        for i, c in enumerate(cell):
            if (first[int(c) // chunk] > 1
                    and p.rows[i, 1] % positions < positions - 1):
                p.rows[i, 1] += 1
        return p

    table.map = late
    return s
