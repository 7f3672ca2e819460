"""Median device duration of one execution of a compiled program, from
the device trace's program line."""

import statistics

from chipbench.trace.reduce import program_seconds


def read(run: dict, how: dict):
    if run["trace"] is None:
        return None
    durs = program_seconds(run["trace"], how["program"])
    return statistics.median(durs) * 1e3 if durs else None
