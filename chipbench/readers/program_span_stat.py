"""A statistic, in milliseconds, of the program's own spans of one name
(or of several, summed within a call where ``sum_per_call`` is set), over
every call that began inside the window. ``stat`` is ``"mean"`` or a
percentile."""

import statistics

import numpy as np

from chipbench.readers.program_phases import window_calls


def read(run: dict, how: dict):
    names = [how["span"]] if isinstance(how["span"], str) else how["span"]
    samples = []
    for call in window_calls(run):
        durs = [end - start for name, start, end in call.spans()
                if name in names]
        if durs:
            samples += [sum(durs)] if how.get("sum_per_call") else durs
    if not samples:
        return None
    if how["stat"] == "mean":
        return statistics.fmean(samples) * 1e3
    return float(np.percentile(samples, how["stat"])) * 1e3
