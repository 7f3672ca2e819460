"""A whole program's share of the chip's bf16 peak, where the FLOPs a row
depend on what the calls attended over: the model's FLOPs a row
(``counts/<model>.py``'s ``how["flops"]``, handed ``seen``: each counter
``how["per_event"]`` names, summed over the calls that began inside the
traced slice, over their events) times the call's rows, over the median
device duration of the program. A program that counts none of them has
nothing to read."""

import statistics

from chipbench.readers.program_phases import all_calls
from chipbench.trace.reduce import program_seconds


def seen_per_event(run: dict, names: list):
    """``{name: the counter's sum over the traced slice's calls, over their
    events}``, or None where they counted no event or none of ``names``."""
    marks = run.get("trace_marks") or {}
    if "lo" not in marks:
        return None
    calls = [c for c in all_calls() if marks["lo"] <= c.t0 <= marks["hi"]]
    events = sum(c.counts.get("flow.events", 0) for c in calls)
    if not events or not any(name in c.counts for c in calls
                             for name in names):
        return None
    return {name: sum(c.counts.get(name, 0) for c in calls) / events
            for name in names}


def read(run: dict, how: dict):
    if run["trace"] is None or run["peaks"] is None:
        return None
    durs = program_seconds(run["trace"], how["program"])
    seen = seen_per_event(run, how["per_event"])
    if not durs or seen is None:
        return None
    flops = (getattr(run["counts"], how["flops"])(run["config"]["model"],
                                                  seen)
             * run["rows_per_call"])
    return 100.0 * flops / statistics.median(durs) / run["peaks"][
        "bf16_flops_per_s"]
