"""The device's idle time put down to what the host was doing, as a share
of the traced slice. Busy intervals are the operations of the first
device's programs (those that lie wholly inside the slice, as the
reduction keeps them); idle is what they leave of the slice. With
``spans``: the length of idle time that lies inside the union of the
program's spans of those names, by interval intersection (the slice less
the busy intervals and less the gaps between those spans). With
``outside_all_but``: the idle time inside none of the program's spans,
the named waits aside (a wait is not work the host did). Spans of
different threads overlap, so the first kind may sum to more than the
idle they cover; the second is exact."""

from chipbench.readers.program_phases import all_calls
from chipbench.trace.reduce import gaps, union_seconds


def read(run: dict, how: dict):
    trace, marks = run["trace"], run.get("trace_marks") or {}
    calls = all_calls()
    if trace is None or "hi" not in marks or not calls:
        return None
    programs = trace["programs"]
    if not programs:
        return None

    def ns(t: float) -> float:
        return (t - marks["clock0"]) * 1e9

    def wanted(name: str) -> bool:
        return (name in how["spans"] if "spans" in how
                else name not in how["outside_all_but"])

    lo, hi = ns(marks["lo"]), ns(marks["hi"])
    first = min(p["plane"] for p in programs)
    busy = [(op["start"], op["start"] + op["dur"]) for p in programs
            if p["plane"] == first for op in p["ops"]]
    spans = [(max(ns(start), lo), min(ns(end), hi))
             for call in calls for name, start, end in call.spans()
             if wanted(name) and ns(end) > lo and ns(start) < hi]
    # what neither the device nor (the complement of) the spans cover
    if "spans" in how:
        left = gaps(busy + gaps(spans, lo, hi), lo, hi)
    else:
        left = gaps(busy + spans, lo, hi)
    return 100.0 * union_seconds(left) / ((hi - lo) / 1e9)
