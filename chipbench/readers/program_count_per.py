"""One of the program's counts over another, each as it rose over the
calls that began inside the window, times ``scale`` (1/2**20 for MiB,
100 for a share in per cent)."""

from chipbench.readers.program_phases import window_calls


def read(run: dict, how: dict):
    calls = window_calls(run)
    per = sum(c.counts.get(how["per"], 0) for c in calls)
    if not per:
        return None
    return (sum(c.counts.get(how["count"], 0) for c in calls) / per
            * how.get("scale", 1))
