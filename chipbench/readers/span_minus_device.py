"""Host time of a call: the mean duration of the harness's own span
around it (a score call from due to scores readable, a fit from start to
end, by the host's clock), less the device time of the programs it runs
(their mean duration times how many a call runs). Spans and programs are
those that lie wholly inside the same traced slice."""

import statistics

from chipbench.trace.reduce import program_seconds


def read(run: dict, how: dict):
    if run["trace"] is None:
        return None
    spans = [b - a for a, b in run["trace"]["spans"].get(how["span"], [])]
    durs = program_seconds(run["trace"], how["program"])
    if not spans or not durs:
        return None
    per_span = (run["config"]["telemeter"][how["programs_per_span_key"]]
                if "programs_per_span_key" in how else 1)
    return (statistics.mean(spans) / 1e9
            - per_span * statistics.mean(durs)) * 1e3
