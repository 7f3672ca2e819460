"""A kernel's share of its roofline: the least time the chip could take
for the call (the larger of FLOPs over peak and bytes over peak
bandwidth, both the algorithm's, from ``counts/<model>.py``) over the
kernel's device time in one execution of its program (median)."""

import statistics

from chipbench.trace.reduce import op_seconds_per_execution


def read(run: dict, how: dict):
    if run["trace"] is None or run["peaks"] is None:
        return None
    secs = [s for s in op_seconds_per_execution(
        run["trace"], how["program"], how["op"]) if s > 0]
    if not secs:
        return None
    model, rows = run["config"]["model"], run["rows_per_call"]
    least = max(
        getattr(run["counts"], how["flops"])(model) * rows
        / run["peaks"]["bf16_flops_per_s"],
        getattr(run["counts"], how["bytes"])(model) * rows
        / run["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least / statistics.median(secs)
