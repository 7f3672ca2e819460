"""A kernel's share of its roofline where its work depends on what the
calls attended over: ``kernel_roofline``'s, the operations and bytes a row
(``counts/<model>.py``) handed ``seen`` (``program_mfu_seen.seen_per_event``:
the counters ``how["per_event"]`` names, over the traced slice's calls and
their events)."""

import statistics

from chipbench.readers.program_mfu_seen import seen_per_event
from chipbench.trace.reduce import op_seconds_per_execution


def read(run: dict, how: dict):
    if run["trace"] is None or run["peaks"] is None:
        return None
    secs = [s for s in op_seconds_per_execution(
        run["trace"], how["program"], how["op"]) if s > 0]
    seen = seen_per_event(run, how["per_event"])
    if not secs or seen is None:
        return None
    model, rows = run["config"]["model"], run["rows_per_call"]
    least = max(
        getattr(run["counts"], how["flops"])(model, seen) * rows
        / run["peaks"]["bf16_flops_per_s"],
        getattr(run["counts"], how["bytes"])(model, seen) * rows
        / run["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least / statistics.median(secs)
