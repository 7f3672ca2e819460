"""A whole program's share of the chip's bf16 peak: the call's rows times
the model's FLOPs a row (``counts/<model>.py``, from the configuration's
widths) over the median device duration of the program."""

import statistics

from chipbench.trace.reduce import program_seconds


def read(run: dict, how: dict):
    if run["trace"] is None or run["peaks"] is None:
        return None
    durs = program_seconds(run["trace"], how["program"])
    if not durs:
        return None
    flops = (getattr(run["counts"], how["flops"])(run["config"]["model"])
             * run["rows_per_call"])
    return 100.0 * flops / statistics.median(durs) / run["peaks"][
        "bf16_flops_per_s"]
