"""A percentile, in milliseconds, over ALL calls of the window: from the
instant a call was due (its slot was free) to its scores readable on the
host. Calls still outstanding when the window closed were waited for and
count with the time they took."""

import numpy as np


def read(run: dict, how: dict):
    lat = [c["done"] - c["due"] for c in run["window"]["calls"]]
    if not lat:
        return None
    return float(np.percentile(lat, how["percentile"])) * 1e3
