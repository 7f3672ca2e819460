"""1 - the union of the device's operation intervals over the traced
slice's length."""


def read(run: dict, how: dict):
    t = run["trace"]
    if t is None or not t["window_s"]:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
