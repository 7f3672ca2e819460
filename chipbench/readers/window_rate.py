"""All rows whose scores came back, over all the time it took: the window
sends nothing once its seconds are up, waits for what it sent, and reads
the clock after that wait (``t_end``). A call of the bulk cells is 2 M
rows, so rows counted at a fixed instant would step by whole calls. A
fitted batch's rows are counted once, when they were scored."""


def read(run: dict, how: dict):
    w = run["window"]
    rows = run["rows_per_call"] * sum(c["ok"] for c in w["calls"])
    return rows / (w["t_end"] - w["t0"])
