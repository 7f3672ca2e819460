"""The program's own phase log (``linkerd_tpu/telemetry/phases.py``), as the
span and count readers take it: one record a ``score`` or ``fit`` call,
stamped by ``time.monotonic()``, the clock of the window and of
``trace_marks``. The harness has closed the scorer by the time a reader
runs, so the log is read from the program's module, as the entry imports
the program. A program without that module (or an entry that makes no
call into it) has no records, and the readers then return nothing."""


def all_calls() -> list:
    try:
        from linkerd_tpu.telemetry import phases
    except ImportError:
        return []
    return phases.records()


def window_calls(run: dict) -> list:
    """The calls that began inside the window, all of it and not the
    traced slice alone."""
    w = run["window"]
    return [c for c in all_calls() if w["t0"] <= c.t0 <= w["t_end"]]

