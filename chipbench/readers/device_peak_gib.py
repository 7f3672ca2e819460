"""What the fullest chip held at one instant of the window, as the result
line's ``memory_peak_bytes`` has it (``harness.device_block``): the
allocator's peak of bytes in use and the standing reservation for the
loaded programs' temporaries. Read once the window has closed and before
the reference runs."""


def read(run: dict, how: dict):
    peak = run["device"]["memory_peak_bytes"]
    return peak / 2 ** 30 if peak else None
