"""From the start of the process (as /proc has it) to the window's first
call, less the interval in which JAX's runtime started up and found the
chip (the first ``jax.devices()``): interpreter and JAX imports, rows and
weights from the seed, compiles or cache reads, the set-up fit, the warm
calls."""


def read(run: dict, how: dict):
    return run["setup_s"]
