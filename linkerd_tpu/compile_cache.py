"""Where this checkout's processes keep JAX's persistent compile cache.

Score and fit steps compile once per power-of-two batch bucket, inside
the serving process, on first use; a process that starts cold pays all
of it again. Every process entry that reaches the device
(``python -m linkerd_tpu``, the scorer sidecar, ``bench.py``,
``chip_smoke.py``'s children) calls ``place_compile_cache()`` before
its first ``import jax`` so they all share one cache.

Placement comes from outside: where ``JAX_COMPILATION_CACHE_DIR`` is
set it is honoured and nothing else is set. Otherwise the cache lives
at one fixed, git-ignored path under the checkout — never a tempdir, a
pid or a timestamp, because a cache that moves never hits. This module
is the only place the directory is chosen; it imports no JAX (the
settings travel as the environment variables JAX reads at import, so
child processes inherit them too).
"""

from __future__ import annotations

import os
import sys

ENV_DIR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    ".jax_cache")


def place_compile_cache() -> str:
    """Point JAX's persistent compilation cache at the shared directory
    and return it. Must run before the first ``import jax`` of the
    process: JAX reads these variables once, at import."""
    if "jax" in sys.modules:
        raise RuntimeError(
            "place_compile_cache() must run before the first `import jax`")
    path = os.environ.get(ENV_DIR)
    if not path:
        path = DEFAULT_DIR
        os.environ[ENV_DIR] = path
    # JAX persists only programs that took >= 1.0 s to compile by
    # default; the scorer's per-bucket steps are far smaller than that,
    # so without this the cache would stay empty
    os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
    return path
