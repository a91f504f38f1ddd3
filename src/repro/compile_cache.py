"""The persistent XLA compilation cache, switched on by entry-point scripts.

Compiling the streamed token-plate step for a TPU takes tens of seconds, and
a second run of the same script at the same shapes can read it back instead.
Only entry points (``chip_smoke.py``, ``examples/lda_topics.py``,
``benchmarks/run.py``) call :func:`enable`: importing ``repro`` or running
the tests leaves jax's cache settings alone.
"""

from __future__ import annotations

import os
import pathlib

import jax

# the repository checkout this package is imported from (``<checkout>/src``)
CHECKOUT = pathlib.Path(__file__).resolve().parents[2]


def enable() -> str:
    """Turn the persistent compilation cache on; return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, jax has already read it and
    nothing is set here.  Otherwise the cache lives at the fixed
    ``<checkout>/.jax_cache``: a run finds only what an earlier run wrote
    to the same directory, so the path never depends on a temporary
    directory, a process id or the time."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if path:
        return path
    path = str(CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
