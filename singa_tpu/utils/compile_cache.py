"""Where JAX's persistent compilation cache lives.

Every process entry point (`singa_tpu.main`, `benchmark/run.py`,
`chip_smoke.py`, `__graft_entry__`, the convergence tool, the timing
scripts under `tools/`) calls `enable()` before it compiles anything,
so a second process compiling the same programs reads them back
instead of paying the compile again.

The directory is placeable from outside: when
`JAX_COMPILATION_CACHE_DIR` is set, JAX reads it itself and nothing
here touches `jax.config`.  Otherwise the cache goes to one fixed
directory inside the checkout.  A directory that moves between runs
(a temp name, a pid, a timestamp) never hits, so the path is never
derived from one.  Pytest does not call this (tests/conftest.py).
"""

from __future__ import annotations

import os

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"

#: `<checkout>/.jax_cache` (git- and chiprun-ignored).
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def enable() -> str:
    """Point JAX's persistent compile cache at its directory and
    return that directory."""
    placed = os.environ.get(ENV_VAR)
    if placed:
        return placed
    import jax
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
