"""Where compiled device programs are kept between processes.

One rule, applied by every entry point that may compile for a chip
(`cli.py` before a node starts, the bench scripts, `chip_smoke.py`):

- `JAX_COMPILATION_CACHE_DIR` set: JAX reads it itself. Nothing here,
  and nothing else in the tree, sets a directory in code.
- unset, TPU backend: `<checkout>/.jax_cache`. The path is fixed
  because it is part of how a cache is found again: a directory named
  after a process or a moment never hits.
- unset, any other backend: no cache. On jax 0.9.0 a CPU cache no
  longer slows a cold compile (the sharded verify program: 13.3 s with,
  13.6 s without, this sandbox), but loading from it logs XLA:CPU's
  "machine type doesn't match ... could lead to SIGILL" warning, and the
  tier-1 suite compiles each CPU shape once per process anyway. Tests
  and CPU child processes strip the variable (tests/conftest.py,
  utils/procs.node_child_env).
"""

from __future__ import annotations

import os
from typing import Optional

CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
DEFAULT_DIR = os.path.join(CHECKOUT, ".jax_cache")


def enable() -> Optional[str]:
    """Apply the rule above; returns the cache directory in force, or
    None. Call before the first compile. A process whose JAX_PLATFORMS
    rules a TPU out is answered without importing jax (plain CPU nodes
    must not pay the import); otherwise this brings the backend up."""
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        return env_dir
    platforms = os.environ.get("JAX_PLATFORMS")
    if platforms and "tpu" not in platforms.split(","):
        return None
    import jax
    if jax.default_backend() != "tpu":
        return None
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
