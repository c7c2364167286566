"""Where a worker's persistent XLA compilation cache lives.

The cache directory is part of nothing but this decision, and the
decision has two cases:

* ``JAX_COMPILATION_CACHE_DIR`` is set — JAX reads it itself and this
  package sets no directory at all, so whoever runs the fleet (a CI
  job, the chip tool) places the cache from outside.
* it is not set — one fixed directory inside the checkout (git-ignored)
  shared by every worker of every fleet, ``%dist_init`` and gateway
  pools alike.  The path is part of the cache key, so it must not move
  between runs: never under a run dir, never holding a pid, a time or
  a temporary name.

Only workers compile device programs, so only
:mod:`nbdistributed_tpu.runtime.worker` calls this.
"""

from __future__ import annotations

import os
from collections.abc import Mapping

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"

# <checkout>/.nbd_xla_cache — beside the package, not inside it.
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".nbd_xla_cache")


def resolve(environ: Mapping[str, str] | None = None) -> str | None:
    """The directory a worker must hand to
    ``jax.config.update("jax_compilation_cache_dir", ...)``, or None
    when ``JAX_COMPILATION_CACHE_DIR`` already says where the cache
    goes and the worker must leave JAX's own handling alone."""
    environ = os.environ if environ is None else environ
    if environ.get(ENV_VAR):
        return None
    return DEFAULT_DIR
