"""Persistent XLA compilation cache: fast pipeline startup.

The reference's backends amortize startup by caching *engines* on disk
(e.g. TensorRT builds then caches serialized engines,
``ext/nnstreamer/tensor_filter/tensor_filter_tensorrt.cc``).  The XLA
analog is jax's persistent compilation cache: compiled executables keyed
by (HLO, flags, platform) survive process restarts, so a production
pipeline's first frame costs a cache read instead of a TPU compile.

Where the cache lives is decided OUTSIDE the program:

* ``JAX_COMPILATION_CACHE_DIR`` set — jax reads the variable itself and
  this module writes no directory into ``jax.config``;
* not set — ``<checkout>/.jax_cache`` (git-ignored).  The path is part of
  the cache key's neighbourhood: a directory that moves never hits, so it
  is fixed, not derived from the host or the user.

Without the variable, XLA:CPU programs are not cached: a CPU run is a
test or dry run in a fresh checkout, where a cache can only cost writes.

:func:`enable` is the one call; the jax-xla filter backend, the
generator and the trainer all make it before their first compile.
"""

from __future__ import annotations

import os
from typing import Optional

from .log import get_logger

log = get_logger("compile_cache")

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT_CACHE = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))),
    ".jax_cache",
)


def enable() -> Optional[str]:
    """Turn on the persistent cache (idempotent); returns the directory
    in use, or None for an XLA:CPU process without the variable."""
    import jax

    path = os.environ.get(ENV_VAR)
    if not path:
        if jax.default_backend() == "cpu":
            return None
        path = CHECKOUT_CACHE
        if jax.config.jax_compilation_cache_dir != path:
            os.makedirs(path, exist_ok=True)
            jax.config.update("jax_compilation_cache_dir", path)
            log.info("XLA persistent compilation cache at %s", path)
    # min 0: streaming pipelines recompile per shape bucket, and those
    # sub-second compiles are exactly the ones worth persisting
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
