"""Where the persistent compile cache lives, and how much compiling a phase
did.  Both copied from the repo's sound pieces (bench.use_compile_cache,
chip_smoke._CompileClock)."""

import os

import jax
import jax.monitoring

from chipbench.manifest import REPO


def use_compile_cache():
    """`JAX_COMPILATION_CACHE_DIR` set: JAX reads it itself and nothing is set
    in code.  Unset: one fixed directory inside the checkout (the path is part
    of the cache key, so a directory that moves never hits)."""
    if "JAX_COMPILATION_CACHE_DIR" in os.environ:
        return os.environ["JAX_COMPILATION_CACHE_DIR"]
    path = os.path.join(REPO, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path


class CompileClock:
    """Backend compiles (or fetches from the persistent cache) since the last
    `take()`: how many and how many seconds, from JAX's own events."""

    def __init__(self):
        self._secs, self._count = 0.0, 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self._secs += secs
            self._count += 1

    def take(self):
        out = (self._count, self._secs)
        self._secs, self._count = 0.0, 0
        return out
