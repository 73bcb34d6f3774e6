"""Small shared helpers."""
from __future__ import annotations

import os
from pathlib import Path

__all__ = ["CHECKOUT_CACHE", "CompileWatch", "round_up",
           "setup_compile_cache", "zeros_like_specs"]

# Caches the program fills at run time (JAX compile cache, kernel
# autotune winners) live in the checkout, at a fixed path, listed in
# .gitignore: a moving path would never be hit again.
CHECKOUT_CACHE = Path(__file__).resolve().parents[2] / ".cache"


def setup_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache.

    If ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and no
    other directory is set here; otherwise the cache goes to
    ``<checkout>/.cache/jax``.  Returns the directory in use."""
    import jax

    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(CHECKOUT_CACHE / "jax")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


class CompileWatch:
    """Counts, while entered, the programs JAX lowers (each one a
    compile or a load from the persistent cache), the seconds spent in
    backend compiles, and the persistent-cache hits."""

    _LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
    _BACKEND = "/jax/core/compile/backend_compile_duration"
    _HIT = "/jax/compilation_cache/cache_hits"

    def __init__(self) -> None:
        self.lowered = 0
        self.compile_s = 0.0
        self.cache_hits = 0

    def _on_duration(self, name: str, secs: float, **_) -> None:
        if name == self._LOWER:
            self.lowered += 1
        elif name == self._BACKEND:
            self.compile_s += secs

    def _on_event(self, name: str, **_) -> None:
        if name == self._HIT:
            self.cache_hits += 1

    def __enter__(self) -> "CompileWatch":
        from jax import monitoring

        monitoring.register_event_duration_secs_listener(self._on_duration)
        monitoring.register_event_listener(self._on_event)
        return self

    def __exit__(self, *exc) -> None:
        from jax import monitoring

        monitoring.unregister_event_duration_listener(self._on_duration)
        monitoring.unregister_event_listener(self._on_event)


def round_up(x: int, m: int) -> int:
    """Smallest multiple of ``m`` that is >= ``x``."""
    return -(-x // m) * m


def zeros_like_specs(tree):
    """Zero-initialized arrays for a pytree of ``jax.ShapeDtypeStruct``.

    Shared by the dense decode cache (``serving.serve_step.init_cache``)
    and the paged KV pool (``serving.engine.kv_pool``), which both
    materialize ``registry`` cache specs.
    """
    import jax
    import jax.numpy as jnp

    return jax.tree_util.tree_map(
        lambda s: jnp.zeros(s.shape, s.dtype), tree,
        is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct),
    )
