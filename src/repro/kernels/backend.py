"""Backend selection shared by the Pallas kernel wrappers.

Mosaic (the Pallas TPU compiler) only exists on TPU; everywhere else the
kernels run in interpret mode for correctness.  Kernel entry points take
``interpret=None`` and resolve it here at trace time, so real hardware gets
compiled kernels by default while tests can still force either mode
explicitly.
"""
from __future__ import annotations

import os
from typing import Optional

import jax
import jax.numpy as jnp

# <checkout>/.jax_cache, fixed by this file's place in the tree
# (src/repro/kernels/backend.py): the directory is part of the cache key,
# so it must not move with the working directory
_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def use_compile_cache() -> Optional[str]:
    """Turn on JAX's persistent compilation cache for an entry point.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is JAX's own choice and is
    left alone (returns ``None``); otherwise the cache goes to the fixed
    ``<checkout>/.jax_cache``, whose path is returned.  Entry points call
    this once; importing a library module never does.
    """
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return None
    jax.config.update("jax_compilation_cache_dir", _CACHE_DIR)
    return _CACHE_DIR


def default_interpret() -> bool:
    """Interpret everywhere except on a TPU backend."""
    return jax.default_backend() != "tpu"


def resolve_interpret(interpret: Optional[bool]) -> bool:
    return default_interpret() if interpret is None else bool(interpret)


def pad_to_multiple(x: jnp.ndarray, multiple: int, axis: int) -> jnp.ndarray:
    """Zero-pad ``axis`` up to the next multiple (the shared pad-then-slice
    policy of the kernel wrappers; callers slice the result back)."""
    pad = (-x.shape[axis]) % multiple
    if not pad:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)
