"""Jitted dispatch wrappers over the Pallas kernels.

On the CPU container Pallas executes in interpret mode (correctness); on TPU
the same ``pl.pallas_call`` compiles to Mosaic.  ``use_pallas`` resolves to
False off TPU, where the wrappers fall back to the pure-jnp/numpy oracles,
while tests force interpret-mode kernels to validate them.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from . import ref as kref
from .backend import default_interpret  # noqa: F401  (re-export)
from .gf2 import gf2_find_low, gf2_serial_reduce
from .pairwise_dist import pairwise_sq_dists


def default_use_pallas() -> bool:
    return jax.default_backend() == "tpu"


def pairwise_distances(x, y=None, block: int = 256, use_pallas=None,
                       interpret=None) -> jnp.ndarray:
    """Euclidean distances through the Pallas kernel (which pads ragged
    row counts to the block multiples internally)."""
    use_pallas = default_use_pallas() if use_pallas is None else use_pallas
    self_dist = y is None
    y = x if y is None else y
    x = jnp.asarray(x, jnp.float32)
    y = jnp.asarray(y, jnp.float32)
    if not use_pallas:
        d2 = kref.pairwise_sq_dists_ref(x, y)
    else:
        d2 = pairwise_sq_dists(x, y, block_m=block, block_n=block,
                               interpret=interpret)
    if self_dist:
        # kill catastrophic-cancellation residue on the diagonal
        d2 = d2 * (1.0 - jnp.eye(d2.shape[0], dtype=d2.dtype))
    return jnp.sqrt(d2)


def find_low(cols, use_pallas=None, interpret=None) -> jnp.ndarray:
    use_pallas = default_use_pallas() if use_pallas is None else use_pallas
    if not use_pallas:
        return jnp.asarray(kref.gf2_find_low_ref(np.asarray(cols)))
    return gf2_find_low(jnp.asarray(cols), interpret=interpret)


def serial_reduce_bits(blocks, use_pallas=None, interpret=None):
    use_pallas = default_use_pallas() if use_pallas is None else use_pallas
    if not use_pallas:
        b, l, r = kref.gf2_serial_reduce_ref(np.asarray(blocks))
        return jnp.asarray(b), jnp.asarray(l), jnp.asarray(r)
    return gf2_serial_reduce(jnp.asarray(blocks), interpret=interpret)

