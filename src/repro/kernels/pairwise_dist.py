"""Pallas TPU kernel: blocked pairwise squared Euclidean distances.

Filtration construction starts with the distance matrix — for n up to
millions of points this is the paper's first compute wall.  On TPU it is a
classic MXU workload via ``|x|^2 - 2 x.y + |y|^2``: the cross term is a
(bm, d) x (d, bn) matmul per tile, staged HBM->VMEM by BlockSpecs.

Tiling: grid (M/bm, N/bn); X tile (bm, d) and Y tile (bn, d) live in VMEM
(d is kept whole — point dims are small for VR workloads), output tile
(bm, bn).  bm = bn = 256 keeps the working set at
2*256*d*4 + 256*256*4 ≈ 0.5 MB for d<=64 — far under the ~16 MB VMEM budget,
leaving room for double buffering; the 256x256 output tile is MXU-aligned
(multiples of 128).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .backend import pad_to_multiple, resolve_interpret


def _pairwise_kernel(x_ref, y_ref, o_ref):
    x = x_ref[...].astype(jnp.float32)
    y = y_ref[...].astype(jnp.float32)
    xx = jnp.sum(x * x, axis=-1)[:, None]
    yy = jnp.sum(y * y, axis=-1)[None, :]
    # HIGHEST: on a TPU the default f32 matmul is one bf16 pass, whose
    # error on the cross term (~2e-2 on o3 at d = 9, v5e) is far beyond
    # the f32 candidate margin of scale/tiles.py — the harvest then drops
    # true edges.  HIGHEST stays within ~2e-6 there.
    xy = jax.lax.dot_general(
        x, y, (((1,), (1,)), ((), ())), precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)
    o_ref[...] = jnp.maximum(xx + yy - 2.0 * xy, 0.0)


@functools.partial(jax.jit, static_argnames=("block_m", "block_n", "interpret"))
def pairwise_sq_dists(x: jnp.ndarray, y: jnp.ndarray,
                      block_m: int = 256, block_n: int = 256,
                      interpret: Optional[bool] = None) -> jnp.ndarray:
    """Squared distances (M, N) between rows of x (M, d) and y (N, d).

    Ragged M/N are zero-padded to the block multiples and the result sliced
    back, so any point count works.  ``interpret=None`` resolves per backend
    (compiled on TPU only).
    """
    interpret = resolve_interpret(interpret)
    m, d = x.shape
    n = y.shape[0]
    x = pad_to_multiple(x, block_m, axis=0)
    y = pad_to_multiple(y, block_n, axis=0)
    grid = (x.shape[0] // block_m, y.shape[0] // block_n)
    out = pl.pallas_call(
        _pairwise_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_m, d), lambda i, j: (i, 0)),
            pl.BlockSpec((block_n, d), lambda i, j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((block_m, block_n), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((x.shape[0], y.shape[0]),
                                       jnp.float32),
        interpret=interpret,
        name="pairwise_sq_dists",
    )(x, y)
    return out[:m, :n]
