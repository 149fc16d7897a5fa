"""Pure-jnp oracles for every Pallas kernel (the allclose ground truth)."""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np


def pairwise_sq_dists_ref(x: jnp.ndarray, y: jnp.ndarray) -> jnp.ndarray:
    x = x.astype(jnp.float32)
    y = y.astype(jnp.float32)
    xx = jnp.sum(x * x, axis=-1)[:, None]
    yy = jnp.sum(y * y, axis=-1)[None, :]
    return jnp.maximum(xx + yy - 2.0 * (x @ y.T), 0.0)


def gf2_find_low_ref(cols: np.ndarray) -> np.ndarray:
    """First set bit per packed column; 2^31-1 when empty. cols: (C, W)."""
    cols = np.asarray(cols, dtype=np.uint32)
    out = np.full(cols.shape[0], 2**31 - 1, dtype=np.int32)
    for i, col in enumerate(cols):
        nz = np.nonzero(col)[0]
        if nz.size:
            w = int(nz[0])
            bit = int(col[w] & -col[w]).bit_length() - 1
            out[i] = w * 32 + bit
    return out


def gf2_serial_reduce_ref(blocks: np.ndarray):
    """Reference intra-block serial reduction (standard column algorithm
    restricted to the block)."""
    blocks = np.array(blocks, dtype=np.uint32, copy=True)
    G, C, W = blocks.shape
    lows = np.full((G, C), 2**31 - 1, dtype=np.int32)
    reds = np.zeros(G, dtype=np.int32)
    for g in range(G):
        for c in range(C):
            while True:
                low = gf2_find_low_ref(blocks[g, c:c + 1])[0]
                if low == 2**31 - 1:
                    break
                hit = np.nonzero(lows[g, :c] == low)[0]
                if hit.size == 0:
                    break
                blocks[g, c] ^= blocks[g, hit[0]]
                reds[g] += 1
            lows[g, c] = low
    return blocks, lows, reds

