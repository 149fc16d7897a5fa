"""Point clouds of the benchmark's deployments, by their published definitions.

Kept with the benchmark so that its inputs cannot change under a later PR:

``o3`` (Dory, arXiv 2103.05608, Table 1; Ripser's benchmark, arXiv
1908.02518): random orthogonal 3x3 matrices, Haar-distributed on O(3), as
points of R^9.
"""
from __future__ import annotations

import numpy as np


def haar_o3(rng: np.random.Generator, n: int) -> np.ndarray:
    """``n`` Haar-random orthogonal 3x3 matrices, shape ``(n, 3, 3)``."""
    q, r = np.linalg.qr(rng.normal(size=(n, 3, 3)))
    return q * np.sign(np.diagonal(r, axis1=1, axis2=2))[:, None, :]


def o3(rng: np.random.Generator, n: int) -> np.ndarray:
    """``n`` o3 points: Haar-random orthogonal 3x3 matrices as rows of R^9."""
    return haar_o3(rng, n).reshape(n, 9)


def random_isometry(rng: np.random.Generator, d: int) -> np.ndarray:
    """A Haar-random orthogonal ``d x d`` matrix."""
    q, r = np.linalg.qr(rng.normal(size=(d, d)))
    return q * np.sign(np.diag(r))[None, :]


def rotate(points: np.ndarray, q: np.ndarray) -> np.ndarray:
    """``points @ q.T``, each row on its own in a fixed order, so a row's
    image does not depend on the rows beside it (a prefix of a cloud maps to
    the same bits as the cloud's first rows)."""
    out = np.zeros(points.shape)
    for k in range(points.shape[1]):
        out += points[:, k, None] * q[None, :, k]
    return out


def isometric_copy(base: np.ndarray, rng: np.random.Generator) -> np.ndarray:
    """``base`` under a Haar-random orthogonal map of R^d.

    Distances are kept up to rounding, so the copy has the same complex,
    with the same vertex labels, and asks the same work of the system in
    other coordinates.  The seed then changes the inputs and not the amount
    of work.  (Shuffled labels would change the order in which the packed
    reduction meets equal-diameter columns, and with it the work.)
    """
    return rotate(base, random_isometry(rng, base.shape[1]))

