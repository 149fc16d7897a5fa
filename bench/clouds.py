"""Isometric copies of point clouds, shared by every dataset.

A dataset's own points come from ``bench/datasets/<name>.py``; a run then
moves them by an isometry drawn from ``--seed``, so every seed asks the same
work in other coordinates.
"""
from __future__ import annotations

import numpy as np


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
