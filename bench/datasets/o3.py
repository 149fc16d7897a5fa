"""o3 (Dory, arXiv 2103.05608, Table 1; Ripser's benchmark, arXiv
1908.02518): random orthogonal 3x3 matrices, Haar-distributed on O(3), as
points of R^9."""
from __future__ import annotations

from typing import Dict

import numpy as np


def haar_o3(rng: np.random.Generator, n: int) -> np.ndarray:
    """``n`` Haar-random orthogonal 3x3 matrices, shape ``(n, 3, 3)``."""
    q, r = np.linalg.qr(rng.normal(size=(n, 3, 3)))
    return q * np.sign(np.diagonal(r, axis1=1, axis2=2))[:, None, :]


def points(config: Dict, rng: np.random.Generator, n: int) -> np.ndarray:
    """``n`` o3 points: Haar-random orthogonal 3x3 matrices as rows of R^9."""
    return haar_o3(rng, n).reshape(n, 9)
