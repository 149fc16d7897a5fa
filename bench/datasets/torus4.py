"""torus4 (Dory, arXiv 2103.05608, Table 1; Ripser's benchmark, arXiv
1908.02518): points drawn uniformly at random on the Clifford torus
S^1 x S^1 in R^4, each circle of radius 1/sqrt(2), with no noise."""
from __future__ import annotations

from typing import Dict

import numpy as np


def points(config: Dict, rng: np.random.Generator, n: int) -> np.ndarray:
    """``n`` torus4 points: two uniform angles per point, the first then
    the second drawn for all points, as rows ``(cos a, sin a, cos b, sin b)
    / sqrt(2)`` of R^4."""
    a = rng.uniform(0.0, 2.0 * np.pi, n)
    b = rng.uniform(0.0, 2.0 * np.pi, n)
    return np.stack([np.cos(a), np.sin(a), np.cos(b), np.sin(b)],
                    axis=1) / np.sqrt(2.0)
