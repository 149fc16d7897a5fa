"""Comparison of diagrams with the reference's.

A diagram is a multiset of ``(birth, death)`` bars.  The comparison is
exact: the deployment states float64 distances, and the system and the
reference compute the same float64 lengths, so equal diagrams are equal
bar for bar.  ``bars_off`` counts the bars in the symmetric difference of
the two multisets over every dimension: 0 for a sound answer.
"""
from __future__ import annotations

from collections import Counter
from typing import Dict

import numpy as np


def _bars(d: np.ndarray) -> Counter:
    rows = np.asarray(d, dtype=np.float64).reshape(-1, 2)
    return Counter(map(tuple, rows.tolist()))


def bars_off(got: Dict[int, np.ndarray], want: Dict[int, np.ndarray]) -> int:
    """Bars that one diagram set has and the other lacks, in all dims."""
    off = 0
    for dim in set(got) | set(want):
        a = _bars(got.get(dim, np.zeros((0, 2))))
        b = _bars(want.get(dim, np.zeros((0, 2))))
        off += sum(((a - b) + (b - a)).values())
    return off
