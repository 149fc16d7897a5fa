"""Plain persistent homology of a sparse distance map: the reference of the
COO cells.

Independent of the code under test: it imports nothing from ``repro``.  A
pixel table ``(bin1, bin2, contact)`` of balanced contacts becomes
distances ``d = 1 / contact`` in ``dtype``; a pixel with no positive,
finite contact is no edge.  ``(i, j)`` and ``(j, i)`` fold into one pair
and the diagonal is dropped; duplicates keep their smallest distance.
Pairs at most ``tau`` apart are the edges, sorted by (length, i, j).  From
there the steps are ``bench/reference.py``'s own: Kruskal for H0, cofaces
from the edge graph, and the column-at-a-time set reduction with clearing.

In float64 that is the deployment's stated arithmetic (a quotient is
correctly rounded, so the distances order pixels exactly as their contacts
do, reversed); float32 is the control that a comparison has to catch.
"""
from __future__ import annotations

from typing import Dict, Tuple

import numpy as np

from bench import reference


def edge_list(n: int, bin1: np.ndarray, bin2: np.ndarray,
              contact: np.ndarray, tau: float, dtype=np.float64
              ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Edges ``(i, j, length)``, ``i < j``, of length at most ``tau``,
    sorted by (length, i, j)."""
    c = np.asarray(contact, dtype=dtype)
    ok = np.isfinite(c) & (c > 0)
    i = np.minimum(bin1, bin2)[ok].astype(np.int64)
    j = np.maximum(bin1, bin2)[ok].astype(np.int64)
    d = (dtype(1.0) / c[ok]).astype(np.float64)
    off = i != j
    i, j, d = i[off], j[off], d[off]
    order = np.lexsort((d, j, i))             # by pair, then distance
    i, j, d = i[order], j[order], d[order]
    first = np.ones(i.size, dtype=bool)
    first[1:] = (i[1:] != i[:-1]) | (j[1:] != j[:-1])
    i, j, d = i[first], j[first], d[first]
    keep = d <= tau
    i, j, d = i[keep], j[keep], d[keep]
    order = np.lexsort((j, i, d))
    return i[order], j[order], d[order]


def diagrams(n: int, bin1: np.ndarray, bin2: np.ndarray, contact: np.ndarray,
             tau: float, maxdim: int, dtype=np.float64
             ) -> Dict[int, np.ndarray]:
    """Persistence diagrams H0..H``maxdim`` (``maxdim`` <= 2) of the flag
    filtration of the map's ``n`` bins up to ``tau``."""
    ei, ej, lens = edge_list(n, np.asarray(bin1), np.asarray(bin2), contact,
                             tau, dtype)
    out: Dict[int, np.ndarray] = {}
    out[0], merges = reference._h0(n, ei, ej, lens)
    if maxdim < 1:
        return out
    g = reference._Graph(n, ei, ej)
    edges = np.stack([ei, ej], axis=1)
    tris, tri_diam = reference._in_filtration_order(
        *reference._cofaces(g, edges, np.arange(len(lens))))
    tri_len = lens[tri_diam]
    out[1], tri_pivots = reference._reduce(
        reference._coboundary_columns(edges, tris, n), merges, lens, tri_len)
    if maxdim < 2:
        return out
    tets, tet_diam = reference._in_filtration_order(
        *reference._cofaces(g, tris, tri_diam))
    out[2], _ = reference._reduce(
        reference._coboundary_columns(tris, tets, n), set(tri_pivots),
        tri_len, lens[tet_diam])
    return out
