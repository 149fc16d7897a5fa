"""Plain Vietoris-Rips persistent homology: the benchmark's reference.

Independent of the code under test: it imports nothing from ``repro``.  It
builds the whole VR complex up to the simplices one dimension above
``maxdim`` (every simplex whose diameter is at most ``tau``), orders each
dimension by (diameter, vertex tuple), and reduces the coboundary matrices
one column at a time over GF(2), low dimension first, with clearing
(Chen-Kerber / de Silva-Morozov-Vejdemo-Johansson).  Columns are Python
sets of cofacet ranks, the pivot of a column is its smallest rank.

Conventions, the same as the system's diagrams (``(birth, death)`` rows,
``inf`` for classes that never die below ``tau``): H0 births are 0 and
zero-length bars are dropped in every dimension.  A diagram, as a multiset
of bars of non-zero length, does not depend on how equal diameters are
ordered, so this reference can use its own tie-break.

Distances are Euclidean, ``sqrt(max(|x|^2 + |y|^2 - 2 x.y, 0))`` with the
dot product summed over coordinates in ascending order, in ``dtype``.  In
float64 that is the deployment's stated arithmetic; float32 is the control
that a comparison has to catch.
"""
from __future__ import annotations

from typing import Dict, Tuple

import numpy as np

BLOCK_ROWS = 1024


def edge_list(points: np.ndarray, tau: float, dtype=np.float64
              ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Edges ``(i, j, length)``, ``i < j``, of length at most ``tau``,
    sorted by (length, i, j).

    A matrix product finds the candidate pairs, with a margin far above its
    rounding; each candidate's length is then computed in the stated form,
    coordinates summed in ascending order."""
    p = np.asarray(points, dtype=dtype)
    n, d = p.shape
    sq = np.sum(p * p, axis=1)
    cols = np.ascontiguousarray(p.T)
    if np.isfinite(tau):
        slack = 64.0 * d * float(np.finfo(dtype).eps) * float(sq.max(initial=1.0))
        cap = tau * tau * (1.0 + 1e-6) + slack
    else:
        cap = np.inf
    ii, jj = [], []
    for s in range(0, n, BLOCK_ROWS):
        e = min(s + BLOCK_ROWS, n)
        near = sq[s:e, None] + sq[None, s:] - 2.0 * (p[s:e] @ cols[:, s:])
        r, c = np.nonzero(near <= cap)
        keep = r < c
        ii.append(s + r[keep])
        jj.append(s + c[keep])
    i = np.concatenate(ii)
    j = np.concatenate(jj)
    acc = np.zeros(i.shape[0], dtype=dtype)
    for k in range(d):
        acc += p[i, k] * p[j, k]
    d2 = sq[i] + sq[j] - 2.0 * acc
    lens = np.sqrt(np.maximum(d2, 0.0))
    keep = lens <= tau
    i, j, lens = i[keep], j[keep], lens[keep].astype(np.float64)
    order = np.lexsort((j, i, lens))
    return i[order], j[order], lens[order]


def _h0(n: int, ei: np.ndarray, ej: np.ndarray, lens: np.ndarray
        ) -> Tuple[np.ndarray, set]:
    """Kruskal: H0 diagram and the set of edge ranks that merge components."""
    parent = list(range(n))

    def find(x: int) -> int:
        while parent[x] != x:
            parent[x] = parent[parent[x]]
            x = parent[x]
        return x

    deaths, merges = [], set()
    for o, (a, b) in enumerate(zip(ei.tolist(), ej.tolist())):
        ra, rb = find(a), find(b)
        if ra != rb:
            parent[max(ra, rb)] = min(ra, rb)
            merges.add(o)
            deaths.append(float(lens[o]))
    bars = [(0.0, d) for d in deaths if d > 0.0]
    bars += [(0.0, np.inf)] * (n - len(merges))
    return np.array(bars, dtype=np.float64).reshape(-1, 2), merges


def _segments(starts: np.ndarray, counts: np.ndarray) -> np.ndarray:
    """Concatenated index ranges ``[starts[i], starts[i] + counts[i])``."""
    total = int(counts.sum())
    offsets = np.arange(total) - np.repeat(np.cumsum(counts) - counts, counts)
    return np.repeat(starts, counts) + offsets


class _Graph:
    """The edges as a lookup from vertex pairs to edge ranks, and for every
    vertex its neighbours above it (ascending) with their edge ranks."""

    def __init__(self, n: int, ei: np.ndarray, ej: np.ndarray):
        self.n = n
        code = ei * n + ej
        order = np.argsort(code)
        self.codes, self.ranks = code[order], order
        self.up_ptr = np.concatenate(
            [[0], np.cumsum(np.bincount(ei, minlength=n))])
        self.up_nbr = ej[order]        # sorted by (i, j): ascending per i

    def rank(self, a: np.ndarray, b: np.ndarray):
        """``(is_edge, rank)`` of the pairs ``a < b``."""
        code = a * self.n + b
        pos = np.minimum(np.searchsorted(self.codes, code),
                         len(self.codes) - 1)
        return self.codes[pos] == code, self.ranks[pos]


def _cofaces(g: _Graph, faces: np.ndarray, face_diam: np.ndarray
             ) -> Tuple[np.ndarray, np.ndarray]:
    """All (k+1)-simplices on the sorted vertex tuples ``faces``, each once,
    from the face without its largest vertex.  Returns them (sorted vertex
    tuples) with their diameters as edge ranks."""
    last = faces[:, -1]
    counts = g.up_ptr[last + 1] - g.up_ptr[last]
    at = _segments(g.up_ptr[last], counts)
    src = np.repeat(np.arange(faces.shape[0]), counts)
    top = g.up_nbr[at]
    diam = np.maximum(face_diam[src], g.ranks[at])
    keep = np.ones(top.shape[0], dtype=bool)
    for c in range(faces.shape[1] - 1):
        ok, r = g.rank(faces[src, c], top)
        keep &= ok
        diam = np.maximum(diam, r)
    out = np.concatenate([faces[src], top[:, None]], axis=1)
    return out[keep], diam[keep]


def _in_filtration_order(simplices: np.ndarray, diam: np.ndarray
                         ) -> Tuple[np.ndarray, np.ndarray]:
    """Sort by (diameter, vertex tuple)."""
    keys = [simplices[:, c] for c in range(simplices.shape[1] - 1, -1, -1)]
    order = np.lexsort(keys + [diam])
    return simplices[order], diam[order]


def _codes(simplices: np.ndarray, n: int) -> np.ndarray:
    code = np.zeros(simplices.shape[0], dtype=np.int64)
    for c in range(simplices.shape[1]):
        code = code * n + simplices[:, c]
    return code


def _coboundary_columns(faces: np.ndarray, cofaces: np.ndarray, n: int
                        ) -> Tuple[np.ndarray, np.ndarray]:
    """For each face (by rank), the ranks of its cofaces, as CSR
    ``(indptr, indices)``."""
    face_codes = _codes(faces, n)
    order = np.argsort(face_codes)
    sorted_codes = face_codes[order]
    owners, members = [], []
    for drop in range(cofaces.shape[1]):
        sub = np.delete(cofaces, drop, axis=1)
        owners.append(order[np.searchsorted(sorted_codes, _codes(sub, n))])
        members.append(np.arange(cofaces.shape[0]))
    owner = np.concatenate(owners)
    member = np.concatenate(members)
    by_owner = np.argsort(owner, kind="stable")
    indptr = np.concatenate(
        [[0], np.cumsum(np.bincount(owner, minlength=faces.shape[0]))])
    return indptr, member[by_owner]


def _reduce(cols: Tuple[np.ndarray, np.ndarray], skip: set,
            birth: np.ndarray, death: np.ndarray
            ) -> Tuple[np.ndarray, Dict[int, set]]:
    """Cohomology reduction: columns in decreasing rank, pivot = smallest
    row.  Returns the diagram and the pivot rows (for clearing above)."""
    indptr, rows = cols
    owner: Dict[int, set] = {}
    bars = []
    for j in range(len(indptr) - 2, -1, -1):
        if j in skip:
            continue
        col = set(rows[indptr[j]:indptr[j + 1]].tolist())
        while col:
            low = min(col)
            other = owner.get(low)
            if other is None:
                owner[low] = col
                if death[low] > birth[j]:
                    bars.append((float(birth[j]), float(death[low])))
                break
            col = col ^ other
        else:
            bars.append((float(birth[j]), np.inf))
    return np.array(bars, dtype=np.float64).reshape(-1, 2), owner


def diagrams(points: np.ndarray, tau: float, maxdim: int,
             dtype=np.float64) -> Dict[int, np.ndarray]:
    """Persistence diagrams H0..H``maxdim`` (``maxdim`` <= 2) of the VR
    filtration of ``points`` up to ``tau``, distances computed in ``dtype``."""
    points = np.asarray(points)
    n = points.shape[0]
    ei, ej, lens = edge_list(points, tau, dtype)
    out: Dict[int, np.ndarray] = {}
    out[0], merges = _h0(n, ei, ej, lens)
    if maxdim < 1:
        return out
    g = _Graph(n, ei, ej)
    edges = np.stack([ei, ej], axis=1)
    tris, tri_diam = _in_filtration_order(
        *_cofaces(g, edges, np.arange(len(lens))))
    tri_len = lens[tri_diam]
    out[1], tri_pivots = _reduce(_coboundary_columns(edges, tris, n), merges,
                                 lens, tri_len)
    if maxdim < 2:
        return out
    tets, tet_diam = _in_filtration_order(*_cofaces(g, tris, tri_diam))
    out[2], _ = _reduce(_coboundary_columns(tris, tets, n), set(tri_pivots),
                        tri_len, lens[tet_diam])
    return out
