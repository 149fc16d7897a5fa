"""Sparse (COO triplet) distance input — Hi-C contact graphs, no dense matrix.

The paper's §6 genome workload starts from a Hi-C contact map: a sparse
symmetric matrix of contact counts over genomic loci.  This module feeds such
data straight into the pipeline as ``(row, col, value)`` triplets — entries
absent from the COO set are treated as infinitely far (no edge), exactly like
a dense matrix whose missing entries exceed ``tau_max``, so
``build_filtration_coo`` is bit-identical to a dense ``dists=`` call on the
materialized matrix (asserted in tests) while never allocating ``O(n^2)``.
``compute_ph(coo=(rows, cols, vals, n))`` runs this build inside its own
filtration stopwatch, under the ``coo/*`` spans.  Workload walk-through and
field reference: ``docs/architecture.md`` and ``docs/api.md``.
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple, Union

import numpy as np

from ..core.filtration import Filtration, filtration_from_edges
from ..obs.trace import span


def _checked_coo(
    rows: np.ndarray,
    cols: np.ndarray,
    vals: np.ndarray,
    n: Optional[int],
) -> Tuple[int, np.ndarray, np.ndarray, np.ndarray]:
    """``(n, rows, cols, vals)`` as int64/float64 arrays: shapes agree, no
    id is negative, and ``n`` (inferred from the largest id when ``None``)
    holds every id."""
    rows = np.asarray(rows, dtype=np.int64)
    cols = np.asarray(cols, dtype=np.int64)
    vals = np.asarray(vals, dtype=np.float64)
    if not (rows.shape == cols.shape == vals.shape):
        raise ValueError("rows/cols/vals must have identical shapes")
    if rows.size and (rows.min() < 0 or cols.min() < 0):
        raise ValueError("negative vertex ids in COO input")
    inferred = int(max(rows.max(), cols.max())) + 1 if rows.size else 0
    n = inferred if n is None else int(n)
    if inferred > n:
        raise ValueError(f"COO ids need n >= {inferred}, got n={n}")
    return n, rows, cols, vals


def coo_symmetrize(
    rows: np.ndarray,
    cols: np.ndarray,
    vals: np.ndarray,
    n: Optional[int] = None,
) -> Tuple[int, np.ndarray, np.ndarray, np.ndarray]:
    """Canonicalize COO triplets to unique upper-triangular ``(i < j)`` form.

    Diagonal entries are dropped; (a, b) and (b, a) collapse to
    ``(min, max)``; duplicate entries for the same pair resolve to the
    *minimum* value (for distance data the shortest measurement wins, and the
    rule is symmetric-input invariant).  Returns ``(n, iu, ju, vals)``.
    """
    n, rows, cols, vals = _checked_coo(rows, cols, vals, n)
    iu = np.minimum(rows, cols)
    ju = np.maximum(rows, cols)
    off = iu != ju
    iu, ju, vals = iu[off], ju[off], vals[off]
    # group duplicates: sort by (pair, value) so the first of each run is the min
    pair = iu * np.int64(n) + ju
    srt = np.lexsort((vals, pair))
    pair, iu, ju, vals = pair[srt], iu[srt], ju[srt], vals[srt]
    first = np.ones(pair.size, dtype=bool)
    np.not_equal(pair[1:], pair[:-1], out=first[1:])
    return n, iu[first], ju[first], vals[first]


def _count_pairs(rows: np.ndarray, cols: np.ndarray, n: int) -> int:
    """Unique off-diagonal pairs among the ids, ``(a, b)`` and ``(b, a)``
    counted once: one sort of the pair code ``min * n + max``, in int32 while
    ``n * n`` fits."""
    code_t = np.int32 if n * n < 2**31 else np.int64
    lo = np.minimum(rows, cols)
    hi = np.maximum(rows, cols)
    off = lo != hi
    code = lo[off].astype(code_t) * code_t(n) + hi[off].astype(code_t)
    if code.size == 0:
        return 0
    code.sort()
    return 1 + int(np.count_nonzero(code[1:] != code[:-1]))


def build_filtration_coo(
    rows: np.ndarray,
    cols: np.ndarray,
    vals: np.ndarray,
    n: Optional[int] = None,
    tau_max: float = np.inf,
    with_dense_order: bool = False,
    return_stats: bool = False,
) -> Union[Filtration, Tuple[Filtration, Dict[str, float]]]:
    """Sparse-input :class:`Filtration`: COO distances in, Dory structure out.

    Memory is ``O(nnz + n)`` throughout; the dense order matrix stays lazy
    (``with_dense_order=False``) so the sparse Dory path runs order-free.
    Non-finite values (the ``contacts_to_distances`` "no information" inf)
    never become edges, even at ``tau_max=inf``.

    Pass the bin count ``n``: left ``None``, it is taken from the largest
    id of *all* triplets, so bins past the last contact of a map (the end of
    a chromosome) vanish, and with them their infinite H0 bars.
    ``compute_ph(coo=...)`` requires it.

    Only the triplets at most ``tau_max`` can become edges (a pair's minimum
    is at most ``tau_max`` exactly when one of its entries is), so the τ
    mask runs first and ``coo_symmetrize`` dedups just those candidates;
    a pair whose minimum is ``-inf`` is then dropped.  With ``return_stats``
    the exact counts come back too: ``coo_entries`` (triplets in),
    ``coo_candidates`` (triplets at most ``tau_max``, the dedup's input),
    ``coo_pairs`` (unique off-diagonal pairs of all triplets) and
    ``coo_edges`` (pairs kept at ``tau_max``).
    """
    with span("coo/build"):
        with span("coo/filter"):
            n, rows, cols, vals = _checked_coo(rows, cols, vals, n)
            cand = vals <= tau_max          # keeps -inf; drops NaN and +inf
        with span("coo/symmetrize"):
            n_pairs = _count_pairs(rows, cols, n) if return_stats else None
            _, iu, ju, lens = coo_symmetrize(rows[cand], cols[cand],
                                             vals[cand], n=n)
            keep = np.isfinite(lens)
            iu, ju, lens = iu[keep], ju[keep], lens[keep]
        with span("coo/edges"):
            filt = filtration_from_edges(n, iu, ju, lens, tau_max,
                                         with_dense_order=with_dense_order)
    if not return_stats:
        return filt
    return filt, {"coo_entries": float(rows.size),
                  "coo_candidates": float(np.count_nonzero(cand)),
                  "coo_pairs": float(n_pairs),
                  "coo_edges": float(filt.n_e)}


def contacts_to_distances(
    counts: np.ndarray,
    alpha: float = -1.0,
    scale: float = 1.0,
) -> np.ndarray:
    """Hi-C contact counts -> distances via the power law ``d = s * c^alpha``.

    The standard polymer-physics conversion (Lieberman-Aiden et al.):
    frequently contacting loci are spatially close.  Zero, negative and
    non-finite counts (a balanced map's NaN for a masked bin) map to
    ``inf`` (no information, no edge).  At ``alpha = -1`` the
    distance is the correctly rounded quotient ``scale / c``, so distances
    order pixels exactly as their contacts do, reversed; ``pow`` may be a
    unit in the last place off and reorder near-equal contacts.
    """
    counts = np.asarray(counts, dtype=np.float64)
    out = np.full(counts.shape, np.inf)
    pos = np.isfinite(counts) & (counts > 0)
    if alpha == -1.0:
        out[pos] = scale / counts[pos]
    else:
        out[pos] = scale * np.power(counts[pos], alpha)
    return out
