"""compute_ph's COO entry: a contact map in, diagrams out.

The maps follow a Hi-C model at a size a test holds: an ``s**-1`` decay of
the expected count with the separation ``s``, a lognormal bias per bin,
Poisson counts handed on balanced, a few enriched loop pixels.  Then the
pixel table is made untidy the ways real ones are: duplicate pixels,
``(j, i)`` entries, zero, NaN and infinite contacts, the diagonal, and bins
at the end with no contact at all.  ``compute_ph(coo=...)`` has to give the
diagrams of the dense ``dists=`` call on the materialised matrix and of
``build_filtration_coo`` then ``compute_ph(filtration=...)``, for every
engine, and count the map's entries, candidates, pairs and edges
exactly.
"""
import numpy as np
import pytest

from repro.core import compute_ph
from repro.core.filtration import filtration_from_edges
from repro.obs.trace import Tracer
from repro.scale import build_filtration_coo, contacts_to_distances

TAU = 0.1          # balanced contact at least 10


def contact_map(seed, n=60, width=12, trailing=4):
    """``(bin1, bin2, contact)`` over ``n`` bins; the last ``trailing`` bins
    carry no contact."""
    rng = np.random.default_rng(seed)
    bias = np.exp(rng.normal(0.0, 0.3, n))
    live = n - trailing
    i, s = np.meshgrid(np.arange(live), np.arange(1, width + 1),
                       indexing="ij")
    i, s = i.ravel(), s.ravel()
    keep = i + s < live
    i, j, s = i[keep], (i + s)[keep], s[keep]
    lam = 40.0 / s * bias[i] * bias[j]
    loops = rng.choice(i.size, size=3, replace=False)
    lam[loops] *= 4.0
    counts = rng.poisson(lam)
    hit = counts > 0
    i, j = i[hit], j[hit]
    c = counts[hit] / (bias[i] * bias[j])
    # untidy: duplicates with other values, (j, i) entries, the diagonal,
    # zero, NaN and infinite contacts
    dup = rng.choice(i.size, size=10, replace=False)
    flip = rng.choice(i.size, size=10, replace=False)
    bad = rng.choice(i.size, size=4, replace=False)
    diag = rng.choice(live, size=3, replace=False)
    rows = np.concatenate([i, j[dup], j[flip], i[bad], diag])
    cols = np.concatenate([j, i[dup], i[flip], j[bad], diag])
    vals = np.concatenate([c, c[dup] * rng.uniform(0.5, 2.0, 10),
                           c[flip] * rng.uniform(0.5, 2.0, 10),
                           [0.0, np.nan, np.inf, -1.0],
                           rng.uniform(1.0, 50.0, 3)])
    order = rng.permutation(rows.size)
    return rows[order], cols[order], vals[order], n


def dense_dists(rows, cols, d, n):
    """The materialised matrix: the smallest distance of a pair's entries,
    no edge where a pair has no finite entry."""
    big = 1e18
    m = np.full((n, n), big)
    np.fill_diagonal(m, 0.0)
    for a, b, v in zip(rows.tolist(), cols.tolist(), d.tolist()):
        if a != b and np.isfinite(v):
            lo, hi = min(a, b), max(a, b)
            m[lo, hi] = m[hi, lo] = min(m[lo, hi], v)
    return m


def assert_same_diagrams(a, b):
    assert sorted(a.diagrams) == sorted(b.diagrams)
    for dim in a.diagrams:
        x = a.diagrams[dim][np.lexsort(a.diagrams[dim].T[::-1])]
        y = b.diagrams[dim][np.lexsort(b.diagrams[dim].T[::-1])]
        assert np.array_equal(x, y), dim


@pytest.mark.parametrize("engine,kernels", [("single", False),
                                            ("packed", False),
                                            ("packed", True)])
@pytest.mark.parametrize("seed", [0, 1])
def test_coo_entry_matches_dense_and_prebuilt(monkeypatch, seed, engine,
                                              kernels):
    monkeypatch.setattr("repro.core.packed_reduce._resolve_use_kernels",
                        lambda use_kernels: kernels)
    rows, cols, vals, n = contact_map(seed)
    d = contacts_to_distances(vals)
    kw = dict(tau_max=TAU, maxdim=2, engine=engine)
    got = compute_ph(coo=(rows, cols, d, n), **kw)
    dense = compute_ph(dists=dense_dists(rows, cols, d, n), **kw)
    prebuilt = compute_ph(
        filtration=build_filtration_coo(rows, cols, d, n=n, tau_max=TAU),
        **kw)
    assert_same_diagrams(got, dense)
    assert_same_diagrams(got, prebuilt)
    assert got.stats["n"] == n and got.stats["n_e"] > 0
    assert len(got.diagrams[1]) > 0
    if engine == "packed":
        assert got.stats["h1_use_kernels"] == float(kernels)


def test_trailing_empty_bins_keep_their_infinite_h0_bars():
    rows, cols, vals, n = contact_map(2, trailing=5)
    d = contacts_to_distances(vals)
    res = compute_ph(coo=(rows, cols, d, n), tau_max=TAU, maxdim=0)
    inferred = compute_ph(
        filtration=build_filtration_coo(rows, cols, d, tau_max=TAU),
        maxdim=0)
    essential = int(np.isinf(res.diagrams[0][:, 1]).sum())
    assert essential >= 5
    assert int(np.isinf(inferred.diagrams[0][:, 1]).sum()) == essential - 5


@pytest.mark.parametrize("seed", [3, 4])
def test_coo_counters_are_exact(seed):
    rows, cols, vals, n = contact_map(seed)
    d = contacts_to_distances(vals)
    best = {}
    for a, b, v in zip(rows.tolist(), cols.tolist(), d.tolist()):
        if a != b:
            key = (min(a, b), max(a, b))
            best[key] = min(best.get(key, np.inf), v)
    edges = sum(1 for v in best.values() if np.isfinite(v) and v <= TAU)
    res = compute_ph(coo=(rows, cols, d, n), tau_max=TAU, maxdim=1)
    assert res.stats["coo_entries"] == rows.size
    assert res.stats["coo_candidates"] == sum(1 for v in d if v <= TAU)
    assert res.stats["coo_candidates"] < rows.size
    assert res.stats["coo_pairs"] == len(best)
    assert res.stats["coo_edges"] == edges == res.stats["n_e"]


def untidy_triplets(seed, n=30, size=400, pool=60):
    """Triplets over ``pool`` pairs in both orientations, the diagonal
    among them, with values on a grid of eighths (so ties and values equal
    to a τ of the grid) and NaN, ``+inf`` and ``-inf``."""
    rng = np.random.default_rng(seed)
    pairs = rng.integers(0, n, size=(pool, 2))
    pick = pairs[rng.integers(0, pool, size)]
    flip = rng.random(size) < 0.5
    rows = np.where(flip, pick[:, 1], pick[:, 0])
    cols = np.where(flip, pick[:, 0], pick[:, 1])
    vals = rng.integers(-2, 9, size) * 0.125
    special = rng.choice(size, 30, replace=False)
    vals[special] = np.resize([np.nan, np.inf, -np.inf], 30)
    return rows, cols, vals, n


def reference_filtration(rows, cols, vals, n, tau):
    """Each off-diagonal pair at its smallest value, NaN only where every
    entry is NaN; the pairs whose smallest value is finite and at most
    ``tau`` become edges.  Also the count of unique pairs."""
    best = {}
    for a, b, v in zip(rows.tolist(), cols.tolist(), vals.tolist()):
        if a == b:
            continue
        key = (min(a, b), max(a, b))
        old = best.get(key, np.nan)
        best[key] = v if np.isnan(old) or v < old else old
    kept = sorted((k, v) for k, v in best.items()
                  if np.isfinite(v) and v <= tau)
    iu = np.array([k[0] for k, _ in kept], dtype=np.int64)
    ju = np.array([k[1] for k, _ in kept], dtype=np.int64)
    lens = np.array([v for _, v in kept], dtype=np.float64)
    return filtration_from_edges(n, iu, ju, lens, tau), len(best)


@pytest.mark.parametrize("tau", [0.25, 0.5, np.inf])
@pytest.mark.parametrize("seed", [7, 8, 9])
def test_masking_by_tau_first_keeps_the_edges_of_the_full_dedup(seed, tau):
    rows, cols, vals, n = untidy_triplets(seed)
    assert np.any(vals == tau) or np.isinf(tau)
    filt, st = build_filtration_coo(rows, cols, vals, n=n, tau_max=tau,
                                    return_stats=True)
    ref, n_pairs = reference_filtration(rows, cols, vals, n, tau)
    assert filt.n == ref.n and filt.n_e == ref.n_e > 0
    for field in ("edges", "edge_len", "nbr_vtx", "nbr_edge_ord"):
        assert np.array_equal(getattr(filt, field), getattr(ref, field)), \
            field
    assert st["coo_pairs"] == n_pairs
    assert st["coo_candidates"] == sum(1 for v in vals if v <= tau)
    assert st["coo_edges"] == ref.n_e


def test_inferred_bin_count_counts_an_id_seen_only_beyond_tau():
    rows, cols, vals, n = untidy_triplets(10)
    rows, cols = rows % (n - 1), cols % (n - 1)     # id n - 1 unused
    rows = np.append(rows, n - 1)
    cols = np.append(cols, 0)
    vals = np.append(vals, 10.0)                    # far beyond tau
    filt, st = build_filtration_coo(rows, cols, vals, tau_max=0.5,
                                    return_stats=True)
    assert filt.n == n and filt.degree[n - 1] == 0
    assert st["coo_candidates"] < rows.size


def test_coo_build_is_inside_the_filtration_stopwatch():
    rows, cols, vals, n = contact_map(5)
    tr = Tracer()
    res = compute_ph(coo=(rows, cols, contacts_to_distances(vals), n),
                     tau_max=TAU, maxdim=1, engine="packed", trace=tr)
    tr.assert_balanced()
    by_name = {s.name: s for s in tr.spans}
    filt, build = by_name["ph/filtration"], by_name["coo/build"]
    assert filt.t0 <= build.t0 and build.t1 <= filt.t1
    leaves = [by_name[leaf]
              for leaf in ("coo/filter", "coo/symmetrize", "coo/edges")]
    for s in leaves:
        assert build.t0 <= s.t0 and s.t1 <= build.t1, s.name
    for a, b in zip(leaves, leaves[1:]):
        assert a.t1 <= b.t0, (a.name, b.name)
    assert res.stats["t_filtration"] >= build.t1 - build.t0


@pytest.mark.parametrize("coo,extra", [
    ((np.array([0]), np.array([1]), np.array([0.5])), {}),
    ((np.array([0]), np.array([1]), np.array([0.5]), 3),
     {"points": np.zeros((3, 2))}),
])
def test_coo_entry_refuses_a_missing_bin_count_or_a_second_input(coo, extra):
    with pytest.raises(ValueError):
        compute_ph(coo=coo, tau_max=1.0, maxdim=1, **extra)


def test_contacts_to_distances_reciprocal_is_correctly_rounded():
    c = np.random.default_rng(6).uniform(0.01, 1000.0, 20_000)
    d = contacts_to_distances(c)
    assert np.array_equal(d, 1.0 / c)
    # so distances never order two pixels against their contacts
    assert np.all(np.diff(d[np.argsort(c)]) <= 0.0)
    bad = contacts_to_distances(np.array([0.0, -2.0, np.nan, np.inf]))
    assert np.all(np.isinf(bad))
