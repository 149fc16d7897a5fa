"""The path the benchmark times, held to the host reference engine exactly.

Every cell runs one of two calls: ``compute_ph(points, backend="tiled",
engine="packed")`` on a point cloud, or ``compute_ph(coo=..., engine=
"packed")`` on a contact map.  Here both run against ``engine="single"`` on
the dense distance matrix, over the paper's point generators, and the
diagrams must be equal with no tolerance.  On the CPU the tiled harvest and
the packed reduction take their host branches (the numpy tile refine and
the numpy GF(2) mirrors); tiles of 32 make n <= 120 cross tiles as the
8,192-point cell does at the default 2,048.
"""
import numpy as np
import pytest

from repro.core.diagrams import assert_diagrams_equal
from repro.core.filtration import pairwise_distances
from repro.core.homology import compute_ph
from repro.data import pointclouds as pc

# generator -> (input, tau): n <= 120, and a tau with H1 bars in each
CLOUDS = {
    "circle_points": (lambda: pc.circle_points(120, noise=0.05, seed=1), 0.3),
    "two_circles": (lambda: pc.two_circles(60), 0.5),
    "sphere_points": (lambda: pc.sphere_points(120, seed=2), 0.9),
    "clifford_torus": (lambda: pc.clifford_torus(120, seed=3), 0.8),
    "o3_points": (lambda: pc.o3_points(120, seed=4), 1.4),
    "dragon_like": (lambda: pc.dragon_like(120, seed=5), 1.6),
    "fractal_like": (lambda: pc.fractal_like(120, seed=6), 0.5),
    "genome_like": (lambda: pc.genome_like(120, 4, seed=7), 0.3),
}
DISTANCE_MATRICES = {"fractal_like"}
MODES = ("explicit", "implicit")


def _h1_bars(res) -> int:
    h1 = res.diagrams[1]
    return int((h1[:, 1] > h1[:, 0]).sum())


@pytest.mark.parametrize("mode", MODES)
@pytest.mark.parametrize("maxdim", [1, 2])
@pytest.mark.parametrize("name", sorted(CLOUDS))
def test_tiled_packed_matches_reference(name, maxdim, mode):
    make, tau = CLOUDS[name]
    x = make()
    data = {"dists": x} if name in DISTANCE_MATRICES else {"points": x}
    ref = compute_ph(**data, tau_max=tau, maxdim=maxdim, mode=mode,
                     engine="single", backend="dense")
    got = compute_ph(**data, tau_max=tau, maxdim=maxdim, mode=mode,
                     engine="packed", backend="tiled", tile_m=32, tile_n=32)
    assert _h1_bars(ref) > 0
    assert sorted(got.diagrams) == list(range(maxdim + 1))
    assert_diagrams_equal(got.diagrams, ref.diagrams, atol=0.0)


@pytest.mark.parametrize("mode", MODES)
@pytest.mark.parametrize("condition", ["control", "auxin"])
def test_coo_packed_matches_reference(condition, mode):
    """Each cloud of ``hic_pair`` handed over as the COO triplets of its
    pairs within tau, as a contact map's distances reach ``compute_ph``."""
    tau = 0.3
    control, auxin = pc.hic_pair(120, n_loops=4, seed=7)
    points = control if condition == "control" else auxin
    d = pairwise_distances(points)
    n = d.shape[0]
    rows, cols = np.triu_indices(n, k=1)
    keep = d[rows, cols] <= tau
    rows, cols = rows[keep], cols[keep]
    coo = (rows, cols, d[rows, cols], n)
    ref = compute_ph(dists=d, tau_max=tau, maxdim=1, mode=mode,
                     engine="single")
    got = compute_ph(coo=coo, tau_max=tau, maxdim=1, mode=mode,
                     engine="packed")
    assert _h1_bars(ref) > 0
    assert_diagrams_equal(got.diagrams, ref.diagrams, dims=[0, 1], atol=0.0)
