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
from repro.obs.trace import Tracer, tracing
from repro.scale import make_data_mesh, tile_grid

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


# ---------------------------------------------------------------------------
# torus4 at the density of its cell (n = 50,000, tau 0.025): mean degree
# n * pi * tau^2 / (2 * pi^2) held at about 5 by tau = 0.025 * sqrt(50,000 / n)
# ---------------------------------------------------------------------------

def _torus4_tau(n: int) -> float:
    return 0.025 * np.sqrt(50_000 / n)


@pytest.fixture
def device_path(monkeypatch):
    """The chip's branches on the CPU: the Pallas tile harvest and the gf2
    kernels, both in interpret mode."""
    import repro.core.packed_reduce as packed_reduce
    import repro.scale.tiles as tiles

    monkeypatch.setattr(tiles, "_resolve_backend",
                        lambda b: "pallas" if b == "auto" else b)
    monkeypatch.setattr(packed_reduce, "_resolve_use_kernels",
                        lambda u: True if u is None else bool(u))


@pytest.mark.parametrize("mode", MODES)
def test_torus4_across_ragged_tiles_matches_reference(mode):
    """600 points in tiles of 64: 55 tiles, the last row and column of
    them 24 wide, as 50,000 in tiles of 2,048 leaves 848."""
    x = pc.clifford_torus(600, seed=0)
    tau = _torus4_tau(600)
    ref = compute_ph(points=x, tau_max=tau, maxdim=1, mode=mode,
                     engine="single", backend="dense")
    got = compute_ph(points=x, tau_max=tau, maxdim=1, mode=mode,
                     engine="packed", backend="tiled", tile_m=64, tile_n=64)
    assert _h1_bars(ref) > 0
    assert got.stats["harvest_tiles"] == len(tile_grid(600, 64, 64)) == 55
    assert_diagrams_equal(got.diagrams, ref.diagrams, atol=0.0)


def _tile_bytes(n: int, tm: int, tn: int) -> int:
    return sum((min(si + tm, n) - si) * (min(sj + tn, n) - sj) * 4
               for si, sj in tile_grid(n, tm, tn))


def test_torus4_device_path_matches_reference(device_path):
    n, tm = 200, 64
    x = pc.clifford_torus(n, seed=1)
    tau = _torus4_tau(n)
    ref = compute_ph(points=x, tau_max=tau, maxdim=1, engine="single",
                     backend="dense")
    got = compute_ph(points=x, tau_max=tau, maxdim=1, engine="packed",
                     backend="tiled", tile_m=tm, tile_n=tm)
    assert got.stats["harvest_pallas"] == 1.0
    assert got.stats["h1_use_kernels"] == 1.0
    assert _h1_bars(ref) > 0
    assert_diagrams_equal(got.diagrams, ref.diagrams, atol=0.0)


HARVEST_COUNTERS = ("harvest_tiles", "harvest_fetch_bytes",
                    "harvest_candidates")


@pytest.mark.parametrize("n,tile", [(200, 64), (130, 32)])
def test_harvest_counters_on_the_pallas_path(device_path, n, tile):
    x = pc.clifford_torus(n, seed=2)
    got = compute_ph(points=x, tau_max=_torus4_tau(n), maxdim=1,
                     engine="packed", backend="tiled", tile_m=tile,
                     tile_n=tile)
    assert got.stats["harvest_tiles"] == len(tile_grid(n, tile, tile))
    assert got.stats["harvest_fetch_bytes"] == _tile_bytes(n, tile, tile)
    assert got.stats["harvest_candidates"] >= got.stats["n_e"] > 0


def test_harvest_counters_on_the_numpy_path():
    n, tile = 200, 64
    got = compute_ph(points=pc.clifford_torus(n, seed=2),
                     tau_max=_torus4_tau(n), maxdim=1, engine="packed",
                     backend="tiled", tile_m=tile, tile_n=tile)
    assert got.stats["harvest_pallas"] == 0.0
    assert got.stats["harvest_tiles"] == len(tile_grid(n, tile, tile))
    assert got.stats["harvest_fetch_bytes"] == 0.0
    assert got.stats["harvest_candidates"] == 0.0


def test_sharded_build_counts_as_the_serial_build(device_path):
    """The mesh's rounds (one device here) fetch, visit and re-measure
    what the serial stream does, tile for tile."""
    n, tile = 200, 64
    x = pc.clifford_torus(n, seed=3)
    kw = dict(tau_max=_torus4_tau(n), maxdim=1, engine="packed",
              backend="tiled", tile_m=tile, tile_n=tile)
    serial = compute_ph(points=x, **kw)
    sharded = compute_ph(points=x, mesh=make_data_mesh(1), **kw)
    assert sharded.stats["n_shards"] == 1.0
    for k in HARVEST_COUNTERS:
        assert sharded.stats[k] == serial.stats[k] > 0, k
    assert_diagrams_equal(sharded.diagrams, serial.diagrams, atol=0.0)


def test_threshold_and_remeasure_spans_once_per_tile(device_path):
    n, tile = 200, 64
    tr = Tracer()
    with tracing(tr):
        compute_ph(points=pc.clifford_torus(n, seed=4),
                   tau_max=_torus4_tau(n), maxdim=1, engine="packed",
                   backend="tiled", tile_m=tile, tile_n=tile)
    tr.assert_balanced()
    refines = [s for s in tr.spans if s.name == "harvest/refine"]
    assert len(refines) == len(tile_grid(n, tile, tile))
    for leaf in ("harvest/threshold", "harvest/remeasure"):
        spans = [s for s in tr.spans if s.name == leaf]
        assert len(spans) == len(refines), leaf
        # each inside its own tile's refine
        for sp, outer in zip(sorted(spans, key=lambda s: s.t0),
                             sorted(refines, key=lambda s: s.t0)):
            assert outer.t0 <= sp.t0 <= sp.t1 <= outer.t1, leaf
