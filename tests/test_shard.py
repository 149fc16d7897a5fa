"""Sharded tile harvest (``repro.scale.shard``) vs serial tiled vs dense.

The contract is the tentpole invariant: the mesh-sharded harvest must be
**bit-identical** to the serial tiled build and to dense ``build_filtration``
for every shard/device count.  The host-partitioned numpy path reproduces
any device count's work split without devices, so the identity sweep always
runs; the ``shard_map`` device path is parametrized over 1/2/4 devices and
skips the counts the process doesn't have (CI runs a job under
``XLA_FLAGS=--xla_force_host_platform_device_count=4`` so they all run
there).  Per-device memory accounting is asserted against
``scale.budget``'s a-priori bounds.
"""
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import compute_ph
from repro.core.filtration import build_filtration, pairwise_distances
from repro.scale import (TileStats, build_filtration_sharded,
                         build_filtration_tiled, estimate_tau_max,
                         harvest_edges, harvest_edges_sharded,
                         partition_tiles, sharded_edge_budget, tile_grid,
                         tile_transient_bytes)

FILT_FIELDS = ("edges", "edge_len", "degree", "nbr_vtx", "nbr_vtx_ord",
               "nbr_edge_ord", "nbr_edge_vtx")


def assert_filtrations_identical(a, b, label=""):
    assert a.n == b.n, label
    assert a.n_e == b.n_e, (label, a.n_e, b.n_e)
    for f in FILT_FIELDS:
        assert np.array_equal(getattr(a, f), getattr(b, f)), (label, f)


def _data_mesh(n_devices):
    import jax

    if len(jax.devices()) < n_devices:
        pytest.skip(f"needs {n_devices} devices (run under XLA_FLAGS="
                    f"--xla_force_host_platform_device_count={n_devices})")
    from repro.scale import make_data_mesh
    return make_data_mesh(n_devices)


# ---------------------------------------------------------------------------
# tile partition invariants
# ---------------------------------------------------------------------------

@settings(max_examples=20, deadline=None)
@given(st.data())
def test_partition_covers_grid_exactly_once(data):
    n = data.draw(st.integers(0, 300), label="n")
    tile_m = data.draw(st.sampled_from([3, 16, 64, 257]), label="tile_m")
    tile_n = data.draw(st.sampled_from([4, 23, 128]), label="tile_n")
    n_shards = data.draw(st.integers(1, 7), label="n_shards")
    tiles = tile_grid(n, tile_m, tile_n)
    shards = partition_tiles(n, tile_m, tile_n, n_shards)
    assert len(shards) == n_shards
    flat = [t for s in shards for t in s]
    assert sorted(flat) == sorted(tiles)            # disjoint exact cover
    assert len(set(flat)) == len(flat)
    # round-robin balance: shard sizes differ by at most one tile
    sizes = [len(s) for s in shards]
    assert max(sizes) - min(sizes) <= 1


def test_tile_grid_covers_all_pairs():
    n, tm, tn = 57, 13, 9
    seen = np.zeros((n, n), dtype=int)
    for si, sj in tile_grid(n, tm, tn):
        ei, ej = min(si + tm, n), min(sj + tn, n)
        ii, jj = np.meshgrid(np.arange(si, ei), np.arange(sj, ej),
                             indexing="ij")
        m = ii < jj
        seen[ii[m], jj[m]] += 1
    iu, ju = np.triu_indices(n, k=1)
    assert np.all(seen[iu, ju] == 1)                # each pair exactly once
    assert seen.sum() == len(iu)


def test_partition_rejects_bad_shard_count():
    with pytest.raises(ValueError):
        partition_tiles(10, 4, 4, 0)


# ---------------------------------------------------------------------------
# bit-identity: host-partitioned shards (any count, no devices needed)
# ---------------------------------------------------------------------------

@settings(max_examples=10, deadline=None)
@given(st.data())
def test_sharded_numpy_bit_identical_to_serial_and_dense(data):
    n = data.draw(st.integers(2, 120), label="n")
    d = data.draw(st.integers(1, 4), label="d")
    tile_m = data.draw(st.sampled_from([7, 16, 37, 256]), label="tile_m")
    tile_n = data.draw(st.sampled_from([5, 23, 64]), label="tile_n")
    n_shards = data.draw(st.sampled_from([1, 2, 3, 4, 8]), label="n_shards")
    rng = np.random.default_rng(data.draw(st.integers(0, 2**16), label="seed"))
    pts = rng.normal(size=(n, d))
    if n >= 4:                                      # distance ties
        pts[n // 2] = pts[0]
    tau = data.draw(st.sampled_from([np.inf, 1.0, 2.0]), label="tau")

    dense = build_filtration(points=pts, tau_max=tau)
    serial = build_filtration_tiled(points=pts, tau_max=tau, tile_m=tile_m,
                                    tile_n=tile_n, backend="numpy")
    sharded, stats = build_filtration_sharded(
        points=pts, tau_max=tau, tile_m=tile_m, tile_n=tile_n,
        n_shards=n_shards, backend="numpy", return_stats=True)
    assert_filtrations_identical(dense, serial, "serial vs dense")
    assert_filtrations_identical(serial, sharded,
                                 f"sharded[{n_shards}] vs serial")
    assert sharded.dense_order is None
    assert stats.n_shards == n_shards
    assert stats.tiles_visited == len(tile_grid(n, tile_m, tile_n))


def test_sharded_dists_matrix_matches_dense():
    rng = np.random.default_rng(1)
    pts = rng.normal(size=(64, 3))
    dmat = pairwise_distances(pts)
    tau = float(np.quantile(dmat[np.triu_indices(64, k=1)], 0.5))
    dense = build_filtration(dists=dmat, tau_max=tau)
    for k in (1, 3):
        sharded = build_filtration_sharded(dists=dmat, tau_max=tau,
                                           tile_m=17, tile_n=29, n_shards=k)
        assert_filtrations_identical(dense, sharded, f"dists shards={k}")


def test_sharded_harvest_matches_serial_harvest_arrays():
    rng = np.random.default_rng(2)
    pts = rng.normal(size=(90, 3))
    ref = harvest_edges(points=pts, tau_max=1.5, tile_m=32, tile_n=32,
                        backend="numpy")
    got = harvest_edges_sharded(points=pts, tau_max=1.5, tile_m=32, tile_n=32,
                                n_shards=4, backend="numpy")
    for a, b in zip(ref, got):
        assert np.array_equal(a, b)


# ---------------------------------------------------------------------------
# bit-identity: shard_map device path (1/2/4 virtual devices)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n_devices", [1, 2, 4])
def test_sharded_mesh_bit_identical(n_devices):
    mesh = _data_mesh(n_devices)
    rng = np.random.default_rng(3)
    pts = rng.normal(size=(130, 4)) * 5.0       # larger scale stresses margin
    tau = 6.0
    dense = build_filtration(points=pts, tau_max=tau)
    sharded, stats = build_filtration_sharded(
        points=pts, tau_max=tau, tile_m=48, tile_n=64, mesh=mesh,
        backend="pallas", interpret=True, return_stats=True)
    assert_filtrations_identical(dense, sharded, f"mesh[{n_devices}]")
    assert stats.n_shards == n_devices
    assert stats.mesh_axis == "data"
    assert stats.backend == "pallas"
    assert stats.candidate_pairs >= dense.n_e   # filter over-, never under-
    assert stats.gather_bytes > 0               # round stack was accounted


@pytest.mark.parametrize("n_devices", [2, 4])
def test_sharded_mesh_per_device_budget_respected(n_devices):
    """Per-device peak (TileStats) must land under the a-priori per-device
    budget that ``estimate_tau_max``'s sharded account reserved."""
    mesh = _data_mesh(n_devices)
    rng = np.random.default_rng(7)
    pts = rng.normal(size=(400, 3))
    tile = 64
    budget = 220_000                            # per device
    tau = estimate_tau_max(pts, budget, n_shards=n_devices,
                           tile_m=tile, tile_n=tile, seed=0)
    assert np.isfinite(tau) and tau > 0
    filt, stats = build_filtration_sharded(
        points=pts, tau_max=tau, tile_m=tile, tile_n=tile, mesh=mesh,
        backend="pallas", interpret=True, return_stats=True)
    # a-priori transient bound holds a posteriori (f32 path is smaller than
    # the numpy bound used by the account; fragments ride the edge share)
    transient = tile_transient_bytes(tile, tile, n_devices)
    assert stats.peak_tile_bytes + stats.gather_bytes <= transient
    # per-device account: duplicated vertex arrays + edge share under budget
    assert stats.per_device_base_bytes() <= 1.15 * budget
    # the global edge count respects the sharded (scaled) account
    global_edges = sharded_edge_budget(len(pts), budget, n_devices,
                                       tile, tile)
    assert filt.n_e <= 1.1 * global_edges + 16


def test_per_device_stats_numpy_path():
    """Host-partitioned path fills the same per-device accounting fields."""
    pts = np.random.default_rng(5).normal(size=(200, 3))
    _, stats = build_filtration_sharded(
        points=pts, tau_max=1.0, tile_m=64, tile_n=64, n_shards=4,
        backend="numpy", return_stats=True)
    assert stats.per_device_peak_bytes() >= stats.peak_tile_bytes
    assert stats.shard_peak_harvest_bytes > 0
    # per-shard fragments are a fraction of the whole harvest
    assert stats.shard_peak_harvest_bytes < stats.harvest_bytes
    assert stats.per_device_base_bytes() < stats.base_memory_bytes


# ---------------------------------------------------------------------------
# budget accounting (scale.budget sharded forms)
# ---------------------------------------------------------------------------

def test_tile_transient_bytes_accounts_gather():
    serial = tile_transient_bytes(64, 64, n_shards=1)
    sharded = tile_transient_bytes(64, 64, n_shards=4)
    assert sharded > serial                     # gather stack is charged
    assert sharded - serial >= 4 * 64 * 64 * 4  # >= D f32 output tiles
    # the stacked input blocks scale with the real point dimension
    assert tile_transient_bytes(64, 64, n_shards=4, d=32) \
        == sharded + 4 * (64 + 64) * (32 - 8) * 4


@pytest.mark.parametrize("n_devices", [2])
def test_sharded_mesh_wide_points_bound_holds(n_devices):
    """d > 8 clouds: the a-priori transient bound must use the real point
    dimension (regression — a hardcoded d=8 under-reserved the gather)."""
    mesh = _data_mesh(n_devices)
    rng = np.random.default_rng(17)
    pts = rng.normal(size=(150, 32))
    _, stats = build_filtration_sharded(
        points=pts, tau_max=4.0, tile_m=64, tile_n=64, mesh=mesh,
        backend="pallas", interpret=True, return_stats=True)
    bound = tile_transient_bytes(64, 64, n_shards=n_devices, d=32)
    assert stats.peak_tile_bytes + stats.gather_bytes <= bound


def test_mesh_and_conflicting_n_shards_rejected():
    mesh = _data_mesh(1)
    pts = np.zeros((8, 2))
    with pytest.raises(ValueError):
        harvest_edges_sharded(points=pts, mesh=mesh, n_shards=3,
                              tile_m=4, tile_n=4)
    # agreeing values are fine
    iu, _, _ = harvest_edges_sharded(points=pts, mesh=mesh, n_shards=1,
                                     tile_m=4, tile_n=4)
    assert iu.size == 0 or iu.ndim == 1


def test_sharded_edge_budget_scales_and_guards():
    n = 10_000
    per_dev = 20_000_000                        # budget >> tile transient
    e1 = sharded_edge_budget(n, per_dev, 1, 256, 256)
    e4 = sharded_edge_budget(n, per_dev, 4, 256, 256)
    assert e4 > e1                              # fleet affords more edges
    assert e4 <= 4 * e1                         # but pays vertex duplication
    with pytest.raises(ValueError):
        sharded_edge_budget(n, 1000, 4, 1024, 1024)   # tile doesn't even fit


def test_estimate_tau_max_sharded_needs_tiles_and_shrinks():
    pts = np.random.default_rng(0).normal(size=(300, 3))
    with pytest.raises(ValueError):
        estimate_tau_max(pts, 100_000, n_shards=2)    # tile dims required
    # the sharded account charges tile + gather per device before scaling
    # the edge share up by the device count (the serial form charged
    # nothing, under-reserving on every device of a mesh); whether the net
    # tau lands above or below the serial estimate depends on which effect
    # wins, but it must be monotone in the transient:
    tau_2dev = estimate_tau_max(pts, 100_000, n_shards=2,
                                tile_m=32, tile_n=32, seed=0)
    # a fatter resident tile eats more of the per-device budget
    tau_fat_tile = estimate_tau_max(pts, 100_000, n_shards=2,
                                    tile_m=48, tile_n=48, seed=0)
    assert tau_fat_tile <= tau_2dev
    # a tile transient bigger than the whole per-device budget is an error
    with pytest.raises(ValueError):
        estimate_tau_max(pts, 100_000, n_shards=2, tile_m=96, tile_n=96)
    # more devices at a generous per-device budget afford more global edges
    tau_4dev = estimate_tau_max(pts, 300_000, n_shards=4,
                                tile_m=64, tile_n=64, seed=0)
    tau_1dev_eq = estimate_tau_max(pts, 300_000 - tile_transient_bytes(
        64, 64, 4), seed=0)
    assert tau_4dev >= tau_1dev_eq


# ---------------------------------------------------------------------------
# compute_ph(..., mesh=...) end to end
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n_devices", [1, 2])
def test_compute_ph_mesh_matches_serial(n_devices):
    mesh = _data_mesh(n_devices)
    rng = np.random.default_rng(11)
    pts = rng.normal(size=(150, 3))
    got = compute_ph(points=pts, tau_max=1.2, maxdim=2, backend="tiled",
                     mesh=mesh, tile_m=64, tile_n=64)
    ref = compute_ph(points=pts, tau_max=1.2, maxdim=2)
    for dim in (0, 1, 2):
        assert np.array_equal(got.diagrams[dim], ref.diagrams[dim]), dim
    assert got.stats["n_shards"] == n_devices
    assert got.stats["per_device_peak_bytes"] > 0


def test_compute_ph_mesh_with_budget():
    mesh = _data_mesh(1)
    rng = np.random.default_rng(13)
    pts = rng.normal(size=(180, 3))
    res = compute_ph(points=pts, maxdim=1, backend="tiled", mesh=mesh,
                     memory_budget_bytes=150_000, tile_m=64, tile_n=64)
    assert "tau_max_estimated" in res.stats
    assert res.stats["per_device_base_bytes"] <= 1.15 * 150_000
    ref = compute_ph(points=pts, tau_max=res.stats["tau_max_estimated"],
                     maxdim=1)
    for dim in (0, 1):
        assert np.array_equal(res.diagrams[dim], ref.diagrams[dim])


def test_compute_ph_dense_rejects_mesh():
    pts = np.zeros((4, 2))
    with pytest.raises(ValueError):
        compute_ph(points=pts, backend="dense", mesh=object())
    # a prebuilt filtration can't be sharded either — reject, don't ignore
    filt = build_filtration(points=np.random.default_rng(0).normal(
        size=(10, 2)), tau_max=1.0)
    with pytest.raises(ValueError):
        compute_ph(filtration=filt, mesh=object())


def test_sharded_pallas_without_mesh_runs_pallas():
    """backend='pallas' + n_shards (no mesh) must actually run the f32
    candidate path per shard — not silently fall back to numpy while
    TileStats claims otherwise."""
    rng = np.random.default_rng(21)
    pts = rng.normal(size=(90, 3)) * 3.0
    dense = build_filtration(points=pts, tau_max=2.5)
    sharded, stats = build_filtration_sharded(
        points=pts, tau_max=2.5, tile_m=32, tile_n=32, n_shards=3,
        backend="pallas", interpret=True, return_stats=True)
    assert_filtrations_identical(dense, sharded, "host pallas shards")
    assert stats.backend == "pallas"
    assert stats.candidate_pairs >= dense.n_e   # the filter really ran


# ---------------------------------------------------------------------------
# budgeted reduction (first bite): h2 cap + pivot-store spill
# ---------------------------------------------------------------------------

def test_h2_columns_budget_cap_identical():
    from repro.core.homology import h2_columns, make_h1_adapter
    from repro.core.reduction import reduce_dimension

    rng = np.random.default_rng(6)
    pts = rng.normal(size=(40, 3))
    filt = build_filtration(points=pts, tau_max=1.5)
    adapter = make_h1_adapter(filt, sparse=True)
    cols1 = np.arange(filt.n_e - 1, -1, -1, dtype=np.int64)
    res1 = reduce_dimension(adapter, cols1, cleared=None)
    ref = h2_columns(filt, res1.pivot_lows, sparse=True)
    for budget in (1, 10_000, 10**9):
        got = h2_columns(filt, res1.pivot_lows, sparse=True,
                         memory_budget_bytes=budget)
        assert np.array_equal(ref, got), budget


def test_reduction_store_spill_same_diagrams():
    rng = np.random.default_rng(8)
    pts = rng.normal(size=(60, 3))
    ref = compute_ph(points=pts, tau_max=1.5, maxdim=2)
    capped = compute_ph(points=pts, tau_max=1.5, maxdim=2,
                        memory_budget_bytes=1_000, backend="dense")
    for dim in (0, 1, 2):
        assert np.array_equal(ref.diagrams[dim], capped.diagrams[dim]), dim
    assert capped.stats["h1_n_spilled"] > 0     # the cap actually engaged


def test_reduction_store_spill_sweep():
    """Mixed explicit/implicit stores must re-materialize *complete*
    δ-expansions: a spilled column that absorbed explicit-stored owners
    depends on their tracked gens (regression — a sweep like this caught
    incomplete expansions producing wrong addends)."""
    for seed in range(12):
        rng = np.random.default_rng(seed)
        pts = rng.normal(size=(36, 3))
        ref = compute_ph(points=pts, tau_max=1.8, maxdim=2)
        for budget in (200, 1_500):
            capped = compute_ph(points=pts, tau_max=1.8, maxdim=2,
                                memory_budget_bytes=budget, backend="dense")
            for dim in (0, 1, 2):
                assert np.array_equal(ref.diagrams[dim],
                                      capped.diagrams[dim]), (seed, budget,
                                                              dim)
