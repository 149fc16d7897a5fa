"""Distributed packed reduction (``repro.core.packed_reduce`` + the shared
``repro.core.pivot_cache``): bit-identity across shard counts, transports,
modes, cadences and store budgets.

The contract is the tentpole invariant: partitioning the column batches of
a dimension over ``P`` shards — concurrent phases against a replica pivot
store fed by Elias–Fano wire payloads, tournament catch-up, exact commit
sweeps — must produce diagrams **bit-identical** to every single-device
engine, for every ``P``, exchange cadence and storage mode.  The
host-partitioned driver reproduces any device count's work split without
devices, so the identity sweep always runs; the mesh-collective transport
is parametrized over 1/2/4 virtual devices and skips counts the process
doesn't have (CI's ``reduce-bench-4dev`` job runs them all under
``XLA_FLAGS=--xla_force_host_platform_device_count=4``).

Also here: the pivot-cache memo/codec property tests (S1) and the
near-clique coboundary fast-path guard (dense-grid tie-heavy identity).
"""
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import build_filtration, compute_ph
from repro.core.coboundary import edge_cobdy_ns, edge_cobdy_sparse
from repro.core.diagrams import assert_diagrams_equal
from repro.core.pairing import EMPTY_KEY
from repro.core.pivot_cache import (PackedPivotCache, decode_commit_delta,
                                    encode_commit_delta)
from repro.data.pointclouds import fractal_like

DIMS = (0, 1, 2)


def tie_heavy_cloud(seed, n=16):
    """Integer grid points: many exactly-equal pairwise distances, the
    adversarial regime for any ordering-sensitive reduction schedule."""
    rng = np.random.default_rng(seed)
    return rng.integers(0, 4, size=(n, 3)).astype(np.float64)


def _data_mesh(n_devices):
    import jax

    if len(jax.devices()) < n_devices:
        pytest.skip(f"needs {n_devices} devices (run under XLA_FLAGS="
                    f"--xla_force_host_platform_device_count={n_devices})")
    from repro.scale import make_data_mesh
    return make_data_mesh(n_devices)


def _assert_same_diagrams(ref, got, label):
    for dim in DIMS:
        assert np.array_equal(ref.diagrams[dim], got.diagrams[dim]), \
            (label, dim)


# ---------------------------------------------------------------------------
# bit-identity sweep: host-partitioned shards (any count, no devices needed)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("mode", ["explicit", "implicit"])
@pytest.mark.parametrize("n_shards", [1, 2, 4])
def test_dist_packed_matches_single(mode, n_shards):
    dists = fractal_like(40, seed=3)
    ref = compute_ph(dists=dists, maxdim=2, engine="single", mode=mode)
    got = compute_ph(dists=dists, maxdim=2, engine="packed", mode=mode,
                     n_shards=n_shards, batch_size=64)
    _assert_same_diagrams(ref, got, f"P={n_shards} {mode}")
    assert got.stats["h1_n_shards"] == n_shards
    if n_shards > 1:   # H2 is the long pass: exchanges must really happen
        rounds = got.stats["h1_n_exchange_rounds"] \
            + got.stats["h2_n_exchange_rounds"]
        wire = got.stats["h1_exchange_bytes"] + got.stats["h2_exchange_bytes"]
        assert rounds >= 1 and wire > 0


@pytest.mark.parametrize("mode", ["explicit", "implicit"])
def test_dist_packed_tie_heavy(mode):
    """Exactly-equal filtration values: the canonical-pairing argument says
    any left-to-right GF(2) schedule pairs identically — the distributed
    schedule included.  Ties are where a wrong tie-break would show."""
    pts = tie_heavy_cloud(5, n=18)
    ref = compute_ph(points=pts, maxdim=2, engine="single", mode=mode)
    for P in (2, 3):
        got = compute_ph(points=pts, maxdim=2, engine="packed", mode=mode,
                         n_shards=P, batch_size=32)
        _assert_same_diagrams(ref, got, f"ties P={P} {mode}")


@pytest.mark.parametrize("budget", [None, 4096])
@pytest.mark.parametrize("n_shards", [2, 4])
def test_dist_packed_store_budget(n_shards, budget):
    """Spill-to-implicit under a store budget must not perturb distributed
    diagrams (spill decisions are per-store, replicas included)."""
    dists = fractal_like(36, seed=9)
    ref = compute_ph(dists=dists, maxdim=2, engine="single")
    got = compute_ph(dists=dists, maxdim=2, engine="packed",
                     n_shards=n_shards, batch_size=48,
                     memory_budget_bytes=budget)
    _assert_same_diagrams(ref, got, f"P={n_shards} budget={budget}")


@pytest.mark.parametrize("exchange_every", [1, 3, 8])
def test_dist_packed_cadence_independent(exchange_every):
    """Diagrams can't depend on how many supersteps ride between pivot
    exchanges — the cadence only moves wall time and wire bytes."""
    dists = fractal_like(36, seed=11)
    ref = compute_ph(dists=dists, maxdim=2, engine="packed", n_shards=1)
    got = compute_ph(dists=dists, maxdim=2, engine="packed", n_shards=4,
                     mode="implicit", batch_size=48,
                     exchange_every=exchange_every)
    _assert_same_diagrams(ref, got, f"ee={exchange_every}")


@settings(max_examples=8, deadline=None)
@given(seed=st.integers(0, 2**16), n_shards=st.integers(1, 5),
       mode=st.sampled_from(["explicit", "implicit"]))
def test_dist_packed_hypothesis(seed, n_shards, mode):
    rng = np.random.default_rng(seed)
    pts = rng.normal(size=(int(rng.integers(10, 26)), 3))
    ref = compute_ph(points=pts, maxdim=2, engine="single", mode=mode)
    got = compute_ph(points=pts, maxdim=2, engine="packed", mode=mode,
                     n_shards=n_shards, batch_size=16)
    _assert_same_diagrams(ref, got, f"hyp P={n_shards} {mode}")


# ---------------------------------------------------------------------------
# bit-identity: mesh-collective transport (1/2/4 virtual devices)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n_devices", [1, 2, 4])
def test_dist_packed_mesh_bit_identical(n_devices):
    """Same work split as the host driver, but the pivot exchange really
    cross-ships through ``jax.lax.all_gather`` under ``shard_map``."""
    mesh = _data_mesh(n_devices)
    dists = fractal_like(40, seed=3)
    ref = compute_ph(dists=dists, maxdim=2, engine="single")
    got = compute_ph(dists=dists, maxdim=2, engine="packed", mesh=mesh,
                     batch_size=64, mode="implicit")
    _assert_same_diagrams(ref, got, f"mesh[{n_devices}]")
    assert got.stats["h1_n_shards"] == n_devices


def test_dist_packed_mesh_vs_host_same_split():
    """Mesh transport and the host loop-back are the same partition: every
    counter that describes the work split must agree exactly."""
    mesh = _data_mesh(2)
    dists = fractal_like(36, seed=7)
    a = compute_ph(dists=dists, maxdim=2, engine="packed", mesh=mesh,
                   batch_size=48)
    b = compute_ph(dists=dists, maxdim=2, engine="packed", n_shards=2,
                   batch_size=48)
    _assert_same_diagrams(a, b, "mesh vs host")
    for k in ("h1_n_supersteps", "h1_n_tournament_reductions",
              "h2_n_supersteps", "h2_n_tournament_reductions",
              "h1_n_reductions", "h2_n_reductions"):
        assert a.stats[k] == b.stats[k], k


# ---------------------------------------------------------------------------
# pivot cache: memo + codec properties (S1)
# ---------------------------------------------------------------------------

def test_cache_position_memo_epoch_invalidates():
    cache = PackedPivotCache()
    pos = np.array([3, 17, 64], dtype=np.int64)
    assert cache.get_positions(7) is None
    cache.put_positions(7, pos)
    np.testing.assert_array_equal(cache.get_positions(7), pos)
    assert cache.n_packs == 1 and cache.n_pack_hits == 1
    cache.bump_epoch()                      # layout changed: memo is stale
    assert cache.get_positions(7) is None
    assert cache.n_pack_hits == 1           # a miss is not a hit


def test_cache_column_memo_fifo_budget():
    cache = PackedPivotCache(budget_bytes=3 * 8 * 4)   # room for ~3 columns
    for low in range(6):
        cache.put_column(low, np.arange(4, dtype=np.int64))
    assert cache.column_bytes <= 3 * 8 * 4
    assert cache.n_col_evictions >= 3
    assert cache.get_column(0) is None      # FIFO: oldest went first
    assert cache.get_column(5) is not None  # newest survives


@settings(max_examples=25, deadline=None)
@given(seed=st.integers(0, 2**31 - 1))
def test_commit_delta_roundtrip(seed):
    """The replication codec is lossless for any mix of explicit/implicit
    records, including empty columns and empty deltas."""
    rng = np.random.default_rng(seed)
    records = []
    for i in range(int(rng.integers(0, 12))):
        mode = "explicit" if rng.integers(2) else "implicit"
        keys = np.unique(rng.integers(0, 2**40, size=rng.integers(0, 30))
                            .astype(np.int64))
        records.append({
            "low": int(rng.integers(0, 2**40)),
            "col_id": int(rng.integers(0, 2**32)),
            "mode": mode,
            "column": keys if mode == "explicit" else None,
            "gens": rng.integers(0, 2**31, size=rng.integers(0, 9))
                       .astype(np.int64),
        })
    back = decode_commit_delta(encode_commit_delta(records))
    assert len(back) == len(records)
    for r, g in zip(records, back):
        assert g["low"] == r["low"] and g["col_id"] == r["col_id"]
        assert g["mode"] == r["mode"]
        if r["mode"] == "explicit":
            np.testing.assert_array_equal(g["column"], r["column"])
        else:
            assert g["column"] is None
        np.testing.assert_array_equal(g["gens"], np.sort(r["gens"]))


def test_cache_hit_rate_on_workload():
    """The S1 contract: with the shared cache each stored pivot is packed
    about once — packs stay bounded by the stored-pivot count (cleared
    columns never pack at all) instead of growing with consumer count."""
    dists = fractal_like(48, seed=0)
    res = compute_ph(dists=dists, maxdim=2, engine="packed",
                     mode="implicit", batch_size=64)
    s = res.stats
    for dim in ("h1", "h2"):
        packs = s[f"{dim}_cache_n_packs"]
        stored = s[f"{dim}_n_stored_columns"] + s[f"{dim}_n_spilled"]
        assert packs <= stored + 1, (dim, packs, stored)
        # each committed pivot's column is enumerated at most once (the
        # memo absorbs every later request; without it the count grows
        # with the number of consuming rounds)
        mats = s[f"{dim}_cache_n_materializations"]
        assert mats <= s[f"{dim}_n_pairs"] + 1, (dim, mats)
    # and the memo really serves repeat requests on the long pass
    assert s["h2_cache_n_mat_hits"] > 0


@settings(max_examples=20, deadline=None)
@given(seed=st.integers(0, 2**31 - 1))
def test_wire_payload_stack_roundtrip(seed):
    """The collective wire buffer is lossless and power-of-two bucketed."""
    from repro.kernels.gf2 import stack_wire_payloads, unstack_wire_payloads

    rng = np.random.default_rng(seed)
    payloads = [rng.integers(0, 2**32, size=rng.integers(0, 3000),
                             dtype=np.uint64).astype(np.uint32)
                for _ in range(int(rng.integers(1, 6)))]
    buf, lens = stack_wire_payloads(payloads, min_words=64)
    L = buf.shape[1]
    assert L & (L - 1) == 0 and L >= max(64, max(lens, default=1))
    back = unstack_wire_payloads(buf, lens)
    for a, b in zip(payloads, back):
        np.testing.assert_array_equal(a, b)


# ---------------------------------------------------------------------------
# near-clique coboundary fast path (S2): dense-grid identity guard
# ---------------------------------------------------------------------------

def test_edge_cobdy_ns_matches_sparse_rows():
    """The compacted case-1/case-2 assembly must emit exactly the sorted
    key rows the old full-row sort produced — checked against the sparse
    path, which sorts unconditionally."""
    pts = tie_heavy_cloud(2, n=20)          # grid: near-clique neighborhoods
    filt = build_filtration(points=pts, tau_max=np.inf)
    orders = np.arange(filt.n_e, dtype=np.int64)
    ns = edge_cobdy_ns(filt, orders)
    sp = edge_cobdy_sparse(filt, orders)
    for r in range(filt.n_e):
        a = ns[r][ns[r] != EMPTY_KEY]
        b = sp[r][sp[r] != EMPTY_KEY]
        np.testing.assert_array_equal(a, b)
        assert np.all(np.diff(a) > 0)       # strictly ascending, no dupes


@pytest.mark.parametrize("engine", ["single", "packed"])
def test_dense_grid_ns_vs_sparse_diagrams(engine):
    """End-to-end guard: the NS (dense order) pipeline with the fast path
    and the order-free sparse pipeline agree on a tie-heavy grid."""
    pts = tie_heavy_cloud(4, n=16)
    ns = compute_ph(points=pts, maxdim=2, engine=engine, sparse=False)
    sp = compute_ph(points=pts, maxdim=2, engine=engine, sparse=True)
    _assert_same_diagrams(ns, sp, f"ns vs sparse [{engine}]")


# ---------------------------------------------------------------------------
# dists matrix through the sharded device tile path (S3)
# ---------------------------------------------------------------------------

def test_sharded_device_dists_bit_identical():
    from repro.scale import build_filtration_sharded, build_filtration_tiled

    mesh = _data_mesh(2)
    rng = np.random.default_rng(2)
    pts = rng.normal(size=(80, 3))
    d = np.sqrt(((pts[:, None] - pts[None, :]) ** 2).sum(-1))
    np.fill_diagonal(d, 0.0)
    tau = float(np.quantile(d[np.triu_indices(80, k=1)], 0.4))
    ref = build_filtration_tiled(dists=d, tau_max=tau, tile_m=32, tile_n=32)
    got, st_ = build_filtration_sharded(dists=d, tau_max=tau, tile_m=32,
                                        tile_n=32, mesh=mesh,
                                        return_stats=True)
    assert np.array_equal(ref.edges, got.edges)
    assert np.array_equal(ref.edge_len, got.edge_len)
    assert st_.gather_bytes > 0             # the device rounds really ran
