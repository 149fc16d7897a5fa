"""Sharded tiled filtration harvest over the ``data`` mesh axis.

``tiles.py`` streams the ``(tile_m, tile_n)`` block grid serially, so wall
time on million-point clouds is bounded by one device even when the paper's
``(3n + 12 n_e) * 4``-byte account already fits.  This module partitions the
upper-triangular tile grid **round-robin** across the ``data`` mesh axis and
harvests all shards' tiles concurrently — the distributed-reduction route
past the single-device wall (cf. DIPHA's spectral-sequence distribution,
arXiv:1310.0710).

Execution model (one *round* = one tile per device):

* the FLOP-dominant f32 candidate tiles are computed **on device** under
  ``jax.shard_map``: point blocks for the round are stacked on a leading
  axis sharded over ``data`` (specs from :func:`tile_specs`)
  and each device runs the Pallas ``pairwise_sq_dists`` kernel on its own
  block pair — no cross-device communication inside a round;
* the round's stacked f32 output is gathered back to the host (this is the
  ``gather_bytes`` transient in :class:`~repro.scale.tiles.TileStats`),
  where each tile's candidates get the exact f64 re-measure
  (``pair_sq_dists``) and become a per-shard COO fragment — COO fragments
  are variably sized, which is exactly what cannot live under ``jit``;
* fragments from all shards merge through the single canonical
  ``(length, i, j)`` lexsort (``merge_edge_chunks``).

The ``numpy`` backend shards the same tile partition on the host (no mesh
required — ``n_shards`` alone reproduces any device count's work split),
which is what the bit-identity tests sweep.

**Bit-identity is structural, not numeric luck**: every unordered pair
(i < j) lives in exactly one tile, every tile in exactly one shard, each
tile's exact lengths come from the same fixed-order f64 kernels as the
serial and dense paths, and the final lexsort is a total order — so the
sorted edge list (and hence the whole :class:`Filtration`) is bit-identical
for every device count, including 1 and the serial/dense builders.
"""
from __future__ import annotations

from typing import List, Optional, Tuple

import numpy as np

from ..core.filtration import filtration_from_edges
from ..obs.trace import span
from .tiles import (DEFAULT_TILE, TileStats, _f32_dists_threshold,
                    _f32_threshold, _refine_f32_dists_tile, _refine_f32_tile,
                    _resolve_backend, iter_tile_edges, merge_edge_chunks,
                    tile_grid)

__all__ = ["build_filtration_sharded", "harvest_edges_sharded",
           "make_data_mesh", "partition_tiles", "shard_of_mesh", "tile_specs"]


def partition_tiles(n: int, tile_m: int, tile_n: int,
                    n_shards: int) -> List[List[Tuple[int, int]]]:
    """Round-robin partition of the upper-triangular tile grid.

    Tile ``t`` (row-major :func:`~repro.scale.tiles.tile_grid` order) goes to
    shard ``t % n_shards``; consecutive grid tiles land on different shards,
    which balances the diagonal tiles (cheaper: half masked out) across
    devices instead of clustering them on one.  Every tile appears in
    exactly one shard — the disjoint-cover invariant the bit-identity
    guarantee rests on.
    """
    if n_shards < 1:
        raise ValueError(f"n_shards must be >= 1, got {n_shards}")
    tiles = tile_grid(n, tile_m, tile_n)
    return [tiles[k::n_shards] for k in range(n_shards)]


def make_data_mesh(n_devices: Optional[int] = None):
    """1-D ``(data,)`` mesh over the first ``n_devices`` (default: all).

    The mesh shape this module and ``compute_ph(..., backend="tiled",
    mesh=...)`` expect for sharding the tile harvest; on a CPU host,
    virtual devices come from
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` (set before jax
    initializes — the 4-device CI job does this).
    """
    import jax
    from jax.sharding import Mesh

    devices = jax.devices()
    n = len(devices) if n_devices is None else int(n_devices)
    if len(devices) < n:
        raise RuntimeError(f"need {n} devices for a (data={n},) mesh, "
                           f"have {len(devices)}")
    return Mesh(np.array(devices[:n]), ("data",))


def tile_specs(mesh):
    """Specs for the sharded tile-harvest ``shard_map``.

    One round stacks each device's ``(tile_m, d)`` / ``(tile_n, d)`` point
    blocks on a leading axis of size ``data``; that leading axis shards over
    the innermost data axis (``data`` when present, else ``pod``) and
    everything else — including any ``model`` axis present — sees the work
    replicated (tile harvesting is pure data parallelism; other axes
    contribute nothing and must not split a tile).

    Returns ``(in_specs, out_specs, axis_name)`` ready to pass to
    ``jax.shard_map``: ``in_specs`` for the (x-blocks, y-blocks) pair,
    ``out_specs`` for the stacked ``(data, tile_m, tile_n)`` output.
    """
    from jax.sharding import PartitionSpec

    names = tuple(getattr(mesh, "axis_names", ()))
    data_axes = [a for a in ("pod", "data") if a in names]
    if not data_axes:
        raise ValueError(f"mesh axes {names} have no data axis to shard "
                         "over")
    spec = PartitionSpec(data_axes[-1])
    return (spec, spec), spec, data_axes[-1]


def shard_of_mesh(mesh) -> Tuple[str, int]:
    """(axis name, size) of the mesh axis tiles shard over (the data axis)."""
    _, _, axis = tile_specs(mesh)
    return axis, int(mesh.shape[axis])


def _harvest_shards_host(points, dists, shards, tau_max, tile_m, tile_n,
                         backend, interpret, stats, chunks):
    """Host-partitioned harvest: each shard's tile list replayed through
    the serial :func:`iter_tile_edges` dispatch (exact-f64 numpy, or the
    pallas f32-candidate/f64-refine path when that backend was requested
    without a mesh) — one per-tile implementation, so the serial-vs-sharded
    bit-identity contract cannot drift.  Fragment bytes tracked per shard.
    """
    ii, jj, ll = chunks
    for k, shard in enumerate(shards):
        shard_bytes = 0
        # the host simulation replays shards back-to-back; lane attribution
        # renders them as the parallel device tracks a mesh would run
        with span("harvest/shard", lane=k, n_tiles=len(shard)):
            for iu, ju, lens in iter_tile_edges(points=points, dists=dists,
                                                tau_max=tau_max,
                                                tile_m=tile_m, tile_n=tile_n,
                                                backend=backend,
                                                interpret=interpret,
                                                stats=stats, tiles=shard):
                ii.append(iu.astype(np.int64))
                jj.append(ju.astype(np.int64))
                ll.append(lens)
                shard_bytes += ii[-1].nbytes + jj[-1].nbytes + ll[-1].nbytes
        if stats is not None:
            stats.shard_peak_harvest_bytes = max(
                stats.shard_peak_harvest_bytes, shard_bytes)


def _candidate_round_fn(x, y, interpret=None):
    """Per-device body of one points-harvest round: ``(1, tile_m, d)`` x
    ``(1, tile_n, d)`` blocks -> the ``(1, tm, tn)`` f32 candidate tile.
    No collectives by design — each device's tile is independent; kept at
    module level (closed only over static config) so
    ``repro.analyze.collectives`` can trace and pin that schedule."""
    from ..kernels.pairwise_dist import pairwise_sq_dists

    return pairwise_sq_dists(x[0], y[0], interpret=interpret)[None]


def _dists_round_fn(t, thr32):
    """Per-device body of one dists-harvest round: threshold the device's
    own f32 tile; only the 1-byte candidate mask gathers back.  Module
    level for the same static-traceability reason as
    :func:`_candidate_round_fn`."""
    return (t[0] <= thr32)[None]


def _harvest_shards_device(points, sq, shards, tau_max, tile_m, tile_n,
                           mesh, interpret, stats, chunks):
    """Device rounds under ``shard_map``: one f32 candidate tile per device
    per round, exact f64 refine + COO extraction on the host."""
    import functools

    import jax
    import jax.numpy as jnp

    n, d = points.shape
    n_shards = len(shards)
    thr32 = _f32_threshold(points, sq, tau_max)
    pts32 = np.asarray(points, dtype=np.float32)
    in_specs, out_specs, _ = tile_specs(mesh)

    sharded = jax.shard_map(
        functools.partial(_candidate_round_fn, interpret=interpret),
        mesh=mesh, in_specs=in_specs, out_specs=out_specs, check_vma=False)

    ii, jj, ll = chunks
    shard_bytes = [0] * n_shards
    xs = np.zeros((n_shards, tile_m, d), dtype=np.float32)
    ys = np.zeros((n_shards, tile_n, d), dtype=np.float32)
    n_rounds = max(len(s) for s in shards)
    for r in range(n_rounds):
        live = []
        xs[:] = 0.0
        ys[:] = 0.0
        for k, shard in enumerate(shards):
            if r >= len(shard):
                continue            # exhausted shard recomputes a zero block
            si, sj = shard[r]
            ei, ej = min(si + tile_m, n), min(sj + tile_n, n)
            xs[k, :ei - si] = pts32[si:ei]
            ys[k, :ej - sj] = pts32[sj:ej]
            live.append((k, si, ei, sj, ej))
        with span("harvest/round", round=r, n_live=len(live)):
            # analyze: allow[host-sync] one round gather per tile wave is the harvest schedule (gather_bytes transient)
            d2 = np.asarray(sharded(jnp.asarray(xs), jnp.asarray(ys)))
        if stats is not None:
            stats.gather_bytes = max(stats.gather_bytes,
                                     d2.nbytes + xs.nbytes + ys.nbytes)
        for k, si, ei, sj, ej in live:
            if stats is not None:
                stats.tiles_visited += 1
                stats.fetch_bytes += (ei - si) * (ej - sj) * d2.itemsize
            with span("harvest/refine", lane=k, round=r, tile=f"{si},{sj}"):
                # crop to the real extent first: zero-padded rows fabricate
                # origin distances that must never reach the threshold test
                iu, ju, lens = _refine_f32_tile(
                    d2[k, :ei - si, :ej - sj], points, sq, si, ei, sj, ej,
                    tau_max, thr32, stats)
            ii.append(iu.astype(np.int64))
            jj.append(ju.astype(np.int64))
            ll.append(lens)
            shard_bytes[k] += ii[-1].nbytes + jj[-1].nbytes + ll[-1].nbytes
    if stats is not None:
        stats.shard_peak_harvest_bytes = max(stats.shard_peak_harvest_bytes,
                                             max(shard_bytes, default=0))


def _harvest_shards_device_dists(dists, shards, tau_max, tile_m, tile_n,
                                 mesh, stats, chunks):
    """Device rounds for a precomputed distance matrix: each device
    thresholds its own f32 tile under ``shard_map`` (the gathered per-round
    transient is the 1-byte candidate mask, a quarter of the f32 tile), and
    the host re-measures candidates straight from the exact f64 matrix."""
    import functools

    import jax
    import jax.numpy as jnp

    n = dists.shape[0]
    n_shards = len(shards)
    thr32 = _f32_dists_threshold(tau_max)
    _, spec, _ = tile_specs(mesh)

    sharded = jax.shard_map(
        functools.partial(_dists_round_fn, thr32=thr32),
        mesh=mesh, in_specs=spec, out_specs=spec, check_vma=False)

    ii, jj, ll = chunks
    shard_bytes = [0] * n_shards
    buf = np.zeros((n_shards, tile_m, tile_n), dtype=np.float32)
    n_rounds = max(len(s) for s in shards)
    for r in range(n_rounds):
        live = []
        buf[:] = np.inf   # exhausted-shard padding must never pass thr32
        for k, shard in enumerate(shards):
            if r >= len(shard):
                continue
            si, sj = shard[r]
            ei, ej = min(si + tile_m, n), min(sj + tile_n, n)
            buf[k, :ei - si, :ej - sj] = dists[si:ei, sj:ej]
            live.append((k, si, ei, sj, ej))
        with span("harvest/round", round=r, n_live=len(live)):
            # analyze: allow[host-sync] the per-round candidate-mask gather is the schedule; the f64 re-measure needs it on host
            cand = np.asarray(sharded(jnp.asarray(buf)))
        if stats is not None:
            stats.gather_bytes = max(stats.gather_bytes,
                                     cand.nbytes + buf.nbytes)
        for k, si, ei, sj, ej in live:
            if stats is not None:
                stats.tiles_visited += 1
                stats.fetch_bytes += (ei - si) * (ej - sj) * cand.itemsize
            with span("harvest/refine", lane=k, round=r, tile=f"{si},{sj}"):
                # crop to the real extent first: the inf padding is masked
                # out by construction, the crop keeps the index math honest
                iu, ju, lens = _refine_f32_dists_tile(
                    cand[k, :ei - si, :ej - sj], dists, si, ei, sj, ej,
                    tau_max, stats)
            ii.append(iu.astype(np.int64))
            jj.append(ju.astype(np.int64))
            ll.append(lens)
            shard_bytes[k] += ii[-1].nbytes + jj[-1].nbytes + ll[-1].nbytes
    if stats is not None:
        stats.shard_peak_harvest_bytes = max(stats.shard_peak_harvest_bytes,
                                             max(shard_bytes, default=0))


def harvest_edges_sharded(
    points: Optional[np.ndarray] = None,
    dists: Optional[np.ndarray] = None,
    tau_max: float = np.inf,
    tile_m: int = DEFAULT_TILE,
    tile_n: int = DEFAULT_TILE,
    mesh=None,
    n_shards: Optional[int] = None,
    backend: str = "auto",
    interpret: Optional[bool] = None,
    stats: Optional[TileStats] = None,
) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Sharded harvest: all permissible edges as one canonical sorted list.

    Bit-identical to :func:`~repro.scale.tiles.harvest_edges` (and the dense
    upper triangle) for every shard/device count.  Exactly one of ``mesh``
    (its data axis fixes the shard count, and the ``pallas`` backend runs
    rounds under ``shard_map``) or ``n_shards`` (host-partitioned execution,
    no devices needed) is typically given; both default to 1 shard.

    A ``dists`` matrix rides the device rounds too when a mesh is given:
    each device thresholds its own f32 tile of the matrix and only the
    candidate mask gathers back (``_harvest_shards_device_dists``), with
    the exact f64 re-measure read straight from the matrix on the host.
    Without a mesh — or with the ``numpy`` backend — ``dists`` harvests on
    the host, reproducing the multi-device *work split* (and its
    per-device :class:`TileStats` accounting) without device transfers.
    """
    if (points is None) == (dists is None):
        raise ValueError("provide exactly one of points or dists")
    if mesh is not None:
        axis, mesh_shards = shard_of_mesh(mesh)
        if n_shards is not None and int(n_shards) != mesh_shards:
            raise ValueError(
                f"n_shards={n_shards} disagrees with the mesh's "
                f"{axis}-axis size {mesh_shards}; pass only one of them")
        n_shards = mesh_shards
        if stats is not None:
            stats.mesh_axis = axis
    n_shards = 1 if n_shards is None else int(n_shards)
    if mesh is not None and backend in ("auto", "pallas"):
        # a mesh asks for device execution: "auto" means the shard_map path
        # (interpret-mode pallas off-TPU), not the host split the serial
        # resolver would pick on CPU — for points and dists inputs alike
        backend = "pallas"
    elif points is not None:
        backend = _resolve_backend(backend)
    else:
        backend = "numpy"

    if dists is not None:
        dists = np.asarray(dists)
        n = dists.shape[0]
        if dists.shape != (n, n):
            raise ValueError(f"dists must be square, got {dists.shape}")
        points = sq = None
    else:
        points = np.asarray(points, dtype=np.float64)
        n = points.shape[0]
        sq = np.sum(points * points, axis=1)

    if stats is not None:
        stats.n = n
        stats.tile_m, stats.tile_n = tile_m, tile_n
        stats.backend = backend
        stats.n_shards = n_shards

    shards = partition_tiles(n, tile_m, tile_n, n_shards)
    chunks: Tuple[list, list, list] = ([], [], [])
    if backend == "pallas" and mesh is not None and points is not None:
        _harvest_shards_device(points, sq, shards, tau_max, tile_m, tile_n,
                               mesh, interpret, stats, chunks)
    elif backend == "pallas" and mesh is not None and dists is not None:
        _harvest_shards_device_dists(dists, shards, tau_max, tile_m, tile_n,
                                     mesh, stats, chunks)
    else:
        _harvest_shards_host(points, dists, shards, tau_max,
                             tile_m, tile_n, backend, interpret, stats,
                             chunks)
    return merge_edge_chunks(*chunks, stats=stats)


def build_filtration_sharded(
    points: Optional[np.ndarray] = None,
    dists: Optional[np.ndarray] = None,
    tau_max: float = np.inf,
    tile_m: int = DEFAULT_TILE,
    tile_n: int = DEFAULT_TILE,
    mesh=None,
    n_shards: Optional[int] = None,
    backend: str = "auto",
    interpret: Optional[bool] = None,
    with_dense_order: bool = False,
    return_stats: bool = False,
):
    """Mesh-sharded streamed :class:`Filtration` build.

    The multi-device form of
    :func:`~repro.scale.tiles.build_filtration_tiled`: output is
    bit-identical to it (and to dense ``build_filtration``) for every device
    count; wall time scales with the data-axis size; per-device peak memory
    is one tile + the round gather + this device's fragment share — see
    :meth:`TileStats.per_device_peak_bytes` and
    ``scale.budget.tile_transient_bytes``.

    Returns ``filt`` or ``(filt, TileStats)`` with ``return_stats``.
    """
    stats = TileStats()
    iu, ju, lens = harvest_edges_sharded(
        points=points, dists=dists, tau_max=tau_max, tile_m=tile_m,
        tile_n=tile_n, mesh=mesh, n_shards=n_shards, backend=backend,
        interpret=interpret, stats=stats)
    filt = filtration_from_edges(stats.n, iu, ju, lens, tau_max,
                                 presorted=True,
                                 with_dense_order=with_dense_order)
    stats.base_memory_bytes = filt.base_memory_bytes()
    if return_stats:
        return filt, stats
    return filt
