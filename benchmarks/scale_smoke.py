"""CI benchmark smoke for ``repro.scale``: tiled build + PH -> BENCH_scale.json.

Small enough for a CI runner, real enough to populate the perf trajectory:
streams a torus4 cloud through the tiled builder under a byte budget, runs
``compute_ph`` on the resulting order-free filtration, and writes one JSON
record (n, n_e, tau, peak-RSS estimate, wall times, memory accounts).
``--devices N`` shards the harvest (mesh or host-partitioned) and adds the
per-device fields.  Field-by-field reference: docs/benchmarks.md.

    PYTHONPATH=src python -m benchmarks.scale_smoke --n 3000 --out BENCH_scale.json
"""
from __future__ import annotations

import argparse
import json
import resource
import sys
import time


def peak_rss_bytes() -> int:
    """ru_maxrss is KiB on Linux, bytes on macOS."""
    rss = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss
    return int(rss) * (1 if sys.platform == "darwin" else 1024)


def run(n: int, budget_mb: float, tile: int, maxdim: int, seed: int,
        devices: int = 1) -> dict:
    import numpy as np

    from repro.core import compute_ph
    from repro.data import pointclouds as pc
    from repro.scale import (build_filtration_sharded, build_filtration_tiled,
                             estimate_tau_max)

    budget = int(budget_mb * 2**20)
    pts = pc.clifford_torus(n, seed=seed)

    t0 = time.perf_counter()
    tau = estimate_tau_max(pts, budget, seed=seed, n_shards=devices,
                           tile_m=tile, tile_n=tile)
    t_budget = time.perf_counter() - t0

    shard_mode = None
    t0 = time.perf_counter()
    if devices > 1:
        # real (data=N,) mesh when the process has the devices (CI's
        # 4-virtual-device job), host-partitioned shards otherwise — the
        # tile split, merge, and per-device accounting are identical
        import jax
        mesh = None
        if len(jax.devices()) >= devices:
            from repro.scale import make_data_mesh
            mesh = make_data_mesh(devices)
            shard_mode = "mesh"
        else:
            shard_mode = "host"
        filt, stats = build_filtration_sharded(
            points=pts, tau_max=tau, tile_m=tile, tile_n=tile, mesh=mesh,
            n_shards=None if mesh is not None else devices,
            return_stats=True)
    else:
        filt, stats = build_filtration_tiled(points=pts, tau_max=tau,
                                             tile_m=tile, tile_n=tile,
                                             return_stats=True)
    t_filtration = time.perf_counter() - t0

    t0 = time.perf_counter()
    res = compute_ph(filtration=filt, maxdim=maxdim)
    t_ph = time.perf_counter() - t0

    from repro.scale import account_bytes
    predicted = account_bytes(filt.n, filt.n_e)

    record = {
        "benchmark": "scale_smoke",
        "dataset": "torus4",
        "n": int(filt.n),
        "n_e": int(filt.n_e),
        "maxdim": int(maxdim),
        "tau_max": float(tau) if np.isfinite(tau) else None,   # stable schema
        "tile": int(tile),
        "backend": stats.backend,
        "tiles_visited": int(stats.tiles_visited),
        "memory_budget_bytes": budget,
        "base_memory_bytes": int(filt.base_memory_bytes()),
        "peak_tile_bytes": int(stats.peak_tile_bytes),
        "harvest_bytes": int(stats.harvest_bytes),
        "dense_path_bytes": int(n) * int(n) * 8,   # what the seed path needs
        "peak_rss_bytes": peak_rss_bytes(),
        "t_budget_s": round(t_budget, 4),
        "t_filtration_s": round(t_filtration, 4),
        "t_ph_s": round(t_ph, 4),
        # per-phase breakdown (docs/observability.md; schema-checked by
        # tools/check_bench_schema.py) + observed-vs-predicted memory
        "phases": {
            "budget": round(t_budget, 4),
            "filtration": round(t_filtration, 4),
            "ph": round(t_ph, 4),
        },
        "predicted_account_bytes": int(predicted),
        "observed_peak_harvest_bytes": int(stats.peak_extra_bytes()),
        "budget_drift_ratio": round(
            (filt.base_memory_bytes() + stats.peak_extra_bytes())
            / max(predicted, 1.0), 3),
        "n_pairs": {str(d): int(len(pd)) for d, pd in res.diagrams.items()},
    }
    if devices > 1:
        record.update({
            "n_shards": int(stats.n_shards),
            "shard_mode": shard_mode,
            "gather_bytes": int(stats.gather_bytes),
            "shard_peak_harvest_bytes": int(stats.shard_peak_harvest_bytes),
            "per_device_peak_bytes": int(stats.per_device_peak_bytes()),
            "per_device_base_bytes": int(stats.per_device_base_bytes()),
        })
    # the whole point: the streamed build must fit the account it was given
    # (per device when sharded — every device duplicates the 3n vertex words
    # but holds only its edge share)
    fit = record["per_device_base_bytes"] if devices > 1 \
        else record["base_memory_bytes"]
    assert fit <= 1.2 * budget, record
    assert record["peak_tile_bytes"] < record["dense_path_bytes"], record
    return record


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--n", type=int, default=3000)
    ap.add_argument("--budget-mb", type=float, default=1.5)
    ap.add_argument("--tile", type=int, default=1024)
    ap.add_argument("--maxdim", type=int, default=1)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--devices", type=int, default=1,
                    help="shard the harvest over N devices (a real mesh "
                         "when available, host-partitioned otherwise)")
    ap.add_argument("--out", type=str, default="BENCH_scale.json")
    args = ap.parse_args()

    record = run(args.n, args.budget_mb, args.tile, args.maxdim, args.seed,
                 devices=args.devices)
    with open(args.out, "w") as f:
        json.dump(record, f, indent=2, sort_keys=True)
        f.write("\n")
    print(json.dumps(record, indent=2, sort_keys=True))
    print(f"wrote {args.out}")


if __name__ == "__main__":
    main()
