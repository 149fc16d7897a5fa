"""PH serving driver over synthetic traffic.

``PHServeEngine``: admission-controlled persistent homology serving with
union-batched cold requests and warm-start incremental updates (tau growth
/ point arrival) against the dataset cache.

Usage::

    PYTHONPATH=src python -m repro.launch.serve \
        --requests 24 --cloud-size 48 --update-fraction 0.5
"""
from __future__ import annotations

import argparse

import numpy as np

from repro.obs.trace import stopwatch


def run_ph(args) -> None:
    from repro.serve.ph import PHRequest, PHServeEngine

    engine = PHServeEngine(
        memory_budget_bytes=args.budget_bytes,
        store_budget_bytes=args.store_budget_bytes,
        max_batch_clouds=args.max_batch_clouds,
        landmark_cap=args.landmark_cap,
        seed=args.seed,
        engine=args.reduce_engine,
        batch_size=args.batch_size,
        n_shards=args.n_shards)
    rng = np.random.default_rng(args.seed)
    n_cold = max(1, int(round(args.requests * (1 - args.update_fraction))))
    clouds = [rng.normal(size=(args.cloud_size, 3)) for _ in range(n_cold)]
    uid = 0
    for k, p in enumerate(clouds):
        engine.submit(PHRequest(uid=uid, points=p, tau_max=args.tau,
                                dataset=f"ds{k}"))
        uid += 1
    with stopwatch("serve_ph/cold_wave") as sw_cold:
        engine.run()
    # update wave: alternate tau growth and point arrival on cached datasets
    while uid < args.requests:
        k = int(rng.integers(0, n_cold))
        if uid % 2 == 0:
            engine.submit(PHRequest(uid=uid, points=clouds[k],
                                    tau_max=args.tau * 1.5,
                                    dataset=f"ds{k}"))
        else:
            grown = np.concatenate(
                [clouds[k], rng.normal(size=(args.arrivals, 3))], axis=0)
            engine.submit(PHRequest(uid=uid, points=grown,
                                    tau_max=args.tau, dataset=f"ds{k}"))
        uid += 1
    with stopwatch("serve_ph/update_wave") as sw_warm:
        engine.run()
    s = engine.stats()
    served = int(s.get("serve_ph_n_admitted", 0))
    wall = sw_cold.elapsed + sw_warm.elapsed
    hits = s.get("serve_ph_n_cache_hits", 0.0)
    hit_ratio = hits / max(1.0, s.get("serve_ph_n_requests", 0.0))
    print(f"served {served}/{args.requests} PH requests in {wall:.2f}s "
          f"({served / wall:.1f} req/s), cache-hit ratio {hit_ratio:.2f}")
    for key in ("serve_ph_n_cold", "serve_ph_n_batched",
                "serve_ph_n_warm_tau", "serve_ph_n_warm_points",
                "serve_ph_n_rejected", "serve_ph_store_bytes"):
        print(f"  {key} = {s.get(key, 0.0):.0f}")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--cloud-size", type=int, default=48)
    ap.add_argument("--tau", type=float, default=1.6)
    ap.add_argument("--arrivals", type=int, default=6,
                    help="points appended per point-arrival update")
    ap.add_argument("--update-fraction", type=float, default=0.5,
                    help="fraction of requests that are warm updates")
    ap.add_argument("--budget-bytes", type=int, default=None,
                    help="admission memory budget per reduction")
    ap.add_argument("--store-budget-bytes", type=int, default=None,
                    help="per-tenant cached-state budget")
    ap.add_argument("--max-batch-clouds", type=int, default=8)
    ap.add_argument("--landmark-cap", type=int, default=None)
    ap.add_argument("--reduce-engine", default="single",
                    choices=("single", "batch", "packed"))
    ap.add_argument("--batch-size", type=int, default=64)
    ap.add_argument("--n-shards", type=int, default=None)
    run_ph(ap.parse_args())


if __name__ == "__main__":
    main()
