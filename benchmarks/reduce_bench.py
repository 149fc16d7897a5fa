"""CI benchmark for the reduction engines -> BENCH_reduce.json.

Runs the same reduction-heavy smoke workload through every engine
(``single`` / ``batch`` / ``packed``) in both storage modes and records
reduction wall time plus reductions/sec, so the perf trajectory of the
packed hot path is pinned per push.  The workload is the suite's
``fractal`` regime (a self-similar random distance matrix, ``maxdim=2``) —
the reduction-bound corner of Table 2, where column chains are deep and the
engines differ the most; the geometric datasets are filtration-bound and
land in ``BENCH_scale.json`` instead.

    PYTHONPATH=src python -m benchmarks.reduce_bench --n 64 --out BENCH_reduce.json

``--min-speedup X`` makes the run assert that the packed engine beats the
single engine by at least ``X``x reductions/sec in the implicit (paper
§4.3.4, memory-bound) mode — the CI contract.  Diagrams are asserted
identical across engines while at it, so the benchmark doubles as an
end-to-end bit-identity check.

``--dist-shards 1,4`` additionally runs the distributed packed driver at
each listed shard count (over a real ``data`` mesh when that many jax
devices exist, the host-partitioned simulation otherwise) and records the
simulated critical-path reduction wall ``sim_wall_s`` per run — the wall a
``P``-device mesh would execute, with per-superstep concurrent phases
taking the slowest shard's time and exchange/tournament/sweep costs on the
critical path (for ``P == 1`` the same accounting reproduces the measured
wall).  ``--max-dist-ratio X`` asserts
``sim_wall(P_max) <= X * sim_wall(P=1)`` in implicit mode — the 4-device
CI contract (``BENCH_reduce_4dev.json``).  Distributed diagrams are
asserted bit-identical to every engine's while at it.
"""
from __future__ import annotations

import argparse
import json
import time

ENGINES = ("single", "batch", "packed")
MODES = ("explicit", "implicit")

# distributed pivot-exchange cadence per mode: implicit ships gens-only
# payloads (cheap wire) and likes frequent rounds; explicit ships full
# R^perp columns, so batching more supersteps per round pays for itself
DIST_EXCHANGE_EVERY = {"implicit": 4, "explicit": 8}

# packed-engine stats surfaced per entry: block-engine counters plus the
# shared pivot-cache counters (cache_n_packs ~ one pack per stored pivot;
# cache_n_pack_hits counts the re-packs the cache absorbed)
PACKED_COUNTERS = (
    "n_rounds", "n_expansions", "n_evictions", "n_consolidations",
    "peak_block_bytes",
    "cache_n_packs", "cache_n_pack_hits",
    "cache_n_materializations", "cache_n_mat_hits",
)
DIST_COUNTERS = (
    "n_supersteps", "n_exchange_rounds", "n_tournament_reductions",
    "n_sweep_probes", "exchange_bytes",
)


def _summed(stats: dict, key: str) -> float:
    """Sum a per-dimension packed counter over the H1 + H2 passes."""
    return stats.get(f"h1_{key}", 0.0) + stats.get(f"h2_{key}", 0.0)


def _cache_summary(stats: dict) -> dict:
    """The S1 story in three numbers: with the shared pivot cache each
    committed pivot is bit-packed once, then every later probe reuses the
    cached positions — packs/pivot sits at ~1 instead of growing with the
    number of times a pivot is hit."""
    packs = _summed(stats, "cache_n_packs")
    hits = _summed(stats, "cache_n_pack_hits")
    stored = _summed(stats, "n_stored_columns")
    return {
        "cache_n_packs": int(packs),
        "cache_n_pack_hits": int(hits),
        "packs_per_stored_pivot": round(packs / max(stored, 1.0), 3),
    }


def run(n: int, seed: int, batch_size: int, maxdim: int = 2) -> dict:
    from repro.core import compute_ph
    from repro.core.diagrams import assert_diagrams_equal
    from repro.data import pointclouds as pc

    dists = pc.fractal_like(n, seed=seed)
    record: dict = {
        "benchmark": "reduce_bench",
        "dataset": "fractal",
        "n": int(n),
        "maxdim": int(maxdim),
        "batch_size": int(batch_size),
        "engines": {},
    }
    reference = None
    for mode in MODES:
        for engine in ENGINES:
            t0 = time.perf_counter()
            res = compute_ph(dists=dists, maxdim=maxdim, engine=engine,
                             mode=mode, batch_size=batch_size)
            wall = time.perf_counter() - t0
            s = res.stats
            red_t = s.get("t_h1", 0.0) + s.get("t_h2", 0.0)
            n_red = s.get("h1_n_reductions", 0.0) \
                + s.get("h2_n_reductions", 0.0)
            entry = {
                "mode": mode,
                "t_reduction_s": round(red_t, 4),
                "t_total_s": round(wall, 4),
                "n_reductions": int(n_red),
                "reductions_per_s": round(n_red / max(red_t, 1e-9), 1),
                "stored_bytes": int(s.get("h2_stored_bytes", 0)),
                # per-phase span breakdown (docs/observability.md;
                # schema-checked by tools/check_bench_schema.py)
                "phases": {
                    "filtration": round(s.get("t_filtration", 0.0), 4),
                    "h0": round(s.get("t_h0", 0.0), 4),
                    "h1": round(s.get("t_h1", 0.0), 4),
                    "h2": round(s.get("t_h2", 0.0), 4),
                },
            }
            if engine == "packed":
                for k in PACKED_COUNTERS:
                    entry[k] = int(_summed(s, k))
                entry.update(_cache_summary(s))
            record["engines"][f"{engine}_{mode}"] = entry
            record["n_e"] = int(s["n_e"])
            if reference is None:
                reference = res.diagrams
            else:   # every engine x mode must reproduce identical diagrams
                assert_diagrams_equal(reference, res.diagrams,
                                      dims=list(range(maxdim + 1)))

    eng = record["engines"]
    for mode in MODES:
        record[f"speedup_rps_packed_vs_single_{mode}"] = round(
            eng[f"packed_{mode}"]["reductions_per_s"]
            / max(eng[f"single_{mode}"]["reductions_per_s"], 1e-9), 2)
    # headline: the memory-bound (implicit) regime the paper optimizes for
    record["speedup_rps_packed_vs_single"] = \
        record["speedup_rps_packed_vs_single_implicit"]
    record["_reference_diagrams"] = reference
    return record


def run_distributed(record: dict, dists, shards: list, batch_size: int,
                    maxdim: int) -> None:
    """Distributed packed runs at each shard count, into ``record``.

    A run at ``P`` shards uses the real ``(data=P,)`` mesh when jax exposes
    exactly ``P`` devices (collective pivot exchange through
    ``jax.lax.all_gather``), and the host-partitioned ``n_shards``
    simulation otherwise — the work split and diagrams are identical either
    way; only the exchange transport differs.
    """
    import jax

    from repro.core import compute_ph
    from repro.core.diagrams import assert_diagrams_equal
    from repro.scale import make_data_mesh

    reference = record.pop("_reference_diagrams")
    n_dev = jax.device_count()
    record["distributed"] = {}
    for mode in MODES:
        ee = DIST_EXCHANGE_EVERY[mode]
        for P in shards:
            kwargs = ({"mesh": make_data_mesh()} if P == n_dev and P > 1
                      else {"n_shards": P})
            t0 = time.perf_counter()
            res = compute_ph(dists=dists, maxdim=maxdim, engine="packed",
                             mode=mode, batch_size=batch_size,
                             exchange_every=ee, **kwargs)
            wall = time.perf_counter() - t0
            s = res.stats
            entry = {
                "mode": mode,
                "n_shards": int(P),
                "transport": "mesh" if "mesh" in kwargs else "host",
                "exchange_every": int(ee),
                "sim_wall_s": round(_summed(s, "sim_wall_s"), 4),
                "sim_conc_s": round(_summed(s, "sim_conc_s"), 4),
                "sim_sweep_s": round(_summed(s, "sim_sweep_s"), 4),
                "sim_sync_s": round(_summed(s, "sim_sync_s"), 4),
                "t_total_s": round(wall, 4),
                # the sim_wall_s decomposition as the per-phase breakdown
                "phases": {
                    "conc": round(_summed(s, "sim_conc_s"), 4),
                    "sweep": round(_summed(s, "sim_sweep_s"), 4),
                    "sync": round(_summed(s, "sim_sync_s"), 4),
                },
            }
            for k in DIST_COUNTERS:
                entry[k] = int(_summed(s, k))
            entry.update(_cache_summary(s))
            record["distributed"][f"p{P}_{mode}"] = entry
            # the exit bar: diagrams bit-identical to every single-device
            # engine for every shard count
            assert_diagrams_equal(reference, res.diagrams,
                                  dims=list(range(maxdim + 1)))

    p_max = max(shards)
    dist = record["distributed"]
    for mode in MODES:
        base = dist[f"p1_{mode}"]["sim_wall_s"]
        record[f"dist_sim_ratio_{mode}"] = round(
            dist[f"p{p_max}_{mode}"]["sim_wall_s"] / max(base, 1e-9), 3)
    # headline gate metric: the implicit regime (gens-only wire payloads)
    record["dist_sim_ratio"] = record["dist_sim_ratio_implicit"]
    record["dist_p_max"] = int(p_max)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--n", type=int, default=64,
                    help="fractal point count (reduction work grows ~n^3)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--batch-size", type=int, default=256)
    ap.add_argument("--maxdim", type=int, default=2)
    ap.add_argument("--min-speedup", type=float, default=None,
                    help="assert packed >= X times single reductions/sec "
                         "(implicit mode); the CI contract")
    ap.add_argument("--dist-shards", type=str, default=None,
                    help="comma list of shard counts to run the distributed "
                         "packed driver at, e.g. 1,4")
    ap.add_argument("--max-dist-ratio", type=float, default=None,
                    help="assert sim_wall(P_max) <= X * sim_wall(P=1) in "
                         "implicit mode; the 4-device CI contract")
    ap.add_argument("--out", type=str, default="BENCH_reduce.json")
    args = ap.parse_args()

    from repro.data import pointclouds as pc

    record = run(args.n, args.seed, args.batch_size, maxdim=args.maxdim)
    if args.dist_shards:
        # analyze: allow[raw-filtration-sort] shard counts, not filtration values
        shards = sorted({int(p) for p in args.dist_shards.split(",")})
        assert shards[0] == 1, "--dist-shards needs the P=1 baseline"
        dists = pc.fractal_like(args.n, seed=args.seed)
        run_distributed(record, dists, shards, args.batch_size, args.maxdim)
    else:
        record.pop("_reference_diagrams")
    with open(args.out, "w") as f:
        json.dump(record, f, indent=2, sort_keys=True)
        f.write("\n")
    print(json.dumps(record, indent=2, sort_keys=True))
    print(f"wrote {args.out}")
    if args.min_speedup is not None:
        got = record["speedup_rps_packed_vs_single"]
        assert got >= args.min_speedup, (
            f"packed engine speedup regressed: {got}x < "
            f"{args.min_speedup}x (implicit mode)")
        print(f"speedup {got}x >= {args.min_speedup}x: ok")
    if args.max_dist_ratio is not None:
        got = record["dist_sim_ratio"]
        assert got <= args.max_dist_ratio, (
            f"distributed reduction scaling regressed: sim_wall ratio "
            f"{got} > {args.max_dist_ratio} at P={record['dist_p_max']} "
            f"(implicit mode)")
        print(f"dist sim_wall ratio {got} <= {args.max_dist_ratio}: ok")


if __name__ == "__main__":
    main()
