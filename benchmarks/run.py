"""Benchmark orchestrator: one section per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--scale 1.0] [--skip table3]
"""
from __future__ import annotations

import argparse
import time
import traceback


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--scale", type=float, default=1.0,
                    help="dataset size multiplier (--scale 4 ~ paper-size "
                         "regimes, minutes of CPU)")
    ap.add_argument("--skip", action="append", default=[],
                    help="section name to skip (repeatable)")
    args = ap.parse_args()

    from . import (fig21_hic, table1_datasets, table2_phases,
                   table3_vs_baseline, table4_variants)

    sections = [
        ("table1_datasets (paper Table 1)",
         lambda: table1_datasets.main(args.scale)),
        ("table2_phases (paper Table 2)",
         lambda: table2_phases.main(["--scale", str(args.scale)])),
        ("table3_vs_baseline (paper Table 3 / Fig. 18)",
         table3_vs_baseline.main),
        ("table4_variants (paper Table 4)",
         lambda: table4_variants.main(args.scale)),
        ("fig21_hic (paper Fig. 21)",
         lambda: fig21_hic.main(args.scale)),
    ]

    failures = 0
    for name, fn in sections:
        short = name.split(" ")[0]
        print(f"\n{'=' * 72}\n== {name}\n{'=' * 72}")
        if short in args.skip:
            print("(skipped)")
            continue
        t0 = time.perf_counter()
        try:
            fn()
            print(f"-- section ok in {time.perf_counter() - t0:.1f}s")
        except Exception:                                # noqa: BLE001
            failures += 1
            print(f"-- SECTION FAILED:\n{traceback.format_exc()[-2000:]}")
    print(f"\n{'=' * 72}\nbenchmarks done, {failures} failed sections")
    raise SystemExit(1 if failures else 0)


if __name__ == "__main__":
    main()
