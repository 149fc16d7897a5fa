"""Work counts for roofline shares, and the table of chip peaks.

The work is what the input needs, not what a kernel happens to do, so a
later kernel that does less for the same input is measured against the same
work.  Peaks come from ``peaks.json`` beside this file, keyed by JAX's
``device_kind``; a device missing from the table is an error.
"""
from __future__ import annotations

import json
import os
from typing import Dict

_PEAKS = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                      "peaks.json")


def peaks(device_kind: str) -> Dict[str, float]:
    with open(_PEAKS) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{_PEAKS}; known: {sorted(table)}")
    return table[device_kind]


def dist_work(n: int, d: int, n_e: int) -> Dict[str, float]:
    """Pairwise distances of ``n`` points in R^d, thresholded to ``n_e``
    edges: one multiply and one add per coordinate of each of the
    ``n(n-1)/2`` pairs; ``n*d`` f32 coordinates in and one ``(i, j, length)``
    triple of 12 bytes per edge out."""
    return {"flops": 2.0 * d * n * (n - 1) / 2.0,
            "bytes": 4.0 * n * d + 12.0 * n_e}


def least_seconds(work: Dict[str, float], peak: Dict[str, float]) -> float:
    """The least time the chip could take: the larger of operations over
    the bf16 peak and bytes over HBM bandwidth."""
    return max(work["flops"] / peak["bf16_flops_per_s"],
               work["bytes"] / peak["hbm_bytes_per_s"])
