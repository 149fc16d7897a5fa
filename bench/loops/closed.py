"""Closed loop: one caller runs ``compute_ph`` back to back for the window.

The timed entry is the program's own public call,
``repro.core.homology.compute_ph(points, tau_max, maxdim, backend="tiled",
engine="packed")``: the Pallas tile harvest, H0 and the packed GF(2)
reduction.  Calls start until ``seconds`` have passed; the last one may end
after that and counts whole.
"""
from __future__ import annotations

import time
from typing import Callable, Dict, List, Optional, Tuple

import numpy as np
from jax.profiler import TraceAnnotation

from bench import compare, reference, traffic

Solve = Callable[[traffic.Query], Tuple[Dict[int, np.ndarray], Dict]]


def program_solve(q: traffic.Query) -> Tuple[Dict[int, np.ndarray], Dict]:
    from repro.core.homology import compute_ph

    res = compute_ph(q.points, tau_max=q.tau, maxdim=q.maxdim,
                     backend="tiled", engine="packed")
    return res.diagrams, res.stats


def device_path_errors(stats: Dict, maxdim: int) -> List[str]:
    """What in a call's stats says it ran a host fallback."""
    errors = []
    if stats.get("harvest_pallas") != 1:
        errors.append("the tile harvest did not run the Pallas kernel")
    for d in range(1, maxdim + 1):
        if stats.get(f"h{d}_use_kernels") != 1:
            errors.append(f"the H{d} reduction did not run the gf2 kernels")
    return errors


class ClosedLoop:
    def __init__(self, cell, seed: int, solve: Optional[Solve] = None,
                 guard: bool = True):
        self.pool, self.warm = traffic.closed_loop(cell.config, cell.mix,
                                                   seed, cell.root)
        self.maxdim = cell.mix["maxdim"]
        self.solve = solve or program_solve
        self.guard = guard
        self.calls: List[Dict] = []

    def setup(self) -> None:
        """One call on another copy of each of the pool's clouds: the same
        complexes, so every shape the window meets is built here."""
        for q in self.warm:
            _, stats = self.solve(q)
            self._check_path(stats)

    def _check_path(self, stats: Dict) -> None:
        if self.guard:
            errors = device_path_errors(stats, self.maxdim)
            if errors:
                raise RuntimeError("; ".join(errors))

    def window(self, seconds: float) -> None:
        start = time.perf_counter()
        k = 0
        while True:
            t0 = time.perf_counter()
            if t0 - start >= seconds:
                break
            q = self.pool[k % len(self.pool)]
            with TraceAnnotation("bench/call"):
                diagrams, stats = self.solve(q)
            self.calls.append({"t0": t0, "t1": time.perf_counter(),
                               "query": k % len(self.pool),
                               "d": q.points.shape[1],
                               "diagrams": diagrams, "stats": stats})
            k += 1
        for call in self.calls:
            self._check_path(call["stats"])

    def end_to_end(self) -> Dict[str, float]:
        c = self.calls
        return {"ph_s": (c[-1]["t1"] - c[0]["t0"]) / len(c)}

    def attempted_failed(self) -> Tuple[int, int]:
        return len(self.calls), 0

    def notes(self) -> Dict:
        """Every call's seconds in order, and per pool cloud: calls made,
        their fastest, median and slowest seconds, and the reduction rounds
        they took."""
        out = {"call_s": [round(c["t1"] - c["t0"], 4) for c in self.calls]}
        for k, q in enumerate(self.pool):
            mine = [c for c in self.calls if c["query"] == k]
            if not mine:
                continue
            walls = sorted(c["t1"] - c["t0"] for c in mine)
            rounds = sorted({c["stats"].get("h1_n_rounds", 0)
                             + c["stats"].get("h2_n_rounds", 0)
                             for c in mine})
            out[f"base{q.base}"] = {
                "calls": len(mine), "call_s_min": walls[0],
                "call_s_median": walls[len(walls) // 2],
                "call_s_max": walls[-1], "rounds_seen": rounds}
        return out

    def release(self) -> None:
        self.warm = None

    def check(self) -> Dict[str, Tuple[float, float]]:
        """Every call of the window against the reference of its cloud."""
        refs = {}
        off = 0
        for call in self.calls:
            q = self.pool[call["query"]]
            if call["query"] not in refs:
                refs[call["query"]] = reference.diagrams(
                    q.points, q.tau, q.maxdim)
            off += compare.bars_off(call["diagrams"], refs[call["query"]])
        return {"bars_off": (off, 0)}


def driver(cell, seed: int) -> ClosedLoop:
    return ClosedLoop(cell, seed)


def control(cell, seed: int, dtype) -> ClosedLoop:
    """The reference, computed in ``dtype``, in ``compute_ph``'s place."""
    def solve(q):
        return reference.diagrams(q.points, q.tau, q.maxdim, dtype), {}
    return ClosedLoop(cell, seed, solve=solve, guard=False)
