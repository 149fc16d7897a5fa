"""Closed loop over contact maps: one caller runs ``compute_ph``'s COO entry
back to back for the window.

The timed call goes from balanced contacts in host arrays to diagrams in
host arrays: ``repro.scale.contacts_to_distances`` (``d = 1 / contact``),
then ``repro.core.homology.compute_ph(coo=(bin1, bin2, d, n), tau_max,
maxdim, engine="packed")``, which is the COO build, H0 and the packed GF(2)
reduction, with no tile harvest.  The configuration's dataset
(``bench/datasets/<dataset>.py``) gives one pixel table per condition from
its ``base_seed``; the pool holds each condition once, in the
configuration's order, its pixels listed in an order drawn from ``--seed``,
so every seed asks the same work.  The window, the notes and ``ph_s`` are
``ClosedLoop``'s.

(No ``from __future__ import annotations`` here: ``spec.load_part`` runs
the file as a module that ``sys.modules`` does not list, and ``dataclasses``
looks string annotations up there.)
"""
import dataclasses
from typing import Dict, List, Optional, Tuple

import numpy as np

from bench import compare, reference_coo, spec, traffic
from bench.loops.closed import ClosedLoop, Solve


@dataclasses.dataclass
class MapQuery:
    """One call: a condition's pixel table, the bin count and the query."""
    bin1: np.ndarray
    bin2: np.ndarray
    contact: np.ndarray         # balanced contacts
    n: int
    tau: float
    maxdim: int
    base: int                   # index of the condition in the configuration

    @property
    def points(self) -> np.ndarray:
        """The map's bins, as ``ClosedLoop`` records a cloud's width: ``n``
        rows with no coordinates."""
        return np.empty((self.n, 0))


def pools(cell, seed: int) -> Tuple[List[MapQuery], List[MapQuery]]:
    """``(pool, warmup)``: each condition in the configuration's order, its
    pixels in an order drawn from ``seed``; the warm-up lists them in
    another order."""
    config = cell.config
    dataset = spec.load_part(cell.root, "datasets", config["dataset"])
    maps = dataset.contacts(config, traffic.rng_of(config["base_seed"]))
    n = dataset.n_bins(config)
    rng = traffic.rng_of(seed)

    def query(k: int, pixels) -> MapQuery:
        order = rng.permutation(pixels[0].size)
        b1, b2, c = (a[order] for a in pixels)
        return MapQuery(b1, b2, c, n, cell.mix["tau_max"], cell.mix["maxdim"],
                        k)
    pool = [query(k, p) for k, p in enumerate(maps.values())]
    warm = [query(k, p) for k, p in enumerate(maps.values())]
    return pool, warm


def program_solve(q: MapQuery) -> Tuple[Dict[int, np.ndarray], Dict]:
    from repro.core.homology import compute_ph
    from repro.scale import contacts_to_distances

    res = compute_ph(coo=(q.bin1, q.bin2, contacts_to_distances(q.contact),
                          q.n),
                     tau_max=q.tau, maxdim=q.maxdim, engine="packed")
    return res.diagrams, res.stats


class MapLoop(ClosedLoop):
    def __init__(self, cell, seed: int, solve: Optional[Solve] = None,
                 guard: bool = True):
        self.pool, self.warm = pools(cell, seed)
        self.maxdim = cell.mix["maxdim"]
        self.solve = solve or program_solve
        self.guard = guard
        self.calls: List[Dict] = []

    def _check_path(self, stats: Dict) -> None:
        """No harvest runs here: only the gf2 kernels are asked for."""
        if self.guard:
            missing = [d for d in range(1, self.maxdim + 1)
                       if stats.get(f"h{d}_use_kernels") != 1]
            if missing:
                raise RuntimeError(f"the H{missing[0]} reduction did not run "
                                   f"the gf2 kernels")

    def check(self) -> Dict[str, Tuple[float, float]]:
        """Every call of the window against the reference of its map."""
        refs = {}
        off = 0
        for call in self.calls:
            q = self.pool[call["query"]]
            if call["query"] not in refs:
                refs[call["query"]] = reference_coo.diagrams(
                    q.n, q.bin1, q.bin2, q.contact, q.tau, q.maxdim)
            off += compare.bars_off(call["diagrams"], refs[call["query"]])
        return {"bars_off": (off, 0)}


def driver(cell, seed: int) -> MapLoop:
    return MapLoop(cell, seed)


def control(cell, seed: int, dtype) -> MapLoop:
    """The reference, computed in ``dtype``, in ``compute_ph``'s place."""
    def solve(q):
        return reference_coo.diagrams(q.n, q.bin1, q.bin2, q.contact, q.tau,
                                      q.maxdim, dtype), {}
    return MapLoop(cell, seed, solve=solve, guard=False)
