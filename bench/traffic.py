"""The benchmark's one traffic generator.

A traffic mix is a data file ``bench/traffic/<name>.json``; a configuration
is ``bench/configs/<name>.json``.  This module turns the two and ``--seed``
into every input of a run, before the window opens.

One loop exists, ``closed``: one caller, calls back to back over a pool of
clouds.  The configuration names fixed samples of its dataset
(``base_seeds``); the pool holds one isometric copy of each, in the
configuration's order and in coordinates drawn from ``--seed``.  Every run
thus asks the same work of the system on other inputs, and the check still
covers several complexes.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Tuple

import numpy as np

from bench import clouds


@dataclasses.dataclass
class Query:
    """One call: a cloud, its threshold and the sample it was copied from."""
    points: np.ndarray
    tau: float
    maxdim: int
    base: int                   # index into the configuration's base_seeds


def rng_of(seed: int) -> np.random.Generator:
    return np.random.default_rng(int(seed) % (1 << 64))


def make_cloud(config: Dict, rng: np.random.Generator, n: int) -> np.ndarray:
    if config["dataset"] == "o3":
        return clouds.o3(rng, n)
    raise ValueError(f"unknown dataset {config['dataset']!r}")


def closed_loop(config: Dict, mix: Dict, seed: int
                ) -> Tuple[List[Query], List[Query]]:
    """``(pool, warmup)``: the window's calls in the order they cycle, and
    one more call on each base sample, in coordinates of its own, to warm
    up every shape the pool asks for."""
    bases = [make_cloud(config, rng_of(s), config["n"])
             for s in config["base_seeds"]]
    rng = rng_of(seed)

    def copy(k: int) -> Query:
        return Query(clouds.isometric_copy(bases[k], rng), mix["tau_max"],
                     mix["maxdim"], k)
    pool = [copy(k) for k in range(len(bases))]
    warmup = [copy(k) for k in range(len(bases))]
    return pool, warmup
