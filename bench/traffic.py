"""The benchmark's one traffic generator.

A traffic mix is a data file ``bench/traffic/<name>.json``; a configuration
is ``bench/configs/<name>.json``.  This module turns the two and ``--seed``
into every input of a run, before the window opens.  The mix's ``"loop"``
names the driver that sends them (``bench/loops/<loop>.py``), and the
configuration's ``"dataset"`` the generator of its points
(``bench/datasets/<dataset>.py``); both are found by file
(``bench/spec.py``).

The configuration fixes the work and ``--seed`` only the coordinates:
``closed_loop``'s pool holds one isometric copy of each of the
configuration's fixed samples (``base_seeds``), in the configuration's order
and in coordinates drawn from ``--seed``.  Every run thus asks the same work
of the system on other inputs, and the check still covers several
complexes.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Tuple

import numpy as np

from bench import clouds, spec


@dataclasses.dataclass
class Query:
    """One call: a cloud, its threshold and the sample it was copied from."""
    points: np.ndarray
    tau: float
    maxdim: int
    base: int                   # index into the configuration's base_seeds


def rng_of(seed: int) -> np.random.Generator:
    return np.random.default_rng(int(seed) % (1 << 64))


def make_cloud(config: Dict, rng: np.random.Generator, n: int,
               root: str = spec.ROOT) -> np.ndarray:
    """``n`` points of the configuration's dataset."""
    return spec.load_part(root, "datasets", config["dataset"]).points(
        config, rng, n)


def closed_loop(config: Dict, mix: Dict, seed: int, root: str = spec.ROOT
                ) -> Tuple[List[Query], List[Query]]:
    """``(pool, warmup)``: the window's calls in the order they cycle, and
    one more call on each base sample, in coordinates of its own, to warm
    up every shape the pool asks for."""
    bases = [make_cloud(config, rng_of(s), config["n"], root)
             for s in config["base_seeds"]]
    rng = rng_of(seed)

    def copy(k: int) -> Query:
        return Query(clouds.isometric_copy(bases[k], rng), mix["tau_max"],
                     mix["maxdim"], k)
    pool = [copy(k) for k in range(len(bases))]
    warmup = [copy(k) for k in range(len(bases))]
    return pool, warmup
