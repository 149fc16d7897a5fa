"""Hi-C contact maps of one chromosome, control and auxin (Dory, arXiv
2103.05608, §6: HCT116-RAD21-mAID, Rao et al. 2017, GEO GSE104334).

The real maps are a download and are not in the repository, so the
contacts are drawn from a model whose parts the configuration names under
``assumed``:

* decay: the expected raw count between bins ``s`` apart is
  ``decay_scale * s**decay_exponent`` (an ``s**-1`` decay, Lieberman-Aiden
  et al. 2009), for ``1 <= s <= max_separation_bins``;
* bias: each bin has a lognormal bias ``b`` (``log b ~ N(0, bias_sigma)``);
  raw counts are ``Poisson(lambda * b_i * b_j)`` and the value handed on is
  the balanced ``count / (b_i * b_j)``, as ICE/KR leaves a map;
* loops: ``loops.count`` anchor pairs, separations log-uniform over
  ``[loops.min_separation_bins, loops.max_separation_bins]``, whose pixel
  and its 8 neighbours have their expected count times the condition's
  enrichment (``conditions``: control 4, auxin 1, cohesin gone);
* the centromere block ``centromere_bp`` carries no contacts; its bins stay.

Both conditions come from ``base_seed``: one draw of bias, loop anchors and
raw counts is the auxin map, and the control map is that map with its loop
pixels drawn again at the control's enrichment.  Only pixels with a
non-zero count are kept: an upper-triangle pixel table ``(bin1, bin2,
balanced)``, ``bin1 < bin2``, sorted by separation, then ``bin1``.
"""
from __future__ import annotations

from typing import Dict, Tuple

import numpy as np

Pixels = Tuple[np.ndarray, np.ndarray, np.ndarray]


def n_bins(config: Dict) -> int:
    """Bins of the chromosome at the configuration's resolution."""
    return -(-int(config["chrom_length_bp"]) // int(config["resolution_bp"]))


def _band(n: int, width: int) -> Tuple[np.ndarray, np.ndarray]:
    """``(bin1, sep)`` of every pixel ``bin1 < bin1 + sep < n`` with
    ``1 <= sep <= width``, ordered by ``sep`` then ``bin1``."""
    seps = np.arange(1, width + 1)
    counts = n - seps
    sep = np.repeat(seps, counts)
    starts = np.cumsum(counts) - counts
    bin1 = np.arange(sep.size) - np.repeat(starts, counts)
    return bin1, sep


def _flat(n: int, bin1: np.ndarray, sep: np.ndarray) -> np.ndarray:
    """Position of pixel ``(bin1, bin1 + sep)`` in ``_band``'s order."""
    return (sep - 1) * n - (sep - 1) * sep // 2 + bin1


def _loop_pixels(config: Dict, rng: np.random.Generator, n: int,
                 empty: np.ndarray) -> np.ndarray:
    """Band positions of the loop footprints: each anchor pixel and its 8
    neighbours, each position once; none touches an empty bin."""
    loops = config["loops"]
    width = int(config["max_separation_bins"])
    lo, hi = np.log(loops["min_separation_bins"]), \
        np.log(loops["max_separation_bins"])
    sep = np.rint(np.exp(rng.uniform(lo, hi, loops["count"]))).astype(
        np.int64)
    bin1 = rng.integers(1, n - sep - 1)
    d = np.array([-1, 0, 1])
    a = (bin1[:, None, None] + d[None, :, None]).repeat(3, axis=2).ravel()
    b = (bin1[:, None, None] + sep[:, None, None]
         + d[None, None, :]).repeat(3, axis=1).ravel()
    keep = ~(empty[a] | empty[b]) & (b - a >= 1) & (b - a <= width)
    return np.unique(_flat(n, a[keep], (b - a)[keep]))


def contacts(config: Dict, rng: np.random.Generator) -> Dict[str, Pixels]:
    """The balanced pixel table of each condition, by condition name."""
    n = n_bins(config)
    res = int(config["resolution_bp"])
    bias = np.exp(rng.normal(0.0, config["bias_sigma"], n))
    c0, c1 = config["centromere_bp"]
    empty = np.zeros(n, dtype=bool)
    empty[c0 // res:-(-c1 // res)] = True
    loop_at = _loop_pixels(config, rng, n, empty)

    bin1, sep = _band(n, int(config["max_separation_bins"]))
    bin2 = bin1 + sep
    expected = (config["decay_scale"]
                * sep.astype(np.float64) ** config["decay_exponent"]
                * bias[bin1] * bias[bin2])
    expected[empty[bin1] | empty[bin2]] = 0.0
    raw = rng.poisson(expected)
    out = {}
    for name, enrichment in config["conditions"].items():
        counts = raw
        if enrichment != 1.0:
            counts = raw.copy()
            counts[loop_at] = rng.poisson(enrichment * expected[loop_at])
        hit = np.flatnonzero(counts)
        i, j = bin1[hit], bin2[hit]
        out[name] = (i, j, counts[hit] / (bias[i] * bias[j]))
    return out
