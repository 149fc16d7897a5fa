"""The generator: same seed, same inputs; another seed, the same work."""
import hashlib
import json
import os

import numpy as np
import pytest

from bench import clouds, spec, traffic

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)


def _load(kind, name):
    with open(os.path.join(BENCH, kind, name + ".json")) as f:
        return json.load(f)


def test_isometric_copy_keeps_distances():
    rng = np.random.default_rng(0)
    base = spec.load_part(ROOT, "datasets", "o3").points({}, rng, 50)
    copy = clouds.isometric_copy(base, np.random.default_rng(3_000_000_019))
    d = lambda p: np.sort(np.linalg.norm(p[:, None] - p[None], axis=-1),
                          axis=None)
    assert np.allclose(d(base), d(copy), atol=1e-12)
    assert not np.allclose(np.sort(base, axis=None), np.sort(copy, axis=None))


def test_closed_loop_pool_from_seed():
    config = dict(_load("configs", "o3_8192"), n=64)
    mix = _load("traffic", "h2")
    a, wa = traffic.closed_loop(config, mix, 2**31 + 5)
    b, _ = traffic.closed_loop(config, mix, 2**31 + 5)
    c, _ = traffic.closed_loop(config, mix, 11)
    assert all(np.array_equal(x.points, y.points) for x, y in zip(a, b))
    assert not np.array_equal(a[0].points, c[0].points)
    assert [q.base for q in a] == [q.base for q in c] == [0, 1, 2]
    assert [q.base for q in wa] == [0, 1, 2]
    assert all(q.tau == mix["tau_max"] and q.maxdim == mix["maxdim"]
               for q in a + wa)


def _dists(p):
    return np.sort(np.linalg.norm(p[:, None] - p[None], axis=-1), axis=None)


def test_pool_holds_distinct_complexes_whatever_the_seed():
    """Each pool cloud is a copy of its own base sample: the seed moves the
    coordinates, never the distances, and the samples differ."""
    config = dict(_load("configs", "o3_8192"), n=40)
    mix = _load("traffic", "h2")
    a, wa = traffic.closed_loop(config, mix, 3)
    b, _ = traffic.closed_loop(config, mix, 2**33 + 1)
    for x, y, w in zip(a, b, wa):
        assert np.allclose(_dists(x.points), _dists(y.points), atol=1e-12)
        assert np.allclose(_dists(x.points), _dists(w.points), atol=1e-12)
        assert not np.array_equal(x.points, w.points)
    assert not np.allclose(_dists(a[0].points), _dists(a[1].points))


def _digest(queries):
    h = hashlib.sha256()
    for q in queries:
        h.update(np.ascontiguousarray(q.points).tobytes())
        h.update(repr((q.tau, q.maxdim, q.base)).encode())
    return h.hexdigest()


# the o3 pools and warm-ups as the generator made them before the datasets
# moved to bench/datasets/ (sha256 of every call's points, tau, maxdim and
# base sample, in order)
O3_DIGESTS = {
    ("h2", 2**31 + 5):
        "662ca2a190df4613d8b5c96d6c4ec0d01312b96b75bf3d92af23b76763a0039c",
    ("h2", 7):
        "f42393b55eeec44be63f7f35ea7fa84c0111a3305462fa4c172e364458a643b1",
    ("h1", 2**33 + 1):
        "22a8a81dd82a147dcf4e0caed27a58612ccca4bdab8d1e1e1401526004204f69",
}


@pytest.mark.parametrize("mix,seed", sorted(O3_DIGESTS))
def test_o3_pool_is_bit_identical_to_before_the_move(mix, seed):
    pool, warm = traffic.closed_loop(_load("configs", "o3_8192"),
                                     _load("traffic", mix), seed)
    assert _digest(pool + warm) == O3_DIGESTS[(mix, seed)]


@pytest.mark.parametrize("kind,name", [("datasets", "o4"),
                                       ("loops", "half_open")])
def test_an_unknown_loop_or_dataset_fails_with_its_name(kind, name):
    with pytest.raises(ValueError, match=f"unknown {kind[:-1]} '{name}'"):
        spec.load_part(ROOT, kind, name)
    config = dict(_load("configs", "o3_8192"), n=8)
    if kind == "datasets":
        with pytest.raises(ValueError, match=name):
            traffic.closed_loop(dict(config, dataset=name),
                                _load("traffic", "h2"), 1)
