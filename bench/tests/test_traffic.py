"""The generator: same seed, same inputs; another seed, the same work."""
import json
import os

import numpy as np

from bench import clouds, traffic

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load(kind, name):
    with open(os.path.join(BENCH, kind, name + ".json")) as f:
        return json.load(f)


def test_isometric_copy_keeps_distances():
    rng = np.random.default_rng(0)
    base = clouds.o3(rng, 50)
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
