"""The reference against the program's textbook oracle, on small clouds."""
import numpy as np
import pytest

from bench import compare, reference


def _textbook(points, tau, maxdim):
    from repro.core.ref import standard_reduction_points

    return standard_reduction_points(points, tau, maxdim)


@pytest.mark.parametrize("seed,n,tau,maxdim", [
    (0, 12, 0.9, 2), (1, 14, 1.3, 2), (2, 13, np.inf, 2), (3, 16, 1.1, 1),
    (4, 15, 0.7, 0)])
def test_reference_equals_textbook(seed, n, tau, maxdim):
    points = np.random.default_rng(seed).normal(size=(n, 3))
    got = reference.diagrams(points, tau, maxdim)
    want = _textbook(points, tau, maxdim)
    assert sorted(got) == list(range(maxdim + 1))
    assert compare.bars_off(got, want) == 0


def test_float32_control_moves_every_finite_value():
    points = np.random.default_rng(5).normal(size=(40, 4))
    f64 = reference.diagrams(points, 1.2, 2)
    f32 = reference.diagrams(points, 1.2, 2, dtype=np.float32)
    finite = sum(int(np.isfinite(d[:, 1]).sum()) for d in f64.values())
    assert compare.bars_off(f32, f64) >= finite > 0


def test_bars_off_counts_the_symmetric_difference():
    a = {0: np.array([[0.0, 1.0], [0.0, np.inf]]), 1: np.array([[0.5, 0.7]])}
    b = {0: np.array([[0.0, 1.0], [0.0, 2.0]]), 1: np.zeros((0, 2))}
    assert compare.bars_off(a, a) == 0
    assert compare.bars_off(a, b) == 3
