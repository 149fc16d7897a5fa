"""Per-kernel validation: Pallas (interpret=True) vs pure-jnp/numpy oracles,
swept over shapes and dtypes, plus hypothesis property tests."""
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

import jax.numpy as jnp

from repro.kernels import ref as kref
from repro.kernels.gf2 import (find_low_np, gf2_find_low, gf2_parallel_xor,
                               gf2_serial_reduce)
from repro.kernels.pairwise_dist import pairwise_sq_dists
from repro.kernels import ops


# ---------------------------------------------------------------------------
# pairwise_dist
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("m,n,d,block", [
    (256, 256, 3, 128), (128, 256, 9, 128), (256, 128, 4, 64),
    (512, 256, 16, 256),
])
def test_pairwise_dist_kernel(m, n, d, block):
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(m, d)), jnp.float32)
    y = jnp.asarray(rng.normal(size=(n, d)), jnp.float32)
    out = pairwise_sq_dists(x, y, block_m=block, block_n=block, interpret=True)
    expect = kref.pairwise_sq_dists_ref(x, y)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               rtol=1e-5, atol=1e-4)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_pairwise_dist_dtypes(dtype):
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.normal(size=(128, 8)), dtype)
    out = pairwise_sq_dists(x, x, block_m=128, block_n=128, interpret=True)
    expect = kref.pairwise_sq_dists_ref(x, x)
    atol = 1e-4 if dtype == jnp.float32 else 0.15
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect), atol=atol)
    assert np.allclose(np.diag(np.asarray(out)), 0.0, atol=atol)


def test_pairwise_kernel_asks_for_f32_matmul():
    """A TPU runs a default-precision f32 matmul as one bf16 pass, whose
    error breaks the harvest's f32 candidate margin (interpret mode on a
    CPU cannot show it): the kernel's cross term must ask for HIGHEST."""
    import jax

    def precisions(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "dot_general":
                yield eqn.params["precision"]
            for p in eqn.params.values():
                inner = getattr(p, "jaxpr", p)
                if hasattr(inner, "eqns"):
                    yield from precisions(inner)

    closed = jax.make_jaxpr(
        lambda x, y: pairwise_sq_dists(x, y, interpret=False))(
        jnp.zeros((256, 9)), jnp.zeros((256, 9)))
    found = list(precisions(closed.jaxpr))
    assert found and all(
        p == (jax.lax.Precision.HIGHEST, jax.lax.Precision.HIGHEST)
        for p in found)


def test_ops_pairwise_padding_path():
    """ops wrapper pads ragged row counts before tiling."""
    rng = np.random.default_rng(2)
    x = rng.normal(size=(77, 5))
    out = ops.pairwise_distances(x, use_pallas=True, interpret=True, block=64)
    from repro.core.filtration import pairwise_distances as np_pd
    np.testing.assert_allclose(np.asarray(out), np_pd(x), rtol=1e-4, atol=2e-3)


# ---------------------------------------------------------------------------
# gf2
# ---------------------------------------------------------------------------

# the last three are the packed engine's shapes: rows bucketed to 32,
# widths rounded up to 128 words
@pytest.mark.parametrize("c,w", [(128, 8), (256, 64), (128, 1),
                                 (32, 128), (96, 384), (128, 640)])
def test_find_low_kernel(c, w):
    rng = np.random.default_rng(3)
    cols = rng.integers(0, 2**32, size=(c, w), dtype=np.uint32)
    lead = rng.integers(0, w, size=c)         # zero words before the low
    cols[np.arange(w)[None, :] < lead[:, None]] = 0
    cols[::7] = 0                             # some empty columns
    out = np.asarray(gf2_find_low(jnp.asarray(cols), block_c=128,
                                  interpret=True))
    np.testing.assert_array_equal(out, kref.gf2_find_low_ref(cols))


@settings(max_examples=20, deadline=None)
@given(seed=st.integers(0, 2**31 - 1))
def test_find_low_hypothesis(seed):
    rng = np.random.default_rng(seed)
    w = int(rng.integers(1, 16))
    cols = (rng.integers(0, 2**32, size=(128, w), dtype=np.uint32)
            * rng.integers(0, 2, size=(128, w), dtype=np.uint32))
    out = np.asarray(gf2_find_low(jnp.asarray(cols), interpret=True))
    np.testing.assert_array_equal(out, kref.gf2_find_low_ref(cols))


# rows not a multiple of the 128-row block, engine widths of 128-1,024
# words; the addends cancel some rows to zero and reach into others' lead
@pytest.mark.parametrize("c,w", [(32, 128), (96, 384), (130, 640),
                                 (200, 1024), (256, 256)])
def test_gf2_parallel_xor_returns_lows_of_its_sum(c, w):
    rng = np.random.default_rng(c + w)
    cols = rng.integers(0, 2**32, size=(c, w), dtype=np.uint32)
    cols[np.arange(w)[None, :] < rng.integers(0, w, size=c)[:, None]] = 0
    addends = np.zeros_like(cols)
    hit = rng.random(c) < 0.7
    addends[hit] = rng.integers(0, 2**32, size=(hit.sum(), w),
                                dtype=np.uint32)
    addends[hit] &= rng.integers(0, 2**32, size=(hit.sum(), w),
                                 dtype=np.uint32)
    lead = rng.integers(0, w, size=c)
    addends[np.arange(w)[None, :] < lead[:, None]] = 0
    addends[::5] = cols[::5]                  # sums that are all zero
    cols[::9] = 0
    xored, lows = gf2_parallel_xor(jnp.asarray(cols), jnp.asarray(addends),
                                   interpret=True)
    xored, lows = np.asarray(xored), np.asarray(lows)
    np.testing.assert_array_equal(xored, cols ^ addends)
    np.testing.assert_array_equal(lows, find_low_np(xored))
    assert lows.shape == (c,) and lows.dtype == np.int32
    assert (lows == 2**31 - 1).any() and (lows != 2**31 - 1).any()


@pytest.mark.parametrize("g,c,w", [(1, 8, 4), (2, 16, 8), (4, 32, 2),
                                   (1, 32, 128), (1, 128, 384)])
def test_gf2_serial_reduce_kernel(g, c, w):
    rng = np.random.default_rng(4)
    # sparse-ish random columns so collisions actually happen
    blocks = (rng.integers(0, 2**32, size=(g, c, w), dtype=np.uint32)
              & rng.integers(0, 2**32, size=(g, c, w), dtype=np.uint32)
              & rng.integers(0, 2**32, size=(g, c, w), dtype=np.uint32))
    got_b, got_l, got_r = gf2_serial_reduce(jnp.asarray(blocks),
                                            interpret=True)
    exp_b, exp_l, exp_r = kref.gf2_serial_reduce_ref(blocks)
    np.testing.assert_array_equal(np.asarray(got_b), exp_b)
    np.testing.assert_array_equal(np.asarray(got_l), exp_l)
    np.testing.assert_array_equal(np.asarray(got_r), exp_r)


def test_gf2_serial_reduce_invariant():
    """Post-condition: non-empty columns have pairwise-distinct lows."""
    rng = np.random.default_rng(5)
    blocks = (rng.integers(0, 2**32, size=(2, 24, 4), dtype=np.uint32)
              & rng.integers(0, 2**32, size=(2, 24, 4), dtype=np.uint32))
    _, lows, _ = gf2_serial_reduce(jnp.asarray(blocks), interpret=True)
    lows = np.asarray(lows)
    for g in range(lows.shape[0]):
        nz = lows[g][lows[g] != 2**31 - 1]
        assert len(np.unique(nz)) == len(nz)


def test_gf2_reduction_preserves_span():
    """GF(2) row space of the block is invariant under reduction."""
    rng = np.random.default_rng(6)
    blocks = rng.integers(0, 2**8, size=(1, 10, 1), dtype=np.uint32)
    red, _, _ = gf2_serial_reduce(jnp.asarray(blocks), interpret=True)

    def span(mat):
        vecs = set()
        rows = [int(x) for x in mat]
        for m in range(2 ** len(rows)):
            acc = 0
            for i, r in enumerate(rows):
                if m >> i & 1:
                    acc ^= r
            vecs.add(acc)
        return vecs

    assert span(blocks[0, :, 0]) == span(np.asarray(red)[0, :, 0])


# ---------------------------------------------------------------------------
# backend: persistent compilation cache
# ---------------------------------------------------------------------------

def test_compile_cache_leaves_env_choice_alone(monkeypatch, tmp_path):
    import jax

    from repro.kernels.backend import use_compile_cache
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert use_compile_cache() is None
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_dir_fixed_across_cwd_and_process(tmp_path):
    import os
    import subprocess
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env["PYTHONPATH"] = os.path.join(repo, "src")
    code = ("import jax\n"
            "from repro.kernels.backend import use_compile_cache\n"
            "print(use_compile_cache(), jax.config.jax_compilation_cache_dir)")
    seen = set()
    for cwd in (str(tmp_path), repo):
        out = subprocess.run([sys.executable, "-c", code], cwd=cwd, env=env,
                             capture_output=True, text=True, timeout=120)
        assert out.returncode == 0, out.stderr
        seen.add(out.stdout.strip().splitlines()[-1])
    want = os.path.join(repo, ".jax_cache")
    assert seen == {f"{want} {want}"}


def test_kernel_vs_engine_distance_path():
    """The Pallas distance kernel feeds the PH engine identically to the
    numpy path (filtration-level end-to-end check)."""
    rng = np.random.default_rng(10)
    pts = rng.normal(size=(40, 3))
    d_pallas = np.asarray(ops.pairwise_distances(pts, use_pallas=True,
                                                 interpret=True, block=64))
    from repro.core import compute_ph
    from repro.core.diagrams import assert_diagrams_equal
    a = compute_ph(points=pts, maxdim=1)
    b = compute_ph(dists=np.asarray(d_pallas, np.float64), maxdim=1)
    assert_diagrams_equal(a.diagrams, b.diagrams, dims=[0, 1], atol=1e-5)
