"""Compile the main path's Pallas kernels for a described TPU v5e.

The TPU compiler ships with jaxlib and compiles for a chip that is
described, not attached, so these tests run on a CPU-only host: they catch
what interpret mode cannot (unsupported primitives, block shapes that break
the (8, 128) tiling rule, scoped-VMEM overflow).  Nothing runs; a compile
that passes is not a chip run.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and every test worker
imports this file.
"""
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.kernels.gf2 import gf2_find_low, gf2_parallel_xor, gf2_serial_reduce
from repro.kernels.pairwise_dist import pairwise_sq_dists

# widest packed rows, in uint32 words, that chip_smoke.py prints: phase A
# (o3, n = 8192, tau = 0.6) and phase B (torus4, n = 4096, tau 0.15)
PHASE_A_WORDS, SMOKE_WORDS = 384, 640


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:   # whatever the reason, there is no chip
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described chip's executables cannot be read back from the
    # persistent cache: keep these compiles out of it.  And compile in
    # JAX's default 32-bit mode, which the device path runs in: under
    # x64 Mosaic refuses the 64-bit index maps kernels trace to
    prev = (jax.config.jax_enable_compilation_cache,
            jax.config.jax_enable_x64)
    jax.config.update("jax_enable_compilation_cache", False)
    jax.config.update("jax_enable_x64", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev[0])
    jax.config.update("jax_enable_x64", prev[1])


def compiled_text(fn, sharding, *shapes) -> str:
    args = [jax.ShapeDtypeStruct(s, dt, sharding=sharding)
            for s, dt in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("d", [9, 4])
def test_pairwise_sq_dists_compiles(one_chip, d):
    text = compiled_text(
        lambda x, y: pairwise_sq_dists(x, y, interpret=False), one_chip,
        ((2048, d), jnp.float32), ((2048, d), jnp.float32))
    assert "tpu_custom_call" in text


# torus4 at n = 50,000 in tiles of 2,048 leaves a last tile row and column
# 848 wide: the wrapper pads those to its 256 blocks and crops the result
@pytest.mark.parametrize("m,n", [(2048, 848), (848, 2048), (848, 848)])
def test_pairwise_sq_dists_compiles_ragged(one_chip, m, n):
    text = compiled_text(
        lambda x, y: pairwise_sq_dists(x, y, interpret=False), one_chip,
        ((m, 4), jnp.float32), ((n, 4), jnp.float32))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("w", [PHASE_A_WORDS, SMOKE_WORDS])
def test_gf2_find_low_compiles(one_chip, w):
    text = compiled_text(lambda c: gf2_find_low(c, interpret=False),
                         one_chip, ((128, w), jnp.uint32))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("w", [PHASE_A_WORDS, SMOKE_WORDS])
def test_gf2_parallel_xor_compiles(one_chip, w):
    # the sum and its per-row lows leave one kernel, under the op name
    # the benchmark's trace reader finds
    text = compiled_text(
        lambda c, a: gf2_parallel_xor(c, a, interpret=False), one_chip,
        ((128, w), jnp.uint32), ((128, w), jnp.uint32))
    assert text.count("tpu_custom_call") == 1
    assert "gf2_parallel_xor" in text


@pytest.mark.parametrize("g,c,w", [(1, 128, SMOKE_WORDS), (4, 32, 128)])
def test_gf2_serial_reduce_compiles(one_chip, g, c, w):
    text = compiled_text(lambda b: gf2_serial_reduce(b, interpret=False),
                         one_chip, ((g, c, w), jnp.uint32))
    assert "tpu_custom_call" in text
