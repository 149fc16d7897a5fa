"""The COO cell's parts on the CPU: the Hi-C dataset, the reference of
sparse maps, the map loop, and the cell end to end at a size a test holds.

The reference has to agree with ``compute_ph(coo=...)`` bar for bar on
small maps and catch a planted fault (one contact dropped, distances in
float32).  A run's pool is the same bits for the same seed, and the same
pixels in another order for another seed.  The loop and the dataset are
found by name (``spec.load_part``), with no edit to the harness.
"""
import hashlib
import json
import os
import shutil

import numpy as np
import pytest

from bench import compare, control, reference_coo, run, spec

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
CELL = "hic_chr16_5kb.coo_h1"


def _config():
    with open(os.path.join(BENCH, "configs", "hic_chr16_5kb.json")) as f:
        return json.load(f)


def tiny_config():
    """The configuration's model over 300 bins: contacts within 30 bins,
    a shallower decay, six loops and a 20-bin centromere."""
    return dict(_config(), chrom_length_bp=1_500_000,
                max_separation_bins=30, decay_scale=100.0,
                loops={"count": 6, "min_separation_bins": 8,
                       "max_separation_bins": 20},
                centromere_bp=[600_000, 700_000])


def hic():
    return spec.load_part(ROOT, "datasets", "hic")


def program(n, b1, b2, contact, tau, maxdim):
    from repro.core.homology import compute_ph
    from repro.scale import contacts_to_distances

    return compute_ph(coo=(b1, b2, contacts_to_distances(contact), n),
                      tau_max=tau, maxdim=maxdim, engine="packed").diagrams


def untidy(b1, b2, c, rng):
    """The same map with pixels listed twice, some as ``(j, i)`` with a
    smaller contact (a larger distance, which loses), and a diagonal."""
    k = rng.choice(b1.size, size=20, replace=False)
    return (np.concatenate([b1, b2[k], [5]]),
            np.concatenate([b2, b1[k], [5]]),
            np.concatenate([c, c[k] * 0.5, [99.0]]))


@pytest.mark.parametrize("seed,maxdim", [(0, 1), (1, 1), (2, 2)])
def test_reference_equals_compute_ph(seed, maxdim):
    config = tiny_config()
    n = hic().n_bins(config)
    rng = np.random.default_rng(seed)
    for b1, b2, c in hic().contacts(config, rng).values():
        b1, b2, c = untidy(b1, b2, c, rng)
        want = reference_coo.diagrams(n, b1, b2, c, 0.05, maxdim)
        assert compare.bars_off(program(n, b1, b2, c, 0.05, maxdim),
                                want) == 0
        assert len(want[1]) > 0
        # the centromere's bins stay, each its own component
        assert int(np.isinf(want[0][:, 1]).sum()) >= 20


def test_planted_faults_are_caught():
    config = tiny_config()
    n = hic().n_bins(config)
    b1, b2, c = hic().contacts(config, np.random.default_rng(3))["control"]
    want = reference_coo.diagrams(n, b1, b2, c, 0.05, 1)
    drop = np.arange(c.size) != np.argmax(c)      # the shortest edge
    got = program(n, b1[drop], b2[drop], c[drop], 0.05, 1)
    assert compare.bars_off(got, want) > 0
    f32 = reference_coo.diagrams(n, b1, b2, c, 0.05, 1, np.float32)
    assert compare.bars_off(f32, want) > 0


def _cell(config):
    return spec.Cell(root=ROOT, name="tiny", chips=1, config=config,
                     mix={"loop": "coo", "tau_max": 0.05, "maxdim": 1},
                     end_to_end=[], per_layer=[], readers={})


def _digest(queries):
    h = hashlib.sha256()
    for q in queries:
        for a in (q.bin1, q.bin2, q.contact):
            h.update(np.ascontiguousarray(a).tobytes())
        h.update(repr((q.n, q.tau, q.maxdim, q.base)).encode())
    return h.hexdigest()


# sha256 of the tiny map's pool and warm-up for seed 2**31 + 11, as this
# generator first made them: a change to the maps or to the order drawn
# from the seed shows here
TINY_DIGEST = \
    "c9997a8d4ce78c89208186d329e856eb233c19f6a71ddcd83eefbd773c359e66"


def test_pool_is_the_same_bits_for_a_seed_and_the_same_pixels_for_any():
    loop = spec.load_part(ROOT, "loops", "coo")
    cell = _cell(tiny_config())
    a, wa = loop.pools(cell, 2**31 + 11)
    b, wb = loop.pools(cell, 2**31 + 11)
    c, _ = loop.pools(cell, 3_000_000_019)
    assert _digest(a + wa) == _digest(b + wb) == TINY_DIGEST
    assert _digest(a) != _digest(c)
    assert [q.base for q in a] == [q.base for q in c] == [0, 1]
    for x, y, w in zip(a, c, wa):
        def pixels(q):
            return sorted(zip(q.bin1.tolist(), q.bin2.tolist(),
                              q.contact.tolist()))
        assert pixels(x) == pixels(y) == pixels(w)
        assert not np.array_equal(x.bin1, y.bin1)
    # the two conditions differ only at loop pixels, where control is
    # enriched
    assert len(a[0].bin1) != len(a[1].bin1) \
        or not np.array_equal(np.sort(a[0].contact), np.sort(a[1].contact))


def test_configuration_maps():
    """hg38 chr16 at 5 kb: 18,068 bins; the centromere block carries no
    pixel; control and auxin differ at the loops, control enriched."""
    config = _config()
    assert hic().n_bins(config) == 18_068
    maps = hic().contacts(config, np.random.default_rng(config["base_seed"]))
    assert list(maps) == ["control", "auxin"]
    c0, c1 = (x // config["resolution_bp"] for x in config["centromere_bp"])
    for b1, b2, c in maps.values():
        assert 4_500_000 < b1.size < 4_800_000
        assert np.all(b1 < b2) and np.all(b2 - b1 <= 400)
        assert not np.any((b1 >= c0) & (b1 < c1) | (b2 >= c0) & (b2 < c1))
        assert np.all(c > 0)
    ctl = dict(zip(zip(maps["control"][0].tolist(),
                       maps["control"][1].tolist()), maps["control"][2]))
    aux = dict(zip(zip(maps["auxin"][0].tolist(),
                       maps["auxin"][1].tolist()), maps["auxin"][2]))
    moved = [k for k in ctl.keys() | aux.keys()
             if ctl.get(k, 0.0) != aux.get(k, 0.0)]
    assert 0 < len(moved) <= 9 * config["loops"]["count"]
    assert sum(ctl.get(k, 0.0) for k in moved) \
        > 2.0 * sum(aux.get(k, 0.0) for k in moved)


# ---------------------------------------------------------------------------
# the cell end to end
# ---------------------------------------------------------------------------

@pytest.fixture
def device_path(monkeypatch):
    import repro.core.packed_reduce as packed_reduce

    monkeypatch.setattr(packed_reduce, "_resolve_use_kernels",
                        lambda u: True if u is None else bool(u))


@pytest.fixture
def root(tmp_path):
    """A checkout with the benchmark's files, and the COO cell's
    configuration cut to ``tiny_config``."""
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    with open(tmp_path / "bench" / "configs" / "hic_chr16_5kb.json",
              "w") as f:
        json.dump(tiny_config(), f)
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    return str(tmp_path)


def _run(root, traced=False, seed=2**31 + 7, driver=None):
    cell = spec.load(CELL, root)
    return run.run(cell, seed, 2.0, traced, require_tpu=False, driver=driver)


def test_cell_runs_and_is_correct(root, device_path, capsys):
    assert run.main(["--workload", CELL, "--seed", str(2**32 + 1),
                     "--seconds", "2", "--trace", "1"],
                    root=root, require_tpu=False) == 0
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert out["correct"] and out["attempted"] > 0
    assert out["checks"]["bars_off"] == {"value": 0, "limit": 0}
    assert {"coo_build_s", "reduce_s", "reduce_rounds",
            "reduce_device_calls", "reduce_wait_s"} <= set(out["metrics"])
    assert not {"harvest_s", "harvest_wait_s",
                "dist_roofline"} & set(out["metrics"])
    untraced = _run(root)
    assert untraced["correct"]
    assert set(untraced["metrics"]) == {"ph_s", "setup_s"}
    assert {"base0", "base1"} <= set(untraced["notes"])


def test_host_fallback_is_refused(root):
    with pytest.raises(RuntimeError, match="gf2 kernels"):
        _run(root)


def test_a_dropped_contact_is_not_correct(root, device_path, monkeypatch):
    import repro.scale.sparse_input as sparse_input

    original = sparse_input.coo_symmetrize

    def drop_shortest(rows, cols, vals, n=None):
        keep = np.arange(np.size(vals)) != np.argmin(vals)
        return original(np.asarray(rows)[keep], np.asarray(cols)[keep],
                        np.asarray(vals)[keep], n)
    monkeypatch.setattr(sparse_input, "coo_symmetrize", drop_shortest)
    out = _run(root)
    assert not out["correct"]
    assert out["checks"]["bars_off"]["value"] > 0


def test_float32_control_is_not_correct(root):
    cell = spec.load(CELL, root)
    seed = 2**31 + 9
    out = run.run(cell, seed, 2.0, False, require_tpu=False,
                  driver=control.control_driver(cell, seed))
    assert not out["correct"]
    assert out["checks"]["bars_off"]["value"] > 0
