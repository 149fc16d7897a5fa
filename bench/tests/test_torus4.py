"""The torus4 cell (``torus4_50k.h1_t025``): its generator, the cell end to
end on the CPU at a size a test run holds, and the two harvest readers it
brings (``harvest_fetch_mb``, ``harvest_threshold_s``)."""
import json
import os
import shutil

import numpy as np
import pytest

from bench import control, run, spec, trace, traffic

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
CELL = "torus4_50k.h1_t025"


def torus4():
    return spec.load_part(ROOT, "datasets", "torus4")


def test_points_lie_on_the_clifford_torus():
    x = torus4().points({}, traffic.rng_of(2**33 + 1), 5_000)
    assert x.shape == (5_000, 4)
    np.testing.assert_allclose(np.sum(x[:, :2] ** 2, axis=1), 0.5,
                               rtol=0, atol=1e-15)
    np.testing.assert_allclose(np.sum(x[:, 2:] ** 2, axis=1), 0.5,
                               rtol=0, atol=1e-15)
    # both angles cover the circle
    for a in (np.arctan2(x[:, 1], x[:, 0]), np.arctan2(x[:, 3], x[:, 2])):
        assert a.min() < -3.0 and a.max() > 3.0


def test_points_repeat_bit_for_bit_from_their_rng():
    make = torus4().points
    a = make({}, traffic.rng_of(7), 1_000)
    b = make({}, traffic.rng_of(7), 1_000)
    assert a.tobytes() == b.tobytes()
    assert make({}, traffic.rng_of(8), 1_000).tobytes() != a.tobytes()


def test_configuration_is_the_published_set():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = spec.load(CELL, ROOT)
    assert cell.chips == 1
    assert cell.config["dataset"] == "torus4"
    assert (cell.config["n"], cell.config["d"]) == (50_000, 4)
    assert cell.config["base_seeds"] == [0, 1]
    assert cell.mix == {"loop": "closed", "tau_max": 0.025, "maxdim": 1}
    entry = {c["name"]: c for c in bench["configs"]}["torus4_50k"]
    assert entry["reduced"] == cell.config["reduced"] == ["tau_max"]
    assert "torus4" in entry["source"]
    others = [c["source"] for c in bench["configs"] if c["name"] != "torus4_50k"]
    assert entry["source"] not in others
    assert {"harvest_fetch_mb", "harvest_threshold_s", "harvest_s",
            "reduce_s", "dist_roofline"} <= set(cell.readers)
    assert "coo_build_s" not in cell.readers


# ---------------------------------------------------------------------------
# the cell end to end, cut to a size a test run holds
# ---------------------------------------------------------------------------

@pytest.fixture
def device_path(monkeypatch):
    import repro.core.packed_reduce as packed_reduce
    import repro.scale.tiles as tiles

    monkeypatch.setattr(tiles, "_resolve_backend",
                        lambda b: "pallas" if b == "auto" else b)
    monkeypatch.setattr(packed_reduce, "_resolve_use_kernels",
                        lambda u: True if u is None else bool(u))


@pytest.fixture
def root(tmp_path):
    """A checkout with the benchmark's files; the torus4 configuration cut
    to 300 points and its mix's tau raised to keep the cell's density
    (mean degree about 5: tau 0.025 * sqrt(50,000 / 300))."""
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    b = tmp_path / "bench"
    with open(b / "configs" / "torus4_50k.json") as f:
        config = json.load(f)
    with open(b / "configs" / "torus4_50k.json", "w") as f:
        json.dump(dict(config, n=300), f)
    with open(b / "traffic" / "h1_t025.json") as f:
        mix = json.load(f)
    with open(b / "traffic" / "h1_t025.json", "w") as f:
        json.dump(dict(mix, tau_max=0.025 * np.sqrt(50_000 / 300)), f)
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    return str(tmp_path)


def test_cell_runs_and_is_correct(root, device_path, capsys):
    assert run.main(["--workload", CELL, "--seed", str(2**32 + 3),
                     "--seconds", "2", "--trace", "1"],
                    root=root, require_tpu=False) == 0
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert out["correct"] and out["attempted"] > 0
    assert out["checks"]["bars_off"] == {"value": 0, "limit": 0}
    m = out["metrics"]
    assert {"harvest_s", "harvest_wait_s", "harvest_threshold_s",
            "reduce_s", "reduce_rounds", "window_compiles.batch"} <= set(m)
    # 300 points in tiles of 2,048: one tile of 300 x 300 f32 a call
    assert m["harvest_fetch_mb"]["value"] == 300 * 300 * 4 / 1e6
    assert 0.0 < m["harvest_threshold_s"]["value"] \
        < m["harvest_s"]["value"]
    assert {"base0", "base1"} <= set(out["notes"])


def test_float32_control_is_not_correct(root):
    cell = spec.load(CELL, root)
    seed = 2**31 + 11
    out = run.run(cell, seed, 2.0, False, require_tpu=False,
                  driver=control.control_driver(cell, seed))
    assert not out["correct"]
    assert out["checks"]["bars_off"]["value"] > 0


# ---------------------------------------------------------------------------
# the two readers
# ---------------------------------------------------------------------------

def reader(name):
    return spec.load_reader(os.path.join(BENCH, "metrics", name + ".py"))


def synthetic(spans, stats=({}, {})):
    """Two calls in a window of 1,000-9,000 ns."""
    tr = trace.Trace(window=(1_000.0, 9_000.0), devices={},
                     spans=sorted(spans, key=lambda s: (s[0], -s[1])))
    return run.Run(device_kind="TPU v5 lite",
                   calls=[{"stats": dict(s)} for s in stats],
                   window_compiles=0, trace=tr)


SPANS = [
    (500.0, 900.0, "harvest/threshold"),      # before the window
    (1_100.0, 3_000.0, "harvest/refine"),
    (1_100.0, 1_400.0, "harvest/threshold"),
    (1_400.0, 2_900.0, "harvest/remeasure"),
    (3_100.0, 5_000.0, "harvest/refine"),
    (3_100.0, 3_300.0, "harvest/threshold"),
    (8_950.0, 9_050.0, "harvest/threshold"),  # starts inside, ends after
    (9_000.0, 9_200.0, "harvest/threshold"),  # starts at the window's end
]


def test_threshold_reader_sums_spans_starting_in_the_window():
    assert reader("harvest_threshold_s")(synthetic(SPANS)) == \
        pytest.approx((300 + 200 + 100) * 1e-9 / 2, rel=1e-12)


def test_fetch_reader_averages_the_counter():
    r = synthetic([], stats=({"harvest_fetch_bytes": 5_202_764_800.0},
                             {"harvest_fetch_bytes": 167_772_160.0}))
    assert reader("harvest_fetch_mb")(r) == pytest.approx(
        (5_202.7648 + 167.77216) / 2, rel=1e-12)


@pytest.mark.parametrize("name", ["harvest_threshold_s", "harvest_fetch_mb"])
def test_reader_finds_nothing_in_a_program_without_it(name):
    """A program without the spans or the counter (an earlier program) reads
    nothing and raises nothing, traced or not."""
    old = [s for s in SPANS if s[2] == "harvest/refine"]
    assert reader(name)(synthetic(old, stats=({"harvest_pallas": 1.0},))) \
        is None
    untraced = synthetic([], stats=({"harvest_pallas": 1.0},))
    untraced.trace = None
    assert reader(name)(untraced) is None
    assert reader(name)(run.Run("TPU v5 lite", [], 0, None)) is None
