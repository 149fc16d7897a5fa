"""The readers of the program's leaf spans and device round-trip counter
(``reduce_wait_s``, ``harvest_wait_s``, ``reduce_device_calls``): on
synthetic traces, where every number is known, and once end to end on the
CPU, where the spans have to travel through the profiler to be read."""
import collections
import json
import os
import shutil

import pytest

from bench import run, spec, trace

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NEW = ("reduce_device_calls", "reduce_wait_s", "harvest_wait_s")


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
    (500.0, 900.0, "gf2/xor"),            # before the window
    (1_000.0, 1_500.0, "gf2/xor"),
    (1_100.0, 2_000.0, "harvest/tile"),
    (1_200.0, 1_800.0, "harvest/fetch"),
    (2_000.0, 2_250.0, "gf2/find_low"),
    (2_000.0, 2_600.0, "reduce/xor"),
    (8_900.0, 9_400.0, "gf2/serial"),     # starts inside, ends after
    (9_000.0, 9_100.0, "gf2/xor"),        # starts at the window's end
    (9_500.0, 9_900.0, "harvest/fetch"),  # after the window
]


def test_wait_readers_sum_spans_starting_in_the_window():
    r = synthetic(SPANS)
    assert reader("reduce_wait_s")(r) == pytest.approx(
        (500 + 250 + 500) * 1e-9 / 2, rel=1e-12)
    assert reader("harvest_wait_s")(r) == pytest.approx(600e-9 / 2,
                                                        rel=1e-12)


def test_device_calls_reader_averages_the_counter():
    r = synthetic([], stats=({"h1_n_device_calls": 30.0,
                              "h2_n_device_calls": 100.0},
                             {"h1_n_device_calls": 10.0}))
    assert reader("reduce_device_calls")(r) == 70.0


@pytest.mark.parametrize("name", NEW)
def test_reader_finds_nothing_in_a_program_without_it(name):
    """A program without the spans or the counter (the earlier program)
    reads nothing and raises nothing, traced or not."""
    old = [s for s in SPANS if not s[2].startswith(("gf2/", "harvest/f"))]
    assert reader(name)(synthetic(old, stats=({"h1_n_rounds": 3.0},))) \
        is None
    untraced = synthetic([], stats=({"h1_n_rounds": 3.0},))
    untraced.trace = None
    assert reader(name)(untraced) is None
    assert reader(name)(run.Run("TPU v5 lite", [], 0, None)) is None


# one warm compute_ph(backend="tiled", engine="packed") call on o3 at
# n = 8192, tau 0.3, maxdim 1, recorded on a TPU v5e under
# tracing(Tracer(bridge=True)); its stats counted 36 gf2 round trips
RECORDED = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                        "o3_h1_call_spans.xplane.pb")


def test_recorded_call_carries_the_leaf_spans():
    tr = trace.read(RECORDED)
    names = collections.Counter(name for _, _, name in tr.spans)
    assert {"ph/filtration", "ph/h0", "ph/h1", "ph/adapter",
            "reduce/fused", "reduce/sweep", "reduce/cobdy", "reduce/probe",
            "reduce/pack", "reduce/gens", "reduce/xor", "reduce/commit",
            "gf2/xor", "gf2/find_low", "harvest/fetch", "harvest/refine",
            "harvest/build"} <= set(names)
    assert names["harvest/tile"] == names["harvest/fetch"] == \
        names["harvest/refine"] == 10
    assert sum(n for k, n in names.items() if k.startswith("gf2/")) == 36
    r = run.Run("TPU v5 lite", [{"stats": {"h1_n_device_calls": 36.0}}], 0,
                tr)
    assert reader("reduce_device_calls")(r) == 36.0
    assert 0.0 < reader("harvest_wait_s")(r) < tr.window_s
    assert 0.0 < reader("reduce_wait_s")(r) < tr.window_s
    # the device's idle time is named by the leaf spans, not the call
    gaps = dict(tr.idle_gaps())
    assert "ph/compute_ph" not in gaps
    assert max(gaps, key=gaps.get) == "harvest/refine"


def _write(path, obj):
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        json.dump(obj, f)


@pytest.fixture
def root(tmp_path):
    """A checkout with the benchmark's files and a tiny o3 cell that
    reports the three metrics."""
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    b = tmp_path / "bench"
    with open(b / "configs" / "o3_8192.json") as f:
        o3 = json.load(f)
    _write(str(b / "configs" / "tiny_o3.json"),
           dict(o3, n=160, base_seeds=[0]))
    _write(str(b / "traffic" / "tiny.json"),
           {"loop": "closed", "tau_max": 1.6, "maxdim": 2})
    cell = "tiny_o3.tiny"
    _write(str(tmp_path / "BENCHMARK.json"), {
        "command": ["python3", "bench/run.py"], "paths": ["bench"],
        "run_seconds": 2,
        "configs": [{"name": "tiny_o3", "source": "test",
                     "file": "bench/configs/tiny_o3.json", "reduced": ["n"],
                     "why": "test"}],
        "workloads": [{"name": cell, "config": "tiny_o3", "traffic": "tiny",
                       "chips": 1, "why": "test"}],
        "end_to_end": [
            {"name": "ph_s", "unit": "s", "better": "lower", "bound": 0.1,
             "source": "host_clock", "workloads": [cell]},
            {"name": "setup_s", "unit": "s", "better": "lower",
             "bound": 0.25, "source": "host_clock"}],
        "per_layer": [{"name": n, "unit": "x", "better": "lower",
                       "source": "program_span", "layer": "test",
                       "moves": "ph_s", "workloads": [cell]} for n in NEW],
    })
    return str(tmp_path)


@pytest.fixture
def device_path(monkeypatch):
    monkeypatch.setattr("repro.scale.tiles._resolve_backend",
                        lambda b: "pallas" if b == "auto" else b)
    monkeypatch.setattr("repro.core.packed_reduce._resolve_use_kernels",
                        lambda u: True if u is None else bool(u))


def test_traced_run_reads_the_bridged_spans(root, device_path, capsys):
    """The window's spans reach the profiler's trace (the CPU's host plane
    here) and the three readers each print a number."""
    assert run.main(["--workload", "tiny_o3.tiny", "--seed", str(2**33 + 5),
                     "--seconds", "2", "--trace", "1"],
                    root=root, require_tpu=False) == 0
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert out["correct"]
    for name in NEW:
        assert out["metrics"][name]["value"] > 0, name
