"""The harness end to end on the CPU, at a size a test run holds.

It skips the look for a chip (``require_tpu=False``) and drives the rest of
a run: set-up, window, check, the result line.  The device path is forced
on (Pallas and the gf2 kernels in interpret mode), so the harness's guards
see what they see on the chip.  Then the timed path is broken underneath,
once for each fault the cell can have (an answer altered where it is
produced, half of the harvested edges left out), and ``correct`` has to
come out false; so does the control, the float32 reference in the
program's place.  One chip has no exchange to leave out, and a closed loop
of whole calls keeps no state from one step to the next.
"""
import json
import os
import shutil

import pytest

from bench import control, run, spec

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def device_path(monkeypatch):
    import repro.core.packed_reduce as packed_reduce
    import repro.scale.tiles as tiles

    monkeypatch.setattr(tiles, "_resolve_backend",
                        lambda b: "pallas" if b == "auto" else b)
    monkeypatch.setattr(packed_reduce, "_resolve_use_kernels",
                        lambda u: True if u is None else bool(u))


def _write(path, obj):
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        json.dump(obj, f)


@pytest.fixture
def root(tmp_path):
    """A checkout with the benchmark's files and a tiny cell, whose mix and
    one of whose metrics exist only as new files."""
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    b = tmp_path / "bench"
    with open(b / "configs" / "o3_8192.json") as f:
        o3 = json.load(f)
    _write(str(b / "configs" / "tiny_o3.json"),
           dict(o3, n=160, base_seeds=[0, 1]))
    _write(str(b / "traffic" / "dummy.json"),
           {"loop": "closed", "tau_max": 1.6, "maxdim": 2})
    (b / "metrics" / "calls_in_window.py").write_text(
        "def read(run):\n    return len(run.calls)\n")
    o3_cell = "tiny_o3.dummy"

    def metric(name, unit, moves, cell, source="host_clock"):
        return {"name": name, "unit": unit, "better": "lower",
                "source": source, "layer": "test", "moves": moves,
                "workloads": [cell]}
    per_layer = [metric(n, "x", "ph_s", o3_cell) for n in (
        "harvest_s", "reduce_s", "reduce_rounds", "dist_roofline",
        "gf2_kernel_ms", "device_idle.batch", "window_compiles.batch",
        "calls_in_window")]
    bench = {
        "command": ["python3", "bench/run.py"], "paths": ["bench"],
        "run_seconds": 2,
        "configs": [
            {"name": "tiny_o3", "source": "test",
             "file": "bench/configs/tiny_o3.json", "reduced": ["n"],
             "why": "test"}],
        "workloads": [
            {"name": o3_cell, "config": "tiny_o3", "traffic": "dummy",
             "chips": 1, "why": "test"}],
        "end_to_end": [
            {"name": "ph_s", "unit": "s", "better": "lower", "bound": 0.1,
             "source": "host_clock", "workloads": [o3_cell]},
            {"name": "setup_s", "unit": "s", "better": "lower",
             "bound": 0.25, "source": "host_clock"}],
        "per_layer": per_layer,
    }
    _write(str(tmp_path / "BENCHMARK.json"), bench)
    return str(tmp_path)


def _run(root, workload, traced=False, seed=2**31 + 7, driver=None):
    cell = spec.load(workload, root)
    return run.run(cell, seed, 2.0, traced, require_tpu=False, driver=driver)


def test_new_mix_and_metric_files_run(root, device_path, capsys):
    assert run.main(["--workload", "tiny_o3.dummy", "--seed", str(2**32 + 1),
                     "--seconds", "2", "--trace", "1"],
                    root=root, require_tpu=False) == 0
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert out["correct"] and out["attempted"] > 0
    assert out["metrics"]["calls_in_window"]["value"] == out["attempted"]
    assert {"harvest_s", "reduce_s", "reduce_rounds"} <= set(out["metrics"])
    assert "ph_s" not in out["metrics"]
    assert list(out)[-1] == "checks"
    assert out["checks"]["bars_off"] == {"value": 0, "limit": 0}


def test_sound_runs_are_correct(root, device_path):
    out = _run(root, "tiny_o3.dummy")
    assert out["correct"], out
    assert set(out["metrics"]) == {"ph_s", "setup_s"}
    # every base sample of the configuration was called in the window
    assert set(out["notes"]) == {"call_s", "base0", "base1"}
    assert len(out["notes"]["call_s"]) == out["attempted"]


def test_host_fallback_is_refused(root):
    with pytest.raises(RuntimeError, match="Pallas"):
        _run(root, "tiny_o3.dummy")


def _answer_altered(monkeypatch):
    import repro.core.packed_reduce as packed_reduce

    original = packed_reduce.reduce_dimension_packed

    def altered(*args, **kwargs):
        res = original(*args, **kwargs)
        if res.pairs.size:
            res.pairs = res.pairs.copy()
            res.pairs[0, 1] += 1e-9
        elif res.essentials.size:
            res.essentials = res.essentials.copy()
            res.essentials[0] += 1e-9
        return res
    monkeypatch.setattr(packed_reduce, "reduce_dimension_packed", altered)


def _half_the_edges_left_out(monkeypatch):
    import repro.scale.tiles as tiles

    original = tiles.iter_tile_edges

    def half(*args, **kwargs):
        for iu, ju, lens in original(*args, **kwargs):
            yield iu[::2], ju[::2], lens[::2]
    monkeypatch.setattr(tiles, "iter_tile_edges", half)


@pytest.mark.parametrize("fault", [_answer_altered, _half_the_edges_left_out])
def test_closed_loop_faults_are_not_correct(root, device_path, monkeypatch,
                                            fault):
    fault(monkeypatch)
    out = _run(root, "tiny_o3.dummy")
    assert not out["correct"]
    assert out["checks"]["bars_off"]["value"] > 0


def test_float32_control_is_not_correct(root):
    cell = spec.load("tiny_o3.dummy", root)
    seed = 2**31 + 9
    out = run.run(cell, seed, 2.0, False, require_tpu=False,
                  driver=control.control_driver(cell, seed))
    assert not out["correct"]
    assert out["checks"]["bars_off"]["value"] > 0


NEW_DATASET = '''"""Points on a circle of radius 1 in R^2."""
import numpy as np


def points(config, rng, n):
    a = rng.uniform(0.0, 2.0 * np.pi, n)
    return np.stack([np.cos(a), np.sin(a)], axis=1)
'''

NEW_LOOP = '''"""One call in the window, however long it is."""
import time

from bench.loops.closed import ClosedLoop


class Once(ClosedLoop):
    def window(self, seconds):
        t0 = time.perf_counter()
        diagrams, stats = self.solve(self.pool[0])
        self.calls.append({"t0": t0, "t1": time.perf_counter(), "query": 0,
                           "d": 2, "diagrams": diagrams, "stats": stats})


def driver(cell, seed):
    return Once(cell, seed)
'''


def test_a_new_dataset_and_loop_are_one_file_each(root, device_path, capsys):
    """A cell whose configuration names a new dataset and whose mix names a
    new loop runs with nothing edited: each is one new file under bench/."""
    b = os.path.join(root, "bench")
    with open(os.path.join(b, "datasets", "circle.py"), "w") as f:
        f.write(NEW_DATASET)
    with open(os.path.join(b, "loops", "once.py"), "w") as f:
        f.write(NEW_LOOP)
    _write(os.path.join(b, "configs", "circle.json"),
           {"dataset": "circle", "n": 40, "base_seeds": [3]})
    _write(os.path.join(b, "traffic", "once.json"),
           {"loop": "once", "tau_max": 0.4, "maxdim": 1})
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        bench = json.load(f)
    bench["configs"].append({"name": "circle", "source": "test",
                             "file": "bench/configs/circle.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "circle.once", "config": "circle",
                               "traffic": "once", "chips": 1, "why": "test"})
    bench["end_to_end"][0]["workloads"].append("circle.once")
    _write(path, bench)
    assert run.main(["--workload", "circle.once", "--seed", "5",
                     "--seconds", "1"], root=root, require_tpu=False) == 0
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert out["correct"] and out["attempted"] == 1
    assert set(out["metrics"]) == {"ph_s", "setup_s"}
