"""bench/trace.py on a trace recorded once on a TPU v5e: one
``compute_ph(backend="tiled", engine="packed")`` call on o3 at n = 8192,
tau 0.3, maxdim 1, with the program's spans bridged into the profiler."""
import os

import pytest

from bench import trace

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                    "o3_h1_call.xplane.pb")


@pytest.fixture(scope="module")
def tr():
    return trace.read(DATA)


def test_device_plane_and_window(tr):
    assert list(tr.devices) == ["/device:TPU:0"]
    # no bench/window span in this recording: the window is the device's
    # first op start to its last op end
    assert tr.window == (46338736.0, 335328506.0)
    assert 0.0 < tr.busy_s() < tr.window_s


def test_kernel_time_by_name(tr):
    dist = tr.kernel_s("pairwise_sq_dists")
    assert dist == pytest.approx(430.256e-6, rel=1e-9)   # ten 2048^2 tiles
    assert tr.kernel_s("gf2_") == pytest.approx(3.85e-6, rel=1e-6)
    assert tr.top_ops()[0][0] == "pairwise_sq_dists"
    assert len(tr.top_ops()) == 10


def test_idle_gaps_named_by_host_spans(tr):
    gaps = dict(tr.idle_gaps())
    assert set(gaps) <= {"harvest/tile", "harvest/merge", "ph/compute_ph",
                         trace.NO_SPAN}
    assert gaps["harvest/tile"] > 0.1
    assert sum(gaps.values()) == pytest.approx(tr.window_s - tr.busy_s(),
                                               rel=1e-9)


def test_interval_arithmetic():
    ops = [trace.Op("a", 0, 10), trace.Op("b", 5, 10), trace.Op("c", 30, 5)]
    busy = trace._union(ops, (2.0, 40.0))
    assert busy == [(2.0, 15.0), (30.0, 35.0)]
    assert trace._gaps(busy, (2.0, 40.0)) == [(15.0, 30.0), (35.0, 40.0)]
    spans = [(0.0, 100.0, "outer"), (0.0, 20.0, "inner"), (25.0, 32.0, "x")]
    assert trace._innermost(spans, [10.0, 22.0, 30.0, 50.0]) == \
        ["inner", "outer", "x", "outer"]
    assert trace._innermost(spans, [200.0]) == [trace.NO_SPAN]


def test_short_op_name():
    assert trace.short_op_name(
        "%pairwise_sq_dists.1 = f32[2048,2048]{1,0} custom-call(...)") == \
        "pairwise_sq_dists"
    assert trace.short_op_name("%copy-done = u32[32,128] copy-done(x)") == \
        "copy-done"
