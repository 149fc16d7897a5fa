"""repro.obs: span tracing, Chrome-trace export, metrics registry.

Covers span nesting and exception safety, disabled-mode cost, the
profiler bridge (every recorded span annotated exactly once), the leaf
spans and the device round-trip counter of the chip path, Chrome
trace_event schema with per-device lanes and >= 95% wall coverage on the
packed 4-shard path, stats-dict backward compatibility across engines x
modes x shard counts, and the span-derived simulated critical path.
"""
import collections
import contextlib
import json
import os
import time

import numpy as np
import pytest

from repro.core import compute_ph
from repro.obs.metrics import SCHEMA, MetricsRegistry, schema_markdown
from repro.obs.trace import (Span, Tracer, active_tracer, chrome_trace,
                             coverage, critical_path, span, stopwatch,
                             tracing)


def cloud(seed=3, n=24):
    rng = np.random.default_rng(seed)
    return rng.normal(size=(n, 3))


# ---------------------------------------------------------------------------
# span lifecycle
# ---------------------------------------------------------------------------

def test_spans_nest_and_record_attrs():
    tr = Tracer()
    with tracing(tr):
        with span("ph/compute_ph", engine="packed"):
            with span("harvest/tile", tile="0,1", lane=2) as sp:
                sp.set(n_edges=7)
    tr.assert_balanced()
    names = [s.name for s in tr.spans]
    assert names == ["harvest/tile", "ph/compute_ph"]  # inner closes first
    tile = tr.spans[0]
    assert tile.lane == 2
    assert tile.attrs == {"tile": "0,1", "n_edges": 7}
    assert tile.dur >= 0.0
    outer = tr.spans[1]
    assert outer.t0 <= tile.t0 and tile.t1 <= outer.t1


def test_span_closes_on_exception_path():
    tr = Tracer()
    with pytest.raises(RuntimeError):
        with tracing(tr):
            with span("ph/h1"):
                raise RuntimeError("boom")
    # the span still closed and recorded; nothing left open
    tr.assert_balanced()
    assert [s.name for s in tr.spans] == ["ph/h1"]
    assert active_tracer() is None          # tracing() restored the global


def test_open_spans_reported_while_inside():
    tr = Tracer()
    with tracing(tr):
        with span("ph/h1"):
            assert tr.open_spans() == ["ph/h1"]
            with pytest.raises(RuntimeError):
                tr.assert_balanced()
    tr.assert_balanced()


def test_stopwatch_times_even_when_disabled():
    assert active_tracer() is None
    with stopwatch("ph/filtration") as sw:
        time.sleep(0.002)
    assert sw.elapsed >= 0.002
    tr = Tracer()
    with tracing(tr):
        with stopwatch("ph/filtration") as sw:
            pass
    assert [s.name for s in tr.spans] == ["ph/filtration"]
    assert sw.elapsed >= 0.0


def test_disabled_mode_is_a_shared_noop():
    assert active_tracer() is None
    a = span("reduce/fused", step=0)
    b = span("harvest/tile", tile="0,0")
    assert a is b                           # singleton: no allocation
    with a as sp:
        sp.set(anything=1)                  # no-op, no state
    assert a.dur == 0.0


def test_disabled_mode_overhead_is_small():
    """100k disabled span entries must cost well under a second."""
    assert active_tracer() is None
    t0 = time.perf_counter()
    for _ in range(100_000):
        with span("reduce/fused", step=0):
            pass
    assert time.perf_counter() - t0 < 1.0


# ---------------------------------------------------------------------------
# the profiler bridge, leaf spans and the device round-trip counter
# ---------------------------------------------------------------------------

# host work and device round trips inside the reduction, harvest and
# pipeline phases; none carries ``step``
LEAF_SPANS = {
    "reduce/cobdy", "reduce/probe", "reduce/pack", "reduce/gens",
    "reduce/xor", "reduce/serial", "reduce/commit",
    "gf2/serial",
    "harvest/fetch", "harvest/refine", "harvest/build",
    "ph/adapter", "ph/h2_columns",
    "coo/symmetrize", "coo/filter", "coo/edges",
}


@pytest.fixture
def annotations(monkeypatch):
    """Names of the profiler annotations the bridge opens, in order."""
    names = []

    def annotate(name):
        names.append(name)
        return contextlib.nullcontext()

    monkeypatch.setattr("repro.obs.trace._jax_annotation", annotate)
    return names


@pytest.fixture
def device_path(monkeypatch):
    """compute_ph's chip path on the CPU: the Pallas tile harvest and the
    gf2 kernels, interpreted."""
    monkeypatch.setattr("repro.core.packed_reduce._resolve_use_kernels",
                        lambda use_kernels: True)
    monkeypatch.setattr("repro.scale.tiles._resolve_backend",
                        lambda backend: "pallas")


def device_call(tracer=None):
    return compute_ph(points=cloud(seed=13, n=16), maxdim=2,
                      backend="tiled", tile_m=8, tile_n=8, engine="packed",
                      batch_size=16, trace=tracer)


def coo_call(tracer=None):
    """The COO entry on the same cloud's distances, as a contact map would
    give them: every pair once, in both orders, beside one diagonal entry."""
    pts = cloud(seed=13, n=16)
    i, j = np.triu_indices(16, k=1)
    d = np.linalg.norm(pts[i] - pts[j], axis=1)
    rows = np.concatenate([i, j, [3]])
    cols = np.concatenate([j, i, [3]])
    vals = np.concatenate([d, d, [0.0]])
    return compute_ph(coo=(rows, cols, vals, 16), maxdim=2, engine="packed",
                      batch_size=16, trace=tracer)


def test_bridge_annotates_every_span_once(device_path, annotations):
    tr = Tracer(bridge=True)
    device_call(tr)
    coo_call(tr)
    tr.assert_balanced()
    recorded = collections.Counter(s.name for s in tr.spans)
    assert collections.Counter(annotations) == recorded
    # stopwatches, the packed engine's local timeline, and every leaf
    assert {"ph/filtration", "ph/h0", "ph/h1", "ph/h2"} <= set(recorded)
    assert {"reduce/fused", "reduce/slice", "reduce/sweep"} <= set(recorded)
    assert "coo/build" in recorded
    assert LEAF_SPANS <= set(recorded)


def test_no_annotation_without_bridge(device_path, annotations):
    tr = Tracer()
    device_call(tr)
    coo_call(tr)
    assert LEAF_SPANS <= {s.name for s in tr.spans}
    assert annotations == []


def test_forwarding_tracer_takes_bridge_from_target(annotations):
    outer = Tracer(bridge=True)
    local = Tracer(forward_to=outer)
    assert local.bridge
    assert not Tracer(forward_to=Tracer()).bridge
    with local.span("reduce/fused", step=1):
        pass
    with tracing(outer):
        with stopwatch("ph/h1"):
            pass
    # forwarding a closed span records it and opens no second annotation
    assert annotations == ["reduce/fused", "ph/h1"]
    assert [s.name for s in outer.spans] == ["reduce/fused", "ph/h1"]


def test_leaf_spans_carry_no_step_and_leave_sim_wall(device_path):
    tr = Tracer()
    res = device_call(tr)
    leaves = [s for s in tr.spans if s.name in LEAF_SPANS]
    assert leaves and all("step" not in s.attrs for s in leaves)
    for dim in ("h1", "h2"):
        (phase,) = [s for s in tr.spans if s.name == f"ph/{dim}"]
        inside = [s for s in tr.spans
                  if phase.t0 <= s.t0 and s.t1 <= phase.t1]
        timeline = [s for s in inside if s.name not in LEAF_SPANS]
        wall = res.stats[f"{dim}_sim_wall_s"]
        assert critical_path(inside)["sim_wall_s"] == wall, dim
        assert critical_path(timeline)["sim_wall_s"] == wall, dim


@pytest.mark.parametrize("kernels", [True, False])
def test_device_calls_count_kernel_round_trips(monkeypatch, kernels):
    from repro.kernels import gf2

    monkeypatch.setattr("repro.core.packed_reduce._resolve_use_kernels",
                        lambda use_kernels: kernels)
    calls = collections.Counter()
    for name in ("gf2_find_low", "gf2_parallel_xor", "gf2_serial_reduce"):
        def counted(*args, _fn=getattr(gf2, name), _name=name, **kwargs):
            calls[_name] += 1
            return _fn(*args, **kwargs)
        monkeypatch.setattr(gf2, name, counted)
    tr = Tracer()
    res = device_call(tr)
    n = res.stats["h1_n_device_calls"] + res.stats["h2_n_device_calls"]
    assert n == sum(calls.values())
    spans = collections.Counter(s.name for s in tr.spans
                                if s.name.startswith("gf2/"))
    # every gf2 call returns the lows of the rows it wrote: no find-low
    # round trip is left on the kernel path
    assert calls["gf2_find_low"] == 0 and spans["gf2/find_low"] == 0
    assert spans["gf2/serial"] == calls["gf2_serial_reduce"]
    assert sum(spans.values()) == n
    # the parallel phase adds in place on the host block on every path:
    # the serial pre-pass is the only gf2 round trip left
    assert calls["gf2_parallel_xor"] == 0 and spans["gf2/xor"] == 0
    assert n == calls["gf2_serial_reduce"]
    lows = res.stats["h1_n_kernel_lows"] + res.stats["h2_n_kernel_lows"]
    assert res.stats["h1_n_host_xor_rounds"] > 0
    assert res.stats["h2_n_host_xor_rounds"] > 0
    if kernels:
        assert calls["gf2_serial_reduce"] > 0
        assert lows > 0
    else:
        assert n == 0 and lows == 0


# ---------------------------------------------------------------------------
# tracing() knob resolution
# ---------------------------------------------------------------------------

def test_tracing_false_is_noop():
    with tracing(False):
        assert active_tracer() is None
        assert span("ph/h1").dur == 0.0


def test_tracing_env_path_exports(tmp_path, monkeypatch):
    out = tmp_path / "env_trace.json"
    monkeypatch.setenv("REPRO_TRACE", str(out))
    with tracing(None):
        with span("ph/compute_ph"):
            pass
    doc = json.loads(out.read_text())
    assert any(e.get("name") == "ph/compute_ph" for e in doc["traceEvents"])


def test_tracing_nested_none_keeps_outer_tracer():
    tr = Tracer()
    with tracing(tr):
        with tracing(None) as inner:
            assert inner is tr
            with span("ph/h0"):
                pass
    assert [s.name for s in tr.spans] == ["ph/h0"]


def test_tracing_rejects_garbage():
    with pytest.raises(TypeError):
        with tracing(123):
            pass


# ---------------------------------------------------------------------------
# Chrome trace schema
# ---------------------------------------------------------------------------

def _check_chrome_schema(doc):
    assert isinstance(doc["traceEvents"], list) and doc["traceEvents"]
    xs = [e for e in doc["traceEvents"] if e["ph"] == "X"]
    ms = [e for e in doc["traceEvents"] if e["ph"] == "M"]
    assert xs and ms
    for e in xs:
        assert set(e) >= {"name", "ph", "ts", "dur", "pid", "tid"}
        assert e["ts"] >= 0 and e["dur"] >= 0
        json.dumps(e["args"])               # attrs must be JSON-clean
    names = {e["args"]["name"] for e in ms if e["name"] == "thread_name"}
    return xs, names


def test_chrome_trace_synthetic_lanes():
    tr = Tracer()
    with tracing(tr):
        with span("reduce/slice", lane=0, step=0):
            pass
        with span("reduce/slice", lane=3, step=0):
            pass
        with span("ph/compute_ph"):
            pass
    xs, thread_names = _check_chrome_schema(tr.chrome_trace())
    assert {e["tid"] for e in xs} == {0, 1, 4}   # host + lanes 0 and 3
    assert "host" in thread_names and "device:3" in thread_names


def test_export_refuses_unbalanced(tmp_path):
    tr = Tracer()
    with tracing(tr):
        ctx = tr.span("ph/h1")
        ctx.__enter__()                     # deliberately leaked open
        with pytest.raises(RuntimeError):
            tr.export_chrome(str(tmp_path / "bad.json"))
        ctx.__exit__(None, None, None)


def test_compute_ph_trace_has_device_lanes_and_coverage(tmp_path):
    """Acceptance: packed 4-shard trace is Perfetto-loadable, >= 4 device
    lanes, spans covering >= 95% of the traced wall."""
    out = tmp_path / "packed4.json"
    res = compute_ph(points=cloud(), engine="packed", n_shards=4,
                     trace=str(out))
    doc = json.loads(out.read_text())
    xs, thread_names = _check_chrome_schema(doc)
    device_tids = {e["tid"] for e in xs if e["tid"] > 0}
    assert len(device_tids) >= 4
    assert {"device:0", "device:1", "device:2", "device:3"} <= thread_names
    # reconstruct coverage: union of spans / extent of the trace
    t0 = min(e["ts"] for e in xs)
    t1 = max(e["ts"] + e["dur"] for e in xs)
    ivs = sorted((e["ts"], e["ts"] + e["dur"]) for e in xs)
    covered, hi = 0.0, t0
    for a, b in ivs:
        a = max(a, hi)
        if b > a:
            covered += b - a
            hi = b
    assert covered / (t1 - t0) >= 0.95
    assert res.stats["h1_n_pairs"] >= 0        # result itself is intact


def test_coverage_helper_merges_overlaps():
    mk = lambda a, b: Span("x", None, a, b, {})
    assert coverage([mk(0, 1), mk(0.5, 2), mk(3, 4)]) == pytest.approx(0.75)
    assert coverage([]) == 0.0


# ---------------------------------------------------------------------------
# metrics registry
# ---------------------------------------------------------------------------

def test_registry_is_typed():
    reg = MetricsRegistry()
    reg.counter("n_reductions").inc(3)
    with pytest.raises(TypeError):
        reg.gauge("n_reductions")           # declared a counter
    with pytest.raises(KeyError):
        reg.counter("not_a_metric")
    reg.register("not_a_metric", "counter")
    reg.counter("not_a_metric").inc()
    assert reg.as_stats()["not_a_metric"] == 1.0


def test_registry_histogram_flattens():
    reg = MetricsRegistry()
    h = reg.histogram("superstep_conc_s")
    for v in (0.5, 1.5, 1.0):
        h.observe(v)
    s = reg.as_stats()
    assert s["superstep_conc_s_count"] == 3.0
    assert s["superstep_conc_s_sum"] == pytest.approx(3.0)
    assert s["superstep_conc_s_min"] == 0.5
    assert s["superstep_conc_s_max"] == 1.5


def test_registry_update_from_legacy_dict():
    reg = MetricsRegistry()
    reg.counter("cache_n_pack_hits").inc(2)
    reg.update_from({"cache_n_pack_hits": 3, "stored_bytes": 100,
                     "unknown_key": 1})
    s = reg.as_stats()
    assert s["cache_n_pack_hits"] == 5.0      # counters add
    assert s["stored_bytes"] == 100.0       # gauges set
    assert "unknown_key" not in s           # off-schema keys dropped


def test_schema_markdown_lists_every_metric():
    table = schema_markdown()
    for name in SCHEMA:
        assert f"`{name}`" in table


# ---------------------------------------------------------------------------
# stats backward compatibility across engines x modes x shards
# ---------------------------------------------------------------------------

LEGACY_KEYS = ("n", "n_e", "t_filtration", "t_h1",
               "h1_n_columns", "h1_n_reductions", "h1_n_pairs",
               "h1_stored_bytes", "h2_n_columns",
               "predicted_account_bytes", "budget_drift_ratio")


@pytest.mark.parametrize("engine", ["single", "batch", "packed"])
@pytest.mark.parametrize("mode", ["explicit", "implicit"])
def test_stats_schema_stable_across_engines(engine, mode):
    res = compute_ph(points=cloud(), engine=engine, mode=mode)
    for key in LEGACY_KEYS:
        assert key in res.stats, key
    # every emitted stat resolves to a schema entry (base name for
    # histogram expansions, h1_/h2_ prefixes stripped)
    for key in res.stats:
        base = key[3:] if key.startswith(("h1_", "h2_")) else key
        for suffix in ("_count", "_sum", "_min", "_max"):
            if base.endswith(suffix) and base[:-len(suffix)] in SCHEMA:
                base = base[:-len(suffix)]
                break
        assert base in SCHEMA, key


@pytest.mark.parametrize("n_shards", [1, 4])
def test_stats_schema_stable_across_shards(n_shards):
    res = compute_ph(points=cloud(), engine="packed", n_shards=n_shards)
    assert res.stats["h1_n_shards"] == n_shards
    for key in ("h1_sim_wall_s", "h1_n_device_calls", "h1_n_supersteps"):
        assert key in res.stats, key


def test_engines_agree_on_counted_work():
    """Migrating stats to the registry must not change their values:
    pair/column counts agree across engines on the same cloud."""
    pts = cloud(seed=11)
    per = {e: compute_ph(points=pts, engine=e).stats
           for e in ("single", "batch", "packed")}
    for key in ("h1_n_pairs", "h1_n_essential", "h2_n_pairs", "n", "n_e"):
        vals = {round(s[key], 6) for s in per.values()}
        assert len(vals) == 1, (key, per)


# ---------------------------------------------------------------------------
# simulated critical path (satellite b)
# ---------------------------------------------------------------------------

def test_critical_path_synthetic_dag():
    mk = lambda name, lane, dur, **at: Span(name, lane, 0.0, dur, at)
    spans = [
        mk("reduce/fused", None, 1.0, step=0, weights=(0.5, 0.5)),
        mk("reduce/slice", 0, 0.2, step=0),
        mk("reduce/slice", 1, 0.6, step=0),
        mk("reduce/tournament", None, 0.1, step=0),
        mk("reduce/sweep", 0, 0.3, step=0, deps=()),
        mk("reduce/sweep", 1, 0.4, step=0, deps=(0,)),
        mk("reduce/encode", 0, 0.2, step=0),
        mk("reduce/encode", 1, 0.5, step=0),
        mk("reduce/exchange", None, 0.3, step=0),
        mk("ph/compute_ph", None, 99.0),          # ignored: not reduce/*
    ]
    cp = critical_path(spans)
    assert cp["sim_conc_s"] == pytest.approx(1.1)    # max(.5+.2, .5+.6)
    assert cp["sim_sweep_s"] == pytest.approx(0.7)   # 0.3 then dependent 0.4
    assert cp["sim_sync_s"] == pytest.approx(0.9)    # .1 + max(enc) + .3
    assert cp["sim_wall_s"] == pytest.approx(2.7)


def test_sim_wall_matches_bookkeeping_on_4dev_path():
    """The span-derived critical path of the 4-virtual-device path is
    positive and no longer than the superstep timeline it is derived from:
    the summed ``reduce/*`` spans with a ``step`` that the caller's tracer
    received for that dimension."""
    tr = Tracer()
    res = compute_ph(points=cloud(seed=5, n=32), engine="packed",
                     n_shards=4, trace=tr)
    for dim in ("h1", "h2"):
        (phase,) = [s for s in tr.spans if s.name == f"ph/{dim}"]
        timeline = sum(s.dur for s in tr.spans
                       if s.name.startswith("reduce/") and "step" in s.attrs
                       and phase.t0 <= s.t0 and s.t1 <= phase.t1)
        wall = res.stats[f"{dim}_sim_wall_s"]
        assert 0.0 < wall <= timeline, dim


# ---------------------------------------------------------------------------
# memory observability
# ---------------------------------------------------------------------------

def test_memory_gauges_on_tiled_backend():
    from repro.scale import account_bytes
    pts = cloud(seed=7, n=64)
    res = compute_ph(points=pts, backend="tiled", tile_m=16, tile_n=16)
    s = res.stats
    n, n_e = int(s["n"]), int(s["n_e"])
    assert s["predicted_account_bytes"] == account_bytes(n, n_e)
    assert account_bytes(n, n_e) == (3 * n + 12 * n_e) * 4
    assert s["observed_peak_harvest_bytes"] > 0
    assert s["observed_peak_reduce_bytes"] > 0
    assert s["budget_drift_ratio"] > 0
