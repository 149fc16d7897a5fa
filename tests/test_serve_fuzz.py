"""Fuzz + soak of randomized PH-serving request streams (ISSUE 9).

Properties, over randomized streams of cold / tau-growth / point-arrival /
repeat requests across tenants:

* **determinism** — the engine is a pure function of (seed, arrival
  order): same stream twice -> byte-identical responses, paths, and
  admission log;
* **exactness** — every admitted response equals a cold ``compute_ph`` at
  the granted tau, whatever path served it;
* **isolation** — no tenant's resident cache bytes ever exceed
  ``store_budget_bytes``, checked after every step;
* **accountability** — every rejection is reproducible offline from the
  logged admission account.

Runs under real hypothesis or the deterministic fallback shim in
``tests/_hypothesis_fallback.py`` (same API subset).
"""
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.homology import compute_ph
from repro.core.resume import canonical_diagram
from repro.serve.ph import PHRequest, PHServeEngine


def _gen_stream(rng, n_requests, n_datasets):
    """A randomized but replayable request stream (list of PHRequest)."""
    base = {k: rng.normal(size=(int(rng.integers(6, 16)), 3))
            for k in range(n_datasets)}
    latest = dict(base)
    taus = {k: 1.2 for k in range(n_datasets)}
    reqs = []
    for uid in range(n_requests):
        k = int(rng.integers(0, n_datasets))
        kind = int(rng.integers(0, 4))
        if kind == 0:        # cold / repeat at current state
            pts, tau = latest[k], taus[k]
        elif kind == 1:      # tau growth
            taus[k] = taus[k] + float(rng.uniform(0.1, 0.6))
            pts, tau = latest[k], taus[k]
        elif kind == 2:      # point arrival
            latest[k] = np.concatenate(
                [latest[k], rng.normal(size=(int(rng.integers(1, 4)), 3))],
                axis=0)
            pts, tau = latest[k], taus[k]
        else:                # reset to the base cloud (cache invalidation)
            latest[k] = base[k]
            taus[k] = 1.2
            pts, tau = latest[k], taus[k]
        reqs.append(PHRequest(uid=uid, points=pts, tau_max=tau,
                              dataset=f"ds{k}",
                              tenant=f"t{k % 2}"))
    return reqs


def _run_stream(reqs, **engine_kw):
    eng = PHServeEngine(engine="single", **engine_kw)
    tenant_ok = True
    for req in reqs:
        eng.submit(PHRequest(uid=req.uid, points=req.points,
                             tau_max=req.tau_max, dataset=req.dataset,
                             tenant=req.tenant, maxdim=req.maxdim))
        eng.step()
        budget = engine_kw.get("store_budget_bytes")
        if budget is not None:
            tenant_ok &= all(v <= budget
                             for v in eng.tenant_bytes().values())
    return eng, tenant_ok


def _response_signature(eng):
    sig = []
    for uid in sorted(eng.done):
        r = eng.done[uid]
        dg = tuple((d, r.diagrams[d].tobytes()) for d in sorted(r.diagrams)) \
            if r.diagrams is not None else None
        sig.append((uid, r.path, r.admitted, round(r.granted_tau, 12), dg))
    return sig


@settings(max_examples=3, deadline=None)
@given(st.integers(0, 10_000), st.integers(6, 10), st.integers(1, 3))
def test_stream_determinism(seed, n_requests, n_datasets):
    reqs = _gen_stream(np.random.default_rng(seed), n_requests, n_datasets)
    eng_a, _ = _run_stream(reqs)
    eng_b, _ = _run_stream(reqs)
    assert _response_signature(eng_a) == _response_signature(eng_b)
    assert [(d.uid, d.admitted, d.predicted_bytes, d.granted_tau)
            for d in eng_a.admission_log] == \
        [(d.uid, d.admitted, d.predicted_bytes, d.granted_tau)
         for d in eng_b.admission_log]


@settings(max_examples=3, deadline=None)
@given(st.integers(0, 10_000), st.integers(5, 9))
def test_every_path_matches_cold_compute(seed, n_requests):
    reqs = _gen_stream(np.random.default_rng(seed), n_requests, 2)
    eng, _ = _run_stream(reqs)
    assert sorted(eng.done) == list(range(n_requests))
    for req in reqs:
        r = eng.done[req.uid]
        assert r.admitted, r
        ref = compute_ph(points=req.points, tau_max=r.granted_tau,
                         maxdim=2, mode="implicit")
        for d in (0, 1, 2):
            assert np.array_equal(r.diagrams[d],
                                  canonical_diagram(ref.diagrams[d])), \
                (req.uid, r.path, d)


@settings(max_examples=3, deadline=None)
@given(st.integers(0, 10_000), st.sampled_from([20_000, 60_000, 200_000]))
def test_tenant_bytes_never_exceed_store_budget(seed, budget):
    reqs = _gen_stream(np.random.default_rng(seed), 8, 3)
    eng, tenant_ok = _run_stream(reqs, store_budget_bytes=budget)
    assert tenant_ok
    # final state also respects the budget, and the gauge agrees
    tb = eng.tenant_bytes()
    assert all(v <= budget for v in tb.values())
    assert eng.stats()["serve_ph_store_bytes"] == pytest.approx(
        sum(tb.values()))


@settings(max_examples=3, deadline=None)
@given(st.integers(0, 10_000), st.sampled_from([800, 3_000, 12_000]))
def test_rejections_reproducible_from_admission_log(seed, budget):
    rng = np.random.default_rng(seed)
    reqs = [PHRequest(uid=u, points=rng.normal(size=(int(rng.integers(4, 40)),
                                                     3)),
                      tau_max=2.0, dataset=f"d{u}")
            for u in range(6)]
    eng, _ = _run_stream(reqs, memory_budget_bytes=budget)
    assert len(eng.admission_log) == len(reqs)
    for req, dec in zip(reqs, eng.admission_log):
        replay = eng.admission_account(req.points, req.tau_max)
        assert replay.admitted == dec.admitted
        assert replay.granted_tau == dec.granted_tau
        assert replay.predicted_bytes == dec.predicted_bytes
        assert replay.reason == dec.reason
        if not dec.admitted:
            assert eng.done[req.uid].path == "rejected"
        else:
            assert eng.done[req.uid].diagrams is not None


def test_soak_long_mixed_stream():
    """One long deterministic stream: every request answered, metrics
    internally consistent, warm paths actually exercised."""
    rng = np.random.default_rng(1234)
    reqs = _gen_stream(rng, 30, 3)
    eng, tenant_ok = _run_stream(reqs, store_budget_bytes=300_000)
    assert tenant_ok
    assert sorted(eng.done) == list(range(30))
    s = eng.stats()
    assert s["serve_ph_n_requests"] == 30
    assert s["serve_ph_n_admitted"] + s.get("serve_ph_n_rejected", 0) == 30
    assert s["serve_ph_n_cache_hits"] + s["serve_ph_n_cache_misses"] \
        == s["serve_ph_n_admitted"]
    assert s["serve_ph_n_warm_tau"] > 0
    assert s["serve_ph_n_warm_points"] > 0
    assert s["serve_ph_latency_s_count"] == 30
    # spot-check exactness across the stream tail
    for req in reqs[-6:]:
        r = eng.done[req.uid]
        ref = compute_ph(points=req.points, tau_max=r.granted_tau,
                         maxdim=2, mode="implicit")
        for d in (0, 1, 2):
            assert np.array_equal(r.diagrams[d],
                                  canonical_diagram(ref.diagrams[d]))
