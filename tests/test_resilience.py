"""repro.resilience: deterministic fault injection + recovery (ISSUE 10).

The contract under test, per fault class:

* **exactness under failure** — a distributed reduction that loses a
  shard, drops/corrupts exchange payloads, or limps behind a straggler
  produces diagrams *bit-identical* to the fault-free run (and to the
  single engine);
* **determinism of the adversary** — a :class:`FaultPlan` replays an
  identical failure history from its seed, so every red run is
  reproducible;
* **checkpoint integrity** — a bit-flipped, truncated, or
  version-skewed :class:`ReductionCheckpoint` is *detected*
  (:class:`CheckpointCorruption`), never silently restored;
* **graceful degradation** — the serve engine answers overload and
  repeated cold failure with explicit ``degraded`` responses, never an
  exception and never silently wrong diagrams.

Runs under real hypothesis or the deterministic fallback shim in
``tests/_hypothesis_fallback.py``.
"""
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.homology import compute_ph
from repro.core.pivot_cache import (decode_commit_delta, encode_commit_delta,
                                    verify_commit_delta)
from repro.core.resume import CHECKPOINT_VERSION, cold_reduce
from repro.core.filtration import build_filtration
from repro.resilience.faults import (CheckpointCorruption, FaultInjector,
                                     FaultPlan, FaultSpec, TransientFault,
                                     WireCorruption, backoff_delays,
                                     corrupt_payload, flip_bit, inject,
                                     retry_with_backoff)
from repro.resilience.supervisor import ShardSupervisor, speculative_reassign
from repro.serve.ph import PHRequest, PHServeEngine


def _cloud(n=48, seed=7):
    rng = np.random.default_rng(seed)
    return rng.normal(size=(n, 3))


def _diagrams(points, plan=None, **kw):
    kw.setdefault("tau_max", 1.2)
    kw.setdefault("maxdim", 2)
    with inject(plan):
        return compute_ph(points, **kw)


def _assert_same(res_a, res_b):
    assert set(res_a.diagrams) == set(res_b.diagrams)
    for d in res_a.diagrams:
        np.testing.assert_array_equal(res_a.diagrams[d], res_b.diagrams[d])


DIST = dict(engine="packed", n_shards=4, batch_size=16, exchange_every=1)

FAULT_CASES = [
    ("kill_start", FaultSpec("reduce.superstep", "kill_shard", at=2, shard=1,
                             params=(("when", "start"),))),
    ("kill_mid", FaultSpec("reduce.superstep", "kill_shard", at=2, shard=2,
                           params=(("when", "mid"),))),
    ("slow_shard", FaultSpec("reduce.superstep", "slow_shard", at=1, shard=3,
                             times=2, params=(("lag", 2.0),
                                              ("duration", 2)))),
    ("drop", FaultSpec("exchange.wire", "drop", at=1, shard=0, times=2)),
    ("corrupt", FaultSpec("exchange.wire", "corrupt", at=1, shard=1,
                          params=(("bit", 37),))),
    ("delay", FaultSpec("exchange.wire", "delay", at=1, shard=2,
                        params=(("delay_s", 1e-3),))),
]


# ---------------------------------------------------------------------------
# fault sweep: exactness under every fault class
# ---------------------------------------------------------------------------

class TestFaultSweepExactness:
    @pytest.fixture(scope="class")
    def clean(self):
        pts = _cloud()
        return {
            "pts": pts,
            "single": _diagrams(pts, engine="single"),
            "dist": _diagrams(pts, **DIST),
        }

    def test_fault_free_distributed_matches_single(self, clean):
        _assert_same(clean["dist"], clean["single"])

    @pytest.mark.parametrize("name,spec",
                             FAULT_CASES, ids=[n for n, _ in FAULT_CASES])
    def test_faulted_run_is_bit_identical(self, clean, name, spec):
        plan = FaultPlan.of(spec, seed=11)
        with inject(plan) as inj:
            faulted = compute_ph(clean["pts"], tau_max=1.2, maxdim=2, **DIST)
            assert inj.fired, f"{name} never fired - dead test"
        _assert_same(faulted, clean["dist"])
        _assert_same(faulted, clean["single"])

    @pytest.mark.parametrize("n_shards", [2, 4])
    def test_combined_plan_across_shard_counts(self, clean, n_shards):
        plan = FaultPlan.of(
            FaultSpec("reduce.superstep", "kill_shard", at=2, shard=1,
                      params=(("when", "start"),)),
            FaultSpec("exchange.wire", "drop", at=2, shard=0),
            FaultSpec("exchange.wire", "corrupt", at=3, shard=0,
                      params=(("bit", 5),)),
            seed=3)
        kw = dict(DIST, n_shards=n_shards)
        with inject(plan) as inj:
            faulted = compute_ph(clean["pts"], tau_max=1.2, maxdim=2, **kw)
            assert inj.fired
        _assert_same(faulted, clean["single"])

    def test_recovery_counters_surface_in_stats(self, clean):
        plan = FaultPlan.of(FAULT_CASES[0][1], seed=0)
        with inject(plan):
            res = compute_ph(clean["pts"], tau_max=1.2, maxdim=2, **DIST)
        # per-dim reduction stats are prefixed h{d}_; the kill at superstep 2
        # lands in whichever dimension is reducing then — require it counted
        deaths = sum(v for k, v in res.stats.items()
                     if k.endswith("resilience_n_shard_deaths"))
        redeals = sum(v for k, v in res.stats.items()
                      if k.endswith("resilience_n_redeals"))
        assert deaths == 1 and redeals >= 1

    def test_all_shards_dead_raises(self, clean):
        specs = [FaultSpec("reduce.superstep", "kill_shard", at=1, shard=s,
                           params=(("when", "start"),)) for s in range(4)]
        with inject(FaultPlan.of(*specs)):
            with pytest.raises(RuntimeError, match="every reduction shard"):
                compute_ph(clean["pts"], tau_max=1.2, maxdim=2, **DIST)


# ---------------------------------------------------------------------------
# FaultPlan determinism (hypothesis fuzz)
# ---------------------------------------------------------------------------

@settings(max_examples=25, deadline=None)
@given(seed=st.integers(min_value=0, max_value=2**31 - 1),
       n_faults=st.integers(min_value=1, max_value=8))
def test_random_plan_is_pure_function_of_seed(seed, n_faults):
    a = FaultPlan.random(seed, n_faults=n_faults)
    b = FaultPlan.random(seed, n_faults=n_faults)
    assert a == b and hash(a) == hash(b)
    assert len(a.specs) == n_faults
    for spec in a.specs:
        FaultSpec(site=spec.site, kind=spec.kind, at=spec.at,
                  shard=spec.shard, times=spec.times, params=spec.params)


@settings(max_examples=15, deadline=None)
@given(seed=st.integers(min_value=0, max_value=2**31 - 1))
def test_injector_replays_identical_history(seed):
    plan = FaultPlan.random(seed, n_faults=6)
    rng = np.random.default_rng(seed ^ 0xA5)
    sites = [(s, int(rng.integers(0, 9)), int(rng.integers(0, 4)))
             for s in np.array(
                 [sp.site for sp in plan.specs])[
                     rng.integers(0, len(plan.specs), size=40)]]
    logs = []
    for _ in range(2):
        inj = FaultInjector(plan)
        for site, idx, shard in sites:
            inj.fire(site, index=idx, shard=shard)
        logs.append(inj.fired)
    assert logs[0] == logs[1]


class TestFaultPlanDeterminism:
    def test_spec_validation_fails_loudly(self):
        with pytest.raises(ValueError, match="unknown injection site"):
            FaultSpec("no.such.site", "drop")
        with pytest.raises(ValueError, match="not legal"):
            FaultSpec("exchange.wire", "kill_shard")
        with pytest.raises(ValueError, match="times"):
            FaultSpec("exchange.wire", "drop", times=0)

    def test_backoff_schedule_is_deterministic_and_monotone_in_base(self):
        a = backoff_delays(6, base_s=1e-3, seed=9)
        b = backoff_delays(6, base_s=1e-3, seed=9)
        np.testing.assert_array_equal(a, b)
        assert (a > 0).all()
        # exponential envelope: delay[a] within [base*2^a, base*2^a*(1+j)]
        env = 1e-3 * 2.0 ** np.arange(6)
        assert (a >= env).all() and (a <= env * 1.5 + 1e-12).all()

    def test_retry_with_backoff_budget(self):
        calls = []

        def flaky(a):
            calls.append(a)
            if a < 2:
                raise TransientFault("again")
            return "ok"

        assert retry_with_backoff(flaky, attempts=3, sleep=None) == "ok"
        assert calls == [0, 1, 2]
        with pytest.raises(TransientFault):
            retry_with_backoff(lambda a: (_ for _ in ()).throw(
                TransientFault("always")), attempts=2, sleep=None)


# ---------------------------------------------------------------------------
# shard supervision
# ---------------------------------------------------------------------------

def test_straggler_policy():
    """ShardSupervisor detects the lagging shard on a deterministic clock
    and speculative_reassign duplicates its work onto the least-loaded
    survivor (the policy the packed reduction driver uses)."""
    sup = ShardSupervisor(n_shards=4, timeout=100.0, factor=3.0)
    now = 10.0
    plan = sup.observe(now, beats={h: now - (2.0 if h == 2 else 0.1)
                                   for h in range(4)})
    assert plan.dead == []
    assert plan.stragglers == [2]
    assert plan.active == [0, 1, 3]          # sidelined, not dead
    assignment = {h: [i for i in range(16) if i % 4 == h] for h in range(4)}
    backups = speculative_reassign(assignment, plan.stragglers)
    assert 2 in backups
    assert set(assignment[backups[2]]) >= {2, 6, 10, 14}
    # the sideline expires: shard 2 beats on time next superstep
    later = now + sup.sideline + 1.0
    plan2 = sup.observe(later, beats={h: later for h in range(4)})
    assert plan2.active == [0, 1, 2, 3]


def test_shard_supervisor_death_is_permanent():
    sup = ShardSupervisor(n_shards=4, timeout=1.5)
    # shard 3 stops beating at t=1; dead once lag > timeout
    for t in (1.0, 2.0, 3.0):
        plan = sup.observe(t, beats={h: t for h in range(4) if h != 3})
    assert 3 not in sup.live
    assert plan.active == [0, 1, 2]
    # it never comes back, even if a stale beat arrives
    plan = sup.observe(4.0, beats={h: 4.0 for h in range(4)})
    assert sup.live == [0, 1, 2] and plan.dead == []


# ---------------------------------------------------------------------------
# wire integrity
# ---------------------------------------------------------------------------

class TestWireIntegrity:
    def _payload(self):
        records = [
            {"low": 5, "col_id": 9, "mode": "explicit",
             "column": np.array([1, 5, 8], dtype=np.int64), "gens": None},
            {"low": 12, "col_id": 3, "mode": "implicit", "column": None,
             "gens": np.array([3, 7], dtype=np.int64)},
        ]
        return encode_commit_delta(records), records

    def test_checksum_round_trip(self):
        payload, records = self._payload()
        assert verify_commit_delta(payload)
        out = decode_commit_delta(payload)
        assert len(out) == len(records)
        for got, want in zip(out, records):
            assert (got["low"], got["col_id"], got["mode"]) == \
                (want["low"], want["col_id"], want["mode"])
            if want["column"] is not None:
                np.testing.assert_array_equal(got["column"], want["column"])

    def test_single_bit_flip_detected(self):
        payload, _ = self._payload()
        rng = np.random.default_rng(0)
        for bit in rng.integers(0, payload.nbytes * 8, size=16):
            bad = corrupt_payload(payload, int(bit))
            if np.array_equal(bad, payload):    # flipped a don't-care? never
                continue
            assert not verify_commit_delta(bad)
            with pytest.raises(WireCorruption):
                decode_commit_delta(bad)

    def test_flip_bit_is_involution(self):
        buf = b"resilience"
        assert flip_bit(flip_bit(buf, 13), 13) == buf
        assert flip_bit(b"", 3) == b""


# ---------------------------------------------------------------------------
# checkpoint integrity
# ---------------------------------------------------------------------------

class TestCheckpointIntegrity:
    @pytest.fixture()
    def ckpt(self):
        filt = build_filtration(points=_cloud(32, seed=5), tau_max=1.1)
        diags, ck = cold_reduce(filt, maxdim=2)
        return diags, ck

    def test_round_trip_preserves_hash_and_state(self, ckpt, tmp_path):
        _, ck = ckpt
        path = str(tmp_path / "ck.npz")
        digest = ck.save(path)
        loaded = type(ck).load(path)
        assert loaded.content_hash() == digest == ck.content_hash()

    def test_bitflip_detected(self, ckpt, tmp_path):
        _, ck = ckpt
        path = str(tmp_path / "ck.npz")
        ck.save(path)
        plan = FaultPlan.of(FaultSpec("resume.load", "bitflip",
                                      params=(("bit", 31337),)))
        with inject(plan) as inj:
            with pytest.raises(CheckpointCorruption):
                type(ck).load(path)
            assert inj.n_fired("resume.load", "bitflip") == 1

    def test_truncation_detected(self, ckpt, tmp_path):
        _, ck = ckpt
        path = str(tmp_path / "ck.npz")
        ck.save(path)
        plan = FaultPlan.of(FaultSpec("resume.load", "truncate"))
        with inject(plan):
            with pytest.raises(CheckpointCorruption, match="unreadable"):
                type(ck).load(path)

    def test_wrong_version_detected(self, ckpt, tmp_path):
        _, ck = ckpt
        path = str(tmp_path / "ck.npz")
        ck.save(path)
        with np.load(path, allow_pickle=False) as z:
            arrays = {k: z[k] for k in z.files}
        meta = arrays["__meta__"].copy()
        meta[0] = CHECKPOINT_VERSION + 1
        arrays["__meta__"] = meta
        np.savez_compressed(path, **arrays)
        with pytest.raises(CheckpointCorruption, match="version"):
            type(ck).load(path)

    def test_corruption_falls_back_to_cold(self, ckpt, tmp_path):
        diags, ck = ckpt
        path = str(tmp_path / "ck.npz")
        ck.save(path)
        plan = FaultPlan.of(FaultSpec("resume.load", "bitflip"))
        filt = build_filtration(points=_cloud(32, seed=5), tau_max=1.1)
        with inject(plan):
            try:
                type(ck).load(path)
                raise AssertionError("corruption must be detected")
            except CheckpointCorruption:
                cold_diags, _ = cold_reduce(filt, maxdim=2)
        for d in diags:
            np.testing.assert_array_equal(diags[d], cold_diags[d])


# ---------------------------------------------------------------------------
# serve degradation (graceful, explicit, never silent)
# ---------------------------------------------------------------------------

class TestServeDegradation:
    def _pts(self, seed=0, n=24):
        return np.random.default_rng(seed).normal(size=(n, 3))

    def test_cold_failure_degrades_then_breaker_opens(self):
        eng = PHServeEngine(max_cold_retries=1, breaker_threshold=1,
                            breaker_cooldown_steps=2)
        plan = FaultPlan.of(
            FaultSpec("serve.step", "fail_reduce", at=1, times=2))
        with inject(plan):
            eng.submit(PHRequest(uid=0, points=self._pts(), tau_max=1.4))
            eng.step()
            r0 = eng.done[0]
            assert r0.degraded and r0.degraded_reason == "cold_failed"
            assert r0.diagrams is None and r0.path == "degraded"
            eng.submit(PHRequest(uid=1, points=self._pts(), tau_max=1.4))
            eng.step()
            assert eng.done[1].degraded_reason == "circuit_open"
            for _ in range(2):          # cooldown passes
                eng.step()
            eng.submit(PHRequest(uid=2, points=self._pts(), tau_max=1.4))
            eng.step()
        r2 = eng.done[2]
        assert not r2.degraded and r2.diagrams is not None
        s = eng.stats()
        assert s["serve_ph_n_degraded"] == 2
        assert s["serve_ph_n_cold_retries"] == 1
        assert s["serve_ph_n_circuit_open"] == 1

    def test_overload_sheds_with_clamped_contract(self):
        eng = PHServeEngine(degrade_tau_factor=0.5, degrade_maxdim=1)
        with inject(FaultPlan.of(FaultSpec("serve.step", "overload", at=1))):
            eng.submit(PHRequest(uid=0, points=self._pts(1), tau_max=2.0,
                                 maxdim=2))
            eng.step()
        r = eng.done[0]
        assert r.degraded and r.degraded_reason == "overload"
        assert r.granted_tau == pytest.approx(1.0)
        assert set(r.diagrams) == {0, 1}     # maxdim clamped to 1
        assert not r.cached                  # brown-outs never cached
        assert eng.stats()["serve_ph_n_shed"] == 1

    def test_queue_depth_shedding_is_positional_and_explicit(self):
        eng = PHServeEngine(shed_queue_depth=1)
        eng.submit(PHRequest(uid=0, points=self._pts(2), tau_max=1.2))
        eng.submit(PHRequest(uid=1, points=self._pts(3), tau_max=1.2))
        eng.step()
        assert not eng.done[0].degraded
        assert eng.done[1].degraded
        assert eng.done[1].degraded_reason == "queue_depth"
        assert eng.done[1].diagrams is not None   # degraded, not refused

    def test_deadline_degrade_uses_observed_cold_latency(self):
        eng = PHServeEngine(default_deadline_s=1e-12, degrade_maxdim=1)
        eng.submit(PHRequest(uid=0, points=self._pts(4), tau_max=1.2,
                             maxdim=2))
        eng.step()                  # establishes the cold-latency EWMA
        assert not eng.done[0].degraded
        eng.submit(PHRequest(uid=1, points=self._pts(5), tau_max=1.2,
                             maxdim=2))
        eng.step()
        r = eng.done[1]
        assert r.degraded and r.degraded_reason == "deadline"
        assert set(r.diagrams) == {0, 1}
        assert eng.stats()["serve_ph_n_deadline_degraded"] == 1
        # a per-request deadline overrides the engine default
        eng2 = PHServeEngine(default_deadline_s=None, degrade_maxdim=1)
        eng2.submit(PHRequest(uid=0, points=self._pts(4), tau_max=1.2))
        eng2.step()
        eng2.submit(PHRequest(uid=1, points=self._pts(5), tau_max=1.2,
                              maxdim=2, deadline_s=1e-12))
        eng2.step()
        assert eng2.done[1].degraded_reason == "deadline"

    def test_degraded_diagrams_match_direct_clamped_request(self):
        pts = self._pts(6)
        eng = PHServeEngine(degrade_tau_factor=0.5, degrade_maxdim=1)
        with inject(FaultPlan.of(FaultSpec("serve.step", "overload", at=1))):
            eng.submit(PHRequest(uid=0, points=pts, tau_max=2.0, maxdim=2))
            eng.step()
        ref = PHServeEngine()
        ref.submit(PHRequest(uid=0, points=pts, tau_max=1.0, maxdim=1))
        ref.step()
        for d in ref.done[0].diagrams:
            np.testing.assert_array_equal(eng.done[0].diagrams[d],
                                          ref.done[0].diagrams[d])
