"""repro.analyze: lint rules, collective-schedule checks, GF(2) sanitizer.

Every checker must catch its negative fixture — a checker that cannot
fail its target bug class is decoration, not analysis.  Fixtures under
``tests/fixtures/analyze/`` are linted as text and never imported.
"""
import os
import subprocess
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.analyze import SanitizeViolation, Sanitizer, sanitizing
from repro.analyze.collectives import (
    CollectiveOp, check_exchange_consistency, check_repo, collective_schedule,
    collective_schedule_from_hlo, repo_programs, schedule_signature,
    verify_axes)
from repro.analyze.lint import (
    BareExceptRule, DtypeBoundaryRule, HostSyncRule, RawFiltrationSortRule,
    RawTimingRule, RetryWithoutBackoffRule, SpanLeakRule, UnseededRngRule,
    default_rules, lint_file, lint_source)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FIXTURES = os.path.join(REPO, "tests", "fixtures", "analyze")


def lint_fixture(name, rule):
    path = os.path.join(FIXTURES, name)
    return lint_file(path, root=REPO, rules=[rule], force=True)


# ---------------------------------------------------------------------------
# Lint rules vs their negative fixtures
# ---------------------------------------------------------------------------

def test_host_sync_fixture_caught():
    found = lint_fixture("bad_host_sync.py", HostSyncRule())
    msgs = " ".join(f.message for f in found)
    assert len(found) == 4
    assert ".item()" in msgs and "block_until_ready" in msgs
    assert "device_get" in msgs and "host gather" in msgs


def test_host_sync_applies_via_marker_not_force():
    # the "# analyze: hot" marker alone must opt the file in
    path = os.path.join(FIXTURES, "bad_host_sync.py")
    found = lint_file(path, root=REPO, rules=[HostSyncRule()], force=False)
    assert len(found) == 4


def test_sort_fixture_caught():
    found = lint_fixture("bad_sort.py", RawFiltrationSortRule())
    assert len(found) == 3          # argsort, sorted, 2-key lexsort
    lines = sorted(f.line for f in found)
    src = open(os.path.join(FIXTURES, "bad_sort.py")).read().splitlines()
    assert "good" not in src[lines[-1] - 1]   # 3-key lexsort stays clean


def test_dtype_fixture_caught():
    found = lint_fixture("bad_dtype.py", DtypeBoundaryRule())
    assert len(found) == 1
    assert found[0].rule == "f32-exact-compare"


def test_rng_fixture_caught():
    found = lint_fixture("bad_rng.py", UnseededRngRule())
    assert len(found) == 3          # np.random.rand, default_rng(), random.random
    # the seeded rng.normal(...) must not be flagged
    assert all("normal" not in f.message for f in found)


def test_raw_timing_fixture_caught():
    found = lint_fixture("bad_raw_timing.py", RawTimingRule())
    assert len(found) == 4          # time, perf_counter x2, process_time
    assert all("stopwatch" in f.message for f in found)
    # monotonic (deadlines) and sleep stay legal
    assert all("monotonic" not in f.message and "sleep" not in f.message
               for f in found)


def test_raw_timing_exempts_obs_and_benchmarks():
    src = "import time\nt0 = time.perf_counter()\n"
    rule = RawTimingRule()
    assert not lint_source(src, "src/repro/obs/trace.py", rules=[rule])
    assert not lint_source(src, "benchmarks/reduce_bench.py", rules=[rule])
    assert len(lint_source(src, "src/repro/core/homology.py",
                           rules=[rule])) == 1


def test_span_leak_fixture_caught():
    found = lint_fixture("bad_span_leak.py", SpanLeakRule())
    assert len(found) == 3          # span, stopwatch, tl.span — bare calls
    # the `with span(...)` / `with stopwatch(...)` uses must not be flagged
    assert all(f.line < 18 for f in found)


def test_bare_except_fixture_caught():
    found = lint_fixture("bad_bare_except.py", BareExceptRule())
    assert len(found) == 2          # the two bare handlers, not the typed ones
    assert all(f.rule == "bare-except" for f in found)
    assert all("typed fault" in f.message for f in found)


def test_retry_without_backoff_fixture_caught():
    found = lint_fixture("bad_retry_no_backoff.py",
                         RetryWithoutBackoffRule())
    assert len(found) == 2          # time.sleep(0.1) and bare sleep(1)
    assert all("retry_with_backoff" in f.message for f in found)
    # computed-duration sleeps and sleeps outside try/except stay legal
    lines = sorted(f.line for f in found)
    src = open(os.path.join(FIXTURES, "bad_retry_no_backoff.py")
               ).read().splitlines()
    assert all("BAD" in src[ln - 1] for ln in lines)


def test_retry_with_backoff_itself_lints_clean():
    # the blessed helper's own retry loop (variable delay via its `sleep`
    # parameter) must not trip the rule that points offenders at it
    path = os.path.join(REPO, "src", "repro", "resilience", "faults.py")
    assert not [f for f in lint_file(path, root=REPO,
                                     rules=[RetryWithoutBackoffRule(),
                                            BareExceptRule()], force=True)
                if not f.allowed]


def test_new_rules_registered_in_defaults():
    names = {r.name for r in default_rules()}
    assert {"raw-timing", "span-leak",
            "bare-except", "retry-without-backoff"} <= names


def test_allow_pragma_suppresses_with_justification():
    src = (
        "import numpy as np\n"
        "def f(edge_lens):\n"
        "    # analyze: allow[raw-filtration-sort] presorted upstream\n"
        "    return np.argsort(edge_lens)\n")
    found = lint_source(src, "x.py", rules=[RawFiltrationSortRule()],
                        force=True)
    assert len(found) == 1 and found[0].allowed
    assert found[0].justification == "presorted upstream"


def test_bare_allow_pragma_is_itself_a_finding():
    src = (
        "import numpy as np\n"
        "def f(edge_lens):\n"
        "    return np.argsort(edge_lens)  # analyze: allow\n")
    found = lint_source(src, "x.py", rules=[RawFiltrationSortRule()],
                        force=True)
    rules = {f.rule for f in found}
    assert "bare-allow" in rules
    # and the unjustified pragma does NOT suppress the real finding
    assert any(f.rule == "raw-filtration-sort" and not f.allowed
               for f in found)


def test_repo_tree_lints_clean():
    """Satellite contract: zero unexplained findings at merge."""
    from repro.analyze.lint import lint_paths
    bad = [f for f in lint_paths(REPO) if not f.allowed]
    assert not bad, "\n".join(f.format() for f in bad)


# ---------------------------------------------------------------------------
# Collective schedules: jaxpr walker
# ---------------------------------------------------------------------------

def test_divergent_cond_detected():
    def fn(x):
        return jax.lax.cond(x.sum() > 0,
                            lambda v: jax.lax.psum(v, "data"),
                            lambda v: v,
                            x)

    sched = collective_schedule(fn, (jnp.zeros(4, jnp.float32),),
                                axis_env=(("data", 4),))
    assert any(v.kind == "divergent-cond" for v in sched.violations)
    # the longest branch still contributes to the schedule
    assert schedule_signature(sched.ops) == (("psum", ("data",)),)


def test_uniform_cond_is_clean():
    def fn(x):
        return jax.lax.cond(x.sum() > 0,
                            lambda v: jax.lax.psum(v, "data") + 1.0,
                            lambda v: jax.lax.psum(v, "data") - 1.0,
                            x)

    sched = collective_schedule(fn, (jnp.zeros(4, jnp.float32),),
                                axis_env=(("data", 4),))
    assert not sched.violations
    assert schedule_signature(sched.ops) == (("psum", ("data",)),)


def test_while_collective_detected():
    def fn(x):
        return jax.lax.while_loop(lambda v: v.sum() < 10.0,
                                  lambda v: jax.lax.psum(v, "data") + 1.0,
                                  x)

    sched = collective_schedule(fn, (jnp.zeros(4, jnp.float32),),
                                axis_env=(("data", 4),))
    assert any(v.kind == "while-collective" for v in sched.violations)


def test_unknown_axis_detected():
    def fn(x):
        return jax.lax.psum(x, "data")

    sched = collective_schedule(fn, (jnp.zeros(4, jnp.float32),),
                                axis_env=(("data", 4),))
    assert not verify_axes(sched, mesh_axes=("data",))
    bad = verify_axes(sched, mesh_axes=("batch",))
    assert bad and bad[0].kind == "unknown-axis"


def test_schedule_recurses_through_scan():
    def fn(x):
        def body(carry, _):
            return jax.lax.psum(carry, "data"), None
        out, _ = jax.lax.scan(body, x, None, length=3)
        return out

    sched = collective_schedule(fn, (jnp.zeros(4, jnp.float32),),
                                axis_env=(("data", 4),))
    assert ("psum", ("data",)) in schedule_signature(sched.ops)


# ---------------------------------------------------------------------------
# Collective schedules: HLO cross-check
# ---------------------------------------------------------------------------

_HLO_CLEAN = """\
HloModule clean

ENTRY %main (p0: f32[8]) -> f32[32] {
  %p0 = f32[8] parameter(0)
  ROOT %ag = f32[32] all-gather(%p0), replica_groups={{0,1,2,3}}, dimensions={0}
}
"""

_HLO_WHILE = """\
HloModule loopy

%body (x: f32[8]) -> f32[8] {
  %x = f32[8] parameter(0)
  ROOT %ar = f32[8] all-reduce(%x), replica_groups={{0,1,2,3}}
}

%cond (x.1: f32[8]) -> pred[] {
  %x.1 = f32[8] parameter(0)
  ROOT %lt = pred[] constant(1)
}

ENTRY %main (p: f32[8]) -> f32[8] {
  %p = f32[8] parameter(0)
  ROOT %w = f32[8] while(%p), condition=%cond, body=%body
}
"""


def test_hlo_schedule_extraction():
    sched = collective_schedule_from_hlo(_HLO_CLEAN)
    assert [op.name for op in sched.ops] == ["all-gather"]
    assert sched.ops[0].group_size == 4
    assert not sched.violations


def test_hlo_while_collective_flagged():
    sched = collective_schedule_from_hlo(_HLO_WHILE)
    assert [op.name for op in sched.ops] == ["all-reduce"]
    assert any(v.kind == "while-collective" for v in sched.violations)


def test_hlo_cross_check_on_real_lowering():
    """The HLO walker agrees with a real XLA lowering (no collectives)."""
    def f(a):
        return jnp.tanh(a) @ a

    x = jax.ShapeDtypeStruct((16, 16), jnp.float32)
    hlo = jax.jit(f).lower(x).compile().as_text()
    sched = collective_schedule_from_hlo(hlo)
    assert sched.ops == [] and not sched.violations


# ---------------------------------------------------------------------------
# The repo registry
# ---------------------------------------------------------------------------

def test_repo_registry_traces_clean():
    schedules, violations = check_repo()
    assert len(schedules) == len(repo_programs())
    assert not violations, "\n".join(str(v) for v in violations)


def test_exchange_consistency_clean():
    assert check_exchange_consistency() == []


# ---------------------------------------------------------------------------
# GF(2) sanitizer
# ---------------------------------------------------------------------------

def test_duplicate_pivot_low_caught():
    san = Sanitizer()
    san.check_fresh_pivot({}, 5)                      # fresh: fine
    with pytest.raises(SanitizeViolation) as exc:
        san.check_fresh_pivot({5: 0}, 5)
    assert exc.value.check == "pivot-low-unique"
    assert "REPRO_SANITIZE[pivot-low-unique]" in str(exc.value)


def test_noncanonical_column_caught():
    san = Sanitizer()
    san.check_canonical_column(np.array([1, 4, 9], dtype=np.int64))
    with pytest.raises(SanitizeViolation):
        san.check_canonical_column(np.array([1, 9, 4], dtype=np.int64))
    with pytest.raises(SanitizeViolation):      # duplicates are not strict
        san.check_canonical_column(np.array([1, 4, 4], dtype=np.int64))


def test_pair_order_caught():
    san = Sanitizer()
    san.check_pair_orders(np.array([0.0, 1.0]), np.array([0.5, 2.0]))
    with pytest.raises(SanitizeViolation) as exc:
        san.check_pair_orders(np.array([1.0]), np.array([0.5]))
    assert exc.value.check == "pair-order"


def test_rematerialization_mismatch_caught():
    san = Sanitizer()
    a = np.array([2, 5], dtype=np.int64)
    san.check_rematerialization(a, a.copy(), col_id=3)
    with pytest.raises(SanitizeViolation) as exc:
        san.check_rematerialization(a, np.array([2, 7], dtype=np.int64), 3)
    assert exc.value.check == "spill-rematerialization"


def test_corrupted_packed_segment_caught():
    """A stray bit planted past a segment's key universe must be caught
    by consolidation instead of silently dropped by its keep filter."""
    from repro.core.packed_reduce import EMPTY_KEY, _PackedBatch

    def build():
        cob = np.full((2, 3), EMPTY_KEY, dtype=np.int64)
        cob[0] = [2, 5, 9]
        cob[1, :2] = [5, 11]
        batch = _PackedBatch(cob, [], use_kernels=False)
        batch.add_segment(np.array([20, 30], dtype=np.int64))
        return batch

    with sanitizing(True):
        build().consolidate()                    # clean block: no violation
        batch = build()
        # plant a set bit at rank 5 of the 2-key second segment
        batch.block[0, batch.seg_off[1]] |= np.uint32(1 << 5)
        with pytest.raises(SanitizeViolation) as exc:
            batch.consolidate()
    assert exc.value.check == "packed-segment"


def test_broken_wire_roundtrip_caught():
    from repro.core.pivot_cache import decode_commit_delta, encode_commit_delta

    records = [{"low": 3, "col_id": 7, "mode": "explicit",
                "column": np.array([3, 5, 9], dtype=np.int64),
                "gens": np.array([1], dtype=np.int64)}]
    with sanitizing(True):                      # honest codec: no violation
        payload = encode_commit_delta(records)

    san = Sanitizer()

    def lossy_decode(p):
        out = decode_commit_delta(p)
        out[0]["low"] += 1
        return out

    with pytest.raises(SanitizeViolation) as exc:
        san.check_wire_roundtrip(records, payload, lossy_decode)
    assert exc.value.check == "wire-roundtrip"

    corrupt = payload.copy()
    corrupt[0] = 0                              # smash the magic word
    with pytest.raises(SanitizeViolation):
        san.check_wire_roundtrip(records, corrupt, decode_commit_delta)


def test_violation_carries_context_and_location():
    san = Sanitizer()
    san.set_context(dim=2, superstep=7)
    with pytest.raises(SanitizeViolation) as exc:
        san.check_fresh_pivot({1: 0}, 1)
    v = exc.value
    assert v.context == {"dim": 2, "superstep": 7}
    assert __file__.split(os.sep)[-1] in v.location   # this call site
    san.set_context(dim=None, superstep=None)
    assert san.context == {}


def test_sanitizing_scopes_nest_and_restore():
    from repro.analyze import active_sanitizer
    with sanitizing(False):
        assert active_sanitizer() is None
        with sanitizing(True) as inner:
            assert active_sanitizer() is inner and inner is not None
            with sanitizing(None) as ambient:   # None defers to ambient
                assert ambient is inner
        assert active_sanitizer() is None


def test_compute_ph_sanitize_end_to_end():
    from repro.core import compute_ph
    from repro.core.diagrams import assert_diagrams_equal

    rng = np.random.default_rng(0)
    pts = rng.normal(size=(14, 3))
    plain = compute_ph(points=pts, maxdim=1, mode="implicit")
    checked = compute_ph(points=pts, maxdim=1, mode="implicit",
                         sanitize=True)
    assert_diagrams_equal(plain.diagrams, checked.diagrams, dims=[0, 1])
    assert checked.stats["sanitize_checks"] > 0
    assert "sanitize_checks" not in plain.stats


def test_compute_ph_sanitize_packed_engine():
    from repro.core import compute_ph
    from repro.core.diagrams import assert_diagrams_equal

    rng = np.random.default_rng(1)
    pts = rng.normal(size=(14, 3))
    plain = compute_ph(points=pts, maxdim=1, engine="packed",
                       mode="explicit", batch_size=8)
    checked = compute_ph(points=pts, maxdim=1, engine="packed",
                         mode="explicit", batch_size=8, sanitize=True)
    assert_diagrams_equal(plain.diagrams, checked.diagrams, dims=[0, 1])
    assert checked.stats["sanitize_checks"] > 0


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------

def test_cli_lint_exits_zero():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    env.setdefault("JAX_PLATFORMS", "cpu")
    proc = subprocess.run(
        [sys.executable, "-m", "repro.analyze", "lint", "--root", REPO],
        capture_output=True, text=True, env=env, cwd=REPO)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "lint: 0 finding(s)" in proc.stdout
