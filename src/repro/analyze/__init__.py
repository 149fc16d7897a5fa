"""repro.analyze — correctness tooling for the whole pipeline.

Dory's output is only as good as a set of fragile invariants: exact GF(2)
algebra (``R = ∂V``, unique pivot lows), canonical filtration tie-breaking,
and lock-step collective schedules across mesh shards.  The repo's own
history shows these break *silently* — the f32-candidate/f64-refine dtype
discipline of the tiled harvest, the ``exchange_every`` cadence rules of the
distributed reduction.
This package is the gate that catches that bug class before (or the moment)
it ships, in three layers:

* :mod:`repro.analyze.lint` — an AST lint pass with repo-specific rules
  derived from bugs we have actually shipped (host↔device syncs in
  superstep/harvest hot loops, raw sorts on filtration values without the
  canonical ``(length, i, j)`` tie-break, f32 candidates compared against
  exact thresholds, unseeded RNG in benchmarks).  Deliberate exceptions carry a justified
  ``# analyze: allow[rule] why`` pragma — a bare pragma is itself a
  finding.
* :mod:`repro.analyze.collectives` — a jaxpr/HLO walker that extracts the
  ordered collective schedule of every ``shard_map`` program in the repo
  and statically verifies axis names, shard-uniformity (divergent
  ``cond`` branches and data-dependent ``while`` trip counts around
  collectives are the distributed-deadlock bug class), and
  replica-consistency of the pivot-exchange wire.
* :mod:`repro.analyze.invariants` — an opt-in runtime GF(2) sanitizer
  (``compute_ph(sanitize=True)`` / ``REPRO_SANITIZE=1``) instrumenting the
  reduction engines with cheap incremental checks: pivot-low uniqueness,
  packed-block segment consistency, Elias–Fano wire round-trips, and
  R-column re-materialization equality on budget spills — reporting a
  structured :class:`SanitizeViolation` (file:line, batch, superstep)
  instead of a silently wrong diagram.

``python -m repro.analyze`` runs the static layers over the repo and exits
non-zero on any unjustified finding; CI runs it on every push.  See
``docs/analysis.md`` for the field guide.
"""
from .invariants import (SanitizeViolation, Sanitizer, active_sanitizer,
                         sanitizing)

__all__ = [
    "SanitizeViolation",
    "Sanitizer",
    "active_sanitizer",
    "sanitizing",
]
