"""Static collective-schedule extraction and deadlock detection.

Every ``shard_map`` program in this repo must satisfy one property to be
deadlock-free: **all shards execute the identical ordered sequence of
collectives**.  A collective reached from only one branch of a
data-dependent ``lax.cond`` (the ``exchange_every`` / tournament bug
class), or sitting inside a ``while_loop`` whose trip count can differ
per shard, hangs the mesh — and only at scale, never under the
single-process simulator the tests run.

This module verifies the property *statically*, at trace level, with no
devices attached:

* :func:`collective_schedule` traces a function under
  ``jax.make_jaxpr(..., axis_env=...)`` and walks the jaxpr (recursing
  through ``pjit`` / ``scan`` / ``shard_map`` sub-jaxprs), emitting the
  ordered :class:`CollectiveOp` list plus :class:`Violation` records for
  divergent ``cond`` branches and collectives under ``while``.
* :func:`collective_schedule_from_hlo` does the same walk over compiled
  HLO text, parsed by :mod:`repro.analyze.hlo` — the post-XLA
  cross-check (DCE or rewrites can change the schedule the jaxpr
  promised).
* :func:`check_repo` traces the registered ``shard_map`` round functions
  of ``scale/shard.py`` and ``core/packed_reduce.py``, verifies their
  axis names against the mesh they run on, pins each traced schedule
  against the registry, and exercises replica-consistency of the
  pivot-exchange wire (``stack_wire_payloads`` round-trip + Elias–Fano
  delta codec) on deliberately uneven per-shard payloads.

Heavy imports (``jax``, the repro modules under test) happen inside
functions so that importing this module stays cheap.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, List, Mapping, Optional, Sequence, Tuple

__all__ = [
    "CollectiveOp",
    "Violation",
    "Schedule",
    "collective_schedule",
    "collective_schedule_from_hlo",
    "schedule_signature",
    "verify_axes",
    "Program",
    "repo_programs",
    "check_exchange_consistency",
    "check_repo",
]

# jaxpr primitive names that lower to cross-replica communication.
COLLECTIVE_PRIMS = frozenset({
    "all_gather", "all_to_all", "pbroadcast", "pmax", "pmin", "ppermute",
    "pshuffle", "psum", "psum_scatter", "reduce_scatter",
})


@dataclasses.dataclass(frozen=True)
class CollectiveOp:
    """One collective in program order."""

    name: str
    axes: Tuple[str, ...] = ()
    shapes: Tuple[Tuple[int, ...], ...] = ()
    group_size: int = 0

    def __str__(self) -> str:
        axes = ",".join(self.axes) if self.axes else "?"
        return f"{self.name}[{axes}]"


@dataclasses.dataclass(frozen=True)
class Violation:
    """A statically detected shard-uniformity / axis problem."""

    kind: str  # divergent-cond | while-collective | unknown-axis | ...
    where: str
    detail: str

    def __str__(self) -> str:
        return f"[{self.kind}] {self.where}: {self.detail}"


@dataclasses.dataclass
class Schedule:
    """Ordered collective schedule of one traced program."""

    where: str
    ops: List[CollectiveOp]
    violations: List[Violation]

    def signature(self) -> Tuple[Tuple[str, Tuple[str, ...]], ...]:
        return schedule_signature(self.ops)


def schedule_signature(
        ops: Sequence[CollectiveOp]) -> Tuple[Tuple[str, Tuple[str, ...]], ...]:
    """The order-sensitive (primitive, axes) fingerprint of a schedule."""
    return tuple((op.name, op.axes) for op in ops)


def _normalize_axes(value: Any) -> Tuple[str, ...]:
    if value is None:
        return ()
    if isinstance(value, (tuple, list)):
        return tuple(str(v) for v in value)
    return (str(value),)


def _eqn_axes(params: Mapping[str, Any]) -> Tuple[str, ...]:
    for key in ("axes", "axis_name", "axis_names"):
        if key in params:
            return _normalize_axes(params[key])
    return ()


def _as_jaxpr(obj: Any) -> Any:
    """Unwrap ClosedJaxpr-likes to the inner Jaxpr (duck-typed)."""
    inner = getattr(obj, "jaxpr", None)
    if inner is not None and hasattr(inner, "eqns"):
        return inner
    if hasattr(obj, "eqns"):
        return obj
    return None


def _sub_jaxprs(params: Mapping[str, Any],
                skip: Tuple[str, ...]) -> List[Any]:
    subs: List[Any] = []
    for key, value in params.items():
        if key in skip:
            continue
        values = value if isinstance(value, (tuple, list)) else (value,)
        for item in values:
            jaxpr = _as_jaxpr(item)
            if jaxpr is not None:
                subs.append(jaxpr)
    return subs


def _walk(jaxpr: Any, where: str, ops: List[CollectiveOp],
          violations: List[Violation]) -> None:
    for eqn in jaxpr.eqns:
        prim = eqn.primitive.name
        params = eqn.params
        if prim in COLLECTIVE_PRIMS:
            shapes = tuple(tuple(int(d) for d in v.aval.shape)
                           for v in eqn.outvars)
            ops.append(CollectiveOp(prim, _eqn_axes(params), shapes))
        elif prim == "cond":
            branch_runs = []
            for i, branch in enumerate(params.get("branches", ())):
                sub_ops: List[CollectiveOp] = []
                _walk(_as_jaxpr(branch), f"{where}/cond.branch{i}", sub_ops,
                      violations)
                branch_runs.append(sub_ops)
            signatures = {schedule_signature(run) for run in branch_runs}
            if len(signatures) > 1:
                pretty = sorted(
                    "(" + ", ".join(map(str, run)) + ")"
                    for run in branch_runs)
                violations.append(Violation(
                    "divergent-cond", where,
                    "lax.cond branches disagree on their collective "
                    f"schedule: {' vs '.join(pretty)}; a shard taking the "
                    "other branch deadlocks the mesh"))
            if branch_runs:
                ops.extend(max(branch_runs, key=len))
        elif prim == "while":
            body_ops: List[CollectiveOp] = []
            for key in ("cond_jaxpr", "body_jaxpr"):
                sub = _as_jaxpr(params.get(key))
                if sub is not None:
                    _walk(sub, f"{where}/while.{key}", body_ops, violations)
            if body_ops:
                violations.append(Violation(
                    "while-collective", where,
                    "collective(s) "
                    f"({', '.join(map(str, body_ops))}) inside a "
                    "while_loop; shards that disagree on the trip count "
                    "deadlock — hoist the collective or fix the trip count"))
                ops.extend(body_ops)
        else:
            for sub in _sub_jaxprs(params, skip=("branches",)):
                _walk(sub, f"{where}/{prim}", ops, violations)


def collective_schedule(fn: Callable[..., Any], args: Sequence[Any],
                        axis_env: Sequence[Tuple[str, int]],
                        where: Optional[str] = None) -> Schedule:
    """Trace ``fn(*args)`` under ``axis_env`` and extract its schedule.

    ``axis_env`` is a sequence of ``(axis_name, size)`` pairs, exactly as
    accepted by ``jax.make_jaxpr`` — no devices or mesh required.
    """
    import jax

    label = where or getattr(fn, "__name__", repr(fn))
    closed = jax.make_jaxpr(fn, axis_env=list(axis_env))(*args)
    ops: List[CollectiveOp] = []
    violations: List[Violation] = []
    _walk(closed.jaxpr, label, ops, violations)
    return Schedule(label, ops, violations)


def verify_axes(schedule: Schedule,
                mesh_axes: Sequence[str]) -> List[Violation]:
    """Every collective axis must exist on the mesh it runs under."""
    known = set(mesh_axes)
    violations: List[Violation] = []
    for op in schedule.ops:
        missing = [a for a in op.axes if a not in known]
        if missing:
            violations.append(Violation(
                "unknown-axis", schedule.where,
                f"{op} names axis(es) {missing} absent from the mesh axes "
                f"{sorted(known)}"))
    return violations


# ---------------------------------------------------------------------------
# HLO-level cross-check (post-XLA), over the analyze/hlo.py parser.
# ---------------------------------------------------------------------------

def collective_schedule_from_hlo(hlo_text: str,
                                 where: str = "<hlo>") -> Schedule:
    """Extract the collective schedule from compiled HLO text.

    Walks the entry computation in program order, inlining called and
    fusion-called computations and while bodies (parsed by
    :mod:`repro.analyze.hlo`).  A collective reached through a while loop
    whose trip count the parser cannot prove is flagged
    ``while-collective`` — the same deadlock class as the jaxpr walker,
    but after XLA had its say (DCE and rewrites can change the schedule
    the jaxpr promised).
    """
    from .hlo import COLLECTIVES, group_size, parse_module

    entry, parsed = parse_module(hlo_text)

    ops: List[CollectiveOp] = []
    violations: List[Violation] = []

    def visit(name: str, in_unproven_while: bool,
              stack: Tuple[str, ...]) -> None:
        comp = parsed.get(name)
        if comp is None or name in stack:
            return
        stack = stack + (name,)
        for op in comp.ops:
            base = op.opcode[:-len("-start")] \
                if op.opcode.endswith("-start") else op.opcode
            if base in COLLECTIVES:
                ops.append(CollectiveOp(base, (), (), group_size(op.line)))
                if in_unproven_while:
                    violations.append(Violation(
                        "while-collective", where,
                        f"HLO {base} executes under a while loop with an "
                        "unproven trip count; shards that disagree on the "
                        "trip count deadlock"))
        for callee in comp.calls:
            visit(callee, in_unproven_while, stack)
        for callee in comp.fusion_calls:
            visit(callee, in_unproven_while, stack)
        for cond, body, trip in comp.whiles:
            risky = in_unproven_while or trip <= 0
            visit(cond, risky, stack)
            visit(body, risky, stack)

    if entry is not None:
        visit(entry, False, ())
    return Schedule(where, ops, violations)


# ---------------------------------------------------------------------------
# The repo registry: every shard_map program we ship, with its pinned
# schedule.  A mismatch is a violation — update the registry only together
# with the driver change that alters the schedule.
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Program:
    """A registered shard_map round function and its pinned schedule."""

    name: str
    build: Callable[[], Tuple[Callable[..., Any], Tuple[Any, ...],
                              Tuple[Tuple[str, int], ...]]]
    mesh_axes: Tuple[str, ...]
    expect: Tuple[Tuple[str, Tuple[str, ...]], ...]


def repo_programs() -> List[Program]:
    """Build closures for every shard_map round function in the repo."""
    import functools

    def candidate_round() -> Tuple[Callable[..., Any], Tuple[Any, ...],
                                   Tuple[Tuple[str, int], ...]]:
        import jax.numpy as jnp
        from ..scale.shard import _candidate_round_fn
        fn = functools.partial(_candidate_round_fn, interpret=True)
        x = jnp.zeros((1, 8, 3), jnp.float32)
        return fn, (x, x), (("data", 4),)

    def dists_round() -> Tuple[Callable[..., Any], Tuple[Any, ...],
                               Tuple[Tuple[str, int], ...]]:
        import numpy as np
        import jax.numpy as jnp
        from ..scale.shard import _dists_round_fn
        fn = functools.partial(_dists_round_fn, thr32=np.float32(1.0))
        return fn, (jnp.zeros((1, 8, 8), jnp.float32),), (("data", 4),)

    def exchange_round() -> Tuple[Callable[..., Any], Tuple[Any, ...],
                                  Tuple[Tuple[str, int], ...]]:
        import jax.numpy as jnp
        from ..core.packed_reduce import _exchange_round_fn
        fn = functools.partial(_exchange_round_fn, axis_name="data")
        return fn, (jnp.zeros((1, 1024), jnp.uint32),), (("data", 4),)

    return [
        Program("scale.shard._candidate_round_fn", candidate_round,
                ("data",), expect=()),
        Program("scale.shard._dists_round_fn", dists_round,
                ("data",), expect=()),
        Program("core.packed_reduce._exchange_round_fn", exchange_round,
                ("data",), expect=(("all_gather", ("data",)),)),
    ]


def check_exchange_consistency() -> List[Violation]:
    """Replica-consistency of the pivot-exchange wire, statically.

    Every shard enters the ``all_gather`` with the *same* padded payload
    length, whatever its local commit count — that is the job of
    ``stack_wire_payloads``.  And a replica applies exactly the records
    the owner committed — that is the job of the Elias–Fano delta codec.
    Both are pure host code, so we can verify them here on deliberately
    uneven per-shard loads without any devices.
    """
    import numpy as np

    from ..core.pivot_cache import decode_commit_delta, encode_commit_delta
    from ..kernels.gf2 import stack_wire_payloads, unstack_wire_payloads

    violations: List[Violation] = []
    where = "pivot-exchange wire"

    for sizes in [(0, 0, 0, 0), (0, 1, 7, 1000), (5, 5, 5, 5),
                  (1023, 1025, 1, 64)]:
        payloads = [np.arange(s, dtype=np.uint32) % 97 for s in sizes]
        stacked, lengths = stack_wire_payloads(payloads)
        if stacked.ndim != 2 or stacked.shape[0] != len(sizes):
            violations.append(Violation(
                "wire-shape", where,
                f"stack_wire_payloads({sizes}) produced shape "
                f"{stacked.shape}; shards would all_gather unequal blocks"))
            continue
        width = int(stacked.shape[1])
        if width < max(sizes) or (width & (width - 1)) != 0:
            violations.append(Violation(
                "wire-shape", where,
                f"padded wire width {width} for shard loads {sizes} is not "
                "a power-of-two cover; shards would disagree on the "
                "all_gather element count"))
        back = unstack_wire_payloads(stacked, lengths)
        if not all(np.array_equal(a, b) for a, b in zip(payloads, back)):
            violations.append(Violation(
                "wire-roundtrip", where,
                f"stack/unstack round-trip corrupted a payload ({sizes})"))

    lows = np.array([3, 11, 12, 40], dtype=np.int64)
    records = [
        {"low": int(lows[0]), "col_id": 7, "mode": "explicit",
         "column": np.array([3, 5, 9], dtype=np.int64),
         "gens": np.array([1], dtype=np.int64)},
        {"low": int(lows[1]), "col_id": 8, "mode": "implicit",
         "column": None, "gens": np.array([2, 4], dtype=np.int64)},
        {"low": int(lows[2]), "col_id": 9, "mode": "explicit",
         "column": np.array([12], dtype=np.int64), "gens": None},
        {"low": int(lows[3]), "col_id": 13, "mode": "implicit",
         "column": None, "gens": None},
    ]
    for count in (0, 1, len(records)):
        subset = records[:count]
        decoded = decode_commit_delta(encode_commit_delta(subset))
        same = len(decoded) == len(subset) and all(
            int(a["low"]) == int(b["low"])
            and int(a["col_id"]) == int(b["col_id"])
            and str(a["mode"]) == str(b["mode"])
            for a, b in zip(subset, decoded))
        if not same:
            violations.append(Violation(
                "wire-roundtrip", where,
                f"Elias–Fano commit-delta codec failed the {count}-record "
                "round-trip; replicas would apply a different pivot set "
                "than the owner committed"))
    return violations


def check_repo() -> Tuple[List[Schedule], List[Violation]]:
    """Trace every registered program; collect all violations."""
    schedules: List[Schedule] = []
    violations: List[Violation] = []
    for program in repo_programs():
        try:
            fn, args, axis_env = program.build()
            schedule = collective_schedule(fn, args, axis_env, program.name)
        except Exception as exc:  # noqa: BLE001 - reported, not raised
            violations.append(Violation(
                "trace-error", program.name,
                f"failed to trace the registered program: {exc!r}"))
            continue
        schedules.append(schedule)
        violations.extend(schedule.violations)
        violations.extend(verify_axes(schedule, program.mesh_axes))
        signature = schedule.signature()
        if signature != program.expect:
            violations.append(Violation(
                "schedule-mismatch", program.name,
                f"traced collective schedule {signature} != registered "
                f"{program.expect}; update the registry only together with "
                "the driver change that re-orders the schedule"))
    violations.extend(check_exchange_consistency())
    return schedules, violations
