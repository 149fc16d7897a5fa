"""Reduction (``core/packed_reduce.py``): ``t_h1 + t_h2`` of ``compute_ph``,
mean seconds per call."""


def read(run):
    if not run.calls:
        return None
    return sum(c["stats"].get("t_h1", 0.0) + c["stats"].get("t_h2", 0.0)
               for c in run.calls) / len(run.calls)
