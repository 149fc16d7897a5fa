"""Reduction (``core/packed_reduce.py``): ``h1_n_rounds + h2_n_rounds``,
mean per call.  An exact count: it repeats on every platform."""


def read(run):
    if not run.calls:
        return None
    return sum(c["stats"].get("h1_n_rounds", 0.0)
               + c["stats"].get("h2_n_rounds", 0.0)
               for c in run.calls) / len(run.calls)
