"""Harvest (``scale/tiles.py``): ``compute_ph``'s ``t_filtration``, mean
seconds per call.  A host stopwatch around work that ends in host arrays."""


def read(run):
    if not run.calls:
        return None
    return sum(c["stats"]["t_filtration"] for c in run.calls) / len(run.calls)
