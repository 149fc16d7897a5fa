"""Input (``scale/sparse_input.py``): seconds per call of the COO build, the
summed ``coo/build`` spans (symmetrize, filter, filtration from edges) that
start inside the traced window.  A program without the span reads
nothing."""


def read(run):
    if run.trace is None or not run.calls:
        return None
    w0, w1 = run.trace.window
    builds = [end - start for start, end, name in run.trace.spans
              if name == "coo/build" and w0 <= start < w1]
    return sum(builds) * 1e-9 / len(run.calls) if builds else None
