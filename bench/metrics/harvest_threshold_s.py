"""Harvest (``scale/tiles.py``): seconds per call the host spends finding a
tile's f32 candidates (the candidate and upper masks and ``np.nonzero``),
the summed ``harvest/threshold`` spans that start inside the traced window.
A program without those spans reads nothing."""


def read(run):
    if run.trace is None or not run.calls:
        return None
    w0, w1 = run.trace.window
    spans = [end - start for start, end, name in run.trace.spans
             if name == "harvest/threshold" and w0 <= start < w1]
    return sum(spans) * 1e-9 / len(run.calls) if spans else None
