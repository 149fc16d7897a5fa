"""Reduction (``core/packed_reduce.py``): seconds per call the host waits on
gf2 kernel round trips, the summed ``gf2/*`` spans (host array to the
device, kernel, result back) that start inside the traced window.  A
program without those spans reads nothing."""


def read(run):
    if run.trace is None or not run.calls:
        return None
    w0, w1 = run.trace.window
    waits = [end - start for start, end, name in run.trace.spans
             if name.startswith("gf2/") and w0 <= start < w1]
    return sum(waits) * 1e-9 / len(run.calls) if waits else None
