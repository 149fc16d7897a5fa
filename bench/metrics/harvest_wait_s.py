"""Harvest (``scale/tiles.py``): seconds per call the host waits on the
Pallas distance tiles, the summed ``harvest/fetch`` spans (kernel plus the
tile's copy to the host) that start inside the traced window.  A program
without those spans reads nothing."""


def read(run):
    if run.trace is None or not run.calls:
        return None
    w0, w1 = run.trace.window
    waits = [end - start for start, end, name in run.trace.spans
             if name == "harvest/fetch" and w0 <= start < w1]
    return sum(waits) * 1e-9 / len(run.calls) if waits else None
