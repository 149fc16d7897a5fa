"""Kernels ``gf2_*`` (``kernels/gf2.py``): summed device time of the GF(2)
kernels in the trace, milliseconds per call."""


def read(run):
    if run.trace is None or not run.calls:
        return None
    spent = run.trace.kernel_s("gf2_")
    return 1e3 * spent / len(run.calls) if spent > 0.0 else None
