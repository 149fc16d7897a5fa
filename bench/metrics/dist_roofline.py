"""Kernel ``pairwise_sq_dists`` (``kernels/pairwise_dist.py``): the least
time the chip needs for the distances the calls' clouds ask for
(``bench/work.py``), as a share of the kernel's summed device time in the
trace, in percent."""
from bench import work


def read(run):
    if run.trace is None or not run.calls:
        return None
    spent = run.trace.kernel_s("pairwise_sq_dists")
    if spent <= 0.0:
        return None
    peak = work.peaks(run.device_kind)
    need = sum(work.least_seconds(
        work.dist_work(int(c["stats"]["n"]), c["d"], int(c["stats"]["n_e"])),
        peak) for c in run.calls)
    return 100.0 * need / spent
