"""Reduction (``core/packed_reduce.py``): gf2 kernel round trips per call,
``h1_n_device_calls + h2_n_device_calls`` of ``compute_ph``'s stats, mean
per call.  An exact count.  A program without the counter reads nothing."""


def read(run):
    if not run.calls or "h1_n_device_calls" not in run.calls[0]["stats"]:
        return None
    return sum(c["stats"].get("h1_n_device_calls", 0.0)
               + c["stats"].get("h2_n_device_calls", 0.0)
               for c in run.calls) / len(run.calls)
