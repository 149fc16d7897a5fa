"""Harvest (``scale/tiles.py``): megabytes of distance tiles copied from the
chip to the host per call, ``harvest_fetch_bytes`` of ``compute_ph``'s stats
over 10^6, mean per call.  An exact count: each tile at its cropped extent,
4 bytes a distance.  A program without the counter reads nothing."""


def read(run):
    if not run.calls or "harvest_fetch_bytes" not in run.calls[0]["stats"]:
        return None
    return sum(c["stats"]["harvest_fetch_bytes"]
               for c in run.calls) / len(run.calls) / 1e6
