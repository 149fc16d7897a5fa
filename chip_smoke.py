"""Smoke run of the persistent-homology device path on a TPU.

    python chip_smoke.py              # one chip: phases A and B
    python chip_smoke.py --chips 4    # four chips: the mesh path only

Phase A runs ``compute_ph(backend="tiled", engine="packed")`` on paper
Table 1's ``o3`` cloud at its published size (8192 points in R^9): the
Pallas tile harvest and the packed GF(2) kernels.  Its diagrams must equal
the host reference engine's (``engine="single", backend="dense"``) bit for
bit.  Phase B sends three requests to ``PHServeEngine(engine="packed")``
on a 4096-point Clifford torus (cold, cache hit, tau growth) and checks
each answer against a cold host ``compute_ph``.

With ``--chips 4`` only the mesh path runs: the sharded tile harvest and
the distributed packed reduction over a ``(data=4,)`` mesh, compared bit
for bit with the same call on one device, in the same process.  A
profiler trace of the mesh call reports which device ran the GF(2)
kernels.

Wall times printed are smoke timings of a single run, not benchmark
numbers.  Any failed check raises; the last line of standard output is
one JSON object with ``ok`` and the device as JAX reports it, printed only
when every phase passed on a TPU.
"""
from __future__ import annotations

import argparse
import glob
import json
import os
import sys
import tempfile
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

# o3 at n = 8192: n_e = 68,696 edges and 409 H2* bars at tau 0.6; the
# host reference takes about a minute and a half on one CPU core
O3_N, O3_TAU = 8192, 0.6
# torus4 at n = 4096: a cold request at 0.10 (n_e = 13,335), then tau
# growth to 0.15 (n_e = 30,118)
TORUS_N, TORUS_TAU0, TORUS_TAU1 = 4096, 0.10, 0.15


class CompileCounter:
    """Counts XLA backend compilations through ``jax.monitoring``."""

    def __init__(self):
        import jax

        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, **kwargs):
        if event == "/jax/core/compile/backend_compile_duration":
            self.n += 1


def check(cond, msg: str) -> None:
    if not cond:
        raise RuntimeError(msg)


def check_same_diagrams(got, want, what: str) -> None:
    from repro.core.resume import canonical_diagram

    check(sorted(got) == sorted(want), f"{what}: dimensions differ")
    for d in sorted(want):
        a, b = canonical_diagram(got[d]), canonical_diagram(want[d])
        check(np.array_equal(a, b),
              f"{what}: H{d} differs from the host reference "
              f"({len(a)} vs {len(b)} bars)")


def bars(diagrams) -> str:
    return "/".join(str(len(diagrams[d])) for d in sorted(diagrams))


def o3_cloud(n: int) -> np.ndarray:
    from repro.data.pointclouds import o3_points

    return o3_points(n, seed=0)


def phase_a(compiles: CompileCounter, n: int = O3_N,
            tau: float = O3_TAU) -> None:
    """One PH run on the device path, checked against the host engine."""
    from repro.core.homology import compute_ph

    points = o3_cloud(n)
    c0, t0 = compiles.n, time.perf_counter()
    dev = compute_ph(points, tau_max=tau, maxdim=2, backend="tiled",
                     engine="packed")
    wall = time.perf_counter() - t0
    n_comp = compiles.n - c0
    st = dev.stats
    check(st["harvest_pallas"] == 1, "the tile harvest did not run on pallas")
    for d in ("h1", "h2"):
        check(st[f"{d}_use_kernels"] == 1,
              f"{d} reduction did not run the gf2 kernels")
    t0 = time.perf_counter()
    ref = compute_ph(points, tau_max=tau, maxdim=2, engine="single",
                     backend="dense")
    ref_wall = time.perf_counter() - t0
    check_same_diagrams(dev.diagrams, ref.diagrams, "phase A")
    check(len(ref.diagrams[2]) > 0, "phase A: H2* is empty at this tau")
    width = int(max(st["h1_max_block_words"], st["h2_max_block_words"]))
    print(f"phase A: o3 n={int(st['n'])} n_e={int(st['n_e'])} tau={tau} "
          f"maxdim=2, H0/H1/H2 bars {bars(ref.diagrams)} equal to host")
    print(f"phase A: largest packed width {width} words")
    print(f"phase A: smoke timing (not a benchmark): device path "
          f"{wall:.1f} s, host reference {ref_wall:.1f} s")
    print(f"phase A: {n_comp} compilations in the device run")


def phase_b(compiles: CompileCounter, n: int = TORUS_N,
            tau0: float = TORUS_TAU0, tau1: float = TORUS_TAU1) -> None:
    """Cold, repeat and tau-growth requests to the packed service."""
    from repro.core.homology import compute_ph
    from repro.data.pointclouds import clifford_torus
    from repro.serve.ph import PHRequest, PHServeEngine

    points = clifford_torus(n, seed=0)
    eng = PHServeEngine(engine="packed")
    # the service keeps only diagrams: read the per-dimension reduction
    # stats off its reducer to check the device path ran
    red_stats = []
    reducer = eng._reducer

    def spy(*args, **kwargs):
        res = reducer(*args, **kwargs)
        red_stats.append(res.stats)
        return res

    eng._reducer = spy
    c0, t0 = compiles.n, time.perf_counter()
    served = []
    for uid, tau, path in ((0, tau0, "cold"), (1, tau0, "hit"),
                           (2, tau1, "warm_tau")):
        eng.submit(PHRequest(uid=uid, points=points, tau_max=tau, maxdim=2))
        eng.step()
        resp = eng.done[uid]
        check(resp.path == path,
              f"phase B: request {uid} took path {resp.path}, not {path}")
        served.append((uid, tau, resp))
    wall = time.perf_counter() - t0
    n_comp = compiles.n - c0
    check(red_stats and all(s["use_kernels"] == 1 for s in red_stats),
          "phase B: the service did not run the gf2 kernels")
    refs = {}
    t0 = time.perf_counter()
    for uid, tau, resp in served:
        if tau not in refs:
            refs[tau] = compute_ph(points, tau_max=tau, maxdim=2,
                                   engine="single", backend="dense")
        check_same_diagrams(resp.diagrams, refs[tau].diagrams,
                            f"phase B request {uid} ({resp.path})")
        print(f"phase B: request {uid} {resp.path} torus4 n={n} "
              f"n_e={int(refs[tau].stats['n_e'])} tau={tau}, H0/H1/H2 "
              f"bars {bars(resp.diagrams)} equal to host")
    ref_wall = time.perf_counter() - t0
    width = int(max(s.get("max_block_words", 0) for s in red_stats))
    print(f"phase B: largest packed width {width} words")
    print(f"phase B: smoke timing (not a benchmark): service {wall:.1f} s "
          f"for 3 requests, host references {ref_wall:.1f} s")
    print(f"phase B: {n_comp} compilations in the service run")


def kernel_events_per_device(trace_dir: str) -> dict:
    """``{device plane: {"gf2": n, "all": n}}``: trace events that name a
    gf2 kernel, and all events, on each device of the profiler's trace."""
    import jax

    (path,) = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                        recursive=True)
    out = {}
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if plane.name.startswith("/device:"):
            names = [ev.name for line in plane.lines for ev in line.events]
            out[plane.name] = {"gf2": sum("gf2" in nm for nm in names),
                               "all": len(names)}
    return out


def phase_mesh(compiles: CompileCounter, n: int = O3_N,
               tau: float = O3_TAU) -> None:
    """The four-chip path, compared with the same call on one device."""
    import jax

    from repro.core.homology import compute_ph
    from repro.scale import make_data_mesh

    points = o3_cloud(n)
    mesh = make_data_mesh(4)
    c0, t0 = compiles.n, time.perf_counter()
    with tempfile.TemporaryDirectory() as trace_dir:
        with jax.profiler.trace(trace_dir):
            sharded = compute_ph(points, tau_max=tau, maxdim=2,
                                 backend="tiled", engine="packed", mesh=mesh)
        wall = time.perf_counter() - t0
        placement = kernel_events_per_device(trace_dir)
    n_comp = compiles.n - c0
    st = sharded.stats
    check(st["harvest_pallas"] == 1 and st["n_shards"] == 4,
          "the harvest did not shard over four devices")
    for d in ("h1", "h2"):
        check(st[f"{d}_use_kernels"] == 1 and st[f"{d}_n_shards"] == 4,
              f"{d} reduction did not run distributed on the kernels")
    t0 = time.perf_counter()
    single = compute_ph(points, tau_max=tau, maxdim=2, backend="tiled",
                        engine="packed")
    single_wall = time.perf_counter() - t0
    check_same_diagrams(sharded.diagrams, single.diagrams,
                        "mesh vs one device")
    width = int(max(st["h1_max_block_words"], st["h2_max_block_words"]))
    print(f"mesh: o3 n={int(st['n'])} n_e={int(st['n_e'])} tau={tau} "
          f"maxdim=2 on (data=4,), H0/H1/H2 bars {bars(sharded.diagrams)} "
          f"equal to the one-device run")
    print(f"mesh: largest packed width {width} words")
    print(f"mesh: smoke timing (not a benchmark): mesh run {wall:.1f} s "
          f"(profiler on), one-device run {single_wall:.1f} s")
    print(f"mesh: {n_comp} compilations in the mesh run")
    print(f"mesh: gf2 kernel trace events per device: "
          f"{json.dumps(placement, sort_keys=True)}")


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--chips", type=int, choices=(1, 4), default=1,
                        help="4 runs only the mesh path on four chips")
    args = parser.parse_args(argv)

    import jax

    if jax.default_backend() != "tpu":
        print(f"chip_smoke: no TPU (JAX backend is "
              f"{jax.default_backend()!r})", file=sys.stderr)
        return 1
    devices = jax.devices()
    check(len(devices) >= args.chips,
          f"--chips {args.chips} needs {args.chips} devices, "
          f"JAX sees {len(devices)}")

    from repro.kernels.backend import use_compile_cache

    cache = use_compile_cache()
    print(f"compile cache: {cache or os.environ['JAX_COMPILATION_CACHE_DIR']}")
    compiles = CompileCounter()
    if args.chips == 4:
        phase_mesh(compiles)
    else:
        phase_a(compiles)
        phase_b(compiles)
    dev = devices[0]
    print(json.dumps({"ok": True,
                      "device": {"platform": dev.platform,
                                 "kind": dev.device_kind,
                                 "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
