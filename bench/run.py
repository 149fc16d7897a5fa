"""On-chip benchmark of the persistent-homology engine: one cell, one run.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Cells are named in ``BENCHMARK.json``; their configuration, traffic mix and
per-layer metric readers are files under ``bench/`` (``bench/spec.py``).
A run makes its inputs from ``--seed``, warms up the cell's shapes (set-up,
timed as ``setup_s`` from process start), measures for ``--seconds``, then
compares the window's answers with the plain reference
(``bench/reference.py``) and prints one JSON line last on standard output.
With ``--trace 1`` the window runs under the JAX profiler with the
program's spans bridged into it, and the line carries the per-layer
metrics, the device's busy time and a breakdown instead of the end-to-end
metrics.  Without a TPU, or with fewer chips than the cell asks for, it
exits 1 and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse    # noqa: E402
import dataclasses    # noqa: E402
import json    # noqa: E402
import math    # noqa: E402
import os    # noqa: E402
import shutil    # noqa: E402
import sys    # noqa: E402
from typing import Dict, List, Optional    # noqa: E402

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if __name__ == "__main__":
    # run as a script: the checkout's root replaces bench/ on the path, so
    # bench's modules import as a package and shadow nothing
    sys.path[0] = _ROOT
sys.path.insert(1, os.path.join(_ROOT, "src"))

from bench import spec    # noqa: E402
from bench import trace as btrace    # noqa: E402

TRACE_DIR = ".bench_trace"     # under the checkout, rewritten by each run


class CompileCounter:
    """Counts XLA programs built (compiled, or loaded from the persistent
    cache) through ``jax.monitoring``; one listener per process."""

    _instance: Optional["CompileCounter"] = None

    def __init__(self):
        import jax

        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    @classmethod
    def get(cls) -> "CompileCounter":
        if cls._instance is None:
            cls._instance = cls()
        return cls._instance

    def _on_event(self, event, duration, **kwargs):
        if event == "/jax/core/compile/backend_compile_duration":
            self.n += 1


@dataclasses.dataclass
class Run:
    """What a per-layer metric reader (``bench/metrics/<name>.py``) reads."""
    device_kind: str
    calls: List[Dict]            # one dict per call: "stats", "d", ...
    window_compiles: int
    trace: Optional[btrace.Trace]


def make_driver(cell, seed: int):
    """The driver of the cell's loop, from ``bench/loops/<loop>.py``."""
    return spec.load_part(cell.root, "loops", cell.mix["loop"]).driver(
        cell, seed)


def devices_or_exit(chips: int, require_tpu: bool):
    import jax

    devices = jax.devices()
    if require_tpu and (devices[0].platform != "tpu" or len(devices) < chips):
        print(f"bench: the cell needs {chips} TPU chip(s); JAX sees "
              f"{len(devices)} {devices[0].platform} device(s)",
              file=sys.stderr)
        raise SystemExit(1)
    return devices


def start_cache() -> None:
    import jax

    from repro.kernels.backend import use_compile_cache

    use_compile_cache()
    # every program, however quick to compile, so that a run after the
    # first builds nothing
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)


def run(cell, seed: int, seconds: float, traced: bool,
        require_tpu: bool = True, driver=None) -> Dict:
    import jax

    devices = devices_or_exit(cell.chips, require_tpu)
    start_cache()
    compiles = CompileCounter.get()
    driver = driver or make_driver(cell, seed)
    driver.setup()
    if jax.config.jax_enable_x64:
        raise RuntimeError("jax_enable_x64 is on: Mosaic refuses the "
                           "kernels; something imported core/jax_engine.py")
    trace_dir = os.path.join(cell.root, TRACE_DIR)
    if traced:
        from repro.obs.trace import Tracer, tracing

        shutil.rmtree(trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    c0 = compiles.n
    setup_s = time.perf_counter() - T_START
    if traced:
        with tracing(Tracer(bridge=True)), \
                jax.profiler.TraceAnnotation(btrace.WINDOW_SPAN):
            driver.window(seconds)
        jax.profiler.stop_trace()
    else:
        driver.window(seconds)
    window_compiles = compiles.n - c0
    used = devices[:cell.chips]
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in used)
    attempted, failed = driver.attempted_failed()
    e2e = dict(driver.end_to_end(), setup_s=setup_s)
    record = Run(device_kind=devices[0].device_kind,
                 calls=driver.calls,
                 window_compiles=window_compiles,
                 trace=btrace.read(btrace.find_xplane(trace_dir))
                 if traced else None)
    driver.release()
    checks = driver.check()
    correct = all(v <= lim for v, lim in checks.values()) and attempted > 0
    units = {m["name"]: m["unit"] for m in cell.end_to_end + cell.per_layer}
    if traced:
        values = {name: read(record) for name, read in cell.readers.items()}
    else:
        values = {m["name"]: e2e[m["name"]] for m in cell.end_to_end}
    # a reader that finds nothing returns None: not a number to print
    metrics = {k: {"value": v, "unit": units[k]}
               for k, v in values.items()
               if v is not None and math.isfinite(v)}
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices), "memory_peak_bytes": int(peak)}
    out = {"correct": bool(correct), "attempted": attempted,
           "failed": failed, "metrics": metrics, "device": device}
    if traced:
        tr = record.trace
        device.update(busy_s=tr.busy_s(), window_s=tr.window_s)
        out["breakdown"] = {"device_ops": tr.top_ops(),
                            "idle_gaps": tr.idle_gaps()}
    out["notes"] = driver.notes()
    out["checks"] = {k: {"value": v, "limit": lim}
                     for k, (v, lim) in checks.items()}
    return out


def main(argv=None, root: str = _ROOT, require_tpu: bool = True) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = parser.parse_args(argv)
    cell = spec.load(args.workload, root)
    out = run(cell, args.seed, args.seconds, bool(args.trace), require_tpu)
    for name, c in out["checks"].items():
        print(f"check {name} = {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
