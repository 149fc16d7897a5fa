"""Reduction of a profiler trace (``.xplane.pb``) to the benchmark's numbers.

Read with ``jax.profiler.ProfileData``, which needs nothing but JAX.  On a
TPU the trace holds one plane per chip (``/device:TPU:k``) whose ``XLA Ops``
line has one event per operation run, named by its HLO text
(``%pairwise_sq_dists.1 = f32[2048,2048] custom-call(...)``), and a host
plane (``/host:CPU``) whose lines hold ``jax.profiler.TraceAnnotation``
events: the program's spans (``area/what``, bridged by
``repro.obs.trace.Tracer(bridge=True)``) and the benchmark's own
``bench/window``.  Both planes share one clock.

* busy time: the union of the op intervals of a chip, clipped to the window;
* kernel time: the summed durations of the ops whose name holds a pattern;
* idle gaps: the holes in the union, each named by the innermost host span
  open at its midpoint (``no span`` where none is).
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
from collections import defaultdict
from typing import Dict, List, Sequence, Tuple

WINDOW_SPAN = "bench/window"
NO_SPAN = "no span"
_SPAN_NAME = re.compile(r"^[A-Za-z0-9_.]+(/[A-Za-z0-9_.]+)+$")
_OP_SUFFIX = re.compile(r"\.\d+$")

Interval = Tuple[float, float]


@dataclasses.dataclass
class Op:
    name: str            # short op name: the HLO name without ``%`` and ``.N``
    start_ns: float
    dur_ns: float


@dataclasses.dataclass
class Trace:
    """What the benchmark reads from one trace."""
    window: Interval                  # ns, on the trace's clock
    devices: Dict[str, List[Op]]      # device plane name -> ops
    spans: List[Tuple[float, float, str]]   # host spans (start, end, name)

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    def busy_s(self) -> float:
        """Busy seconds inside the window, averaged over the chips used
        (a chip that ran no op inside the window is not counted)."""
        busy = [_length(_union(ops, self.window))
                for ops in self.devices.values()]
        busy = [b for b in busy if b > 0]
        return sum(busy) / len(busy) * 1e-9 if busy else 0.0

    def kernel_s(self, pattern: str) -> float:
        """Summed device seconds of the ops whose name holds ``pattern``."""
        w0, w1 = self.window
        return sum(op.dur_ns for ops in self.devices.values() for op in ops
                   if pattern in op.name and w0 <= op.start_ns < w1) * 1e-9

    def top_ops(self, k: int = 10) -> List[List]:
        """The ``k`` op names that took most device time: ``[name, s]``."""
        w0, w1 = self.window
        total: Dict[str, float] = defaultdict(float)
        for ops in self.devices.values():
            for op in ops:
                if w0 <= op.start_ns < w1:
                    total[op.name] += op.dur_ns * 1e-9
        return [[n, s] for n, s in
                sorted(total.items(), key=lambda kv: -kv[1])[:k]]

    def idle_gaps(self, k: int = 10) -> List[List]:
        """Idle seconds of the first chip, summed by the host span open in
        each gap; the ``k`` largest: ``[span name, s]``."""
        if not self.devices:
            return []
        ops = self.devices[sorted(self.devices)[0]]
        gaps = _gaps(_union(ops, self.window), self.window)
        total: Dict[str, float] = defaultdict(float)
        for (g0, g1), name in zip(gaps, _innermost(self.spans,
                                                   [(a + b) / 2
                                                    for a, b in gaps])):
            total[name] += (g1 - g0) * 1e-9
        return [[n, s] for n, s in
                sorted(total.items(), key=lambda kv: -kv[1])[:k]]


def short_op_name(hlo: str) -> str:
    name = hlo.split(" = ", 1)[0].strip().lstrip("%")
    return _OP_SUFFIX.sub("", name)


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def read(path: str) -> Trace:
    """Parse one ``.xplane.pb`` file."""
    import jax

    data = jax.profiler.ProfileData.from_file(path)
    devices: Dict[str, List[Op]] = {}
    spans: List[Tuple[float, float, str]] = []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            ops = []
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops.extend(Op(short_op_name(ev.name), ev.start_ns,
                                  ev.duration_ns) for ev in line.events)
            devices[plane.name] = ops
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if _SPAN_NAME.match(ev.name):
                        spans.append((ev.start_ns,
                                      ev.start_ns + ev.duration_ns, ev.name))
    spans.sort(key=lambda s: (s[0], -s[1]))   # outer span first
    windows = [(a, b) for a, b, n in spans if n == WINDOW_SPAN]
    if windows:
        window = windows[0]
    else:
        starts = [op.start_ns for ops in devices.values() for op in ops]
        ends = [op.start_ns + op.dur_ns for ops in devices.values()
                for op in ops]
        window = (min(starts), max(ends)) if starts else (0.0, 0.0)
    return Trace(window=window, devices=devices, spans=spans)


def _union(ops: Sequence[Op], window: Interval) -> List[Interval]:
    w0, w1 = window
    ivs = sorted((max(op.start_ns, w0), min(op.start_ns + op.dur_ns, w1))
                 for op in ops)
    out: List[List[float]] = []
    for a, b in ivs:
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def _length(ivs: Sequence[Interval]) -> float:
    return sum(b - a for a, b in ivs)


def _gaps(busy: Sequence[Interval], window: Interval) -> List[Interval]:
    out, t = [], window[0]
    for a, b in busy:
        if a > t:
            out.append((t, a))
        t = max(t, b)
    if window[1] > t:
        out.append((t, window[1]))
    return out


def _innermost(spans: Sequence[Tuple[float, float, str]],
               points: Sequence[float]) -> List[str]:
    """For each time in ``points`` (ascending), the name of the innermost
    span that contains it.  Spans come from one thread, so they nest."""
    names, stack, i = [], [], 0
    for t in points:
        while i < len(spans) and spans[i][0] <= t:
            while stack and stack[-1][1] <= spans[i][0]:
                stack.pop()
            stack.append(spans[i])
            i += 1
        while stack and stack[-1][1] <= t:
            stack.pop()
        names.append(stack[-1][2] if stack else NO_SPAN)
    return names
