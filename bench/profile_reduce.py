"""From a JAX profiler trace to device busy time, program time and idle gaps.

The profiler writes one ``.xplane.pb`` per traced window.  Its device
planes (``/device:TPU:<i>``) hold a line ``XLA Modules``, one event per
execution of a compiled program, and a line ``XLA Ops``, one event per
operation inside it; the host plane (``/host:CPU``) holds the
``jax.profiler.TraceAnnotation`` spans the benchmark opens around its calls
(``bench.*``).  All share one clock, in nanoseconds.

``read_xspace`` walks the file once and keeps only aggregates (interval
lists of programs, time per program and per op name), so a trace with
millions of op events costs memory by the number of distinct names.  The
rest are pure functions over intervals, tested on synthetic traces.
"""
from __future__ import annotations

import dataclasses
import glob
import os

DEVICE_PREFIX = "/device:TPU:"
MODULES_LINE = "XLA Modules"
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
ANNOTATION_PREFIX = "bench."
NAME_CHARS = 120


@dataclasses.dataclass
class DeviceTrace:
    """Aggregates of one device plane (times in ns on the trace's clock)."""

    name: str
    programs: list  # [(start, end, program name)]
    op_ns: dict  # op name -> summed duration


@dataclasses.dataclass
class TraceSummary:
    devices: list  # [DeviceTrace]
    annotations: list  # [(start, end, name)] bench.* spans on the host


def find_xspace(trace_dir: str) -> str:
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one .xplane.pb under {trace_dir}, "
                           f"found {len(paths)}")
    return paths[0]


def read_xspace(path: str) -> TraceSummary:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices, annotations = [], []
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            dev = DeviceTrace(plane.name, [], {})
            for line in plane.lines:
                if line.name == MODULES_LINE:
                    for ev in line.events:
                        dev.programs.append(
                            (ev.start_ns, ev.start_ns + ev.duration_ns,
                             ev.name))
                elif line.name == OPS_LINE:
                    ops = dev.op_ns
                    for ev in line.events:
                        ops[ev.name] = ops.get(ev.name, 0.0) + ev.duration_ns
            devices.append(dev)
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(ANNOTATION_PREFIX):
                        annotations.append(
                            (ev.start_ns, ev.start_ns + ev.duration_ns,
                             ev.name))
    return TraceSummary(devices, annotations)


def merge(intervals) -> list:
    """Union of ``(start, end, ...)`` intervals as sorted disjoint pairs."""
    out = []
    for s, e, *_ in sorted(intervals, key=lambda iv: iv[0]):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals, lo: float, hi: float) -> list:
    """Intervals cut to ``[lo, hi]``; those outside are dropped."""
    out = []
    for s, e, *rest in intervals:
        s, e = max(s, lo), min(e, hi)
        if e > s:
            out.append((s, e, *rest))
    return out


def busy_ns(intervals, lo: float, hi: float) -> float:
    """Length of the union of ``intervals`` inside ``[lo, hi]``."""
    return float(sum(e - s for s, e in merge(clip(intervals, lo, hi))))


def idle_gaps(intervals, lo: float, hi: float) -> list:
    """The ``(start, end)`` stretches of ``[lo, hi]`` no interval covers."""
    gaps, cur = [], lo
    for s, e in merge(clip(intervals, lo, hi)):
        if s > cur:
            gaps.append((cur, s))
        cur = max(cur, e)
    if hi > cur:
        gaps.append((cur, hi))
    return gaps


def attribute(gap, annotations) -> str:
    """What the host was doing in a gap: the innermost annotation that
    covers the gap's midpoint, or ``"host:outside"``."""
    mid = 0.5 * (gap[0] + gap[1])
    best = None
    for s, e, name in annotations:
        if s <= mid <= e and (best is None or e - s < best[1] - best[0]):
            best = (s, e, name)
    return best[2] if best else "host:outside"


def program_ns(intervals, lo: float, hi: float, match: str) -> float:
    """Device time inside ``[lo, hi]`` of programs whose name contains
    ``match`` (programs of one device never overlap)."""
    return float(sum(e - s for s, e, name in clip(intervals, lo, hi)
                     if match in name))


def window_of(annotations, name: str) -> tuple:
    """``(start, end)`` of the single annotation ``name``: the traced window."""
    found = [(s, e) for s, e, n in annotations if n == name]
    if len(found) != 1:
        raise RuntimeError(f"expected one {name!r} annotation in the trace, "
                           f"found {len(found)}")
    return found[0]


def breakdown(summary: TraceSummary, lo: float, hi: float, top: int = 10):
    """Top device ops (or programs, where no op line exists) by seconds,
    and the longest idle gaps by what the host was doing, summed over the
    device planes."""
    ops: dict = {}
    for dev in summary.devices:
        src = dev.op_ns or {}
        if not src:
            for s, e, name in clip(dev.programs, lo, hi):
                src[name] = src.get(name, 0.0) + (e - s)
        for name, ns in src.items():
            ops[name] = ops.get(name, 0.0) + ns
    top_ops = sorted(ops.items(), key=lambda kv: -kv[1])[:top]
    gaps = []
    for dev in summary.devices:
        for g in idle_gaps(dev.programs, lo, hi):
            gaps.append((g[1] - g[0], attribute(g, summary.annotations)))
    gaps.sort(key=lambda t: -t[0])
    return {
        # an XLA op's event name is its whole HLO line: keep its head
        "device_ops": [[name[:NAME_CHARS], ns * 1e-9] for name, ns in top_ops],
        "idle_gaps": [[name, ns * 1e-9] for ns, name in gaps[:top]],
    }
