"""From a profiler trace to numbers: device busy time, per-kernel time,
and idle gaps named by the benchmark's own host spans.

A trace is reduced to plain events first (:func:`load`), so the
arithmetic here runs the same on a recorded fixture as on a fresh trace:

* ``device`` -- ``[name, start_ns, dur_ns]`` of every op on the "XLA Ops"
  line of each ``/device:TPU:<n>`` plane, per device;
* ``host`` -- ``[name, start_ns, dur_ns]`` of every ``bench.*`` span the
  benchmark opened with ``jax.profiler.TraceAnnotation``.

Host and device events share the trace's clock.
"""
from __future__ import annotations

import glob
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Interval = Tuple[float, float]
SPAN_PREFIX = "bench."
NAME_CHARS = 300                       # an op's name is its HLO text


def load(trace_dir: str) -> dict:
    """The newest ``.xplane.pb`` under ``trace_dir`` as plain events."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = ProfileData.from_file(paths[-1])
    device: Dict[str, list] = {}
    host: list = []
    for plane in data.planes:
        if re.fullmatch(r"/device:TPU:\d+", plane.name):
            evs = device.setdefault(plane.name, [])
            for line in plane.lines:
                if line.name == "XLA Ops":
                    evs.extend([e.name[:NAME_CHARS], e.start_ns,
                                e.duration_ns] for e in line.events)
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                host.extend([e.name, e.start_ns, e.duration_ns]
                            for e in line.events
                            if e.name.startswith(SPAN_PREFIX))
    return {"device": device, "host": host}


def union(events: Iterable[Sequence]) -> List[Interval]:
    """Merged [start, end) intervals of ``[name, start, dur]`` events."""
    out: List[List[float]] = []
    for s, e in sorted((ev[1], ev[1] + ev[2]) for ev in events):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def covered(intervals: Sequence[Interval], lo: float, hi: float) -> float:
    """Length of ``intervals`` inside [lo, hi)."""
    return sum(max(0.0, min(e, hi) - max(s, lo)) for s, e in intervals)


def gaps(intervals: Sequence[Interval], lo: float, hi: float
         ) -> List[Interval]:
    """The parts of [lo, hi) that no interval covers."""
    out, t = [], lo
    for s, e in intervals:
        if e <= lo or s >= hi:
            continue
        if s > t:
            out.append((t, min(s, hi)))
        t = max(t, e)
    if t < hi:
        out.append((t, hi))
    return out


def spans(trace: dict, name: str) -> List[Interval]:
    return sorted((s, s + d) for n, s, d in trace["host"] if n == name)


def innermost(trace: dict, t: float, exclude: Sequence[str] = ()
              ) -> Optional[str]:
    """The shortest ``bench.*`` span open at ``t``."""
    best = None
    for n, s, d in trace["host"]:
        if n not in exclude and s <= t < s + d \
                and (best is None or d < best[1]):
            best = (n, d)
    return best[0] if best else None


def window(trace: dict, name: str = "bench.window") -> Interval:
    (w,) = spans(trace, name)
    return w


def busy(trace: dict, lo: float, hi: float) -> float:
    """Device-busy nanoseconds in [lo, hi), averaged over the devices."""
    devs = list(trace["device"].values())
    if not devs:                       # a CPU rehearsal has no device plane
        return 0.0
    return sum(covered(union(evs), lo, hi) for evs in devs) / len(devs)


def admit_host_ms(trace: dict, span: str = "bench.admit"
                  ) -> Optional[float]:
    """Mean over the ``span`` spans of their length less the device-busy
    time inside them, in ms; None without such spans."""
    sp = spans(trace, span)
    if not sp:
        return None
    return sum((e - s) - busy(trace, s, e) for s, e in sp) / len(sp) / 1e6


def kernel_ns(trace: dict, pattern: str, lo: float, hi: float
              ) -> Tuple[float, int]:
    """Summed device durations and count of the ops whose name matches
    ``pattern`` and that start in [lo, hi), averaged over the devices."""
    rx = re.compile(pattern)
    tot, n = 0.0, 0
    for evs in trace["device"].values():
        for name, s, d in evs:
            if lo <= s < hi and rx.search(name):
                tot += d
                n += 1
    k = max(len(trace["device"]), 1)
    return tot / k, n // k


def op_label(name: str, kernels: Dict[str, str]) -> str:
    for label, pattern in kernels.items():
        if re.search(pattern, name):
            return f"{label} kernel"
    m = re.match(r"%([A-Za-z_\-]+)", name)
    return m.group(1) if m else name[:40]


def breakdown(trace: dict, kernels: Dict[str, str], top: int = 10) -> dict:
    """The device ops that took most time in the window, by label, and
    the longest idle gaps, each named by the innermost benchmark span
    open in the middle of it."""
    lo, hi = window(trace)
    ops: Dict[str, float] = {}
    for evs in trace["device"].values():
        for name, s, d in evs:
            if lo <= s < hi:
                lab = op_label(name, kernels)
                ops[lab] = ops.get(lab, 0.0) + d / 1e9
    k = max(len(trace["device"]), 1)
    idle = []
    for evs in trace["device"].values():
        for s, e in gaps(union(evs), lo, hi):
            idle.append([innermost(trace, (s + e) / 2) or "outside spans",
                         (e - s) / 1e9])
    idle.sort(key=lambda x: -x[1])
    return {"device_ops": sorted(([n, v / k] for n, v in ops.items()),
                                 key=lambda x: -x[1])[:top],
            "idle_gaps": idle[:top]}
