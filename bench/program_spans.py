"""The program's own host spans in a traced window, for the per-layer
metrics that split the admission sweep and the fleet drain into phases.

The program opens them with ``repro.core.obs.span`` (a
``jax.profiler.TraceAnnotation``), on the same clock as the device's
ops: ``admit.*`` in ``CarbonPlanner.plan_batch`` and the kernel tiers,
``gw.*`` in the streaming gateway, ``fleet.*`` in the fleet controller.
:func:`load` reduces them to ``[name, start_ns, dur_ns]``, the plain form
of ``trace_reduce.load``. A trace that already holds them under
``"program"`` (a recorded fixture) is read as it is. A program without
such spans reads as none, and every reader then returns None.

Nesting follows the calls, so a parent's self time is its length less
its children's. The shards' re-plans run the same planner inside the
fleet's pumps (``fleet.replan``): admission phases count only the
``admit.*`` spans outside every ``fleet.pump``.
"""
from __future__ import annotations

import bisect
import glob
from typing import Dict, List, Optional, Sequence

from bench import harness, trace_reduce

PREFIXES = ("admit.", "gw.", "fleet.")
_loaded: Dict[str, list] = {}


def load(trace_dir) -> list:
    """The program's spans in the newest ``.xplane.pb`` under
    ``trace_dir``, sorted by start; each file is read once."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True))
    if not paths:
        return []
    if paths[-1] not in _loaded:
        evs = []
        for plane in ProfileData.from_file(paths[-1]).planes:
            if plane.name == "/host:CPU":
                for line in plane.lines:
                    evs.extend([e.name, e.start_ns, e.duration_ns]
                               for e in line.events
                               if e.name.startswith(PREFIXES))
        _loaded[paths[-1]] = sorted(evs, key=lambda e: e[1])
    return _loaded[paths[-1]]


def in_window(run) -> list:
    """The run's program spans that lie inside ``bench.window``."""
    if run.trace is None:
        return []
    evs = run.trace.get("program")
    if evs is None:
        evs = load(harness.OUT / "trace" / run.cell)
    lo, hi = trace_reduce.window(run.trace)
    return [e for e in evs if lo <= e[1] and e[1] + e[2] <= hi]


def admission(evs: Sequence[list]) -> List[list]:
    """The ``admit.*`` spans that lie outside every ``fleet.pump``."""
    fleet = trace_reduce.union(e for e in evs if e[0] == "fleet.pump")
    starts = [s for s, _ in fleet]
    out = []
    for e in evs:
        if e[0].startswith("admit."):
            i = bisect.bisect_right(starts, e[1]) - 1
            if i < 0 or e[1] >= fleet[i][1]:
                out.append(e)
    return out


def per_sweep_ms(run, names: Sequence[str], *,
                 less_device: bool = False) -> Optional[float]:
    """Host ms per admission sweep in the spans named ``names``: their
    summed length (less the device-busy time inside them with
    ``less_device``) over the window's admission sweeps (``admit.sweep``);
    None where no sweep took that phase."""
    evs = admission(in_window(run))
    sweeps = sum(e[0] == "admit.sweep" for e in evs)
    phase = [e for e in evs if e[0] in names]
    if not sweeps or not phase:
        return None
    ns = sum(d for _, _, d in phase)
    if less_device:
        ns -= sum(trace_reduce.busy(run.trace, s, s + d) for _, s, d in phase)
    return ns / sweeps / 1e6


def per_job_ms(run, names: Sequence[str]) -> Optional[float]:
    """Host ms per completed job in the spans named ``names``, each
    counted whole (its children included)."""
    phase = [e for e in in_window(run) if e[0] in names]
    if not phase or not run.jobs_done:
        return None
    return sum(d for _, _, d in phase) / run.jobs_done / 1e6
