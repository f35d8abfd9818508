"""The benchmark's only contact with the program: building the system
under test from a configuration, timing its admission sweeps, and reading
the deployment's data (routes, link capacities, zone parameters) for the
plain reference. Nothing here computes a result the check compares."""
from __future__ import annotations

import dataclasses
import time
from typing import Dict, Iterable, List, Sequence, Tuple

from bench.reference import Job, UC_TACC_ZONES


def compile_counter():
    """A listener on JAX's compile events and a reader of the running
    (backend compiles, backend compile seconds, persistent-cache hits).
    Copied from ``chip_smoke.py``."""
    import jax

    total = [0, 0.0, 0]

    def on_duration(event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            total[0] += 1
            total[1] += secs
        elif event == "/jax/compilation_cache/cache_retrieval_time_sec":
            total[2] += 1

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    return lambda: tuple(total)


def same_cell(a, b) -> bool:
    """Copied from ``chip_smoke.py``."""
    return (a.start_t, a.source, a.ftn, a.feasible) == \
        (b.start_t, b.source, b.ftn, b.feasible)


def rel_err(a: float, b: float) -> float:
    """Copied from ``chip_smoke.py``."""
    return abs(a - b) / max(abs(b), 1e-12)


def install_topology(config: dict) -> None:
    kind = config["topology"]
    if kind.startswith("lattice:"):
        from repro.core.carbon.lattice import default_lattice
        default_lattice(int(kind.split(":", 1)[1]))
    elif kind != "paper":
        raise ValueError(f"unknown topology {kind!r}")


def ftns(config: dict):
    from repro.core.scheduler.overlay import FTN
    return [FTN(f["name"], f["profile"], f["max_gbps"])
            for f in config["ftns"]]


def transfer_job(job: Job):
    from repro.core.scheduler.planner import SLA, TransferJob
    return TransferJob(uuid=job.uuid, size_bytes=job.size_bytes,
                       replicas=job.replicas, dst=job.dst,
                       sla=SLA(deadline_s=job.deadline_s,
                               carbon_budget_g=job.budget_g,
                               w_carbon=job.w_carbon, w_perf=job.w_perf),
                       submitted_t=job.submitted_t,
                       parallelism=job.parallelism,
                       concurrency=job.concurrency)


@dataclasses.dataclass
class Sweep:
    """One admission sweep: its wall-clock span, its jobs and plans, and
    whether it ran on the device tier."""
    t0: float
    t1: float
    jobs: list
    plans: list
    device: bool

    @property
    def wall_s(self) -> float:
        return self.t1 - self.t0


def timed_planner(config: dict, span: str, **kw):
    """A ``CarbonPlanner`` whose ``plan_batch`` records each sweep's wall
    time, jobs and plans, inside a profiler annotation named ``span``."""
    import jax
    from repro.core.scheduler.planner import CarbonPlanner

    class TimedPlanner(CarbonPlanner):
        def plan_batch(self, jobs, previous=None, drift_tol=None):
            n0 = self.device_sweeps
            with jax.profiler.TraceAnnotation(span):
                t0 = time.perf_counter()
                plans = super().plan_batch(jobs, previous, drift_tol)
                t1 = time.perf_counter()
            self.sweeps.append(Sweep(t0, t1, list(jobs), list(plans),
                                     self.device_sweeps > n0))
            return plans

    pl = TimedPlanner(ftns(config), batch_backend=config["admission_tier"],
                      slot_s=config["slot_s"], **kw)
    pl.sweeps = []
    return pl


def deployment_data(pairs: Iterable[Tuple[str, str]]
                    ) -> Tuple[Dict[str, list], Dict[str, float],
                               Dict[str, list]]:
    """Routes, link capacities and zone parameters of the deployment, as
    plain data for the reference: read from the program's topology and
    region registries, which are this deployment's inputs."""
    from repro.core.carbon.intensity import REGIONS
    from repro.core.carbon.path import discover_path
    from repro.core.transfer.throughput import base_capacity

    routes, capacity, zones = {}, {}, {}
    for a, b in set(pairs):
        p = discover_path(a, b)
        routes[f"{a}>{b}"] = [(h.ip, h.zone, h.info.org) for h in p.hops]
        capacity[f"{a}>{b}"] = float(base_capacity(a, b))
    for z in {h[1] for hops in routes.values() for h in hops} \
            | set(UC_TACC_ZONES):
        r = REGIONS[z]
        zones[z] = [r.base_ci, r.diurnal_amp, r.solar_dip, r.noise,
                    r.peak_hour]
    return routes, capacity, zones


def legs_of(config: dict, jobs: Sequence[Job]) -> List[Tuple[str, str]]:
    """Every (src, dst) leg the jobs' grids use."""
    out = set()
    for job in jobs:
        for f in config["ftns"]:
            for src in job.replicas:
                out.add((src, f["name"]))
            if f["name"] != job.dst:
                out.add((f["name"], job.dst))
    return sorted(out)
