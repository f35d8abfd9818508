"""Closed-loop re-plan sweeps: back-to-back ``CarbonPlanner.plan_batch``
calls, each on a fresh backlog of ``jobs_per_sweep`` jobs from the
configuration's law, anchored ``sweep_gap_h`` hours after the previous
one. Builds the planner in ``setup`` (set-up time), runs ``window`` (the
measured window), and hands the check its (job, plan) pairs."""
from __future__ import annotations

import time
from typing import Dict, List, Tuple

from bench import check, reference, system, traffic


class Loop:

    def __init__(self, config: dict, mix: dict, seed: int):
        self.config, self.mix, self.seed = config, mix, seed
        self.law = config["jobs"]
        self.backlog = traffic.law(self.law).backlog
        self.jobs: Dict[str, reference.Job] = {}

    def _sweep(self, k: int) -> List[reference.Job]:
        t0 = traffic.origin(self.seed) + k * self.mix["sweep_gap_h"] * 3600.0
        return self.backlog(self.law, self.mix["jobs_per_sweep"], t0,
                            tag=f"s{k}")

    def setup(self) -> None:
        from repro.core.scheduler.planner import CarbonPlanner
        system.install_topology(self.config)
        warm = self._sweep(-1)
        self.dep = reference.Deployment(
            self.config, *system.deployment_data(
                system.legs_of(self.config, warm)))
        self.planner = system.timed_planner(self.config, "bench.admit")
        # every sweep has the same shapes: one sweep compiles them all
        CarbonPlanner(system.ftns(self.config),
                      batch_backend=self.config["admission_tier"],
                      slot_s=self.config["slot_s"]).plan_batch(
            [system.transfer_job(j) for j in warm])

    def window(self, seconds: float) -> dict:
        t_end = time.perf_counter() + seconds
        t0 = time.perf_counter()
        k = 0
        while time.perf_counter() < t_end:
            jobs = self._sweep(k)
            self.jobs.update((j.uuid, j) for j in jobs)
            self.planner.plan_batch([system.transfer_job(j) for j in jobs])
            k += 1
        wall = time.perf_counter() - t0
        n = sum(len(s.jobs) for s in self.planner.sweeps)
        return {"wall_s": wall, "jobs_done": n,
                "end_to_end": {"replan_jobs_per_s": n / wall}}

    def pairs(self) -> List[Tuple[reference.Job, object]]:
        return [(self.jobs[j.uuid], p) for s in self.planner.sweeps
                for j, p in zip(s.jobs, s.plans)]

    def sampled(self) -> List[Tuple[reference.Job, object]]:
        return check.sample(self.pairs(), self.mix["sample"], self.seed)

    def numbers(self) -> Dict[str, float]:
        pairs = self.pairs()
        out = {"unplanned": float(check.unplanned(pairs)
                                  + abs(len(pairs) - len(self.jobs)))}
        out.update(check.plan_numbers(self.dep, self.sampled()))
        return out

    def sweep_work(self):
        """Per window sweep, the live pairs (hops, steps) and cells (leg
        hops, slots) of the admission grid, for the roofline counts."""
        for s in self.planner.sweeps:
            pairs: Dict[tuple, list] = {}
            cells = []
            for job in s.jobs:
                rj = self.jobs[job.uuid]
                for c in self.dep.cells(rj):
                    need = (len(c.starts) - 1) * int(
                        self.dep.slot_s // reference.DT_S) \
                        + reference.steps(c.dur_s)
                    hops = []
                    for leg in c.legs:
                        h = len(self.dep.routes[f"{leg[0]}>{leg[1]}"])
                        hops.append(h)
                        key = (rj.submitted_t, leg)
                        prev = pairs.get(key)
                        pairs[key] = [h, max(need, prev[1] if prev else 0)]
                    cells.append((tuple(hops), len(c.starts)))
            yield [tuple(v) for v in pairs.values()], cells
