"""A served arrival stream: the configuration's law, replayed in sim time
as fast as the host goes, through ``StreamingGateway`` over a
``ShardedFleet``. The gateway admits on a timed ``CarbonPlanner``; the
stream is cut at a micro-batch boundary once ``--seconds`` are up, and
the run drains every admitted job.

The mix's ``gateway`` entry holds the gateway's keyword arguments
(``window_s``, ``pipeline``, ``max_batch``, ``backfill``,
``max_inflight``, ``checkpoint_every_s``, ...), and its ``fleet`` entry,
if any, overrides the configuration's ``fleet`` keywords of
``ShardedFleet`` (``parallel``, ``shard_backend``, ...). A planner
subclass is not cloneable, so with ``pipeline`` on the gateway plans
each batch inline at its close."""
from __future__ import annotations

import math
import time
from typing import Dict, List, Tuple

from bench import check, reference, system, traffic


class Loop:

    def __init__(self, config: dict, mix: dict, seed: int):
        self.config, self.mix, self.seed = config, mix, seed
        self.gw_kw = dict(mix["gateway"])
        self.fleet_kw = {**config["fleet"], **mix.get("fleet", {})}

    def _signature(self, idx: range) -> tuple:
        """What fixes the device sweep's compiled shapes, at a finer grain
        than the program's buckets of 64 cells and 64 (anchor, path)
        pairs, so one warm-up sweep per signature covers the window."""
        cells, pairs, hops = 0, set(), 0
        for i in idx:
            job = self.jobs[i]
            for f in self.config["ftns"]:
                for src in job.replicas:
                    cells += 1
                    legs = [(src, f["name"])]
                    if f["name"] != job.dst:
                        legs.append((f["name"], job.dst))
                    for leg in legs:
                        pairs.add((job.submitted_t, leg))
                        hops = max(hops, len(
                            self.dep.routes[f"{leg[0]}>{leg[1]}"]))
        return -(-cells // 64), -(-len(pairs) // 64), hops

    def setup(self) -> None:
        from repro.core.controlplane import ShardedFleet, StreamingGateway
        from repro.core.scheduler.planner import CarbonPlanner
        system.install_topology(self.config)
        self.fleet = ShardedFleet(system.ftns(self.config),
                                  batch_backend=self.config["admission_tier"],
                                  **self.fleet_kw)
        self.planner = system.timed_planner(self.config, "bench.admit",
                                            field=self.fleet.field)
        self.planner.emission_scale_fn = self.fleet.planner.emission_scale_fn
        self.gw = StreamingGateway(self.fleet, planner=self.planner,
                                   **self.gw_kw)
        self.jobs = traffic.stream(self.config["jobs"], self.seed,
                                   self.mix["horizon_h"],
                                   self.mix["max_horizons"])
        self.tjobs = [system.transfer_job(j) for j in self.jobs]
        self.batches = traffic.batches(self.jobs, self.gw.window_s,
                                       self.gw.max_batch)
        self.dep = reference.Deployment(
            self.config, *system.deployment_data(
                system.legs_of(self.config, self.jobs)))
        # warm each compiled shape the stream's sweeps take, on a planner
        # of its own so the gateway's counters start at zero
        warm = CarbonPlanner(system.ftns(self.config),
                             field=self.fleet.field,
                             batch_backend=self.config["admission_tier"],
                             slot_s=self.config["slot_s"])
        seen = set()
        for b in self.batches:
            sig = self._signature(b)
            if sig not in seen:
                seen.add(sig)
                warm.plan_batch([self.tjobs[i] for i in b])
        self.n_warm = len(seen)

    def window(self, seconds: float) -> dict:
        import jax
        starts = {b.start for b in self.batches}
        phase: list = []               # the open host span: stream, drain
        pulled = []
        t_first: List[float] = []

        def enter(name):
            if phase:
                phase.pop().__exit__(None, None, None)
            phase.append(jax.profiler.TraceAnnotation(name))
            phase[0].__enter__()

        def stream():
            t_end = None
            for i, job in enumerate(self.tjobs):
                if t_end is None:
                    t_first.append(time.perf_counter())
                    t_end = t_first[0] + seconds
                    enter("bench.stream")
                elif i in starts and time.perf_counter() >= t_end:
                    break
                pulled.append(i)
                yield job
            enter("bench.drain")

        try:
            rep = self.gw.run(stream())
        finally:
            if phase:
                phase.pop().__exit__(None, None, None)
        wall = time.perf_counter() - t_first[0]
        self.rep, self.stats = rep, self.gw.stats()
        self.pulled = pulled
        walls = sorted(s.wall_s for s in self.planner.sweeps
                       for _ in s.jobs)
        p95 = walls[math.ceil(0.95 * len(walls)) - 1]   # nearest rank
        return {"wall_s": wall, "jobs_done": rep.n_completed,
                "end_to_end": {"served_jobs_per_s": rep.n_completed / wall,
                               "admit_p95_ms": 1000.0 * p95}}

    def pairs(self) -> List[Tuple[reference.Job, object]]:
        plans = {j.uuid: p for s in self.planner.sweeps
                 for j, p in zip(s.jobs, s.plans)}
        return [(self.jobs[i], plans.get(self.jobs[i].uuid))
                for i in self.pulled]

    def sampled(self) -> List[Tuple[reference.Job, object]]:
        """A sample drawn from the seed, with the largest transfer in it."""
        planned = [(j, p) for j, p in self.pairs() if p is not None]
        largest = max(range(len(planned)),
                      key=lambda i: planned[i][0].size_bytes)
        return check.sample(planned, self.mix["sample"], self.seed,
                            must=[largest])

    def numbers(self) -> Dict[str, float]:
        pairs = self.pairs()
        out = {"unplanned": float(check.unplanned(pairs)),
               "incomplete": float(len(pairs) - self.rep.n_completed
                                   + abs(self.rep.n_jobs - len(pairs))),
               "audit": system.rel_err(self.rep.ledger_total_g,
                                       self.rep.total_actual_g)}
        out.update(check.plan_numbers(self.dep, self.sampled()))
        return out
