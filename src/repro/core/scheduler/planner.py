"""The joint planner: time × space × overlay under an SLA [paper §5].

Searches the (start slot, source replica, FTN) grid, predicting duration
from the throughput model and emissions from the [14] power models, and
minimizes a QoS-weighted objective:

    cost = w_carbon · gCO₂(plan) + w_perf · (finish − submit) / deadline

subject to: finish before the deadline; optional carbon budget. This is the
"SLA" §5 proposes: the user picks the carbon/performance trade-off.

``plan()`` scores the whole grid with array ops on the shared
:class:`CarbonField` — every (FTN, source) leg evaluates all start slots
from one prefix-sum emission pass. ``plan_reference()`` keeps the scalar
nested-loop implementation as the oracle the equivalence tests compare
against; ``plan_batch()`` amortizes the field/path caches over a fleet of
jobs.
"""
from __future__ import annotations

import dataclasses
import math
import time
from typing import Callable, Iterator, List, Optional, Sequence, Tuple

import numpy as np

from repro.core.carbon.energy import HOST_PROFILES, host_profile_for_endpoint
from repro.core.carbon.field import (CarbonField, default_field,
                                     device_weights)
from repro.core.carbon.path import NetworkPath, discover_path
from repro.core.carbon.score import (carbonscore, transfer_emissions_g,
                                     transfer_emissions_g_reference)
from repro.core.obs.host import span
from repro.core.obs.metrics import log_bounds
from repro.core.scheduler.overlay import FTN
from repro.core.scheduler.time_shift import expected_transfer_ci
from repro.core.transfer.throughput import ThroughputModel

# plan_batch wall-time histogram bounds: 10 µs .. 100 s (fixed so every
# shard's buckets merge exactly)
_WALL_BOUNDS = log_bounds(1e-5, 1e2, per_decade=2)


def check_batch_backend(name) -> None:
    """Raise ``ValueError`` unless ``name`` is a tier of ``plan_batch``:
    "numpy" (the pinned oracle) or "pallas" (the fused kernels of
    ``grid_pallas``, the one device tier)."""
    if name not in ("numpy", "pallas"):
        raise ValueError(f"batch_backend must be 'numpy' or 'pallas', got "
                         f"{name!r}")


@dataclasses.dataclass(frozen=True)
class SLA:
    deadline_s: float                  # relative to submission
    carbon_budget_g: Optional[float] = None
    w_carbon: float = 1.0
    w_perf: float = 0.0                # 0 = pure carbon minimization


@dataclasses.dataclass(frozen=True)
class TransferJob:
    uuid: str
    size_bytes: float
    replicas: Tuple[str, ...]          # candidate sources (space shifting)
    dst: str                           # final destination endpoint
    sla: SLA
    submitted_t: float
    parallelism: int = 4
    concurrency: int = 2
    pipelining: int = 4


@dataclasses.dataclass(frozen=True)
class Plan:
    job_uuid: str
    start_t: float
    source: str
    ftn: str
    path: NetworkPath
    predicted_gbps: float
    predicted_duration_s: float
    predicted_emissions_g: float
    predicted_avg_ci: float
    predicted_carbonscore: float
    cost: float
    feasible: bool
    alternatives: int = 0
    # counterfactual anchor for the attribution rollups (core.obs): the
    # emissions of the greedy-now baseline — dispatch immediately on the
    # fastest (FTN, replica) cell, no time/space deliberation. Captured
    # only under observability (None otherwise — NaN would break the
    # Plan equality the replay tests pin).
    greedy_g: Optional[float] = None


def _plan_cost(sla: SLA, emissions_g: float, finish_rel_s) -> float:
    """The SLA objective: w_carbon·emissions + w_perf·normalized duration.

    The perf term is the job's wall-clock span normalized by the deadline —
    it must NOT rescale with emissions (the seed multiplied the two, so
    w_perf silently grew with job size). Accepts scalars or arrays.
    """
    slack = max(sla.deadline_s, 1.0)
    return sla.w_carbon * emissions_g + sla.w_perf * finish_rel_s / slack


def _steps(dur: np.ndarray, dt_s: float) -> Tuple[np.ndarray, np.ndarray]:
    """(n_steps, rem_s) of transfers lasting ``dur``: whole ``dt_s`` steps,
    at least one, and the pro-rated seconds of the last."""
    n_steps = np.maximum(np.ceil(dur / dt_s - 1e-12), 1).astype(np.int64)
    return n_steps, dur - (n_steps - 1) * dt_s


def _n_valid(sub: np.ndarray, slot_s: float, n_slots: np.ndarray,
             dur: np.ndarray, deadline_t: np.ndarray) -> np.ndarray:
    """Per cell, the count of its slots ``sub + slot_s * k`` (k below
    ``n_slots``) that finish by the deadline — ``np.sum(ts + dur <=
    deadline_t + 1e-9)`` of :meth:`CarbonPlanner.plan`. ``n_slots`` comes
    from the same bound, so every slot but the last ends a whole slot
    (at least one ``dt_s``) before it, far beyond float rounding: the last
    slot alone decides the count."""
    last = (sub + slot_s * (n_slots - 1).astype(np.float64)) + dur \
        <= deadline_t + 1e-9
    return (n_slots - 1 + last).astype(np.float64)


def _first_min(cost: np.ndarray, lo: np.ndarray, hi: np.ndarray
               ) -> np.ndarray:
    """Per job, the first of its cells ``lo..hi`` with the least finite
    cost, or -1 where none is finite."""
    win = np.full(len(lo), -1, dtype=np.int64)
    ok = np.isfinite(cost)
    has = hi > lo
    if not ok.any() or not has.any():
        return win
    c = np.where(ok, cost, np.inf)
    job_of = np.repeat(np.arange(len(lo)), hi - lo)
    jmin = np.full(len(lo), np.inf)
    jmin[has] = np.minimum.reduceat(c, lo[has])
    at = np.flatnonzero(ok & (c == jmin[job_of]))
    j, first = np.unique(job_of[at], return_index=True)
    win[j] = at[first]
    return win


@dataclasses.dataclass
class _SweepCells:
    """The planner's columns beside a sweep's ``CellTable``."""
    lo: np.ndarray                     # (J,) first cell of each job
    hi: np.ndarray                     # (J,) one past its last cell
    fallback: np.ndarray               # (J,) grid past the cap: plan()
    n_alt: np.ndarray                  # (J,) starts over all its candidates
    sub: np.ndarray                    # (J,) submission times
    size: np.ndarray                   # (J,) bytes
    dur: np.ndarray                    # (C,) transfer seconds
    cand: np.ndarray                   # (C,) row of ``cands``
    cands: List[Tuple]                 # (ftn, src, paths, gbps)
    groups: int                        # distinct device-weight closures


@dataclasses.dataclass
class _Winners:
    """A sweep's winning cells, one row per job that has one, in job
    order."""
    job: np.ndarray                    # (W,) index into the sweep's jobs
    cost: np.ndarray                   # (W,) the cell's least cost
    emis: np.ndarray                   # (W,) its emissions at that slot
    start_t: np.ndarray                # (W,) that slot's start
    dur: np.ndarray                    # (W,) transfer seconds
    size: np.ndarray                   # (W,) bytes
    n_alt: np.ndarray                  # (W,) the job's alternatives
    cand: np.ndarray                   # (W,) row of the sweep's ``cands``
    legs: np.ndarray                   # (W, 2) path ids, -1 past the last


class _CellColumns:
    """Builds a ``CellTable`` from a sweep's candidates. Each distinct
    (FTN, replica, dst, parallelism, concurrency) candidate is resolved
    once — legs, memoized paths, ``throughput.predict`` per (a, b, par,
    con) — and each distinct device-weight closure (path, receiver,
    parallelism, concurrency) is looked up once; ``groups`` counts
    those the table's cells use."""

    def __init__(self, planner: "CarbonPlanner"):
        self._pl = planner
        self._cand: dict = {}          # candidate key -> row of ``cands``
        self._rate: dict = {}          # (a, b, par, con) -> gbps
        self._pid: dict = {}           # id(path) -> path id
        self._wkey: dict = {}          # closure key -> closure id
        self._wfn: List[Tuple] = []    # closure id -> (path, recv, par, con)
        self.paths: List[NetworkPath] = []
        self.cands: List[Tuple] = []   # (ftn, src, paths, gbps)
        self._legs: List[Tuple[int, int]] = []     # path ids per candidate
        self._w: List[Tuple[int, int]] = []        # closure ids per candidate
        self.groups = 0                # closures the table's cells use

    def candidate(self, ftn: FTN, src: str, dst: str, par: int, con: int
                  ) -> int:
        """The row of one (FTN, replica) cell's candidate in ``cands``:
        its paths and predicted gbps as :meth:`CarbonPlanner._candidates`
        computes them."""
        key = (ftn.name, src, dst, par, con)
        row = self._cand.get(key)
        if row is not None:
            return row
        legs = [(src, ftn.name)]
        if ftn.name != dst:
            legs.append((ftn.name, dst))
        gbps = min(self._predict(a, b, par, con) for a, b in legs)
        gbps = min(gbps, ftn.max_gbps)
        paths = [discover_path(a, b) for a, b in legs]
        pids, wids = [-1, -1], [-1, -1]
        for li, p in enumerate(paths):
            pid = self._pid.get(id(p))
            if pid is None:
                pid = self._pid[id(p)] = len(self.paths)
                self.paths.append(p)
            wk = (pid, ftn.power_model.name, par, con)
            wid = self._wkey.get(wk)
            if wid is None:
                wid = self._wkey[wk] = len(self._wfn)
                self._wfn.append((p, ftn.power_model, par, con))
            pids[li], wids[li] = pid, wid
        row = self._cand[key] = len(self.cands)
        self.cands.append((ftn, src, paths, gbps))
        self._legs.append(tuple(pids))
        self._w.append(tuple(wids))
        return row

    def _predict(self, a: str, b: str, par: int, con: int) -> float:
        key = (a, b, par, con)
        g = self._rate.get(key)
        if g is None:
            g = self._rate[key] = self._pl.throughput.predict(a, b, par, con)
        return g

    def gbps(self) -> np.ndarray:
        """(candidates,) predicted gbps."""
        return np.array([c[3] for c in self.cands], dtype=np.float64)

    def table(self, cand: np.ndarray, *, anchor: np.ndarray,
              n_slots: np.ndarray, n_steps: np.ndarray,
              rem_s: np.ndarray) -> "CellTable":
        """The table of the cells of candidates ``cand``, in that order.
        A cell's device weights depend on its candidate alone: they
        evaluate once per (candidate, leg), in one pass over the stacked
        coefficients of the distinct closures those use."""
        from repro.core.scheduler.grid_jax import CellTable
        sender = HOST_PROFILES["storage_frontend"]
        legs = np.array(self._legs, dtype=np.int32).reshape(-1, 2)
        wid = np.array(self._w, dtype=np.int64).reshape(-1, 2)
        h = max((p.n_hops for p in self.paths), default=1)
        live = np.zeros(wid.shape, dtype=bool)
        live[np.unique(cand)] = True
        live &= wid >= 0
        used, row = np.unique(wid[live], return_inverse=True)
        self.groups = len(used)
        coef = [np.zeros((len(used), h)) for _ in range(4)] \
            + [np.ones((len(used), h)), np.zeros((len(used), 1))]
        for k, w in enumerate(used):
            p, recv, par, con = self._wfn[w]
            cs = self._pl.field.device_weight_coeffs(p, sender, recv, par,
                                                     con)
            for x, c in zip(coef[:5], cs[:5]):
                x[k, :p.n_hops] = c
            coef[5][k] = cs[5]
        w_cand = np.zeros(wid.shape + (h,))
        w_cand[live] = device_weights(
            tuple(x[row] for x in coef),
            np.broadcast_to(self.gbps()[:, None], wid.shape)[live][:, None])
        return CellTable(tuple(self.paths), legs[cand], anchor, n_slots,
                         n_steps, rem_s, w_cand[cand])


class CarbonPlanner:
    def __init__(self, ftns: Sequence[FTN],
                 throughput: Optional[ThroughputModel] = None,
                 slot_s: float = 3600.0,
                 ci_fn: Optional[Callable[[NetworkPath, float], float]] = None,
                 field: Optional[CarbonField] = None,
                 batch_backend: str = "numpy"):
        check_batch_backend(batch_backend)
        self.ftns = list(ftns)
        self._ftn_by_name = {f.name: f for f in self.ftns}
        self.throughput = throughput or ThroughputModel()
        self.slot_s = slot_s
        self.ci_fn = ci_fn             # forecast hook; None = oracle trace
        self.field = field or default_field()
        # batch_backend governs plan_batch's sweeps only: "pallas" scores
        # whole fleets (and large re-score sweeps) in the fused
        # grid_pallas kernels, while single plan()/rescore() calls stay on
        # numpy (small arrays beat kernel dispatch there). A device tier
        # that cannot run is an error, here or when its kernel fails to
        # lower: the planner never swaps one tier for another behind the
        # caller's back.
        if batch_backend == "pallas":
            from repro.core.scheduler.grid_jax import HAVE_JAX
            if not HAVE_JAX:
                raise ImportError(
                    "batch_backend='pallas' needs jax; install it or use "
                    "batch_backend='numpy'")
        self.batch_backend = batch_backend
        # drift hook (the fleet controller's forecast-shock nowcast): a
        # (path, start_times) -> multiplier-array applied to the forecast
        # emission integral, so re-plans during measured CI drift can
        # route around it instead of re-deriving the same shocked plan
        self.emission_scale_fn: Optional[
            Callable[[NetworkPath, np.ndarray], np.ndarray]] = None
        # observability (core.obs): with capture_greedy on, every Plan
        # carries the greedy-now counterfactual; _metrics is the owning
        # observer's registry for plan_batch timing — both plain data,
        # so they pickle with the planner (registry identity with the
        # controller's observer survives via the pickle memo)
        self.capture_greedy = False
        self._metrics = None

    def observe_with(self, obs) -> None:
        """Attach a :class:`~repro.core.obs.observer.FleetObserver`:
        turns on greedy-now capture and routes plan_batch timing /
        cell counts into its metrics registry."""
        self.capture_greedy = True
        self._metrics = obs.registry

    def __getstate__(self) -> dict:
        """Pickle support for checkpointing: ``emission_scale_fn`` is the
        owning controller's bound hook — the controller re-wires it in its
        own ``__setstate__``, so a planner never drags a stale owner
        through a checkpoint."""
        d = self.__dict__.copy()
        d["emission_scale_fn"] = None
        return d

    def __setstate__(self, d: dict) -> None:
        # a planner pickled on a tier that no longer exists raises rather
        # than plan on another one
        check_batch_backend(d.get("batch_backend"))
        self.__dict__.update(d)

    def _leg_emissions(self, path: NetworkPath, receiver, job: TransferJob,
                       ts: np.ndarray, gbps: float) -> np.ndarray:
        """Emission integral for one leg over all candidate starts — the
        grid-scoring hot path, on the numpy field."""
        emis = self.field.transfer_emissions_g(
            path, HOST_PROFILES["storage_frontend"], receiver,
            job.size_bytes, ts, gbps,
            parallelism=job.parallelism, concurrency=job.concurrency)
        if self.emission_scale_fn is not None:
            emis = emis * self.emission_scale_fn(path, np.atleast_1d(ts))
        return emis

    def _ci(self, path: NetworkPath, t0: float, dur: float) -> float:
        if self.ci_fn is not None:
            return self.ci_fn(path, t0)
        return expected_transfer_ci(path, t0, dur)

    def _ci_vec(self, path: NetworkPath, t0s: np.ndarray, dur: float
                ) -> np.ndarray:
        if self.ci_fn is not None:
            return np.array([self.ci_fn(path, float(t)) for t in t0s])
        return self.field.expected_transfer_ci(path, t0s, dur)

    def _resolve_greedy(self, job: TransferJob,
                        captured: Optional[float]) -> Optional[float]:
        """The greedy-now counterfactual for a finished plan: the slot-0
        emission of the fastest cell, read off the already-scored grid
        (``captured``, free) when the scan produced one, else one
        fallback integral (fused/pallas grids never materialize slot
        values; infeasible fallbacks never scanned)."""
        if not self.capture_greedy:
            return None
        return captured if captured is not None \
            else self._greedy_now_g(job)

    def _greedy_now_g(self, job: TransferJob) -> Optional[float]:
        """The counterfactual baseline: start *now* (slot 0) on the
        fastest (FTN, replica) cell — what a carbon-blind dispatcher
        would do. Fallback path only (see :meth:`_resolve_greedy`): one
        single-slot emission integral on the numpy oracle path."""
        best = None                    # (dur, ftn, legs, gbps)
        for ftn, src, legs, gbps, dur in self._candidates(job):
            if gbps <= 0:
                continue
            if best is None or dur < best[0]:
                best = (dur, ftn, legs, gbps)
        if best is None:
            return None
        dur, ftn, legs, gbps = best
        ts = np.array([job.submitted_t])
        g = 0.0
        for (a, b) in legs:
            p = discover_path(a, b)
            emis = self.field.transfer_emissions_g(
                p, HOST_PROFILES["storage_frontend"], ftn.power_model,
                job.size_bytes, ts, gbps,
                parallelism=job.parallelism, concurrency=job.concurrency)
            if self.emission_scale_fn is not None:
                emis = emis * self.emission_scale_fn(p, ts)
            g += float(np.asarray(emis).reshape(-1)[0])
        return g

    def _candidates(self, job: TransferJob
                    ) -> Iterator[Tuple[FTN, str, List[Tuple[str, str]],
                                        float, float]]:
        """(ftn, source, legs, predicted_gbps, predicted_duration) for every
        (FTN × replica) cell of the grid — shared by plan()/plan_reference()
        so both scan the identical candidate set in the identical order."""
        for ftn in self.ftns:
            # an FTN relays source → ftn → dst; a direct transfer is the
            # degenerate FTN co-located with dst.
            for src in job.replicas:
                legs = [(src, ftn.name)]
                if ftn.name != job.dst:
                    legs.append((ftn.name, job.dst))
                gbps = min(self.throughput.predict(a, b, job.parallelism,
                                                   job.concurrency)
                           for a, b in legs)
                gbps = min(gbps, ftn.max_gbps)
                dur = job.size_bytes * 8.0 / (gbps * 1e9)
                yield ftn, src, legs, gbps, dur

    def _slot_starts(self, job: TransferJob, dur: float,
                     deadline_t: float) -> np.ndarray:
        """Candidate start times: every slot that finishes by the deadline,
        or just the immediate start when none fits (SLA-first)."""
        latest = deadline_t - dur
        n = 1
        if latest + 1e-9 >= job.submitted_t:
            n = int((latest + 1e-9 - job.submitted_t) // self.slot_s) + 1
        return job.submitted_t + self.slot_s * np.arange(n)

    # --- vectorized fast path ---------------------------------------------
    def plan(self, job: TransferJob) -> Plan:
        deadline_t = job.submitted_t + job.sla.deadline_s
        best: Optional[Tuple] = None   # (cost, emis, t, ftn, src, paths,
        n_alt = 0                      #  gbps, dur)
        g0: Optional[Tuple] = None     # (dur, emis[0]): greedy-now capture
        for ftn, src, legs, gbps, dur in self._candidates(job):
            ts = self._slot_starts(job, dur, deadline_t)
            emis = np.zeros(ts.shape)
            paths = [discover_path(a, b) for (a, b) in legs]
            for p in paths:
                emis += self._leg_emissions(p, ftn.power_model, job, ts, gbps)
            # ts[0] is always the submission instant, so the scan already
            # scored the carbon-blind start-now cell — keep the fastest
            if self.capture_greedy and gbps > 0 \
                    and (g0 is None or dur < g0[0]):
                g0 = (dur, float(emis[0]))
            feasible = ts + dur <= deadline_t + 1e-9
            if job.sla.carbon_budget_g is not None:
                feasible &= emis <= job.sla.carbon_budget_g
            cost = _plan_cost(job.sla, emis, ts + dur - job.submitted_t)
            n_alt += len(ts)
            if not feasible.any():
                continue
            i = int(np.argmin(np.where(feasible, cost, np.inf)))
            if best is None or cost[i] < best[0]:
                best = (float(cost[i]), float(emis[i]), float(ts[i]),
                        ftn, src, paths, gbps, dur)
        if best is None:
            return self._fallback(job, n_alt,
                                  greedy=g0[1] if g0 else None)
        return self._finish_plan(job, best, n_alt,
                                 greedy=g0[1] if g0 else None)

    def _finish_plan(self, job: TransferJob, best: Tuple,
                     n_alt: int, greedy: Optional[float] = None) -> Plan:
        """Materialize the winning cell into a Plan. The avg-CI/carbonscore
        annotations never enter the cost, so they are sampled once for the
        winner here instead of for every candidate slot of the scan (~30%
        of the old grid-scan cost). plan() finishes here, and
        plan_batch_jax()'s :meth:`_finish_plans` gives the same floats
        column by column."""
        cost_i, emis_i, t_i, ftn, src, paths, gbps, dur = best
        t_arr = np.array([t_i])
        avg_ci = sum(float(self._ci_vec(p, t_arr, dur)[0])
                     for p in paths) / len(paths)
        return Plan(
            job_uuid=job.uuid, start_t=t_i, source=src, ftn=ftn.name,
            path=discover_path(src, ftn.name), predicted_gbps=gbps,
            predicted_duration_s=dur, predicted_emissions_g=emis_i,
            predicted_avg_ci=avg_ci,
            predicted_carbonscore=carbonscore(job.size_bytes, avg_ci, dur),
            cost=cost_i, feasible=True, alternatives=n_alt,
            greedy_g=self._resolve_greedy(job, greedy))

    def _finish_plans(self, jobs: Sequence[TransferJob], won: _Winners,
                      cands: Sequence[Tuple], paths: Sequence[NetworkPath]
                      ) -> Tuple[List[Plan], int, int]:
        """:meth:`_finish_plan` for a sweep's winners, column by column,
        with bit-identical fields: the avg CI of every leg from
        :meth:`_winner_ci`, carbonscore with its own guard over the
        columns, and each candidate's source, FTN and path resolved once.
        Returns the plans, the distinct paths of the winners' legs and
        the ``path_ci`` calls made. A user ``ci_fn`` is a scalar callback,
        so its winners finish one by one."""
        n_paths = len(np.unique(won.legs[won.legs >= 0]))
        if self.ci_fn is not None:
            return [self._finish_plan(jobs[j], (c, e, t, *cands[k], d), n)
                    for j, c, e, t, k, d, n in zip(
                        won.job.tolist(), won.cost.tolist(),
                        won.emis.tolist(), won.start_t.tolist(),
                        won.cand.tolist(), won.dur.tolist(),
                        won.n_alt.tolist())], n_paths, 0
        avg, n_calls = self._winner_ci(won, paths)
        with np.errstate(divide="ignore", invalid="ignore"):
            score = np.where((avg <= 0) | (won.dur <= 0), 0.0,
                             won.size / (avg * won.dur))
        row = {}                       # candidate -> (src, ftn, path, gbps)
        for k in np.unique(won.cand).tolist():
            ftn, src, _, gbps = cands[k]
            row[k] = (src, ftn.name, discover_path(src, ftn.name), gbps)
        # the kernels return no slot values: the greedy-now capture falls
        # back to one integral a job (_resolve_greedy)
        greedy = ([self._resolve_greedy(jobs[j], None)
                   for j in won.job.tolist()] if self.capture_greedy
                  else [None] * len(won.job))
        plans = []
        for j, k, t, d, e, a, sc, c, n, g in zip(
                won.job.tolist(), won.cand.tolist(), won.start_t.tolist(),
                won.dur.tolist(), won.emis.tolist(), avg.tolist(),
                score.tolist(), won.cost.tolist(), won.n_alt.tolist(),
                greedy):
            src, ftn, path, gbps = row[k]
            plans.append(Plan(jobs[j].uuid, t, src, ftn, path, gbps, d, e,
                              a, sc, c, True, n, g))
        return plans, n_paths, n_calls

    def _winner_ci(self, won: _Winners, paths: Sequence[NetworkPath]
                   ) -> Tuple[np.ndarray, int]:
        """Each winner's avg CI as :meth:`_finish_plan` samples it, and
        the ``path_ci`` calls made. The legs that share a path and a
        midpoint count n (``expected_transfer_ci``'s rule) form one
        (legs, n) block of the same midpoints, evaluated in one call and
        summed row by row, which sums each row in the order of a
        one-row block."""
        n = np.maximum((won.dur // 900.0).astype(np.int64), 1)
        w, leg = np.nonzero(won.legs >= 0)         # by winner, leg order
        m = int(n.max(initial=1)) + 1
        grp, inv = np.unique(won.legs[w, leg].astype(np.int64) * m + n[w],
                             return_inverse=True)
        order = np.argsort(inv, kind="stable")
        ends = np.cumsum(np.bincount(inv, minlength=len(grp)))
        span_s = np.maximum(won.dur, 0.0)   # no duration: the start alone
        ci = np.zeros(won.legs.shape)
        for g, lo, hi in zip(grp.tolist(), [0, *ends[:-1].tolist()],
                             ends.tolist()):
            p, k = divmod(g, m)
            r = order[lo:hi]
            tt = won.start_t[w[r], None] \
                + (np.arange(k) + 0.5) * span_s[w[r], None] / k
            v = self.field.path_ci(paths[p], tt.ravel()).reshape(tt.shape)
            ci[w[r], leg[r]] = v.sum(axis=1) / k
        # sum(...) / len(paths): 0 + leg 0 + leg 1, a missing leg adds 0
        return (0.0 + ci[:, 0] + ci[:, 1]) / (won.legs >= 0).sum(axis=1), \
            len(grp)

    def plan_batch(self, jobs: Sequence[TransferJob],
                   previous: Optional[Sequence[Optional[Plan]]] = None,
                   drift_tol: Optional[float] = None) -> List[Plan]:
        """Fleet-scale planning: one call, shared caches. On the numpy
        batch backend the first plan warms the path/noise/trace caches and
        the rest reuse them; with ``batch_backend="pallas"`` a sweep of at
        least ``_BATCH_MIN_JOBS`` jobs is stacked into one
        :meth:`plan_batch_jax` call on the device.

        Incremental mode (the control plane's forecast-drift path): with
        ``previous`` plans and a ``drift_tol``, each job's old grid cell is
        first re-scored under current conditions; if it is still feasible
        and its predicted *emissions* moved by at most ``drift_tol``
        (relative), the job keeps its cell without a full grid scan —
        O(1 cell) instead of O(FTN x replica x slot). Emissions, not cost,
        is the drift metric: the w_perf term is measured from the job's
        submission base, which a queue rebase shifts without any real
        change in conditions. ``drift_tol=0.0`` degenerates to a full
        re-plan of every job whose conditions changed at all — and the
        drifted jobs are themselves re-planned as one batch.
        """
        with span("admit.sweep", jobs=len(jobs), tier=self.batch_backend):
            if self._metrics is None:
                return self._plan_batch(jobs, previous, drift_tol)
            t0 = time.perf_counter()
            plans = self._plan_batch(jobs, previous, drift_tol)
            # wall time goes to metrics only, never into sim-clock spans —
            # traces stay deterministic under replay, timings do not
            self._metrics.histogram("planner_plan_batch_wall_s",
                                    bounds=_WALL_BOUNDS) \
                .observe(time.perf_counter() - t0)
            self._metrics.counter("planner_plan_batches_total",
                                  backend=self.batch_backend).inc()
            self._metrics.counter("planner_cells_scored_total").inc(
                float(sum(p.alternatives for p in plans if p is not None)))
            return plans

    def _plan_batch(self, jobs: Sequence[TransferJob],
                    previous: Optional[Sequence[Optional[Plan]]] = None,
                    drift_tol: Optional[float] = None) -> List[Plan]:
        if previous is None or drift_tol is None:
            return self._plan_batch_full(list(jobs))
        jobs, previous = list(jobs), list(previous)
        out: List[Optional[Plan]] = [None] * len(jobs)
        miss: List[int] = []
        with span("admit.rescore", jobs=len(jobs)):
            rescored = self.rescore_batch(jobs, previous)
        for i, (prev, re) in enumerate(zip(previous, rescored)):
            if (re is not None and re.feasible
                    and abs(re.predicted_emissions_g
                            - prev.predicted_emissions_g)
                    <= drift_tol * max(prev.predicted_emissions_g, 1e-12)):
                out[i] = re
            else:
                miss.append(i)
        if miss:
            for i, plan in zip(miss,
                               self._plan_batch_full([jobs[i]
                                                      for i in miss])):
                out[i] = plan
        return out                     # type: ignore[return-value]

    # below these sizes the device path's fixed dispatch cost loses to
    # the numpy per-job scan, so small sweeps stay on the oracle.
    # Re-scores are single-cell (one slot, one anchor each): the kernels'
    # per-(anchor, path) rate table only amortizes on very large sweeps.
    _BATCH_MIN_JOBS = 8
    _RESCORE_MIN_CELLS = 512

    # observability: cell count of the most recent plan_batch_jax call —
    # the scale bench reads it to report peak admission-grid size — and
    # the sweeps / cells this planner has scored on a device tier so far.
    last_batch_cells = 0
    device_sweeps = 0
    device_cells = 0

    def _plan_batch_full(self, jobs: Sequence[TransferJob]) -> List[Plan]:
        if self.batch_backend == "pallas" \
                and len(jobs) >= self._BATCH_MIN_JOBS:
            return self.plan_batch_jax(jobs)
        with span("admit.numpy", jobs=len(jobs)):
            return [self.plan(job) for job in jobs]

    def _batch_cells(self, jobs: Sequence[TransferJob], dt_s: float,
                     stride: int) -> Tuple["CellTable", np.ndarray,
                                           "_SweepCells"]:
        """The stacked cell table of :meth:`plan_batch_jax`: one
        :class:`~repro.core.scheduler.grid_jax.CellTable` row and one SLA
        row ``[n_valid, dur_s, w_perf/slack, w_carbon, budget_g]`` per
        live (job, FTN, replica) cell, job-major, plus the planner's own
        columns (:class:`_SweepCells`). A job whose rate grid is past the
        per-cell cap has no cells and falls back to :meth:`plan`.

        Jobs that share a candidate signature (replicas, dst, parallelism,
        concurrency) share its candidates, which are resolved once; every
        column is then one vector expression over all (job, candidate)
        entries, with the per-element arithmetic of :meth:`_candidates`
        and :meth:`_slot_starts`."""
        from repro.core.scheduler.grid_jax import _MAX_GRID
        n_jobs = len(jobs)
        build = _CellColumns(self)
        sigs: dict = {}
        sig_cands: List[np.ndarray] = []
        sig_of = np.zeros(n_jobs, dtype=np.int64)
        for i, job in enumerate(jobs):
            key = (job.replicas, job.dst, job.parallelism, job.concurrency)
            s = sigs.get(key)
            if s is None:
                s = sigs[key] = len(sig_cands)
                sig_cands.append(np.array(
                    [build.candidate(ftn, src, *key[1:])
                     for ftn in self.ftns for src in job.replicas],
                    dtype=np.int64))
            sig_of[i] = s
        # (job, candidate) entries, job-major, then FTN, then replica
        n_cand = np.array([len(c) for c in sig_cands], dtype=np.int64)
        job = np.repeat(np.arange(n_jobs), n_cand[sig_of])
        cand = (np.concatenate([sig_cands[s] for s in sig_of])
                if len(job) else np.zeros(0, dtype=np.int64))
        sub = np.array([j.submitted_t for j in jobs], dtype=np.float64)
        size = np.array([j.size_bytes for j in jobs], dtype=np.float64)
        deadline = sub + np.array([j.sla.deadline_s for j in jobs],
                                  dtype=np.float64)
        gbps = build.gbps()[cand]
        sub_e, deadline_e = sub[job], deadline[job]
        with np.errstate(divide="ignore", invalid="ignore"):
            dur = size[job] * 8.0 / (gbps * 1e9)
            latest = (deadline_e - dur) + 1e-9
            n_slots = np.where(latest >= sub_e, np.floor_divide(
                latest - sub_e, self.slot_s), 0).astype(np.int64) + 1
            n_steps, rem = _steps(dur, dt_s)
        live = gbps > 0                # inf emissions: never feasible
        fallback = np.zeros(n_jobs, dtype=bool)
        fallback[job[live & ((n_slots - 1) * stride + n_steps
                             > _MAX_GRID)]] = True
        n_alt = np.bincount(job, weights=n_slots,
                            minlength=n_jobs).astype(np.int64)
        c = np.flatnonzero(live & ~fallback[job])
        jc = job[c]
        n_cells = np.bincount(jc, minlength=n_jobs)
        hi = np.cumsum(n_cells)
        lo = hi - n_cells
        # the deadline mask is monotone in the slot index, so the fused
        # kernel takes it as a host-side count; the budget mask depends on
        # in-kernel emissions and stays in-kernel
        sla = np.stack([
            _n_valid(sub_e[c], self.slot_s, n_slots[c], dur[c],
                     deadline_e[c]),
            dur[c],
            np.array([j.sla.w_perf / max(j.sla.deadline_s, 1.0)
                      for j in jobs], dtype=np.float64)[jc],
            np.array([j.sla.w_carbon for j in jobs], dtype=np.float64)[jc],
            np.array([np.inf if j.sla.carbon_budget_g is None
                      else j.sla.carbon_budget_g for j in jobs],
                     dtype=np.float64)[jc]], axis=1).reshape(-1, 5)
        table = build.table(cand[c], anchor=sub_e[c], n_slots=n_slots[c],
                            n_steps=n_steps[c], rem_s=rem[c])
        meta = _SweepCells(lo=lo, hi=hi, fallback=fallback, n_alt=n_alt,
                           sub=sub, size=size, dur=dur[c], cand=cand[c],
                           cands=build.cands, groups=build.groups)
        return table, sla, meta

    def plan_batch_jax(self, jobs: Sequence[TransferJob]) -> List[Plan]:
        """Fleet planning on the device: every job's (FTN x replica x
        slot) grid is stacked into one columnar cell table, and
        ``grid_pallas.batch_cell_best`` scores it per memory chunk in two
        fused Pallas kernels — the scoring chain *and* each cell's
        feasible-argmin — so only the per-cell winner (cost, emissions,
        slot) crosses back to the host.

        The numpy :meth:`plan_batch` is the pinned oracle: this path must
        pick the same grid cells with emissions within 1e-4 relative
        (in practice ~1e-7). Jobs whose layout the kernels cannot host
        (non-dt-aligned slots, a rate grid past the per-cell cap) fall
        back to the numpy :meth:`plan`. A kernel that cannot run on this
        backend raises.
        """
        from repro.core.scheduler import grid_pallas
        dt_s = 60.0
        stride = self.slot_s / dt_s
        if stride != int(stride) or stride <= 0:
            return [self.plan(job) for job in jobs]
        stride = int(stride)
        with span("admit.cells") as sp:
            cells, sla_rows, meta = self._batch_cells(jobs, dt_s, stride)
            sp.set_metadata(cells=len(cells), groups=meta.groups)
        self.last_batch_cells = len(cells)
        cost, emis, slot = np.zeros(0), np.zeros(0), np.zeros(0, np.int64)
        if len(cells):
            self.device_sweeps += 1
            self.device_cells += len(cells)
            cost, emis, slot = grid_pallas.batch_cell_best(
                self.field, cells, sla_rows, dt_s=dt_s,
                slot_stride=stride, slot_s=self.slot_s,
                scale_fn=self.emission_scale_fn)
        with span("admit.select"):
            win = _first_min(cost, meta.lo, meta.hi)   # in-kernel mask
            plans: List[Optional[Plan]] = [None] * len(jobs)
            # a fallback job has no cells, so no winner either
            for j in np.flatnonzero(win < 0).tolist():
                plans[j] = self.plan(jobs[j]) if meta.fallback[j] \
                    else self._fallback(jobs[j], int(meta.n_alt[j]))
            jw = np.flatnonzero(win >= 0)
            c = win[jw]
            won = _Winners(job=jw, cost=cost[c], emis=emis[c],
                           start_t=meta.sub[jw] + self.slot_s * slot[c],
                           dur=meta.dur[c], size=meta.size[jw],
                           n_alt=meta.n_alt[jw], cand=meta.cand[c],
                           legs=cells.legs[c])
        with span("admit.finish", plans=len(jw)) as sp:
            done, n_paths, n_calls = self._finish_plans(jobs, won,
                                                        meta.cands,
                                                        cells.paths)
            sp.set_metadata(paths=n_paths, ci_calls=n_calls)
        for j, plan in zip(jw.tolist(), done):
            plans[j] = plan
        return plans                   # type: ignore[return-value]

    def rescore_batch(self, jobs: Sequence[TransferJob],
                      previous: Sequence[Optional[Plan]]
                      ) -> List[Optional[Plan]]:
        """:meth:`rescore` for a whole sweep. On the pallas batch backend
        a sweep of at least ``_RESCORE_MIN_CELLS`` jobs scores all
        surviving cells (one slot each) in one
        ``grid_pallas.batch_cell_best`` call: a one-slot cell's winner is
        its slot 0, so the kernels' emissions are the cell's value, within
        f32 noise (~1e-7) of per-job :meth:`rescore` — a sweep with
        ``drift_tol=0.0`` should therefore use the numpy backend, where
        re-scores are bit-stable. Feasibility and cost stay on the host in
        float64. Otherwise falls back to per-job :meth:`rescore`. ``None``
        entries mean the cell no longer exists and the caller must
        full-plan."""
        dt_s = 60.0
        stride = self.slot_s / dt_s
        if self.batch_backend != "pallas" \
                or len(jobs) < self._RESCORE_MIN_CELLS \
                or stride != int(stride) or stride <= 0:
            return [self.rescore(j, p) if p is not None else None
                    for j, p in zip(jobs, previous)]
        from repro.core.scheduler import grid_pallas
        from repro.core.scheduler.grid_jax import _MAX_GRID
        out: List[Optional[Plan]] = [None] * len(jobs)
        items: List[Tuple] = []        # candidate key per live cell
        rows: List[int] = []           # its job
        for i, (job, prev) in enumerate(zip(jobs, previous)):
            if prev is None:
                continue
            ftn = self._ftn_by_name.get(prev.ftn)
            if ftn is None or prev.start_t < job.submitted_t - 1e-9:
                continue               # stale cell: caller full-plans
            items.append((ftn, prev.source, job.dst, job.parallelism,
                          job.concurrency))
            rows.append(i)
        build = _CellColumns(self)
        cand = np.array([build.candidate(*key) for key in items],
                        dtype=np.int64).reshape(-1)
        i_of = np.array(rows, dtype=np.int64)
        size = np.array([jobs[i].size_bytes for i in rows], dtype=np.float64)
        dur = size * 8.0 / (build.gbps()[cand] * 1e9)
        n_steps, rem = _steps(dur, dt_s)
        keep = n_steps <= _MAX_GRID
        for i in i_of[~keep]:
            out[i] = self.rescore(jobs[i], previous[i])
        k = np.flatnonzero(keep)
        if len(k):
            table = build.table(
                cand[k], anchor=np.array([previous[i].start_t
                                          for i in i_of[k]]),
                n_slots=np.ones(len(k), dtype=np.int64),
                n_steps=n_steps[k], rem_s=rem[k])
            sla = [jobs[i].sla for i in i_of[k]]
            # slot 0 alone is live, and no budget masks it
            sla_rows = np.stack([
                np.ones(len(k)), dur[k],
                np.array([s.w_perf / max(s.deadline_s, 1.0) for s in sla]),
                np.array([s.w_carbon for s in sla]),
                np.full(len(k), np.inf)], axis=1)
            _, emis_k, _ = grid_pallas.batch_cell_best(
                self.field, table, sla_rows, dt_s=dt_s,
                slot_stride=int(stride), slot_s=self.slot_s,
                scale_fn=self.emission_scale_fn)
            for i, c, d, e in zip(i_of[k], cand[k], dur[k], emis_k):
                job, prev = jobs[i], previous[i]
                gbps = build.cands[c][3]
                d, emis = float(d), float(e)
                deadline_t = job.submitted_t + job.sla.deadline_s
                feasible = prev.start_t + d <= deadline_t + 1e-9
                if job.sla.carbon_budget_g is not None:
                    feasible = feasible and emis <= job.sla.carbon_budget_g
                cost = float(_plan_cost(job.sla, emis,
                                        prev.start_t + d - job.submitted_t))
                out[i] = dataclasses.replace(
                    prev, predicted_gbps=gbps, predicted_duration_s=d,
                    predicted_emissions_g=emis, cost=cost,
                    feasible=bool(feasible))
        return out

    def rescore(self, job: TransferJob, prev: Plan) -> Optional[Plan]:
        """Re-evaluate one existing plan's (source, FTN, start) cell under
        current forecasts/throughput. Returns the refreshed Plan (possibly
        infeasible), or None when the cell no longer exists — start slot in
        the past, unknown FTN (the infeasible fallback's pseudo-cell) — in
        which case the caller must run a full :meth:`plan`."""
        ftn = self._ftn_by_name.get(prev.ftn)
        if ftn is None or prev.start_t < job.submitted_t - 1e-9:
            return None
        deadline_t = job.submitted_t + job.sla.deadline_s
        legs = [(prev.source, ftn.name)]
        if ftn.name != job.dst:
            legs.append((ftn.name, job.dst))
        gbps = min(self.throughput.predict(a, b, job.parallelism,
                                           job.concurrency)
                   for a, b in legs)
        gbps = min(gbps, ftn.max_gbps)
        dur = job.size_bytes * 8.0 / (gbps * 1e9)
        ts = np.array([prev.start_t])
        emis = np.zeros(1)
        for (a, b) in legs:
            p = discover_path(a, b)
            emis += self._leg_emissions(p, ftn.power_model, job, ts, gbps)
        feasible = prev.start_t + dur <= deadline_t + 1e-9
        if job.sla.carbon_budget_g is not None:
            feasible = feasible and float(emis[0]) <= job.sla.carbon_budget_g
        cost = float(_plan_cost(job.sla, float(emis[0]),
                                prev.start_t + dur - job.submitted_t))
        # the avg-CI/carbonscore annotations are kept from the previous
        # plan: they do not enter the cost, and re-sampling them would cost
        # more than the whole O(1) re-score
        return dataclasses.replace(
            prev, predicted_gbps=gbps, predicted_duration_s=dur,
            predicted_emissions_g=float(emis[0]),
            cost=cost, feasible=bool(feasible))

    # --- scalar reference oracle ------------------------------------------
    def plan_reference(self, job: TransferJob) -> Plan:
        """The seed's nested-loop scan, kept as the correctness oracle for
        the vectorized ``plan()`` (tests assert both pick the same
        (start, source, ftn) cell with emissions within 1e-6)."""
        deadline_t = job.submitted_t + job.sla.deadline_s
        best: Optional[Plan] = None
        n_alt = 0
        for ftn, src, legs, gbps, dur in self._candidates(job):
            t = job.submitted_t
            while t + dur <= deadline_t + 1e-9 or t == job.submitted_t:
                emis, ci_acc = 0.0, 0.0
                for (a, b) in legs:
                    p = discover_path(a, b)
                    emis += transfer_emissions_g_reference(
                        p, HOST_PROFILES["storage_frontend"],
                        ftn.power_model, job.size_bytes, t, gbps,
                        parallelism=job.parallelism,
                        concurrency=job.concurrency)
                    ci_acc += self._ci(p, t, dur)
                avg_ci = ci_acc / len(legs)
                feasible = t + dur <= deadline_t + 1e-9
                if job.sla.carbon_budget_g is not None:
                    feasible &= emis <= job.sla.carbon_budget_g
                cost = _plan_cost(job.sla, emis, t + dur - job.submitted_t)
                n_alt += 1
                cand = Plan(
                    job_uuid=job.uuid, start_t=t, source=src,
                    ftn=ftn.name, path=discover_path(src, ftn.name),
                    predicted_gbps=gbps, predicted_duration_s=dur,
                    predicted_emissions_g=emis, predicted_avg_ci=avg_ci,
                    predicted_carbonscore=carbonscore(
                        job.size_bytes, avg_ci, dur),
                    cost=cost, feasible=feasible)
                if feasible and (best is None or cand.cost < best.cost):
                    best = cand
                t += self.slot_s
        if best is None:
            return self._fallback(job, n_alt, reference=True)
        return dataclasses.replace(best, alternatives=n_alt)

    def _fallback(self, job: TransferJob, n_alt: int, *,
                  reference: bool = False,
                  greedy: Optional[float] = None) -> Plan:
        """SLA-infeasible: start now on the best-throughput direct path.
        The receiver power model is derived from the actual destination
        endpoint (the seed hard-coded the TPU-host profile)."""
        src = job.replicas[0]
        gbps = self.throughput.predict(src, job.dst, job.parallelism,
                                       job.concurrency)
        dur = job.size_bytes * 8.0 / (gbps * 1e9)
        p = discover_path(src, job.dst)
        emis_fn = (transfer_emissions_g_reference if reference
                   else transfer_emissions_g)
        emis = emis_fn(
            p, HOST_PROFILES["storage_frontend"],
            host_profile_for_endpoint(job.dst), job.size_bytes,
            job.submitted_t, gbps)
        ci = self._ci(p, job.submitted_t, dur)
        return Plan(job.uuid, job.submitted_t, src, job.dst, p, gbps,
                    dur, emis, ci,
                    carbonscore(job.size_bytes, ci, dur),
                    cost=math.inf, feasible=False, alternatives=n_alt,
                    greedy_g=None if reference
                    else self._resolve_greedy(job, greedy))
