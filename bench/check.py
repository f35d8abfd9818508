"""The comparison that decides ``correct``: the window's plans against the
plain reference, and the deployment's guarantees.

Numbers (each has a limit in the configuration file's ``limits``):

* ``unplanned`` -- jobs of the window with no plan, or a plan for another
  job. Exact: limit 0.
* ``feasible_mismatch`` -- sampled jobs where the program and the
  reference disagree on whether any cell is feasible. Exact: limit 0.
* ``regret`` -- over sampled jobs, the largest relative excess of the
  reference's cost of the (start slot, source replica, FTN) cell the
  program chose over the reference's least cost. A near-tie picked the
  other way reads about the rounding of the costs; a wrong choice reads
  the gap between the cells, even where its emissions are reported
  right.
* ``emis_err`` -- over sampled jobs, the largest relative gap between the
  program's predicted emissions and the reference's emissions of the
  same cell.
* ``incomplete`` (served) -- admitted jobs that did not complete. Exact.
* ``audit`` (served) -- |ledger - actual| / actual of the merged report.
"""
from __future__ import annotations

import math
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from bench import reference


def sample(items: Sequence, k: int, seed: int, must: Sequence[int] = ()
           ) -> List:
    """``k`` items drawn from ``seed`` (after the indices in ``must``)."""
    rng = np.random.default_rng(np.random.SeedSequence([int(seed), 7]))
    rest = [i for i in range(len(items)) if i not in set(must)]
    pick = list(must) + [int(i) for i in rng.choice(
        rest, size=min(k - len(must), len(rest)), replace=False)]
    return [items[i] for i in sorted(pick)]


def unplanned(pairs: Sequence[Tuple[reference.Job, object]]) -> int:
    return sum(1 for job, plan in pairs
               if plan is None or plan.job_uuid != job.uuid)


def plan_numbers(dep: reference.Deployment,
                 pairs: Sequence[Tuple[reference.Job, object]],
                 rate_dtype: Optional[str] = None) -> Dict[str, float]:
    """Regret, emission error and feasibility mismatches of (job, plan)
    pairs against the reference computed at ``rate_dtype``. A missing
    plan is ``unplanned``'s to count."""
    regret = emis = 0.0
    mism = 0
    for job, plan in pairs:
        if plan is None or plan.job_uuid != job.uuid:
            continue
        cells = dep.score(job, rate_dtype)
        opt = reference.best(cells)
        if opt is None or not plan.feasible:
            mism += int((opt is None) != (not plan.feasible))
            continue
        got = reference.lookup(cells, plan.ftn, plan.source, plan.start_t)
        if got is None or not got[0].feasible[got[1]]:
            regret = math.inf
            continue
        c, i = got
        best_cost = float(opt[0].cost[opt[1]])
        regret = max(regret, (float(c.cost[i]) - best_cost)
                     / max(abs(best_cost), 1e-12))
        ref_g = float(c.emis_g[i])
        emis = max(emis, abs(plan.predicted_emissions_g - ref_g)
                   / max(abs(ref_g), 1e-12))
    return {"feasible_mismatch": float(mism), "regret": regret,
            "emis_err": emis}


def runner_up(cells: Sequence[reference.Cell]
              ) -> Optional[Tuple[reference.Cell, int]]:
    """The feasible (cell, slot) of second-least cost (the least where
    only one is feasible): the nearest wrong answer."""
    flat = sorted((float(c.cost[i]), k, int(i)) for k, c in enumerate(cells)
                  for i in np.flatnonzero(c.feasible))
    if not flat:
        return None
    _, k, i = flat[min(1, len(flat) - 1)]
    return cells[k], i


def control_numbers(dep: reference.Deployment,
                    jobs: Sequence[reference.Job],
                    rate_dtype: Optional[str], choose=reference.best
                    ) -> Dict[str, float]:
    """The reference at ``rate_dtype`` put in the program's place, its
    ``choose`` picking each job's cell, judged by the float64 reference.
    The control is the least-cost choice at a lower precision; the fault
    ``runner_up`` at float64 is a wrong cell with its own emissions."""
    from types import SimpleNamespace
    pairs = []
    for job in jobs:
        opt = choose(dep.score(job, rate_dtype))
        if opt is None:
            plan = SimpleNamespace(job_uuid=job.uuid, feasible=False)
        else:
            c, i = opt
            plan = SimpleNamespace(
                job_uuid=job.uuid, feasible=True, ftn=c.ftn,
                source=c.source, start_t=float(c.starts[i]),
                predicted_emissions_g=float(c.emis_g[i]))
        pairs.append((job, plan))
    return plan_numbers(dep, pairs)


def judge(numbers: Dict[str, float], limits: Dict[str, float]
          ) -> Tuple[bool, List[Tuple[str, float, float]]]:
    """Each number the configuration limits, beside its limit; correct
    when none is over."""
    rows = [(k, float(v), float(limits[k])) for k, v in numbers.items()
            if k in limits]
    return all(v <= lim for _, v, lim in rows), rows
