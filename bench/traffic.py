"""The one traffic generator: a configuration's job law, offered as a
traffic mix file says.

A configuration's ``jobs`` entry names its law (``"law"``) and holds the
law's parameters. The law is a file of its own, ``bench/laws/<law>.py``,
found by that name; it offers jobs in one or both of two ways:

* ``backlog(law, n_jobs, t0, tag)`` -- a backlog of ``n_jobs`` jobs
  anchored at ``t0``, for the loops that re-plan whole backlogs;
* ``arrivals(law, k, t0, horizon_s)`` -- horizon ``k`` of an arrival
  stream, ``horizon_s`` seconds from ``t0``, for the loops that serve a
  stream.

A new law is a new file there and a configuration that names it.

Every mix starts at the paper window's origin plus a whole number of
weeks drawn from ``seed``: seeds see the same hours and weekdays under
different weather, so different cells can win, with the same jobs, the
same compiled shapes and about the same work.
"""
from __future__ import annotations

from typing import List

import numpy as np

from bench import harness
from bench.reference import PAPER_T0, Job

WEEKS = 52


def origin(seed: int) -> float:
    rng = np.random.default_rng(np.random.SeedSequence([int(seed), 0]))
    return PAPER_T0 + 7 * 86400.0 * int(rng.integers(WEEKS))


def law(jobs: dict):
    """The law module a configuration's ``jobs`` entry names."""
    return harness.plugin("laws", jobs["law"])


def stream(jobs: dict, seed: int, horizon_h: float, n_horizons: int
           ) -> List[Job]:
    """``n_horizons`` consecutive horizons of the law's arrival stream."""
    arrivals = law(jobs).arrivals
    t0, h = origin(seed), horizon_h * 3600.0
    out: List[Job] = []
    for k in range(n_horizons):
        out.extend(arrivals(jobs, k, t0 + k * h, h))
    return out


def batches(jobs: List[Job], window_s: float, max_batch: int) -> List[range]:
    """The micro-batches a gateway with this window forms over ``jobs``:
    each opens at its first arrival and takes every arrival up to
    ``window_s`` later, at most ``max_batch`` of them."""
    out, i = [], 0
    while i < len(jobs):
        t_open, j = jobs[i].submitted_t, i + 1
        while (j < len(jobs) and j - i < max_batch
               and jobs[j].submitted_t <= t_open + window_s):
            j += 1
        out.append(range(i, j))
        i = j
    return out
