"""Columnar plan assembly against a per-winner reference.

``CarbonPlanner.plan_batch_jax`` picks each job's winning cell and
builds its ``Plan`` from arrays (``admit.select`` + ``admit.finish``).
Here the kernels are replaced by a seeded stand-in that returns a (cost,
emissions, slot) per cell, with ties and cells that are never feasible,
and the reference walks each job's cells in Python and finishes every
winner with the per-winner ``_finish_plan`` that ``plan()`` uses. Every
``Plan`` field must come out bit for bit the same, in job order.
"""
import glob
import itertools
import math
import struct

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from repro.core.carbon.intensity import PAPER_WINDOW_T0  # noqa: E402
from repro.core.carbon.path import NetworkPath  # noqa: E402
from repro.core.scheduler import grid_pallas  # noqa: E402
from repro.core.scheduler.overlay import FTN  # noqa: E402
from repro.core.scheduler.planner import (SLA, CarbonPlanner,  # noqa: E402
                                          Plan, TransferJob)
from repro.core.workloads.scenarios import (PLANNER_SCALE_FTNS,  # noqa: E402
                                            get_scenario, planner_scale_job)

T0 = PAPER_WINDOW_T0
FTNS = [FTN("uc", "skylake", 10.0), FTN("m1", "apple_m1", 1.2),
        FTN("tacc", "cascade_lake", 10.0)]


# --- the kernels' stand-in and the per-winner reference ---------------------
def _fake_kernels(seed):
    """A ``batch_cell_best`` stand-in: per cell a cost on a coarse grid
    (so cells tie), +inf on about a tenth of the cells and on every cell
    with no valid slot, an emissions value and a slot below its count."""
    def best(field, cells, sla_rows, **kw):
        rng = np.random.default_rng(seed + len(cells))
        n_valid = np.asarray(sla_rows)[:, 0]
        cost = np.floor(rng.uniform(0.0, 6.0, len(cells))) * 0.25 + 1.0
        cost[(rng.uniform(size=len(cells)) < 0.1) | (n_valid < 1)] = np.inf
        emis = rng.uniform(1.0, 900.0, len(cells))
        slot = np.floor(rng.uniform(size=len(cells))
                        * np.maximum(n_valid, 1)).astype(np.int64)
        best.out = (cost, emis, slot)
        return cost, emis, slot
    return best


def ref_plans(pl, jobs, cost, emis, slot):
    """Each job's first cell of least finite cost, walked cell by cell,
    finished by ``_finish_plan``; ``plan()`` past the grid cap and the
    infeasible fallback where no cell is feasible. Also returns each
    winner's legs as (path, midpoint count) pairs."""
    stride = int(pl.slot_s / 60.0)
    _, _, meta = pl._batch_cells(jobs, 60.0, stride)
    plans, legs = [], []
    for j, job in enumerate(jobs):
        if meta.fallback[j]:
            plans.append(pl.plan(job))
            continue
        c = None
        for k in range(meta.lo[j], meta.hi[j]):
            if math.isfinite(cost[k]) and (c is None or cost[k] < cost[c]):
                c = k
        n_alt = int(meta.n_alt[j])
        if c is None:
            plans.append(pl._fallback(job, n_alt))
            continue
        ftn, src, paths, gbps = meta.cands[meta.cand[c]]
        dur = float(meta.dur[c])
        best = (float(cost[c]), float(emis[c]),
                job.submitted_t + pl.slot_s * int(slot[c]),
                ftn, src, paths, gbps, dur)
        plans.append(pl._finish_plan(job, best, n_alt))
        legs += [(id(p), max(int(dur // 900.0), 1)) for p in paths]
    return plans, legs


def _bits(x):
    """A field's value with its type and, for a float, its exact bits."""
    if isinstance(x, float):
        return "float", struct.pack("<d", x)
    if isinstance(x, NetworkPath):
        return "path", id(x)
    return type(x).__name__, x


def _same_plans(got, ref):
    assert len(got) == len(ref)
    for a, b in zip(got, ref):
        assert isinstance(a, Plan) and isinstance(b, Plan)
        for f in Plan.__dataclass_fields__:
            assert _bits(getattr(a, f)) == _bits(getattr(b, f)), \
                (b.job_uuid, f, getattr(a, f), getattr(b, f))


# --- batches ----------------------------------------------------------------
def _scale(n=4096):
    return (CarbonPlanner(list(PLANNER_SCALE_FTNS)),
            [planner_scale_job(i) for i in range(n)])


def _lattice(n=160):
    """8-replica sets over a 200-zone lattice: legs share paths across
    jobs, and each job has 40 cells."""
    sc = get_scenario("metro_space_shift")
    return (CarbonPlanner(sc.ftns),
            list(itertools.islice(sc.jobs(seed=11, t0=T0), n)))


def _long(n=96):
    """10-60 TB transfers of 2 h to several days, so every leg samples
    8 to hundreds of midpoints, where numpy's pairwise sum and a
    sequential one differ."""
    jobs = [TransferJob(f"L{i}", (10.0 + 50.0 * ((7 * i) % n) / n) * 1e12,
                        (("uc",), ("m1",), ("uc", "m1"))[i % 3], "tacc",
                        SLA(deadline_s=(150 + i % 40) * 3600.0),
                        T0 + 1234.5 * i)
            for i in range(n)]
    return CarbonPlanner(FTNS), jobs


def _edge():
    """Jobs past the per-cell grid cap (``plan()``), jobs with no valid
    slot (the infeasible fallback) and an FTN no transfer can use,
    between ordinary jobs."""
    ftns = FTNS[:2] + [FTN("site_qc", "skylake", -1.0)] + FTNS[2:]
    jobs = []
    for i in range(24):
        if i % 7 == 3:
            jobs.append(TransferJob(f"huge{i}", 9000e9, ("uc",), "tacc",
                                    SLA(deadline_s=30 * 86400.0),
                                    T0 + 600.0 * i))
        elif i % 5 == 1:
            jobs.append(TransferJob(f"late{i}", 2000e9, ("uc", "m1"),
                                    "tacc", SLA(deadline_s=60.0), T0 + i))
        else:
            jobs.append(TransferJob(
                f"e{i}", (40 + 30 * i) * 1e9, ("uc", "m1"), "tacc",
                SLA(deadline_s=(10 + i) * 3600.0,
                    carbon_budget_g=None if i % 2 else 400.0,
                    w_perf=0.2 * (i % 3)), T0 + 1800.0 * i))
    return CarbonPlanner(ftns), jobs


def _few(n):
    def build():
        return CarbonPlanner(FTNS), [
            TransferJob(f"f{i}", (50 + 90 * i) * 1e9, ("uc", "m1"), "tacc",
                        SLA(deadline_s=(14 + 5 * i) * 3600.0),
                        T0 + 2700.0 * i) for i in range(n)]
    return build


def _ci_fn():
    """A planner with a user CI callback: its winners finish one by one."""
    pl, jobs = _scale(64)
    pl.ci_fn = lambda path, t: 300.0 + 40.0 * math.sin(t / 7200.0) \
        + path.n_hops
    return pl, jobs


def _greedy():
    pl, jobs = _edge()
    pl.capture_greedy = True
    return pl, jobs


BATCHES = {"scale_4096": _scale, "lattice": _lattice, "long": _long,
           "edge": _edge, "one": _few(1), "two": _few(2), "three": _few(3),
           "ci_fn": _ci_fn, "greedy": _greedy}


# --- the tests --------------------------------------------------------------
@pytest.mark.parametrize("batch", list(BATCHES))
def test_columnar_plans_match_per_winner_finish(batch, monkeypatch):
    pl, jobs = BATCHES[batch]()
    fake = _fake_kernels(seed=len(batch))
    monkeypatch.setattr(grid_pallas, "batch_cell_best", fake)
    got = pl.plan_batch_jax(jobs)
    ref, legs = ref_plans(pl, jobs, *fake.out)
    _same_plans(got, ref)
    assert [p.job_uuid for p in got] == [j.uuid for j in jobs]
    won = sum(p.feasible for p in got)
    if batch in ("one", "two", "three"):
        assert won == len(jobs)
    elif batch in ("edge", "greedy"):
        assert 0 < won < len(jobs) - 4
        assert any(p.ftn == "tacc" and not p.feasible for p in got)
    if batch == "long":
        assert min(n for _, n in legs) >= 8 and won > len(jobs) // 2
    if batch == "greedy":
        assert all(p.greedy_g is not None for p in got if p.feasible)


def test_admit_finish_span_counts_paths_and_ci_calls(tmp_path, monkeypatch):
    """A traced sweep records ``admit.finish`` with the plans it built,
    the distinct paths of the winners' legs and the (path, midpoint
    count) groups, one ``path_ci`` call each."""
    from jax.profiler import ProfileData
    pl, jobs = _scale(600)
    fake = _fake_kernels(seed=3)
    monkeypatch.setattr(grid_pallas, "batch_cell_best", fake)
    with jax.profiler.trace(str(tmp_path)):
        got = pl.plan_batch_jax(jobs)
    _, legs = ref_plans(pl, jobs, *fake.out)
    (xplane,) = glob.glob(f"{tmp_path}/**/*.xplane.pb", recursive=True)
    args = [dict(e.stats)
            for plane in ProfileData.from_file(xplane).planes
            if plane.name == "/host:CPU"
            for line in plane.lines for e in line.events
            if e.name == "admit.finish"]
    assert args == [{"plans": sum(p.feasible for p in got),
                     "paths": len({p for p, _ in legs}),
                     "ci_calls": len(set(legs))}]
    # the re-plan fleet's winners use all six of its paths
    assert args[0]["paths"] == 6 and 6 < args[0]["ci_calls"] < 40
