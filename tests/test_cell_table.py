"""The columnar admission cell table against a per-cell reference build.

``CarbonPlanner._batch_cells`` builds one ``CellTable`` of columns, and
``grid_jax._iter_chunks`` / ``_chunk_tables`` cut and lay it out with
array operations. The reference below builds the same sweep one
``CellTask`` per cell and walks it cell by cell. Every column, SLA row,
chunk list, ``ChunkTables`` and ``KernelInputs`` array must come out
bit-identical, and ``plan_batch_jax`` must return equal plans (same cell,
same floats) on the Pallas tier (interpret mode on the CPU).
"""
import dataclasses
import itertools
import math
from typing import Dict, List, Optional, Tuple

import numpy as np
import pytest

pytest.importorskip("jax")

from repro.core.carbon.energy import HOST_PROFILES  # noqa: E402
from repro.core.carbon.intensity import (PAPER_WINDOW_T0,  # noqa: E402
                                         REGIONS, get_calibration)
from repro.core.carbon.path import NetworkPath, discover_path  # noqa: E402
from repro.core.scheduler import grid_jax, grid_pallas  # noqa: E402
from repro.core.scheduler.grid_jax import (_B_HOURS, _B_PAIRS,  # noqa: E402
                                           _B_SLOTS, _B_ZONES, _GRID_BUCKET,
                                           _MAX_GRID, CellTable, CellTask,
                                           ChunkTables, LegTask, _round_up)
from repro.core.scheduler.overlay import FTN  # noqa: E402
from repro.core.scheduler.planner import (SLA, CarbonPlanner,  # noqa: E402
                                          Plan, TransferJob)
from repro.core.workloads.scenarios import (PLANNER_SCALE_FTNS,  # noqa: E402
                                            get_scenario, planner_scale_job)

T0 = PAPER_WINDOW_T0
DT_S, STRIDE, SLOT_S = 60.0, 60, 3600.0


# --- the per-cell reference build -------------------------------------------
def ref_batch_cells(self, jobs, dt_s, stride):
    """The stacked cell table of :meth:`plan_batch_jax`: one
    ``CellTask`` and one SLA row ``[n_valid, dur_s, w_perf/slack,
    w_carbon, budget_g]`` per (job, FTN, replica) cell, plus per-job
    metadata (``None`` for a job whose rate grid is past the per-cell
    cap, which falls back to :meth:`plan`)."""
    sender = HOST_PROFILES["storage_frontend"]
    cells: List[CellTask] = []
    sla_rows: List[Tuple] = []     # per cell, aligned with ``cells``
    meta: List[Optional[List[Tuple]]] = []
    wcache: dict = {}              # (path, recv, gbps, par, con) -> w

    def leg_w(p, pm, gbps, par, con):
        k = (id(p), pm.name, gbps, par, con)
        w = wcache.get(k)
        if w is None:
            w = wcache[k] = self.field.device_weight_fn(
                p, sender, pm, par, con)(gbps)
        return w

    for job in jobs:
        deadline_t = job.submitted_t + job.sla.deadline_s
        jcells: Optional[List[Tuple]] = []
        job_cell0 = len(cells)
        for ftn, src, legs, gbps, dur in self._candidates(job):
            ts = self._slot_starts(job, dur, deadline_t)
            paths = [discover_path(a, b) for (a, b) in legs]
            if gbps <= 0:          # inf emissions: never feasible
                jcells.append((None, ftn, src, paths, gbps, dur, ts))
                continue
            n_steps = max(int(math.ceil(dur / dt_s - 1e-12)), 1)
            if (len(ts) - 1) * stride + n_steps > _MAX_GRID:
                jcells = None      # degenerate rate grid: numpy plan()
                del cells[job_cell0:]   # drop its half-built cells
                del sla_rows[job_cell0:]
                break
            jcells.append((len(cells), ftn, src, paths, gbps, dur, ts))
            cells.append(CellTask(
                legs=tuple(LegTask(
                    path=p, anchor=float(ts[0]),
                    w_dev=leg_w(p, ftn.power_model, gbps,
                                job.parallelism, job.concurrency))
                    for p in paths),
                n_slots=len(ts), n_steps=n_steps,
                rem_s=dur - (n_steps - 1) * dt_s))
            # the deadline mask is monotone in the slot index, so the
            # fused kernel takes it as a host-side count; the budget
            # mask depends on in-kernel emissions and stays in-kernel
            sla_rows.append((
                float(np.sum(ts + dur <= deadline_t + 1e-9)), dur,
                job.sla.w_perf / max(job.sla.deadline_s, 1.0),
                job.sla.w_carbon,
                job.sla.carbon_budget_g
                if job.sla.carbon_budget_g is not None else np.inf))
        meta.append(jcells)
    return cells, sla_rows, meta


def ref_iter_chunks(cells, slot_stride, max_elems):
    """Split a fleet of cells into anchor-sorted chunks whose
    pairs*hops*grid element count stays under ``max_elems`` (pathological
    fleets with thousands of distinct anchors would otherwise materialize
    a multi-GB rate table in one call). Yields lists of original
    indices."""
    order = sorted(range(len(cells)),
                   key=lambda i: cells[i].legs[0].anchor)
    i = 0
    while i < len(order):
        chunk: List[int] = []
        pairs: Dict[Tuple, None] = {}
        grid_max = hops_max = 0
        while i < len(order):
            c = cells[order[i]]
            trial = dict(pairs)
            for leg in c.legs:
                # discover_path memoizes paths: identity is a stable key
                trial.setdefault((leg.anchor, id(leg.path)), None)
            g = max(grid_max, (c.n_slots - 1) * slot_stride + c.n_steps)
            h = max(hops_max, max(leg.path.n_hops for leg in c.legs))
            if chunk and len(trial) * h * g > max_elems:
                break
            pairs, grid_max, hops_max = trial, g, h
            chunk.append(order[i])
            i += 1
        yield chunk


def ref_chunk_tables(field, cells, *, dt_s, slot_stride, cell_bucket):
    # --- dedupe (anchor, path) pairs and paths ----------------------------
    paths: Dict[Tuple, int] = {}
    path_objs: List[NetworkPath] = []
    anchors: Dict[float, int] = {}
    pair_ids: Dict[Tuple, int] = {}
    pair_path: List[int] = []
    pair_anchor: List[int] = []
    n_grid = 1
    for c in cells:
        n_grid = max(n_grid, (c.n_slots - 1) * slot_stride + c.n_steps)
        for leg in c.legs:
            pk = id(leg.path)          # memoized paths: identity is stable
            if pk not in paths:
                paths[pk] = len(path_objs)
                path_objs.append(leg.path)
            if leg.anchor not in anchors:
                anchors[leg.anchor] = len(anchors)
            ak = (leg.anchor, pk)
            if ak not in pair_ids:
                pair_ids[ak] = len(pair_path)
                pair_path.append(paths[pk])
                pair_anchor.append(anchors[leg.anchor])
    n_hops = max(p.n_hops for p in path_objs)
    n_slots = max(c.n_slots for c in cells)
    zones = sorted({h.zone for p in path_objs for h in p.hops})
    # --- window: one hour-aligned anchor covering every pair's grid -------
    t0w = 3600.0 * math.floor(min(anchors) / 3600.0)
    t_end = max(a + n_grid * dt_s for a in anchors)
    hours = _round_up(int(math.ceil((t_end - t0w) / 3600.0)) + 1, _B_HOURS)
    hour0 = int(t0w // 3600.0)
    hour_idx = np.arange(hour0, hour0 + hours)
    n_z = _round_up(len(zones), _B_ZONES)
    znoise = np.zeros((n_z, hours), dtype=np.float32)
    for zi_, z in enumerate(zones):
        znoise[zi_] = (field._zone_noise.lookup(z, hour_idx) - 0.5) * 2.0
    regs = [REGIONS[z] for z in zones]

    def _zcol(attr):
        col = np.zeros(n_z, dtype=np.float32)
        col[:len(regs)] = [getattr(r, attr) for r in regs]
        return col

    cal_a, cal_b = get_calibration()
    # --- per-path hop tables (padded to n_hops; pads weigh 0) -------------
    n_p = _round_up(len(path_objs), 2)
    zone_idx = np.zeros((n_p, n_hops), dtype=np.int32)
    band = np.zeros((n_p, n_hops), dtype=np.float32)
    hnoise = np.zeros((n_p, n_hops, hours), dtype=np.float32)
    for pi, p in enumerate(path_objs):
        for hi_, h in enumerate(p.hops):
            zone_idx[pi, hi_] = zones.index(h.zone)
            band[pi, hi_] = field._hop_band(h.ip)
            hnoise[pi, hi_] = field._hop_noise.lookup(h.ip, hour_idx) - 0.5
    # --- anchor, pair and cell tables -------------------------------------
    n_anch = _round_up(len(anchors), 32)
    rel0a = np.zeros(n_anch)
    rel0a[:len(anchors)] = np.fromiter(anchors, dtype=np.float64,
                                       count=len(anchors)) - t0w
    n_a = _round_up(len(pair_path), _B_PAIRS)
    path_idx = np.zeros(n_a, dtype=np.int32)
    path_idx[:len(pair_path)] = pair_path
    anchor_idx = np.zeros(n_a, dtype=np.int32)
    anchor_idx[:len(pair_anchor)] = pair_anchor
    n_c = _round_up(len(cells), cell_bucket)
    pair_idx = np.zeros((n_c, 2), dtype=np.int32)
    w_dev = np.zeros((n_c, 2, n_hops))
    n_steps = np.ones(n_c, dtype=np.int32)
    rem = np.zeros(n_c)
    for ci_, c in enumerate(cells):
        for li, leg in enumerate(c.legs):
            pair_idx[ci_, li] = pair_ids[(leg.anchor, id(leg.path))]
            w_dev[ci_, li, :leg.path.n_hops] = leg.w_dev
        n_steps[ci_] = c.n_steps
        rem[ci_] = c.rem_s
    inv_pair: List[Optional[Tuple[float, int]]] = [None] * len(pair_ids)
    for (anchor, _pk), row in pair_ids.items():
        inv_pair[row] = (anchor, pair_path[row])
    return ChunkTables(
        zcols=tuple(_zcol(a) for a in ("base_ci", "diurnal_amp",
                                       "solar_dip", "noise", "peak_hour")),
        znoise=znoise, cal_a=np.float32(cal_a), cal_b=np.float32(cal_b),
        h_of_day0=(t0w / 3600.0) % 24.0,
        day_frac_s=t0w - 86400.0 * math.floor(t0w / 86400.0),
        dow0=int(t0w // 86400.0) % 7,
        zone_idx=zone_idx, band=band, hnoise=hnoise, rel0a=rel0a,
        anchor_idx=anchor_idx, path_idx=path_idx, pair_idx=pair_idx,
        w_dev=w_dev, n_steps=n_steps, rem=rem,
        n_grid_pad=_round_up(n_grid, _GRID_BUCKET),
        n_slots_pad=_round_up(n_slots, _B_SLOTS),
        n_hops=n_hops, n_pairs=len(pair_ids),
        hop_keys=len({h.ip for p in path_objs for h in p.hops}),
        pair_paths=[path_objs[p] for _, p in inv_pair],
        pair_anchors=[a for a, _ in inv_pair])



def ref_plan_batch_jax(self, jobs):
    """``plan_batch_jax`` on the reference build: per-cell tables, chunks
    and layout, the same kernels, a per-cell selection walk, and each
    winner finished by the per-winner ``_finish_plan``."""
    dt_s, stride = DT_S, int(self.slot_s / DT_S)
    cells, sla_rows, meta = ref_batch_cells(self, jobs, dt_s, stride)
    sla_rows = np.asarray(sla_rows, dtype=np.float64).reshape(-1, 5)
    chunks = list(ref_iter_chunks(cells, stride, grid_jax._MAX_ELEMS))
    fused = (np.full(len(cells), np.inf), np.full(len(cells), np.inf),
             np.zeros(len(cells), dtype=np.int64))
    for chunk in chunks:
        x = ref_kernel_inputs(self.field, [cells[j] for j in chunk],
                              sla_rows[chunk], stride, self.slot_s,
                              self.emission_scale_fn)
        best = np.asarray(grid_pallas._fused_call()(
            *x, stride=stride, dt_s=dt_s, slot_s=self.slot_s,
            interpret=True))
        n = len(chunk)
        fused[0][chunk] = best[:n, 0, 0]
        fused[1][chunk] = best[:n, 0, 1]
        fused[2][chunk] = best[:n, 0, 2].astype(np.int64)
    plans: List[Optional[Plan]] = []
    winners = []
    for job, jcells in zip(jobs, meta):
        if jcells is None:
            plans.append(self.plan(job))
            continue
        best: Optional[Tuple] = None
        n_alt = 0
        for idx, ftn, src, paths, gbps, dur, ts in jcells:
            n_alt += len(ts)
            if idx is None:
                continue
            c_cost = float(fused[0][idx])
            if not math.isfinite(c_cost):
                continue
            if best is None or c_cost < best[0]:
                i = int(fused[2][idx])
                best = (c_cost, float(fused[1][idx]),
                        float(ts[i]), ftn, src, paths, gbps, dur)
        if best is None:
            plans.append(self._fallback(job, n_alt))
        else:
            winners.append((len(plans), (job, best, n_alt, None)))
            plans.append(None)
    for slot, (job, best, n_alt, greedy) in winners:
        plans[slot] = self._finish_plan(job, best, n_alt, greedy)
    return plans


def ref_layout(t, cells, sla_rows, *, stride, slot_s, scale_fn, dt_s=DT_S):
    """The kernels' inputs from one chunk's tables, element by element:
    every noise element gathered at its row's candidate hour clipped to
    the window, and the time rows from int64 step indices."""
    dt = int(dt_s)
    sph, spd = 3600 // dt, 86400 // dt
    p = int(stride)
    width = _round_up(-(-t.n_grid_pad // p), grid_pallas._LANES)
    a_pad, h_hops = t.path_idx.shape[0], t.n_hops
    hours = t.znoise.shape[1]
    rel0 = t.rel0a[t.anchor_idx]
    q = np.floor(rel0 / dt).astype(np.int64)
    fsec = (rel0 - q * dt).astype(np.float32)
    u = q[:, None] + p * np.arange(width)[None, :]
    hour0 = u // sph
    n_cand = (sph - 1 + p - 1) // sph + 1
    hrs = np.clip(hour0[:, None, :] + np.arange(n_cand)[None, :, None],
                  0, hours - 1)
    zid = t.zone_idx[t.path_idx]
    zk = t.znoise[zid[:, None, :, None], hrs[:, :, None, :]]
    hk = t.hnoise[t.path_idx[:, None, None, None],
                  np.arange(h_hops)[None, None, :, None],
                  hrs[:, :, None, :]]
    hod0 = int(round(t.h_of_day0))
    day = (u + int(round(t.day_frac_s)) // dt) // spd
    dow = (t.dow0 + day) % 7
    ti = np.stack([u - hour0 * sph, (hod0 * sph + u) % spd],
                  axis=1).astype(np.int32)
    wk = grid_pallas._WEEKEND
    tf = np.stack([np.broadcast_to(fsec[:, None], u.shape),
                   np.where(dow >= 5, wk, np.float32(1.0)),
                   np.where((dow + 1) % 7 >= 5, wk, np.float32(1.0))],
                  axis=1).astype(np.float32)
    zbase, zamp, zdip, znamp, zpeak = t.zcols
    hp = np.stack([zbase[zid], zamp[zid], zdip[zid], znamp[zid],
                   zpeak[zid], t.band[t.path_idx],
                   np.full(zid.shape, t.cal_a), np.full(zid.shape, t.cal_b)],
                  axis=-1).astype(np.float32)
    scl = np.ones((a_pad, 1, width), dtype=np.float32)
    if scale_fn is not None:
        for a in range(t.n_pairs):
            ts = t.pair_anchors[a] + slot_s * np.arange(width)
            scl[a, 0] = scale_fn(t.pair_paths[a], ts)
    c_pad = t.pair_idx.shape[0]
    last = t.n_steps.astype(np.int64) - 1
    nval = np.zeros(c_pad, dtype=np.int32)
    nval[:len(cells)] = sla_rows[:, 0]
    cf = np.zeros((c_pad, 1, grid_pallas._CELL_COLS), dtype=np.float32)
    cf[:, 0, 0] = t.rem
    cf[:len(cells), 0, 1:5] = sla_rows[:, 1:5]
    return grid_pallas.KernelInputs(
        hp=hp, zk=zk.astype(np.float32), hk=hk.astype(np.float32), ti=ti,
        tf=tf, pidx=t.pair_idx.reshape(-1).astype(np.int32),
        ph=(last % p).astype(np.int32),
        sh=((width - last // p) % width).astype(np.int32), nval=nval,
        w=t.w_dev.astype(np.float32)[..., None], cf=cf, scl=scl)


def ref_kernel_inputs(field, cells, sla_rows, stride, slot_s, scale_fn):
    """``grid_pallas._kernel_inputs`` on the reference: the per-cell
    tables laid out element by element."""
    t = ref_chunk_tables(field, cells, dt_s=DT_S, slot_stride=stride,
                         cell_bucket=grid_jax._B_CELLS)
    return ref_layout(t, cells, sla_rows, stride=stride, slot_s=slot_s,
                      scale_fn=scale_fn)


# --- fleets -----------------------------------------------------------------
def _scale_fn(path, ts):
    """A drift hook that varies by path and by slot time."""
    return 1.0 + 0.05 * np.sin(np.asarray(ts) / 5400.0 + path.n_hops)


def _scale_fleet(n=700):
    return (CarbonPlanner(list(PLANNER_SCALE_FTNS)),
            [planner_scale_job(i) for i in range(n)])


def _lattice_batch(n=20):
    sc = get_scenario("metro_space_shift")
    jobs = list(itertools.islice(sc.jobs(seed=5, t0=T0), n))
    pl = CarbonPlanner(sc.ftns)
    pl.emission_scale_fn = _scale_fn
    return pl, jobs


def _edge_fleet():
    """A job past the per-cell grid cap, an FTN no transfer can use
    (gbps <= 0), jobs sharing one anchor, a carbon budget, w_perf."""
    ftns = [FTN("uc", "skylake", 10.0), FTN("m1", "apple_m1", 1.2),
            FTN("site_qc", "skylake", -1.0), FTN("tacc", "cascade_lake", 10.0)]
    jobs = [TransferJob(f"a{i}", (40 + 30 * i) * 1e9, ("uc", "m1"), "tacc",
                        SLA(deadline_s=(10 + 3 * i) * 3600.0,
                            carbon_budget_g=None if i % 2 else 400.0,
                            w_perf=0.2 * (i % 3)), T0 + 1800.0)
            for i in range(6)]
    jobs.insert(3, TransferJob("huge", 9000e9, ("uc",), "tacc",
                               SLA(deadline_s=30 * 86400.0), T0 + 600.0))
    jobs += [TransferJob(f"b{i}", 25e9 * (i + 1), ("m1",), "tacc",
                         SLA(deadline_s=8 * 3600.0), T0 + 7 * 3600.0 + i)
             for i in range(4)]
    jobs.append(TransferJob("late", 2000e9, ("uc", "m1"), "tacc",
                            SLA(deadline_s=60.0), T0))
    # time alone in the cost: equal-rate cells tie, the first must win
    jobs.append(TransferJob("tie", 50e9, ("uc", "m1"), "tacc",
                            SLA(deadline_s=9 * 3600.0, w_carbon=0.0,
                                w_perf=1.0), T0 + 900.0))
    return CarbonPlanner(ftns), jobs


def _rounding_fleet(n=300):
    """Odd sizes, submission times and deadlines, each deadline within a
    few ulps of a slot boundary, so the per-element float order of the
    slot and deadline arithmetic decides counts."""
    rng = np.random.default_rng(7)
    pl = CarbonPlanner(list(PLANNER_SCALE_FTNS))
    jobs = []
    for i in range(n):
        size = float(rng.uniform(5e9, 700e9))
        sub = T0 + float(rng.uniform(0, 86400.0))
        gbps = min(pl.throughput.predict("uc", "tacc", 4, 2), 10.0)
        dur = size * 8.0 / (gbps * 1e9)
        dl = dur + SLOT_S * int(rng.integers(0, 30)) \
            + float(rng.choice([-2e-9, -1e-9, 0.0, 1e-9, 2e-9]))
        jobs.append(TransferJob(f"r{i}", size, ("uc",), "tacc",
                                SLA(deadline_s=max(dl, 1.0)), sub))
    return pl, jobs


FLEETS = {"scale": _scale_fleet, "lattice": _lattice_batch,
          "edge": _edge_fleet, "rounding": _rounding_fleet}


# --- equality helpers -------------------------------------------------------
def _same(a, b):
    a, b = np.asarray(a), np.asarray(b)
    assert a.dtype == b.dtype and a.shape == b.shape
    assert np.array_equal(a, b, equal_nan=a.dtype.kind == "f")


def _same_tables(new: ChunkTables, ref: ChunkTables):
    for f in dataclasses.fields(ChunkTables):
        a, b = getattr(new, f.name), getattr(ref, f.name)
        if f.name == "zcols":
            for x, y in zip(a, b):
                _same(x, y)
        elif f.name == "pair_paths":
            assert [id(p) for p in a] == [id(p) for p in b]
        elif f.name == "pair_anchors":
            assert a == b
        elif isinstance(b, np.ndarray):
            _same(a, b)
        else:
            assert type(a) is type(b) and a == b, f.name


def _same_inputs(new, ref):
    for a, b in zip(new, ref):
        _same(a, b)


# --- the tests --------------------------------------------------------------
@pytest.mark.parametrize("fleet", sorted(FLEETS))
def test_cell_table_matches_per_cell_build(fleet):
    """Every column and SLA row bit-identical; fallbacks and alternatives
    per job as the per-cell walk counts them."""
    pl, jobs = FLEETS[fleet]()
    table, sla, meta = pl._batch_cells(jobs, DT_S, STRIDE)
    cells, ref_sla, ref_meta = ref_batch_cells(pl, jobs, DT_S, STRIDE)
    assert isinstance(table, CellTable) and len(table) == len(cells) > 0
    _same(sla, np.asarray(ref_sla, dtype=np.float64).reshape(-1, 5))
    _same(table.n_slots, np.array([c.n_slots for c in cells]))
    _same(table.n_steps, np.array([c.n_steps for c in cells]))
    _same(table.rem_s, np.array([c.rem_s for c in cells]))
    _same(table.anchor, np.array([c.legs[0].anchor for c in cells]))
    for i, c in enumerate(cells):
        assert [table.paths[p] for p in table.legs[i] if p >= 0] == \
            [leg.path for leg in c.legs]
        for li in range(2):
            w = table.w_dev[i, li]
            ref = c.legs[li].w_dev if li < len(c.legs) else np.zeros(0)
            _same(w[:len(ref)], ref)
            assert not w[len(ref):].any()
    _same(meta.fallback, np.array([m is None for m in ref_meta]))
    _same(meta.n_alt[~meta.fallback],
          np.array([sum(len(c[-1]) for c in m) for m in ref_meta
                    if m is not None], dtype=np.int64))
    live = [[c for c in m if c[0] is not None] for m in ref_meta
            if m is not None]
    _same(meta.dur, np.array([c[5] for m in live for c in m]))
    assert [meta.cands[k][:2] for k in meta.cand] == \
        [(c[1], c[2]) for m in live for c in m]
    if fleet == "edge":
        assert meta.fallback.sum() == 1 and meta.groups > 0
    assert meta.groups <= len(table)


def _ref_elems(cells, k):
    """The reference's pairs x hops x grid count of the first ``k`` cells
    in anchor order."""
    order = sorted(range(len(cells)), key=lambda i: cells[i].legs[0].anchor)
    sub = [cells[i] for i in order[:k]]
    pairs = {(leg.anchor, id(leg.path)) for c in sub for leg in c.legs}
    return (len(pairs) * max(leg.path.n_hops for c in sub for leg in c.legs)
            * max((c.n_slots - 1) * STRIDE + c.n_steps for c in sub))


@pytest.mark.parametrize("fleet,k", [("scale", 97), ("scale", 1500),
                                     ("lattice", 333)])
def test_chunk_cut_at_exact_budget(fleet, k):
    """A budget equal to a prefix's element count keeps that prefix: the
    cut falls where the count first exceeds the budget."""
    pl, jobs = FLEETS[fleet]()
    table, _, _ = pl._batch_cells(jobs, DT_S, STRIDE)
    cells, _, _ = ref_batch_cells(pl, jobs, DT_S, STRIDE)
    budget = _ref_elems(cells, k)
    chunks = [c.tolist() for c in
              grid_jax._iter_chunks(table, STRIDE, budget)]
    assert chunks == list(ref_iter_chunks(cells, STRIDE, budget))
    assert len(chunks[0]) >= k


@pytest.mark.parametrize("fleet,max_elems", [
    ("scale", grid_jax._MAX_ELEMS), ("scale", 1 << 20), ("scale", 1),
    ("rounding", grid_jax._MAX_ELEMS),
    ("lattice", grid_jax._MAX_ELEMS), ("lattice", 1 << 21),
    ("edge", grid_jax._MAX_ELEMS), ("edge", 1 << 16)])
def test_chunks_tables_and_inputs_match_per_cell_build(fleet, max_elems):
    """The same chunk index lists, and every ``ChunkTables`` and
    ``KernelInputs`` array of every chunk bit-identical."""
    pl, jobs = FLEETS[fleet]()
    table, sla, _ = pl._batch_cells(jobs, DT_S, STRIDE)
    cells, _, _ = ref_batch_cells(pl, jobs, DT_S, STRIDE)
    chunks = [c.tolist() for c in
              grid_jax._iter_chunks(table, STRIDE, max_elems)]
    assert chunks == list(ref_iter_chunks(cells, STRIDE, max_elems))
    if max_elems < 1 << 20:
        assert len(chunks) > 1
    for chunk in chunks[:4]:
        sub = [cells[j] for j in chunk]
        for bucket in (grid_jax._B_CELLS, 192):
            _same_tables(
                grid_jax._chunk_tables(pl.field, table.take(chunk),
                                       dt_s=DT_S, slot_stride=STRIDE,
                                       cell_bucket=bucket),
                ref_chunk_tables(pl.field, sub, dt_s=DT_S,
                                 slot_stride=STRIDE, cell_bucket=bucket))
        _same_inputs(
            grid_pallas._kernel_inputs(
                pl.field, table.take(chunk), sla[chunk], dt_s=DT_S,
                slot_stride=STRIDE, slot_s=SLOT_S,
                scale_fn=pl.emission_scale_fn),
            ref_kernel_inputs(pl.field, sub, sla[chunk], STRIDE, SLOT_S,
                              pl.emission_scale_fn))


@pytest.mark.parametrize("greedy", [False, True])
@pytest.mark.parametrize("fleet", ["lattice", "edge"])
def test_plans_match_per_cell_build(fleet, greedy):
    """``plan_batch_jax`` returns the reference's plans: same cell, same
    floats, with and without the greedy-now capture."""
    pl, jobs = FLEETS[fleet]()
    pl.batch_backend = "pallas"
    pl.capture_greedy = greedy
    plans = pl.plan_batch_jax(jobs)
    assert plans == ref_plan_batch_jax(pl, jobs)
    assert all((p.greedy_g is not None) == greedy for p in plans)


def test_plans_match_per_cell_build_on_scale_fleet():
    pl, jobs = _scale_fleet(48)
    pl.batch_backend = "pallas"
    assert pl.plan_batch_jax(jobs) == ref_plan_batch_jax(pl, jobs)


def test_cell_table_rows_serve_object_callers():
    """``len``, iteration, indexing and ``.legs[0].anchor`` work on a
    ``CellTable`` as they did on a list of ``CellTask`` — the access a
    wrapper of ``grid_pallas.batch_cell_best`` makes."""
    pl, jobs = _edge_fleet()
    table, sla, _ = pl._batch_cells(jobs, DT_S, STRIDE)
    cells, _, _ = ref_batch_cells(pl, jobs, DT_S, STRIDE)
    assert len(table) == len(cells)
    assert [c.legs[0].anchor for c in table] == \
        [c.legs[0].anchor for c in cells]
    for got, ref in zip(table, cells):
        assert isinstance(got, CellTask)
        assert (got.n_slots, got.n_steps, got.rem_s) == \
            (ref.n_slots, ref.n_steps, ref.rem_s)
        assert [leg.path for leg in got.legs] == \
            [leg.path for leg in ref.legs]
        for a, b in zip(got.legs, ref.legs):
            _same(a.w_dev, b.w_dev)
    assert table[3].legs[0].anchor == cells[3].legs[0].anchor
    seen = []
    orig = grid_pallas.batch_cell_best

    def wrapped(field, cells, sla_rows, **kw):
        seen.append((len(cells), [c.legs[0].anchor for c in cells]))
        return orig(field, cells, sla_rows, **kw)

    grid_pallas.batch_cell_best = wrapped
    try:
        pl.batch_backend = "pallas"
        pl.plan_batch_jax(jobs)
    finally:
        grid_pallas.batch_cell_best = orig
    assert seen == [(len(table), table.anchor.tolist())]


def test_admit_cells_span_counts_weight_groups():
    """The build evaluates one device-weight closure per distinct (path,
    receiver, parallelism, concurrency): the re-plan fleet's thousands
    of cells share a handful."""
    pl, jobs = _scale_fleet(700)
    table, _, meta = pl._batch_cells(jobs, DT_S, STRIDE)
    keys = {(id(leg.path), c_ftn.profile)
            for c, c_ftn in zip(table, (meta.cands[k][0]
                                        for k in meta.cand))
            for leg in c.legs}
    assert meta.groups == len(keys) < 10 < len(table)


def test_rescore_batch_builds_one_slot_table():
    """``rescore_batch`` on the Pallas tier builds its one-slot cells with
    the same ``_CellColumns`` and agrees with per-job ``rescore``: the
    same cell, gbps and duration, emissions within the oracle's 1e-4; a
    missing or stale plan stays ``None``; a cell past the grid cap
    re-scores on numpy."""
    pl, jobs = _scale_fleet(40)
    pl.emission_scale_fn = _scale_fn
    prev = [pl.plan(j) for j in jobs]
    prev[1] = None
    prev[2] = dataclasses.replace(prev[2], start_t=jobs[2].submitted_t - 60)
    huge = TransferJob("big", 4e15, ("uc",), "tacc", SLA(deadline_s=1e9), T0)
    jobs.append(huge)
    prev.append(dataclasses.replace(prev[0], job_uuid="big", ftn="uc",
                                    source="uc", start_t=T0))
    pl.batch_backend = "pallas"
    pl._RESCORE_MIN_CELLS = 1
    got = pl.rescore_batch(jobs, prev)
    ref = [pl.rescore(j, p) if p is not None else None
           for j, p in zip(jobs, prev)]
    assert got[1] is None and got[2] is None
    assert got[-1] == ref[-1]
    for a, b in zip(got, ref):
        if b is None:
            assert a is None
            continue
        assert (a.start_t, a.source, a.ftn, a.feasible,
                a.predicted_gbps, a.predicted_duration_s) == \
            (b.start_t, b.source, b.ftn, b.feasible,
             b.predicted_gbps, b.predicted_duration_s)
        assert a.predicted_emissions_g == pytest.approx(
            b.predicted_emissions_g, rel=1e-4)


@pytest.mark.parametrize("hook", [False, True])
def test_rescore_batch_scores_on_pallas(monkeypatch, hook):
    """A Pallas planner's re-score sweep is one ``batch_cell_best`` call
    at the planner's own stride, with SLA rows that leave slot 0 alone
    live and the drift hook passed to the layout; its plans agree with
    the numpy planner's per-job ``rescore`` (emissions and cost within
    1e-4, the rest equal)."""
    pl, jobs = _scale_fleet(40)
    ref_pl = CarbonPlanner(list(PLANNER_SCALE_FTNS))
    if hook:
        pl.emission_scale_fn = ref_pl.emission_scale_fn = _scale_fn
    prev = ref_pl.plan_batch(jobs)
    calls = []
    real = grid_pallas.batch_cell_best

    def spy(field, cells, sla_rows, **kw):
        calls.append((cells, np.asarray(sla_rows), kw))
        return real(field, cells, sla_rows, **kw)

    monkeypatch.setattr(grid_pallas, "batch_cell_best", spy)
    pl.batch_backend = "pallas"
    pl._RESCORE_MIN_CELLS = 1
    got = pl.rescore_batch(jobs, prev)
    ((cells, sla, kw),) = calls
    assert len(cells) == len(jobs) and (cells.n_slots == 1).all()
    assert (sla[:, 0] == 1).all() and np.isinf(sla[:, 4]).all()
    assert kw["slot_stride"] == STRIDE and kw["slot_s"] == SLOT_S
    assert kw["scale_fn"] is pl.emission_scale_fn
    for a, b in zip(got, ref_pl.rescore_batch(jobs, prev)):
        assert (a.start_t, a.source, a.ftn, a.feasible, a.predicted_gbps,
                a.predicted_duration_s) == \
            (b.start_t, b.source, b.ftn, b.feasible, b.predicted_gbps,
             b.predicted_duration_s)
        assert a.predicted_emissions_g == pytest.approx(
            b.predicted_emissions_g, rel=1e-4)
        assert a.cost == pytest.approx(b.cost, rel=1e-4)
