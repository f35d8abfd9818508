"""The Pallas kernels' inputs laid out by rows against the element gather.

With a slot of whole hours, ``grid_pallas._layout`` copies each noise
plane as a strided window of a table row, edge-padded where the rows run
past the window, and builds the time rows from per-pair offsets in int32;
any other stride gathers the noise element by element. ``_chunk_tables``
looks each distinct hop key up once. Every ``KernelInputs`` array must
come out bit-identical to the element-gather reference of
``test_cell_table`` (dtype and shape included), every hop table to the
per-(path, hop) loop, and ``admit.inputs`` must count which layout ran
and how many hop keys the chunk looked up.
"""
import itertools

import numpy as np
import pytest

pytest.importorskip("jax")

import jax  # noqa: E402

from repro.core.carbon.intensity import PAPER_WINDOW_T0 as T0  # noqa: E402
from repro.core.scheduler import grid_jax, grid_pallas  # noqa: E402
from repro.core.scheduler.planner import (SLA, CarbonPlanner,  # noqa: E402
                                          TransferJob)
from repro.core.workloads.scenarios import (PLANNER_SCALE_FTNS,  # noqa: E402
                                            get_scenario, planner_scale_job)
from test_cell_table import (DT_S, _same, _scale_fn,  # noqa: E402
                             ref_chunk_tables, ref_kernel_inputs)
from test_obs_host import FTNS, _events, _named, _sweep_jobs  # noqa: E402


def _sweep(pl, jobs, slot_s):
    stride = int(slot_s / DT_S)
    table, sla, _ = pl._batch_cells(jobs, DT_S, stride)
    return pl.field, table, sla, stride, slot_s, pl.emission_scale_fn


def _scale(slot_s=3600.0, n=700):
    pl = CarbonPlanner(list(PLANNER_SCALE_FTNS), slot_s=slot_s)
    return _sweep(pl, [planner_scale_job(i) for i in range(n)], slot_s)


def _lattice():
    sc = get_scenario("metro_space_shift")
    pl = CarbonPlanner(sc.ftns)
    pl.emission_scale_fn = _scale_fn
    return _sweep(pl, list(itertools.islice(sc.jobs(seed=5, t0=T0), 20)),
                  3600.0)


def _past_window():
    """Anchors over five days and short deadlines: the window ends a few
    hours after the last grid, but every pair's 128 rows run on."""
    pl = CarbonPlanner(list(PLANNER_SCALE_FTNS))
    jobs = [TransferJob(f"w{i}", (30 + 7 * i) * 1e9, ("uc", "m1"), "tacc",
                        SLA(deadline_s=(6 + i % 5) * 3600.0),
                        T0 + 9000.0 * i + 60.0 * (i % 7))
            for i in range(48)]
    return _sweep(pl, jobs, 3600.0)


def _rescore(stride):
    """The one-slot cells and SLA rows ``rescore_batch`` builds, as it
    scores them, laid out at ``stride``."""
    def run():
        pl = CarbonPlanner(list(PLANNER_SCALE_FTNS), batch_backend="pallas")
        pl.emission_scale_fn = _scale_fn
        pl._RESCORE_MIN_CELLS = 1
        jobs = [planner_scale_job(i) for i in range(60)]
        prev = [pl.plan(j) for j in jobs]
        seen = []
        real = grid_pallas.batch_cell_best

        def capture(field, cells, sla_rows, **kw):
            seen.append((cells, sla_rows))
            return real(field, cells, sla_rows, **kw)

        grid_pallas.batch_cell_best = capture
        try:
            pl.rescore_batch(jobs, prev)
        finally:
            grid_pallas.batch_cell_best = real
        ((table, sla),) = seen
        assert (table.n_slots == 1).all()
        return pl.field, table, sla, stride, 3600.0, pl.emission_scale_fn
    return run


CASES = {
    "scale": (_scale, True),
    "lattice": (_lattice, True),
    "past_window": (_past_window, True),
    "stride_2h": (lambda: _scale(slot_s=7200.0, n=300), True),
    "stride_15min": (lambda: _scale(slot_s=900.0, n=120), False),
    "rescore_stride_1": (_rescore(1), False),
    "rescore_stride_60": (_rescore(60), True),
}


def _runs_past(field, cells, stride):
    """Whether some pair's rows read hours past the window's last."""
    t = grid_jax._chunk_tables(field, cells, dt_s=DT_S, slot_stride=stride,
                               cell_bucket=grid_jax._B_CELLS)
    width = grid_jax._round_up(-(-t.n_grid_pad // stride),
                               grid_pallas._LANES)
    last = int(t.rel0a.max() // 3600) + 1 + (stride // 60) * (width - 1)
    return last >= t.znoise.shape[1]


@pytest.mark.parametrize("case", sorted(CASES))
def test_layout_matches_element_gather(case):
    """Every ``KernelInputs`` array of every chunk bit-identical to the
    reference's; the row copy runs exactly at whole-hour slots."""
    build, rows = CASES[case]
    field, table, sla, stride, slot_s, scale_fn = build()
    assert (grid_pallas._slot_hours(DT_S, stride) > 0) == rows
    chunks = list(grid_jax._iter_chunks(table, stride,
                                        grid_jax._MAX_ELEMS))
    past = []
    for chunk in chunks[:2]:
        sub = table.take(chunk)
        new = grid_pallas._kernel_inputs(
            field, sub, sla[chunk], dt_s=DT_S, slot_stride=stride,
            slot_s=slot_s, scale_fn=scale_fn)
        ref = ref_kernel_inputs(field, sub, sla[chunk], stride, slot_s,
                                scale_fn)
        for name, a, b in zip(new._fields, new, ref):
            assert a.flags.c_contiguous, name
            _same(a, b)
        past.append(_runs_past(field, sub, stride))
    if case in ("past_window", "stride_2h"):
        assert any(past)


def test_hop_tables_match_per_hop_loop():
    """``hnoise``, ``band`` and ``zone_idx`` as the per-(path, hop) loop
    fills them, from one lookup per distinct hop key."""
    field, table, _, stride, _, _ = _lattice()
    t = grid_jax._chunk_tables(field, table, dt_s=DT_S, slot_stride=stride,
                               cell_bucket=grid_jax._B_CELLS)
    ref = ref_chunk_tables(field, table, dt_s=DT_S, slot_stride=stride,
                           cell_bucket=grid_jax._B_CELLS)
    for name in ("hnoise", "band", "zone_idx"):
        _same(getattr(t, name), getattr(ref, name))
    paths = {id(p): p for p in t.pair_paths}.values()
    assert t.hop_keys == ref.hop_keys == len(
        {h.ip for p in paths for h in p.hops})
    assert t.hop_keys < sum(p.n_hops for p in paths)


@pytest.mark.parametrize("slot_s,rows", [(3600.0, 1), (900.0, 0),
                                         (7200.0, 1)])
def test_admit_inputs_counts_rows_and_hop_keys(tmp_path, slot_s, rows):
    """A traced ``plan_batch_jax`` sweep records ``rows`` (1: the row
    copy laid the chunk out, 0: the element gather) and ``hop_keys``,
    the distinct ``Hop.ip`` of the chunk."""
    pl = CarbonPlanner(FTNS, batch_backend="pallas", slot_s=slot_s)
    jobs = _sweep_jobs()
    with jax.profiler.trace(str(tmp_path)):
        pl.plan_batch_jax(jobs)
    table = pl._batch_cells(jobs, DT_S, int(slot_s / DT_S))[0]
    used = np.unique(table.legs[table.legs >= 0])
    keys = len({h.ip for p in used for h in table.paths[p].hops})
    (ev,) = _named(_events(tmp_path), "admit.inputs")
    assert ev[3]["hop_keys"] == keys
    assert ev[3]["rows"] == rows
