"""Fused Pallas admission kernel (grid_pallas) vs the numpy oracle —
interpret mode on CPU, so every test here runs in
CI without an accelerator. All node ids contain "pallas" on purpose: the
CI kernels job selects this subset with ``-k pallas``."""
import numpy as np
import pytest

pytest.importorskip("jax")

from repro.core.carbon.intensity import PAPER_WINDOW_T0
from repro.core.scheduler import grid_pallas
from repro.core.scheduler.overlay import FTN
from repro.core.scheduler.planner import SLA, CarbonPlanner, TransferJob

T0 = PAPER_WINDOW_T0
FTNS = [FTN("uc", "skylake", 10.0), FTN("m1", "apple_m1", 1.2),
        FTN("site_qc", "cascade_lake", 40.0),
        FTN("tacc", "cascade_lake", 10.0)]


def _against_oracle(jobs):
    """numpy oracle == pallas fused, cell-for-cell."""
    ref = CarbonPlanner(FTNS).plan_batch(jobs)
    fus = CarbonPlanner(FTNS, batch_backend="pallas").plan_batch_jax(jobs)
    assert len(fus) == len(ref)
    for a, c in zip(ref, fus):
        assert (a.start_t, a.source, a.ftn, a.feasible) \
            == (c.start_t, c.source, c.ftn, c.feasible)
        assert c.predicted_emissions_g == pytest.approx(
            a.predicted_emissions_g, rel=1e-4)
        assert c.cost == pytest.approx(a.cost, rel=1e-4)
        assert a.alternatives == c.alternatives
    return ref


def test_pallas_zero_cell_batch():
    """Empty sweep: no cells, no kernel launch, empty plan list."""
    pl = CarbonPlanner(FTNS, batch_backend="pallas")
    assert pl.plan_batch_jax([]) == []
    assert pl.batch_backend == "pallas"    # no spurious degrade


def test_pallas_single_slot_grid():
    """Deadline so tight only slot 0 fits: the in-kernel sweep runs one
    slot block with one live column and must still match the oracle."""
    jobs = [TransferJob(f"ss{i}", 30e9, ("uc",), "tacc",
                        SLA(deadline_s=3700.0 + i * 10.0), T0 + i * 13.0)
            for i in range(3)]
    plans = _against_oracle(jobs)
    assert all(p.feasible for p in plans)


def test_pallas_all_cells_masked_falls_back():
    """A job no slot can satisfy: every cell's cost is +inf in-kernel and
    the planner must produce the identical SLA-first fallback plan."""
    jobs = [TransferJob("late", 2000e9, ("uc", "m1"), "tacc",
                        SLA(deadline_s=60.0), T0)]
    plans = _against_oracle(jobs)
    assert not plans[0].feasible


def test_pallas_one_step_clamped_window():
    """Tiny transfers: duration under one dt step clamps n_steps to 1 and
    the remainder term carries the whole integral."""
    jobs = [TransferJob(f"tiny{i}", 1e9 + i * 2e8, ("uc", "m1"), "tacc",
                        SLA(deadline_s=6 * 3600.0), T0 + i * 950.0)
            for i in range(4)]
    _against_oracle(jobs)


def test_pallas_carbon_budget_mask_in_kernel():
    """The budget mask depends on in-kernel emissions (not host-side
    feasibility): a binding budget must flip winners identically."""
    jobs = [TransferJob(f"bg{i}", (100 + 40 * i) * 1e9, ("uc", "m1"),
                        "tacc",
                        SLA(deadline_s=24 * 3600.0,
                            carbon_budget_g=None if i % 2 else 90.0),
                        T0 + i * 1700.0) for i in range(8)]
    _against_oracle(jobs)


def test_pallas_lowering_failure_raises(monkeypatch):
    """A kernel that fails to lower is an error the caller sees: the
    planner keeps the tier it was asked for and never re-plans the batch
    on another one."""
    def boom(*a, **k):
        raise NotImplementedError("no lowering for this kernel")

    monkeypatch.setattr(grid_pallas, "_fused_call", lambda: boom)
    jobs = [TransferJob(f"rt{i}", 80e9, ("uc",), "tacc",
                        SLA(deadline_s=12 * 3600.0), T0 + i * 600.0)
            for i in range(4)]
    pl = CarbonPlanner(FTNS, batch_backend="pallas")
    with pytest.raises(NotImplementedError, match="no lowering"):
        pl.plan_batch_jax(jobs)
    assert pl.batch_backend == "pallas"


def test_pallas_interpret_only_on_cpu(monkeypatch):
    """Interpret mode is chosen from the backend alone: on the CPU by
    default, never on an accelerator, and an explicit choice wins."""
    import jax

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert grid_pallas._resolve_interpret(None) is False
    monkeypatch.setattr(jax, "default_backend", lambda: "cpu")
    assert grid_pallas._resolve_interpret(None) is True
    assert grid_pallas._resolve_interpret(False) is False


def test_pallas_batch_cell_best_validates_sla_rows():
    jobs = [TransferJob("v", 80e9, ("uc",), "tacc",
                        SLA(deadline_s=12 * 3600.0), T0)]
    pl = CarbonPlanner(FTNS, batch_backend="pallas")
    plans = pl.plan_batch_jax(jobs)      # builds a real cell table
    assert plans[0].feasible
    with pytest.raises(ValueError):
        grid_pallas.batch_cell_best(pl.field, [], np.zeros((1, 5)))


def test_pallas_sharded_fleet_smoke():
    """Backend plumb-through: a ShardedFleet admits on the fused kernel
    and completes every job (ledger audit is the fleet's own gate)."""
    from repro.core.controlplane import ShardedFleet

    jobs = [TransferJob(f"fl{i}", (60 + 30 * i) * 1e9,
                        ("uc", "m1") if i % 2 else ("uc",), "tacc",
                        SLA(deadline_s=18 * 3600.0), T0 + i * 700.0)
            for i in range(10)]
    fleet = ShardedFleet(FTNS, n_shards=2, batch_backend="pallas")
    fleet.submit_many(jobs)
    rep = fleet.run()
    assert rep.n_completed == rep.n_jobs == len(jobs)
