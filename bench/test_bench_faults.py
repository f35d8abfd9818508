"""A run with its timed path broken underneath comes out not correct, for
each fault the cells can have: an answer altered where it is produced,
a wrong cell chosen with its own emissions reported right, half of a
sweep's plans left out, a step that returns its state unchanged. (The
cells run on one chip: no exchange between chips exists to leave out.)"""
import numpy as np
import pytest

from bench import rehearsal


def _altered_slots(monkeypatch):
    """The fused kernel's winning slot moved by one where the deadline
    allows, its cost and emissions kept: a wrong answer at the source."""
    from repro.core.scheduler import grid_pallas
    orig = grid_pallas.batch_cell_best

    def wrong(field, cells, sla_rows, **kw):
        cost, emis, slot = orig(field, cells, sla_rows, **kw)
        nval = np.asarray(sla_rows, dtype=np.float64)[:, 0]
        up = slot + 1 < nval
        return cost, emis, np.where(up, slot + 1, np.maximum(slot - 1, 0))

    monkeypatch.setattr(grid_pallas, "batch_cell_best", wrong)


def _runner_up_cell(monkeypatch):
    """Each job's least-cost cell taken out of the fused kernel's answer
    where another cell is feasible: the planner picks the next one and
    reports that cell's own cost and emissions."""
    from repro.core.scheduler import grid_pallas
    orig = grid_pallas.batch_cell_best

    def wrong(field, cells, sla_rows, **kw):
        cost, emis, slot = orig(field, cells, sla_rows, **kw)
        cost = np.array(cost, dtype=np.float64)
        job = [c.legs[0].anchor for c in cells]   # a job's cells share it
        i = 0
        while i < len(cells):
            j = i
            while j < len(cells) and job[j] == job[i]:
                j += 1
            live = [k for k in range(i, j) if np.isfinite(cost[k])]
            if len(live) > 1:
                cost[min(live, key=lambda k: cost[k])] = np.inf
            i = j
        return cost, emis, slot

    monkeypatch.setattr(grid_pallas, "batch_cell_best", wrong)


def _half_left_out(monkeypatch):
    from repro.core.scheduler.planner import CarbonPlanner
    orig = CarbonPlanner.plan_batch_jax

    def half(self, jobs, **kw):
        plans = orig(self, jobs, **kw)
        keep = len(plans) // 2
        return plans[:keep] + [None] * (len(plans) - keep)

    monkeypatch.setattr(CarbonPlanner, "plan_batch_jax", half)


def _stale_sweep(monkeypatch):
    """Every device sweep after the first returns the first one's plans."""
    from repro.core.scheduler.planner import CarbonPlanner
    orig = CarbonPlanner.plan_batch_jax
    first = []

    def stale(self, jobs, **kw):
        if not first:
            first.append(orig(self, jobs, **kw))
        return list(first[0])[:len(jobs)] + \
            [None] * max(0, len(jobs) - len(first[0]))

    monkeypatch.setattr(CarbonPlanner, "plan_batch_jax", stale)


def _completion_unchanged(monkeypatch):
    """The drain's completion step leaves the job's record as it was."""
    from repro.core.controlplane import controller
    from repro.core.controlplane.events import JobComplete
    orig = controller.FleetController._HANDLERS[JobComplete]

    def unchanged(self, ev):
        orig(self, ev)
        self._records[ev.job_uuid].completed_t = None

    monkeypatch.setitem(controller.FleetController._HANDLERS, JobComplete,
                        unchanged)


FAULTS = {
    "altered_answer": _altered_slots,
    "runner_up_cell": _runner_up_cell,
    "half_left_out": _half_left_out,
    "state_unchanged": None,           # per cell, below
}
STATE = {"ftn_overlay.replan": _stale_sweep,
         "metro_fanout_200.served": _completion_unchanged}


@pytest.mark.parametrize("cell", sorted(STATE))
@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_fault_is_not_correct(cell, fault, monkeypatch):
    (FAULTS[fault] or STATE[cell])(monkeypatch)
    out = rehearsal.run(cell, seconds=0.5)
    assert not out["correct"], out["checks"]
    if fault == "runner_up_cell":
        # the emissions of the cell it chose are right: only the choice
        # is wrong, and the regret is what reads it
        checks = out["checks"]
        assert checks["emis_err"]["value"] <= checks["emis_err"]["limit"]
        assert checks["regret"]["value"] > checks["regret"]["limit"]
