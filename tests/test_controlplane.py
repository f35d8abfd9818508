"""Fleet control plane: event loop, closed-loop controller, incremental
re-planning, checkpointed migration, and the Pallas batch tier."""
import dataclasses

import pytest

from repro.core.carbon.intensity import PAPER_WINDOW_T0
from repro.core.controlplane import FleetController
from repro.core.controlplane.events import (EventLoop, JobArrival, JobReady,
                                            ReplanTick, StepTick)
from repro.core.scheduler.overlay import FTN
from repro.core.scheduler.planner import SLA, CarbonPlanner, TransferJob

T0 = PAPER_WINDOW_T0
FTNS = [FTN("uc", "skylake", 10.0), FTN("m1", "apple_m1", 1.2),
        FTN("site_qc", "cascade_lake", 40.0),
        FTN("tacc", "cascade_lake", 10.0)]
SHOCK_ZONES = ("CA-QC", "US-NY-NYIS")


def _heavy(i, t_off_h=10.0, deadline_h=24.0):
    return TransferJob(f"h{i}", 2000e9 + i * 1e9, ("uc",), "tacc",
                       SLA(deadline_s=deadline_h * 3600.0),
                       T0 + t_off_h * 3600.0 + i * 600.0)


# --- event loop -------------------------------------------------------------
def test_event_loop_orders_ties_and_cancels():
    loop = EventLoop(t0=0.0)
    a = loop.push(StepTick(t=5.0, job_uuid="a"))
    loop.push(StepTick(t=1.0, job_uuid="b"))
    loop.push(StepTick(t=5.0, job_uuid="c"))     # same t: insertion order
    assert len(loop) == 3
    loop.cancel(a)
    assert len(loop) == 2
    assert loop.pop().job_uuid == "b"
    assert loop.now == 1.0
    assert loop.pop().job_uuid == "c"            # a was cancelled
    assert loop.pop() is None and loop.empty


def test_event_loop_clock_is_monotone():
    loop = EventLoop()
    loop.push(StepTick(t=10.0, job_uuid="x"))
    loop.pop()
    with pytest.raises(ValueError):
        loop.push(StepTick(t=2.0, job_uuid="y"))  # behind the clock
    assert loop.pop_due(5.0) is None              # nothing due


def test_event_loop_pop_due_respects_now():
    loop = EventLoop()
    loop.push(JobArrival(t=3.0, job=None))
    loop.push(JobArrival(t=8.0, job=None))
    assert loop.pop_due(5.0).t == 3.0
    assert loop.pop_due(5.0) is None
    assert len(loop) == 1


# --- closed-loop controller -------------------------------------------------
@pytest.fixture(scope="module")
def shocked_run():
    fc = FleetController(FTNS, migration_threshold=250.0)
    fc.submit_many([_heavy(i) for i in range(12)])
    fc.inject_shock(T0 + 11 * 3600.0, 6.0, duration_s=6 * 3600.0,
                    zones=SHOCK_ZONES)
    report = fc.run()
    return fc, report


def test_controller_completes_fleet_and_reports(shocked_run):
    fc, report = shocked_run
    assert report.n_completed == report.n_jobs == 12
    assert len(report.outcomes) == 12
    assert len(fc.queue) == 0 and fc.events.empty
    assert report.total_actual_g > 0 and report.total_planned_g > 0
    assert report.jobs_per_s > 0
    for o in report.outcomes:
        assert o.actual_duration_s > 0
        assert o.completed_t >= o.start_t


def test_controller_report_matches_ledger_audit(shocked_run):
    _, report = shocked_run
    rel = abs(report.ledger_total_g - report.total_actual_g) \
        / report.total_actual_g
    assert rel < 0.05                  # acceptance bound; in practice ~1e-12


def test_drift_triggers_migration_and_replan(shocked_run):
    fc, report = shocked_run
    assert report.migrations >= 1
    assert report.replan_events >= 1
    # the overlay event log mirrors the controller's hand-offs
    assert len(fc.overlay.events) == report.migrations
    ev = fc.overlay.events[0]
    assert ev.ci_at_migration > fc.overlay.threshold
    assert ev.from_ftn != ev.to_ftn


def test_migration_resumes_from_checkpoint(shocked_run):
    fc, report = shocked_run
    migrated = [o for o in report.outcomes if o.migrations]
    assert migrated
    for o in migrated:
        rec = fc._records[o.job_uuid]
        # ledger wire-bytes are monotone: a hand-off resumes, never restarts
        bs = [s.bytes_total for s in rec.ledger.samples]
        assert all(b2 >= b1 for b1, b2 in zip(bs, bs[1:]))
        assert len(o.ftn_sequence) == o.migrations + 1


def test_migration_is_emission_rational(shocked_run):
    """A hand-off must have projected lower remaining emissions than
    staying — the CI-only ranking would hand 2 TB to the 1.2 Gbps node."""
    _, report = shocked_run
    for o in report.outcomes:
        assert "m1" not in o.ftn_sequence[1:]


def test_sla_miss_flags_are_consistent(shocked_run):
    fc, report = shocked_run
    for o in report.outcomes:
        rec = fc._records[o.job_uuid]
        deadline = rec.job.submitted_t + rec.job.sla.deadline_s
        assert o.sla_miss == (o.completed_t > deadline + 1e-6)
    assert report.sla_misses == sum(o.sla_miss for o in report.outcomes)


def test_controller_without_shock_sticks_to_plan():
    fc = FleetController(FTNS, migration_threshold=250.0)
    fc.submit_many([_heavy(i, t_off_h=2.0) for i in range(4)])
    report = fc.run()
    assert report.n_completed == 4
    # no drift: planned and actual emissions agree to modeling noise
    # (congestion band, path-mean vs hop-resolved CI)
    assert report.total_actual_g == pytest.approx(report.total_planned_g,
                                                  rel=0.25)


def test_shock_replans_see_the_drift():
    """Re-plans during a shock run against the measured drift, not the
    stale forecast: a queued job whose clean-relay route is shocked must
    be re-planned off it instead of being dispatched into the drift."""
    fc = FleetController(FTNS, migration_threshold=250.0)
    # queued far ahead: planned (greenest forecast) route relays via the
    # hydro FTN; the shock lands before its start slot
    job = TransferJob("q0", 2000e9, ("uc",), "tacc",
                      SLA(deadline_s=30 * 3600.0), T0)
    fc.submit(job)
    fc.inject_shock(T0 + 600.0, 8.0, duration_s=40 * 3600.0,
                    zones=SHOCK_ZONES)
    report = fc.run()
    rec = fc._records["q0"]
    assert rec.admitted_plan.ftn == "site_qc"       # forecast optimum
    assert rec.plan.ftn != "site_qc"                # drift-aware re-plan
    assert report.n_completed == 1


# --- incremental plan_batch -------------------------------------------------
def test_plan_batch_incremental_keeps_cells_when_nothing_drifts():
    pl = CarbonPlanner(FTNS)
    jobs = [_heavy(i) for i in range(4)]
    plans = pl.plan_batch(jobs)
    again = pl.plan_batch(jobs, previous=plans, drift_tol=0.0)
    for a, b in zip(plans, again):
        assert (a.source, a.ftn, a.start_t) == (b.source, b.ftn, b.start_t)
        assert b.predicted_emissions_g == pytest.approx(
            a.predicted_emissions_g, rel=1e-9)


def test_plan_batch_incremental_full_replan_on_drift():
    pl = CarbonPlanner(FTNS)
    jobs = [_heavy(i) for i in range(3)]
    plans = pl.plan_batch(jobs)
    # throughput drift: the learned correction halves the predicted rate
    for _ in range(30):
        pl.throughput.observe("uc", "site_qc", 4, 2, 4.0)
        pl.throughput.observe("uc", "tacc", 4, 2, 4.0)
    kept = pl.plan_batch(jobs, previous=plans, drift_tol=1e9)
    fresh = pl.plan_batch(jobs, previous=plans, drift_tol=0.0)
    for a, k in zip(plans, kept):
        # huge tolerance: the old cell is kept, just re-scored
        assert (a.source, a.ftn, a.start_t) == (k.source, k.ftn, k.start_t)
        assert k.predicted_gbps < a.predicted_gbps
    assert fresh == pl.plan_batch(jobs)   # zero tolerance == full re-plan


def test_rescore_rejects_stale_cells():
    pl = CarbonPlanner(FTNS)
    job = _heavy(0)
    plan = pl.plan(job)
    late = dataclasses.replace(job, submitted_t=plan.start_t + 3600.0)
    assert pl.rescore(late, plan) is None   # start slot is in the past


# --- batched stepping vs event-time accounting -------------------------------
def test_shock_mid_batch_is_scored_identically():
    """Emission accounting must be invariant to step batching: a shock
    firing *inside* a step batch (between the StepTick that started it
    and the completion) has to scale exactly the steps it covers. With a
    24 h migration interval the whole transfer runs as one batch; with
    30 s checks it runs step-by-step — same trajectory, and the actual
    emissions must agree to float rounding (the flush happens at the
    JobComplete event, after the shock popped)."""
    # a 1800 s deadline leaves exactly one feasible slot (start now), so
    # the ~423 s transfer cannot be time-shifted around the shock
    def run_with(check_every_s, shock):
        fc = FleetController([FTN("tacc", "cascade_lake", 10.0)],
                             migrate_check_every_s=check_every_s)
        fc.submit(TransferJob("sb", 500e9, ("uc",), "tacc",
                              SLA(deadline_s=1800.0), T0))
        if shock:
            fc.inject_shock(T0 + 120.0, 6.0, duration_s=3600.0)
        return fc.run()

    batched = run_with(24 * 3600.0, True)
    stepped = run_with(30.0, True)
    assert batched.n_completed == stepped.n_completed == 1
    assert batched.n_steps == stepped.n_steps
    assert batched.total_actual_g == pytest.approx(
        stepped.total_actual_g, rel=1e-9)
    # sanity: the shock actually moved the number (6x from 120 s in must
    # beat the unshocked run by a wide margin)
    clean = run_with(24 * 3600.0, False)
    assert batched.total_actual_g > 2.0 * clean.total_actual_g


def test_run_until_freezes_batched_steps_at_horizon():
    """run(until) must stop batched stepping at the horizon exactly like
    per-event stepping: the job stays in flight, its state within one
    engine step of the cut, and the report still settles its emissions."""
    fc = FleetController([FTN("tacc", "cascade_lake", 10.0)],
                         migrate_check_every_s=24 * 3600.0)
    fc.submit(TransferJob("hz", 500e9, ("uc",), "tacc",
                          SLA(deadline_s=1800.0), T0))   # one slot: now
    report = fc.run(until=T0 + 120.0)
    assert report.n_completed == 0
    rec = fc._records["hz"]
    assert rec.state.t_now <= T0 + 120.0 + fc.engine.dt_s + 1e-6
    assert not rec.pending                 # report settled the segment
    assert report.total_actual_g > 0


# --- bottleneck-leg observation attribution ---------------------------------
def test_leg2_bottleneck_feeds_throughput_model():
    """When the relay's second hop binds the rate, the achieved throughput
    must teach (relay, dst) — the ROADMAP open item: leg-2 learning was
    forfeited before. The 200 Gbps site_ca -> site_or leg never binds; the
    100 Gbps site_or -> tacc leg does."""
    fc = FleetController([FTN("site_or", "tpu_host", 200.0)])
    fc.submit(TransferJob("l2", 400e9, ("site_ca",), "tacc",
                          SLA(deadline_s=6 * 3600.0), T0))
    report = fc.run()
    assert report.n_completed == 1
    corr = fc.engine.model.correction
    assert ("site_or", "tacc") in corr
    assert ("site_ca", "site_or") not in corr


def test_ftn_nic_cap_observes_neither_leg():
    """An FTN cap below both legs binds the stream itself: the achieved
    rate says nothing about either (src, dst) pair and must not poison
    the learned corrections."""
    fc = FleetController([FTN("site_or", "tpu_host", 1.0)])
    fc.submit(TransferJob("cap", 10e9, ("site_ca",), "tacc",
                          SLA(deadline_s=12 * 3600.0), T0))
    report = fc.run()
    assert report.n_completed == 1
    assert fc.engine.model.correction == {}


def test_device_weight_fn_matches_device_weights():
    """The baked-route weight closure is the controller's per-step power
    model: it must be float-identical to _device_weights for scalars and
    stack the same values for gbps vectors."""
    import numpy as np

    from repro.core.carbon.energy import HOST_PROFILES
    from repro.core.carbon.field import default_field
    from repro.core.carbon.path import discover_path

    f = default_field()
    p = discover_path("uc", "tacc")
    s, r = HOST_PROFILES["storage_frontend"], HOST_PROFILES["cascade_lake"]
    fn = f.device_weight_fn(p, s, r, 4, 2)
    for g in (0.05, 1.2, 7.7, 9.99, 40.0):
        assert fn(g).tolist() == f._device_weights(p, s, r, g, 4, 2).tolist()
    gs = np.array([0.05, 1.2, 7.7])
    W = fn(gs)
    assert W.shape == (p.n_hops, 3)
    for j, g in enumerate(gs):
        assert W[:, j].tolist() == fn(float(g)).tolist()


# --- batch tiers -----------------------------------------------------------
def test_planner_rejects_unknown_backend():
    """``batch_backend`` is exactly "numpy" or "pallas": the deleted
    "jax" tier is as unknown as any other name."""
    for name in ("jax", "tpu", None):
        with pytest.raises(ValueError, match="batch_backend"):
            CarbonPlanner(FTNS, batch_backend=name)
    with pytest.raises(TypeError):
        CarbonPlanner(FTNS, backend="numpy")   # no per-leg tier knob


# --- fleet batch on the Pallas tier (plan_batch_jax) -------------------------
def _batch_jobs(n=24):
    """Mixed fleet: spread anchors, two replica sets, varied sizes and
    deadlines — enough shape diversity to exercise padding/masking."""
    return [TransferJob(f"pb{i}", (60 + (53 * i) % 900) * 1e9,
                        ("uc", "site_ne") if i % 3 else ("uc",), "tacc",
                        SLA(deadline_s=(5 + i % 9) * 3600.0,
                            w_perf=0.2 if i % 2 else 0.0),
                        T0 + (i % 7) * 1800.0 + (i % 3) * 17.0)
            for i in range(n)]


def _batch_planner():
    """Planner on the Pallas batch tier (interpret mode on CPU — slow but
    exact)."""
    pytest.importorskip("jax")
    return CarbonPlanner(FTNS, batch_backend="pallas")


BATCH_MIXES = {
    "fleet": _batch_jobs,
    # one two-replica job with a 48 h deadline: a long slot grid
    "two_replica_48h": lambda: [
        TransferJob("jx", 300e9, ("uc", "m1"), "tacc",
                    SLA(deadline_s=48 * 3600.0), PAPER_WINDOW_T0)],
    # four single-replica 24 h jobs, staggered by half an hour
    "four_24h": lambda: [
        TransferJob(f"jb{i}", (50 + 70 * i) * 1e9, ("uc",), "tacc",
                    SLA(deadline_s=24 * 3600.0),
                    PAPER_WINDOW_T0 + i * 1800.0) for i in range(4)],
}


@pytest.mark.parametrize("mix", sorted(BATCH_MIXES))
def test_plan_batch_jax_matches_numpy_oracle(mix):
    """Acceptance: the fused Pallas fleet path picks the same grid cells
    as the numpy plan_batch oracle with emissions within 1e-4 relative
    (in practice ~1e-7)."""
    jobs = BATCH_MIXES[mix]()
    ref = CarbonPlanner(FTNS).plan_batch(jobs)
    fast = _batch_planner().plan_batch_jax(jobs)
    assert len(fast) == len(ref)
    for a, b in zip(ref, fast):
        assert (a.start_t, a.source, a.ftn) == (b.start_t, b.source, b.ftn)
        assert b.predicted_emissions_g == pytest.approx(
            a.predicted_emissions_g, rel=1e-4)
        assert b.predicted_avg_ci == pytest.approx(a.predicted_avg_ci,
                                                   rel=1e-9)
        assert b.cost == pytest.approx(a.cost, rel=1e-4)
        assert a.alternatives == b.alternatives


def test_plan_batch_routes_through_jax_when_configured():
    jobs = _batch_jobs(12)
    pl = _batch_planner()
    ref = CarbonPlanner(FTNS).plan_batch(jobs)
    for a, b in zip(ref, pl.plan_batch(jobs)):
        assert (a.start_t, a.source, a.ftn) == (b.start_t, b.source, b.ftn)


def test_plan_batch_jax_infeasible_falls_back_like_numpy():
    """A job no slot can satisfy must yield the same SLA-first fallback
    plan (start now, direct path, feasible=False) as the numpy oracle."""
    job = TransferJob("late", 2000e9, ("uc",), "tacc",
                      SLA(deadline_s=120.0), T0)
    ref = CarbonPlanner(FTNS).plan(job)
    fast = _batch_planner().plan_batch_jax([job])[0]
    assert not ref.feasible and not fast.feasible
    assert (ref.start_t, ref.source, ref.ftn) == \
        (fast.start_t, fast.source, fast.ftn)
    assert fast.predicted_emissions_g == pytest.approx(
        ref.predicted_emissions_g, rel=1e-9)


def test_plan_batch_jax_applies_emission_scale_hook():
    """The controller's forecast-shock nowcast multiplies the forecast
    integral per leg; the batched paths must honor it like plan() does."""
    import numpy as np

    def scale(path, ts):
        f = 4.0 if any(h.zone == "CA-QC" for h in path.hops) else 1.0
        return np.full(np.shape(ts), f)

    jobs = _batch_jobs(10)
    ref_pl = CarbonPlanner(FTNS)
    ref_pl.emission_scale_fn = scale
    jax_pl = _batch_planner()
    jax_pl.emission_scale_fn = scale
    for a, b in zip(ref_pl.plan_batch(jobs), jax_pl.plan_batch_jax(jobs)):
        assert (a.start_t, a.source, a.ftn) == (b.start_t, b.source, b.ftn)
        assert b.predicted_emissions_g == pytest.approx(
            a.predicted_emissions_g, rel=1e-4)


def test_incremental_plan_batch_on_pallas_matches_numpy(monkeypatch):
    """``plan_batch(previous, drift_tol=0.05)`` on the Pallas tier keeps
    and re-plans the same jobs as the numpy planner: the re-scores run
    as one sweep of one-slot cells through ``grid_pallas.batch_cell_best``
    and the drifted jobs re-plan on the same cells."""
    import numpy as np

    from repro.core.scheduler import grid_pallas

    def scale(path, ts):
        f = 1.5 if any(h.zone == "CA-QC" for h in path.hops) else 1.0
        return np.full(np.shape(ts), f)

    jobs = _batch_jobs(16)
    previous = CarbonPlanner(FTNS).plan_batch(jobs)
    ref_pl, fast_pl = CarbonPlanner(FTNS), _batch_planner()
    fast_pl._RESCORE_MIN_CELLS = 1
    for pl in (ref_pl, fast_pl):
        pl.emission_scale_fn = scale
    sweeps = []
    real = grid_pallas.batch_cell_best

    def spy(field, cells, sla_rows, **kw):
        sweeps.append(int(cells.n_slots.max()))
        return real(field, cells, sla_rows, **kw)

    monkeypatch.setattr(grid_pallas, "batch_cell_best", spy)
    ref = ref_pl.plan_batch(jobs, previous, drift_tol=0.05)
    fast = fast_pl.plan_batch(jobs, previous, drift_tol=0.05)
    assert sweeps[0] == 1                  # the one-slot re-score sweep
    moved = [abs(a.predicted_emissions_g - p.predicted_emissions_g)
             > 1e-9 * p.predicted_emissions_g
             for a, p in zip(ref, previous)]
    assert any(moved) and not all(moved)
    for a, b in zip(ref, fast):
        assert (a.start_t, a.source, a.ftn, a.feasible) == \
            (b.start_t, b.source, b.ftn, b.feasible)
        assert b.predicted_emissions_g == pytest.approx(
            a.predicted_emissions_g, rel=1e-4)
        assert b.cost == pytest.approx(a.cost, rel=1e-4)
