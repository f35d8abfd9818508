"""Sharded fleet scale-out: partitioning, merged reports (property-tested
conservation + the exact ledger re-integration audit), and the admission
tier a fleet picks by default."""
import math

import pytest

from _hyp import given, hst, settings
from repro.core.carbon.intensity import PAPER_WINDOW_T0
from repro.core.controlplane import FleetReport, ShardedFleet
from repro.core.controlplane.controller import JobOutcome
from repro.core.scheduler.overlay import FTN
from repro.core.scheduler.planner import SLA, TransferJob

T0 = PAPER_WINDOW_T0
FTNS = [FTN("uc", "skylake", 10.0), FTN("m1", "apple_m1", 1.2),
        FTN("site_qc", "cascade_lake", 40.0),
        FTN("tacc", "cascade_lake", 10.0)]


def _jobs(n=12):
    return [TransferJob(f"s{i}", (300 + 100 * i) * 1e9,
                        ("uc", "site_ne") if i % 2 else ("uc",), "tacc",
                        SLA(deadline_s=(8 + i % 6) * 3600.0),
                        T0 + i * 1200.0) for i in range(n)]


def _report_for(rows, wall_s=1.0):
    """A synthetic shard report: rows are (planned, actual, ledger,
    migrations, sla_miss) tuples; totals derive from them the way a
    controller's _report does."""
    outcomes = [JobOutcome(
        job_uuid=f"j{i}", source="uc", ftn_sequence=("tacc",),
        start_t=0.0, completed_t=60.0, planned_emissions_g=p,
        actual_emissions_g=a, planned_duration_s=60.0,
        actual_duration_s=60.0, migrations=m, replanned=False,
        sla_miss=s, feasible=True)
        for i, (p, a, _, m, s) in enumerate(rows)]
    return FleetReport(
        outcomes=outcomes, n_jobs=len(rows), n_completed=len(rows),
        total_planned_g=sum(p for p, *_ in rows),
        total_actual_g=sum(a for _, a, *_ in rows),
        ledger_total_g=sum(led for _, _, led, *_ in rows),
        migrations=sum(m for *_, m, _ in rows),
        replan_events=1, plans_changed=0,
        sla_misses=sum(s for *_, s in rows),
        n_events=3 * len(rows), n_steps=2 * len(rows),
        sim_span_s=60.0, wall_s=wall_s,
        jobs_per_s=len(rows) / wall_s)


_row = hst.tuples(hst.floats(0.0, 1e6), hst.floats(0.0, 1e6),
                  hst.floats(0.0, 1e6), hst.integers(0, 4),
                  hst.booleans())


@settings(max_examples=60, deadline=None)
@given(hst.lists(_row, min_size=1, max_size=24),
       hst.lists(hst.integers(0, 4), min_size=1, max_size=24),
       hst.integers(2, 5))
def test_merged_report_conserves_totals_over_any_partition(rows, labels,
                                                           n_shards):
    """Acceptance property: however the fleet is partitioned, the merged
    report's totals equal the unpartitioned report's, counters exactly and
    emission sums to float rounding — and the merged ledger audit is the
    sum of per-shard audits, so re-integration still balances."""
    labels = [labels[i % len(labels)] % n_shards for i in range(len(rows))]
    shards = [[r for r, l in zip(rows, labels) if l == s]
              for s in range(n_shards)]
    merged = FleetReport.merged([_report_for(s) for s in shards if s])
    whole = _report_for(rows)
    assert merged.n_jobs == whole.n_jobs
    assert merged.n_completed == whole.n_completed
    assert merged.migrations == whole.migrations
    assert merged.sla_misses == whole.sla_misses
    assert merged.n_events == whole.n_events
    assert merged.n_steps == whole.n_steps
    assert len(merged.outcomes) == len(whole.outcomes)
    for got, want in ((merged.total_planned_g, whole.total_planned_g),
                      (merged.total_actual_g, whole.total_actual_g),
                      (merged.ledger_total_g, whole.ledger_total_g)):
        assert math.isclose(got, want, rel_tol=1e-12, abs_tol=1e-9)
    # the audit invariant survives the merge: |ledger - actual| merged is
    # bounded by the sum of per-shard audit gaps
    gap = sum(abs(_report_for(s).ledger_total_g
                  - _report_for(s).total_actual_g) for s in shards if s)
    assert abs(merged.ledger_total_g - merged.total_actual_g) \
        <= gap + 1e-6


@settings(max_examples=30, deadline=None)
@given(hst.lists(hst.lists(_row, min_size=1, max_size=8),
                 min_size=2, max_size=6))
def test_merged_report_merge_is_associative(shards):
    """merge(merge(a, b), merge(c, ...)) must agree with merge(a, b, c,
    ...): counters exactly, float totals to rounding."""
    reports = [_report_for(s) for s in shards]
    flat = FleetReport.merged(reports)
    k = len(reports) // 2
    nested = FleetReport.merged([FleetReport.merged(reports[:k]),
                                 FleetReport.merged(reports[k:])])
    assert (flat.n_jobs, flat.n_completed, flat.migrations,
            flat.sla_misses, flat.n_events, flat.n_steps) == \
        (nested.n_jobs, nested.n_completed, nested.migrations,
         nested.sla_misses, nested.n_events, nested.n_steps)
    assert math.isclose(flat.total_actual_g, nested.total_actual_g,
                        rel_tol=1e-12, abs_tol=1e-9)
    assert math.isclose(flat.ledger_total_g, nested.ledger_total_g,
                        rel_tol=1e-12, abs_tol=1e-9)
    assert math.isclose(flat.wall_s, nested.wall_s,
                        rel_tol=1e-12, abs_tol=1e-12)


def test_merged_wall_defaults_to_sequential_sum():
    a, b = _report_for([(1, 2, 2, 0, False)], 2.0), \
        _report_for([(3, 4, 4, 1, True)], 3.0)
    m = FleetReport.merged([a, b])
    assert m.wall_s == pytest.approx(5.0)
    assert m.jobs_per_s == pytest.approx(2 / 5.0)
    m2 = FleetReport.merged([a, b], wall_s=2.5)
    assert m2.jobs_per_s == pytest.approx(2 / 2.5)


# --- the real thing ---------------------------------------------------------
@pytest.fixture(scope="module")
def sharded_run():
    fleet = ShardedFleet(FTNS, n_shards=3, migration_threshold=250.0)
    jobs = _jobs(12)
    fleet.submit_many(jobs)
    fleet.inject_shock(T0 + 5 * 3600.0, 6.0, duration_s=5 * 3600.0,
                       zones=("CA-QC", "US-NY-NYIS"))
    report = fleet.run()
    return fleet, jobs, report


def test_sharded_fleet_partitions_and_completes(sharded_run):
    fleet, jobs, report = sharded_run
    assert report.n_jobs == report.n_completed == len(jobs)
    # every job lands on exactly the shard its stable hash names
    per_shard = [r.n_jobs for r in fleet.shard_reports]
    assert sum(per_shard) == len(jobs)
    for job in jobs:
        si = fleet.shard_of(job)
        assert any(o.job_uuid == job.uuid
                   for o in fleet.shard_reports[si].outcomes)


def test_sharded_fleet_merged_ledger_audit_is_exact(sharded_run):
    """Acceptance: the merged report's ledger re-integration must still
    balance the summed step accumulators to < 1e-9 relative."""
    fleet, _, report = sharded_run
    rel = abs(report.ledger_total_g - report.total_actual_g) \
        / max(report.total_actual_g, 1e-12)
    assert rel < 1e-9
    # and the merge itself is the plain sum of the shard reports
    assert report.total_actual_g == pytest.approx(
        sum(r.total_actual_g for r in fleet.shard_reports), rel=1e-15)
    assert report.ledger_total_g == pytest.approx(
        sum(r.ledger_total_g for r in fleet.shard_reports), rel=1e-15)
    assert report.n_steps == sum(r.n_steps for r in fleet.shard_reports)


def test_sharded_fleet_reacts_to_drift(sharded_run):
    _, _, report = sharded_run
    assert report.replan_events >= 1
    assert report.n_completed == 12


def test_partition_modes_are_stable_and_validated():
    fleet = ShardedFleet(FTNS, n_shards=4)
    job = _jobs(1)[0]
    assert fleet.shard_of(job) == fleet.shard_of(job)   # blake2b, not hash()
    by_source = ShardedFleet(FTNS, n_shards=4, partition="source")
    same_src = _jobs(6)
    shards = {by_source.shard_of(j) for j in same_src
              if j.replicas[0] == "uc"}
    assert len(shards) == 1            # a site's jobs stay together
    custom = ShardedFleet(FTNS, n_shards=2, partition=lambda j: 7)
    assert custom.shard_of(job) == 1
    with pytest.raises(ValueError):
        ShardedFleet(FTNS, n_shards=0)
    with pytest.raises(ValueError):
        ShardedFleet(FTNS, partition="range")


def test_admission_prices_in_preannounced_shocks():
    """A shock injected before submit_many must steer batched admission
    the way single-controller arrival-time planning would: the queued job
    whose clean-relay route is shocked is admitted off it, not merely
    re-planned later."""
    job = TransferJob("q0", 2000e9, ("uc",), "tacc",
                      SLA(deadline_s=30 * 3600.0), T0)
    fleet = ShardedFleet(FTNS, n_shards=2)
    fleet.inject_shock(T0 + 600.0, 8.0, duration_s=40 * 3600.0,
                       zones=("CA-QC", "US-NY-NYIS"))
    fleet.submit_many([job])
    report = fleet.run()
    ctl = fleet.controllers[fleet.shard_of(job)]
    # the forecast optimum relays via the hydro FTN; shock-aware
    # admission must not (cf. test_shock_replans_see_the_drift)
    assert ctl._records["q0"].admitted_plan.ftn != "site_qc"
    assert report.n_completed == 1


def test_single_submit_routes_to_owning_shard():
    fleet = ShardedFleet(FTNS, n_shards=2, batch_backend="numpy")
    job = _jobs(1)[0]
    fleet.submit(job)
    report = fleet.run()
    assert report.n_completed == 1
    assert fleet.shard_reports[fleet.shard_of(job)].n_jobs == 1


# --- the default admission tier --------------------------------------------
def test_sharded_fleet_admits_on_pallas_by_default():
    """With no ``batch_backend`` the fleet planner and, in this process,
    every shard planner run the Pallas tier."""
    pytest.importorskip("jax")
    fleet = ShardedFleet(FTNS, n_shards=3)
    assert fleet.planner.batch_backend == "pallas"
    assert fleet.shard_backend == "pallas"
    assert [c.planner.batch_backend for c in fleet.controllers] == \
        ["pallas"] * 3


def test_worker_shards_default_to_numpy_beside_a_pallas_planner():
    """Worker processes plan on numpy by default: the coordinator keeps
    the accelerator for its Pallas admission planner (no worker starts
    before the first shard command)."""
    from repro.core.controlplane.parallel import WORKER_BACKEND
    pytest.importorskip("jax")
    fleet = ShardedFleet(FTNS, n_shards=3, parallel="spawn")
    try:
        assert fleet.planner.batch_backend == "pallas"
        assert fleet.shard_backend == WORKER_BACKEND == "numpy"
    finally:
        fleet.close()


@pytest.mark.parametrize("key", ["batch_backend", "shard_backend"])
def test_sharded_fleet_rejects_the_deleted_jax_tier(key):
    with pytest.raises(ValueError, match="'jax'"):
        ShardedFleet(FTNS, n_shards=2, parallel="spawn", **{key: "jax"})
