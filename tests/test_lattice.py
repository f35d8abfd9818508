"""Mesoscale zone-lattice correctness harness.

The lattice tentpole's contract, pinned three ways:

* **scalar oracle** — every lattice zone's vectorized ``zone_ci`` matches
  the scalar ``GridRegion.ci`` + calibration within 1e-6 relative, and a
  200-zone plan sweep picks the same cells as ``plan_reference``;
* **differential sweep** — numpy == pallas-interpret within 1e-4 on the
  same lattice-sized cell tables;
* **properties** — zone-relabeling (replica-permutation) invariance of
  chosen plans, monotonicity under uniform CI scaling, and CSV → field →
  CSV bit-stability of the ingestion path. Each property has a hypothesis
  version (skips when hypothesis is absent) and a seeded deterministic
  sweep that always runs.
"""
import itertools

import numpy as np
import pytest
from _hyp import given, hst, settings  # optional-hypothesis shim

from repro.core.carbon import ingest, lattice
from repro.core.carbon.field import CarbonField
from repro.core.carbon.intensity import (PAPER_WINDOW_T0, REGIONS,
                                         get_calibration)
from repro.core.scheduler.planner import CarbonPlanner
from repro.core.scheduler.space_shift import best_source, best_source_batch
from repro.core.workloads.scenarios import get_scenario

T0 = PAPER_WINDOW_T0


def _rel(a, b):
    return abs(a - b) / max(abs(b), 1e-12)


def _fanout_jobs(n, *, seed=3):
    sc = get_scenario("metro_space_shift")
    return sc, list(itertools.islice(sc.jobs(seed=seed, t0=T0), n))


# --- lattice construction ---------------------------------------------------
def test_install_idempotent_and_tiered():
    lat = lattice.default_lattice(200)
    assert lattice.default_lattice(200) is lat
    assert len(lat.zones) == 200 and len(set(lat.zones)) == 200
    tiers = {t: len(lat.endpoints(t)) for t in ("edge", "metro", "core")}
    assert tiers["core"] >= 2 and tiers["metro"] >= 2
    assert sum(tiers.values()) == 200
    # deterministic reconstruction: a fresh uninstalled preset agrees
    fresh = lattice.preset(200)
    assert fresh.spec == lat.spec
    assert fresh.regions == lat.regions
    assert [fresh.tier(c) for c in fresh.cells] == \
        [lat.tier(c) for c in lat.cells]


def test_lattice_routes_climb_tiers():
    lat = lattice.default_lattice(200)
    from repro.core.carbon.path import discover_path
    e1 = lat.endpoints("edge")[0]
    e2 = lat.endpoints("edge")[-1]
    p = discover_path(e1, e2)
    orgs = [h.info.org for h in p.hops[1:-1]]
    assert "LatMetro" in orgs and "LatCore" in orgs
    assert p.distance_km() > 0
    # bridge to a foreign endpoint crosses the I2 core
    pb = discover_path(e1, "tacc")
    assert any(h.info.org == "Internet2" for h in pb.hops)
    # tier capacities bound the pair
    from repro.core.transfer.throughput import base_capacity
    assert base_capacity(e1, e2) == lattice.TIER_GBPS["edge"]
    core = lat.endpoints("core")[0]
    metro = lat.endpoints("metro")[0]
    assert base_capacity(metro, core) == lattice.TIER_GBPS["metro"]


# --- scalar per-zone oracle -------------------------------------------------
def test_zone_ci_matches_scalar_oracle_all_200_zones():
    lat = lattice.default_lattice(200)
    f = CarbonField()
    a, b = get_calibration()
    ts = T0 + 3600.0 * np.arange(30)
    for zone in lat.zones:
        vec = f.zone_ci(zone, ts)
        r = REGIONS[zone]
        ref = np.array([max(a * r.ci(float(t)) + b, 0.5) for t in ts])
        np.testing.assert_allclose(vec, ref, rtol=1e-6)


def test_plan_sweep_matches_scalar_oracle():
    sc, jobs = _fanout_jobs(6)
    planner = CarbonPlanner(sc.ftns)
    for job in jobs:
        fast = planner.plan(job)
        ref = planner.plan_reference(job)
        assert (fast.source, fast.ftn, fast.start_t) == \
            (ref.source, ref.ftn, ref.start_t)
        assert _rel(fast.predicted_emissions_g,
                    ref.predicted_emissions_g) <= 1e-6
        assert _rel(fast.cost, ref.cost) <= 1e-6


# --- differential sweep: numpy oracle against the Pallas tier ---------------
def test_differential_sweep_numpy_pallas():
    pytest.importorskip("jax")
    sc, jobs = _fanout_jobs(16)
    plans_np = CarbonPlanner(sc.ftns, batch_backend="numpy").plan_batch(jobs)
    p = CarbonPlanner(sc.ftns, batch_backend="pallas")
    plans = p.plan_batch(jobs)
    assert p.last_batch_cells >= 100, \
        "lattice fan-out should produce a 100+-cell table"
    assert len(plans) == len(plans_np)
    for got, ref in zip(plans, plans_np):
        assert (got.source, got.ftn, got.start_t) == \
            (ref.source, ref.ftn, ref.start_t)
        assert _rel(got.predicted_emissions_g,
                    ref.predicted_emissions_g) <= 1e-4


# --- space-shift fan-out ----------------------------------------------------
def test_best_source_batch_matches_scalar():
    lat = lattice.default_lattice(200)
    eps = lat.endpoints()
    dst = lat.endpoints("core")[0]
    sets = [tuple(eps[i::40]) for i in range(12)]     # 12 sets of 5
    t = T0 + 7 * 3600.0
    batch = best_source_batch(sets, dst, t)
    for reps, got in zip(sets, batch):
        ref = best_source(reps, dst, t)
        assert got.source == ref.source
        assert _rel(got.expected_ci, ref.expected_ci) <= 1e-9
        assert [s for s, _ in got.ranking] == [s for s, _ in ref.ranking]


# --- property: replica-permutation invariance -------------------------------
def _permutation_invariant(job, planner, perm):
    shuffled = dataclasses_replace_replicas(job, perm)
    a = planner.plan(job)
    b = planner.plan(shuffled)
    assert (a.source, a.ftn, a.start_t) == (b.source, b.ftn, b.start_t)
    assert a.predicted_emissions_g == b.predicted_emissions_g


def dataclasses_replace_replicas(job, perm):
    import dataclasses
    reps = tuple(job.replicas[i] for i in perm)
    return dataclasses.replace(job, replicas=reps)


def test_permutation_invariance_seeded():
    sc, jobs = _fanout_jobs(4)
    planner = CarbonPlanner(sc.ftns)
    rng = np.random.default_rng(11)
    for job in jobs:
        for _ in range(3):
            perm = rng.permutation(len(job.replicas))
            _permutation_invariant(job, planner, perm)


@settings(max_examples=20, deadline=None)
@given(hst.integers(min_value=0, max_value=3),
       hst.randoms(use_true_random=False))
def test_permutation_invariance_property(job_idx, rnd):
    sc, jobs = _fanout_jobs(4)
    planner = CarbonPlanner(sc.ftns)
    job = jobs[job_idx]
    perm = list(range(len(job.replicas)))
    rnd.shuffle(perm)
    _permutation_invariant(job, planner, perm)


# --- property: monotonicity under uniform CI scaling ------------------------
def _scaling_holds(reps, dst, t, k):
    base = best_source(reps, dst, t)
    scaled = best_source(reps, dst, t,
                         ci_fn=lambda p, tt, _k=k: p.ci(tt) * _k)
    # uniform scaling never changes the argmin, and the score is linear
    assert scaled.source == base.source
    assert _rel(scaled.expected_ci, base.expected_ci * k) <= 1e-9
    if k >= 1.0:
        assert scaled.expected_ci >= base.expected_ci


def test_ci_scaling_monotone_seeded():
    lat = lattice.default_lattice(200)
    eps = lat.endpoints()
    dst = lat.endpoints("core")[1]
    reps = tuple(eps[3::37])[:6]
    for k in (1.0, 1.5, 2.0, 5.0):
        _scaling_holds(reps, dst, T0 + 5 * 3600.0, k)


@settings(max_examples=25, deadline=None)
@given(hst.floats(min_value=1.0, max_value=10.0,
                  allow_nan=False, allow_infinity=False))
def test_ci_scaling_monotone_property(k):
    lat = lattice.default_lattice(200)
    eps = lat.endpoints()
    reps = tuple(eps[3::37])[:6]
    _scaling_holds(reps, lat.endpoints("core")[1], T0 + 5 * 3600.0, k)


# --- property: ingestion round trip -----------------------------------------
def _round_trip_stable(csv_text):
    traces = ingest.parse_csv(csv_text)
    f = CarbonField()
    ingest.install_traces(traces, f)
    out1 = ingest.export_csv(f, traces)
    traces2 = ingest.parse_csv(out1)
    f2 = CarbonField()
    ingest.install_traces(traces2, f2)
    out2 = ingest.export_csv(f2, traces2)
    assert out2 == out1                      # CSV -> field -> CSV bit-stable
    return out1


def test_ingest_round_trip_fixture_bit_stable():
    csv0 = ingest.synthetic_lattice_csv(8, hours=30)
    out1 = _round_trip_stable(csv0)
    # the generator emits pre-quantized canonical rows: identity round trip
    assert out1 == csv0


def test_ingest_round_trip_200_zone_fixture():
    csv0 = ingest.synthetic_lattice_csv(200, hours=12)
    traces = ingest.parse_csv(csv0)
    assert len(traces) == 200
    f = CarbonField()
    ingest.install_traces(traces, f)
    tr = next(iter(traces.values()))
    ts = tr.t0 + 3600.0 * np.arange(tr.hours)
    got = f.zone_ci(tr.zone, ts, calibrated=False)
    assert np.array_equal(got, tr.values)    # exact, not approx
    assert ingest.export_csv(f, traces) == csv0


@settings(max_examples=25, deadline=None)
@given(hst.lists(hst.floats(min_value=1.0, max_value=2000.0,
                            allow_nan=False, allow_infinity=False),
                 min_size=1, max_size=48))
def test_ingest_round_trip_property(values):
    import datetime as dt
    rows = [ingest.CSV_HEADER]
    for i, v in enumerate(values):
        stamp = dt.datetime.fromtimestamp(
            int(T0) + 3600 * i, tz=dt.timezone.utc).isoformat()
        rows.append(f"{stamp},HYP-Z,{v!r}")
    _round_trip_stable("\n".join(rows) + "\n")


# --- end-to-end: lattice scenario through the sharded fleet -----------------
def test_edge_lattice_day_through_sharded_fleet():
    from repro.core.controlplane.sharded import ShardedFleet
    sc = get_scenario("edge_lattice_day")
    jobs = list(itertools.islice(sc.jobs(seed=7, t0=T0), 30))
    fleet = ShardedFleet(sc.ftns, n_shards=2, shard_backend="numpy")
    fleet.submit_many(jobs)
    report = fleet.run()
    fleet.close()
    assert report.n_completed == len(jobs)
    audit = abs(report.ledger_total_g - report.total_actual_g) \
        / max(report.total_actual_g, 1e-12)
    assert audit < 1e-9
    by_uuid = {j.uuid: j for j in jobs}
    cross = sum(
        1 for o in report.outcomes
        if o.source != by_uuid[o.job_uuid].replicas[0]
        and lattice.tier_of_endpoint(o.source)
        != lattice.tier_of_endpoint(by_uuid[o.job_uuid].replicas[0]))
    assert cross >= 1, "no emission-rational cross-tier placement"
