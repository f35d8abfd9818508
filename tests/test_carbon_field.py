"""Vectorized CarbonField / prefix-sum emissions / grid planner vs the
scalar reference oracles, within 1e-6 relative tolerance (the testing
contract: the scalar seed implementations stay in-tree as the ground truth
the fast paths must reproduce)."""
import dataclasses

import numpy as np
import pytest

from repro.core.carbon.energy import HOST_PROFILES
from repro.core.carbon.field import CarbonField, default_field
from repro.core.carbon.intensity import (PAPER_WINDOW_T0, REGIONS,
                                         calibrated_ci, region_ci)
from repro.core.carbon.path import discover_path
from repro.core.carbon.score import (transfer_emissions_g,
                                     transfer_emissions_g_batch,
                                     transfer_emissions_g_reference)
from repro.core.scheduler.overlay import FTN
from repro.core.scheduler.planner import (SLA, CarbonPlanner, TransferJob,
                                          _plan_cost)
from repro.core.scheduler.time_shift import (best_start_time,
                                             expected_transfer_ci)

T0 = PAPER_WINDOW_T0
RTOL = 1e-6
FTNS = [FTN("uc", "skylake", 10.0), FTN("m1", "apple_m1", 1.2),
        FTN("tacc", "cascade_lake", 10.0)]

# windows probing weekends, fractional hours, and pre/post paper-window
TS = T0 + np.concatenate([
    np.linspace(-36.0, 120.0, 257) * 3600.0,
    np.array([0.0, 0.4, 13.0, 23.999, 24.0, 47.5, 50.99]) * 3600.0,
])


def test_zone_ci_matches_scalar_all_zones():
    f = CarbonField()
    for zone in REGIONS:
        for calibrated, scalar in ((False, region_ci), (True, calibrated_ci)):
            vec = f.zone_ci(zone, TS, calibrated=calibrated)
            ref = np.array([scalar(zone, t) for t in TS])
            np.testing.assert_allclose(vec, ref, rtol=RTOL)


def test_hop_ci_matrix_matches_scalar():
    f = CarbonField()
    for src, dst in (("uc", "tacc"), ("m1", "tacc"), ("site_qc", "site_de")):
        p = discover_path(src, dst)
        vec = f.hop_ci_matrix(p, TS)
        ref = np.array([[h.ci(t) for t in TS] for h in p.hops])
        np.testing.assert_allclose(vec, ref, rtol=RTOL)


def test_path_ci_matches_scalar():
    f = CarbonField()
    p = discover_path("uc", "tacc")
    np.testing.assert_allclose(
        f.path_ci(p, TS), np.array([p.ci(t) for t in TS]), rtol=RTOL)
    # degenerate self-path (direct transfer's second leg)
    p2 = discover_path("tacc", "tacc")
    np.testing.assert_allclose(
        f.path_ci(p2, TS), np.array([p2.ci(t) for t in TS]), rtol=RTOL)


def test_expected_transfer_ci_matches_scalar():
    f = CarbonField()
    p = discover_path("uc", "tacc")
    starts = T0 + 3600.0 * np.arange(30)
    for dur in (0.0, 300.0, 3600.0, 5.5 * 3600.0, 26 * 3600.0):
        vec = f.expected_transfer_ci(p, starts, dur)
        ref = np.array([expected_transfer_ci(p, t, dur) for t in starts])
        np.testing.assert_allclose(vec, ref, rtol=RTOL)


@pytest.mark.parametrize("size_bytes,gbps", [
    (300e9, 3.7), (42e9, 1.2), (5e9, 9.5), (2000e9, 0.9)])
def test_prefix_sum_emissions_match_scalar_on_slot_grid(size_bytes, gbps):
    f = CarbonField()
    p = discover_path("uc", "tacc")
    snd, rcv = HOST_PROFILES["storage_frontend"], HOST_PROFILES["skylake"]
    starts = T0 + 3600.0 * np.arange(48)
    vec = f.transfer_emissions_g(p, snd, rcv, size_bytes, starts, gbps,
                                 parallelism=4, concurrency=2)
    ref = np.array([transfer_emissions_g_reference(
        p, snd, rcv, size_bytes, t, gbps, parallelism=4, concurrency=2)
        for t in starts])
    np.testing.assert_allclose(vec, ref, rtol=RTOL)


def test_prefix_sum_emissions_match_scalar_unaligned_starts():
    f = CarbonField()
    p = discover_path("m1", "tacc")
    snd, rcv = HOST_PROFILES["storage_frontend"], HOST_PROFILES["apple_m1"]
    starts = T0 + np.array([0.0, 123.456, 9999.9, 50000.1, 86400.7])
    vec = f.transfer_emissions_g(p, snd, rcv, 42e9, starts, 1.1)
    ref = np.array([transfer_emissions_g_reference(p, snd, rcv, 42e9, t, 1.1)
                    for t in starts])
    np.testing.assert_allclose(vec, ref, rtol=RTOL)


def test_score_module_fast_scalar_and_batch_agree():
    p = discover_path("uc", "tacc")
    snd, rcv = HOST_PROFILES["storage_frontend"], HOST_PROFILES["cascade_lake"]
    ref = transfer_emissions_g_reference(p, snd, rcv, 100e9, T0, 4.0)
    assert transfer_emissions_g(p, snd, rcv, 100e9, T0, 4.0) == \
        pytest.approx(ref, rel=RTOL)
    batch = transfer_emissions_g_batch(p, snd, rcv, 100e9,
                                       T0 + 3600.0 * np.arange(5), 4.0)
    assert batch.shape == (5,)
    assert batch[0] == pytest.approx(ref, rel=RTOL)
    # zero throughput guard
    assert np.isinf(transfer_emissions_g(p, snd, rcv, 1e9, T0, 0.0))


PLANNER_JOBS = [
    TransferJob("a", 300e9, ("uc", "m1"), "tacc",
                SLA(deadline_s=48 * 3600.0), T0),
    TransferJob("b", 50e9, ("uc", "site_ne", "site_qc"), "tacc",
                SLA(deadline_s=24 * 3600.0), T0 + 7 * 3600.0),
    TransferJob("c", 800e9, ("m1",), "tacc",
                SLA(deadline_s=12 * 3600.0, w_perf=0.5), T0 + 3600.0),
    TransferJob("d", 300e9, ("uc",), "tacc", SLA(deadline_s=1.0), T0),
    TransferJob("e", 100e9, ("uc", "m1"), "tacc",
                SLA(deadline_s=36 * 3600.0, carbon_budget_g=30.0), T0),
]


@pytest.mark.parametrize("job", PLANNER_JOBS, ids=lambda j: j.uuid)
def test_grid_planner_matches_scalar_oracle(job):
    pl = CarbonPlanner(FTNS)
    ref = pl.plan_reference(job)
    fast = pl.plan(job)
    assert (fast.start_t, fast.source, fast.ftn) == \
        (ref.start_t, ref.source, ref.ftn)
    assert fast.feasible == ref.feasible
    assert fast.alternatives == ref.alternatives
    assert fast.predicted_emissions_g == \
        pytest.approx(ref.predicted_emissions_g, rel=RTOL)
    assert fast.predicted_avg_ci == \
        pytest.approx(ref.predicted_avg_ci, rel=RTOL)
    if np.isfinite(ref.cost):
        assert fast.cost == pytest.approx(ref.cost, rel=RTOL)


def test_plan_batch_equals_individual_plans():
    pl = CarbonPlanner(FTNS)
    plans = pl.plan_batch(PLANNER_JOBS)
    for job, batched in zip(PLANNER_JOBS, plans):
        single = pl.plan(job)
        assert (batched.start_t, batched.source, batched.ftn,
                batched.feasible) == \
            (single.start_t, single.source, single.ftn, single.feasible)
        assert batched.predicted_emissions_g == \
            pytest.approx(single.predicted_emissions_g, rel=RTOL)


def test_cost_objective_perf_term_does_not_scale_with_emissions():
    """Regression for the seed precedence bug: the w_perf term multiplied
    the emissions, so the perf weight silently rescaled with job size."""
    sla = SLA(deadline_s=10.0, w_carbon=2.0, w_perf=3.0)
    assert _plan_cost(sla, 100.0, 5.0) == pytest.approx(2.0 * 100.0
                                                        + 3.0 * 5.0 / 10.0)
    # pure-perf objective is independent of emissions magnitude
    perf_only = SLA(deadline_s=10.0, w_carbon=0.0, w_perf=1.0)
    assert _plan_cost(perf_only, 1.0, 5.0) == \
        pytest.approx(_plan_cost(perf_only, 1e9, 5.0))
    # with pure perf weighting the planner starts immediately
    pl = CarbonPlanner(FTNS)
    job = TransferJob("p", 200e9, ("uc",), "tacc",
                      SLA(deadline_s=24 * 3600.0, w_carbon=0.0, w_perf=1.0),
                      T0)
    assert pl.plan(job).start_t == T0
    assert pl.plan_reference(job).start_t == T0


def test_infeasible_fallback_uses_destination_receiver_profile():
    """Regression: the seed fallback hard-coded the tpu_host receiver; the
    receiver must follow the actual destination endpoint."""
    pl = CarbonPlanner([FTN("uc", "skylake", 10.0)])
    job = TransferJob("x", 300e9, ("uc",), "m1", SLA(deadline_s=1.0), T0)
    plan = pl.plan(job)
    assert not plan.feasible
    gbps = pl.throughput.predict("uc", "m1", job.parallelism, job.concurrency)
    expect = transfer_emissions_g_reference(
        discover_path("uc", "m1"), HOST_PROFILES["storage_frontend"],
        HOST_PROFILES["apple_m1"], job.size_bytes, T0, gbps)
    assert plan.predicted_emissions_g == pytest.approx(expect, rel=RTOL)
    wrong = transfer_emissions_g_reference(
        discover_path("uc", "m1"), HOST_PROFILES["storage_frontend"],
        HOST_PROFILES["tpu_host"], job.size_bytes, T0, gbps)
    assert abs(plan.predicted_emissions_g - wrong) > 1.0   # materially fixed


def test_best_start_time_vectorized_matches_scalar_scan():
    p = discover_path("uc", "tacc")
    for dur_h, dl_h in ((1.0, 48), (5.5, 24), (0.25, 51)):
        d = best_start_time(p, now=T0, deadline=T0 + dl_h * 3600.0,
                            predicted_duration_s=dur_h * 3600.0)
        # scalar scan over the same slots
        best_t, best_ci = None, None
        t = T0
        while t <= T0 + dl_h * 3600.0 - dur_h * 3600.0 + 1e-9:
            ci = expected_transfer_ci(p, t, dur_h * 3600.0)
            if best_ci is None or ci < best_ci:
                best_t, best_ci = t, ci
            t += 3600.0
        assert d.start_t == best_t
        assert d.expected_ci == pytest.approx(best_ci, rel=RTOL)
        assert d.expected_ci <= d.baseline_ci + 1e-9


def test_default_field_is_shared_singleton():
    assert default_field() is default_field()


def test_noise_cache_survives_far_flung_queries():
    """A stray query far from the working window (e.g. t=0) must neither
    stall on a dense gap-fill nor corrupt later in-window results."""
    import time

    f = CarbonField()
    f.zone_ci("US-TEX-ERCO", T0 + 3600.0 * np.arange(48))
    t_start = time.perf_counter()
    v = f.zone_ci("US-TEX-ERCO", 0.0)
    assert time.perf_counter() - t_start < 1.0     # not ~476k hashes
    assert float(v) == pytest.approx(calibrated_ci("US-TEX-ERCO", 0.0),
                                     rel=RTOL)
    spread = np.array([0.0, T0, T0 + 50 * 365 * 86400.0])
    np.testing.assert_allclose(
        f.zone_ci("US-TEX-ERCO", spread),
        [calibrated_ci("US-TEX-ERCO", t) for t in spread], rtol=RTOL)
    back = f.zone_ci("US-TEX-ERCO", T0 + 3600.0 * np.arange(48))
    ref = np.array([calibrated_ci("US-TEX-ERCO", T0 + 3600.0 * i)
                    for i in range(48)])
    np.testing.assert_allclose(back, ref, rtol=RTOL)


def test_scalar_fast_paths_match_array_oracle():
    """The control plane's per-step scalar CI paths (zone_ci_scalar /
    path_ci_scalar / hop_ci_scalar / path_device_rate_scalar /
    path_power_w) must reproduce the array engine — the fast-path
    contract applies to scalar shortcuts too."""
    f = CarbonField()
    p = discover_path("uc", "tacc")
    ts = TS[::16]
    for zone in REGIONS:
        vec = f.zone_ci(zone, ts)
        for t, v in zip(ts, vec):
            assert f.zone_ci_scalar(zone, float(t)) == \
                pytest.approx(float(v), rel=RTOL)
    path_vec = f.path_ci(p, ts)
    hop_mat = f.hop_ci_matrix(p, ts)
    w = f._device_weights(p, HOST_PROFILES["storage_frontend"],
                          HOST_PROFILES["cascade_lake"], 8.8, 4, 2)
    for j, t in enumerate(ts):
        t = float(t)
        assert f.path_ci_scalar(p, t) == \
            pytest.approx(float(path_vec[j]), rel=RTOL)
        for i, h in enumerate(p.hops):
            zci = f.zone_ci_scalar(h.zone, t)
            assert f.hop_ci_scalar(h.ip, zci, t) == \
                pytest.approx(float(hop_mat[i, j]), rel=RTOL)
        assert f.path_device_rate_scalar(p, w, t) == \
            pytest.approx(float(w @ hop_mat[:, j]), rel=RTOL)
    assert f.path_power_w(p, HOST_PROFILES["storage_frontend"],
                          HOST_PROFILES["cascade_lake"], 8.8,
                          parallelism=4, concurrency=2) == \
        pytest.approx(float(w.sum()), rel=RTOL)


def test_scalar_fast_path_zone_scale_hook():
    f = CarbonField()
    p = discover_path("uc", "tacc")
    t = float(T0 + 12 * 3600.0)
    scale = lambda z: 2.0 if z == "US-MIDW-MISO" else 1.0  # noqa: E731
    plain = {h.zone: f.zone_ci_scalar(h.zone, t) for h in p.hops}
    counts = {z: sum(1 for h in p.hops if h.zone == z) for z in plain}
    expect = sum(n * plain[z] * (2.0 if z == "US-MIDW-MISO" else 1.0)
                 for z, n in counts.items()) / p.n_hops
    assert f.path_ci_scalar(p, t, zone_scale=scale) == \
        pytest.approx(expect, rel=RTOL)


def test_queue_submit_many_matches_submit():
    from repro.core.scheduler.queue import CarbonAwareQueue

    q1 = CarbonAwareQueue(CarbonPlanner(FTNS))
    q2 = CarbonAwareQueue(CarbonPlanner(FTNS))
    jobs = [dataclasses.replace(j, uuid=f"q{j.uuid}")
            for j in PLANNER_JOBS[:3]]
    singles = [q1.submit(j) for j in jobs]
    batched = q2.submit_many(jobs)
    assert len(q2) == len(jobs)
    for s, b in zip(singles, batched):
        assert (s.start_t, s.source, s.ftn) == (b.start_t, b.source, b.ftn)


def test_pmeter_field_ci_and_emissions():
    from repro.core.carbon.telemetry import Pmeter

    pm = Pmeter("tacc", "cascade_lake", zone="US-TEX-ERCO")
    for i in range(4):
        pm.measure(T0 + 60.0 * i, cpu_util=0.5, mem_util=0.4,
                   tx_gbps=0.0, rx_gbps=5.0)
    assert pm.ci(T0) == pytest.approx(calibrated_ci("US-TEX-ERCO", T0))
    # left-step integral of P·CI over the three 60 s intervals
    expect = sum(pm.power_w(r) * calibrated_ci("US-TEX-ERCO", r.t) * 60.0
                 for r in pm.records[:-1]) / 3.6e6
    assert pm.emissions_g() == pytest.approx(expect, rel=RTOL)
    # zone-less meters price at zero rather than guessing a grid
    assert Pmeter("n0").ci(T0) == 0.0
