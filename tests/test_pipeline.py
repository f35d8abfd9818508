"""Async admission pipeline: pipelined (double-buffered) streamed runs must
be bit-identical to the ``pipeline="off"`` oracle — merge totals, trace and
ledger audit — sequentially and over worker pools; the adaptive pump
quantum schedule must be a pinned pure function (coarse idle, fine near
boundaries) that never changes outcomes; a crash between plan dispatch and
batch close must replay the in-flight batch exactly from the last
checkpoint."""
import dataclasses
import multiprocessing as mp

import pytest

from repro.core.carbon.intensity import PAPER_WINDOW_T0
from repro.core.controlplane import (FleetController, PumpQuanta,
                                     ShardedFleet, StreamingGateway,
                                     quantum_schedule)
from repro.core.controlplane import persistence
from repro.core.scheduler.overlay import FTN
from repro.core.scheduler.planner import CarbonPlanner
from repro.core.workloads import PoissonArrivals, UniformSizes, Workload

T0 = PAPER_WINDOW_T0
FTNS = [FTN("uc", "skylake", 10.0), FTN("m1", "apple_m1", 1.2),
        FTN("site_qc", "cascade_lake", 40.0),
        FTN("tacc", "cascade_lake", 10.0)]
END = T0 + 24 * 3600.0

HAVE_FORK = "fork" in mp.get_all_start_methods()
MODE = "fork" if HAVE_FORK else "spawn"
QUANTA = PumpQuanta(coarse_s=3600.0, fine_s=300.0, band_s=900.0)


def _jobs(n=36, seed=5):
    w = Workload("eq", PoissonArrivals(rate_per_h=6.0),
                 UniformSizes(lo_gb=80.0, hi_gb=600.0),
                 replica_sets=(("uc",), ("uc", "site_qc")),
                 deadline_h=(6.0, 14.0))
    return list(w.jobs(seed, T0, 8 * 3600.0))[:n]


def _fleet(parallel="off", **kw):
    kw.setdefault("batch_backend", "numpy")
    if parallel != "off":
        kw.setdefault("shard_backend", "numpy")
    return ShardedFleet(FTNS, n_shards=3, migration_threshold=250.0,
                        parallel=parallel, **kw)


def _totals(rep):
    return (rep.n_jobs, rep.n_completed, rep.total_planned_g,
            rep.total_actual_g, rep.ledger_total_g, rep.migrations,
            rep.sla_misses, rep.n_events, rep.n_steps)


def _stream(parallel="off", *, jobs=None, obs=False, **gw_kw):
    fleet = _fleet(parallel, obs=obs)
    fleet.inject_shock(T0 + 5 * 3600.0, 6.0, duration_s=5 * 3600.0,
                       zones=("CA-QC", "US-NY-NYIS"))
    gw = StreamingGateway(fleet, window_s=900.0, max_batch=16, **gw_kw)
    rep = gw.run(jobs if jobs is not None else _jobs(), until=END)
    close = getattr(fleet, "close", None)
    if close is not None:
        close()
    return rep, gw


# --- the quantum schedule is a pinned pure function --------------------------
def test_quantum_schedule_coarse_idle_fine_near_boundary():
    """Idle spans stride coarse_s; inside band_s of a boundary (or of the
    pump bound itself) the schedule drops to fine_s and lands exactly on
    the boundary. Pinned literally: this is the contract, not a sample."""
    cuts = quantum_schedule(0.0, 10000.0, [3600.0], QUANTA)
    assert cuts == [2700.0, 3000.0, 3300.0, 3600.0,
                    7200.0, 9100.0, 9400.0, 9700.0, 10000.0]


def test_quantum_schedule_properties():
    cuts = quantum_schedule(T0, T0 + 86400.0, [T0 + 7 * 3600.0], QUANTA)
    assert cuts == sorted(cuts) and len(set(cuts)) == len(cuts)
    assert cuts[-1] == T0 + 86400.0
    assert T0 + 7 * 3600.0 in cuts          # lands exactly on the boundary
    assert all(c > T0 for c in cuts)
    # determinism: same inputs, same cuts
    assert cuts == quantum_schedule(T0, T0 + 86400.0,
                                    [T0 + 7 * 3600.0], QUANTA)


def test_quantum_schedule_degenerate_spans_collapse():
    assert quantum_schedule(5.0, 5.0, [], QUANTA) == [5.0]
    assert quantum_schedule(9.0, 5.0, [], QUANTA) == [5.0]
    assert quantum_schedule(0.0, float("inf"), [], QUANTA) == [float("inf")]
    # boundaries outside (t0, t1) are ignored
    assert quantum_schedule(0.0, 500.0, [-10.0, 0.0, 500.0, 900.0],
                            QUANTA) == [300.0, 500.0]


def test_pump_quanta_validation():
    with pytest.raises(ValueError):
        PumpQuanta(fine_s=0.0)
    with pytest.raises(ValueError):
        PumpQuanta(coarse_s=10.0, fine_s=60.0)
    with pytest.raises(ValueError):
        PumpQuanta(band_s=-1.0)


def test_gateway_kwarg_validation():
    fleet = _fleet()
    with pytest.raises(ValueError):
        StreamingGateway(fleet, pipeline="sideways")
    with pytest.raises(ValueError):
        StreamingGateway(fleet, frontends="rack")
    with pytest.raises(TypeError):
        StreamingGateway(fleet, quanta=300.0)


# --- pipelined == sequential oracle, bit for bit -----------------------------
def test_pipelined_matches_off_sequential_with_trace():
    r_off, _ = _stream("off", obs=True, pipeline="off")
    r_on, gw = _stream("off", obs=True, pipeline="on")
    assert _totals(r_off) == _totals(r_on)
    assert r_off.trace == r_on.trace
    rel = abs(r_on.ledger_total_g - r_on.total_actual_g) \
        / max(r_on.total_actual_g, 1e-12)
    assert rel < 1e-9
    st = gw.stats()
    assert st.pipeline == "on"
    assert st.n_pipelined_batches == st.n_batches
    assert st.plan_wall_s > 0.0


def test_pipelined_matches_off_over_worker_pool():
    r_off, _ = _stream("off", obs=True, pipeline="off")
    r_par, gw = _stream(MODE, obs=True, pipeline="on")
    assert _totals(r_off) == _totals(r_par)
    assert r_off.trace == r_par.trace
    st = gw.stats()
    assert st.n_pipelined_batches == st.n_batches


def test_spawn_pipelined_matches_off():
    if "spawn" not in mp.get_all_start_methods():
        pytest.skip("no spawn start method")
    jobs = _jobs(12)
    r_off, _ = _stream("off", jobs=jobs, pipeline="off")
    r_sp, _ = _stream("spawn", jobs=jobs, pipeline="on")
    assert _totals(r_off) == _totals(r_sp)


def test_auto_resolves_to_on():
    fleet = _fleet()
    gw = StreamingGateway(fleet, pipeline="auto")
    assert gw.pipeline == "on"


def test_off_mode_stats_are_zero():
    rep, gw = _stream("off", pipeline="off")
    st = gw.stats()
    assert st.n_pipelined_batches == 0
    assert st.plan_wall_s == 0.0 and st.stall_wall_s == 0.0
    assert st.overlap_fraction == 0.0 and st.admit_stall_ms == 0.0


def test_pipeline_metrics_recorded():
    rep, gw = _stream("off", obs=True, pipeline="on")
    names = {e["name"] for entries in rep.metrics.values()
             for e in entries}
    assert "gw_pipeline_batches_total" in names
    assert "gw_pipeline_plan_wall_s" in names


# --- planner-thread isolation: private field/registry, degradations ----------
def test_batch_planner_clone_is_private():
    """The batch planner must share no mutable state with the
    coordinator's planner: the carbon field's noise tables re-anchor via
    a non-atomic del+rebind on lookup and registry instruments are plain
    ``+=`` writes, so the pipelined planner thread gets its own copies.
    Over a sharded fleet (whose fleet-level throughput model is never
    observed into) the clone is overlap-safe."""
    fleet = _fleet(obs=True)
    gw = StreamingGateway(fleet, pipeline="on")
    bp, pl = gw._batch_planner, gw.planner
    assert bp is not pl
    assert bp.field is not pl.field
    assert bp._metrics is not None and bp._metrics is not pl._metrics
    assert gw._overlap_safe


def _counter_total(rep, name):
    return sum(e["value"] for e in rep.metrics["counters"]
               if e["name"] == name)


def test_planner_metrics_fold_is_exact_across_modes():
    """The clone's private registry folds back into the shared one at
    every checkpoint and at run end (reset after each absorb), so the
    merged planner counters of a pipelined, checkpointing run equal the
    sequential oracle's exactly — nothing dropped, nothing counted
    twice."""
    r_off, _ = _stream("off", obs=True, pipeline="off")
    r_on, _ = _stream("off", obs=True, pipeline="on",
                      checkpoint_every_s=3600.0)
    for name in ("planner_plan_batches_total",
                 "planner_cells_scored_total"):
        tot = _counter_total(r_on, name)
        assert tot > 0
        assert tot == _counter_total(r_off, name)


def test_subclass_planner_pipelined_degrades_to_inline_and_matches():
    """A planner subclass is the admission policy — it cannot be cloned,
    and completion hooks re-enter it from the coordinator mid-pump, so
    ``pipeline="on"`` must keep the bit-identical contract by planning
    at the batch close: zero pipelined batches, same totals as off."""
    class TaggedPlanner(CarbonPlanner):
        pass

    def _run(pipeline):
        fleet = _fleet()
        gw = StreamingGateway(fleet, window_s=900.0, max_batch=16,
                              planner=TaggedPlanner(FTNS,
                                                    batch_backend="numpy"),
                              pipeline=pipeline)
        rep = gw.run(_jobs(), until=END)
        return rep, gw

    r_off, _ = _run("off")
    r_on, gw = _run("on")
    assert gw._batch_planner is gw.planner
    assert not gw._overlap_safe
    assert gw.stats().n_pipelined_batches == 0
    assert _totals(r_off) == _totals(r_on)


def test_bare_controller_pipelined_degrades_and_matches():
    """A bare FleetController's transfer engine observes achieved
    throughput into its planner's model as jobs step — between plan
    dispatch and claim — so overlapping would diverge from the
    plan-at-close oracle. The gateway detects the shared model, keeps
    the private clone but plans inline, and still matches off bit for
    bit."""
    jobs = _jobs(18)

    def _run(pipeline):
        ctl = FleetController(FTNS, migration_threshold=250.0,
                              planner=CarbonPlanner(
                                  FTNS, batch_backend="numpy"))
        gw = StreamingGateway(ctl, window_s=900.0, max_batch=16,
                              pipeline=pipeline)
        rep = gw.run(jobs, until=END)
        return rep, gw

    r_off, _ = _run("off")
    r_on, gw = _run("on")
    assert gw._batch_planner is not gw.planner
    assert not gw._overlap_safe
    assert gw.stats().n_pipelined_batches == 0
    assert _totals(r_off) == _totals(r_on)


# --- adaptive quanta / per-shard frontends are outcome-neutral ---------------
def test_quanta_pump_schedule_is_outcome_neutral():
    r_plain, _ = _stream("off", pipeline="on")
    r_q, _ = _stream("off", pipeline="on", quanta=QUANTA)
    assert _totals(r_plain) == _totals(r_q)


def test_quanta_over_worker_pool_matches_sequential():
    r_off, _ = _stream("off", pipeline="off")
    r_q, _ = _stream(MODE, pipeline="on", quanta=QUANTA)
    assert _totals(r_off) == _totals(r_q)


def test_shard_frontends_plan_bit_identically():
    r_fleet, _ = _stream("off", obs=True, pipeline="on", frontends="fleet")
    r_shard, _ = _stream("off", obs=True, pipeline="on", frontends="shard")
    assert _totals(r_fleet) == _totals(r_shard)
    assert r_fleet.trace == r_shard.trace


# --- durability: crash between plan dispatch and batch close -----------------
def test_mid_overlap_crash_replays_inflight_batch_exactly():
    """Kill the run while batch k's plan is in flight on the planner
    thread (the watermark pump raises — exactly the dispatch..close
    window). The in-flight batch was never consumed, so the restored
    gateway re-pulls and replans it and the resumed run matches the
    uninterrupted oracle bit for bit."""
    jobs = _jobs()
    oracle, _ = _stream("off", pipeline="on",
                        checkpoint_every_s=3600.0)

    fleet = _fleet("off")
    fleet.inject_shock(T0 + 5 * 3600.0, 6.0, duration_s=5 * 3600.0,
                       zones=("CA-QC", "US-NY-NYIS"))
    gw = StreamingGateway(fleet, window_s=900.0, max_batch=16,
                          pipeline="on", checkpoint_every_s=3600.0)
    pumps = {"n": 0}
    orig = gw._pump_all

    def crashing_pump(t, **kw):
        pumps["n"] += 1
        if pumps["n"] == 8:
            raise RuntimeError("simulated coordinator crash mid-overlap")
        return orig(t, **kw)

    gw._pump_all = crashing_pump
    with pytest.raises(RuntimeError, match="mid-overlap"):
        gw.run(jobs, until=END)
    assert gw.last_checkpoint is not None
    consumed_at_cut = gw._consumed
    assert 0 < consumed_at_cut < len(jobs)

    gw2 = persistence.restore_gateway(gw.last_checkpoint)
    assert gw2.pipeline == "on"
    assert gw2._consumed <= consumed_at_cut
    rep2 = gw2.resume(jobs, until=END)
    assert _totals(rep2) == _totals(oracle)
    rel = abs(rep2.ledger_total_g - rep2.total_actual_g) \
        / max(rep2.total_actual_g, 1e-12)
    assert rel < 1e-9


def test_pipeline_config_checkpoints_and_restores():
    ckpts = []
    fleet = _fleet("off")
    gw = StreamingGateway(fleet, window_s=900.0, max_batch=8,
                          pipeline="on", quanta=QUANTA, frontends="shard",
                          checkpoint_every_s=3600.0,
                          checkpoint_fn=ckpts.append)
    rep = gw.run(_jobs(24), until=END)
    assert ckpts
    gw2 = persistence.restore_gateway(ckpts[-1])
    assert (gw2.pipeline, gw2.frontends, gw2.quanta) == ("on", "shard",
                                                         QUANTA)
    rep2 = gw2.resume(_jobs(24), until=END)
    assert _totals(rep) == _totals(rep2)
    # wall occupancy resumes from the cut, it never goes backwards
    assert gw2.stats().n_pipelined_batches >= 1
