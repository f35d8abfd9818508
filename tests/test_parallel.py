"""Process-parallel shard execution: worker-per-shard runs pinned
bit-identical to the sequential oracle, per-quantum barrier pumping, the
streaming gateway over worker pools, worker supervision (kill / hang /
pipe / backend faults recover bit-identically), and fork/spawn safety of
the process-wide field cache."""
import dataclasses
import multiprocessing as mp
import os
import pickle
import signal
import subprocess
import sys
import time

import pytest

from repro.core.carbon.field import CarbonField
from repro.core.carbon.intensity import PAPER_WINDOW_T0
from repro.core.controlplane import (FaultAction, FaultPlan, ShardedFleet,
                                     SupervisionPolicy)
from repro.core.controlplane.parallel import ParallelShardRunner
from repro.core.controlplane.streaming import StreamingGateway
from repro.core.scheduler.overlay import FTN
from repro.core.scheduler.planner import SLA, TransferJob

T0 = PAPER_WINDOW_T0
FTNS = [FTN("uc", "skylake", 10.0), FTN("m1", "apple_m1", 1.2),
        FTN("site_qc", "cascade_lake", 40.0),
        FTN("tacc", "cascade_lake", 10.0)]

HAVE_FORK = "fork" in mp.get_all_start_methods()
# the parallel machinery itself is start-method agnostic; fork is the
# cheap path every CI platform we target has, spawn is covered by the
# dedicated spawn test
MODE = "fork" if HAVE_FORK else "spawn"


def _jobs(n=24, spread_s=1200.0):
    return [TransferJob(f"p{i}", (300 + 53 * i % 1500) * 1e9,
                        ("uc", "site_ne") if i % 2 else ("uc",), "tacc",
                        SLA(deadline_s=(8 + i % 6) * 3600.0),
                        T0 + i * spread_s) for i in range(n)]


def _fleet(parallel, **kw):
    """All fleets on the numpy batch backend: the equality contract is
    bit-level, and numpy planning is deterministic on both sides of the
    process boundary (fork workers force it anyway — XLA does not
    survive a fork)."""
    kw.setdefault("batch_backend", "numpy")
    return ShardedFleet(FTNS, n_shards=3, migration_threshold=250.0,
                        parallel=parallel, **kw)


def _run(fleet, jobs):
    fleet.submit_many(jobs)
    fleet.inject_shock(T0 + 5 * 3600.0, 6.0, duration_s=5 * 3600.0,
                       zones=("CA-QC", "US-NY-NYIS"))
    rep = fleet.run()
    fleet.close()
    return rep


# --- the acceptance pin ------------------------------------------------------
def test_parallel_run_is_bit_identical_to_sequential_oracle():
    """Acceptance: the parallel worker-per-shard run must merge to the
    exact same FleetReport totals as the sequential oracle on identical
    seeds — every total, counter and outcome row, not just within
    tolerance — and the merged ledger audit must stay < 1e-9."""
    jobs = _jobs()
    seq = _run(_fleet("off"), jobs)
    par = _run(_fleet(MODE), jobs)
    assert seq.n_jobs == par.n_jobs == len(jobs)
    assert seq.n_completed == par.n_completed == len(jobs)
    assert par.total_actual_g == seq.total_actual_g
    assert par.total_planned_g == seq.total_planned_g
    assert par.ledger_total_g == seq.ledger_total_g
    assert (par.n_events, par.n_steps, par.migrations, par.replan_events,
            par.plans_changed, par.sla_misses) == \
        (seq.n_events, seq.n_steps, seq.migrations, seq.replan_events,
         seq.plans_changed, seq.sla_misses)
    assert par.sim_span_s == seq.sim_span_s
    assert par.outcomes == seq.outcomes          # same rows, same order
    rel = abs(par.ledger_total_g - par.total_actual_g) \
        / max(par.total_actual_g, 1e-12)
    assert rel < 1e-9


def test_parallel_routing_and_shard_reports():
    jobs = _jobs(10)
    fleet = _fleet(MODE)
    rep = _run(fleet, jobs)
    assert rep.n_completed == len(jobs)
    per_shard = [r.n_jobs for r in fleet.shard_reports]
    assert sum(per_shard) == len(jobs)
    for job in jobs:
        si = fleet.shard_of(job)
        assert any(o.job_uuid == job.uuid
                   for o in fleet.shard_reports[si].outcomes)


def test_parallel_single_submit_routes_to_owning_shard():
    fleet = _fleet(MODE)
    job = _jobs(1)[0]
    fleet.submit(job)
    rep = fleet.run()
    fleet.close()
    assert rep.n_completed == 1
    assert fleet.shard_reports[fleet.shard_of(job)].n_jobs == 1


def test_parallel_validates_mode():
    with pytest.raises(ValueError):
        ShardedFleet(FTNS, parallel="threads")
    with pytest.raises(ValueError):
        # in-process objects cannot cross the spec boundary
        ShardedFleet(FTNS, parallel=MODE, planner=object())


def test_worker_construction_failure_surfaces_its_traceback():
    """A bad controller kwarg only explodes inside the worker; the
    coordinator must raise the worker's shipped traceback (not a bare
    BrokenPipeError from writing to a dead pipe)."""
    fleet = ShardedFleet(FTNS, n_shards=2, batch_backend="numpy",
                         parallel=MODE, bogus_knob=1)
    with pytest.raises(RuntimeError, match="bogus_knob"):
        for job in _jobs(40):
            fleet.submit(job)
        fleet.run()
    fleet.close()


def test_parallel_close_is_idempotent_and_context_managed():
    jobs = _jobs(6)
    with _fleet(MODE) as fleet:
        fleet.submit_many(jobs)
        rep = fleet.run()
        assert rep.n_completed == len(jobs)
        fleet.close()
    fleet.close()                       # second close is a no-op
    with pytest.raises(RuntimeError):
        # workers carry the shard state: a closed fleet must refuse to
        # restart silently on fresh (empty) workers
        fleet.submit(_jobs(1)[0])


# --- per-quantum barrier pumping ---------------------------------------------
def test_pump_all_in_quanta_equals_one_terminal_run():
    """Driving the worker pool in bounded time quanta (the streaming
    gateway's watermark pattern) then finishing with run() must replay
    exactly the run a single drain would have produced — the resumable
    pump contract, now across process boundaries."""
    jobs = _jobs(18)
    seq = _run(_fleet("off"), jobs)

    fleet = _fleet(MODE)
    fleet.submit_many(jobs)
    fleet.inject_shock(T0 + 5 * 3600.0, 6.0, duration_s=5 * 3600.0,
                       zones=("CA-QC", "US-NY-NYIS"))
    n_pumped = 0
    for k in range(1, 9):               # eight 3 h quanta, then drain
        # horizon=inf mirrors the gateway: the quantum cut must not
        # fragment step batches, or the event count drifts vs one run
        n_pumped += fleet.pump_all(T0 + k * 3 * 3600.0,
                                   horizon=float("inf"))
    rep = fleet.run()
    fleet.close()
    assert n_pumped > 0
    assert rep.n_completed == seq.n_completed
    assert rep.total_actual_g == seq.total_actual_g
    assert rep.ledger_total_g == seq.ledger_total_g
    assert (rep.n_events, rep.n_steps) == (seq.n_events, seq.n_steps)


def test_proxy_clock_view_tracks_worker_state():
    fleet = _fleet(MODE)
    job = _jobs(1)[0]
    fleet.submit(job)
    proxy = fleet.controllers[fleet.shard_of(job)]
    assert proxy.events.peek_t() is not None     # optimistic push hint
    assert proxy.events.peek_t() == pytest.approx(job.submitted_t)
    fleet.pump_all(job.submitted_t + 1.0)
    assert proxy.events.now >= job.submitted_t   # authoritative after sync
    fleet.run()
    fleet.close()
    assert proxy.events.peek_t() is None


# --- the streaming gateway over a worker pool --------------------------------
def test_streamed_gateway_over_parallel_fleet_equals_batch():
    """window_s=0 streamed admission over the parallel fleet must replay
    a batch submit_many run event for event (the gateway equivalence pin,
    with the watermark pump now a per-quantum worker barrier)."""
    jobs = _jobs(20, spread_s=700.0)
    batch = _fleet("off")
    batch.submit_many(jobs)
    rb = batch.run()

    par = _fleet(MODE)
    gw = StreamingGateway(par, window_s=0.0)
    rs = gw.run(iter(jobs))
    par.close()
    assert rs.n_completed == rb.n_completed == len(jobs)
    assert rs.total_actual_g == rb.total_actual_g
    assert rs.ledger_total_g == rb.ledger_total_g
    assert rs.n_events == rb.n_events


def test_capacity_gated_backfill_over_parallel_fleet():
    """Capacity deferral + backfill across the IPC boundary: completions
    ship back as data and re-fire the gateway's hooks, so deferred jobs
    still promote (at quantum granularity) and every job completes with
    the exact ledger audit intact."""
    jobs = _jobs(20, spread_s=700.0)
    fleet = _fleet(MODE)
    gw = StreamingGateway(fleet, window_s=900.0, max_inflight=4,
                          backfill=True)
    rep = gw.run(iter(jobs))
    fleet.close()
    st = gw.stats()
    assert rep.n_completed == len(jobs)
    assert st.n_deferred > 0
    assert st.n_promotions >= st.n_deferred
    rel = abs(rep.ledger_total_g - rep.total_actual_g) \
        / max(rep.total_actual_g, 1e-12)
    assert rel < 1e-9


# --- worker supervision: kills, hangs, pipe loss, backend faults -------------
def _assert_identical(a, b):
    """Bit-identical FleetReports modulo wall clock and the degradation
    trail (the faulted run records its recoveries; the oracle has none)."""
    for f in dataclasses.fields(a):
        if f.name in ("wall_s", "jobs_per_s", "degradations"):
            continue
        assert getattr(a, f.name) == getattr(b, f.name), f.name


def _drive(fleet, quanta=8, quantum_h=1.0):
    for k in range(1, quanta + 1):
        fleet.pump_all(T0 + k * quantum_h * 3600.0, strict=True,
                       horizon=float("inf"))
    return fleet.run()


def test_mid_run_worker_kill_recovers_bit_identical():
    """Satellite: SIGKILL a worker between pump quanta. The supervisor
    must respawn it from the last per-shard checkpoint, replay the
    command delta, and merge a report equal to the sequential oracle —
    with the recovery surfaced in the report, not swallowed."""
    jobs = _jobs(18)
    seq = _fleet("off")
    seq.submit_many(jobs)
    oracle = _drive(seq)
    assert oracle.degradations == ()

    fleet = _fleet(MODE, supervision=SupervisionPolicy(checkpoint_every=2))
    fleet.submit_many(jobs)
    for k in range(1, 9):
        fleet.pump_all(T0 + k * 3600.0, strict=True, horizon=float("inf"))
        if k in (3, 6):                  # two kills, straddling checkpoints
            victim = fleet._runner._handles[k % 3]
            os.kill(victim.proc.pid, signal.SIGKILL)
            victim.proc.join(5)
    rep = fleet.run()
    fleet.close()

    _assert_identical(rep, oracle)
    assert len(rep.degradations) == 2
    assert all("respawned" in d for d in rep.degradations)
    assert "degradations:" in rep.summary()
    assert len(fleet._runner.recoveries) == 2
    for rec in fleet._runner.recoveries:
        assert rec["outcome"] == "respawn"
        assert rec["wall_s"] >= 0.0


def test_worker_kill_without_checkpoints_replays_full_journal():
    """No checkpoint cadence: recovery must rebuild the dead shard from
    scratch by replaying its entire command journal, still exactly."""
    jobs = _jobs(10)
    seq = _fleet("off")
    seq.submit_many(jobs)
    oracle = _drive(seq, quanta=4)

    fleet = _fleet(MODE)                 # default policy: no checkpoints
    fleet.submit_many(jobs)
    for k in range(1, 5):
        fleet.pump_all(T0 + k * 3600.0, strict=True, horizon=float("inf"))
        if k == 2:
            os.kill(fleet._runner._handles[0].proc.pid, signal.SIGKILL)
    rep = fleet.run()
    fleet.close()
    _assert_identical(rep, oracle)
    assert any("respawned" in d for d in rep.degradations)
    assert fleet._runner.recoveries[0]["from_checkpoint"] is False


def test_fault_plan_full_ladder_recovers_bit_identical():
    """The whole fault matrix in one supervised run — worker kill, a
    worker-reported backend fault, a severed pipe, and a hung worker
    (caught by the command timeout) — and the merged report still equals
    the no-fault sequential oracle with the ledger audit exact."""
    jobs = _jobs(18)
    seq = _fleet("off")
    seq.submit_many(jobs)
    seq.inject_shock(T0 + 5 * 3600.0, 6.0, duration_s=5 * 3600.0,
                     zones=("CA-QC", "US-NY-NYIS"))
    oracle = _drive(seq)

    plan = FaultPlan(actions=(
        FaultAction(quantum=1, shard=0, kind="kill"),
        FaultAction(quantum=2, shard=1, kind="backend"),
        FaultAction(quantum=3, shard=2, kind="kill"),
        FaultAction(quantum=4, shard=1, kind="pipe"),
        FaultAction(quantum=5, shard=0, kind="hang", severity_s=2.0),
    ))
    pol = SupervisionPolicy(command_timeout_s=0.75, checkpoint_every=2)
    fleet = _fleet(MODE, supervision=pol, fault_plan=plan)
    fleet.submit_many(jobs)
    fleet.inject_shock(T0 + 5 * 3600.0, 6.0, duration_s=5 * 3600.0,
                       zones=("CA-QC", "US-NY-NYIS"))
    rep = _drive(fleet)
    fleet.close()

    _assert_identical(rep, oracle)
    rel = abs(rep.ledger_total_g - rep.total_actual_g) \
        / max(rep.total_actual_g, 1e-12)
    assert rel < 1e-9
    recs = fleet._runner.recoveries
    assert len(recs) >= 5
    reasons = " ".join(r["reason"] for r in recs)
    assert "WorkerDied" in reasons
    assert "WorkerTimeout" in reasons


def test_backend_fault_downgrades_shard_to_numpy():
    """Degradation ladder rung 1: a worker that *reports* a failure (is
    alive, spoke a traceback) on a non-numpy shard backend respawns with
    batch_backend='numpy' first — and, pre-fault Pallas having planned
    nothing yet, the run still matches the numpy oracle exactly."""
    from repro.core.scheduler.grid_jax import HAVE_JAX
    if not HAVE_JAX:
        pytest.skip("jax not importable")
    jobs = _jobs(8)
    seq = _fleet("off")
    seq.submit_many(jobs)
    oracle = _drive(seq, quanta=2, quantum_h=2.0)

    plan = FaultPlan(actions=(
        FaultAction(quantum=0, shard=1, kind="backend"),))
    fleet = _fleet(MODE, shard_backend="pallas",
                   supervision=SupervisionPolicy(), fault_plan=plan)
    fleet.submit_many(jobs)
    rep = _drive(fleet, quanta=2, quantum_h=2.0)
    fleet.close()
    _assert_identical(rep, oracle)
    assert any("pallas -> numpy" in d for d in rep.degradations), \
        rep.degradations


def test_fault_plan_requires_timeout_for_hangs():
    plan = FaultPlan(actions=(
        FaultAction(quantum=0, shard=0, kind="hang", severity_s=1.0),))
    with pytest.raises(ValueError, match="command_timeout_s"):
        _fleet(MODE, fault_plan=plan)
    with pytest.raises(ValueError, match="unknown fault kind"):
        _fleet(MODE, fault_plan=FaultPlan(actions=(
            FaultAction(quantum=0, shard=0, kind="gremlin"),)))
    with pytest.raises(ValueError, match="fault_plan"):
        _fleet("off", fault_plan=plan)


def test_seeded_fault_plan_is_deterministic():
    a = FaultPlan.seeded(3, seed=7, horizon=6, kills=2, backend_faults=1,
                         hangs=1)
    b = FaultPlan.seeded(3, seed=7, horizon=6, kills=2, backend_faults=1,
                         hangs=1)
    c = FaultPlan.seeded(3, seed=8, horizon=6, kills=2, backend_faults=1,
                         hangs=1)
    assert a.actions == b.actions
    assert a.actions != c.actions
    assert all(0 <= act.shard < 3 and 0 <= act.quantum < 6
               for act in a.actions)


def test_hung_worker_cannot_wedge_close():
    """Satellite regression: close() must escalate join-timeout ->
    terminate() -> kill() instead of blocking on a worker that will
    never answer the stop command."""
    fleet = _fleet(MODE)
    fleet.submit(_jobs(1)[0])            # starts the workers
    h = fleet._runner._handles[0]
    h.send("_fault", ("sleep", 30.0))    # worker naps through its stop
    t0 = time.monotonic()
    h.close(timeout=0.5)
    assert time.monotonic() - t0 < 10.0, "close() waited for the nap"
    fleet.close()                        # remaining handles + the closed
    assert time.monotonic() - t0 < 20.0  # one reap fast and idempotent


def test_runner_del_is_idempotent_with_close():
    """Satellite regression: __del__ after close() (or on a half-built
    runner) must be a silent no-op — interpreter shutdown runs it with
    module globals already torn down."""
    fleet = _fleet(MODE)
    fleet.submit(_jobs(1)[0])
    runner = fleet._runner
    fleet.run()
    fleet.close()
    runner.__del__()                     # after close: no-op
    runner.__del__()                     # and again
    half = ParallelShardRunner.__new__(ParallelShardRunner)
    half.__del__()                       # never __init__-ed: no-op

    fleet2 = _fleet(MODE)
    fleet2.submit(_jobs(1)[0])
    runner2 = fleet2._runner
    handles = list(runner2._handles)     # close() hands the list off
    runner2.__del__()                    # dropped without close(): reaps
    assert runner2._closed
    for h in handles:
        with pytest.raises(ValueError):  # multiprocessing's closed-proc
            h.proc.is_alive()            # marker: the worker was reaped


# --- spawn-mode worker (ships the frozen snapshot instead of forking) --------
def test_spawn_mode_matches_sequential():
    if "spawn" not in mp.get_all_start_methods():
        pytest.skip("no spawn start method")
    jobs = _jobs(8)
    seq = _run(_fleet("off"), jobs)
    par = _run(_fleet("spawn"), jobs)
    assert par.n_completed == seq.n_completed == len(jobs)
    assert par.total_actual_g == seq.total_actual_g
    assert par.ledger_total_g == seq.ledger_total_g
    assert (par.n_events, par.n_steps) == (seq.n_events, seq.n_steps)


# --- default_field() fork/spawn safety ---------------------------------------
def test_forked_child_adopts_inherited_default_field():
    if not HAVE_FORK:
        pytest.skip("no fork start method")
    import numpy as np

    from repro.core.carbon import field as field_mod

    f = field_mod.default_field()
    ts = T0 + 3600.0 * np.arange(8)
    parent_vals = f.zone_ci("US-TEX-ERCO", ts)

    def child(conn):
        g = field_mod.default_field()
        # the inherited warm cache is adopted as this process's private
        # copy (re-stamped, not re-hashed): the range is already dense
        conn.send((field_mod._DEFAULT_PID == os.getpid(),
                   g.zone_ci("US-TEX-ERCO", ts).tolist()))
        conn.close()

    ctx = mp.get_context("fork")
    a, b = ctx.Pipe()
    p = ctx.Process(target=child, args=(b,))
    p.start()
    assert a.poll(60), "forked child hung"
    restamped, child_vals = a.recv()
    p.join(10)
    assert restamped
    assert child_vals == parent_vals.tolist()


def test_spawned_worker_rebuilds_default_field_from_frozen_snapshot(
        tmp_path):
    """Satellite regression: a spawned worker must not silently re-warm a
    divergent process-wide cache. With the coordinator's snapshot
    installed, the worker's default_field() must come back pre-warmed
    (zero re-hashing over the snapshot range) and bit-identical."""
    import numpy as np

    f = CarbonField()
    ts = T0 + 3600.0 * np.arange(12)
    want = f.zone_ci("CA-QC", ts)
    snap = tmp_path / "frozen.pkl"
    snap.write_bytes(pickle.dumps(f.freeze()))
    out = tmp_path / "vals.npy"
    code = f"""
import pickle, numpy as np
from repro.core.carbon import field as field_mod

frozen = pickle.loads(open({str(snap)!r}, "rb").read())
field_mod.install_frozen_default(frozen)
f = field_mod.default_field()
# the snapshot must arrive warm: hashing even one hour in the snapshot
# range means the worker silently rebuilt a divergent cache
f._zone_noise._hash = lambda *a: (_ for _ in ()).throw(
    AssertionError("re-hashed inside the snapshot range"))
ts = {T0!r} + 3600.0 * np.arange(12)
np.save({str(out)!r}, f.zone_ci("CA-QC", ts))
print("OK")
"""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(root, "src") + os.pathsep \
        + env.get("PYTHONPATH", "")
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "OK" in proc.stdout
    got = np.load(out)
    assert got.tolist() == want.tolist()


# --- effective CPU count resolves the process's own cgroup -------------------
def _mk_cgroup_tree(tmp_path, layout, self_path):
    """Build a fake cgroup v2 tree: ``layout`` maps a relative cgroup
    path ('' = root) to its cpu.max content; ``self_path`` becomes the
    /proc/self/cgroup v2 entry."""
    root = tmp_path / "cgroup"
    for rel, content in layout.items():
        d = root / rel if rel else root
        d.mkdir(parents=True, exist_ok=True)
        (d / "cpu.max").write_text(content)
    proc = tmp_path / "proc_self_cgroup"
    proc.write_text(f"0::{self_path}\n")
    return str(root), str(proc)


def test_cgroup_quota_found_on_own_nested_cgroup_not_root():
    """The root says 'max' (unlimited) while the process's own nested
    cgroup carries the throttle — the systemd-slice / cgroup-namespaced
    container shape the root-only read used to miss."""
    from repro.core.controlplane.parallel import _cgroup_cpu_quota
    import pathlib
    import tempfile
    with tempfile.TemporaryDirectory() as td:
        tmp = pathlib.Path(td)
        root, proc = _mk_cgroup_tree(
            tmp,
            {"": "max 100000",
             "a.slice": "max 100000",
             "a.slice/runner": "250000 100000"},
            "/a.slice/runner")
        assert _cgroup_cpu_quota(root, proc) == (3, "/a.slice/runner")


def test_cgroup_quota_takes_tightest_ancestor():
    from repro.core.controlplane.parallel import _cgroup_cpu_quota
    import pathlib
    import tempfile
    with tempfile.TemporaryDirectory() as td:
        tmp = pathlib.Path(td)
        root, proc = _mk_cgroup_tree(
            tmp,
            {"": "max 100000",
             "a.slice": "200000 100000",     # 2 CPUs at the slice
             "a.slice/runner": "600000 100000"},  # looser leaf: 6
            "/a.slice/runner")
        assert _cgroup_cpu_quota(root, proc) == (2, "/a.slice")


def test_cgroup_quota_none_without_any_limit():
    from repro.core.controlplane.parallel import _cgroup_cpu_quota
    import pathlib
    import tempfile
    with tempfile.TemporaryDirectory() as td:
        tmp = pathlib.Path(td)
        root, proc = _mk_cgroup_tree(
            tmp, {"": "max 100000", "a": "max 100000"}, "/a")
        assert _cgroup_cpu_quota(root, proc) is None
        # v1-only host: no cpu.max files, no /proc v2 entry
        assert _cgroup_cpu_quota(str(tmp / "nope"),
                                 str(tmp / "missing")) is None


def test_effective_cpu_count_records_quota_in_note():
    from repro.core.controlplane.parallel import effective_cpu_count
    eff, note = effective_cpu_count()
    assert eff >= 1
    assert "effective cpus" in note
    assert ("cgroup cpu.max" in note) or ("no cgroup quota" in note)
