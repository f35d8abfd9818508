"""Durable fleet checkpoints: crash-kill-resume replay equivalence.

The contract under test (``core/controlplane/persistence.py``): a run
checkpointed at ANY quantum boundary, killed, and restored resumes
**bit-identical** to the run that was never interrupted — every
``FleetReport`` total, counter and outcome row equal under ``==``, not
approximately. Cuts are exercised four ways: plain fixed cuts, a
hypothesis sweep over arbitrary cut instants, a checkpoint that crosses
execution modes (parallel -> off and back), and an actual ``os._exit``
process kill with restore-from-disk in the parent. The soak test (opt-in
via RUN_SOAK=1) layers seeded worker faults and two whole-coordinator
kill/restore cycles on top and audits the merged ledger.
"""
import dataclasses
import os
import pickle
import subprocess
import sys
import textwrap

import multiprocessing as mp
import pytest

from _hyp import given, hst, settings
from repro.core.carbon.intensity import PAPER_WINDOW_T0 as T0
from repro.core.controlplane import (FaultPlan, FleetController, ShardedFleet,
                                     StreamingGateway, SupervisionPolicy)
from repro.core.controlplane import persistence
from repro.core.scheduler.overlay import FTN
from repro.core.scheduler.planner import SLA, TransferJob

HAVE_FORK = "fork" in mp.get_all_start_methods()
MODE = "fork" if HAVE_FORK else "spawn"
INF = float("inf")

FTNS = [FTN("uc", "skylake", 10.0), FTN("m1", "apple_m1", 1.2),
        FTN("site_qc", "cascade_lake", 40.0),
        FTN("tacc", "cascade_lake", 10.0)]


def _jobs(n=12):
    return [TransferJob(f"s{i}", (300 + 100 * i) * 1e9,
                        ("uc", "site_ne") if i % 2 else ("uc",), "tacc",
                        SLA(deadline_s=(8 + i % 6) * 3600.0),
                        T0 + i * 1200.0) for i in range(n)]


def _assert_identical(a, b, *, ignore=("wall_s", "jobs_per_s")):
    """Bit-identical FleetReports: every field equal except wall-clock."""
    for f in dataclasses.fields(a):
        if f.name in ignore:
            continue
        assert getattr(a, f.name) == getattr(b, f.name), f.name


def _mk_controller():
    ctl = FleetController(FTNS, migration_threshold=250.0)
    for job in _jobs():
        ctl.submit(job)
    ctl.inject_shock(T0 + 5 * 3600.0, 6.0, duration_s=5 * 3600.0,
                     zones=("CA-QC", "US-NY-NYIS"))
    return ctl


def _mk_sharded(**kw):
    fl = ShardedFleet(FTNS, n_shards=3, shard_backend="numpy",
                      migration_threshold=250.0, **kw)
    fl.submit_many(_jobs())
    fl.inject_shock(T0 + 5 * 3600.0, 6.0, duration_s=5 * 3600.0,
                    zones=("CA-QC", "US-NY-NYIS"))
    return fl


@pytest.fixture(scope="module")
def controller_oracle():
    return _mk_controller().run()


@pytest.fixture(scope="module")
def sharded_oracle():
    return _mk_sharded().run()


# --- bare-controller checkpoints ---------------------------------------------
def test_controller_round_trip_is_bit_identical(controller_oracle):
    for cut_h in (0.5, 2.0, 4.7, 9.0, 30.0):
        ctl = _mk_controller()
        ctl.pump(T0 + cut_h * 3600.0, strict=True, horizon=INF)
        ckpt = persistence.capture(ctl)
        # the checkpoint itself must survive the wire (pickle round-trip)
        ckpt = pickle.loads(pickle.dumps(ckpt))
        restored = persistence.restore(ckpt)
        assert restored is not ctl
        _assert_identical(restored.run(), controller_oracle)


@settings(max_examples=8, deadline=None)
@given(cut_h=hst.floats(min_value=0.1, max_value=40.0,
                        allow_nan=False, allow_infinity=False))
def test_restore_equivalence_at_arbitrary_cut(cut_h, controller_oracle):
    """Crash-kill-resume replay equivalence, property-tested: cutting the
    run at ANY instant and restoring from the checkpoint reproduces the
    uninterrupted oracle exactly."""
    ctl = _mk_controller()
    ctl.pump(T0 + cut_h * 3600.0, strict=True, horizon=INF)
    ckpt = pickle.loads(pickle.dumps(persistence.capture(ctl)))
    _assert_identical(persistence.restore(ckpt).run(), controller_oracle)


def test_checkpoint_drops_derived_state_but_replays_it():
    """Caches and closures are rebuilt, not shipped: the blob holds no
    device-weight closures, and the restored controller still priced its
    in-flight routes (power segments repopulated from the route log)."""
    ctl = _mk_controller()
    # 11.1h lands inside the green start window the planner defers this
    # workload into, so several transfers are genuinely mid-flight here
    ctl.pump(T0 + 11.1 * 3600.0, strict=True, horizon=INF)
    n_active = len(ctl._active)
    assert n_active > 0
    restored = persistence.restore(persistence.capture(ctl))
    assert len(restored._active) == n_active
    for rec in restored._active.values():
        assert rec.power_segments, "power closures not replayed"
        assert callable(rec.power_segments[-1][1])


# --- sharded fleets, including cross-mode ------------------------------------
def test_sharded_sequential_round_trip(sharded_oracle):
    fl = _mk_sharded()
    fl.pump_all(T0 + 4 * 3600.0, strict=True, horizon=INF)
    ckpt = pickle.loads(pickle.dumps(persistence.capture(fl)))
    assert ckpt.kind == "sharded"
    assert len(ckpt.shards) == 3
    assert ckpt.sim_now >= T0 + 3 * 3600.0
    _assert_identical(persistence.restore(ckpt).run(), sharded_oracle)


def test_parallel_checkpoint_restores_across_modes(sharded_oracle):
    """Blobs are full controllers, so a checkpoint cut under worker
    processes restores under 'off' (the audit path) and back under
    workers, both bit-identical to the sequential oracle."""
    fl = _mk_sharded(parallel=MODE)
    fl.pump_all(T0 + 4 * 3600.0, strict=True, horizon=INF)
    ckpt = persistence.capture(fl)
    fl.close()

    _assert_identical(persistence.restore(ckpt, parallel="off").run(),
                      sharded_oracle)
    with persistence.restore(ckpt, parallel=MODE) as fl2:
        _assert_identical(fl2.run(), sharded_oracle)


def test_restore_preserves_supervision_policy():
    pol = SupervisionPolicy(command_timeout_s=4.0, checkpoint_every=2)
    fl = _mk_sharded(parallel=MODE, supervision=pol)
    fl.pump_all(T0 + 3600.0, strict=True, horizon=INF)
    ckpt = persistence.capture(fl)
    fl.close()
    fl2 = persistence.restore(ckpt, parallel=MODE)
    try:
        assert fl2.supervision == pol
    finally:
        fl2.close()


# --- streaming gateway -------------------------------------------------------
def _mk_gateway(**kw):
    return StreamingGateway(
        ShardedFleet(FTNS, n_shards=3, shard_backend="numpy",
                     migration_threshold=250.0),
        window_s=1800.0, max_inflight=4, backfill=True, **kw)


def test_gateway_checkpoint_cadence_does_not_perturb_the_run():
    plain = _mk_gateway().run(_jobs())
    caps = []
    rep = _mk_gateway(checkpoint_every_s=3600.0,
                      checkpoint_fn=caps.append).run(_jobs())
    _assert_identical(rep, plain)
    assert caps, "checkpoint cadence never fired"
    assert all(c.gateway is not None for c in caps)


def test_gateway_restore_resume_equivalence():
    """Kill the streaming run at its last periodic checkpoint, restore,
    re-feed the SAME arrival stream: resume() skips the consumed prefix
    and the final merged report matches the uninterrupted run."""
    oracle = _mk_gateway().run(_jobs())
    caps = []
    _mk_gateway(checkpoint_every_s=3600.0,
                checkpoint_fn=caps.append).run(_jobs())
    for ckpt in (caps[0], caps[-1]):
        gw = persistence.restore_gateway(pickle.loads(pickle.dumps(ckpt)))
        assert gw._consumed == ckpt.gateway["_consumed"]
        _assert_identical(gw.resume(_jobs()), oracle)


# --- an actual process kill --------------------------------------------------
_CHILD = """
import os, sys
from repro.core.carbon.intensity import PAPER_WINDOW_T0 as T0
from repro.core.controlplane import FleetController, persistence
from repro.core.scheduler.overlay import FTN
from repro.core.scheduler.planner import SLA, TransferJob

FTNS = [FTN("uc", "skylake", 10.0), FTN("m1", "apple_m1", 1.2),
        FTN("site_qc", "cascade_lake", 40.0),
        FTN("tacc", "cascade_lake", 10.0)]
ctl = FleetController(FTNS, migration_threshold=250.0)
for i in range(12):
    ctl.submit(TransferJob(f"s{i}", (300 + 100 * i) * 1e9,
                           ("uc", "site_ne") if i % 2 else ("uc",), "tacc",
                           SLA(deadline_s=(8 + i % 6) * 3600.0),
                           T0 + i * 1200.0))
ctl.inject_shock(T0 + 5 * 3600.0, 6.0, duration_s=5 * 3600.0,
                 zones=("CA-QC", "US-NY-NYIS"))
ctl.pump(T0 + 4.0 * 3600.0, strict=True, horizon=float("inf"))
persistence.save(persistence.capture(ctl), sys.argv[1])
os._exit(17)  # hard kill: no atexit, no cleanup, nothing flushed
"""


def test_checkpoint_survives_a_hard_process_kill(tmp_path, controller_oracle):
    """End-to-end crash story: a child process checkpoints to disk and
    dies via os._exit; the parent loads the file, restores, and finishes
    the run bit-identical to the never-killed oracle."""
    script = tmp_path / "child.py"
    script.write_text(textwrap.dedent(_CHILD))
    ckpt_path = tmp_path / "fleet.ckpt"
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in (os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "src"),
            env.get("PYTHONPATH")) if p)
    proc = subprocess.run([sys.executable, str(script), str(ckpt_path)],
                          env=env, capture_output=True, text=True,
                          timeout=300)
    assert proc.returncode == 17, proc.stderr
    restored = persistence.restore(persistence.load(ckpt_path))
    _assert_identical(restored.run(), controller_oracle)


# --- refusal paths -----------------------------------------------------------
def test_restore_refuses_version_mismatch():
    ckpt = persistence.capture(_mk_controller())
    stale = dataclasses.replace(ckpt, version=ckpt.version + 1)
    with pytest.raises(ValueError, match="version"):
        persistence.restore(stale)


@pytest.mark.parametrize("key", ["batch_backend", "shard_backend"])
def test_restore_refuses_a_checkpoint_naming_the_jax_tier(key):
    """A sharded checkpoint whose config names the deleted "jax" tier
    raises the planner's ValueError on restore, under worker processes
    too (where a worker's planner error would only degrade to numpy)."""
    ckpt = persistence.capture(_mk_sharded())
    stale = dataclasses.replace(ckpt, config=dict(ckpt.config, **{key: "jax"}))
    for mode in ("off", MODE):
        with pytest.raises(ValueError, match="batch_backend.*'jax'"):
            persistence.restore(stale, parallel=mode)


def test_restore_refuses_a_controller_planned_on_the_jax_tier():
    """A controller blob whose planner was pickled on the "jax" tier does
    not unpickle onto another tier."""
    ctl = _mk_controller()
    ctl.planner.batch_backend = "jax"
    with pytest.raises(ValueError, match="batch_backend.*'jax'"):
        persistence.restore(persistence.capture(ctl))


def test_capture_rejects_unknown_fleet_type():
    with pytest.raises(TypeError, match="cannot checkpoint"):
        persistence.capture(object())


def test_restore_gateway_requires_gateway_state():
    ckpt = persistence.capture(_mk_controller())
    with pytest.raises(ValueError, match="no gateway state"):
        persistence.restore_gateway(ckpt)


def test_load_rejects_non_checkpoint_files(tmp_path):
    path = tmp_path / "junk.ckpt"
    with open(path, "wb") as f:
        pickle.dump({"not": "a checkpoint"}, f)
    with pytest.raises(TypeError, match="FleetCheckpoint"):
        persistence.load(path)


def test_save_is_atomic_and_loads_back(tmp_path):
    ckpt = persistence.capture(_mk_controller())
    path = tmp_path / "ctl.ckpt"
    persistence.save(ckpt, path)
    assert not list(tmp_path.glob("*.tmp.*")), "temp file left behind"
    assert persistence.load(path).kind == "controller"


# --- the soak: seeded faults + two coordinator kill/restore cycles -----------
@pytest.mark.soak
def test_seeded_fault_soak_with_two_kill_restore_cycles(tmp_path):
    """Nightly-ish durability soak (RUN_SOAK=1): a supervised parallel
    run absorbs a seeded fault plan (worker kills + a backend fault + a
    hang), is checkpointed to disk and fully torn down twice mid-run,
    restored from the file each time, and still completes every job with
    the merged ledger audit exact to 1e-9 and totals bit-identical to
    the sequential oracle."""
    def drive_to(fl, k):
        fl.pump_all(T0 + k * 3600.0, strict=True, horizon=INF)

    oracle = _mk_sharded().run()

    plan = FaultPlan.seeded(3, seed=11, horizon=4, kills=2,
                            backend_faults=1, hangs=1, hang_s=3.0)
    pol = SupervisionPolicy(command_timeout_s=1.5, checkpoint_every=2)
    fl = _mk_sharded(parallel=MODE, supervision=pol, fault_plan=plan)
    path = tmp_path / "soak.ckpt"
    degradations = []
    for k in range(1, 11):
        drive_to(fl, k)
        if k in (4, 8):
            persistence.save(persistence.capture(fl), path)
            degradations += list(fl.degradations)
            fl.close()     # whole-coordinator kill
            fl = persistence.restore(persistence.load(path), parallel=MODE)
    rep = fl.run()
    degradations += list(rep.degradations)
    fl.close()

    _assert_identical(rep, oracle,
                      ignore=("wall_s", "jobs_per_s", "degradations"))
    rel = abs(rep.ledger_total_g - rep.total_actual_g) \
        / max(rep.total_actual_g, 1e-12)
    assert rel < 1e-9
    assert any("respawned" in d for d in degradations), degradations
