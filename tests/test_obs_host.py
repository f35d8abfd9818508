"""Wall-clock host spans (``repro.core.obs.host``): a no-op without a
profiler session and without jax; under ``jax.profiler.trace`` the
admission sweep, the gateway and the fleet controller write the span tree
that ``docs/observability.md`` lists, with its args; and an active
profiler changes no plan, report or sim-clock span."""
import dataclasses
import glob
import subprocess
import sys
import textwrap

import pytest

from repro.core.carbon.intensity import PAPER_WINDOW_T0 as T0
from repro.core.controlplane import ShardedFleet, StreamingGateway
from repro.core.obs import span
from repro.core.scheduler import grid_pallas
from repro.core.scheduler.overlay import FTN
from repro.core.scheduler.planner import SLA, CarbonPlanner, TransferJob
from repro.core.workloads import PoissonArrivals, UniformSizes, Workload

jax = pytest.importorskip("jax")

FTNS = [FTN("uc", "skylake", 10.0), FTN("m1", "apple_m1", 1.2),
        FTN("tacc", "cascade_lake", 10.0)]
PREFIXES = ("admit.", "gw.", "fleet.")


def _sweep_jobs(n=8):
    return [TransferJob(f"hs{i}", (60 + 30 * i) * 1e9, ("uc", "m1"), "tacc",
                        SLA(deadline_s=(12 + i) * 3600.0), T0 + i * 900.0)
            for i in range(n)]


def _stream_jobs():
    w = Workload("hs", PoissonArrivals(rate_per_h=6.0),
                 UniformSizes(lo_gb=80.0, hi_gb=400.0),
                 replica_sets=(("uc",), ("uc", "m1")),
                 deadline_h=(6.0, 14.0))
    return list(w.jobs(3, T0, 6 * 3600.0))[:24]


def _served():
    fleet = ShardedFleet(FTNS, n_shards=2, parallel="off",
                         batch_backend="numpy", obs=True)
    fleet.inject_shock(T0 + 3 * 3600.0, 4.0, duration_s=3 * 3600.0,
                       zones=("US-IL",))
    rep = StreamingGateway(fleet, window_s=900.0).run(_stream_jobs())
    fleet.close()
    return rep


def _totals(rep):
    return (rep.n_jobs, rep.n_completed, rep.total_planned_g,
            rep.total_actual_g, rep.ledger_total_g, rep.migrations,
            rep.sla_misses, rep.n_events, rep.n_steps)


def _events(trace_dir):
    """Host events of the program's spans: (name, start, end, args)."""
    from jax.profiler import ProfileData
    (path,) = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            out.extend((e.name, e.start_ns, e.start_ns + e.duration_ns,
                        dict(e.stats))
                       for e in line.events if e.name.startswith(PREFIXES))
    return sorted(out, key=lambda e: (e[1], -e[2]))


def _inside(child, parents):
    return [p for p in parents if p[1] <= child[1] and child[2] <= p[2]]


def _named(evs, name):
    return [e for e in evs if e[0] == name]


def test_span_is_a_no_op_without_a_session():
    from jax.profiler import TraceAnnotation
    assert not TraceAnnotation.is_enabled()
    with span("admit.sweep", jobs=3, tier="numpy") as sp:
        sp.set_metadata(cells=5)


def test_span_is_a_shared_no_op_without_jax():
    code = textwrap.dedent("""
        import sys
        sys.modules["jax"] = None          # jax cannot be imported
        from repro.core.obs import span
        a, b = span("admit.sweep", jobs=1), span("fleet.pump")
        assert a is b and type(a).__name__ == "_NoSpan"
        with a as sp:
            sp.set_metadata(cells=2)
        from repro.core.carbon.intensity import PAPER_WINDOW_T0 as T0
        from repro.core.controlplane import FleetController
        from repro.core.scheduler.overlay import FTN
        from repro.core.scheduler.planner import SLA, TransferJob
        fc = FleetController([FTN("uc", "skylake", 10.0),
                              FTN("tacc", "cascade_lake", 10.0)])
        fc.submit(TransferJob("j0", 100e9, ("uc",), "tacc",
                              SLA(deadline_s=6 * 3600.0), T0))
        assert fc.run().n_completed == 1
        print("ok")
    """)
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, check=True)
    assert out.stdout.strip() == "ok"


def test_pallas_sweep_span_tree_and_compiled_arg(tmp_path, monkeypatch):
    """Two identical sweeps on a fresh jit: the first launch grows the
    in-process cache (compiled=1), the repeat does not (compiled=0)."""
    monkeypatch.setattr(grid_pallas, "_fused_jit", None)
    pl = CarbonPlanner(FTNS, batch_backend="pallas")
    jobs = _sweep_jobs()
    with jax.profiler.trace(str(tmp_path)):
        pl.plan_batch(jobs)
        cells = pl.last_batch_cells
        pl.plan_batch(jobs)
    evs = _events(tmp_path)
    sweeps = _named(evs, "admit.sweep")
    assert len(sweeps) == 2
    for s in sweeps:
        assert s[3] == {"jobs": len(jobs), "tier": "pallas"}
    for name in ("admit.cells", "admit.chunks", "admit.inputs",
                 "admit.device", "admit.select", "admit.finish"):
        got = _named(evs, name)
        assert len(got) == 2, name
        assert all(len(_inside(e, sweeps)) == 1 for e in got), name
    groups = pl._batch_cells(jobs, 60.0, 60)[2].groups
    assert 0 < groups <= cells
    assert [e[3] for e in _named(evs, "admit.cells")] == \
        [{"cells": cells, "groups": groups}] * 2
    assert all(e[3] == {"chunks": 1} for e in _named(evs, "admit.chunks"))
    for e in _named(evs, "admit.inputs"):
        assert e[3]["cells"] == cells and e[3]["pairs"] > 0
    assert all(e[3] == {"plans": len(jobs)}
               for e in _named(evs, "admit.finish"))
    device = _named(evs, "admit.device")
    for name in ("admit.launch", "admit.fetch"):
        assert all(len(_inside(e, device)) == 1 for e in _named(evs, name))
    assert [e[3] for e in _named(evs, "admit.launch")] == \
        [{"compiled": 1}, {"compiled": 0}]
    assert not _named(evs, "admit.numpy")


def test_served_gateway_span_tree(tmp_path):
    with jax.profiler.trace(str(tmp_path)):
        rep = _served()
    evs = _events(tmp_path)
    names = {e[0] for e in evs}
    assert {"gw.pump", "gw.admit", "gw.drain", "fleet.pump",
            "fleet.arrival", "fleet.ready", "fleet.step", "fleet.complete",
            "fleet.replan", "fleet.migrate_check", "fleet.shock",
            "admit.sweep", "admit.numpy"} <= names
    (drain,) = _named(evs, "gw.drain")
    admits = _named(evs, "gw.admit")
    assert sum(e[3]["jobs"] for e in admits) == rep.n_jobs
    pumps = _named(evs, "fleet.pump")
    for e in evs:
        if e[0].startswith("fleet.") and e[0] != "fleet.pump":
            assert len(_inside(e, pumps)) == 1, e
    for e in _named(evs, "fleet.pump"):
        assert _inside(e, _named(evs, "gw.pump") + [drain]), e
    assert all(isinstance(e[3]["queued"], int)
               for e in _named(evs, "fleet.replan"))
    # the gateway's sweeps sit in its admits, the shards' re-plans in the
    # fleet's handlers
    for e in _named(evs, "admit.sweep"):
        assert len(_inside(e, admits + pumps)) == 1


def test_plans_reports_and_sim_trace_identical_under_a_profiler(tmp_path):
    jobs = _sweep_jobs()
    plain = CarbonPlanner(FTNS, batch_backend="pallas").plan_batch(jobs)
    rep = _served()
    with jax.profiler.trace(str(tmp_path)):
        traced = CarbonPlanner(FTNS, batch_backend="pallas").plan_batch(jobs)
        rep_t = _served()
    assert [dataclasses.astuple(p) for p in plain] == \
        [dataclasses.astuple(p) for p in traced]
    assert rep.trace and rep.trace == rep_t.trace
    assert rep.outcomes == rep_t.outcomes
    assert _totals(rep) == _totals(rep_t)
