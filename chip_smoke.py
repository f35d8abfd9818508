#!/usr/bin/env python3
"""Smoke test of the admission path on a TPU, through the normal entry
points, in one process.

    python chip_smoke.py              # one chip: both device tiers + served
    python chip_smoke.py --chips 4    # the 4-device MeshConfig sweep only
    JAX_PLATFORMS=cpu python chip_smoke.py --size 64   # CPU rehearsal

Phases (one chip):

* admission — one ``--size``-job chunk of the planner-scale fleet through
  ``CarbonPlanner.plan_batch_jax`` on ``batch_backend="jax"`` and
  ``"pallas"``: compile time and one warm wall per tier (plans come back
  to the host, so a wall covers the device work), a 32-job sample held
  to the numpy ``plan_batch`` oracle (same cell, emissions <= 1e-4
  relative), and the tier still the one asked for;
* served — the ``metro_space_shift`` scenario through
  ``StreamingGateway(ShardedFleet(..., batch_backend="pallas"))``: every
  job completes, the merged ledger audit is < 1e-9, admission sweeps ran
  on the device, and ``total_planned_g`` is within 1e-4 of the same
  stream planned on the numpy oracle.

With ``--chips 4`` the only phase is the chunk planned with
``shard=MeshConfig(n_devices=4)`` against ``shard=False``: identical
cells, and the cell axis of the sharded score table on all four devices.

The last line of a passing run on a TPU is one JSON object,
``{"ok": true, "device": {...}}``. Anywhere else (the CPU, or a failed
phase) the script exits non-zero and prints no such line.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

SAMPLE = 32
REL_TOL = 1e-4
AUDIT_TOL = 1e-9
SERVED_WINDOW_S = 1800.0        # ~20 arrivals per batch at 40/h, >= 8


class SmokeFailure(RuntimeError):
    pass


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def compile_counter():
    """A listener on JAX's compile events, and a reader of the running
    (backend compile seconds, persistent-cache hits)."""
    import jax

    total = [0.0, 0]

    def on_duration(event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            total[0] += secs
        elif event == "/jax/compilation_cache/cache_retrieval_time_sec":
            total[1] += 1

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    return lambda: tuple(total)


def same_cell(a, b) -> bool:
    return (a.start_t, a.source, a.ftn, a.feasible) == \
        (b.start_t, b.source, b.ftn, b.feasible)


def rel_err(a: float, b: float) -> float:
    return abs(a - b) / max(abs(b), 1e-12)


def admission_phase(n: int, compiled) -> None:
    import numpy as np

    from repro.core.scheduler.planner import CarbonPlanner
    from repro.core.workloads.scenarios import (PLANNER_SCALE_FTNS,
                                                planner_scale_job)

    ftns = list(PLANNER_SCALE_FTNS)
    jobs = [planner_scale_job(i) for i in range(n)]
    idxs = sorted({int(i) for i in np.linspace(0, n - 1, SAMPLE).round()})
    oracle = CarbonPlanner(ftns, batch_backend="numpy").plan_batch(
        [jobs[i] for i in idxs])
    for tier in ("jax", "pallas"):
        pl = CarbonPlanner(ftns, batch_backend=tier)
        (s0, h0), t0 = compiled(), time.perf_counter()
        pl.plan_batch_jax(jobs)
        cold = time.perf_counter() - t0
        s1, h1 = compiled()
        t0 = time.perf_counter()
        plans = pl.plan_batch_jax(jobs)
        warm = time.perf_counter() - t0
        mism = sum(not same_cell(plans[i], w) for i, w in zip(idxs, oracle))
        err = max((rel_err(plans[i].predicted_emissions_g,
                           w.predicted_emissions_g)
                   for i, w in zip(idxs, oracle) if w.feasible),
                  default=0.0)
        print(f"admission[{tier}]: {n} jobs, {pl.last_batch_cells} cells; "
              f"compile {s1 - s0:.2f} s, {h1 - h0} persistent-cache hits "
              f"(cold call {cold:.2f} s); warm wall "
              f"{warm:.4f} s; oracle sample {len(idxs)} jobs, "
              f"{mism} cell mismatches, max emissions rel err {err:.3e}; "
              f"batch_backend={pl.batch_backend}")
        check(mism == 0, f"{tier}: {mism} cells differ from the oracle")
        check(err <= REL_TOL, f"{tier}: emissions rel err {err:.3e}")
        check(pl.batch_backend == tier,
              f"batch_backend changed {tier} -> {pl.batch_backend}")


def served_phase() -> None:
    from repro.core.carbon.intensity import PAPER_WINDOW_T0 as T0
    from repro.core.controlplane import ShardedFleet, StreamingGateway
    from repro.core.workloads import get_scenario

    sc = get_scenario("metro_space_shift")

    def run(backend):
        fleet = ShardedFleet(list(sc.ftns), n_shards=4, parallel="off",
                             batch_backend=backend)
        gw = StreamingGateway(fleet, window_s=SERVED_WINDOW_S)
        t0 = time.perf_counter()
        rep = gw.run(sc.jobs(seed=11, t0=T0))
        return rep, gw.stats(), time.perf_counter() - t0

    rep, st, wall = run("pallas")
    audit = rel_err(rep.ledger_total_g, rep.total_actual_g)
    print(f"served[pallas]: {st.n_jobs} arrivals in {st.n_batches} batches "
          f"(mean {st.mean_batch:.1f}); {rep.n_completed}/{rep.n_jobs} "
          f"completed; {st.device_sweeps} device sweeps over "
          f"{st.device_cells} cells; ledger audit {audit:.2e}; "
          f"wall {wall:.2f} s")
    ref, _, ref_wall = run("numpy")
    drift = rel_err(rep.total_planned_g, ref.total_planned_g)
    print(f"served[numpy]: total_planned_g {ref.total_planned_g:.6f} vs "
          f"pallas {rep.total_planned_g:.6f} (rel {drift:.2e}); "
          f"wall {ref_wall:.2f} s")
    check(rep.n_completed == rep.n_jobs == st.n_jobs,
          f"completed {rep.n_completed} of {rep.n_jobs} ({st.n_jobs})")
    check(audit < AUDIT_TOL, f"ledger audit {audit:.2e}")
    check(st.device_sweeps > 0, "no admission sweep ran on the device")
    check(drift <= REL_TOL, f"total_planned_g rel drift {drift:.2e}")


def mesh_phase(n: int, n_dev: int) -> None:
    import jax

    from repro.core.scheduler import grid_jax
    from repro.core.scheduler.grid_jax import MeshConfig
    from repro.core.scheduler.planner import CarbonPlanner
    from repro.core.workloads.scenarios import (PLANNER_SCALE_FTNS,
                                                planner_scale_job)

    check(jax.device_count() >= n_dev,
          f"{n_dev} devices asked for, {jax.device_count()} visible")
    jobs = [planner_scale_job(i) for i in range(n)]
    pl = CarbonPlanner(list(PLANNER_SCALE_FTNS), batch_backend="jax")
    mesh = MeshConfig(n_devices=n_dev)
    walls = {}
    for name, shard in (("1 device", False), (f"{n_dev} devices", mesh)):
        pl.plan_batch_jax(jobs, shard=shard)                 # compile
        t0 = time.perf_counter()
        walls[name] = (pl.plan_batch_jax(jobs, shard=shard),
                       time.perf_counter() - t0)
    (one, w1), (many, wn) = walls.values()
    mism = sum(not same_cell(a, b) for a, b in zip(one, many))
    err = max((rel_err(b.predicted_emissions_g, a.predicted_emissions_g)
               for a, b in zip(one, many) if a.feasible), default=0.0)
    print(f"mesh: {n} jobs, {pl.last_batch_cells} cells; warm wall 1 "
          f"device {w1:.4f} s, {n_dev} devices {wn:.4f} s; {mism} cell "
          f"mismatches, max emissions rel err {err:.3e}")
    # where the cell axis landed: score one chunk, read the shards
    cells, _, _ = pl._batch_cells(jobs, 60.0, 60)
    chunk = next(grid_jax._iter_chunks(cells, 60, grid_jax._MAX_ELEMS))
    out = grid_jax.chunk_scores(pl.field, [cells[j] for j in chunk],
                                dt_s=60.0, slot_stride=60, n_dev=n_dev,
                                mesh=mesh.build())
    rows = sorted((s.device.id, s.data.shape[0])
                  for s in out.addressable_shards)
    devs = {d for d, _ in rows}
    print(f"mesh: score table {out.shape} on devices "
          f"{sorted(devs)}; rows per shard {[r for _, r in rows]}")
    check(mism == 0, f"{mism} cells differ between 1 and {n_dev} devices")
    check(err <= REL_TOL, f"1 vs {n_dev} devices emissions rel err {err}")
    check(len(devs) == n_dev and all(r == out.shape[0] // n_dev
                                     for _, r in rows),
          f"cell axis not split over {n_dev} devices: {rows}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--size", type=int, default=None,
                    help="jobs in the admission chunk (default 4096; "
                         "required off the TPU, where only a rehearsal "
                         "runs)")
    args = ap.parse_args(argv)

    import jax

    from repro.core.compile_cache import use_compile_cache

    cache = use_compile_cache(ROOT)
    d0 = jax.devices()[0]
    device = {"platform": d0.platform, "kind": d0.device_kind,
              "count": len(jax.devices())}
    print(f"device: platform={device['platform']} kind={device['kind']} "
          f"count={device['count']}; compile cache {cache}", flush=True)
    on_tpu = device["platform"] == "tpu"
    if not on_tpu and args.size is None:
        print("no TPU: pass --size N for a CPU rehearsal", file=sys.stderr)
        return 2
    rehearsal = not on_tpu or args.size is not None
    n = args.size or 4096
    compiled = compile_counter()
    try:
        if args.chips == 4:
            mesh_phase(n, 4)
        else:
            admission_phase(n, compiled)
            served_phase()
    except SmokeFailure as e:
        print(f"FAILED: {e}", file=sys.stderr)
        return 1
    if rehearsal:
        print("rehearsal passed (not a full-size TPU run: no result line)",
              file=sys.stderr)
        return 3
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
