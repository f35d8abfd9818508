#!/usr/bin/env bash
# Tier-1 verify entrypoint (see ROADMAP.md): run from the repo root or any
# subdirectory; mirrors exactly what CI runs. The docs gate (intra-repo
# markdown links + docs/ snippet execution) always runs; set CHECK_BENCH=1
# to follow the tests with the bench smoke (planner grid scan + the 10^4
# planner_scale admission rung,
# which gates oracle + pallas-interpret spot-checks — raise the rungs
# with BENCH_PLANNER_SCALE_RUNGS — + the field_lattice 8/64/200-zone
# plan sweep, whose scalar-oracle spot-checks gate unconditionally on
# every host — + fleet control loop + sharded scale-out
# sweep incl. the process-parallel worker-per-shard runner, which gates
# an exact-merge match always and a >= 2x throughput floor on hosts with
# >= 4 CPUs — below that the numbers are recorded and the floor is
# skipped — + streaming gateway, which gates a sustained-throughput floor
# of 0.8x the co-measured sharded run plus the pipelined-admission
# subsection: pipeline="on" over the worker pool vs the sequential
# pipeline="off" oracle, exact merge gated always, a >= 2x streamed-drain
# floor armed on >= 4 effective CPUs (cgroup cpu.max quota respected),
# overlap_fraction / admit_stall_ms recorded on every host —
# + the scenario x policy x window
# matrix, + the fault-injection durability bench, which gates an exact
# merge after two worker kills + a backend fault and a <= 10% checkpoint
# overhead, + the fleet_obs observability bench, which co-measures an
# instrumented vs uninstrumented fleet loop and gates the tracing +
# metrics overhead at <= 5%), refreshing BENCH_planner.json /
# BENCH_fleet.json and printing the scripts/bench_summary.py trajectory
# table, with the examples/fleet_stream.py end-to-end scenario run
# (backfill on, merged ledger audit asserted), and with the seeded
# fault-injection soak (RUN_SOAK=1: checkpoint/kill/restore the whole
# coordinator twice mid-run, ledger audit < 1e-9 — the nightly
# durability job).
set -euo pipefail
cd "$(dirname "$0")/.."
PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}" python -m pytest -x -q "$@"
PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}" python scripts/check_docs.py
if [[ "${CHECK_BENCH:-0}" == "1" ]]; then
  PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}" python -m benchmarks.run \
    --only planner_scan
  BENCH_PLANNER_SCALE_RUNGS="${BENCH_PLANNER_SCALE_RUNGS:-10000}" \
    PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}" python -m benchmarks.run \
    --only planner_scale
  PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}" python -m benchmarks.run \
    --only field_lattice
  PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}" python -m benchmarks.run \
    --only fleet_loop
  PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}" python -m benchmarks.run \
    --only fleet_sharded
  PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}" python -m benchmarks.run \
    --only fleet_streaming
  PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}" python -m benchmarks.run \
    --only fleet_matrix
  PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}" python -m benchmarks.run \
    --only fleet_faults
  PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}" python -m benchmarks.run \
    --only fleet_obs
  PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}" python scripts/bench_summary.py
  PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}" python examples/fleet_stream.py
  RUN_SOAK=1 PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}" \
    python -m pytest -x -q -m soak tests/test_persistence.py
fi
