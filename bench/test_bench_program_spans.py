"""The readers of the program's own host spans, on hand-made events and
on traces recorded on a TPU v5e chip, in the plain form of
``trace_reduce.load`` and ``program_spans.load``: three 4,096-job sweeps
of ``ftn_overlay.replan``, and a slice of ``metro_fanout_200.served`` from
the start of a gateway pump to the end of the third admission after it.
Each slice's ``bench.window`` is the slice, and ``jobs_done`` counts the
``fleet.complete`` spans in it."""
import json
import re

import pytest

from bench import harness, program_spans, roofline, trace_reduce

FIXTURE = harness.BENCH / "fixtures" / "tpu_program_spans.json"
MS = 1e6                               # ns per ms
SWEEP = ["admit_cells_ms", "admit_inputs_ms", "admit_launch_ms",
         "admit_finish_ms"]
DRAIN = ["drain_replan_ms_per_job", "drain_step_ms_per_job"]
REPLAN = [f"{m}.replan" for m in SWEEP]
SERVED = [f"{m}.served" for m in SWEEP] + ["admit_numpy_ms.served"] + DRAIN


def _run(program, device=(), jobs_done=4):
    trace = {"device": {"/device:TPU:0": [list(d) for d in device]},
             "host": [["bench.window", 0, 100 * MS]],
             "program": [list(e) for e in program]}
    return harness.Run(cell="hand", loop=None, sweeps=[], wall_s=0.1,
                       jobs_done=jobs_done, device_kind="TPU v5 lite",
                       trace=trace)


# two admission sweeps (one on the device, one on numpy), and a drain
# whose shard re-plan runs the same planner inside the fleet's pump
DEVICE_SWEEP = [["admit.sweep", 1 * MS, 40 * MS],
                ["admit.cells", 1 * MS, 5 * MS],
                ["admit.chunks", 6 * MS, 1 * MS],
                ["admit.inputs", 7 * MS, 3 * MS],
                ["admit.device", 10 * MS, 10 * MS],
                ["admit.launch", 10 * MS, 1 * MS],
                ["admit.fetch", 11 * MS, 9 * MS],
                ["admit.select", 20 * MS, 4 * MS],
                ["admit.finish", 24 * MS, 16 * MS]]
NUMPY_SWEEP = [["admit.sweep", 42 * MS, 8 * MS],
               ["admit.numpy", 42 * MS, 8 * MS]]
DRAIN_SPANS = [["fleet.pump", 50 * MS, 40 * MS],
               ["fleet.replan", 50 * MS, 20 * MS],
               ["admit.sweep", 51 * MS, 18 * MS],
               ["admit.numpy", 51 * MS, 18 * MS],
               ["fleet.step", 70 * MS, 12 * MS],
               ["fleet.complete", 82 * MS, 4 * MS]]
OUTSIDE = [["admit.sweep", 200 * MS, 5 * MS],
           ["admit.cells", 200 * MS, 5 * MS]]
KERNEL = [["%sweep.1 = f32[64,1,128] custom-call(", 12 * MS, 6 * MS]]

HAND = {"admit_cells_ms.replan": 5 / 2, "admit_inputs_ms.replan": 4 / 2,
        "admit_launch_ms.replan": (10 - 6) / 2,
        "admit_finish_ms.replan": 20 / 2, "admit_numpy_ms.served": 8 / 2,
        "drain_replan_ms_per_job": 20 / 4,
        "drain_step_ms_per_job": 16 / 4}


@pytest.mark.parametrize("metric", sorted(HAND))
def test_reader_on_hand_made_events(metric):
    run = _run(DEVICE_SWEEP + NUMPY_SWEEP + DRAIN_SPANS + OUTSIDE, KERNEL)
    assert harness.reader(metric)(run) == pytest.approx(HAND[metric])


@pytest.mark.parametrize("cell", ["replan", "served"])
def test_replan_and_served_readers_agree(cell):
    run = _run(DEVICE_SWEEP + NUMPY_SWEEP, KERNEL)
    for m in SWEEP:
        assert harness.reader(f"{m}.{cell}")(run) == \
            harness.reader(f"{m}.replan")(run)


def test_admission_leaves_out_the_fleets_replans():
    evs = DEVICE_SWEEP + NUMPY_SWEEP + DRAIN_SPANS
    got = program_spans.admission(evs)
    assert sum(e[0] == "admit.sweep" for e in got) == 2
    assert ["admit.numpy", 51 * MS, 18 * MS] not in got
    assert all(e[0].startswith("admit.") for e in got)


@pytest.mark.parametrize("metric", SERVED + REPLAN)
def test_none_where_nothing_to_read(metric):
    read = harness.reader(metric)
    # a program without spans (the parent of this benchmark's readers)
    assert read(_run([])) is None
    # an untraced run
    run = _run(DEVICE_SWEEP)
    run.trace = None
    assert read(run) is None


@pytest.mark.parametrize("metric", ["admit_cells_ms.served",
                                    "admit_launch_ms.served",
                                    "drain_replan_ms_per_job"])
def test_none_where_the_phase_is_absent(metric):
    # numpy sweeps only, no fleet: no cell table, no device, no re-plan
    assert harness.reader(metric)(_run(NUMPY_SWEEP)) is None


def test_no_completed_job_reads_none():
    run = _run(DRAIN_SPANS, jobs_done=0)
    assert harness.reader("drain_step_ms_per_job")(run) is None


@pytest.fixture(scope="module")
def recorded():
    fx = json.loads(FIXTURE.read_text())
    return {cell: harness.Run(cell=cell, loop=None, sweeps=[], wall_s=0.0,
                              jobs_done=t["jobs_done"],
                              device_kind="TPU v5 lite",
                              trace={k: t[k] for k in ("device", "host",
                                                       "program")})
            for cell, t in fx.items()}


@pytest.mark.parametrize("metric", REPLAN)
def test_fixture_replan_readers_read(recorded, metric):
    assert harness.reader(metric)(recorded["replan"]) > 0


@pytest.mark.parametrize("metric", sorted(set(SERVED)
                                          - {"admit_numpy_ms.served"}))
def test_fixture_served_readers_read(recorded, metric):
    assert harness.reader(metric)(recorded["served"]) > 0


def test_fixture_served_sweeps_all_ran_on_the_device(recorded):
    assert harness.reader("admit_numpy_ms.served")(recorded["served"]) \
        is None


@pytest.mark.parametrize("cell", ["replan", "served"])
def test_fixture_phases_cover_the_admission_host_time(recorded, cell):
    """The four phases add up to 90-102 % of ``admit_host_ms`` (each
    ``bench.admit`` span less the device busy inside it), and the program
    sees as many admission sweeps as the benchmark."""
    run = recorded[cell]
    parts = sum(harness.reader(f"{m}.{cell}")(run) for m in SWEEP)
    assert 0.90 <= parts / trace_reduce.admit_host_ms(run.trace) <= 1.02
    sweeps = [e for e in program_spans.admission(program_spans.in_window(
        run)) if e[0] == "admit.sweep"]
    assert len(sweeps) == len(trace_reduce.spans(run.trace, "bench.admit"))


def test_fixture_shard_replans_are_not_admission(recorded):
    run = recorded["served"]
    evs = program_spans.in_window(run)
    inner = [e for e in evs if e[0] == "admit.sweep"]
    outer = [e for e in program_spans.admission(evs)
             if e[0] == "admit.sweep"]
    assert len(inner) > len(outer) == 3
    assert harness.reader("drain_replan_ms_per_job")(run) > \
        harness.reader("drain_step_ms_per_job")(run)


def test_fixture_device_trace_names_both_kernels(recorded):
    """Since the ``pallas_call``s carry ``name=``, the device ops read
    ``%rate_prefix.<n>`` and ``%sweep.<n>``, and the benchmark's shape
    patterns still find them."""
    for run in recorded.values():
        (evs,) = run.trace["device"].values()
        for label, pattern in roofline.KERNELS.items():
            hits = [n for n, _, _ in evs if re.search(pattern, n)]
            assert hits and all(n.startswith(f"%{label}.") for n in hits)
