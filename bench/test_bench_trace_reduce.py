"""The trace reduction, on hand-made intervals and on a trace recorded on
a TPU v5e chip (three 4,096-job re-plan sweeps of ``ftn_overlay.replan``,
cut to plain events by ``trace_reduce.load``)."""
import json

import pytest

from bench import harness, roofline, trace_reduce as tr

FIXTURE = harness.BENCH / "fixtures" / "tpu_replan_trace.json"


def test_union_covered_gaps_by_hand():
    evs = [["a", 0, 10], ["b", 5, 10], ["c", 30, 5], ["d", 31, 1]]
    assert tr.union(evs) == [(0, 15), (30, 35)]
    assert tr.covered(tr.union(evs), 10, 32) == 5 + 2
    assert tr.gaps(tr.union(evs), -5, 40) == [(-5, 0), (15, 30), (35, 40)]
    assert tr.gaps([], 0, 3) == [(0, 3)]


def test_innermost_span_names_a_gap():
    t = {"device": {}, "host": [["bench.window", 0, 100],
                                ["bench.admit", 10, 20],
                                ["bench.drain", 60, 30]]}
    assert tr.innermost(t, 15) == "bench.admit"
    assert tr.innermost(t, 40) == "bench.window"
    assert tr.innermost(t, 70) == "bench.drain"
    assert tr.innermost(t, 150) is None


@pytest.fixture(scope="module")
def trace():
    return json.loads(FIXTURE.read_text())


def test_fixture_busy_and_kernels(trace):
    lo, hi = tr.window(trace)
    (evs,) = trace["device"].values()
    busy = tr.busy(trace, lo, hi)
    assert 0 < busy < hi - lo
    assert busy == tr.covered(tr.union(evs), lo, hi)
    sweeps = tr.spans(trace, "bench.admit")
    assert len(sweeps) == 3
    # each sweep runs both kernels once per memory chunk (two chunks)
    for name in ("rate_prefix", "sweep"):
        ns, n = tr.kernel_ns(trace, roofline.KERNELS[name], lo, hi)
        assert n == 2 * len(sweeps) and 0 < ns < busy
    # each op is one kernel at most
    for name, _, _ in evs:
        hits = [k for k, p in roofline.KERNELS.items()
                if __import__("re").search(p, name)]
        assert len(hits) <= 1


def test_fixture_admission_host_time(trace):
    host_ms = tr.admit_host_ms(trace)
    walls = [(e - s) / 1e6 for s, e in tr.spans(trace, "bench.admit")]
    assert 0 < host_ms < sum(walls) / len(walls)


def test_fixture_breakdown_names_kernels_and_spans(trace):
    b = tr.breakdown(trace, roofline.KERNELS)
    names = [n for n, _ in b["device_ops"]]
    assert {"rate_prefix kernel", "sweep kernel"} <= set(names)
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10
    assert all(n.startswith("bench.") for n, _ in b["idle_gaps"])
    gaps = [s for _, s in b["idle_gaps"]]
    assert gaps == sorted(gaps, reverse=True) and gaps[0] > 0
