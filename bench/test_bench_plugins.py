"""A job law is a file of its own, found by the name a configuration
gives it: a cell over a new law needs that file and nothing else changed
in the harness."""
import shutil

from bench import check, harness, rehearsal

EQUAL_BACKLOG = '''"""Every job the same transfer, one every five minutes."""
from bench.reference import Job


def backlog(law, n_jobs, t0, tag):
    return [Job(uuid=f"{tag}-{i}", size_bytes=law["size_gb"] * 1e9,
                replicas=tuple(law["replicas"]), dst=law["dst"],
                deadline_s=law["deadline_h"] * 3600.0,
                submitted_t=t0 + 300.0 * i)
            for i in range(n_jobs)]
'''


def test_a_new_law_is_a_new_file(tmp_path, monkeypatch):
    # a copy of the benchmark's own files, with one new law added
    for kind in ("traffic", "loops", "laws", "metrics"):
        shutil.copytree(harness.BENCH / kind, tmp_path / kind)
    (tmp_path / "laws" / "equal_backlog.py").write_text(EQUAL_BACKLOG)
    monkeypatch.setattr(harness, "BENCH", tmp_path)
    law = {"law": "equal_backlog", "size_gb": 50.0, "replicas": ["uc", "m1"],
           "dst": "tacc", "deadline_h": 24.0}
    r, _, drv = harness.build(
        "ftn_overlay.replan", rehearsal.SEED, require_accelerator=False,
        overrides={"config": {"jobs": law},
                   "mix": rehearsal.TINY["ftn_overlay.replan"]["mix"]})
    drv.setup()
    drv.window(0.2)
    assert {j.size_bytes for j, _ in drv.pairs()} == {50e9}
    ok, rows = check.judge(drv.numbers(), r["config"]["limits"])
    assert ok, rows
