"""A deterministic job table (the planner-scale fleet), offered as a
backlog: job ``i`` has size ``size_gb[0] + (size_gb[1] * i) % size_gb[2]``
GB, replica set ``replica_cycle[i % len]``, deadline ``deadline_h[0] +
i % deadline_h[1]`` hours and arrives ``(i % anchors) * anchor_step_s``
after the backlog's origin. Every backlog has the same sizes, so every
seed compiles the same shapes; the seed and the sweep move the origin in
time, which changes the carbon field each backlog is planned against.
Copied from the program's ``scenarios.planner_scale_job``, so the
yardstick stays put when the program's scenarios change."""
from typing import List

from bench.reference import Job


def backlog(law: dict, n_jobs: int, t0: float, tag: str) -> List[Job]:
    """``n_jobs`` jobs anchored at ``t0``, named ``<tag>-<i>``."""
    base, mult, mod = law["size_gb"]
    cycle = [tuple(r) for r in law["replica_cycle"]]
    dl0, dl_mod = law["deadline_h"]
    return [Job(uuid=f"{tag}-{i}",
                size_bytes=(base + (mult * i) % mod) * 1e9,
                replicas=cycle[i % len(cycle)], dst=law["dst"],
                deadline_s=(dl0 + i % dl_mod) * 3600.0,
                submitted_t=t0 + (i % law["anchors"]) * law["anchor_step_s"],
                parallelism=law["parallelism"],
                concurrency=law["concurrency"])
            for i in range(n_jobs)]
