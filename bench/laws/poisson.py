"""An arrival stream: exponential gaps at ``rate_per_h``, lognormal sizes
(``median_gb``, ``sigma``, clamped to [min_gb, cap_gb]), a replica set and
a deadline drawn uniformly, ``w_perf`` drawn from ``w_perf_choices``;
drawn in that order per job, like the program's
``core/workloads/generators.py`` (copied here, so the yardstick stays put
when the program's generators change). The draws come from the law's own
``draw_seed``, horizon ``k`` seeded apart from the others, so every run
offers the same jobs and the same work."""
from typing import List

import numpy as np

from bench.reference import Job


def arrivals(law: dict, k: int, t0: float, horizon_s: float) -> List[Job]:
    """Horizon ``k``: the arrivals of ``horizon_s`` seconds from ``t0``."""
    rng = np.random.default_rng(np.random.SeedSequence(
        [int(law["draw_seed"]), k]))
    mean_s = 3600.0 / law["rate_per_h"]
    sets = [tuple(s) for s in law["replica_sets"]]
    lo_h, hi_h = law["deadline_h"]
    out: List[Job] = []
    t = rng.exponential(mean_s)
    while t < horizon_s:
        size_gb = float(law["median_gb"]
                        * np.exp(rng.normal(0.0, law["sigma"])))
        size_gb = min(max(size_gb, law["min_gb"]), law["cap_gb"])
        reps = sets[int(rng.integers(len(sets)))]
        dl_h = float(rng.uniform(lo_h, hi_h))
        w_perf = law["w_perf_choices"][int(rng.integers(
            len(law["w_perf_choices"])))]
        out.append(Job(uuid=f"{law['name']}-{k}-{len(out):05d}",
                       size_bytes=size_gb * 1e9, replicas=reps,
                       dst=law["dst"], deadline_s=dl_h * 3600.0,
                       submitted_t=t0 + t, w_perf=float(w_perf),
                       parallelism=law["parallelism"],
                       concurrency=law["concurrency"]))
        t += rng.exponential(mean_s)
    return out
